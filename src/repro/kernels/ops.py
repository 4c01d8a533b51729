"""jit'd public wrappers for the Pallas kernels.

On a TPU backend the kernels run natively; on the CPU dry-run container
``interpret=True`` executes the kernel bodies in Python for correctness
validation (the models' default compute path stays pure-jnp — see
DESIGN.md §7).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import int8_matmul as _im
from repro.kernels import rglru_scan as _rs
from repro.kernels import topk_sample as _ts


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    interpret: bool = None):
    """q: (B, S, H, D); k/v: (B, S, Hkv, D) — GQA heads expanded here."""
    if interpret is None:
        interpret = _default_interpret()
    b, s, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    o = _fa.flash_attention(qf, kf, vf, causal=causal, interpret=interpret)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k_cache, v_cache, pos, *, interpret: bool = None):
    """q: (B, S, H, D); caches: (B, W, Hkv, D); pos: (B,) tokens written
    INCLUDING the S queries (S=1: classic decode; S>1: chunked-prefill
    chunk with per-query causal validity)."""
    if interpret is None:
        interpret = _default_interpret()
    b, sq, h, d = q.shape
    w, hkv = k_cache.shape[1], k_cache.shape[2]
    rep = h // hkv
    k = jnp.repeat(k_cache, rep, axis=2) if rep > 1 else k_cache
    v = jnp.repeat(v_cache, rep, axis=2) if rep > 1 else v_cache
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, w, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, w, d)
    nv = jnp.repeat(jnp.minimum(pos, w).astype(jnp.int32), h)
    o = _da.decode_attention(qf, kf, vf, nv, interpret=interpret)
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, page_table, pos, *,
                           interpret: bool = None):
    """q: (B, S, H, D); k/v_pool: (P, ps, Hkv, D) shared page pools;
    page_table: (B, n_pages) int32; pos: (B,) tokens written INCLUDING the
    S queries. Model-layout twin of ``repro.models.layers.
    paged_decode_attention`` running the block-sparse Pallas kernel."""
    if interpret is None:
        interpret = _default_interpret()
    b, sq, h, d = q.shape
    ps, hkv = k_pool.shape[1], k_pool.shape[2]
    g = h // hkv
    n_pages = page_table.shape[1]
    # (B, S, H, D) -> (B, KVH, G*S, D), rows (g, s)-ordered
    qf = (q.reshape(b, sq, hkv, g, d).transpose(0, 2, 3, 1, 4)
          .reshape(b, hkv, g * sq, d))
    kf = k_pool.transpose(2, 0, 1, 3)  # (KVH, P, ps, D)
    vf = v_pool.transpose(2, 0, 1, 3)
    nv = jnp.minimum(pos, n_pages * ps).astype(jnp.int32)
    o = _da.paged_decode_attention(
        qf, kf, vf, page_table.reshape(-1).astype(jnp.int32), nv, s_q=sq,
        interpret=interpret)
    return (o.reshape(b, hkv, g, sq, d).transpose(0, 3, 1, 2, 4)
            .reshape(b, sq, h, d))


@partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_int8(q, k_pool, v_pool, k_scale, v_scale,
                                page_table, pos, *, interpret: bool = None):
    """Quantized-pool twin of ``paged_decode_attention``: pools are int8
    (P, ps, Hkv, D) with per-vector fp32 scales (P, ps, Hkv, 1) addressed
    by the same page ids; dequantization is fused into the kernel (inline
    in VMEM, right before the dots). Model-layout twin:
    ``repro.models.layers.paged_decode_attention_int8``."""
    if interpret is None:
        interpret = _default_interpret()
    b, sq, h, d = q.shape
    ps, hkv = k_pool.shape[1], k_pool.shape[2]
    g = h // hkv
    n_pages = page_table.shape[1]
    qf = (q.reshape(b, sq, hkv, g, d).transpose(0, 2, 3, 1, 4)
          .reshape(b, hkv, g * sq, d))
    kf = k_pool.transpose(2, 0, 1, 3)  # (KVH, P, ps, D)
    vf = v_pool.transpose(2, 0, 1, 3)
    ksf = k_scale.transpose(2, 0, 1, 3)  # (KVH, P, ps, 1)
    vsf = v_scale.transpose(2, 0, 1, 3)
    nv = jnp.minimum(pos, n_pages * ps).astype(jnp.int32)
    o = _da.paged_decode_attention_int8(
        qf, kf, vf, ksf, vsf, page_table.reshape(-1).astype(jnp.int32), nv,
        s_q=sq, interpret=interpret)
    return (o.reshape(b, hkv, g, sq, d).transpose(0, 3, 1, 2, 4)
            .reshape(b, sq, h, d))


@partial(jax.jit, static_argnames=("interpret",))
def topk_sample(logits, k, temperature, uniform, *, interpret: bool = None):
    """Fused top-k + softmax sampling: one categorical draw per row from
    the temperature-scaled softmax restricted to the ``k`` largest logits
    (radix select over float bits + Gumbel argmax, one VMEM pass — no
    sort). logits (B, V); k (B,) int32 in [1, V]; temperature (B,) > 0;
    uniform (B, V) noise in [0, 1) — the caller keys it (the engine uses
    per-slot PRNG keys folded with the token position). Returns (B,)
    int32. Model-layout twin with top-p and the greedy mask:
    ``repro.models.layers.sample_tokens``."""
    if interpret is None:
        interpret = _default_interpret()
    return _ts.topk_sample(logits, k, temperature, uniform,
                           interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def rglru_scan(a, x, h0, *, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _rs.rglru_scan_kernel(a, x, h0, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def int8_matmul(x, w_q, scales, *, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _im.int8_matmul(x, w_q, scales, interpret=interpret)


quantize_int8 = _im.quantize_int8
