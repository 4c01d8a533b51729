"""Primitive layers: norms, RoPE variants, MLPs, attention.

All functions are pure (params passed explicitly) and shape-polymorphic over
batch/sequence. Matmuls accumulate in fp32 via ``preferred_element_type``;
softmax/normalization statistics are computed in fp32.

The long-sequence attention path (``block_causal_attention``) is a
flat block-pair online-softmax scan: it enumerates only the (q_chunk,
kv_chunk) pairs allowed by the mask structure (causal lower-triangle or a
sliding-window band), so HLO FLOPs match the true masked FLOPs instead of
the 2x overcount of mask-and-discard flash variants. This is the jnp oracle
twin of the Pallas flash kernel in ``repro.kernels.flash_attention``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.util import attn_chunk_default, hint_opt, hints, scan as uscan, wsc

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    y = x.astype(F32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(F32))).astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(F32) + bias.astype(F32)).astype(x.dtype)


def apply_norm(cfg, p, x):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


def init_norm(cfg, d, dtype):
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {"scale": jnp.zeros((d,), dtype)}


# ---------------------------------------------------------------------------
# RoPE (standard / half / M-RoPE)
# ---------------------------------------------------------------------------


def _rope_angles(positions, dim_half: int, theta: float):
    """positions (...,) -> angles (..., dim_half) in fp32."""
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(dim_half, dtype=F32) / dim_half
    )
    return positions.astype(F32)[..., None] * freqs


def _rotate(x, angles):
    """x (..., 2*Dh) split-half rotation with angles (..., Dh)."""
    d_half = angles.shape[-1]
    x1, x2 = x[..., :d_half], x[..., d_half:]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1f, x2f = x1.astype(F32), x2.astype(F32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x1f * sin + x2f * cos], axis=-1)
    return out.astype(x.dtype)


def apply_rope(cfg, x, positions):
    """x: (B, S, H, D). positions: (B, S) int32, or (3, B, S) for M-RoPE."""
    variant = cfg.rope_variant
    if variant == "none":
        return x
    D = x.shape[-1]
    if variant == "standard":
        ang = _rope_angles(positions, D // 2, cfg.rope_theta)  # (B,S,Dh)
        return _rotate(x, ang[:, :, None, :])
    if variant == "half":  # ChatGLM 2d-rope: rotate first half of head dim
        d_rot = D // 2
        ang = _rope_angles(positions, d_rot // 2, cfg.rope_theta)
        rotated = _rotate(x[..., :d_rot], ang[:, :, None, :])
        return jnp.concatenate([rotated, x[..., d_rot:]], axis=-1)
    if variant == "mrope":  # Qwen2-VL: 3 position streams over freq sections
        assert positions.ndim == 3, "mrope needs (3, B, S) positions"
        sections = cfg.mrope_sections
        assert sum(sections) == D // 2, (sections, D)
        angs = []
        off = 0
        for i, sec in enumerate(sections):
            freqs = jnp.exp(
                -math.log(cfg.rope_theta)
                * (jnp.arange(sec, dtype=F32) + off)
                / (D // 2)
            )
            angs.append(positions[i].astype(F32)[..., None] * freqs)
            off += sec
        ang = jnp.concatenate(angs, axis=-1)  # (B, S, D//2)
        return _rotate(x, ang[:, :, None, :])
    raise ValueError(f"unknown rope variant {variant}")


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(cfg, key, d, ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    std_in, std_out = d ** -0.5, ff ** -0.5
    if cfg.mlp_variant in ("swiglu", "geglu"):
        return {
            "w_gate": jax.random.normal(k1, (d, ff), dtype) * std_in,
            "w_up": jax.random.normal(k2, (d, ff), dtype) * std_in,
            "w_down": jax.random.normal(k3, (ff, d), dtype) * std_out,
        }
    return {
        "w_up": jax.random.normal(k1, (d, ff), dtype) * std_in,
        "w_down": jax.random.normal(k2, (ff, d), dtype) * std_out,
    }


def _ar_barrier(y):
    """Perf lever "bf16_ar": anchor the tensor-parallel partial-sum in the
    model dtype. Without the barrier XLA hoists the downstream fp32 norm
    upcast ABOVE the SPMD-inserted all-reduce, doubling every per-layer
    activation all-reduce (observed on starcoder2 prefill: f32[2,32768,6144]
    ARs; EXPERIMENTS.md §Perf H2)."""
    if hint_opt("bf16_ar"):
        return jax.lax.optimization_barrier(y)
    return y


def linear(x, w, eq: str):
    """Matmul that dispatches on the weight leaf: a plain array runs the
    ORIGINAL einsum untouched (byte-identical numerics to the pre-quant
    path); a ``{"w_q": int8, "scale": fp32}`` dict (see
    ``model.quantize_weights``) runs weight-only int8 with fp32
    accumulation and applies the per-output-channel scale AFTER the dot —
    the ``kernels/int8_matmul.py`` contract (matmul-then-scale is exact
    for per-column scales since each output column touches one scale)."""
    if isinstance(w, dict):
        y = jnp.einsum(eq, x.astype(F32), w["w_q"].astype(F32))
        return (y * w["scale"]).astype(x.dtype)
    return jnp.einsum(eq, x, w)


def apply_mlp(cfg, p, x):
    with jax.named_scope("mlp"):
        if cfg.mlp_variant in ("swiglu", "geglu"):
            act = jax.nn.silu if cfg.mlp_variant == "swiglu" else jax.nn.gelu
            g = linear(x, p["w_gate"], "...d,df->...f")
            u = linear(x, p["w_up"], "...d,df->...f")
            h = act(g) * u
        else:
            h = jax.nn.gelu(linear(x, p["w_up"], "...d,df->...f"))
        return _ar_barrier(linear(h, p["w_down"], "...f,fd->...d"))


# ---------------------------------------------------------------------------
# Attention — dense reference path (small sequences)
# ---------------------------------------------------------------------------


def _expand_kv(k, n_rep: int):
    """(B, S, Hkv, D) -> (B, S, Hkv * n_rep, D) by repeat (GQA)."""
    if n_rep == 1:
        return k
    b, s, hkv, d = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, hkv, n_rep, d))
    return k.reshape(b, s, hkv * n_rep, d)


def dense_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_offset: int = 0):
    """Plain masked attention. q (B,Sq,H,D), k/v (B,Skv,Hkv,D).

    ``q_offset``: absolute position of q[0] relative to k[0] (decode=Skv-1).
    ``window``: if >0, keys further than `window` behind the query are masked.
    """
    n_rep = q.shape[2] // k.shape[2]
    k, v = _expand_kv(k, n_rep), _expand_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=F32)
    scores = scores * scale
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v,
                     preferred_element_type=F32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention — flat block-pair online-softmax scan (long sequences)
# ---------------------------------------------------------------------------


def _block_pairs(n_chunks: int, causal: bool, window_chunks: int):
    """Static (i, j) q/kv chunk-pair list, row-major so each q row's pairs
    are contiguous and ascending in j (required by the online softmax)."""
    pairs = []
    for i in range(n_chunks):
        lo = 0
        if window_chunks:
            lo = max(0, i - window_chunks)
        hi = i if causal or window_chunks else n_chunks - 1
        for j in range(lo, hi + 1):
            pairs.append((i, j))
    return np.asarray(pairs, np.int32)


def block_attention(q, k, v, *, causal: bool, window: int = 0,
                    chunk: int = 1024):
    """Memory-efficient attention over long sequences.

    Scans a static list of (q_chunk, kv_chunk) block pairs, maintaining
    online-softmax statistics per q row, writing each finished row into the
    carried output. Only mask-allowed blocks are enumerated, so compiled
    FLOPs ~= true masked FLOPs. Peak memory is O(chunk^2) per head.
    """
    b, s, h, d = q.shape
    hkv = k.shape[2]
    n_rep = h // hkv
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n = s // chunk
    wc = 0
    if window:
        assert window % chunk == 0 or window < chunk, (window, chunk)
        wc = max(1, window // chunk)
    pairs = _block_pairs(n, causal, wc)
    scale = d ** -0.5

    qc = q.reshape(b, n, chunk, h, d)
    kc = k.reshape(b, n, chunk, hkv, d)
    vc = v.reshape(b, n, chunk, hkv, d)

    # Perf lever "attn_carry" (EXPERIMENTS.md §Perf): pin the sharding of
    # the scanned q/k/v blocks and of the carried output/statistics. Without
    # this GSPMD cannot propagate a consistent sharding through the
    # dynamic-update on the carry and falls back to involuntary full
    # rematerialization — an all-gather of the whole output every scan step.
    pin = hint_opt("attn_carry")
    if pin:
        h_ = hints()
        ba, ma = h_["batch_axes"], h_["model_axis"]
        bspec = ba if len(ba) > 1 else ba[0]
        qc = wsc(qc, bspec, None, None, None, ma)
        kc = wsc(kc, bspec, None, None, None, ma)
        vc = wsc(vc, bspec, None, None, None, ma)

        def pin_carry(carry):
            out, m, l, acc = carry
            out = wsc(out, bspec, None, None, None, ma)
            m = wsc(m, bspec, None, None)
            l = wsc(l, bspec, None, None)
            acc = wsc(acc, bspec, None, None, ma)
            return out, m, l, acc
    else:
        def pin_carry(carry):
            return carry

    def step(carry, pair):
        out, m, l, acc = pin_carry(carry)
        i, j = pair[0], pair[1]
        is_row_start = (pair[2] == 1)
        qi = jax.lax.dynamic_index_in_dim(qc, i, 1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kc, j, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vc, j, 1, keepdims=False)
        kj, vj = _expand_kv(kj, n_rep), _expand_kv(vj, n_rep)

        m0 = jnp.where(is_row_start, jnp.full_like(m, -1e30), m)
        l0 = jnp.where(is_row_start, jnp.zeros_like(l), l)
        a0 = jnp.where(is_row_start, jnp.zeros_like(acc), acc)

        s_ij = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                          preferred_element_type=F32) * scale
        qpos = i * chunk + jnp.arange(chunk)[:, None]
        kpos = j * chunk + jnp.arange(chunk)[None, :]
        mask = jnp.ones((chunk, chunk), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s_ij = jnp.where(mask[None, None], s_ij, -1e30)

        m_new = jnp.maximum(m0, s_ij.max(axis=-1))
        alpha = jnp.exp(m0 - m_new)
        p = jnp.exp(s_ij - m_new[..., None])
        l_new = l0 * alpha + p.sum(axis=-1)
        a_new = a0 * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vj.dtype), vj,
            preferred_element_type=F32)

        row_out = (a_new / jnp.maximum(l_new, 1e-30)[..., None]).astype(q.dtype)
        is_row_end = (pair[3] == 1)
        out = jax.lax.cond(
            is_row_end,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, row_out.transpose(0, 2, 1, 3), i, 1),
            lambda o: o,
            out,
        )
        return pin_carry((out, m_new, l_new, a_new)), None

    # annotate row starts / ends statically
    starts = np.zeros(len(pairs), np.int32)
    ends = np.zeros(len(pairs), np.int32)
    for idx, (i, j) in enumerate(pairs):
        if idx == 0 or pairs[idx - 1][0] != i:
            starts[idx] = 1
        if idx == len(pairs) - 1 or pairs[idx + 1][0] != i:
            ends[idx] = 1
    xs = jnp.concatenate(
        [jnp.asarray(pairs), starts[:, None], ends[:, None]], axis=1)

    out0 = jnp.zeros((b, n, chunk, h, d), q.dtype)
    m0 = jnp.full((b, h, chunk), -1e30, F32)
    l0 = jnp.zeros((b, h, chunk), F32)
    acc0 = jnp.zeros((b, h, chunk, d), F32)
    (out, _, _, _), _ = uscan(step, pin_carry((out0, m0, l0, acc0)), xs)
    return out.reshape(b, s, h, d)


def attention(q, k, v, *, causal: bool, window: int = 0, q_offset: int = 0,
              chunk: int = 0, dense_threshold: int = 2048):
    """Dispatch: dense path for short sequences, block scan for long.
    chunk=0 uses the context default (bigger under the dry-run's unrolled
    count-mode to bound the enumerated block-pair count)."""
    if not chunk:
        chunk = attn_chunk_default()
    s = q.shape[1]
    if s <= dense_threshold or s % chunk or q_offset:
        return dense_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)
    return block_attention(q, k, v, causal=causal, window=window, chunk=chunk)


# ---------------------------------------------------------------------------
# Attention — single-token decode against a (possibly rolling) KV cache
# ---------------------------------------------------------------------------


def paged_decode_attention(q, k_pool, v_pool, page_table, pos):
    """Decode attention through a paged KV cache.

    q (B,S,H,D); k/v_pool (P,page_size,Hkv,D) — the device-resident page
    pool shared by every slot; page_table (B,n_pages) int32 maps a slot's
    logical page i (tokens [i*ps, (i+1)*ps)) to a physical pool page;
    pos (B,) counts tokens written including the S queries.

    Gathers the slot's pages into a (B, n_pages*ps, Hkv, D) view and
    reuses the rolling-cache masked softmax (``decode_attention``), so the
    numerics are identical to a rolling window of width n_pages*ps —
    garbage in not-yet-written page slots is hidden by the same per-query
    validity mask. This is the jnp oracle of the Pallas kernel in
    ``repro.kernels.decode_attention.paged_decode_attention``, and the
    path wherever ``served_paged_decode_attention`` does not take it.
    """
    b = q.shape[0]
    _, ps, hkv, d = k_pool.shape
    n_pages = page_table.shape[1]
    k = jnp.take(k_pool, page_table, axis=0).reshape(b, n_pages * ps, hkv, d)
    v = jnp.take(v_pool, page_table, axis=0).reshape(b, n_pages * ps, hkv, d)
    return decode_attention(q, k, v, pos)


def paged_kernel_applies(q, k_pool) -> bool:
    """Whether the Pallas paged kernel can serve this call, judged from
    what the trace shows: one query per slot, bf16 query and pools, an
    even number of kv heads of a multiple of 128, and pools on one device
    (a sharded engine's pools carry their mesh into every trace, and a
    kernel over pools split by kv head would gather them)."""
    hkv, d = k_pool.shape[-2:]
    mesh = jax.typeof(k_pool).sharding.mesh
    return (q.shape[1] == 1 and q.dtype == k_pool.dtype == jnp.bfloat16
            and hkv % 2 == 0 and d % 128 == 0
            and (mesh.empty or mesh.size == 1))


def served_paged_decode_attention(q, k_pool, v_pool, page_table, pos):
    """``paged_decode_attention`` as the engine serves it: where
    ``paged_kernel_applies``, a program lowered for TPU reads the pools
    in place through the Pallas kernel (only each slot's ``pos`` tokens,
    no gather, no mask over the whole row); every other platform and call
    takes the oracle. The arguments are those of the oracle."""
    if not paged_kernel_applies(q, k_pool):
        return paged_decode_attention(q, k_pool, v_pool, page_table, pos)
    from repro.kernels import ops

    return jax.lax.platform_dependent(
        q, k_pool, v_pool, page_table, pos,
        tpu=partial(ops.paged_decode_attention, interpret=False),
        default=paged_decode_attention)


def paged_decode_attention_int8(q, k_pool, v_pool, k_scale, v_scale,
                                page_table, pos):
    """Quantized-pool twin of ``paged_decode_attention``: pools hold int8
    values and per-(token, kv-head) fp32 scales (P, ps, Hkv, 1) addressed
    by the SAME page ids. Gathers values and scales through the page
    table, dequantizes to the compute dtype and runs the identical masked
    softmax — the jnp oracle twin of the fused-dequant Pallas kernel in
    ``repro.kernels.decode_attention.paged_decode_attention_int8`` (both
    dequantize-then-attend, so their numerics agree up to dot-order).
    Trash-page garbage is hidden by the same per-query validity mask."""
    b = q.shape[0]
    _, ps, hkv, d = k_pool.shape
    n_pages = page_table.shape[1]

    def gather(pool, scale):
        vals = jnp.take(pool, page_table, axis=0)
        sc = jnp.take(scale, page_table, axis=0)
        deq = (vals.astype(F32) * sc).astype(q.dtype)
        return deq.reshape(b, n_pages * ps, hkv, d)

    return decode_attention(q, gather(k_pool, k_scale),
                            gather(v_pool, v_scale), pos)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0):
    """q (B,S,H,D); k/v_cache (B,W,Hkv,D); pos (B,) int32 = per-slot count
    of tokens already written INCLUDING all S queries. S=1 is the decode
    step; S>1 is a chunked-prefill chunk whose keys were just written at
    slots [pos-S, pos): query i attends cache slots < pos-S+1+i, which is
    causal within the chunk because chunk keys sit at their own positions.
    Valid cache slots cap at W (rolling buffers overwrite at pos % W, so
    all W slots are valid once pos >= W)."""
    b, w, hkv, d = k_cache.shape
    sq = q.shape[1]
    h = q.shape[2]
    g = h // hkv
    # grouped-GQA einsum: q reshaped to (B, S, Hkv, G, D) contracts the
    # shared kv heads directly — the KV cache is never materialized at
    # q-head multiplicity (a 6x HBM-traffic saving for 48q/8kv configs).
    qg = q.reshape(b, sq, hkv, g, d)
    # Perf lever "kv_seq" (flash-decoding style): the cache is sharded
    # along the sequence dim, so scores/probs inherit a seq-sharded layout
    # and softmax statistics reduce across shards — pin the intermediates
    # so GSPMD keeps everything length-parallel instead of replicating.
    pin_seq = hint_opt("kv_seq")
    k, v = k_cache, v_cache
    if pin_seq:
        h_ = hints()
        ba, ma = h_["batch_axes"], h_["model_axis"]
        bspec = ba if len(ba) > 1 else ba[0]
        k = wsc(k, bspec, ma, None, None)
        v = wsc(v, bspec, ma, None, None)
    scale = d ** -0.5
    scores = jnp.einsum("bqcgd,bwcd->bcgqw", qg, k,
                        preferred_element_type=F32) * scale
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    # per-query valid count: query i (of S) sees pos - S + 1 + i slots
    n_valid = jnp.minimum(
        pos[:, None] - (sq - 1) + jnp.arange(sq, dtype=jnp.int32)[None, :],
        w)  # (B, S)
    valid = (jnp.arange(w)[None, None, None, None, :]
             < n_valid[:, None, None, :, None])
    scores = jnp.where(valid, scores, -1e30)
    if pin_seq:
        scores = wsc(scores, bspec, None, None, None, ma)
    probs = jax.nn.softmax(scores, axis=-1)
    if pin_seq:
        probs = wsc(probs, bspec, None, None, None, ma)
    out = jnp.einsum("bcgqw,bwcd->bqcgd", probs.astype(q.dtype), v,
                     preferred_element_type=F32)
    return out.astype(q.dtype).reshape(b, sq, h, d)


# ---------------------------------------------------------------------------
# Logit processor — per-slot stochastic decode (temperature / top-k / top-p)
# ---------------------------------------------------------------------------


def _float_bits_descending(x):
    """Order-isomorphic uint32 image of f32: bigger float <=> bigger
    unsigned int (sign bit flipped for positives, all bits inverted for
    negatives; +0.0 canonicalizes -0.0 first)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.uint32)
    return jnp.where(bits >> 31 == 0, bits | jnp.uint32(0x80000000), ~bits)


def _radix_threshold(weights, mapped, target):
    """Per row, the maximal representable value t (as a mapped uint32)
    with ``sum(weights where mapped >= t) >= target``: 32 rounds of
    MSB-first bit building over the float-bit image — an exact order
    statistic in O(32 V) vector work, no sort (XLA's CPU sort is ~15x
    slower and this is the decode hot path). ``weights`` of 1 recover
    "count >= k" (the k-th largest); softmax probs recover the nucleus
    boundary (smallest probability the top-p mass still needs)."""

    def body(b, t):
        cand = t | jax.lax.shift_left(jnp.uint32(1), jnp.uint32(31 - b))
        acc = jnp.sum(jnp.where(mapped >= cand[:, None], weights, 0.0),
                      axis=-1)
        return jnp.where(acc >= target, cand, t)

    t0 = jnp.zeros((weights.shape[0],), jnp.uint32)
    return jax.lax.fori_loop(0, 32, body, t0)


def _restricted_probs(x, top_k, top_p):
    """The shared restriction recipe, both cuts as thresholds over ONE
    LOGIT-bit image: the k-th largest logit by a count radix, then the
    nucleus boundary by a mass radix — the maximal logit value whose
    restricted tail still carries ``top_p`` of the restricted mass
    (entries outside the top-k carry zero weight, so candidates below
    the k-th threshold see no mass). Cutting in logit space matters:
    float32 softmax collapses near-tied logits to bit-equal
    probabilities, so a probability-space cut could not separate them.
    Returns (keep mask, softmax weights with 0 outside the mask — the
    restricted distribution up to one shared normalizer).
    ``process_logits`` and the ``sample_tokens`` hot path both call
    this, so their masks are identical by construction."""
    v = x.shape[1]
    b = x.shape[0]
    mapped = _float_bits_descending(x)
    no_thresh = jnp.zeros((b,), jnp.uint32)  # mapped >= 0: keeps all
    k = jnp.where(top_k > 0, jnp.clip(top_k, 1, v), v).astype(F32)
    # a batch with no top-k (or no top-p) rows skips that 32-round radix
    # at runtime — temperature-only sampling pays for neither loop — while
    # staying inside the same trace (lax.cond, not a retrace)
    kth = jax.lax.cond(
        jnp.all(top_k <= 0), lambda _: no_thresh,
        lambda _: _radix_threshold(jnp.ones_like(x), mapped, k), None)
    keep = mapped >= kth[:, None]
    w = jnp.where(keep, jax.nn.softmax(x, axis=-1), 0.0)
    pth = jax.lax.cond(
        jnp.all(top_p >= 1.0), lambda _: no_thresh,
        lambda _: _radix_threshold(w, mapped, jnp.clip(top_p, 1e-30, 1.0)
                                   * jnp.sum(w, axis=-1)), None)
    keep &= (mapped >= pth[:, None]) | (top_p >= 1.0)[:, None]
    return keep, jnp.where(keep, w, 0.0)


def process_logits(logits, temperature, top_k, top_p):
    """Per-row logit processor: temperature scale, then top-k and top-p
    (nucleus) restriction. logits (B,V); temperature (B,) > 0; top_k (B,)
    int32 (0 = no top-k cut); top_p (B,) (>= 1 = no top-p cut). Every
    parameter is a traced per-row array, so one trace serves any mix of
    restrictions in the batch. Removed entries come back -inf; each row
    keeps at least its argmax (top-k clamps to >= 1, the nucleus boundary
    never exceeds the largest probability).

    Both cuts are value thresholds found by radix select over float bits
    (same algorithm as the fused Pallas op in ``kernels/topk_sample.py``;
    the sort-based oracle is ``kernels/ref.py``): entries tied with the
    k-th largest logit / the nucleus-boundary probability all survive,
    and the thresholds are exact bit patterns — no epsilon, so every
    engine configuration computes the identical mask."""
    x = logits.astype(F32) / jnp.maximum(temperature, 1e-6)[:, None]
    keep, _ = _restricted_probs(x, top_k, top_p)
    return jnp.where(keep, x, -jnp.inf)


def sample_tokens(logits, samp, pos):
    """Engine-facing masked composition: greedy rows take pure argmax,
    stochastic rows draw one token from the temperature-scaled,
    top-k/top-p-restricted softmax — ONE trace for any greedy/stochastic
    mix (every parameter is a per-slot traced array). Semantics twin of
    ``process_logits`` + a categorical draw (and of the fused Pallas op
    ``repro.kernels.ops.topk_sample``), but built for the decode hot
    path: the kept set is computed by the exact ``process_logits``
    recipe (top-k radix over LOGIT bits — a prob-space cut would merge
    near-tied logits that float32 softmax collapses to bit-equal
    probabilities — then the nucleus radix over the renormalized
    restricted probabilities), and the draw is inverse-CDF — ONE uniform
    per row against the cumulative masked distribution, instead of a
    vocab-wide Gumbel field (the per-slot threefry work was the single
    biggest cost of the stochastic tick).

    logits (B,V); pos (B,) absolute position of the token being drawn;
    ``samp`` leaves (all (B,...)): greedy bool, temperature f32, top_k
    i32, top_p f32, key uint32 (B,2) per-slot PRNG key material. The
    uniform is keyed by ``fold_in(key, pos)`` — a pure function of (seed,
    position), never of slot index, batch composition, or tick count —
    which is what makes seeded streams bit-reproducible across restarts,
    slot assignments, and cluster replicas. An all-greedy batch skips the
    whole branch at runtime (lax.cond), so deterministic serving pays
    nothing per tick."""
    last = logits.astype(F32)
    greedy_tok = jnp.argmax(last, axis=-1).astype(jnp.int32)

    def draw(_):
        x = last / jnp.maximum(samp["temperature"], 1e-6)[:, None]
        _, pk = _restricted_probs(x, samp["top_k"], samp["top_p"])

        def row_u(key, pp):
            return jax.random.uniform(jax.random.fold_in(key, pp), (), F32)

        u = jax.vmap(row_u)(samp["key"], pos.astype(jnp.int32))
        c = jnp.cumsum(pk, axis=-1)
        total = c[:, -1]
        # u * total can round UP to total (leaving no CDF entry strictly
        # above the threshold -> argmax of all-False would emit token 0);
        # cap at the largest float below total — bias bounded by one ulp,
        # not a truncated tail of the distribution
        thresh = jnp.minimum(u * total, jnp.nextafter(total, 0.0))
        stoch = jnp.argmax(c > thresh[:, None], axis=-1).astype(jnp.int32)
        return jnp.where(samp["greedy"], greedy_tok, stoch)

    return jax.lax.cond(jnp.all(samp["greedy"]),
                        lambda _: greedy_tok, draw, None)
