"""Pallas TPU fused top-k + softmax sampling (the decode logit tail).

One grid step = one batch row: the row's logits live in VMEM, the k-th
largest scaled logit is found by a 32-step radix select over the
order-isomorphic uint32 image of float32 (no sort — Pallas has none, and
a full sort would be O(V log V) of serial work for a single order
statistic), and the categorical draw over the surviving top-k softmax is
taken as a Gumbel argmax in the same pass. Threshold semantics are
``x >= kth`` (value ties all survive), exactly matching the sort-based
oracle ``repro.kernels.ref.ref_topk_sample``; ``-0.0`` is canonicalized
to ``+0.0`` before the bit mapping so the uint32 order agrees with IEEE
float order everywhere the oracle can reach.

Uniform noise is an input (the serving engine derives it from per-slot
PRNG keys folded with the absolute token position; see
``repro.models.layers.sample_tokens``, the model-layout twin that adds
top-p and the greedy mask), which also makes kernel-vs-oracle equality
exact instead of distributional.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _topk_sample_kernel(k_ref, temp_ref, x_ref, u_ref, o_ref):
    i = pl.program_id(0)
    v = x_ref.shape[2]
    # temperature scale (+0.0 canonicalizes -0.0 for the bit mapping)
    x = x_ref[0].astype(F32) / temp_ref[i] + 0.0  # (1, V)
    # order-isomorphic uint32 image of float32: descending float order ==
    # descending unsigned order (sign bit flipped for positives, all bits
    # inverted for negatives)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    mapped = jnp.where(bits >> 31 == 0,
                       bits | jnp.uint32(0x80000000), ~bits)
    k = k_ref[i]

    # radix select: build the k-th largest mapped value MSB-first; a bit
    # stays set iff at least k elements still reach the candidate prefix
    def body(b, t):
        cand = t | jax.lax.shift_left(jnp.uint32(1),
                                      jnp.uint32(31 - b))
        cnt = jnp.sum(jnp.where(mapped >= cand, 1, 0))
        return jnp.where(cnt >= k, cand, t)

    kth = jax.lax.fori_loop(0, 32, body, jnp.uint32(0))
    keep = mapped >= kth

    # Gumbel argmax over the surviving entries == one categorical draw
    # from their softmax (temperature already applied)
    u = jnp.maximum(u_ref[0].astype(F32), 1e-12)
    z = jnp.where(keep, x - jnp.log(-jnp.log(u)), NEG_INF)
    m = jnp.max(z)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, v), 1)
    o_ref[...] = jnp.full(o_ref.shape, jnp.min(jnp.where(z == m, idx, v)),
                          jnp.int32)


def topk_sample(logits, k, temperature, uniform, *, interpret: bool = False):
    """logits (B, V) float; k (B,) int32 in [1, V]; temperature (B,) > 0;
    uniform (B, V) in [0, 1). Returns (B,) int32 — one token per row drawn
    from the temperature-scaled, top-k-restricted softmax."""
    b, v = logits.shape
    # k and temperature are scalar-prefetch operands (whole arrays in SMEM);
    # rows ride as (B, 1, V) so each block's last two dims equal the
    # array's — the TPU lowering refuses a (1, V) block of a (B, V) array
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, v), lambda i, k_, t_: (i, 0, 0)),
            pl.BlockSpec((1, 1, v), lambda i, k_, t_: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1), lambda i, k_, t_: (i, 0, 0)),
    )
    return pl.pallas_call(
        _topk_sample_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, 1), jnp.int32),
        interpret=interpret,
    )(k.astype(jnp.int32), temperature.astype(F32),
      logits.reshape(b, 1, v), uniform.reshape(b, 1, v))[:, 0, 0]
