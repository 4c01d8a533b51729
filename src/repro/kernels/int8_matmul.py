"""Pallas TPU int8-weight matmul (serving-side weight quantization).

Weight-only int8 halves the decode step's dominant HBM term (the survey's
TB-scale DLRM remark and the memory-bound §3 tenant class). Per-output-
channel fp32 scales; accumulation in fp32 on the MXU; dequantize once per
output tile. Grid (M/bm, N/bn, K/bk), K innermost with a VMEM accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _int8_mm_kernel(x_ref, w_ref, scale_ref, o_ref, acc_scr):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(F32)  # (bm, bk)
    w = w_ref[...].astype(F32)  # (bk, bn) int8 -> f32
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=F32)

    @pl.when(ki == nk - 1)
    def _final():
        o_ref[...] = (acc_scr[...] * scale_ref[...]).astype(o_ref.dtype)


def int8_matmul(x, w_q, scales, *, block_m: int = 128, block_n: int = 128,
                block_k: int = 128, interpret: bool = False):
    """x: (M, K) float; w_q: (K, N) int8; scales: (N,) fp32 per-channel.
    Returns (M, N) in x.dtype."""
    m, k = x.shape
    k2, n = w_q.shape
    assert k == k2
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _int8_mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            # scales ride as a (1, N) row: a rank-1 (block_n,) block gets a
            # different tiled layout from XLA than the TPU kernel expects
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), F32)],
        interpret=interpret,
    )(x, w_q, scales.reshape(1, n))


def quantize_int8(w, axis: int = 0):
    """Symmetric per-output-channel int8 quantization. w: (K, N)."""
    amax = jnp.max(jnp.abs(w.astype(F32)), axis=axis, keepdims=True)
    scale = (amax / 127.0).clip(1e-12)
    w_q = jnp.clip(jnp.round(w.astype(F32) / scale), -127, 127).astype(jnp.int8)
    return w_q, scale.reshape(-1)
