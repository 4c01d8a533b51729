"""Pallas TPU RG-LRU linear-recurrence scan.

TPU adaptation (DESIGN.md §4): instead of a log-depth associative scan
(whose intermediate (a, x) pairs round-trip HBM log(S) times on TPU), the
kernel keeps the hidden state h resident in VMEM and walks time
sequentially in channel-blocked tiles: grid (batch, channel_blocks,
time_blocks) with time innermost; each step applies ``block_t`` recurrence
iterations on-chip. Bandwidth = one read of (a, x) + one write of y —
optimal for this memory-bound op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _rglru_kernel(a_ref, x_ref, h0_ref, y_ref, hT_ref, h_scr, a_scr, x_scr,
                  y_scr, *, block_t: int):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(F32)  # (1, block_l)

    # one (1, block_l) row per step, read and written through f32 VMEM
    # scratch: the TPU lowering has no dynamic slice of a loaded value, and
    # a dynamic single-row access must not split a packed (bf16) sublane
    a_scr[...] = a_ref[0].astype(F32)
    x_scr[...] = x_ref[0].astype(F32)

    def step(t, h):
        row = pl.ds(t, 1)
        h_new = a_scr[row, :] * h + x_scr[row, :]
        y_scr[row, :] = h_new
        return h_new

    h = jax.lax.fori_loop(0, block_t, step, h_scr[...])
    h_scr[...] = h
    y_ref[0] = y_scr[...].astype(y_ref.dtype)

    @pl.when(ti == nt - 1)
    def _final():
        hT_ref[0] = h.astype(hT_ref.dtype)


def rglru_scan_kernel(a, x, h0, *, block_l: int = 128, block_t: int = 128,
                      interpret: bool = False):
    """Linear recurrence h_t = a_t * h_{t-1} + x_t.

    a/x: (B, S, L); h0: (B, L). Returns (y (B, S, L), h_last (B, L))."""
    b, s, l = a.shape
    block_l = min(block_l, l)
    block_t = min(block_t, s)
    assert l % block_l == 0 and s % block_t == 0, (l, s, block_l, block_t)
    grid = (b, l // block_l, s // block_t)
    kernel = functools.partial(_rglru_kernel, block_t=block_t)
    y, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_l), lambda b_, c, t: (b_, t, c)),
            pl.BlockSpec((1, block_t, block_l), lambda b_, c, t: (b_, t, c)),
            # h0/hT ride as (B, 1, L) so each block's last two dims are
            # (1, block_l) of a (1, L) array, as the TPU lowering requires
            pl.BlockSpec((1, 1, block_l), lambda b_, c, t: (b_, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_l), lambda b_, c, t: (b_, t, c)),
            pl.BlockSpec((1, 1, block_l), lambda b_, c, t: (b_, 0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(a.shape, a.dtype),
            jax.ShapeDtypeStruct((b, 1, l), h0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_l), F32),
                        pltpu.VMEM((block_t, block_l), F32),
                        pltpu.VMEM((block_t, block_l), F32),
                        pltpu.VMEM((block_t, block_l), F32)],
        interpret=interpret,
    )(a, x, h0.reshape(b, 1, l))
    return y, hT.reshape(b, l)
