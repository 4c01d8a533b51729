"""Pallas TPU decode attention (the memory-bound serving hot-spot).

Attention for a small number of new queries against a (rolling) KV cache.
S=1 is the classic decode step: bandwidth-bound (survey §3: the
memory-intensive tenant class), so the kernel's job is streaming K/V
through VMEM exactly once per step at full HBM bandwidth. S>1 is a
chunked-prefill chunk whose keys were just written at slots
[n_valid - S, n_valid): per-query validity (query i sees
``n_valid - (S-1) + i`` slots) makes the mask causal within the chunk.
Grid (batch*heads, kv_blocks): online softmax over kv blocks; invalid
cache slots are masked per query row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _decode_kernel(nvalid_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, block_kv: int, scale: float):
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    sq = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(F32)  # (sq, d)
    k = k_ref[0].astype(F32)  # (bkv, d)
    v = v_ref[0].astype(F32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale  # (sq, bkv)
    slot = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (sq, block_kv), 1)
    # per-query valid slot count: row i sees n_valid - (sq - 1) + i slots
    row = jax.lax.broadcasted_iota(jnp.int32, (sq, block_kv), 0)
    limit = nvalid_ref[bi] - (sq - 1) + row
    s = jnp.where(slot < limit, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
    acc_scr[...] = (acc_scr[...] * alpha[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=F32))
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _paged_decode_kernel(table_ref, nvalid_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, page_size: int, s_q: int,
                         scale: float):
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    rows = q_ref.shape[2]  # G * S query rows sharing this kv head

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    nv = nvalid_ref[bi]

    # Block-sparsity: logical page ki covers cache slots [ki*ps, (ki+1)*ps);
    # pages entirely past the last valid slot contribute nothing and are
    # skipped (their DMA is still scheduled by the grid, but no FLOPs run).
    @pl.when(ki * page_size < nv)
    def _compute():
        q = q_ref[0, 0].astype(F32)  # (rows, d)
        k = k_ref[0, 0].astype(F32)  # (ps, d) — gathered via the page table
        v = v_ref[0, 0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        slot = (ki * page_size
                + jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 1))
        # rows are ordered (g, s): query s of chunk S sees
        # n_valid - (S - 1) + s slots, identically for each of the g heads
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0)
        limit = nv - (s_q - 1) + jax.lax.rem(row, s_q)
        s = jnp.where(slot < limit, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=F32))
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _paged_decode_kernel_int8(table_ref, nvalid_ref, q_ref, k_ref, v_ref,
                              ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr,
                              *, page_size: int, s_q: int, scale: float):
    """Fused-dequant variant of ``_paged_decode_kernel``: K/V pages arrive
    in VMEM as int8 plus one fp32 scale per (slot, kv-head) vector —
    gathered through the SAME scalar-prefetched page-table index map — and
    dequantize inline right before the dots, so HBM traffic per resident
    token is the int8 payload + one fp32 scalar instead of the full-width
    vector (the memory-bound decode step's win)."""
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    rows = q_ref.shape[2]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    nv = nvalid_ref[bi]

    @pl.when(ki * page_size < nv)
    def _compute():
        q = q_ref[0, 0].astype(F32)  # (rows, d)
        # inline dequant in VMEM: int8 page values * per-slot fp32 scale
        k = k_ref[0, 0].astype(F32) * ks_ref[0, 0]  # (ps, d) * (ps, 1)
        v = v_ref[0, 0].astype(F32) * vs_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        slot = (ki * page_size
                + jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 1))
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0)
        limit = nv - (s_q - 1) + jax.lax.rem(row, s_q)
        s = jnp.where(slot < limit, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=F32))
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, table_flat, n_valid, *,
                           s_q: int, interpret: bool = False):
    """Block-sparse decode attention through a paged KV cache.

    q: (B, KVH, R, D) with R = G*S query rows per kv head, ordered (g, s);
    k/v_pool: (KVH, P, ps, D) — the shared page pool, kv-head major;
    table_flat: (B * n_pages,) int32 — slot b's logical page j lives in
    physical pool page ``table_flat[b * n_pages + j]``;
    n_valid: (B,) valid cache slots for the LAST query row of the chunk.

    The page table is a scalar-prefetch operand: the grid's kv step j
    resolves its physical page in the BlockSpec index map, so the kernel
    streams exactly the slot's pages (plus skips compute on pages past
    ``n_valid`` — the block-sparse fast path). Returns (B, KVH, R, D)."""
    b, hkv, rows, d = q.shape
    _, _, ps, _ = k_pool.shape
    n_pages = table_flat.shape[0] // b
    scale = d ** -0.5
    kernel = functools.partial(_paged_decode_kernel, page_size=ps, s_q=s_q,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda bi, hi, ji, t, nv: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda bi, hi, ji, t, nv: (hi, t[bi * n_pages + ji],
                                                    0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda bi, hi, ji, t, nv: (hi, t[bi * n_pages + ji],
                                                    0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda bi, hi, ji, t, nv: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows,), F32),
            pltpu.VMEM((rows,), F32),
            pltpu.VMEM((rows, d), F32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(table_flat, n_valid, q, k_pool, v_pool)


def paged_decode_attention_int8(q, k_pool, v_pool, k_scale, v_scale,
                                table_flat, n_valid, *, s_q: int,
                                interpret: bool = False):
    """Quantized-pool paged decode attention with fused inline dequant.

    Same layout contract as ``paged_decode_attention`` except the pools
    are int8 and each carries a scale pool: k/v_scale (KVH, P, ps, 1)
    fp32 — one symmetric scale per (slot, kv-head) vector, living in pages
    addressed by the SAME page ids, so the scalar-prefetched table
    resolves both the value page and its scale page in the BlockSpec
    index maps. Dequantization happens in VMEM right before the QK/PV
    dots (``kernels/ref.ref_paged_decode_attention_int8`` is the oracle;
    ``ref.int8_attention_error_bound`` bounds the logit error)."""
    b, hkv, rows, d = q.shape
    _, _, ps, _ = k_pool.shape
    n_pages = table_flat.shape[0] // b
    scale = d ** -0.5
    kernel = functools.partial(_paged_decode_kernel_int8, page_size=ps,
                               s_q=s_q, scale=scale)
    page_map = lambda bi, hi, ji, t, nv: (hi, t[bi * n_pages + ji], 0, 0)
    # the trailing singleton keeps the scale block's last two dims equal
    # to the array's, as the TPU lowering requires of a (ps,)-wide block
    scale_map = lambda bi, hi, ji, t, nv: (hi, t[bi * n_pages + ji], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda bi, hi, ji, t, nv: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, ps, d), page_map),
            pl.BlockSpec((1, 1, ps, d), page_map),
            pl.BlockSpec((1, 1, ps, 1), scale_map),
            pl.BlockSpec((1, 1, ps, 1), scale_map),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda bi, hi, ji, t, nv: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows,), F32),
            pltpu.VMEM((rows,), F32),
            pltpu.VMEM((rows, d), F32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(table_flat, n_valid, q, k_pool, v_pool, k_scale, v_scale)


def decode_attention(q, k, v, n_valid, *, block_kv: int = 256,
                     interpret: bool = False):
    """q: (BH, S, D); k/v: (BH, W, D); n_valid: (BH,) int32 — number of
    valid cache slots for the LAST query row (row i of S sees
    ``n_valid - (S-1) + i``; S=1 recovers the classic per-row count).
    Returns (BH, S, D)."""
    bh, w, d = k.shape
    sq = q.shape[1]
    block_kv = min(block_kv, w)
    assert w % block_kv == 0, (w, block_kv)
    scale = d ** -0.5
    grid = (bh, w // block_kv)
    kernel = functools.partial(_decode_kernel, block_kv=block_kv, scale=scale)
    # n_valid is a scalar-prefetch operand (whole array in SMEM): the TPU
    # lowering refuses a (1,)-block over a rank-1 array
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda b, j, nv: (b, 0, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, j, nv: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, j, nv: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, sq, d), lambda b, j, nv: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((sq,), F32),
            pltpu.VMEM((sq,), F32),
            pltpu.VMEM((sq, d), F32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(n_valid, q, k, v)
