"""Pallas TPU decode attention (the memory-bound serving hot-spot).

Attention for a small number of new queries against a KV cache. S=1 is
the classic decode step: bandwidth-bound (survey §3: the
memory-intensive tenant class), so a kernel's job is streaming K/V
through VMEM exactly once per step at full HBM bandwidth.

- ``decode_attention``: a rolling (B*H, W, D) cache. S>1 is a
  chunked-prefill chunk whose keys were just written at slots
  [n_valid - S, n_valid): per-query validity (query i sees
  ``n_valid - (S-1) + i`` slots) makes the mask causal within the chunk.
  Grid (batch*heads, kv_blocks): online softmax over kv blocks.
- ``paged_decode_attention``: the served S=1 step through the engine's
  page pools, read in place in their token-major layout, only up to each
  slot's length (``models/layers.paged_decode_attention`` is its oracle).
- ``paged_decode_attention_int8``: int8 pages dequantized in VMEM, over
  kv-head-major pools (not served).
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


def _head_rows(buf, h):
    """Kv head ``h``'s rows of a VMEM block ``buf`` (pages, ps, KVH, D)
    bf16, as (pages * ps, D) float32, exactly. The block is token-major, so
    one head's rows are every KVH-th row of its (pages * ps * KVH, D)
    view; bf16 packs row pairs into 32-bit words, so the word rows of
    heads (2i, 2i + 1) are read with stride KVH / 2 and split by halves
    (the even head in the low 16 bits)."""
    n_pages, ps, kvh, d = buf.shape
    t = n_pages * ps
    words = buf.reshape(t * kvh, d).bitcast(jnp.uint32)
    w = words[pl.ds(h // 2, t, stride=kvh // 2), :] if kvh > 2 else words[...]
    w = w << 16 if h % 2 == 0 else w & jnp.uint32(0xFFFF0000)
    return pltpu.bitcast(w, F32)


def _paged_decode_kernel(table_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sem, carry, m_scr, l_scr, acc_scr, *,
                         n_pages: int, scale: float):
    """One grid step attends one slot's S=1 query to its first
    ``len_ref[b]`` tokens, a block of pages at a time.

    The pools stay where they are in HBM: each live page of a block is
    copied into VMEM by its own DMA, addressed through the page table,
    while the previous block is attended (two buffers). The last block of
    a slot starts the next slot's first block, and ``carry`` hands that
    over to the next grid step (started?, buffer). Blocks and pages past
    the slot's length are neither copied nor computed."""
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    _, ppb, ps, kvh, _ = k_buf.shape
    blk = ppb * ps
    n = len_ref[b]
    n_blocks = pl.cdiv(n, blk)

    def for_live_pages(i, j, slot, act):
        """``act`` on the DMAs of slot i's block j: one per live page, of
        K and of V, into buffer ``slot``."""
        live = pl.cdiv(len_ref[i], ps) - j * ppb
        for p in range(ppb):
            @pl.when(p < live)
            def _():
                page = table_ref[i * n_pages + j * ppb + p]
                for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    act(pltpu.make_async_copy(hbm.at[page], buf.at[slot, p],
                                              sem.at[slot]))

    def start(i, j, slot):
        for_live_pages(i, j, slot, lambda c: c.start())

    @pl.when(b == 0)
    def _():
        carry[0] = 0
        carry[1] = 0

    @pl.when(jnp.logical_and(n_blocks > 0, carry[0] == 0))
    def _():
        start(b, 0, carry[1])

    nxt = jnp.minimum(b + 1, n_slots - 1)
    nxt_live = jnp.logical_and(b + 1 < n_slots, len_ref[nxt] > 0)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(j, slot):
        other = 1 - slot

        @pl.when(j + 1 < n_blocks)
        def _():
            start(b, j + 1, other)

        @pl.when(jnp.logical_and(j + 1 == n_blocks, nxt_live))
        def _():
            start(nxt, 0, other)

        for_live_pages(b, j, slot, lambda c: c.wait())
        rem = n - j * blk  # valid tokens from this block's first on
        for h in range(kvh):
            q = q_ref[0, h]  # (G, D): the query heads of kv head h
            k = _head_rows(k_buf.at[slot], h).astype(q.dtype)
            v = _head_rows(v_buf.at[slot], h)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32) * scale
            tok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(tok < rem, s, NEG_INF)
            # rows past the length may hold what an earlier block left
            # (or nothing ever written): zero them, as p is 0 there
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < rem, v, 0.0).astype(q.dtype)
            m_prev = m_scr[h]  # (G, 128), equal along lanes
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_scr[h] = alpha * l_scr[h] + p.sum(axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha[:, :1] + jax.lax.dot_general(
                p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=F32)
            m_scr[h] = m_new
        return other

    slot = jax.lax.fori_loop(0, n_blocks, block, carry[1])
    carry[0] = jnp.logical_and(n_blocks > 0, nxt_live).astype(jnp.int32)
    carry[1] = slot
    # a slot of length 0 attends nothing and returns zeros
    l = jnp.maximum(l_scr[...][:, :, :1], 1e-30)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_decode_kernel_int8(table_ref, nvalid_ref, q_ref, k_ref, v_ref,
                              ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr,
                              *, page_size: int, s_q: int, scale: float):
    """Fused-dequant paged decode over kv-head-major pools: K/V pages arrive
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


#: bytes of one K (or V) block in VMEM: 512 tokens of 2 kv heads of 128
#: (chatglm3), 128 tokens of 8 (granite); two buffers each of K and V
BLOCK_BYTES = 256 * 1024


def pages_per_block(n_pages: int, page_bytes: int) -> int:
    """Pages of one block: the largest power of two, at most ``n_pages``,
    whose K (or V) fits ``BLOCK_BYTES``."""
    ppb = 1
    while ppb * 2 <= n_pages and 2 * ppb * page_bytes <= BLOCK_BYTES:
        ppb *= 2
    return ppb


def paged_decode_attention(q, k_rows, v_rows, table, lengths, *,
                           interpret: bool = False):
    """S=1 decode attention read in place from the engine's page pools.

    q: (B, KVH, G, D) bf16, the G query heads of each kv head;
    k/v_rows: (N, ps, KVH, D) bf16, the pool rows in HBM, token-major as
    the engine keeps them (a layer scan's stacked pools as their
    (L*P, ps, KVH, D) rows), never copied or transposed; table: (B,
    n_pages) int32, slot b's logical page j is row ``table[b, j]``;
    lengths: (B,) int32 tokens slot b attends (its last query included).
    KVH is even and D a multiple of 128. The table and lengths are
    scalar-prefetched; a slot costs one grid step and ceil(length / block)
    blocks of pages, sized by ``BLOCK_BYTES``. Returns (B, KVH, G, D)."""
    b, kvh, g, d = q.shape
    ps = k_rows.shape[1]
    n_pages = table.shape[1]
    ppb = pages_per_block(n_pages, ps * kvh * d * k_rows.dtype.itemsize)
    kernel = functools.partial(_paged_decode_kernel, n_pages=n_pages,
                               scale=d ** -0.5)
    per_slot = pl.BlockSpec((1, kvh, g, d), lambda i, t, n: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[per_slot, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=per_slot,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, kvh, d), k_rows.dtype),
            pltpu.VMEM((2, ppb, ps, kvh, d), v_rows.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            # running max and sum, equal along the 128 lanes
            pltpu.VMEM((kvh, g, 128), F32),
            pltpu.VMEM((kvh, g, 128), F32),
            pltpu.VMEM((kvh, g, d), F32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # a slot's last block starts the next slot's first: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(table.reshape(-1), jnp.minimum(lengths, n_pages * ps), q, k_rows,
      v_rows)


def paged_decode_attention_int8(q, k_pool, v_pool, k_scale, v_scale,
                                table_flat, n_valid, *, s_q: int,
                                interpret: bool = False):
    """Quantized-pool paged decode attention with fused inline dequant.

    q: (B, KVH, R, D) with R = G*S query rows per kv head, ordered (g, s);
    k/v_pool: (KVH, P, ps, D) int8, kv-head major; table_flat:
    (B * n_pages,) int32; n_valid: (B,) valid cache slots for the LAST
    query row. Each pool carries a scale pool: k/v_scale (KVH, P, ps, 1)
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
