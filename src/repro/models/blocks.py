"""Transformer/SSM/recurrent blocks with a unified apply interface.

Block types:
  dense      — GQA attention + MLP (pre-norm residual)
  moe        — GQA attention + MoE MLP
  encoder    — bidirectional attention + MLP (audio encoder)
  local_attn — sliding-window attention + MLP (recurrentgemma)
  rglru      — RG-LRU temporal mixing + MLP
  ssd        — Mamba-2 SSD mixing (no separate MLP)

``apply_block(cfg, btype, p, x, rope_pos, mode, cache)`` returns
``(x, new_cache, aux_loss)``. Caches are dict pytrees; ``None`` cache means
train/prefill-from-scratch. Position bookkeeping (`pos` scalar) lives in the
model-level cache, passed down here.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.moe import apply_moe, init_moe
from repro.models.rglru import apply_rglru_block, init_rglru, init_rglru_cache
from repro.models.ssm import apply_ssd, init_ssd, init_ssd_cache

F32 = jnp.float32

# Block types whose decode cache is a KV ring buffer (vs recurrent state).
# The serving engine keys bucketed/chunked prefill eligibility off this.
KV_CACHE_BLOCKS = ("dense", "moe", "encoder", "local_attn")

# Block types servable from a paged KV cache. local_attn is excluded: its
# ring IS the sliding window (slot index != absolute position), while pages
# address tokens by absolute position; recurrent mixers have no KV at all.
PAGED_BLOCKS = ("dense", "moe")


# ---------------------------------------------------------------------------
# KV quantization (int8 values + per-vector fp32 scales)
# ---------------------------------------------------------------------------


def quantize_kv(t, group: int = 0):
    """Symmetric int8 quantization of a (..., S, kv, hd) K/V tensor: one
    fp32 scale per (token, kv-head) vector, shaped (..., S, kv, 1) so
    scale leaves ride the same rank-4 tree transforms (page scatter /
    gather) as the value leaves. ``group`` > 0 coarsens to one scale per
    ``group`` consecutive tokens (the "page" scale granularity — every
    token of a page shares one dequant multiplier) when the token axis
    divides evenly; otherwise falls back to per-token scales, which only
    tightens the error bound."""
    a = jnp.max(jnp.abs(t.astype(F32)), axis=-1, keepdims=True)
    s = t.shape[-3]
    if group and group > 1 and s % group == 0:
        shp = a.shape
        g = a.reshape(shp[:-3] + (s // group, group) + shp[-2:])
        g = jnp.max(g, axis=-3, keepdims=True)
        a = jnp.broadcast_to(
            g, shp[:-3] + (s // group, group) + shp[-2:]).reshape(shp)
    scale = jnp.maximum(a / 127.0, 1e-8)
    q8 = jnp.clip(jnp.round(t.astype(F32) / scale), -127, 127).astype(jnp.int8)
    return q8, scale


def dequantize_kv(q8, scale, dtype):
    return (q8.astype(F32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_attn(cfg, key, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    q_dim, kv_dim = cfg.num_heads * hd, cfg.num_kv_heads * hd
    ks = jax.random.split(key, 4)
    std = d ** -0.5
    return {
        "wq": jax.random.normal(ks[0], (d, q_dim), dtype) * std,
        "wk": jax.random.normal(ks[1], (d, kv_dim), dtype) * std,
        "wv": jax.random.normal(ks[2], (d, kv_dim), dtype) * std,
        "wo": jax.random.normal(ks[3], (q_dim, d), dtype) * (q_dim ** -0.5),
    }


def init_block(cfg, btype: str, key, dtype):
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"norm1": L.init_norm(cfg, d, dtype)}
    if btype in ("dense", "encoder", "local_attn"):
        p["attn"] = init_attn(cfg, k1, dtype)
        p["norm2"] = L.init_norm(cfg, d, dtype)
        ff = cfg.dense_d_ff or cfg.d_ff
        p["mlp"] = L.init_mlp(cfg, k2, d, ff, dtype)
    elif btype == "moe":
        p["attn"] = init_attn(cfg, k1, dtype)
        p["norm2"] = L.init_norm(cfg, d, dtype)
        p["moe"] = init_moe(cfg, k2, dtype)
    elif btype == "rglru":
        p["mixer"] = init_rglru(cfg, k1, dtype)
        p["norm2"] = L.init_norm(cfg, d, dtype)
        p["mlp"] = L.init_mlp(cfg, k2, d, cfg.d_ff, dtype)
    elif btype == "ssd":
        p["mixer"] = init_ssd(cfg, k1, dtype)
    else:
        raise ValueError(btype)
    return p


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def attn_cache_window(cfg, btype: str, seq_len: int) -> int:
    """KV window for decode: local blocks use their native window; full
    attention uses the full seq unless the model-level sliding window is
    engaged (long_500k)."""
    if btype == "local_attn":
        return min(cfg.local_window, seq_len)
    return seq_len


def init_block_cache(cfg, btype: str, batch: int, window: int, dtype,
                     kv_dtype: str = ""):
    hd, kv = cfg.resolved_head_dim, cfg.num_kv_heads
    if btype in KV_CACHE_BLOCKS:
        w = min(window, cfg.local_window) if btype == "local_attn" else window
        if kv_dtype == "int8":
            # quantized serving cache: per-(token, kv-head) symmetric scale;
            # the trailing singleton keeps scale leaves rank-4 so every
            # page scatter/gather treats them exactly like value leaves
            return {
                "k": jnp.zeros((batch, w, kv, hd), jnp.int8),
                "v": jnp.zeros((batch, w, kv, hd), jnp.int8),
                "k_scale": jnp.zeros((batch, w, kv, 1), jnp.float32),
                "v_scale": jnp.zeros((batch, w, kv, 1), jnp.float32),
            }
        return {
            "k": jnp.zeros((batch, w, kv, hd), dtype),
            "v": jnp.zeros((batch, w, kv, hd), dtype),
        }
    if btype == "rglru":
        return init_rglru_cache(cfg, batch, dtype)
    if btype == "ssd":
        return init_ssd_cache(cfg, batch, dtype)
    raise ValueError(btype)


# ---------------------------------------------------------------------------
# attention block body
# ---------------------------------------------------------------------------


def init_paged_block_cache(cfg, btype: str, n_pages: int, page_size: int,
                           dtype, kv_dtype: str = ""):
    """Paged serving cache for one attention block: a page POOL shared by
    every decode slot (no batch axis — slots own disjoint page sets via the
    model-level page table). Only KV blocks are pageable; recurrent mixers
    keep their per-slot state and the engine falls back to rolling windows
    for archs that contain them. ``kv_dtype`` "int8" stores int8 values
    plus per-vector fp32 scale pages addressed by the SAME page ids (the
    host-side allocator and page tables are unchanged)."""
    if btype not in KV_CACHE_BLOCKS:
        raise ValueError(f"{btype} blocks have no pageable KV cache")
    hd, kv = cfg.resolved_head_dim, cfg.num_kv_heads
    if kv_dtype == "int8":
        return {
            "k": jnp.zeros((n_pages, page_size, kv, hd), jnp.int8),
            "v": jnp.zeros((n_pages, page_size, kv, hd), jnp.int8),
            "k_scale": jnp.zeros((n_pages, page_size, kv, 1), jnp.float32),
            "v_scale": jnp.zeros((n_pages, page_size, kv, 1), jnp.float32),
        }
    return {
        "k": jnp.zeros((n_pages, page_size, kv, hd), dtype),
        "v": jnp.zeros((n_pages, page_size, kv, hd), dtype),
    }


def _paged_attn_decode(cfg, q, k, v, cache, pages, pos, layer=None):
    """Write the chunk's K/V through the page table and attend.

    ``cache`` holds the shared pools (P, ps, kv, hd); ``pages`` is the
    (B, n_pages) page table; token t of slot b lands in physical page
    ``pages[b, t // ps]`` at offset ``t % ps``. The allocator guarantees
    live slots own disjoint pages, so the batched scatter has no
    cross-slot collisions (freed/inactive slots all alias the reserved
    trash page 0, whose contents are never attended with weight).

    With ``layer`` (the traced index in the layer scan) ``cache`` holds
    the body's stacked pools (L, P, ps, kv, hd), written and read in
    place as their (L*P, ps, kv, hd) rows (a bitcast), in which this
    layer's page p is row ``layer * P + p``: no layer's pool is sliced
    out of the stack and no copy of the stack is written back."""
    b, s = q.shape[:2]
    n_pool, ps = cache["k"].shape[-4:-2]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    rows = cache
    if layer is not None:
        rows = {name: a.reshape((-1,) + a.shape[2:])
                for name, a in cache.items()}
        pages = pages + layer * n_pool
    with jax.named_scope("attn_kv_write"):
        t = pos_b[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # (B, S)
        phys = jnp.take_along_axis(pages, t // ps, axis=1)  # (B, S)
        off = t % ps
        if cache["k"].dtype == jnp.int8:
            # quantized pools: scatter int8 values AND their per-token
            # scales at the same (page, offset) addresses — decode-time
            # appends are always per-token regardless of the prefill
            # scale granularity
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            writes = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        else:
            writes = {"k": k.astype(cache["k"].dtype),
                      "v": v.astype(cache["v"].dtype)}
        rows = {name: a.at[phys, off].set(writes[name])
                for name, a in rows.items()}
    with jax.named_scope("attn_core"):
        if "k_scale" in rows:
            out = L.paged_decode_attention_int8(
                q, rows["k"], rows["v"], rows["k_scale"], rows["v_scale"],
                pages, pos_b + s)
        else:
            out = L.served_paged_decode_attention(
                q, rows["k"], rows["v"], pages, pos_b + s)
    return out, {name: a.reshape(cache[name].shape)
                 for name, a in rows.items()}


def _attn_apply(cfg, p, x, rope_pos, *, mode: str, cache, pos, window: int,
                causal: bool, project: bool = True, pages=None, layer=None):
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    with jax.named_scope("attn_qkv"):
        q = L.linear(x, p["wq"], "bsd,de->bse").reshape(b, s, h, hd)
        k = L.linear(x, p["wk"], "bsd,de->bse").reshape(b, s, kv, hd)
        v = L.linear(x, p["wv"], "bsd,de->bse").reshape(b, s, kv, hd)
        q = L.apply_rope(cfg, q, rope_pos)
        k = L.apply_rope(cfg, k, rope_pos)

    quantized = cache is not None and cache["k"].dtype == jnp.int8

    new_cache = cache
    if mode == "decode" and pages is not None:
        out, new_cache = _paged_attn_decode(cfg, q, k, v, cache, pages, pos,
                                            layer)
    elif mode == "decode":
        # s == 1: one decode step. s > 1: one chunked-prefill chunk — the
        # chunk's keys are written at their rolling slots and the per-query
        # validity mask in decode_attention makes attention causal within
        # the chunk (chunk i must satisfy pos + s <= W; the engine
        # guarantees this by falling back to single-shot prefill).
        assert cache is not None
        w = cache["k"].shape[1]
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        with jax.named_scope("attn_kv_write"):
            slots = jax.lax.rem(
                pos_b[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :], w)
            rows = jnp.arange(b)[:, None]
            if quantized:
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                new_cache = {
                    "k": cache["k"].at[rows, slots].set(kq),
                    "v": cache["v"].at[rows, slots].set(vq),
                    "k_scale": cache["k_scale"].at[rows, slots].set(ks),
                    "v_scale": cache["v_scale"].at[rows, slots].set(vs),
                }
            else:
                new_cache = {"k": cache["k"].at[rows, slots].set(k),
                             "v": cache["v"].at[rows, slots].set(v)}
        with jax.named_scope("attn_core"):
            if quantized:
                kc = dequantize_kv(new_cache["k"], new_cache["k_scale"],
                                   k.dtype)
                vc = dequantize_kv(new_cache["v"], new_cache["v_scale"],
                                   v.dtype)
            else:
                kc, vc = new_cache["k"], new_cache["v"]
            out = L.decode_attention(q, kc, vc, pos_b + s, window=window)
    else:
        with jax.named_scope("attn_core"):
            out = L.attention(q, k, v, causal=causal, window=window)
        if cache is not None:  # prefill: fill the cache with the last W keys
            w = cache["k"].shape[1]
            with jax.named_scope("attn_kv_write"):
                k_w, v_w = (k[:, -w:], v[:, -w:]) if s >= w else (k, v)
                if quantized:
                    from repro.util import hint_val

                    # single-shot prefill is the one write whose token
                    # positions are guaranteed page-aligned from 0, so the
                    # "page" scale granularity groups here (hint_val is 0 =
                    # per-token otherwise); a truncated window (s > w) starts
                    # mid-page and keeps per-token scales, which only
                    # tightens the error bound
                    group = hint_val("kv_scale_page") if s <= w else 0
                    kq, ks = quantize_kv(k_w, group=group)
                    vq, vs = quantize_kv(v_w, group=group)
                    writes = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
                else:
                    writes = {"k": k_w, "v": v_w}
                if s >= w:
                    new_cache = writes
                else:
                    new_cache = {
                        name: jax.lax.dynamic_update_slice_in_dim(
                            cache[name], val, 0, 1)
                        for name, val in writes.items()
                    }
    out = out.reshape(b, s, h * hd)
    if not project:
        return out, new_cache
    with jax.named_scope("attn_out"):
        out = L._ar_barrier(L.linear(out, p["wo"], "bse,ed->bsd"))
    return out, new_cache


def apply_block(cfg, btype: str, p, x, rope_pos, *, mode: str, cache=None,
                pos=None, pages=None, layer=None):
    """Returns (x, new_cache, aux_loss). ``pages`` (B, n_pages) switches
    attention blocks to the paged KV cache (decode mode only); with
    ``layer`` (traced) ``cache`` is the scanned body's stacked pools and
    the block updates its layer's pages in place."""
    from repro.util import hint_opt

    aux = jnp.zeros((), F32)
    if btype in KV_CACHE_BLOCKS:
        causal = cfg.causal and btype != "encoder"
        window = cfg.local_window if btype == "local_attn" else 0
        if (hint_opt("parallel_block") and btype != "moe"
                and not isinstance(p["attn"]["wo"], dict)):
            # (int8 weight leaves are {"w_q", "scale"} dicts — the fused
            # wo/w_down concat below needs plain matrices, so quantized
            # weights take the unfused path)
            # PaLM-style parallel attention+MLP with FUSED output
            # projection: concat the attention context and the MLP hidden
            # along the (model-sharded) contraction dim and project with
            # one dot — one partial sum, hence ONE tensor-parallel
            # all-reduce per layer instead of two. (Perf lever; a serving
            # variant for models trained with parallel blocks.)
            h = L.apply_norm(cfg, p["norm1"], x)
            a_ctx, new_attn_cache = _attn_apply(
                cfg, p["attn"], h, rope_pos, mode=mode, cache=cache,
                pos=pos, window=window, causal=causal, project=False,
                pages=pages, layer=layer)
            h2 = L.apply_norm(cfg, p["norm2"], x)
            if cfg.mlp_variant in ("swiglu", "geglu"):
                act = jax.nn.silu if cfg.mlp_variant == "swiglu" else jax.nn.gelu
                hid = act(jnp.einsum("...d,df->...f", h2, p["mlp"]["w_gate"])) \
                    * jnp.einsum("...d,df->...f", h2, p["mlp"]["w_up"])
            else:
                hid = jax.nn.gelu(
                    jnp.einsum("...d,df->...f", h2, p["mlp"]["w_up"]))
            z = jnp.concatenate([a_ctx, hid], axis=-1)
            w_cat = jnp.concatenate([p["attn"]["wo"], p["mlp"]["w_down"]],
                                    axis=0)
            out = jnp.einsum("bsz,zd->bsd", z, w_cat)
            return x + out, new_attn_cache, aux
        h = L.apply_norm(cfg, p["norm1"], x)
        a, new_attn_cache = _attn_apply(
            cfg, p["attn"], h, rope_pos, mode=mode, cache=cache, pos=pos,
            window=window, causal=causal, pages=pages, layer=layer)
        x = x + a
        h = L.apply_norm(cfg, p["norm2"], x)
        if btype == "moe":
            m, aux = apply_moe(cfg, p["moe"], h)
        else:
            m = L.apply_mlp(cfg, p["mlp"], h)
        x = x + m
        return x, new_attn_cache, aux
    if btype == "rglru":
        h = L.apply_norm(cfg, p["norm1"], x)
        m, new_cache = apply_rglru_block(cfg, p["mixer"], h, cache=cache)
        x = x + m
        h = L.apply_norm(cfg, p["norm2"], x)
        x = x + L.apply_mlp(cfg, p["mlp"], h)
        return x, new_cache, aux
    if btype == "ssd":
        h = L.apply_norm(cfg, p["norm1"], x)
        m, new_cache = apply_ssd(cfg, p["mixer"], h, cache=cache)
        return x + m, new_cache, aux
    raise ValueError(btype)
