"""Unified model: block program -> scan-over-layers forward/decode.

The layer stack is expressed as a *block program*: a repeating ``pattern``
of block types scanned ``n_repeat`` times (stacked weights, O(1) HLO in
depth) plus an unrolled ``tail`` when ``num_layers`` is not a multiple of
the pattern length (e.g. recurrentgemma's 38 = 12*(r,r,a) + (r,r)).

Public API:
  block_program(cfg)                   -> (pattern, n_repeat, tail)
  init_params(cfg, key)                -> params pytree (real arrays)
  param_specs(cfg)                     -> ShapeDtypeStruct pytree (dry-run)
  forward(cfg, params, batch, mode)    -> (logits, aux, cache_or_None)
  init_cache(cfg, batch, window)       -> decode cache pytree
  cache_specs(cfg, batch, window)      -> ShapeDtypeStruct cache (dry-run)
  decode_step(cfg, params, cache, batch) -> (logits, new_cache)
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.blocks import (
    PAGED_BLOCKS,
    apply_block,
    init_block,
    init_block_cache,
    init_paged_block_cache,
)
from repro.util import scan as uscan

F32 = jnp.float32


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# block program
# ---------------------------------------------------------------------------


def block_program(cfg) -> Tuple[Tuple[str, ...], int, Tuple[str, ...]]:
    if cfg.arch_type in ("dense", "vlm"):
        pattern = ("dense",)
    elif cfg.arch_type == "audio":
        pattern = ("encoder",)
    elif cfg.arch_type == "moe":
        k = cfg.moe_layer_period
        pattern = ("dense",) * (k - 1) + ("moe",)
    elif cfg.arch_type == "ssm":
        pattern = ("ssd",)
    elif cfg.arch_type == "hybrid":
        pattern = cfg.block_pattern or ("rglru", "rglru", "local_attn")
    else:
        raise ValueError(cfg.arch_type)
    n_repeat = cfg.num_layers // len(pattern)
    tail = cfg.block_pattern[: cfg.num_layers % len(pattern)] if cfg.num_layers % len(pattern) else ()
    if cfg.num_layers % len(pattern):
        tail = pattern[: cfg.num_layers % len(pattern)]
    return pattern, n_repeat, tail


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg, key):
    dtype = _dtype(cfg)
    pattern, n_repeat, tail = block_program(cfg)
    keys = jax.random.split(key, 4)

    def stacked_block(btype, k):
        ks = jax.random.split(k, n_repeat)
        return jax.vmap(lambda kk: init_block(cfg, btype, kk, dtype))(ks)

    body_keys = jax.random.split(keys[0], len(pattern))
    body = [stacked_block(bt, bk) for bt, bk in zip(pattern, body_keys)]
    tail_keys = jax.random.split(keys[1], max(len(tail), 1))
    tail_p = [init_block(cfg, bt, tk, dtype) for bt, tk in zip(tail, tail_keys)]

    d, v = cfg.d_model, cfg.vocab_size
    params = {
        "body": body,
        "tail": tail_p,
        "final_norm": L.init_norm(cfg, d, dtype),
    }
    if cfg.modality != "audio":  # audio: stubbed frontend, no token embed
        params["embed"] = jax.random.normal(keys[2], (v, d), dtype) * (d ** -0.5)
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(keys[3], (d, v), dtype) * (d ** -0.5)
    return params


def param_specs(cfg):
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))


def param_count_tree(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_cache(cfg, batch: int, window: int, kv_dtype: str = ""):
    """Decode cache: per-block state + per-slot position. ``kv_dtype``
    "int8" enables the quantized serving cache (values + per-vector
    scales; EXPERIMENTS.md §Perf H1 it.3)."""
    dtype = _dtype(cfg)
    pattern, n_repeat, tail = block_program(cfg)

    def stacked_cache(btype):
        c = init_block_cache(cfg, btype, batch, window, dtype, kv_dtype)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_repeat,) + x.shape), c)

    return {
        "body": [stacked_cache(bt) for bt in pattern],
        "tail": [init_block_cache(cfg, bt, batch, window, dtype, kv_dtype)
                 for bt in tail],
        "pos": jnp.zeros((batch,), jnp.int32),  # per-slot decode position
    }


def cache_specs(cfg, batch: int, window: int, kv_dtype: str = ""):
    return jax.eval_shape(lambda: init_cache(cfg, batch, window, kv_dtype))


def paged_ok(cfg) -> bool:
    """True when every block can serve from a paged KV cache."""
    pattern, _, tail = block_program(cfg)
    return all(bt in PAGED_BLOCKS for bt in pattern + tail)


def init_paged_cache(cfg, batch: int, n_pages: int, page_size: int,
                     max_pages_per_slot: int, kv_dtype: str = ""):
    """Paged decode cache: one page POOL per attention block (shared by all
    slots, stacked over ``n_repeat`` for the scanned body) + one page-table
    row and position per slot. Table entries start at 0 — the reserved
    trash page — so uninitialized slots can never write into a live page.
    ``kv_dtype`` "int8" quantizes the pools (int8 values + fp32 scale
    pages addressed by the same page ids).
    """
    assert paged_ok(cfg), f"{cfg.name}: arch has non-pageable blocks"
    dtype = _dtype(cfg)
    pattern, n_repeat, tail = block_program(cfg)

    def stacked_pool(btype):
        c = init_paged_block_cache(cfg, btype, n_pages, page_size, dtype,
                                   kv_dtype)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_repeat,) + x.shape), c)

    return {
        "body": [stacked_pool(bt) for bt in pattern],
        "tail": [init_paged_block_cache(cfg, bt, n_pages, page_size, dtype,
                                        kv_dtype)
                 for bt in tail],
        "pos": jnp.zeros((batch,), jnp.int32),
        "page_table": jnp.zeros((batch, max_pages_per_slot), jnp.int32),
    }


# ---------------------------------------------------------------------------
# weight-only int8 quantization
# ---------------------------------------------------------------------------

#: attention/MLP matmul weights eligible for weight-only int8. Embeddings,
#: lm_head and norms stay in the model dtype (quality-critical, tiny).
QUANT_WEIGHT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_weights(cfg, params):
    """Weight-only int8: replace each attention/MLP matmul weight with a
    ``{"w_q": int8, "scale": fp32}`` leaf pair — symmetric per-OUTPUT-
    channel scales (``kernels/int8_matmul.py`` semantics: int8 values,
    fp32 accumulation, scale applied per output column after the dot).
    ``layers.linear`` dispatches on the dict. The scale reduction is over
    the contraction dim (axis=-2, keepdims), so stacked body weights
    (leading layer axis) quantize layer-by-layer and still slice
    correctly under the scan."""

    def _q_leaf(w):
        a = jnp.max(jnp.abs(w.astype(F32)), axis=-2, keepdims=True)
        scale = jnp.maximum(a / 127.0, 1e-12)
        q8 = jnp.clip(jnp.round(w.astype(F32) / scale), -127, 127)
        return {"w_q": q8.astype(jnp.int8), "scale": scale}

    def _q_block(p):
        p = dict(p)
        for sub in ("attn", "mlp"):
            if sub in p:
                p[sub] = {k: (_q_leaf(v) if k in QUANT_WEIGHT_KEYS else v)
                          for k, v in p[sub].items()}
        return p

    out = dict(params)
    out["body"] = [_q_block(b) for b in params["body"]]
    out["tail"] = [_q_block(b) for b in params["tail"]]
    return out


# ---------------------------------------------------------------------------
# embedding / frontends
# ---------------------------------------------------------------------------


def _embed_inputs(cfg, params, batch):
    """Returns (x, rope_pos). Stubbed modality frontends (see DESIGN.md):
    audio gets precomputed frame embeddings; VLM gets patch embeddings
    fused (early fusion) ahead of text token embeddings."""
    if cfg.modality == "audio":
        x = batch["frames"].astype(_dtype(cfg))
        b, s = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        return x, pos
    tokens = batch["tokens"]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.modality == "vision_text" and "patches" in batch:
        patches = batch["patches"].astype(_dtype(cfg))
        x = jnp.concatenate([patches, x], axis=1)  # early fusion prefix
    b, s = x.shape[:2]
    if cfg.rope_variant == "mrope":
        pos = batch["positions"]  # (3, B, S) from the (stubbed) frontend
    else:
        if "pos" in batch:  # decode: per-slot absolute start positions (B,)
            p = jnp.broadcast_to(jnp.asarray(batch["pos"], jnp.int32), (b,))
            pos = p[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        else:
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return x, pos


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(cfg, params, batch, *, mode: str = "train",
            cache: Optional[dict] = None, remat: bool = None):
    """Full-sequence forward. mode: "train" | "prefill".

    If ``cache`` is given (prefill), it is filled and returned; otherwise
    cache out is None. Returns (logits, aux_loss, cache_out).
    """
    pattern, n_repeat, tail = block_program(cfg)
    if remat is None:
        remat = mode == "train"
    x, rope_pos = _embed_inputs(cfg, params, batch)
    pos0 = jnp.zeros((), jnp.int32)

    def blockset(x, p_slices, c_slices):
        aux_sum = jnp.zeros((), F32)
        new_cs = []
        for bt, p, c in zip(pattern, p_slices, c_slices):
            x, c_new, aux = apply_block(
                cfg, bt, p, x, rope_pos, mode=mode,
                cache=c, pos=pos0)
            new_cs.append(c_new if c_new is not None else c)
            aux_sum = aux_sum + aux
        return x, new_cs, aux_sum

    if remat:
        blockset = jax.checkpoint(
            blockset, policy=jax.checkpoint_policies.nothing_saveable)

    def scan_body(carry, slices):
        x, aux_acc = carry
        p_slices, c_slices = slices
        x, new_cs, aux = blockset(x, p_slices, c_slices)
        return (x, aux_acc + aux), new_cs

    if cache is None:
        (x, aux), _ = uscan(
            lambda c, p: (scan_body(c, (p, [None] * len(pattern)))[0], None),
            (x, jnp.zeros((), F32)), params["body"])
        new_body = None
    else:
        (x, aux), new_body = uscan(
            scan_body, (x, jnp.zeros((), F32)),
            (params["body"], cache["body"]))

    new_tail = []
    for bt, p, c in zip(tail, params["tail"],
                        (cache["tail"] if cache is not None else [None] * len(tail))):
        x, c_new, aux_t = apply_block(cfg, bt, p, x, rope_pos, mode=mode,
                                      cache=c, pos=pos0)
        new_tail.append(c_new)
        aux = aux + aux_t

    with jax.named_scope("lm_head"):
        x = L.apply_norm(cfg, params["final_norm"], x)
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", x, head,
                            preferred_element_type=F32)

    cache_out = None
    if cache is not None:
        b = x.shape[0]
        cache_out = {"body": new_body, "tail": new_tail,
                     "pos": jnp.full((b,), x.shape[1], jnp.int32)}
    return logits, aux, cache_out


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------


def decode_step(cfg, params, cache, batch):
    """Incremental decode against the cache. batch: {"tokens": (B,S)}
    (+ positions for mrope). S=1 is the classic one-token decode step;
    S>1 is a chunked-prefill chunk (attention-block archs only: recurrent
    mixers carry single-step state). Returns (logits (B,S,V), new_cache)
    with pos advanced by S.

    A paged cache (one with a ``page_table``) keeps the body's stacked
    page pools in the layer scan's carry, and each block writes and reads
    its layer's pages in place. Passed as the scan's xs and ys, each
    layer's pools would be sliced out of the stacks and the stacks written
    back and copied, whole, on every call."""
    pattern, n_repeat, tail = block_program(cfg)
    pos = cache["pos"]
    pages = cache.get("page_table")  # paged serving cache (shared pools)
    batch = dict(batch)
    batch.setdefault("pos", pos)
    x, rope_pos = _embed_inputs(cfg, params, batch)

    def scan_body(carry, slices):
        x, aux_acc = carry
        p_slices, c_slices = slices
        new_cs = []
        for bt, p, c in zip(pattern, p_slices, c_slices):
            x, c_new, aux = apply_block(cfg, bt, p, x, rope_pos,
                                        mode="decode", cache=c, pos=pos)
            new_cs.append(c_new)
            aux_acc = aux_acc + aux
        return (x, aux_acc), new_cs

    def paged_body(carry, p_slices):
        x, pools, layer = carry
        new_pools = []
        for bt, p, pool in zip(pattern, p_slices, pools):
            x, pool, _ = apply_block(cfg, bt, p, x, rope_pos, mode="decode",
                                     cache=pool, pos=pos, pages=pages,
                                     layer=layer)
            new_pools.append(pool)
        return (x, new_pools, layer + 1), None

    if pages is None:
        (x, _), new_body = uscan(
            scan_body, (x, jnp.zeros((), F32)),
            (params["body"], cache["body"]))
    else:
        (x, new_body, _), _ = uscan(
            paged_body, (x, cache["body"], jnp.zeros((), jnp.int32)),
            params["body"])

    new_tail = []
    for bt, p, c in zip(tail, params["tail"], cache["tail"]):
        x, c_new, _ = apply_block(cfg, bt, p, x, rope_pos, mode="decode",
                                  cache=c, pos=pos, pages=pages)
        new_tail.append(c_new)

    with jax.named_scope("lm_head"):
        x = L.apply_norm(cfg, params["final_norm"], x)
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", x, head,
                            preferred_element_type=F32)
    new_cache = {"body": new_body, "tail": new_tail, "pos": pos + x.shape[1]}
    if pages is not None:
        new_cache["page_table"] = pages
    return logits, new_cache
