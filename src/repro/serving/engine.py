"""Serving engine: jit'd prefill / decode steps + a continuous-batching
executor (the survey's "adaptive batching" [8][4] in its modern form).

The engine maintains B decode slots backed by one batched cache pytree.
Each slot runs an independent request (per-slot positions / rolling KV).
The steady-state decode loop is zero-copy and zero-recompile:

  * buffer donation — the batched KV cache is donated to the jit'd decode
    tick and to the jit'd slot-scatter (``cache_insert``), so XLA updates
    it in place instead of copying every leaf every tick;
  * device-resident tokens — the sampled-token carry and (m)rope positions
    never leave the device in steady state; token values are synced to the
    host once every ``sync_every`` ticks in a single transfer;
  * bucketed prefill — prompts are padded to power-of-two buckets so jit's
    shape-keyed compile cache retraces once per bucket, not once per
    prompt length (``prefill_traces`` is the compile-count probe);
  * chunked prefill — long prompts are split into fixed-size chunks that
    interleave with decode ticks (``ChunkedPrefillPolicy`` decides how
    many chunks fit per tick from the cost model), so admitting a long
    request no longer stalls in-flight decode slots;
  * cost-model admission — slot count and queue flush deadlines come from
    ``repro.core.misd.batching.plan_admission`` instead of constants;
  * shared-prefix KV cache (opt-in ``prefix_cache=True``, paged only) —
    finished prompts' full pages stay in a radix ``PrefixIndex``; a new
    request aliases the longest cached prefix (refcounted pages, zero
    prefill compute for the hit) and prefills only its suffix from a
    nonzero offset, with copy-on-write for a partially-matched tail page;
  * device-resident sampling — per-request ``SamplingParams``
    (temperature / top-k / top-p / seed; greedy is the degenerate
    default) live in a per-slot device state next to the token carry:
    greedy and stochastic slots compose by masking inside the SAME
    decode trace and the SAME fused scan window (no per-config retrace),
    and noise is keyed by (seed, absolute position) so seeded streams
    are bit-identical across restarts, slot assignments, and replicas.

All steps are pure jit functions; the executor is the only stateful part.
"""
from __future__ import annotations

import contextlib
import math
import time
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import (
    collective_s_per_axis,
    estimate_backlog_s,
    estimate_decode,
    estimate_prefill,
    kv_bytes_per_token,
)
from repro.core.misd.batching import BatchAccumulator, plan_admission
from repro.core.misd.scheduler import ChunkedPrefillPolicy
from repro.core.simd.sharding import (
    cache_pspecs,
    paged_cache_pspecs,
    param_pspecs,
    serving_policy,
    to_shardings,
)
from repro.launch.mesh import make_serving_mesh
from repro.models import (
    decode_step,
    forward,
    init_cache,
    init_paged_cache,
    paged_ok,
    quantize_weights,
)
from repro.models.blocks import KV_CACHE_BLOCKS
from repro.models.layers import sample_tokens
from repro.models.model import block_program
from repro.models.moe import drop_free_group
from repro.serving.config import DeviceTopology, EngineConfig
from repro.serving.metrics import MetricsRegistry, latency_histogram
from repro.serving.paging import PageAllocator, PrefixHit, PrefixIndex
from repro.serving.request import (
    Request,
    RequestRejected,
    RequestState,
    SamplingParams,
    ServeMetrics,
)
from repro.serving.telemetry import LoadReport
from repro.serving.tracing import Trace, Tracer
from repro.util import sharding_hints

__all__ = [  # noqa: F822 — LoadReport/DeviceTopology re-exported for callers
    "DeviceTopology", "EngineConfig", "LoadReport", "PREEMPT_POLICIES",
    "ServingEngine", "bucketed_prefill_step", "cache_insert",
    "decode_scan_step", "decode_tick", "generate", "init_sampling_state",
    "page_table_append", "paged_prefill_step", "pages_insert",
    "pages_insert_prefix", "prefill_chunk_step", "prefill_step",
    "prefix_seed_cache", "prompt_bucket", "sampling_row", "sampling_set",
    "serve_step", "slot_release",
]


# ---------------------------------------------------------------------------
# jit'd steps (also the units the dry-run lowers)
# ---------------------------------------------------------------------------


def prefill_step(cfg, params, batch, *, window: int, kv_dtype: str = ""):
    """Full-prompt forward filling a fresh cache. Returns (last_token_logits,
    cache)."""
    b = (batch["frames"] if cfg.modality == "audio" else batch["tokens"]).shape[0]
    cache = init_cache(cfg, b, window, kv_dtype)
    logits, _, cache = forward(cfg, params, batch, mode="prefill", cache=cache)
    return logits[:, -1], cache


def bucketed_prefill_step(cfg, params, batch, true_len, *, window: int,
                          kv_dtype: str = ""):
    """Prefill a prompt padded (at the end) to a bucket length. ``true_len``
    is a traced int32 scalar, so every prompt length inside one bucket
    shares a single trace. Causality keeps the pad garbage out of the real
    tokens' keys; the returned cache's ``pos`` is clamped to ``true_len``
    so decode's validity mask hides the garbage slots until the rolling
    write index overwrites them. Returns (first_token (B,), last_true_token
    logits (B, V), cache)."""
    b = batch["tokens"].shape[0]
    cache = init_cache(cfg, b, window, kv_dtype)
    logits, _, cache = forward(cfg, params, batch, mode="prefill", cache=cache)
    true_len = jnp.asarray(true_len, jnp.int32)
    last = jax.lax.dynamic_index_in_dim(logits, true_len - 1, axis=1,
                                        keepdims=False)
    cache["pos"] = jnp.full((b,), true_len, jnp.int32)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return tok, last, cache


def prefill_chunk_step(cfg, params, cache, tokens, true_len):
    """One chunk of incremental prefill into a (B=1) cache via the
    multi-token decode path. ``tokens`` (B, C) may carry end padding on the
    final chunk; ``true_len`` (traced int32) clamps the advanced position
    so the pad keys stay masked. Returns (token (B,) argmax at the last
    true position, last-true-position logits (B, V), new_cache)."""
    b, c = tokens.shape
    start = cache["pos"]
    batch = {"tokens": tokens}
    if cfg.rope_variant == "mrope":
        p = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        batch["positions"] = jnp.broadcast_to(p[None], (3, b, c))
    logits, new_cache = decode_step(cfg, params, cache, batch)
    true_len = jnp.asarray(true_len, jnp.int32)
    new_cache["pos"] = jnp.minimum(new_cache["pos"], true_len)
    idx = jnp.clip(true_len - 1 - start[0], 0, c - 1)
    last = jax.lax.dynamic_index_in_dim(logits, idx, axis=1, keepdims=False)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return tok, last, new_cache


def paged_prefill_step(cfg, params, batch, true_len, kv_dtype: str = ""):
    """Prefill for the paged engine: the B=1 cache window IS the padded
    prompt length (a LINEAR buffer — no rolling wrap), so every key of the
    padded prompt survives for the page scatter. ``true_len`` is traced;
    one trace serves every prompt inside a bucket. Returns (first_token
    (B,), last-true-token logits (B, V), linear cache with pos=true_len)."""
    padded = batch["tokens"].shape[1]
    return bucketed_prefill_step(cfg, params, batch, true_len, window=padded,
                                 kv_dtype=kv_dtype)


def pages_insert(paged_cache, linear_cache, pages, slot, true_len):
    """Admit a prefilled request into the paged cache: scatter the B=1
    linear prefill cache's K/V into the pool pages granted to the slot,
    then point the slot's page-table row at them and set its position.

    No other slot's state is touched — admission cost is O(prompt pages),
    not O(slots * window). ``pages`` (n,), ``slot`` and ``true_len`` may
    all be traced: one trace covers every slot index and page assignment
    for a given bucket size (n is static per bucket). The row is written
    in full, so entries past the prompt reset to the trash page."""
    n = pages.shape[0]

    def ins(pool, small):
        # pool: (P, ps, kv, hd), or (n_repeat, P, ps, kv, hd) for stacked
        # body leaves; small: the matching linear cache leaf holding the
        # prompt's n * ps tokens at the front of its window axis (wider
        # buffers — the shared chunked-prefill cache — are sliced down, so
        # every chunked job reuses ONE compiled chunk step; the batch axis
        # is 1 and is absorbed by the reshape).
        ax = small.ndim - 4
        ps = pool.shape[ax + 1]
        if small.shape[ax + 1] > n * ps:
            small = jax.lax.slice_in_dim(small, 0, n * ps, axis=ax + 1)
        if ax == 0:
            chunks = small.reshape((n, ps) + small.shape[2:])
            return pool.at[pages].set(chunks.astype(pool.dtype))
        chunks = small.reshape((small.shape[0], n, ps) + small.shape[3:])
        return pool.at[:, pages].set(chunks.astype(pool.dtype))

    table = paged_cache["page_table"]
    row = jnp.zeros((table.shape[1],), jnp.int32).at[:n].set(pages)
    true_len = jnp.asarray(true_len, jnp.int32)
    return {
        "body": jax.tree.map(ins, paged_cache["body"], linear_cache["body"]),
        "tail": jax.tree.map(ins, paged_cache["tail"], linear_cache["tail"]),
        "page_table": jax.lax.dynamic_update_slice(table, row[None], (slot, 0)),
        "pos": jax.lax.dynamic_update_slice(
            paged_cache["pos"], true_len[None], (slot,)),
    }


def prefix_seed_cache(paged_cache, pages, start):
    """Gather a cached page chain into a fresh B=1 LINEAR cache — the
    working buffer for suffix-offset prefill. ``pages`` (max_pages,) is
    the hit's chain (full pages + the shared COW tail) padded with the
    trash page, so its shape is FIXED: one trace covers every hit length.
    Page i lands at linear positions [i*ps, (i+1)*ps); ``start`` (traced)
    is the suffix-restart offset -> the cache's pos, which masks both the
    trash-page garbage beyond the chain and the donor's tokens beyond the
    matched span. Read-only over the pools (never donated)."""

    def gather(pool):
        ax = pool.ndim - 4  # page axis (stacked body leaves lead n_repeat)
        take = jnp.take(pool, pages, axis=ax)  # (..., n, ps, kv, hd)
        s = take.shape
        merged = take.reshape(s[:ax] + (s[ax] * s[ax + 1],) + s[ax + 2:])
        return jnp.expand_dims(merged, ax)  # B=1 axis where pages were

    return {
        "body": jax.tree.map(gather, paged_cache["body"]),
        "tail": jax.tree.map(gather, paged_cache["tail"]),
        "pos": jnp.full((1,), jnp.asarray(start, jnp.int32), jnp.int32),
    }


def pages_insert_prefix(paged_cache, linear_cache, scatter_pages, table_pages,
                        slot, true_len):
    """Admit a prefix-hit request: the slot's table row aliases the cached
    full pages while only privately-owned pages receive the linear
    cache's data. Both page rows are max_pages wide (the linear buffer IS
    max_seq tokens), so ONE trace covers every hit length / suffix shape.

    ``scatter_pages`` carries the trash page at every aliased (shared)
    position — shared pages are never written. This is where copy-on-
    write lands: the shared tail page's matched tokens were gathered into
    the linear buffer (prefix_seed_cache), the suffix prefill overwrote
    from the hit boundary on, and the whole span now scatters into the
    private replacement page named by ``table_pages``."""
    n = scatter_pages.shape[0]

    def ins(pool, small):
        ax = small.ndim - 4
        ps = pool.shape[ax + 1]
        if ax == 0:
            chunks = small.reshape((n, ps) + small.shape[2:])
            return pool.at[scatter_pages].set(chunks.astype(pool.dtype))
        chunks = small.reshape((small.shape[0], n, ps) + small.shape[3:])
        return pool.at[:, scatter_pages].set(chunks.astype(pool.dtype))

    table = paged_cache["page_table"]
    true_len = jnp.asarray(true_len, jnp.int32)
    return {
        "body": jax.tree.map(ins, paged_cache["body"], linear_cache["body"]),
        "tail": jax.tree.map(ins, paged_cache["tail"], linear_cache["tail"]),
        "page_table": jax.lax.dynamic_update_slice(
            table, table_pages[None], (slot, 0)),
        "pos": jax.lax.dynamic_update_slice(
            paged_cache["pos"], true_len[None], (slot,)),
    }


def page_table_append(paged_cache, slot, idx, page):
    """Grant one more page to a slot mid-decode: table[slot, idx] = page.
    All three indices are traced — one trace covers every grant."""
    new = dict(paged_cache)
    new["page_table"] = jax.lax.dynamic_update_slice(
        paged_cache["page_table"],
        jnp.asarray(page, jnp.int32)[None, None], (slot, idx))
    return new


def slot_release(paged_cache, slot):
    """Retire a finished slot: point its whole page-table row at the trash
    page and zero its position. The slot keeps riding in the fused decode
    batch, but its writes can no longer land in a reclaimed page."""
    table = paged_cache["page_table"]
    new = dict(paged_cache)
    new["page_table"] = jax.lax.dynamic_update_slice(
        table, jnp.zeros((1, table.shape[1]), jnp.int32), (slot, 0))
    new["pos"] = jax.lax.dynamic_update_slice(
        paged_cache["pos"], jnp.zeros((1,), jnp.int32), (slot,))
    return new


def serve_step(cfg, params, cache, batch):
    """One decode step for every active slot: ONE new token against the KV
    cache. Returns (next_tokens (B,), logits (B,V), new_cache)."""
    logits, new_cache = decode_step(cfg, params, cache, batch)
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return nxt, logits[:, -1], new_cache


def init_sampling_state(slots: int) -> dict:
    """Per-slot device-resident sampling state: the greedy mask, the logit-
    processor parameters, and each slot's PRNG key material (raw uint32
    pairs, scatterable like any other carry leaf). Defaults are all-greedy,
    so a fresh engine's decode pays no sampling work."""
    return {
        "greedy": jnp.ones((slots,), jnp.bool_),
        "temperature": jnp.ones((slots,), jnp.float32),
        "top_k": jnp.zeros((slots,), jnp.int32),
        "top_p": jnp.ones((slots,), jnp.float32),
        "key": jnp.zeros((slots, 2), jnp.uint32),
    }


_GREEDY_KEY = np.zeros((2,), np.uint32)


def sampling_row(sp: Optional[SamplingParams]) -> dict:
    """Host-side one-slot update for ``init_sampling_state`` leaves. Every
    value is passed traced, so one ``sampling_set`` trace covers every
    request configuration (no per-config retrace). Greedy rows skip the
    PRNG key init — their lane never draws."""
    sp = sp or SamplingParams()
    greedy = sp.greedy
    return {
        "greedy": np.bool_(greedy),
        "temperature": np.float32(1.0 if greedy
                                  else max(sp.temperature, 1e-6)),
        "top_k": np.int32(0 if greedy else sp.top_k),
        "top_p": np.float32(1.0 if greedy else sp.top_p),
        "key": (_GREEDY_KEY if greedy
                else np.asarray(jax.random.PRNGKey(sp.seed), np.uint32)),
    }


def sampling_set(samp, slot, row):
    """Scatter one slot's sampling params into the per-slot state. ``slot``
    and every ``row`` value may be traced — one trace covers every slot
    index and parameter setting."""
    out = {}
    for name, leaf in samp.items():
        val = jnp.asarray(row[name], leaf.dtype)
        out[name] = jax.lax.dynamic_update_slice(
            leaf, val[None] if leaf.ndim == 1 else val[None, :],
            (slot,) + (0,) * (leaf.ndim - 1))
    return out


def decode_tick(cfg, params, cache, tokens, samp=None, *,
                logits_sharding=None):
    """The engine's steady-state step: ``tokens`` (B,) is the device-resident
    last-token carry; (m)rope positions are built on device from the cache's
    ``pos`` leaf — no host round-trip. ``samp`` (optional) is the per-slot
    sampling state: greedy slots take argmax, stochastic slots draw from the
    processed distribution with noise keyed by (seed, absolute position) —
    masked composition, so ONE trace serves any mix. Returns
    (next_tokens (B,), new_cache). Jitted with the cache donated: the KV
    pytree updates in place.

    ``logits_sharding``: sharded engines pass a replicated NamedSharding —
    the lm-head output is vocab-sharded under tensor parallelism, and the
    sampler's softmax/cumsum over a sharded vocab axis would reorder float
    sums (argmax is comparator-exact, the distributions are not).
    Constraining here inserts ONE all-gather (pure concatenation, bitwise
    exact) so sharded streams stay bit-identical to the 1-chip engine."""
    batch = {"tokens": tokens[:, None]}
    if cfg.rope_variant == "mrope":
        b = tokens.shape[0]
        batch["positions"] = jnp.broadcast_to(
            cache["pos"][None, :, None], (3, b, 1))
    logits, new_cache = decode_step(cfg, params, cache, batch)
    last = logits[:, -1]
    if logits_sharding is not None:
        last = jax.lax.with_sharding_constraint(last, logits_sharding)
    with jax.named_scope("sampler"):
        if samp is None:
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        else:
            # the token being drawn lands at absolute position new_pos - 1 +
            # 1 == the post-step pos: the same fold key the prefill paths use
            # for the first token (pos = prompt_len), advanced per tick
            nxt = sample_tokens(last, samp, new_cache["pos"])
    return nxt, new_cache


def decode_scan_step(cfg, params, cache, tokens, samp=None, *, n: int,
                     logits_sharding=None):
    """``n`` fused decode ticks as one jitted ``lax.scan``: one dispatch and
    one host sync per ``n`` tokens instead of per token. The engine uses
    this whenever nothing interrupts the window (no pending admissions, no
    prefill chunks, every active request has >= n tokens to go), falling
    back to single ticks at scheduling boundaries. ``samp`` is scan-
    invariant (slot membership is fixed across the window; per-tick noise
    comes from the advancing cache ``pos``), so stochastic slots survive
    multi-tick fusion with the SAME single trace. Returns
    (final_tokens (B,), token_history (n, B), new_cache)."""

    def body(carry, _):
        toks, c = carry
        nxt, c = decode_tick(cfg, params, c, toks, samp,
                             logits_sharding=logits_sharding)
        return (nxt, c), nxt

    (toks, cache), hist = jax.lax.scan(body, (tokens, cache), None, length=n)
    return toks, hist, cache


def _cache_batch_axis(big_shape, small_shape, batch: int):
    """Find the slot (batch) axis of a batched cache leaf: the axis where
    the batched leaf has ``batch`` entries and the B=1 leaf has one. Both
    conditions are required — stacked body leaves carry an ``n_repeat``
    leading axis that can collide with ``batch`` by value."""
    for ax, (n_big, n_small) in enumerate(zip(big_shape, small_shape)):
        if n_big == batch and n_small == 1:
            return ax
    raise ValueError(f"no batch axis {batch} in {big_shape} vs {small_shape}")


def cache_insert(batched_cache, single_cache, slot, batch: int):
    """Scatter a B=1 cache into slot ``slot`` of a batched cache. ``slot``
    may be a traced int32 scalar — one trace covers every slot index (the
    engine jits this with the batched cache donated, making admission a
    true in-place scatter instead of a full-cache copy)."""

    def ins(big, small):
        ax = _cache_batch_axis(big.shape, small.shape, batch)
        return jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), slot, ax)

    return jax.tree.map(ins, batched_cache, single_cache)


def _token_set(tokens, tok, slot):
    """Write a (1,) token into the (B,) device carry at ``slot`` (traced)."""
    return jax.lax.dynamic_update_slice_in_dim(tokens, tok.astype(tokens.dtype),
                                               slot, 0)


# ---------------------------------------------------------------------------
# engine helpers
# ---------------------------------------------------------------------------


_NO_PHASE = contextlib.nullcontext()


def _no_phase(name: str):
    """Stand-in for ``jax.profiler.TraceAnnotation`` with tracing off."""
    return _NO_PHASE


def _attn_only(cfg) -> bool:
    """True when every block's decode cache is a KV buffer (no recurrent
    state) — the precondition for end-padded bucketing and chunked prefill."""
    pattern, _, tail = block_program(cfg)
    return all(bt in KV_CACHE_BLOCKS for bt in pattern + tail)


def _min_cache_window(cfg, window: int) -> int:
    """Smallest KV ring among the model's attention blocks: bucketed /
    chunked prefill must fit entirely inside it (a multi-query chunk that
    wraps the ring would expose chunk-future keys to earlier queries)."""
    pattern, _, tail = block_program(cfg)
    w = window
    for bt in pattern + tail:
        if bt == "local_attn":
            w = min(w, cfg.local_window)
    return w


def prompt_bucket(n: int, *, min_bucket: int = 16) -> int:
    """Power-of-two bucket for a prompt of ``n`` tokens."""
    return max(min_bucket, 1 << max(n - 1, 1).bit_length())


@dataclass
class _PrefillJob:
    """A request mid-way through chunked prefill (slot reserved, B=1 cache
    accumulating chunks)."""

    req: Request
    slot: int
    cache: dict
    tokens: jnp.ndarray  # (1, padded_len) device-resident prompt
    true_len: np.int32
    next_off: int = 0
    # first-token logits come from the chunk containing position
    # true_len-1, which is NOT always the last chunk (the padded buffer
    # is quantum-aligned; trailing chunks can be pure pad) — stash both
    # the greedy token and the logits (a sampled request draws its first
    # token from these at activation)
    tok: Optional[jnp.ndarray] = None
    logits: Optional[jnp.ndarray] = None


@dataclass
class _HitAdmission:
    """Host-side plan for a prefix-hit admission, staged between
    reservation and activation: which table positions alias shared pages
    (scatter to trash) and which receive the suffix prefill's data."""

    scatter_pages: np.ndarray  # (max_pages,) trash at aliased positions
    table_pages: np.ndarray  # (max_pages,) the slot's full table row
    n_tabled: int  # owned pages written into the row (incl. decode tail)


# ---------------------------------------------------------------------------
# preemption victim policies (pluggable: name -> chooser)
# ---------------------------------------------------------------------------


def _urgency(req: Request):
    """Total order on request urgency: higher priority beats any deadline,
    then earlier TTFT deadline wins. Smaller tuple = more urgent."""
    return (-req.priority, req.ttft_deadline)


def _victim_latest_deadline(engine, eligible: List[int]) -> int:
    """Latest-deadline-first: evict the slot whose request is least urgent
    (ties: most remaining budget — it has paid the least per page)."""
    return max(eligible,
               key=lambda i: (_urgency(engine.active[i]),
                              engine.active[i].remaining_tokens, i))


def _victim_most_remaining(engine, eligible: List[int]) -> int:
    """Most-remaining-first: evict the slot with the most budget left —
    it frees decode capacity the longest (ties: latest deadline)."""
    return max(eligible,
               key=lambda i: (engine.active[i].remaining_tokens,
                              _urgency(engine.active[i]), i))


PREEMPT_POLICIES = {
    "latest-deadline": _victim_latest_deadline,
    "most-remaining": _victim_most_remaining,
}


# ---------------------------------------------------------------------------
# continuous-batching executor
# ---------------------------------------------------------------------------


class ServingEngine:
    """Single-instance engine (SISD quadrant) with continuous batching.

    ``slots``: max concurrent decode streams (0/None -> derived from the
    cost model via ``plan_admission``). ``window``: KV window.
    ``sync_every``: decode ticks between device->host token syncs (forced
    to 1 when ``eos_id`` >= 0, since stopping needs token values).
    ``chunk_prefill``: chunk size for interleaved prefill (0 disables).
    ``bucket_prompts``: pad prefill to power-of-two buckets.
    ``donate``: donate the KV cache to the jit'd steps (in-place update).

    ``paged``: serve from a paged KV cache (None -> auto: on whenever every
    block is pageable; recurrent / local-attention archs fall back to
    rolling windows). ``page_size``: tokens per page (power of two).
    ``max_seq``: per-request token cap (page-table width; defaults to
    ``window`` for cost parity with the rolling cache — raise it to serve
    prompts longer than the old window cap). ``pool_pages``: total device
    pages shared by all slots (defaults to full headroom
    ``slots * max_seq / page_size + 1``, the +1 being the reserved trash
    page; pass less to oversubscribe — admission then backpressures when
    the pool runs dry).
    ``kv_hbm_budget``: optional KV-memory budget (bytes) handed to
    ``plan_admission`` when ``slots=0`` — the paged cache only needs the
    *expected* resident tokens per slot rather than a full window, so the
    same budget admits more concurrent slots.
    ``prefix_cache``: keep finished prompts' full KV pages in a radix
    ``PrefixIndex`` so later requests sharing a prefix alias those pages
    (refcounted) and prefill only their suffix — zero prefill compute for
    the cached span. Requires the paged cache. Off by default: cached
    pages outlive their requests, so ``pages_in_use`` no longer drains to
    zero between waves (use ``clear_prefix_cache()`` / ``reset()``).
    """

    def __init__(self, cfg, params,
                 config: Optional[EngineConfig] = None, **legacy):
        if legacy:
            # the one-PR from_legacy_kwargs shim (PR 7) is gone: keyword
            # construction fails loudly with the migration recipe
            raise TypeError(
                "ServingEngine(cfg, params, slots=..., ...) keyword "
                "construction was removed — build an EngineConfig and pass "
                "ServingEngine(cfg, params, EngineConfig(slots=..., ...)). "
                "Field names match the former keywords one-for-one except "
                "n_chips -> modeled_chips; serving-path precision (int8 "
                "KV pages / weights) is EngineConfig(precision="
                "PrecisionConfig(...)). Unknown keywords: "
                f"{sorted(legacy)}")
        if config is None:
            config = EngineConfig()
        config.validate(cfg)
        self.config = config
        self.topology = config.topology
        # locals mirror the former keywords: the executor body predates the
        # config object and reads these names throughout
        slots, window = config.slots, config.window
        eos_id, sync_every = config.eos_id, config.sync_every
        donate, bucket_prompts = config.donate, config.bucket_prompts
        chunk_prefill, sla_s = config.chunk_prefill, config.sla_s
        prefill_policy, paged = config.prefill_policy, config.paged
        page_size, pool_pages = config.page_size, config.pool_pages
        max_seq, kv_hbm_budget = config.max_seq, config.kv_hbm_budget
        expected_len, prefix_cache = config.expected_len, config.prefix_cache
        preemption = config.preemption
        preempt_policy = config.preempt_policy
        shed_overdue = config.shed_overdue
        n_chips = config.n_chips

        self.cfg = cfg
        self.n_chips = n_chips
        if config.precision.quantized_weights:
            # weight-only int8 at load time: attention/MLP matmul leaves
            # become {"w_q": int8, "scale": fp32} (layers.linear
            # dispatches); validate() already rejected sharded replicas
            # and non-quantizable block types
            params = quantize_weights(cfg, params)
        # --- sharded replica: mesh + bit-exact GSPMD profile ---
        # serving_policy shards only concat-dim weights (output dims, the
        # vocab axis, MoE expert axis) and the KV pools' kv-head axis;
        # GSPMD then all-gathers activations (pure concatenation) instead
        # of psum-reducing partial products, so every reduction keeps the
        # 1-chip operand order and streams stay bit-identical.
        self.mesh = None
        self._policy = None
        self._replicated = None
        self._logits_sharding = None
        if self.topology.sharded:
            from jax.sharding import NamedSharding, PartitionSpec

            self.mesh = make_serving_mesh(self.topology)
            self._policy = serving_policy(cfg, self.mesh)
            params = jax.device_put(
                params,
                to_shardings(self.mesh,
                             param_pspecs(cfg, params, self._policy)))
            self._replicated = NamedSharding(self.mesh, PartitionSpec())
            self._logits_sharding = self._replicated
        self.params = params
        # EDF ordering of the admission backlog (earliest TTFT deadline
        # first); FIFO stays the default so single-trace probes and every
        # pre-cluster caller see identical admission order.
        self.edf_backlog = config.edf_backlog
        if paged and not paged_ok(cfg):
            raise ValueError(
                f"{cfg.name}: arch has non-pageable blocks (recurrent or "
                f"local-attention); pass paged=None to auto-fall back to "
                f"rolling windows")
        self.paged = paged_ok(cfg) if paged is None else bool(paged)
        # quantized KV pages: validate(cfg) guaranteed the paged cache is
        # available whenever a kv_cache_dtype is set (paged=None resolves
        # to paged=True here because the arch is fully pageable)
        self.kv_dtype = config.precision.kv_cache_dtype
        assert page_size > 0 and page_size & (page_size - 1) == 0, page_size
        self.page_size = page_size
        self.max_seq = _padded_len(int(max_seq or window), page_size)
        self.max_pages = self.max_seq // page_size
        self.plan = plan_admission(
            cfg, context=window, sla_s=sla_s, n_chips=n_chips,
            kv_hbm_budget_bytes=kv_hbm_budget,
            mean_context=(expected_len or None) if self.paged else window,
            kv_cache_dtype=self.kv_dtype)
        if not slots:
            slots = self.plan.slots
        # --- MoE capacity policy (overflow as typed backpressure) ---
        self.moe_capacity_policy = (config.resolved_moe_policy(cfg)
                                    if cfg.arch_type == "moe" else "")
        self._moe_gmax = 0  # drop-free group bound (backpressure only)
        # every model-forward trace runs under self._trace_ctx; it carries
        # the scalar hints the model reads at trace time: the strict-MoE
        # full-capacity opt and/or the quantized cache's prefill scale
        # granularity ("page" granularity coarsens single-shot prefill
        # scale writes to one per page — see blocks.quantize_kv)
        hint_kw = {}
        if self.kv_dtype and config.precision.kv_scale_granularity == "page":
            hint_kw["kv_scale_page"] = page_size
        self._trace_ctx = (partial(sharding_hints, **hint_kw) if hint_kw
                           else contextlib.nullcontext)
        if self.moe_capacity_policy == "strict":
            # every serving trace runs under the full-capacity hint: the
            # (N, g, E, C) combine buffer covers the whole group, so no
            # routing pattern can drop a token (see models.moe._capacity)
            self._trace_ctx = partial(sharding_hints,
                                      opts=frozenset({"moe_full_cap"}),
                                      **hint_kw)
        elif self.moe_capacity_policy == "backpressure":
            self._moe_gmax = drop_free_group(cfg)
            # the decode group IS the slot count (garbage lanes route too):
            # clamping here makes every decode tick provably drop-free
            slots = min(slots, self._moe_gmax)
        self.slots = slots
        self.window = window
        # cost-model latency of one batched decode tick (load_report);
        # sharded replicas bill per-axis collective time on top
        self._mesh_axes = (self.topology.mesh_axes
                           if self.topology.sharded else None)
        self._tick_est_s = estimate_decode(
            cfg, slots, window, n_chips=n_chips,
            mesh_axes=self._mesh_axes).latency_s
        self._axis_collective_s = (
            collective_s_per_axis(cfg, slots, mesh_axes=self._mesh_axes)
            if self._mesh_axes else {})
        self.eos_id = eos_id
        self.sync_every = 1 if eos_id >= 0 else max(1, sync_every)
        self.metrics = ServeMetrics()
        # --- observability: span tracing + profiling hooks ---
        # Stamping discipline: host timestamps only, and only at existing
        # sync points (the caller-supplied ``now`` the engine already has
        # in hand) — tracing never adds a device sync. With tracing off a
        # request's ``trace`` stays None and every stamp site is a single
        # attribute check.
        self._trace_on = bool(config.tracing)
        # head-sampling: trace rids where rid % trace_sample_n == 0 (1 =
        # everything); the rollups then cover the sampled subset only
        self._trace_every = max(1, config.trace_sample_n)
        self.tracer = Tracer(enabled=self._trace_on, ring=config.trace_ring)
        self._win_t0 = 0.0  # serving-clock start of the open decode window
        self._last_now = 0.0  # most recent caller clock (compile events)
        # jit traces per trace-cache key proxy (shape-derived): the "flat
        # compile count" invariants as a queryable metric
        self.compile_events: Dict[str, int] = {}
        self._tick_wall = latency_histogram()  # step() wall s (tracing only)
        self._profiling = False
        # engine phases on the profiler's clock (tracing only): step() runs
        # under a StepTraceAnnotation and _step marks each phase, so a
        # device-idle gap can be put down to the host work over it
        self._phase = (jax.profiler.TraceAnnotation if self._trace_on
                       else _no_phase)
        self._steps = 0  # step() calls (the annotation's step_num)

        self._attn_only = _attn_only(cfg)
        self._min_window = _min_cache_window(cfg, window)
        self.bucket_prompts = bucket_prompts and self._attn_only
        if prefill_policy is not None:  # the policy's chunk size wins
            chunk_prefill = prefill_policy.chunk
        self.chunk = chunk_prefill if (chunk_prefill and self._attn_only) else 0
        self.prefill_policy = prefill_policy or ChunkedPrefillPolicy(
            chunk=self.chunk or 64)
        # chunked-prefill buffers must be both chunk- and page-aligned
        self._chunk_quantum = (math.lcm(self.chunk, page_size)
                               if self.chunk else page_size)

        # --- device state (exclusively owned: donation-safe) ---
        if prefix_cache and not (paged_ok(cfg) if paged is None else paged):
            raise ValueError(
                f"{cfg.name}: prefix_cache requires the paged KV cache "
                f"(rolling windows cannot alias another slot's KV)")
        # --- fault tolerance / lifecycle knobs ---
        if preemption and not self.paged:
            raise ValueError(
                f"{cfg.name}: preemption requires the paged KV cache (a "
                f"victim's pages must be releasable mid-stream)")
        if preempt_policy not in PREEMPT_POLICIES:
            raise ValueError(f"unknown preempt_policy {preempt_policy!r} "
                             f"(want one of {sorted(PREEMPT_POLICIES)})")
        self.preemption = preemption
        self.preempt_policy = preempt_policy
        self._preempt_victim_fn = PREEMPT_POLICIES[preempt_policy]
        # shed queued requests whose TTFT deadline already passed (graceful
        # degradation under overload: stop burning prefill/decode budget on
        # requests that can no longer meet their SLO). Off by default —
        # SLO-miss accounting tests rely on late requests still finishing.
        self.shed_overdue = shed_overdue
        if self.paged:
            self.pool_pages = pool_pages or slots * self.max_pages + 1
            self.allocator = PageAllocator(self.pool_pages, page_size)
            self.prefix_index = (PrefixIndex(self.allocator, page_size)
                                 if prefix_cache else None)
            self.cache = init_paged_cache(cfg, slots, self.pool_pages,
                                          page_size, self.max_pages,
                                          kv_dtype=self.kv_dtype)
            self._pos_h: List[int] = [0] * slots  # host mirror of cache pos
            # pages of the slot's reservation already written into its
            # device page-table row (the decode tail is appended lazily)
            self._tabled: List[int] = [0] * slots
        else:
            self.prefix_index = None
            self.cache = init_cache(cfg, slots, window)
        if self.mesh is not None:
            # KV pools shard over the kv-head axis; the page table, pos,
            # and recurrent/conv leaves replicate — host-side layouts
            # (PageAllocator / PrefixIndex / preemption snapshots) stay
            # identical to the 1-chip engine
            pfn = paged_cache_pspecs if self.paged else cache_pspecs
            self.cache = jax.device_put(
                self.cache,
                to_shardings(self.mesh,
                             pfn(cfg, self.cache, self._policy, self.mesh)))
        # staged prefix-hit admission plans, keyed by slot (consumed at
        # activation; see _HitAdmission)
        self._hit_pending: Dict[int, _HitAdmission] = {}
        self._tokens = jnp.zeros((slots,), jnp.int32)
        if self.mesh is not None:
            self._tokens = jax.device_put(self._tokens, self._replicated)
        # per-slot sampling state rides next to the token carry: scattered
        # at activation, reset to greedy on release (so a vacated slot's
        # garbage lane never re-enters the stochastic branch); the host
        # mirror of the greedy flags makes release a no-op for greedy slots
        self._samp = init_sampling_state(slots)
        if self.mesh is not None:
            self._samp = jax.device_put(self._samp, self._replicated)
        self._samp_greedy_h: List[bool] = [True] * slots
        self.active: List[Optional[Request]] = [None] * slots
        self.decoding: List[bool] = [False] * slots
        self._unsynced: List[jnp.ndarray] = []  # per-tick (B,) token arrays
        self._finished: List[Request] = []
        self._jobs: Deque[_PrefillJob] = deque()

        # --- admission queue (deadline from the cost model) ---
        self.backlog: Deque[Request] = deque()
        self.admission = BatchAccumulator(
            target_batch=slots, deadline_s=self.plan.flush_deadline_s)

        # --- jit'd steps with compile-count probes ---
        self.prefill_traces = 0
        self.decode_traces = 0
        donate_cache = (1,) if donate else ()

        # every model-forward trace runs under self._trace_ctx (the MoE
        # "strict" capacity hint; a no-op otherwise) — the hint is read at
        # TRACE time, and these closures are per-engine, so the contextvar
        # scope is safe
        def _probed_decode(params, cache, tokens, samp):
            self.decode_traces += 1
            self._note_compile("decode/tick")
            with self._trace_ctx():
                return decode_tick(cfg, params, cache, tokens, samp,
                                   logits_sharding=self._logits_sharding)

        def _probed_scan(params, cache, tokens, samp):
            self.decode_traces += 1
            self._note_compile(f"decode/scan{self.sync_every}")
            with self._trace_ctx():
                return decode_scan_step(
                    cfg, params, cache, tokens, samp, n=self.sync_every,
                    logits_sharding=self._logits_sharding)

        def _probed_bucketed(params, batch, true_len):
            self.prefill_traces += 1
            self._note_compile(f"prefill/bucket{_batch_len(batch)}")
            with self._trace_ctx():
                return bucketed_prefill_step(cfg, params, batch, true_len,
                                             window=window)

        def _probed_exact(params, batch):
            self.prefill_traces += 1
            self._note_compile(f"prefill/exact{_batch_len(batch)}")
            with self._trace_ctx():
                return prefill_step(cfg, params, batch, window=window)

        def _probed_paged_prefill(params, batch, true_len):
            self.prefill_traces += 1
            self._note_compile(f"prefill/paged{_batch_len(batch)}")
            with self._trace_ctx():
                return paged_prefill_step(cfg, params, batch, true_len,
                                          kv_dtype=self.kv_dtype)

        def _probed_suffix(params, cache, tokens, true_len):
            # suffix-offset prefill over a seeded linear cache: retraces
            # once per SUFFIX bucket width (cache width is always
            # max_seq), never per hit length — start/true_len are traced
            self.prefill_traces += 1
            self._note_compile(f"prefill/suffix{tokens.shape[1]}")
            with self._trace_ctx():
                return prefill_chunk_step(cfg, params, cache, tokens,
                                          true_len)

        def _chunk_step(params, cache, tokens, true_len):
            self._note_compile(f"prefill/chunk{tokens.shape[1]}")
            with self._trace_ctx():
                return prefill_chunk_step(cfg, params, cache, tokens,
                                          true_len)

        def _first_token(logits, samp1, pos):
            # prefill logits are vocab-sharded under TP; replicate before
            # the stochastic draw (see decode_tick's logits_sharding)
            if self._logits_sharding is not None:
                logits = jax.lax.with_sharding_constraint(
                    logits, self._logits_sharding)
            with self._trace_ctx(), jax.named_scope("sampler"):
                return sample_tokens(logits, samp1, pos)

        donate0 = (0,) if donate else ()
        self._decode = jax.jit(_probed_decode, donate_argnums=donate_cache)
        self._decode_scan = jax.jit(_probed_scan, donate_argnums=donate_cache)
        self._prefill_bucketed = jax.jit(_probed_bucketed)
        self._prefill_exact = jax.jit(_probed_exact)
        self._prefill_paged = jax.jit(_probed_paged_prefill)
        self._prefill_chunk = jax.jit(
            _chunk_step, donate_argnums=(1,) if donate else ())
        self._insert = jax.jit(
            partial(cache_insert, batch=slots),
            donate_argnums=donate0)
        self._pages_insert = jax.jit(pages_insert, donate_argnums=donate0)
        # prefix-hit path: the seed reads the pools (never donated); the
        # suffix step consumes the seeded linear cache; the scatter
        # donates the pools like every other admission write
        self._prefix_seed = jax.jit(prefix_seed_cache)
        self._prefill_suffix = jax.jit(
            _probed_suffix, donate_argnums=(1,) if donate else ())
        self._pages_insert_prefix = jax.jit(pages_insert_prefix,
                                            donate_argnums=donate0)
        self._table_append = jax.jit(page_table_append, donate_argnums=donate0)
        self._release = jax.jit(slot_release, donate_argnums=donate0)
        self._set_token = jax.jit(_token_set)
        # sampling: one scatter trace for every (slot, params) setting; one
        # B=1 sampler trace for every sampled request's FIRST token (the
        # decode ticks sample in-trace — see decode_tick)
        self._samp_set = jax.jit(sampling_set, donate_argnums=donate0)
        self._sample_first = jax.jit(_first_token)

    # -- observability helpers ---------------------------------------------
    def _note_compile(self, key: str):
        """Count one jit trace against its trace-cache key proxy. Runs at
        TRACE time only (inside the probed closures), so warm calls cost
        nothing; the key is shape-derived, so growth in any one key is a
        trace-cache regression."""
        self.compile_events[key] = self.compile_events.get(key, 0) + 1
        if self._trace_on:
            self.tracer.event("compile", self._last_now, key=key)

    def _tr(self, req: Request) -> Optional[Trace]:
        """The trace to stamp for ``req``: its existing one (a tracing
        frontend may have created it), a fresh one when engine tracing is
        on and the rid falls in the sample (``rid % trace_sample_n == 0``),
        or None (tracing off / rid sampled out — no stamping)."""
        t = req.trace
        if (t is None and self._trace_on
                and req.rid % self._trace_every == 0):
            t = req.trace = Trace(req.rid)
        return t

    def _tr_admit(self, req: Request, now: float, path: str, slot: int):
        """Close the queued span and open the prefill span at admission."""
        t = self._tr(req)
        if t is None:
            return
        if t.is_open("queued"):
            t.end("queued", now)
        t.begin("prefill", now, path=path, slot=slot)

    def _tr_terminal(self, req: Request, now: float, kind: str, **meta):
        """Stamp a terminal event (rejected/abort) and fold the trace into
        the engine rollup."""
        t = req.trace
        if t is None:
            return
        t.close_all(now)
        t.event(kind, now, **meta)
        self.tracer.collect(t)

    def start_profile(self) -> bool:
        """Arm ``jax.profiler`` tracing into ``config.profile_dir``; no-op
        (False) when no directory is configured or already profiling."""
        if not self.config.profile_dir or self._profiling:
            return False
        jax.profiler.start_trace(self.config.profile_dir)
        self._profiling = True
        if self._trace_on:
            self.tracer.event("profile_start", self._last_now,
                              dir=self.config.profile_dir)
        return True

    def stop_profile(self) -> bool:
        if not self._profiling:
            return False
        jax.profiler.stop_trace()
        self._profiling = False
        if self._trace_on:
            self.tracer.event("profile_stop", self._last_now)
        return True

    def metrics_registry(self) -> MetricsRegistry:
        """This engine's metrics as a registry (exposition-ready):
        ServeMetrics counters/histograms plus engine-level accounting —
        per-key compile events, per-kind span totals, per-step wall time."""
        reg = self.metrics.registry()
        reg.set_counter("serving_prefill_traces_total", self.prefill_traces)
        reg.set_counter("serving_decode_traces_total", self.decode_traces)
        for key, n in sorted(self.compile_events.items()):
            reg.set_counter(
                f"serving_compile_events_total{{key=\"{key}\"}}", n)
        for kind, (c, s) in sorted(self.tracer.span_totals.items()):
            reg.set_counter(f"serving_span_count_total{{kind=\"{kind}\"}}", c)
            reg.set_gauge(f"serving_span_seconds{{kind=\"{kind}\"}}", s)
        if self._tick_wall.count:
            reg.register("serving_step_wall_seconds", self._tick_wall)
        return reg

    # -- admission ---------------------------------------------------------
    def submit(self, req: Request, now: float) -> bool:
        """Admit immediately while free capacity exists (holding a request
        back from an idle slot buys nothing); once saturated, queue and
        batch admissions up to the cost-model deadline (``plan_admission``)
        so freed slots refill in groups. Unservable requests (prompt beyond
        max_seq) are rejected HERE, before queueing — a poison request must
        never reach the backlog, where its admission failure would abort
        every subsequent tick. Rejection is a typed OUTCOME, not an
        exception: the request comes back FAILED (with ``fail_reason``)
        from the next ``step``, and ``False`` is returned so a frontend
        never tracks it as in-flight."""
        self._last_now = now
        t = self._tr(req)
        if t is not None and not t.is_open("queued"):
            t.begin("queued", now)
        try:
            self._check_servable(req)
        except RequestRejected as e:
            self._reject(req, now, str(e))
            return False
        if (not self.backlog and not self.admission.pending
                and self.try_admit(req, now)):
            return True
        flushed = self.admission.add(req, now)
        if flushed:
            self.backlog.extend(flushed)
            self._drain_backlog(now)
        return True

    def _reject(self, req: Request, now: float, reason: str):
        """Turn an unservable submission into a terminal FAILED outcome
        (surfaced by the next ``step`` like any finished request)."""
        req.state = RequestState.FAILED
        req.fail_reason = reason
        req.finish_time = now
        self.metrics.rejected += 1
        if req.tenant:
            self.metrics.tenant(req.tenant).rejected += 1
        self._tr_terminal(req, now, "rejected", reason=reason[:120])
        self._finished.append(req)

    def _pump_admissions(self, now: float):
        flushed = self.admission.poll(now)
        if flushed:
            self.backlog.extend(flushed)
        self._drain_backlog(now)

    def _drain_backlog(self, now: float):
        while self.backlog:
            idx = 0
            if self.edf_backlog:
                # earliest TTFT deadline first; FIFO among equal deadlines
                # (untracked requests have an infinite deadline and drain
                # after every SLO-tracked one)
                idx = min(range(len(self.backlog)),
                          key=lambda k: (self.backlog[k].ttft_deadline, k))
            if not self._admit_or_preempt(self.backlog[idx], now):
                break
            del self.backlog[idx]

    def _admit_or_preempt(self, req: Request, now: float) -> bool:
        """Admit ``req``; when admission backpressures (no slot / no pages)
        and preemption is on, evict strictly-less-urgent victims (policy-
        chosen) until it fits or no eligible victim remains. Victims
        requeue at the back of the backlog; strictness of the urgency
        comparison bounds preemption chains and prevents two requests
        from evicting each other forever."""
        if self.try_admit(req, now):
            return True
        if not self.preemption:
            return False
        while True:
            slot = self._choose_victim(req)
            if slot is None:
                return False
            victim = self.preempt(slot, now)
            if victim is not None:
                self.backlog.append(victim)
            if self.try_admit(req, now):
                return True

    def _choose_victim(self, cand: Request) -> Optional[int]:
        """Slot to evict so ``cand`` can run: decoding slots whose request
        is STRICTLY less urgent are eligible; the configured policy picks
        among them (default latest-deadline-first). None = don't preempt."""
        eligible = [i for i, (r, d) in enumerate(zip(self.active,
                                                     self.decoding))
                    if r is not None and d and _urgency(cand) < _urgency(r)]
        if not eligible:
            return None
        return self._preempt_victim_fn(self, eligible)

    def preempt(self, slot: int, now: float) -> Optional[Request]:
        """Evict the decoding request in ``slot`` mid-stream and return it
        for requeueing (state PREEMPTED). Deferred tokens are flushed
        first, so the victim's ``output`` is complete up to its cache
        position; the generated tokens fold into its prompt
        (``fold_output_into_prompt``) and — when the prefix cache is on —
        every full page of now-valid KV is registered in the
        ``PrefixIndex`` BEFORE the slot's references drop, so re-admission
        restores the stream with suffix-only prefill (recompute-free).
        Seeded sampling keys noise by absolute position, so the restored
        stream is bit-identical to an unpreempted run. Returns None when
        the flush finished the request (nothing to evict)."""
        assert self.paged, "preemption requires the paged KV cache"
        self._flush(now)
        req = self.active[slot]
        if req is None or not self.decoding[slot]:
            return None
        req.fold_output_into_prompt()
        if self.prefix_index is not None:
            # KV is valid through position pos-1 (= prompt_len-2 after the
            # fold: the newest token lives only in the device carry), so
            # only pages fully inside that span are indexable
            ps = self.page_size
            owned = self.allocator.owned(slot)
            n = min(self._pos_h[slot] // ps, len(owned))
            if n > 0:
                self.prefix_index.register(req.prompt[:n * ps], owned[:n])
        self.release_slot(slot)
        req.state = RequestState.PREEMPTED
        req.preemptions += 1
        self.metrics.preempted += 1
        t = req.trace
        if t is not None:
            if t.is_open("decode"):
                t.end("decode", now, tokens=len(req.output))
            t.event("preempt", now, slot=slot, policy=self.preempt_policy)
            t.begin("queued", now)  # the victim requeues for restore
        return req

    def try_admit(self, req: Request, now: float) -> bool:
        """Claim a free slot for ``req``. Long prompts (when chunking is on
        and the prompt fits the prefill buffer) enter chunked prefill: the
        slot is reserved and the prompt is processed ``chunk`` tokens per
        tick, interleaved with decode. Short prompts prefill immediately
        (bucketed when possible). In paged mode the request's worst-case
        pages (padded prompt + token budget) are reserved up front; an
        exhausted pool rejects the admission (backpressure — the request
        stays queued until pages free up)."""
        self._check_servable(req)
        for i, slot in enumerate(self.active):
            if slot is None and not any(j.slot == i for j in self._jobs):
                hit = None
                if self.prefix_index is not None:
                    hit = self.prefix_index.lookup(req.prompt)
                if self.paged and not self._reserve_pages(req, i, hit):
                    return False  # out of pages: backpressure
                if hit is not None:
                    self._admit_prefix(req, i, hit, now)
                elif self._chunkable(req):
                    self._start_chunked(req, i, now)
                else:
                    self._admit_now(req, i, now)
                return True
        return False

    def _check_servable(self, req: Request):
        if self.paged and req.prompt_len > self.max_seq:
            raise RequestRejected(
                f"prompt of {req.prompt_len} tokens exceeds max_seq="
                f"{self.max_seq}; raise ServingEngine(max_seq=...)")
        if self._moe_gmax and self._moe_prefill_group(req) > self._moe_gmax:
            raise RequestRejected(
                f"prefill group of {self._moe_prefill_group(req)} tokens "
                f"exceeds the drop-free MoE bound {self._moe_gmax} "
                f"(capacity_factor={self.cfg.moe_capacity_factor}): routing "
                f"could silently drop tokens; raise moe_capacity_factor, "
                f"use moe_capacity_policy='strict', or shorten the prompt")

    def _moe_prefill_group(self, req: Request) -> int:
        """Upper bound on the MoE routing group a prefill of ``req`` can
        see: chunked prefill routes one chunk at a time, single-shot
        prefill routes the padded prompt (``apply_moe`` caps groups at
        2048 and only ever SHRINKS to divide the token count)."""
        g = self.chunk if self._chunkable(req) else self._prefill_len(req)
        return min(2048, g)

    def _chunkable(self, req: Request) -> bool:
        cap = self.max_seq if self.paged else self._min_window
        quantum = self._chunk_quantum if self.paged else self.chunk
        return (self.chunk > 0
                and req.prompt_len > self.chunk
                and _padded_len(req.prompt_len, quantum) <= cap)

    def _bucket_for(self, plen: int) -> Optional[int]:
        if not self.bucket_prompts:
            return None
        if self.paged:
            b = prompt_bucket(plen, min_bucket=max(16, self.page_size))
            return b if b <= self.max_seq else None
        b = prompt_bucket(plen)
        return b if b <= self._min_window else None

    def _prefill_len(self, req: Request) -> int:
        """Token capacity the prefill path will occupy for ``req`` (the
        padded prompt length — every variant page-aligned in paged mode)."""
        plen = req.prompt_len
        if self._chunkable(req):
            quantum = self._chunk_quantum if self.paged else self.chunk
            return _padded_len(plen, quantum)
        bucket = self._bucket_for(plen)
        if bucket is not None:
            return bucket
        return _padded_len(plen, self.page_size) if self.paged else plen

    def _suffix_chunked(self, req: Request, hit: PrefixHit) -> bool:
        """Whether the hit's suffix goes through interleaved chunk steps
        (long suffix) instead of one synchronous bucketed suffix step."""
        return (self.chunk > 0
                and req.prompt_len - hit.tokens > self.chunk
                and _padded_len(req.prompt_len, self._chunk_quantum)
                <= self.max_seq)

    def _suffix_plan(self, req: Request, hit: PrefixHit):
        """(start, end) of the suffix-offset prefill in the linear buffer:
        tokens [start, end) are (re)computed — start <= hit.tokens keeps
        the span aligned to the chunk grid / bucket width so hit lengths
        share traces; end never exceeds max_seq (the linear buffer must
        not wrap)."""
        plen, h = req.prompt_len, hit.tokens
        if self._suffix_chunked(req, hit):
            s = (h // self.chunk) * self.chunk
            return s, _padded_len(plen, self._chunk_quantum)
        c = min(prompt_bucket(plen - h, min_bucket=max(16, self.page_size)),
                self.max_seq)
        s = min(h, self.max_seq - c)
        return s, s + c

    def _alloc_evicting(self, slot: int, n: int) -> bool:
        """All-or-nothing grant, evicting idle cached prefixes (LRU) to
        cover a shortfall before refusing."""
        if (not self.allocator.can_alloc(n)
                and self.prefix_index is not None):
            self.prefix_index.evict(n - self.allocator.free_pages)
        return self.allocator.alloc(slot, n) is not None

    def _reserve_pages(self, req: Request, slot: int,
                       hit: Optional[PrefixHit] = None) -> bool:
        """Grant ``req``'s worst-case lifetime pages to ``slot`` before any
        prefill compute runs: the padded prompt plus its full token budget
        (capped at max_seq). All-or-nothing — reserving the decode tail up
        front means pool shortage always surfaces HERE as admission
        backpressure, never as mid-stream exhaustion (requests that stop
        early at eos return the tail unused).

        With a prefix ``hit``, the matched full pages are SHARED into the
        slot (refcount+1, no pool spend) and only the remainder — the COW
        tail replacement, suffix pages, decode tail — is allocated. Under
        pool pressure, idle cached prefixes are evicted (oldest first)
        before the admission is refused."""
        if self.allocator.owned(slot):
            # Lifecycle bypassed (e.g. a slot vacated without release):
            # reclaim on device first so the stale table row can never
            # alias pages about to be re-granted.
            self.cache = self._release(self.cache, np.int32(slot))
            self.allocator.free_slot(slot)
            self._pos_h[slot] = 0
            self._tabled[slot] = 0
            self._hit_pending.pop(slot, None)
        # restore-aware lifetime: a preempted request's folded tokens are
        # already inside prompt_len AND inside max_new_tokens (its output
        # keeps them), so only the REMAINING budget extends the stream
        lifetime = min(req.prompt_len + max(1, req.remaining_tokens) - 1,
                       self.max_seq)
        if hit is None:
            n = self.allocator.pages_for(max(self._prefill_len(req), lifetime))
            return self._alloc_evicting(slot, n)
        # Share first: a shared page is no longer evictable, so the
        # eviction pass below can never reclaim the chain we are using.
        shared = self.allocator.share(slot, list(hit.full_pages))
        if hit.tail_page >= 0:
            self.allocator.retain(hit.tail_page)  # pin the COW source
        _, end = self._suffix_plan(req, hit)
        n_priv = self.allocator.pages_for(max(end, lifetime)) - len(shared)
        if not self._alloc_evicting(slot, n_priv):
            if hit.tail_page >= 0:
                self.allocator.release(hit.tail_page)
            self.allocator.free_slot(slot)  # drop the shares (rollback)
            return False
        return True

    def _admit_now(self, req: Request, slot: int, now: float):
        self._tr_admit(req, now, "full", slot)
        plen = req.prompt_len
        bucket = None if self.paged else self._bucket_for(plen)
        if self.paged:
            # page-aligned linear prefill (bucketed, or page-rounded exact)
            padded_len = self._prefill_len(req)
            padded = np.zeros((1, padded_len), np.int32)
            padded[0, :plen] = req.prompt
            batch = {"tokens": jnp.asarray(padded)}
            if self.cfg.rope_variant == "mrope":
                batch["positions"] = jnp.broadcast_to(
                    jnp.arange(padded_len, dtype=jnp.int32), (3, 1, padded_len))
            tok, last, cache1 = self._prefill_paged(
                self.params, batch, np.int32(plen))
        elif bucket is not None:
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = req.prompt
            batch = {"tokens": jnp.asarray(padded)}
            if self.cfg.rope_variant == "mrope":
                batch["positions"] = jnp.broadcast_to(
                    jnp.arange(bucket, dtype=jnp.int32), (3, 1, bucket))
            tok, last, cache1 = self._prefill_bucketed(
                self.params, batch, np.int32(plen))
        else:
            batch = {"tokens": jnp.asarray(req.prompt[None, :], jnp.int32)}
            if self.cfg.rope_variant == "mrope":
                batch["positions"] = jnp.broadcast_to(
                    jnp.arange(plen, dtype=jnp.int32), (3, 1, plen))
            last, cache1 = self._prefill_exact(self.params, batch)
            tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        self._activate(req, slot, tok, last, cache1, now)

    def _admit_prefix(self, req: Request, slot: int, hit: PrefixHit,
                      now: float):
        """Admit a request whose prefix is cached: alias the matched full
        pages into the slot's table row (zero prefill compute for the
        hit), gather the chain into a seeded linear buffer, and prefill
        ONLY the suffix from a nonzero offset — synchronously in one
        bucketed multi-token step, or through the interleaved chunk path
        when the suffix is long. A partially-matched tail page is never
        aliased: its matched tokens ride the gathered buffer and scatter
        into a private page at activation (copy-on-write)."""
        self._tr_admit(req, now, "prefix", slot)
        if req.trace is not None:
            req.trace.spans[-1].meta["prefix_hit"] = hit.tokens
        plen, ps = req.prompt_len, self.page_size
        n_full = len(hit.full_pages)
        owned = self.allocator.owned(slot)  # [shared full..., private...]
        start, end = self._suffix_plan(req, hit)
        # gather chain: full pages + COW tail source, trash-padded to the
        # fixed max_pages width (one seed trace for every hit length)
        chain = list(hit.full_pages)
        if hit.tail_page >= 0:
            chain.append(hit.tail_page)
        gpages = np.zeros((self.max_pages,), np.int32)
        gpages[:len(chain)] = chain
        # table row: aliased fulls, then privates (COW tail replacement,
        # suffix, decode tail); scatter row: privates only — a shared
        # page is never written
        trow = np.zeros((self.max_pages,), np.int32)
        trow[:len(owned)] = owned
        srow = np.zeros((self.max_pages,), np.int32)
        srow[n_full:len(owned)] = owned[n_full:]
        self._hit_pending[slot] = _HitAdmission(srow, trow, len(owned))
        req.prefix_hit_tokens = hit.tokens
        self.metrics.prefix_hits += 1
        self.metrics.prefix_hit_tokens += hit.tokens
        cache1 = self._prefix_seed(self.cache, jnp.asarray(gpages),
                                   np.int32(start))
        if hit.tail_page >= 0:
            self.allocator.release(hit.tail_page)  # gather done: unpin
        padded = np.zeros((1, end), np.int32)
        padded[0, :plen] = req.prompt
        if self._suffix_chunked(req, hit):
            self._jobs.append(_PrefillJob(
                req=req, slot=slot, cache=cache1,
                tokens=jnp.asarray(padded), true_len=np.int32(plen),
                next_off=start))
            req.state = RequestState.PREFILL
            self.active[slot] = req  # reserve (decoding stays False)
            return
        toks = jnp.asarray(padded[:, start:end])
        tok, last, cache1 = self._prefill_suffix(self.params, cache1, toks,
                                                 np.int32(plen))
        self._activate(req, slot, tok, last, cache1, now)

    def _put_linear(self, cache1):
        """Commit a host-built B=1 linear cache to the replica mesh (KV
        sharded over kv heads, like every other cache); identity on 1-chip
        engines. Keeps chunked-prefill working buffers from pinning a
        replicated copy on every device."""
        if self.mesh is None:
            return cache1
        return jax.device_put(
            cache1,
            to_shardings(self.mesh, cache_pspecs(self.cfg, cache1,
                                                 self._policy, self.mesh)))

    def _start_chunked(self, req: Request, slot: int, now: float):
        self._tr_admit(req, now, "chunked", slot)
        padded_len = self._prefill_len(req)
        padded = np.zeros((1, padded_len), np.int32)
        padded[0, :req.prompt_len] = req.prompt
        # paged: a LINEAR buffer at the shared max_seq width (every chunked
        # job then hits one compiled chunk step; pages_insert slices the
        # prompt's pages out at activation); rolling: the window-size ring.
        buf = self.max_seq if self.paged else self.window
        self._jobs.append(_PrefillJob(
            req=req, slot=slot,
            cache=self._put_linear(init_cache(self.cfg, 1, buf,
                                              self.kv_dtype)),
            tokens=jnp.asarray(padded),
            true_len=np.int32(req.prompt_len)))
        req.state = RequestState.PREFILL
        self.active[slot] = req  # reserve (decoding stays False)

    def _run_prefill_chunks(self, now: float):
        if not self._jobs:
            return
        pending = sum(
            (j.tokens.shape[1] - j.next_off) // self.chunk for j in self._jobs)
        n = self.prefill_policy.chunks_this_tick(
            self.cfg, n_decoding=self.n_decoding, pending_chunks=pending,
            context=self.window)
        for _ in range(n):
            if not self._jobs:
                break
            job = self._jobs[0]
            chunk_toks = jax.lax.slice_in_dim(
                job.tokens, job.next_off, job.next_off + self.chunk, axis=1)
            tok, last, job.cache = self._prefill_chunk(
                self.params, job.cache, chunk_toks, job.true_len)
            prev_off = job.next_off
            job.next_off += self.chunk
            if prev_off <= int(job.true_len) - 1 < job.next_off:
                # first-token logits live in the chunk holding position
                # true_len-1; later chunks (pure quantum padding) return
                # a clamped garbage index — keep the real one
                job.tok = tok
                job.logits = last
            self.metrics.prefill_chunks += 1
            if job.req.trace is not None:
                job.req.trace.event("prefill_chunk", now, offset=prev_off,
                                    slot=job.slot)
            if job.next_off >= job.tokens.shape[1]:
                self._jobs.popleft()
                self._activate(job.req, job.slot,
                               tok if job.tok is None else job.tok,
                               last if job.logits is None else job.logits,
                               job.cache, now)

    def _activate(self, req: Request, slot: int, tok, last, cache1,
                  now: float):
        """Install a prefilled request into its slot: scatter the B=1 cache
        (donated, in-place), set the device token carry, record the first
        token. Forces a token flush first so the deferred-sync window only
        ever spans a fixed slot membership. Paged mode scatters into the
        slot's reserved pool pages and writes its page-table row instead of
        copying into a per-slot window.

        ``last`` is the prompt's last-true-position logits (1, V): a
        stochastic request draws its first token from them here, with the
        same (seed, position=prompt_len) noise key every admission path —
        full, bucketed, chunked, or prefix-hit suffix — would produce, so
        a prompt's stream is independent of HOW it was prefilled. The
        slot's sampling state is scattered before the first decode tick
        can read it."""
        self._flush(now)
        sp = req.sampling or SamplingParams()
        if not (sp.greedy and self._samp_greedy_h[slot]):
            # greedy request on an already-greedy lane: no row to build,
            # no scatter — the default path stays key-init-free
            row = sampling_row(sp)
            self._samp = self._samp_set(self._samp, np.int32(slot), row)
        self._samp_greedy_h[slot] = sp.greedy
        if not sp.greedy:
            self.metrics.sampled_requests += 1
            samp1 = {k: jnp.asarray(v)[None] for k, v in row.items()}
            tok = self._sample_first(last, samp1,
                                     np.full((1,), req.prompt_len, np.int32))
        if self.paged:
            info = self._hit_pending.pop(slot, None)
            if info is not None:
                # prefix hit: the fixed-width scatter writes the suffix
                # into private pages (trash at aliased positions) and the
                # FULL table row — shared fulls, COW tail, decode tail —
                # in one go (one trace for every hit shape)
                self.cache = self._pages_insert_prefix(
                    self.cache, cache1, jnp.asarray(info.scatter_pages),
                    jnp.asarray(info.table_pages), np.int32(slot),
                    np.int32(req.prompt_len))
                self._pos_h[slot] = req.prompt_len
                self._tabled[slot] = info.n_tabled
            else:
                # scatter the prompt into the reservation's LEADING pages;
                # the decode-tail pages (also reserved) enter the table
                # row lazily as the stream grows, so pages_insert keeps
                # one trace per bucket regardless of each token budget
                n_pref = self.allocator.pages_for(self._prefill_len(req))
                pages = jnp.asarray(self.allocator.owned(slot)[:n_pref],
                                    jnp.int32)
                self.cache = self._pages_insert(
                    self.cache, cache1, pages, np.int32(slot),
                    np.int32(req.prompt_len))
                self._pos_h[slot] = req.prompt_len
                self._tabled[slot] = n_pref
            if self.prefix_index is not None:
                # register the finished prompt's FULL pages (only spans
                # entirely inside the prompt: an indexed page is never
                # appended to again — the COW invariant)
                n_full = req.prompt_len // self.page_size
                owned = self.allocator.owned(slot)
                if n_full:
                    self.prefix_index.register(req.prompt, owned[:n_full])
            # the page table caps a request's lifetime tokens at max_seq;
            # surface the truncation on the request instead of failing.
            # Restore-aware: a preempted request's folded tokens already
            # count against both prompt_len and output, so only the
            # REMAINING budget is compared against the cap.
            already = len(req.output)
            cap = max(1, self.max_seq - req.prompt_len)
            if req.max_new_tokens - already > cap:
                req.max_new_tokens = already + cap
                req.budget_capped = True
        else:
            self.cache = self._insert(self.cache, cache1, np.int32(slot))
        self._tokens = self._set_token(self._tokens, tok, np.int32(slot))
        req.output.append(int(tok[0]))
        if req.prefill_done < 0:
            req.prefill_done = now
            self.metrics.ttfts.append(req.ttft)
            # brownout is counted where the request SERVES (here), not at
            # the frontend that trimmed it — merged cluster metrics must
            # not double-count a request that crossed both layers
            if req.browned_out_tokens:
                self.metrics.browned_out += 1
            if req.tenant:
                tm = self.metrics.tenant(req.tenant)
                tm.admitted += 1
                tm.ttfts.append(req.ttft)
                if req.browned_out_tokens:
                    tm.browned_out += 1
                    tm.brownout_trimmed_tokens += req.browned_out_tokens
        if req.state is RequestState.PREEMPTED:
            self.metrics.preempt_restores += 1
        t = req.trace
        if t is not None:
            if t.is_open("queued"):  # direct try_admit paths skip submit
                t.end("queued", now)
            if t.is_open("prefill"):
                t.end("prefill", now, tokens=req.prompt_len)
            if not sp.greedy:
                t.event("sample", now, seed=sp.seed)
            if req.state is RequestState.PREEMPTED:
                t.event("restore", now, slot=slot,
                        preemptions=req.preemptions)
            t.begin("decode", now, slot=slot)
        req.state = RequestState.DECODE
        self.active[slot] = req
        self.decoding[slot] = True
        if req.done:
            # The prefill token alone met the budget (max_new_tokens <= 1,
            # or the prompt filled max_seq): finalize here — the decode
            # loop only finalizes requests as it appends tokens, and a
            # done-at-activation slot would otherwise zombie forever,
            # holding its pages.
            self._finalize_request(req, slot, now)

    # -- decode tick --------------------------------------------------------
    def step(self, now: float) -> List[Request]:
        """One engine tick: pump queued admissions, run prefill chunks per
        the interleave policy, then batched decode. In steady state (no
        pending admissions or prefill chunks, every active request has >=
        sync_every tokens to go) the whole deferred-sync window runs as ONE
        fused jitted scan — one dispatch and one host transfer per
        sync_every tokens. Scheduling boundaries fall back to single ticks.
        Returns the requests that finished (host-visible) this tick —
        including aborted ones (cancelled / timed out / shed / failed),
        which come back in a terminal ``RequestState`` with
        ``fail_reason`` set."""
        self._last_now = now
        if not self._trace_on:
            return self._step(now)
        # per-tick wall accounting (profiling hook): host wall seconds per
        # step() call — the virtual `now` clock says nothing about what a
        # tick actually cost
        w0 = time.perf_counter()
        self._steps += 1
        try:
            with jax.profiler.StepTraceAnnotation("engine.step",
                                                  step_num=self._steps):
                return self._step(now)
        finally:
            self._tick_wall.observe(time.perf_counter() - w0)

    def _step(self, now: float) -> List[Request]:
        phase = self._phase
        with phase("engine.reap"):
            self._reap_doomed(now)
        with phase("engine.admit"):
            self._pump_admissions(now)
        with phase("engine.prefill_chunks"):
            self._run_prefill_chunks(now)
        if not any(self.decoding):
            return self._take_finished()
        if self._fusable():
            if self.paged:
                with phase("engine.pages"):
                    self._ensure_headroom(self.sync_every, now)
            with phase("engine.dispatch"):
                toks, hist, self.cache = self._decode_scan(
                    self.params, self.cache, self._tokens, self._samp)
            self._tokens = toks
            self.metrics.decode_ticks += self.sync_every
            self.metrics.fused_ticks += self.sync_every
            self._advance_pos(self.sync_every)
            with phase("engine.sync"):
                hist = np.asarray(hist)
            with phase("engine.deliver"):
                self._distribute(hist, now)
            return self._take_finished()
        if self.paged:
            with phase("engine.pages"):
                self._ensure_headroom(1, now)
        with phase("engine.dispatch"):
            nxt, self.cache = self._decode(self.params, self.cache,
                                           self._tokens, self._samp)
        self._tokens = nxt
        self._unsynced.append(nxt)
        self.metrics.decode_ticks += 1
        self._advance_pos(1)
        pend = len(self._unsynced)
        if (pend >= self.sync_every
                or any(r is not None and d
                       and len(r.output) + pend >= r.max_new_tokens
                       for r, d in zip(self.active, self.decoding))):
            self._flush(now)
        return self._take_finished()

    # -- lifecycle: deadline-abort / cancel / shed --------------------------
    def _reap_doomed(self, now: float):
        """Abort every doomed request — client-cancelled, past its
        whole-request deadline, or (``shed_overdue``) queued past its TTFT
        deadline — wherever it sits: frontend-visible queues, chunked
        prefill, or a live decode slot. Freed slots and pages go back to
        the pool the same tick, so a doomed request never burns another
        decode step's budget."""

        def doom(req: Request) -> Optional[RequestState]:
            d = req.overdue(now)
            if d is not None:
                return d
            if (self.shed_overdue and req.prefill_done < 0
                    and now > req.ttft_deadline):
                return RequestState.TIMED_OUT  # shed (counted separately)
            return None

        # queued (backlog + admission accumulator): no resources held
        for queue in (self.backlog, self.admission.pending):
            doomed = [r for r in queue if doom(r) is not None]
            for req in doomed:
                queue.remove(req)
                self._abort(req, now, doom(req))
        # chunked prefill jobs: slot + page reservation held
        for job in [j for j in self._jobs if doom(j.req) is not None]:
            self._jobs.remove(job)
            state = doom(job.req)
            self.release_slot(job.slot)
            self._abort(job.req, now, state)
        # live decode slots: flush deferred tokens first so the abort
        # decision (and every OTHER slot's stream) sees a complete output
        if any(r is not None and d and doom(r) is not None
               for r, d in zip(self.active, self.decoding)):
            self._flush(now)
            for i, (r, d) in enumerate(zip(self.active, self.decoding)):
                if r is None or not d:
                    continue
                state = doom(r)
                if state is not None:
                    self.release_slot(i)
                    self._abort(r, now, state)

    def _abort(self, req: Request, now: float, state: RequestState):
        """Terminal bookkeeping for an aborted request (slot/pages already
        released by the caller)."""
        shed = (state is RequestState.TIMED_OUT
                and not req.cancel_requested and now <= req.jct_deadline)
        req.state = state
        req.finish_time = now
        if state is RequestState.CANCELLED:
            req.fail_reason = req.fail_reason or "cancelled by client"
            self.metrics.cancelled += 1
        elif shed:
            req.fail_reason = (f"shed: TTFT deadline "
                               f"{req.ttft_deadline:.4f} unreachable at "
                               f"{now:.4f} (overload)")
            self.metrics.shed += 1
            if req.tenant:
                self.metrics.tenant(req.tenant).shed += 1
        else:
            req.fail_reason = req.fail_reason or (
                f"timed out: exceeded timeout_s={req.timeout_s:.4f} "
                f"after arrival")
            self.metrics.timed_out += 1
        self._tr_terminal(req, now, "abort", state=state.value,
                          reason=req.fail_reason[:120])
        self._finished.append(req)

    def _fail_slot(self, slot: int, now: float, reason: str):
        """Fail ONLY the request in ``slot`` (mid-stream resource loss —
        e.g. a bypassed page reservation surfacing as pool exhaustion):
        the engine and every other stream keep running."""
        req = self.active[slot]
        self.release_slot(slot)
        req.state = RequestState.FAILED
        req.fail_reason = reason
        req.finish_time = now
        self.metrics.failed += 1
        self._tr_terminal(req, now, "abort", state="failed",
                          reason=reason[:120])
        self._finished.append(req)

    def takeover_queue(self) -> List[Request]:
        """Hand back every queued-but-unstarted request (backlog +
        admission accumulator, in drain order) — the migration primitive:
        a retiring replica's queue moves through the cluster frontend to
        survivors instead of waiting out the drain here. In-flight work
        (decode slots, chunk jobs) stays and finishes locally."""
        out = list(self.backlog)
        self.backlog.clear()
        out.extend(self.admission.flush())
        return out

    def _advance_pos(self, n: int):
        """Advance the host mirror of each decoding slot's cache position
        (paged mode tracks it to pre-allocate decode pages without a
        device sync) over ``n`` decode ticks, and count the pages their
        attention reads: tick k of a slot at position p attends p + k + 1
        tokens."""
        if not self.paged:
            return
        ps, m = self.page_size, self.metrics
        m.decode_kv_pages_row += n * self.slots * self.max_pages
        for i, d in enumerate(self.decoding):
            if d:
                p = self._pos_h[i]
                m.decode_kv_pages_read += sum(
                    -(-min(p + k, self.max_seq) // ps)
                    for k in range(1, n + 1))
                self._pos_h[i] = p + n

    def _ensure_headroom(self, n: int, now: float = 0.0):
        """Write every decoding slot enough page-table entries to absorb
        ``n`` more tokens BEFORE the fused window runs — table writes are
        host decisions and cannot happen inside the scan. The pages come
        from the slot's admission-time reservation; allocating here is a
        defensive fallback (reachable only when the reservation lifecycle
        was bypassed). A shortage — after evicting idle cached prefixes —
        fails ONLY the starved request (loud ``OutOfPagesError`` text in
        its ``fail_reason``, naming the sizing fix); the engine and every
        other stream keep serving."""
        for i, (r, d) in enumerate(zip(self.active, self.decoding)):
            if r is None or not d:
                continue
            end = min(self._pos_h[i] + n, self.max_seq)
            need = self.allocator.pages_for(end)
            if need <= self._tabled[i]:
                continue
            owned = self.allocator.owned(i)
            if need > len(owned):
                if not self._alloc_evicting(i, need - len(owned)):
                    self._fail_slot(i, now, (
                        f"OutOfPagesError: slot {i} needs "
                        f"{need - len(owned)} page(s) mid-decode but the "
                        f"pool is exhausted ({self.allocator.pages_in_use}/"
                        f"{self.allocator.capacity} in use); size pool_pages "
                        f"for decode headroom "
                        f"(slots * max_seq / page_size + 1)"))
                    continue
                owned = self.allocator.owned(i)
            for k in range(self._tabled[i], need):
                self.cache = self._table_append(
                    self.cache, np.int32(i), np.int32(k), np.int32(owned[k]))
            self._tabled[i] = need

    def _finalize_request(self, req: Request, slot: int, now: float):
        """Retire a finished request: record metrics, free the slot (and
        its pages), and stage it for the caller."""
        req.state = RequestState.FINISHED
        req.finish_time = now
        self._finished.append(req)
        self.release_slot(slot)
        self.metrics.completed += 1
        self.metrics.total_tokens += len(req.output)
        if req.tenant:
            tm = self.metrics.tenant(req.tenant)
            tm.completed += 1
            tm.total_tokens += len(req.output)
        jct = now - req.arrival_time
        self.metrics.jcts.append(jct)
        self.metrics.latencies.append(jct)
        if req.tpot > 0:
            self.metrics.tpots.append(req.tpot)
        self.metrics.record_slo(req)
        t = req.trace
        if t is not None:
            if t.is_open("decode"):
                t.end("decode", now, tokens=len(req.output))
            self.tracer.collect(t)

    def release_slot(self, slot: int):
        """Retire ``slot`` (finished or cancelled request): return its pages
        to the allocator and neutralize its device page-table row."""
        self.active[slot] = None
        self.decoding[slot] = False
        self._hit_pending.pop(slot, None)
        if not bool(self._samp_greedy_h[slot]):
            # reset the lane to greedy so an all-greedy batch's decode
            # skips the sampling branch again (the lane's draws were
            # already inert: a vacated slot's tokens go nowhere)
            self._samp = self._samp_set(self._samp, np.int32(slot),
                                        sampling_row(None))
            self._samp_greedy_h[slot] = True
        if self.paged:
            self.cache = self._release(self.cache, np.int32(slot))
            self.allocator.free_slot(slot)  # decref: shared pages survive
            self._pos_h[slot] = 0
            self._tabled[slot] = 0

    def _fusable(self) -> bool:
        return (self.sync_every > 1
                and not self._unsynced
                and not self._jobs
                and not self.backlog
                and not self.admission.pending
                and all(r.max_new_tokens - len(r.output) >= self.sync_every
                        for r, d in zip(self.active, self.decoding)
                        if r is not None and d))

    def _flush(self, now: float = None):
        """One host sync for the whole deferred window: transfers the
        stacked (T, B) token block and distributes tokens to requests."""
        if not self._unsynced:
            return
        with self._phase("engine.sync"):
            toks = np.asarray(jnp.stack(self._unsynced))
        self._unsynced = []
        with self._phase("engine.deliver"):
            self._distribute(toks, now)

    def _distribute(self, toks: np.ndarray, now: float = None):
        """Hand a (T, B) host token block to the per-slot requests."""
        self.metrics.host_syncs += 1
        t_now = time.time() if now is None else now
        for i, r in enumerate(self.active):
            if r is None or not self.decoding[i]:
                continue
            tr = r.trace
            n0 = len(r.output) if tr is not None else 0
            done = False
            for t in range(toks.shape[0]):
                if r.done:
                    break
                tok = int(toks[t, i])
                r.output.append(tok)
                if r.done or tok == self.eos_id:
                    done = True
                    break
            if tr is not None and len(r.output) > n0:
                # one span per fused window whose host sync delivered
                # tokens to this slot; t0 floors at the trace's latest
                # span so a freshly (re)activated request's window never
                # pre-dates its decode span (prefill_done keeps the FIRST
                # activation time across preempt/restore). Appended BEFORE
                # finalization so the terminal collect() sees it.
                t0 = max(self._win_t0, r.prefill_done)
                if tr.spans:
                    t0 = max(t0, tr.spans[-1].t0)
                tr.add("decode_window", min(t0, t_now), t_now,
                       tokens=len(r.output) - n0)
            if done:
                self._finalize_request(r, i, t_now)
        self._win_t0 = t_now

    def _take_finished(self) -> List[Request]:
        out, self._finished = self._finished, []
        return out

    def drain(self, now: float):
        """Flush any deferred tokens (end-of-run bookkeeping)."""
        self._flush(now)
        return self._take_finished()

    def reset(self):
        """Return the engine to an empty state — every slot vacated (pages
        reclaimed), queues and metrics cleared — while keeping its compiled
        steps warm, so bench/test rounds reuse one engine without paying
        recompiles. In-flight requests are abandoned, not finished."""
        self.drain(0.0)
        for i in range(self.slots):
            if self.active[i] is not None:
                self.release_slot(i)
        self._jobs.clear()
        self._hit_pending.clear()
        if self.prefix_index is not None:
            self.prefix_index.clear()  # cached pages back to the pool
        self.backlog.clear()
        self.admission.flush()
        self._unsynced = []
        self._finished = []
        self.metrics = ServeMetrics()
        # fresh span rollups + wall accounting; compile_events persist —
        # they mirror the jit caches, which reset() deliberately keeps warm
        self.tracer = Tracer(enabled=self._trace_on,
                             ring=self.config.trace_ring)
        self._tick_wall = latency_histogram()
        self._win_t0 = 0.0

    # -- prefix cache ------------------------------------------------------
    def prefix_match_len(self, tokens) -> int:
        """Cached-prefix length a prompt would hit HERE (0 when the index
        is off) — the cluster frontend's affinity probe. Read-only: no
        LRU touch, no counters."""
        if self.prefix_index is None:
            return 0
        return self.prefix_index.match_len(tokens)

    def clear_prefix_cache(self) -> int:
        """Drop every cached prefix (pages with no live alias return to
        the pool immediately). Returns pages freed."""
        if self.prefix_index is None:
            return 0
        return self.prefix_index.clear()

    # -- telemetry ---------------------------------------------------------
    def load_report(self) -> LoadReport:
        """Snapshot the engine's load for cluster routing: free slots and
        pages, queued prefill tokens, unfinished decode budgets (scalar
        and per-slot/per-queued for the frontend's slot-availability
        simulation), and the cost model's predicted seconds to drain it
        all. Pure host-side arithmetic — safe to call every dispatch
        without a device sync."""
        queued = list(self.backlog) + list(self.admission.pending)
        if self.edf_backlog:
            queued.sort(key=lambda r: r.ttft_deadline)
        chunks_left = {j.slot: -(-(j.tokens.shape[1] - j.next_off)
                                 // max(1, self.chunk))
                       for j in self._jobs}
        remaining = []
        for i, r in enumerate(self.active):
            if r is None:
                continue
            rem = max(0, r.max_new_tokens - len(r.output))
            remaining.append(rem + chunks_left.get(i, 0))
        q_pref = sum(r.prompt_len for r in queued)
        q_pref += sum(max(0, j.tokens.shape[1] - j.next_off)
                      for j in self._jobs)
        dec_rem = sum(remaining) + sum(r.max_new_tokens for r in queued)
        pre_s = (estimate_prefill(self.cfg, 1, q_pref,
                                  n_chips=self.n_chips,
                                  mesh_axes=self._mesh_axes).latency_s
                 if q_pref > 0 else 0.0)
        # backlog_s = prefill term (computed once, above) + decode term
        dec_s = estimate_backlog_s(
            self.cfg, queued_prefill_tokens=0,
            decode_tokens_remaining=dec_rem, slots=self.slots,
            context=self.window, n_chips=self.n_chips,
            mesh_axes=self._mesh_axes)
        idx = self.prefix_index
        tick = self._tick_est_s
        axis_cs = tuple(sorted(self._axis_collective_s.items()))
        return LoadReport(
            slots=self.slots,
            free_slots=sum(r is None for r in self.active),
            queued_requests=len(queued),
            queued_prefill_tokens=q_pref,
            decode_tokens_remaining=dec_rem,
            free_pages=self.allocator.free_pages if self.paged else -1,
            total_pages=self.allocator.capacity if self.paged else 0,
            backlog_s=pre_s + dec_s,
            tick_est_s=self._tick_est_s,
            queued_prefill_s=pre_s,
            active_remaining=tuple(remaining),
            queued_budgets=tuple(r.max_new_tokens for r in queued),
            prefix_cached_pages=idx.cached_pages if idx else 0,
            prefix_cached_tokens=idx.cached_tokens if idx else 0,
            prefix_hits=self.metrics.prefix_hits,
            prefix_hit_tokens=self.metrics.prefix_hit_tokens,
            rejected=self.metrics.rejected,
            cancelled=self.metrics.cancelled,
            timed_out=self.metrics.timed_out,
            shed=self.metrics.shed,
            failed=self.metrics.failed,
            preempted=self.metrics.preempted,
            mesh_axes=self.topology.mesh_axes,
            axis_collective_s=axis_cs,
            axis_util=tuple((a, s / tick if tick > 0 else 0.0)
                            for a, s in axis_cs),
            moe_capacity_policy=self.moe_capacity_policy,
            moe_drop_free_group=self._moe_gmax,
            histograms=self.metrics.histogram_wire(),
            span_totals=self.tracer.totals_wire(),
            compile_events=tuple(sorted(self.compile_events.items())),
            browned_out=self.metrics.browned_out,
            tenant_stats=self.metrics.tenant_wire(),
            kv_bytes_per_token=kv_bytes_per_token(self.cfg, self.kv_dtype),
            kv_cache_dtype=self.kv_dtype,
            weight_dtype=self.config.precision.weight_dtype)

    @property
    def mesh_axes(self):
        """((name, size), ...) of a sharded replica's mesh, None on 1-chip
        engines — the cost-model key for collective-aware estimates."""
        return self._mesh_axes

    @property
    def idle(self) -> bool:
        """No active, prefilling, or queued work (drain-complete test)."""
        return (self.n_active == 0 and not self._jobs and not self.backlog
                and not self.admission.pending and not self._unsynced)

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.active)

    @property
    def n_decoding(self) -> int:
        return sum(self.decoding)

    @property
    def n_prefilling(self) -> int:
        return len(self._jobs)


def _padded_len(n: int, chunk: int) -> int:
    return ((n + chunk - 1) // chunk) * chunk


def _batch_len(batch) -> int:
    """Padded sequence length of a prefill batch (tokens or audio frames)
    — the shape component of its trace-cache key."""
    b = batch.get("tokens")
    if b is None:
        b = next(iter(batch.values()))
    return int(b.shape[1])


def generate(cfg, params, prompt: np.ndarray, max_new_tokens: int,
             *, window: int = 512,
             sampling: Optional[SamplingParams] = None) -> List[int]:
    """Simple single-request generation helper (examples/quickstart)."""
    eng = ServingEngine(cfg, params, EngineConfig(slots=1, window=window))
    req = Request(rid=0, prompt=prompt, max_new_tokens=max_new_tokens,
                  sampling=sampling or SamplingParams())
    assert eng.try_admit(req, now=0.0)
    t = 0.0
    while not req.done:
        t += 1.0
        eng.step(t)
    return req.output
