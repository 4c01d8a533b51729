#!/usr/bin/env python3
"""Serve granite-8b at its published widths on a TPU v5e and check the result.

    python chip_smoke.py                # one chip: the served path, twice
    python chip_smoke.py --four-chips   # a tp=4 replica against one chip

The model is granite-8b (``repro/configs/granite_8b.py``) in bfloat16 at
its published widths, with the depth cut to 16 of its 36 layers so that
the weights (~7.8 GB) and the paged KV pool fit one chip's 16 GB. Weights
are random, drawn from a fixed seed. Requests go through the path a user
calls: ``EngineConfig`` -> ``ServingEngine.submit`` / ``step`` /
``drain``.

One chip: eight requests (prompts of 128-1024 tokens, 32 new tokens each,
two of them seeded-sampled) are served twice. The first wave compiles;
the second must compile nothing and reproduce every stream. One greedy
stream is re-scored by the plain forward pass (no cache).

``--four-chips`` runs only the tensor-parallel phase: the same requests on
a one-chip engine, then on one replica spanning four chips
(``DeviceTopology(tp=4)``), and compares the streams.

The last line of stdout is one JSON object, printed only when every check
passed. Without a TPU the script exits non-zero before any result: there
is no CPU fallback.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "granite-8b"
LAYERS = 16  # of 36: weights + KV must fit one chip's 16 GB
SEED = 0
SLOTS, MAX_SEQ, PAGE_SIZE = 8, 2048, 16
# prompts <= 256 tokens take the bucketed prefill, longer ones the chunked
# prefill (256-token chunks interleaved with decode)
CHUNK = 256
PROMPT_LENS = (128, 200, 256, 384, 512, 700, 900, 1024)
MAX_NEW = 32
SAMPLED_RIDS = (2, 6)  # one bucketed, one chunked
RESCORE_RID = 7  # greedy, chunked, the longest stream
# Re-score tolerance, in standard deviations of the forward pass's logits
# at that position. The engine (paged KV, chunked prefill, one-token
# decode) and the forward pass (dense attention over the whole sequence)
# compute the same function in bfloat16 with different op orders and
# fusions. bfloat16 keeps 8 significant bits, so each rounded op moves a
# value by up to 2**-9 of itself; over 16 layers of ~10 rounded ops the
# two residual streams drift apart by about sqrt(160) * 2**-9 ~ 2.5% as a
# random walk, and each logit by that share of the logit scale. The
# engine's token can then trail the forward pass's best logit by at most
# twice that drift (a near-tie resolved the other way): ~5% of the scale.
# 0.15 allows three times that.
RESCORE_TOL_STD = 0.15


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def smoke_config():
    from repro.configs import get_config

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, num_layers=LAYERS)
    print(f"config: {ARCH} at published widths, depth cut "
          f"{full.num_layers} -> {cfg.num_layers} layers: "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype} "
          f"params={cfg.param_count() / 1e9:.3f}B", flush=True)
    return cfg


def init_weights(cfg):
    """Random weights from SEED, built by one jitted program so no f32
    temporary of a stacked leaf is materialised on the device."""
    import jax

    from repro.models import init_params

    t0 = time.perf_counter()
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(SEED))
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"weights: {n_bytes / 1e9:.3f} GB initialised in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    return params


def build_engine(cfg, params, topology=None):
    from repro.serving import DeviceTopology, EngineConfig, ServingEngine

    return ServingEngine(cfg, params, EngineConfig(
        slots=SLOTS, max_seq=MAX_SEQ, page_size=PAGE_SIZE, paged=True,
        chunk_prefill=CHUNK, topology=topology or DeviceTopology()))


def make_prompts(vocab: int):
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in PROMPT_LENS]


def serve_wave(engine, prompts):
    """Submit one request per prompt at t=0, step until every one is back,
    and return (requests, wall seconds). A rejected or failed request
    comes back from ``step`` like a finished one, so the caller checks
    each state."""
    from repro.serving import Request, SamplingParams

    reqs = [Request(rid=i, prompt=p, max_new_tokens=MAX_NEW,
                    sampling=(SamplingParams(temperature=0.8, top_k=50,
                                             top_p=0.95, seed=1000 + i)
                              if i in SAMPLED_RIDS else SamplingParams()))
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    back = []
    for r in reqs:
        engine.submit(r, 0.0)
    for _ in range(100 * MAX_NEW * len(reqs)):
        if len(back) == len(reqs):
            break
        back += engine.step(time.perf_counter() - t0)
    back += engine.drain(time.perf_counter() - t0)
    wall = time.perf_counter() - t0
    if len(back) != len(reqs):
        fail(f"{len(reqs) - len(back)} requests never came back")
    return reqs, wall


def check_finished(reqs, label: str):
    from repro.serving import RequestState

    for r in reqs:
        if r.state is not RequestState.FINISHED or len(r.output) != MAX_NEW:
            fail(f"{label}: request {r.rid} ended {r.state.value} with "
                 f"{len(r.output)}/{MAX_NEW} tokens: {r.fail_reason}")
    print(f"{label}: all {len(reqs)} requests FINISHED with {MAX_NEW} "
          f"tokens each (prompts {min(PROMPT_LENS)}-{max(PROMPT_LENS)}, "
          f"sampled rids {list(SAMPLED_RIDS)})", flush=True)


def rescore(cfg, params, req, label: str):
    """Re-score ``req``'s stream with the plain forward pass over prompt +
    generated tokens; fail unless, at each generated position, the
    engine's token is within RESCORE_TOL_STD logit standard deviations of
    the forward pass's best logit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import forward

    start = req.prompt_len - 1
    n = len(req.output)
    seq = np.concatenate([req.prompt, np.asarray(req.output[:-1], np.int32)])

    @jax.jit
    def gaps(params, tokens, toks):
        logits, _, _ = forward(cfg, params, {"tokens": tokens}, mode="prefill")
        lg = jax.lax.dynamic_slice_in_dim(logits[0], start, n, axis=0)
        mine = jnp.take_along_axis(lg, toks[:, None], axis=-1)[:, 0]
        return lg.max(axis=-1) - mine, lg.std(axis=-1)

    t0 = time.perf_counter()
    gap, std = (np.asarray(x) for x in gaps(
        params, jnp.asarray(seq[None]), jnp.asarray(req.output, np.int32)))
    rel = gap / std
    worst = float(rel.max())
    print(f"{label}: re-score of rid {req.rid} ({req.prompt_len}+{n} "
          f"tokens) by the plain forward pass in "
          f"{time.perf_counter() - t0:.1f}s: engine token = forward argmax "
          f"at {int((gap == 0).sum())}/{n} positions, worst gap "
          f"{float(gap.max()):.4f} = {worst:.4f} logit std (tolerance "
          f"{RESCORE_TOL_STD})", flush=True)
    if not np.isfinite(rel).all() or worst > RESCORE_TOL_STD:
        fail(f"{label}: re-score gap {worst:.4f} std exceeds "
             f"{RESCORE_TOL_STD}")


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


def one_chip(cfg):
    import jax

    t0 = time.perf_counter()
    params = init_weights(cfg)
    engine = build_engine(cfg, params)
    prompts = make_prompts(cfg.vocab_size)
    first, wall1 = serve_wave(engine, prompts)
    setup = time.perf_counter() - t0
    check_finished(first, "wave 1")
    compiled = dict(engine.compile_events)
    print(f"set-up: {setup:.1f}s (weights, engine, first wave with its "
          f"compiles; first wave {wall1:.1f}s), "
          f"{sum(compiled.values())} traces over {len(compiled)} programs",
          flush=True)
    second, wall2 = serve_wave(engine, prompts)
    check_finished(second, "wave 2")
    if engine.compile_events != compiled:
        grew = {k: v for k, v in engine.compile_events.items()
                if compiled.get(k) != v}
        fail(f"second wave compiled again: {grew}")
    same = [a.output == b.output for a, b in zip(first, second)]
    print(f"wave 2: {wall2:.2f}s wall for {len(second) * MAX_NEW} tokens, "
          f"no new compiles, {sum(same)}/{len(same)} streams identical to "
          f"wave 1", flush=True)
    if not all(same):
        fail(f"wave 2 streams differ from wave 1 for rids "
             f"{[r.rid for r, s in zip(second, same) if not s]}")
    rescore(cfg, params, second[RESCORE_RID], "one chip")
    print(f"peak_bytes_in_use: {peak_bytes(jax.devices()[0])}", flush=True)


def four_chips(cfg):
    import jax

    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--four-chips needs 4 devices, jax reports {len(devs)}")
    from repro.serving import DeviceTopology

    prompts = make_prompts(cfg.vocab_size)
    params = init_weights(cfg)
    engine = build_engine(cfg, params)
    ref, wall1 = serve_wave(engine, prompts)
    check_finished(ref, "1 chip")
    ref_streams = [list(r.output) for r in ref]
    print(f"1 chip: {wall1:.1f}s wall (compiles included)", flush=True)
    del engine, ref
    gc.collect()

    t0 = time.perf_counter()
    engine = build_engine(cfg, params, DeviceTopology(tp=4))
    del params
    gc.collect()
    spans = {len(x.sharding.device_set)
             for x in jax.tree.leaves((engine.params, engine.cache))}
    if spans != {4}:
        fail(f"tp=4 params/KV span device sets of sizes {sorted(spans)}")
    pool = jax.tree.leaves(engine.cache["body"])[0]
    print(f"tp=4: params and KV pools span 4 distinct devices "
          f"{sorted(d.id for d in pool.sharding.device_set)}; KV pool shard "
          f"{pool.sharding.shard_shape(pool.shape)} of {pool.shape}",
          flush=True)
    got, wall4 = serve_wave(engine, prompts)
    check_finished(got, "tp=4")
    print(f"tp=4: {time.perf_counter() - t0:.1f}s from engine build, "
          f"{wall4:.1f}s wave wall (compiles included)", flush=True)
    for r, want in zip(got, ref_streams):
        first_diff = next((i for i, (a, b) in enumerate(zip(r.output, want))
                           if a != b), None)
        kind = "sampled" if r.rid in SAMPLED_RIDS else "greedy"
        print(f"  rid {r.rid} ({kind}, prompt {r.prompt_len}): "
              + ("bit-identical to 1 chip" if first_diff is None else
                 f"differs from 1 chip first at token {first_diff}"),
              flush=True)
    n_same = sum(r.output == w for r, w in zip(got, ref_streams))
    print(f"tp=4 vs 1 chip: {n_same}/{len(got)} streams bit-identical",
          flush=True)
    rescore(cfg, engine.params, got[RESCORE_RID], "tp=4")
    print("peak_bytes_in_use per device: "
          + ", ".join(f"{d.id}={peak_bytes(d)}" for d in devs[:4]), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 replica phase against a "
                         "one-chip engine (needs four chips)")
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU chip present (jax platform "
                         f"{devs[0].platform!r}); there is no CPU fallback")
    from repro.core.hardware import TPU_V5E
    from repro.launch.runtime import device_report, use_compile_cache

    print(device_report(use_compile_cache()), flush=True)
    if devs[0].device_kind not in TPU_V5E.device_kinds:
        fail(f"device_kind {devs[0].device_kind!r} is not the v5e the cost "
             f"model steers by ({TPU_V5E.name}: {TPU_V5E.device_kinds})")

    cfg = smoke_config()
    (four_chips if args.four_chips else one_chip)(cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
