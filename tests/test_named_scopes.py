"""The model scopes and engine phases are metadata: every served program
lowers to the same text with and without ``jax.named_scope``, the scopes
are there to read in its ``op_name`` locations, the engine marks its
phases on the profiler's clock only when tracing is on, and
``fused_ticks`` counts the ticks of the fused scan and nothing else."""
import contextlib
import dataclasses
import glob
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import (
    EngineConfig,
    PrecisionConfig,
    Request,
    SamplingParams,
    ServeMetrics,
    ServingEngine,
)
from repro.serving.engine import init_cache, sampling_row

SCOPES = ("attn_qkv", "attn_kv_write", "attn_core", "attn_out", "mlp",
          "lm_head", "sampler")
MODEL = tuple(s for s in SCOPES if s != "sampler")

#: engine variants: (arch, paged, kv_cache_dtype)
VARIANTS = {
    "granite-paged": ("granite-8b", True, ""),
    "granite-paged-int8": ("granite-8b", True, "int8"),
    "granite-rolling": ("granite-8b", False, ""),
    "chatglm-paged": ("chatglm3-6b", True, ""),
}
#: program -> the scopes its text has to carry
PROGRAMS = {
    "decode": SCOPES,
    "scan": SCOPES,
    "chunk": MODEL,
    "prefill": MODEL,
    "first_token": ("sampler",),
}


@pytest.fixture(scope="module")
def weights():
    out = {}
    for arch in ("granite-8b", "chatglm3-6b"):
        cfg = dataclasses.replace(get_config(arch).reduced(),
                                  dtype="bfloat16")
        out[arch] = cfg, init_params(cfg, jax.random.key(0))
    return out


def _lower(weights, variant: str, program: str) -> str:
    """One served program of a fresh engine, lowered."""
    arch, paged, kv = VARIANTS[variant]
    cfg, params = weights[arch]
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=4, window=128, max_seq=128, page_size=16, chunk_prefill=32,
        paged=paged, sync_every=4,
        precision=PrecisionConfig(kv_cache_dtype=kv)))
    tokens = {"tokens": jnp.zeros((1, 32), jnp.int32)}
    if program == "decode":
        lo = eng._decode.lower(eng.params, eng.cache, eng._tokens, eng._samp)
    elif program == "scan":
        lo = eng._decode_scan.lower(eng.params, eng.cache, eng._tokens,
                                    eng._samp)
    elif program == "chunk":
        buf = eng.max_seq if paged else eng.window
        lo = eng._prefill_chunk.lower(
            eng.params, init_cache(cfg, 1, buf, eng.kv_dtype),
            tokens["tokens"], np.int32(20))
    elif program == "prefill":
        step = eng._prefill_paged if paged else eng._prefill_bucketed
        lo = step.lower(eng.params, tokens, np.int32(20))
    else:
        samp1 = {k: jnp.asarray(v)[None]
                 for k, v in sampling_row(SamplingParams(seed=1)).items()}
        lo = eng._sample_first.lower(
            jnp.zeros((1, cfg.vocab_size), jnp.float32), samp1,
            np.full((1,), 20, np.int32))
    return lo


@pytest.mark.parametrize("program", sorted(PROGRAMS))
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_served_programs_lower_the_same_without_scopes(weights, variant,
                                                       program, monkeypatch):
    scoped = _lower(weights, variant, program)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = _lower(weights, variant, program)
    assert scoped.as_text(debug_info=False) == bare.as_text(debug_info=False)
    with_locs, without = (_scopes_in(scoped.as_text(debug_info=True)),
                          _scopes_in(bare.as_text(debug_info=True)))
    want = set(PROGRAMS[program])
    arch, paged, kv = VARIANTS[variant]
    if program == "prefill" and paged and not kv:
        # the paged prefill's cache is exactly the prompt: it is returned
        # as computed, and pages_insert writes the pool
        want.discard("attn_kv_write")
    assert with_locs == want and not without


def _scopes_in(text: str) -> set:
    """The model scopes named in a lowered program's locations."""
    return {s for s in SCOPES if re.search(rf'loc\("([^"]*/)?{s}/', text)}


def _requests(n, *, max_new=10, sampled=()):
    return [Request(rid=i, prompt=(np.arange(9 + 7 * i) % 400).astype(
        np.int32), max_new_tokens=max_new,
        sampling=SamplingParams(temperature=0.7, top_k=20, top_p=0.9,
                                seed=i) if i in sampled else None)
        for i in range(n)]


def _serve(eng, reqs):
    for r in reqs:
        eng.submit(r, 0.0)
    t = 0.0
    while not eng.idle:
        t += 1.0
        eng.step(t)
    eng.drain(t)


@pytest.mark.parametrize("sync_every", [1, 4])
def test_fused_ticks_count_the_scan_ticks_and_nothing_else(weights,
                                                           sync_every):
    cfg, params = weights["granite-8b"]
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=2, max_seq=128, sync_every=sync_every))
    calls = Counter()
    for attr in ("_decode", "_decode_scan"):
        fn = getattr(eng, attr)

        def counted(*a, _fn=fn, _attr=attr):
            calls[_attr] += 1
            return _fn(*a)

        setattr(eng, attr, counted)
    _serve(eng, _requests(3, max_new=13, sampled=(1,)))
    m = eng.metrics
    assert m.fused_ticks == sync_every * calls["_decode_scan"]
    assert m.decode_ticks - m.fused_ticks == calls["_decode"]
    assert (m.fused_ticks > 0) == (sync_every > 1)
    reg = eng.metrics_registry()
    assert reg.get("serving_fused_ticks_total").value == m.fused_ticks
    total = ServeMetrics()
    total.merge(m)
    total.merge(m)
    assert total.fused_ticks == 2 * m.fused_ticks


PHASES = ("engine.reap", "engine.admit", "engine.prefill_chunks",
          "engine.pages", "engine.dispatch", "engine.sync",
          "engine.deliver")


def _host_events(weights, tracing: bool, tmp_path):
    from jax.profiler import ProfileData

    cfg, params = weights["granite-8b"]
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=2, max_seq=128, sync_every=4, tracing=tracing))
    _serve(eng, _requests(2, max_new=6))  # compile outside the profile
    eng.reset()
    jax.profiler.start_trace(str(tmp_path))
    _serve(eng, _requests(3, max_new=9))
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:CPU") for line in p.lines
            for e in line.events if e.name.startswith("engine.")]


def test_engine_phases_on_the_profiler_clock(weights, tmp_path):
    events = _host_events(weights, True, tmp_path)
    steps = [e for e in events if e[0] == "engine.step"]
    assert steps and set(PHASES) <= {e[0] for e in events}
    for name, a, b in events:  # every phase inside one step
        if name != "engine.step":
            assert any(s <= a and b <= t for _, s, t in steps), name


def test_no_engine_annotation_with_tracing_off(weights, tmp_path):
    assert _host_events(weights, False, tmp_path) == []
