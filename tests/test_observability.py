"""Observability layer: histograms/registry, span tracing across engine
and cluster paths (admission, preemption, failover), compile/profile
hooks, and the zero-behavior-change guarantees (bit-identical streams,
mean-preserving router correction)."""
import json
import time

import jax
import numpy as np
import pytest
from conftest import make_engine

from repro.configs import get_config
from repro.models import init_params
from repro.serving import (
    ClusterFrontend,
    Counter,
    EngineConfig,
    FaultyEngine,
    Histogram,
    MetricsRegistry,
    Request,
    RequestState,
    SamplingParams,
    ServeMetrics,
    ServingEngine,
    Trace,
    chrome_trace,
    latency_histogram,
    request_traces,
    validate_chrome_trace,
)


@pytest.fixture(scope="module")
def granite():
    cfg = get_config("granite-8b").reduced()
    return cfg, init_params(cfg, jax.random.key(0))


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 500, n).astype(np.int32)


def _samp(seed):
    return SamplingParams(temperature=0.7, top_k=20, top_p=0.95, seed=seed)


def _run(eng, reqs, *, max_steps=500):
    resolved, t = {}, 0.0
    for r in reqs:
        eng.submit(r, t)
    while len(resolved) < len(reqs) and max_steps:
        t += 1.0
        for r in eng.step(t):
            resolved[r.rid] = r
        max_steps -= 1
    for r in eng.drain(t):
        resolved[r.rid] = r
    return resolved, t


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------


def test_histogram_percentiles_within_one_bucket():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-3.0, sigma=2.0, size=500)
    h = latency_histogram()
    h.extend(vals)
    assert h.count == 500 and len(h) == 500
    assert h.mean == pytest.approx(float(np.mean(vals)))  # exact sum
    for q in (0, 10, 50, 90, 99, 100):
        want = float(np.percentile(vals, q))
        got = h.percentile(q)
        assert abs(h.bucket_index(got) - h.bucket_index(want)) <= 1, q
    assert h.percentile(0) == float(np.min(vals))
    assert h.percentile(100) == float(np.max(vals))


def test_histogram_merge_is_exact_and_checks_bounds():
    a, b = latency_histogram(), latency_histogram()
    va = [0.001, 0.5, 3.0]
    vb = [0.02, 7.0]
    a.extend(va)
    b.extend(vb)
    pooled = latency_histogram()
    pooled.extend(va + vb)
    merged = a.copy().merge(b)
    # counts/extremes are exactly the pooled histogram's; the sum matches
    # to addition-order float tolerance
    assert merged.counts == pooled.counts
    assert merged.count == pooled.count
    assert (merged.vmin, merged.vmax) == (pooled.vmin, pooled.vmax)
    assert merged.sum == pytest.approx(pooled.sum, rel=1e-15)
    with pytest.raises(ValueError, match="different bounds"):
        a.merge(Histogram([1.0, 2.0]))


def test_histogram_wire_round_trip_and_empty_json_safety():
    h = latency_histogram()
    h.extend([0.004, 0.004, 12.0])
    rt = Histogram.from_wire(
        json.loads(json.dumps(list(h.to_wire()), default=list)))
    assert rt == h and rt.preset == "latency_s"
    # empty histograms must not leak inf into JSON
    wire = latency_histogram().to_wire()
    assert wire[4] == 0.0 and wire[5] == 0.0
    assert "Infinity" not in json.dumps(list(wire), default=list)
    assert Histogram.from_wire(wire).count == 0


def test_histogram_list_compat_shims():
    """ServeMetrics call sites kept their list idioms: .append and
    truthiness."""
    h = latency_histogram()
    assert not h
    h.append(0.25)
    assert h and len(h) == 1


def test_counter_and_registry_exposition():
    reg = MetricsRegistry()
    reg.counter("requests_total", help="all requests").inc(3)
    reg.gauge("qps").set(1.5)
    h = reg.histogram("lat_seconds")
    h.observe(0.02)
    with pytest.raises(ValueError, match="only go up"):
        reg.get("requests_total").inc(-1)
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("requests_total")
    text = reg.exposition()
    assert "# HELP requests_total all requests" in text
    assert "# TYPE requests_total counter" in text
    assert "requests_total 3" in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_count 1" in text
    snap = reg.snapshot()
    assert snap["requests_total"] == 3
    assert snap["lat_seconds"]["count"] == 1
    json.dumps(snap)  # JSON-safe throughout
    assert isinstance(Counter(), Counter)


def test_serve_metrics_is_bounded_and_merges_exactly():
    a, b = ServeMetrics(), ServeMetrics()
    for i in range(100):
        a.latencies.append(0.01 * (i + 1))
        b.latencies.append(0.02 * (i + 1))
        a.ttfts.append(0.001)
        b.tpots.append(0.005)
    pooled = ServeMetrics()
    pooled.merge(a)
    pooled.merge(b)
    assert pooled.latencies.count == 200
    want = float(np.percentile([0.01 * (i + 1) for i in range(100)]
                               + [0.02 * (i + 1) for i in range(100)], 99))
    got = pooled.p(99)
    assert abs(pooled.latencies.bucket_index(got)
               - pooled.latencies.bucket_index(want)) <= 1
    assert pooled.ttft_p(50) == 0.001
    assert pooled.tpot_p(50) == 0.005
    # memory is O(buckets): the histogram never stores samples
    assert len(pooled.latencies.counts) == len(pooled.latencies.bounds) + 1


# ---------------------------------------------------------------------------
# util.timeit samples
# ---------------------------------------------------------------------------


def test_timeit_returns_mean_with_samples():
    from repro.util import timeit

    t = timeit(lambda: time.sleep(0.001), iters=5, warmup=1)
    assert isinstance(t, float)
    assert len(t.samples) == 5
    assert float(t) == pytest.approx(sum(t.samples) / 5)
    assert min(t.samples) <= t.median <= max(t.samples)
    assert t * 1e6 > 0  # the microbench idiom still works


# ---------------------------------------------------------------------------
# Trace primitives
# ---------------------------------------------------------------------------


def test_trace_lifecycle_and_validation():
    t = Trace(rid=7)
    t.begin("queued", 1.0)
    t.end("queued", 2.0)
    t.begin("decode", 2.0, slot=0)
    assert t.is_open("decode")
    assert t.validate() != []  # open span on a terminal trace
    t.end("decode", 5.0, tokens=3)
    assert t.validate() == []
    assert t.totals()["decode"] == (1, 3.0)
    # lenient end: no open span of that kind is a no-op, not an error
    assert t.end("prefill", 6.0) is None
    bad = Trace(rid=8)
    bad.add("a", 3.0, 2.0)
    bad.add("b", 1.0, 1.5)
    probs = bad.validate()
    assert any("negative" in p for p in probs)
    assert any("before" in p for p in probs)


def test_chrome_trace_export_structure():
    t = Trace(rid=4)
    t.add("queued", 0.0, 1.0)
    t.event("dispatch", 1.0, replica="e0")
    doc = chrome_trace([("e0", t)])
    assert validate_chrome_trace(doc) == []
    phases = {ev["ph"] for ev in doc["traceEvents"]}
    assert {"M", "X", "i"} <= phases
    span = next(ev for ev in doc["traceEvents"] if ev["ph"] == "X")
    assert span["ts"] == 0.0 and span["dur"] == 1e6  # seconds -> us
    assert validate_chrome_trace({"traceEvents": []}) != []
    assert validate_chrome_trace(
        {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1}]}) != []


# ---------------------------------------------------------------------------
# engine span integrity
# ---------------------------------------------------------------------------


def test_engine_stamps_full_lifecycle(granite):
    cfg, params = granite
    eng = make_engine(cfg, params, slots=2, window=64, max_seq=128,
                      sync_every=4, tracing=True)
    reqs = [Request(rid=i, prompt=_prompt(8 + i, seed=i), max_new_tokens=4,
                    sampling=_samp(100 + i) if i % 2 else None)
            for i in range(5)]
    resolved, _ = _run(eng, reqs)
    assert len(resolved) == 5
    for r in resolved.values():
        assert r.trace is not None
        assert r.trace.validate() == [], (r.rid, r.trace.validate())
        kinds = set(r.trace.kinds())
        assert {"queued", "prefill", "decode"} <= kinds, (r.rid, kinds)
        if r.sampling is not None:
            assert "sample" in kinds
    # terminal traces folded into the engine rollup
    assert eng.tracer.collected == 5
    assert eng.tracer.span_totals["decode"][0] == 5
    # per-step wall accounting only exists when tracing is on
    assert eng._tick_wall.count > 0


def test_tracing_off_means_no_trace_objects(granite):
    cfg, params = granite
    eng = make_engine(cfg, params, slots=2, window=64, sync_every=4)
    reqs = [Request(rid=i, prompt=_prompt(8), max_new_tokens=3)
            for i in range(3)]
    resolved, _ = _run(eng, reqs)
    assert all(r.trace is None for r in resolved.values())
    assert eng._tick_wall.count == 0
    assert eng.tracer.collected == 0


def test_streams_bit_identical_tracing_on_vs_off(granite):
    cfg, params = granite
    outs = {}
    for tracing in (False, True):
        eng = ServingEngine(cfg, params, EngineConfig(
            slots=2, window=64, max_seq=128, sync_every=4, tracing=tracing))
        reqs = [Request(rid=i, prompt=_prompt(9 + i, seed=i),
                        max_new_tokens=5, sampling=_samp(300 + i))
                for i in range(4)]
        resolved, _ = _run(eng, reqs)
        outs[tracing] = {rid: list(map(int, r.output))
                         for rid, r in resolved.items()}
    assert outs[False] == outs[True]


def test_preempt_restore_spans(granite):
    cfg, params = granite
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=2, window=64, max_seq=128, sync_every=1, prefix_cache=True,
        tracing=True))
    victim = Request(rid=0, prompt=_prompt(12), max_new_tokens=8,
                     sampling=_samp(42))
    eng.submit(victim, 0.0)
    for t in (1.0, 2.0, 3.0):
        eng.step(t)
    assert eng.preempt(0, 3.0) is victim
    assert victim.state is RequestState.PREEMPTED
    eng.submit(victim, 4.0)  # requeue for restore
    t = 4.0
    while not victim.done:
        t += 1.0
        eng.step(t)
    eng.drain(t)
    kinds = victim.trace.kinds()
    assert {"preempt", "restore", "queued", "prefill", "decode"} <= set(kinds)
    assert victim.trace.validate() == []
    # two decode spans: pre-eviction and post-restore
    decodes = [sp for sp in victim.trace.spans if sp.kind == "decode"]
    assert len(decodes) == 2 and all(not sp.open for sp in decodes)


def test_failover_spans_survive_replica_death(granite):
    cfg, params = granite
    proxies = [FaultyEngine(ServingEngine(cfg, params, EngineConfig(
        slots=2, window=64, max_seq=128, sync_every=1, tracing=True)))
        for _ in range(2)]
    fe = ClusterFrontend(proxies, policy="round-robin", seed=0,
                         health_timeout_s=50.0, max_retries=3,
                         retry_backoff_s=1.0, tracing=True)
    reqs = [Request(rid=i, prompt=_prompt(10 + i, seed=i), max_new_tokens=6,
                    sampling=_samp(500 + i)) for i in range(6)]
    resolved, t = {}, 0.0
    for r in reqs:
        fe.submit(r, 0.0)
    while len(resolved) < len(reqs) and t < 200.0:
        t += 1.0
        if t == 2.0:
            proxies[0].inject("kill")
        for r in fe.step(t):
            resolved[r.rid] = r
    assert len(resolved) == len(reqs)
    assert all(r.state is RequestState.FINISHED for r in resolved.values())
    retried = [r for r in resolved.values()
               if "failover_retry" in r.trace.kinds()]
    assert retried, "the dead replica held work; someone must have failed over"
    for r in resolved.values():
        assert r.trace.validate() == [], (r.rid, r.trace.validate())
        assert "dispatch" in r.trace.kinds()
    # the frontend-created traces flow through lanes by serving replica
    lanes = {lane for lane, _t in request_traces(resolved.values())}
    assert lanes and all(lane.startswith("pool/") for lane in lanes)


def test_engine_histograms_agree_with_raw_request_samples(granite):
    """The engine's bounded TTFT/TPOT/JCT histograms over a seeded Poisson
    workload hold one sample per request and give p50/p90/p99 within one
    bucket of the percentiles of the requests' own raw values."""
    cfg, params = granite
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / 0.8, 24))
    reqs = []
    for i in range(24):
        prompt = rng.integers(0, 500, int(rng.integers(8, 33))).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(8, 17)),
                            arrival_time=float(arrivals[i]),
                            sampling=_samp(7000 + i)))
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=2, window=128, max_seq=192, sync_every=4, tracing=True))
    pending, now = list(reqs), 0.0
    while not all(r.done for r in reqs):
        while pending and pending[0].arrival_time <= now:
            eng.submit(pending.pop(0), now)
        eng.step(now)
        now += 1.0
        assert now < 2000
    eng.drain(now)
    raw = {"ttft": [r.ttft for r in reqs if r.ttft >= 0],
           "tpot": [r.tpot for r in reqs if r.tpot > 0],
           "jct": [r.finish_time - r.arrival_time for r in reqs]}
    hists = {"ttft": eng.metrics.ttfts, "tpot": eng.metrics.tpots,
             "jct": eng.metrics.jcts}
    for name, samples in raw.items():
        h = hists[name]
        assert h.count == len(samples) == len(reqs), name
        for q in (50, 90, 99):
            want = float(np.percentile(samples, q))
            got = h.percentile(q)
            assert abs(h.bucket_index(got) - h.bucket_index(want)) <= 1, (
                name, q, got, want)


def test_exported_trace_covers_every_request_through_failover_and_preemption(
        granite):
    """One Chrome-trace document exported from a cluster run that loses a
    replica and from an engine that preempts a request: the document is
    well formed, every request thread in it shows queued, prefill and
    decode, and the failover retry and the preempt/restore pair are in it."""
    cfg, params = granite
    proxies = [FaultyEngine(ServingEngine(cfg, params, EngineConfig(
        slots=2, window=64, max_seq=128, sync_every=1, tracing=True)))
        for _ in range(2)]
    fe = ClusterFrontend(proxies, policy="round-robin", seed=0,
                         health_timeout_s=50.0, max_retries=3, tracing=True)
    failover = [Request(rid=i, prompt=_prompt(10 + i, seed=i),
                        max_new_tokens=6, sampling=_samp(500 + i))
                for i in range(6)]
    for r in failover:
        fe.submit(r, 0.0)
    t = 0.0
    while not all(r.done for r in failover):
        t += 1.0
        if t == 2.0:
            proxies[0].inject("kill")
        fe.step(t)
        assert t < 200
    fe.drain(t)

    eng = ServingEngine(cfg, params, EngineConfig(
        slots=1, window=64, max_seq=64, sync_every=1, chunk_prefill=0,
        prefix_cache=True, preemption=True, tracing=True))
    victim = Request(rid=10, prompt=_prompt(20), max_new_tokens=10,
                     sampling=_samp(77), ttft_slo_s=100.0)
    eng.submit(victim, 0.0)
    for t in (1.0, 2.0, 3.0):
        eng.step(t)
    hot = Request(rid=11, prompt=_prompt(10, seed=9), max_new_tokens=3,
                  priority=1, ttft_slo_s=1.0)
    eng.submit(hot, 3.0)
    t = 3.0
    while not (victim.done and hot.done):
        t += 1.0
        eng.step(t)
        assert t < 200
    eng.drain(t)
    assert victim.preemptions >= 1

    doc = chrome_trace(request_traces(failover, prefix="kill/")
                       + request_traces([victim, hot], prefix="churn/"))
    assert validate_chrome_trace(doc) == []
    threads = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] in ("X", "i"):
            threads.setdefault((ev["pid"], ev["tid"]), set()).add(ev["name"])
    assert len(threads) == len(failover) + 2
    for key, kinds in threads.items():
        assert {"queued", "prefill", "decode"} <= kinds, (key, kinds)
    every = set().union(*threads.values())
    assert {"failover_retry", "preempt", "restore"} <= every


# ---------------------------------------------------------------------------
# compile accounting + profiler hook + registries
# ---------------------------------------------------------------------------


def test_compile_events_flat_across_second_workload(granite):
    cfg, params = granite
    eng = make_engine(cfg, params, slots=2, window=64, max_seq=128,
                      sync_every=4, tracing=True)
    _run(eng, [Request(rid=i, prompt=_prompt(9 + i, seed=i),
                       max_new_tokens=4) for i in range(3)])
    assert eng.compile_events
    assert sum(eng.compile_events.values()) >= eng.decode_traces
    warm = dict(eng.compile_events)
    eng.reset()
    assert eng.compile_events == warm  # reset keeps warm jit caches
    _run(eng, [Request(rid=10 + i, prompt=_prompt(9 + i, seed=i),
                       max_new_tokens=4) for i in range(3)])
    assert eng.compile_events == warm, "second workload must not retrace"
    rep = eng.load_report()
    assert dict((k, v) for k, v in rep.compile_events) == warm


def test_profiler_hook_gated_by_config(granite, monkeypatch, tmp_path):
    cfg, params = granite
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    bare = make_engine(cfg, params, slots=2, window=64)
    assert bare.start_profile() is False  # no profile_dir: disarmed
    eng = make_engine(cfg, params, slots=2, window=64,
                      profile_dir=str(tmp_path))
    assert eng.start_profile() is True
    assert eng.start_profile() is False  # already profiling
    assert eng.stop_profile() is True
    assert eng.stop_profile() is False
    assert calls == [("start", str(tmp_path)), ("stop", None)]


def test_engine_and_cluster_metrics_registries(granite):
    cfg, params = granite
    engines = [ServingEngine(cfg, params, EngineConfig(
        slots=2, window=64, max_seq=128, sync_every=4, tracing=True))
        for _ in range(2)]
    fe = ClusterFrontend(engines, policy="round-robin", seed=0, tracing=True)
    reqs = [Request(rid=i, prompt=_prompt(8 + i, seed=i), max_new_tokens=4)
            for i in range(6)]
    resolved, t = {}, 0.0
    for r in reqs:
        fe.submit(r, 0.0)
    while len(resolved) < len(reqs) and t < 200.0:
        t += 1.0
        for r in fe.step(t):
            resolved[r.rid] = r
    reg = engines[0].metrics_registry()
    assert reg.get("serving_completed_total").value \
        == engines[0].metrics.completed
    assert "serving_prefill_traces_total" in reg
    creg = fe.metrics_registry()
    assert creg.get("cluster_completed_total").value == len(reqs)
    jct = creg.get("cluster_jct_seconds")
    assert jct.count == len(reqs)
    expo = creg.exposition()
    assert "cluster_jct_seconds_bucket" in expo
    snap = creg.snapshot()
    json.dumps(snap)
    assert snap["cluster_completed_total"] == len(reqs)


def test_load_report_histograms_merge_across_replicas(granite):
    """The v3 wire histograms rebuild and merge exactly — the cluster
    percentile path without sample shipping."""
    cfg, params = granite
    merged = latency_histogram()
    total = 0
    for k in range(2):
        eng = make_engine(cfg, params, slots=2, window=64, sync_every=4)
        _run(eng, [Request(rid=10 * k + i, prompt=_prompt(8 + i, seed=i),
                           max_new_tokens=4) for i in range(3)])
        hists = dict(eng.load_report().histograms)
        h = Histogram.from_wire(hists["jct_s"])
        total += h.count
        merged.merge(h)
    assert merged.count == total == 6
    assert merged.percentile(50) > 0


# ---------------------------------------------------------------------------
# interference residual histogram (mean-preserving closed loop)
# ---------------------------------------------------------------------------


def test_interference_correction_equals_running_mean():
    from repro.core.misd.interference import InterferencePredictor

    p = InterferencePredictor()
    rng = np.random.default_rng(3)
    resids = []
    for _ in range(50):
        pred = float(rng.uniform(0.5, 2.0))
        act = float(rng.uniform(0.5, 2.0))
        p.observe(pred, act)
        resids.append(-(act - pred) / pred)
    # bit-equal to the bare accumulator it replaced: same sum, same order
    want = 0.0
    for r in resids:
        want += r
    assert p.correction == want / len(resids)
    assert p._n == 50 and p._resid_sum == want  # compat views
    assert p.residuals.count == 50  # the distribution is now observable
    assert p.residuals.percentile(50) != 0.0 or all(r == 0 for r in resids)
