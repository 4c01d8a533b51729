"""Overload control: SLO tiers, token-bucket admission, weighted-fair
DRR queueing, the degradation ladder, the circuit breaker, per-tenant
metrics/LoadReport v4, and the trace-sampling/ring satellites."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.misd.scheduler import Device, Job
from repro.core.mimd.router import Instance, ServiceRouter
from repro.models import init_params
from repro.serving import (
    BROWNOUT,
    REJECT,
    SHED,
    CircuitBreaker,
    ClusterFrontend,
    EngineConfig,
    LoadReport,
    OverloadDetector,
    RequestRejected,
    RequestState,
    ServeMetrics,
    ServingEngine,
    TenantAdmission,
    TenantClass,
    TenantMetrics,
    TokenBucket,
    WeightedFairQueue,
    request_cost,
)
from repro.serving.metrics import latency_histogram

from conftest import make_request as Request


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 500, n).astype(np.int32)


def _req(rid, tenant="", plen=8, budget=4, arrival=0.0, slo=0.0, seed=0):
    return Request(rid, _prompt(plen, seed=seed or rid), budget,
                   arrival_time=arrival, tenant=tenant, ttft_slo_s=slo)


# -- token bucket / admission ------------------------------------------------


def test_token_bucket_admits_then_meters():
    b = TokenBucket(rate=10.0, capacity=20.0)
    assert b.take(20.0, 0.0) == 0.0  # full burst admitted
    wait = b.take(10.0, 0.0)
    assert wait == pytest.approx(1.0)  # refills at 10 tok/s
    assert b.take(10.0, 0.0 + wait) == 0.0  # honored retry horizon


def test_token_bucket_oversized_request_finite_retry():
    b = TokenBucket(rate=10.0, capacity=20.0)
    wait = b.take(50.0, 0.0)  # larger than the bucket can ever hold
    assert 0 < wait < float("inf")


def test_tenant_admission_typed_rejection():
    adm = TenantAdmission({"t": TenantClass("t", rate_tokens_s=10.0,
                                            burst_tokens=16.0)})
    assert adm.admit(_req(0, "t", plen=8, budget=4), 0.0) is None
    with pytest.raises(RequestRejected) as ei:
        adm.admit(_req(1, "t", plen=8, budget=8), 0.0)
    assert ei.value.retry_after_s > 0
    # unknown / unlimited tenants always pass
    adm.admit(_req(2, "other", plen=100, budget=100), 0.0)


# -- weighted-fair queue -----------------------------------------------------


def test_wfq_single_tenant_is_flat_edf():
    """One (untagged) tenant drains in exactly (ttft_deadline, seq)
    order — the pre-DRR frontend contract."""
    q = WeightedFairQueue(edf=True)
    reqs = [_req(i, arrival=0.0, slo=[5.0, 2.0, 9.0, 2.0][i])
            for i in range(4)]
    for r in reqs:
        q.push(r)
    assert [r.rid for r in q.drain()] == [1, 3, 0, 2]
    assert not q and len(q) == 0


def test_wfq_weights_share_token_throughput():
    """Over a long backlog, a weight-2 tenant pops ~2x the token cost of
    a weight-1 tenant (DRR's defining property)."""
    w = {"a": 2.0, "b": 1.0}
    q = WeightedFairQueue(quantum=16.0, weight_of=lambda t: w[t])
    for i in range(40):
        q.push(_req(100 + i, "a", plen=8, budget=8))
        q.push(_req(200 + i, "b", plen=8, budget=8))
    cost = {"a": 0.0, "b": 0.0}
    for _ in range(30):
        r = q.pop()
        cost[r.tenant] += request_cost(r)
    assert cost["a"] / cost["b"] == pytest.approx(2.0, rel=0.35)


def test_wfq_backlogged_tenant_bounded_wait():
    """A flood from one tenant cannot starve another: the victim's head
    is served within the provable grant bound."""
    q = WeightedFairQueue(quantum=8.0)
    for i in range(50):
        q.push(_req(i, "flood", plen=16, budget=16))
    q.push(_req(99, "victim", plen=16, budget=16))
    bound = q.starvation_bound(request_cost(_req(0, plen=16, budget=16)))
    popped = []
    while True:
        r = q.pop()
        popped.append(r.tenant)
        if r.tenant == "victim":
            break
    assert q.max_wait_rounds <= bound
    # and the victim was NOT last: it interleaved within a few pops
    assert len(popped) <= bound * 2


def test_wfq_drained_tenant_forfeits_deficit():
    q = WeightedFairQueue(quantum=1000.0)
    q.push(_req(0, "a"))
    q.pop()  # a granted a huge quantum, then drained
    q.push(_req(1, "a", plen=8, budget=8))
    q.push(_req(2, "b", plen=8, budget=8))
    # a's old credit is gone: b is served within its own grant round
    assert {q.pop().rid, q.pop().rid} == {1, 2}
    assert q.max_wait_rounds <= q.starvation_bound(16.0)


# -- overload detector -------------------------------------------------------


def _report(backlog_s=0.0, ttfts=()):
    h = latency_histogram()
    for v in ttfts:
        h.observe(v)
    return LoadReport(slots=2, free_slots=0, queued_requests=0,
                      queued_prefill_tokens=0, decode_tokens_remaining=0,
                      free_pages=-1, total_pages=0, backlog_s=backlog_s,
                      tick_est_s=0.01, queued_prefill_s=0.0,
                      histograms=(("ttft_s", h.to_wire()),) if ttfts else ())


def test_detector_escalates_with_hysteresis_and_relaxes():
    det = OverloadDetector(ttft_slo_s=1.0, backlog_high_s=2.0,
                           period_s=1.0, patience=2, relax_patience=2)
    t = 0.0
    det.observe(t, [_report(5.0)])  # arms the eval clock
    for _ in range(3):
        t += 1.0
        det.observe(t, [_report(5.0)])
    assert det.level == SHED  # 2 breaches -> one rung, not three
    for _ in range(2):
        t += 1.0
        det.observe(t, [_report(5.0)])
    assert det.level == BROWNOUT
    while det.level < REJECT:
        t += 1.0
        det.observe(t, [_report(5.0)])
    assert det.level == REJECT  # clamped at max_level
    for _ in range(8):
        t += 1.0
        det.observe(t, [_report(0.1)])
    assert det.level < REJECT  # relax walks back down
    assert det.transitions  # every move recorded
    assert det.retry_after_s() >= det.ttft_slo_s


def test_detector_tail_window_accumulates_until_min_window():
    """Sparse completions must not reset the tail window each period:
    the p99 signal fires once enough samples ACCUMULATE."""
    det = OverloadDetector(ttft_slo_s=1.0, backlog_high_s=1e9,
                           period_s=1.0, patience=1, min_window=4)
    t, ttfts = 0.0, []
    det.observe(t, [_report(0.0)])
    for i in range(3):  # one slow TTFT per period: under min_window
        t += 1.0
        ttfts.append(5.0)
        det.observe(t, [_report(0.0, ttfts)])
        assert det.level == 0
    t += 1.0
    ttfts.append(5.0)  # 4th sample: window evaluates, p99 breaches
    det.observe(t, [_report(0.0, ttfts)])
    assert det.level == SHED


def test_detector_counts_frontend_backlog():
    det = OverloadDetector(ttft_slo_s=1.0, backlog_high_s=2.0,
                           period_s=1.0, patience=1)
    det.observe(0.0, [_report(0.1)])
    det.observe(1.0, [_report(0.1)], frontend_backlog_s=10.0)
    assert det.level == SHED  # the paced-dispatch burst waits upstream


def test_histogram_delta_exact_window():
    a = latency_histogram()
    for v in (0.1, 0.2, 0.5):
        a.observe(v)
    b = a.copy()
    for v in (3.0, 4.0):
        b.observe(v)
    win = b.delta(a)
    assert win.count == 2
    assert win.sum == pytest.approx(7.0)
    assert win.percentile(99) >= 2.0  # only the new tail in the window


# -- circuit breaker ---------------------------------------------------------


def test_breaker_open_halfopen_closed_cycle():
    br = CircuitBreaker(cooldown_s=1.0, probe_limit=1, close_after=2)
    assert br.allow("r", 0.0)  # unknown replicas are healthy
    br.trip("r", 0.0)
    assert not br.allow("r", 0.5)  # OPEN during cooldown
    assert br.allow("r", 1.5)  # HALF_OPEN after cooldown
    br.note_dispatch("r", 1.5)
    assert not br.allow("r", 1.6)  # probe limit reached
    br.note_success("r", 2.0)
    assert br.allow("r", 2.1)
    br.note_dispatch("r", 2.1)
    br.note_success("r", 2.5)  # close_after successes
    assert br.state("r", 2.6) == "closed"
    br.note_failure("r", 3.0)  # failure re-trips
    assert not br.allow("r", 3.1)


# -- per-tenant metrics / LoadReport v4 --------------------------------------


def test_tenant_metrics_merge_and_wire_roundtrip():
    a, b = TenantMetrics(), TenantMetrics()
    a.admitted, a.completed, a.total_tokens = 3, 2, 50
    a.ttfts.observe(0.5)
    b.admitted, b.shed, b.browned_out = 2, 1, 1
    b.ttfts.observe(1.5)
    merged = TenantMetrics().merge(a).merge(b)
    assert (merged.admitted, merged.completed, merged.shed) == (5, 2, 1)
    assert merged.ttfts.count == 2
    rt = TenantMetrics.from_wire(merged.to_wire())
    assert rt.to_wire() == merged.to_wire()


def test_serve_metrics_merge_folds_tenants():
    m1, m2 = ServeMetrics(), ServeMetrics()
    m1.tenant("gold").admitted = 2
    m2.tenant("gold").admitted = 3
    m2.tenant("bulk").shed = 4
    m1.merge(m2)
    assert m1.tenant("gold").admitted == 5
    assert m1.tenant("bulk").shed == 4
    reg = m1.registry()
    text = reg.exposition()
    assert 'tenant_admitted_total{tenant="gold"} 5' in text


def test_load_report_v4_roundtrip_and_version_guard():
    m = ServeMetrics()
    tm = m.tenant("gold")
    tm.admitted = 2
    tm.ttfts.observe(0.25)
    rep = LoadReport(slots=2, free_slots=2, queued_requests=0,
                     queued_prefill_tokens=0, decode_tokens_remaining=0,
                     free_pages=-1, total_pages=0, backlog_s=0.0,
                     tick_est_s=0.0, queued_prefill_s=0.0,
                     browned_out=3, tenant_stats=m.tenant_wire())
    rt = LoadReport.from_dict(rep.to_dict())
    assert rt.browned_out == 3
    assert rt.tenant_stats == rep.tenant_stats
    name, counters, wire = rt.tenant_stats[0]
    assert name == "gold"
    assert TenantMetrics.from_wire((counters, wire)).admitted == 2
    # older readers' reports still parse; future ones refuse
    d = rep.to_dict()
    d.pop("tenant_stats"), d.pop("browned_out")
    d["schema_version"] = 3
    assert LoadReport.from_dict(d).tenant_stats == ()
    d["schema_version"] = 99
    with pytest.raises(ValueError):
        LoadReport.from_dict(d)


# -- router satellites -------------------------------------------------------


def test_pressure_weighs_chips_not_replicas():
    r = ServiceRouter()
    r.register(Instance("tp8", "m", Device("d0", speed=8.0), queue_s=8.0))
    assert r.pressure("m") == pytest.approx(1.0)  # 8s over 8 chips
    assert r.want_scale("m", high_s=2.0) == 0  # NOT 8x-too-eager scale-out
    r2 = ServiceRouter()
    r2.register(Instance("one", "m", Device("d1", speed=1.0), queue_s=8.0))
    assert r2.want_scale("m", high_s=2.0) == 1  # same queue, 1 chip: scale


def test_route_eligible_filter():
    r = ServiceRouter(policy="least-loaded")
    a = r.register(Instance("a", "m", Device("da"), queue_s=0.0))
    r.register(Instance("b", "m", Device("db"), queue_s=5.0))
    job = Job(jid=0, model="m", demand=1, service_s=1.0, arrival=0.0)
    assert r.route(job, eligible={"b"}).name == "b"  # filter beats load
    assert r.route(job, eligible=set()) is None
    assert r.route(job) is a  # no filter: normal policy


# -- cluster integration -----------------------------------------------------


@pytest.fixture(scope="module")
def granite():
    cfg = get_config("granite-8b").reduced()
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _engines(cfg, params, n=2):
    return [ServingEngine(cfg, params, EngineConfig(
        slots=2, window=64, max_seq=128, sync_every=4)) for _ in range(n)]


TENANTS = {
    "gold": TenantClass("gold", tier=1, weight=2.0),
    "bulk": TenantClass("bulk", tier=0, weight=1.0),
}


def _drive(fe, reqs, *, max_steps=600):
    pending = sorted(reqs, key=lambda r: (r.arrival_time, r.rid))
    resolved, i, now = {}, 0, 0.0
    while len(resolved) < len(pending):
        while i < len(pending) and pending[i].arrival_time <= now:
            fe.submit(pending[i], now)
            i += 1
        for r in fe.step(now):
            resolved[r.rid] = r
        now += 1.0
        assert now < max_steps
    return resolved


def test_cluster_ladder_sheds_low_tier_protects_top(granite):
    # backlog_high_s is on the engine cost-model scale (ticks estimate in
    # milliseconds of virtual compute), not the 1s driver cadence: a few
    # modeled requests of backlog per replica trip the ladder.
    cfg, params = granite
    det = OverloadDetector(ttft_slo_s=8.0, backlog_high_s=0.002,
                           period_s=1.0, patience=1, relax_patience=50)
    fe = ClusterFrontend(_engines(cfg, params), tenants=TENANTS,
                         overload=det, fair_quantum=32.0)
    reqs = ([_req(i, "bulk", plen=12, budget=10, arrival=0.0)
             for i in range(12)]
            + [_req(100 + i, "gold", plen=8, budget=6, arrival=4.0,
                    slo=30.0) for i in range(3)])
    resolved = _drive(fe, reqs)
    golds = [resolved[100 + i] for i in range(3)]
    assert all(g.state is RequestState.FINISHED for g in golds)
    shed = [r for r in resolved.values()
            if r.fail_reason.startswith("shed: overload ladder")]
    assert shed and all(r.tenant == "bulk" for r in shed)
    assert all(r.retry_after_s > 0 for r in shed)
    m = fe.merged_metrics()
    assert m.tenant("bulk").shed == len(shed)
    assert fe._queue.max_wait_rounds <= fe._queue.starvation_bound(
        max(request_cost(r) for r in reqs))


def test_cluster_brownout_trims_and_counts_once(granite):
    cfg, params = granite
    det = OverloadDetector(ttft_slo_s=8.0, backlog_high_s=0.002,
                           period_s=1.0, patience=1, relax_patience=50,
                           max_level=BROWNOUT)
    tenants = {"gold": TenantClass("gold", tier=2),
               "mid": TenantClass("mid", tier=1, brownout_frac=0.5),
               "bulk": TenantClass("bulk", tier=0)}
    fe = ClusterFrontend(_engines(cfg, params), tenants=tenants,
                         overload=det)
    reqs = ([_req(i, "bulk", plen=12, budget=10, arrival=0.0)
             for i in range(10)]
            + [_req(50 + i, "mid", plen=8, budget=8, arrival=5.0)
               for i in range(3)])
    resolved = _drive(fe, reqs)
    browned = [r for r in resolved.values() if r.browned_out_tokens]
    assert browned and all(r.tenant == "mid" for r in browned)
    for r in browned:
        assert r.state is RequestState.FINISHED
        assert len(r.output) <= r.max_new_tokens  # served to trimmed cap
    m = fe.merged_metrics()
    # counted exactly once (at the serving engine), with trim accounting
    assert m.browned_out == len(browned)
    assert m.tenant("mid").browned_out == len(browned)
    assert m.tenant("mid").brownout_trimmed_tokens == sum(
        r.browned_out_tokens for r in browned)


def test_cluster_reject_level_typed_retry_after(granite):
    cfg, params = granite
    det = OverloadDetector(ttft_slo_s=8.0, backlog_high_s=0.002,
                           period_s=1.0, patience=1, relax_patience=50)
    fe = ClusterFrontend(_engines(cfg, params), tenants=TENANTS,
                         overload=det)
    for i in range(14):  # saturate until the ladder tops out
        fe.submit(_req(i, "bulk", plen=12, budget=10), 0.0)
    now = 0.0
    while det.level < REJECT:
        now += 1.0
        fe.step(now)
        assert now < 100
    late = _req(500, "bulk", plen=8, budget=4, arrival=now)
    assert fe.submit(late, now) is False
    assert late.state is RequestState.FAILED
    assert late.fail_reason.startswith("rejected: cluster overloaded")
    assert late.retry_after_s > 0
    gold = _req(501, "gold", plen=8, budget=4, arrival=now)
    assert fe.submit(gold, now) is True  # top tier admitted even here


def test_cluster_tenant_stats_on_wire(granite):
    cfg, params = granite
    fe = ClusterFrontend(_engines(cfg, params), tenants=TENANTS)
    resolved = _drive(fe, [_req(i, "gold", plen=8, budget=4, slo=30.0)
                           for i in range(3)])
    assert all(r.state is RequestState.FINISHED
               for r in resolved.values())
    stats = {}
    for eng in fe.engines:
        for name, counters, wire in eng.load_report().tenant_stats:
            tm = TenantMetrics.from_wire((counters, wire))
            stats.setdefault(name, TenantMetrics()).merge(tm)
    assert stats["gold"].admitted == 3
    assert stats["gold"].completed == 3
    assert stats["gold"].ttfts.count == 3
    assert stats["gold"].slo_tracked == 3


def test_cluster_single_tenant_path_unchanged(granite):
    """Untagged traffic through a tenant-less frontend: no pacing, no
    per-tenant accounting, identical streams to a fresh single engine."""
    cfg, params = granite
    fe = ClusterFrontend(_engines(cfg, params, n=1))
    reqs = [_req(i, plen=8, budget=6) for i in range(4)]
    resolved = _drive(fe, reqs)
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=2, window=64, max_seq=128, sync_every=4))
    solo = {}
    for i in range(4):
        r = _req(i, plen=8, budget=6)
        eng.submit(r, 0.0)
        solo[i] = r
    now = 0.0
    while any(s.finish_time < 0 for s in solo.values()):
        now += 1.0
        eng.step(now)
        assert now < 300
    for i in range(4):
        assert list(resolved[i].output) == list(solo[i].output)
    assert fe.merged_metrics().tenants == {}


# -- trace sampling + ring satellites ----------------------------------------


def test_trace_sampling_every_nth_rid(granite):
    cfg, params = granite
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=2, window=64, max_seq=128, sync_every=4, tracing=True,
        trace_sample_n=3))
    reqs = [_req(i, plen=8, budget=4) for i in range(6)]
    for r in reqs:
        eng.submit(r, 0.0)
    now = 0.0
    while any(r.finish_time < 0 for r in reqs):
        now += 1.0
        eng.step(now)
        assert now < 300
    traced = {r.rid for r in reqs if r.trace is not None}
    assert traced == {0, 3}
    assert eng.tracer.collected == 2


def test_trace_ring_bounded(granite):
    cfg, params = granite
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=2, window=64, max_seq=128, sync_every=4, tracing=True,
        trace_ring=2))
    reqs = [_req(i, plen=8, budget=4) for i in range(5)]
    for r in reqs:
        eng.submit(r, 0.0)
    now = 0.0
    while any(r.finish_time < 0 for r in reqs):
        now += 1.0
        eng.step(now)
        assert now < 300
    assert eng.tracer.collected == 5
    assert len(eng.tracer.ring) == 2  # bounded retention
    assert {t.rid for t in eng.tracer.ring} <= {r.rid for r in reqs}
    eng.reset()
    assert eng.tracer.ring is not None and len(eng.tracer.ring) == 0


def test_config_validates_trace_knobs():
    with pytest.raises(ValueError):
        EngineConfig(trace_sample_n=0)
    with pytest.raises(ValueError):
        EngineConfig(trace_ring=-1)


def test_overload_stack_streams_match_unloaded_reference(granite):
    """A bulk flood at several times the cluster's drain rate, beside steady
    gold and silver traffic, through the whole overload stack (tenants,
    detector, paced fair queue, token bucket): every finished stream equals
    the one an unloaded engine serves for that request, a browned-out
    stream is an exact prefix of it, and every request the ladder turned
    away carries a finite retry-after."""
    cfg, params = granite
    rng = np.random.default_rng(0)
    reqs, rid = [], 0
    for tenant, n, t0, gap, lo, hi, slo in (
            ("gold", 10, 2.0, 6.0, 8, 17, 10.0),
            ("silver", 6, 4.0, 9.0, 8, 17, 20.0),
            ("bulk", 36, 10.0, 0.5, 12, 25, 0.0)):
        for i in range(n):
            plen = int(rng.integers(lo, hi))
            budget = int(rng.integers(8, 13) if tenant != "bulk"
                         else rng.integers(10, 17))
            reqs.append(Request(rid, rng.integers(0, 500, plen)
                                .astype(np.int32), budget,
                                arrival_time=t0 + gap * i, tenant=tenant,
                                ttft_slo_s=slo))
            rid += 1
    ref_eng = ServingEngine(cfg, params, EngineConfig(
        slots=2, window=128, max_seq=192, sync_every=4))
    ref = [Request(r.rid, r.prompt.copy(), r.max_new_tokens) for r in reqs]
    _drive(ref_eng, ref)
    want = {r.rid: list(r.output) for r in ref}

    det = OverloadDetector(ttft_slo_s=10.0, backlog_high_s=0.002,
                           period_s=1.0, patience=1, relax_patience=6)
    tenants = {
        "gold": TenantClass("gold", tier=2, weight=4.0),
        "silver": TenantClass("silver", tier=1, weight=2.0,
                              brownout_frac=0.5),
        "bulk": TenantClass("bulk", tier=0, weight=1.0, rate_tokens_s=256.0,
                            burst_tokens=2048.0),
    }
    engines = [ServingEngine(cfg, params, EngineConfig(
        slots=2, window=128, max_seq=192, sync_every=4)) for _ in range(2)]
    fe = ClusterFrontend(engines, policy="predicted", seed=0,
                         tenants=tenants, overload=det, fair_quantum=64.0)
    resolved = _drive(fe, reqs)
    finished = [r for r in resolved.values()
                if r.state is RequestState.FINISHED]
    turned_away = [r for r in resolved.values()
                   if r.state is not RequestState.FINISHED]
    assert turned_away and any(r.browned_out_tokens for r in finished)
    for r in finished:
        got = list(r.output)
        if r.browned_out_tokens:
            assert got == want[r.rid][:len(got)], r.rid
        else:
            assert got == want[r.rid], r.rid
    for r in turned_away:
        assert 0 < r.retry_after_s < float("inf"), (r.rid, r.fail_reason)
