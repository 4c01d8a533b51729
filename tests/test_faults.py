"""Replica failure detection + failover: kill/hang/slow injection, the
staleness watchdog, retry budgets, and bit-identical replay."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import (
    ClusterFrontend,
    EngineConfig,
    EngineFailure,
    FaultInjector,
    FaultyEngine,
    RequestState,
    SamplingParams,
    ServingEngine,
)

from conftest import make_request as Request


@pytest.fixture(scope="module")
def granite():
    cfg = get_config("granite-8b").reduced()
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 500, n).astype(np.int32)


def _samp(seed):
    return SamplingParams(temperature=0.7, top_k=20, top_p=0.95, seed=seed)


def _workload(n, budget=5):
    """Sampled requests (stochastic streams: the strong replay claim)."""
    return [Request(i, _prompt(10 + i % 5, seed=i), max_new_tokens=budget,
                    sampling=_samp(500 + i)) for i in range(n)]


def _engines(cfg, params, n):
    return [ServingEngine(cfg, params, EngineConfig(slots=2, window=64, max_seq=128,
                          sync_every=1)) for _ in range(n)]


def _drive(fe, reqs, *, fault_at=None, max_steps=500):
    """Submit everything at t=0, optionally fire faults at virtual times
    via ``fault_at`` {t: callable}, collect every resolved request."""
    resolved, t = {}, 0.0
    for r in reqs:
        fe.submit(r, 0.0)
    while len(resolved) < len(reqs):
        t += 1.0
        if fault_at and t in fault_at:
            fault_at.pop(t)()
        for r in fe.step(t):
            resolved[r.rid] = r
        assert t < max_steps, f"{len(resolved)}/{len(reqs)} resolved"
    for r in fe.drain(t):
        resolved[r.rid] = r
    return resolved


def _reference(cfg, params, reqs):
    eng = _engines(cfg, params, 1)[0]
    fe = ClusterFrontend([eng], policy="round-robin", seed=0)
    res = _drive(fe, reqs)
    return {rid: list(r.output) for rid, r in res.items()}


# ---------------------------------------------------------------------------
# the proxy
# ---------------------------------------------------------------------------


def test_faulty_engine_is_transparent(granite):
    cfg, params = granite
    eng = _engines(cfg, params, 1)[0]
    proxy = FaultyEngine(eng)
    assert proxy.slots == eng.slots  # reads forward
    proxy.edf_backlog = True  # writes forward (ClusterFrontend does this)
    assert eng.edf_backlog is True
    assert proxy.engine is eng
    req = Request(0, _prompt(8), max_new_tokens=2)
    assert proxy.submit(req, 0.0)
    t = 0.0
    while not req.done:
        t += 1.0
        proxy.step(t)
        assert t < 50
    proxy.inject("kill")
    with pytest.raises(EngineFailure):
        proxy.step(t + 1.0)
    with pytest.raises(EngineFailure):
        proxy.submit(Request(1, _prompt(8, seed=1), 2), t + 1.0)
    proxy.inject("recover")
    proxy.step(t + 2.0)  # healthy again
    with pytest.raises(ValueError, match="unknown fault kind"):
        proxy.inject("meteor")


def test_fault_injector_schedule_is_deterministic(granite):
    cfg, params = granite
    proxy = FaultyEngine(_engines(cfg, params, 1)[0])
    inj = FaultInjector({"e0": proxy})
    inj.schedule(5.0, "e0", "hang")
    inj.schedule(2.0, "e0", "slow", slow_every=3)
    with pytest.raises(KeyError, match="no proxy"):
        inj.schedule(1.0, "nope", "kill")
    with pytest.raises(ValueError, match="unknown fault kind"):
        inj.schedule(1.0, "e0", "meteor")
    assert inj.tick(1.0) == [] and proxy.mode is None
    assert inj.tick(2.0) == [(2.0, "e0", "slow")]
    assert proxy.mode == "slow" and proxy.slow_every == 3
    assert inj.tick(10.0) == [(5.0, "e0", "hang")]  # late tick still fires
    assert proxy.mode == "hang" and inj.pending == 0
    assert inj.fired == [(2.0, "e0", "slow"), (5.0, "e0", "hang")]


# ---------------------------------------------------------------------------
# failover
# ---------------------------------------------------------------------------


def test_kill_fails_over_bit_identical(granite):
    """A replica crash mid-workload loses nothing: the frontend harvests
    its outstanding ledger, survivors replay, and every stream —
    stochastic included — matches the failure-free run exactly."""
    cfg, params = granite
    reqs = _workload(8)
    reference = _reference(cfg, params, _workload(8))

    proxies = [FaultyEngine(e) for e in _engines(cfg, params, 2)]
    fe = ClusterFrontend(proxies, policy="round-robin", seed=0,
                         max_retries=3)
    resolved = _drive(fe, reqs,
                      fault_at={2.0: lambda: proxies[0].inject("kill")})
    assert len(resolved) == 8
    assert all(r.state is RequestState.FINISHED for r in resolved.values())
    assert {rid: list(r.output) for rid, r in resolved.items()} == reference
    m = fe.merged_metrics()
    assert len(fe.failed) == 1 and fe.failed[0].failed
    assert m.failed_over > 0 and m.retried > 0
    assert max(r.retries for r in resolved.values()) <= 3
    # the survivor holds no leaked pages
    survivor = fe.instances[0].engine
    assert survivor.allocator.pages_in_use == 0
    assert survivor.allocator.total_refs == 0


def test_hang_detected_by_watchdog(granite):
    """A wedged replica raises nothing — it accepts work and makes no
    progress. Only the staleness watchdog can declare it dead; its
    requests then fail over and finish."""
    cfg, params = granite
    reqs = _workload(6)
    reference = _reference(cfg, params, _workload(6))
    proxies = [FaultyEngine(e) for e in _engines(cfg, params, 2)]
    fe = ClusterFrontend(proxies, policy="round-robin", seed=0,
                         health_timeout_s=4.0, max_retries=3)
    resolved = _drive(fe, reqs,
                      fault_at={2.0: lambda: proxies[0].inject("hang")})
    assert len(resolved) == 6
    assert all(r.state is RequestState.FINISHED for r in resolved.values())
    assert {rid: list(r.output) for rid, r in resolved.items()} == reference
    assert len(fe.failed) == 1
    assert fe.merged_metrics().failed_over > 0


def test_slow_replica_is_not_declared_dead(granite):
    """Slow != dead: a replica making progress every k-th tick keeps its
    work (the closed-loop residual repels future load instead)."""
    cfg, params = granite
    proxies = [FaultyEngine(e) for e in _engines(cfg, params, 2)]
    fe = ClusterFrontend(proxies, policy="round-robin", seed=0,
                         health_timeout_s=4.0, max_retries=3)
    resolved = _drive(fe, _workload(6),
                      fault_at={2.0: lambda: proxies[0].inject(
                          "slow", slow_every=3)})
    assert len(resolved) == 6
    assert all(r.state is RequestState.FINISHED for r in resolved.values())
    assert fe.failed == [] and fe.merged_metrics().failed_over == 0


def test_idle_hung_replica_stays_healthy_until_it_holds_work(granite):
    """Idle replicas are healthy by definition — a hang is only
    observable (and only matters) once work sinks into it."""
    cfg, params = granite
    proxies = [FaultyEngine(e) for e in _engines(cfg, params, 1)]
    fe = ClusterFrontend(proxies, policy="round-robin", seed=0,
                         health_timeout_s=3.0, max_retries=3)
    proxies[0].inject("hang")
    for t in range(1, 8):  # idle well past the timeout: still trusted
        fe.step(float(t))
    assert fe.failed == [] and len(fe.instances) == 1
    req = Request(0, _prompt(8), max_new_tokens=2)
    fe.submit(req, 8.0)
    for t in range(9, 20):  # work sinks in; watchdog now trips
        fe.step(float(t))
        if fe.failed:
            break
    assert len(fe.failed) == 1
    assert req.retries == 1  # harvested and requeued (held: empty pool)
    # recovery: a fresh replica repopulates the pool; the request lands
    fe.add_engine(_engines(cfg, params, 1)[0])
    t = 20.0
    while not req.done:
        t += 1.0
        fe.step(t)
        assert t < 100
    assert req.state is RequestState.FINISHED and len(req.output) == 2


def test_retry_budget_exhaustion_resolves_failed(granite):
    """When no retry budget remains, a harvested request resolves FAILED
    (typed, with a reason) instead of looping or raising."""
    cfg, params = granite
    proxies = [FaultyEngine(e) for e in _engines(cfg, params, 1)]
    fe = ClusterFrontend(proxies, policy="round-robin", seed=0,
                         max_retries=0)
    reqs = _workload(3)
    for r in reqs:
        fe.submit(r, 0.0)
    fe.step(0.0)  # dispatch: all three on the doomed replica's ledger
    proxies[0].inject("kill")
    resolved = {}
    for t in range(1, 10):
        for r in fe.step(float(t)):
            resolved[r.rid] = r
        if len(resolved) == 3:
            break
    assert len(resolved) == 3
    assert all(r.state is RequestState.FAILED for r in resolved.values())
    assert all("retry budget exhausted" in r.fail_reason
               for r in resolved.values())
    assert fe.merged_metrics().failed >= 3


def test_retry_backoff_delays_resubmission(granite):
    """With retry_backoff_s set, a failed-over request is held off the
    queue for base*2^(retries-1) before re-dispatch."""
    cfg, params = granite
    proxies = [FaultyEngine(e) for e in _engines(cfg, params, 2)]
    fe = ClusterFrontend(proxies, policy="round-robin", seed=0,
                         max_retries=3, retry_backoff_s=4.0)
    reqs = _workload(4)
    for r in reqs:
        fe.submit(r, 0.0)
    fe.step(0.0)
    proxies[0].inject("kill")
    fe.step(1.0)  # detection: harvested requests held until t=5
    held = [r for r in reqs if r.retries == 1 and not r.done]
    assert held and fe._held_retries
    assert not fe.idle  # held retries keep the cluster busy
    resolved = {}
    for t in range(2, 60):
        for r in fe.step(float(t)):
            resolved[r.rid] = r
        if len(resolved) == 4:
            break
    assert len(resolved) == 4
    assert all(r.state is RequestState.FINISHED for r in resolved.values())
    # replay could not have finished before the backoff released (t>=5)
    assert all(r.finish_time >= 5.0 for r in held)


# ---------------------------------------------------------------------------
# one replica of four wedged or slowed mid-workload
# ---------------------------------------------------------------------------


def _poisson_sampled(n=24, *, rate=0.8, seed=0):
    """Poisson arrivals; every request sampled (seed 7000+rid)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    return [Request(i, rng.integers(0, 500, int(rng.integers(8, 33)))
                    .astype(np.int32), int(rng.integers(8, 17)),
                    arrival_time=float(arrivals[i]), ttft_slo_s=24.0,
                    sampling=_samp(7000 + i))
            for i in range(n)]


def _fault_round(proxies, fault):
    """The workload through a predicted-policy frontend over ``proxies``,
    with ``fault`` injected into the first replica at the median arrival
    (none for None). Returns the frontend and every resolved request."""
    for p in proxies:
        p.inject("recover")
        p.engine.reset()
    fe = ClusterFrontend(proxies, policy="predicted", seed=0,
                         health_timeout_s=4.0, max_retries=3)
    reqs = sorted(_poisson_sampled(), key=lambda r: r.arrival_time)
    injector = FaultInjector({fe.instances[0].name: proxies[0]})
    if fault is not None:
        injector.schedule(reqs[len(reqs) // 2].arrival_time,
                          fe.instances[0].name, fault, slow_every=4)
    resolved, i, now = {}, 0, 0.0
    while len(resolved) < len(reqs):
        injector.tick(now)
        while i < len(reqs) and reqs[i].arrival_time <= now:
            fe.submit(reqs[i], now)
            i += 1
        for r in fe.step(now):
            resolved[r.rid] = r
        now += 1.0
        assert now < 2000, f"{len(resolved)}/{len(reqs)} resolved"
    for r in fe.drain(now):
        resolved[r.rid] = r
    return fe, resolved


@pytest.fixture(scope="module")
def four(granite):
    """Four proxied replicas and the streams of a failure-free round."""
    cfg, params = granite
    proxies = [FaultyEngine(ServingEngine(cfg, params, EngineConfig(
        slots=2, window=128, max_seq=192, sync_every=4)))
        for _ in range(4)]
    _, resolved = _fault_round(proxies, None)
    return proxies, {rid: list(r.output) for rid, r in resolved.items()}


@pytest.mark.parametrize("fault", ["hang", "slow"])
def test_wedged_or_slow_replica_of_four_loses_nothing(four, fault):
    """One replica of four hangs (only the watchdog can tell) or drops to a
    quarter of its speed mid-workload: every request still finishes its
    full budget with the failure-free stream, no request exceeds its retry
    budget, the surviving replicas hold no page or reference, and only
    the hung replica is declared dead."""
    proxies, baseline = four
    fe, resolved = _fault_round(proxies, fault)
    assert len(resolved) == len(baseline)
    assert all(r.state is RequestState.FINISHED
               and len(r.output) == r.max_new_tokens
               for r in resolved.values())
    assert {rid: list(r.output) for rid, r in resolved.items()} == baseline
    m = fe.merged_metrics()
    assert max(r.retries for r in resolved.values()) <= 3
    assert m.retried <= len(baseline)
    for inst in fe.instances + fe.draining + fe.retired:
        inst.engine.clear_prefix_cache()
        assert inst.engine.allocator.pages_in_use == 0
        assert inst.engine.allocator.total_refs == 0
    if fault == "hang":
        assert len(fe.failed) == 1 and m.failed_over > 0
    else:
        assert fe.failed == [] and m.failed_over == 0
