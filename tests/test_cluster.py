"""Cluster frontend: SLO-aware routing of live traffic across
ServingEngine replicas — load_report telemetry, EDF ordering, policy
routing, retire/drain, autoscale hooks, closed-loop correction, and the
bit-identical-streams / no-page-leak invariants."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.costmodel import estimate_backlog_s
from repro.core.mimd.router import POLICIES
from repro.core.misd.interference import InterferencePredictor
from repro.models import init_params
from repro.serving import (
    ClusterFrontend,
    EngineConfig,
    RequestState,
    ServeMetrics,
    ServingEngine,
)

# Requests ride the CI config matrix (rid-stable sampled seeds under
# REPRO_ENGINE_SAMPLING=sampled; conftest.make_request shares Request's
# positional signature), so routing/SLO/stream-identity invariants are
# exercised under stochastic decode as well.
from conftest import make_request as Request


@pytest.fixture(scope="module")
def granite():
    cfg = get_config("granite-8b").reduced()
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


@pytest.fixture(scope="module")
def pair(granite):
    """Two live replicas shared (and reset) across tests so their jit
    caches stay warm."""
    cfg, params = granite
    engines = [ServingEngine(cfg, params, EngineConfig(slots=2, window=64, max_seq=128,
                             sync_every=4)) for _ in range(2)]
    return cfg, params, engines


def _reset(eng):
    eng.reset()


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 500, n).astype(np.int32)


def _drive(server, reqs, *, t0=0.0, max_steps=5000):
    done, t = 0, t0
    for r in reqs:
        server.submit(r, t)
    while done < len(reqs):
        t += 1.0
        done += len(server.step(t))
        assert t - t0 < max_steps
    server.drain(t)
    return t


# ---------------------------------------------------------------------------
# telemetry + SLO plumbing (no cluster needed)
# ---------------------------------------------------------------------------


def test_load_report_tracks_queue_and_slots(pair):
    _, _, engines = pair
    eng = engines[0]
    _reset(eng)
    rep = eng.load_report()
    assert rep.free_slots == eng.slots and not rep.saturated
    assert rep.backlog_s == 0.0 and rep.queued_requests == 0
    reqs = [Request(i, _prompt(12, seed=i), max_new_tokens=8)
            for i in range(4)]
    for r in reqs:
        eng.submit(r, 0.0)
    rep = eng.load_report()
    assert rep.free_slots == 0 and rep.saturated
    assert rep.queued_requests == 2  # 2 admitted, 2 queued
    assert rep.queued_prefill_tokens == 24
    assert len(rep.active_remaining) == 2 and len(rep.queued_budgets) == 2
    assert rep.decode_tokens_remaining > 0 and rep.backlog_s > 0
    assert rep.tick_est_s > 0
    assert rep.free_pages >= 0 and rep.total_pages > 0
    t = 0.0
    while not all(r.done for r in reqs):
        t += 1.0
        eng.step(t)
    eng.drain(t)
    rep = eng.load_report()
    assert rep.free_slots == eng.slots and rep.backlog_s == 0.0


def test_estimate_backlog_monotone(granite):
    cfg, _ = granite
    kw = dict(slots=2, context=128)
    zero = estimate_backlog_s(cfg, queued_prefill_tokens=0,
                              decode_tokens_remaining=0, **kw)
    some = estimate_backlog_s(cfg, queued_prefill_tokens=64,
                              decode_tokens_remaining=32, **kw)
    more = estimate_backlog_s(cfg, queued_prefill_tokens=64,
                              decode_tokens_remaining=320, **kw)
    assert zero == 0.0 and 0 < some < more


def test_slo_fields_and_goodput_metrics():
    req = Request(0, _prompt(8), max_new_tokens=5, arrival_time=2.0,
                  ttft_slo_s=3.0, tpot_slo_s=1.5)
    assert req.ttft_deadline == 5.0
    req.prefill_done = 4.0
    req.output = [1, 2, 3, 4, 5]
    req.finish_time = 8.0
    assert req.ttft == 2.0 and req.tpot == 1.0
    assert req.meets_slo() is True
    late = Request(1, _prompt(8), 5, arrival_time=0.0, ttft_slo_s=1.0)
    late.prefill_done, late.finish_time, late.output = 2.0, 3.0, [1]
    assert late.meets_slo() is False
    untracked = Request(2, _prompt(8), 5)
    assert untracked.meets_slo() is None
    assert untracked.ttft_deadline == float("inf")
    m = ServeMetrics()
    for r in (req, late, untracked):
        m.record_slo(r)
    assert m.slo_tracked == 2 and m.slo_met == 1
    assert m.ttft_slo_misses == 1 and m.tpot_slo_misses == 0
    assert m.goodput == 0.5
    m2 = ServeMetrics()
    m2.record_slo(req)
    m2.merge(m)
    assert m2.slo_tracked == 3 and m2.slo_met == 2
    assert ServeMetrics().goodput == 1.0  # nothing tracked = nothing missed


def test_engine_records_slo_attainment(granite):
    """The engine folds each finished request's SLO verdict into its
    metrics; a generous TTFT SLO passes, an impossible one misses."""
    cfg, params = granite
    eng = ServingEngine(cfg, params, EngineConfig(slots=1, window=64, chunk_prefill=0))
    good = Request(0, _prompt(8), max_new_tokens=3, arrival_time=0.0,
                   ttft_slo_s=100.0)
    bad = Request(1, _prompt(8, seed=1), max_new_tokens=3, arrival_time=-50.0,
                  ttft_slo_s=1e-9)
    t = 0.0
    for r in (good, bad):
        eng.submit(r, t)
        while not r.done:
            t += 1.0
            eng.step(t)
    eng.drain(t)
    m = eng.metrics
    assert m.slo_tracked == 2 and m.slo_met == 1 and m.ttft_slo_misses == 1
    assert m.goodput == 0.5


def test_engine_edf_backlog_ordering(granite):
    """With edf_backlog the engine admits the earliest-TTFT-deadline
    request first, regardless of submission order; FIFO stays default."""
    cfg, params = granite

    def run(edf):
        eng = ServingEngine(cfg, params, EngineConfig(slots=1, window=64,
                            chunk_prefill=0, edf_backlog=edf))
        blocker = Request(9, _prompt(8, seed=9), max_new_tokens=2)
        eng.submit(blocker, 0.0)  # occupies the only slot
        loose = Request(0, _prompt(8, seed=1), max_new_tokens=2,
                        arrival_time=0.0, ttft_slo_s=100.0)
        tight = Request(1, _prompt(8, seed=2), max_new_tokens=2,
                        arrival_time=0.0, ttft_slo_s=1.0)
        eng.submit(loose, 0.0)
        eng.submit(tight, 0.0)
        t = 0.0
        while not (loose.done and tight.done):
            t += 1.0
            eng.step(t)
        eng.drain(t)
        return loose, tight

    loose, tight = run(edf=True)
    assert tight.prefill_done < loose.prefill_done  # EDF: tight jumps ahead
    loose, tight = run(edf=False)
    assert loose.prefill_done < tight.prefill_done  # FIFO preserved


def test_interference_latency_loop():
    """observe_latency shifts corrected_latency toward reality; out-of-band
    observations (mismatched regimes) are rejected, in-band outliers are
    clamped."""
    p = InterferencePredictor()
    assert p.corrected_latency(1.0) == pytest.approx(1.0)
    for _ in range(50):
        p.observe_latency(1.0, 2.0)  # consistently 2x slower than predicted
    assert p.corrected_latency(1.0) == pytest.approx(2.0, rel=0.05)
    q = InterferencePredictor()
    q.observe_latency(1.0, 1e-6)  # out of band: ignored entirely
    q.observe_latency(1.0, 1e6)
    assert q.correction == 0.0
    q.observe_latency(1.0, 20.0)  # in band, clamped to 4x
    assert q.corrected_latency(1.0) <= 4.5


# ---------------------------------------------------------------------------
# cluster routing over live engines
# ---------------------------------------------------------------------------


def test_cluster_streams_bit_identical_to_single_engine(pair):
    """Acceptance: token streams from the cluster frontend match
    single-engine serving for the same requests, for every policy."""
    cfg, params, engines = pair

    def mk_reqs():
        return [Request(i, _prompt(10 + 7 * i, seed=i), max_new_tokens=5,
                        arrival_time=0.0, ttft_slo_s=50.0)
                for i in range(5)]

    _reset(engines[0])
    ref = mk_reqs()
    _drive(engines[0], ref)
    ref_out = {r.rid: r.output for r in ref}
    for policy in ("round-robin", "predicted"):
        for eng in engines:
            _reset(eng)
        fe = ClusterFrontend(engines, policy=policy, seed=0)
        reqs = mk_reqs()
        _drive(fe, reqs)
        assert {r.rid: r.output for r in reqs} == ref_out, policy
        assert all(r.routed_to for r in reqs)


def test_cluster_releases_pages_on_every_engine(pair):
    """Satellite: slot release under the cluster frontend never leaks
    pages — after a full run every replica's allocator is empty and the
    allocators never shared a page (per-engine pools are disjoint by
    construction; the leak mode is a request finishing on engine A while
    its pages were reserved on B)."""
    _, _, engines = pair
    for eng in engines:
        _reset(eng)
    fe = ClusterFrontend(engines, policy="least-loaded", seed=0)
    reqs = [Request(i, _prompt(12 + 5 * i, seed=i), max_new_tokens=4)
            for i in range(8)]
    _drive(fe, reqs)
    for eng in engines:
        assert eng.paged and eng.allocator.pages_in_use == 0
        assert eng.allocator.free_pages == eng.allocator.capacity
    # every request was admitted (and its pages charged) on the engine it
    # was routed to — not on any other replica
    names = {i.name for i in fe.instances}
    assert {r.routed_to for r in reqs} <= names


def test_cluster_retire_drains_without_new_routes(pair):
    """A retired replica finishes its in-flight work but receives no new
    routes, and drops out of the cluster once idle."""
    _, _, engines = pair
    for eng in engines:
        _reset(eng)
    fe = ClusterFrontend(engines, policy="round-robin", seed=0)
    first = [Request(i, _prompt(10, seed=i), max_new_tokens=6)
             for i in range(2)]
    for r in first:
        fe.submit(r, 0.0)
    fe.step(1.0)  # one request on each replica
    victim_name = first[0].routed_to
    victim = fe.retire(victim_name)
    assert victim is not None and victim.draining
    assert len(fe.instances) == 1 and fe.pool() and len(fe.pool()) == 1
    late = [Request(10 + i, _prompt(9, seed=10 + i), max_new_tokens=3)
            for i in range(3)]
    t = 1.0
    for r in late:
        fe.submit(r, t)
    while not all(r.done for r in first + late):
        t += 1.0
        fe.step(t)
    fe.drain(t)
    assert all(r.routed_to != victim_name for r in late)
    assert all(len(r.output) == r.max_new_tokens for r in first + late)
    fe.step(t + 1.0)  # reap: the drained victim leaves the cluster
    assert fe.draining == []
    assert victim.engine.allocator.pages_in_use == 0


def test_cluster_retire_requeues_unstarted_backlog(pair):
    """Satellite fix: retiring a replica used to strand its queued-but-
    unstarted backlog behind the drain (they'd finish, but only on the
    retiree, defeating the retire). Now `retire` pulls that backlog back
    through the frontend and re-routes it to live replicas; the retiree
    only finishes what it had actually started."""
    _, _, engines = pair
    for eng in engines:
        _reset(eng)
    fe = ClusterFrontend(engines[:1], policy="round-robin", seed=0)
    reqs = [Request(i, _prompt(10 + i, seed=i), max_new_tokens=4)
            for i in range(6)]
    for r in reqs:
        fe.submit(r, 0.0)
    fe.step(1.0)  # slots=2: two started, four queued on the lone replica
    victim_name = fe.instances[0].name
    started = [r for r in reqs if r.prefill_done >= 0]
    assert 0 < len(started) <= 2
    fe.add_engine(engines[1])
    victim = fe.retire(victim_name)
    assert victim is not None and victim.draining
    # the retiree's queue was taken over, not left to drain
    assert len(victim.engine.backlog) == 0
    assert len(victim.engine.admission.pending) == 0
    t = 1.0
    while not all(r.done for r in reqs):
        t += 1.0
        fe.step(t)
        assert t < 200
    fe.drain(t)
    assert all(len(r.output) == 4 for r in reqs)
    unstarted = [r for r in reqs if r not in started]
    assert all(r.routed_to != victim_name for r in unstarted)
    assert fe.merged_metrics().completed == 6
    for eng in engines[:2]:
        assert eng.allocator.pages_in_use == 0


def test_cluster_autoscale_hooks(pair):
    """Queue pressure grows the pool via the spawn callback; an idle pool
    shrinks by retiring (and draining) the least-loaded replica."""
    _, _, engines = pair
    for eng in engines:
        _reset(eng)
    fe = ClusterFrontend(engines[:1], policy="predicted", seed=0)
    assert len(fe.instances) == 1
    # queue pressure: saturate the lone replica, then autoscale out
    reqs = [Request(i, _prompt(16, seed=i), max_new_tokens=12)
            for i in range(6)]
    for r in reqs:
        fe.submit(r, 0.0)
    fe.step(1.0)
    assert fe.instances[0].queue_s > 0  # sync() mirrored real telemetry
    grown = fe.autoscale(1.0, spawn=lambda: engines[1], high_s=1e-9)
    assert grown is not None and len(fe.instances) == 2
    t = 1.0
    while not all(r.done for r in reqs):
        t += 1.0
        fe.step(t)
    fe.drain(t)
    # idle now: pressure ~ 0 -> shrink retires one replica
    shrunk = fe.autoscale(t, low_s=1.0)
    assert shrunk is not None and len(fe.instances) == 1
    fe.step(t + 1.0)
    assert fe.draining == []  # already idle, reaped immediately


def test_cluster_emptied_pool_holds_queue_until_replica_returns(pair):
    """Retiring the last replica of a pool must not crash the step or
    drop queued requests: they wait at the frontend and dispatch as soon
    as a replica registers again."""
    _, _, engines = pair
    for eng in engines:
        _reset(eng)
    fe = ClusterFrontend(engines[:1], policy="predicted", seed=0)
    req = Request(0, _prompt(10), max_new_tokens=3, arrival_time=0.0)
    fe.submit(req, 0.0)
    fe.retire(fe.instances[0].name)  # pool now empty, request still queued
    fe.step(1.0)  # must hold, not crash/lose
    assert not req.routed_to and fe._queue
    fe.add_engine(engines[1])
    t = 1.0
    while not req.done:
        t += 1.0
        fe.step(t)
    fe.drain(t)
    assert req.routed_to and len(req.output) == 3


def test_cluster_multi_model_pools(pair):
    """Requests tagged with a model only ever land in that model's pool;
    an untagged request with no default pool is rejected loudly."""
    _, _, engines = pair
    for eng in engines:
        _reset(eng)
    fe = ClusterFrontend({"chat": engines[:1], "code": engines[1:]},
                         policy="predicted", seed=0)
    chat = [Request(i, _prompt(10, seed=i), max_new_tokens=3, model="chat")
            for i in range(2)]
    code = [Request(10 + i, _prompt(14, seed=9 + i), max_new_tokens=3,
                    model="code") for i in range(2)]
    _drive(fe, chat + code)
    assert {r.routed_to for r in chat} == {"chat/e0"}
    assert {r.routed_to for r in code} == {"code/e1"}
    # an unroutable model tag is a typed rejection, not a frontend crash:
    # the request resolves FAILED through the next step and is counted
    stray = Request(99, _prompt(8), 2, model="missing")
    assert fe.submit(stray, 0.0) is False
    assert stray.state is RequestState.FAILED
    assert "no engine pool" in stray.fail_reason
    assert stray in fe.step(0.0)
    assert fe.merged_metrics().rejected == 1


def test_cluster_edf_frontend_dispatch_order(pair):
    """Within one tick, the tightest TTFT deadline is routed (and thus
    admitted) first even when submitted last."""
    _, _, engines = pair
    for eng in engines:
        _reset(eng)
    fe = ClusterFrontend(engines[:1], policy="round-robin", seed=0)
    loose = Request(0, _prompt(8, seed=1), max_new_tokens=2,
                    arrival_time=0.0, ttft_slo_s=90.0)
    tight = Request(1, _prompt(8, seed=2), max_new_tokens=2,
                    arrival_time=0.0, ttft_slo_s=1.0)
    mid = Request(2, _prompt(8, seed=3), max_new_tokens=2,
                  arrival_time=0.0, ttft_slo_s=30.0)
    for r in (loose, mid, tight):  # deliberately worst-case order
        fe.submit(r, 0.0)
    t = 0.0
    while not all(r.done for r in (loose, mid, tight)):
        t += 1.0
        fe.step(t)
    fe.drain(t)
    assert tight.prefill_done <= mid.prefill_done <= loose.prefill_done


def test_heterogeneous_pool_routes_more_to_bigger_replica(granite):
    """Satellite: per-replica n_chips flows through EngineInstance into
    predicted-completion routing — a 4-chip replica's cost-model service
    is cheaper, so it should absorb clearly more of the traffic than its
    1-chip sibling (and the pool still drains correctly)."""
    cfg, params = granite
    engines = [ServingEngine(cfg, params, EngineConfig(slots=2, window=64, max_seq=128,
                             sync_every=4, modeled_chips=c)) for c in (1, 4)]
    fe = ClusterFrontend(engines, policy="predicted", seed=0)
    small, big = fe.instances
    assert small.device.speed == 1.0 and big.device.speed == 4.0
    reqs = [Request(i, _prompt(12 + (i % 5), seed=i), max_new_tokens=6)
            for i in range(12)]
    # trickle arrivals so routing reacts to load, not just an empty tie
    t, done, pending = 0.0, 0, list(reqs)
    while done < len(reqs):
        if pending:
            fe.submit(pending.pop(0), t)
        t += 1.0
        done += len(fe.step(t))
        assert t < 5000
    fe.drain(t)
    assert small.routed + big.routed == len(reqs)
    assert big.routed > small.routed  # more chips -> more traffic
    for eng in engines:
        assert eng.allocator.pages_in_use == 0


def test_prefix_affinity_routes_template_to_warm_replica(granite):
    """Satellite/tentpole: with prefix caching on, predicted-completion
    routing includes the affinity term — requests sharing a template land
    on the replica that already holds its pages (and actually hit)."""
    cfg, params = granite
    engines = [ServingEngine(cfg, params, EngineConfig(slots=2, window=64, max_seq=128,
                             sync_every=4, prefix_cache=True))
               for _ in range(2)]
    fe = ClusterFrontend(engines, policy="predicted", seed=0)
    tpl = _prompt(48, seed=40)
    # warm the SECOND replica directly: on an idle cluster the routing
    # tie-break alone would pick e0, so landing on e1 proves the
    # affinity term (not registration order) steered the choice
    primer = Request(0, tpl.copy(), max_new_tokens=1)
    assert engines[1].try_admit(primer, 0.0)
    engines[1].drain(0.0)
    home = fe.instances[1].name
    followups = [Request(1 + i,
                         np.concatenate([tpl, _prompt(4 + i, seed=41 + i)]
                                        ).astype(np.int32),
                         max_new_tokens=2) for i in range(4)]
    t = 1000.0
    for r in followups:  # idle cluster each time: affinity is the tiebreak
        t = _drive(fe, [r], t0=t) + 1.0
    assert all(r.routed_to == home for r in followups)
    assert all(r.prefix_hit_tokens == 48 for r in followups)
    # unrelated traffic is NOT pulled toward the warm replica's pages
    stranger = Request(99, _prompt(20, seed=77), max_new_tokens=2)
    probe_inst = next(i for i in fe.instances if i.name == home)
    job = fe._job_for(stranger, t)
    assert probe_inst.prefix_hit_s(job) == 0.0
    for eng in engines:
        assert eng.allocator.pages_in_use == eng.prefix_index.cached_pages


def test_cluster_closed_loop_observes(pair):
    """Serving traffic populates each instance's corrector with residual
    observations (predicted vs observed TTFT/JCT)."""
    _, _, engines = pair
    for eng in engines:
        _reset(eng)
    fe = ClusterFrontend(engines, policy="predicted", seed=0)
    # drive on the cost-model tick scale so observed waits land in the
    # corrector's accepted band (wall-clock-consistent virtual time)
    dt = engines[0].load_report().tick_est_s
    reqs = [Request(i, _prompt(10 + i, seed=i), max_new_tokens=6,
                    arrival_time=0.0, ttft_slo_s=1000 * dt)
            for i in range(6)]
    done, t = 0, 0.0
    for r in reqs:
        fe.submit(r, t)
    while done < len(reqs):
        t += dt
        done += len(fe.step(t))
    fe.drain(t)
    assert sum(inst.corrector._n for inst in fe.instances) > 0
    m = fe.merged_metrics()
    assert m.completed == len(reqs) and m.slo_tracked == len(reqs)
    util = fe.utilization()
    assert set(util) == {i.name for i in fe.instances}
    assert all(0.0 <= u <= 1.0 for u in util.values())


def test_cluster_sampled_streams_stable_under_routing(pair):
    """ISSUE 5 acceptance: a seeded sampled request produces the SAME
    token stream no matter which replica the policy lands it on — noise
    is keyed by (seed, position), never by placement. Streams also match
    single-engine serving, and per-replica compile counts stay at the
    single-trace budget with mixed greedy/sampled traffic."""
    from repro.serving import SamplingParams

    cfg, params, engines = pair

    def mk_reqs():
        return [Request(i, _prompt(10 + 3 * i, seed=i), max_new_tokens=5,
                        arrival_time=0.0,
                        sampling=(SamplingParams(temperature=0.9, top_k=30,
                                                 top_p=0.95, seed=40 + i)
                                  if i % 2 else SamplingParams()))
                for i in range(6)]

    _reset(engines[0])
    ref = mk_reqs()
    _drive(engines[0], ref)
    ref_out = {r.rid: r.output for r in ref}
    placements = set()
    for policy in ("round-robin", "p2c", "predicted"):
        for eng in engines:
            _reset(eng)
        fe = ClusterFrontend(engines, policy=policy, seed=1)
        reqs = mk_reqs()
        _drive(fe, reqs)
        assert {r.rid: r.output for r in reqs} == ref_out, policy
        placements.add(tuple(r.routed_to for r in reqs))
        for eng in engines:
            assert eng.decode_traces <= 2, policy
    assert len(placements) > 1  # the policies really did place differently
    m = fe.merged_metrics()
    assert m.sampled_requests == 3  # cluster rollup counts sampled traffic


@pytest.fixture(scope="module")
def poisson_pair(granite):
    """Two replicas at a width where long prompts take chunked prefill."""
    cfg, params = granite
    engines = [ServingEngine(cfg, params, EngineConfig(
        slots=2, window=128, max_seq=192, sync_every=4)) for _ in range(2)]
    return cfg, params, engines


def _poisson_workload(n=24, *, rate=0.6, seed=0):
    """Poisson arrivals of a bimodal mix: mostly short prompts with short
    budgets, the rest long chunk-prefilled prompts with long budgets."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    reqs = []
    for i in range(n):
        short = rng.random() < 0.7
        plen = int(rng.integers(8, 25) if short else rng.integers(48, 97))
        budget = int(rng.integers(4, 9) if short else rng.integers(24, 41))
        reqs.append(Request(i, rng.integers(0, 500, plen).astype(np.int32),
                            budget, arrival_time=float(arrivals[i]),
                            ttft_slo_s=6.0 if short else 16.0))
    return reqs


def _drive_arrivals(server, reqs, *, max_steps=5000):
    """Submit each request when the clock passes its arrival; step once a
    second until every request has finished."""
    pending = sorted(reqs, key=lambda r: r.arrival_time)
    i, now, done = 0, 0.0, 0
    while done < len(reqs):
        while i < len(pending) and pending[i].arrival_time <= now:
            server.submit(pending[i], now)
            i += 1
        done += len(server.step(now))
        now += 1.0
        assert now < max_steps
    server.drain(now)


@pytest.mark.parametrize("policy", POLICIES)
def test_cluster_policy_keeps_engine_invariants_under_poisson_arrivals(
        poisson_pair, policy):
    """Every routing policy, under Poisson arrivals of short and long
    (chunked) prompts: streams equal one engine serving the same requests,
    every replica drains its pages, each keeps the single-engine trace
    budget (two decode traces; one prefill trace per prompt bucket and one
    chunk trace), and round-robin reaches every replica."""
    _, _, engines = poisson_pair
    _reset(engines[0])
    ref = _poisson_workload()
    _drive_arrivals(engines[0], ref)
    for eng in engines:
        _reset(eng)
    fe = ClusterFrontend(engines, policy=policy, seed=0)
    reqs = _poisson_workload()
    _drive_arrivals(fe, reqs)
    assert {r.rid: r.output for r in reqs} == {r.rid: r.output for r in ref}
    assert fe.merged_metrics().completed == len(reqs)
    for eng in engines:
        assert eng.allocator.pages_in_use == 0
        assert eng.decode_traces <= 2
        assert eng.prefill_traces <= 4
    if policy == "round-robin":
        assert all(inst.routed > 0 for inst in fe.instances)
