"""Zero-copy continuous-batching engine: donation round-trips, bucketed
prefill, chunked prefill, deferred host sync, and admission isolation.

Engine-level tests build engines/requests through the conftest
``make_engine`` / ``make_request`` helpers, so the CI config matrix
({paged, rolling, prefix_cache} x {greedy, sampled}) replays them under
every configuration; raw-step tests (exact logits math) stay pinned."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_engine, make_request

from repro.configs import get_config
from repro.models import forward, init_cache, init_params
from repro.serving.engine import (
    bucketed_prefill_step,
    cache_insert,
    prefill_chunk_step,
    prefill_step,
    prompt_bucket,
)


@pytest.fixture(scope="module")
def granite():
    cfg = get_config("granite-8b").reduced()
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 500, n).astype(np.int32)


def _run(cfg, params, reqs, **kw):
    eng = make_engine(cfg, params, **kw)
    for r in reqs:
        assert eng.try_admit(r, 0.0)
    t = 0.0
    while not all(r.done for r in reqs):
        t += 1.0
        eng.step(t)
    return eng


# ---------------------------------------------------------------------------
# cache_insert under donation
# ---------------------------------------------------------------------------


def test_cache_insert_donated_roundtrip(granite):
    """The jit'd, donated slot-scatter must place a B=1 cache exactly and
    leave other slots untouched — across repeated donated calls (the donated
    buffer is consumed and rebound every call)."""
    cfg, params = granite
    slots, w, plen = 3, 64, 10
    batched = init_cache(cfg, slots, w)
    batch = {"tokens": jnp.asarray(_prompt(plen)[None, :], jnp.int32)}
    cache1 = init_cache(cfg, 1, w)
    _, _, cache1 = forward(cfg, params, batch, mode="prefill", cache=cache1)

    ins = jax.jit(lambda big, small, slot: cache_insert(big, small, slot, slots),
                  donate_argnums=(0,))
    for slot in (1, 2):  # one trace serves every slot index
        batched = ins(batched, cache1, np.int32(slot))
    k_big = batched["body"][0]["k"]  # (n_repeat, slots, w, kv, hd)
    k_one = cache1["body"][0]["k"]
    np.testing.assert_array_equal(np.asarray(k_big[:, 1]), np.asarray(k_one[:, 0]))
    np.testing.assert_array_equal(np.asarray(k_big[:, 2]), np.asarray(k_one[:, 0]))
    assert not np.asarray(k_big[:, 0]).any()  # untouched slot stays zero
    assert int(batched["pos"][1]) == plen and int(batched["pos"][0]) == 0


def test_cache_insert_slot_axis_disambiguation(granite):
    """Stacked body leaves have an n_repeat axis that can equal the slot
    count by value; the scatter must still pick the slot axis (the axis
    where the B=1 leaf has extent 1)."""
    cfg, params = granite  # n_repeat == 2 == slots below
    slots, w = 2, 32
    batched = init_cache(cfg, slots, w)
    batch = {"tokens": jnp.asarray(_prompt(6)[None, :], jnp.int32)}
    cache1 = init_cache(cfg, 1, w)
    _, _, cache1 = forward(cfg, params, batch, mode="prefill", cache=cache1)
    out = cache_insert(batched, cache1, 1, slots)
    k_big = np.asarray(out["body"][0]["k"])
    k_one = np.asarray(cache1["body"][0]["k"])
    np.testing.assert_array_equal(k_big[:, 1], k_one[:, 0])
    assert not k_big[:, 0].any()


# ---------------------------------------------------------------------------
# bucketed prefill
# ---------------------------------------------------------------------------


def test_bucketed_prefill_matches_unpadded(granite):
    """End-padding to a bucket must not change the last true token's logits
    or the decoded continuation."""
    cfg, params = granite
    w, plen = 64, 11
    prompt = _prompt(plen)
    bucket = prompt_bucket(plen)
    assert bucket == 16

    exact_logits, _ = prefill_step(
        cfg, params, {"tokens": jnp.asarray(prompt[None, :], jnp.int32)},
        window=w)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :plen] = prompt
    tok, bucket_logits, cache = bucketed_prefill_step(
        cfg, params, {"tokens": jnp.asarray(padded)}, np.int32(plen), window=w)
    np.testing.assert_allclose(np.asarray(bucket_logits), np.asarray(exact_logits),
                               atol=1e-5, rtol=1e-5)
    assert int(tok[0]) == int(jnp.argmax(exact_logits[0]))
    assert int(cache["pos"][0]) == plen


def test_bucketed_prefill_single_trace(granite):
    """Acceptance probe: every prompt length inside one power-of-two bucket
    shares exactly one trace of the prefill step."""
    cfg, params = granite
    eng = make_engine(cfg, params, slots=4, window=128, chunk_prefill=0)
    for i, plen in enumerate((9, 12, 15, 16)):
        assert eng.try_admit(make_request(i, _prompt(plen, seed=i), 4), 0.0)
    assert eng.prefill_traces == 1
    # a new bucket costs exactly one more trace
    eng2 = make_engine(cfg, params, slots=4, window=128, chunk_prefill=0)
    for i, plen in enumerate((9, 17)):
        assert eng2.try_admit(make_request(i, _prompt(plen, seed=i), 4), 0.0)
    assert eng2.prefill_traces == 2


def test_bucketed_engine_outputs_match_exact(granite):
    """Whole-engine check: bucketing on vs off produces identical streams."""
    cfg, params = granite
    out = {}
    for bucketed in (True, False):
        req = make_request(0, _prompt(13), max_new_tokens=6)
        _run(cfg, params, [req], slots=2, window=64,
             bucket_prompts=bucketed, chunk_prefill=0)
        out[bucketed] = req.output
    assert out[True] == out[False]


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_single_shot_cache(granite):
    """Running a prompt through chunk steps must build the same KV cache
    (values, pos) and the same first token as one single-shot prefill."""
    cfg, params = granite
    w, plen, chunk = 64, 20, 8
    prompt = _prompt(plen)
    padded_len = 24  # padded to a multiple of the chunk
    padded = np.zeros((1, padded_len), np.int32)
    padded[0, :plen] = prompt

    cache = init_cache(cfg, 1, w)
    toks = jnp.asarray(padded)
    for off in range(0, padded_len, chunk):
        tok, _, cache = prefill_chunk_step(
            cfg, params, cache, toks[:, off:off + chunk], np.int32(plen))

    ref_cache = init_cache(cfg, 1, w)
    ref_logits, _, ref_cache = forward(
        cfg, params, {"tokens": jnp.asarray(prompt[None, :], jnp.int32)},
        mode="prefill", cache=ref_cache)
    assert int(cache["pos"][0]) == int(ref_cache["pos"][0]) == plen
    np.testing.assert_allclose(
        np.asarray(cache["body"][0]["k"][:, :, :plen]),
        np.asarray(ref_cache["body"][0]["k"][:, :, :plen]),
        atol=1e-5, rtol=1e-5)
    assert int(tok[0]) == int(jnp.argmax(ref_logits[0, -1]))


def test_chunked_engine_outputs_match_single_shot(granite):
    """Long prompts admitted via interleaved chunks decode identically to
    single-shot admission."""
    cfg, params = granite
    out = {}
    for chunk in (16, 0):
        req = make_request(0, _prompt(40), max_new_tokens=6)
        _run(cfg, params, [req], slots=2, window=128, chunk_prefill=chunk)
        out[chunk] = req.output
    assert out[16] == out[0]


def test_admission_during_decode_no_interference(granite):
    """Acceptance: admitting a new (long, chunk-prefilled) request while >= 2
    slots are decoding changes no tokens of the in-flight requests."""
    cfg, params = granite

    def run_pair(with_admission):
        eng = make_engine(cfg, params, slots=3, window=128,
                          chunk_prefill=16, sync_every=4)
        a = make_request(0, _prompt(12, seed=1), max_new_tokens=24)
        b = make_request(1, _prompt(9, seed=2), max_new_tokens=24)
        assert eng.try_admit(a, 0.0) and eng.try_admit(b, 0.0)
        t = 0.0
        for _ in range(4):  # both slots decoding
            t += 1.0
            eng.step(t)
        late = None
        if with_admission:
            late = make_request(2, _prompt(48, seed=3), max_new_tokens=4)
            assert eng.try_admit(late, t)
            assert eng.n_prefilling == 1  # chunked: decode keeps running
        while not (a.done and b.done and (late is None or late.done)):
            t += 1.0
            eng.step(t)
        return a.output, b.output

    a0, b0 = run_pair(False)
    a1, b1 = run_pair(True)
    assert a0 == a1
    assert b0 == b1


# ---------------------------------------------------------------------------
# deferred sync / fused decode window
# ---------------------------------------------------------------------------


def test_deferred_sync_matches_per_tick(granite):
    """sync_every=N (with the fused scan window) and sync_every=1 produce
    identical token streams; N syncs the host ~1/N as often."""
    cfg, params = granite
    outs, engines = {}, {}
    for sync in (1, 8):
        req = make_request(0, _prompt(12), max_new_tokens=20)
        engines[sync] = _run(cfg, params, [req], slots=1, window=64,
                             sync_every=sync)
        outs[sync] = req.output
    assert outs[1] == outs[8]
    assert engines[8].metrics.host_syncs < engines[1].metrics.host_syncs


def test_mixed_bucket_workload_keeps_trace_and_sync_budget(granite):
    """Six prompts over two power-of-two buckets, served through slot churn
    on three slots: one prefill trace per bucket, at most two decode traces
    (single tick + fused scan), and at most one host sync per ``sync_every``
    decode ticks plus one per request (its admission) and one to drain."""
    cfg, params = granite
    sync_every = 4
    eng = make_engine(cfg, params, slots=3, window=128,
                      sync_every=sync_every, chunk_prefill=0)
    reqs = [make_request(i, _prompt(plen, seed=i), max_new_tokens=9)
            for i, plen in enumerate((9, 12, 15, 17, 21, 31))]
    for r in reqs:
        eng.submit(r, 0.0)
    t = 0.0
    while not all(r.done for r in reqs):
        t += 1.0
        eng.step(t)
    eng.drain(t)
    m = eng.metrics
    assert eng.prefill_traces == 2
    assert eng.decode_traces <= 2
    assert m.host_syncs <= m.decode_ticks / sync_every + len(reqs) + 1
    assert m.completed == len(reqs)
    assert all(len(r.output) == 9 for r in reqs)
    if eng.paged:  # every page back, but those the prefix index holds
        held = eng.prefix_index.cached_pages if eng.prefix_index else 0
        assert eng.allocator.pages_in_use == held


def test_mrope_decode_on_device(granite):
    """The mrope decode path builds positions from the cache's pos leaf on
    device (no per-tick host round-trip) and still decodes correctly."""
    cfg = get_config("qwen2-vl-7b").reduced()
    params = init_params(cfg, jax.random.key(0))
    req = make_request(0, _prompt(10), max_new_tokens=8)
    eng = _run(cfg, params, [req], slots=2, window=64, sync_every=4)
    assert len(req.output) == 8
    assert eng.metrics.host_syncs <= eng.metrics.decode_ticks / 2


# ---------------------------------------------------------------------------
# cost-model admission plan
# ---------------------------------------------------------------------------


def test_adaptive_slot_plan(granite):
    """slots=0 derives slot count + flush deadline from the cost model."""
    from repro.core.misd.batching import plan_admission

    cfg, params = granite
    eng = make_engine(cfg, params, slots=0, window=128, sla_s=0.05)
    # oracle plans with the engine's own chip count, so the tp8 matrix
    # cell (an 8-way replica plans bigger batches) validates too
    plan = plan_admission(cfg, context=128, sla_s=0.05,
                          n_chips=eng.config.n_chips)
    assert eng.slots == plan.slots > 0
    assert eng.admission.deadline_s == plan.flush_deadline_s > 0


def test_chunk_beyond_min_kv_ring_falls_back_to_single_shot(granite):
    """ROADMAP regression (rolling-window chunk safety): when a chunked
    prompt's padded length exceeds the SMALLEST KV ring (a local-attention
    block's window), multi-query chunks would alias overwritten ring slots
    — the engine must fall back to exact single-shot prefill and still
    produce correct streams. A prompt that does fit the ring keeps the
    chunked path."""
    import dataclasses

    cfg = dataclasses.replace(get_config("granite-8b").reduced(),
                              arch_type="hybrid",
                              block_pattern=("dense", "local_attn"),
                              local_window=16)
    params = init_params(cfg, jax.random.key(0))
    eng = make_engine(cfg, params, slots=2, window=128, chunk_prefill=8)
    assert not eng.paged and eng._min_window == 16  # ring < window
    # padded(40, 8) = 40 > 16: chunking would wrap the local ring
    unsafe = make_request(0, _prompt(40, seed=1), max_new_tokens=4)
    assert eng.try_admit(unsafe, 0.0)
    assert eng.n_prefilling == 0  # fell back: no chunk job was queued
    # padded(12, 8) = 16 <= 16: chunked path stays on
    safe = make_request(1, _prompt(12, seed=2), max_new_tokens=4)
    assert eng.try_admit(safe, 0.0)
    assert eng.n_prefilling == 1
    t = 0.0
    while not (unsafe.done and safe.done):
        t += 1.0
        eng.step(t)
    # both streams match a no-chunking engine exactly
    # same sampling identity as the chunked originals: the comparison is
    # chunking on/off, everything else equal
    ref_u = make_request(2, _prompt(40, seed=1), max_new_tokens=4,
                         sampling=unsafe.sampling)
    ref_s = make_request(3, _prompt(12, seed=2), max_new_tokens=4,
                         sampling=safe.sampling)
    _run(cfg, params, [ref_u, ref_s], slots=2, window=128, chunk_prefill=0)
    assert unsafe.output == ref_u.output
    assert safe.output == ref_s.output


def test_recurrent_arch_falls_back_to_exact_prefill(granite):
    """Archs with recurrent state (no end-paddable KV) must skip bucketing
    and chunking but still serve correctly."""
    cfg = get_config("recurrentgemma-9b").reduced()
    params = init_params(cfg, jax.random.key(0))
    eng = make_engine(cfg, params, slots=2, window=64)
    assert not eng.bucket_prompts and eng.chunk == 0
    req = make_request(0, _prompt(12), max_new_tokens=5)
    assert eng.try_admit(req, 0.0)
    t = 0.0
    while not req.done:
        t += 1.0
        eng.step(t)
    assert len(req.output) == 5


def test_bfloat16_engine_serves_deterministically():
    """The dtype every published config declares: a bfloat16 model served
    from bfloat16 KV pages through bucketed and chunked prefill and a fused
    decode window, one request sampled, finishes every request with its
    full token count and reproduces every stream on a fresh engine."""
    import dataclasses

    from repro.serving import Request, RequestState, SamplingParams

    cfg = dataclasses.replace(get_config("granite-8b").reduced(),
                              dtype="bfloat16")
    params = init_params(cfg, jax.random.key(0))
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))

    def serve():
        eng = make_engine(cfg, params, slots=3, paged=True, sync_every=4,
                          chunk_prefill=32)
        assert eng.cache["body"][0]["k"].dtype == jnp.bfloat16
        reqs = [Request(0, _prompt(12, seed=1), 9),
                Request(1, _prompt(70, seed=2), 9),  # chunked prefill
                Request(2, _prompt(20, seed=3), 9,
                        sampling=SamplingParams(temperature=0.8, top_k=20,
                                                top_p=0.9, seed=7))]
        for r in reqs:
            assert eng.submit(r, 0.0)
        t = 0.0
        while not all(r.done for r in reqs):
            t += 1.0
            eng.step(t)
        eng.drain(t)
        assert eng.metrics.prefill_chunks > 0
        assert any(k.startswith("decode/scan") for k in eng.compile_events)
        assert all(r.state is RequestState.FINISHED and len(r.output) == 9
                   for r in reqs)
        return [list(r.output) for r in reqs]

    assert serve() == serve()
