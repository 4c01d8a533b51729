"""Device-resident stochastic sampling through the fused decode window.

The contract under test (ISSUE 5 acceptance): one decode trace + one
fused-window trace no matter the greedy/stochastic slot mix; seeded
sampled streams bit-identical across engine restarts, slot assignments,
admission paths (bucketed / chunked / prefix-hit suffix), and cache
layouts; greedy as the exact degenerate case (temperature -> 0 converges,
top-k = 1 equals greedy outright)."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params, process_logits
from repro.serving import EngineConfig, Request, SamplingParams, ServingEngine

SP = SamplingParams(temperature=0.8, top_k=20, top_p=0.95, seed=7)


@pytest.fixture(scope="module")
def granite():
    cfg = get_config("granite-8b").reduced()
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 500, n).astype(np.int32)


def _serve(eng, reqs, *, t0=0.0):
    for r in reqs:
        eng.submit(r, t0)
    t = t0
    while not all(r.done for r in reqs):
        t += 1.0
        eng.step(t)
    eng.drain(t)
    return t


def _streams(cfg, params, rids, *, sampling=None, engine=None, **kw):
    """Serve one request per rid (prompt/seed keyed by rid); returns
    {rid: output}. ``sampling`` may be a callable rid -> SamplingParams."""
    eng = engine or ServingEngine(cfg, params, EngineConfig(**kw))
    if engine is not None:
        eng.reset()
    reqs = []
    for rid in rids:
        sp = sampling(rid) if callable(sampling) else (sampling
                                                       or SamplingParams())
        reqs.append(Request(rid=rid, prompt=_prompt(10 + rid % 3, seed=rid),
                            max_new_tokens=8, sampling=sp))
    _serve(eng, reqs)
    return {r.rid: r.output for r in reqs}, eng


# ---------------------------------------------------------------------------
# single-trace probes with mixed greedy/stochastic batches
# ---------------------------------------------------------------------------


def test_mixed_batch_single_decode_trace(granite):
    """Acceptance probe: greedy and sampled slots share ONE decode trace
    and ONE fused-window trace — the stochastic branch is masked
    composition inside the same jit program, never a retrace."""
    cfg, params = granite
    mix = lambda rid: SP if rid % 2 else SamplingParams()  # noqa: E731
    out, eng = _streams(cfg, params, range(4), sampling=mix,
                        slots=4, window=64, sync_every=4, chunk_prefill=0)
    assert eng.decode_traces <= 2  # single tick + fused scan
    assert eng.prefill_traces <= 2  # one per prompt bucket
    assert eng.metrics.sampled_requests == 2
    # the sampled slots actually diverge from greedy decode
    greedy_out, _ = _streams(cfg, params, [1], slots=4, window=64,
                             sync_every=4, chunk_prefill=0)
    assert out[1] != greedy_out[1]
    # admitting MORE sampled traffic onto the warm engine retraces nothing
    before = eng.decode_traces
    _streams(cfg, params, range(4), sampling=SP, engine=eng)
    assert eng.decode_traces == before


def test_all_greedy_batch_unchanged_by_sampling_state(granite):
    """A fully greedy batch on the sampling-capable engine produces the
    same streams as before the subsystem existed (the greedy lane is
    argmax, not a temperature-1 draw)."""
    cfg, params = granite
    out, eng = _streams(cfg, params, range(3), slots=3, window=64,
                        sync_every=4)
    from repro.serving import generate

    for rid, stream in out.items():
        assert stream == generate(cfg, params, _prompt(10 + rid % 3,
                                                       seed=rid), 8, window=64)


# ---------------------------------------------------------------------------
# seeded-stream reproducibility
# ---------------------------------------------------------------------------


def test_sampled_streams_reproducible_across_restart_and_slot_order(granite):
    """Fixed seed => bit-identical stream on a fresh engine, under a
    different submission order (different slot assignment), and alongside
    a different batch mix."""
    cfg, params = granite
    a, _ = _streams(cfg, params, [0, 1, 2, 3], sampling=SP, slots=4,
                    window=64, sync_every=4)
    b, _ = _streams(cfg, params, [3, 1, 0, 2], sampling=SP, slots=4,
                    window=64, sync_every=4)
    assert a == b
    # same request alone in the batch: stream unchanged
    solo, _ = _streams(cfg, params, [2], sampling=SP, slots=4, window=64,
                       sync_every=4)
    assert solo[2] == a[2]


def test_sampled_streams_reproducible_across_cache_layout_and_fusion(granite):
    """The same seeded request decodes identically under paged vs rolling
    caches and fused vs single-tick windows."""
    cfg, params = granite
    base, _ = _streams(cfg, params, [0, 1], sampling=SP, slots=2,
                       window=64, sync_every=4)
    rolling, _ = _streams(cfg, params, [0, 1], sampling=SP, slots=2,
                          window=64, sync_every=4, paged=False)
    unfused, _ = _streams(cfg, params, [0, 1], sampling=SP, slots=2,
                          window=64, sync_every=1)
    assert base == rolling == unfused


def test_sampled_streams_identical_after_engine_reset(granite):
    """A mixed greedy/sampled workload over three prompt buckets, replayed
    on the same engine after ``reset()`` (warm jit caches, every slot, queue
    and counter cleared), reproduces every stream of the first run and of a
    fresh engine, and compiles nothing new."""
    cfg, params = granite

    def workload():
        return [Request(rid=rid, prompt=_prompt((9, 14, 21, 33)[rid % 4],
                                                seed=rid),
                        max_new_tokens=8,
                        sampling=(SamplingParams(temperature=0.8, top_k=40,
                                                 top_p=0.95, seed=500 + rid)
                                  if rid % 2 else SamplingParams()))
                for rid in range(6)]

    def serve(eng):
        reqs = workload()
        _serve(eng, reqs)
        return {r.rid: r.output for r in reqs}

    kw = dict(slots=3, window=128, sync_every=4)
    eng = ServingEngine(cfg, params, EngineConfig(**kw))
    first = serve(eng)
    traces = (eng.prefill_traces, eng.decode_traces)
    assert traces[0] <= 3 and traces[1] <= 2  # a trace per bucket; tick+scan
    eng.reset()
    assert serve(eng) == first
    assert (eng.prefill_traces, eng.decode_traces) == traces
    assert serve(ServingEngine(cfg, params, EngineConfig(**kw))) == first


def test_seed_changes_the_stream(granite):
    cfg, params = granite
    a, _ = _streams(cfg, params, [0], sampling=SamplingParams(
        temperature=1.2, seed=1), slots=1, window=64)
    b, _ = _streams(cfg, params, [0], sampling=SamplingParams(
        temperature=1.2, seed=2), slots=1, window=64)
    assert a[0] != b[0]


def test_sampled_stream_survives_every_admission_path(granite):
    """Bucketed single-shot, interleaved chunked prefill, and the
    prefix-cache hit (suffix-offset prefill over aliased pages) must all
    produce the same seeded stream — the first token's noise is keyed by
    (seed, prompt_len) in every path."""
    cfg, params = granite
    prompt = _prompt(40, seed=9)

    def run(**kw):
        eng = ServingEngine(cfg, params, EngineConfig(slots=2, window=128, sync_every=4,
                            **kw))
        r = Request(rid=0, prompt=prompt, max_new_tokens=8, sampling=SP)
        assert eng.try_admit(r, 0.0)
        t = 0.0
        while not r.done:
            t += 1.0
            eng.step(t)
        eng.drain(t)
        return r.output, eng

    single, _ = run(chunk_prefill=0)
    chunked, _ = run(chunk_prefill=16)
    assert chunked == single

    eng = ServingEngine(cfg, params, EngineConfig(slots=2, window=128, sync_every=4,
                        prefix_cache=True))
    cold = Request(rid=0, prompt=prompt, max_new_tokens=8, sampling=SP)
    assert eng.try_admit(cold, 0.0)
    t = 0.0
    while not cold.done:
        t += 1.0
        eng.step(t)
    eng.drain(t)
    warm = Request(rid=1, prompt=prompt, max_new_tokens=8, sampling=SP)
    assert eng.try_admit(warm, t)
    while not warm.done:
        t += 1.0
        eng.step(t)
    eng.drain(t)
    assert eng.metrics.prefix_hits == 1
    assert warm.output == cold.output == single


# ---------------------------------------------------------------------------
# logit-processor invariants (hypothesis properties: test_sampling_property)
# ---------------------------------------------------------------------------


def test_logit_processor_masks():
    """top-k keeps exactly k survivors (no value ties in model logits);
    the nucleus always covers mass >= top_p; both off = pure rescale."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((3, 200)) * 2, jnp.float32)
    temp = jnp.full((3,), 0.9, jnp.float32)
    k = jnp.asarray([1, 7, 0], jnp.int32)
    proc = process_logits(logits, temp, k, jnp.ones((3,), jnp.float32))
    alive = np.isfinite(np.asarray(proc)).sum(axis=1)
    assert list(alive) == [1, 7, 200]
    topp = jnp.asarray([0.3, 0.8, 1.0], jnp.float32)
    proc = process_logits(logits, temp, jnp.zeros((3,), jnp.int32), topp)
    p = np.asarray(jax.nn.softmax(logits / 0.9, axis=-1))
    for row, thresh in enumerate((0.3, 0.8)):
        kept = np.isfinite(np.asarray(proc[row]))
        assert p[row, kept].sum() >= thresh  # nucleus reaches the mass
        # minimality: dropping the smallest kept entry dips below it
        smallest = p[row][kept].min()
        assert p[row, kept].sum() - smallest < thresh


def test_hot_path_draw_agrees_with_logit_processor_mask(granite):
    """The inverse-CDF hot path (sample_tokens, prob-space radix) must
    only ever emit tokens inside the logit-space processor's kept set —
    the two mask formulations are order-isomorphic by construction."""
    import jax.numpy as jnp

    from repro.models.layers import sample_tokens

    rng = np.random.default_rng(11)
    b, v = 4, 300
    logits = jnp.asarray(rng.standard_normal((b, v)) * 2, jnp.float32)
    temp = jnp.full((b,), 0.8, jnp.float32)
    k = jnp.asarray([1, 5, 40, 0], jnp.int32)
    tp = jnp.asarray([1.0, 0.9, 0.6, 0.4], jnp.float32)
    allowed = np.isfinite(np.asarray(process_logits(logits, temp, k, tp)))
    samp = {
        "greedy": jnp.zeros((b,), jnp.bool_),
        "temperature": temp, "top_k": k, "top_p": tp,
        "key": jnp.stack([jnp.asarray(jax.random.PRNGKey(i))
                          for i in range(b)]).astype(jnp.uint32),
    }
    for pos0 in range(0, 64, 4):
        pos = jnp.arange(pos0, pos0 + b, dtype=jnp.int32)
        tok = np.asarray(sample_tokens(logits, samp, pos))
        assert all(allowed[i, tok[i]] for i in range(b)), (pos0, tok)


def test_hot_path_mask_exact_on_prob_collapsed_ties():
    """Adversarial tie (found in review): two distinct logits whose
    float32 softmax probabilities are bit-equal. The top-k cut must run
    in logit space — a prob-space cut would keep both and emit a token
    outside the configured top-k set."""
    import jax.numpy as jnp

    from repro.models.layers import sample_tokens

    v = 64
    row = np.zeros(v, np.float32)
    row[0], row[1], row[2] = 5.0, 1.0, 1.0 + 1e-7
    logits = jnp.asarray(row[None])
    assert float(jax.nn.softmax(logits)[0, 1]) == float(
        jax.nn.softmax(logits)[0, 2])  # the collapse this test needs
    samp = {
        "greedy": jnp.zeros((1,), jnp.bool_),
        "temperature": jnp.ones((1,), jnp.float32),
        "top_k": jnp.full((1,), 2, jnp.int32),
        "top_p": jnp.ones((1,), jnp.float32),
        "key": jnp.asarray(jax.random.PRNGKey(0))[None].astype(jnp.uint32),
    }
    allowed = np.isfinite(np.asarray(process_logits(
        logits, samp["temperature"], samp["top_k"], samp["top_p"])))[0]
    assert allowed.sum() == 2 and allowed[0] and allowed[2]
    for p in range(200):
        tok = int(sample_tokens(logits, samp,
                                jnp.asarray([p], jnp.int32))[0])
        assert allowed[tok], (p, tok)
