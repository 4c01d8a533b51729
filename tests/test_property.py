"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip, don't fail collection
from hypothesis import given, settings, strategies as st

from repro.core.costmodel import WorkEstimate, estimate_decode
from repro.core.misd.interference import progress_rates
from repro.models.layers import block_attention, dense_attention

demand = st.tuples(st.floats(0.01, 1.0), st.floats(0.01, 1.0))


@given(st.lists(demand, min_size=1, max_size=8))
@settings(max_examples=50, deadline=None)
def test_progress_rates_valid(demands):
    rates = progress_rates(demands)
    assert len(rates) == len(demands)
    assert all(0 < r <= 1.0 for r in rates)
    # adding a tenant never speeds anyone up
    if len(demands) > 1:
        fewer = progress_rates(demands[:-1])
        assert all(a <= b + 1e-12 for a, b in zip(rates, fewer))


@given(st.integers(1, 256), st.integers(128, 8192))
@settings(max_examples=30, deadline=None)
def test_decode_estimate_monotone(batch, context):
    from repro.configs import get_config

    cfg = get_config("granite-8b")
    e1 = estimate_decode(cfg, batch, context)
    e2 = estimate_decode(cfg, batch + 1, context)
    e3 = estimate_decode(cfg, batch, context + 128)
    assert e2.flops > e1.flops
    assert e3.hbm_bytes >= e1.hbm_bytes
    assert e1.latency_s > 0
    assert e1.bottleneck in ("compute", "memory", "collective")


@given(
    st.sampled_from([64, 128, 256]),
    st.sampled_from([32, 64]),
    st.booleans(),
    st.integers(0, 2 ** 31 - 1),
)
@settings(max_examples=12, deadline=None)
def test_block_attention_matches_dense(s, d, causal, seed):
    """The flat block-pair online-softmax scan == plain masked attention."""
    b, h = 1, 2
    key = jax.random.key(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    got = block_attention(q, k, v, causal=causal, chunk=32)
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-4)


@given(st.sampled_from([128, 256]), st.sampled_from([32, 64, 96]))
@settings(max_examples=8, deadline=None)
def test_block_attention_window(s, w):
    """Sliding-window block attention == dense with the same band mask."""
    b, h, d = 1, 2, 32
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    got = block_attention(q, k, v, causal=True, window=w, chunk=32)
    want = dense_attention(q, k, v, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-4)


@given(st.floats(1e6, 1e15), st.floats(1e3, 1e12), st.floats(0, 1e12))
@settings(max_examples=50, deadline=None)
def test_work_estimate_roofline(flops, hbm, coll):
    e = WorkEstimate(flops, hbm, coll)
    assert e.latency_s >= max(e.compute_s, e.memory_s, e.collective_s)
    c, m = e.demand
    assert 0 <= c <= 1 and 0 <= m <= 1
