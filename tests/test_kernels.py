"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles,
executed with interpret=True (kernel bodies run on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as da
from repro.kernels import ops, ref
from repro.models import layers as L


def _mk(key, shape, dtype):
    x = jax.random.normal(jax.random.key(key), shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("s", [128, 256, 512])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(s, d, dtype, causal):
    b, h, kv = 2, 4, 2
    q = _mk(1, (b, s, h, d), dtype)
    k = _mk(2, (b, s, kv, d), dtype)
    v = _mk(3, (b, s, kv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    kk = jnp.repeat(k, h // kv, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vv = jnp.repeat(v, h // kv, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, d)
    qq = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    want = ref.ref_attention(qq, kk, vv, causal=causal)
    want = want.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("w", [128, 512])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(w, d, dtype):
    b, h, kv = 2, 4, 2
    q = _mk(4, (b, 1, h, d), dtype)
    kc = _mk(5, (b, w, kv, d), dtype)
    vc = _mk(6, (b, w, kv, d), dtype)
    pos = jnp.asarray([w // 3, w], jnp.int32)  # one partial, one full cache
    out = ops.decode_attention(q, kc, vc, pos, interpret=True)
    kk = jnp.repeat(kc, h // kv, axis=2).transpose(0, 2, 1, 3).reshape(b * h, w, d)
    vv = jnp.repeat(vc, h // kv, axis=2).transpose(0, 2, 1, 3).reshape(b * h, w, d)
    qq = q.transpose(0, 2, 1, 3).reshape(b * h, 1, d)
    nv = jnp.repeat(jnp.minimum(pos, w), h)
    want = ref.ref_decode_attention(qq, kk, vv, nv)
    want = want.reshape(b, h, 1, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("sq", [4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_chunked(sq, dtype):
    """Chunked-prefill queries: per-query validity == causal-within-chunk.
    The S-query kernel call must match S separate 1-query calls."""
    b, h, kv, w, d = 2, 4, 2, 128, 64
    q = _mk(7, (b, sq, h, d), dtype)
    kc = _mk(8, (b, w, kv, d), dtype)
    vc = _mk(9, (b, w, kv, d), dtype)
    pos = jnp.asarray([40, w], jnp.int32)  # tokens written incl. the chunk
    out = ops.decode_attention(q, kc, vc, pos, interpret=True)
    kk = jnp.repeat(kc, h // kv, axis=2).transpose(0, 2, 1, 3).reshape(b * h, w, d)
    vv = jnp.repeat(vc, h // kv, axis=2).transpose(0, 2, 1, 3).reshape(b * h, w, d)
    qq = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    nv = jnp.repeat(jnp.minimum(pos, w), h)
    want = ref.ref_decode_attention(qq, kk, vv, nv)
    want = want.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])
    # row-by-row against single-query calls with shrinking validity
    for i in range(sq):
        one = ops.decode_attention(q[:, i:i + 1], kc, vc,
                                   pos - (sq - 1 - i), interpret=True)
        np.testing.assert_allclose(
            np.asarray(out[:, i:i + 1], np.float32),
            np.asarray(one, np.float32), atol=TOL[dtype], rtol=TOL[dtype])


#: (kv heads, query heads per kv head): chatglm3's near-MQA and granite's
#: GQA (blocks of 32 and 8 pages of 16 tokens)
PAGED_HEADS = {"kvh2-g16": (2, 16), "kvh8-g4": (8, 4)}
PS, MAX_PAGES, LAYERS = 16, 40, 2
#: tested lengths by name; "edge" is the kernel's block of pages
PAGED_LENGTHS = {
    "empty": lambda edge: 0, "one": lambda edge: 1,
    "page-1": lambda edge: PS - 1, "page": lambda edge: PS,
    "page+1": lambda edge: PS + 1, "edge-1": lambda edge: edge - 1,
    "edge": lambda edge: edge, "edge+1": lambda edge: edge + 1,
    "full": lambda edge: MAX_PAGES * PS,
}


def _paged_case(kvh, g, length, order):
    """Three slots of stacked two-layer pools, read as layer 1's rows: the
    slot under test, one of the full table and one empty (on the trash
    page). A slot's pages lie in the pool scattered out of order, or in
    order (slot b's page j is row b * MAX_PAGES + j + 1)."""
    b, d = 3, 128
    pool = b * MAX_PAGES + 1
    stack = (LAYERS, pool, PS, kvh, d)
    k_rows = _mk(30, stack, jnp.bfloat16).reshape((-1,) + stack[2:])
    v_rows = _mk(31, stack, jnp.bfloat16).reshape((-1,) + stack[2:])
    q = _mk(32, (b, 1, kvh * g, d), jnp.bfloat16)
    pages = np.arange(1, pool)
    if order == "scattered":
        pages = np.random.default_rng(length).permutation(pages)
    table = pages.reshape(b, MAX_PAGES)
    table[2] = 0
    table = jnp.asarray(table + pool, jnp.int32)  # layer 1 of the stack
    lengths = jnp.asarray([length, MAX_PAGES * PS, 0], jnp.int32)
    return q, k_rows, v_rows, table, lengths


@pytest.mark.parametrize("order", ["scattered", "in-order"])
@pytest.mark.parametrize("length", sorted(PAGED_LENGTHS))
@pytest.mark.parametrize("heads", sorted(PAGED_HEADS))
def test_paged_decode_attention(heads, length, order):
    """The Pallas paged kernel against the gather-then-mask oracle it
    replaces on the served path, on the stacked pools' rows: lanes with
    tokens agree, empty lanes are finite."""
    kvh, g = PAGED_HEADS[heads]
    edge = PS * da.pages_per_block(MAX_PAGES, PS * kvh * 128 * 2)
    n = PAGED_LENGTHS[length](edge)
    q, k_rows, v_rows, table, lengths = _paged_case(kvh, g, n, order)
    out = ops.paged_decode_attention(q, k_rows, v_rows, table, lengths,
                                     interpret=True)
    want = L.paged_decode_attention(q, k_rows, v_rows, table, lengths)
    live = np.asarray(lengths) > 0
    out, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(out[live], want[live], atol=2e-2, rtol=2e-2)
    assert np.isfinite(out).all()


@pytest.mark.parametrize("s", [128, 384])
@pytest.mark.parametrize("l", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_rglru_scan(s, l, dtype):
    b = 2
    a = jax.random.uniform(jax.random.key(7), (b, s, l), minval=0.7,
                           maxval=0.999).astype(dtype)
    x = (_mk(8, (b, s, l), dtype) * 0.1).astype(dtype)
    h0 = _mk(9, (b, l), dtype)
    y, hT = ops.rglru_scan(a, x, h0, interpret=True)
    ry, rhT = ref.ref_rglru_scan(a, x, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(rhT), atol=1e-4,
                               rtol=1e-4)


def test_rglru_scan_matches_naive_loop():
    b, s, l = 1, 64, 128
    a = jax.random.uniform(jax.random.key(1), (b, s, l), minval=0.5, maxval=1.0)
    x = jax.random.normal(jax.random.key(2), (b, s, l)) * 0.2
    h0 = jnp.zeros((b, l))
    y, hT = ops.rglru_scan(a, x, h0, interpret=True)
    h = np.zeros((b, l), np.float32)
    an, xn = np.asarray(a), np.asarray(x)
    for t in range(s):
        h = an[:, t] * h + xn[:, t]
        np.testing.assert_allclose(np.asarray(y[:, t]), h, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), h, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (256, 128, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_matmul(m, k, n, dtype):
    x = _mk(10, (m, k), dtype)
    w = _mk(11, (k, n), jnp.float32)
    wq, sc = ops.quantize_int8(w)
    out = ops.int8_matmul(x, wq, sc, interpret=True)
    want = ref.ref_int8_matmul(x, wq, sc)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=5e-2, rtol=5e-2)


def test_int8_quantization_error_bounded():
    w = jax.random.normal(jax.random.key(3), (256, 256))
    wq, sc = ops.quantize_int8(w)
    deq = np.asarray(wq, np.float32) * np.asarray(sc)[None, :]
    rel = np.abs(deq - np.asarray(w)).max() / np.abs(np.asarray(w)).max()
    assert rel < 0.01  # <1% of max magnitude per channel


@pytest.mark.parametrize("v", [128, 500])
@pytest.mark.parametrize("seed", [0, 1])
def test_topk_sample_matches_oracle(v, seed):
    """Radix-select kernel vs the sort-based oracle: exact token equality
    (same noise input) across per-row k / temperature mixes."""
    b = 6
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.standard_normal((b, v)) * 3, jnp.float32)
    k = jnp.asarray(rng.integers(1, v + 1, b), jnp.int32)
    temp = jnp.asarray(rng.uniform(0.2, 2.0, b), jnp.float32)
    u = jnp.asarray(rng.uniform(0, 1, (b, v)), jnp.float32)
    got = ops.topk_sample(logits, k, temp, u, interpret=True)
    want = ref.ref_topk_sample(logits, k, temp, u)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_topk_sample_value_ties_keep_oracle_semantics():
    """Duplicated logit values straddling the k-th rank: the kernel's
    radix-select threshold keeps every tied entry, exactly like the
    oracle's ``x >= kth`` mask."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(np.repeat(rng.standard_normal((2, 32)), 2, axis=1),
                         jnp.float32)
    k = jnp.asarray([3, 7], jnp.int32)
    temp = jnp.ones((2,), jnp.float32)
    u = jnp.asarray(rng.uniform(0, 1, logits.shape), jnp.float32)
    got = ops.topk_sample(logits, k, temp, u, interpret=True)
    want = ref.ref_topk_sample(logits, k, temp, u)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_topk_sample_k1_is_greedy():
    """k=1 restricts the distribution to the (unique) argmax: the draw is
    deterministic no matter the noise."""
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    k = jnp.ones((4,), jnp.int32)
    temp = jnp.asarray([0.3, 0.7, 1.0, 2.0], jnp.float32)
    for s in range(3):
        u = jnp.asarray(rng.uniform(0, 1, logits.shape), jnp.float32)
        got = ops.topk_sample(logits, k, temp, u, interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jnp.argmax(logits, -1)))


def test_topk_sample_respects_the_mask():
    """Across many draws every sampled token is inside the top-k set and
    the model-layout twin (layers.process_logits) agrees on that set."""
    from repro.models.layers import process_logits

    rng = np.random.default_rng(5)
    b, v, kk = 3, 96, 8
    logits = jnp.asarray(rng.standard_normal((b, v)) * 2, jnp.float32)
    k = jnp.full((b,), kk, jnp.int32)
    temp = jnp.full((b,), 0.9, jnp.float32)
    allowed = np.asarray(process_logits(
        logits, temp, k, jnp.ones((b,), jnp.float32))) > -np.inf
    assert (allowed.sum(axis=1) == kk).all()
    for s in range(8):
        u = jnp.asarray(rng.uniform(0, 1, (b, v)), jnp.float32)
        tok = np.asarray(ops.topk_sample(logits, k, temp, u, interpret=True))
        assert all(allowed[i, tok[i]] for i in range(b))
