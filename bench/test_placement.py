"""CPU tests of a sharded cell's placement: the weights drawn over a tp=4
engine's chips, the reference computed over them, and one-chip cells
drawn and scored as before.

The tests need four devices. Each runs this file as a script in a child
process with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the
test process has one CPU device and JAX in it is already started), which
prints one JSON line of readings at a tiny Granite shape; the tests
judge them.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/test_placement.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
SEED = 2**31 + 77


def tiny_conf(tp: int = 0) -> dict:
    """granite-8b-tp4's file at a tiny shape (8 query heads over 4 KV
    heads of 16, 2 layers), with the topology ``tp`` (none for 0)."""
    with open(os.path.join(BENCH, "configs", "granite-8b-tp4.json")) as f:
        conf = json.load(f)
    d, ff, vocab, layers = 128, 256, 512, 2
    conf.update(hidden_size=d, num_attention_heads=8, num_key_value_heads=4,
                head_dim=16, intermediate_size=ff, vocab_size=vocab,
                num_hidden_layers=layers, rope_theta=10000.0)
    conf["program"]["overrides"].update(
        num_layers=layers, d_model=d, num_heads=8, num_kv_heads=4,
        head_dim=16, d_ff=ff, vocab_size=vocab, rope_theta=10000.0)
    conf["engine"].update(slots=4, max_seq=512, chunk_prefill=64)
    conf["engine"].pop("topology")
    conf["engine"].pop("pool_pages")
    if tp:
        conf["engine"]["topology"] = {"tp": tp}
    return conf


def tiny_sat_cell(conf: dict):
    """A tp=4 cell of ``conf`` under a small copy of the chat-sat mix."""
    import harness

    with open(os.path.join(BENCH, "traffic", "chat-sat.json")) as f:
        mix = json.load(f)
    mix["prompt"].update(median=150, min=64, max=400)
    mix["output"].update(median=8, min=4, max=16)
    mix.update(pool_size=64, check={"min_tokens": 40, "max_requests": 6})
    load = {"clients": 8,
            "check": {"mean_logit_gap": 0.002, "mean_nucleus_gap": 0.002}}
    return harness.Cell("tiny-tp4", 4, conf, mix, load, [],
                        [{"name": "output_tok_per_s", "unit": "tokens/s"},
                         {"name": "setup_s", "unit": "s"}])


def _run(cell) -> dict:
    import time

    import harness

    r = harness.run_cell(cell, SEED, 1.5, False, t_start=time.perf_counter(),
                         peak=None, log=lambda m: None)
    return {"correct": r["correct"], "failed": r["failed"],
            "checks": r["checks"]}


def readings() -> dict:
    """What the tests judge, computed on four devices."""
    import jax
    import numpy as np

    sys.path.insert(0, BENCH)
    import harness
    import reference
    from weights import published, seed_key, to_program

    assert jax.device_count() == 4, jax.devices()
    one, four = tiny_conf(), tiny_conf(tp=4)
    out = {}

    # the weights: the sharded draw against the one-device draw, and each
    # leaf against the sharding the engine computes for itself
    e4, s = harness.build(four, SEED, False)
    e1, _ = harness.build(one, SEED, False)
    want = jax.tree.leaves(harness.placement(e4.cfg, e4.topology))
    out["weights"] = {}
    for (path, a), b, sh in zip(
            jax.tree_util.tree_leaves_with_path(e4.params),
            jax.tree.leaves(e1.params), want):
        out["weights"][jax.tree_util.keystr(path)] = {
            "equal": bool(np.array_equal(np.asarray(a).view(np.uint16),
                                         np.asarray(b).view(np.uint16))),
            "placed": a.sharding == sh,
            "share": max(x.data.size for x in a.addressable_shards) / a.size}
    out["engine_mesh_devices"] = e4.mesh.devices.size
    del e4, e1

    # the reference over the four devices against the one-device one
    sampling = (0.7, 50, 0.9)
    w1 = harness.published_program(s, harness.topology(one))(seed_key(SEED))
    w4 = harness.published_program(s, harness.topology(four))(seed_key(SEED))
    out["reference_largest_share"] = max(
        max(x.data.size for x in a.addressable_shards) / a.size
        for k, a in w4.items() if a.ndim == 3)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, s.vocab, 700).astype(np.int32)
    served = rng.integers(0, s.vocab, 60).astype(np.int32)
    g1 = reference.gaps(s, w1, prompt, served, sampling)
    g4 = reference.gaps(s, w4, prompt, served, sampling)
    out["reference_gap_max"] = max(float(np.max(np.abs(g1[0]))),
                                   float(np.max(np.abs(g1[1]))))
    out["reference_diff"] = max(float(np.max(np.abs(g1[i] - g4[i])))
                                for i in range(2))

    # a whole tp=4 run, and one with the exchange between chips left out:
    # the tensor-parallel contractions of wo and w_down keep the first
    # chip's share of their input (its partial sum, as if the all-reduce
    # that adds the other chips' were dropped)
    out["run"] = _run(tiny_sat_cell(four))
    import repro.models.layers as layers

    real = layers.linear

    def first_share(x, w, eq):
        if eq in ("bse,ed->bsd", "...f,fd->...d"):
            k = x.shape[-1] // 4
            return real(x[..., :k], w[:k], eq)
        return real(x, w, eq)

    layers.linear = first_share
    try:
        out["run_without_exchange"] = _run(tiny_sat_cell(four))
    finally:
        layers.linear = real

    # and one with each decode tick's tokens altered where they are made
    import repro.serving.engine as eng

    tick = eng.decode_tick

    def altered(cfg, *a, **k):
        nxt, cache = tick(cfg, *a, **k)
        return (nxt + 1) % cfg.vocab_size, cache

    eng.decode_tick = altered
    try:
        out["run_with_altered_tokens"] = _run(tiny_sat_cell(four))
    finally:
        eng.decode_tick = tick

    # one-chip programs lower to the text they always had
    cfg1, _ = harness.program_config(one)
    key = seed_key(SEED)

    def init(k):
        return to_program(s, published(s, k))

    out["build_text_same"] = (
        harness.weights_program(cfg1, s, harness.topology(one)).lower(
            key).as_text() == jax.jit(init).lower(key).as_text())
    out["check_text_same"] = (
        harness.published_program(s, harness.topology(one)).lower(
            key).as_text()
        == jax.jit(lambda k: published(s, k)).lower(key).as_text())
    return out


_cache: dict = {}


def _readings() -> dict:
    if not _cache:
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip())
        p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           env=env, capture_output=True, text=True,
                           timeout=600)
        assert p.returncode == 0, p.stderr[-4000:]
        _cache.update(json.loads(p.stdout.strip().splitlines()[-1]))
    return _cache


def test_sharded_draw_equals_the_one_chip_draw_in_the_engines_sharding():
    r = _readings()
    assert r["engine_mesh_devices"] == 4
    w = r["weights"]
    assert len(w) == 11
    assert all(v["equal"] and v["placed"] for v in w.values())
    # the exact profile: outputs of wq, wk, wv, w_gate, w_up and the
    # tied embedding's vocabulary split four ways; wo, w_down and the
    # norms whole on every chip
    split = {k for k, v in w.items() if v["share"] == 0.25}
    assert {k.split("'")[-2] for k in split} == {
        "wq", "wk", "wv", "w_gate", "w_up", "embed"}
    assert all(v["share"] == 1.0 for k, v in w.items() if k not in split)


def test_sharded_reference_equals_the_one_chip_reference():
    r = _readings()
    assert r["reference_largest_share"] == 0.25
    assert r["reference_gap_max"] > 0.1  # the served tokens are not its best
    assert r["reference_diff"] <= 1e-5


def test_a_sharded_run_passes_and_fails_without_the_exchange():
    r = _readings()
    assert r["run"]["correct"] and r["run"]["failed"] == 0
    bad = r["run_without_exchange"]
    assert not bad["correct"]
    assert bad["checks"]["mean_logit_gap"]["value"] > \
        bad["checks"]["mean_logit_gap"]["limit"]


def test_a_sharded_run_fails_with_a_token_altered_where_it_is_produced():
    bad = _readings()["run_with_altered_tokens"]
    assert not bad["correct"] and bad["failed"] == 0
    assert bad["checks"]["mean_nucleus_gap"]["value"] > \
        bad["checks"]["mean_nucleus_gap"]["limit"]


def test_one_chip_build_and_check_lower_to_the_same_programs():
    r = _readings()
    assert r["build_text_same"] and r["check_text_same"]


if __name__ == "__main__":
    print(json.dumps(readings()), flush=True)
