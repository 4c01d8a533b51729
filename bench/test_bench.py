"""CPU tests of the benchmark: the traffic generator, the work counts, the
trace reduction, finding cells and readers by name, and the correctness
check with its control and a planted fault, at a size a test run holds.

Importing this file loads no accelerator library: JAX starts on whatever
platform the environment gives it (the test suite runs on the CPU).
"""
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import harness  # noqa: E402
import tracereduce  # noqa: E402
import traffic  # noqa: E402
from weights import shapes  # noqa: E402


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


MIXES = ("code-burst", "chat-sat")
CONFIGS = ("granite-8b-l16", "chatglm3-6b-l20")
SEEDS = (0, 7, 2**31 + 5, 2**33 + 1)


# --- traffic ---------------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_open_schedule_is_one_window_cycle(seed):
    mix = _json("traffic", "code-burst.json")
    g = traffic.open_requests(mix, {"rate_per_s": 3.0}, 45.0)
    a = [next(g) for _ in range(3 * 135)]
    win = [r for r in a if r.due < 45.0]
    assert len(win) == 135 and win[0].due == 0.0
    assert a[135].due == 45.0 and a[270].due == 90.0
    assert [(r.prompt_len, r.max_new) for r in a[135:270]] == \
        [(r.prompt_len, r.max_new) for r in win]
    assert [r.due + 45.0 for r in win] == pytest.approx(
        [r.due for r in a[135:270]])
    tokens = traffic.prompt_tokens(seed, 3, 50, 100)
    assert np.array_equal(tokens, traffic.prompt_tokens(seed, 3, 50, 100))
    assert not np.array_equal(tokens,
                              traffic.prompt_tokens(seed + 1, 3, 50, 100))


@pytest.mark.parametrize("name", MIXES)
def test_clipped_lognormals_match_their_parameters(name):
    mix = _json("traffic", name + ".json")
    sizes = np.array(traffic.multiset(mix, 20000))
    for col, key in ((0, "prompt"), (1, "output")):
        spec, x = mix[key], sizes[:, col]
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert abs(np.median(x) / spec["median"] - 1) < 0.03
        inner = np.log(x[(x > spec["min"]) & (x < spec["max"])])
        assert np.std(inner) < spec["sigma"] * 1.02
    share = sizes[:, 2].mean()
    assert share == pytest.approx(mix["sampled_share"], abs=1e-3)


def test_gamma_gaps_are_bursty_and_fill_the_window():
    mix = _json("traffic", "code-burst.json")
    g = traffic.gaps(mix, 5000, 100.0)
    assert g.sum() == pytest.approx(100.0)
    assert np.std(g) / np.mean(g) == pytest.approx(2.0, rel=0.1)  # shape 0.25


def test_closed_pool_is_one_cycle_from_its_start():
    mix = _json("traffic", "chat-sat.json")
    n = mix["pool_size"]
    g = traffic.closed_requests(mix)
    a = [next(g) for _ in range(2 * n)]
    key = [(r.prompt_len, r.max_new, r.sampled) for r in a]
    assert key[:n] == key[n:] == traffic.multiset(mix, n)
    assert [r.rid for r in a] == list(range(2 * n))
    again = traffic.closed_requests(mix)
    assert [(r.prompt_len, r.max_new, r.sampled)
            for r in (next(again) for _ in range(n))] == key[:n]


@pytest.mark.parametrize("seed", SEEDS)
def test_check_sample_takes_each_lanes_longest_and_its_share(seed):
    reqs = [traffic.Req(i, 100 + 7 * i, 10 + i % 5, bool(i % 3))
            for i in range(40)]
    spec = {"min_tokens": 40, "max_requests": 4}
    out = traffic.check_sample(reqs, seed, spec)
    assert out == traffic.check_sample(reqs, seed, spec)
    for lane in (False, True):
        mine = [r for r in out if r.sampled == lane]
        pool = [r for r in reqs if r.sampled == lane]
        assert mine[0] is max(pool, key=lambda r: r.prompt_len + r.max_new)
        assert 1 <= len(mine) <= spec["max_requests"]
        assert (sum(r.max_new for r in mine) >= spec["min_tokens"]
                or len(mine) == spec["max_requests"])
    assert traffic.check_sample(reqs[:1], seed, spec) == reqs[:1]


@pytest.mark.parametrize("chunk,lo,hi,want", [
    (256, 256, 3584, [256] + [256 * k for k in range(2, 15)]),
    (256, 32, 2048, [32, 64, 128, 256] + [256 * k for k in range(2, 9)]),
    (64, 64, 400, [64, 128, 192, 256, 320, 384, 400]),
])
def test_warm_lengths_cover_every_prefill_shape(chunk, lo, hi, want):
    mix = {"prompt": {"min": lo, "max": hi}}
    got = traffic.warm_lengths(mix, chunk, 16)
    assert got == want

    def shape(n):  # the engine's padding: bucket up to chunk, then chunks
        if n <= chunk:
            return max(16, 1 << (n - 1).bit_length())
        return -(-n // chunk) * chunk

    assert {shape(n) for n in range(lo, hi + 1)} == {shape(n) for n in got}


# --- work counts ------------------------------------------------------------


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_counts_agree_with_the_program(name):
    conf = _json("configs", name + ".json")
    cfg, s = harness.program_config(conf)
    assert s.params() == cfg.param_count()
    # all but an untied embedding table, which a tick only indexes
    head_and_body = s.params() - (0 if s.tied else s.vocab * s.d)
    assert flops.decode_weight_bytes(s) == 2 * head_and_body


def test_flop_counts_by_hand():
    s = shapes(_json("configs", "granite-8b-l16.json"))
    per_layer = 4096 * (4096 + 2 * 1024) + 4096 * 4096 + 3 * 4096 * 14336
    assert flops.body_flops_per_token(s) == 2 * 16 * per_layer
    # two prompt tokens: the second query sees 2 keys, the first 1
    assert flops.prefill_flops(s, 2) == (2 * flops.body_flops_per_token(s)
                                         + 4 * 16 * 32 * 128 * 3
                                         + 2 * 4096 * 49152)
    assert flops.kv_bytes_per_token(s) == 64 * 1024
    assert flops.decode_bytes(s, 3, [10, 20]) == \
        3 * flops.decode_weight_bytes(s) + 30 * 64 * 1024


# --- trace reduction -------------------------------------------------------


def _recorded():
    with open(os.path.join(BENCH, "testdata", "trace_excerpt.json")) as f:
        return json.load(f)


def test_reduction_of_a_recorded_trace():
    tr = _recorded()
    t0, t1 = tracereduce.window(tr)
    red = tracereduce.reduce(tr, t0, t1)
    ops = tracereduce._clipped(tr["devices"][0]["ops"], t0, t1)
    # busy time is the union: never more than the sum of the ops, nor the
    # window, and at least the longest op
    total = sum(b - a for _, a, b in ops) * 1e-9
    assert max(b - a for _, a, b in ops) * 1e-9 <= red["busy_s"] <= total
    assert red["busy_s"] <= red["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    idle = sum(v for _, v in tracereduce.idle_by_host(
        tr, tr["devices"][0], t0, t1))
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-9)
    assert sum(red["module_s"].values()) <= red["window_s"]
    assert any("_probed_" in m or "_chunk_step" in m for m in red["module_s"])
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_reduction_by_hand():
    ms = 1e6
    tr = {"host": [["bench.window", 0, 100 * ms], ["bench.step", 10 * ms, 30 * ms],
                   ["bench.submit", 60 * ms, 10 * ms]],
          "devices": [{"id": 0,
                       "modules": [["jit__probed_decode(3)", 20 * ms, 20 * ms],
                                   ["jit__chunk_step(9)", 50 * ms, 30 * ms]],
                       "ops": [["fusion.1", 20 * ms, 15 * ms],
                               ["fusion.2", 30 * ms, 10 * ms],
                               ["dot.3", 50 * ms, 30 * ms]]}]}
    red = tracereduce.reduce(tr, *tracereduce.window(tr))
    assert red["busy_s"] == pytest.approx(0.050)
    assert red["module_s"] == pytest.approx({"_probed_decode": 0.020,
                                             "_chunk_step": 0.030})
    assert red["device_ops"][0] == ["_chunk_step/dot.3", pytest.approx(0.030)]
    # idle [0,20) has its middle inside bench.step (10..40); [40,50) and
    # [80,100) lie under no benchmark span
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"bench.step": 0.020, "host.other": 0.030})


# --- finding cells and readers by name --------------------------------------


def test_a_new_cell_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tmp_path
    for d in ("configs", "traffic", "cells", "metrics"):
        (root / "bench" / d).mkdir(parents=True)
    conf = _json("configs", "granite-8b-l16.json")
    conf["name"] = "new-model"
    (root / "bench/configs/new-model.json").write_text(json.dumps(conf))
    mix = _json("traffic", "chat-sat.json")
    mix["pool_size"] = 64
    (root / "bench/traffic/new-mix.json").write_text(json.dumps(mix))
    (root / "bench/cells/new-model.new-mix.json").write_text(
        json.dumps({"clients": 3, "check": {"mean_logit_gap": 1.0}}))
    (root / "bench/metrics/answer.sat.py").write_text(
        "def read(run):\n    return run.slots * 2\n")
    bm = {"configs": [{"name": "new-model",
                       "file": "bench/configs/new-model.json"}],
          "workloads": [{"name": "new-model.new-mix", "config": "new-model",
                         "traffic": "new-mix", "chips": 1}],
          "end_to_end": [{"name": "output_tok_per_s", "unit": "tokens/s"},
                         {"name": "setup_s", "unit": "s"}],
          "per_layer": [{"name": "answer.sat", "unit": "x",
                         "moves": "output_tok_per_s"}]}
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = harness.find_cell("new-model.new-mix", str(root))
    assert cell.conf["name"] == "new-model" and cell.mix["pool_size"] == 64
    assert cell.load["clients"] == 3
    assert [m["name"] for m in cell.per_layer] == ["answer.sat"]
    read = harness.reader("answer.sat", str(root / "bench"))
    assert read(type("R", (), {"slots": 21})) == 42


def test_every_listed_metric_has_a_reader_and_every_cell_its_files():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bm = json.load(f)
    for w in bm["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(harness.reader(m["name"]))


# --- run.py without a chip ------------------------------------------------


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "granite-8b-l16.code-burst", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# --- the correctness check, its control and a planted fault -----------------


def tiny_cell(family: str, mix_name: str = "code-burst", d: int = 64,
              vocab: int = 256, layers: int = 2) -> "harness.Cell":
    """A cell of the given family at toy widths (4 query heads over 2 KV
    heads of ``d / 4``), served on the CPU, under a small copy of the mix
    ``mix_name``."""
    hd, ff = d // 4, 2 * d
    if family == "llama":
        conf = _json("configs", "granite-8b-l16.json")
        conf.update(hidden_size=d, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=hd, intermediate_size=ff,
                    vocab_size=vocab, num_hidden_layers=layers,
                    rope_theta=10000.0)
        over = dict(rope_theta=10000.0)
    else:
        conf = _json("configs", "chatglm3-6b-l20.json")
        conf.update(hidden_size=d, num_attention_heads=4,
                    multi_query_group_num=2, kv_channels=hd,
                    ffn_hidden_size=ff, padded_vocab_size=vocab,
                    num_layers=layers)
        over = {}
    conf["program"]["overrides"].update(
        num_layers=layers, d_model=d, num_heads=4, num_kv_heads=2,
        head_dim=hd, d_ff=ff, vocab_size=vocab, **over)
    conf["engine"].update(slots=4, max_seq=512, chunk_prefill=64)
    mix = _json("traffic", mix_name + ".json")
    mix["prompt"].update(median=150, min=64, max=400)
    mix["output"].update(median=8, min=4, max=16)
    mix["check"] = {"min_tokens": 40, "max_requests": 6}
    if mix["kind"] == "open":
        load = {"rate_per_s": 5.0}
        e2e = [("ttft_p75_ms", "ms"), ("tpot_p75_ms", "ms"), ("setup_s", "s")]
    else:
        mix["pool_size"] = 64
        load = {"clients": 8}
        e2e = [("output_tok_per_s", "tokens/s"), ("setup_s", "s")]
    load["check"] = {"mean_logit_gap": 0.002, "mean_nucleus_gap": 0.002}
    return harness.Cell("tiny", 1, conf, mix, load, [],
                        [{"name": n, "unit": u} for n, u in e2e])


def _run(cell, seed):
    return harness.run_cell(cell, seed, 1.5, False, t_start=time.perf_counter(),
                            peak=None, log=lambda m: None)


@pytest.mark.parametrize("family,mix", [("llama", "code-burst"),
                                        ("chatglm", "code-burst"),
                                        ("chatglm", "chat-sat")])
def test_served_tokens_pass_the_check(family, mix):
    r = _run(tiny_cell(family, mix), 2**31 + 3)
    c = r["checks"]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    numbers = ["mean_logit_gap"] + (["mean_nucleus_gap"] if mix == "chat-sat"
                                    else [])
    assert sorted(c) == numbers
    for k in numbers:
        assert c[k]["value"] <= c[k]["limit"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("family,mix,seed", [("llama", "code-burst", 3),
                                             ("chatglm", "chat-sat", 11)])
def test_the_int8_control_fails_where_bf16_passes(family, mix, seed):
    """The control is the program's own int8 weights and KV pages, at a
    size (4 layers, width 256, 16384 tokens) where int8 rounding moves
    the greedy choice among near-tied logits, as it does at full size."""
    for prec, want in (({}, True), ({"kv_cache_dtype": "int8",
                                     "weight_dtype": "int8"}, False)):
        cell = tiny_cell(family, mix, d=256, vocab=16384, layers=4)
        cell.conf["engine"]["precision"] = prec
        assert _run(cell, seed)["correct"] is want, prec


def test_a_token_altered_where_it_is_produced_fails_the_check(monkeypatch):
    import repro.serving.engine as eng

    real = eng.decode_tick

    def altered(cfg, *a, **k):
        nxt, cache = real(cfg, *a, **k)
        return (nxt + 1) % cfg.vocab_size, cache

    monkeypatch.setattr(eng, "decode_tick", altered)
    r = _run(tiny_cell("llama"), 11)
    assert not r["correct"]
    c = r["checks"]["mean_logit_gap"]
    assert c["value"] > c["limit"]


def _unrestricted(real):
    def sampler(logits, samp, pos):  # top-k and top-p ignored
        return real(logits, dict(samp, top_k=0 * samp["top_k"],
                                 top_p=0 * samp["top_p"] + 1.0), pos)
    return sampler


def _other_lane(real):
    def sampler(logits, samp, pos):  # each row draws from its neighbour's
        return real(jnp.roll(logits, 1, axis=0), samp, pos)
    return sampler


@pytest.mark.parametrize("fault", [_unrestricted, _other_lane])
def test_a_sampler_fault_fails_the_sampled_lane(monkeypatch, fault):
    import repro.serving.engine as eng

    monkeypatch.setattr(eng, "sample_tokens", fault(eng.sample_tokens))
    r = _run(tiny_cell("llama", "chat-sat"), 13)
    assert not r["correct"]
    c = r["checks"]["mean_nucleus_gap"]
    assert c["value"] > c["limit"]


def test_a_wrong_rope_layout_fails_the_check(monkeypatch):
    import weights

    monkeypatch.setattr(weights, "rope_permutation",
                        lambda s: np.arange(s.head_dim))
    cell = copy.deepcopy(tiny_cell("chatglm"))
    assert not _run(cell, 12)["correct"]
