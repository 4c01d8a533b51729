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
CONFIGS = ("granite-8b-l16", "chatglm3-6b-l20", "granite-8b-tp4")
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


@pytest.mark.parametrize("served,distinct,at", [
    ([4, 5] * 10, "0.500", 5),  # a loop: the gap at token 5 recurs
    (list(range(10, 30)), "1.000", 10),
])
def test_look_counts_gapped_tokens_and_their_contexts(served, distinct, at):
    g = np.array([0.0, 0.5] * 10, np.float32)
    assert harness.look(np.array([1, 2, 3], np.int32), served, g) == (
        f"20 tokens, distinct contexts {distinct} of them; 10 with a gap, "
        f"sum 5.0000, widest 0.5000, at {at} distinct contexts")


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


@pytest.mark.parametrize("name", ["trace_excerpt.json",
                                  "scoped_excerpt.json"])
def test_reduction_keeps_its_keys_and_values(name):
    with open(os.path.join(BENCH, "testdata", name)) as f:
        tr = json.load(f)
    before = _json("testdata", "reduced_before_collectives.json")[name]
    red = tracereduce.reduce(tr, *tracereduce.window(tr))
    assert set(red) == set(before) | {"collective_s", "devices_read"}
    assert {k: red[k] for k in before} == before
    # one chip: no collective anywhere
    assert red["collective_s"] == {}


def test_collective_time_by_hand():
    ms = 1e6
    tr = {"host": [["bench.window", 0, 100 * ms]],
          "devices": [{"id": 0,
                       "modules": [["jit__probed_decode(3)", 0, 60 * ms],
                                   ["jit__chunk_step(9)", 60 * ms, 40 * ms]],
                       "ops": [["%while.1", 0, 60 * ms],
                               ["%fusion.1 fusion bf16[8]", 0, 10 * ms],
                               ["%all-gather-start.2", 5 * ms, 1 * ms],
                               ["%fusion.2 fusion bf16[8]", 6 * ms, 10 * ms],
                               ["%all-gather-done.2 all-gather-done "
                                "bf16[8,4096]", 16 * ms, 4 * ms],
                               ["%fusion.3 all-reduce f32[8]", 30 * ms,
                                10 * ms],
                               ["%dot.4 dot f32[8]", 35 * ms, 10 * ms],
                               ["%all-reduce.5 all-reduce f32[8]", 58 * ms,
                                6 * ms]]}]}
    red = tracereduce.reduce(tr, *tracereduce.window(tr))
    # decode: the start (inside fusion.1), the done 16..20 exposed, the
    # fused all-reduce 30..40 exposed 30..35, the all-reduce 58..64 split
    # at the program boundary
    got = {(m, k): v for m, d in red["collective_s"].items()
           for k, v in d.items()}
    assert got == pytest.approx({
        ("_probed_decode", "all"): 0.001 + 0.004 + 0.010 + 0.002,
        ("_probed_decode", "exposed"): 0.004 + 0.005 + 0.002,
        ("_chunk_step", "all"): 0.004, ("_chunk_step", "exposed"): 0.004})
    assert tracereduce.is_collective("%all-reduce-start.3")
    assert tracereduce.is_collective("%fusion.7 reduce-scatter bf16[8]")
    assert not tracereduce.is_collective("%fusion.7 fusion bf16[8]")
    assert not tracereduce.is_collective("%reduce.1 reduce f32[8]")


def test_a_device_whose_record_lost_events_is_left_out():
    ms = 1e6
    tick = [["%fusion.1 fusion bf16[8]", 0, 4 * ms],
            ["%all-reduce.2 all-reduce bf16[8]", 4 * ms, 1 * ms],
            ["%fusion.3 fusion bf16[8]", 5 * ms, 5 * ms]]

    def dev(i, ticks):
        return {"id": i,
                "modules": [["jit__probed_decode(1)", k * 20 * ms, 10 * ms]
                            for k in ticks],
                "ops": [[n, k * 20 * ms + s, d] for k in ticks
                        for n, s, d in tick]}

    tr = {"host": [["bench.window", 0, 100 * ms]],
          "devices": [dev(0, [0, 1, 2]), dev(1, range(5)), dev(2, range(5))]}
    red = tracereduce.reduce(tr, *tracereduce.window(tr))
    assert red["devices_read"] == [1, 2]
    assert red["busy_s"] == pytest.approx(0.050)
    assert red["module_s"] == pytest.approx({"_probed_decode": 0.050})
    assert red["collective_s"]["_probed_decode"]["exposed"] == \
        pytest.approx(0.005)


def _xspace(planes) -> bytes:
    """A profiler ``XSpace`` message: ``planes`` is [(name, {line name:
    [(event name, start_ns, duration_ns)]})]."""
    def varint(n):
        out = bytearray()
        while True:
            b, n = n & 0x7F, n >> 7
            out.append(b | 0x80 if n else b)
            if not n:
                return bytes(out)

    def field(num, val):
        if isinstance(val, int):
            return varint(num << 3) + varint(val)
        val = val.encode() if isinstance(val, str) else val
        return varint(num << 3 | 2) + varint(len(val)) + val

    out = b""
    for pid, (pname, lines) in enumerate(planes):
        ids = {}
        body = field(1, pid) + field(2, pname)
        for lid, (lname, evs) in enumerate(lines.items()):
            line = field(1, lid) + field(2, lname) + field(3, 0)
            for name, s, d in evs:
                mid = ids.setdefault(name, len(ids) + 1)
                line += field(4, field(1, mid) + field(2, s * 1000)
                              + field(3, d * 1000))
            body += field(3, line)
        for name, mid in ids.items():
            body += field(4, field(1, mid) + field(2, field(1, mid)
                                                   + field(2, name)))
        out += field(1, body)
    return out


def test_load_names_the_operations_of_the_device_it_reads(tmp_path):
    tick = [("%fusion.1 = bf16[8]{0} fusion(%a)", 0, 4),
            ("%all-reduce.2 = bf16[8]{0} all-reduce(%b)", 4, 1),
            ("%fusion.3 = bf16[8]{0} fusion(%c)", 5, 5)]

    def chip(ticks):
        return {"XLA Modules": [("jit__probed_decode(1)", 20 * k, 10)
                                for k in ticks],
                "XLA Ops": [(n, 20 * k + s, d) for k in ticks
                            for n, s, d in tick]}

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace([
        ("/device:TPU:0", chip([0, 1])), ("/device:TPU:1", chip(range(4))),
        ("/host:CPU", {"main": [("bench.window", 0, 80),
                                ("other", 10, 5)]})]))
    tr = tracereduce.load(str(path))
    assert [d["id"] for d in tr["devices"]] == [0, 1]
    assert {n for n, _, _ in tr["devices"][0]["ops"]} == {None}
    assert [n for n, _, _ in tr["devices"][1]["ops"]][:3] == \
        [n for n, _, _ in tick]
    assert tr["host"] == [("bench.window", 0, 80)]
    every = tracereduce.load(str(path), every_name=True)
    assert {n for n, _, _ in every["devices"][0]["ops"]} == \
        {n for n, _, _ in tick}
    window = tracereduce.window(tr)
    red = tracereduce.reduce(tr, *window)
    assert red == tracereduce.reduce(every, *window)
    assert red["devices_read"] == [1]
    assert red["device_ops"][0][0] == "_probed_decode/%fusion.3 fusion bf16[8]"


def test_one_tp4_decode_tick_of_a_recorded_trace():
    """A decode tick of granite-8b-tp4 on chip 0 of a v5e 2x2 host: two
    all-reduces in each of its 36 layers (the attention output and the
    MLP's down projection, whose inputs are split over the chips and
    whose weights are whole on each), one for the embedding lookup from
    the vocabulary-split table, and the tied head's all-gather of that
    table as an asynchronous collective fusion."""
    import types

    tr = _json("testdata", "tp4_tick_excerpt.json")
    ops = tr["devices"][0]["ops"]
    names = [n.split(" ")[0].rsplit(".", 1)[0] for n, _, _ in ops
             if tracereduce.is_collective(n)]
    assert names.count("%all-reduce") == 2 * 36 + 1
    assert {n for n in names if n != "%all-reduce"} == {
        "%async-collective-start", "%async-collective-done"}
    red = tracereduce.reduce(tr, *tracereduce.window(tr))
    tick = red["module_s"]["_probed_decode"]
    c = red["collective_s"]["_probed_decode"]
    # nothing else runs while the collectives run on this chip
    assert c["exposed"] == pytest.approx(c["all"])
    assert c["all"] == pytest.approx(
        sum(d for n, _, d in ops if tracereduce.is_collective(n)) * 1e-9)
    share = harness.reader("collective_share.sat")(
        types.SimpleNamespace(trace=red))
    assert share == pytest.approx(100 * c["exposed"] / tick)
    assert 5.0 < share < 8.0


def test_collective_share_reads_the_decode_programs_only():
    import types

    read = harness.reader("collective_share.sat")
    trace = {"module_s": {"_probed_decode": 0.5, "_probed_scan": 1.5,
                          "_chunk_step": 1.0},
             "collective_s": {"_probed_scan": {"all": 0.3, "exposed": 0.1},
                              "_chunk_step": {"all": 0.5, "exposed": 0.5}}}
    assert read(types.SimpleNamespace(trace=trace)) == pytest.approx(5.0)
    trace["collective_s"].pop("_probed_scan")
    assert read(types.SimpleNamespace(trace=trace)) is None
    assert read(types.SimpleNamespace(trace=None)) is None


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


def test_every_cell_asks_for_the_chips_its_engine_spans():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bm = json.load(f)
    for w in bm["workloads"]:
        cell = harness.find_cell(w["name"])
        assert harness.topology(cell.conf).n_chips == cell.chips, w["name"]


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


@pytest.mark.parametrize("seed", [3, 11])
def test_the_int8_reference_control_fails_the_check(seed):
    """The control of a cell whose engine refuses int8 weights: the
    reference with int8 weights in the program's place, at the positions
    of served requests (``control.py``). Judged without a window, on
    requests drawn from the seed: the sound served tokens of this tiny
    cell read under 0.001 (``test_served_tokens_pass_the_check``)."""
    import types

    cell = tiny_cell("chatglm", "chat-sat", d=256, vocab=16384, layers=4)
    sample = [types.SimpleNamespace(
        spec=types.SimpleNamespace(rid=rid, sampled=bool(rid % 2)),
        req=types.SimpleNamespace(
            prompt=traffic.prompt_tokens(seed, rid, 300, 16384),
            output=list(traffic.prompt_tokens(seed, 100 + rid, 60, 16384))))
        for rid in range(4)]
    gaps, n = harness.check(cell.conf, cell.mix, seed, sample, control=True)
    assert n == 4 * 60
    limit = cell.load["check"]["mean_logit_gap"]
    assert gaps["mean_logit_gap"][0] > limit


def test_a_sharded_engine_with_int8_weights_is_refused_before_any_draw():
    cell = tiny_cell("llama", "chat-sat")
    cell.conf["engine"].update(topology={"tp": 4}, precision={
        "kv_cache_dtype": "int8", "weight_dtype": "int8"})
    with pytest.raises(SystemExit, match="int8ref"):
        harness.build(cell.conf, 1, False)


@pytest.mark.parametrize("part_s", [None, 0.8])
def test_a_traced_run_profiles_and_counts_the_same_span(monkeypatch, part_s):
    """Without ``trace_seconds`` the profile spans the window and the
    readers get the window's work; with it, the profiler starts when that
    many seconds of the window are left, and the readers get the work,
    host seconds and page shares of that part alone. The profile is
    recorded (on the CPU) and its ``bench.window`` read back."""
    import glob

    import jax

    cell = tiny_cell("llama", "chat-sat")
    if part_s:
        cell.load["trace_seconds"] = part_s
    seconds, seen, starts = 2.0, {}, []
    real_window, real_start = harness.run_window, jax.profiler.start_trace

    def run_window(d, *a, **k):
        seen["d"] = d
        seen["out"] = real_window(d, *a, **k)
        return seen["out"]

    def start_trace(path):
        starts.append(time.perf_counter())
        real_start(path)

    def per_layer(cell, span, window, prof_dir, peak, slots):
        path, = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                          recursive=True)
        tr = tracereduce.load(path)
        t0, t1 = tracereduce.window(tr)
        seen.update(span=span, traced_s=(t1 - t0) * 1e-9, steps=sum(
            1 for n, s, _ in tr["host"] if n == "bench.step" and t0 <= s < t1))
        return {"metrics": {}, "busy_s": 1.0, "trace_window_s": 1.0,
                "devices_read": [0], "breakdown": {}}

    monkeypatch.setattr(harness, "run_window", run_window)
    monkeypatch.setattr(harness, "per_layer", per_layer)
    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    r = harness.run_cell(cell, 2**31 + 9, seconds, True,
                         t_start=time.perf_counter(), peak=None,
                         log=lambda m: None)
    d, out, span = seen["d"], seen["out"], seen["span"]
    assert r["correct"] and len(starts) == 1
    assert seen["traced_s"] == pytest.approx(span["window_s"], abs=0.01)
    assert abs(seen["steps"] - len(span["pages_share"])) <= 1
    whole = d.work
    if not part_s:
        assert starts[0] < d.t0 and out["part"] is None
        assert span["work"] is whole and span["window_s"] == out["window_s"]
        assert span["pages_share"] == d.pages_share
        return
    assert starts[0] - d.t0 >= seconds - part_s
    # less the profiler's start, plus the step that crosses the close
    assert part_s / 2 < span["window_s"] < part_s + 0.3
    assert 0 < span["work"]["ticks"] < whole["ticks"]
    assert 0 < span["work"]["output_tokens"] < whole["output_tokens"]
    assert 0 < span["work"]["decode_bytes"] < whole["decode_bytes"]
    assert 0 < len(span["pages_share"]) < len(d.pages_share)


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

    monkeypatch.setattr(weights.family("chatglm"), "rope_permutation",
                        lambda s: np.arange(s.head_dim))
    cell = copy.deepcopy(tiny_cell("chatglm"))
    assert not _run(cell, 12)["correct"]
