"""CPU tests of ``scopes.py``: reading each operation's ``op_name`` out of
a profile, booking decode time by model scope, putting idle gaps down to
the innermost engine phase, and the readings, on hand-made traces, on a
CPU profile and on a recorded chip trace."""
from __future__ import annotations

import copy
import glob
import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "metrics"))

import harness  # noqa: E402
import scopes  # noqa: E402
import tracereduce  # noqa: E402

MS = 1e6


def _recorded(name):
    with open(os.path.join(BENCH, "testdata", name)) as f:
        return json.load(f)


# --- op_name out of the profile ----------------------------------------------


def test_op_names_are_read_from_the_profiles_hlo(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, w):
        with jax.named_scope("mlp"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("attn_core"):
            y = jax.lax.cond(y[0, 0] > 0, lambda v: v * 2.0,
                             lambda v: v - 1.0, y)
        return jnp.sum(y)

    x = jnp.ones((64, 64))
    f(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x, x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = scopes.program_op_names(path)
    prog = [p for p in names if tracereduce.module_name(p) == "f"]
    assert len(prog) == 1
    ops = names[prog[0]]
    found = {scopes.scope_of(op) for op in ops.values() if op is not None}
    assert {"mlp", "attn_core", scopes.UNSCOPED} <= found
    # the conditional runs the instructions of its branches: no op_name
    conds = [k for k, op in ops.items() if op is None]
    assert conds and all("cond" in k for k in conds)


def test_scope_of_takes_the_innermost_model_scope():
    assert scopes.scope_of("jit(_probed_scan)/while/body/attn_core/"
                           "mlp/dot_general") == "mlp"
    assert scopes.scope_of("jit(_probed_decode)/while/body/add") == \
        scopes.UNSCOPED
    assert scopes.scope_of("") == scopes.UNSCOPED


def _hand_trace():
    """A decode program of six operations (a ``while`` and a
    ``conditional`` contain others), a prefill program, and the engine's
    phases over a step."""
    return {
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.step", 0, 100 * MS]],
        "engine": [["engine.step", 1 * MS, 98 * MS],
                   ["engine.admit", 2 * MS, 8 * MS],
                   ["engine.dispatch", 12 * MS, 5 * MS],
                   ["engine.sync", 18 * MS, 50 * MS]],
        "devices": [{"id": 0,
                     "modules": [["jit__chunk_step(5)", 3 * MS, 6 * MS],
                                 ["jit__probed_decode(8)", 15 * MS, 40 * MS]],
                     "ops": [["%fusion.2 = bf16[8]{0} fusion()", 3 * MS,
                              6 * MS],
                             ["%while.1 = (s32[]) while()", 15 * MS, 40 * MS],
                             ["%fusion.2 = bf16[4]{0} fusion()", 15 * MS,
                              10 * MS],
                             ["%copy.3 = bf16[4]{0} copy()", 25 * MS, 5 * MS],
                             ["%fusion.7 = bf16[4]{0} fusion()", 30 * MS,
                              20 * MS],
                             ["%cond.4 = (s32[8]{0}) conditional()", 50 * MS,
                              5 * MS],
                             ["%fusion.9 = s32[8]{0} fusion()", 50 * MS,
                              4 * MS]]}]}


NAMES = {"jit__chunk_step(5)": {"fusion.2": "jit(_chunk_step)/mlp/dot"},
         "jit__probed_decode(8)": {
             "fusion.2": "jit(_probed_decode)/while/body/attn_core/dot",
             "copy.3": "",
             "fusion.7": "jit(_probed_decode)/while/body/mlp/dot",
             "cond.4": None,
             "fusion.9": "jit(_probed_decode)/sampler/argmax"}}


def test_operations_are_named_by_their_program_and_instruction():
    tr = scopes.with_scopes(_hand_trace(), NAMES)
    got = [p for p, _, _ in tr["devices"][0]["op_scopes"]]
    assert got == ["jit(_chunk_step)/mlp/dot", "",
                   "jit(_probed_decode)/while/body/attn_core/dot", "",
                   "jit(_probed_decode)/while/body/mlp/dot", None,
                   "jit(_probed_decode)/sampler/argmax"]
    # the metadata's program id differs from the execution's: the one
    # program of that function name is taken
    renamed = {"jit__probed_decode(99)": NAMES["jit__probed_decode(8)"]}
    tr = scopes.with_scopes(_hand_trace(), renamed)
    assert tr["devices"][0]["op_scopes"][4][0].endswith("/mlp/dot")


def test_decode_time_is_booked_by_scope():
    tr = scopes.with_scopes(_hand_trace(), NAMES)
    split = scopes.decode_scope_s(tr, 0, 100 * MS)
    # the chunk step's mlp is prefill; the while and the conditional
    # contain the others
    assert split["seconds"] == pytest.approx(
        {"attn_core": 0.010, "mlp": 0.020, "sampler": 0.004,
         scopes.UNSCOPED: 0.005})
    assert split["top"][scopes.UNSCOPED] == [
        ["_probed_decode/%copy.3 copy bf16[4]", pytest.approx(0.005)]]


def test_a_trace_without_scoped_decode_ops_reads_none():
    tr = scopes.with_scopes(_hand_trace(), {})
    assert scopes.decode_scope_s(tr, 0, 100 * MS) is None
    tr = _recorded("trace_excerpt.json")  # recorded before the scopes
    t0, t1 = tracereduce.window(tr)
    assert scopes.decode_scope_s(tr, t0, t1) is None
    got = scopes.readings(None, None, (t1 - t0) * 1e-9, 10, {})
    assert got == dict.fromkeys(got)


def test_an_idle_gap_goes_to_the_innermost_engine_phase():
    tr = scopes.with_scopes(_hand_trace(), NAMES)
    idle = dict(scopes.idle_by_phase(tr, 0, 100 * MS))
    # the gaps' middles: [0,3) 1.5 ms in engine.step; [9,15) 12 ms in
    # engine.dispatch; [55,100) 77.5 ms in engine.step, past engine.sync
    assert idle == pytest.approx({"engine.step": 0.048,
                                  "engine.dispatch": 0.006})
    # without the engine's annotations the same gaps fall to bench.step
    bare = dict(scopes.idle_by_phase({**tr, "engine": []}, 0, 100 * MS))
    assert bare == pytest.approx({"bench.step": 0.054})


def test_readings_by_hand():
    tr = scopes.with_scopes(_hand_trace(), NAMES)
    split = scopes.decode_scope_s(tr, 0, 100 * MS)
    idle = scopes.idle_by_phase(tr, 0, 100 * MS)
    got = scopes.readings(split, idle, 0.1, 5,
                          {"decode_ticks": 5, "fused_ticks": 4})
    assert got == pytest.approx({
        "decode_attn_ms_per_tick": 2.0, "decode_kv_write_ms_per_tick": 0.0,
        "decode_weights_ms_per_tick": 4.0, "sampler_ms_per_tick": 0.8,
        "fused_tick_share": 80.0, "engine_idle_share": 54.0})


# --- a recorded chip trace -----------------------------------------------------


def _readers_on(tr):
    """Every per-layer metric of ``BENCHMARK.json`` read from ``tr``'s
    window, with a fixed window's work."""
    t0, t1 = tracereduce.window(tr)
    run = types.SimpleNamespace(
        trace=tracereduce.reduce(tr, t0, t1), window_s=(t1 - t0) * 1e-9,
        work=dict(prefill_flops=4e12, decode_flops=2e12, decode_bytes=5e10,
                  decode_tokens=40, prompt_tokens=3000, output_tokens=48,
                  ticks=5),
        peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, chips=1,
        slots=8, queue_waits_ms=[1.0, 2.0], pages_share=[0.5])
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        metrics = [m["name"] for m in json.load(f)["per_layer"]]
    return {m: harness.reader(m)(run) for m in metrics}


@pytest.mark.parametrize("name", ["trace_excerpt.json", "scoped_excerpt.json"])
def test_existing_readers_ignore_the_engine_phases_and_scopes(name):
    tr = _recorded(name)
    bare = copy.deepcopy(tr)
    bare.pop("engine", None)
    for dev in bare["devices"]:
        dev.pop("op_scopes", None)
    t0, t1 = tracereduce.window(tr)
    assert tracereduce.reduce(tr, t0, t1) == tracereduce.reduce(bare, t0, t1)
    assert _readers_on(tr) == _readers_on(bare)


def test_a_recorded_decode_tick_adds_up_by_scope():
    tr = _recorded("scoped_excerpt.json")
    t0, t1 = tracereduce.window(tr)
    tick = tracereduce.reduce(tr, t0, t1)["module_s"]["_probed_decode"]
    split = scopes.decode_scope_s(tr, t0, t1)["seconds"]
    assert set(split) == set(scopes.SCOPES) | {scopes.UNSCOPED}
    assert sum(split.values()) == pytest.approx(tick, rel=0.005)
    assert max(split, key=split.get) == "attn_core"
    # counting the sampler's conditionals as well as what they run would
    # book the sampler twice
    flat = copy.deepcopy(tr)
    for dev in flat["devices"]:
        dev["op_scopes"] = [(p or "jit(_probed_decode)/sampler/cond", s, d)
                            for p, s, d in dev["op_scopes"]]
    twice = scopes.decode_scope_s(flat, t0, t1)["seconds"]
    assert sum(twice.values()) > 1.02 * tick


def test_recorded_idle_goes_to_the_innermost_engine_phase():
    tr = _recorded("scoped_excerpt.json")
    t0, t1 = tracereduce.window(tr)
    assert [k for k, _ in scopes.idle_by_phase(tr, t0, t1)] == ["engine.sync"]
    bare = {**tr, "engine": []}
    assert [k for k, _ in scopes.idle_by_phase(bare, t0, t1)] == ["bench.step"]
