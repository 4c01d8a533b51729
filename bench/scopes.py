#!/usr/bin/env python3
"""Split a cell's decode tick by model scope and its device idle time by
engine phase, from one profiled window.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s> \\
        [--excerpt <file.json>]

The program names its work on the profiler's clock: ``jax.named_scope``
puts ``attn_qkv``, ``attn_kv_write``, ``attn_core``, ``attn_out``,
``mlp``, ``lm_head`` and ``sampler`` into each XLA operation's
``op_name``, and an engine built with ``tracing=True`` runs each step
under ``engine.step`` and marks its phases (``engine.reap``, ``.admit``,
``.prefill_chunks``, ``.pages``, ``.dispatch``, ``.sync``,
``.deliver``). This script runs the cell's window as a ``--trace 1``
benchmark run does, with the engine's own annotations, and prints one
JSON line: the benchmark's per-layer metrics of that window, the decode
programs' device seconds by scope (``unscoped`` for what no scope
claims), the device's idle seconds by the innermost host annotation
over each gap, the engine's counters over the window, and what
``readings`` makes of them. ``--excerpt`` writes a few decode ticks of
the trace in the reduced form, for tests. The benchmark's own runs do
not call this script.

Where XLA fuses across scopes, a fusion carries its root instruction's
``op_name`` and is booked to that scope. A persistent compilation cache
keys programs with their debug information stripped, so an executable
compiled from unscoped programs serves scoped ones too, and its
operations carry no scope: the readings are then None.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tracereduce  # noqa: E402
from metriclib import DECODE_PROGRAMS, share  # noqa: E402

#: the program's model scopes (``op_name`` path components)
SCOPES = ("attn_qkv", "attn_kv_write", "attn_core", "attn_out", "mlp",
          "lm_head", "sampler")
#: scopes whose time is reading the weights (projections, MLP, head)
WEIGHT_SCOPES = ("attn_qkv", "attn_out", "mlp", "lm_head")
UNSCOPED = "unscoped"
#: prefix of the engine's phase annotations on the host
PHASE = "engine."
#: the statistic of the profile's ``/host:metadata`` plane that holds each
#: compiled program's HLO, whose instructions carry their ``op_name``
HLO_STAT = "Hlo Proto"

# ---------------------------------------------------------------------------
# reading op_name from the profile
# ---------------------------------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int = 0) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for varint
    and fixed fields, a memoryview for length-delimited ones."""
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _str(v) -> str:
    return bytes(v).decode()


#: HLO opcodes of instructions that run others (a scanned layer stack's
#: ``while``, a ``lax.cond``): their time is that of the instructions
#: they run, which the profile lists themselves
CONTAINERS = ("while", "conditional", "call")


def _hlo_op_names(hlo_proto) -> Dict[str, Optional[str]]:
    """Instruction name -> ``op_name`` over every computation of an
    ``xla.HloProto`` (module 1; its computations 3; their instructions 2,
    each with name 1, opcode 2 and ``OpMetadata`` 7, whose op_name is 2);
    None for an instruction that contains others."""
    out = {}
    for k, module in _fields(hlo_proto):
        if k != 1:
            continue
        for kk, comp in _fields(module):
            if kk != 3:
                continue
            for kkk, ins in _fields(comp):
                if kkk != 2:
                    continue
                name, op, code = "", "", ""
                for f, v in _fields(ins):
                    if f == 1:
                        name = _str(v)
                    elif f == 2:
                        code = _str(v)
                    elif f == 7:
                        op = next((_str(x) for g, x in _fields(v) if g == 2),
                                  "")
                out[name] = None if code in CONTAINERS else op
    return out


def program_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """Per compiled program, named as the device's ``XLA Modules`` line
    names its executions (``jit__probed_decode(<id>)``): instruction name
    -> ``op_name``. Read from the ``HLO_STAT`` statistic of the event
    metadata of the profile's ``/host:metadata`` plane, in the profile's
    ``XSpace`` message (planes 1; a plane's name 2, event metadata 4, stat
    metadata 5): ``ProfileEvent.stats`` lists an event's own statistics
    only, and an XLA operation's own carry no ``op_name``."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    for num, plane in _fields(buf):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if next((_str(v) for k, v in fields if k == 2), "") != \
                "/host:metadata":
            continue
        stat_ids = set()
        for k, v in fields:
            for kk, m in _fields(v) if k == 5 else ():
                if kk == 2:  # map value: XStatMetadata (id 1, name 2)
                    st = dict(_fields(m))
                    if _str(st.get(2, b"")) == HLO_STAT:
                        stat_ids.add(st.get(1, 0))
        out = {}
        for k, v in fields:
            for kk, m in _fields(v) if k == 4 else ():
                if kk != 2:  # map value: XEventMetadata (name 2, stats 5)
                    continue
                name, ops = "", {}
                for f, x in _fields(m):
                    if f == 2:
                        name = _str(x)
                    elif f == 5:  # XStat: metadata_id 1, bytes_value 6
                        st = dict(_fields(x))
                        if st.get(1) in stat_ids and 6 in st:
                            ops = _hlo_op_names(st[6])
                out[name] = ops
        return out
    return {}


def _instruction(op: str) -> str:
    """``%fusion.4 = bf16[8]{0} fusion(...)`` -> ``fusion.4``."""
    return op.split(" ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """``tracereduce.load`` of the profile, with, per device, ``op_scopes``
    parallel to ``ops``: (op_name, start_ns, duration_ns), the op_name ""
    where the program or the instruction has none and None where the
    instruction contains others; and ``engine``, the engine's own host
    annotations (``engine.*``)."""
    from jax.profiler import ProfileData

    tr = tracereduce.load(path, every_name=True)
    tr["engine"] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                tr["engine"] += [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events
                                 if e.name.startswith(PHASE)]
    return with_scopes(tr, program_op_names(path))


def with_scopes(tr: dict, names: Dict[str, Dict[str, str]]) -> dict:
    """Give each device of ``tr`` its ``op_scopes``: every operation's
    op_name, looked up in ``names`` (``program_op_names``) by the program
    it ran in and its instruction name."""
    # a program the metadata names otherwise is found by its function's
    # name where only one program has it
    by_fn = defaultdict(list)
    for prog, ops in names.items():
        by_fn[tracereduce.module_name(prog)].append(ops)
    for dev in tr["devices"]:
        mods = sorted(dev["modules"], key=lambda e: e[1])
        starts = [m[1] for m in mods]
        found = {}
        for name, _, _ in mods:
            fn = by_fn.get(tracereduce.module_name(name), [])
            found[name] = names.get(name, fn[0] if len(fn) == 1 else {})
        scoped = []
        for op, s, d in dev["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            ops = (found[mods[i][0]]
                   if i >= 0 and s < mods[i][1] + mods[i][2] else {})
            scoped.append((ops.get(_instruction(op), ""), s, d))
        dev["op_scopes"] = scoped
    return tr


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def scope_of(op_name: str) -> str:
    """The innermost model scope among ``op_name``'s path components."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def owned_ops(dev: dict, t0: float, t1: float) -> List[tuple]:
    """(program, op, start, end, op_name) of each operation in the window,
    its program found as ``tracereduce.reduce`` finds it; operations that
    contain others (by their HLO opcode, or by name where the profile
    holds no HLO) are left out, as their bodies' operations are listed
    themselves."""
    mods = sorted(tracereduce._clipped(dev["modules"], t0, t1),
                  key=lambda e: e[1])
    scoped = dev.get("op_scopes") or [("", s, d) for _, s, d in dev["ops"]]
    ops = []
    for (name, s, d), (path, _, _) in zip(dev["ops"], scoped):
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            ops.append((tracereduce.op_name(name), a, b, path))
    ops.sort(key=lambda e: e[1])
    out, j = [], 0
    for name, a, b, path in ops:
        if path is None or name.startswith(tracereduce.CONTAINERS):
            continue
        while j < len(mods) and mods[j][2] <= a:
            j += 1
        owner = (tracereduce.module_name(mods[j][0])
                 if j < len(mods) and mods[j][1] <= a else "?")
        out.append((owner, name, a, b, path))
    return out


def decode_scope_s(tr: dict, t0: float, t1: float, top: int = 3) -> dict:
    """Device seconds of the decode programs on the first device whose
    record is whole (``tracereduce.whole_records``) by model scope
    (``unscoped`` included), and the ``top`` longest operations of each,
    or None where no decode operation carries any scope."""
    by: Dict[str, float] = defaultdict(float)
    ops: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    dev = tracereduce.whole_records(tr, t0, t1)[0]
    for owner, name, a, b, path in owned_ops(dev, t0, t1):
        if not any(p in owner for p in DECODE_PROGRAMS):
            continue
        scope = scope_of(path)
        by[scope] += (b - a) * 1e-9
        ops[scope][f"{owner}/{name}"] += (b - a) * 1e-9
    if not set(by) - {UNSCOPED}:
        return None
    return {"seconds": dict(by),
            "top": {k: sorted(([n, s] for n, s in v.items()),
                              key=lambda kv: -kv[1])[:top]
                    for k, v in ops.items()}}


def idle_by_phase(tr: dict, t0: float, t1: float) -> List[list]:
    """Idle seconds of the first device whose record is whole by the
    innermost host annotation, benchmark's or engine's, over each gap (all
    labels, longest first)."""
    host = {"host": tr["host"] + tr.get("engine", [])}
    return tracereduce.idle_by_host(
        host, tracereduce.whole_records(tr, t0, t1)[0], t0, t1)


def readings(split: Optional[dict], idle: Optional[List[list]],
             window_s: float, ticks: int, counters: dict) -> dict:
    """What the scope and phase split reads in a window: per decode tick,
    the device milliseconds of ``attn_core``, ``attn_kv_write``, the
    weight-reading scopes and the ``sampler`` (from ``decode_scope_s``);
    the share of decode ticks run in the fused scan (from the engine's
    counters); and the share of the window in which the device idled
    under an engine phase (from ``idle_by_phase``, None for a trace with
    no engine annotation). None where there is nothing to read."""
    sec = split["seconds"] if split else {}

    def per_tick(*scopes):
        if not split or not ticks:
            return None
        return sum(sec.get(s, 0.0) for s in scopes) * 1e3 / ticks

    engine_idle = None
    if idle is not None:
        engine_idle = sum(s for k, s in idle if k.startswith(PHASE))
    return {
        "decode_attn_ms_per_tick": per_tick("attn_core"),
        "decode_kv_write_ms_per_tick": per_tick("attn_kv_write"),
        "decode_weights_ms_per_tick": per_tick(*WEIGHT_SCOPES),
        "sampler_ms_per_tick": per_tick("sampler"),
        "fused_tick_share": share(counters.get("fused_ticks"),
                                  counters.get("decode_ticks")),
        "engine_idle_share": share(engine_idle, window_s),
    }


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

#: ServeMetrics counters whose change over the window is reported
COUNTERS = ("decode_ticks", "fused_ticks", "host_syncs", "prefill_chunks",
            "completed")


def excerpt(tr: dict, t0: float, t1: float) -> dict:
    """``tr`` cut to [t0, t1): every event that starts inside it, of the
    first device whose record is whole, with a ``bench.window`` of that
    span."""

    def cut(evs):
        return [list(e) for e in evs if t0 <= e[1] < t1]

    devs = [{"id": d["id"], "modules": cut(d["modules"]),
             "ops": [[tracereduce.op_name(n), s, d_]
                     for n, s, d_ in cut(d["ops"])],
             "op_scopes": cut(d["op_scopes"])}
            for d in tracereduce.whole_records(tr, t0, t1)[:1]]
    host = [list(e) for e in tr["host"]
            if e[0] != "bench.window" and e[1] < t1 and e[1] + e[2] > t0]
    return {"devices": devs, "host": host + [["bench.window", t0, t1 - t0]],
            "engine": [list(e) for e in tr["engine"]
                       if e[1] < t1 and e[1] + e[2] > t0]}


def _decode_excerpt_span(tr: dict, t0: float, t1: float, n: int = 3):
    """[start, end) of ``n`` decode programs back to back from the middle
    of the window."""
    dev = tracereduce.whole_records(tr, t0, t1)[0]
    mods = sorted((e for e in dev["modules"]
                   if t0 <= e[1] and e[1] + e[2] <= t1
                   and any(p in e[0] for p in DECODE_PROGRAMS)),
                  key=lambda e: e[1])
    mid = mods[len(mods) // 2:][:n]
    return mid[0][1], mid[-1][1] + mid[-1][2] + 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--excerpt", default="")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import harness

    cell = harness.find_cell(args.workload)
    harness.use_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit("scopes: no TPU")
    with open(os.path.join(harness.BENCH, "peaks.json")) as f:
        peak = json.load(f)["devices"][devs[0].device_kind]

    def log(m):
        print(m, file=sys.stderr, flush=True)

    engine, s = harness.build(cell.conf, args.seed, True)
    harness.warm_up(engine, s, cell.mix, cell.conf, args.seed)
    d = harness.Client(engine, s, args.seed, cell.mix)
    prof = tempfile.mkdtemp(prefix="bench-scopes-")
    jax.profiler.start_trace(prof)
    d.annotate = True
    ann = jax.profiler.TraceAnnotation("bench.window")
    m0 = {k: getattr(engine.metrics, k) for k in COUNTERS}
    counters = {}

    def close():
        ann.__exit__(None, None, None)
        counters.update({k: getattr(engine.metrics, k) - m0[k]
                         for k in COUNTERS})

    log(f"set-up: {time.perf_counter() - t_start:.1f} s")
    ann.__enter__()
    out = harness.run_window(d, cell, args.seconds, on_close=close)
    jax.profiler.stop_trace()
    d.annotate = False
    path = glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                     recursive=True)[0]
    t_load = time.perf_counter()
    tr = load(path)
    log(f"profile {os.path.getsize(path) / 1e6:.1f} MB loaded in "
        f"{time.perf_counter() - t_load:.1f} s")
    t0, t1 = tracereduce.window(tr)
    named = sum(bool(p) for p, _, _ in tr["devices"][0]["op_scopes"])
    log(f"{named} of {len(tr['devices'][0]['ops'])} device ops have an "
        f"op_name")
    bench = harness.per_layer(cell, d, out, prof, peak, engine.slots)
    shutil.rmtree(prof, ignore_errors=True)
    red = tracereduce.reduce(tr, t0, t1)
    ticks = d.work["ticks"]
    split = decode_scope_s(tr, t0, t1)
    idle = idle_by_phase(tr, t0, t1)
    if split is None:
        log("no decode operation carries a model scope: were the programs "
            "compiled from unscoped ones (a persistent cache entry)?")
    result = {
        "workload": args.workload, "seed": args.seed,
        "per_layer": {k: v["value"] for k, v in bench["metrics"].items()},
        "breakdown": bench["breakdown"],
        "ticks": ticks, "counters": counters,
        "decode_program_s": {k: v for k, v in red["module_s"].items()
                             if any(p in k for p in DECODE_PROGRAMS)},
        "decode_scope_s": split,
        "idle_by_phase": idle,
        "busy_s": red["busy_s"], "window_s": red["window_s"],
        "generator_late_max_s": max(out["lates"], default=None),
        "readings": readings(split, idle if tr["engine"] else None,
                             red["window_s"], ticks, counters),
    }
    if args.excerpt:
        a, b = _decode_excerpt_span(tr, t0, t1)
        with open(args.excerpt, "w") as f:
            json.dump(excerpt(tr, a, b), f)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
