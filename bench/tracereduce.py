"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` turns the profiler's ``.xplane.pb`` into a small plain form:
per device, the executions of each compiled program (``modules``) and of
each operation (``ops``) as (name, start_ns, duration_ns), an operation's
name on one device only; and the benchmark's own host annotations
(``bench.*``). ``reduce`` takes that
form and a window on the trace's clock. Tests feed it a recorded trace.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load(path: str, every_name: bool = False) -> dict:
    """The plain form of the profile at ``path``. An operation's name is
    read only on the device whose names ``reduce`` reads, the first
    whose record of the ``bench.window`` is whole (``whole_records``),
    and is None on the others, unless ``every_name``: a four-chip window
    holds about ten million operations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, op_lines = [], [], {}
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = {"id": int(m.group(1)), "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    op_lines[dev["id"]] = line
                    dev["ops"] = [(e.name if every_name else None,
                                   e.start_ns, e.duration_ns)
                                  for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name.startswith("bench.")]
    devices.sort(key=lambda d: d["id"])
    tr = {"devices": devices, "host": host}
    if not every_name and op_lines:
        has_window = any(e[0] == "bench.window" for e in host)
        named = (whole_records(tr, *window(tr))[:1] if has_window
                 else devices)
        for dev in named:
            if dev["id"] in op_lines:
                dev["ops"] = [(e.name, e.start_ns, e.duration_ns)
                              for e in op_lines[dev["id"]].events]
    return tr


def module_name(name: str) -> str:
    """``jit__probed_decode(123)`` -> ``_probed_decode``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"^jit_", "", name)


#: operations whose time is that of the operations they run (a scanned
#: layer stack is one ``while``)
CONTAINERS = ("%while", "%conditional", "%call")


def op_name(hlo: str) -> str:
    """``%copy.4 = bf16[8,128]{1,0:T(8,128)} copy(%x)`` -> ``%copy.4 copy
    bf16[8,128]``: the operation, its opcode and its output shape."""
    name, _, rest = hlo.partition(" = ")
    if not rest or rest.startswith("("):  # a tuple result: the name alone
        return name
    shape, _, rest = rest.partition(" ")
    return f"{name} {rest.split('(', 1)[0]} {shape.split('{', 1)[0]}"


def window(tr: dict, name: str = "bench.window") -> Tuple[float, float]:
    ev = [e for e in tr["host"] if e[0] == name]
    if not ev:
        raise ValueError(f"no {name} annotation in the trace")
    _, t0, d = max(ev, key=lambda e: e[2])
    return t0, t0 + d


def _clipped(events: List[Event], t0: float, t1: float) -> List[tuple]:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals: List[tuple]) -> List[Tuple[float, float]]:
    """Merged (start, end) of (name, start, end) intervals."""
    merged: List[List[float]] = []
    for _, a, b in sorted(intervals, key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _busy_events(dev: dict) -> List[Event]:
    return dev["ops"] or dev["modules"]


#: opcodes of the operations that move data between chips, synchronous
#: or as the start and done of an asynchronous one
#: (``all-gather-start`` ...); an instruction is named after its opcode.
#: The TPU compiler also runs a collective as a pair of fusions named
#: ``async-collective-start`` / ``-done`` (the tp=4 tick's all-gather of
#: the vocabulary-split embedding for the tied head)
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all", "async-collective")


def is_collective(op: str) -> bool:
    """Whether an ``op_name`` (``%name opcode shape``, or ``%name`` alone
    for a tuple result) is a collective, by its opcode or by its name."""
    parts = op.split(" ")
    words = [parts[0].lstrip("%")] + parts[1:2]
    return any(w.startswith(COLLECTIVES) for w in words)


def _minus(a: List[Tuple[float, float]], b: List[Tuple[float, float]]):
    """Merged intervals ``a`` less merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if e > s:
            out.append((s, e))
    return out


def collective_s(ops: List[tuple], mods: List[tuple]) -> Dict[str, dict]:
    """Per program: the seconds in which a collective ran (``all``), and
    of those the seconds in which no other operation ran (``exposed``).
    ``ops`` are (``op_name``, start, end) of the operations that contain no
    others, ``mods`` (name, start, end) of the programs sorted by start,
    both clipped to the window."""
    kind: Dict[str, bool] = {}
    coll, other = [], []
    for e in ops:
        c = kind.get(e[0])
        if c is None:
            c = kind[e[0]] = is_collective(e[0])
        (coll if c else other).append(e)
    coll = union(coll)
    exposed = _minus(coll, union(other))
    starts = [e[1] for e in mods]
    owners = [module_name(e[0]) for e in mods]
    out: Dict[str, dict] = {}
    for key, spans in (("all", coll), ("exposed", exposed)):
        for a, b in spans:
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(mods) and mods[i][1] < b:
                _, s, e = mods[i]
                x = min(b, e) - max(a, s)
                if x > 0:
                    d = out.setdefault(owners[i], {"all": 0.0, "exposed": 0.0})
                    d[key] += x * 1e-9
                i += 1
    return out


#: share of the fullest record's operations in the window below which a
#: device's record is taken to have lost events
WHOLE = 0.99


def whole_records(tr: dict, t0: float, t1: float) -> List[dict]:
    """The devices whose record of the window is whole. The profiler can
    drop part of a device's events (on a four-chip v5e host it kept about
    two thirds of chip 0's operations and every one of the other chips');
    the chips of one replica run the same programs, so a record with
    fewer operations in the window than ``WHOLE`` of the fullest one has
    lost some, and is left out. Chips that run different work (replicas
    behind a router) need another rule."""
    n = [sum(1 for _, s, d in dev["ops"] if s < t1 and s + d > t0)
         for dev in tr["devices"]]
    return [dev for dev, k in zip(tr["devices"], n) if k >= WHOLE * max(n)]


def reduce(tr: dict, t0: float, t1: float, top: int = 10) -> dict:
    """Busy seconds per device (averaged), device seconds per program on
    the first device, its longest operations, its time in collectives,
    and its idle time grouped by what the host was doing; of the devices
    whose record is whole (``whole_records``), which ``devices_read``
    names."""
    if not tr["devices"]:
        raise ValueError("no TPU device in the trace")
    devs = whole_records(tr, t0, t1)
    busy = []
    for dev in devs:
        busy.append(sum(b - a for a, b in
                        union(_clipped(_busy_events(dev), t0, t1))))
    dev0 = devs[0]
    mods = _clipped(dev0["modules"], t0, t1)
    module_s: Dict[str, float] = defaultdict(float)
    for name, a, b in mods:
        module_s[module_name(name)] += (b - a) * 1e-9
    mods.sort(key=lambda e: e[1])
    owners = [module_name(e[0]) for e in mods]
    # op_name once per distinct operation: a tick repeats the same ones
    short: Dict[str, str] = {}
    ops = []
    for name, a, b in _clipped(dev0["ops"], t0, t1):
        n = short.get(name)
        if n is None:
            n = short[name] = op_name(name)
        if not n.startswith(CONTAINERS):  # its body's ops are listed
            ops.append((n, a, b))
    ops.sort(key=lambda e: e[1])
    ops_s: Dict[str, float] = defaultdict(float)
    j = 0
    for name, a, b in ops:
        while j < len(mods) and mods[j][2] <= a:
            j += 1
        owner = owners[j] if j < len(mods) and mods[j][1] <= a else "?"
        ops_s[f"{owner}/{name}"] += (b - a) * 1e-9
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (t1 - t0) * 1e-9,
        "module_s": dict(module_s),
        "collective_s": collective_s(ops, mods),
        "device_ops": sorted(([k, v] for k, v in ops_s.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": idle_by_host(tr, dev0, t0, t1)[:top],
        "devices_read": [dev["id"] for dev in devs],
    }


def idle_by_host(tr: dict, dev: dict, t0: float, t1: float) -> List[list]:
    """Idle seconds on ``dev`` grouped by the innermost benchmark host
    annotation (other than the window itself) that covers each gap's
    middle; ``host.other`` where none does."""
    busy = union(_clipped(_busy_events(dev), t0, t1))
    gaps, prev = [], t0
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if t1 > prev:
        gaps.append((prev, t1))
    spans = sorted((e for e in tr["host"] if e[0] != "bench.window"),
                   key=lambda e: e[1])
    starts = [e[1] for e in spans]
    out: Dict[str, float] = defaultdict(float)
    longest = max((e[2] for e in spans), default=0.0)
    for a, b in gaps:
        mid = (a + b) / 2
        label, width = "host.other", float("inf")
        i = bisect.bisect_right(starts, mid)
        while i > 0 and spans[i - 1][1] >= mid - longest:
            name, s, d = spans[i - 1]
            if s <= mid <= s + d and d < width:
                label, width = name, d
            i -= 1
        out[label] += (b - a) * 1e-9
    return sorted(([k, v] for k, v in out.items()), key=lambda kv: -kv[1])

