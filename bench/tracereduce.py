"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` turns the profiler's ``.xplane.pb`` into a small plain form:
per device, the executions of each compiled program (``modules``) and of
each operation (``ops``) as (name, start_ns, duration_ns); and the
benchmark's own host annotations (``bench.*``). ``reduce`` takes that
form and a window on the trace's clock. Tests feed it a recorded trace.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = {"id": int(m.group(1)), "modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name.startswith("bench.")]
    devices.sort(key=lambda d: d["id"])
    return {"devices": devices, "host": host}


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


def reduce(tr: dict, t0: float, t1: float, top: int = 10) -> dict:
    """Busy seconds per device (averaged), device seconds per program on
    the first device, its longest operations, and its idle time grouped by
    what the host was doing."""
    devs = tr["devices"]
    if not devs:
        raise ValueError("no TPU device in the trace")
    busy = []
    for dev in devs:
        busy.append(sum(b - a for a, b in
                        union(_clipped(_busy_events(dev), t0, t1))))
    dev0 = devs[0]
    mods = _clipped(dev0["modules"], t0, t1)
    module_s: Dict[str, float] = defaultdict(float)
    for name, a, b in mods:
        module_s[module_name(name)] += (b - a) * 1e-9
    ops_s: Dict[str, float] = defaultdict(float)
    mods.sort(key=lambda e: e[1])
    j = 0
    for name, a, b in sorted(_clipped(dev0["ops"], t0, t1),
                             key=lambda e: e[1]):
        name = op_name(name)
        if name.startswith(CONTAINERS):
            continue  # its body's operations are listed themselves
        while j < len(mods) and mods[j][2] <= a:
            j += 1
        owner = (module_name(mods[j][0])
                 if j < len(mods) and mods[j][1] <= a else "?")
        ops_s[f"{owner}/{name}"] += (b - a) * 1e-9
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (t1 - t0) * 1e-9,
        "module_s": dict(module_s),
        "device_ops": sorted(([k, v] for k, v in ops_s.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": idle_by_host(tr, dev0, t0, t1)[:top],
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

