"""Run one benchmark cell: set up, warm up, measure, check, report.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Everything it
names is data found by name: ``configs/<config>.json`` (model and engine),
``families/<family>.py`` (the configuration's model family: its shapes,
weights, reference and work counts), ``traffic/<traffic>.json`` (the
mix), ``cells/<cell>.json`` (the cell's own rate or clients, the limit
of its correctness check and, where a traced run profiles only the
window's last seconds, ``trace_seconds``), and ``metrics/<metric>.py``
(one reader per per-layer metric).

The window drives the user's entry: ``ServingEngine.submit`` / ``step``
from an ``EngineConfig``, with ``now`` the wall-clock seconds since the
window opened. Every timestamp is ``time.perf_counter()`` taken when the
engine call that delivered the token returns.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import operator
import os
import sys
import tempfile
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH,
                os.path.join(BENCH, "metrics")]

import flops  # noqa: E402
import traffic  # noqa: E402
from weights import (  # noqa: E402
    family_of, published, seed_key, shapes, to_program)

#: request states after which the engine does no more work on it
TERMINAL = ("finished", "cancelled", "timed_out", "failed", "shed")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict  # configuration file
    mix: dict  # traffic file
    load: dict  # the cell's own file
    per_layer: list  # BENCHMARK.json per_layer entries this cell reports
    end_to_end: list


def find_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with every file it
    names loaded."""
    bench = os.path.join(root, "bench")
    bm = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    conf_file = {c["name"]: c["file"] for c in bm["configs"]}[w["config"]]

    def here(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bm["end_to_end"] if here(m)]
    moves = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if here(m) and m["moves"] in moves]
    return Cell(name, w["chips"], _json(os.path.join(root, conf_file)),
                _json(os.path.join(bench, "traffic", w["traffic"] + ".json")),
                _json(os.path.join(bench, "cells", name + ".json")),
                per_layer, e2e)


def reader(metric: str, bench: str = BENCH):
    """``read`` of the per-layer metric's own file."""
    path = os.path.join(bench, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def use_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment says, with every program cached."""
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCount:
    """Compile requests seen so far in this process, persistent-cache hits
    and misses alike."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.n += 1


def program_config(conf: dict):
    """The program's ArchConfig for a configuration file, checked against
    the fields its model family asks of it (``program_check``)."""
    from repro.configs import get_config

    p = conf["program"]
    cfg = dataclasses.replace(get_config(p["arch"]), **p.get("overrides", {}))
    s = shapes(conf)
    want = family_of(s).program_check(s)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise SystemExit(f"program config {p} does not match the file's "
                         f"shapes: {got} != {want}")
    return cfg, s


def topology(conf: dict):
    """The program's ``DeviceTopology`` of a configuration's engine: one
    chip where the file names none."""
    from repro.serving import DeviceTopology

    return DeviceTopology(**conf["engine"].get("topology", {}))


def placement(cfg, topo):
    """The shardings a sharded engine gives its parameter tree on the mesh
    it builds for ``topo``, from the program's own functions, so that
    weights drawn into them are already where the engine keeps them."""
    from repro.core.simd.sharding import (param_pspecs, serving_policy,
                                          to_shardings)
    from repro.launch.mesh import make_serving_mesh
    from repro.models import param_specs

    mesh = make_serving_mesh(topo)
    pspecs = param_pspecs(cfg, param_specs(cfg), serving_policy(cfg, mesh))
    return to_shardings(mesh, pspecs)


def weights_program(cfg, s, topo, int8_weights: bool = False):
    """The jitted call that draws the program's weights from a key: on
    one chip, whole; over a sharded engine's chips, each leaf straight
    into the sharding the engine gives it."""
    from repro.models import quantize_weights

    def init(k):
        p = to_program(s, published(s, k))
        return quantize_weights(cfg, p) if int8_weights else p

    if not topo.sharded:
        return jax.jit(init)
    out = placement(cfg, topo)
    first = {name: functools.reduce(operator.getitem, path, out)
             for name, path in family_of(s).split_first.items()}

    def init_placed(k):
        # the family's ``split_first`` leaves are split as the engine
        # keeps them before ``to_program`` reads them
        w = published(s, k)
        for name, sharding in first.items():
            w[name] = jax.lax.with_sharding_constraint(w[name], sharding)
        return to_program(s, w)

    return jax.jit(init_placed, out_shardings=out)


def build(conf: dict, seed: int, trace: bool):
    """Weights on the device from the seed in one jitted call, in the
    program's layout, and the engine.

    An engine ``precision`` of int8 weights (the program's own quantized
    path) has the program's ``quantize_weights`` run inside that same
    call, so that no float32 copy of a stacked matrix is made on the
    device, and hands the engine the weights already quantized.

    Where the engine spans several chips (a ``topology``), the call
    draws each leaf straight into the sharding the engine gives it: each
    chip computes only its share, and no chip ever holds the whole
    model. The draw is partitionable (``jax_threefry_partitionable``),
    so the values are those of the one-chip draw."""
    from repro.models import param_specs
    from repro.serving import EngineConfig, PrecisionConfig, ServingEngine

    cfg, s = program_config(conf)
    e = dict(conf["engine"])
    prec = PrecisionConfig(**e.pop("precision", {}))
    topo = topology(conf)
    e.pop("topology", None)
    int8_weights = prec.quantized_weights
    if topo.sharded and int8_weights:
        raise SystemExit("bench: a sharded engine refuses int8 weights; "
                         "its control is calibrate.py --precisions int8ref")
    params = weights_program(cfg, s, topo, int8_weights)(seed_key(seed))
    if not int8_weights:
        want = jax.tree.map(lambda x: (x.shape, x.dtype), param_specs(cfg))
        got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        if got != want:
            raise SystemExit("weights do not match the program's parameter "
                             "tree")
    prec = dataclasses.replace(prec, weight_dtype="")
    engine = ServingEngine(cfg, params, EngineConfig(
        **e, topology=topo, precision=prec, tracing=trace))
    return engine, s


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Rec:
    spec: traffic.Req
    req: object  # repro.serving.Request
    first: Optional[float] = None
    end: Optional[float] = None
    seen: int = 0

    @property
    def done(self) -> bool:
        return self.req.state.value in TERMINAL


class Client:
    """Submits requests and steps the engine, stamping every token's
    arrival on the host clock and counting the window's work."""

    def __init__(self, engine, s, seed: int, mix: dict):
        self.engine, self.s, self.seed, self.mix = engine, s, seed, mix
        self.live: List[Rec] = []
        self.recs: List[Rec] = []
        self.t0 = 0.0
        self.close = math.inf  # window close, seconds after t0
        self.work = dict(prefill_flops=0.0, decode_flops=0.0,
                         decode_bytes=0.0, decode_tokens=0, prompt_tokens=0,
                         output_tokens=0, ticks=0)
        self.contexts = 0  # sum of decode contexts in the window
        self.pages_share: List[float] = []
        self.annotate = False

    def clock(self) -> float:
        return time.perf_counter() - self.t0

    def _ann(self, name):
        if not self.annotate:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def submit(self, spec: traffic.Req):
        from repro.serving import Request, SamplingParams

        sp = SamplingParams()
        if spec.sampled:
            sp = SamplingParams(seed=traffic.sample_seed(self.seed, spec.rid),
                                **self.mix["sampling"])
        req = Request(rid=spec.rid, prompt=traffic.prompt_tokens(
            self.seed, spec.rid, spec.prompt_len, self.s.vocab),
            max_new_tokens=spec.max_new, sampling=sp)
        rec = Rec(spec, req)
        self.live.append(rec)
        self.recs.append(rec)
        with self._ann("bench.submit"):
            self.engine.submit(req, self.clock())
        self._collect()
        return rec

    def step(self) -> None:
        with self._ann("bench.step"):
            self.engine.step(self.clock())
        self._collect()
        if self.engine.paged and self.clock() < self.close:
            a = self.engine.allocator
            self.pages_share.append(a.pages_in_use / a.capacity)

    def _collect(self):
        t = self.clock()
        in_window = t < self.close
        still = []
        for rec in self.live:
            n = len(rec.req.output)
            if n > rec.seen:
                if rec.first is None:
                    rec.first = t
                    if in_window:
                        p = rec.spec.prompt_len
                        self.work["prefill_flops"] += flops.prefill_flops(
                            self.s, p)
                        self.work["prompt_tokens"] += p
                if in_window:
                    p = rec.spec.prompt_len
                    self.work["output_tokens"] += n - rec.seen
                    for k in range(max(rec.seen + 1, 2), n + 1):
                        c = p + k - 1  # keys the k-th token's query sees
                        self.work["decode_flops"] += flops.decode_flops(
                            self.s, c)
                        self.work["decode_tokens"] += 1
                        self.contexts += c
                rec.seen = n
            if rec.done:
                rec.end = t
            else:
                still.append(rec)
        self.live = still


def warm_up(engine, s, mix: dict, conf: dict, seed: int) -> None:
    """Serve one request per prefill shape the mix can reach, greedy and
    (where the mix samples) sampled, to completion: every program the
    window will run is compiled, or loaded from the cache, here."""
    from repro.serving import Request, SamplingParams

    e = conf["engine"]
    lengths = traffic.warm_lengths(mix, e["chunk_prefill"], e["page_size"])
    n_new = 3 * e.get("sync_every", 8)
    reqs = []
    for i, n in enumerate(lengths):
        sp = SamplingParams()
        if mix.get("sampled_share", 0) and i % 2:
            sp = SamplingParams(seed=i, **mix["sampling"])
        reqs.append(Request(rid=-1 - i, prompt=traffic.prompt_tokens(
            seed, (1 << 40) + i, n, s.vocab), max_new_tokens=n_new,
            sampling=sp))
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r, time.perf_counter() - t0)
    while not engine.idle:
        engine.step(time.perf_counter() - t0)
    engine.drain(time.perf_counter() - t0)
    bad = [r.rid for r in reqs if r.state.value != "finished"]
    if bad:
        raise SystemExit(f"warm-up requests {bad} did not finish")
    # the engine stacks 1..sync_every deferred token rows on the host (an
    # eager concatenate per count); compile each count here, not in the
    # window. A sharded engine's rows are replicated over its mesh, as its
    # token carry is, and compile apart from one-chip rows
    row = jnp.zeros((engine.slots,), jnp.int32)
    if engine.mesh is not None:
        row = jax.device_put(row, engine._tokens.sharding)
    for k in range(1, engine.sync_every + 1):
        jnp.stack([row] * k).block_until_ready()
    engine.reset()


def _tally(d: Client, ticks: int) -> dict:
    """The window's work so far, with ``ticks`` decode ticks."""
    lanes = d.work["decode_tokens"] / ticks if ticks else 0.0
    return dict(d.work, ticks=ticks, decode_bytes=flops.decode_bytes(
        d.s, ticks, [d.contexts], lanes))


def run_window(d: Client, cell: Cell, seconds: float, on_close,
               period: Optional[float] = None, on_part=None,
               part_s: float = 0.0) -> dict:
    """Open or closed loop for ``seconds``; an open mix then drains (its
    arrivals going on) until the window's requests are done or the cap.
    An open mix's schedule repeats every ``period`` seconds (by default
    the window's length). With ``on_part``, it is called once between
    engine steps when ``part_s`` seconds of the window are left, and
    ``part`` holds the clock and the work done when it returned."""
    mix, load = cell.mix, cell.load
    d.t0 = time.perf_counter()
    d.close = seconds
    ticks0 = d.engine.metrics.decode_ticks
    lates = []
    idle_s = 0.0  # window seconds in which the engine had nothing to do
    part = None
    mark = seconds - part_s if on_part else math.inf

    def at_mark():
        nonlocal part, mark
        if d.clock() >= mark:
            mark = math.inf
            on_part()
            part = {"t": d.clock(), "pages": len(d.pages_share),
                    "work": _tally(d, d.engine.metrics.decode_ticks - ticks0)}
    if mix["kind"] == "open":
        gen = traffic.open_requests(mix, load, period or seconds)
        nxt = next(gen)
        cap = seconds + mix["drain_cap_s"]
        closed = False
        while True:
            now = d.clock()
            if not closed and now >= seconds:
                at_mark()
                closed = True
                window_s = now
                d.work["ticks"] = d.engine.metrics.decode_ticks - ticks0
                on_close()
            if closed and (now >= cap or all(
                    r.done for r in d.recs if r.spec.due < seconds)):
                break
            while nxt.due <= now:
                lates.append(now - nxt.due)
                d.submit(nxt)
                nxt = next(gen)
                now = d.clock()
            at_mark()
            if d.engine.idle:
                t_idle = d.clock()
                with d._ann("bench.wait"):
                    time.sleep(max(0.0, min(nxt.due, mark, seconds if not
                                            closed else cap) - d.clock()))
                if not closed:
                    idle_s += min(d.clock(), seconds) - t_idle
                continue
            d.step()
        window = [r for r in d.recs if r.spec.due < seconds]
    elif mix["kind"] == "closed":
        gen = traffic.closed_requests(mix)
        while True:
            while len(d.live) < load["clients"]:  # each client's next
                d.submit(next(gen))
            at_mark()
            d.step()
            now = d.clock()
            if now >= seconds:
                at_mark()
                window_s = now
                d.work["ticks"] = d.engine.metrics.decode_ticks - ticks0
                on_close()
                break
        window = list(d.recs)
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    d.engine.drain(d.clock())
    d.work = _tally(d, d.work["ticks"])
    return {"window_s": window_s, "window": window, "lates": lates,
            "end": d.clock(), "idle_s": idle_s, "part": part}


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def published_program(s, topo):
    """The jitted call that draws the published weights from a key for the
    reference: whole on one chip, and over a sharded engine's chips split
    by the model family's ``published_shardings`` on the engine's mesh."""
    if not topo.sharded:
        return jax.jit(lambda k: published(s, k))
    from repro.launch.mesh import make_serving_mesh

    mesh = make_serving_mesh(topo)
    return jax.jit(lambda k: published(s, k),
                   out_shardings=family_of(s).published_shardings(s, mesh))


#: the number each lane's served tokens are judged by
NUMBER = {False: "mean_logit_gap", True: "mean_nucleus_gap"}


#: tokens before a served token that make its context in ``look``
CONTEXT = 8


def look(prompt, served, g) -> str:
    """Where one request's gaps ``g`` come from: how many of its served
    tokens have a gap, their sum and widest, and at how many distinct
    contexts (the ``CONTEXT`` tokens before, and the token) they lie,
    beside the share of distinct contexts among all its served tokens.
    A greedy continuation that loops repeats its contexts, and a gap at
    one of them recurs with every turn of the loop."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    at = len(prompt) + np.arange(len(served))
    ctx = [tuple(seq[max(i - CONTEXT, 0):i + 1]) for i in at]
    hit = np.flatnonzero(g > 0)
    return (f"{len(served)} tokens, distinct contexts "
            f"{len(set(ctx)) / max(len(ctx), 1):.3f} of them; {len(hit)} "
            f"with a gap, sum {float(np.sum(g)):.4f}, widest "
            f"{float(np.max(g, initial=0)):.4f}, at "
            f"{len({ctx[i] for i in hit})} distinct contexts")


def check(conf: dict, mix: dict, seed: int, sample: List[Rec],
          control: bool = False, log=None):
    """Per lane, the mean and the widest gap over every served token of
    the sample: for greedy requests between the reference's best logit
    and its logit for the served token, for sampled ones between the
    lowest logit the sampler may draw and the served token's. Returns
    ``{number: (mean, widest)}`` and the number of tokens scored. With
    ``control``, the tokens judged are the control's picks at the same
    positions (``control.py``), not the served ones. With ``log``, each
    request's ``look``."""
    import control as ctl
    import reference

    s = shapes(conf)
    sp = mix["sampling"]
    sampling = (sp["temperature"], sp["top_k"], sp["top_p"])
    w = published_program(s, topology(conf))(seed_key(seed))
    w8 = ctl.int8_weights(s, w) if control else None
    lanes = {}
    for rec in sample:
        if control:
            key = jax.random.fold_in(seed_key(seed), rec.spec.rid)
            g = ctl.gaps(s, w, w8, rec.req.prompt, rec.req.output,
                         sampling, key)
        else:
            g = reference.gaps(s, w, rec.req.prompt, rec.req.output,
                               sampling)
        lanes.setdefault(rec.spec.sampled, []).append(g[rec.spec.sampled])
        if log:
            log(f"check rid {rec.spec.rid} ({NUMBER[rec.spec.sampled]}"
                f"{', control' if control else ''}): "
                + look(rec.req.prompt, rec.req.output, g[rec.spec.sampled]))
    del w, w8
    gc.collect()
    out = {}
    for lane, gs in lanes.items():
        g = np.concatenate(gs)
        ok = np.all(np.isfinite(g))
        out[NUMBER[lane]] = (float(np.mean(g)) if ok else math.inf,
                             float(np.max(g)) if ok else math.inf)
    return out, sum(len(g) for gs in lanes.values() for g in gs)


def _q(values, q):
    return float(np.percentile(np.asarray(values, float), q))


def peak_bytes(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, peak: Optional[dict], log=print,
             control: bool = False) -> dict:
    """One run of ``cell``. With ``control``, the served tokens are also
    judged as a benchmark run judges them (``sound_checks``), and
    ``correct`` and ``checks`` are those of the control (``control.py``)
    at the same positions."""
    devs = jax.devices()[:cell.chips]
    compiles = CompileCount()
    engine, s = build(cell.conf, seed, trace)
    warm_up(engine, s, cell.mix, cell.conf, seed)
    d = Client(engine, s, seed, cell.mix)
    prof_dir = None
    # a cell's ``trace_seconds`` profiles only the window's last seconds
    part_s = cell.load.get("trace_seconds", seconds) if trace else seconds
    part_s = part_s if part_s < seconds else 0.0
    ann = []  # the window's annotation, made once the profiler runs

    def open_trace():
        jax.profiler.start_trace(prof_dir)
        d.annotate = True
        ann.append(jax.profiler.TraceAnnotation("bench.window"))

    if trace:
        prof_dir = tempfile.mkdtemp(prefix="bench-trace-")
        if not part_s:
            open_trace()
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.4f} s (process start to window open)")
    c0 = compiles.n
    if ann:
        ann[0].__enter__()

    def on_part():
        open_trace()
        ann[0].__enter__()

    out = run_window(d, cell, seconds,
                     on_close=lambda: ann and ann[0].__exit__(None, None,
                                                              None),
                     on_part=on_part if part_s else None, part_s=part_s)
    in_window = compiles.n - c0
    win = out["window"]
    mem = peak_bytes(devs)
    log(f"window: {out['window_s']:.4f} s, {len(win)} requests attempted, "
        f"{sum(r.first is not None for r in win)} with a first token, "
        f"drain ended at {out['end']:.3f} s; compiles inside the window "
        f"and drain: {in_window}; memory_peak_bytes {mem}")
    if out["lates"]:
        log(f"generator lateness: median {_q(out['lates'], 50) * 1e3:.3f} "
            f"ms, max {max(out['lates']) * 1e3:.3f} ms over "
            f"{len(out['lates'])} submissions")

    failed = [r for r in win if r.done and r.req.state.value != "finished"
              or (cell.mix["kind"] == "open" and not r.done)]
    metrics = {}
    e2e = {m["name"] for m in cell.end_to_end}
    if cell.mix["kind"] == "open":
        ttft = [(r.first if r.first is not None else out["end"])
                - r.spec.due for r in win]
        tpot = [(r.end - r.first) / (len(r.req.output) - 1) for r in win
                if r.req.state.value == "finished" and len(r.req.output) > 1]
        half = len(ttft) // 2
        if half:
            log(f"ttft median, first half of the window's requests "
                f"{_q(ttft[:half], 50) * 1e3:.1f} ms, second half "
                f"{_q(ttft[half:], 50) * 1e3:.1f} ms (a growing queue "
                f"shows as a larger second half)")
        for name, vals in (("ttft", ttft), ("tpot", tpot)):
            if vals:
                log(f"{name}: median {_q(vals, 50) * 1e3:.3f} ms, p75 "
                    f"{_q(vals, 75) * 1e3:.3f} ms, p90 "
                    f"{_q(vals, 90) * 1e3:.3f} ms over {len(vals)} requests")
        if "ttft_p75_ms" in e2e and ttft:
            metrics["ttft_p75_ms"] = _q(ttft, 75) * 1e3
        if "tpot_p75_ms" in e2e and tpot:
            metrics["tpot_p75_ms"] = _q(tpot, 75) * 1e3
    if "output_tok_per_s" in e2e:
        metrics["output_tok_per_s"] = (d.work["output_tokens"]
                                       / out["window_s"])
    log(f"work in the window: {d.work}")
    metrics["setup_s"] = setup_s
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    result = {"metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items()}}

    if trace:
        t_trace = time.perf_counter()
        jax.profiler.stop_trace()
        d.annotate = False
        t_read = time.perf_counter()
        part = out["part"]
        span = {"work": d.work, "window_s": out["window_s"],
                "pages_share": d.pages_share}
        if part:
            span = {"work": {k: v - part["work"][k]
                             for k, v in d.work.items()},
                    "window_s": out["window_s"] - part["t"],
                    "pages_share": d.pages_share[part["pages"]:]}
            log(f"traced part: opened at {part['t']:.4f} s, "
                f"{span['window_s']:.4f} s long; its work {span['work']}")
        result.update(per_layer(cell, span, out["window"], prof_dir, peak,
                                engine.slots))
        log(f"trace: stopped and written in {t_read - t_trace:.1f} s, read "
            f"and reduced in {time.perf_counter() - t_read:.1f} s; devices "
            f"read {result.pop('devices_read')} of {cell.chips}")

    done = {r.spec.rid: r for r in win if r.req.state.value == "finished"}
    sample = [done[r.rid] for r in traffic.check_sample(
        [r.spec for r in done.values()], seed, cell.mix["check"])]
    del engine, d
    gc.collect()
    full = all(len(r.req.output) == r.spec.max_new for r in sample)
    t_check = time.perf_counter()
    gaps, n = check(cell.conf, cell.mix, seed, sample, log=log)
    log(f"check took {time.perf_counter() - t_check:.1f} s")
    limits = cell.load["check"]
    sound = {}
    if control:
        sound = {k: {"value": v[0], "limit": limits[k]}
                 for k, v in sorted(gaps.items())}
        log(f"check of the served tokens: {sound}")
        gaps, n = check(cell.conf, cell.mix, seed, sample, control=True,
                        log=log)
    share = cell.mix.get("sampled_share", 0.0)
    lanes = {NUMBER[False]} if share < 1 else set()
    lanes |= {NUMBER[True]} if share > 0 else set()
    correct = (full and set(gaps) == lanes
               and all(v[0] <= limits[k] for k, v in gaps.items()))
    log(f"check: {n} served tokens of {len(sample)} requests "
        f"({sum(r.spec.sampled for r in sample)} sampled; longest "
        f"{max((r.spec.prompt_len + r.spec.max_new for r in sample), default=0)}"
        f" tokens) against the float32 reference; all full length: {full}; "
        f"lanes scored {sorted(gaps)} of {sorted(lanes)}")
    for k, v in sorted(gaps.items()):
        log(f"check {k}: widest gap of the lane (not compared) {v[1]!r}")
    result.update({
        "correct": correct,
        "attempted": len(win),
        "failed": len(failed),
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs), "memory_peak_bytes": mem},
    })
    if trace:
        result["device"]["busy_s"] = result.pop("busy_s")
        result["device"]["window_s"] = result.pop("trace_window_s")
    if control:
        result["sound_checks"] = sound
    result["checks"] = {k: {"value": v[0], "limit": limits[k]}
                        for k, v in sorted(gaps.items())}
    return result


def per_layer(cell, span: dict, window: List[Rec], prof_dir: str, peak,
              slots: int):
    """The cell's per-layer metrics from the profile in ``prof_dir``, with
    the work, host seconds and page shares of the traced ``span`` (the
    window, or its traced part) and the queue waits of ``window``'s
    requests."""
    import glob
    import shutil
    import types

    import tracereduce

    paths = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                      recursive=True)
    tr = tracereduce.load(paths[0])
    shutil.rmtree(prof_dir, ignore_errors=True)
    t0, t1 = tracereduce.window(tr)
    red = tracereduce.reduce(tr, t0, t1)
    waits = []
    for r in window:
        t = r.req.trace
        q = [sp for sp in (t.spans if t else []) if sp.kind == "queued"
             and sp.t1 is not None]
        if q:
            waits.append((q[0].t1 - r.spec.due) * 1e3)
    run = types.SimpleNamespace(
        trace=red, window_s=span["window_s"], work=span["work"], peak=peak,
        chips=cell.chips, slots=slots, queue_waits_ms=waits,
        pages_share=span["pages_share"])
    metrics = {}
    for m in cell.per_layer:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return {"metrics": metrics, "busy_s": red["busy_s"],
            "trace_window_s": red["window_s"],
            "devices_read": red["devices_read"],
            "breakdown": {"device_ops": red["device_ops"],
                          "idle_gaps": red["idle_gaps"]}}
