#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on: sets up (weights from the seed, engine, warm-up of every
shape the window uses), measures for ``--seconds``, checks the served
tokens against the plain reference, and prints one JSON object as the
last line of standard output. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiled
window. Exits non-zero, with no result, when the cell's chips are not
those its engine's topology spans, or JAX finds no TPU, fewer chips than
the cell asks for, or a chip missing from ``peaks.json``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    harness.use_cache()
    n = harness.topology(cell.conf).n_chips
    if n != cell.chips:
        raise SystemExit(f"bench: {args.workload} asks for {cell.chips} "
                         f"chips, its engine's topology spans {n}")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (jax platform {devs[0].platform!r})"
                         f"; there is no CPU fallback")
    if len(devs) < cell.chips:
        raise SystemExit(f"bench: {args.workload} needs {cell.chips} chips, "
                         f"jax reports {len(devs)}")
    with open(os.path.join(harness.BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if devs[0].device_kind not in peaks:
        raise SystemExit(f"bench: device_kind {devs[0].device_kind!r} is not "
                         f"in peaks.json")
    log(f"device: {devs[0].platform} {devs[0].device_kind!r} x{len(devs)}, "
        f"jax {jax.__version__}; cell {args.workload}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}")
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), t_start=T_START,
        peak=peaks[devs[0].device_kind], log=log)
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
