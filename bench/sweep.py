#!/usr/bin/env python3
"""Find an open cell's knee: the highest arrival rate whose queue does not
grow. Weights and warm-up once, then for each rate and seed one run of
``--cycles`` back-to-back periods of the cell's schedule.

    python3 bench/sweep.py --workload <cell> --period 50 --cycles 2 \\
        --rates 0.6,0.9,1.2 --seeds 1,2

The schedule repeats every period, so every cycle offers the same
requests at the same offsets. Where the queue holds, the k-th request of
the last cycle waits about as long as the k-th of the first; where it
grows, the last cycle's waits are longer. Prints one JSON line per run:
time to first token by cycle, the median of the paired differences
(last cycle minus first), the drain after the last cycle, and the share
of the run in which the engine had nothing to do. The sweep runs no
correctness check.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def sweep(cell, rates, seeds, period, cycles, build_seed, log):
    engine, s = harness.build(cell.conf, build_seed, False)
    harness.warm_up(engine, s, cell.mix, cell.conf, build_seed)
    for rate in rates:
        for seed in seeds:
            cell.load["rate_per_s"] = rate
            d = harness.Client(engine, s, seed, cell.mix)
            out = harness.run_window(d, cell, period * cycles, lambda: None,
                                     period=period)
            win = out["window"]
            ttft = [(r.first if r.first is not None else out["end"])
                    - r.spec.due for r in win]
            n = len(win) // cycles
            first, last = ttft[:n], ttft[-n:]
            row = {
                "rate_per_s": rate, "seed": seed, "per_cycle": n,
                "ttft_ms_by_cycle": [
                    {q: harness._q(ttft[i * n:(i + 1) * n], q) * 1e3
                     for q in (50, 75, 90)} for i in range(cycles)],
                "paired_growth_ms": harness._q(
                    [b - a for a, b in zip(first, last)], 50) * 1e3,
                "drain_s": out["end"] - out["window_s"],
                "unfinished": sum(not r.done for r in win),
                "engine_idle_share": out["idle_s"] / out["window_s"],
            }
            log(json.dumps(row))
            engine.reset()
    return engine


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--period", type=float, required=True)
    ap.add_argument("--cycles", type=int, default=2)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = harness.find_cell(args.workload)
    if cell.mix["kind"] != "open":
        raise SystemExit("sweep: only an open mix has an arrival rate")
    harness.use_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: no TPU")
    seeds = [int(x) for x in args.seeds.split(",")]
    t0 = time.perf_counter()
    sweep(cell, [float(x) for x in args.rates.split(",")], seeds,
          args.period, args.cycles, seeds[0],
          lambda m: print(m, flush=True))
    print(f"sweep took {time.perf_counter() - t0:.1f} s", file=sys.stderr)


if __name__ == "__main__":
    main()
