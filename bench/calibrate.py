#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3 \
        [--precisions bf16,int8]

For each seed and precision: a full run of the cell (weights, warm-up, a
window of ``--seconds`` at the cell's own load) and the check of its
served tokens, judged by the same comparison with the cell's limits as
a benchmark run. ``bf16`` is the program as the configuration states it;
``int8`` is the control: the program's own quantized path (int8 weights
and int8 KV pages), which has to come out not correct; ``int8kv`` is
int8 KV pages alone; ``int8ref`` is the control of a cell whose engine
refuses int8 weights (a sharded one): the run serves in bf16, its served
tokens are judged too (read as bf16), and ``correct`` is that of the
reference with int8 weights in the program's place (``control.py``). Prints one JSON
line per run and, per precision and number, the largest and smallest
reading at the end. The benchmark's own runs never run the control.
"""
import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

#: engine precision of each reading; int8 is the program's quantized path,
#: int8kv its int8 KV pages alone (a sharded engine refuses int8 weights);
#: int8ref serves in bf16 and judges the int8 reference's picks
PRECISIONS = {"bf16": {},
              "int8": {"kv_cache_dtype": "int8", "weight_dtype": "int8"},
              "int8kv": {"kv_cache_dtype": "int8"},
              "int8ref": {}}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precisions", help="default: bf16,int8, and "
                    "bf16,int8ref for a sharded engine (which refuses "
                    "int8 weights)")
    args = ap.parse_args()
    base = harness.find_cell(args.workload)
    if args.precisions is None:
        sharded = harness.topology(base.conf).sharded
        args.precisions = "bf16,int8ref" if sharded else "bf16,int8"
    harness.use_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: no TPU")
    readings = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        for prec in args.precisions.split(","):
            cell = copy.deepcopy(base)
            cell.conf["engine"]["precision"] = PRECISIONS[prec]
            r = harness.run_cell(cell, seed, args.seconds, False,
                                 t_start=time.perf_counter(), peak=None,
                                 log=lambda m: print(m, file=sys.stderr),
                                 control=prec == "int8ref")
            for p, checks in ((prec, r["checks"]),
                              ("bf16", r.get("sound_checks", {}))):
                for k, c in checks.items():
                    readings.setdefault(p, {}).setdefault(k, []).append(
                        c["value"])
            print(json.dumps({"seed": seed, "precision": prec,
                              "correct": r["correct"],
                              "attempted": r["attempted"],
                              "failed": r["failed"], "checks": r["checks"],
                              "sound_checks": r.get("sound_checks")}),
                  flush=True)
    print(json.dumps({p: {k: {"largest": max(v), "smallest": min(v),
                              "all": v} for k, v in numbers.items()}
                      for p, numbers in readings.items()}))


if __name__ == "__main__":
    main()
