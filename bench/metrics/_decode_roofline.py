"""Least time the window's decode ticks could take on this chip (the
larger of their operations over peak FLOP/s and their bytes, weights once
per tick plus each token's KV, over HBM bandwidth), as a share of the
decode programs' device time."""
from metriclib import DECODE_PROGRAMS, program_seconds, share


def read(run):
    w, peak = run.work, run.peak
    least = max(w["decode_flops"] / (run.chips * peak["bf16_flops"]),
                w["decode_bytes"] / (run.chips * peak["hbm_bytes_per_s"]))
    return share(least, program_seconds(run, DECODE_PROGRAMS))
