"""Model operations of every prompt and output token processed in the
window, over the window's host seconds times chips times peak FLOP/s."""
from metriclib import share


def read(run):
    w = run.work
    return share(w["prefill_flops"] + w["decode_flops"],
                 run.window_s * run.chips * run.peak["bf16_flops"])
