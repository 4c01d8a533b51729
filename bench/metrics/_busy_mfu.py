"""Model operations processed in the window over the device's busy
seconds times chips times peak FLOP/s: at a fixed offered load the work
is fixed, so the share of peak is taken over the time the device worked."""
from metriclib import share


def read(run):
    if run.trace is None:
        return None
    w = run.work
    return share(w["prefill_flops"] + w["decode_flops"],
                 run.trace["busy_s"] * run.chips * run.peak["bf16_flops"])
