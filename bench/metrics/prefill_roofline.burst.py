"""Prefill operations (matmuls and causal attention of the prompts
prefilled in the window) at peak FLOP/s, as a share of the prefill
programs' device time."""
from metriclib import PREFILL_PROGRAMS, program_seconds, share


def read(run):
    least = run.work["prefill_flops"] / (run.chips * run.peak["bf16_flops"])
    return share(least, program_seconds(run, PREFILL_PROGRAMS))
