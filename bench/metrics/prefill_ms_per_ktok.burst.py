"""Device milliseconds of the prefill programs per 1000 prompt tokens
prefilled in the window."""
from metriclib import PREFILL_PROGRAMS, program_seconds


def read(run):
    s = program_seconds(run, PREFILL_PROGRAMS)
    tokens = run.work["prompt_tokens"]
    if s is None or not tokens:
        return None
    return s * 1e3 / (tokens / 1e3)
