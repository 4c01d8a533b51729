"""Device milliseconds of the decode programs per decode tick."""
from metriclib import DECODE_PROGRAMS, program_seconds


def read(run):
    s = program_seconds(run, DECODE_PROGRAMS)
    if s is None or not run.work["ticks"]:
        return None
    return s * 1e3 / run.work["ticks"]
