"""90th percentile of the wait from when a request fell due to when the
engine gave it a slot: the end of the engine's ``queued`` span, stamped
with the wall-clock seconds the benchmark passes as ``now``."""
from metriclib import p90


def read(run):
    return p90(run.queue_waits_ms)
