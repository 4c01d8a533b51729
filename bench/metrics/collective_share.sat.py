"""Share of the decode programs' device time on the first chip in which
a collective ran and no other operation did: the exchange between chips
that the tick waits for. None where the decode programs ran no
collective (one chip)."""
from metriclib import DECODE_PROGRAMS, program_seconds, share


def read(run):
    if run.trace is None:
        return None
    mine = [v for m, v in run.trace["collective_s"].items()
            if any(n in m for n in DECODE_PROGRAMS)]
    if not any(v["all"] for v in mine):
        return None
    return share(sum(v["exposed"] for v in mine),
                 program_seconds(run, DECODE_PROGRAMS))
