"""Decoded tokens delivered in the window over decode ticks times slots:
the share of decode lanes that carried a live request."""
from metriclib import share


def read(run):
    return share(run.work["decode_tokens"], run.work["ticks"] * run.slots)
