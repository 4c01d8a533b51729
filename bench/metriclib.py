"""What the per-layer metric readers share: which compiled programs are
prefill and which are decode, and a percentile.

A reader is a file ``metrics/<name>.py`` with ``read(run)``, returning a
number or None when the run gave it nothing to read. ``run`` carries:
``trace`` (``tracereduce.reduce`` of the traced window, or None),
``window_s`` (host seconds the window lasted), ``work`` (model work done
in the window, from ``flops``), ``peak`` (the chip's row of
``peaks.json``), ``chips``, ``slots``, ``queue_waits_ms`` and
``pages_share``. Where the cell traces only the window's last
``trace_seconds``, ``window_s``, ``work`` and ``pages_share`` are those
of that part; ``queue_waits_ms`` stays the window's.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

#: substrings of the program names (``jax.jit`` function names) that run
#: the model over prompts, and over one decode tick or a fused window
PREFILL_PROGRAMS = ("_chunk_step", "_probed_paged_prefill",
                    "_probed_bucketed", "_probed_exact", "_probed_suffix")
DECODE_PROGRAMS = ("_probed_decode", "_probed_scan")


def program_seconds(run, names) -> Optional[float]:
    if run.trace is None:
        return None
    s = sum(t for m, t in run.trace["module_s"].items()
            if any(n in m for n in names))
    return s or None


def p90(values) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, float), 90))


def share(num: Optional[float], den: Optional[float]) -> Optional[float]:
    """100 * num / den, or None where either is missing or den is 0."""
    if num is None or not den:
        return None
    return 100.0 * num / den
