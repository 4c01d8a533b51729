"""MISD scheduling primitives the serving stack uses: the ``Job`` and
``Device`` a router places (``core/mimd/router.py``), and the
``ChunkedPrefillPolicy`` that decides how many prefill chunks the serving
engine runs ahead of each decode tick.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple


@dataclass
class Job:
    jid: int
    model: str
    demand: Tuple[float, float]  # (compute, memory) fractions
    service_s: float  # isolated latency on the target device
    arrival: float = 0.0
    priority: int = 0
    sla_s: float = 0.0
    # token-level shape (0/None when the caller only knows service_s):
    # lets per-replica routing re-estimate service for heterogeneous
    # hardware (n_chips) and probe prefix-cache affinity on the prompt
    prompt_tokens: int = 0
    new_tokens: int = 0
    tokens: Optional[Sequence[int]] = None  # prompt ids (affinity probe)


@dataclass
class Device:
    """One schedulable hardware unit (whole chip, or a meshlet slice)."""

    name: str
    max_tenants: int = 4
    speed: float = 1.0  # relative to the reference chip (meshlet fraction)
    running: List[Job] = field(default_factory=list)


# ---------------------------------------------------------------------------
# chunked prefill <-> decode interleaving (serving-engine hook)
# ---------------------------------------------------------------------------


@dataclass
class ChunkedPrefillPolicy:
    """Decide how many prefill chunks to run ahead of each decode tick.

    Admitting a long prompt as one prefill stalls every in-flight decode
    stream for the whole prompt (head-of-line blocking — the survey's
    batching/latency tension in its sharpest form). The serving engine
    instead splits prompts into ``chunk``-token pieces and asks this policy,
    each tick, how many pieces fit: the budget is a multiple of the decode
    step's cost-model latency, so decode tick inflation is bounded by
    ``budget_ratio`` regardless of prompt length. With no active decode
    streams there is nothing to starve and prefill runs nearly unthrottled.
    """

    chunk: int = 64
    budget_ratio: float = 2.0  # max decode-tick inflation while prefilling
    max_chunks: int = 4        # hard cap per tick with active decodes
    idle_burst: int = 16       # chunks per tick when no decode is active

    def chunks_this_tick(self, cfg, *, n_decoding: int, pending_chunks: int,
                         context: int, n_chips: int = 1) -> int:
        if pending_chunks <= 0:
            return 0
        if n_decoding <= 0:
            return min(pending_chunks, self.idle_burst)
        from repro.core.costmodel import estimate_decode, estimate_prefill

        dec = estimate_decode(cfg, n_decoding, context,
                              n_chips=n_chips).latency_s
        pre = estimate_prefill(cfg, 1, self.chunk,
                               n_chips=n_chips).latency_s
        budget = max(self.budget_ratio - 1.0, 0.0) * dec
        n = int(budget // max(pre, 1e-12))
        return max(1, min(n, self.max_chunks, pending_chunks))
