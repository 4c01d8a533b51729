"""Work the model needs, counted from its shapes by its family
(``families/<family>.py``): operations and bytes per prefilled prompt,
per decoded token and per decode tick.

Every family counts by the same rules. It counts what the model
requires, not what an implementation does: padding, garbage lanes and
recomputation are not work. Matrix products count 2 operations per
multiply-add. Attention counts QK and PV over the causal context only.
The output head counts once per token that is produced: the last prompt
position and each decoded token.
"""
from __future__ import annotations

from typing import Iterable, Optional

from weights import family_of


def prefill_flops(s, prompt_len: int) -> float:
    """A whole prompt."""
    return family_of(s).prefill_flops(s, prompt_len)


def decode_flops(s, context: int) -> float:
    """One decoded token whose query sees ``context`` keys (itself
    included)."""
    return family_of(s).decode_flops(s, context)


def decode_bytes(s, ticks: int, contexts: Iterable[int],
                 lanes_per_tick: Optional[float] = None) -> float:
    """Bytes ``ticks`` decode ticks must move, whose tokens' contexts sum
    to ``sum(contexts)``; ``lanes_per_tick`` tokens a tick on average."""
    return family_of(s).decode_bytes(s, ticks, contexts, lanes_per_tick)


def __getattr__(name: str):
    """Any other count of the family of ``s``: ``flops.<name>(s, ...)``."""
    if name.startswith("__"):
        raise AttributeError(name)

    def count(s, *args):
        return getattr(family_of(s), name)(s, *args)

    return count
