"""Work the model needs, counted from its shapes: operations and bytes per
prefilled prompt and per decoded token.

It counts what the model requires, not what an implementation does:
padding, garbage lanes and recomputation are not work. Matrix products
count 2 operations per multiply-add. Attention counts QK and PV over the
causal context only. The output head counts once per token that is
produced: the last prompt position and each decoded token.
"""
from __future__ import annotations

from typing import Iterable

from weights import Shapes

BYTES = 2  # bfloat16 weights and KV


def body_flops_per_token(s: Shapes) -> float:
    per_layer = s.d * (s.q_dim + 2 * s.kv_dim) + s.q_dim * s.d + 3 * s.d * s.ff
    return 2.0 * s.layers * per_layer


def head_flops(s: Shapes) -> float:
    return 2.0 * s.d * s.vocab


def attn_flops(s: Shapes, keys: float) -> float:
    """One query attending ``keys`` positions, in every layer."""
    return 4.0 * s.layers * s.heads * s.head_dim * keys


def prefill_flops(s: Shapes, prompt_len: int) -> float:
    """A whole prompt: its matmuls, causal attention (query t sees t + 1
    keys) and the head at its last position."""
    p = prompt_len
    return (p * body_flops_per_token(s) + attn_flops(s, p * (p + 1) / 2)
            + head_flops(s))


def decode_flops(s: Shapes, context: int) -> float:
    """One decoded token whose query sees ``context`` keys (itself
    included)."""
    return body_flops_per_token(s) + attn_flops(s, context) + head_flops(s)


def decode_weight_bytes(s: Shapes) -> float:
    """Weights one decode tick must read: every layer, the final norm and
    the head. The embedding table is only indexed, a row per token."""
    return BYTES * (s.layers * s.layer_params() + s.d + s.d * s.vocab)


def kv_bytes_per_token(s: Shapes) -> float:
    return BYTES * 2 * s.layers * s.kv_dim


def decode_bytes(s: Shapes, ticks: int, contexts: Iterable[int]) -> float:
    """Bytes ``ticks`` decode ticks must move: the weights once per tick,
    and for each decoded token the KV of its context read and its own K
    and V written."""
    kv = kv_bytes_per_token(s)
    return ticks * decode_weight_bytes(s) + kv * sum(contexts)
