"""Llama (Granite): the dense GQA decoder (``_dense.py``) with RoPE over
the whole head in split halves (i, i + hd/2), the published layout
itself. Keys of a Hugging Face ``LlamaForCausalLM`` config.json.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from families import _dense
from families._dense import (  # noqa: F401
    body_flops_per_token, decode_bytes, decode_flops, decode_weight_bytes,
    kv_bytes_per_token, matrices, prefill_flops, published,
    published_shardings, split_first)


def shapes(conf: dict) -> _dense.Shapes:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return _dense.Shapes("llama", conf["num_hidden_layers"], d, h,
                         conf["num_key_value_heads"],
                         conf.get("head_dim") or d // h,
                         conf["intermediate_size"], conf["vocab_size"],
                         conf["rms_norm_eps"], conf["rope_theta"],
                         bool(conf.get("tie_word_embeddings", False)))


def program_check(s: _dense.Shapes) -> dict:
    return _dense.program_check(s, "standard")


def rope_permutation(s: _dense.Shapes) -> np.ndarray:
    """The identity: the published layout already uses split halves."""
    return np.arange(s.head_dim)


def to_program(s: _dense.Shapes, w: dict) -> dict:
    return _dense.to_program(s, w, rope_permutation(s))


def _split_halves(xr, cos, sin):
    rot = xr.shape[-1]
    x0, x1 = xr[..., :rot // 2], xr[..., rot // 2:]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)


def rope(s: _dense.Shapes, x, pos):
    return _dense.rope(s, x, pos, s.head_dim, _split_halves)


def logits(s: _dense.Shapes, w: dict, tokens):
    return _dense.logits(s, w, tokens, rope)
