"""ChatGLM: the dense GQA decoder (``_dense.py``) whose RoPE rotates
interleaved pairs (2i, 2i+1) of the first half of each head only
(``original_rope``). The program rotates split halves of that half
(``rope_variant`` "half"), so the weights it is handed have the columns
of wq and wk permuted, as a checkpoint loader would; the reference keeps
the published interleaved form. Keys of ChatGLM3's config.json.
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
    return _dense.Shapes("chatglm", conf["num_layers"], conf["hidden_size"],
                         conf["num_attention_heads"],
                         conf["multi_query_group_num"], conf["kv_channels"],
                         conf["ffn_hidden_size"], conf["padded_vocab_size"],
                         conf["layernorm_epsilon"], 10000.0,
                         bool(conf.get("tie_word_embeddings", False)))


def program_check(s: _dense.Shapes) -> dict:
    return _dense.program_check(s, "half")


def rope_permutation(s: _dense.Shapes) -> np.ndarray:
    """Column order that turns the interleaved RoPE pairs (2i, 2i+1) of
    the rotated dimensions into split halves (i, i + rot/2), per head."""
    hd, rot = s.head_dim, s.head_dim // 2
    inner = np.arange(hd)
    inner[:rot] = np.concatenate([np.arange(0, rot, 2),
                                  np.arange(1, rot, 2)])
    return inner


def to_program(s: _dense.Shapes, w: dict) -> dict:
    return _dense.to_program(s, w, rope_permutation(s))


def _interleaved(xr, cos, sin):
    x0, x1 = xr[..., 0::2], xr[..., 1::2]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
    return out.reshape(xr.shape)


def rope(s: _dense.Shapes, x, pos):
    return _dense.rope(s, x, pos, s.head_dim // 2, _interleaved)


def logits(s: _dense.Shapes, w: dict, tokens):
    return _dense.logits(s, w, tokens, rope)
