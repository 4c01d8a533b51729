"""Model shapes read from a configuration file, and random weights drawn
from a run's seed in the published layout.

``published`` draws the weights as the published model lays them out,
one stacked array per kind of matrix (x @ W orientation), in the dtype
they are served in. ``to_program`` rearranges them into the program's
parameter tree, as a checkpoint loader would. The plain reference reads
the published layout and nothing of the program.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Shapes:
    family: str  # "llama" | "chatglm"
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    rope_theta: float
    tied: bool = False  # the output head is the embedding table

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def rot_dim(self) -> int:
        """Dimensions of each head that RoPE rotates."""
        return self.head_dim // 2 if self.family == "chatglm" else self.head_dim

    def layer_params(self) -> int:
        return (self.d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * self.d
                + 3 * self.d * self.ff + 2 * self.d)

    def params(self) -> int:
        tables = 1 if self.tied else 2
        return (self.layers * self.layer_params()
                + tables * self.vocab * self.d + self.d)


def shapes(conf: dict) -> Shapes:
    """Read the sizes from a configuration file in its source's own keys."""
    if conf["family"] == "llama":
        d, h = conf["hidden_size"], conf["num_attention_heads"]
        return Shapes("llama", conf["num_hidden_layers"], d, h,
                      conf["num_key_value_heads"],
                      conf.get("head_dim") or d // h,
                      conf["intermediate_size"], conf["vocab_size"],
                      conf["rms_norm_eps"], conf["rope_theta"],
                      bool(conf.get("tie_word_embeddings", False)))
    if conf["family"] == "chatglm":
        return Shapes("chatglm", conf["num_layers"], conf["hidden_size"],
                      conf["num_attention_heads"],
                      conf["multi_query_group_num"], conf["kv_channels"],
                      conf["ffn_hidden_size"], conf["padded_vocab_size"],
                      conf["layernorm_epsilon"], 10000.0,
                      bool(conf.get("tie_word_embeddings", False)))
    raise ValueError(f"unknown family {conf['family']!r}")


def seed_key(seed: int):
    """A JAX key from any non-negative seed, including ones past 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def published(s: Shapes, key, dtype=jnp.bfloat16) -> dict:
    """Weights in the published layout, drawn from ``key``: matrices with
    standard deviation 1/sqrt(fan-in), norm weights near 1. A tied model
    has no ``lm_head``: its head is the embedding table."""
    ks = iter(jax.random.split(key, 16))
    L = s.layers

    # drawn in the served dtype, so that no float32 copy of a stacked
    # matrix is ever made on the device
    def mat(shape, fan_in):
        return jax.random.normal(next(ks), shape, dtype) * fan_in ** -0.5

    def norm(shape):
        return 1.0 + 0.05 * jax.random.normal(next(ks), shape, dtype)

    w = {
        "embed": mat((s.vocab, s.d), s.d),
        "attn_norm": norm((L, s.d)),
        "wq": mat((L, s.d, s.q_dim), s.d),
        "wk": mat((L, s.d, s.kv_dim), s.d),
        "wv": mat((L, s.d, s.kv_dim), s.d),
        "wo": mat((L, s.q_dim, s.d), s.q_dim),
        "mlp_norm": norm((L, s.d)),
        "w_gate": mat((L, s.d, s.ff), s.d),
        "w_up": mat((L, s.d, s.ff), s.d),
        "w_down": mat((L, s.ff, s.d), s.ff),
        "final_norm": norm((s.d,)),
    }
    if not s.tied:
        w["lm_head"] = mat((s.d, s.vocab), s.d)
    return w


def rope_permutation(s: Shapes) -> np.ndarray:
    """Column order that turns ChatGLM's interleaved RoPE pairs (2i, 2i+1)
    of the rotated dimensions into split halves (i, i + rot/2), per head.
    The identity for a Llama model, whose published layout already uses
    split halves."""
    hd, rot = s.head_dim, s.rot_dim
    inner = np.arange(hd)
    if s.family == "chatglm":
        inner[:rot] = np.concatenate([np.arange(0, rot, 2),
                                      np.arange(1, rot, 2)])
    return inner


def to_program(s: Shapes, w: dict) -> dict:
    """The program's parameter tree (``repro.models.init_params`` layout:
    one scanned body of stacked dense blocks, norms stored as scale - 1)."""
    perm = rope_permutation(s)

    def heads_perm(m, n_heads):
        cols = (np.arange(n_heads)[:, None] * s.head_dim + perm[None]).ravel()
        return m[..., cols]

    def scale(x):  # the program's RMSNorm multiplies by (1 + scale)
        return (x.astype(jnp.float32) - 1.0).astype(x.dtype)

    block = {
        "norm1": {"scale": scale(w["attn_norm"])},
        "attn": {"wq": heads_perm(w["wq"], s.heads),
                 "wk": heads_perm(w["wk"], s.kv_heads),
                 "wv": w["wv"], "wo": w["wo"]},
        "norm2": {"scale": scale(w["mlp_norm"])},
        "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                "w_down": w["w_down"]},
    }
    out = {"body": [block], "tail": [],
           "final_norm": {"scale": scale(w["final_norm"])},
           "embed": w["embed"]}
    if not s.tied:
        out["lm_head"] = w["lm_head"]
    return out
