"""A dense GQA decoder, the code that ``llama`` and ``chatglm`` share:
RMSNorm, GQA attention with RoPE, a SwiGLU MLP and a head that may be
the embedding table. The families differ in their keys, their RoPE
layout and the program's RoPE variant, and pass those in.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference import Q_BLOCK

F32 = jnp.float32
BYTES = 2  # bfloat16 weights and KV


@dataclasses.dataclass(frozen=True)
class Shapes:
    family: str
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

    def layer_params(self) -> int:
        return (self.d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * self.d
                + 3 * self.d * self.ff + 2 * self.d)

    def params(self) -> int:
        tables = 1 if self.tied else 2
        return (self.layers * self.layer_params()
                + tables * self.vocab * self.d + self.d)


def program_check(s: Shapes, rope_variant: str) -> dict:
    return {"num_layers": s.layers, "d_model": s.d, "num_heads": s.heads,
            "num_kv_heads": s.kv_heads, "resolved_head_dim": s.head_dim,
            "d_ff": s.ff, "vocab_size": s.vocab, "rope_theta": s.rope_theta,
            "rope_variant": rope_variant, "tie_embeddings": s.tied,
            "arch_type": "dense"}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


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


def to_program(s: Shapes, w: dict, perm: np.ndarray) -> dict:
    """The program's parameter tree (``repro.models.init_params`` layout:
    one scanned body of stacked dense blocks, norms stored as scale - 1),
    with the columns of each head of wq and wk in the order ``perm``."""
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


#: to_program permutes the columns of wq and wk (a gather): a sharded
#: draw splits them as the engine keeps them first, or GSPMD draws each
#: whole on every chip
split_first = {"wq": ("body", 0, "attn", "wq"),
               "wk": ("body", 0, "attn", "wk")}


def published_shardings(s: Shapes, mesh) -> dict:
    """Every stacked matrix split on its last axis, the embedding on the
    vocabulary, norms whole on each chip. The reference's einsums are
    then partitioned by GSPMD, and no chip holds more than its share of
    the weights, a layer of them in float32 and one sequence's logits."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    last = NamedSharding(mesh, P(None, None, "model"))
    whole = NamedSharding(mesh, P())
    out = {k: last for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                             "w_down")}
    out.update(embed=NamedSharding(mesh, P("model", None)),
               attn_norm=whole, mlp_norm=whole, final_norm=whole)
    if not s.tied:
        out["lm_head"] = NamedSharding(mesh, P(None, "model"))
    return out


#: the input axis, so that each output column has its own scale; the
#: embedding, which is also the tied head, one per row
matrices = {"wq": -2, "wk": -2, "wv": -2, "wo": -2, "w_gate": -2,
            "w_up": -2, "w_down": -2, "lm_head": -2, "embed": -1}


# ---------------------------------------------------------------------------
# the plain reference: no cache, no batching, no kernels, one sequence at a
# time layer by layer, in float32
# ---------------------------------------------------------------------------


def mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(s: Shapes, x, pos, rot: int, pair):
    """RoPE on the first ``rot`` dimensions of each head of x (S, H, hd)
    at positions pos (S,); ``pair(xr, cos, sin)`` rotates them as the
    family pairs them."""
    freqs = s.rope_theta ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = pos.astype(F32)[:, None] * freqs[None]  # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    xr, rest = x[..., :rot], x[..., rot:]
    return jnp.concatenate([pair(xr, cos, sin), rest], axis=-1)


def attention(s: Shapes, q, k, v):
    """Causal GQA attention, one block of query rows at a time."""
    n = q.shape[0]
    g = s.heads // s.kv_heads
    q = q.reshape(n // Q_BLOCK, Q_BLOCK, s.kv_heads, g, s.head_dim)
    kpos = jnp.arange(n)

    def block(args):
        i, qb = args
        sc = mm("qkgd,tkd->kgqt", qb, k) * s.head_dim ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return mm("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(block, (jnp.arange(n // Q_BLOCK), q))
    return out.reshape(n, s.q_dim)


def logits(s: Shapes, w: dict, tokens, rope):
    """Logits (n, vocab) at every position of ``tokens``, with the
    family's ``rope(s, x, pos)``."""
    n = tokens.shape[0]
    pos = jnp.arange(n)
    x = w["embed"][tokens].astype(F32)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(F32), lw)
        h = rms(x, lw["attn_norm"], s.eps)
        q = mm("sd,de->se", h, lw["wq"]).reshape(n, s.heads, s.head_dim)
        k = mm("sd,de->se", h, lw["wk"]).reshape(n, s.kv_heads, s.head_dim)
        v = mm("sd,de->se", h, lw["wv"]).reshape(n, s.kv_heads, s.head_dim)
        q, k = rope(s, q, pos), rope(s, k, pos)
        x = x + mm("se,ed->sd", attention(s, q, k, v), lw["wo"])
        h = rms(x, lw["mlp_norm"], s.eps)
        a = (jax.nn.silu(mm("sd,df->sf", h, lw["w_gate"]))
             * mm("sd,df->sf", h, lw["w_up"]))
        return x + mm("sf,fd->sd", a, lw["w_down"]), None

    keys = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
            "w_up", "w_down")
    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in keys})
    h = rms(x, w["final_norm"].astype(F32), s.eps)
    head = w["embed"].T if s.tied else w["lm_head"]
    return mm("sd,dv->sv", h, head.astype(F32))


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------


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


def decode_bytes(s: Shapes, ticks: int, contexts: Iterable[int],
                 lanes_per_tick: Optional[float] = None) -> float:
    """Bytes ``ticks`` decode ticks must move: the weights once per tick,
    whatever the tick's lanes, and for each decoded token the KV of its
    context read and its own K and V written."""
    kv = kv_bytes_per_token(s)
    return ticks * decode_weight_bytes(s) + kv * sum(contexts)
