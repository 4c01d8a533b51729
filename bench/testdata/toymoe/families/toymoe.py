"""A toy mixture-of-experts decoder, the family of the CPU test that a
model family is added as files only (``bench/test_families.py``).

Each layer is the dense families' GQA attention with split-half RoPE
(``_dense.py``), then routed experts in place of the MLP: a float32
router over all experts, softmax, the top k by probability, and each of
those experts' GeGLU (GELU in its tanh form) weighted by its probability,
not renormalized. Keys after Mixtral's config.json (``num_local_experts``,
``num_experts_per_tok``).
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

import jax
import jax.numpy as jnp

from families import _dense
from families.llama import rope

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
    ff: int  # one expert's width
    experts: int
    top_k: int
    vocab: int
    eps: float
    rope_theta: float
    tied: bool = False

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def shapes(conf: dict) -> Shapes:
    return Shapes("toymoe", conf["num_hidden_layers"], conf["hidden_size"],
                  conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"], conf["intermediate_size"],
                  conf["num_local_experts"], conf["num_experts_per_tok"],
                  conf["vocab_size"], conf["rms_norm_eps"],
                  conf["rope_theta"],
                  bool(conf.get("tie_word_embeddings", False)))


def program_check(s: Shapes) -> dict:
    return {"arch_type": "moe", "num_layers": s.layers, "d_model": s.d,
            "num_heads": s.heads, "num_kv_heads": s.kv_heads,
            "resolved_head_dim": s.head_dim, "d_ff": s.ff,
            "num_experts": s.experts, "experts_per_token": s.top_k,
            "moe_layer_period": 1, "moe_shared_expert": False,
            "mlp_variant": "geglu", "vocab_size": s.vocab,
            "rope_variant": "standard", "rope_theta": s.rope_theta,
            "tie_embeddings": s.tied}


def published(s: Shapes, key, dtype=jnp.bfloat16) -> dict:
    ks = iter(jax.random.split(key, 16))
    L, E = s.layers, s.experts

    def mat(shape, fan_in, dt=dtype):
        return jax.random.normal(next(ks), shape, dt) * fan_in ** -0.5

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
        "router": mat((L, s.d, E), s.d, F32),
        "w_gate": mat((L, E, s.d, s.ff), s.d),
        "w_up": mat((L, E, s.d, s.ff), s.d),
        "w_down": mat((L, E, s.ff, s.d), s.ff),
        "final_norm": norm((s.d,)),
    }
    if not s.tied:
        w["lm_head"] = mat((s.d, s.vocab), s.d)
    return w


def to_program(s: Shapes, w: dict) -> dict:
    def scale(x):  # the program's RMSNorm multiplies by (1 + scale)
        return (x.astype(F32) - 1.0).astype(x.dtype)

    block = {
        "norm1": {"scale": scale(w["attn_norm"])},
        "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
        "norm2": {"scale": scale(w["mlp_norm"])},
        "moe": {k: w[k] for k in ("router", "w_gate", "w_up", "w_down")},
    }
    out = {"body": [block], "tail": [],
           "final_norm": {"scale": scale(w["final_norm"])},
           "embed": w["embed"]}
    if not s.tied:
        out["lm_head"] = w["lm_head"]
    return out


split_first: dict = {}  # to_program only renames


def published_shardings(s: Shapes, mesh) -> dict:
    from jax.sharding import NamedSharding, PartitionSpec as P

    return {k: NamedSharding(mesh, P()) for k in
            jax.eval_shape(lambda: published(s, jax.random.key(0)))}


matrices = {"wq": -2, "wk": -2, "wv": -2, "wo": -2, "w_gate": -2,
            "w_up": -2, "w_down": -2, "lm_head": -2, "embed": -1}


def _experts(s: Shapes, h, lw):
    """Every expert over every row, each row's top k kept at their
    router probabilities."""
    n = h.shape[0]
    probs = jax.nn.softmax(_dense.mm("sd,de->se", h, lw["router"]), axis=-1)
    gate, idx = jax.lax.top_k(probs, s.top_k)
    weight = jnp.zeros_like(probs).at[jnp.arange(n)[:, None], idx].set(gate)
    a = (jax.nn.gelu(_dense.mm("sd,edf->sef", h, lw["w_gate"]))
         * _dense.mm("sd,edf->sef", h, lw["w_up"]))
    y = _dense.mm("sef,efd->sed", a, lw["w_down"])
    return _dense.mm("se,sed->sd", weight, y)


def logits(s: Shapes, w: dict, tokens):
    n = tokens.shape[0]
    pos = jnp.arange(n)
    x = w["embed"][tokens].astype(F32)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(F32), lw)
        h = _dense.rms(x, lw["attn_norm"], s.eps)
        q = _dense.mm("sd,de->se", h, lw["wq"]).reshape(n, s.heads, s.head_dim)
        k = _dense.mm("sd,de->se", h, lw["wk"]).reshape(n, s.kv_heads,
                                                         s.head_dim)
        v = _dense.mm("sd,de->se", h, lw["wv"]).reshape(n, s.kv_heads,
                                                         s.head_dim)
        q, k = rope(s, q, pos), rope(s, k, pos)
        x = x + _dense.mm("se,ed->sd", _dense.attention(s, q, k, v), lw["wo"])
        return x + _experts(s, _dense.rms(x, lw["mlp_norm"], s.eps), lw), None

    keys = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "router",
            "w_gate", "w_up", "w_down")
    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in keys})
    h = _dense.rms(x, w["final_norm"].astype(F32), s.eps)
    head = w["embed"].T if s.tied else w["lm_head"]
    return _dense.mm("sd,dv->sv", h, head.astype(F32))


# --- work counts: the k routed experts of each token, and the router ---


def _attn_params(s: Shapes) -> int:
    return s.d * (s.q_dim + 2 * s.kv_dim) + s.q_dim * s.d


def _body_flops(s: Shapes) -> float:
    per_layer = _attn_params(s) + s.d * s.experts + s.top_k * 3 * s.d * s.ff
    return 2.0 * s.layers * per_layer


def _attn_flops(s: Shapes, keys: float) -> float:
    return 4.0 * s.layers * s.heads * s.head_dim * keys


def prefill_flops(s: Shapes, prompt_len: int) -> float:
    p = prompt_len
    return (p * _body_flops(s) + _attn_flops(s, p * (p + 1) / 2)
            + 2.0 * s.d * s.vocab)


def decode_flops(s: Shapes, context: int) -> float:
    return _body_flops(s) + _attn_flops(s, context) + 2.0 * s.d * s.vocab


def decode_bytes(s: Shapes, ticks: int, contexts: Iterable[int],
                 lanes_per_tick: float) -> float:
    """Weights a tick reads: all but the experts, and of each layer's
    experts those that the tick's lanes route to, expected under uniform
    routing; and each token's KV."""
    touched = s.experts * (1 - (1 - s.top_k / s.experts) ** lanes_per_tick)
    per_layer = (_attn_params(s) + 2 * s.d + touched * 3 * s.d * s.ff
                 + 2 * s.d * s.experts)  # the router is float32
    weights = s.layers * per_layer + s.d + s.d * s.vocab
    kv = 2 * s.layers * s.kv_dim * sum(contexts)
    return BYTES * (ticks * weights + kv)
