"""The plain reference: the published forward pass in float32 jax.numpy.

No cache, no batching, no kernels, and nothing imported from the program.
It reads the weights in the published layout (``weights.published``),
runs one sequence at a time layer by layer, and scores the served tokens
against its logits. A greedy token is judged by how far its logit lies
below the best; a sampled one by how far it lies below the set the
sampler may draw from.

Llama (Granite): RMSNorm, RoPE over the whole head in split halves, GQA
attention, SwiGLU MLP. ChatGLM: the same, but RoPE rotates interleaved
pairs of the first half of each head only (``original_rope``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512  # query rows per attention block


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(s, x, pos):
    """x (S, H, hd), pos (S,)."""
    rot = s.rot_dim
    freqs = s.rope_theta ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = pos.astype(F32)[:, None] * freqs[None]  # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    xr, rest = x[..., :rot], x[..., rot:]
    if s.family == "chatglm":  # interleaved pairs (2i, 2i+1)
        x0, x1 = xr[..., 0::2], xr[..., 1::2]
        out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
        out = out.reshape(xr.shape)
    else:  # split halves (i, i + rot/2)
        x0, x1 = xr[..., :rot // 2], xr[..., rot // 2:]
        out = jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)
    return jnp.concatenate([out, rest], axis=-1)


def _attention(s, q, k, v):
    """Causal GQA attention, one block of query rows at a time."""
    n = q.shape[0]
    g = s.heads // s.kv_heads
    q = q.reshape(n // Q_BLOCK, Q_BLOCK, s.kv_heads, g, s.head_dim)
    kpos = jnp.arange(n)

    def block(args):
        i, qb = args
        sc = _mm("qkgd,tkd->kgqt", qb, k) * s.head_dim ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(block, (jnp.arange(n // Q_BLOCK), q))
    return out.reshape(n, s.q_dim)


def _logits(s, w, tokens):
    """Logits (n, vocab) at every position of ``tokens``."""
    n = tokens.shape[0]
    pos = jnp.arange(n)
    x = w["embed"][tokens].astype(F32)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(F32), lw)
        h = _rms(x, lw["attn_norm"], s.eps)
        q = _mm("sd,de->se", h, lw["wq"]).reshape(n, s.heads, s.head_dim)
        k = _mm("sd,de->se", h, lw["wk"]).reshape(n, s.kv_heads, s.head_dim)
        v = _mm("sd,de->se", h, lw["wv"]).reshape(n, s.kv_heads, s.head_dim)
        q, k = _rope(s, q, pos), _rope(s, k, pos)
        x = x + _mm("se,ed->sd", _attention(s, q, k, v), lw["wo"])
        h = _rms(x, lw["mlp_norm"], s.eps)
        a = (jax.nn.silu(_mm("sd,df->sf", h, lw["w_gate"]))
             * _mm("sd,df->sf", h, lw["w_up"]))
        return x + _mm("sf,fd->sd", a, lw["w_down"]), None

    keys = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
            "w_up", "w_down")
    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in keys})
    h = _rms(x, w["final_norm"].astype(F32), s.eps)
    head = w["embed"].T if s.tied else w["lm_head"]
    return _mm("sd,dv->sv", h, head.astype(F32))


def _nucleus_floor(logits, sampling):
    """Per row, the lowest logit the sampler may draw: the temperature
    scales the logits, top-k keeps the k largest, and top-p keeps the
    fewest of those, best first, whose share of the kept probability
    reaches p. In the logits' own units."""
    temperature, top_k, top_p = sampling
    x = logits / temperature
    k = top_k if top_k > 0 else x.shape[-1]
    top, _ = jax.lax.top_k(x, k)  # (n, k), best first
    if top_p >= 1.0:
        return top[:, -1] * temperature
    cum = jnp.cumsum(jax.nn.softmax(top, axis=-1), axis=-1)
    j = jnp.argmax(cum >= top_p, axis=-1)
    return jnp.take_along_axis(top, j[:, None], -1)[:, 0] * temperature


@functools.partial(jax.jit, static_argnums=(0, 4))
def _gaps(s, w, tokens, picks, sampling):
    ref = _logits(s, w, tokens)
    mine = jnp.take_along_axis(ref, picks[:, None], axis=-1)[:, 0]
    below_floor = jnp.maximum(_nucleus_floor(ref, sampling) - mine, 0.0)
    return ref.max(axis=-1) - mine, below_floor


def gaps(s, w, prompt, served, sampling):
    """For one served request: run the reference over the prompt and the
    served tokens, and return two numbers per served token. The first is
    how far its reference logit lies below the reference's best (the
    measure of a greedy token); the second how far it lies below the
    lowest logit that the sampler's ``sampling`` = (temperature, top_k,
    top_p) lets through (the measure of a sampled token: 0 inside the
    allowed set)."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    n = -(-len(seq) // Q_BLOCK) * Q_BLOCK  # causal: end padding is inert
    tokens = np.zeros(n, np.int32)
    tokens[:len(seq)] = seq
    first = len(prompt) - 1  # the row whose logits chose served[0]
    picks = np.zeros(n, np.int32)
    picks[first:first + len(served)] = served
    best, floor = _gaps(s, w, jnp.asarray(tokens), jnp.asarray(picks),
                        tuple(sampling))
    rows = slice(first, first + len(served))
    return np.asarray(best)[rows], np.asarray(floor)[rows]
