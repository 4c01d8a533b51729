"""The control of the correctness check: the plain reference with its
weights rounded to int8, put in the served program's place.

The configurations state bfloat16; int8 is the step below it that a
later change could be tempted by. Every matrix that the model family
lists in its ``matrices`` is rounded to int8 with one scale per output
column (the dense families' embedding, which is also the tied head, one
per row), in the published layout and the served dtype, and the
family's reference forward pass runs on those weights. At each position
of a served request's prompt and served tokens the control picks its
token as the lane does: a greedy lane the int8 reference's best, a sampled
lane a draw from the int8 reference's distribution under the mix's
temperature, top-k and top-p. The float32 reference then judges those
picks as ``reference.gaps`` judges the served tokens. A sharded
engine refuses the program's own int8 weights, so this control stands
in for the program's int8 path there. The benchmark's own runs never run
it (``bench/calibrate.py --precisions int8ref``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference
from weights import family_of


def _int8(m, axis: int):
    x = m.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    q = jnp.round(x / jnp.where(scale > 0, scale, 1.0))
    return (q * scale).astype(m.dtype)


@functools.partial(jax.jit, static_argnums=0)
def int8_weights(s, w: dict) -> dict:
    """``w`` (``weights.published``) with every matrix of the family's
    ``matrices`` rounded to int8, its scales along the axis given there."""
    axes = family_of(s).matrices
    return {k: _int8(v, axes[k]) if k in axes else v for k, v in w.items()}


@functools.partial(jax.jit, static_argnums=(0, 3))
def _picks(s, w8, tokens, sampling, key):
    """The int8 reference's greedy and sampled token at every row."""
    logits = family_of(s).logits(s, w8, tokens)
    temperature, top_k, top_p = sampling
    top, idx = jax.lax.top_k(logits / temperature,
                             top_k if top_k > 0 else logits.shape[-1])
    p = jax.nn.softmax(top, axis=-1)
    keep = jnp.cumsum(p, axis=-1) - p < top_p  # fewest reaching top_p
    draw = jax.random.categorical(key, jnp.where(keep, top, -jnp.inf))
    sampled = jnp.take_along_axis(idx, draw[:, None], axis=-1)[:, 0]
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), sampled


def gaps(s, w, w8, prompt, served, sampling, key):
    """For one served request, like ``reference.gaps``: the float32
    reference's gap of the int8 reference's greedy pick at every served
    position, and the gap below the sampler's allowed set of its sampled
    pick."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    n = -(-len(seq) // reference.Q_BLOCK) * reference.Q_BLOCK
    tokens = np.zeros(n, np.int32)
    tokens[:len(seq)] = seq
    tokens = jnp.asarray(tokens)
    sampling = tuple(sampling)
    greedy, sampled = _picks(s, w8, tokens, sampling, key)
    best, _ = reference._gaps(s, w, tokens, greedy, sampling)
    _, floor = reference._gaps(s, w, tokens, sampled, sampling)
    first = len(prompt) - 1  # the row whose logits chose served[0]
    rows = slice(first, first + len(served))
    return np.asarray(best)[rows], np.asarray(floor)[rows]
