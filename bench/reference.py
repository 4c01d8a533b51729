"""The plain reference's judgement of served tokens.

The reference is the model family's published forward pass in float32
jax.numpy (``logits`` of ``families/<family>.py``): no cache, no
batching, no kernels, and nothing imported from the program. It reads
the weights in the published layout (``weights.published``) and runs one
sequence at a time. Here the served tokens are scored against its
logits: a greedy token by how far its logit lies below the best, a
sampled one by how far it lies below the set the sampler may draw from.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from weights import family_of

#: query rows per block of a family's reference attention; a scored
#: sequence is padded to a multiple of it
Q_BLOCK = 512


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
    ref = family_of(s).logits(s, w, tokens)
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
