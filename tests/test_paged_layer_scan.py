"""The paged decode updates the stacked page pools in place through the
layer scan.

The body's pools ride in the scan's carry and each block scatters and
gathers its layer's pages by flat row ``layer * P + page``. The compiled
decode tick and fused window therefore hold no copy, slice or
update-slice of a stacked pool (nor a layer's pool sliced out of it), and
they compute, bit for bit, what the layer scan computed when it took the
pools as ``xs`` and returned them as ``ys`` (kept here as the oracle).
"""
import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import (
    block_program,
    decode_step,
    init_paged_cache,
    init_params,
)
from repro.models import layers as L
from repro.models.blocks import apply_block
from repro.serving import EngineConfig, PrecisionConfig, ServingEngine
from repro.serving import engine as engine_mod
from repro.serving.engine import (
    decode_scan_step,
    page_table_append,
    slot_release,
)

#: (arch, num_layers, kv_cache_dtype); block programs: dense x n,
#: (dense, moe) x n, and (dense, moe) x 2 + a dense tail
VARIANTS = {
    "granite": ("granite-8b", 3, ""),
    "granite-int8": ("granite-8b", 3, "int8"),
    "chatglm": ("chatglm3-6b", 3, ""),
    "moe": ("llama4-maverick-400b", 4, ""),
    "moe-tail": ("llama4-maverick-400b", 5, ""),
}
SLOTS, PS, MAX_PAGES = 4, 16, 8
POOL = SLOTS * MAX_PAGES + 1


def _model(variant):
    arch, layers, kv = VARIANTS[variant]
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="bfloat16",
                              num_layers=layers)
    return cfg, init_params(cfg, jax.random.key(0)), kv


# ---------------------------------------------------------------------------
# structure: what the compiler is left with
# ---------------------------------------------------------------------------

_INSTR = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\](?:\{[^}]*\})? ([\w\-]+)\(")
_MOVES = ("copy", "copy-done", "dynamic-slice", "dynamic-update-slice")


def pool_moves(hlo: str, shapes) -> list:
    """The copies, slices and update-slices in a compiled program's text
    whose result has one of ``shapes``, fused computations included."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and m.group(2) in _MOVES:
            shape = tuple(int(d) for d in m.group(1).split(",") if d)
            if shape in shapes:
                out.append((m.group(2), shape))
    return out


@pytest.mark.parametrize("program", ["decode", "scan"])
@pytest.mark.parametrize("variant", ["granite", "granite-int8", "chatglm",
                                     "moe"])
def test_decode_programs_move_no_pool(variant, program):
    cfg, params, kv = _model(variant)
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=SLOTS, max_seq=PS * MAX_PAGES, page_size=PS, paged=True,
        sync_every=4, precision=PrecisionConfig(kv_cache_dtype=kv)))
    fn = eng._decode if program == "decode" else eng._decode_scan
    hlo = fn.lower(eng.params, eng.cache, eng._tokens,
                   eng._samp).compile().as_text()
    leaves = jax.tree.leaves(eng.cache["body"])
    assert len(leaves) == len(block_program(cfg)[0]) * (4 if kv else 2)

    def shapes(arrays):  # a stacked pool and one layer's slice of it
        return {s for a in arrays for s in (a.shape, a.shape[1:])}

    # XLA's CPU layout pass keeps the fp32 scale stacks of int8 pages (a
    # trailing unit axis) with that axis major inside the loop and copies
    # them there, however the scan treats them: the int8 values, the
    # pools' bulk, and every bf16 pool are held to no copy at all
    held = shapes(a for a in leaves if kv != "int8" or a.dtype == jnp.int8)
    moves = pool_moves(hlo, shapes(leaves))
    assert [m for m in moves if m[0] != "copy" or m[1] in held] == []


# ---------------------------------------------------------------------------
# numbers: bit for bit what the xs/ys layer scan computed
# ---------------------------------------------------------------------------


def layer_sliced_decode_step(cfg, params, cache, batch):
    """The oracle: the paged decode with each layer's pools sliced out of
    the stacks as scan ``xs`` and written back as ``ys``."""
    pattern, _, tail = block_program(cfg)
    pos, pages = cache["pos"], cache["page_table"]
    tokens = batch["tokens"]
    x = jnp.take(params["embed"], tokens, axis=0)
    rope_pos = pos[:, None] + jnp.arange(tokens.shape[1],
                                         dtype=jnp.int32)[None, :]

    def block(bt, p, x, pool):
        return apply_block(cfg, bt, p, x, rope_pos, mode="decode",
                           cache=pool, pos=pos, pages=pages)[:2]

    def body(x, slices):
        new = []
        for bt, p, pool in zip(pattern, *slices):
            x, pool = block(bt, p, x, pool)
            new.append(pool)
        return x, new

    x, body_pools = jax.lax.scan(body, x, (params["body"], cache["body"]))
    tail_pools = []
    for bt, p, pool in zip(tail, params["tail"], cache["tail"]):
        x, pool = block(bt, p, x, pool)
        tail_pools.append(pool)
    x = L.apply_norm(cfg, params["final_norm"], x)
    head = params.get("lm_head")
    head = params["embed"].T if head is None else head
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    return logits, {"body": body_pools, "tail": tail_pools,
                    "pos": pos + tokens.shape[1], "page_table": pages}


def _filled_cache(cfg, kv):
    """Pools full of seeded values (so every gathered row counts), slots
    at different positions on disjoint pages; a slot's table row ends
    at the page of its next write (the rest is the trash page 0)."""
    cache = init_paged_cache(cfg, SLOTS, POOL, PS, MAX_PAGES, kv)
    keys = iter(jax.random.split(jax.random.key(7), 64))

    def fill(a):
        k = next(keys)
        if a.dtype == jnp.int8:
            return jax.random.randint(k, a.shape, -127, 128, jnp.int8)
        if a.shape[-1] == 1:  # int8 scales
            return jax.random.uniform(k, a.shape, a.dtype, 1e-3, 2e-2)
        return jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)

    cache["body"] = jax.tree.map(fill, cache["body"])
    cache["tail"] = jax.tree.map(fill, cache["tail"])
    pos = np.array([5, 15, 30, 45], np.int32)
    pages = np.random.default_rng(3).permutation(np.arange(1, POOL))
    table = np.zeros((SLOTS, MAX_PAGES), np.int32)
    for s, p in enumerate(pos):
        n = p // PS + 1
        table[s, :n] = pages[s * MAX_PAGES:s * MAX_PAGES + n]
    cache["pos"] = jnp.asarray(pos)
    cache["page_table"] = jnp.asarray(table)
    return cache, pages


def _serve(cfg, params, cache, pages, step):
    """Three single ticks (slot 1 crosses into a page granted after the
    first, slot 2 is released onto the trash page after the second), then
    a fused window of four ticks in which slot 3 crosses into a page
    granted before it. ``step`` is the decode step the ticks run."""
    tick = jax.jit(lambda p, c, t: step(cfg, p, c, {"tokens": t[:, None]}))
    tokens = jnp.asarray([11, 22, 33, 44], jnp.int32)
    seen = []
    for i in range(3):
        logits, cache = tick(params, cache, tokens)
        tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        seen.append((logits, tokens, cache))
        if i == 0:
            cache = page_table_append(cache, 1, 1, int(pages[MAX_PAGES + 1]))
        if i == 1:
            cache = slot_release(cache, 2)
    cache = page_table_append(cache, 3, 3, int(pages[3 * MAX_PAGES + 3]))
    saved = engine_mod.decode_step
    engine_mod.decode_step = step
    try:  # a fresh jit, so the window is traced with ``step``
        window = jax.jit(partial(decode_scan_step, cfg, n=4))
        seen.append(window(params, cache, tokens))
    finally:
        engine_mod.decode_step = saved
    return seen


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_paged_decode_matches_the_layer_sliced_scan(variant):
    cfg, params, kv = _model(variant)
    cache, pages = _filled_cache(cfg, kv)
    got = _serve(cfg, params, cache, pages, decode_step)
    want = _serve(cfg, params, cache, pages, layer_sliced_decode_step)
    final = got[-1][2]
    assert int(final["pos"][3]) == 45 + 3 + 4  # slot 3 crossed a page
    assert int(final["page_table"][2].max()) == 0  # slot 2 on the trash page
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
