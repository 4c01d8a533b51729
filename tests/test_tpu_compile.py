"""Compile the Pallas kernels and the fused decode step for a TPU v5e.

Nothing runs: the installed TPU compiler compiles each program for a
described (not attached) ``v5e:2x2`` topology at granite-8b's published
widths, so a block shape or layout the chip's compiler refuses fails here,
where interpret mode cannot see it. The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import get_config
from repro.core.hardware import TPU_V5E
from repro.kernels import ops
from repro.models import decode_step, init_paged_cache, param_specs
from repro.serving.engine import (
    decode_scan_step,
    decode_tick,
    init_sampling_state,
)
from test_paged_layer_scan import pool_moves

# granite-8b's published attention widths; serving shapes of chip_smoke.py
B, H, KV, D = 8, 32, 8, 128
PS, N_PAGES = 16, 128  # 2048-token page tables
POOL = B * N_PAGES + 1  # full headroom + the trash page
VOCAB, D_MODEL, D_FF = 49152, 4096, 14336


@pytest.fixture(scope="module")
def v5e_devices():
    """The four chips of a described v5e:2x2 host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_devices):
    return SingleDeviceSharding(v5e_devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(name, sh):
    bf, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
    s = partial(_sds, sh)
    return {
        "flash_attention": (s((1, 512, H, D), bf), s((1, 512, KV, D), bf),
                            s((1, 512, KV, D), bf)),
        "decode_attention": (s((B, 1, H, D), bf), s((B, 2048, KV, D), bf),
                             s((B, 2048, KV, D), bf), s((B,), i32)),
        "paged_decode_attention": (
            s((B, 1, H, D), bf), s((POOL, PS, KV, D), bf),
            s((POOL, PS, KV, D), bf), s((B, N_PAGES), i32), s((B,), i32)),
        "paged_decode_attention_int8": (
            s((B, 1, H, D), bf), s((POOL, PS, KV, D), i8),
            s((POOL, PS, KV, D), i8), s((POOL, PS, KV, 1), f32),
            s((POOL, PS, KV, 1), f32), s((B, N_PAGES), i32), s((B,), i32)),
        "topk_sample": (s((B, VOCAB), f32), s((B,), i32), s((B,), f32),
                        s((B, VOCAB), f32)),
        "int8_matmul": (s((B, D_MODEL), bf), s((D_MODEL, D_FF), i8),
                        s((D_FF,), f32)),
        "rglru_scan": (s((1, 512, D_MODEL), bf), s((1, 512, D_MODEL), bf),
                       s((1, D_MODEL), bf)),
    }[name]


@pytest.mark.parametrize("name", [
    "flash_attention", "decode_attention", "paged_decode_attention",
    "paged_decode_attention_int8", "topk_sample", "int8_matmul",
    "rglru_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    compiled = getattr(ops, name).lower(
        *_kernel_args(name, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: the cells' attention widths: granite-8b (8 kv heads, 8 slots) and
#: chatglm3-6b (2 kv heads, 16 slots), at 2048-token page tables
WIDTHS = {"granite-8b": B, "chatglm3-6b": 16}


def _paged_program(arch, sharding, slots, kv_dtype=""):
    """(cfg, params, cache, tokens, samp) of a paged engine at the arch's
    published widths and two layers, as shapes placed by ``sharding``
    (one sharding, or a function of (cfg, tree) -> tree of them)."""
    cfg = dataclasses.replace(get_config(arch), num_layers=2)
    assert cfg.dtype == "bfloat16" and cfg.resolved_head_dim == D
    place = sharding if callable(sharding) else (
        lambda c, tree: jax.tree.map(lambda _: sharding, tree))
    pool = slots * N_PAGES + 1

    def placed(tree):
        return jax.tree.map(lambda x, s: _sds(s, x.shape, x.dtype), tree,
                            place(cfg, tree))

    params = placed(param_specs(cfg))
    cache = placed(jax.eval_shape(lambda: init_paged_cache(
        cfg, slots, pool, PS, N_PAGES, kv_dtype)))
    samp = placed(jax.eval_shape(lambda: init_sampling_state(slots)))
    tokens = placed(jax.ShapeDtypeStruct((slots,), jnp.int32))
    return cfg, params, cache, tokens, samp


def whole_pool_shapes(cfg, cache, slots):
    """Shapes under which an op would yield a layer's or the stack's
    whole pool other than as the engine keeps it: kv-head major
    (transposed), or gathered into every slot's whole row."""
    l, p, ps, kvh, d = jax.tree.leaves(cache["body"])[0].shape
    return {(kvh, l * p, ps, d), (kvh, p, ps, d), (l, kvh, p, ps, d),
            (slots, N_PAGES, ps, kvh, d), (slots, N_PAGES * ps, kvh, d)}


_RESULT = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]")


def result_shapes(hlo: str) -> set:
    """The result shape of every instruction of a compiled program's text,
    fused computations included."""
    return {tuple(int(d) for d in m.group(1).split(",") if d)
            for m in map(_RESULT.match, hlo.splitlines()) if m}


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_decode_scan_step_compiles_for_v5e(one_chip, arch):
    """The served decode window (8 fused ticks, paged KV, per-slot
    sampling) at published widths in bfloat16, two scanned layers: it
    attends through the Pallas paged kernel, booked to ``attn_core``, and
    no op copies, slices, transposes or gathers a whole pool."""
    slots = WIDTHS[arch]
    cfg, params, cache, tokens, samp = _paged_program(arch, one_chip, slots)
    step = jax.jit(partial(decode_scan_step, cfg, n=8), donate_argnums=(1,))
    compiled = step.lower(params, cache, tokens, samp).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < TPU_V5E.hbm_bytes)
    hlo = compiled.as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all("attn_core" in line for line in kernels)
    # the layer scan updates the stacked pools in place: no copy of a
    # stack, and no layer's pool sliced out of it
    pools = {s for a in jax.tree.leaves(cache["body"])
             for s in (a.shape, a.shape[1:])}
    assert pool_moves(hlo, pools) == []
    assert result_shapes(hlo) & whole_pool_shapes(cfg, cache, slots) == set()


def _tp4(v5e_devices):
    """Placement of a tp=4 replica over the four chips, as the engine's
    serving policy lays it out: pools split by kv head."""
    from jax.sharding import Mesh

    from repro.core.simd.sharding import (
        paged_cache_pspecs,
        param_pspecs,
        serving_policy,
        to_shardings,
    )

    mesh = Mesh(np.array(v5e_devices).reshape(1, 4), ("data", "model"))
    replicated = NamedSharding(mesh, PartitionSpec())

    def place(cfg, tree):
        pol = serving_policy(cfg, mesh)
        if not isinstance(tree, dict):
            return replicated
        if "page_table" in tree:
            return to_shardings(mesh, paged_cache_pspecs(cfg, tree, pol,
                                                         mesh))
        if "embed" in tree:
            return to_shardings(mesh, param_pspecs(cfg, tree, pol))
        return jax.tree.map(lambda _: replicated, tree)

    return place


@pytest.mark.parametrize("case,kernel", [
    ("one-chip", True), ("tp4", False), ("int8", False), ("chunk", False)])
def test_decode_lowers_the_kernel_only_where_it_applies(v5e_devices,
                                                        one_chip, case,
                                                        kernel):
    """Lowered for TPU, a one-chip bf16 decode tick calls the Pallas
    paged kernel; a tp=4 replica's tick, an int8-pool tick and an S>1
    chunk through the pages keep the jnp path."""
    place = _tp4(v5e_devices) if case == "tp4" else one_chip
    cfg, params, cache, tokens, samp = _paged_program(
        "granite-8b", place, B, kv_dtype="int8" if case == "int8" else "")
    if case == "chunk":
        chunk = _sds(one_chip, (B, 4), jnp.int32)
        lowered = jax.jit(lambda p, c, t: decode_step(
            cfg, p, c, {"tokens": t})).lower(params, cache, chunk)
    else:
        lowered = jax.jit(partial(decode_tick, cfg)).lower(
            params, cache, tokens, samp)
    assert ("tpu_custom_call" in lowered.as_text()) == kernel
