"""Compile the Pallas kernels and the fused decode step for a TPU v5e.

Nothing runs: the installed TPU compiler compiles each program for a
described (not attached) ``v5e:2x2`` topology at granite-8b's published
widths, so a block shape or layout the chip's compiler refuses fails here,
where interpret mode cannot see it. The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.hardware import TPU_V5E
from repro.kernels import ops
from repro.models import init_paged_cache, param_specs
from repro.serving.engine import decode_scan_step, init_sampling_state
from test_paged_layer_scan import pool_moves

# granite-8b's published attention widths; serving shapes of chip_smoke.py
B, H, KV, D = 8, 32, 8, 128
PS, N_PAGES = 16, 128  # 2048-token page tables
POOL = B * N_PAGES + 1  # full headroom + the trash page
VOCAB, D_MODEL, D_FF = 49152, 4096, 14336


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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


def test_decode_scan_step_compiles_for_v5e(one_chip):
    """The served decode window (8 fused ticks, paged KV, per-slot
    sampling) at published widths in bfloat16, two scanned layers."""
    cfg = dataclasses.replace(get_config("granite-8b"), num_layers=2)
    assert cfg.dtype == "bfloat16" and cfg.d_model == D_MODEL

    def placed(tree):
        return jax.tree.map(
            lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = placed(param_specs(cfg))
    cache = placed(jax.eval_shape(
        lambda: init_paged_cache(cfg, B, POOL, PS, N_PAGES)))
    samp = placed(jax.eval_shape(lambda: init_sampling_state(B)))
    step = jax.jit(partial(decode_scan_step, cfg, n=8), donate_argnums=(1,))
    compiled = step.lower(params, cache, _sds(one_chip, (B,), jnp.int32),
                          samp).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < TPU_V5E.hbm_bytes)
    # the layer scan updates the stacked pools in place: no copy of a
    # stack, and no layer's pool sliced out of it
    pools = {s for a in jax.tree.leaves(cache["body"])
             for s in (a.shape, a.shape[1:])}
    assert pool_moves(compiled.as_text(), pools) == []
