"""The served paged decode attention: on the CPU the engine's decode
programs compile to what the jnp oracle alone compiles to, and the
engine counts the pages its decode attends against the whole rows.

(The TPU side, where the Pallas kernel is lowered and where it is not,
is in ``tests/test_tpu_compile.py``; the kernel against the oracle is in
``tests/test_kernels.py``.)
"""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.models import layers as L
from repro.serving import EngineConfig, Request, ServingEngine


def _kernel_width(arch):
    """A tiny bfloat16 model whose attention the kernel would take on
    TPU: an even number of kv heads of 128."""
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="bfloat16",
                              head_dim=128)
    assert cfg.num_kv_heads % 2 == 0
    return cfg, init_params(cfg, jax.random.key(0))


def _compiled(cfg, params, program) -> str:
    """One decode program of a fresh paged engine, compiled for the CPU,
    without metadata and the debug tables ahead of the computations."""
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=4, max_seq=128, page_size=16, paged=True, sync_every=4))
    fn = eng._decode if program == "decode" else eng._decode_scan
    hlo = fn.lower(eng.params, eng.cache, eng._tokens,
                   eng._samp).compile().as_text()
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    return hlo[hlo.index("\n%"):]


@pytest.mark.parametrize("program", ["decode", "scan"])
@pytest.mark.parametrize("arch", ["granite-8b", "chatglm3-6b"])
def test_cpu_decode_compiles_as_the_oracle_alone(arch, program,
                                                 monkeypatch):
    cfg, params = _kernel_width(arch)
    with_dispatch = _compiled(cfg, params, program)
    monkeypatch.setattr(L, "paged_kernel_applies", lambda q, k: False)
    oracle_only = _compiled(cfg, params, program)
    assert "tpu_custom_call" not in with_dispatch
    assert with_dispatch == oracle_only


@pytest.mark.parametrize("sync_every", [1, 4])
def test_decode_counts_the_pages_it_attends(sync_every):
    """Each decode tick of a request at position p attends p + 1 tokens,
    ceil((p + 1) / 16) pages; the whole rows are ticks x slots x 8."""
    cfg = dataclasses.replace(get_config("granite-8b").reduced(),
                              num_layers=1)
    eng = ServingEngine(cfg, init_params(cfg, jax.random.key(1)),
                        EngineConfig(slots=3, max_seq=128, page_size=16,
                                     paged=True, sync_every=sync_every))
    reqs = [Request(rid=i, prompt=np.arange(5 + 13 * i, dtype=np.int32),
                    max_new_tokens=9 + 7 * i) for i in range(4)]
    for r in reqs:
        eng.submit(r, 0.0)
    t = 0.0
    while not eng.idle:
        t += 1.0
        eng.step(t)
    eng.drain(t)
    m = eng.metrics
    assert all(len(r.output) == r.max_new_tokens for r in reqs)
    # the first token comes from prefill; every later one from a tick
    want = sum(-(-c // 16) for r in reqs
               for c in range(r.prompt_len + 1,
                              r.prompt_len + r.max_new_tokens))
    assert m.decode_kv_pages_read == want
    assert m.decode_kv_pages_row == m.decode_ticks * 3 * 8
    assert (m.fused_ticks > 0) == (sync_every > 1)
    reg = eng.metrics_registry()
    assert (reg.get("serving_decode_kv_pages_read_total").value
            == m.decode_kv_pages_read)
