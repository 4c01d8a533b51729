"""Tensor/expert-parallel sharded serving: one replica over an 8-device
mesh must be observably IDENTICAL to the 1-chip engine — bit-identical
streams (the exact GSPMD profile all-gathers activations instead of
psum-reducing partial products), flat trace counts, and the same page
accounting under preemption churn.

These tests need 8 XLA devices. Where the process has them (the CI
shard8 matrix cell, whose conftest forces 8 host devices before jax
starts) they run here. Elsewhere this file runs once more in a child
pytest with ``--xla_force_host_platform_device_count=8`` added to
``XLA_FLAGS`` on the CPU, and each test here passes or fails as it did in
that child, printing the child's failure and output. Engines are built
directly from pinned ``EngineConfig``s — each test needs a tp=1 and a
tp=8 engine side by side, so the matrix cell's topology override must
not apply."""
import dataclasses
import functools
import inspect
import os
import subprocess
import sys
from xml.etree import ElementTree

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import (
    DeviceTopology,
    EngineConfig,
    Request,
    SamplingParams,
    ServingEngine,
)

NDEV = 8
FORCE = f"--xla_force_host_platform_device_count={NDEV}"
IN_PROCESS = jax.local_device_count() >= NDEV


@pytest.fixture(scope="module")
def child_run(tmp_path_factory):
    """Runs this file once in a child pytest on NDEV forced CPU devices.
    Returns a function from a test's name to why it failed there ('' when
    it passed)."""
    flags = os.environ.get("XLA_FLAGS", "")
    assert FORCE not in flags, (
        f"{FORCE} gave {jax.local_device_count()} devices, not {NDEV}")
    report = tmp_path_factory.mktemp("shard8") / "junit.xml"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} {FORCE}".strip())
    child = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         f"--junitxml={report}", os.path.abspath(__file__)],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=900)
    output = (child.stdout + child.stderr)[-8000:]
    cases = {}
    if report.exists():
        for case in ElementTree.parse(report).iter("testcase"):
            cases[case.get("name")] = "\n".join(
                f"{e.tag}: {e.get('message', '')}\n{e.text or ''}"
                for e in case if e.tag in ("failure", "error", "skipped"))

    def failure(name):
        if name not in cases:
            return (f"{name}: no result from the {NDEV}-device child "
                    f"(exit {child.returncode})\n{output}")
        if cases[name]:
            return (f"{name} failed on {NDEV} devices in the child:\n"
                    f"{cases[name]}\n--- child output ---\n{output}")
        return ""

    return failure


def on_ndev(test):
    """``test`` itself where this process has NDEV devices. Elsewhere a
    stand-in with the same name, marks and parameters that passes or fails
    as ``test`` did in the child run."""
    if IN_PROCESS:
        return test

    @functools.wraps(test)
    def judged(request, **_):
        why = request.getfixturevalue("child_run")(request.node.name)
        if why:
            pytest.fail(why, pytrace=False)

    sig = inspect.signature(test)
    judged.__signature__ = sig.replace(parameters=[
        inspect.Parameter("request", inspect.Parameter.POSITIONAL_OR_KEYWORD),
        *sig.parameters.values()])
    return judged


@pytest.fixture(scope="module")
def dense():
    """8 kv heads so the paged pools' kv-head axis splits 8 ways."""
    cfg = dataclasses.replace(get_config("granite-8b").reduced(),
                              num_heads=NDEV, num_kv_heads=NDEV)
    return cfg, init_params(cfg, jax.random.key(0))


@pytest.fixture(scope="module")
def moe():
    cfg = dataclasses.replace(get_config("grok-1-314b").reduced(),
                              num_heads=NDEV, num_kv_heads=NDEV,
                              num_experts=NDEV, moe_expert_parallel=True)
    return cfg, init_params(cfg, jax.random.key(1))


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 500, n).astype(np.int32)


def _mixed_workload(n, *, max_new=6):
    """Greedy and seeded-stochastic streams interleaved: identity must
    hold through BOTH the argmax and the gumbel/top-k sampling paths."""
    return [Request(rid=i, prompt=_prompt(8 + 2 * i, seed=i),
                    max_new_tokens=max_new,
                    sampling=(SamplingParams() if i % 2 == 0 else
                              SamplingParams(temperature=0.8, top_k=40,
                                             seed=100 + i)))
            for i in range(n)]


def _serve(eng, reqs, t0=0.0):
    t = t0
    for r in reqs:
        eng.submit(r, t)
    while not all(r.done for r in reqs):
        t += 1.0
        eng.step(t)
    eng.drain(t + 1.0)
    return [tuple(r.output) for r in reqs]


def _pair(cfg, params, **kw):
    mk = lambda tp: ServingEngine(cfg, params, EngineConfig(
        slots=2, window=64, chunk_prefill=16,
        topology=DeviceTopology(tp=tp), **kw))
    return mk(1), mk(NDEV)


# ---------------------------------------------------------------------------
# stream bit-identity: the sharded-replica contract
# ---------------------------------------------------------------------------


@on_ndev
@pytest.mark.parametrize("kw", [dict(paged=True), dict(prefix_cache=True)],
                         ids=["paged", "prefix_cache"])
def test_sharded_streams_bit_identical(dense, kw):
    cfg, params = dense
    base, shard = _pair(cfg, params, **kw)
    assert shard.mesh is not None and base.mesh is None
    sb = _serve(base, _mixed_workload(4))
    ss = _serve(shard, _mixed_workload(4))
    assert sb == ss  # not close — EQUAL, token for token


@on_ndev
def test_sharded_trace_parity(dense):
    """Tensor parallelism must not multiply compiles: the sharded engine
    reuses one prefill and one decode trace exactly like 1-chip."""
    cfg, params = dense
    base, shard = _pair(cfg, params)
    _serve(base, _mixed_workload(4))
    _serve(shard, _mixed_workload(4))
    assert (shard.prefill_traces, shard.decode_traces) \
        == (base.prefill_traces, base.decode_traces)


@on_ndev
def test_sharded_moe_expert_parallel_bit_identical(moe):
    """Expert-parallel MoE decode under the strict capacity policy (the
    sharded-MoE default): the expert all-to-all must not perturb a single
    logit. Policy pinned on BOTH engines so capacity dims match."""
    cfg, params = moe
    base, shard = _pair(cfg, params, moe_capacity_policy="strict")
    assert shard.moe_capacity_policy == "strict"
    sb = _serve(base, _mixed_workload(3, max_new=5))
    ss = _serve(shard, _mixed_workload(3, max_new=5))
    assert sb == ss


@on_ndev
def test_sharded_moe_strict_is_default(moe):
    cfg, params = moe
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=2, window=64, topology=DeviceTopology(tp=NDEV)))
    assert eng.moe_capacity_policy == "strict"


# ---------------------------------------------------------------------------
# preemption over sharded paged pools
# ---------------------------------------------------------------------------


@on_ndev
def test_sharded_preempt_restore_exact_and_pages_drain(dense):
    """Host-side page tables are layout-identical under sharding, so the
    preempt/restore machinery must work unchanged: the restored stream is
    bit-identical to an undisturbed sharded run and no page leaks."""
    cfg, params = dense
    kw = dict(slots=1, window=64, max_seq=64, sync_every=1, chunk_prefill=0,
              topology=DeviceTopology(tp=NDEV))
    samp = SamplingParams(temperature=0.7, top_k=20, top_p=0.95, seed=77)

    ref_eng = ServingEngine(cfg, params, EngineConfig(**kw))
    ref = Request(0, _prompt(20), max_new_tokens=10, sampling=samp)
    assert ref_eng.try_admit(ref, 0.0)
    _serve(ref_eng, [ref], t0=0.0)

    eng = ServingEngine(cfg, params, EngineConfig(**kw, preemption=True))
    victim = Request(0, _prompt(20), max_new_tokens=10, sampling=samp,
                     ttft_slo_s=100.0)
    assert eng.try_admit(victim, 0.0)
    for t in (1.0, 2.0, 3.0):
        eng.step(t)
    assert len(victim.output) >= 2  # mid-decode when the preemptor lands
    hot = Request(1, _prompt(10, seed=9), max_new_tokens=3, priority=1,
                  ttft_slo_s=1.0)
    eng.submit(hot, 3.0)
    t = 3.0
    while not (victim.done and hot.done):
        t += 1.0
        eng.step(t)
    eng.drain(t + 1.0)
    assert victim.preemptions >= 1
    assert list(victim.output) == list(ref.output)
    assert eng.allocator.pages_in_use == 0
    assert eng.allocator.total_refs == 0


# ---------------------------------------------------------------------------
# telemetry: the router's sharding signal
# ---------------------------------------------------------------------------


@on_ndev
def test_sharded_load_report_axis_fields(dense):
    cfg, params = dense
    _, shard = _pair(cfg, params)
    rep = shard.load_report()
    assert rep.n_chips == NDEV
    assert dict(rep.mesh_axes) == {"data": 1, "model": NDEV}
    cs = dict(rep.axis_collective_s)
    assert cs["model"] > 0.0 and cs["data"] == 0.0
    util = dict(rep.axis_util)
    assert 0.0 < util["model"] < 1.0
    # the wire shape survives the new fields
    from repro.serving import LoadReport
    assert LoadReport.from_dict(rep.to_dict()) == rep
