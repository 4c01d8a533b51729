"""SIMD + MIMD quadrants: sharding specs and the service router."""
import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core.misd.scheduler import Device, Job
from repro.core.mimd import Instance, ServiceRouter
from repro.core.simd.sharding import (
    batch_pspecs,
    cache_pspecs,
    make_policy,
    param_pspecs,
)
from repro.launch.mesh import make_local_mesh
from repro.models import cache_specs, param_specs


def _mesh11():
    return make_local_mesh()


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_structurally_match(arch):
    cfg = get_config(arch)
    mesh = _mesh11()
    pol = dataclasses.replace(make_policy(cfg, mesh), model_size=16,
                              data_size=16)
    sds = param_specs(cfg)
    specs = param_pspecs(cfg, sds, pol)
    # same tree structure, every spec rank-matching and divisible
    jax.tree.map(
        lambda s, x: _check(s, x),
        specs, sds,
        is_leaf=lambda x: isinstance(x, P))


def _check(spec, sds):
    assert len(spec) == len(sds.shape), (spec, sds.shape)
    for dim, entry in zip(sds.shape, spec):
        if entry is None:
            continue
        n = {"model": 16, "data": 16, "pod": 2}[entry] if isinstance(entry, str) else np.prod(
            [{"model": 16, "data": 16, "pod": 2}[a] for a in entry])
        assert dim % n == 0, (spec, sds.shape)


def test_fsdp_engages_only_for_giants():
    mesh = _mesh11()
    big = dataclasses.replace(
        make_policy(get_config("grok-1-314b"), mesh), model_size=16)
    # recompute with true axis sizes
    from repro.core.simd.sharding import make_policy as mp

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16))

    assert mp(get_config("grok-1-314b"), FakeMesh()).fsdp
    assert mp(get_config("llama4-maverick-400b-a17b"), FakeMesh()).fsdp
    assert not mp(get_config("granite-8b"), FakeMesh()).fsdp
    assert not mp(get_config("starcoder2-15b"), FakeMesh()).fsdp


def test_cache_specs_shard_every_kv_leaf():
    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16))

    cfg = get_config("phi3-medium-14b")
    pol = make_policy(cfg, FakeMesh())
    cs = cache_specs(cfg, 128, 32768)
    specs = cache_pspecs(cfg, cs, pol, FakeMesh())
    flat, _ = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))
    kv = [s for p, s in flat if str(p[-1]) in ("['k']", "['v']") or
          getattr(p[-1], "key", "") in ("k", "v")]
    assert kv and all("model" in [e for e in s if e] for s in kv)


# --- MIMD router -------------------------------------------------------------


def _router(policy):
    r = ServiceRouter(policy=policy)
    for i in range(4):
        r.register(Instance(f"i{i}", "m", Device(f"d{i}", 4)))
    return r


@pytest.mark.parametrize("policy", ["least-loaded", "p2c", "round-robin"])
def test_router_balances(policy):
    r = _router(policy)
    counts = {}
    for i in range(400):
        inst = r.route(Job(i, "m", (0.5, 0.5), 0.01))
        counts[inst.name] = counts.get(inst.name, 0) + 1
        for pool in r.pools.values():
            for it in pool:
                r.drain(it, 0.01)
    assert len(counts) == 4
    assert max(counts.values()) < 3 * min(counts.values())


def test_router_autoscale_signals():
    r = _router("least-loaded")
    assert r.want_scale("m") in (-1, 0)
    for i in range(200):
        r.route(Job(i, "m", (0.5, 0.5), 0.5))
    assert r.want_scale("m") == 1  # pressure built up
    assert r.route(Job(0, "unknown", (0.5, 0.5), 0.01)) is None


def test_router_deregister_stops_routes():
    """A deregistered instance is marked draining and never routed again;
    pools can now shrink as well as grow."""
    r = _router("round-robin")
    gone = r.deregister("i2")
    assert gone is not None and gone.draining and gone.name == "i2"
    assert len(r.pools["m"]) == 3
    hits = {r.route(Job(i, "m", (0.5, 0.5), 0.01)).name for i in range(30)}
    assert hits == {"i0", "i1", "i3"}
    assert r.deregister("i2") is None  # absent now
    assert r.deregister("i0", model="never-registered") is None  # no KeyError
    # re-registering clears draining and restores routes
    r.register(gone)
    assert not gone.draining
    hits = {r.route(Job(i, "m", (0.5, 0.5), 0.01)).name for i in range(40)}
    assert "i2" in hits


def test_router_p2c_single_instance_pool():
    """p2c must degrade to the only instance instead of crashing when a
    pool has shrunk to one replica."""
    r = _router("p2c")
    for name in ("i1", "i2", "i3"):
        r.deregister(name)
    inst = r.route(Job(0, "m", (0.5, 0.5), 0.01))
    assert inst is not None and inst.name == "i0"


def test_router_p2c_deterministic_under_seed():
    """Same seed -> identical p2c routing sequence even under permanent
    exact ties (the seeded sample order is the tie-break), and ties still
    spread across the pool instead of starving later registrations."""

    def choices(seed):
        r = ServiceRouter(policy="p2c", seed=seed)
        for i in range(4):
            r.register(Instance(f"i{i}", "m", Device(f"d{i}", 4)))
        out = []
        for i in range(80):
            inst = r.route(Job(i, "m", (0.5, 0.5), 0.01))
            out.append(inst.name)
            inst.queue_s = 0.0  # force a permanent exact tie
        return out

    a, b = choices(7), choices(7)
    assert a == b
    assert set(a) == {"i0", "i1", "i2", "i3"}  # ties spread, nobody starves


def test_router_predicted_policy():
    """'predicted' scans the whole pool for the minimum predicted
    completion (p2c with full visibility)."""
    r = ServiceRouter(policy="predicted", seed=0)
    for i in range(4):
        inst = r.register(Instance(f"i{i}", "m", Device(f"d{i}", 4)))
        inst.queue_s = 3.0 - 0.5 * i  # i3 is least loaded
    chosen = r.route(Job(0, "m", (0.5, 0.5), 0.01))
    assert chosen.name == "i3"
