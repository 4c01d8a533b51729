"""Process set-up shared by the entry points that drive a chip
(``chip_smoke.py``, ``repro.launch.serve``): the persistent compilation
cache and the one-line device report.

Nothing here runs at import; tests never call it.
"""
from __future__ import annotations

import importlib.metadata
import os

import jax

#: the repository root: src/repro/launch/runtime.py -> three levels up
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, stands: JAX reads it itself
    and nothing else is set here. Otherwise the cache lives at
    ``<repo>/.jax_cache``, a path derived from this file's location and
    never from a temp name, pid or time — the path is part of what a later
    process looks up, so it must be the same in every run."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


def device_report(cache_dir: str) -> str:
    """One line naming what this process runs on: platform, device kind
    and count as jax reports them, the jax/jaxlib/libtpu versions, and the
    compilation-cache directory in use."""
    devs = jax.devices()
    return (f"device: platform={devs[0].platform} "
            f"kind={devs[0].device_kind!r} count={len(devs)} "
            f"jax={jax.__version__} jaxlib={_version('jaxlib')} "
            f"libtpu={_version('libtpu')} "
            f"compile_cache={cache_dir}")
