"""Model shapes read from a configuration file, and random weights drawn
from a run's seed in the published layout, by the configuration's model
family.

A family is a module of its own, ``families/<family>.py``, found by the
configuration file's ``family`` key; ``families/__init__.py`` lists what
it defines. ``published`` draws the weights as the published model lays
them out, in the dtype they are served in. ``to_program`` rearranges them
into the program's parameter tree, as a checkpoint loader would. The
plain reference reads the published layout and nothing of the program.
"""
from __future__ import annotations

import importlib
import os
import sys

import jax
import jax.numpy as jnp

BENCH = os.path.dirname(os.path.abspath(__file__))


def family(name: str):
    """The module ``families/<name>.py``."""
    mod = sys.modules.get("families." + name)
    if mod is None:
        path = os.path.join(BENCH, "families", name + ".py")
        if not os.path.isfile(path):
            raise SystemExit(f"unknown model family {name!r}: no {path}")
        mod = importlib.import_module("families." + name)
    return mod


def family_of(s):
    """The family module of shapes ``s``."""
    return family(s.family)


def shapes(conf: dict):
    """Read the sizes from a configuration file in its source's own keys."""
    return family(conf["family"]).shapes(conf)


def seed_key(seed: int):
    """A JAX key from any non-negative seed, including ones past 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def published(s, key, dtype=jnp.bfloat16) -> dict:
    return family_of(s).published(s, key, dtype)


def to_program(s, w: dict) -> dict:
    return family_of(s).to_program(s, w)
