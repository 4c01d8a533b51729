"""Quickstart: serve a reduced model on the CPU, then a cost-model roofline
of the full one.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_config
from repro.core import estimate
from repro.configs import get_shape
from repro.models import init_params
from repro.serving import EngineConfig, Request, ServingEngine


def main():
    # --- pick an assigned architecture, reduced for the CPU container -----
    cfg = get_config("granite-8b").reduced()
    print(f"model: {cfg.name} ({cfg.arch_type}), "
          f"{cfg.param_count()/1e6:.1f}M params (reduced)")

    # --- single-instance serving with continuous batching ------------------
    params = init_params(cfg, jax.random.key(0))
    eng = ServingEngine(cfg, params, EngineConfig(slots=2, window=64))
    reqs = [Request(i, np.arange(8 + i, dtype=np.int32), max_new_tokens=6)
            for i in range(3)]
    queue, t = list(reqs), 0.0
    while queue or eng.n_active:
        while queue and eng.try_admit(queue[0], t):
            queue.pop(0)
        eng.step(t)
        t += 1.0
    print(f"served {eng.metrics.completed} requests, "
          f"tokens={eng.metrics.total_tokens}")

    # --- roofline for one assigned shape (full config, cost model) --------
    full = get_config("granite-8b")
    est = estimate(full, get_shape("decode_32k"), n_chips=256)
    print(f"decode_32k on 256 chips: compute={est.compute_s*1e3:.2f}ms "
          f"memory={est.memory_s*1e3:.2f}ms -> bottleneck={est.bottleneck}")


if __name__ == "__main__":
    main()
