"""MISD example: size each tenant's batch to its latency SLA (survey §3) —
three tenant models, each on an 8-chip slice, batched as large as the
cost model says its decode step can go while staying inside the SLA.

    PYTHONPATH=src python examples/multi_tenant_serving.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_config
from repro.core.misd import adaptive_batch_size

CHIPS = 8


def main():
    tenants = [
        {"name": "chat", "cfg": get_config("chatglm3-6b"), "context": 4096,
         "sla_s": 0.05},
        {"name": "code", "cfg": get_config("granite-8b"), "context": 8192,
         "sla_s": 0.08},
        {"name": "vision", "cfg": get_config("qwen2-vl-7b"), "context": 4096,
         "sla_s": 0.10},
    ]
    print(f"adaptive batching, {CHIPS} chips per tenant:")
    for t in tenants:
        b, lat = adaptive_batch_size(t["cfg"], context=t["context"],
                                     sla_s=t["sla_s"], n_chips=CHIPS)
        print(f"  {t['name']}: batch={b} "
              f"(step {lat*1e3:.1f}ms <= SLA {t['sla_s']*1e3:.0f}ms)")


if __name__ == "__main__":
    main()
