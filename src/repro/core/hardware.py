"""Hardware constants for the target platform (TPU v5e) plus the survey's
comparison devices (Fig. 4). All roofline math reads from here."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Chip:
    name: str
    peak_flops: float  # FLOP/s (bf16 for accelerators, fp32 for CPU)
    hbm_bw: float  # bytes/s
    hbm_bytes: float
    link_bw: float  # bytes/s per ICI/NVLink-class link
    tdp_watts: float
    # jax ``Device.device_kind`` strings this row stands for
    device_kinds: tuple[str, ...] = ()


TPU_V5E = Chip(
    name="tpu-v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    hbm_bytes=16 * 2 ** 30,
    link_bw=50e9,
    tdp_watts=200.0,
    device_kinds=("TPU v5 lite", "TPU v5e"),
)

# Survey Fig. 4 comparison points (nominal public specs)
XEON_4116 = Chip("xeon-4116", 0.8e12, 115e9, 192 * 2 ** 30, 10e9, 85.0)
RTX_2080TI = Chip("rtx-2080ti", 26.9e12, 616e9, 11 * 2 ** 30, 16e9, 250.0)
V100 = Chip("v100", 130e12, 900e9, 32 * 2 ** 30, 25e9, 300.0)
A100 = Chip("a100", 312e12, 1555e9, 40 * 2 ** 30, 37.5e9, 400.0)

CHIPS = {c.name: c for c in (TPU_V5E, XEON_4116, RTX_2080TI, V100, A100)}

# Fixed per-dispatch overhead (host->device launch, runtime) seconds.
DISPATCH_OVERHEAD_S = 45e-6
# Meshlet/partition reconfiguration cost (survey §3.3.2: "several seconds"
# for MIG-class repartitioning; TPU analogue = recompile + weight reshard).
RECONFIG_COST_S = 5.0
