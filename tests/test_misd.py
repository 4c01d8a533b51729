"""MISD quadrant: interference model and batching. Validates the survey's
§3 qualitative claims on our own stack."""
from repro.configs import get_config
from repro.core.costmodel import estimate_decode
from repro.core.misd import (
    adaptive_batch_size,
    pairwise_degradation,
    progress_rates,
)

COMPUTE = (0.92, 0.25)  # compute-bound demand vector
MEMORY = (0.18, 0.90)  # memory-bound demand vector


def test_rates_bounds_and_monotonicity():
    r1 = progress_rates([COMPUTE])[0]
    r2 = progress_rates([COMPUTE, COMPUTE])[0]
    r3 = progress_rates([COMPUTE, COMPUTE, COMPUTE])[0]
    assert 0 < r3 < r2 < r1 <= 1.0


def test_complementary_pairs_interfere_less():
    """Survey §3.2.1: compute+memory co-location beats compute+compute."""
    mixed = pairwise_degradation(COMPUTE, MEMORY)
    same = pairwise_degradation(COMPUTE, COMPUTE)
    assert mixed < same
    assert mixed < 1.35  # within the Fig. 3b 90th-percentile band


# --- adaptive batching -------------------------------------------------------


def test_adaptive_batch_respects_sla():
    cfg = get_config("granite-8b")
    b, lat = adaptive_batch_size(cfg, context=4096, sla_s=0.05, n_chips=8)
    assert b >= 1 and lat <= 0.05
    b2, _ = adaptive_batch_size(cfg, context=4096, sla_s=0.5, n_chips=8)
    assert b2 >= b  # looser SLA admits bigger batches


def test_batching_amortizes_weights():
    """Throughput/chip rises with batch until compute-bound (the Fig. 4
    GPU-vs-CPU mechanism)."""
    cfg = get_config("granite-8b")
    lat1 = estimate_decode(cfg, 1, 4096, n_chips=8).latency_s
    lat64 = estimate_decode(cfg, 64, 4096, n_chips=8).latency_s
    tput1, tput64 = 1 / lat1, 64 / lat64
    assert tput64 > 10 * tput1
