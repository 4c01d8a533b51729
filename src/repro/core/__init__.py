"""The survey's SISD/MISD/SIMD/MIMD taxonomy as the serving stack uses it:
cost model + hardware constants at the root, one subpackage per quadrant
(misd/, simd/, mimd/)."""
from repro.core.costmodel import (
    WorkEstimate,
    estimate,
    estimate_decode,
    estimate_prefill,
    estimate_train,
    model_flops,
)
from repro.core.hardware import CHIPS, TPU_V5E
