from repro.core.misd.batching import (
    AdmissionPlan,
    BatchAccumulator,
    adaptive_batch_size,
    plan_admission,
)
from repro.core.misd.interference import (
    InterferencePredictor,
    pairwise_degradation,
    progress_rates,
)
from repro.core.misd.scheduler import ChunkedPrefillPolicy, Device, Job
