from repro.core.simd.sharding import (
    ShardingPolicy,
    batch_pspecs,
    cache_pspecs,
    make_policy,
    opt_pspecs,
    param_pspecs,
    to_shardings,
)
