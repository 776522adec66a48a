"""Kernel autotuning + dispatch: the paper's static hardware choices
(multiplier replication, tile shape, iteration counter) as a runtime
policy selected per (kernel, shape-bucket, dtype, backend).

Usage::

    from repro.kernels import tuning

    tuning.autotune("gs_recip", (4096, 128))   # times candidates, persists
    tuning.enable_tuning(True)                 # or REPRO_AUTOTUNE=1
    ops.gs_recip(x)                            # now dispatches the winner
"""

from repro.kernels.tuning.autotune import (
    AutotuneResult,
    Trial,
    autotune,
    autotune_for_model,
    time_call,
)
from repro.kernels.tuning.cache import (
    TuningCache,
    cache_key,
    cache_path,
    clear_cache,
    get_cache,
    shape_bucket,
)
from repro.kernels.tuning.dispatch import (
    enable_tuning,
    finalize,
    resolve,
    tuning_enabled,
)
from repro.kernels.tuning.registry import REGISTRY, KernelSpec, get_spec

__all__ = [
    "AutotuneResult",
    "KernelSpec",
    "REGISTRY",
    "Trial",
    "TuningCache",
    "autotune",
    "autotune_for_model",
    "cache_key",
    "cache_path",
    "clear_cache",
    "enable_tuning",
    "finalize",
    "get_cache",
    "get_spec",
    "resolve",
    "shape_bucket",
    "time_call",
    "tuning_enabled",
]
