"""Config resolution for every kernel call.

Precedence (highest first):

1. explicit kwargs at the call site (``ops.gs_recip(x, variant="pipelined")``),
2. the persisted autotune cache entry for ``(kernel, shape-bucket, dtype,
   backend)`` — consulted only when tuning is enabled,
3. the registry defaults (the seed's hard-coded literals).

Tuning is off by default; enable with ``REPRO_AUTOTUNE=1`` or
:func:`enable_tuning`.  With tuning disabled — or enabled but cold — every
resolution is exactly the pre-tuning behavior.

Resolution happens in Python at trace time (it reads only ``.shape`` /
``.dtype``), so it is jit-safe and each distinct config stays one compiled
executable.
"""

from __future__ import annotations

import os
import warnings
from collections import Counter
from typing import Any, Callable, Dict, Mapping, Optional, Sequence

import jax

from repro.kernels import common
from repro.kernels.tuning import cache as cache_mod
from repro.kernels.tuning import registry

ENV_ENABLE = "REPRO_AUTOTUNE"
ENV_FALLBACK = "REPRO_KERNEL_FALLBACK"

_enabled_override: Optional[bool] = None
_fallback_override: Optional[bool] = None
_fallback_counts: Counter = Counter()
_resolve_counts: Counter = Counter()
_tune_hits: Counter = Counter()
_tune_misses: Counter = Counter()


def tuning_enabled() -> bool:
    if _enabled_override is not None:
        return _enabled_override
    return os.environ.get(ENV_ENABLE, "0").lower() not in ("0", "", "false")


def enable_tuning(on: Optional[bool] = True) -> None:
    """Force tuned dispatch on/off for this process; ``None`` defers back
    to the ``REPRO_AUTOTUNE`` environment variable."""
    global _enabled_override
    _enabled_override = on


# -- pallas -> jnp fallback route (opt-in) -----------------------------------
# A kernel that fails to trace/lower raises by default: on the measured
# path a Mosaic lowering hole must fail the run, not be timed as jnp.
# Opted in (REPRO_KERNEL_FALLBACK=1 or enable_fallback(True)), the failing
# kernel downgrades to its jnp oracle (kernels/ref.py) and serving keeps
# answering, slower; the downgrade is counted per kernel so the serving
# metrics (ServeMetrics.kernel_fallbacks) and operators can see it.


def fallback_enabled() -> bool:
    if _fallback_override is not None:
        return _fallback_override
    return os.environ.get(ENV_FALLBACK, "0").lower() not in ("0", "", "false")


def enable_fallback(on: Optional[bool] = True) -> None:
    """Force the fallback route on/off for this process; ``None`` defers
    back to the ``REPRO_KERNEL_FALLBACK`` environment variable."""
    global _fallback_override
    _fallback_override = on


def fallback_stats() -> Dict[str, int]:
    """Per-kernel downgrade counts since process start (or last reset)."""
    return dict(_fallback_counts)


def fallback_total() -> int:
    return sum(_fallback_counts.values())


def reset_fallback_stats() -> None:
    _fallback_counts.clear()


# -- dispatch-layer observability --------------------------------------------
# Per-kernel counters the serving metrics and obsview attribute against:
# how often each kernel's launch config was resolved (trace-time: one
# resolution per call site per compilation — a warm jit cache resolves
# nothing, so this counts lowerings, not executions), and whether the
# autotune cache answered (hit) or fell through to registry defaults
# (miss) when tuning was enabled.  Fallback counts (above) complete the
# per-kernel picture: resolved -> tuned-or-default -> ran-or-downgraded.


def dispatch_snapshot() -> Dict[str, Dict[str, int]]:
    """Copy of every per-kernel dispatch counter; diff two snapshots
    with :func:`dispatch_delta` to attribute one run's activity."""
    return {
        "resolves": dict(_resolve_counts),
        "tune_hits": dict(_tune_hits),
        "tune_misses": dict(_tune_misses),
        "fallbacks": dict(_fallback_counts),
    }


def dispatch_delta(start: Dict[str, Dict[str, int]],
                   end: Optional[Dict[str, Dict[str, int]]] = None,
                   ) -> Dict[str, Dict[str, int]]:
    """Per-kernel counter deltas since ``start`` (zero entries dropped);
    ``end`` defaults to a fresh snapshot."""
    end = end if end is not None else dispatch_snapshot()
    out: Dict[str, Dict[str, int]] = {}
    for section, counts in end.items():
        base = start.get(section, {})
        d = {k: v - base.get(k, 0) for k, v in counts.items()
             if v - base.get(k, 0)}
        out[section] = d
    return out


def reset_dispatch_stats() -> None:
    """Clear resolve/tune counters (fallbacks have their own reset)."""
    _resolve_counts.clear()
    _tune_hits.clear()
    _tune_misses.clear()


def call_with_fallback(kernel: str, primary: Callable[[], Any],
                       fallback: Callable[[], Any]) -> Any:
    """Run ``primary`` (the Pallas kernel call, as a thunk).  With the
    route opted in (:func:`fallback_enabled`), an exception records the
    downgrade and runs ``fallback`` (the jnp oracle) instead; otherwise
    it propagates.  Resolution and the kernels run at trace time, so this
    catches trace/lower/compile failures — exactly where kernel faults
    surface in this stack (interpret mode included)."""
    if not fallback_enabled():
        return primary()
    try:
        return primary()
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:  # noqa: BLE001 - the whole point is containment
        _fallback_counts[kernel] += 1
        warnings.warn(
            f"kernel {kernel} failed ({type(e).__name__}: {e}); "
            "downgrading to the jnp reference", RuntimeWarning,
            stacklevel=3)
        return fallback()


def finalize(config: Mapping[str, Any], dtype=None) -> Dict[str, Any]:
    """Concretize deferred values.

    ``interpret=None`` → derived from the backend
    (:func:`repro.kernels.common.interpret_flag`); ``p``/``iters`` = None →
    the :func:`repro.core.goldschmidt.precision_policy` pair for ``dtype``
    ((7, 2) for fp32 — the seed literals — seed-only for bf16 with p ≥ 8).
    A pinned ``p`` derives its matching pass count; a pinned ``iters``
    keeps the default table (see ``resolve_precision``).
    """
    cfg = dict(config)
    if cfg.get("interpret") is None:
        cfg["interpret"] = common.interpret_flag()
    if "frac_bits" in cfg:
        # Fixed-point kernel: the (p, iters) pair comes from the measured
        # fixed frontier (formats.fixed_precision_policy), budgeted at the
        # int8 target — the operand dtype (int8) has no mantissa to derive
        # from.
        from repro.core import formats

        if cfg.get("frac_bits") is None:
            cfg["frac_bits"] = formats.DEFAULT_FRAC_BITS
        if cfg.get("mitchell_iters") is None:
            cfg["mitchell_iters"] = 0
        if cfg.get("p") is None and cfg.get("iters") is None:
            cfg["p"], cfg["iters"] = formats.fixed_precision_policy(
                cfg["frac_bits"], formats.INT8_TARGET_BITS,
                cfg["mitchell_iters"])
        elif cfg.get("iters") is None:
            cfg["iters"] = formats.fixed_iters_needed(
                cfg["p"], cfg["frac_bits"], formats.INT8_TARGET_BITS,
                cfg["mitchell_iters"])
        elif cfg.get("p") is None:
            cfg["p"], _ = formats.fixed_precision_policy(
                cfg["frac_bits"], formats.INT8_TARGET_BITS,
                cfg["mitchell_iters"])
        return cfg
    if "p" in cfg or "iters" in cfg:
        if cfg.get("p") is None or cfg.get("iters") is None:
            from repro.core.goldschmidt import resolve_precision

            cfg["p"], cfg["iters"] = resolve_precision(
                dtype if dtype is not None else jax.numpy.float32,
                cfg.get("p"), cfg.get("iters"),
            )
    return cfg


def resolve(
    kernel: str,
    shape: Sequence[int],
    dtype,
    overrides: Optional[Mapping[str, Any]] = None,
) -> Dict[str, Any]:
    """Launch config for one kernel call; see module docstring for the
    precedence.  ``overrides`` entries that are ``None`` are treated as
    "not specified" so call sites can forward optional policy fields
    (e.g. ``iters=policy.iters``) verbatim."""
    spec = registry.get_spec(kernel)
    cfg = dict(spec.defaults)
    _resolve_counts[kernel] += 1
    if tuning_enabled():
        key = cache_mod.cache_key(kernel, shape, dtype, jax.default_backend())
        entry = cache_mod.get_cache().get(key)
        (_tune_hits if entry is not None else _tune_misses)[kernel] += 1
        if entry is not None:
            tuned = entry.get("config", {})
            # Unknown keys in a stale/foreign cache entry must not reach
            # the kernel signature.
            cfg.update({k: v for k, v in tuned.items() if k in cfg})
    if overrides:
        ov = {k: v for k, v in overrides.items() if v is not None}
        # (p, iters) is a joint accuracy budget: pinning one half must not
        # inherit a tuned value of the other half (tuned for a DIFFERENT
        # pair), or the result can undershoot the dtype's target bits.
        # Reset the unpinned partner so finalize re-derives it.
        if ("p" in cfg or "iters" in cfg) and (("p" in ov) != ("iters" in ov)):
            cfg["iters" if "p" in ov else "p"] = None
        cfg.update(ov)
    return finalize(cfg, dtype)
