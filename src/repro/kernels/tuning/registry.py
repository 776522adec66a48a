"""Declarative registry of the tunable Goldschmidt Pallas kernels.

Each :class:`KernelSpec` names the kernel's tunable axes — the knobs the
paper treats as *hardware* choices (replicated vs reused multiplier pair,
tile shape, predetermined iteration counter) that this subsystem turns
into a runtime policy:

* ``variant``     — ``feedback`` (one multiplier pair + feedback mux) vs
                    ``pipelined`` (unrolled replicated pairs),
* ``block_rows`` / ``block_q`` / ``block_kv`` — VMEM tile shape,
* ``p``           — ROM index width: the seed-vs-iteration trade the paper
                    spends its §II on, swept jointly with
* ``iters``       — §III's accuracy counter, derived from the output dtype
                    via :func:`repro.core.goldschmidt.precision_policy`;
                    the (p, iters) product is pruned to pairs that reach
                    the dtype's target bits with no wasted pass,
* ``interpret``   — not a choice: the one value the backend allows
                    (interpreted on CPU, Mosaic-compiled elsewhere).

``defaults`` reproduce the seed's hard-coded literals exactly, so a cold
cache (or tuning disabled) is behavior-identical to the pre-tuning tree.
``make_args`` builds representative operands for the autotuner's timing
runs.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import formats
from repro.core.goldschmidt import iters_needed, target_bits_for
from repro.kernels import common
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_bwd_bench)
from repro.kernels.gs_adam import gs_adam_update
from repro.kernels.gs_fixed import (gs_fixed_recip, gs_fixed_rmsnorm,
                                    gs_fixed_softmax)
from repro.kernels.gs_recip import gs_recip
from repro.kernels.gs_rmsnorm import gs_rmsnorm
from repro.kernels.gs_rsqrt import gs_rsqrt
from repro.kernels.gs_softmax import gs_softmax

Shape = Tuple[int, ...]
AxisValues = Sequence[Any]
AxisFn = Callable[[Shape, Any, str], AxisValues]


def _p_axis(shape: Shape, dtype, backend: str) -> AxisValues:
    """ROM index widths on the seed-vs-iteration frontier for this dtype.

    fp32-grade targets trade the paper's (7, 2) point against a 4096-entry
    table that needs a single pass (p=12 → 1 iteration); low-precision
    targets sweep the seed-only widths up to 2^9 entries (the in-kernel
    one-hot ROM read grows with 2^p, so wider candidates never win and
    only stretch the sweep).
    """
    if target_bits_for(dtype) >= 24:
        return (common.DEFAULT_P, 12)
    return (common.DEFAULT_P, 8, 9)


def _iters_axis(shape: Shape, dtype, backend: str) -> AxisValues:
    """Accuracy-predetermined counters matching the ``p`` axis: for each
    candidate table width, the measured pass count that reaches the output
    dtype's bits.  The (p, iters) product is pruned to exactly these pairs
    by :func:`_precision_ok`."""
    tb = target_bits_for(dtype)
    return tuple(sorted({
        iters_needed(p, tb) for p in _p_axis(shape, dtype, backend)
    }))


def _precision_ok(config: Mapping[str, Any], dtype) -> bool:
    """Keep only frontier (p, iters) pairs: enough bits for the dtype
    (never an accuracy regression past the target), no wasted passes
    (a pair with more passes than its seed needs is dominated)."""
    p, iters = config.get("p"), config.get("iters")
    if p is None or iters is None:
        return True
    return iters == iters_needed(p, target_bits_for(dtype))


def _fixed_p_axis(shape: Shape, dtype, backend: str) -> AxisValues:
    # the fixed frontier's seed widths: the paper's default plus the
    # seed-only widths that certify the int8 target without a pass
    return (common.DEFAULT_P, 8, 9)


def _fixed_iters_axis(shape: Shape, dtype, backend: str) -> AxisValues:
    return tuple(sorted({
        formats.fixed_iters_needed(p, fb, formats.INT8_TARGET_BITS, mit)
        for p in _fixed_p_axis(shape, dtype, backend)
        for fb in formats.FIXED_FRAC_BITS
        for mit in (0, 1)
        if fb >= p + 2
    }))


def _fixed_precision_ok(config: Mapping[str, Any], dtype) -> bool:
    """The fixed-kernel frontier rule: a (p, frac_bits, iters,
    mitchell_iters) point survives iff the register can hold the ROM word,
    the pass count is exactly what the MEASURED ladder needs for the int8
    target (no wasted pass, no undershoot), and every Mitchell pass
    actually runs (a Mitchell format with fewer passes than
    ``mitchell_iters`` is the exact format wearing a different label)."""
    p, it = config.get("p"), config.get("iters")
    fb = config.get("frac_bits")
    mit = config.get("mitchell_iters", 0) or 0
    if p is None or it is None or fb is None:
        return True
    if fb < p + 2 or mit > it:
        return False
    return it == formats.fixed_iters_needed(
        p, fb, formats.INT8_TARGET_BITS, mit)


def _interpret_axis(shape: Shape, dtype, backend: str) -> AxisValues:
    # CPU has no Mosaic lowering: interpret is the only path.  On real
    # backends interpret mode is orders of magnitude slower and never
    # wins — sweeping it would dominate the tuning wall-clock, so only
    # the compiled path is a candidate there.
    return (True,) if backend == "cpu" else (False,)


def _seq_block_axis(shape: Shape, dtype, backend: str) -> AxisValues:
    s = shape[2]
    cands = tuple(b for b in (64, 128, 256) if b <= s and s % b == 0)
    return cands or (common.fit_block(s, 128),)


def _logpos(shape: Shape, dtype, seed: int = 0) -> jnp.ndarray:
    r = np.random.RandomState(seed)
    a = np.exp(r.uniform(-3.0, 3.0, shape)).astype(np.float32)
    return jnp.asarray(a).astype(dtype)


def _args_elementwise(shape, dtype):
    return (_logpos(shape, dtype),), {}


def _args_rowwise(shape, dtype):
    r = np.random.RandomState(1)
    x = jnp.asarray((r.randn(*shape) * 4).astype(np.float32)).astype(dtype)
    return (x,), {}


def _args_rmsnorm(shape, dtype):
    r = np.random.RandomState(2)
    x = jnp.asarray(r.randn(*shape).astype(np.float32)).astype(dtype)
    g = jnp.asarray(r.randn(shape[-1]).astype(np.float32))
    return (x, g), {}


def _args_adam(shape, dtype):
    r = np.random.RandomState(3)
    mk = lambda scale=1.0: jnp.asarray((r.randn(*shape) * scale).astype(np.float32))
    args = (mk(), mk(), mk(0.1), jnp.abs(mk(0.01)), jnp.asarray(1))
    return args, {"lr": 1e-3}


def _args_fixed_elementwise(shape, dtype):
    r = np.random.RandomState(6)
    sgn = np.where(r.rand(*shape) < 0.5, -1, 1)
    x = (r.randint(1, 128, shape) * sgn).astype(np.int8)  # nonzero: recip
    return (jnp.asarray(x), 0.02), {}


def _args_fixed_rowwise(shape, dtype):
    r = np.random.RandomState(7)
    x = r.randint(-127, 128, shape).astype(np.int8)
    return (jnp.asarray(x), 0.03), {}


def _args_fixed_rmsnorm(shape, dtype):
    r = np.random.RandomState(8)
    x = r.randint(-127, 128, shape).astype(np.int8)
    g = jnp.asarray(r.randn(shape[-1]).astype(np.float32))
    return (jnp.asarray(x), 0.03, g), {}


def _args_flash(shape, dtype):
    b, h, s, d = shape
    r = np.random.RandomState(4)
    mk = lambda: jnp.asarray(r.randn(b, h, s, d).astype(np.float32)).astype(dtype)
    return (mk(), mk(), mk()), {"causal": True}


def _args_flash_bwd(shape, dtype):
    (q, k, v), kw = _args_flash(shape, dtype)
    r = np.random.RandomState(5)
    do = jnp.asarray(r.randn(*shape).astype(np.float32)).astype(dtype)
    return (q, k, v, do), kw


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    name: str
    fn: Callable[..., Any]
    defaults: Mapping[str, Any]
    axes: Mapping[str, Any]  # axis -> values tuple | AxisFn
    make_args: Callable[[Shape, Any], Tuple[tuple, dict]]
    supports: Callable[[Shape], bool] = lambda shape: len(shape) >= 1
    # candidate filter; None -> the float (p, iters) frontier rule.  Fixed
    # kernels swap in _fixed_precision_ok (the measured int8 ladder).
    prune: Optional[Callable[[Mapping[str, Any], Any], bool]] = None

    def candidates(
        self, shape: Shape, dtype, backend: str
    ) -> Sequence[Dict[str, Any]]:
        """Cartesian product of the axes, concretized for shape/dtype/
        backend, pruned to the (p, iters) accuracy frontier.  The
        dtype-derived defaults are axis members by construction, so the
        autotuned winner can never lose to them — nor undershoot the
        output dtype's accuracy target."""
        names = list(self.axes)
        values = [
            v(shape, dtype, backend) if callable(v) else v
            for v in (self.axes[n] for n in names)
        ]
        ok = self.prune if self.prune is not None else _precision_ok
        return [
            cfg
            for combo in itertools.product(*values)
            if ok(cfg := dict(zip(names, combo)), dtype)
        ]


# ``p``/``iters`` defaults are ``None`` = derived from the operand dtype by
# :func:`repro.core.goldschmidt.precision_policy` at dispatch-finalize time:
# (7, 2) for fp32 — exactly the seed literals, so cold-start fp32 behavior
# is bit-identical — and seed-only / single-pass pairs for bf16 / fp16.
_ELEMENTWISE_AXES = {
    "variant": ("feedback", "pipelined"),
    "block_rows": (32, 64, 128),
    "p": _p_axis,
    "iters": _iters_axis,
    "interpret": _interpret_axis,
}

_ROWWISE_AXES = {
    "variant": ("feedback", "pipelined"),
    "block_rows": (8, 16, 32),
    "p": _p_axis,
    "iters": _iters_axis,
    "interpret": _interpret_axis,
}

# Fixed-point (int8) kernel axes: ``frac_bits`` (register width) and
# ``mitchell_iters`` (approximate-multiplier passes) join the sweep; the
# joint candidate set is pruned to the measured int8 frontier by
# :func:`_fixed_precision_ok`.
_FIXED_ELEMENTWISE_AXES = {
    "variant": ("feedback", "pipelined"),
    "block_rows": (32, 64, 128),
    "frac_bits": formats.FIXED_FRAC_BITS,
    "mitchell_iters": (0, 1),
    "p": _fixed_p_axis,
    "iters": _fixed_iters_axis,
    "interpret": _interpret_axis,
}

_FIXED_ROWWISE_AXES = {
    "variant": ("feedback", "pipelined"),
    "block_rows": (8, 16, 32),
    "frac_bits": formats.FIXED_FRAC_BITS,
    "mitchell_iters": (0, 1),
    "p": _fixed_p_axis,
    "iters": _fixed_iters_axis,
    "interpret": _interpret_axis,
}

_FIXED_DEFAULTS = {"variant": "feedback", "p": None, "iters": None,
                   "frac_bits": None, "mitchell_iters": None,
                   "interpret": None}

REGISTRY: Dict[str, KernelSpec] = {
    spec.name: spec
    for spec in (
        KernelSpec(
            name="gs_recip",
            fn=gs_recip,
            defaults={"variant": "feedback", "block_rows": 64, "p": None,
                      "iters": None, "interpret": None},
            axes=_ELEMENTWISE_AXES,
            make_args=_args_elementwise,
        ),
        KernelSpec(
            name="gs_rsqrt",
            fn=gs_rsqrt,
            defaults={"variant": "feedback", "block_rows": 64, "p": None,
                      "iters": None, "interpret": None},
            axes=_ELEMENTWISE_AXES,
            make_args=_args_elementwise,
        ),
        KernelSpec(
            name="gs_rmsnorm",
            fn=gs_rmsnorm,
            defaults={"variant": "feedback", "block_rows": 8, "p": None,
                      "iters": None, "interpret": None},
            axes=_ROWWISE_AXES,
            make_args=_args_rmsnorm,
            supports=lambda shape: len(shape) >= 2,
        ),
        KernelSpec(
            name="gs_softmax",
            fn=gs_softmax,
            defaults={"variant": "feedback", "block_rows": 8, "p": None,
                      "iters": None, "interpret": None},
            axes=_ROWWISE_AXES,
            make_args=_args_rowwise,
            supports=lambda shape: len(shape) >= 2,
        ),
        KernelSpec(
            name="gs_fixed_recip",
            fn=gs_fixed_recip,
            defaults={**_FIXED_DEFAULTS, "block_rows": 64},
            axes=_FIXED_ELEMENTWISE_AXES,
            make_args=_args_fixed_elementwise,
            prune=_fixed_precision_ok,
        ),
        KernelSpec(
            name="gs_fixed_softmax",
            fn=gs_fixed_softmax,
            defaults={**_FIXED_DEFAULTS, "block_rows": 8},
            axes=_FIXED_ROWWISE_AXES,
            make_args=_args_fixed_rowwise,
            supports=lambda shape: len(shape) >= 2,
            prune=_fixed_precision_ok,
        ),
        KernelSpec(
            name="gs_fixed_rmsnorm",
            fn=gs_fixed_rmsnorm,
            defaults={**_FIXED_DEFAULTS, "block_rows": 8},
            axes=_FIXED_ROWWISE_AXES,
            make_args=_args_fixed_rmsnorm,
            supports=lambda shape: len(shape) >= 2,
            prune=_fixed_precision_ok,
        ),
        KernelSpec(
            name="gs_adam",
            fn=gs_adam_update,
            defaults={"variant": "feedback", "block_rows": 32, "p": None,
                      "iters": None, "interpret": None},
            axes={
                "variant": ("feedback", "pipelined"),
                "block_rows": (16, 32, 64),
                "p": _p_axis,
                "iters": _iters_axis,
                "interpret": _interpret_axis,
            },
            make_args=_args_adam,
        ),
        KernelSpec(
            name="flash_attention",
            fn=flash_attention,
            defaults={"variant": "feedback", "block_q": 128, "block_kv": 128,
                      "p": None, "iters": None, "interpret": None},
            axes={
                "variant": ("feedback", "pipelined"),
                "block_q": _seq_block_axis,
                "block_kv": _seq_block_axis,
                "p": _p_axis,
                "iters": _iters_axis,
                "interpret": _interpret_axis,
            },
            make_args=_args_flash,
            supports=lambda shape: len(shape) == 4,
        ),
        # Backward tile shapes for the flash-attention vjp (dq + dk/dv
        # kernel pair), resolved by the custom_vjp's bwd rule.  Only the
        # tile axes are swept: the backward's Goldschmidt variant/iters
        # always follow the forward call (policy-pinned nondiff args), so
        # tuning them here could never apply at dispatch — they remain
        # kwargs on flash_attention_bwd_bench for standalone experiments.
        KernelSpec(
            name="flash_attention_bwd",
            fn=flash_attention_bwd_bench,
            defaults={"block_q": 128, "block_kv": 128, "interpret": None},
            axes={
                "block_q": _seq_block_axis,
                "block_kv": _seq_block_axis,
                "interpret": _interpret_axis,
            },
            make_args=_args_flash_bwd,
            supports=lambda shape: len(shape) == 4,
        ),
    )
}


def get_spec(name: str) -> KernelSpec:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel {name!r}; registered: {sorted(REGISTRY)}"
        ) from None
