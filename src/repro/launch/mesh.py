"""Production mesh construction.

A function, not a module constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).

Topology (v5e-like, DESIGN.md §5):
  single-pod: (16, 16)   axes ("data", "model")   = 256 chips
  multi-pod:  (2, 16, 16) axes ("pod", "data", "model") = 512 chips

'model' is the ICI-contiguous TP axis; 'data' carries batch + FSDP;
'pod' is pure DP across the inter-pod links (optionally FSDP too — ZeRO-3
— for models whose optimizer state exceeds a single pod; see
runtime/sharding.py fsdp_axes).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: GSPMD propagates shardings and the
    layers' ``with_sharding_constraint`` calls apply (Explicit axes, the
    default since JAX 0.7, reject both)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many (possibly fake) devices a test has."""
    return auto_mesh(shape, axes)


def parse_mesh_spec(spec: str):
    """``"DxM"`` or ``"data=D,model=M"`` -> ((D, M), ("data", "model")).

    The serving CLI's ``--mesh`` grammar.  ``M = 0`` (or a missing axis)
    means "whatever is left": the axis size is derived from the device
    count so ``--mesh 2x0`` works on any host.  A bare integer ``"M"``
    is TP-only shorthand for ``1xM``.
    """
    spec = spec.strip().lower()
    if "=" in spec:
        sizes = {"data": 0, "model": 0}  # 0 = derive from device count
        for part in spec.split(","):
            name, _, val = part.partition("=")
            name, val = name.strip(), val.strip()
            if name not in sizes:
                raise ValueError(
                    f"unknown serving mesh axis {name!r} "
                    f"(expected data/model)")
            sizes[name] = int(val)
        d, m = sizes["data"], sizes["model"]
    elif "x" in spec:
        d_s, _, m_s = spec.partition("x")
        d, m = int(d_s), int(m_s)
    else:
        d, m = 1, int(spec)
    n = jax.device_count()
    if d == 0 and m == 0:
        raise ValueError("at most one mesh axis may be 0 (derived)")
    if d == 0:
        d = n // m
    if m == 0:
        m = n // d
    if d < 1 or m < 1 or d * m != n:
        raise ValueError(
            f"mesh {d}x{m} does not cover the {n} available devices")
    return (d, m), ("data", "model")


def make_serving_mesh(spec: str = "auto"):
    """Serving mesh from a ``--mesh`` spec string (see parse_mesh_spec).

    ``("data", "model")`` axes like the training mesh: 'data' shards the
    slot pool (batch rows), 'model' is TP over heads / d_ff / d_inner and
    the decode-cache head_dim.  ``"auto"`` (the default) is TP over every
    device — decode batches are small, so the model axis is where serving
    wins.
    """
    spec = (spec or "").strip().lower()
    if spec in ("auto", "0x0", ""):
        shape, axes = (1, jax.device_count()), ("data", "model")
    else:
        shape, axes = parse_mesh_spec(spec)
    return auto_mesh(shape, axes)


# Hardware constants for the roofline (TPU v5e-like, per chip).
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9            # B/s
ICI_BW = 50e9             # B/s per link (~per-chip injection, one direction)
HBM_PER_CHIP = 16 * 1024**3
