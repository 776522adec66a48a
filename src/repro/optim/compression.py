"""Error-feedback int8 gradient compression for the cross-pod all-reduce.

The 'pod' mesh axis is the slow (inter-pod DCI) link; DP gradient traffic
across it is the term worth compressing (DESIGN.md §5).  Scheme: per-leaf
scale = max|g_local|/127, int8 quantize, integer all-reduce (exact in
int32), dequantize with the psum'd per-pod scales, and keep the local
quantization residual as error feedback added to the next step's gradient
(EF14 — convergence-safe for SGD-family updates).

Implemented as a fully manual ``jax.shard_map`` whose specs reference only
'pod': unreferenced mesh axes see replicated operands inside, which is
exact here because the pod mean is computed locally on each device after
the int8 all-gather.  Cross-pod gradient bytes drop 4x (fp32->int8) minus
one scalar per leaf.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def ef_init(params) -> Any:
    """Zero error-feedback residuals, mirroring the param tree (fp32)."""
    return jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)


def _quantize(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    amax = jnp.max(jnp.abs(g))
    scale = jnp.maximum(amax, 1e-30) * (1.0 / 127.0)
    q = jnp.clip(jnp.round(g * (1.0 / scale)), -127, 127).astype(jnp.int8)
    return q, scale


def _leaf_reduce(g: jnp.ndarray, ef: jnp.ndarray, axis: str):
    """EF-compressed mean of one gradient leaf over the pod axis.

    Each pod quantizes with its own scale.  Scales differ across pods, so
    a summed-int8 / shared-scale reconstruction is wrong; instead the
    int8 payloads (+ scalar scales) are all-gathered — the wire bytes are
    the same int8 payload a ring reduction would move — and each pod
    dequantize-sums locally.  Exact up to per-pod quantization error,
    which the error-feedback residual retains locally.
    """
    g32 = g.astype(jnp.float32) + ef
    q, scale = _quantize(g32)
    q_all = jax.lax.all_gather(q, axis)          # (npods, ...) int8 wire
    s_all = jax.lax.all_gather(scale, axis)      # (npods,) scalars
    npods = q_all.shape[0]
    mean = jnp.tensordot(s_all, q_all.astype(jnp.float32), axes=(0, 0))
    mean = mean * (1.0 / npods)
    residual = g32 - q.astype(jnp.float32) * scale  # local quant error
    return mean, residual


def compressed_grad_fn(
    loss_fn: Callable, mesh: Mesh, axis: str = "pod"
) -> Callable:
    """Wrap ``loss_fn(params, batch) -> scalar`` into a per-pod grad step.

    Returns ``fn(params, batch, ef) -> (loss, grads, ef')`` where grads are
    the cross-pod EF-int8 mean and batch leaves are sharded over 'pod' on
    their leading axis.
    """

    def fn(params, batch, ef):
        @partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(), P(axis), P()), out_specs=(P(), P(), P()),
            check_vma=False,
        )
        def run(params, batch, ef):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            gl, treedef = jax.tree.flatten(grads)
            el = treedef.flatten_up_to(ef)
            pairs = [_leaf_reduce(g, e, axis) for g, e in zip(gl, el)]
            new_g = treedef.unflatten([p[0] for p in pairs])
            new_e = treedef.unflatten([p[1] for p in pairs])
            return jax.lax.pmean(loss, axis), new_g, new_e

        return run(params, batch, ef)

    return fn
