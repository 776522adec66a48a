"""Fused AdamW update with Goldschmidt sqrt + reciprocal, as a Pallas kernel.

Division site #5 of DESIGN.md §3: the update ``m_hat / (sqrt(v_hat)+eps)``
is the one *unavoidable* divide of every training step, executed once per
parameter element per step.  Fusing moment updates + the Goldschmidt
denominator into one VMEM pass makes the optimizer a single memory-bound
sweep (read p,g,m,v / write p,m,v) with all arithmetic on the VPU/MXU —
no transcendental-unit divide or sqrt.

Bias corrections (1/(1-beta^t)) and the learning rate are scalars,
precomputed outside the kernel and passed via a (1, 3) operand broadcast
to every tile (they change per step / per schedule, so they cannot be
compile-time constants; a traced ``lr`` from a schedule jits without
recompiling).

Tile: (32, 128) f32 — 7 tiles of 16 KB live + two one-hot ROM temps of
(4096, 128) f32 = 2 MB each; working set < 5 MB VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

DEFAULT_BLOCK_ROWS = 32


def _kernel(p_ref, g_ref, m_ref, v_ref, bc_ref, rtab_ref, stab_ref,
            po_ref, mo_ref, vo_ref, *, beta1, beta2, eps, weight_decay,
            p, iters, variant):
    param = p_ref[...].astype(jnp.float32)
    grad = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    bc1 = bc_ref[0, 0]
    bc2 = bc_ref[0, 1]
    lr = bc_ref[0, 2]
    m_new = beta1 * m + (1.0 - beta1) * grad
    v_new = beta2 * v + (1.0 - beta2) * grad * grad
    v_hat = v_new * bc2
    # sqrt(v_hat) via the g-sequence; v_hat may be exactly 0 for untouched
    # params -> clamp into the normal range (eps^2 floor keeps denom ~ eps).
    v_hat = jnp.maximum(v_hat, 1e-38)
    s = common.rsqrt_positive(
        v_hat, stab_ref[...], p=p, iters=iters, variant=variant, mode="sqrt"
    )
    denom = s + eps
    inv = common.recip_positive(
        denom, rtab_ref[...], p=p, iters=iters, variant=variant
    )
    update = (m_new * bc1) * inv
    p_new = param - lr * (update + weight_decay * param)
    po_ref[...] = p_new.astype(po_ref.dtype)
    mo_ref[...] = m_new
    vo_ref[...] = v_new


@functools.partial(
    jax.jit,
    static_argnames=(
        "beta1", "beta2", "eps", "weight_decay", "p", "iters",
        "variant", "block_rows", "interpret",
    ),
)
def gs_adam_update(
    param: jnp.ndarray,
    grad: jnp.ndarray,
    m: jnp.ndarray,
    v: jnp.ndarray,
    step: jnp.ndarray,
    *,
    lr,  # python float or scalar array (scheduled lr traces through)
    beta1: float = 0.9,
    beta2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    p: int = common.DEFAULT_P,
    iters: int = 2,
    variant: str = "feedback",
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
):
    """One fused AdamW step on a flat (or any-shape) parameter tensor.

    Returns (param_new, m_new, v_new).  `step` is a scalar int (1-based).
    """
    orig_shape, orig_dtype = param.shape, param.dtype
    n = param.size
    cols = 128
    rows = -(-n // cols)
    rows_pad = -(-rows // block_rows) * block_rows
    pad = rows_pad * cols - n

    def prep(x, dtype):
        return jnp.pad(x.astype(dtype).reshape(-1), (0, pad)).reshape(
            rows_pad, cols
        )

    p2 = prep(param, jnp.float32)
    g2 = prep(grad, jnp.float32)
    m2 = prep(m, jnp.float32)
    v2 = prep(v, jnp.float32)
    stepf = step.astype(jnp.float32)
    bc = jnp.stack(
        [1.0 / (1.0 - beta1 ** stepf), 1.0 / (1.0 - beta2 ** stepf),
         jnp.asarray(lr, jnp.float32)]
    ).reshape(1, 3)

    p_new, m_new, v_new = pl.pallas_call(
        functools.partial(
            _kernel, beta1=beta1, beta2=beta2, eps=eps,
            weight_decay=weight_decay, p=p, iters=iters, variant=variant,
        ),
        grid=(rows_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
            pl.BlockSpec((1 << p, 1), lambda i: (0, 0)),
            pl.BlockSpec((1 << p, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, cols), jnp.float32),
            jax.ShapeDtypeStruct((rows_pad, cols), jnp.float32),
            jax.ShapeDtypeStruct((rows_pad, cols), jnp.float32),
        ],
        interpret=common.interpret_flag(interpret),
    )(p2, g2, m2, v2, bc, common.rom_table(p), common.rom_table_rsqrt(p))

    unflat = lambda x: x.reshape(-1)[:n].reshape(orig_shape)
    return (
        unflat(p_new).astype(orig_dtype),
        unflat(m_new),
        unflat(v_new),
    )
