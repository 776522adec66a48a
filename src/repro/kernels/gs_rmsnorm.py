"""Fused RMSNorm with Goldschmidt rsqrt, as a Pallas kernel.

Division site #2 of DESIGN.md §3: ``x * rsqrt(mean(x^2) + eps) * gain``
with the rsqrt computed by [4]'s coupled Goldschmidt iteration on the
(block_rows, 1) mean-square column — the fused-epilogue form of the
paper's datapath.  fp32 accumulation regardless of input dtype.

Backward (``custom_vjp``): the differentiated forward emits the
(rows, 1) Goldschmidt rsqrt column ``r`` as a second kernel output and
saves ``(x, gain, r)`` as residuals.  With ``t = ḡ ⊙ gain``:

    dx    = t·r - x ⊙ (r³/d) ⊙ Σ_col(t ⊙ x)
    dgain = Σ_rows(ḡ ⊙ x ⊙ r)

— multiplies, powers of the saved rsqrt, and row sums only; no divide,
and nothing differentiates through the ``fori_loop``/bit-peel (which has
no gradient).  The undifferentiated primal keeps the single-output call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common


def _kernel(x_ref, g_ref, tab_ref, *out_refs, p, iters, variant, eps, d_real,
            save_inv):
    x = x_ref[...].astype(jnp.float32)
    gain = g_ref[...].astype(jnp.float32)
    # Padded feature lanes are zero: sum is exact; divide by the REAL width.
    ms = jnp.sum(x * x, axis=-1, keepdims=True) * (1.0 / d_real)
    inv = common.rsqrt_positive(
        ms + eps, tab_ref[...], p=p, iters=iters, variant=variant
    )
    out_refs[0][...] = (x * inv * gain).astype(out_refs[0].dtype)
    if save_inv:
        out_refs[1][...] = inv


def _run(x, gain, *, eps, p, iters, variant, block_rows, interpret,
         save_inv=False):
    orig_shape, orig_dtype = x.shape, x.dtype
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    d_pad = -(-d // 128) * 128
    rows_pad = -(-rows // block_rows) * block_rows
    x2 = jnp.pad(x2.astype(jnp.float32), ((0, rows_pad - rows), (0, d_pad - d)))
    g2 = jnp.pad(gain.astype(jnp.float32), (0, d_pad - d)).reshape(1, d_pad)
    table = common.rom_table_rsqrt(p)
    out_specs = pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct((rows_pad, d_pad), orig_dtype)
    if save_inv:
        out_specs = [out_specs, pl.BlockSpec((block_rows, 1), lambda i: (i, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((rows_pad, 1), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(
            _kernel, p=p, iters=iters, variant=variant, eps=eps, d_real=d,
            save_inv=save_inv,
        ),
        grid=(rows_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((1 << p, 1), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=common.interpret_flag(interpret),
    )(x2, g2, table)
    if save_inv:
        y, inv = out
        return (y[:rows, :d].reshape(orig_shape), inv[:rows])
    return out[:rows, :d].reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _rmsnorm(x, gain, eps, p, iters, variant, block_rows, interpret):
    return _run(x, gain, eps=eps, p=p, iters=iters, variant=variant,
                block_rows=block_rows, interpret=interpret)


def _rmsnorm_fwd(x, gain, eps, p, iters, variant, block_rows, interpret):
    y, inv = _run(x, gain, eps=eps, p=p, iters=iters, variant=variant,
                  block_rows=block_rows, interpret=interpret, save_inv=True)
    return y, (x, gain, inv)


def _rmsnorm_bwd(eps, p, iters, variant, block_rows, interpret, res, g):
    x, gain, inv = res
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.astype(jnp.float32).reshape(-1, d)
    g2 = g.astype(jnp.float32).reshape(-1, d)
    gain32 = gain.astype(jnp.float32)
    r = inv  # (rows, 1) f32: the saved Goldschmidt rsqrt column
    t = g2 * gain32[None, :]
    proj = jnp.sum(t * x2, axis=-1, keepdims=True)
    dx = t * r - x2 * ((r * r * r) * (proj * (1.0 / d)))
    dgain = jnp.sum(g2 * x2 * r, axis=0)
    return (dx.reshape(orig_shape).astype(x.dtype), dgain.astype(gain.dtype))


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("p", "iters", "variant", "eps", "block_rows", "interpret"),
)
def gs_rmsnorm(
    x: jnp.ndarray,
    gain: jnp.ndarray,
    *,
    eps: float = 1e-6,
    p: int = common.DEFAULT_P,
    iters: int = 2,
    variant: str = "feedback",
    block_rows: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """RMSNorm over the last axis; gain has shape (d,)."""
    return _rmsnorm(x, gain, eps, p, iters, variant, block_rows, interpret)
