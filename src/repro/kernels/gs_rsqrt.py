"""Elementwise Goldschmidt rsqrt / sqrt as a Pallas TPU kernel.

[4]'s coupled square-root iteration (g -> sqrt, 2h -> rsqrt), seeded from
the rsqrt ROM over M in [1, 4) (even exponent), with the same
feedback/pipelined datapath selection as :mod:`gs_recip`.  §IV of the paper
notes the hardware reduction leaves these variants intact — the same single
multiplier pair serves them with a different complement step
(``0.5 - g*h`` instead of ``2 - r``).

Backward (``custom_vjp``): rules run on saved forward outputs, never
through the ``fori_loop``/bit-peel:

* ``gs_rsqrt``: residual is its own output ``y``; ``dx = -y³/2 · ḡ``.
* ``gs_sqrt``: the coupled iteration already produces the rsqrt in its
  ``h`` register, so the differentiated forward emits it as a second
  kernel output and saves it — ``dx = rsqrt(x)/2 · ḡ`` with zero extra
  backward compute (the paper's reuse-the-datapath move applied to
  autodiff).  The undifferentiated primal keeps the single-output call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

DEFAULT_BLOCK_ROWS = 64


def _kernel(x_ref, tab_ref, *out_refs, p: int, iters: int, variant: str,
            mode: str):
    x = x_ref[...]
    table = tab_ref[...]
    _, e, mant = common.split_fields(x)
    m = common.mantissa_to_m(mant)  # [1, 2)
    # Even exponent: E = e-127; if odd, m *= 2 and E -= 1 so m in [1, 4).
    E = e - 127
    odd = (E & 1) != 0
    m = jnp.where(odd, m * 2.0, m)
    Eh = jnp.where(odd, (E - 1) // 2, E // 2)  # E/2 after evening, exact
    g, h = common.gs_rsqrt_core(m, table, p=p, iters=iters, variant=variant)
    rs = (2.0 * h) * common.pow2_from_biased(127 - Eh)  # -> 1/sqrt(x)
    sq = g * common.pow2_from_biased(127 + Eh)          # -> sqrt(x)
    zero_in = e == 0
    inf_in = (e == 255) & (mant == 0)
    nan_in = ((e == 255) & (mant != 0)) | (x < 0.0)
    rs = jnp.where(zero_in, jnp.inf, rs)
    rs = jnp.where(inf_in, 0.0, rs)
    rs = jnp.where(nan_in, jnp.nan, rs)
    sq = jnp.where(zero_in, 0.0, sq)
    sq = jnp.where(inf_in, jnp.inf, sq)
    sq = jnp.where(nan_in, jnp.nan, sq)
    if mode == "rsqrt":
        out_refs[0][...] = rs
    elif mode == "sqrt":
        out_refs[0][...] = sq
    else:  # "sqrt_both": sqrt + its rsqrt co-output (the h register)
        out_refs[0][...] = sq
        out_refs[1][...] = rs


def _run(x, *, p, iters, variant, block_rows, interpret, mode):
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    cols = 128
    rows = -(-n // cols)
    rows_pad = -(-rows // block_rows) * block_rows
    flat = jnp.pad(flat, (0, rows_pad * cols - n), constant_values=1.0)
    x2 = flat.reshape(rows_pad, cols)
    table = common.rom_table_rsqrt(p)
    n_out = 2 if mode == "sqrt_both" else 1
    out_sds = jax.ShapeDtypeStruct((rows_pad, cols), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, p=p, iters=iters, variant=variant, mode=mode),
        grid=(rows_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((1 << p, 1), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))] * n_out
        if n_out > 1 else pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=[out_sds] * n_out if n_out > 1 else out_sds,
        interpret=common.interpret_flag(interpret),
    )(x2, table)
    outs = out if n_out > 1 else (out,)
    trimmed = tuple(
        o.reshape(-1)[:n].reshape(orig_shape).astype(orig_dtype) for o in outs
    )
    return trimmed if n_out > 1 else trimmed[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _rsqrt(x, p, iters, variant, block_rows, interpret):
    return _run(x, p=p, iters=iters, variant=variant, block_rows=block_rows,
                interpret=interpret, mode="rsqrt")


def _rsqrt_fwd(x, p, iters, variant, block_rows, interpret):
    y = _run(x, p=p, iters=iters, variant=variant, block_rows=block_rows,
             interpret=interpret, mode="rsqrt")
    return y, y


def _rsqrt_bwd(p, iters, variant, block_rows, interpret, y, g):
    y32 = y.astype(jnp.float32)
    return ((-0.5 * y32 * y32 * y32 * g.astype(jnp.float32)).astype(y.dtype),)


_rsqrt.defvjp(_rsqrt_fwd, _rsqrt_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _sqrt(x, p, iters, variant, block_rows, interpret):
    return _run(x, p=p, iters=iters, variant=variant, block_rows=block_rows,
                interpret=interpret, mode="sqrt")


def _sqrt_fwd(x, p, iters, variant, block_rows, interpret):
    y, rs = _run(x, p=p, iters=iters, variant=variant, block_rows=block_rows,
                 interpret=interpret, mode="sqrt_both")
    return y, rs


def _sqrt_bwd(p, iters, variant, block_rows, interpret, rs, g):
    return ((0.5 * rs.astype(jnp.float32) * g.astype(jnp.float32))
            .astype(rs.dtype),)


_sqrt.defvjp(_sqrt_fwd, _sqrt_bwd)


@functools.partial(
    jax.jit, static_argnames=("p", "iters", "variant", "block_rows", "interpret")
)
def gs_rsqrt(x, *, p: int = common.DEFAULT_P, iters: int = 2,
             variant: str = "feedback", block_rows: int = DEFAULT_BLOCK_ROWS,
             interpret: bool | None = None):
    return _rsqrt(x, p, iters, variant, block_rows, interpret)


@functools.partial(
    jax.jit, static_argnames=("p", "iters", "variant", "block_rows", "interpret")
)
def gs_sqrt(x, *, p: int = common.DEFAULT_P, iters: int = 2,
            variant: str = "feedback", block_rows: int = DEFAULT_BLOCK_ROWS,
            interpret: bool | None = None):
    return _sqrt(x, p, iters, variant, block_rows, interpret)
