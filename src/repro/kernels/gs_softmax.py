"""Fused row-softmax with a Goldschmidt denominator, as a Pallas kernel.

One VMEM tile = (block_rows, n_cols): row max -> exp -> row sum -> GS
reciprocal of the (block_rows, 1) sums (the paper's datapath applied to the
softmax denominator — division site #1 of DESIGN.md §3) -> scale.

Columns are padded to a lane multiple with -inf so padded lanes contribute
exp(-inf)=0 to the sum and the reciprocal operates on the true row sum.
The full row must fit in VMEM: rows up to ~16k f32 columns are fine
(block_rows * cols * 4B + one-hot (block_rows,128) ~ «8 MB for
block_rows=8, cols=16384).

Backward (``custom_vjp``): softmax is self-residual — the saved output
``y`` gives ``dx = y ⊙ (ḡ - Σ_col y·ḡ)``, multiplies and a row sum only
(division-free, like the forward).  No differentiation through the
Goldschmidt ``fori_loop``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common


def _kernel(x_ref, tab_ref, o_ref, *, p, iters, variant):
    x = x_ref[...].astype(jnp.float32)
    table = tab_ref[...]
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=-1, keepdims=True)  # >= 1 (the max element)
    inv = common.recip_positive(s, table, p=p, iters=iters, variant=variant)
    o_ref[...] = (e * inv).astype(o_ref.dtype)


def _run(x, *, p, iters, variant, block_rows, interpret):
    orig_shape, orig_dtype = x.shape, x.dtype
    cols = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, cols)
    cols_pad = -(-cols // 128) * 128
    rows_pad = -(-rows // block_rows) * block_rows
    x2 = jnp.pad(
        x2.astype(jnp.float32),
        ((0, rows_pad - rows), (0, cols_pad - cols)),
        constant_values=-jnp.inf,
    )
    table = common.rom_table(p)
    out = pl.pallas_call(
        functools.partial(_kernel, p=p, iters=iters, variant=variant),
        grid=(rows_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, cols_pad), lambda i: (i, 0)),
            pl.BlockSpec((1 << p, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, cols_pad), orig_dtype),
        interpret=common.interpret_flag(interpret),
    )(x2, table)
    return out[:rows, :cols].reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _softmax(x, p, iters, variant, block_rows, interpret):
    return _run(x, p=p, iters=iters, variant=variant, block_rows=block_rows,
                interpret=interpret)


def _softmax_fwd(x, p, iters, variant, block_rows, interpret):
    y = _run(x, p=p, iters=iters, variant=variant, block_rows=block_rows,
             interpret=interpret)
    return y, y


def _softmax_bwd(p, iters, variant, block_rows, interpret, y, g):
    y32 = y.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    dot = jnp.sum(y32 * g32, axis=-1, keepdims=True)
    return ((y32 * (g32 - dot)).astype(y.dtype),)


_softmax.defvjp(_softmax_fwd, _softmax_bwd)


@functools.partial(
    jax.jit, static_argnames=("p", "iters", "variant", "block_rows", "interpret")
)
def gs_softmax(
    x: jnp.ndarray,
    *,
    p: int = common.DEFAULT_P,
    iters: int = 2,
    variant: str = "feedback",
    block_rows: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Softmax over the last axis of x (any leading shape)."""
    return _softmax(x, p, iters, variant, block_rows, interpret)
