"""Elementwise Goldschmidt reciprocal as a Pallas TPU kernel.

Datapath per tile (the paper's Fig. 3, one VMEM tile = one operand batch):

    bit-peel -> ROM one-hot matmul seed -> MULT1/2 -> [complement + MULT X/Y]
    (feedback fori_loop or pipelined unroll) -> exponent re-assembly.

BlockSpec: ``(block_rows, 128)`` f32 tiles — lane-aligned; the one-hot ROM
read temp is (block_rows*128, 128) f32, sized so the live working set stays
well under 8 MB of VMEM (block_rows = 64 -> 4 MB one-hot + ~200 KB tiles).

Domain: normal f32 magnitudes (biased exponent in [1, 253]); zeros map to
±inf, inf to ±0, nan propagates; results whose exponent underflows flush
to zero (TPU FTZ).  Subnormal *inputs* are treated as zero.

Backward (``custom_vjp``): the only residual is the kernel's own output
``q`` — the converged quotient is treated as an exact reciprocal
(arXiv:2305.03728's error analysis: correctly rounded after the
predetermined iteration count), so ``dx = -q²·ḡ``.  Nothing
differentiates through the ``fori_loop`` or the bitcast field peel
(which would yield silent zeros).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

DEFAULT_BLOCK_ROWS = 64


def _kernel(x_ref, tab_ref, o_ref, *, p: int, iters: int, variant: str):
    x = x_ref[...]
    table = tab_ref[...]
    sign, e, mant = common.split_fields(x)
    m = common.mantissa_to_m(mant)
    q = common.gs_recip_core(m, table, mant, p=p, iters=iters, variant=variant)
    # 1/x = q * 2^-E ; biased exponent of 2^-E is 254 - e.
    scale = common.pow2_from_biased(254 - e)
    out = q * scale
    out_bits = jax.lax.bitcast_convert_type(out, jnp.int32) | sign
    out = jax.lax.bitcast_convert_type(out_bits, jnp.float32)
    # Specials, branchless.
    zero_in = e == 0  # zero or subnormal input
    inf_in = (e == 255) & (mant == 0)
    nan_in = (e == 255) & (mant != 0)
    signf = jax.lax.bitcast_convert_type(
        sign | jnp.int32(0x3F800000), jnp.float32
    )  # ±1.0
    out = jnp.where(zero_in, signf * jnp.inf, out)
    out = jnp.where(inf_in, signf * 0.0, out)
    out = jnp.where(nan_in, jnp.nan, out)
    o_ref[...] = out


def _run(x, *, p, iters, variant, block_rows, interpret):
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    cols = 128
    rows = -(-n // cols)
    rows_pad = -(-rows // block_rows) * block_rows
    flat = jnp.pad(flat, (0, rows_pad * cols - n), constant_values=1.0)
    x2 = flat.reshape(rows_pad, cols)
    table = common.rom_table(p)

    out = pl.pallas_call(
        functools.partial(_kernel, p=p, iters=iters, variant=variant),
        grid=(rows_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((1 << p, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, cols), jnp.float32),
        interpret=common.interpret_flag(interpret),
    )(x2, table)
    return out.reshape(-1)[:n].reshape(orig_shape).astype(orig_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _recip(x, p, iters, variant, block_rows, interpret):
    return _run(x, p=p, iters=iters, variant=variant, block_rows=block_rows,
                interpret=interpret)


def _recip_fwd(x, p, iters, variant, block_rows, interpret):
    q = _run(x, p=p, iters=iters, variant=variant, block_rows=block_rows,
             interpret=interpret)
    return q, q


def _recip_bwd(p, iters, variant, block_rows, interpret, q, g):
    q32 = q.astype(jnp.float32)
    return ((-(q32 * q32) * g.astype(jnp.float32)).astype(q.dtype),)


_recip.defvjp(_recip_fwd, _recip_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("p", "iters", "variant", "block_rows", "interpret"),
)
def gs_recip(
    x: jnp.ndarray,
    *,
    p: int = common.DEFAULT_P,
    iters: int = 2,
    variant: str = "feedback",
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Reciprocal of x (any shape), elementwise, via the Pallas datapath."""
    return _recip(x, p, iters, variant, block_rows, interpret)
