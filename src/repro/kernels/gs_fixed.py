"""Fixed-point Goldschmidt epilogues over int8 activations, as Pallas kernels.

The quantized-serving siblings of ``gs_recip`` / ``gs_softmax`` /
``gs_rmsnorm``: operands arrive as **int8 registers** plus a per-tensor f32
scale, and every division site runs the paper's narrow integer datapath
(:class:`repro.core.fixed_point_jax.FixedPointJax`) — uint32 registers,
truncating 16-bit-limb multiplier, optional Mitchell log-multiplication on
the early passes — instead of the float mantissa pipeline.

Hardware-block mapping inside a tile:

* **ROM read** — the one-hot × table MXU matmul of :mod:`common`, but the
  table holds the *raw* (p+2)-bit integer words (≤ 2^14, exact in f32);
  the kernel casts the gathered word to uint32 and left-aligns it to the
  register's ``frac_bits`` — the f32 detour never rounds.
* **normalize** — int8 magnitudes normalize with ``msb32`` + shift (the
  recip kernel); f32 statistics (softmax denominator, mean-square) peel
  their IEEE mantissa straight into a ``frac_bits`` register, exactly for
  ``frac_bits ≥ 23`` and by the hardware's truncating narrowing below.
* **datapath** — the shared :class:`FixedPointJax` loops, seeded with the
  gathered ROM word (``k1=``/``y0=``), so kernel and policy route are the
  same bit-exact integer pipeline.

Tiles are ``(block_rows, 128)`` int8 (note: Mosaic's int8 minimum tile is
(32, 128) — on a real TPU pick ``block_rows ≥ 32``; this container runs
interpret mode where any divisor works).  Outputs are f32: these are
*epilogues* — the dequantization boundary of the int8 datapath.

No ``custom_vjp``: the int8 path is a serving datapath; int8 operands have
no gradient to propagate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import lut
from repro.core.fixed_point_jax import (FixedPointJax, _mant_to_reg, _peel,
                                        msb32)
from repro.kernels import common

DEFAULT_BLOCK_ROWS = 64
DEFAULT_ROW_BLOCK = 8
_NEG_BIG = -1e30


def fixed_rom_table(p: int) -> jnp.ndarray:
    """Raw (p+2)-bit reciprocal ROM words as a (2^p, 1) f32 matmul table."""
    return jnp.asarray(lut.reciprocal_table_int(p).astype(np.float32)
                       ).reshape(-1, 1)


def fixed_rsqrt_rom_table(p: int) -> jnp.ndarray:
    return jnp.asarray(lut.rsqrt_table_int(p).astype(np.float32)
                       ).reshape(-1, 1)


def _seed_from_table(idx, table, p: int, frac_bits: int) -> jnp.ndarray:
    """One-hot ROM read → uint32 register left-aligned to frac_bits."""
    word = common.rom_gather(idx, table, p)  # exact: words ≤ 2^(p+2) ≤ 2^14
    # through int32: Mosaic has no f32 -> uint32 cast (the word is positive)
    return (word.astype(jnp.int32).astype(jnp.uint32)
            << jnp.uint32(frac_bits - (p + 2)))


def _recip_reg(dp: FixedPointJax, m_reg, idx, table, *, iters, variant):
    """1/m register for m ∈ [1, 2): the divide datapath with n = 1."""
    k1 = _seed_from_table(idx, table, dp.p, dp.frac_bits)
    one = jnp.full_like(m_reg, jnp.uint32(1 << dp.frac_bits))
    q, _ = dp.divide(one, m_reg, iters, variant, k1=k1)
    return q


def _reg_to_f32(reg, frac_bits: int) -> jnp.ndarray:
    # through int32: Mosaic has no uint32 -> f32 cast; every register read
    # out here is a value < 2 in ≤ 30 fraction bits, so below 2^31
    return (reg.astype(jnp.int32).astype(jnp.float32)
            * np.float32(2.0 ** -frac_bits))


# ---------------------------------------------------------------------------
# gs_fixed_recip: elementwise 1/(x·scale) for int8 x
# ---------------------------------------------------------------------------


def _recip_kernel(x_ref, tab_ref, s_ref, o_ref, *, p, frac_bits, iters,
                  variant, mitchell_iters):
    dp = FixedPointJax(p=p, frac_bits=frac_bits,
                       mitchell_iters=mitchell_iters)
    xi = x_ref[...].astype(jnp.int32)
    a = jnp.maximum(jnp.abs(xi), 1).astype(jnp.uint32)  # |x| ∈ [1, 127]
    e = msb32(a)  # uint32, 0..6
    m_reg = a << (jnp.uint32(frac_bits) - e)  # m ∈ [1, 2)
    idx = ((m_reg - jnp.uint32(1 << frac_bits))
           >> jnp.uint32(frac_bits - p)).astype(jnp.int32)
    idx = jnp.clip(idx, 0, (1 << p) - 1)
    q = _recip_reg(dp, m_reg, idx, tab_ref[...], iters=iters,
                   variant=variant)
    # 1/(x·scale) = (1/m) · 2^-e · (1/scale); inv-scale is precomputed
    # host-side (per-tensor metadata, not a datapath operand).
    mag = (_reg_to_f32(q, frac_bits)
           * common.pow2_from_biased(127 - e.astype(jnp.int32))
           * s_ref[0, 0])
    out = jnp.where(xi < 0, -mag, mag)
    o_ref[...] = jnp.where(xi == 0, jnp.float32(jnp.inf), out)


@functools.partial(jax.jit, static_argnames=(
    "p", "frac_bits", "iters", "variant", "mitchell_iters", "block_rows",
    "interpret"))
def gs_fixed_recip(
    x: jnp.ndarray,
    scale=1.0,
    *,
    p: int = 8,
    frac_bits: int = 24,
    iters: int = 0,
    variant: str = "feedback",
    mitchell_iters: int = 0,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """1/(x·scale) for int8 x (any shape), elementwise, f32 out."""
    orig_shape = x.shape
    flat = x.astype(jnp.int8).reshape(-1)
    n = flat.shape[0]
    cols = 128
    rows = -(-n // cols)
    rows_pad = -(-rows // block_rows) * block_rows
    flat = jnp.pad(flat, (0, rows_pad * cols - n), constant_values=1)
    x2 = flat.reshape(rows_pad, cols)
    inv_scale = (1.0 / jnp.asarray(scale, jnp.float32)).reshape(1, 1)
    table = fixed_rom_table(p)

    out = pl.pallas_call(
        functools.partial(_recip_kernel, p=p, frac_bits=frac_bits,
                          iters=iters, variant=variant,
                          mitchell_iters=mitchell_iters),
        grid=(rows_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((1 << p, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, cols), jnp.float32),
        interpret=common.interpret_flag(interpret),
    )(x2, table, inv_scale)
    return out.reshape(-1)[:n].reshape(orig_shape)


# ---------------------------------------------------------------------------
# gs_fixed_softmax: rowwise softmax of dequantized int8 logits
# ---------------------------------------------------------------------------


def _softmax_kernel(x_ref, tab_ref, s_ref, o_ref, *, p, frac_bits, iters,
                    variant, mitchell_iters, d_real):
    dp = FixedPointJax(p=p, frac_bits=frac_bits,
                       mitchell_iters=mitchell_iters)
    v = x_ref[...].astype(jnp.float32) * s_ref[0, 0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    v = jnp.where(lanes < d_real, v, _NEG_BIG)
    m = jnp.max(v, axis=-1, keepdims=True)
    e = jnp.exp(v - m)
    s = jnp.sum(e, axis=-1, keepdims=True)  # ∈ [1, d]: a positive normal
    eb, mant, _ = _peel(s)
    m_reg = _mant_to_reg(mant, frac_bits)
    # clip in int32: Mosaic has no unsigned min/max (the index is < 2^23)
    idx = jnp.clip(((mant & 0x7FFFFF) >> jnp.uint32(23 - p)).astype(jnp.int32),
                   0, (1 << p) - 1)
    q = _recip_reg(dp, m_reg, idx, tab_ref[...], iters=iters,
                   variant=variant)
    inv = _reg_to_f32(q, frac_bits) * common.pow2_from_biased(254 - eb)
    o_ref[...] = e * inv


@functools.partial(jax.jit, static_argnames=(
    "p", "frac_bits", "iters", "variant", "mitchell_iters", "block_rows",
    "interpret"))
def gs_fixed_softmax(
    x: jnp.ndarray,
    scale=1.0,
    *,
    p: int = 8,
    frac_bits: int = 24,
    iters: int = 0,
    variant: str = "feedback",
    mitchell_iters: int = 0,
    block_rows: int = DEFAULT_ROW_BLOCK,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """softmax(x·scale) over the last axis of int8 x, f32 out."""
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.astype(jnp.int8).reshape(rows, d)
    d_pad = -(-d // 128) * 128
    rows_pad = -(-rows // block_rows) * block_rows
    x2 = jnp.pad(x2, ((0, rows_pad - rows), (0, d_pad - d)))
    inv_scale = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    table = fixed_rom_table(p)

    out = pl.pallas_call(
        functools.partial(_softmax_kernel, p=p, frac_bits=frac_bits,
                          iters=iters, variant=variant,
                          mitchell_iters=mitchell_iters, d_real=d),
        grid=(rows_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((1 << p, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, d_pad), jnp.float32),
        interpret=common.interpret_flag(interpret),
    )(x2, table, inv_scale)
    return out[:rows, :d].reshape(orig_shape)


# ---------------------------------------------------------------------------
# gs_fixed_rmsnorm: RMSNorm of dequantized int8 x, fixed rsqrt core
# ---------------------------------------------------------------------------


def _rmsnorm_kernel(x_ref, g_ref, tab_ref, s_ref, o_ref, *, p, frac_bits,
                    iters, eps, d_real):
    dp = FixedPointJax(p=p, frac_bits=frac_bits)
    xi = x_ref[...].astype(jnp.int32)
    gain = g_ref[...]
    scale = s_ref[0, 0]
    # int8² sums exactly in int32 (127²·d < 2^31 for d ≤ 2^17); padded
    # lanes are zero so the sum is exact and the mean divides by d_real.
    ss = jnp.sum(xi * xi, axis=-1, keepdims=True).astype(jnp.float32)
    ms = ss * (scale * scale) * np.float32(1.0 / d_real) + eps
    eb, mant, _ = _peel(ms)
    ebits = eb - 127
    half_e = ebits >> 1
    rem = ebits - (half_e << 1)  # 0|1: fold into m ∈ [1, 4)
    m_reg = _mant_to_reg(mant, frac_bits) << rem.astype(jnp.uint32)
    t = (m_reg - jnp.uint32(1 << frac_bits)) >> jnp.uint32(frac_bits - p)
    idx = jnp.clip((t // 3).astype(jnp.int32), 0, (1 << p) - 1)
    y0 = _seed_from_table(idx, tab_ref[...], p, frac_bits)
    h2 = dp.rsqrt_reg(m_reg, iters, y0=y0)
    inv = _reg_to_f32(h2, frac_bits) * common.pow2_from_biased(127 - half_e)
    o_ref[...] = xi.astype(jnp.float32) * scale * inv * gain


@functools.partial(jax.jit, static_argnames=(
    "p", "frac_bits", "iters", "eps", "block_rows", "interpret", "variant",
    "mitchell_iters"))
def gs_fixed_rmsnorm(
    x: jnp.ndarray,
    scale,
    gain: jnp.ndarray,
    *,
    eps: float = 1e-6,
    p: int = 8,
    frac_bits: int = 24,
    iters: int = 0,
    variant: str = "feedback",  # accepted for dispatch uniformity; the
    mitchell_iters: int = 0,  # rsqrt core is feedback-shaped & exact-mult
    block_rows: int = DEFAULT_ROW_BLOCK,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """RMSNorm of (x·scale) over the last axis; int8 x, f32 out."""
    del variant, mitchell_iters
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.astype(jnp.int8).reshape(rows, d)
    d_pad = -(-d // 128) * 128
    rows_pad = -(-rows // block_rows) * block_rows
    x2 = jnp.pad(x2, ((0, rows_pad - rows), (0, d_pad - d)))
    g2 = jnp.pad(gain.astype(jnp.float32), (0, d_pad - d)).reshape(1, d_pad)
    sc = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    table = fixed_rsqrt_rom_table(p)

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, p=p, frac_bits=frac_bits,
                          iters=iters, eps=eps, d_real=d),
        grid=(rows_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((1 << p, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, d_pad), jnp.float32),
        interpret=common.interpret_flag(interpret),
    )(x2, g2, table, sc)
    return out[:rows, :d].reshape(orig_shape)
