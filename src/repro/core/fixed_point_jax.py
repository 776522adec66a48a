"""Traceable jax port of the fixed-point Goldschmidt datapath.

:mod:`repro.core.fixed_point` emulates the paper's hardware bit-exactly in
numpy ``uint64`` — but numpy can't sit inside a jitted serving tick.  This
module is the same datapath in jax integer ops, **bit-identical** to the
numpy reference (asserted across p × frac_bits × variant × mitchell in
``tests/test_fixed_point_jax.py``), so the int8 serving path's division
sites run through the narrow datapath the paper actually builds.

Two constraints shape the port:

* **No x64.**  jax's default config has no uint64, so the truncating
  w×w→w multiplier is built from 16-bit limbs in uint32: with registers
  < 2^32 and every *value* < 4.0 (i.e. < 2^(frac_bits+2) ≤ 2^32), the
  truncated product ``(a·b) >> frac_bits`` also fits 32 bits, and is
  reassembled exactly from the (hi, lo) 32-bit product halves as
  ``(hi << (32 − F)) | (lo >> F)``.
* **No float detours.**  Registers stay uint32 end-to-end; the only
  float arithmetic is at the IEEE-754 boundary of the ``*_f32`` wrappers
  (an exact bit-peel of mantissas — no rounding on encode).

The Mitchell log-multiplier option mirrors
``FixedPointDatapath.mitchell_mult`` step-for-step (same clipped shifts),
so approximate-multiplier formats are also bit-identical across the
numpy/jax pair.  The rsqrt datapath (the coupled g/h iteration of the
float kernels, in fixed point) keeps the residual ``0.5 − g·h`` unsigned
by computing magnitude + direction — an add/sub datapath, not a signed
multiplier.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lut

__all__ = [
    "FixedPointJax",
    "recip_f32",
    "divide_f32",
    "rsqrt_f32",
    "sqrt_f32",
]

_MANT_MASK = 0x7FFFFF
_F32_ONE_BITS = 1 << 23


# Unsigned min/max as compare-and-select: Mosaic (the TPU kernel compiler)
# has no unsigned min/max, and the kernels run this datapath.
def _umin(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(a < b, a, b)


def _umax(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(a > b, a, b)


def msb32(x: jnp.ndarray) -> jnp.ndarray:
    """Leading-one index of uint32 registers (mirrors fixed_point.msb)."""
    e = jnp.zeros_like(x)
    t = x
    for sh in (16, 8, 4, 2, 1):
        m = t >= jnp.uint32(1 << sh)
        e = jnp.where(m, e + jnp.uint32(sh), e)
        t = jnp.where(m, t >> jnp.uint32(sh), t)
    return e


@dataclasses.dataclass(frozen=True)
class FixedPointJax:
    """The n-bit divider datapath on uint32 registers, jit-traceable.

    Register convention matches the numpy reference: unsigned, value =
    reg · 2^-frac_bits, every datapath value < 4.0.  ``divide_*`` take
    *registers* (encode at the caller's boundary — the ``*_f32`` wrappers
    peel IEEE-754 mantissas exactly, tests reuse the numpy ``encode``).
    """

    p: int = 7
    frac_bits: int = 28
    mitchell_iters: int = 0

    def __post_init__(self):
        if self.frac_bits > 30:
            raise ValueError("frac_bits > 30 overflows the 32-bit register")
        if self.frac_bits < self.p + 2:
            raise ValueError(
                f"frac_bits={self.frac_bits} cannot hold the (p+2)-bit ROM "
                f"word (p={self.p})")

    # -- hardware primitive blocks ------------------------------------------

    def mult(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """w×w→w truncating multiplier via 16-bit limbs (no uint64)."""
        F = self.frac_bits
        a_lo, a_hi = a & 0xFFFF, a >> 16
        b_lo, b_hi = b & 0xFFFF, b >> 16
        ll = a_lo * b_lo
        m1 = a_hi * b_lo
        m2 = a_lo * b_hi
        lo = ll + ((m1 & 0xFFFF) << 16)
        c1 = (lo < ll).astype(jnp.uint32)  # unsigned wrap = carry out
        lo2 = lo + ((m2 & 0xFFFF) << 16)
        c2 = (lo2 < lo).astype(jnp.uint32)
        hi = a_hi * b_hi + (m1 >> 16) + (m2 >> 16) + c1 + c2
        return (hi << (32 - F)) | (lo2 >> F)

    def mitchell_mult(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Mitchell log-multiplier, bit-identical to the numpy block."""
        F = jnp.uint32(self.frac_bits)
        ea, eb = msb32(a), msb32(b)
        fa, fb = a - (jnp.uint32(1) << ea), b - (jnp.uint32(1) << eb)
        fa_s = jnp.where(ea <= F, fa << (F - _umin(ea, F)),
                         fa >> (_umax(ea, F) - F))
        fb_s = jnp.where(eb <= F, fb << (F - _umin(eb, F)),
                         fb >> (_umax(eb, F) - F))
        s = fa_s + fb_s
        e2 = ea + eb + (s >> F)
        f2 = s & ((jnp.uint32(1) << F) - jnp.uint32(1))
        base = (jnp.uint32(1) << F) + f2
        two_f = jnp.uint32(2 * self.frac_bits)
        shl = _umax(e2, two_f) - two_f
        shr = _umin(two_f - _umin(e2, two_f), jnp.uint32(31))
        res = jnp.where(e2 >= two_f, base << shl, base >> shr)
        return jnp.where((a == 0) | (b == 0), jnp.uint32(0), res)

    def complement(self, r: jnp.ndarray) -> jnp.ndarray:
        """2's complement block: K = 2 − r (2<<30 = 2^31 still fits)."""
        return jnp.uint32(2 << self.frac_bits) - r

    @functools.cached_property
    def _rom_words(self) -> np.ndarray:
        # entries ≤ 2^(p+2) left-aligned to ≤ 2^frac_bits ≤ 2^30: uint32-safe
        return (lut.reciprocal_table_int(self.p).astype(np.uint32)
                << np.uint32(self.frac_bits - (self.p + 2)))

    def rom(self, d_reg: jnp.ndarray) -> jnp.ndarray:
        one = jnp.uint32(1 << self.frac_bits)
        idx = (d_reg - one) >> (self.frac_bits - self.p)
        idx = jnp.clip(idx.astype(jnp.int32), 0, (1 << self.p) - 1)
        return jnp.asarray(self._rom_words)[idx]

    def _pass_mult(self, i: int):
        return self.mitchell_mult if i < self.mitchell_iters else self.mult

    # -- full datapaths ------------------------------------------------------

    def divide_pipelined(self, n_reg: jnp.ndarray, d_reg: jnp.ndarray,
                         passes: int, k1=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Unrolled datapath on registers; returns (q_reg, r_reg).

        ``k1`` overrides the ROM seed — the Pallas kernels gather it with
        a one-hot MXU matmul (a per-lane ``take`` is what the TPU vector
        unit can't do) and hand the register here.
        """
        if k1 is None:
            k1 = self.rom(d_reg)
        q = self.mult(n_reg, k1)  # MULT 1
        r = self.mult(d_reg, k1)  # MULT 2
        for i in range(passes):
            k = self.complement(r)
            mul = self._pass_mult(i)
            q = mul(q, k)  # MULT X_i
            if i != passes - 1:
                r = mul(r, k)  # MULT Y_i
        return q, r

    def divide_feedback(self, n_reg: jnp.ndarray, d_reg: jnp.ndarray,
                        passes: int, k1=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Feedback datapath: one shared multiplier pair in a fori_loop.

        The loop computes both multiplier variants and muxes on the pass
        counter — exactly what a hardware mux in front of two multiplier
        blocks does, and value-identical to the numpy reference's
        python-level dispatch (``r`` returned is the residual fed to the
        final complement, matching ``FixedResult.r``).
        """
        if k1 is None:
            k1 = self.rom(d_reg)
        q = self.mult(n_reg, k1)
        r = self.mult(d_reg, k1)
        if passes == 0:
            return q, r
        mit = jnp.uint32(self.mitchell_iters)

        def body(i, qr):
            q, r = qr
            k = self.complement(r)
            use_mit = jnp.uint32(i) < mit
            q_new = jnp.where(use_mit, self.mitchell_mult(q, k),
                              self.mult(q, k))
            r_new = jnp.where(use_mit, self.mitchell_mult(r, k),
                              self.mult(r, k))
            return q_new, jnp.where(i == passes - 1, r, r_new)

        return jax.lax.fori_loop(0, passes, body, (q, r))

    def divide(self, n_reg, d_reg, passes: int, variant: str = "feedback",
               k1=None):
        fn = (self.divide_pipelined if variant == "pipelined"
              else self.divide_feedback)
        return fn(n_reg, d_reg, passes, k1)

    # -- rsqrt: the coupled g/h iteration in fixed point ---------------------

    @functools.cached_property
    def _rsqrt_rom_words(self) -> np.ndarray:
        return (lut.rsqrt_table_int(self.p).astype(np.uint32)
                << np.uint32(self.frac_bits - (self.p + 2)))

    def rsqrt_reg(self, m_reg: jnp.ndarray, passes: int,
                  y0=None) -> jnp.ndarray:
        """1/sqrt of m ∈ [1, 4): returns the 2h register (→ rsqrt(m)).

        The residual ``r = 0.5 − g·h`` straddles zero once the seed is
        good, so it is carried as (magnitude, direction) and applied with
        an adder/subtractor — registers stay unsigned.  Always exact
        multiplies: Mitchell is a divide-datapath option (§III of the
        companion), and rsqrt's coupled iteration is not where the paper
        spends multiplier area.
        """
        F = self.frac_bits
        one = jnp.uint32(1 << F)
        # bucket index: (m−1)·2^p/3 — scale the fraction to p bits, then
        # the divide-by-3 is an exact small-integer division
        if y0 is None:
            t = (m_reg - one) >> (F - self.p)
            idx = jnp.clip((t // 3).astype(jnp.int32), 0, (1 << self.p) - 1)
            y0 = jnp.asarray(self._rsqrt_rom_words)[idx]
        g = self.mult(m_reg, y0)
        h = y0 >> 1
        half = jnp.uint32(1 << (F - 1))

        def step(gh):
            g, h = gh
            gh_prod = self.mult(g, h)
            pos = gh_prod <= half
            rmag = jnp.where(pos, half - gh_prod, gh_prod - half)
            gd, hd = self.mult(g, rmag), self.mult(h, rmag)
            return (jnp.where(pos, g + gd, g - gd),
                    jnp.where(pos, h + hd, h - hd))

        for _ in range(passes):
            g, h = step((g, h))
        return h << 1


# ---------------------------------------------------------------------------
# IEEE-754 boundary: f32 wrappers for the policy / kernel routes
# ---------------------------------------------------------------------------


def _peel(x: jnp.ndarray):
    """f32 → (biased exponent i32, mantissa-with-hidden-one u32, sign u32)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    e = ((bits >> 23) & 0xFF).astype(jnp.int32)
    mant = (bits & _MANT_MASK) | _F32_ONE_BITS
    return e, mant.astype(jnp.uint32), bits >> 31


def _mant_to_reg(mant: jnp.ndarray, frac_bits: int) -> jnp.ndarray:
    """24-bit mantissa (1.f) → register with frac_bits fraction bits.

    Exact for frac_bits ≥ 23; truncating (the hardware narrowing) below.
    """
    if frac_bits >= 23:
        return mant << (frac_bits - 23)
    return mant >> (23 - frac_bits)


def _reg_to_f32(reg: jnp.ndarray, frac_bits: int) -> jnp.ndarray:
    return reg.astype(jnp.float32) * np.float32(2.0 ** -frac_bits)


def _finite_nonzero(e: jnp.ndarray) -> jnp.ndarray:
    return (e > 0) & (e < 255)


@functools.partial(jax.jit, static_argnames=(
    "frac_bits", "p", "iters", "variant", "mitchell_iters"))
def recip_f32(x: jnp.ndarray, *, frac_bits: int = 28, p: int = 7,
              iters: int = 2, variant: str = "feedback",
              mitchell_iters: int = 0) -> jnp.ndarray:
    """1/x through the fixed-point datapath (normals; specials fall back)."""
    dp = FixedPointJax(p=p, frac_bits=frac_bits,
                       mitchell_iters=mitchell_iters)
    xf = x.astype(jnp.float32)
    e, mant, sign = _peel(xf)
    m_reg = _mant_to_reg(mant, frac_bits)
    one_reg = jnp.full_like(m_reg, jnp.uint32(1 << frac_bits))
    q, _ = dp.divide(one_reg, m_reg, iters, variant)
    mag = jnp.ldexp(_reg_to_f32(q, frac_bits), 127 - e)
    res = jnp.where(sign == 1, -mag, mag)
    out = jnp.where(_finite_nonzero(e), res, 1.0 / xf)
    return out.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=(
    "frac_bits", "p", "iters", "variant", "mitchell_iters"))
def divide_f32(n: jnp.ndarray, d: jnp.ndarray, *, frac_bits: int = 28,
               p: int = 7, iters: int = 2, variant: str = "feedback",
               mitchell_iters: int = 0) -> jnp.ndarray:
    """n/d through the datapath: mantissa ratio ∈ (0.5, 2) fits registers."""
    dp = FixedPointJax(p=p, frac_bits=frac_bits,
                       mitchell_iters=mitchell_iters)
    nf, df = n.astype(jnp.float32), d.astype(jnp.float32)
    en, mn, sn = _peel(nf)
    ed, md, sd = _peel(df)
    q, _ = dp.divide(_mant_to_reg(mn, frac_bits),
                     _mant_to_reg(md, frac_bits), iters, variant)
    mag = jnp.ldexp(_reg_to_f32(q, frac_bits), en - ed)
    res = jnp.where(sn != sd, -mag, mag)
    ok = _finite_nonzero(en) & _finite_nonzero(ed)
    out = jnp.where(ok, res, nf / df)
    return out.astype(jnp.result_type(n, d))


@functools.partial(jax.jit, static_argnames=("frac_bits", "p", "iters"))
def rsqrt_f32(x: jnp.ndarray, *, frac_bits: int = 28, p: int = 7,
              iters: int = 2) -> jnp.ndarray:
    """1/sqrt(x) via the fixed coupled iteration (positive normals)."""
    dp = FixedPointJax(p=p, frac_bits=frac_bits)
    xf = x.astype(jnp.float32)
    e, mant, _ = _peel(xf)
    ebits = e - 127
    half_e = ebits >> 1  # arithmetic floor
    rem = ebits - (half_e << 1)  # 0 or 1
    m_reg = _mant_to_reg(mant, frac_bits) << rem.astype(jnp.uint32)
    h2 = dp.rsqrt_reg(m_reg, iters)
    res = jnp.ldexp(_reg_to_f32(h2, frac_bits), -half_e)
    out = jnp.where(_finite_nonzero(e) & (xf > 0), res,
                    jax.lax.rsqrt(xf))
    return out.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("frac_bits", "p", "iters"))
def sqrt_f32(x: jnp.ndarray, *, frac_bits: int = 28, p: int = 7,
             iters: int = 2) -> jnp.ndarray:
    """sqrt(x) = x · rsqrt(x) with the fixed rsqrt core."""
    xf = x.astype(jnp.float32)
    out = jnp.where(xf == 0, xf, xf * rsqrt_f32(
        xf, frac_bits=frac_bits, p=p, iters=iters))
    return out.astype(x.dtype)
