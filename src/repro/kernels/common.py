"""Shared in-kernel building blocks for the Goldschmidt Pallas kernels.

These are the TPU-native realizations of the paper's hardware blocks
(DESIGN.md §2 table):

* **ROM read** — the paper's p-in/(p+2)-out reciprocal table becomes a
  128-entry (p = 7) VMEM-resident float table read via a **one-hot × table
  matmul on the MXU**.  A per-lane dynamic gather is the one thing the TPU
  vector unit does not do well; a (tile, 128) one-hot contraction against a
  (128, 1) table is exactly what it does best, and 2^7 = 128 is lane-width
  aligned by construction.  This is the hardware adaptation of "ROM", not a
  workaround: the table lives in fast memory and is read combinationally.

* **normalize / renormalize** — the ASIC datapath works on a normalized
  mantissa register; here we peel the IEEE-754 fields with integer bit ops
  on the VPU (bitcast / shift / mask), which is branchless and avoids the
  transcendental path entirely.  Flush-to-zero semantics at the exponent
  extremes match TPU hardware behavior.

* **2's complement block** — ``2.0 - r`` fused into the multiply (an FMA).

* **feedback vs pipelined** — ``jax.lax.fori_loop`` vs an unrolled Python
  loop over the same step-2 body, selected by ``variant``; inside a kernel
  the fori_loop reuses one set of registers (the paper's single multiplier
  pair) while the unrolled form gives Mosaic independent values to schedule
  (the paper's replicated multipliers).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lut
from repro.core.goldschmidt import (F32_EXP_MASK, F32_MANT_MASK,
                                    F32_ONE_BITS, F32_SIGN_BIT)
# one authoritative default table width: the policy's (7, 2) fp32 pair and
# the kernel sweep's defaults must agree for bit-identical cold starts
from repro.core.goldschmidt import DEFAULT_P  # noqa: F401  (2^7 = lane row)

# field constants live in core.goldschmidt (one home for both peels)
_F32_SIGN = F32_SIGN_BIT
_F32_EXP_MASK = F32_EXP_MASK
_F32_MANT_MASK = F32_MANT_MASK
_F32_ONE_BITS = F32_ONE_BITS


def interpret_flag(interpret: bool | None = None) -> bool:
    """Pallas interpret mode: as given, else derived from the backend.

    The CPU backend has no Mosaic compiler, so kernels run interpreted
    there (tests); every other backend compiles them.  Callers pass an
    explicit flag only to compile for a described chip from a CPU host.
    """
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def fit_block(s: int, target: int) -> int:
    """Largest divisor of s that is <= target.

    Block sizes must tile the sequence exactly; tuned/default targets come
    from pow2 buckets, real lengths (1500, 33, ...) do not.  Single home
    for the clamping rule — dispatch (ops), the autotuner's candidate
    generation (tuning.registry), and the chunked jnp attention path
    (layers.attention) all route here.
    """
    blk = min(max(int(target), 1), int(s))
    while s % blk:
        blk -= 1
    return blk


def rom_table(p: int = DEFAULT_P) -> jnp.ndarray:
    """Reciprocal ROM as a (2^p, 1) f32 array (matmul-gather layout)."""
    return jnp.asarray(lut.reciprocal_table_f32(p)).reshape(-1, 1)


def rom_table_rsqrt(p: int = DEFAULT_P) -> jnp.ndarray:
    return jnp.asarray(lut.rsqrt_table_f32(p)).reshape(-1, 1)


ROM_ROW_GROUP = 8  # tile rows per block-diagonal ROM read (one sublane tile)


def _mxu(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    # HIGHEST: the one-hot picks exactly one table word, which must reach
    # the output unrounded (the fixed-point ROM words are 14-bit integers)
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def rom_gather(idx: jnp.ndarray, table: jnp.ndarray, p: int) -> jnp.ndarray:
    """ROM read via one-hot matmul on the MXU.

    idx: (rows, cols) int32 with values in [0, 2^p); rows is a multiple of
    8 when cols > 1.  table: (2^p, 1) float32 (already loaded from the
    ref).  Returns float32 of idx's shape, bit-exact table words.

    Nothing is reshaped across the lane axis, which Mosaic cannot lay out:

    * a column of per-row statistics (cols == 1) broadcasts along the
      lanes into a (rows, 2^p) one-hot against the (2^p, 1) table;
    * a (rows, cols) tile reads 8 rows at a time: each row's indices
      broadcast down the sublanes into a (2^p, cols) one-hot, the 8 are
      stacked into (8·2^p, cols), and a block-diagonal (8, 8·2^p) copy
      of the table contracts them to the (8, cols) words.
    """
    rows, cols = idx.shape
    k = 1 << p
    if cols == 1:
        lanes = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)
        return _mxu((idx == lanes).astype(jnp.float32), table)
    g = min(ROM_ROW_GROUP, rows)
    # the (k, 1) column as a (1, k) row, by a sublane reduction
    eye = (jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (k, k), 1))
    tab_row = jnp.sum(jnp.where(eye, table, 0.0), axis=0, keepdims=True)
    blk_row = jax.lax.broadcasted_iota(jnp.int32, (g, g * k), 0)
    blk_col = jax.lax.broadcasted_iota(jnp.int32, (g, g * k), 1) // k
    diag = jnp.where(blk_row == blk_col,
                     jnp.concatenate([tab_row] * g, axis=1), 0.0)
    words = jax.lax.broadcasted_iota(jnp.int32, (k, cols), 0)
    out = []
    for r0 in range(0, rows, g):
        onehot = jnp.concatenate(
            [(jnp.broadcast_to(idx[r:r + 1, :], (k, cols)) == words)
             .astype(jnp.float32) for r in range(r0, r0 + g)], axis=0)
        out.append(_mxu(diag, onehot))
    return jnp.concatenate(out, axis=0)


def split_fields(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """IEEE-754 field peel: (sign_bits, biased_exp, mantissa_bits), all int32."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    sign = bits & _F32_SIGN
    # np.int32 shift counts here and below: a bare python literal turns
    # weakly-typed i64 under enable_x64 and lax.shift_* does not promote
    e = jax.lax.shift_right_logical(bits, np.int32(23)) & _F32_EXP_MASK
    mant = bits & _F32_MANT_MASK
    return sign, e, mant


def mantissa_to_m(mant: jnp.ndarray) -> jnp.ndarray:
    """mantissa bits -> m in [1, 2) (the normalized divisor register)."""
    return jax.lax.bitcast_convert_type(_F32_ONE_BITS | mant, jnp.float32)


def pow2_from_biased(e_biased: jnp.ndarray) -> jnp.ndarray:
    """2^(e_biased - 127) as f32, for e_biased clamped to [0, 254].

    e_biased == 0 encodes +0.0 — flush-to-zero at the range edge, matching
    TPU FTZ semantics (documented kernel domain: normal floats).
    """
    e = jnp.clip(e_biased, 0, 254)
    return jax.lax.bitcast_convert_type(
        jax.lax.shift_left(e.astype(jnp.int32), np.int32(23)), jnp.float32
    )


def gs_recip_core(
    m: jnp.ndarray,
    table: jnp.ndarray,
    mant: jnp.ndarray,
    *,
    p: int,
    iters: int,
    variant: str,
) -> jnp.ndarray:
    """Goldschmidt reciprocal of m in [1,2) given its mantissa bits.

    The datapath of the paper's Fig. 3: ROM seed -> MULT1/2 -> (complement +
    MULT X/Y) x iters, either unrolled ("pipelined") or as a fori_loop
    ("feedback" — the loop carry is the feedback wire, the trip count the
    logic-block counter).
    """
    idx = jax.lax.shift_right_logical(mant, np.int32(23 - p))
    k1 = rom_gather(idx, table, p)
    q = k1  # MULT 1 with N = 1
    r = m * k1  # MULT 2

    def step(qr):
        q, r = qr
        k = 2.0 - r  # 2's complement block
        return q * k, r * k  # MULT X, MULT Y

    if variant == "pipelined":
        for _ in range(iters):
            q, r = step((q, r))
    else:
        q, r = jax.lax.fori_loop(0, iters, lambda _, qr: step(qr), (q, r))
    return q


def recip_positive(
    x: jnp.ndarray,
    table: jnp.ndarray,
    *,
    p: int,
    iters: int,
    variant: str,
) -> jnp.ndarray:
    """1/x for strictly-positive normal f32 x (no specials) — the epilogue
    form used inside fused kernels (softmax/flash denominators, adam)."""
    _, e, mant = split_fields(x)
    m = mantissa_to_m(mant)
    q = gs_recip_core(m, table, mant, p=p, iters=iters, variant=variant)
    return q * pow2_from_biased(254 - e)


def rsqrt_positive(
    x: jnp.ndarray,
    table: jnp.ndarray,
    *,
    p: int,
    iters: int,
    variant: str,
    mode: str = "rsqrt",
) -> jnp.ndarray:
    """1/sqrt(x) (or sqrt(x) with mode='sqrt') for positive normal f32 x."""
    _, e, mant = split_fields(x)
    m = mantissa_to_m(mant)
    E = e - 127
    odd = (E & 1) != 0
    m = jnp.where(odd, m * 2.0, m)
    Eh = jnp.where(odd, (E - 1) // 2, E // 2)
    g, h = gs_rsqrt_core(m, table, p=p, iters=iters, variant=variant)
    if mode == "rsqrt":
        return (2.0 * h) * pow2_from_biased(127 - Eh)
    return g * pow2_from_biased(127 + Eh)


def gs_rsqrt_core(
    m: jnp.ndarray,
    table: jnp.ndarray,
    *,
    p: int,
    iters: int,
    variant: str,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Goldschmidt sqrt/rsqrt of m in [1, 4).

    Returns (g, h) with g -> sqrt(m) and 2h -> 1/sqrt(m) ([4]'s coupled
    iteration; §IV of the paper keeps these variants intact).
    """
    idx = jnp.floor((m - 1.0) * ((1 << p) / 3.0)).astype(jnp.int32)
    idx = jnp.clip(idx, 0, (1 << p) - 1)
    y0 = rom_gather(idx, table, p)
    g = m * y0
    h = 0.5 * y0

    def step(gh):
        g, h = gh
        r = 0.5 - g * h
        return g + g * r, h + h * r

    if variant == "pipelined":
        for _ in range(iters):
            g, h = step((g, h))
    else:
        g, h = jax.lax.fori_loop(0, iters, lambda _, gh: step(gh), (g, h))
    return g, h
