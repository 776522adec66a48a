"""Property-based differential suite: gs divide/recip/rsqrt/sqrt vs exact.

Yuan et al.'s parametric error analysis of Goldschmidt FP division
(PAPERS.md, arXiv:2305.03728) is the contract this file enforces: the
relative error after a predetermined (p, iters) schedule is *bounded*,
per pair, not hand-waved.  Every public op is compared against the exact
result computed in float64 over all four dtypes × the value classes that
break naive datapaths — subnormals, signed zeros, inf/nan, exact powers
of two, near-overflow magnitudes — asserting the ``precision_policy``
bound for the dtype's derived (p, iters) pair (including the seed-only
``iters=0`` bf16 path) and for explicitly pinned pairs.

Bound model (see core/goldschmidt.py + core/lut.py): a (p, iters)
schedule delivers ``bits = seed_bits(p) · 2^iters`` good bits, capped at
21 by the float32 internal datapath (iteration rounding: ~2 ulp below
the 24-bit mantissa; float64 inputs run through the same f32 pipe and
inherit the cap).  Output rounding adds a half-ulp of the target dtype.
We assert ``rel_err <= 1.5 · (2^-bits + 2^-(mant-1))`` plus an absolute
floor of a few target-dtype subnormal quanta for results that land in
the gradual-underflow range (where no finite relative bound exists).

hypothesis is optional (conftest pattern): the deterministic grids below
always run; the randomized property tests skip cleanly without it.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # property tests skip cleanly without hypothesis
    from conftest import fake_given as given
    from conftest import fake_settings as settings
    from conftest import fake_strategies as st

from repro.core import goldschmidt as gs
from repro.core import lut

F32_ITER_BITS = 21  # the float32 datapath's iteration-rounding floor

# dtype -> (mantissa bits incl. implicit, safe exponent window E such
# that inputs 2^±E keep every tested quotient/root comfortably finite)
DTYPES = {
    "bfloat16": (jnp.bfloat16, 8, 55),
    "float16": (jnp.float16, 11, 6),
    "float32": (jnp.float32, 24, 60),
    "float64": (jnp.float64, 53, 60),  # f32 datapath: window stays f32-safe
}


def pair_for(dtype) -> tuple:
    return gs.precision_policy(dtype)


def rel_bound(dtype_name: str, p: int, iters: int) -> float:
    mant = DTYPES[dtype_name][1]
    bits = min(lut.seed_bits(p) * (2 ** iters), F32_ITER_BITS)
    return 1.5 * (2.0 ** -bits + 2.0 ** -(mant - 1))


def abs_floor(dtype) -> float:
    """Absolute tolerance floor: results in the gradual-underflow range
    have no finite relative bound, and FTZ backends (XLA CPU) flush them
    to zero outright — both the gs datapath and the native exact op.  Two
    smallest-normals covers flush-to-zero and subnormal quantization on
    either kind of backend.  The floor never drops below float32's: the
    internal datapath underflows there even for float64 operands."""
    return 2.0 * max(float(jnp.finfo(dtype).tiny),
                     float(jnp.finfo(jnp.float32).tiny))


def _check(name: str, got, ref64: np.ndarray, bound: float, dtype) -> None:
    got64 = np.asarray(got, np.float64)
    finite = np.isfinite(ref64) & (np.abs(ref64) <= float(jnp.finfo(dtype).max))
    # saturated references (dtype overflow) must saturate identically
    over = ~finite & ~np.isnan(ref64)
    if over.any():
        assert np.all(np.isinf(got64[over]) | (np.abs(got64[over]) >=
                                               float(jnp.finfo(dtype).max))), \
            f"{name}: overflow rows did not saturate"
    err = np.abs(got64[finite] - ref64[finite])
    tol = bound * np.abs(ref64[finite]) + abs_floor(dtype)
    bad = err > tol
    assert not bad.any(), (
        f"{name}: {int(bad.sum())} rows past bound {bound:.3g}; worst rel "
        f"{np.max(err / np.maximum(np.abs(ref64[finite]), 1e-300)):.3g}")


def _log_grid(E: int, n: int = 4001) -> np.ndarray:
    mag = np.exp2(np.linspace(-E, E, n))
    return np.concatenate([mag, -mag])


@pytest.mark.parametrize("dtype_name", list(DTYPES))
class TestPolicyPairBounds:
    """The dtype-derived (p, iters) pair meets its bound vs exact f64."""

    def test_reciprocal(self, dtype_name):
        dt, _, E = DTYPES[dtype_name]
        p, iters = pair_for(dt)
        with jax.enable_x64(True):
            x = jnp.asarray(_log_grid(E)).astype(dt)
            x64 = np.asarray(x, np.float64)
            got = gs.gs_reciprocal(x)
        _check(f"recip/{dtype_name}(p={p},i={iters})", got, 1.0 / x64,
               rel_bound(dtype_name, p, iters), dt)

    def test_divide(self, dtype_name):
        dt, _, E = DTYPES[dtype_name]
        p, iters = pair_for(dt)
        with jax.enable_x64(True):
            x = jnp.asarray(_log_grid(E)).astype(dt)
            x64 = np.asarray(x, np.float64)
            n = x[::-1] * x.dtype.type(1.7)  # quotients stay in-window
            n64 = np.asarray(n, np.float64)
            got = gs.gs_divide(n, x)
        _check(f"divide/{dtype_name}(p={p},i={iters})", got, n64 / x64,
               rel_bound(dtype_name, p, iters) * 2, dt)

    def test_rsqrt(self, dtype_name):
        dt, _, E = DTYPES[dtype_name]
        p, iters = pair_for(dt)
        with jax.enable_x64(True):
            x = jnp.abs(jnp.asarray(_log_grid(E)).astype(dt))
            x64 = np.asarray(x, np.float64)
            got = gs.gs_rsqrt(x)
        _check(f"rsqrt/{dtype_name}(p={p},i={iters})", got,
               1.0 / np.sqrt(x64),
               rel_bound(dtype_name, p, iters) * 2, dt)

    def test_sqrt(self, dtype_name):
        dt, _, E = DTYPES[dtype_name]
        p, iters = pair_for(dt)
        with jax.enable_x64(True):
            x = jnp.abs(jnp.asarray(_log_grid(E)).astype(dt))
            x64 = np.asarray(x, np.float64)
            got = gs.gs_sqrt(x)
        _check(f"sqrt/{dtype_name}(p={p},i={iters})", got,
               np.sqrt(x64),
               rel_bound(dtype_name, p, iters) * 2, dt)

    def test_seed_only_pair_is_iters_zero_for_bf16(self, dtype_name):
        """The bf16 budget must resolve to the seed-only datapath — the
        pair the bound tests above then exercise end-to-end."""
        dt, _, _ = DTYPES[dtype_name]
        p, iters = pair_for(dt)
        if dtype_name == "bfloat16":
            assert iters == 0 and p >= 8
        else:
            assert iters >= 1


class TestPinnedPairBounds:
    """Explicit (p, iters) points along the paper's ROM-vs-passes curve,
    asserted at their own derived bounds (f32 operands)."""

    @pytest.mark.parametrize("p,iters", [(5, 2), (7, 1), (7, 2), (9, 1),
                                         (9, 0), (12, 1)])
    def test_reciprocal_pinned(self, p, iters):
        x = jnp.asarray(_log_grid(60), jnp.float32)
        got = gs.gs_reciprocal(x, p=p, iters=iters)
        bits = min(lut.seed_bits(p) * (2 ** iters), F32_ITER_BITS)
        bound = 1.5 * (2.0 ** -bits + 2.0 ** -23)
        _check(f"recip/f32(p={p},i={iters})", got,
               1.0 / np.asarray(x, np.float64), bound, jnp.float32)

    @pytest.mark.parametrize("p,iters", [(5, 2), (7, 2), (9, 1)])
    def test_divide_pinned(self, p, iters):
        r = np.random.RandomState(7)
        n = np.exp2(r.uniform(-60, 60, 8192)).astype(np.float32)
        d = (np.exp2(r.uniform(-60, 60, 8192))
             * np.where(r.rand(8192) < 0.5, -1, 1)).astype(np.float32)
        got = gs.gs_divide(jnp.asarray(n), jnp.asarray(d), p=p, iters=iters)
        bits = min(lut.seed_bits(p) * (2 ** iters), F32_ITER_BITS)
        bound = 3.0 * (2.0 ** -bits + 2.0 ** -23)
        _check(f"divide/f32(p={p},i={iters})", got,
               n.astype(np.float64) / d.astype(np.float64), bound,
               jnp.float32)


@pytest.mark.parametrize("dtype_name", list(DTYPES))
class TestSpecialValues:
    """IEEE edge classes through the full normalize/renormalize path."""

    def _dt(self, dtype_name):
        return DTYPES[dtype_name][0]

    def test_signed_zeros(self, dtype_name):
        dt = self._dt(dtype_name)
        with jax.enable_x64(True):
            z = jnp.asarray([0.0, -0.0], dt)
            r = np.asarray(gs.gs_reciprocal(z), np.float64)
            assert np.isposinf(r[0]) and np.isneginf(r[1])
            q = np.asarray(gs.gs_divide(z, jnp.asarray([3.0, 3.0], dt)),
                           np.float64)
            assert q[0] == 0 and not np.signbit(q[0])
            assert q[1] == 0 and np.signbit(q[1])
            q = np.asarray(gs.gs_divide(jnp.asarray([1.0, -1.0], dt), z),
                           np.float64)
            assert np.isposinf(q[0]) and np.isposinf(q[1])  # -1/-0 = +inf
            rs = np.asarray(gs.gs_rsqrt(z), np.float64)
            assert np.isposinf(rs[0]) and np.isneginf(rs[1])  # IEEE rsqrt(±0)
            sq = np.asarray(gs.gs_sqrt(z), np.float64)
            assert sq[0] == 0 and not np.signbit(sq[0])
            assert sq[1] == 0 and np.signbit(sq[1])  # IEEE sqrt(-0) = -0

    def test_inf_nan(self, dtype_name):
        dt = self._dt(dtype_name)
        with jax.enable_x64(True):
            inf = jnp.asarray([np.inf, -np.inf], dt)
            r = np.asarray(gs.gs_reciprocal(inf), np.float64)
            assert r[0] == 0 and not np.signbit(r[0])
            assert r[1] == 0 and np.signbit(r[1])
            assert np.isnan(np.asarray(gs.gs_reciprocal(
                jnp.asarray([np.nan], dt)), np.float64)).all()
            two = jnp.asarray([2.0, 2.0], dt)
            q = np.asarray(gs.gs_divide(inf, two), np.float64)
            assert np.isposinf(q[0]) and np.isneginf(q[1])
            q = np.asarray(gs.gs_divide(two, inf), np.float64)
            assert q[0] == 0 and q[1] == 0
            # indeterminate forms
            bad = np.asarray(gs.gs_divide(
                jnp.asarray([np.inf, 0.0, np.nan], dt),
                jnp.asarray([np.inf, 0.0, 1.0], dt)), np.float64)
            assert np.isnan(bad).all()
            assert np.isnan(np.asarray(gs.gs_rsqrt(
                jnp.asarray([-1.0, np.nan], dt)), np.float64)).all()
            assert np.isposinf(np.asarray(gs.gs_sqrt(
                jnp.asarray([np.inf], dt)), np.float64)).all()

    def test_subnormal_inputs(self, dtype_name):
        """Subnormal operands: differential vs the backend's native exact
        ops.  On an IEEE backend the pre-scale peel keeps them in-bound;
        on a DAZ backend (XLA CPU treats denormal inputs as zero in every
        arithmetic op) both sides degrade identically — the differential
        holds either way, which is the point of testing vs the *platform*
        exact op rather than an idealized f64 model."""
        if dtype_name == "float64":
            pytest.skip("f32 datapath: f64 subnormals saturate the cast")
        dt = self._dt(dtype_name)
        fi = jnp.finfo(dt)
        sub0 = float(fi.tiny) * 2.0 ** -(fi.nmant)  # smallest subnormal
        with jax.enable_x64(True):
            x = jnp.asarray(np.asarray(
                [float(fi.tiny) / 2, float(fi.tiny) / 4, sub0 * 3], np.float64
            ), dt)
            p, iters = pair_for(dt)
            bound = rel_bound(dtype_name, p, iters)
            for name, gs_op, exact_op in (
                    ("recip", gs.gs_reciprocal, lambda v: 1.0 / v),
                    ("rsqrt", gs.gs_rsqrt, jax.lax.rsqrt),
                    ("sqrt", gs.gs_sqrt, jnp.sqrt)):
                got = np.asarray(gs_op(x), np.float64)
                ref = np.asarray(exact_op(x), np.float64)
                inf = np.isinf(ref)
                assert np.array_equal(np.isinf(got), inf), (name, got, ref)
                err = np.abs(got[~inf] - ref[~inf])
                assert np.all(err <= 2 * bound * np.abs(ref[~inf])
                              + abs_floor(dt)), (name, got, ref)

    def test_exact_powers_of_two(self, dtype_name):
        """For the fp32 pair the iteration converges past every mantissa
        bit, so 1/2^k and rsqrt(4^k) round to the exact power of two."""
        dt = self._dt(dtype_name)
        with jax.enable_x64(True):
            k = jnp.asarray([2.0 ** e for e in range(-40, 41)], dt)
            got = gs.gs_reciprocal(k)
            ref = (1.0 / np.asarray(k, np.float64)).astype(jnp.float64)
            if dt == jnp.float32:
                assert np.array_equal(np.asarray(got, np.float64), ref)
            else:
                p, iters = pair_for(dt)
                _check(f"pow2/{dtype_name}", got, ref,
                       rel_bound(dtype_name, p, iters), dt)

    def test_near_overflow(self, dtype_name):
        """Denominators at/near dtype max: reciprocals land in the
        gradual-underflow range, where the absolute floor governs (an FTZ
        backend flushes both gs and the native divide to zero; an IEEE
        one keeps subnormals — tolerated either way)."""
        dt = self._dt(dtype_name)
        fi = jnp.finfo(dt)
        # the f32 internal datapath caps the representable magnitude for
        # f64 operands — values beyond it saturate by contract
        mx = min(float(fi.max), float(jnp.finfo(jnp.float32).max))
        with jax.enable_x64(True):
            x = jnp.asarray([mx, mx * 0.5, -mx], dt)
            x64 = np.asarray(x, np.float64)
            got = np.asarray(gs.gs_reciprocal(x), np.float64)
            ref = 1.0 / x64
            p, iters = pair_for(dt)
            err = np.abs(got - ref)
            assert np.all(err <= 2 * rel_bound(dtype_name, p, iters)
                          * np.abs(ref) + abs_floor(dt)), (got, ref)
            # and the rsqrt stays fully normal there: tight bound applies
            gr = np.asarray(gs.gs_rsqrt(jnp.abs(x)), np.float64)
            rr = 1.0 / np.sqrt(np.abs(x64))
            assert np.all(np.abs(gr - rr)
                          <= 2 * rel_bound(dtype_name, p, iters) * rr)


class TestRandomizedProperties:
    """hypothesis-driven randomized differentials (skip without it)."""

    @settings(max_examples=200, deadline=None)
    @given(st.floats(min_value=2.0 ** -60, max_value=2.0 ** 60,
                     allow_nan=False, allow_infinity=False))
    def test_recip_f32_bound(self, x):
        for v in (x, -x):
            got = float(gs.gs_reciprocal(jnp.float32(v)))
            ref = 1.0 / float(np.float32(v))
            assert abs(got - ref) <= rel_bound("float32", 7, 2) * abs(ref)

    @settings(max_examples=200, deadline=None)
    @given(st.floats(min_value=2.0 ** -40, max_value=2.0 ** 40,
                     allow_nan=False, allow_infinity=False),
           st.floats(min_value=2.0 ** -40, max_value=2.0 ** 40,
                     allow_nan=False, allow_infinity=False))
    def test_divide_f32_bound(self, n, d):
        got = float(gs.gs_divide(jnp.float32(n), jnp.float32(-d)))
        ref = float(np.float32(n)) / float(np.float32(-d))
        assert abs(got - ref) <= 2 * rel_bound("float32", 7, 2) * abs(ref)

    @settings(max_examples=200, deadline=None)
    @given(st.floats(min_value=2.0 ** -60, max_value=2.0 ** 60,
                     allow_nan=False, allow_infinity=False))
    def test_rsqrt_f32_bound(self, x):
        got = float(gs.gs_rsqrt(jnp.float32(x)))
        ref = 1.0 / np.sqrt(float(np.float32(x)))
        assert abs(got - ref) <= 2 * rel_bound("float32", 7, 2) * abs(ref)
