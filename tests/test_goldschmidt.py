"""Paper-claim tests for the float Goldschmidt datapaths (core/goldschmidt)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # property tests skip cleanly without hypothesis
    from conftest import fake_given as given
    from conftest import fake_settings as settings
    from conftest import fake_strategies as st

from repro.core import goldschmidt as gs
from repro.core import lut

F32 = np.float32


def _rand(n, lo=1e-3, hi=1e3, seed=0, signed=True):
    r = np.random.RandomState(seed)
    mag = np.exp(r.uniform(np.log(lo), np.log(hi), n)).astype(F32)
    if signed:
        mag *= np.where(r.rand(n) < 0.5, -1, 1).astype(F32)
    return mag


class TestQuadraticConvergence:
    """Seed gives ~(p+1) bits; every step-2 pass doubles them (paper §I)."""

    @pytest.mark.parametrize("p", [5, 7, 9])
    def test_error_squares_per_iteration(self, p):
        m = jnp.asarray(np.linspace(1.0, 2.0, 4097, dtype=F32)[:-1])
        prev_err = None
        for iters in (0, 1, 2):
            if iters == 0:
                k = lut.lookup_reciprocal(m, p)
                err = float(jnp.max(jnp.abs(m * k - 1.0)))
            else:
                q = gs.gs_reciprocal_normalized(m, p=p, iters=iters)
                err = float(jnp.max(jnp.abs(m * q - 1.0)))
            if prev_err is not None and prev_err > 2 ** -20:
                # quadratic: err <= prev^2 (+ float rounding floor)
                assert err <= prev_err ** 2 * 4 + 2 ** -22, (iters, err, prev_err)
            prev_err = err

    def test_two_passes_reach_fp32(self):
        """Paper: 2 step-2 passes (q4) from a p=7 seed give >= 24 bits."""
        d = jnp.asarray(_rand(20000, seed=1))
        q = gs.gs_reciprocal(d, p=7, iters=2)
        rel = np.abs(np.asarray(q) * np.asarray(d) - 1.0)
        assert rel.max() < 2 ** -21  # ~fp32 eps x few ulp of iteration math

    def test_iters_for_counter(self):
        assert gs.iters_for(7, 24) == 2  # 8 -> 16 -> 32 bits
        assert gs.iters_for(7, 8) == 0  # seed suffices: no floor, no pass
        assert gs.iters_for(7, 9) == 1
        assert gs.iters_for(7, 53) == 3  # 8 -> 16 -> 32 -> 64
        assert gs.iters_for(3, 24) == 3  # 4 -> 8 -> 16 -> 32


class TestPrecisionPolicy:
    """The (p, iters) co-design: ROM width vs multiplier passes per dtype."""

    def test_dtype_pairs(self):
        import jax.numpy as jnp

        assert gs.precision_policy(jnp.float32) == (7, 2)  # paper's point
        assert gs.precision_policy(jnp.float64) == (7, 3)
        assert gs.precision_policy(jnp.float16) == (7, 1)
        p, iters = gs.precision_policy(jnp.bfloat16)
        assert iters == 0 and p >= 8  # seed-only with one table step up

    def test_pinned_p_derives_counter(self):
        assert gs.precision_policy(target_bits=24, p=12) == (12, 1)
        assert gs.precision_policy(target_bits=8, p=7) == (7, 1)  # 7 meas. bits
        assert gs.precision_policy(target_bits=8, p=8) == (8, 0)

    def test_backed_by_measured_seed_bits(self):
        # The policy may never promise bits the burned ROM does not hold.
        for p in range(5, 13):
            bits = lut.seed_bits(p)
            err = max(lut.seed_rel_error_bound(p),
                      lut.seed_rel_error_bound_rsqrt(p))
            assert err <= 2.0 ** -bits
            _, iters = gs.precision_policy(target_bits=24, p=p)
            assert bits * 2 ** iters >= 24

    def test_resolve_precision_pinning(self):
        import jax.numpy as jnp

        # pinned iters keeps the default table; pinned p derives its count
        assert gs.resolve_precision(jnp.bfloat16, None, 2, None) == (7, 2)
        assert gs.resolve_precision(jnp.float32, 9, None, None) == (9, 2)
        assert gs.resolve_precision(jnp.float32, 12, 1, None) == (12, 1)
        # explicit target_bits overrides the dtype's budget
        assert gs.resolve_precision(jnp.float32, None, None, 8) == (8, 0)

    def test_seed_only_meets_bf16_budget(self):
        x = jnp.asarray(_rand(20000, seed=11, signed=False))
        q = gs.gs_reciprocal(x, p=8, iters=0)
        rel = np.abs(np.asarray(q) * np.asarray(x) - 1.0)
        assert rel.max() < 2.0 ** -8  # bf16 ulp

    def test_zero_iters_is_seed_only(self):
        m = jnp.asarray(np.linspace(1.0, 2.0, 4097, dtype=F32)[:-1])
        for variant in ("feedback", "pipelined"):
            q = gs.gs_reciprocal_normalized(m, p=8, iters=0, variant=variant)
            np.testing.assert_array_equal(
                np.asarray(q), np.asarray(lut.lookup_reciprocal(m, 8)))


class TestBitPeelParity:
    """The integer bit-peel normalize/renormalize is exactly the frexp/
    ldexp datapath it replaced: bit-identical on finite normals (in and
    out), specials unchanged."""

    @staticmethod
    def _frexp_reciprocal(d, p, iters, variant="feedback"):
        d32 = d.astype(jnp.float32)
        sign = jnp.where(jnp.signbit(d32), -1.0, 1.0).astype(jnp.float32)
        mag = jnp.abs(d32)
        m, e = jnp.frexp(mag)
        m, e = m * 2.0, e - 1
        q = gs.gs_reciprocal_normalized(m, p=p, iters=iters, variant=variant)
        out = sign * jnp.ldexp(q, -e)
        out = jnp.where(mag == 0.0, sign * jnp.inf, out)
        out = jnp.where(jnp.isinf(mag), sign * 0.0, out)
        return jnp.where(jnp.isnan(d32), jnp.nan, out)

    @staticmethod
    def _normals(n, seed):
        r = np.random.RandomState(seed)
        x = np.exp(r.uniform(np.log(2.0 ** -126), np.log(2.0 ** 127), n))
        x = x.astype(F32)
        x = x[np.abs(x) >= np.float32(2.0 ** -126)]  # finite normals only
        return x * np.where(r.rand(x.size) < 0.5, -1, 1).astype(F32)

    @pytest.mark.parametrize("p,iters", [(7, 2), (8, 0), (8, 1), (12, 1)])
    def test_reciprocal_bit_identical_on_normals(self, p, iters):
        x = jnp.asarray(self._normals(100000, seed=20))
        got = np.asarray(gs.gs_reciprocal(x, p=p, iters=iters))
        want = np.asarray(jax.jit(
            lambda d: self._frexp_reciprocal(d, p, iters))(x))
        np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))

    def test_rsqrt_sqrt_bit_identical_on_normals(self):
        x = jnp.asarray(np.abs(self._normals(100000, seed=21)))

        def frexp_rsqrt(z, mode):
            m, e = jnp.frexp(z)
            m, e = m * 2.0, e - 1
            odd = (e % 2) != 0
            m = jnp.where(odd, m * 2.0, m)
            e = jnp.where(odd, e - 1, e)
            if mode == "rsqrt":
                k = gs.gs_rsqrt_normalized(m, p=7, iters=2)
                return jnp.ldexp(k, -(e // 2))
            y0 = lut.lookup_rsqrt(m, 7)
            g, h = m * y0, 0.5 * y0
            for _ in range(2):
                r_ = 0.5 - g * h
                g, h = g + g * r_, h + h * r_
            return jnp.ldexp(g, e // 2)

        got = np.asarray(gs.gs_rsqrt(x, p=7, iters=2))
        want = np.asarray(jax.jit(lambda z: frexp_rsqrt(z, "rsqrt"))(x))
        np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))
        got = np.asarray(gs.gs_sqrt(x, p=7, iters=2, variant="pipelined"))
        want = np.asarray(jax.jit(lambda z: frexp_rsqrt(z, "sqrt"))(x))
        np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))

    def test_specials_unchanged(self):
        x = jnp.asarray(np.array([0.0, -0.0, np.inf, -np.inf, np.nan], F32))
        for p, iters in ((7, 2), (8, 0)):
            out = np.asarray(gs.gs_reciprocal(x, p=p, iters=iters))
            assert np.isposinf(out[0]) and np.isneginf(out[1])
            assert out[2] == 0.0 and out[3] == 0.0 and np.isnan(out[4])


class TestVariantsAgree:
    """Feedback (fori_loop) vs pipelined (unrolled): same arithmetic.

    Float results may differ by compiler FMA contraction only (<= 2 ulp,
    measured); the bit-exact hardware claim is tested in test_fixed_point.
    """

    @pytest.mark.parametrize("fn", [gs.gs_reciprocal, gs.gs_rsqrt, gs.gs_sqrt])
    def test_within_two_ulp(self, fn):
        x = jnp.asarray(np.abs(_rand(8192, seed=2)))
        a = np.asarray(fn(x, variant="pipelined"))
        b = np.asarray(fn(x, variant="feedback"))
        ulp = np.abs(a.view(np.int32) - b.view(np.int32))
        assert ulp.max() <= 2

    def test_divide_matches(self):
        n = jnp.asarray(_rand(4096, seed=3))
        d = jnp.asarray(_rand(4096, seed=4))
        a = np.asarray(gs.gs_divide(n, d, variant="pipelined"))
        b = np.asarray(gs.gs_divide(n, d, variant="feedback"))
        ulp = np.abs(a.view(np.int32) - b.view(np.int32))
        assert ulp.max() <= 2


class TestSpecials:
    def test_reciprocal_specials(self):
        x = jnp.asarray(np.array([0.0, -0.0, np.inf, -np.inf, np.nan], F32))
        out = np.asarray(gs.gs_reciprocal(x))
        assert np.isposinf(out[0]) and np.isneginf(out[1])
        assert out[2] == 0.0 and out[3] == 0.0
        assert np.isnan(out[4])

    def test_divide_specials(self):
        n = jnp.asarray(np.array([1.0, 0.0, np.inf, 0.0, -3.0], F32))
        d = jnp.asarray(np.array([0.0, 0.0, np.inf, 5.0, np.inf], F32))
        out = np.asarray(gs.gs_divide(n, d))
        assert np.isposinf(out[0])
        assert np.isnan(out[1]) and np.isnan(out[2])
        assert out[3] == 0.0 and out[4] == 0.0

    def test_rsqrt_domain(self):
        x = jnp.asarray(np.array([0.0, np.inf, -1.0, np.nan], F32))
        out = np.asarray(gs.gs_rsqrt(x))
        assert np.isposinf(out[0]) and out[1] == 0.0
        assert np.isnan(out[2]) and np.isnan(out[3])


class TestHypothesisProperties:
    @settings(max_examples=200, deadline=None)
    @given(st.floats(min_value=2.0 ** -100, max_value=2.0 ** 100,
                     allow_nan=False, width=32))
    def test_recip_relative_error(self, x):
        xv = jnp.asarray(np.float32(x))
        q = float(gs.gs_reciprocal(xv))
        assert abs(q * x - 1.0) < 2 ** -20

    @settings(max_examples=200, deadline=None)
    @given(st.floats(min_value=2.0 ** -100, max_value=2.0 ** 100,
                     allow_nan=False, width=32))
    def test_rsqrt_relative_error(self, x):
        xv = jnp.asarray(np.float32(x))
        q = float(gs.gs_rsqrt(xv))
        assert abs(q * np.sqrt(np.float64(x)) - 1.0) < 2 ** -20

    @settings(max_examples=100, deadline=None)
    @given(st.floats(min_value=-(2.0 ** 64), max_value=2.0 ** 64,
                     allow_nan=False, width=32),
           st.floats(min_value=2.0 ** -64, max_value=2.0 ** 64,
                     allow_nan=False, width=32))
    def test_divide_matches_native(self, n, d):
        from hypothesis import assume

        ref = np.float64(n) / np.float64(d)
        # documented domain: normal operands and normal-range results
        # (subnormals flush to zero, as on TPU hardware)
        assume(n == 0 or abs(n) >= 2.0 ** -126)
        assume(ref == 0 or 2.0 ** -125 < abs(ref) < 2.0 ** 127)
        q = float(gs.gs_divide(jnp.float32(n), jnp.float32(d)))
        if ref == 0:
            assert abs(q) < 1e-30
        else:
            assert abs(q / ref - 1.0) < 2 ** -18


class TestVariantAB:
    """[4]'s Variants A/B consume q_i and the residual; the paper (§IV)
    claims the feedback datapath leaves them unaffected.  Variant A uses
    the final r to round-correct q; Variant B pipelines the error term.
    Both reduce to: correction computed from (q, r) must be identical
    between datapaths — which holds exactly in fixed point and to float
    fusion noise here."""

    def test_variant_a_round_correction(self):
        m = jnp.asarray(np.linspace(1.0, 2.0, 1025, dtype=F32)[:-1])
        for variant in ("pipelined", "feedback"):
            q = gs.gs_reciprocal_normalized(m, p=7, iters=2, variant=variant)
            # Variant A correction: q' = q * (2 - m*q), one more NR step
            q2 = q * (2.0 - m * q)
            err = float(jnp.max(jnp.abs(m * q2 - 1.0)))
            assert err < 2 ** -22
