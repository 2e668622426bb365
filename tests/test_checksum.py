"""Unit + property tests for the dual-checksum ABFT core (paper §IV)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:       # deterministic fallback (see _hypothesis_stub)
    from _hypothesis_stub import given, settings, st

from repro.core import checksum
from repro.core.ft_gemm import ft_matmul
from repro.core.fault import FaultConfig, flip_bit, inject


def _rand(shape, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


class TestChecksumInvariant:
    @pytest.mark.parametrize("m,k,n", [(8, 16, 8), (32, 64, 16), (128, 256, 64)])
    def test_expected_matches_observed_clean(self, m, k, n):
        x, y = _rand((m, k), 1), _rand((k, n), 2)
        d = x @ y
        exp = checksum.expected_checksums(x, y)
        obs = checksum.observed_checksums(d)
        np.testing.assert_allclose(exp.col1, obs.col1, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(exp.col2, obs.col2, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(exp.row1, obs.row1, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(exp.row2, obs.row2, rtol=1e-4, atol=1e-3)

    def test_clean_product_not_flagged(self):
        x, y = _rand((64, 128), 3), _rand((128, 32), 4)
        d = x @ y
        exp = checksum.expected_checksums(x, y)
        thr = checksum.default_threshold(128) * float(jnp.max(jnp.abs(d)))
        v = checksum.verify(d, exp, thr)
        assert not bool(v.detected)


class TestLocateAndCorrect:
    @pytest.mark.parametrize("i,j", [(0, 0), (17, 3), (63, 31), (5, 0)])
    def test_single_error_located_exactly(self, i, j):
        x, y = _rand((64, 128), 5), _rand((128, 32), 6)
        d = x @ y
        exp = checksum.expected_checksums(x, y)
        bad = d.at[i, j].add(37.5)
        thr = checksum.default_threshold(128) * float(jnp.max(jnp.abs(d)))
        v = checksum.verify(bad, exp, thr)
        assert bool(v.detected)
        assert int(v.row) == i and int(v.col) == j
        fixed = checksum.correct(bad, v)
        np.testing.assert_allclose(fixed, d, rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("i,j,value", [
        (17, 3, float("nan")), (0, 31, float("inf")),
        (63, 0, -float("inf")), (5, 9, 3.0e38)])
    def test_non_finite_or_overflowing_error_corrected(self, i, j, value):
        """An exponent flip can make an element Inf, NaN, or so large that
        its weighted checksum overflows: it is still detected, located by
        the residual argmaxes and rebuilt (or its delta subtracted)."""
        x, y = _rand((64, 128), 5), _rand((128, 32), 6)
        d = x @ y
        exp = checksum.expected_checksums(x, y)
        bad = d.at[i, j].set(value)
        thr = checksum.default_threshold(128) * float(jnp.max(jnp.abs(d)))
        v = checksum.verify(bad, exp, thr)
        assert bool(v.detected)
        assert int(v.row) == i and int(v.col) == j
        fixed = checksum.correct(bad, v)
        assert bool(jnp.all(jnp.isfinite(fixed)))
        atol = 1e-2 + (abs(value) * 2e-5 if np.isfinite(value) else 0.0)
        np.testing.assert_allclose(fixed, d, rtol=1e-3, atol=atol)

    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 31), st.integers(0, 15),
           st.sampled_from([1e3, -1e3, 1e6, -1e-1 * 1e4]))
    def test_property_any_position_any_magnitude(self, i, j, delta):
        x, y = _rand((32, 64), 7), _rand((64, 16), 8)
        d = x @ y
        exp = checksum.expected_checksums(x, y)
        bad = d.at[i, j].add(delta)
        thr = checksum.default_threshold(64) * float(jnp.max(jnp.abs(d)))
        v = checksum.verify(bad, exp, thr)
        assert bool(v.detected)
        fixed = checksum.correct(bad, v)
        # correction recovers delta from f32 checksum sums: the residue is
        # O(eps * |delta| * sqrt(m)) — inherent to fp ABFT (paper §IV).
        atol = 1e-2 + abs(delta) * 2e-5
        np.testing.assert_allclose(fixed, d, rtol=1e-3, atol=atol)


class TestFtMatmul:
    def test_clean(self):
        x, y = _rand((64, 128), 9), _rand((128, 48), 10)
        d, detected = ft_matmul(x, y)
        assert not bool(detected)
        np.testing.assert_allclose(d, x @ y, rtol=1e-5, atol=1e-5)

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(20, 30))
    def test_property_bitflip_corrected(self, seed, bit):
        """SEU model: one bit flip in the product is detected + corrected.

        Correction recovers the delta from f32 checksum sums, so the
        residue after correcting a 2^34-magnitude exponent flip is bounded
        by the delta's ulp (the paper's FP32 scheme shares this): assert
        the corruption is reduced by >= 1e4x, not to zero.
        """
        x, y = _rand((32, 64), 11), _rand((64, 16), 12)
        clean = jnp.matmul(x, y)
        fault = FaultConfig(rate=1.0, bit_low=bit, bit_high=bit, seed=seed)
        key = jax.random.PRNGKey(seed)
        d, detected = ft_matmul(x, y, inject_key=key, fault=fault)
        from repro.core.fault import inject
        before = float(jnp.max(jnp.abs(inject(key, clean, fault) - clean)))
        after = float(jnp.max(jnp.abs(d - clean)))
        assert after <= max(1e-2, before * 1e-4), (before, after)


class TestFaultInjection:
    def test_flip_bit_roundtrip(self):
        x = _rand((4, 4), 13)
        flipped = flip_bit(x, 5, 22)
        assert not np.allclose(flipped, x)
        again = flip_bit(flipped, 5, 22)
        np.testing.assert_array_equal(again, x)

    def test_inject_rate_zero_is_identity(self):
        x = _rand((16,), 14)
        out = inject(jax.random.PRNGKey(0), x, FaultConfig(rate=0.0))
        np.testing.assert_array_equal(out, x)
