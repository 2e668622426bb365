"""Property-based tests for the int8 error-feedback transport
(``repro.dist.compression``) — the numerics contract the compressed
cross-host hop rests on.

Runs under real ``hypothesis`` when installed (CI), else the deterministic
parametrize stub in ``tests/_hypothesis_stub.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # offline escape hatch
    from _hypothesis_stub import given, settings, st

from repro.dist.compression import (BLOCK, compressed_psum, dequantize,
                                    quantize, quantize_rows)


def _values(seed: int, rows: int, n: int, scale: float) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((rows, n)) * scale).astype(np.float32)


class TestQuantizeRoundTrip:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 4),
           st.integers(1, 400), st.sampled_from([64, 128]))
    def test_roundtrip_error_bounded_by_half_step(self, seed, rows, n,
                                                  block):
        """|x - deq(q(x))| <= scale/2 elementwise: symmetric rounding to
        the block's 127-level grid never misses by more than half a step
        (the clip at +-127 is exact at the block max by construction)."""
        x = _values(seed, rows, n, scale=10.0)
        q, scale = quantize(jnp.asarray(x), block)
        deq = np.asarray(dequantize(q, scale, n))
        # broadcast each block's scale back over its elements
        step = np.broadcast_to(np.asarray(scale),
                               scale.shape[:-1] + (block,))
        step = step.reshape(scale.shape[:-2] + (-1,))[..., :n]
        assert np.all(np.abs(x - deq) <= 0.5 * step + 1e-6 * np.abs(x))

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 4),
           st.integers(1, 400), st.sampled_from([64, 128]))
    def test_shape_dtype_invariants(self, seed, rows, n, block):
        """q is int8 over ceil(n/block) blocks, one f32 scale per block,
        and dequantize restores exactly the input shape — ragged tails
        (n % block != 0) round-trip through the zero padding."""
        x = _values(seed, rows, n, scale=1.0)
        q, scale = quantize(jnp.asarray(x), block)
        blocks = -(-n // block)
        assert q.dtype == jnp.int8 and q.shape == (rows, blocks, block)
        assert scale.dtype == jnp.float32
        assert scale.shape == (rows, blocks, 1)
        deq = dequantize(q, scale, n)
        assert deq.dtype == jnp.float32 and deq.shape == (rows, n)

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 8),
           st.integers(2, 256))
    def test_rowwise_integer_identity(self, seed, rows, n):
        """Integer rows that pin a +-127 entry quantize losslessly (scale
        is exactly 1.0) — the int8 kernel template's bit-exactness
        contract."""
        rng = np.random.default_rng(seed)
        x = rng.integers(-126, 127, size=(rows, n)).astype(np.float32)
        x[:, 0] = 127.0        # pin the row max so scale == 1.0 exactly
        q, scale = quantize_rows(jnp.asarray(x))
        assert np.all(np.asarray(scale) == 1.0)
        assert np.array_equal(np.asarray(q, dtype=np.float32)
                              * np.asarray(scale), x)


class TestErrorFeedback:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.floats(0.1, 100.0))
    def test_residual_telescopes(self, seed, magnitude):
        """EF-SGD identity: with a fixed value g, iterating
        ``carried = g + res; res = carried - deq(q(carried))`` telescopes —
        ``sum_t deq_t = T*g - res_T`` — so the time-averaged transported
        value converges to g at rate O(1/T) instead of a constant bias."""
        g = jnp.asarray(_values(seed, 1, 300, magnitude)[0])
        res = jnp.zeros_like(g)
        total = jnp.zeros_like(g)
        errs = {}
        for t in range(1, 33):
            carried = g + res
            q, scale = quantize(carried)
            deq = dequantize(q, scale, g.shape[-1])
            res = carried - deq
            total = total + deq
            if t in (1, 32):
                errs[t] = float(jnp.max(jnp.abs(total / t - g)))
        # exact telescoping: the accumulated transport differs from T*g
        # by exactly the final residual (up to f32 rounding)
        gap = jnp.max(jnp.abs(total - 32.0 * g + res))
        assert float(gap) <= 1e-3 * 32 * magnitude + 1e-5
        # and the residual is bounded (one quantization step), so the
        # time-average tightens ~linearly in T
        assert errs[32] <= errs[1] / 8 + 1e-7


class TestCompressedPsum:
    def test_shape_dtype_and_residual_bound(self):
        """compressed_psum keeps the operand's shape/dtype and returns a
        residual bounded by half a quantization step. A size-1 axis makes
        the reduce an identity transport: red == deq(q(g))."""
        mesh = jax.make_mesh((1,), ("data",))
        from jax.sharding import PartitionSpec as P
        g = jnp.asarray(_values(7, 1, 200, 5.0))

        def f(gl):
            red, res = compressed_psum(gl[0], "data")
            return red[None], res[None]

        red, res = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("data", None),
            out_specs=(P("data", None), P("data", None)),
            check_vma=False))(g)
        assert red.shape == g.shape and red.dtype == jnp.float32
        assert res.shape == g.shape and res.dtype == jnp.float32
        q, scale = quantize(g[0])
        assert np.allclose(np.asarray(red[0]),
                           np.asarray(dequantize(q, scale, 200)))
        step = float(jnp.max(scale))
        assert float(jnp.max(jnp.abs(res))) <= 0.5 * step + 1e-7
        # residual is exactly the transport error
        assert np.allclose(np.asarray(g[0] - red[0]), np.asarray(res[0]),
                           atol=1e-6)
