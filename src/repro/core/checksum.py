"""Dual-checksum (e1/e2) ABFT encodings with location decoding.

Implements the paper's §IV scheme in pure JAX:

  e1 = [1, 1, ..., 1]      detects an error (non-zero residual)
  e2 = [1, 2, ..., n]      locates it: index = round(r2 / r1)

For a matmul D = X @ Y (X: (m, k), Y: (k, n)):

  column checksums:  C1 = e1(m)^T D = (e1^T X) Y       shape (n,)
                     C2 = e2(m)^T D = (e2^T X) Y       shape (n,)
  row checksums:     R1 = D e1(n)   = X (Y e1)         shape (m,)
                     R2 = D e2(n)   = X (Y e2)         shape (m,)

A single corrupted element D[i, j] += delta produces residuals
  r1_col[j] = delta, r2_col[j] = (i+1) * delta   -> i = r2/r1 - 1
  r1_row[i] = delta, r2_row[i] = (j+1) * delta   -> j = r2/r1 - 1
so the element is corrected in place:  D[i, j] -= delta.

All functions are jit-safe (fixed shapes, lax control flow).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def e1(n: int, dtype=jnp.float32) -> jax.Array:
    """The detection vector [1, 1, ..., 1]."""
    return jnp.ones((n,), dtype=dtype)


def e2(n: int, dtype=jnp.float32) -> jax.Array:
    """The location-encoding vector [1, 2, ..., n]."""
    return jnp.arange(1, n + 1, dtype=dtype)


def encode_cols(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Column checksums of x: (e1^T x, e2^T x), each of shape (x.shape[1],)."""
    w = e2(x.shape[0], x.dtype)
    return jnp.sum(x, axis=0), w @ x


def encode_rows(y: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Row checksums of y: (y e1, y e2), each of shape (y.shape[0],)."""
    w = e2(y.shape[1], y.dtype)
    return jnp.sum(y, axis=1), y @ w


def rounding_eps(input_dtype=jnp.float32, acc_dtype=jnp.float32) -> float:
    """Worst-case unit roundoff of a checksummed accumulation whose *inputs*
    are ``input_dtype`` and whose accumulator is ``acc_dtype``.

    The FT kernels compute their checksums on f32 casts of the resident
    tiles, but the main accumulator they are compared against is built from
    products of the *input* dtype — on backends that round those products
    to input precision (rather than multiplying exactly into f32 the way
    the MXU does for bf16), its rounding floor is the input dtype's eps,
    not f32's. A threshold derived from f32 eps alone then flags clean
    bf16/fp16 tiles as corrupted. Taking ``max(eps_in, eps_acc)`` keeps
    the false-positive rate at the design level for every input dtype;
    injected bit-flips in the campaign range (2^4..2^23) still clear the
    bf16-scaled threshold by orders of magnitude.
    """
    eps_in = float(jnp.finfo(jnp.dtype(input_dtype)).eps) \
        if jnp.issubdtype(jnp.dtype(input_dtype), jnp.floating) else 0.0
    return max(eps_in, float(jnp.finfo(jnp.dtype(acc_dtype)).eps))


def threshold_factor(k: int, input_dtype=jnp.float32,
                     acc_dtype=jnp.float32) -> float:
    """Static (Python-float) part of the detection threshold for a length-k
    contraction: ``16 * sqrt(k) * rounding_eps``. Kernels multiply this by
    their runtime magnitude scale (max |accumulator|); ``16`` keeps the
    false-positive rate negligible (paper §II-A) while exponent and
    high-mantissa bit flips exceed it by many orders of magnitude."""
    return 16.0 * (max(k, 1) ** 0.5) * rounding_eps(input_dtype, acc_dtype)


def default_threshold(k: int, dtype=jnp.float32, scale: float = 1.0,
                      input_dtype=None) -> float:
    """Detection threshold delta for a length-k contraction.

    Rounding error of a k-term dot product is ~ sqrt(k) * eps * |x||y| in
    rms; the checksum residual compounds two such sums, so we take
    ``16 * sqrt(k) * eps * scale`` (scale ~ typical |D| magnitude).
    ``dtype`` is the accumulator dtype; pass ``input_dtype`` when the
    operands are lower precision than the accumulator (bf16/fp16 tiles
    with f32 accumulation) so the threshold tracks the larger rounding
    floor — see :func:`rounding_eps`.
    """
    return threshold_factor(
        k, input_dtype if input_dtype is not None else dtype, dtype) * scale


class ChecksumState(NamedTuple):
    """Checksums carried alongside a product D = X @ Y."""

    col1: jax.Array  # e1^T D, shape (n,)
    col2: jax.Array  # e2^T D, shape (n,)
    row1: jax.Array  # D e1,   shape (m,)
    row2: jax.Array  # D e2,   shape (m,)


def expected_checksums(x: jax.Array, y: jax.Array) -> ChecksumState:
    """Checksums computed from the *inputs* (the ABFT invariant side).

    Cost: O((m + n) * k) — the paper's "CUDA-core" encodings e1^T X, Y e1
    plus the e2 variants, followed by O((m + n) * n) / O((m + n) * m)
    one-row GEMMs (the paper's three extra tensor-core MMAs).
    """
    c1x, c2x = encode_cols(x)   # (k,), (k,)
    r1y, r2y = encode_rows(y)   # (k,), (k,)
    return ChecksumState(
        col1=c1x @ y,
        col2=c2x @ y,
        row1=x @ r1y,
        row2=x @ r2y,
    )


def observed_checksums(d: jax.Array) -> ChecksumState:
    """Checksums computed from the (possibly corrupted) output D."""
    c1, c2 = encode_cols(d)
    r1, r2 = encode_rows(d)
    return ChecksumState(col1=c1, col2=c2, row1=r1, row2=r2)


class Verdict(NamedTuple):
    detected: jax.Array   # bool scalar
    row: jax.Array        # int32 scalar (0 if not detected)
    col: jax.Array        # int32 scalar
    delta: jax.Array      # the error magnitude to subtract
    value: jax.Array      # the element rebuilt from its row checksum, for
    #                       a corruption to Inf or NaN that no delta undoes


def verify(d: jax.Array, expected: ChecksumState, threshold) -> Verdict:
    """Compare output-derived checksums against input-derived ones.

    Returns the detection verdict with the located (row, col) and delta.
    Under the SEU model (≤1 error per interval) location decoding is exact.
    """
    obs = observed_checksums(d)
    res_col1 = obs.col1 - expected.col1          # (n,)
    res_row1 = obs.row1 - expected.row1          # (m,)
    res_col2 = obs.col2 - expected.col2
    res_row2 = obs.row2 - expected.row2

    # Detection: any column / row residual above threshold, or not finite
    # (an exponent flip can turn an element into Inf or NaN).
    col_bad = jnp.logical_not(jnp.abs(res_col1) <= threshold)
    row_bad = jnp.logical_not(jnp.abs(res_row1) <= threshold)
    detected = jnp.logical_or(jnp.any(col_bad), jnp.any(row_bad))

    # Location. Primary: the arg-max residual column gives j and delta;
    # the e2/e1 ratio of the *column* residuals gives the row index
    # (paper's location encoding). Cross-check with the row residuals.
    j = jnp.argmax(jnp.abs(res_col1)).astype(jnp.int32)
    delta_col = res_col1[j]
    ratio_i = res_col2[j] / jnp.where(delta_col == 0, 1.0, delta_col)
    # Fall back to the row-residual argmax when column residual is degenerate
    # (e.g. error in a row whose column hit threshold issues), or when the
    # ratio is not finite: an element corrupted to Inf or NaN, or one so
    # large that its weighted checksum overflows. The argmaxes then point
    # at the element (argmax takes a NaN as the largest).
    i_direct = jnp.argmax(jnp.abs(res_row1)).astype(jnp.int32)
    use_ratio = jnp.logical_and(jnp.abs(delta_col) > threshold,
                                jnp.isfinite(ratio_i))
    i = jnp.where(use_ratio, (jnp.round(ratio_i) - 1).astype(jnp.int32),
                  i_direct)
    i = jnp.clip(i, 0, d.shape[0] - 1)
    delta_row = res_row1[i]
    delta = jnp.where(jnp.abs(delta_col) > jnp.abs(delta_row), delta_col, delta_row)
    # If the column residual was degenerate, recover j from the row ratio.
    ratio_j = res_row2[i] / jnp.where(delta_row == 0, 1.0, delta_row)
    j_from_ratio = jnp.clip((jnp.round(ratio_j) - 1).astype(jnp.int32), 0,
                            d.shape[1] - 1)
    j = jnp.where(jnp.logical_or(use_ratio, jnp.logical_not(
        jnp.isfinite(ratio_j))), j, j_from_ratio)
    rest = jnp.sum(jnp.where(jnp.arange(d.shape[1]) == j, 0.0, d[i]))
    value = expected.row1[i] - rest

    zero = jnp.zeros((), jnp.int32)
    return Verdict(
        detected=detected,
        row=jnp.where(detected, i, zero),
        col=jnp.where(detected, j, zero),
        delta=jnp.where(detected, delta, jnp.zeros((), d.dtype)),
        value=value.astype(d.dtype),
    )


def correct(d: jax.Array, verdict: Verdict) -> jax.Array:
    """Subtract the located delta (no-op when nothing was detected); an
    element corrupted to Inf or NaN is rebuilt from its row checksum."""
    at = d.at[verdict.row, verdict.col]
    fixed = jnp.where(jnp.isfinite(verdict.delta), at.add(-verdict.delta),
                      at.set(verdict.value))
    return jnp.where(verdict.detected, fixed, d)
