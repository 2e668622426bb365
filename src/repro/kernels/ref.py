"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics the kernels are tested against (pytest sweeps
shapes/dtypes and asserts allclose). They are also the fallback execution
path on backends without Pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import checksum


def distance_matrix(x: jax.Array, c: jax.Array) -> jax.Array:
    """Full squared-distance matrix ||x_i - c_j||^2, shape (M, K), f32.

    Mirrors the kernel templates' dtype semantics: the GEMM multiplies in
    the input dtype (f32/bf16/fp16) but accumulates in f32, and norms are
    computed in f32 — so this oracle is comparable to the Pallas kernels
    at every compute dtype.
    """
    xf = x.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    xn = jnp.sum(xf * xf, axis=1, keepdims=True)        # (M, 1)
    cn = jnp.sum(cf * cf, axis=1)[None, :]              # (1, K)
    cross = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    return xn + cn - 2.0 * cross


def distance_argmin(x: jax.Array, c: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused kernel: (min partial distance, argmin).

    The fused kernel omits the per-row constant ||x_i||^2 (irrelevant to the
    argmin); the returned min distance is therefore
    ``||c_j||^2 - 2 x_i . c_j`` for the winning j. Use
    ``min_dist + sum(x**2, -1)`` for true squared distances.
    """
    cf = c.astype(jnp.float32)
    cn = jnp.sum(cf * cf, axis=1)[None, :]
    cross = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    d = cn - 2.0 * cross
    return jnp.min(d, axis=1), jnp.argmin(d, axis=1).astype(jnp.int32)


def distance_argmin_ft(
    x: jax.Array,
    c: jax.Array,
    inject_delta: jax.Array | None = None,
    inject_pos: tuple[int, int] | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for the FT fused kernel.

    Simulates one SEU in the distance tile (additive delta at inject_pos of
    the cross-product matrix), then applies dual-checksum verify + correct,
    then reduces. Returns (min_dist, argmin, detected_count).
    """
    cn = jnp.sum(c * c, axis=1)[None, :]
    cross = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
    expected = checksum.expected_checksums(x, c.T)
    detected_count = jnp.zeros((), jnp.int32)
    if inject_delta is not None and inject_pos is not None:
        cross = cross.at[inject_pos].add(inject_delta)
    scale = jnp.maximum(jnp.max(jnp.abs(cross)), 1.0)
    thr = checksum.default_threshold(x.shape[1], cross.dtype) * scale
    verdict = checksum.verify(cross, expected, thr)
    cross = checksum.correct(cross, verdict)
    detected_count = detected_count + verdict.detected.astype(jnp.int32)
    d = cn - 2.0 * cross
    return jnp.min(d, axis=1), jnp.argmin(d, axis=1).astype(jnp.int32), detected_count


def lloyd_step(x: jax.Array, c: jax.Array) -> tuple[jax.Array, jax.Array,
                                                    jax.Array, jax.Array]:
    """Oracle for the one-pass Lloyd kernel.

    Returns (min partial distance, argmin, sums (K, F), counts (K,)) —
    the assignment semantics of :func:`distance_argmin` plus the
    per-cluster sums/counts of :func:`centroid_update`, all from one pass.
    """
    md, am = distance_argmin(x, c)
    sums, counts = centroid_update(x, am, c.shape[0])
    return md, am, sums, counts


def matmul(x: jax.Array, y: jax.Array) -> jax.Array:
    """Oracle for the ABFT matmul kernel: plain product."""
    return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)


def centroid_update(x: jax.Array, assign: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Oracle for the centroid-update: per-cluster sums and counts.

    Returns (sums (K, N) f32, counts (K,) f32). The mean (= new centroids)
    is sums / max(counts, 1); callers handle empty clusters. Accumulation
    is f32 for every input dtype (bf16 counts would lose exactness past
    256 members) — the same contract as the one-pass kernel's epilogue.
    The product is pinned to full precision: XLA's default on TPU rounds
    f32 operands to bf16.
    """
    onehot = jax.nn.one_hot(assign, k, dtype=x.dtype)   # (M, K)
    sums = jax.lax.dot_general(onehot, x, (((0,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    counts = jnp.sum(onehot.astype(jnp.float32), axis=0)
    return sums, counts
