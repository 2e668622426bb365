"""K-means numerics.

The estimator front end lives in ``repro.api`` (:class:`repro.api.KMeans`,
:class:`repro.api.FaultPolicy`). This module keeps the algorithmic pieces it
is built from — initialization (k-means++ / random), the DMR-protected
centroid update (paper §IV intro), empty-cluster reseeding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import dmr as dmr_mod
from repro.kernels import ops


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

# Both inits are jitted with K static: an eager fori_loop/choice retraces
# its body on every call, which put one fresh XLA compile on every
# ``fit`` — the recompile gate (repro.analysis.recompile) caught it on
# the warm-refit path.
@functools.partial(jax.jit, static_argnums=(2,))
def init_random(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    idx = jax.random.choice(key, x.shape[0], (k,), replace=False)
    return x[idx]


@functools.partial(jax.jit, static_argnums=(2,))
def init_kmeanspp(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    """k-means++ seeding (D^2 sampling), jit-safe via fori_loop."""
    m = x.shape[0]
    k0, key = jax.random.split(key)
    first = x[jax.random.randint(k0, (), 0, m)]
    centroids = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(first)
    d2 = jnp.sum((x - first) ** 2, axis=1)

    def body(i, carry):
        centroids, d2, key = carry
        key, sub = jax.random.split(key)
        probs = d2 / jnp.maximum(jnp.sum(d2), 1e-30)
        idx = jax.random.choice(sub, m, p=probs)
        nxt = x[idx]
        centroids = centroids.at[i].set(nxt)
        d2 = jnp.minimum(d2, jnp.sum((x - nxt) ** 2, axis=1))
        return centroids, d2, key

    centroids, _, _ = jax.lax.fori_loop(1, k, body, (centroids, d2, key))
    return centroids


# ---------------------------------------------------------------------------
# Centroid update (paper step 3: memory-bound, DMR-protected)
# ---------------------------------------------------------------------------

def protected_sums(x: jax.Array, assign: jax.Array, k: int, *,
                   use_dmr: bool = True) -> ops.ClusterSums:
    """Per-cluster (sums, counts, windows) of :func:`ops.cluster_sums`,
    optionally under DMR.

    DMR duplicates the arithmetic over once-loaded data (<1 % overhead in
    the paper); a mismatch triggers one recompute (fail-continue fix)."""
    def _sums(x, assign):
        return ops.cluster_sums(x, assign, k)

    if not use_dmr:
        return _sums(x, assign)
    result, bad = dmr_mod.dmr(_sums, x, assign)

    def recompute(_):
        return _sums(jax.lax.optimization_barrier(x),
                     jax.lax.optimization_barrier(assign))

    return jax.lax.cond(bad, recompute, lambda _: result, operand=None)


def means_from_sums(sums: jax.Array, counts: jax.Array,
                    prev: jax.Array) -> jax.Array:
    """New centroids from per-cluster (sums, counts); empty clusters keep
    their previous centroid. The single empty-cluster policy shared by the
    two-pass update and the one-pass (fused-update) step."""
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    return jnp.where((counts > 0)[:, None], means, prev)


def centroid_update(x: jax.Array, assign: jax.Array, k: int,
                    prev: jax.Array, *, use_dmr: bool = True):
    """Means of assigned points; empty clusters keep their previous centroid."""
    sums, counts, _ = protected_sums(x, assign, k, use_dmr=use_dmr)
    return means_from_sums(sums, counts, prev), counts


def reseed_empty(key: jax.Array, x: jax.Array, centroids: jax.Array,
                 counts: jax.Array, min_dist: jax.Array) -> jax.Array:
    """Move empty clusters onto the points farthest from their centroid —
    the standard cuML/sklearn policy, jit-safe."""
    order = jnp.argsort(-min_dist)            # farthest points first
    empty_rank = jnp.cumsum(counts == 0) - 1  # position among empties
    donor = order[jnp.clip(empty_rank, 0, x.shape[0] - 1)]
    return jnp.where((counts == 0)[:, None], x[donor], centroids)

