"""K-means numerics + legacy shims.

The estimator front end lives in ``repro.api`` (:class:`repro.api.KMeans`,
:class:`repro.api.FaultPolicy`). This module keeps the algorithmic pieces it
is built from — initialization (k-means++ / random), the DMR-protected
centroid update (paper §IV intro), empty-cluster reseeding — plus thin
deprecation shims (:class:`KMeansConfig`, :class:`KMeans`,
:func:`fit_kmeans`) that translate the old magic-string surface onto the
typed one.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import dmr as dmr_mod
from repro.core.fault import FaultConfig
from repro.kernels import ops


class KMeansState(NamedTuple):
    centroids: jax.Array       # (K, F)
    assign: jax.Array          # (M,) int32
    inertia: jax.Array         # scalar: sum of squared distances
    shift: jax.Array           # centroid movement (convergence metric)
    iteration: jax.Array       # int32
    detected_errors: jax.Array # cumulative SDCs corrected (int32)


class KMeansResult(NamedTuple):
    centroids: jax.Array
    assign: jax.Array
    inertia: jax.Array
    iterations: int
    detected_errors: jax.Array


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
    two-pass update, the one-pass (fused-update) step and the benchmarks."""
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


# ---------------------------------------------------------------------------
# Legacy shims (deprecated): magic-string config -> repro.api
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KMeansConfig:
    """Deprecated: construct ``repro.api.KMeans`` with a ``FaultPolicy``."""

    k: int
    max_iters: int = 100
    tol: float = 1e-4
    init: str = "kmeans++"            # "kmeans++" | "random"
    assignment: str = "fused"          # registered backend name
    dmr_update: bool = True            # DMR on the memory-bound update phase
    minibatch: Optional[int] = None    # None = full-batch Lloyd
    seed: int = 0
    dtype: str = "float32"


def _policy_for(cfg: KMeansConfig, fault: Optional[FaultConfig]):
    """Translate (assignment string, dmr_update, FaultConfig) -> FaultPolicy."""
    from repro.api import FaultPolicy, InjectionCampaign, get_backend
    backend = get_backend(cfg.assignment)
    campaign = None
    if fault is not None and fault.enabled() and backend.takes_injection:
        campaign = InjectionCampaign(rate=fault.rate, bit_low=fault.bit_low,
                                     bit_high=fault.bit_high, seed=fault.seed)
    if backend.supports_ft:
        mode = "correct" if backend.takes_injection else "detect"
        return FaultPolicy(mode=mode, update_dmr=cfg.dmr_update,
                           injection=campaign)
    # unprotected assignment, but dmr_update is honoured independently
    # (legacy default was DMR-on even for plain backends)
    return FaultPolicy(mode="off", update_dmr=cfg.dmr_update)


def _make_estimator(cfg: KMeansConfig, params,
                    fault: Optional[FaultConfig] = None):
    from repro.api import KMeans as ApiKMeans
    return ApiKMeans(cfg.k, max_iter=cfg.max_iters, tol=cfg.tol,
                     init=cfg.init, fault=_policy_for(cfg, fault),
                     backend=cfg.assignment, batch_size=cfg.minibatch,
                     params=params, random_state=cfg.seed)


class KMeans:
    """Deprecated front end kept for compatibility; delegates to
    ``repro.api.KMeans``. New code should use the typed API directly."""

    def __init__(self, cfg: KMeansConfig, params=None):
        warnings.warn(
            "repro.core.KMeans/KMeansConfig are deprecated; use "
            "repro.api.KMeans(n_clusters=..., fault=FaultPolicy(...))",
            DeprecationWarning, stacklevel=2)
        self.cfg = cfg
        self.params = params
        # one estimator for the shim's lifetime so repeated fits reuse the
        # jit cache (a per-fit FaultConfig only changes the host-side
        # injection schedule, never the compiled step)
        self._est = _make_estimator(cfg, params)

    def init_centroids(self, x: jax.Array, key: Optional[jax.Array] = None):
        return self._est.init_centroids(x, key)

    def fit(self, x: jax.Array, *, centroids: Optional[jax.Array] = None,
            fault: Optional[FaultConfig] = None,
            on_iteration: Optional[Callable] = None) -> KMeansResult:
        est = self._est
        est.fault = _policy_for(self.cfg, fault)
        est.fit(x, centroids=centroids, on_iteration=on_iteration)
        return KMeansResult(est.cluster_centers_, est.labels_,
                            jnp.asarray(est.inertia_), est.n_iter_,
                            jnp.asarray(est.detected_errors_, jnp.int32))


def fit_kmeans(x, k: int, **kw) -> KMeansResult:
    """Deprecated convenience one-shot API (``repro.api.KMeans(...).fit``)."""
    warnings.warn("fit_kmeans is deprecated; use repro.api.KMeans",
                  DeprecationWarning, stacklevel=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        cfg = KMeansConfig(k=k, **kw)
        return KMeans(cfg).fit(x)
