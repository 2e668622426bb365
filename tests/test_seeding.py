"""Seeding determinism + fused k-means++ parity contracts.

What this suite pins:

* ``init_kmeanspp`` / ``init_random`` are pure functions of (key, x, k):
  the same seed gives bit-identical centroids across direct, re-jitted
  and vmapped invocation — no hidden global PRNG state, no trace-order
  sensitivity.
* ``init_kmeanspp_fused`` is deterministic per seed, and its Pallas round
  kernel (interpret mode off-TPU) chooses the *same sample indices* as
  the tile-mirrored XLA twin at a fixed ``block_n``. Both paths gather
  the final centroids from the same unpadded ``x``, so index parity makes
  the returned (B, K, F) arrays bit-identical — which is what we assert.
* Fused seeding draws real sample rows, K distinct ones per problem, and
  problem b's draws depend only on its own key (batch-size invariance).
* ``BatchedKMeans(init="kmeans++-fused")`` produces identical seeds for
  identical ``random_state`` across estimator instances.

The fused key protocol deliberately differs from ``init_kmeanspp``'s
(block uniform draws vs per-round split — see kernels/kmeanspp_init.py),
so there is NO cross-implementation sample equality to pin; the contract
is per-seed self-reproducibility plus kernel/twin index parity.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.batch import BatchedKMeans
from repro.core.kmeans import init_kmeanspp, init_random
from repro.kernels.kmeanspp_init import (clamp_init_block,
                                         init_kmeanspp_fused)


def _x(m, f, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (m, f), jnp.float32)


def _stack(b, n, f, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, n, f),
                             jnp.float32)


def _keys(b, base=0):
    return jax.vmap(jax.random.PRNGKey)(base + jnp.arange(b))


def _rows_of(x, c):
    """For each centroid row, True iff it equals some row of x exactly."""
    eq = jnp.all(c[:, None, :] == x[None, :, :], axis=-1)      # (K, N)
    return jnp.any(eq, axis=1)


class TestSingleProblemInits:
    """init_kmeanspp / init_random: same key => same centroids, on every
    invocation path a fit may reach them through."""

    @pytest.mark.parametrize("fn", [init_kmeanspp, init_random],
                             ids=["kmeanspp", "random"])
    def test_direct_vs_rejit_vs_vmap(self, fn):
        x, k = _x(300, 24), 7
        key = jax.random.PRNGKey(42)
        direct = fn(key, x, k)
        again = fn(key, x, k)                       # cached-jit re-call
        rejit = jax.jit(fn, static_argnums=(2,))(key, x, k)
        vmapped = jax.vmap(fn, in_axes=(0, 0, None))(
            key[None], x[None], k)[0]
        assert jnp.array_equal(direct, again)
        assert jnp.array_equal(direct, rejit)
        assert jnp.array_equal(direct, vmapped)

    @pytest.mark.parametrize("fn", [init_kmeanspp, init_random],
                             ids=["kmeanspp", "random"])
    def test_distinct_keys_distinct_draws(self, fn):
        x, k = _x(300, 24), 7
        a = fn(jax.random.PRNGKey(0), x, k)
        b = fn(jax.random.PRNGKey(1), x, k)
        assert not jnp.array_equal(a, b)

    def test_kmeanspp_centroids_are_sample_rows(self):
        x, k = _x(200, 16), 9
        c = init_kmeanspp(jax.random.PRNGKey(3), x, k)
        assert bool(jnp.all(_rows_of(x, c)))


class TestFusedParity:
    """The Pallas round kernel and the XLA twin must choose the same
    sample indices; both gather from the same x, so the outputs are
    required to be bit-identical arrays."""

    @pytest.mark.parametrize("b,n,f,k,block_n", [
        (4, 600, 48, 9, 256),     # multi-tile, ragged N
        (3, 200, 16, 5, 512),     # single tile after clamp (T == 1 path)
        (2, 1024, 128, 8, 128),   # lane-aligned F, many tiles
    ])
    def test_kernel_matches_twin(self, b, n, f, k, block_n):
        x, keys = _stack(b, n, f), _keys(b)
        ck = init_kmeanspp_fused(keys, x, k, block_n=block_n,
                                 use_kernel=True, interpret=True)
        ct = init_kmeanspp_fused(keys, x, k, block_n=block_n,
                                 use_kernel=False)
        assert jnp.array_equal(ck, ct), (
            "fused kernel and XLA twin chose different sample indices")

    def test_twin_deterministic_and_real_rows(self):
        b, n, f, k = 6, 500, 32, 11
        x, keys = _stack(b, n, f), _keys(b)
        c1 = init_kmeanspp_fused(keys, x, k)
        c2 = init_kmeanspp_fused(keys, x, k)
        assert jnp.array_equal(c1, c2)
        for p in range(b):
            assert bool(jnp.all(_rows_of(x[p], c1[p])))
            # K distinct rows: D² mass at a chosen row is zero afterwards
            assert len(np.unique(np.asarray(c1[p]), axis=0)) == k

    def test_block_n_shapes_cdf_not_distribution_support(self):
        """Different tile sizes may pick different samples (the two-level
        CDF walks tiles in different order), but every pick must still be
        a real row — block_n must never leak padded rows into the draw."""
        b, n, f, k = 3, 700, 24, 8
        x, keys = _stack(b, n, f), _keys(b)
        for bn in (128, 256, 1024):
            c = init_kmeanspp_fused(keys, x, k, block_n=bn,
                                    use_kernel=False)
            for p in range(b):
                assert bool(jnp.all(_rows_of(x[p], c[p]))), f"block_n={bn}"

    def test_batch_invariance(self):
        """Problem b's draws depend only on its own key: the first B'
        problems of a size-B batch reproduce the size-B' batch."""
        b, n, f, k = 8, 400, 16, 6
        x, keys = _stack(b, n, f), _keys(b)
        full = init_kmeanspp_fused(keys, x, k)
        half = init_kmeanspp_fused(keys[:3], x[:3], k)
        assert jnp.array_equal(full[:3], half)

    def test_clamp_init_block(self):
        assert clamp_init_block(600, 512) == 512
        assert clamp_init_block(200, 512) == 256     # ceil to 128-grid
        assert clamp_init_block(600, 64) == 128      # floor at 128
        assert clamp_init_block(4096, 100_000) == 4096


class TestBatchedEstimatorSeeding:
    def test_fused_init_reproducible_across_instances(self):
        x = _stack(5, 300, 16, seed=7)
        a = BatchedKMeans(n_clusters=6, random_state=11,
                          init="kmeans++-fused").init_centroids(x)
        b = BatchedKMeans(n_clusters=6, random_state=11,
                          init="kmeans++-fused").init_centroids(x)
        assert jnp.array_equal(a, b)
        c = BatchedKMeans(n_clusters=6, random_state=12,
                          init="kmeans++-fused").init_centroids(x)
        assert not jnp.array_equal(a, c)

    @pytest.mark.parametrize("init", ["kmeans++", "kmeans++-fused",
                                      "random"])
    def test_fit_deterministic_per_random_state(self, init):
        x = _stack(4, 256, 8, seed=3)
        r1 = BatchedKMeans(n_clusters=4, random_state=0, max_iter=5,
                           init=init).fit(x)
        r2 = BatchedKMeans(n_clusters=4, random_state=0, max_iter=5,
                           init=init).fit(x)
        assert jnp.array_equal(r1.cluster_centers_, r2.cluster_centers_)
        assert jnp.array_equal(r1.labels_, r2.labels_)
