"""Tile-granular triangle-inequality pruning (the bounds-carrying Lloyd
kernel family).

The headline guarantee is *exactness*: the bound check only skips
(row tile, centroid tile) cells that provably lose, so the pruned kernel
must be bit-identical to the unpruned one-pass kernel — same assignments,
same min-distances, same fused sums/counts, same final centroids, same
``n_iter_`` — on every dtype/variant cell. Pruning *effectiveness* is
tested separately in the regime it is built for (clustered data, rows
cluster-contiguous, centroid order aligned with row order, warm bounds):
uniform data or unaligned centroid order legitimately prunes nothing, and
the exactness tests cover that too.

Pallas kernels run interpret=True (kernel bodies execute in Python on CPU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KMeans, get_backend
from repro.api.registry import BackendCapabilityError
from repro.core import assignment, autotune
from repro.core.kmeans import means_from_sums
from repro.kernels import ops
from repro.kernels.ops import BoundsState, KernelParams


def _clustered(m, k, f, seed=0, sep=8.0):
    """Well-separated blobs, rows cluster-contiguous (cluster j owns rows
    j*m/k..(j+1)*m/k) and centers in cluster order — the aligned regime
    tile pruning engages in."""
    kx, kc = jax.random.split(jax.random.PRNGKey(seed))
    centers = jax.random.normal(kc, (k, f), jnp.float32) * sep
    labels = (jnp.arange(m) * k) // m
    x = centers[labels] + jax.random.normal(kx, (m, f), jnp.float32)
    return x, centers


# (m, k, f, dtype): generic multi-centroid-tile and smallk single-tile
# cells, f32 and bf16 — the seeded grid of the acceptance criterion.
GRID = [
    (512, 256, 32, jnp.float32),     # generic: nkt=2, pruning engages
    (512, 256, 32, jnp.bfloat16),
    (512, 16, 32, jnp.float32),      # smallk: nkt=1, statically unprunable
    (512, 16, 32, jnp.bfloat16),
]


class TestKernelBitIdentity:
    @pytest.mark.parametrize("m,k,f,dtype", GRID)
    def test_pruned_matches_unpruned_over_iterations(self, m, k, f, dtype):
        x32, c32 = _clustered(m, k, f)
        x, c = x32.astype(dtype), c32.astype(dtype)
        p = ops.clamp_params(m, k, f, KernelParams(128, 128, 128),
                             dtype=dtype)
        bounds = ops.init_bounds(m, k, f, p, dtype=dtype)
        pruned_any = False
        for it in range(4):
            am_u, md_u, sums_u, cnt_u = ops.fused_lloyd(
                x, c, p, interpret=True)
            am_p, md_p, sums_p, cnt_p, bounds, frac = ops.fused_lloyd_pruned(
                x, c, p, bounds=bounds, interpret=True)
            assert jnp.array_equal(am_u, am_p), f"iter {it}: assignments"
            assert jnp.array_equal(md_u, md_p), f"iter {it}: min-dists"
            assert jnp.array_equal(sums_u, sums_p), f"iter {it}: sums"
            assert jnp.array_equal(cnt_u, cnt_p), f"iter {it}: counts"
            pruned_any |= float(frac) > 0.0
            c32 = means_from_sums(sums_u, cnt_u, c32)
            c = c32.astype(dtype)
        kp = ops._round_up(k, p.block_k)
        if kp // p.block_k > 1:
            # multi-tile aligned clustered data must actually prune —
            # otherwise this test would pass vacuously on an all-compute
            # fallback
            assert pruned_any
        else:
            # a single centroid tile can never be skipped (it holds every
            # row's assigned centroid)
            assert not pruned_any

    def test_first_iteration_is_unpruned(self):
        m, k, f = 512, 256, 32
        x, c = _clustered(m, k, f)
        p = ops.clamp_params(m, k, f, KernelParams(128, 128, 128))
        bounds = ops.init_bounds(m, k, f, p)
        assert bool(bounds.fresh)
        *_, bounds, frac = ops.fused_lloyd_pruned(x, c, p, bounds=bounds,
                                                  interpret=True)
        assert float(frac) == 0.0          # seed pass computes every tile
        assert not bool(bounds.fresh)

    def test_prune_rate_reaches_half_on_aligned_clusters(self):
        # nkt=4 (k=512 at block_k=128), 4 row tiles, alignment 1 centroid
        # tile per row tile -> steady state skips 3/4 of cells; the
        # acceptance bar is >= 50% by the final third of iterations.
        m, k, f = 512, 512, 32
        x, c0 = _clustered(m, k, f, seed=3)
        p = ops.clamp_params(m, k, f, KernelParams(128, 128, 128))
        bounds = ops.init_bounds(m, k, f, p)
        c, fracs = c0, []
        for _ in range(3):
            _, _, sums, cnt, bounds, frac = ops.fused_lloyd_pruned(
                x, c, p, bounds=bounds, interpret=True)
            fracs.append(float(frac))
            c = means_from_sums(sums, cnt, c)
        assert fracs[0] == 0.0
        assert fracs[-1] >= 0.5, fracs


class TestXlaBackendBitIdentity:
    def test_pruned_xla_matches_plain_xla_over_iterations(self):
        m, k, f = 2048, 128, 32
        x, c = _clustered(m, k, f, seed=1)
        plain = get_backend("lloyd_xla")
        pruned = get_backend("lloyd_pruned_xla")
        bounds = assignment.init_bounds_xla(m, k, f)
        for it in range(6):
            am_u, md_u, _, sums_u, cnt_u = plain(x, c)
            am_p, md_p, _, sums_p, cnt_p, bounds, _ = pruned(
                x, c, bounds=bounds)
            assert jnp.array_equal(am_u, am_p), f"iter {it}"
            assert jnp.array_equal(sums_u, sums_p), f"iter {it}"
            assert jnp.array_equal(cnt_u, cnt_p), f"iter {it}"
            np.testing.assert_allclose(md_p, md_u, rtol=1e-5, atol=1e-5)
            c = means_from_sums(sums_u, cnt_u, c)


class TestEstimatorBitIdentity:
    @pytest.mark.parametrize("pruned,plain", [
        ("lloyd_pruned", "lloyd"),             # Pallas pair (interpret)
        ("lloyd_pruned_xla", "lloyd_xla"),     # XLA analogue pair
    ])
    def test_fit_is_bit_identical(self, pruned, plain):
        m, k, f = (256, 40, 32) if pruned == "lloyd_pruned" else (2048, 64, 32)
        x, _ = _clustered(m, k, f, seed=2)
        kms = []
        for name in (pruned, plain):
            km = KMeans(n_clusters=k, backend=name, max_iter=8,
                        random_state=0)
            km.fit(x)
            kms.append(km)
        a, b = kms
        assert a.n_iter_ == b.n_iter_
        assert jnp.array_equal(a.labels_, b.labels_)
        assert jnp.array_equal(a.cluster_centers_, b.cluster_centers_)
        assert a.inertia_ == b.inertia_
        # the plain backend never reports pruning; the pruned one reports
        # one fraction per executed iteration
        assert b.prune_history_ == []
        assert len(a.prune_history_) == a.n_iter_
        # predict routes both through assignment-only kernels
        assert jnp.array_equal(a.predict(x[:64]), b.predict(x[:64]))

    def test_prune_history_reaches_half_in_final_third(self):
        # The refinement regime (warm start from near-true centers — the
        # checkpoint-restart scenario): drifts collapse after the first
        # step and the aligned tiles stay skippable. 8192 rows / 128
        # clusters -> 4 row chunks x 8 centroid groups, 2 groups live per
        # chunk -> steady state skips 3/4.
        m, k, f = 8192, 128, 32
        x, centers = _clustered(m, k, f, seed=4)
        km = KMeans(n_clusters=k, backend="lloyd_pruned_xla", max_iter=9,
                    tol=0.0, random_state=0)
        km.fit(x, centroids=centers + 0.01)
        hist = km.prune_history_
        assert len(hist) == km.n_iter_ == 9
        assert hist[0] == 0.0                      # unpruned seed pass
        final_third = hist[-3:]
        assert min(final_third) >= 0.5, hist


class TestBoundsLifecycle:
    def test_state_roundtrip_warm_refit_matches_cold_fit(self):
        # from_state must not carry bounds: a warm refit from restored
        # centroids has to be bit-identical to a cold fit seeded with the
        # same centroids (stale bounds after a centroid hot-swap is the
        # classic Hamerly bug).
        m, k, f = 2048, 64, 32
        x, _ = _clustered(m, k, f, seed=5)
        km = KMeans(n_clusters=k, backend="lloyd_pruned_xla", max_iter=4,
                    tol=0.0, random_state=0)
        km.fit(x)
        state = km.get_state()
        seed_c = jnp.asarray(state["cluster_centers"])

        warm = KMeans.from_state(state)
        warm.fit(x, centroids=seed_c)
        cold = KMeans(n_clusters=k, backend="lloyd_pruned_xla", max_iter=4,
                      tol=0.0, random_state=0)
        cold.fit(x, centroids=seed_c)
        assert warm.n_iter_ == cold.n_iter_
        assert jnp.array_equal(warm.labels_, cold.labels_)
        assert jnp.array_equal(warm.cluster_centers_, cold.cluster_centers_)

    def test_partial_fit_runs_unpruned_and_matches_plain(self):
        # partial_fit blocks share no bounds lineage, so every streaming
        # step must run with fresh (all-compute) bounds — its update must
        # match the plain backend's bit for bit.
        m, k, f = 1024, 32, 16
        x, _ = _clustered(m, k, f, seed=6)
        results = []
        for name in ("lloyd_pruned_xla", "lloyd_xla"):
            km = KMeans(n_clusters=k, backend=name, random_state=0)
            km.partial_fit(x[:512]).partial_fit(x[512:])
            results.append(km)
        a, b = results
        assert jnp.array_equal(a.labels_, b.labels_)
        assert jnp.array_equal(a.cluster_centers_, b.cluster_centers_)
        assert a.prune_history_ == []

    def test_partial_fit_after_fit_restarts_streaming(self):
        m, k, f = 1024, 32, 16
        x, _ = _clustered(m, k, f, seed=6)
        km = KMeans(n_clusters=k, backend="lloyd_pruned_xla", max_iter=3,
                    random_state=0)
        km.fit(x)
        c_fit = km.cluster_centers_
        km.partial_fit(x[:256])
        assert not jnp.array_equal(km.cluster_centers_, c_fit)

    def test_bounds_state_is_a_registered_pytree(self):
        b = ops.init_bounds(256, 64, 32)
        leaves, treedef = jax.tree_util.tree_flatten(b)
        assert len(leaves) == 5                 # every field is a leaf
        rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
        assert isinstance(rebuilt, BoundsState)
        # survives a scan carry (the whole point of registration)
        out, _ = jax.lax.scan(lambda s, _: (s, None), b, None, length=2)
        assert isinstance(out, BoundsState)


class TestSelectionAndRegistry:
    def test_select_params_rejects_unknown_kind(self):
        with pytest.raises(ValueError, match="pruned"):
            autotune.select_params(1024, 64, 128, kind="bogus")

    def test_kinds_vocabulary_is_single_sourced(self):
        # satellite: KINDS extension is one point of change, shared by the
        # cache schema, the contract checker and selection
        assert autotune.KINDS is ops.PLAN_KINDS
        assert "pruned" in autotune.KINDS

    def test_select_params_pruned_kind(self):
        variant, p = autotune.select_params(4096, 256, 128, kind="pruned")
        assert variant in ops.VARIANTS
        assert ops.pruned_vmem_bytes(
            ops.clamp_params(4096, 256, 128, p), 256, 128,
            jnp.float32) <= autotune.VMEM_BUDGET

    def test_model_score_discounts_by_prune_rate(self):
        p = ops.clamp_params(16384, 256, 128, KernelParams(256, 128, 128))
        s_none = autotune.model_score(16384, 256, 128, p, kind="pruned",
                                      prune_rate=0.0)
        s_half = autotune.model_score(16384, 256, 128, p, kind="pruned",
                                      prune_rate=0.5)
        s_lloyd = autotune.model_score(16384, 256, 128, p, kind="lloyd")
        assert s_half < s_none
        assert s_half < s_lloyd

    def test_bounds_refused_by_non_bounds_backend(self):
        x, c = _clustered(256, 16, 32)
        b = assignment.init_bounds_xla(256, 16, 32)
        with pytest.raises(BackendCapabilityError, match="bounds"):
            get_backend("lloyd_xla")(x, c, bounds=b)

    def test_pruned_backends_declare_the_contract(self):
        for name in ("lloyd_pruned", "lloyd_pruned_xla"):
            b = get_backend(name)
            assert b.supports_bounds and b.fuses_update
            assert b.kernel_kind == "pruned"
            assert b.expected_arity == 7
            assert callable(b.bounds_init)

    def test_measure_score_pruned_runs(self):
        # two-iteration protocol on clustered data: seeding pass, then the
        # warmed timed call (tiny shape; interpret mode)
        t = autotune.measure_score(256, 256, 32, KernelParams(128, 128, 128),
                                   iters=1, kind="pruned", variant="generic")
        assert t > 0.0


class TestClusteredBlobsGenerator:
    def test_rows_are_cluster_contiguous_and_separated(self):
        x, centers = autotune._clustered_data(512, 32, 16, jnp.float32)
        assert x.shape == (512, 16) and centers.shape == (32, 16)
        d = jnp.sum((x[:, None, :] - centers[None, :, :]) ** 2, axis=-1)
        labels = jnp.argmin(d, axis=1)
        # well-separated: every row is nearest its own generator center,
        # and cluster-contiguous: labels are sorted
        assert jnp.array_equal(labels, jnp.sort(labels))
        assert int(labels[0]) == 0 and int(labels[-1]) == 31
        # seeded: same call, same data
        x2, c2 = autotune._clustered_data(512, 32, 16, jnp.float32)
        assert jnp.array_equal(x, x2) and jnp.array_equal(centers, c2)
