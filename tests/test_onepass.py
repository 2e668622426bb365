"""One-pass Lloyd: kernel parity over irregular shapes, the device-resident
chunked fit loop (host-sync accounting), kind-keyed autotune, traffic model.

Kernels run interpret=True (kernel bodies execute in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (AutotuneCache, FaultPolicy, InjectionCampaign,
                       KMeans, get_backend, list_backends)
from repro.core.autotune import (feasible, iteration_traffic, measure_score,
                                 select_params)
from repro.data.blobs import make_blobs
from repro.kernels import ops, ref
from repro.kernels.ops import KernelParams

IRREGULAR = [
    (1000, 7, 33),            # every dim off-grid, K far below a tile
    (513, 129, 257),          # one past a block boundary in every dim
    (256, 128, 512),          # exactly one tile
    (300, 77, 130),           # ragged
    (64, 8, 32),              # tiny: block clamping
]


def _data(m, k, f, seed=0):
    kx, kc = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kx, (m, f), jnp.float32),
            jax.random.normal(kc, (k, f), jnp.float32))


def _int_data(m, k, f, seed=0):
    """Integer-valued f32 data: distances are exactly representable, so
    argmin ties are real ties and tie-break order is observable."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-4, 5, (m, f)), jnp.float32)
    c = jnp.asarray(rng.integers(-4, 5, (k, f)), jnp.float32)
    return x, c


class TestOnePassKernelParity:
    @pytest.mark.parametrize("m,k,f", IRREGULAR)
    def test_matches_two_pass_reference(self, m, k, f):
        x, c = _data(m, k, f)
        am, md, sums, counts = ops.fused_lloyd(x, c, interpret=True)
        assert am.shape == (m,) and md.shape == (m,)
        assert sums.shape == (k, f) and counts.shape == (k,)
        # padded centroid slots never win
        assert int(jnp.max(am)) < k
        rmd, ram = ref.distance_argmin(x, c)
        match = float(jnp.mean((am == ram).astype(jnp.float32)))
        assert match > 0.999, f"argmin mismatch rate {1 - match:.4f}"
        # true squared distance (plan row norms folded in)
        np.testing.assert_allclose(
            md, rmd + jnp.sum(x * x, axis=1), rtol=1e-4, atol=1e-3)
        # the fused update accumulation == the second-pass oracle, given
        # the kernel's own assignment
        rsums, rcounts = ref.centroid_update(x, am, k)
        np.testing.assert_allclose(sums, rsums, rtol=1e-4, atol=1e-3)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(rcounts))
        # counts cover exactly the true rows — padding contributes nothing
        assert float(jnp.sum(counts)) == m

    @pytest.mark.parametrize("m,k,f", [(1000, 7, 33), (513, 129, 257)])
    def test_exact_tiebreak_agreement_vs_fused(self, m, k, f):
        """Exact-arithmetic data with duplicated centroids: both kernels
        must resolve ties to the first (lowest) index, like the oracle."""
        x, c = _int_data(m, k, f, seed=3)
        c = c.at[k - 1].set(c[k // 2])     # guaranteed exact tie pair
        _, ram = ref.distance_argmin(x, c)
        am_f, _ = ops.fused_assign(x, c, interpret=True)
        am_l, _, _, _ = ops.fused_lloyd(x, c, interpret=True)
        np.testing.assert_array_equal(np.asarray(am_f), np.asarray(ram))
        np.testing.assert_array_equal(np.asarray(am_l), np.asarray(ram))
        assert not bool(jnp.any(am_l == k - 1))   # loser of every tie

    def test_plan_reuse_matches_unplanned_call(self):
        x, c = _data(300, 77, 130, seed=5)
        params = ops.clamp_params(300, 77, 130, KernelParams())
        plan = ops.plan_data(x, params)
        a1 = ops.fused_lloyd(plan, c, interpret=True)
        a2 = ops.fused_lloyd(x, c, params, interpret=True)
        for got, want in zip(a1, a2):
            np.testing.assert_allclose(got, want, rtol=1e-6)
        # the plan's norms feed the fused assignment path too
        am_p, md_p, _ = get_backend("fused")(plan, c, params=params)
        am_r, md_r, _ = get_backend("fused")(x, c, params=params)
        np.testing.assert_array_equal(np.asarray(am_p), np.asarray(am_r))
        np.testing.assert_allclose(md_p, md_r, rtol=1e-6)

    def test_lloyd_xla_matches_lloyd_pallas(self):
        x, c = _data(256, 16, 64, seed=6)
        am_x, md_x, _, sums_x, counts_x = get_backend("lloyd_xla")(x, c)
        am_p, md_p, _, sums_p, counts_p = get_backend("lloyd")(
            x, c, params=KernelParams(128, 128, 128))
        np.testing.assert_array_equal(np.asarray(am_x), np.asarray(am_p))
        np.testing.assert_allclose(md_x, md_p, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(sums_x, sums_p, rtol=1e-4, atol=1e-3)
        np.testing.assert_array_equal(np.asarray(counts_x),
                                      np.asarray(counts_p))


@pytest.fixture(scope="module")
def blobs():
    return make_blobs(2000, 16, 8, seed=1, spread=0.5)


class TestDeviceResidentFit:
    def test_no_host_sync_inside_window(self, blobs, monkeypatch):
        """The whole point of the device loop: with sync_every=3 and 9
        iterations, fit performs 3 chunk syncs + 1 final counter read —
        never one per iteration — and compiles a single chunk trace."""
        from repro.api import estimator as est_mod
        x, _ = blobs
        reads = []
        real = est_mod._host_read
        monkeypatch.setattr(est_mod, "_host_read",
                            lambda v: reads.append(1) or real(v))
        km = KMeans(8, max_iter=9, tol=0.0, sync_every=3, random_state=0)
        km.fit(x)
        assert km.n_iter_ == 9
        assert len(reads) == 9 // 3 + 1
        assert len(reads) < km.n_iter_          # strictly sub-iteration
        chunk_traces = [k for k in km._step_cache if k[0] == "chunk"]
        assert len(chunk_traces) == 1           # one trace for all chunks

    def test_on_iteration_replay_is_per_iteration(self, blobs):
        x, _ = blobs
        seen = []
        km = KMeans(8, max_iter=20, tol=1e-5, sync_every=6, random_state=0)
        km.fit(x, on_iteration=lambda it, c, inertia, shift:
               seen.append((it, inertia, shift)))
        its = [s[0] for s in seen]
        assert its == list(range(km.n_iter_))   # contiguous, per-iteration
        inertias = np.asarray([s[1] for s in seen])
        assert np.all(np.diff(inertias) <= np.abs(inertias[:-1]) * 1e-5)

    def test_sync_every_invariance(self, blobs):
        """Chunking is an observation schedule, not a numeric change."""
        x, _ = blobs
        a = KMeans(8, max_iter=12, sync_every=1, random_state=0).fit(x)
        b = KMeans(8, max_iter=12, sync_every=5, random_state=0).fit(x)
        assert a.n_iter_ == b.n_iter_
        assert a.inertia_ == pytest.approx(b.inertia_, rel=1e-6)
        np.testing.assert_array_equal(np.asarray(a.labels_),
                                      np.asarray(b.labels_))

    def test_onepass_backend_reaches_reference_solution(self, blobs):
        x, _ = blobs
        one = KMeans(8, max_iter=30, backend="lloyd_xla",
                     random_state=0).fit(x)
        two = KMeans(8, max_iter=30, random_state=0).fit(x)
        assert abs(one.inertia_ - two.inertia_) <= abs(two.inertia_) * 1e-3
        np.testing.assert_array_equal(np.asarray(one.labels_),
                                      np.asarray(two.labels_))
        # prediction routes through an assignment-only kernel (never the
        # fused-update epilogue) and still matches the fitted labels
        assert not one._predict_backend().fuses_update
        np.testing.assert_array_equal(np.asarray(one.predict(x)),
                                      np.asarray(one.labels_))

    def test_onepass_pallas_backend_fits(self, blobs):
        x, _ = blobs
        km = KMeans(8, max_iter=8, backend="lloyd", sync_every=4,
                    random_state=0).fit(x[:512])
        ref_km = KMeans(8, max_iter=8, random_state=0).fit(x[:512])
        assert abs(km.inertia_ - ref_km.inertia_) \
            <= abs(ref_km.inertia_) * 1e-3

    def test_injection_campaign_schedule_survives_chunking(self, blobs):
        x, _ = blobs
        policy = FaultPolicy.correct(update_dmr=False,
                                     injection=InjectionCampaign(rate=1.0))
        noisy = KMeans(8, max_iter=12, fault=policy, sync_every=4,
                       random_state=0).fit(x[:512])
        clean = KMeans(8, max_iter=12, random_state=0).fit(x[:512])
        assert noisy.detected_errors_ > 0
        assert abs(noisy.inertia_ - clean.inertia_) \
            <= abs(clean.inertia_) * 1e-3

    def test_update_dmr_ignored_with_note_on_fused_update_backend(self):
        """The former hard error is gone: one-pass backends compute the
        update in the kernel epilogue, where the lloyd_ft checksum scheme
        subsumes DMR — the flag is ignored with a deprecation note."""
        with pytest.warns(DeprecationWarning, match="two-pass"):
            km = KMeans(4, backend="lloyd_xla",
                        fault=FaultPolicy(mode="off", update_dmr=True))
        assert km._backend.name == "lloyd_xla"
        with pytest.warns(DeprecationWarning, match="subsumes DMR"):
            KMeans(4, backend="lloyd_ft",
                   fault=FaultPolicy(mode="correct", update_dmr=True))

    def test_registry_declares_fuses_update(self):
        backends = list_backends()
        assert backends["lloyd"].fuses_update
        assert backends["lloyd"].takes_params
        assert backends["lloyd_xla"].fuses_update
        assert not backends["fused"].fuses_update


class TestAutotuneOnePass:
    def test_estimator_resolves_lloyd_kind(self, blobs):
        """An assignment-only winner in the cache must not leak into the
        one-pass kernel's tile selection (the v1 cache bug)."""
        x, _ = blobs
        cache = AutotuneCache()
        distinctive = KernelParams(64, 128, 128)
        cache.put(512, 8, 16, distinctive)          # kind="assign"
        km_a = KMeans(8, backend="fused", autotune=cache)
        pa = km_a._resolve_params(512, 16)
        assert pa.block_m == 64
        km_l = KMeans(8, backend="lloyd", autotune=cache)
        pl = km_l._resolve_params(512, 16)
        assert pl.block_m != 64                     # fell through to model

    def test_lloyd_vmem_model_is_shape_aware(self):
        p = KernelParams(256, 128, 512)
        assert ops.lloyd_vmem_bytes(p, 128, 512) > p.vmem_bytes()
        assert feasible(p, kind="lloyd", shape=(4096, 128, 512))
        # a feature axis too wide for the stashed row tile is infeasible
        assert not feasible(KernelParams(1024, 128, 1024), kind="lloyd",
                            shape=(65536, 128, 200_000))

    def test_select_params_lloyd_kind(self):
        variant, p = select_params(4096, 128, 256, mode="model", kind="lloyd")
        assert feasible(p, kind="lloyd", shape=(4096, 128, 256),
                        variant=variant)
        with pytest.raises(ValueError, match="kind"):
            select_params(4096, 128, 256, kind="one_pass")  # pipeline word

    def test_select_params_infeasible_lloyd_shape_is_a_clear_error(self):
        """When K*F makes the resident partial-sum block exceed VMEM for
        every tile candidate, the selector explains itself instead of
        dying on a bare assert."""
        with pytest.raises(ValueError, match="two-pass"):
            select_params(65536, 8192, 65536, mode="model", kind="lloyd")

    def test_measure_mode_ranks_real_kernels(self):
        """The fixed measure path: seeded-random inputs, precompiled
        callee, per-call sync — returns sane positive wall-times and a
        feasible winner on a tiny shape."""
        s = measure_score(64, 8, 32, KernelParams(64, 128, 128), iters=2)
        assert s > 0.0
        space = [KernelParams(64, 128, 128), KernelParams(128, 128, 128)]
        variant, p = select_params(64, 8, 32, mode="measure", space=space)
        assert p in space and variant in ("generic", "smallk")


class TestTrafficModel:
    def test_one_pass_reads_x_once(self):
        """Acceptance: with K inside one centroid tile (the benchmark's
        default shape), the one-pass model charges exactly one HBM read
        of padded X per iteration; two-pass re-reads it for the update."""
        m, k, f = 16_384, 128, 128
        p = ops.clamp_params(m, k, f, KernelParams())
        one = iteration_traffic(m, k, f, p, pipeline="one_pass")
        two = iteration_traffic(m, k, f, p, pipeline="two_pass")
        mp = -(-m // p.block_m) * p.block_m
        fp = -(-f // p.block_f) * p.block_f
        assert one["x_read"] == mp * fp * 4       # exactly one pass over X
        assert one["update_x_reread"] == 0 and one["prep"] == 0
        assert two["update_x_reread"] > 0 and two["prep"] > 0
        assert one["total"] < two["total"]

    def test_multi_tile_k_charges_per_centroid_tile(self):
        m, k, f = 4096, 512, 128
        p = KernelParams(256, 128, 128)
        one = iteration_traffic(m, k, f, p, pipeline="one_pass")
        assert one["x_read"] == 4096 * 128 * 4 * (512 // 128)
        with pytest.raises(ValueError):
            iteration_traffic(m, k, f, p, pipeline="lloyd")  # kind != pipeline
