"""One-pass FT Lloyd (kernels/lloyd_step_ft.py + the unified protection
stack): clean parity with the unprotected one-pass kernel, in-kernel SEU
correction in both verification intervals (distance GEMM + update
epilogue), dtype-aware detection thresholds, campaign rate semantics, the
lloyd_ft autotune kind, and policy/estimator wiring.

Kernels run interpret=True (kernel bodies execute in Python on CPU)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.api import (AutotuneCache, BackendCapabilityError, FaultPolicy,
                       InjectionCampaign, KMeans, get_backend, list_backends)
from repro.core import checksum
from repro.core.autotune import feasible, model_score, select_params
from repro.core.fault import (draw_step_injection, no_step_injection,
                              planned_injections)
from repro.data.blobs import make_blobs
from repro.kernels import distance_argmin_ft as daft
from repro.kernels import ops, ref
from repro.kernels.distance_argmin import MIN_INIT
from repro.kernels.lloyd_step_ft import INJ_LEN, make_injection, no_injection
from repro.kernels.ops import KernelParams

# smallk-shaped (K fits one centroid tile) and generic-shaped (it doesn't);
# the FT template always runs the generic grid, but both regimes must hold
SHAPES = [
    (64, 8, 32),              # smallk-shaped, tiny: block clamping
    (300, 7, 33),             # smallk-shaped, ragged
    (256, 128, 512),          # exactly one tile
    (513, 129, 257),          # generic-shaped: one past a block boundary
]


def _data(m, k, f, seed=0, dtype=jnp.float32):
    kx, kc = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kx, (m, f), dtype),
            jax.random.normal(kc, (k, f), dtype))


class TestFusedLloydFtParity:
    @pytest.mark.parametrize("m,k,f", SHAPES)
    def test_clean_matches_unprotected_bit_identical(self, m, k, f):
        x, c = _data(m, k, f)
        am0, md0, sums0, cnt0 = ops.fused_lloyd(x, c, interpret=True)
        am, md, sums, cnt, det = ops.fused_lloyd_ft(x, c, interpret=True)
        assert int(det) == 0
        np.testing.assert_array_equal(np.asarray(am), np.asarray(am0))
        np.testing.assert_array_equal(np.asarray(sums), np.asarray(sums0))
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt0))
        np.testing.assert_allclose(md, md0, rtol=1e-6)

    def test_plan_reuse_matches_unplanned_call(self):
        x, c = _data(300, 77, 130, seed=5)
        params = ops.clamp_params(300, 77, 130, KernelParams())
        plan = ops.plan_data(x, params)
        a1 = ops.fused_lloyd_ft(plan, c, interpret=True)
        a2 = ops.fused_lloyd_ft(x, c, params, interpret=True)
        for got, want in zip(a1, a2):
            np.testing.assert_allclose(got, want, rtol=1e-6)


class TestUpdateEpilogueExactness:
    """The f32 update epilogue splits X into three bf16 slices, hi + mid +
    lo == x, and sums the three one-hot products. With every row its own
    cluster each sum holds one row, so it must equal that row bit for bit:
    a split into two slices, or a dropped slice, loses its low bits."""

    @pytest.mark.parametrize("lloyd", [ops.fused_lloyd, ops.fused_lloyd_ft])
    @pytest.mark.parametrize("m,f", [(256, 128), (300, 33)])
    def test_singleton_sums_equal_rows_bit_for_bit(self, lloyd, m, f):
        x = jax.random.normal(jax.random.PRNGKey(11), (m, f), jnp.float32)
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        mid = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
        # the rows use the full 24-bit mantissa: two slices fall short
        assert float(jnp.mean((hi + mid != x).astype(jnp.float32))) > 0.9
        out = lloyd(x, x, interpret=True)           # C = X, K = M
        np.testing.assert_array_equal(np.asarray(out[0]), np.arange(m))
        np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(out[3]), np.ones(m))

    @pytest.mark.parametrize("lloyd", [ops.fused_lloyd, ops.fused_lloyd_ft])
    def test_sums_match_reference(self, lloyd):
        kx, kc = jax.random.split(jax.random.PRNGKey(12))
        # positive rows: no cancellation, so rtol bounds every sum
        x = jax.random.uniform(kx, (512, 128), jnp.float32, 0.5, 1.5)
        c = jax.random.uniform(kc, (64, 128), jnp.float32, 0.5, 1.5)
        out = lloyd(x, c, interpret=True)
        rsums, rcounts = ref.centroid_update(x, out[0], 64)
        np.testing.assert_allclose(out[2], rsums, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(out[3]), np.asarray(rcounts))


class TestInjectionCorrection:
    # injections address tile coordinates -> pin the tile parameters
    PARAMS = KernelParams(block_m=256, block_k=128, block_f=512)

    @pytest.fixture(scope="class")
    def clean(self):
        x, c = _data(512, 256, 1024, seed=6)
        return (x, c) + ops.fused_lloyd_ft(x, c, self.PARAMS, interpret=True)

    @pytest.mark.parametrize("tile", [(0, 0, 0), (1, 1, 0), (0, 1, 1)])
    @pytest.mark.parametrize("delta", [1e4, -1e4])
    def test_distance_seu_corrected(self, clean, tile, delta):
        x, c, am0, md0, sums0, cnt0, det0 = clean
        inj = make_injection(distance=(*tile, 13, 57, delta))
        am, md, sums, cnt, det = ops.fused_lloyd_ft(
            x, c, self.PARAMS, inj=inj, interpret=True)
        assert int(det) == 1
        np.testing.assert_array_equal(np.asarray(am), np.asarray(am0))
        np.testing.assert_array_equal(np.asarray(sums), np.asarray(sums0))
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt0))

    @pytest.mark.parametrize("m_tile,row,col", [(0, 0, 0), (1, 5, 100),
                                                (1, 250, 1023)])
    @pytest.mark.parametrize("delta", [1e6, -1e6])
    def test_update_seu_recomputed_bit_identical(self, clean, m_tile, row,
                                                 col, delta):
        """An SEU in the one-hot update product is detected by the e1/e2
        epilogue checksums and the tile recomputed in the tree-reduction
        — replaying the kernel's own arithmetic, so the recovered sums
        are bit-identical to a clean run."""
        x, c, am0, md0, sums0, cnt0, det0 = clean
        inj = make_injection(update=(m_tile, row, col, delta))
        am, md, sums, cnt, det = ops.fused_lloyd_ft(
            x, c, self.PARAMS, inj=inj, interpret=True)
        assert int(det) == 1
        np.testing.assert_array_equal(np.asarray(am), np.asarray(am0))
        np.testing.assert_array_equal(np.asarray(sums), np.asarray(sums0))
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt0))

    def test_dual_seu_both_intervals_corrected(self, clean):
        """One step exposes two independently verified intervals; a draw
        in each is corrected independently (det counts both)."""
        x, c, am0, md0, sums0, cnt0, det0 = clean
        inj = make_injection(distance=(0, 0, 1, 3, 7, -2e4),
                             update=(0, 2, 33, 5e5))
        am, md, sums, cnt, det = ops.fused_lloyd_ft(
            x, c, self.PARAMS, inj=inj, interpret=True)
        assert int(det) == 2
        np.testing.assert_array_equal(np.asarray(am), np.asarray(am0))
        np.testing.assert_array_equal(np.asarray(sums), np.asarray(sums0))

    def test_descriptor_layout(self):
        assert no_injection().shape == (INJ_LEN,)
        both = make_injection(distance=(0, 0, 0, 1, 2, 3.0),
                              update=(1, 4, 5, 6.0))
        assert int(both[0]) == 1 and int(both[7]) == 1
        only_u = make_injection(update=(1, 4, 5, 6.0))
        assert int(only_u[0]) == 0 and int(only_u[7]) == 1


def _run_ft(kernel, x, c, params, inj=None):
    """Both FT kernels' outputs by name: the one-pass Lloyd step, and the
    FT assignment alone (no sums or counts)."""
    if kernel == "lloyd_ft":
        am, md, sums, cnt, det = ops.fused_lloyd_ft(x, c, params, inj=inj,
                                                    interpret=True)
        return dict(am=am, md=md, sums=sums, cnt=cnt, det=det)
    am, md, det = ops.fused_assign_ft(x, c, params, inj=inj, interpret=True)
    return dict(am=am, md=md, det=det)


class TestLazyLocate:
    """Detection runs on every distance tile; location and correction only
    on a flagged one. On integer data every product, checksum and residual
    is exact in f32, so a corrected tile equals the clean tile bit for bit
    and every output of an injected run must equal the clean run's, the
    minimum distance included: a location or correction off by one element
    or one bit shows."""

    PARAMS = KernelParams(block_m=256, block_k=128, block_f=128)
    M, K, F = 512, 384, 128            # grid (2, 3, 1): one feature step
    ARGMIN_ROW = 77

    @pytest.fixture(scope="class")
    def data(self):
        kx, kc = jax.random.split(jax.random.PRNGKey(21))
        x = jax.random.randint(kx, (self.M, self.F), -3, 4).astype(jnp.float32)
        c = jax.random.randint(kc, (self.K, self.F), -3, 4).astype(jnp.float32)
        return x, c

    @pytest.fixture(scope="class", params=["lloyd_ft", "assign_ft"])
    def clean(self, request, data):
        out = _run_ft(request.param, *data, self.PARAMS)
        assert int(out["det"]) == 0
        return request.param, out

    def _descriptor(self, kernel, m_tile, c_tile, row, col, delta):
        if kernel == "lloyd_ft":
            return make_injection(distance=(m_tile, c_tile, 0, row, col,
                                             delta))
        return daft.make_injection(m_tile, c_tile, 0, row, col, delta)

    @pytest.mark.parametrize("where", [
        (0, 0, 0, 0),                  # first row and column of a tile
        (1, 0, 255, 127),              # last row and column
        (0, 2, 0, 127),                # c_idx > 0, first row, last column
        (1, 1, 255, 0),                # c_idx > 0, last row, first column
        "argmin",                      # the element that is its row's min
    ])
    @pytest.mark.parametrize("delta", [2048.0, -2048.0])
    def test_seu_corrected_bit_equal_to_clean(self, data, clean, where,
                                              delta):
        kernel, want = clean
        if where == "argmin":
            row = self.ARGMIN_ROW
            col = int(want["am"][row])
            where = (row // self.PARAMS.block_m, col // self.PARAMS.block_k,
                     row % self.PARAMS.block_m, col % self.PARAMS.block_k)
        inj = self._descriptor(kernel, *where, delta)
        got = _run_ft(kernel, *data, self.PARAMS, inj=inj)
        assert int(got["det"]) == 1
        for name in want:
            if name != "det":
                np.testing.assert_array_equal(np.asarray(got[name]),
                                              np.asarray(want[name]), name)

    def test_clean_tile_with_negative_zeros_passes_bit_unchanged(self):
        """A tile that detects nothing skips location and correction: the
        accumulator holds its -0.0 entries bit for bit (an added +0.0
        correction would turn them into +0.0), and the min/argmin comes
        from the tile as it was."""
        bm, bk = 64, 128
        kx, kc = jax.random.split(jax.random.PRNGKey(22))
        acc = jax.random.randint(kx, (bm, bk), -40, 41).astype(jnp.float32)
        acc = jnp.where(acc == 0, -0.0, acc)
        assert int(jnp.sum(jnp.signbit(acc) & (acc == 0))) > 0
        cn = jax.random.randint(kc, (1, bk), 0, 200).astype(jnp.float32)
        w_m = jnp.arange(1, bm + 1, dtype=jnp.float32)[:, None]
        w_k = jnp.arange(1, bk + 1, dtype=jnp.float32)[None, :]
        sums = (jnp.sum(acc, 0, keepdims=True),
                jnp.sum(w_m * acc, 0, keepdims=True),
                jnp.sum(acc, 1, keepdims=True),
                jnp.sum(w_k * acc, 1, keepdims=True))

        def kernel(acc_in, col1, col2, row1, row2, cn_ref,
                   acc_ref, mind_ref, argmin_ref, det_ref):
            acc_ref[...] = acc_in[...]
            mind_ref[...] = jnp.full_like(mind_ref, MIN_INIT)
            argmin_ref[...] = jnp.zeros_like(argmin_ref)
            det_ref[...] = jnp.zeros_like(det_ref)
            daft.verify_and_reduce(acc_ref, col1, col2, row1, row2, cn_ref,
                                   mind_ref, argmin_ref, det_ref, 0,
                                   checksum.threshold_factor(self.F))

        out, mind, am, det = pl.pallas_call(
            kernel, interpret=True,
            out_shape=[jax.ShapeDtypeStruct((bm, bk), jnp.float32),
                       jax.ShapeDtypeStruct((bm, 1), jnp.float32),
                       jax.ShapeDtypeStruct((bm, 1), jnp.int32),
                       jax.ShapeDtypeStruct((1, 1, 1), jnp.int32)],
        )(acc, *sums, cn)
        assert int(det[0, 0, 0]) == 0
        np.testing.assert_array_equal(np.asarray(out).view(np.int32),
                                      np.asarray(acc).view(np.int32))
        d = np.asarray(cn) - 2.0 * np.asarray(acc)
        np.testing.assert_array_equal(np.asarray(mind)[:, 0], d.min(axis=1))
        np.testing.assert_array_equal(np.asarray(am)[:, 0], d.argmin(axis=1))


class TestDtypeThresholds:
    def test_threshold_factor_tracks_input_dtype(self):
        f32 = checksum.threshold_factor(1024)
        bf16 = checksum.threshold_factor(1024, jnp.bfloat16)
        fp16 = checksum.threshold_factor(1024, jnp.float16)
        assert f32 == pytest.approx(checksum.default_threshold(1024))
        assert bf16 > fp16 > f32     # eps(bf16) > eps(fp16) > eps(f32)
        assert checksum.default_threshold(
            1024, jnp.float32, input_dtype=jnp.bfloat16) \
            == pytest.approx(bf16)
        # accumulator dtype is the floor
        assert checksum.rounding_eps(jnp.bfloat16) \
            == float(jnp.finfo(jnp.bfloat16).eps)
        assert checksum.rounding_eps(jnp.float32) \
            == float(jnp.finfo(jnp.float32).eps)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16,
                                       jnp.float32])
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_clean_low_precision_zero_detections(self, dtype, seed):
        """False-positive regression (the dtype-threshold footgun): clean
        bf16/fp16 data must never trip the detector, in either the
        distance ABFT or the update-epilogue checksums, over a seeded
        grid of shapes. Clean f32 must not either: its one-hot product is
        taken in three bf16 slices while the expected checksums stay at
        full precision. The last shape has the IVF4096 fit's row tile
        (1024 rows), the update checksums' contraction length."""
        for m, k, f, p in [(256, 16, 64, None), (300, 7, 33, None),
                           (513, 129, 257, None),
                           (2048, 600, 128, KernelParams(1024, 512, 128))]:
            x, c = _data(m, k, f, seed=seed, dtype=dtype)
            _, _, det = ops.fused_assign_ft(x, c, p, interpret=True)
            assert int(det) == 0, (m, k, f, "assign_ft")
            _, _, _, _, det = ops.fused_lloyd_ft(x, c, p, interpret=True)
            assert int(det) == 0, (m, k, f, "lloyd_ft")

    def test_update_thresholds_are_per_checksum_pair(self):
        """Each e1/e2 pair thresholds against its own clean-side
        magnitude: the e2 row runs ~K x larger than e1, and a shared
        scale would raise the e1 detection floor by that factor —
        masking mid-scale deltas at 2-byte dtypes."""
        kx, kc = jax.random.split(jax.random.PRNGKey(4))
        x = jax.random.normal(kx, (512, 512), jnp.bfloat16)
        c = jax.random.normal(kc, (128, 512), jnp.bfloat16)
        p = KernelParams(256, 128, 512)
        _, _, sums0, _, det0 = ops.fused_lloyd_ft(x, c, p, interpret=True)
        assert int(det0) == 0
        for delta in (2.0 ** 13, 2.0 ** 15, -2.0 ** 15):
            inj = make_injection(update=(0, 2, 100, delta))
            _, _, sums, _, det = ops.fused_lloyd_ft(x, c, p, inj=inj,
                                                    interpret=True)
            assert int(det) == 1, delta
            np.testing.assert_array_equal(np.asarray(sums),
                                          np.asarray(sums0))

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
    def test_low_precision_injection_still_detected(self, dtype):
        x, c = _data(512, 256, 512, seed=4, dtype=dtype)
        p = KernelParams(256, 128, 512)
        inj = make_injection(distance=(0, 1, 0, 13, 57, 1e4),
                             update=(1, 3, 40, 1e6))
        am0, _, sums0, cnt0, _ = ops.fused_lloyd_ft(x, c, p, interpret=True)
        am, _, sums, cnt, det = ops.fused_lloyd_ft(x, c, p, inj=inj,
                                                   interpret=True)
        assert int(det) == 2
        np.testing.assert_array_equal(np.asarray(am), np.asarray(am0))
        np.testing.assert_array_equal(np.asarray(sums), np.asarray(sums0))


class TestCampaignRateSemantics:
    def test_rate_boundaries(self):
        rng = np.random.default_rng(0)
        assert planned_injections(rng, 0.0, 2) == 0
        assert all(planned_injections(rng, 1.0, 2) == 1 for _ in range(50))
        # 1 < rate < 2: floor + Bernoulli(frac), both outcomes occur
        draws = {planned_injections(rng, 1.5, 2) for _ in range(200)}
        assert draws == {1, 2}
        # expected count caps at the backend's verified-interval count
        assert all(planned_injections(rng, 3.0, 2) == 2 for _ in range(50))
        assert all(planned_injections(rng, 2.0, 1) == 1 for _ in range(50))

    def test_campaign_validation(self):
        with pytest.raises(ValueError, match="rate"):
            InjectionCampaign(rate=-0.5)
        with pytest.raises(ValueError, match="targets"):
            InjectionCampaign(targets="epilogue")
        lloyd_ft = get_backend("lloyd_ft")
        fused_ft = get_backend("fused_ft")
        camp = InjectionCampaign(targets="both")
        assert camp.resolved_targets(lloyd_ft) == ("distance", "update")
        with pytest.raises(BackendCapabilityError, match="update epilogue"):
            camp.resolved_targets(fused_ft)
        # auto narrows to what the backend protects
        auto = InjectionCampaign()
        assert auto.resolved_targets(lloyd_ft) == ("distance", "update")
        assert auto.resolved_targets(fused_ft) == ("distance",)

    def test_draw_formats_and_dual_slot(self):
        p = KernelParams(256, 128, 512)
        rng = np.random.default_rng(1)
        legacy = draw_step_injection(rng, 512, 8, 64, p, rate=1.0,
                                     kind="assign")
        assert legacy.shape == (8,)
        assert no_step_injection("assign").shape == (8,)
        assert no_step_injection("lloyd_ft").shape == (INJ_LEN,)
        # rate=2 on the dual-interval kernel arms both slots every step
        desc = draw_step_injection(rng, 512, 8, 64, p, rate=2.0,
                                   targets=("distance", "update"),
                                   kind="lloyd_ft")
        assert desc.shape == (INJ_LEN,)
        assert int(desc[0]) == 1 and int(desc[7]) == 1
        # update coordinates address the true (K, F) block
        assert 0 <= int(desc[9]) < 8 and 0 <= int(desc[10]) < 64

    def test_estimator_caps_assign_kind_at_one_per_step(self):
        x, _ = make_blobs(256, 8, 4, seed=0)
        pol = FaultPolicy.correct(
            update_dmr=False,
            injection=InjectionCampaign(rate=2.0, targets="distance"))
        km = KMeans(4, max_iter=3, tol=0.0, fault=pol, backend="fused_ft",
                    sync_every=3, random_state=0).fit(x)
        assert km.detected_errors_ == 3     # one interval -> one per step


class TestAutotuneLloydFtKind:
    def test_select_params_pins_generic(self):
        variant, p = select_params(4096, 64, 256, mode="model",
                                   kind="lloyd_ft")
        assert variant == "generic"       # FT templates keep the full grid
        assert feasible(p, kind="lloyd_ft", shape=(4096, 64, 256))
        assert not feasible(p, kind="lloyd_ft", shape=(4096, 64, 256),
                            variant="smallk")

    def test_model_charges_checksum_overhead(self):
        p = KernelParams(256, 128, 512)
        shape = (16_384, 128, 512)
        assert model_score(*shape, p, kind="lloyd_ft") \
            > model_score(*shape, p, kind="lloyd")
        assert ops.lloyd_ft_vmem_bytes(p, 128, 512) \
            > ops.lloyd_vmem_bytes(p, 128, 512)

    def test_cache_kind_isolation(self):
        """A lloyd winner must not leak into the lloyd_ft lookup (the same
        lesson as assign-vs-lloyd in schema v2)."""
        cache = AutotuneCache()
        distinctive = KernelParams(64, 128, 128)
        cache.put(512, 8, 16, distinctive, kind="lloyd")
        km = KMeans(8, backend="lloyd_ft", autotune=cache,
                    fault=FaultPolicy.correct(update_dmr=False))
        p = km._resolve_params(512, 16)
        assert p.block_m != 64            # fell through to the model

    def test_estimator_resolves_lloyd_ft_kind(self):
        km = KMeans(8, backend="lloyd_ft",
                    fault=FaultPolicy.correct(update_dmr=False))
        assert km._backend.kernel_kind == "lloyd_ft"
        assert get_backend("lloyd").kernel_kind == "lloyd"
        assert get_backend("fused_ft").kernel_kind == "assign"


class TestEstimatorOnePassFt:
    def test_fit_reaches_reference_solution(self):
        x, _ = make_blobs(512, 16, 8, seed=1, spread=0.5)
        km = KMeans(8, max_iter=8, backend="lloyd_ft", sync_every=4,
                    fault=FaultPolicy.correct(update_dmr=False),
                    random_state=0).fit(x)
        ref = KMeans(8, max_iter=8, random_state=0).fit(x)
        assert km.detected_errors_ == 0
        assert abs(km.inertia_ - ref.inertia_) <= abs(ref.inertia_) * 1e-3

    def test_predict_routes_through_protected_assign_kernel(self):
        km = KMeans(8, backend="lloyd_ft",
                    fault=FaultPolicy.correct(update_dmr=False))
        pb = km._predict_backend()
        assert pb.name == "fused_ft"      # same protection level, two-pass
        assert not pb.fuses_update
        km_xla = KMeans(8, backend="lloyd_ft_xla",
                        fault=FaultPolicy.correct(update_dmr=False))
        assert km_xla._predict_backend().name == "abft_offline"

    def test_registry_capabilities(self):
        b = list_backends()
        assert b["lloyd_ft"].supports_ft and b["lloyd_ft"].fuses_update
        assert b["lloyd_ft"].takes_params and b["lloyd_ft"].takes_injection
        assert b["lloyd_ft"].protected_intervals == 2
        assert b["fused_ft"].protected_intervals == 1
        assert b["lloyd_ft_xla"].supports_ft \
            and b["lloyd_ft_xla"].fuses_update
        assert not b["lloyd_ft_xla"].takes_injection

    def test_state_round_trip_preserves_targets(self):
        x, _ = make_blobs(256, 8, 4, seed=2)
        pol = FaultPolicy.correct(
            update_dmr=False,
            injection=InjectionCampaign(rate=1.0, targets="update"))
        km = KMeans(4, max_iter=3, fault=pol, sync_every=3,
                    random_state=0).fit(x)
        km2 = KMeans.from_state(km.get_state())
        assert km2.fault.injection.targets == "update"
        assert km2.fault == km.fault

    def test_update_dmr_subsumed_not_fatal(self):
        # the default (update_dmr=None, auto) is silent on the one-pass
        # FT backend; an *explicit* True draws the deprecation note
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            km = KMeans(4, fault=FaultPolicy.correct())
        assert km._backend.fuses_update
        assert not km._use_dmr
        assert not any(issubclass(i.category, DeprecationWarning)
                       for i in w)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            KMeans(4, fault=FaultPolicy.correct(update_dmr=True))
        assert any(issubclass(i.category, DeprecationWarning) and
                   "subsumes DMR" in str(i.message) for i in w)
        # auto keeps DMR on for two-pass backends (the legacy default)
        km_two = KMeans(4, fault=FaultPolicy.detect(),
                        backend="abft_offline")
        assert km_two._use_dmr
