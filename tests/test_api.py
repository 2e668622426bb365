"""repro.api surface: estimator round-trips, streaming partial_fit,
FaultPolicy matrix, backend-registry capabilities, injectable autotune,
and the one-way layering beneath it."""
import ast
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (AssignmentBackend, AutotuneCache,
                       BackendCapabilityError, FaultPolicy, InjectionCampaign,
                       KMeans, NotFittedError, get_backend, list_backends,
                       register_backend)
from repro.data.blobs import make_blobs


@pytest.fixture(scope="module")
def blobs():
    return make_blobs(4000, 24, 8, seed=1, spread=0.5)


def _purity(assign, labels, k):
    assign, labels = np.asarray(assign), np.asarray(labels)
    total = 0
    for j in range(k):
        members = labels[assign == j]
        if len(members):
            total += np.bincount(members).max()
    return total / len(labels)


class TestEstimator:
    def test_fit_predict_equals_fit_then_predict(self, blobs):
        x, _ = blobs
        lab = KMeans(8, max_iter=30, random_state=0).fit_predict(x)
        km = KMeans(8, max_iter=30, random_state=0).fit(x)
        assert np.array_equal(np.asarray(lab), np.asarray(km.predict(x)))

    def test_fit_recovers_clusters(self, blobs):
        x, labels = blobs
        km = KMeans(8, max_iter=50, tol=1e-5, random_state=0).fit(x)
        assert km.n_iter_ < 50
        assert _purity(km.labels_, labels, 8) > 0.95
        assert km.inertia_ == pytest.approx(-km.score(x), rel=1e-5)

    def test_transform_shape_and_consistency(self, blobs):
        x, _ = blobs
        km = KMeans(8, max_iter=20, random_state=0).fit(x)
        d = km.transform(x[:100])
        assert d.shape == (100, 8)
        assert np.array_equal(np.asarray(jnp.argmin(d, axis=1)),
                              np.asarray(km.predict(x[:100])))

    def test_predict_before_fit_raises(self, blobs):
        x, _ = blobs
        with pytest.raises(NotFittedError):
            KMeans(8).predict(x)

    def test_state_round_trip(self, blobs):
        x, _ = blobs
        km = KMeans(8, max_iter=25,
                    fault=FaultPolicy.correct(), random_state=3).fit(x)
        st = km.get_state()
        km2 = KMeans.from_state(st)
        assert km2.fault == km.fault
        assert np.array_equal(np.asarray(km2.predict(x)),
                              np.asarray(km.labels_))
        # the state dict is plain: survives a numpy savez round trip
        import io
        buf = io.BytesIO()
        np.savez(buf, centers=st["cluster_centers"])
        buf.seek(0)
        back = np.load(buf)["centers"]
        assert np.array_equal(back, st["cluster_centers"])


class TestPartialFit:
    def test_streamed_blobs_converge(self, blobs):
        x, labels = blobs
        km = KMeans(8, random_state=0)
        for epoch in range(4):
            for i in range(0, x.shape[0], 500):
                km.partial_fit(x[i:i + 500])
        assert _purity(km.predict(x), labels, 8) > 0.85

    def test_fit_with_batch_size_uses_minibatches(self, blobs):
        x, labels = blobs
        km = KMeans(8, max_iter=30, batch_size=1024, random_state=0).fit(x)
        assert _purity(km.labels_, labels, 8) > 0.85

    def test_streaming_state_survives_round_trip(self, blobs):
        x, _ = blobs
        km = KMeans(8, random_state=0)
        km.partial_fit(x[:1000])
        km.partial_fit(x[1000:2000])
        km2 = KMeans.from_state(km.get_state())
        km2.partial_fit(x[2000:3000])
        km.partial_fit(x[2000:3000])
        np.testing.assert_allclose(np.asarray(km.cluster_centers_),
                                   np.asarray(km2.cluster_centers_),
                                   rtol=1e-6)


class TestFaultPolicyMatrix:
    @pytest.mark.parametrize("mode", ["off", "detect", "correct"])
    @pytest.mark.parametrize("update_dmr", [False, True])
    def test_policy_matrix_reaches_same_solution(self, blobs, mode,
                                                 update_dmr):
        x, _ = blobs
        policy = FaultPolicy(mode=mode, update_dmr=update_dmr)
        km = KMeans(8, max_iter=30, fault=policy, random_state=0).fit(x)
        ref = KMeans(8, max_iter=30, random_state=0).fit(x)
        assert abs(km.inertia_ - ref.inertia_) <= abs(ref.inertia_) * 1e-3
        if mode != "detect":
            # clean run: the fused kernel's threshold never fires. The
            # offline baseline's materialized-product threshold is tighter
            # and may flag fp accumulation noise (it recomputes, so the
            # solution above is still exact) — the paper's argument for
            # fusion, so no zero-detection assert there.
            assert km.detected_errors_ == 0

    def test_policy_resolution_picks_expected_backends(self):
        assert FaultPolicy.off().resolve_backend(on_tpu=False).name \
            == "gemm_fused"
        assert FaultPolicy.off().resolve_backend(on_tpu=True).name == "fused"
        assert FaultPolicy.detect().resolve_backend(on_tpu=False).name \
            == "abft_offline"
        # correct-mode protection composes with the one-pass iteration:
        # enabling FT must not forfeit the fused-update speedup
        assert FaultPolicy.correct().resolve_backend(on_tpu=False).name \
            == "lloyd_ft_xla"
        tpu = FaultPolicy.correct().resolve_backend(on_tpu=True)
        assert tpu.name == "lloyd_ft"
        assert tpu.fuses_update and tpu.supports_ft and tpu.takes_injection
        # campaigns always need the in-kernel injection surface
        camp = FaultPolicy.correct(injection=InjectionCampaign(rate=1.0))
        assert camp.resolve_backend(on_tpu=False).name == "lloyd_ft"

    def test_injection_campaign_detected_and_corrected(self, blobs):
        x, _ = blobs
        clean = KMeans(8, max_iter=30, fault=FaultPolicy.correct(),
                       random_state=0).fit(x)
        noisy = KMeans(8, max_iter=30, fault=FaultPolicy.correct(
            injection=InjectionCampaign(rate=1.0)), random_state=0).fit(x)
        assert noisy.detected_errors_ > 0
        assert abs(noisy.inertia_ - clean.inertia_) \
            <= abs(clean.inertia_) * 1e-3

    def test_injection_requires_correcting_mode(self):
        with pytest.raises(ValueError):
            FaultPolicy(mode="off", injection=InjectionCampaign())

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError):
            FaultPolicy(mode="detect_and_pray")


class TestRegistry:
    def test_builtin_ladder_registered_with_capabilities(self):
        backends = list_backends()
        for name in ("gemm_fused", "fused", "fused_ft", "abft_offline"):
            assert name in backends
        assert backends["fused_ft"].supports_ft
        assert backends["fused_ft"].takes_injection
        assert not backends["gemm_fused"].supports_ft
        assert not backends["abft_offline"].takes_injection

    def test_injection_into_non_ft_backend_rejected(self):
        policy = FaultPolicy.correct(injection=InjectionCampaign(rate=1.0))
        with pytest.raises(BackendCapabilityError):
            KMeans(4, fault=policy, backend="abft_offline")

    def test_protected_policy_rejects_unprotected_backend(self):
        with pytest.raises(BackendCapabilityError):
            KMeans(4, fault=FaultPolicy.correct(), backend="gemm_fused")

    def test_direct_injection_call_rejected(self):
        b = get_backend("gemm_fused")
        x = jnp.ones((16, 8))
        c = jnp.ones((4, 8))
        with pytest.raises(BackendCapabilityError):
            b(x, c, inj=jnp.zeros((8,), jnp.int32))

    def test_custom_backend_registration(self, blobs):
        x, _ = blobs

        def silly(xx, cc):
            d = jnp.sum((xx[:, None, :] - cc[None]) ** 2, axis=-1)
            return (jnp.argmin(d, axis=1).astype(jnp.int32),
                    jnp.min(d, axis=1), jnp.zeros((), jnp.int32))

        register_backend(AssignmentBackend("test_custom", silly))
        try:
            km = KMeans(8, max_iter=10, backend="test_custom",
                        random_state=0).fit(x[:512])
            assert km.cluster_centers_.shape == (8, x.shape[1])
        finally:
            list_backends()   # registry snapshot still sane
            from repro.api.registry import _REGISTRY
            _REGISTRY.pop("test_custom", None)


class TestLayers:
    def test_core_never_imports_api(self):
        """The layers point one way, api -> core -> kernels: no import
        under repro.core names repro.api, at module level or inside a
        function, and the distributed driver takes only the api
        estimators."""
        import repro.core
        core = pathlib.Path(repro.core.__file__).parent
        offenders = []
        for path in sorted(core.rglob("*.py")):
            pkg = ["repro", "core", *path.relative_to(core).parent.parts]
            for node in ast.walk(ast.parse(path.read_text())):
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    base = pkg[:len(pkg) + 1 - node.level] if node.level else []
                    mod = ".".join(base + ([node.module] if node.module else []))
                    names = [mod] + [f"{mod}.{a.name}" for a in node.names]
                else:
                    continue
                if any(n == "repro.api" or n.startswith("repro.api.")
                       for n in names):
                    offenders.append(f"{path.name}:{node.lineno}")
        assert offenders == []

        from repro.dist.kmeans_dist import DistributedKMeans
        with pytest.raises(TypeError, match="repro.api.BatchedKMeans"):
            DistributedKMeans({"k": 8, "max_iters": 25}, None)


class TestAutotuneInjection:
    def test_estimator_uses_injected_cache(self, tmp_path, blobs):
        x, _ = blobs
        from repro.kernels.ops import KernelParams
        cache = AutotuneCache(str(tmp_path / "t.json"))
        # seed the exact shape bucket fit() will look up, with a
        # distinctive block_m no model winner would pick for this shape
        cache.put(1024, 8, 16, KernelParams(64, 128, 128))
        km = KMeans(8, max_iter=5, backend="fused", autotune=cache,
                    random_state=0)
        km.fit(x[:1024, :16])
        # the estimator consulted *this* cache, not a module global
        p = km._resolve_params(1024, 16)
        assert p.block_m == 64
        default = KMeans(8, backend="fused")._resolve_params(1024, 16)
        assert default.block_m != 64
