"""2D-mesh (rows x problems) distribution tests: bit-identity of the
hierarchical reduce, compressed-hop tolerance, error-feedback convergence,
and the combined row-sharded batched mode.

Bit-identity methodology: integer-valued f32 data keeps every partial sum
exact (well below 2^24), so psum order — flat vs two-hop, 1 vs 8 shards —
cannot perturb a single bit and ``==`` comparisons are meaningful.
"""
import textwrap

import pytest

from _mesh import run_with_devices

pytestmark = pytest.mark.multidevice


def _run(body: str, **kw) -> str:
    """Prefix the shared prelude (already column-0) onto a dedented test
    body — run_with_devices' own dedent would otherwise see the mixed
    indentation as having no common prefix."""
    return run_with_devices(_PRELUDE + textwrap.dedent(body), **kw)


_PRELUDE = """
import numpy as np
import jax, jax.numpy as jnp
from repro.api import BatchedKMeans, KMeans
from repro.dist.kmeans_dist import DistributedKMeans
from repro.dist.reduce import ReducePlan
from repro.dist.sharding import mesh2d

def int_blobs(seed, m, f, lo=-20, hi=20):
    rng = np.random.default_rng(seed)
    x = rng.integers(lo, hi, size=(m, f)).astype(np.float32)
    c0 = x[rng.choice(m, size=8, replace=False)].copy()
    return x, c0

def fit(mesh, x, c0, plan=None, **kw):
    est = KMeans(8, max_iter=15, tol=1e-4, random_state=0, **kw)
    d = DistributedKMeans(est, mesh, reduce=plan)
    c, am, inertia, iters, det = d.fit(d.shard_data(x), c0)
    return np.asarray(c), np.asarray(am), float(inertia), int(iters), int(det)
"""


class TestMesh2DBitIdentity:
    def test_2d_exact_matches_single_device_bitwise(self):
        """The tentpole identity: an 8-device (2 hosts x 4 rows) fit with
        the exact hierarchical reduce is bit-identical to the same fit on
        one device — centroids, assignments, iteration count — and the
        flat-plan fit matches both (psum order is invisible on integer
        data)."""
        out = _run("""
        x, c0 = int_blobs(3, 1680, 16)
        c1, am1, in1, it1, det1 = fit(mesh2d(1), x, c0)
        c8, am8, in8, it8, det8 = fit(mesh2d(8, hosts=2), x, c0)
        cf, amf, inf_, itf, detf = fit(mesh2d(8, hosts=2), x, c0,
                                       plan=ReducePlan.flat())
        print("CENTS", bool((c1 == c8).all()), bool((c1 == cf).all()))
        print("ASSIGN", bool((am1 == am8).all()))
        print("ITERS", it1, it8, itf)
        # inertia psums f32 squared distances (not integers): sum order
        # is visible in the last ulps, so closeness — not equality
        print("INERTIA", abs(in8 - in1) <= 1e-6 * abs(in1), det8)
        """)
        assert "CENTS True True" in out
        assert "ASSIGN True" in out
        its = out.split("ITERS ")[1].split()[:3]
        assert its[0] == its[1] == its[2]
        assert "INERTIA True 0" in out

    def test_2d_exact_matches_api_estimator(self):
        """Cross-driver sanity: the 2D-mesh solution agrees with the
        single-device ``repro.api.KMeans`` fit on the same seeds (inertia
        within float tolerance — the api driver is a different code
        path, so this is a closeness check, not bit-identity)."""
        out = _run("""
        x, c0 = int_blobs(5, 1680, 16)
        c8, am8, in8, it8, det8 = fit(mesh2d(8, hosts=2), x, c0)
        ref = KMeans(8, max_iter=15, tol=1e-4, random_state=0).fit(
            x, centroids=c0)
        rel = abs(in8 - float(ref.inertia_)) / abs(float(ref.inertia_))
        print("REL", rel)
        """)
        assert float(out.split("REL ")[1].split()[0]) < 1e-3

    def test_ft_backend_hierarchical_checksums_clean(self):
        """The protected one-pass path composes with the two-hop reduce:
        checksums re-verify after each hop, a clean run reports zero
        detections, and the result stays bit-identical to flat."""
        out = _run("""
        from repro.api import FaultPolicy
        x, c0 = int_blobs(7, 1680, 16)
        kw = dict(fault=FaultPolicy.correct(update_dmr=False))
        ch, amh, inh, ith, deth = fit(mesh2d(8, hosts=2), x, c0, **kw)
        cf, amf, inf_, itf, detf = fit(mesh2d(8, hosts=2), x, c0,
                                       plan=ReducePlan.flat(), **kw)
        print("SAME", bool((ch == cf).all()), deth, detf)
        """)
        assert "SAME True 0 0" in out


class TestCompressedHop:
    def test_compressed_fit_within_tolerance_and_exact_hatch(self):
        """Routing the cross-host hop through int8+EF keeps the fit close
        to the exact solution (same iteration count, small relative
        centroid error), while ``exact=True`` — the escape hatch — stays
        bit-identical to the default plan."""
        out = _run("""
        x, c0 = int_blobs(11, 1680, 16)
        ce, ame, ine, ite, dete = fit(mesh2d(8, hosts=2), x, c0)
        cc, amc, inc, itc, detc = fit(mesh2d(8, hosts=2), x, c0,
                                      plan=ReducePlan.compressed())
        ch, amh, inh, ith, deth = fit(mesh2d(8, hosts=2), x, c0,
                                      plan=ReducePlan.compressed(exact=True))
        scale = float(np.abs(ce).max())
        rel_c = float(np.abs(cc - ce).max()) / scale
        rel_in = abs(inc - ine) / abs(ine)
        print("HATCH", bool((ch == ce).all()))
        print("RELC", rel_c, "RELIN", rel_in, "DET", detc)
        """)
        assert "HATCH True" in out
        assert float(out.split("RELC ")[1].split()[0]) < 0.15
        assert float(out.split("RELIN ")[1].split()[0]) < 0.02
        # quantization error must never trip the hop checksums
        assert int(out.split("DET ")[1].split()[0]) == 0

    def test_error_feedback_converges_to_exact_fixed_point(self):
        """EF telescoping across the real cross-host hop: repeatedly
        reducing a FIXED per-host contribution with the residual carry,
        the time-averaged reduction converges to the exact psum (err at
        T=32 is an order of magnitude under err at T=1)."""
        out = _run("""
        from jax.sharding import PartitionSpec as P
        from repro.dist.compression import compressed_psum

        mesh = mesh2d(8, hosts=8)   # 8 "hosts", pure cross-host reduce
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal((8, 512)).astype(np.float32))

        def hop(gl, res):
            red, res_n = compressed_psum(gl[0] + res[0], "host")
            return red[None], res_n[None]

        step = jax.jit(jax.shard_map(
            hop, mesh=mesh,
            in_specs=(P("host", None), P("host", None)),
            out_specs=(P("host", None), P("host", None)),
            check_vma=False))
        exact = jnp.sum(g, axis=0)
        res = jnp.zeros_like(g)
        total = jnp.zeros_like(exact)
        errs = {}
        for t in range(1, 33):
            red, res = step(g, res)
            total = total + red[0]
            if t in (1, 32):
                errs[t] = float(jnp.max(jnp.abs(total / t - exact))
                                / jnp.max(jnp.abs(exact)))
        print("ERR1", errs[1], "ERR32", errs[32])
        """)
        e1 = float(out.split("ERR1 ")[1].split()[0])
        e32 = float(out.split("ERR32 ")[1].split()[0])
        assert e32 < e1 / 8 + 1e-7


class TestCombinedMode:
    def test_row_sharded_problems_bit_identical(self):
        """rows x problems: a (2 hosts x 1 row) x 4-problem mesh runs each
        problem row-sharded with a hierarchical per-problem reduce and
        reproduces the single-device BatchedKMeans fit bit-for-bit
        (integer data, no empty clusters)."""
        out = _run("""
        rng = np.random.default_rng(2)
        B, N, K, F = 4, 480, 5, 12
        x = rng.integers(-15, 15, size=(B, N, F)).astype(np.float32)
        c0 = np.stack([xb[rng.choice(N, K, replace=False)] for xb in x])

        ref = BatchedKMeans(n_clusters=K, max_iter=10, tol=1e-4,
                            random_state=0)
        ref.fit(x, centroids=jnp.asarray(c0))
        cref = np.asarray(ref.cluster_centers_)

        mesh = mesh2d(2, problems=4, hosts=2)
        d = DistributedKMeans(BatchedKMeans(n_clusters=K, max_iter=10,
                                            tol=1e-4, random_state=0), mesh)
        c, am, inertia, iters, det = d.fit(d.shard_data(x),
                                           jnp.asarray(c0))
        print("SAME", bool((np.asarray(c) == cref).all()))
        print("ITERS", list(np.asarray(iters)))
        """)
        assert "SAME True" in out

    def test_combined_rejects_int8_hop(self):
        """The int8 transport carries one residual per host group — a
        single-problem contract; the combined mode must refuse it loudly
        rather than silently biasing per-problem updates."""
        out = _run("""
        mesh = mesh2d(2, problems=4, hosts=2)
        d = DistributedKMeans(BatchedKMeans(n_clusters=4, max_iter=3,
                                            random_state=0), mesh,
                              reduce=ReducePlan.compressed())
        x = np.zeros((4, 64, 8), np.float32)
        c0 = jnp.zeros((4, 4, 8), jnp.float32)
        try:
            d.fit(d.shard_data(x), c0)
            print("RAISED False")
        except NotImplementedError:
            print("RAISED True")
        """)
        assert "RAISED True" in out


class TestShardShapeKeys:
    def test_autotune_shard_shape(self):
        """Per-shard autotune keys: winners resolve at (m/shards, k, f);
        non-divisible row counts are a hard error (padding would bias the
        update sums)."""
        from repro.core.autotune import shard_shape
        assert shard_shape(4096, 16, 256, 8) == (512, 16, 256)
        assert shard_shape(4096, 16, 256, 1) == (4096, 16, 256)
        with pytest.raises(ValueError):
            shard_shape(4097, 16, 256, 8)
        with pytest.raises(ValueError):
            shard_shape(4096, 16, 256, 0)

    def test_mesh2d_validation(self):
        """mesh2d is host-count aware and refuses ragged host groups."""
        from repro.dist import sharding as sh
        with pytest.raises(ValueError):
            sh.mesh2d(3, hosts=2)
        with pytest.raises(ValueError):
            sh.mesh2d(0)
