"""Chip smoke test: the library's main path, once, on one TPU v5e.

Drives the public entry points at the SIFT1M shape — N=1,048,576 rows,
F=128 features, K=1024 clusters, f32 (512 MiB of data on the device),
generated by ``make_blobs`` from ``--seed``:

  (a) ``KMeans`` fit, unprotected: the default backend, then the one-pass
      ``lloyd`` kernel;
  (b) ``KMeans`` fit under ``FaultPolicy.correct()`` from the same init:
      clean, then under an SEU campaign on both verified intervals — the
      errors are detected, and the centroids equal the clean fit's;
  (c) ``BatchedKMeans`` over B=16 problems (N=65,536, K=256), seeded by
      ``kmeans++`` and by the fused ``kmeans++-fused`` kernel;
  (d) serving: the FT model's ``to_service()`` answers requests of 1, 100
      and 1000 rows, each equal to ``KMeans.predict``.

Every fit's step is compared with ``repro.kernels.ref`` on 65,536 rows:
labels (distance ties aside), per-cluster sums and counts, inertia. Each
phase prints one JSON line — the resolved backend, whether the compiled
step holds a Mosaic kernel (``tpu_custom_call``), wall and compile
seconds, peak device bytes — and the last line names the device.

``--chips 4`` runs the distributed path instead, and nothing else: a
row-sharded ``DistributedKMeans`` fit under ``FaultPolicy.correct()`` on
N=4,194,304 rows over a four-chip mesh, against a one-chip ``KMeans`` fit
of the same data from the same init.

Usage: ``python chip_smoke.py [--seed S] [--chips {1,4}]``. The script
needs a TPU: without one it exits non-zero before any phase runs. JAX's
compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

N, F, K = 1 << 20, 128, 1024          # SIFT1M shape
ITERS = 5                              # Lloyd steps per fit (tol=0)
CHECK_ROWS = 65_536                    # rows compared with kernels/ref.py
B, BN, BK = 16, 65_536, 256            # the batched problem stack
DIST_N = 1 << 22                       # four-chip fit: 512 MiB per chip
REQUEST_ROWS = (1, 100, 1000)          # serving requests

# Backends that run a Pallas kernel; their ``*_xla`` analogues do not.
PALLAS_BACKENDS = ("fused", "lloyd", "lloyd_ft", "fused_ft", "lloyd_batched")

# f32 tolerances. A distance carries rounding relative to ||x||^2 + ||c||^2,
# not to the (much smaller) distance itself, so label ties and the inertia
# are judged on that scale; sums relative to their largest magnitude.
TIE_RTOL = 1e-5
INERTIA_RTOL = 1e-6
SUM_RTOL = 1e-5


class Smoke:
    """Phase records and failed checks of one run. Compile seconds come
    from JAX's own compile events (trace, lowering, backend compile)."""

    def __init__(self, jax, device):
        self.jax = jax
        self.device = device
        self.failures: list[str] = []
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.compile_s += secs

    def timed(self, fn):
        """(result, wall seconds, compile seconds) of ``fn()``, waited on."""
        c0, t0 = self.compile_s, time.perf_counter()
        out = self.jax.block_until_ready(fn())
        return out, time.perf_counter() - t0, self.compile_s - c0

    def check(self, phase: str, name: str, ok, detail="") -> bool:
        if not bool(ok):
            self.failures.append(f"{phase}: {name} {detail}".rstrip())
        return bool(ok)

    def report(self, phase: str, **fields):
        stats = self.device.memory_stats() or {}
        fields["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        print(json.dumps({"phase": phase, **fields}), flush=True)


def ref_agreement(x, c, am, md, sums, counts) -> dict:
    """One step's (labels, true squared distances, per-cluster sums and
    counts) on ``x`` at centroids ``c``, against ``repro.kernels.ref``."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    d = ref.distance_matrix(x, c)
    d_min = jnp.min(d, axis=1)
    d_lab = jnp.take_along_axis(d, am[:, None], axis=1)[:, 0]
    scale = (jnp.sum(x * x, axis=1)
             + jnp.sum(c * c, axis=1)[am])          # ||x||^2 + ||c_label||^2
    ref_sums, ref_counts = ref.centroid_update(x, am, c.shape[0])
    d_min, d_lab, scale, md, am_ref, am = (
        np.asarray(v, np.float64) for v in
        (d_min, d_lab, scale, md, jnp.argmin(d, axis=1), am))
    sums, ref_sums = np.asarray(sums), np.asarray(ref_sums)
    return {
        "label_mismatches": int(np.sum(am != am_ref)),
        "label_mismatches_off_tie": int(np.sum(
            d_lab - d_min > TIE_RTOL * scale)),
        "counts_equal": bool(np.array_equal(np.asarray(counts),
                                            np.asarray(ref_counts))),
        "sums_max_rel_err": float(np.max(np.abs(sums - ref_sums))
                                  / max(np.max(np.abs(ref_sums)), 1.0)),
        "inertia": float(md.sum()),
        "ref_inertia": float(d_min.sum()),
        "inertia_err_over_scale": float(abs(md.sum() - d_min.sum())
                                        / scale.sum()),
    }


def check_ref(smoke: Smoke, phase: str, agree: dict) -> None:
    smoke.check(phase, "labels agree with ref up to ties",
                agree["label_mismatches_off_tie"] == 0, agree)
    smoke.check(phase, "counts equal ref", agree["counts_equal"])
    smoke.check(phase, "sums agree with ref",
                agree["sums_max_rel_err"] <= SUM_RTOL, agree)
    smoke.check(phase, "inertia agrees with ref",
                agree["inertia_err_over_scale"] <= INERTIA_RTOL, agree)


def compiled_step(backend, params, use_dmr, x, c):
    """One Lloyd step of ``backend`` — assignment plus the centroid sums
    the fit's update uses — compiled for these shapes. Returns
    (holds a Mosaic kernel, (labels, true sq-distances, sums, counts))."""
    import jax
    from repro.core.kmeans import protected_sums

    def step(x, c):
        out = backend(x, c, params=params)
        if backend.fuses_update:
            return out[0], out[1], out[3], out[4]
        upd = protected_sums(x, out[0], c.shape[-2], use_dmr=use_dmr)
        return out[0], out[1], upd.sums, upd.counts

    compiled = jax.jit(step).lower(x, c).compile()
    return "tpu_custom_call" in compiled.as_text(), compiled(x, c)


def check_backend(smoke: Smoke, phase: str, name: str, kernel: bool):
    smoke.check(phase, f"backend {name!r} is a Pallas backend",
                name in PALLAS_BACKENDS)
    smoke.check(phase, "compiled step holds tpu_custom_call", kernel)


def fit_phase(smoke: Smoke, phase: str, km, x, c0) -> dict:
    """Fit twice from ``c0`` (the first call compiles), then compare one
    step at the fitted centroids with the reference on a slice of ``x``."""
    import numpy as np
    _, first_s, compile_s = smoke.timed(
        lambda: km.fit(x, centroids=c0).cluster_centers_)
    _, wall_s, _ = smoke.timed(
        lambda: km.fit(x, centroids=c0).cluster_centers_)
    backend = km._backend
    params = km._resolve_params(*x.shape)
    xs, c = x[:CHECK_ROWS], km.cluster_centers_
    kernel, (am, md, sums, counts) = compiled_step(
        backend, params, km.fault.dmr_enabled(backend), xs, c)
    agree = ref_agreement(xs, c, am, md, sums, counts)
    check_backend(smoke, phase, backend.name, kernel)
    check_ref(smoke, phase, agree)
    smoke.check(phase, "centroids finite",
                np.isfinite(np.asarray(c)).all() and c.shape == (K, F))
    smoke.report(phase, backend=backend.name, tpu_custom_call=kernel,
                 params=str(params), wall_s=wall_s, first_call_s=first_s,
                 compile_s=compile_s, n_iter=km.n_iter_,
                 inertia=km.inertia_, detected_errors=km.detected_errors_,
                 ref=agree)
    return agree


def run_one_chip(smoke: Smoke, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.api import (AutotuneCache, BatchedKMeans, FaultPolicy,
                           InjectionCampaign, KMeans)
    from repro.data.blobs import make_blobs

    # tiles come from the autotuner's model, never from an untracked table
    cache = AutotuneCache(None)
    common = dict(tol=0.0, max_iter=ITERS, autotune=cache, random_state=seed)
    (x, _), setup_s, _ = smoke.timed(lambda: make_blobs(N, F, K, seed=seed))
    c0, init_s, init_compile_s = smoke.timed(
        lambda: KMeans(K, **common).init_centroids(x))
    smoke.report("setup", rows=N, features=F, clusters=K, data_s=setup_s,
                 init_s=init_s, init_compile_s=init_compile_s)

    # (a) unprotected: the policy's default backend, then the one-pass one
    for phase, name in (("a_fit_default", None), ("a_fit_lloyd", "lloyd")):
        fit_phase(smoke, phase, KMeans(K, fault=FaultPolicy.off(),
                                       backend=name, **common), x, c0)

    # (b) protected, clean and under an SEU campaign on both intervals
    clean = KMeans(K, fault=FaultPolicy.correct(), **common)
    fit_phase(smoke, "b_fit_ft", clean, x, c0)
    smoke.check("b_fit_ft", "no detections on a clean fit",
                clean.detected_errors_ == 0, clean.detected_errors_)
    noisy = KMeans(K, fault=FaultPolicy.correct(injection=InjectionCampaign(
        rate=1.0, targets="both", seed=seed)), **common)
    fit_phase(smoke, "b_fit_ft_injected", noisy, x, c0)
    smoke.check("b_fit_ft_injected", "injected errors detected",
                noisy.detected_errors_ > 0, noisy.detected_errors_)
    diff = np.asarray(noisy.cluster_centers_) - np.asarray(
        clean.cluster_centers_)
    smoke.check("b_fit_ft_injected", "centroids equal the clean FT fit's",
                not diff.any(), f"max |diff| {np.abs(diff).max()}")
    smoke.check("b_fit_ft_injected", "labels equal the clean FT fit's",
                np.array_equal(np.asarray(noisy.labels_),
                               np.asarray(clean.labels_)))

    # (c) batched problems, both seedings
    xb = jnp.stack([make_blobs(BN, F, BK, seed=seed + 1 + b)[0]
                    for b in range(B)])
    for init in ("kmeans++", "kmeans++-fused"):
        phase = f"c_batched_{init}"
        bkm = BatchedKMeans(BK, init=init, **common)
        _, first_s, compile_s = smoke.timed(
            lambda: bkm.fit(xb).cluster_centers_)
        _, wall_s, _ = smoke.timed(lambda: bkm.fit(xb).cluster_centers_)
        backend, c = bkm._backend, bkm.cluster_centers_
        params = bkm._resolve_params(*xb.shape)
        kernel, (am, md, sums, counts) = compiled_step(
            backend, params, False, xb, c)
        agree = ref_agreement(xb[0], c[0], am[0], md[0], sums[0], counts[0])
        check_backend(smoke, phase, backend.name, kernel)
        check_ref(smoke, phase, agree)
        smoke.check(phase, "centroids finite",
                    np.isfinite(np.asarray(c)).all() and c.shape == (B, BK, F))
        smoke.report(phase, backend=backend.name, tpu_custom_call=kernel,
                     params=str(params), wall_s=wall_s, first_call_s=first_s,
                     compile_s=compile_s, problems=B, rows=BN, clusters=BK,
                     ref=agree)

    # (d) serving the FT model
    phase = "d_serve_ft"
    svc, build_s, build_compile_s = smoke.timed(clean.to_service)
    backend = svc.compiler.backend.name
    kernel = all("tpu_custom_call" in svc.compiler.cell(b).as_text()
                 for b in svc.compiler.buckets)
    check_backend(smoke, phase, backend, kernel)
    requests = []
    for rows in REQUEST_ROWS:
        q = np.asarray(x[N - rows:])
        res, wall_s, compile_s = smoke.timed(lambda: svc.predict(q))
        want = np.asarray(clean.predict(q))
        smoke.check(phase, f"{rows}-row answer equals KMeans.predict",
                    np.array_equal(res.labels, want))
        smoke.check(phase, f"{rows}-row answer has no detections",
                    int(res.detected) == 0, res.detected)
        requests.append({"rows": rows, "wall_s": wall_s,
                         "compile_s": compile_s})
    smoke.report(phase, backend=backend, tpu_custom_call=kernel,
                 buckets=list(svc.compiler.buckets), build_s=build_s,
                 compile_s=build_compile_s, requests=requests)


def run_four_chips(smoke: Smoke, seed: int) -> None:
    import numpy as np
    from repro.api import AutotuneCache, FaultPolicy, KMeans
    from repro.core.fault import no_step_injection
    from repro.data.blobs import make_blobs
    from repro.dist.kmeans_dist import DistributedKMeans
    from repro.dist.sharding import mesh2d

    phase = "e_distributed_ft"
    (x, labels), setup_s, _ = smoke.timed(
        lambda: make_blobs(DIST_N, F, K, seed=seed))
    # one sample of each generating blob: no two centroids share a blob, so
    # no label sits on a distance tie, and any disagreement between the two
    # fits comes from the sharded reduce, not from a tie broken differently
    first = np.unique(np.asarray(labels), return_index=True)[1]
    c0 = x[first]

    def estimator():
        return KMeans(K, fault=FaultPolicy.correct(), tol=0.0,
                      max_iter=ITERS, autotune=AutotuneCache(None),
                      random_state=seed)

    dk = DistributedKMeans(estimator(), mesh2d(4))
    shard_backend = dk._shard_backend().name
    xs = dk.shard_data(x)
    _, first_s, compile_s = smoke.timed(lambda: dk.fit(xs, c0)[0])
    (c4, am4, inertia4, iters4, det4), wall_s, _ = smoke.timed(
        lambda: dk.fit(xs, c0))
    step = next(iter(dk._steps.values()))
    kernel = "tpu_custom_call" in step.lower(
        xs, c0, no_step_injection("lloyd_ft"),
        np.zeros((1, K, F), np.float32)).compile().as_text()

    one = estimator()
    _, one_first_s, one_compile_s = smoke.timed(
        lambda: one.fit(x, centroids=c0).cluster_centers_)
    _, one_wall_s, _ = smoke.timed(
        lambda: one.fit(x, centroids=c0).cluster_centers_)

    c4, c1 = np.asarray(c4), np.asarray(one.cluster_centers_)
    cent_err = float(np.max(np.abs(c4 - c1)) / np.max(np.abs(c1)))
    inertia_err = abs(float(inertia4) - one.inertia_) / one.inertia_
    check_backend(smoke, phase, shard_backend, kernel)
    smoke.check(phase, "per-shard backend is lloyd_ft",
                shard_backend == "lloyd_ft", shard_backend)
    smoke.check(phase, "centroids agree with the one-chip fit",
                cent_err <= SUM_RTOL, cent_err)
    smoke.check(phase, "inertia agrees with the one-chip fit",
                inertia_err <= SUM_RTOL, inertia_err)
    smoke.check(phase, "labels equal the one-chip fit's",
                np.array_equal(np.asarray(am4), np.asarray(one.labels_)))
    smoke.check(phase, "no detections on clean fits",
                int(det4) == 0 and one.detected_errors_ == 0,
                (int(det4), one.detected_errors_))
    smoke.report(phase, backend=shard_backend, tpu_custom_call=kernel,
                 devices=len(smoke.jax.devices()), rows=DIST_N,
                 data_s=setup_s, wall_s=wall_s, first_call_s=first_s,
                 compile_s=compile_s, iterations=int(iters4),
                 inertia=float(inertia4), one_chip_wall_s=one_wall_s,
                 one_chip_first_call_s=one_first_s,
                 one_chip_compile_s=one_compile_s,
                 one_chip_inertia=one.inertia_,
                 centroid_max_rel_err=cent_err, inertia_rel_err=inertia_err)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated data and of every fit")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip distributed fit and "
                         "the one-chip fit it is compared with")
    args = ap.parse_args(argv)

    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {platform!r}")
    devices = jax.devices()
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"chips; JAX found {len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels import ops
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache(ROOT)
    # the kernels pick interpret mode from this; on the chip it must be off
    if not ops.on_tpu():
        sys.exit("chip_smoke: ops.on_tpu() is False; kernels would run "
                 "in interpret mode")

    smoke = Smoke(jax, devices[0])
    if args.chips == 4:
        run_four_chips(smoke, args.seed)
    else:
        run_one_chip(smoke, args.seed)
    if smoke.failures:
        for failure in smoke.failures:
            print(f"FAILED {failure}", file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
