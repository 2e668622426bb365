"""cuML/sklearn-compatible K-means estimator over the FT kernel stack.

One front end for every scenario in the paper and the roadmap:

    km = KMeans(n_clusters=8, fault=FaultPolicy.correct())
    labels = km.fit_predict(x)            # full-batch Lloyd
    km.partial_fit(block)                 # streaming / mini-batch path
    state = km.get_state()                # serializable fitted state
    km2 = KMeans.from_state(state)        # restore (checkpoint/restart)

Protection is a :class:`~repro.api.policy.FaultPolicy` — policy resolution
picks the assignment kernel from the backend registry; kernel-tile selection
comes from an injectable :class:`~repro.api.cache.AutotuneCache`. The
estimator never branches on backend names.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.cache import AutotuneCache, default_cache
from repro.api.policy import FaultPolicy, InjectionCampaign
from repro.api.registry import AssignmentBackend
from repro.kernels import ops, ref

_INITS = ("kmeans++", "random")
_COMPUTE_DTYPES = ("float32", "bfloat16", "float16", "int8")

# Row-chunk size for one-shot inference (predict/transform/score): bounds
# the padded working set on large inputs instead of materializing a full
# padded copy of X. Overridable per estimator via ``predict_chunk_rows``.
_PREDICT_CHUNK_ROWS = 65_536


class NotFittedError(RuntimeError):
    pass


def _host_read(value: Any) -> Any:
    """The single device->host funnel of the fit loop.

    Every synchronization the full-batch fit performs goes through here —
    once per ``sync_every``-iteration chunk plus once for the final
    counters — so tests can count host transfers by patching one name."""
    return jax.device_get(value)


class KMeans:
    """K-means estimator with composable fault tolerance.

    The sklearn/cuML-shaped front end over the FT kernel stack: protection
    is a :class:`FaultPolicy` (resolved to an assignment backend through
    the registry), kernel tiles come from an injectable
    :class:`AutotuneCache`, and the full-batch Lloyd loop runs
    device-resident (a chunked ``lax.scan`` with the convergence test on
    device).

    Parameters
    ----------
    n_clusters : int, default=8
        Number of clusters K.
    max_iter : int, default=100
        Lloyd iteration budget.
    tol : float, default=1e-4
        Centroid-shift convergence threshold: the fit stops once
        ``||C' - C||_F < tol`` (tested on device).
    init : {"kmeans++", "random"}, default="kmeans++"
        Seeding strategy (D² sampling or uniform rows).
    fault : FaultPolicy, optional
        Protection policy — off / detect / correct, plus an optional SEU
        :class:`InjectionCampaign`. Default: no protection
        (``FaultPolicy.off()``).
    backend : str, optional
        Pin a registered assignment backend by name; default lets the
        policy resolve one (paper §III-B selection). The policy validates
        a pinned backend's capabilities.
    batch_size : int, optional
        When set, ``fit`` runs sampled mini-batches of this many rows per
        iteration; ``partial_fit`` streams caller-provided batches either
        way.
    params : KernelParams, optional
        Explicit tile override for Pallas backends (skips the autotune
        lookup).
    autotune : AutotuneCache, optional
        Injectable kernel-selection table; default = the process cache
        (``default_cache()``).
    sync_every : int, default=10
        Full-batch ``fit`` runs the Lloyd loop device-resident in chunks
        of this many iterations; the host observes progress — and replays
        ``on_iteration`` — only at chunk boundaries.
    compute_dtype : {"float32", "bfloat16", "float16", "int8"}, \
            default="float32"
        Kernel compute dtype. For the float dtypes, X and the centroids
        are cast at the kernel boundary (paper §III-B's dtype-templated
        kernels); accumulators, distances, counts and the stored
        ``cluster_centers_`` stay f32. ``"int8"`` selects the quantized
        distance template instead: X is per-row symmetrically quantized
        once per fit (centroids per iteration, since they move), the
        distance GEMM runs on int8 operands, and the scale correction,
        norms, argmin and the centroid update all stay f32 — so no data
        is ever ``astype``'d to int8. int8 needs an unprotected policy
        (``FaultPolicy.off()``): the quantized template has no FT
        variant.
    predict_chunk_rows : int, optional
        Row-chunk size for one-shot inference (predict/transform/score);
        ``None`` = module default (65 536). Bounds the padded working set
        on large inputs.
    random_state : int, default=0
        Seed for init, mini-batch sampling, empty-cluster reseeding and
        (mixed with the campaign's own seed) injection schedules.

    Attributes
    ----------
    cluster_centers_ : jax.Array, shape (n_clusters, F), float32
        Fitted centroids (always f32, whatever ``compute_dtype``).
    labels_ : jax.Array, shape (M,), int32
        Assignment of each training sample at the final iteration.
    inertia_ : float
        Sum of squared distances at the final iteration.
    n_iter_ : int
        Iterations executed.
    detected_errors_ : int
        SDCs detected (and, under ``mode="correct"``, corrected) across
        the fit — nonzero only with a fault-tolerant backend.
    prune_history_ : list of float
        Per-iteration fraction of (row tile, centroid tile) cells skipped
        by the triangle-inequality filter — populated only by full-batch
        fits on a bounds-carrying backend (``supports_bounds``), empty
        otherwise. Iteration zero is always 0.0 (the seed pass computes
        every tile).
    update_windows_ : float or None
        The two-pass update of the last ``fit`` step: 128-cluster windows
        its sorted-window kernel ran per row tile of label-sorted rows
        (1.0 when every tile fit one window), 0.0 where the dense one-hot
        product ran (``ops.update_path``), None for a one-pass backend.

    See Also
    --------
    FaultPolicy : protection policy and backend resolution.
    InjectionCampaign : SEU campaign semantics (``rate`` / ``targets``).
    repro.batch.BatchedKMeans : many-problem batched variant.

    Examples
    --------
    >>> from repro.api import KMeans, FaultPolicy
    >>> km = KMeans(n_clusters=4, fault=FaultPolicy.correct())
    >>> km.fault.mode
    'correct'
    """

    def __init__(self, n_clusters: int = 8, *, max_iter: int = 100,
                 tol: float = 1e-4, init: str = "kmeans++",
                 fault: Optional[FaultPolicy] = None,
                 backend: Optional[str] = None,
                 batch_size: Optional[int] = None,
                 params: Optional[ops.KernelParams] = None,
                 autotune: Optional[AutotuneCache] = None,
                 sync_every: int = 10,
                 compute_dtype: Any = "float32",
                 predict_chunk_rows: Optional[int] = None,
                 random_state: int = 0) -> None:
        if n_clusters < 1:
            raise ValueError(f"n_clusters must be >= 1, got {n_clusters}")
        if init not in _INITS:
            raise ValueError(f"init must be one of {_INITS}, got {init!r}")
        if sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got {sync_every}")
        try:
            dtype_ok = jnp.dtype(compute_dtype).name in _COMPUTE_DTYPES
        except TypeError:                  # unparseable spec, e.g. "bf16"
            dtype_ok = False
        if not dtype_ok:
            raise ValueError(
                f"compute_dtype must be one of {_COMPUTE_DTYPES}, "
                f"got {compute_dtype!r}")
        if predict_chunk_rows is not None and predict_chunk_rows < 1:
            raise ValueError(f"predict_chunk_rows must be >= 1, "
                             f"got {predict_chunk_rows}")
        self.n_clusters = n_clusters
        self.max_iter = max_iter
        self.tol = tol
        self.init = init
        self.fault = fault if fault is not None else FaultPolicy.off()
        self.backend = backend
        self.batch_size = batch_size
        self.params = params
        self.autotune = autotune if autotune is not None else default_cache()
        self.sync_every = sync_every
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.predict_chunk_rows = predict_chunk_rows
        self.random_state = random_state

        is_int8 = self.compute_dtype == jnp.int8
        if is_int8 and backend is None:
            # the quantized template is assignment-only: Pallas kernel on
            # TPU, its bit-compatible XLA analogue elsewhere. The policy
            # still validates the pick (int8 has no FT variant, so a
            # protected policy is rejected there).
            backend = "int8" if ops.on_tpu() else "int8_xla"
        self._backend: AssignmentBackend = self.fault.resolve_backend(backend)
        if is_int8 != self._backend.supports_int8:
            raise ValueError(
                f"backend {self._backend.name!r} "
                + ("does not consume int8-quantized operands; pick a "
                   "supports_int8 backend or drop compute_dtype='int8'"
                   if is_int8 else
                   "is an int8 template and needs compute_dtype='int8'"))
        self._use_dmr = self.fault.dmr_enabled(self._backend)
        if self.fault.update_dmr and self._backend.fuses_update:
            # DMR was the two-pass pipeline's update protection; one-pass
            # backends compute the update in the kernel epilogue, where the
            # lloyd_ft checksum scheme subsumes it (and the plain lloyd
            # kernel offers no host-side hook to duplicate). An *explicit*
            # True is ignored with a note (the default None is auto and
            # stays silent) — one policy serves both pipeline shapes.
            import warnings
            warnings.warn(
                f"FaultPolicy.update_dmr is a two-pass-backend knob; "
                f"backend {self._backend.name!r} fuses the centroid update "
                f"into the kernel epilogue"
                + (", where its checksum protection subsumes DMR"
                   if self._backend.supports_ft else
                   " (unprotected; use FaultPolicy.correct() for the "
                   "checksummed one-pass kernel)")
                + "; the flag is ignored here",
                DeprecationWarning, stacklevel=2)
        self._step_cache: dict[tuple, Callable[..., Any]] = {}
        # streaming state (partial_fit)
        self._counts: Optional[jax.Array] = None

        self.cluster_centers_: Optional[jax.Array] = None
        self.labels_: Optional[jax.Array] = None
        self.inertia_: Optional[float] = None
        self.n_iter_: int = 0
        self.detected_errors_: int = 0
        self.prune_history_: list = []
        self.update_windows_: Optional[float] = None

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    def _check_fitted(self) -> None:
        if self.cluster_centers_ is None:
            raise NotFittedError(
                "this KMeans instance is not fitted yet; call fit() or "
                "partial_fit() first")

    def _cast(self, a: jax.Array) -> jax.Array:
        """Cast to the compute dtype at the kernel boundary (no-op f32).

        ``int8`` is quantization, not a cast: the backend quantizes per
        row itself (``astype(int8)`` would truncate the data), so the
        int8 kernel boundary keeps X and the centroids f32."""
        if self.compute_dtype == jnp.int8:
            return a if a.dtype == jnp.float32 else a.astype(jnp.float32)
        return a if a.dtype == self.compute_dtype else \
            a.astype(self.compute_dtype)

    def _resolve_params(self, m: int, f: int, *,
                        backend: Optional[AssignmentBackend] = None
                        ) -> Optional[ops.KernelParams]:
        """Tile selection for one problem shape: explicit override, else the
        injectable autotune cache (paper §III-B table lookup), keyed by
        kernel kind *and* compute dtype. One-pass backends consult the
        ``lloyd``-kind entries — an assignment-only winner must never be
        handed to the fused-update kernel — and a winner tuned for f32
        tiles is never handed to the bf16/fp16 templates."""
        backend = backend if backend is not None else self._backend
        if not backend.takes_params:
            return None
        if self.params is not None:
            p = self.params
        else:
            _, p = self.autotune.lookup(m, self.n_clusters, f,
                                        kind=backend.kernel_kind,
                                        dtype=self.compute_dtype)
        return ops.clamp_params(m, self.n_clusters, f, p,
                                dtype=self.compute_dtype)

    def _predict_backend(self) -> AssignmentBackend:
        """Prediction is assignment-only. A one-pass backend would compute
        the whole fused-update epilogue and throw it away (Pallas outputs
        are not dead-code-eliminated), so predict/score route through the
        assignment kernel at the *same protection level*: the one-pass FT
        backend predicts through the fused-ABFT assignment kernel, the
        plain one-pass backends through the unprotected one."""
        from repro.api.registry import get_backend
        b = self._backend
        if not b.fuses_update:
            return b
        if b.supports_ft:
            return get_backend("fused_ft" if b.takes_params
                               else "abft_offline")
        return get_backend("fused" if b.takes_params else "gemm_fused")

    def _assign_fn(self, params: Optional[ops.KernelParams]
                   ) -> Callable[..., Any]:
        """jit'd (x, c[, inj]) -> (assign, true sq-dist, detected)."""
        key = ("assign", params)
        if key not in self._step_cache:
            backend = self._predict_backend()
            cast = self._cast
            if backend.takes_injection:
                fn = jax.jit(lambda x, c, inj: backend(
                    cast(x), cast(c), params=params, inj=inj))
            else:
                fn = jax.jit(lambda x, c: backend(cast(x), cast(c),
                                                  params=params))
            self._step_cache[key] = fn
        return self._step_cache[key]

    def _apply_update(self, out: tuple, x: jax.Array,
                      centroids: jax.Array) -> tuple:
        """One centroid update from a backend result: one-pass backends
        already carry (sums, counts); two-pass backends pay the second
        pass over X (optionally DMR-protected), which also reports its
        windows per row tile (``update_windows_``; None for one-pass)."""
        from repro.core.kmeans import means_from_sums, protected_sums
        windows = None
        if self._backend.fuses_update:
            # bounds-carrying backends extend the 5-tuple by
            # (new_bounds, prune_frac); the update only needs the head
            am, md, det, sums, counts = out[:5]
        else:
            am, md, det = out
            sums, counts, windows = protected_sums(
                x, am, self.n_clusters, use_dmr=self._use_dmr)
        new_c = means_from_sums(sums, counts, centroids)
        return am, md, det, new_c, counts, windows

    def _lloyd_step_fn(self, params: Optional[ops.KernelParams]
                       ) -> Callable[..., Any]:
        """jit'd full Lloyd step: assignment + update (fused or two-pass)."""
        key = ("lloyd", params)
        if key not in self._step_cache:
            backend = self._backend

            def step(x: jax.Array, centroids: jax.Array,
                     inj: Any = None) -> tuple:
                x = self._cast(x)
                out = backend(x, self._cast(centroids), params=params,
                              inj=inj)
                am, md, det, new_c, counts, windows = self._apply_update(
                    out, x, centroids)
                inertia = jnp.sum(md)
                shift = jnp.sqrt(jnp.sum((new_c - centroids) ** 2))
                return new_c, am, counts, md, inertia, shift, det, windows

            static = () if backend.takes_injection else ("inj",)
            self._step_cache[key] = jax.jit(step, static_argnames=static)
        return self._step_cache[key]

    def _stream_step_fn(self, params: Optional[ops.KernelParams]
                        ) -> Callable[..., Any]:
        """jit'd streaming (mini-batch) step with per-center count decay —
        the partial_fit update rule (Sculley-style online k-means)."""
        from repro.core.kmeans import protected_sums
        key = ("stream", params)
        if key not in self._step_cache:
            backend, k = self._backend, self.n_clusters
            use_dmr = self._use_dmr
            fuses = backend.fuses_update

            def step(x: jax.Array, centroids: jax.Array,
                     counts: jax.Array, inj: Any = None) -> tuple:
                x = self._cast(x)
                out = backend(x, self._cast(centroids), params=params,
                              inj=inj)
                if fuses:   # block sums/counts come out of the kernel
                    # bounds backends run unpruned here (bounds=None per
                    # call — streaming blocks share no bounds lineage)
                    am, md, det, sums, bcnt = out[:5]
                else:
                    am, md, det = out
                    sums, bcnt, _ = protected_sums(x, am, k,
                                                   use_dmr=use_dmr)
                new_counts = counts + bcnt
                eta = (bcnt / jnp.maximum(new_counts, 1.0))[:, None]
                bmean = sums / jnp.maximum(bcnt, 1.0)[:, None]
                new_c = jnp.where((bcnt > 0)[:, None],
                                  (1.0 - eta) * centroids + eta * bmean,
                                  centroids)
                return new_c, new_counts, am, jnp.sum(md), det

            static = () if backend.takes_injection else ("inj",)
            self._step_cache[key] = jax.jit(step, static_argnames=static)
        return self._step_cache[key]

    def _chunk_fn(self, params: Optional[ops.KernelParams],
                  n_steps: int) -> Callable[..., Any]:
        """jit'd device-resident chunk of up to ``n_steps`` Lloyd iterations.

        The convergence test runs on device inside a ``lax.scan``: once the
        centroid shift drops below ``tol`` the remaining steps freeze into
        carry passthroughs (a ``lax.cond`` whose dead branch costs nothing),
        so a chunk never round-trips to the host mid-flight. The stacked
        per-iteration history (centroids, inertia, shift, active mask) lets
        the host replay ``on_iteration`` faithfully at the chunk boundary.
        """
        from repro.core.kmeans import reseed_empty
        tol = self.tol   # baked into the trace -> part of the cache key
        cache_key = ("chunk", params, n_steps, tol)
        if cache_key in self._step_cache:
            return self._step_cache[cache_key]
        backend = self._backend
        takes_inj = backend.takes_injection
        takes_params = backend.takes_params
        # int8 backends consume the QuantPlan itself even when they take
        # no tile params (the XLA analogue reuses the per-fit row
        # quantization instead of re-quantizing X every iteration)
        takes_plan = takes_params or backend.supports_int8

        if backend.supports_bounds:
            # Bounds-carrying variant: the BoundsState rides in the scan
            # carry (it is a registered pytree), so upper bounds and
            # centroid drifts survive across iterations without ever
            # touching the host. The history gains a prune-fraction
            # column. Frozen (converged) steps pass the bounds through
            # untouched — they would only decay further, and the fit is
            # over anyway.
            def chunk_bounded(plan: Any, centroids: jax.Array,
                              am0: jax.Array, det0: jax.Array,
                              inertia0: jax.Array, key: jax.Array,
                              it0: Any, bounds0: Any) -> tuple:
                def body(carry: tuple, t: jax.Array) -> tuple:
                    centroids, am, inertia, done, det, bounds = carry

                    @obs.scope("step")
                    def live(_: None) -> tuple:
                        xa = plan if takes_plan else plan.x
                        with obs.scope("assign"):
                            out = backend(xa, self._cast(centroids),
                                          params=params if takes_params
                                          else None, bounds=bounds)
                        with obs.scope("update"):
                            am_b, md, det_i, new_c, counts, _ = \
                                self._apply_update(out, plan.x, centroids)
                        new_bounds, pfrac = out[5], out[6]
                        inertia_i = jnp.sum(md)
                        shift = jnp.sqrt(jnp.sum((new_c - centroids) ** 2))
                        with obs.scope("reseed"):
                            new_c = reseed_empty(
                                jax.random.fold_in(key, it0 + t),
                                plan.x, new_c, counts, md)
                        return (new_c, am_b, inertia_i, shift,
                                det + det_i.astype(jnp.int32),
                                new_bounds, pfrac)

                    def frozen(_: None) -> tuple:
                        return (centroids, am, inertia, jnp.float32(0.0),
                                det, bounds, jnp.float32(0.0))

                    (new_c, am_n, inertia_n, shift, det_n, bounds_n,
                     pfrac) = jax.lax.cond(done, frozen, live, None)
                    active = jnp.logical_not(done)
                    done_n = jnp.logical_or(done, shift < tol)
                    return ((new_c, am_n, inertia_n, done_n, det_n,
                             bounds_n),
                            (new_c, inertia_n, shift, active, pfrac))

                init = (centroids, am0, inertia0, jnp.bool_(False), det0,
                        bounds0)
                (centroids, am, inertia, done, det, bounds), hist = \
                    jax.lax.scan(body, init, jnp.arange(n_steps),
                                 length=n_steps)
                return centroids, am, inertia, det, done, hist, bounds

            fn = jax.jit(chunk_bounded)
            self._step_cache[cache_key] = fn
            return fn

        def chunk(plan: Any, centroids: jax.Array, am0: jax.Array,
                  det0: jax.Array, inertia0: jax.Array, key: jax.Array,
                  it0: Any, inj_stack: Any) -> tuple:
            def body(carry: tuple, xs: tuple) -> tuple:
                centroids, am, inertia, done, det, win = carry
                inj, t = xs

                @obs.scope("step")
                def live(_: None) -> tuple:
                    xa = plan if takes_plan else plan.x
                    with obs.scope("assign"):
                        out = backend(xa, self._cast(centroids),
                                      params=params if takes_params else None,
                                      inj=inj if takes_inj else None)
                    with obs.scope("update"):
                        am_b, md, det_i, new_c, counts, win_i = \
                            self._apply_update(out, plan.x, centroids)
                    inertia_i = jnp.sum(md)
                    shift = jnp.sqrt(jnp.sum((new_c - centroids) ** 2))
                    with obs.scope("reseed"):
                        new_c = reseed_empty(jax.random.fold_in(key, it0 + t),
                                             plan.x, new_c, counts, md)
                    return (new_c, am_b, inertia_i, shift,
                            det + det_i.astype(jnp.int32), win_i)

                def frozen(_: None) -> tuple:
                    return centroids, am, inertia, jnp.float32(0.0), det, win

                new_c, am_n, inertia_n, shift, det_n, win_n = jax.lax.cond(
                    done, frozen, live, None)
                active = jnp.logical_not(done)
                done_n = jnp.logical_or(done, shift < tol)
                return ((new_c, am_n, inertia_n, done_n, det_n, win_n),
                        (new_c, inertia_n, shift, active))

            # the update's window count rides the carry only where a
            # two-pass update reports one (a None carries no value)
            win0 = None if backend.fuses_update else jnp.float32(0.0)
            init = (centroids, am0, inertia0, jnp.bool_(False), det0, win0)
            (centroids, am, inertia, done, det, win), hist = jax.lax.scan(
                body, init, (inj_stack, jnp.arange(n_steps)), length=n_steps)
            return centroids, am, inertia, det, done, hist, win

        fn = jax.jit(chunk)
        self._step_cache[cache_key] = fn
        return fn

    def _campaign_rng(self, offset: int = 0) -> np.random.Generator:
        """Injection-schedule RNG: keyed by the campaign's own seed (so
        repeated campaigns vary independently of data sampling), mixed
        with random_state for distinct estimators. The leading tag keeps
        the stream disjoint from the data-sampling rng even at seed 0."""
        camp = self.fault.injection
        camp_seed = camp.seed if camp is not None else 0
        return np.random.default_rng(
            [0x1427, camp_seed, self.random_state, offset])

    def _draw_injection(self, rng: np.random.Generator, m: int, f: int,
                        params: Optional[ops.KernelParams]) -> jax.Array:
        """Per-iteration campaign draw -> in-kernel injection descriptor
        (dual-slot for the one-pass FT kernel: distance GEMM + update
        epilogue are independently verified intervals)."""
        from repro.core.fault import draw_step_injection, no_step_injection
        camp = self.fault.injection
        kind = self._backend.kernel_kind
        if camp is None or not camp.enabled():
            return no_step_injection(kind)
        return draw_step_injection(
            rng, m, self.n_clusters, f, params, rate=camp.rate,
            targets=camp.resolved_targets(self._backend), kind=kind)

    def init_centroids(self, x: jax.Array,
                        key: Optional[jax.Array] = None) -> jax.Array:
        from repro.core.kmeans import init_kmeanspp, init_random
        key = key if key is not None else jax.random.PRNGKey(self.random_state)
        fn = init_kmeanspp if self.init == "kmeans++" else init_random
        return fn(key, x, self.n_clusters)

    # ------------------------------------------------------------------
    # estimator API
    # ------------------------------------------------------------------

    def fit(self, x: jax.Array, *, centroids: Optional[jax.Array] = None,
            on_iteration: Optional[Callable] = None) -> "KMeans":
        """Run Lloyd iterations to convergence (or ``max_iter``).

        ``centroids`` seeds the run (checkpoint restart / warm start);
        ``on_iteration(it, centroids, inertia, shift)`` observes progress.

        Full-batch fits run device-resident: the loop is a chunked
        ``lax.scan`` with the convergence test on device, the data plan
        (padding + row norms) built once, and the host synchronizing only
        every ``sync_every`` iterations (``on_iteration`` is replayed from
        the chunk history, so its per-iteration semantics are preserved).
        """
        x = jnp.asarray(x)
        key = jax.random.PRNGKey(self.random_state)
        if centroids is None:
            key, sub = jax.random.split(key)
            centroids = self.init_centroids(x, sub)
        # the estimator's centroid state is always f32; the compute dtype
        # applies at the kernel boundary only
        centroids = jnp.asarray(centroids, jnp.float32)
        if self.batch_size is not None:
            return self._fit_minibatch(x, centroids, on_iteration)
        with obs.span("fit"):
            return self._fit_fullbatch(x, centroids, key, on_iteration)

    def _fit_fullbatch(self, x: jax.Array, centroids: jax.Array,
                       key: jax.Array, on_iteration: Optional[Callable]
                       ) -> "KMeans":
        m, f = x.shape
        params = self._resolve_params(m, f)
        takes_inj = self._backend.takes_injection
        inj_rng = self._campaign_rng()
        # per-fit data plan: pad + row-norm X exactly once, reuse every
        # iteration (two-pass pipelines re-did both per kernel call). The
        # plan is built in the compute dtype so the per-iteration cost of a
        # bf16/fp16 fit is zero casts of X — only the (K, F) centroids are
        # cast per step.
        with obs.span("plan"):
            if self._backend.supports_int8:
                # quantize + pad once per fit; QuantPlan.x keeps the original
                # samples, so the two-pass centroid update and empty-cluster
                # reseeding stay full precision
                plan: Any = ops.plan_data_int8(self._cast(x), params)
            else:
                plan = ops.plan_data(self._cast(x), params)
            # bounds-carrying backends start every fit from a fresh (all-
            # compute) state: a warm start / from_state restore never inherits
            # bounds, so a centroid hot-swap can't leave stale Hamerly bounds
            supports_bounds = self._backend.supports_bounds
            bounds = self._backend.bounds_init(
                m, self.n_clusters, f, params, dtype=self.compute_dtype) \
                if supports_bounds else None
        self.prune_history_ = []

        am = jnp.zeros((m,), jnp.int32)
        det = jnp.zeros((), jnp.int32)
        inertia = jnp.float32(jnp.inf)
        inertia_host = float("inf")
        windows = None
        it0 = 0
        while it0 < self.max_iter:
            n_steps = min(self.sync_every, self.max_iter - it0)
            # the chunk's last operand: the bounds state, or its campaign
            if supports_bounds:
                carried: Any = bounds
            elif takes_inj:
                # pre-draw the chunk's campaign schedule: same host RNG
                # consumption order as the per-iteration loop had
                with obs.span("campaign"):
                    carried = jnp.stack([
                        self._draw_injection(inj_rng, m, f, params)
                        for _ in range(n_steps)])
            else:
                carried = jnp.zeros((n_steps, 1), jnp.int32)
            with obs.span("dispatch"):
                out = self._chunk_fn(params, n_steps)(
                    plan, centroids, am, det, inertia, key, jnp.int32(it0),
                    carried)
            centroids, am, inertia, det, done_d, hist = out[:6]
            if supports_bounds:
                bounds = out[6]
            else:
                windows = out[6]
            # the chunk boundary: the only device->host sync of the window.
            # The (n_steps, K, F) centroid history crosses only when a
            # callback will actually read it.
            cs_d, in_d, sh_d, act_d = hist[:4]
            pf_d = hist[4] if supports_bounds else None
            with obs.span("sync"):
                if on_iteration is None:
                    done, in_h, sh_h, act_h, pf_h = _host_read(
                        (done_d, in_d, sh_d, act_d, pf_d))
                else:
                    done, cs_h, in_h, sh_h, act_h, pf_h = _host_read(
                        (done_d, cs_d, in_d, sh_d, act_d, pf_d))
            executed = int(act_h.sum())
            if on_iteration is not None:
                for t in range(executed):
                    on_iteration(it0 + t, cs_h[t], float(in_h[t]),
                                 float(sh_h[t]))
            if pf_h is not None:
                self.prune_history_.extend(
                    float(v_h) for v_h in pf_h[:executed])
            if executed:
                inertia_host = float(in_h[executed - 1])
            it0 += executed
            if bool(done):
                break

        self.cluster_centers_ = centroids
        self.n_iter_ = max(1, it0)
        with obs.span("sync"):
            det_h, win_h = _host_read((det, windows))
        self.detected_errors_ = int(det_h)
        self.update_windows_ = None if win_h is None else float(win_h)
        self._counts = None
        self.labels_ = am
        self.inertia_ = inertia_host
        return self

    def _fit_minibatch(self, x: jax.Array, centroids: jax.Array,
                       on_iteration: Optional[Callable]) -> "KMeans":
        """Sampled mini-batch Lloyd: batch selection is host-driven by
        construction, so this path keeps the per-iteration loop."""
        rng = np.random.default_rng(self.random_state + 1)
        inj_rng = self._campaign_rng()
        takes_inj = self._backend.takes_injection
        self.prune_history_ = []   # mini-batch steps run unpruned

        total_det = jnp.zeros((), jnp.int32)
        inertia = jnp.asarray(jnp.inf)
        windows = None
        it = 0
        for it in range(self.max_iter):
            idx = rng.choice(x.shape[0], min(self.batch_size, x.shape[0]),
                             replace=False)
            batch = x[jnp.asarray(idx)]
            params = self._resolve_params(batch.shape[0], batch.shape[1])
            step = self._lloyd_step_fn(params)

            inj = self._draw_injection(inj_rng, batch.shape[0],
                                       batch.shape[1], params) \
                if takes_inj else None
            centroids, am_b, counts, md, inertia, shift, det, windows = step(
                batch, centroids, inj=inj)
            total_det = total_det + det
            # one funnel read per iteration covers both host consumers
            inertia_h, shift_h = _host_read((inertia, shift))
            if on_iteration is not None:
                on_iteration(it, centroids, float(inertia_h),
                             float(shift_h))
            if float(shift_h) < self.tol:
                break

        self.cluster_centers_ = centroids
        self.n_iter_ = it + 1
        det_h, win_h = _host_read((total_det, windows))
        self.detected_errors_ = int(det_h)
        self.update_windows_ = None if win_h is None else float(win_h)
        self._counts = None
        am, dist, det = self._predict_full(x)
        self.detected_errors_ += int(_host_read(det))
        self.labels_ = am
        self.inertia_ = float(_host_read(jnp.sum(dist)))
        return self

    def partial_fit(self, x: jax.Array) -> "KMeans":
        """One streaming update from a data block (first call initializes).

        Centers move by count-weighted running means, so a stream of blocks
        converges like mini-batch k-means regardless of block order."""
        x = jnp.asarray(x)
        if self.cluster_centers_ is None:
            self.cluster_centers_ = jnp.asarray(self.init_centroids(x),
                                                jnp.float32)
            self._counts = jnp.zeros((self.n_clusters,), jnp.float32)
            self.detected_errors_ = 0
            self.n_iter_ = 0
        elif self._counts is None:   # fitted by fit(); restart streaming
            self._counts = jnp.zeros((self.n_clusters,), jnp.float32)
        params = self._resolve_params(x.shape[0], x.shape[1])
        step = self._stream_step_fn(params)
        if self._backend.takes_injection:
            inj = self._draw_injection(self._campaign_rng(self.n_iter_),
                                       x.shape[0], x.shape[1], params)
        else:
            inj = None
        c, counts, am, inertia, det = step(
            x, self.cluster_centers_, self._counts, inj=inj)
        self.cluster_centers_ = c
        self._counts = counts
        self.labels_ = am
        inertia_h, det_h = _host_read((inertia, det))
        self.inertia_ = float(inertia_h)
        self.n_iter_ += 1
        self.detected_errors_ += int(det_h)
        return self

    def _row_chunks(self, m: int) -> list[slice]:
        """Row slices for one-shot inference: bounds the padded working set
        on large inputs (a full padded copy of X is never materialized).
        At most two distinct chunk shapes compile — the full chunk and the
        remainder."""
        chunk = self.predict_chunk_rows or _PREDICT_CHUNK_ROWS
        return [slice(s, min(s + chunk, m)) for s in range(0, m, chunk)]

    def _predict_block(self, x: jax.Array) -> tuple:
        if x.shape[0] == 0:
            # zero-row request (a serving layer sees these): no labels, no
            # kernel launch — and no autotune lookup keyed by an M=0 shape
            return (jnp.zeros((0,), jnp.int32),
                    jnp.zeros((0,), jnp.float32),
                    jnp.zeros((), jnp.int32))
        backend = self._predict_backend()
        params = self._resolve_params(x.shape[0], x.shape[1],
                                      backend=backend)
        fn = self._assign_fn(params)
        if backend.takes_injection:
            from repro.kernels.distance_argmin_ft import no_injection
            return fn(x, self.cluster_centers_, no_injection())
        return fn(x, self.cluster_centers_)

    def _predict_full(self, x: jax.Array) -> tuple:
        chunks = self._row_chunks(x.shape[0])
        if len(chunks) <= 1:              # includes zero-row input
            return self._predict_block(x)
        parts = [self._predict_block(x[s]) for s in chunks]
        am = jnp.concatenate([p[0] for p in parts])
        dist = jnp.concatenate([p[1] for p in parts])
        det = functools.reduce(lambda a, b: a + b, [p[2] for p in parts])
        return am, dist, det

    def predict(self, x: jax.Array) -> jax.Array:
        """Nearest-centroid labels for new data (no injection, ever)."""
        self._check_fitted()
        am, _, _ = self._predict_full(jnp.asarray(x))
        return am

    def fit_predict(self, x: jax.Array) -> jax.Array:
        return self.fit(x).labels_

    def transform(self, x: jax.Array) -> jax.Array:
        """Distances to every centroid, shape (M, n_clusters). Chunked over
        rows like :meth:`predict`, so the (M, F) working set stays bounded
        for large inputs."""
        self._check_fitted()
        x = jnp.asarray(x)

        def block(b: jax.Array) -> jax.Array:
            d = ref.distance_matrix(b, self.cluster_centers_)
            return jnp.sqrt(jnp.maximum(d, 0.0))

        chunks = self._row_chunks(x.shape[0])
        if len(chunks) <= 1:              # includes zero-row input
            return block(x)
        return jnp.concatenate([block(x[s]) for s in chunks])

    def score(self, x: jax.Array) -> float:
        """Negative inertia on ``x`` (sklearn convention: higher = better)."""
        self._check_fitted()
        _, dist, _ = self._predict_full(jnp.asarray(x))
        return -float(jnp.sum(dist))

    def to_service(self, *, buckets: Optional[tuple] = None,
                   window_s: Optional[float] = None) -> Any:
        """Hand the fitted model to the online serving layer: a
        :class:`repro.serve.KMeansService` with every bucketed predict
        cell AOT-compiled for this model's predict backend and compute
        dtype, centroids hot-swappable via its versioned store, and this
        estimator wired in as the background refinement loop
        (``service.refine`` -> :meth:`partial_fit`). Bucket ladder and
        batching window default to the tuned plan in the autotune cache
        (see ``repro.serve.tuning.plan_ladder``); docs/serving.md covers
        the architecture."""
        self._check_fitted()
        from repro.serve import KMeansService   # circular-import-safe
        return KMeansService.from_estimator(self, buckets=buckets,
                                            window_s=window_s)

    # ------------------------------------------------------------------
    # serializable state
    # ------------------------------------------------------------------

    def get_state(self) -> dict:
        """Fitted state as a flat dict of plain types + numpy arrays —
        feed it to ``np.savez``, JSON+base64, or ``ft.checkpoint``."""
        self._check_fitted()
        camp = self.fault.injection
        return {
            "cluster_centers": np.asarray(self.cluster_centers_),
            "counts": (np.asarray(self._counts)
                       if self._counts is not None else None),
            "n_iter": int(self.n_iter_),
            "inertia": (float(self.inertia_)
                        if self.inertia_ is not None else None),
            "detected_errors": int(self.detected_errors_),
            "config": {
                "n_clusters": self.n_clusters,
                "max_iter": self.max_iter,
                "tol": self.tol,
                "init": self.init,
                "backend": self.backend,
                "batch_size": self.batch_size,
                "sync_every": self.sync_every,
                "compute_dtype": self.compute_dtype.name,
                "predict_chunk_rows": self.predict_chunk_rows,
                "random_state": self.random_state,
                "params": (None if self.params is None else
                           [self.params.block_m, self.params.block_k,
                            self.params.block_f]),
                "fault": {
                    "mode": self.fault.mode,
                    "update_dmr": self.fault.update_dmr,
                    "worker_loss": self.fault.worker_loss,
                    "injection": (None if camp is None else {
                        "rate": camp.rate, "bit_low": camp.bit_low,
                        "bit_high": camp.bit_high, "seed": camp.seed,
                        "targets": camp.targets}),
                },
            },
        }

    @classmethod
    def from_state(cls, state: dict, *,
                   autotune: Optional[AutotuneCache] = None) -> "KMeans":
        """Reconstruct a fitted estimator from :meth:`get_state` output."""
        cfg = state["config"]
        fp = cfg["fault"]
        camp = fp.get("injection")
        fault = FaultPolicy(
            mode=fp["mode"], update_dmr=fp["update_dmr"],
            injection=None if camp is None else InjectionCampaign(**camp),
            worker_loss=fp.get("worker_loss", "fail"))  # pre-v3 states
        tiles = cfg.get("params")
        params = None if tiles is None else ops.KernelParams(*tiles)
        km = cls(cfg["n_clusters"], max_iter=cfg["max_iter"], tol=cfg["tol"],
                 init=cfg["init"], fault=fault, backend=cfg["backend"],
                 batch_size=cfg["batch_size"], params=params,
                 sync_every=cfg.get("sync_every", 10),  # pre-v2 states
                 compute_dtype=cfg.get("compute_dtype", "float32"),
                 predict_chunk_rows=cfg.get("predict_chunk_rows"),
                 random_state=cfg["random_state"], autotune=autotune)
        km.cluster_centers_ = jnp.asarray(state["cluster_centers"])
        counts = state.get("counts")
        km._counts = None if counts is None else jnp.asarray(counts)
        km.n_iter_ = int(state["n_iter"])
        inertia = state.get("inertia")
        km.inertia_ = None if inertia is None else float(inertia)
        km.detected_errors_ = int(state.get("detected_errors", 0))
        return km
