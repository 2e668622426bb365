"""Batched many-problem K-means estimator over the one-pass kernel stack.

One :class:`BatchedKMeans` fits B independent clustering problems at once:

    bkm = BatchedKMeans(n_clusters=8)
    bkm.fit(x)                  # x (B, N, F): B stacked problems
    labels = bkm.predict(x)     # (B, N) per-problem labels
    state = bkm.get_state()     # serializable fitted state

    bkm.fit(rows, lengths=n)    # rows (sum N, F): B ragged problems packed
    bkm.labels_                 # (sum N,) labels, packed the same way

The whole fit is one kernel launch per iteration (the batched one-pass
Lloyd kernel runs over every problem's row tiles) and one
``lax.scan`` per ``sync_every``-iteration chunk: per-problem convergence
masks freeze finished problems in place, so early convergers stop updating
without desynchronizing the batch, and per-problem results are
bit-identical to running each problem alone (same epilogue, same reduction
order, same seeds — problem ``b`` uses ``random_state + b``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.cache import AutotuneCache, default_cache
from repro.api.estimator import _host_read
from repro.api.registry import (AssignmentBackend, BackendCapabilityError,
                                get_backend)
from repro.kernels import ops

_INITS = ("kmeans++", "random", "kmeans++-fused")
_COMPUTE_DTYPES = ("float32", "bfloat16", "float16")


def make_batched_chunk(backend, params, cast, tol: float, n_steps: int):
    """Build the (un-jitted) ``n_steps``-iteration batched Lloyd chunk.

    One definition serves both drivers: :class:`BatchedKMeans` jits it
    directly, and the problem-axis-sharded ``DistributedKMeans`` mode runs
    it inside ``shard_map`` on each shard's slice of the problem stack —
    per-problem arithmetic (masks, reseeding, reduction order) is then
    identical on both paths by construction, which is what makes sharded
    results bit-comparable to single-device ones.

    Every step computes the full batched kernel launch, then a per-problem
    ``where`` mask keeps finished problems' centroids/labels/inertia
    frozen: early convergers stop *changing* without desynchronizing the
    batch (one problem's convergence can never alter another's
    arithmetic). The returned callable maps
    ``(plan, centroids, am0, inertia0, done0, det0)`` to
    ``((centroids, am, inertia, done, det), live_hist)`` where ``plan`` is
    the :class:`~repro.kernels.ops.BatchPlan` of the B problems (labels
    (B, n_max), 0 past each problem's rows) and ``live_hist`` has shape
    ``(n_steps, B)``.
    """
    from repro.core.kmeans import means_from_sums
    takes_params = backend.takes_params

    def chunk(plan, centroids, am0, inertia0, done0, det0):
        @obs.scope("step")
        def body(carry, t):
            c, am, inertia, done, det = carry
            with obs.scope("assign"):
                out = backend(plan, cast(c),
                              params=params if takes_params else None)
            am_n, md, det_i, sums, counts = out
            inertia_n = jnp.sum(md, axis=1)                    # (B,)
            with obs.scope("update"):
                new_c = jax.vmap(means_from_sums)(sums, counts, c)
            shift = jnp.sqrt(jnp.sum((new_c - c) ** 2, axis=(1, 2)))
            with obs.scope("reseed"):
                new_c = reseed_empty_batched(plan, new_c, counts, md)
            live = jnp.logical_not(done)                       # (B,)
            new_c = jnp.where(live[:, None, None], new_c, c)
            am_o = jnp.where(live[:, None], am_n, am)
            inertia_o = jnp.where(live, inertia_n, inertia)
            done_n = jnp.logical_or(done, shift < tol)
            det_o = det + det_i.astype(jnp.int32)
            return (new_c, am_o, inertia_o, done_n, det_o), live

        init = (centroids, am0, inertia0, done0, det0)
        return jax.lax.scan(body, init, jnp.arange(n_steps),
                            length=n_steps)

    return chunk


def reseed_empty_batched(plan, centroids: jax.Array, counts: jax.Array,
                         md: jax.Array) -> jax.Array:
    """:func:`~repro.core.kmeans.reseed_empty` for each problem of a
    :class:`~repro.kernels.ops.BatchPlan`, its donors drawn from its own
    rows only: the ``r``-th empty cluster takes the ``r``-th farthest row
    (``top_k`` orders ties by row, as the stable ``argsort`` does). It
    runs only in a step that left some cluster empty."""
    def reseed(c):
        far = jnp.where(plan.valid(), md, -jnp.inf)
        _, order = jax.lax.top_k(far, min(c.shape[1], plan.n_max))
        rank = jnp.cumsum(counts == 0, axis=1) - 1      # among the empties
        n = jnp.minimum(jnp.asarray(plan.lengths, jnp.int32),
                        order.shape[1])[:, None]
        donors = jnp.take_along_axis(order, jnp.clip(rank, 0, n - 1), axis=1)
        rows = jnp.asarray(plan.offsets, jnp.int32)[:, None] + donors
        return jnp.where((counts == 0)[..., None],
                         plan.xp[rows][..., :plan.f].astype(c.dtype), c)

    return jax.lax.cond(jnp.any(counts == 0), reseed, lambda c: c,
                        centroids)


class BatchedKMeans:
    """K-means over B independent problems, one launch per step.

    Fits ``x`` of shape ``(B, N, F)`` — B problems, each with N samples of
    F features — or, with ``lengths``, B problems of different row counts
    packed back to back as ``(sum N, F)`` rows, against per-problem
    centroid stacks ``(B, K, F)``. The paper's template framework
    (§III-B) adapts one kernel to many shapes; this estimator adapts one
    *launch* to many problems: the batched one-pass Lloyd kernel runs over
    every problem's row tiles, a tile map naming each tile's problem, so
    B small problems cost one dispatch instead of B (the regime where
    per-problem launches waste the MXU).

    Parameters
    ----------
    n_clusters : int, default=8
        Number of clusters K in *every* problem (stacked problems share
        K — ragged K would break the single centroid tile the batched
        template is built on).
    max_iter : int, default=100
        Lloyd iteration budget per problem.
    tol : float, default=1e-4
        Per-problem centroid-shift convergence threshold: problem ``b``
        freezes once ``||C_b' - C_b||_F < tol``. Frozen problems stop
        updating (their carry passes through the scan unchanged) but the
        batch keeps stepping until every problem froze or ``max_iter``.
    init : {"kmeans++", "random", "kmeans++-fused"}, default="kmeans++"
        Per-problem seeding; problem ``b`` draws from its own key (see
        ``random_state``). ``"kmeans++-fused"`` runs D² sampling through
        the fused round kernel (one launch per round for the whole batch,
        tiled inverse-CDF selection) instead of B vmapped categorical
        draws — same distribution, different stream consumption, so its
        seeds are reproducible against itself but not against
        ``"kmeans++"``.
    backend : str, optional
        Pin a registered backend by name; it must declare
        ``supports_batch=True``. Default: the batched Pallas kernel
        (``lloyd_batched``) on TPU, its XLA analogue
        (``lloyd_batched_xla``) elsewhere.
    params : KernelParams, optional
        Explicit tile override for the Pallas backend.
    autotune : AutotuneCache, optional
        Injectable kernel-selection table; defaults to the process cache.
        Batched winners live under the ``batched`` kind and the fit's B
        bucket (cache schema v4).
    sync_every : int, default=10
        Iterations per device-resident ``lax.scan`` chunk; the host
        observes convergence only at chunk boundaries.
    compute_dtype : {"float32", "bfloat16", "float16"}, default="float32"
        The arithmetic. Centroids are cast to it at the kernel boundary
        and the stored ``cluster_centers_`` stay f32 (same contract as
        :class:`repro.api.KMeans`). Rows are cast to it too, but rows
        stored in bf16 under ``"float32"`` stay bf16: the distance product
        takes the f32 centroids' three exact bf16 slices and the update
        one exact pass, so the arithmetic is f32 and no f32 copy of the
        rows is made (``ops.rows_dtype``).
    random_state : int, default=0
        Base seed. Problem ``b`` uses key ``PRNGKey(random_state + b)``
        for init (reseeding draws nothing), so a batched fit is
        bit-identical to B single-problem fits seeded ``random_state + b``.
        Seeds of any size work (the key's high word holds the bits above
        32).

    Attributes
    ----------
    cluster_centers_ : jax.Array, shape (B, K, F), float32
        Fitted per-problem centroids.
    labels_ : jax.Array, shape (B, N) or (sum N,), int32
        Assignment of each sample at the final executed iteration of its
        problem; packed like ``x`` after a ragged fit.
    inertia_ : numpy.ndarray, shape (B,), float
        Per-problem sum of squared distances at that iteration.
    n_iter_ : numpy.ndarray, shape (B,), int
        Iterations each problem actually executed before freezing.
    detected_errors_ : int
        Detected-SDC total (always 0 for the unprotected batched backends;
        the slot keeps the surface uniform with :class:`repro.api.KMeans`).
    rows_valid_, rows_padded_, row_tiles_ : int
        What each kernel launch of the last fit processed: the problems'
        rows, the zero rows that pad them to whole row tiles (and a
        stacked block to the tile grid), and the row tiles. Counters, read
        only; the XLA analogue pads nothing and has no tiles (0, 0).
    feature_lanes_, distance_passes_ : int
        The same launches' padded feature width (F itself on the XLA
        analogue) and bf16 MXU passes per distance product: 6 for f32
        rows, 3 for bf16 rows at f32 arithmetic, 1 for 2-byte arithmetic
        (``ops.distance_passes``).

    See Also
    --------
    repro.api.KMeans : the single-problem estimator (fault policies,
        streaming, chunked inference).
    repro.kernels.ops.fused_lloyd_batched : the underlying batched op.

    Notes
    -----
    Fault policies are not yet wired into the batched path: the batched
    kernel has no FT template, so there is no ``fault`` parameter here.
    Protect giant single problems with ``KMeans(fault=...)``; batched
    traffic is (for now) unprotected by construction.

    Every fit pads each problem to whole row tiles of the kernel; padded
    rows never enter labels, sums, counts, inertia or reseeding. Problems
    of one length launch ``lloyd_step_batched``, problems of different
    lengths the same kernel as ``lloyd_step_ragged``, so a ragged fit of
    equal lengths is the stacked fit, bit for bit. ``predict`` and
    ``score`` take stacked data only, and ``DistributedKMeans`` shards
    stacked problems only.

    The lengths are static: the pack, the chunk's scan and the label
    packing compile once per distinct ``lengths`` tuple, so a caller
    whose batches bring new lengths every time pays those compiles in
    every fit.

    Examples
    --------
    >>> import jax, jax.numpy as jnp
    >>> from repro.api import BatchedKMeans
    >>> x = jax.random.normal(jax.random.PRNGKey(0), (4, 256, 8))
    >>> bkm = BatchedKMeans(n_clusters=3, max_iter=10).fit(x)
    >>> bkm.cluster_centers_.shape
    (4, 3, 8)
    >>> bkm.predict(x).shape
    (4, 256)
    """

    def __init__(self, n_clusters: int = 8, *, max_iter: int = 100,
                 tol: float = 1e-4, init: str = "kmeans++",
                 backend: Optional[str] = None,
                 params=None,
                 autotune: Optional[AutotuneCache] = None,
                 sync_every: int = 10,
                 compute_dtype="float32",
                 random_state: int = 0):
        if n_clusters < 1:
            raise ValueError(f"n_clusters must be >= 1, got {n_clusters}")
        if init not in _INITS:
            raise ValueError(f"init must be one of {_INITS}, got {init!r}")
        if sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got {sync_every}")
        try:
            dtype_ok = jnp.dtype(compute_dtype).name in _COMPUTE_DTYPES
        except TypeError:
            dtype_ok = False
        if not dtype_ok:
            raise ValueError(f"compute_dtype must be one of "
                             f"{_COMPUTE_DTYPES}, got {compute_dtype!r}")
        self.n_clusters = n_clusters
        self.max_iter = max_iter
        self.tol = tol
        self.init = init
        self.backend = backend
        self.params = params
        self.autotune = autotune if autotune is not None else default_cache()
        self.sync_every = sync_every
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.random_state = random_state

        self._backend: AssignmentBackend = self._resolve_backend(backend)
        self._step_cache: dict = {}

        self.cluster_centers_: Optional[jax.Array] = None
        self.labels_: Optional[jax.Array] = None
        self.inertia_: Optional[np.ndarray] = None
        self.n_iter_: Optional[np.ndarray] = None
        self.detected_errors_: int = 0
        self.rows_valid_: int = 0
        self.rows_padded_: int = 0
        self.row_tiles_: int = 0
        self.feature_lanes_: int = 0
        self.distance_passes_: int = 0

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    @staticmethod
    def _resolve_backend(name: Optional[str]) -> AssignmentBackend:
        """Pick the batched backend: the Pallas kernel on TPU, the XLA
        analogue elsewhere; an explicit name must declare the batch
        capability (the (B, N, F) contract is not adapter-compatible with
        single-problem backends)."""
        if name is None:
            name = "lloyd_batched" if ops.on_tpu() else "lloyd_batched_xla"
        backend = get_backend(name)
        if not backend.supports_batch:
            raise BackendCapabilityError(
                f"BatchedKMeans needs a supports_batch backend (stacked "
                f"(B, N, F) contract), but {backend.name!r} declares "
                f"supports_batch=False; use 'lloyd_batched' / "
                f"'lloyd_batched_xla' or register a batched backend")
        return backend

    def _check_fitted(self):
        if self.cluster_centers_ is None:
            from repro.api.estimator import NotFittedError
            raise NotFittedError(
                "this BatchedKMeans instance is not fitted yet; call fit() "
                "first")

    def _cast(self, a: jax.Array) -> jax.Array:
        return a if a.dtype == self.compute_dtype else \
            a.astype(self.compute_dtype)

    def _cast_rows(self, x: jax.Array) -> jax.Array:
        """Rows in the dtype the kernels take them in
        (:func:`~repro.kernels.ops.rows_dtype`)."""
        dtype = ops.rows_dtype(x.dtype, self.compute_dtype)
        return x if x.dtype == dtype else x.astype(dtype)

    def _problem_keys(self, bsz: int) -> jax.Array:
        """Per-problem RNG keys: problem ``b`` seeds from
        ``random_state + b`` so its draws are independent of B (the
        batched-vs-loop bit-equality hinges on this). The keys are
        ``PRNGKey``'s words, built on the host: the seed's low 32 bits,
        and above them its bits past 32 (0 below 2**32 and for negative
        seeds, as an int32 seed gives), so a seed of any size works."""
        seeds = [self.random_state + b for b in range(bsz)]
        words = [((max(s, 0) >> 32) & 0xFFFFFFFF, s & 0xFFFFFFFF)
                 for s in seeds]
        words = np.asarray(words, np.uint32)  # analysis: allow=host-sync
        return jnp.asarray(words)

    def _resolve_params(self, bsz: int, n: int, f: int, rows_dtype=None):
        if not self._backend.takes_params:
            return None
        if self.params is not None:
            p = self.params
        else:
            _, p = self.autotune.lookup(n, self.n_clusters, f,
                                        kind=self._backend.kernel_kind,
                                        dtype=self.compute_dtype, batch=bsz)
        return ops.clamp_params(n, self.n_clusters, f, p,
                                dtype=rows_dtype or self.compute_dtype)

    def init_centroids(self, x: jax.Array,
                       keys: Optional[jax.Array] = None, *,
                       lengths=None) -> jax.Array:
        """Per-problem seeding: (B, K, F) from the stacked (B, N, F) data,
        or from ragged rows ``x`` (sum N, F) with ``lengths``, every
        problem drawing from its own key and its own rows."""
        if lengths is not None:
            return self._init_ragged(x, ops.ragged_lengths(x, lengths), keys)
        from repro.core.kmeans import init_kmeanspp, init_random
        if keys is None:
            keys = self._problem_keys(x.shape[0])
        if self.init == "kmeans++-fused":
            from repro.kernels.kmeanspp_init import init_kmeanspp_fused
            return init_kmeanspp_fused(keys, x, self.n_clusters,
                                       autotune=self.autotune)
        fn = init_kmeanspp if self.init == "kmeans++" else init_random
        return jax.vmap(fn, in_axes=(0, 0, None))(keys, x, self.n_clusters)

    def _init_ragged(self, x: jax.Array, lengths: tuple[int, ...],
                     keys: Optional[jax.Array]) -> jax.Array:
        """Seed ragged problems a length at a time: the problems of one
        length are gathered into a stack (only those rows) and seeded as
        a stacked batch is, so equal lengths seed as the stacked fit."""
        if keys is None:
            keys = self._problem_keys(len(lengths))
        starts = np.cumsum((0,) + lengths[:-1])
        lens = np.asarray(lengths)  # analysis: allow=host-sync (a tuple)
        out = jnp.zeros((len(lengths), self.n_clusters, x.shape[1]),
                        jnp.float32)
        for n in sorted(set(lengths)):
            idx = np.flatnonzero(lens == n)
            rows = starts[idx][:, None] + np.arange(n)[None, :]
            out = out.at[idx].set(self.init_centroids(x[rows], keys[idx]))
        return out

    def _chunk_fn(self, params, n_steps: int):
        """jit'd device-resident chunk of up to ``n_steps`` batched Lloyd
        iterations (see :func:`make_batched_chunk` for the per-problem
        convergence-mask semantics), memoized per (params, n_steps, tol)."""
        cache_key = ("chunk", params, n_steps, self.tol)
        if cache_key in self._step_cache:
            return self._step_cache[cache_key]
        fn = jax.jit(make_batched_chunk(self._backend, params, self._cast,
                                        self.tol, n_steps))
        self._step_cache[cache_key] = fn
        return fn

    # ------------------------------------------------------------------
    # estimator API
    # ------------------------------------------------------------------

    def fit(self, x: jax.Array, *, lengths=None,
            centroids: Optional[jax.Array] = None) -> "BatchedKMeans":
        """Run batched Lloyd iterations to per-problem convergence.

        Parameters
        ----------
        x : jax.Array, shape (B, N, F) or (sum N, F)
            B stacked problems sharing (N, K, F); or, with ``lengths``,
            the rows of B problems of any row counts packed back to back
            (problem 0's rows, then problem 1's, ...).
        lengths : array of int, shape (B,), optional
            Each packed problem's row count; ``sum(lengths)`` must equal
            the rows of ``x``. Every problem is clustered on its own rows
            only: its init draws from them, reseeding takes donors from
            them, and inertia and counts cover them alone.
        centroids : jax.Array, shape (B, K, F), optional
            Warm-start stack; default is per-problem ``init`` seeding.

        Returns
        -------
        self : BatchedKMeans
            With ``cluster_centers_``, ``labels_``, ``inertia_``,
            ``n_iter_`` populated (all carrying the leading B axis, but
            ``labels_`` of a ragged fit, which is packed like ``x``).
        """
        x = jnp.asarray(x)
        if lengths is None:
            if x.ndim != 3:
                raise ValueError(
                    f"BatchedKMeans.fit wants stacked (B, N, F) problems, "
                    f"got shape {x.shape}; pass lengths= for ragged "
                    f"problems packed as (sum N, F) rows, or use "
                    f"repro.api.KMeans for one problem")
            bsz = x.shape[0]
        else:
            lengths = ops.ragged_lengths(x, lengths)
            bsz = len(lengths)
        if centroids is None:
            split = jax.vmap(jax.random.split)(self._problem_keys(bsz))
            centroids = self.init_centroids(x, split[:, 1], lengths=lengths)
        centroids = jnp.asarray(centroids, jnp.float32)
        with obs.span("fit"):
            return self._fit_chunks(x, centroids, lengths)

    def _plan(self, x: jax.Array, lengths: Optional[tuple[int, ...]]):
        """The per-fit :class:`~repro.kernels.ops.BatchPlan` and its
        tiles, once per fit, under the span ``kmeans.plan`` for a stack
        and ``kmeans.pack`` for ragged rows. Ragged problems of different
        lengths take :func:`~repro.kernels.ops.ragged_params` tiles unless
        ``params`` were given."""
        bsz, n, f = x.shape if lengths is None else (
            len(lengths), max(lengths), x.shape[1])
        x = self._cast_rows(x)
        params = self._resolve_params(bsz, n, f, x.dtype)
        if (params is not None and self.params is None
                and len(set(lengths or (n,))) > 1):
            params = ops.ragged_params(params, n, self.n_clusters, f,
                                       x.dtype, self.compute_dtype)
        with obs.span("plan" if lengths is None else "pack"):
            # every problem padded to whole row tiles, once per fit
            plan = ops.plan_data_batched(x, params, lengths)
        self.rows_valid_, self.rows_padded_ = (plan.rows_valid,
                                               plan.rows_padded)
        self.row_tiles_ = int(plan.tile_prob.shape[0])
        self.feature_lanes_ = int(plan.xp.shape[1])
        self.distance_passes_ = ops.distance_passes(plan.xp.dtype,
                                                    self.compute_dtype)
        return plan, params

    def _fit_chunks(self, x: jax.Array, centroids: jax.Array,
                    lengths=None) -> "BatchedKMeans":
        """The device-resident Lloyd loop of :meth:`fit`, from seeded
        centroids: the per-fit plan, then one ``sync_every`` chunk and one
        host read per round."""
        plan, params = self._plan(x, lengths)
        bsz = plan.b

        am = jnp.zeros((bsz, plan.n_max), jnp.int32)
        inertia = jnp.full((bsz,), jnp.inf, jnp.float32)
        done = jnp.zeros((bsz,), jnp.bool_)
        det = jnp.zeros((), jnp.int32)
        iters = np.zeros((bsz,), np.int64)
        it0 = 0
        while it0 < self.max_iter:
            n_steps = min(self.sync_every, self.max_iter - it0)
            with obs.span("dispatch"):
                chunk = self._chunk_fn(params, n_steps)
                (centroids, am, inertia, done, det), live_hist = chunk(
                    plan, centroids, am, inertia, done, det)
            with obs.span("sync"):
                done_h, live_h = _host_read((done, live_hist))
            iters += live_h.sum(axis=0).astype(np.int64)
            it0 += n_steps
            if bool(done_h.all()):
                break

        self.cluster_centers_ = centroids
        self.labels_ = am if lengths is None else plan.packed(am)
        with obs.span("sync"):
            inertia_h, det_h = _host_read((inertia, det))
        self.inertia_ = np.asarray(inertia_h, np.float64)
        self.n_iter_ = np.maximum(iters, 1)
        self.detected_errors_ = int(det_h)
        return self

    def fit_predict(self, x: jax.Array) -> jax.Array:
        """Fit the B problems and return ``labels_`` (shape (B, N))."""
        return self.fit(x).labels_

    def _assign(self, x: jax.Array):
        bsz, n, f = x.shape
        params = self._resolve_params(
            bsz, n, f, ops.rows_dtype(x.dtype, self.compute_dtype))
        key = ("assign", params)
        if key not in self._step_cache:
            backend = self._backend
            cast, cast_rows = self._cast, self._cast_rows
            if backend.takes_params:
                fn = jax.jit(lambda x, c: backend(cast_rows(x), cast(c),
                                                  params=params)[:2])
            else:
                fn = jax.jit(lambda x, c: backend(cast_rows(x), cast(c))[:2])
            self._step_cache[key] = fn
        return self._step_cache[key](x, self.cluster_centers_)

    def _check_stack(self, x: jax.Array) -> jax.Array:
        self._check_fitted()
        x = jnp.asarray(x)
        if x.ndim == 2:
            raise ValueError(
                f"predict and score take stacked (B, N, F) problems; "
                f"ragged rows packed as {x.shape} are not supported")
        if x.ndim != 3 or x.shape[0] != self.cluster_centers_.shape[0]:
            raise ValueError(
                f"predict wants (B, N, F) with B={self.cluster_centers_.shape[0]} "
                f"fitted problems, got shape {x.shape}")
        return x

    def predict(self, x: jax.Array) -> jax.Array:
        """Per-problem nearest-centroid labels for new stacked data.

        Parameters
        ----------
        x : jax.Array, shape (B, N', F)
            New samples; B must match the fitted problem count. Ragged
            (packed) rows are not supported.

        Returns
        -------
        labels : jax.Array, shape (B, N'), int32
        """
        return self._assign(self._check_stack(x))[0]

    def score(self, x: jax.Array) -> np.ndarray:
        """Per-problem negative inertia on ``x`` (sklearn sign convention:
        higher is better). Returns shape (B,)."""
        _, md = self._assign(self._check_stack(x))
        return -np.asarray(_host_read(jnp.sum(md, axis=1)), np.float64)

    # ------------------------------------------------------------------
    # serializable state
    # ------------------------------------------------------------------

    def get_state(self) -> dict:
        """Fitted state as a flat dict of plain types + numpy arrays
        (``np.savez`` / JSON+base64 / ``ft.checkpoint`` compatible)."""
        self._check_fitted()
        return {
            "cluster_centers": np.asarray(self.cluster_centers_),
            "n_iter": np.asarray(self.n_iter_),
            "inertia": (None if self.inertia_ is None
                        else np.asarray(self.inertia_)),
            "detected_errors": int(self.detected_errors_),
            "config": {
                "n_clusters": self.n_clusters,
                "max_iter": self.max_iter,
                "tol": self.tol,
                "init": self.init,
                "backend": self.backend,
                "sync_every": self.sync_every,
                "compute_dtype": self.compute_dtype.name,
                "random_state": self.random_state,
                "params": (None if self.params is None else
                           [self.params.block_m, self.params.block_k,
                            self.params.block_f]),
            },
        }

    @classmethod
    def from_state(cls, state: dict, *,
                   autotune: Optional[AutotuneCache] = None
                   ) -> "BatchedKMeans":
        """Reconstruct a fitted estimator from :meth:`get_state` output."""
        cfg = state["config"]
        tiles = cfg.get("params")
        params = None if tiles is None else ops.KernelParams(*tiles)
        bkm = cls(cfg["n_clusters"], max_iter=cfg["max_iter"],
                  tol=cfg["tol"], init=cfg["init"], backend=cfg["backend"],
                  params=params, sync_every=cfg.get("sync_every", 10),
                  compute_dtype=cfg.get("compute_dtype", "float32"),
                  random_state=cfg["random_state"], autotune=autotune)
        bkm.cluster_centers_ = jnp.asarray(state["cluster_centers"])
        bkm.n_iter_ = np.asarray(state["n_iter"])
        inertia = state.get("inertia")
        bkm.inertia_ = None if inertia is None else np.asarray(inertia)
        bkm.detected_errors_ = int(state.get("detected_errors", 0))
        return bkm
