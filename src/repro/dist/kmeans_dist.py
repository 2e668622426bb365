"""Data-parallel FT K-means over a device mesh — up to pod scale.

Rows of X shard over the row axes; centroids replicate. Each Lloyd step
runs the policy-resolved assignment backend on the local shard (the fused
ABFT kernel protects each shard independently — SEU detection is local by
construction) and reduces per-cluster (sums, counts) across the mesh:
the distributed equality ``mean = psum(sums) / psum(counts)`` makes the
result bit-comparable to the single-device iteration.

The reduce itself follows a :class:`~repro.dist.reduce.ReducePlan`: on a
:func:`~repro.dist.sharding.mesh2d` mesh (axes ``("host", "row",
"problem")``) it runs hierarchically — exact psum inside each host group,
then one cross-host hop per iteration that can route through the int8
error-feedback transport (``ReducePlan.compressed()``) with an
``exact=True`` escape hatch. One-pass FT backends extend ABFT across
every hop: the shard's verified update checksums are psum'd alongside its
partials (they are linear, so the invariant survives each reduction) and
re-checked after *each* hop — corruption introduced by the reduction
itself lands in the returned ``detected`` total, and on the compressed
hop the expectations are taken on the dequantized values so quantization
error is never mistaken for corruption.

Accepts a ``repro.api.KMeans`` estimator or a ``repro.api.BatchedKMeans``
(problem-axis sharding — see below).

Problem-axis mode: handing ``DistributedKMeans`` a
:class:`~repro.batch.BatchedKMeans` switches the sharded dimension from
rows to *problems*. On a flat mesh each device runs the batched one-pass
chunk on its own slice of the (B, N, F) stack — no psum on the hot path,
bit-comparable per problem to the single-device batched fit because both
drivers run the same ``make_batched_chunk`` body. On a 2D mesh with row
parallelism (``mesh2d(rows, problems)`` with rows > 1) each problem's
rows additionally shard over the row axes and the per-problem (sums,
counts) reduce hierarchically — the same per-iteration arithmetic as the
batched chunk minus empty-cluster reseeding (donor rows are shard-local,
so row-sharded modes keep an empty cluster at its previous centroid; the
paths are bit-identical whenever no cluster empties).

Whole-worker failures: :meth:`DistributedKMeans.fit_elastic` runs the
row-mode fit under the recovery ladder's fail-stop rung — on
:class:`~repro.ft.elastic.WorkerLossError` it shrinks the mesh
(``plan_rescale_rows``), restores the last checkpoint and resumes,
when the estimator's :class:`~repro.api.FaultPolicy` says
``worker_loss="shrink"``.
"""
from __future__ import annotations

import json
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.reduce import ReducePlan, hop_axes, reduce_update
from repro.dist.sharding import data_axes
from repro.ft.elastic import WorkerLossError, build_mesh, plan_rescale_rows


def _host_read(value):
    """The one sanctioned device->host sync of the distributed driver:
    chunk-boundary convergence state and detection counters (same funnel
    contract as ``repro.api.estimator._host_read``)."""
    return jax.device_get(value)


def _axes_spec(axes: tuple):
    """PartitionSpec entry for a set of mesh axes (name, tuple, or None)."""
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def restore_estimator(checkpointer):
    """Rebuild ``(estimator, start_iteration)`` from the newest snapshot.

    Row-mode checkpoints written by :meth:`DistributedKMeans.fit` carry
    the estimator's full ``get_state`` config alongside the centroid
    arrays, so the elastic restart path — or a cold process — can restore
    the *estimator*, not just raw centroids: the FaultPolicy (including
    ``worker_loss``), backend pin, dtype and seeds all round-trip.
    Returns ``(None, 0)`` when no restorable snapshot exists.
    """
    st = checkpointer.restore()
    if st is None or "config_json" not in st:
        return None, 0
    from repro.api import KMeans
    cfg = json.loads(bytes(bytearray(st["config_json"])).decode())
    est = KMeans.from_state({
        "cluster_centers": st["centroids"], "counts": None,
        "n_iter": int(st["iteration"]), "inertia": None,
        "detected_errors": 0, "config": cfg})
    return est, int(st["iteration"])


class DistributedKMeans:
    def __init__(self, config, mesh, *, reduce: Optional[ReducePlan] = None):
        from repro.api import BatchedKMeans, KMeans as ApiKMeans
        if not isinstance(config, (ApiKMeans, BatchedKMeans)):
            raise TypeError(
                f"DistributedKMeans takes a repro.api.KMeans or "
                f"repro.api.BatchedKMeans estimator, got "
                f"{type(config).__name__}")
        self.problem_axis = isinstance(config, BatchedKMeans)
        self.est = config
        self.reduce = reduce if reduce is not None else ReducePlan()
        self._bind_mesh(mesh)

    def _bind_mesh(self, mesh) -> None:
        """Adopt a mesh: derive the row/problem axis split, the reduce
        hops, and drop every compiled step (a rescale re-resolves winners
        at the new per-shard shape — see ``autotune.shard_shape``)."""
        self.mesh = mesh
        self._daxes = data_axes(mesh)
        assert self._daxes, ("DistributedKMeans needs a mesh with at least "
                             "one data axis (got model-parallel-only mesh)")
        has_problem = "problem" in self._daxes
        if self.problem_axis:
            self._paxes = ("problem",) if has_problem else self._daxes
            self._raxes = tuple(a for a in self._daxes if a != "problem") \
                if has_problem else ()
        else:
            if has_problem and mesh.shape["problem"] != 1:
                raise ValueError(
                    f"single-problem KMeans on a mesh with problem axis "
                    f"size {mesh.shape['problem']}; shard a BatchedKMeans "
                    f"over it, or build mesh2d(rows, problems=1)")
            self._paxes = ()
            self._raxes = self._daxes
        self._rp = 1
        for a in self._raxes:
            self._rp *= mesh.shape[a]
        self._pp = 1
        for a in self._paxes:
            self._pp *= mesh.shape[a]
        self._row = _axes_spec(self._raxes if not self.problem_axis
                               else self._paxes)   # legacy spec attr
        self._dp = self._rp * self._pp
        self._intra, self._cross = hop_axes(mesh, self._raxes, self.reduce)
        self._compress = (not self.problem_axis) \
            and self.reduce.cross_host == "int8" and self._cross is not None
        self._steps: dict = {}

    # -- data placement -----------------------------------------------------

    def shard_data(self, x: jax.Array) -> jax.Array:
        x = jnp.asarray(x)
        if self.problem_axis:
            assert x.ndim == 3, (
                f"problem-axis mode shards stacked (B, N, F) problems, "
                f"got shape {x.shape}")
            assert x.shape[0] % self._pp == 0, (
                f"problems {x.shape[0]} must divide problem parallelism "
                f"{self._pp}")
            if self._rp > 1:
                assert x.shape[1] % self._rp == 0, (
                    f"rows {x.shape[1]} must divide row parallelism "
                    f"{self._rp}")
                spec = P(_axes_spec(self._paxes), _axes_spec(self._raxes),
                         None)
            else:
                spec = P(_axes_spec(self._paxes), None, None)
            return jax.device_put(x, NamedSharding(self.mesh, spec))
        assert x.shape[0] % self._rp == 0, (
            f"rows {x.shape[0]} must divide data parallelism {self._rp}")
        return jax.device_put(
            x, NamedSharding(self.mesh, P(_axes_spec(self._raxes), None)))

    # -- one reduced Lloyd step ---------------------------------------------

    def _shard_backend(self):
        """The per-shard assignment backend. Off-TPU, Pallas kernels run in
        interpret mode — Python-loop bound and far too slow to trace once
        per shard — so they resolve to their jnp analogues with the same
        protection level (fused_ft -> offline ABFT, fused -> XLA-fused)."""
        from repro.api import get_backend
        from repro.kernels.ops import on_tpu
        backend = self.est._backend
        if not on_tpu():
            backend = get_backend({
                "fused": "gemm_fused", "fused_ft": "abft_offline",
                "lloyd": "lloyd_xla", "lloyd_ft": "lloyd_ft_xla",
                "lloyd_batched": "lloyd_batched_xla",
            }.get(backend.name, backend.name))
        return backend

    def _build_step(self, m_local: int, f: int):
        est = self.est
        backend = self._shard_backend()
        k = est.n_clusters
        params = est._resolve_params(m_local, f) if backend.takes_params \
            else None
        raxes = self._raxes
        intra, cross = self._intra, self._cross
        compress = self._compress
        m_total = m_local * self._rp   # reduce-checksum threshold scale

        use_dmr = est.fault.dmr_enabled(backend)

        def local_step(x, c, inj, res):
            from repro.core.kmeans import means_from_sums, protected_sums
            # the estimator's compute dtype applies per shard, at the same
            # kernel boundary as the single-device fit (the tile selection
            # above is already keyed by it); centroids stay f32 across the
            # reduce and the update
            x = est._cast(x)
            out = backend(
                x, est._cast(c), params=params,
                inj=inj if backend.takes_injection else None)
            checked = backend.fuses_update and backend.supports_ft
            if backend.fuses_update:
                # one-pass backend: the shard's (sums, counts) come out of
                # the kernel epilogue — reduce them directly, no second pass
                am, md, det, sums, cnt = out
            else:
                am, md, det = out
                sums, cnt, _ = protected_sums(x, am, k, use_dmr=use_dmr)
            sums, cnt, bad, res_out = reduce_update(
                sums, cnt, intra=intra, cross=cross, compress=compress,
                residual=res[0] if compress else None,
                checked=checked, m_total=m_total)
            inertia = jax.lax.psum(jnp.sum(md), raxes)
            det = jax.lax.psum(det, raxes) + bad
            new_c = means_from_sums(sums, cnt, c)
            shift = jnp.sqrt(jnp.sum((new_c - c) ** 2))
            outs = (am, new_c, inertia, shift, det)
            if compress:
                outs = outs + (res_out[None],)
            return outs

        rspec = _axes_spec(self._raxes)
        in_specs = [P(rspec, None), P(None, None), P(None)]
        out_specs = [P(rspec), P(None, None), P(), P(), P()]
        if compress:
            # one error-feedback residual per host group, carried across
            # iterations; the intra-host psum makes every group member
            # compute the identical residual, so the block is consistent
            in_specs.append(P("host", None, None))
            out_specs.append(P("host", None, None))
        else:
            in_specs.append(P(None, None, None))

        return jax.jit(jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=tuple(in_specs), out_specs=tuple(out_specs),
            check_vma=False))

    # -- problem-axis mode: shard over B, no psum on the hot path -----------

    def _build_step_problems(self, b_local: int, n: int, f: int,
                             n_steps: int):
        """One ``n_steps``-iteration batched chunk per shard: each device
        runs :func:`~repro.batch.estimator.make_batched_chunk` — the exact
        body the single-device :class:`~repro.batch.BatchedKMeans` jits —
        on its own problems. No collective touches the iteration loop; the
        single psum folds the per-shard detected-error counters once per
        chunk (control plane, not hot path)."""
        from repro.batch.estimator import make_batched_chunk
        from repro.kernels import ops
        est = self.est
        backend = self._shard_backend()
        params = est._resolve_params(b_local, n, f) \
            if backend.takes_params else None
        chunk = make_batched_chunk(backend, params, est._cast, est.tol,
                                   n_steps)
        daxes = self._daxes

        def local_chunk(x, c, am, inertia, done):
            plan = ops.plan_data_batched(est._cast(x), params)
            det0 = jnp.zeros((), jnp.int32)
            (c, am, inertia, done, det), live = chunk(
                plan, c, am, inertia, done, det0)
            return c, am, inertia, done, jax.lax.psum(det, daxes), live

        row = _axes_spec(self._paxes)
        return jax.jit(jax.shard_map(
            local_chunk, mesh=self.mesh,
            in_specs=(P(row, None, None), P(row, None, None), P(row, None),
                      P(row), P(row)),
            out_specs=(P(row, None, None), P(row, None), P(row), P(row),
                       P(), P(None, row)),
            check_vma=False))

    def _fit_problems(self, xs: jax.Array, centroids: jax.Array,
                      max_iters: int, start_iteration: int,
                      checkpointer, checkpoint_interval: int,
                      on_iteration: Optional[Callable] = None):
        est = self.est
        bsz, n, f = xs.shape
        centroids = jnp.asarray(centroids, jnp.float32)
        am = jnp.zeros((bsz, n), jnp.int32)
        inertia = jnp.full((bsz,), jnp.inf, jnp.float32)
        done = jnp.zeros((bsz,), jnp.bool_)
        iters = np.zeros((bsz,), np.int64)
        total_det = 0
        it0 = start_iteration
        saved = False
        while it0 < max_iters:
            if on_iteration is not None:
                on_iteration(it0)
            n_steps = min(est.sync_every, max_iters - it0)
            key = (bsz // self._pp, n, f, n_steps, "problems")
            if key not in self._steps:
                self._steps[key] = self._build_step_problems(
                    bsz // self._pp, n, f, n_steps)
            centroids, am, inertia, done, det, live = self._steps[key](
                xs, centroids, am, inertia, done)
            done_h, live_h, det_h = _host_read((done, live, det))
            iters += live_h.sum(axis=0).astype(np.int64)
            total_det += int(det_h)
            it0 += n_steps
            saved = it0 % checkpoint_interval == 0
            if checkpointer is not None and saved:
                checkpointer.save(
                    it0, self._checkpoint_state(centroids, it0))
            if bool(done_h.all()):
                break
        if checkpointer is not None and not saved and it0 > start_iteration:
            checkpointer.save(it0, self._checkpoint_state(centroids, it0))
        return centroids, am, inertia, np.maximum(iters, 1), total_det

    # -- combined mode: problems x rows, hierarchical per-problem reduce ----

    def _build_step_combined(self, b_local: int, n_local: int, f: int):
        """One reduced Lloyd step for row-sharded stacked problems: the
        per-iteration arithmetic of ``make_batched_chunk``'s body — same
        freeze masks, same update — with the per-problem (sums, counts)
        reduced over the row axes instead of computed whole. Empty-cluster
        reseeding is the one intentional difference (donor rows are
        shard-local; empties keep their previous centroid), so results
        are bit-identical to the single-device batched fit exactly when
        no cluster empties."""
        from repro.core.kmeans import means_from_sums
        from repro.kernels import ops
        est = self.est
        backend = self._shard_backend()
        params = est._resolve_params(b_local, n_local, f) \
            if backend.takes_params else None
        if self.reduce.cross_host == "int8" and self._cross is not None:
            raise NotImplementedError(
                "the int8 cross-host hop carries one residual per host "
                "group and is row-mode (single-problem) only; use "
                "ReducePlan.compressed(exact=True) or the exact default "
                "for row-sharded problem stacks")
        intra, cross = self._intra, self._cross
        raxes, daxes = self._raxes, self._daxes
        tol = est.tol

        def local_step(x, c, am, inertia, done):
            xb = est._cast(x)
            plan = ops.plan_data_batched(xb, params) \
                if backend.takes_params else xb
            out = backend(plan, est._cast(c),
                          params=params if backend.takes_params else None)
            am_n, md, det_i, sums, cnt = out
            # exact hierarchical reduce of the per-problem partials over
            # the row hops; the problem axis is never reduced
            sums, cnt, _, _ = reduce_update(sums, cnt, intra=intra,
                                            cross=cross)
            inertia_n = jax.lax.psum(jnp.sum(md, axis=1), raxes)   # (Bl,)
            new_c = jax.vmap(means_from_sums)(sums, cnt, c)
            shift = jnp.sqrt(jnp.sum((new_c - c) ** 2, axis=(1, 2)))
            live = jnp.logical_not(done)
            new_c = jnp.where(live[:, None, None], new_c, c)
            am_o = jnp.where(live[:, None], am_n, am)
            inertia_o = jnp.where(live, inertia_n, inertia)
            done_n = jnp.logical_or(done, shift < tol)
            det = jax.lax.psum(jnp.sum(det_i).astype(jnp.int32), daxes)
            return new_c, am_o, inertia_o, done_n, det

        pspec = _axes_spec(self._paxes)
        rspec = _axes_spec(self._raxes)
        return jax.jit(jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(P(pspec, rspec, None), P(pspec, None, None),
                      P(pspec, rspec), P(pspec), P(pspec)),
            out_specs=(P(pspec, None, None), P(pspec, rspec), P(pspec),
                       P(pspec), P()),
            check_vma=False))

    def _fit_combined(self, xs: jax.Array, centroids: jax.Array,
                      max_iters: int, start_iteration: int,
                      checkpointer, checkpoint_interval: int,
                      on_iteration: Optional[Callable] = None):
        est = self.est
        bsz, n, f = xs.shape
        key = (bsz // self._pp, n // self._rp, f, "combined")
        if key not in self._steps:
            self._steps[key] = self._build_step_combined(
                bsz // self._pp, n // self._rp, f)
        step = self._steps[key]
        centroids = jnp.asarray(centroids, jnp.float32)
        am = jnp.zeros((bsz, n), jnp.int32)
        inertia = jnp.full((bsz,), jnp.inf, jnp.float32)
        done = jnp.zeros((bsz,), jnp.bool_)
        iters = np.zeros((bsz,), np.int64)
        total_det = 0
        completed = start_iteration
        saved = False
        for it in range(start_iteration, max_iters):
            if on_iteration is not None:
                on_iteration(it)
            done_h = _host_read(done)
            if bool(done_h.all()):
                break
            centroids, am, inertia, done, det = step(
                xs, centroids, am, inertia, done)
            det_h = _host_read(det)
            iters += np.logical_not(done_h).astype(np.int64)
            total_det += int(det_h)
            completed = it + 1
            saved = completed % checkpoint_interval == 0
            if checkpointer is not None and saved:
                checkpointer.save(
                    completed, self._checkpoint_state(centroids, completed))
        if checkpointer is not None and not saved and \
                completed > start_iteration:
            checkpointer.save(
                completed, self._checkpoint_state(centroids, completed))
        return centroids, am, inertia, np.maximum(iters, 1), total_det

    # -- checkpoint payloads -------------------------------------------------

    def _checkpoint_state(self, centroids, iteration: int) -> dict:
        """Snapshot payload: raw arrays plus — when the estimator has a
        ``get_state`` — its serialized config, so ``restore_estimator``
        rebuilds the full estimator (policy, backend, seeds) from the
        checkpoint alone."""
        payload = {"centroids": centroids,
                   "iteration": jnp.asarray(iteration, jnp.int32)}
        est = self.est
        if not self.problem_axis and hasattr(est, "get_state"):
            # mid-fit snapshot: stamp the current centroids so get_state()
            # (which requires a fitted estimator) serializes the config
            est.cluster_centers_ = jnp.asarray(centroids, jnp.float32)
            est.n_iter_ = iteration
            est._counts = getattr(est, "_counts", None)
            est.inertia_ = getattr(est, "inertia_", None)
            est.detected_errors_ = getattr(est, "detected_errors_", 0)
            state = est.get_state()
            payload["config_json"] = np.frombuffer(
                json.dumps(state["config"]).encode(), np.uint8).copy()
        return payload

    # -- driver --------------------------------------------------------------

    def fit(self, xs: jax.Array, centroids: jax.Array, *,
            max_iters: Optional[int] = None, start_iteration: int = 0,
            checkpointer=None, checkpoint_interval: int = 5,
            on_iteration: Optional[Callable] = None):
        """Run Lloyd iterations on sharded data.

        Returns (centroids, assign, inertia, iterations, detected) —
        ``iterations`` counts completed iterations from zero, so a restart
        with ``start_iteration`` continues the same trajectory.

        ``on_iteration`` (optional) is called with the iteration index at
        the *start* of each iteration (each chunk, in legacy problem-axis
        mode) — the fault-drill hook: a
        :class:`~repro.ft.elastic.FailureSchedule` raises
        :class:`~repro.ft.elastic.WorkerLossError` from here, before any
        of the iteration's work is spent.

        Problem-axis mode (a :class:`~repro.batch.BatchedKMeans` was
        passed): ``xs`` is the (B, N, F) problem stack sharded over B,
        ``centroids`` the (B, K, F) stack, and the returned ``assign`` /
        ``inertia`` / ``iterations`` all carry the per-problem leading
        axis (``iterations`` is each problem's executed count). With row
        parallelism (``mesh2d(rows, problems)``, rows > 1) each problem's
        rows also shard and the reduce runs hierarchically per problem.
        """
        est = self.est
        max_iters = max_iters if max_iters is not None else est.max_iter
        if self.problem_axis:
            args = (xs, centroids, max_iters, start_iteration,
                    checkpointer, checkpoint_interval, on_iteration)
            if self._rp > 1:
                return self._fit_combined(*args)
            return self._fit_problems(*args)
        m, f = xs.shape
        from repro.core.autotune import shard_shape
        m_local = shard_shape(m, est.n_clusters, f, self._rp)[0]
        key = (m_local, f, "row")
        if key not in self._steps:
            self._steps[key] = self._build_step(m_local, f)
        step = self._steps[key]
        shard_backend = self._shard_backend()
        if shard_backend.takes_injection:
            rng = est._campaign_rng()
            params = est._resolve_params(m_local, f)
        from repro.core.fault import no_step_injection

        def no_injection():
            return no_step_injection(shard_backend.kernel_kind)

        centroids = jnp.asarray(centroids)
        am = jnp.zeros((m,), jnp.int32)
        inertia = jnp.asarray(jnp.inf)
        total_det = jnp.zeros((), jnp.int32)
        k = est.n_clusters
        if self._compress:
            # per-host-group error-feedback residual, zero at fit start
            # and after every restart (the carry is transient by design:
            # EF bounds the accumulated error to one quantization step)
            res = jax.device_put(
                jnp.zeros((self.mesh.shape["host"], k, f), jnp.float32),
                NamedSharding(self.mesh, P("host", None, None)))
        else:
            res = jnp.zeros((1, k, f), jnp.float32)
        completed = start_iteration
        saved = False
        for it in range(start_iteration, max_iters):
            if on_iteration is not None:
                on_iteration(it)
            inj = no_injection()
            if shard_backend.takes_injection:
                inj = est._draw_injection(rng, m_local, f, params)
            if self._compress:
                am, centroids, inertia, shift, det, res = step(
                    xs, centroids, inj, res)
            else:
                am, centroids, inertia, shift, det = step(
                    xs, centroids, inj, res)
            total_det = total_det + det
            completed = it + 1
            saved = completed % checkpoint_interval == 0
            if checkpointer is not None and saved:
                checkpointer.save(
                    completed, self._checkpoint_state(centroids, completed))
            if float(_host_read(shift)) < est.tol:
                break
        if checkpointer is not None and not saved and \
                completed > start_iteration:
            # final durable snapshot: a run that converges (or crashes the
            # loop) between intervals must still be restartable
            checkpointer.save(
                completed, self._checkpoint_state(centroids, completed))
        return centroids, am, inertia, completed, total_det

    # -- elastic driver: survive fail-stop worker loss ------------------------

    def fit_elastic(self, x: jax.Array, centroids: jax.Array, *,
                    checkpointer, checkpoint_interval: int = 5,
                    max_iters: Optional[int] = None,
                    on_iteration: Optional[Callable] = None,
                    max_restarts: int = 8):
        """Row-mode fit that survives whole-worker loss (recovery ladder
        step 4) when the estimator's policy says ``worker_loss="shrink"``.

        On :class:`~repro.ft.elastic.WorkerLossError` — raised by the
        runtime, or in drills by a
        :class:`~repro.ft.elastic.FailureSchedule` passed as
        ``on_iteration`` — the driver removes the lost devices, replans
        the mesh with :func:`~repro.ft.elastic.plan_rescale_rows` (problem
        groups stay whole, rows shrink), rebinds and recompiles against
        the new per-shard shapes, restores the newest
        :class:`~repro.ft.Checkpointer` snapshot (the serialized
        ``get_state`` written by the fit loop) and resumes the trajectory
        from its iteration. A loss before the first durable snapshot
        restarts from the initial ``centroids``. With a policy of
        ``worker_loss="fail"`` (the default) the error propagates.

        ``x`` is the *unsharded* row matrix — each rescale reshards it.
        Returns ``(centroids, assign, inertia, iterations, detected,
        restarts)``.
        """
        assert not self.problem_axis, (
            "fit_elastic drives the row-sharded mode; problem-axis stacks "
            "restart whole (independent problems have no partial state to "
            "reshard)")
        est = self.est
        shrink = getattr(getattr(est, "fault", None), "worker_loss",
                         "fail") == "shrink"
        devices = list(self.mesh.devices.flat)
        problems = dict(self.mesh.shape).get("problem", 1)
        c = jnp.asarray(centroids)
        it0 = 0
        restarts = 0
        extra_det = 0
        while True:
            try:
                out = self.fit(
                    self.shard_data(x), c, max_iters=max_iters,
                    start_iteration=it0, checkpointer=checkpointer,
                    checkpoint_interval=checkpoint_interval,
                    on_iteration=on_iteration)
                c, am, inertia, completed, det = out
                return c, am, inertia, completed, det + extra_det, restarts
            except WorkerLossError as e:
                if not shrink or restarts >= max_restarts:
                    raise
                restarts += 1
                lost = set(e.lost)
                devices = [d for i, d in enumerate(devices)
                           if i not in lost]
                hosts = dict(self.mesh.shape).get("host", 1)
                plan = plan_rescale_rows(devices, problems=problems,
                                         hosts=hosts)
                self._bind_mesh(build_mesh(plan, devices))
                st = checkpointer.restore()
                if st is None:
                    # lost before the first durable snapshot: restart the
                    # whole trajectory from the initial seeds
                    it0 = 0
                    c = jnp.asarray(centroids)
                else:
                    c = jnp.asarray(st["centroids"])
                    it0 = int(st["iteration"])
