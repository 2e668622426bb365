"""Cluster-assignment backends — the paper's stepwise ladder (§III-A).

Each implementation maps (x (M, F), c (K, F)) ->
(assign (M,) int32, true squared distance (M,), detected errors):

  gemm_fused   paper V2/V3 analogue on XLA: one jit so XLA fuses the GEMM
               epilogue with the reduction (cuML-analogue baseline).
  fused        paper V4/V5: the Pallas fused kernel (MXU + in-VMEM argmin).
  int8         quantized distance template, one dtype notch past the
               paper's fp16 floor: per-row symmetric int8 quantization of
               X and C, i8 x i8 -> i32 MXU tiles, f32 scale correction +
               exact norm terms in the epilogue. Bit-exact argmin vs the
               f32 backends on quantization-safe data, error-bounded on
               floats; accepts a per-fit ``ops.QuantPlan``.
  int8_xla     XLA analogue of the int8 template (f32-carrier GEMM over
               the same quantized integers; non-TPU fast path).
  fused_ft     §IV: fused kernel + dual-checksum ABFT online correction.
  abft_offline Wu-et-al-style baseline: checksummed GEMM *without* fusion —
               detection happens on the materialized product (the scheme the
               paper argues breaks down post-Ampere; here it demonstrates
               the fusion win, not the register-reuse mechanics).
  lloyd        one-pass Lloyd (paper Fig. 4 shape): the Pallas kernel's
               epilogue also accumulates per-cluster sums/counts, so a full
               iteration reads X from HBM once. Extended 5-tuple contract
               (``fuses_update=True``).
  lloyd_xla    XLA analogue of the one-pass kernel (non-TPU fast path).
  lloyd_ft     §IV composed with Fig. 4: the one-pass kernel with the
               dual-checksum ABFT fused around the distance GEMM and the
               checksum-protected update epilogue (verified + recomputed
               in the jitted tree-reduction) — the default ``correct``
               protection path, no longer forfeiting the one-pass speedup.
  lloyd_ft_xla XLA analogue of the one-pass FT backend (non-TPU fast path;
               detection + correction at the XLA level, no in-kernel
               injection surface).
  lloyd_batched     batched one-pass Lloyd: B independent problems, a
               (B, N, F) stack or a BatchPlan of packed rows, against
               (B, K, F) centroids in one kernel launch over the problems'
               row tiles (``supports_batch=True``; every output gains a
               leading B axis).
  lloyd_batched_xla XLA analogue of the batched kernel (batched
               contractions; non-TPU fast path).
  lloyd_pruned one-pass Lloyd with tile-granular triangle-inequality
               pruning: Hamerly bounds carried between iterations skip
               whole centroid tiles that provably cannot change any
               assignment (``supports_bounds=True``; extended 7-tuple with
               the new bounds state and the pruned-tile fraction).
               Bit-identical to ``lloyd`` by construction.
  lloyd_pruned_xla XLA analogue at finer granularity (row chunks x
               16-centroid groups, ``lax.cond`` per cell so skipped groups
               cost nothing off-TPU) — the non-TPU fast path.

This module only defines the functions. ``repro.api.registry`` names each
one as an :class:`~repro.api.registry.AssignmentBackend` declaring its
capabilities (``supports_ft`` / ``takes_params`` / ``takes_injection``);
drivers obtain one via ``repro.api.get_backend(name)`` or let a
``FaultPolicy`` resolve it, and call it with the uniform
``backend(x, c, *, params=None, inj=None)`` signature.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import checksum
from repro.core.ft_gemm import ft_matmul
from repro.kernels import ops, ref


def _zero():
    return jnp.zeros((), jnp.int32)


@jax.jit
def assign_gemm_fused(x: jax.Array, c: jax.Array):
    d = ref.distance_matrix(x, c)
    return jnp.argmin(d, axis=1).astype(jnp.int32), jnp.min(d, axis=1), _zero()


def _row_norms(x) -> jax.Array:
    """True-distance correction term; reuses the DataPlan's precomputed
    norms instead of re-norming X every iteration. Always f32, like the
    plan's norms — bf16/fp16 X must not degrade the distance offsets. The
    QuantPlan's norms are the *unquantized* rows' (exact), matching the
    int8 template's exact-norm contract."""
    if isinstance(x, (ops.DataPlan, ops.QuantPlan)):
        return x.xn
    xf = x.astype(jnp.float32)
    return jnp.sum(xf * xf, axis=1)


def assign_fused(x, c: jax.Array, params=None):
    am, md = ops.fused_assign(x, c, params)
    return am, md + _row_norms(x), _zero()


def assign_fused_ft(x, c: jax.Array, params=None,
                    inj: Optional[jax.Array] = None):
    am, md, det = ops.fused_assign_ft(x, c, params, inj=inj)
    return am, md + _row_norms(x), det


def assign_int8(x, c: jax.Array, params=None):
    # int8 distance template (one dtype notch past the paper's fp16
    # floor): per-row symmetric quantization of X and C, i8 x i8 -> i32
    # tile products, f32 scale correction + exact norm terms in the
    # epilogue. x may be a raw array or a prebuilt ops.QuantPlan (the
    # per-fit quantization); centroids are quantized per call (they move
    # every iteration).
    am, md = ops.fused_assign_int8(x, c, params)
    return am, md + _row_norms(x), _zero()


@jax.jit
def assign_int8_xla(x, c: jax.Array):
    # XLA analogue of the int8 template (non-TPU fast path): the same
    # per-row quantization and scale-corrected epilogue, with the i8 x i8
    # product carried in f32 — XLA's CPU int8 GEMM is several times slower
    # than f32, and the f32 carrier holds the identical integers for any
    # F <= 1040 (F * 127^2 < 2^24), so numerics match the kernel's int32
    # accumulator bit-for-bit on quantization-safe data.
    from repro.dist.compression import quantize_rows
    if isinstance(x, ops.QuantPlan):
        qx = x.xq[:x.m, :x.f].astype(jnp.float32)
        sx = x.sx[:x.m]
        xn = x.xn
    else:
        xf = x.astype(jnp.float32)
        q, sx = quantize_rows(xf)
        qx = q.astype(jnp.float32)
        xn = jnp.sum(xf * xf, axis=1)
    cf = c.astype(jnp.float32)
    qc, sc = quantize_rows(cf)
    cn = jnp.sum(cf * cf, axis=1)
    cross = jnp.matmul(qx, qc.astype(jnp.float32).T,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    d = cn[None, :] - 2.0 * (sx * cross * sc.T)
    am = jnp.argmin(d, axis=1).astype(jnp.int32)
    return am, jnp.min(d, axis=1) + xn, _zero()


def assign_lloyd(x, c: jax.Array, params=None):
    # One-pass Lloyd (paper Fig. 4 shape): the Pallas kernel's epilogue
    # also accumulates per-cluster sums/counts, so the driver never
    # re-reads X for the centroid update. Extended 5-tuple contract.
    am, md, sums, counts = ops.fused_lloyd(x, c, params)
    return am, md, _zero(), sums, counts


def assign_lloyd_ft(x, c: jax.Array, params=None,
                    inj: Optional[jax.Array] = None):
    # One-pass FT Lloyd: the paper's §IV dual-checksum ABFT fused around
    # the distance GEMM *and* checksum protection of the one-hot update
    # epilogue (verified + recomputed in the jitted tree-reduction) — the
    # Fig. 6 scheme composed with the fused-update iteration.
    am, md, sums, counts, det = ops.fused_lloyd_ft(x, c, params, inj=inj)
    return am, md, det, sums, counts


@jax.jit
def assign_lloyd_xla(x: jax.Array, c: jax.Array):
    # XLA analogue of the one-pass kernel: assignment and the one-hot
    # update GEMM in a single fused graph (the non-TPU fast path).
    d = ref.distance_matrix(x, c)
    am = jnp.argmin(d, axis=1).astype(jnp.int32)
    md = jnp.min(d, axis=1)
    onehot = jax.nn.one_hot(am, c.shape[0], dtype=x.dtype)
    sums = jax.lax.dot_general(onehot, x, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    counts = jnp.sum(onehot.astype(jnp.float32), axis=0)
    return am, md, _zero(), sums, counts


@jax.jit
def assign_lloyd_ft_xla(x: jax.Array, c: jax.Array):
    # XLA analogue of the one-pass FT kernel (non-TPU fast path): the
    # distance cross product carries the paper's minimal dual *column*
    # checksum pair — e1/e2 over rows detect a single SEU, locate it
    # (column from the residual position, row from the e2/e1 ratio) and
    # correct it in place; the one-hot update is verified against
    # input-side e1/e2 encodings with a recompute-on-mismatch
    # fail-continue fix. Column-only verification halves the memory
    # passes of the full ft_matmul (this path exists to be the *fast*
    # host analogue); the in-kernel SEU descriptor surface is Pallas-only.
    k, m = c.shape[0], x.shape[0]
    xf = x.astype(jnp.float32)
    cf32 = c.astype(jnp.float32)
    cross = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    e1x = jnp.sum(xf, axis=0)                                # (F,)
    w_m = jnp.arange(1.0, m + 1.0, dtype=jnp.float32)
    e2x = w_m @ xf                                           # (F,)
    exp_c1 = e1x @ cf32.T                                    # (K,)
    exp_c2 = e2x @ cf32.T
    res_c1 = jnp.sum(cross, axis=0) - exp_c1
    res_c2 = w_m @ cross - exp_c2
    # clean-side scale (see the kernels: a corrupted-side scale would
    # self-mask large deltas); the column sums run over M rows, hence the
    # M-length contraction in the factor
    dscale = jnp.maximum(jnp.max(jnp.abs(exp_c1)), 1.0)
    dthr = checksum.threshold_factor(m * x.shape[1], x.dtype) * dscale
    j = jnp.argmax(jnp.abs(res_c1)).astype(jnp.int32)
    delta = res_c1[j]
    det_d = jnp.abs(delta) > dthr
    safe = jnp.where(delta == 0.0, 1.0, delta)
    i = jnp.clip((jnp.round(res_c2[j] / safe) - 1.0).astype(jnp.int32),
                 0, m - 1)
    fixed = cross.at[i, j].add(-delta)
    cross = jnp.where(det_d, fixed, cross)
    d = (jnp.sum(xf ** 2, axis=1, keepdims=True)
         + jnp.sum(cf32 ** 2, axis=1)[None, :] - 2.0 * cross)
    am = jnp.argmin(d, axis=1).astype(jnp.int32)
    md = jnp.min(d, axis=1)

    def update(x, am):
        onehot = jax.nn.one_hot(am, k, dtype=x.dtype)
        sums = jax.lax.dot_general(onehot, x, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        counts = jnp.sum(onehot.astype(jnp.float32), axis=0)
        return sums, counts

    sums, counts = update(x, am)
    # epilogue checksums: e1^T (onehot^T X) = colsum(X) (= e1x, already
    # encoded above) and e2^T (onehot^T X) = (am+1)^T X — computed from
    # the inputs, never from the one-hot product they verify; each pair
    # thresholds against its own clean-side magnitude
    amw = (am + 1).astype(jnp.float32)
    exp2 = amw @ xf
    w_k = jnp.arange(1.0, k + 1.0, dtype=jnp.float32)
    factor = checksum.threshold_factor(m, x.dtype)
    thr1 = factor * jnp.maximum(jnp.max(jnp.abs(e1x)), 1.0)
    thr2 = factor * jnp.maximum(jnp.max(jnp.abs(exp2)), 1.0)
    cexp2 = jnp.sum(amw)
    bad = (jnp.any(jnp.abs(jnp.sum(sums, axis=0) - e1x) > thr1)
           | jnp.any(jnp.abs(w_k @ sums - exp2) > thr2)
           | (jnp.abs(jnp.sum(counts) - m) > factor * m)
           | (jnp.abs(w_k @ counts - cexp2)
              > factor * jnp.maximum(cexp2, 1.0)))

    def recompute(_):
        return update(jax.lax.optimization_barrier(x),
                      jax.lax.optimization_barrier(am))

    sums, counts = jax.lax.cond(bad, recompute,
                                lambda _: (sums, counts), operand=None)
    return (am, md, det_d.astype(jnp.int32) + bad.astype(jnp.int32),
            sums, counts)


def assign_lloyd_pruned(x, c: jax.Array, params=None, *, bounds=None):
    # Pruned one-pass Lloyd: the Pallas kernel skips whole (row tile,
    # centroid tile) cells whose decayed group lower bound cannot beat the
    # row tile's upper bound. Extended 7-tuple contract — the new bounds
    # state threads into the next iteration, the prune fraction into the
    # fit history.
    am, md, sums, counts, new_bounds, frac = ops.fused_lloyd_pruned(
        x, c, params, bounds=bounds)
    return am, md, _zero(), sums, counts, new_bounds, frac


# Granularity of the XLA pruned analogue: row chunks x centroid groups.
# Groups are much finer than a 128-wide MXU tile because XLA's skip
# mechanism (lax.cond) pays no lane-alignment cost — finer groups prune
# more, which is the whole point off-TPU.
_PRUNE_ROWS = 2048
_PRUNE_GROUP = 16


def _pruned_xla_grid(m: int, k: int) -> tuple[int, int, int, int]:
    """(row tile, num row tiles, group size, num groups) for (m, k)."""
    rt = min(_PRUNE_ROWS, m)
    g = min(_PRUNE_GROUP, k)
    return rt, -(-m // rt), g, -(-k // g)


def init_bounds_xla(m: int, k: int, f: int, params=None, *,
                    dtype=jnp.float32) -> ops.BoundsState:
    """Fresh bounds state shaped for the XLA pruned analogue's grid
    (``params`` and ``dtype`` are accepted for signature uniformity with
    :func:`ops.init_bounds` but the XLA grid does not depend on them)."""
    del params, dtype
    rt, nmt, g, kg = _pruned_xla_grid(m, k)
    return ops.BoundsState(
        ub=jnp.zeros((m,), jnp.float32),
        assign=jnp.zeros((m,), jnp.int32),
        tmin=jnp.zeros((nmt, kg), jnp.float32),
        c_prev=jnp.zeros((kg * g, f), jnp.float32),
        fresh=jnp.ones((), bool),
    )


@jax.jit
def assign_lloyd_pruned_xla(x: jax.Array, c: jax.Array, *, bounds=None):
    # XLA analogue of the pruned one-pass kernel: the distance work runs
    # per (row chunk, centroid group) cell under a lax.cond, so a skipped
    # cell costs nothing on CPU/GPU. The min fold over groups is exact
    # (strict compare, earlier group wins ties — the same first-index
    # tie-break as a whole-matrix argmin) and the one-hot update is the
    # verbatim assign_lloyd_xla update, so a run with pruning disabled is
    # bit-identical to this backend with bounds reset every call.
    m, f = x.shape
    k = c.shape[0]
    rt, nmt, g, kg = _pruned_xla_grid(m, k)
    mp, kp = nmt * rt, kg * g
    if bounds is None:
        bounds = init_bounds_xla(m, k, f)
    xp = jnp.pad(x, ((0, mp - m), (0, 0)))
    cp = jnp.pad(c, ((0, kp - k), (0, 0)))
    xf = xp.astype(jnp.float32)
    cf = cp.astype(jnp.float32)
    xn = jnp.sum(xf * xf, axis=1, keepdims=True)                 # (mp, 1)
    cn = jnp.where(jnp.arange(kp) < k,
                   jnp.sum(cf * cf, axis=1), jnp.inf)            # (kp,)
    big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)
    # Skip decision — the same decayed-bound test as ops.fused_lloyd_pruned
    drift = jnp.sqrt(jnp.sum((cf - bounds.c_prev) ** 2, axis=1))   # (kp,)
    gdrift = jnp.max(drift.reshape(kg, g), axis=1)                 # (kg,)
    ub_adj = bounds.ub + drift[bounds.assign]
    maxub = jnp.max(
        jnp.pad(ub_adj, (0, mp - m), constant_values=-jnp.inf)
        .reshape(nmt, rt), axis=1)                                 # (nmt,)
    tlb = bounds.tmin - gdrift[None, :]                            # (nmt, kg)
    if kg == 1:
        skip = jnp.zeros((nmt, kg), bool)
    else:
        can = tlb > maxub[:, None] * (1.0 + ops.PRUNE_SLACK) + ops.PRUNE_SLACK
        skip = jnp.logical_and(can, jnp.logical_not(bounds.fresh))
    ams, mds, tmins = [], [], []
    for i in range(nmt):
        xt = xp[i * rt:(i + 1) * rt]
        xnt = xn[i * rt:(i + 1) * rt]
        valid = (jnp.arange(rt) + i * rt) < m
        md_t = jnp.full((rt,), big, jnp.float32)
        am_t = jnp.zeros((rt,), jnp.int32)
        tmin_t = []
        for j in range(kg):
            cg = cp[j * g:(j + 1) * g]
            cng = cn[j * g:(j + 1) * g]

            def _compute(op, cg=cg, cng=cng, xt=xt, xnt=xnt, valid=valid,
                         base=j * g):
                md_t, am_t = op
                cross = jnp.matmul(xt, cg.T,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
                dcell = xnt + cng[None, :] - 2.0 * cross         # (rt, g)
                gmin = jnp.min(dcell, axis=1)
                garg = jnp.argmin(dcell, axis=1).astype(jnp.int32) + base
                take = gmin < md_t
                tmin_ij = jnp.min(jnp.where(
                    valid, jnp.sqrt(jnp.maximum(gmin, 0.0)), big))
                return (jnp.where(take, gmin, md_t),
                        jnp.where(take, garg, am_t), tmin_ij)

            def _skipped(op):
                md_t, am_t = op
                return md_t, am_t, big

            md_t, am_t, tmin_ij = jax.lax.cond(
                skip[i, j], _skipped, _compute, (md_t, am_t))
            tmin_t.append(tmin_ij)
        ams.append(am_t)
        mds.append(md_t)
        tmins.append(jnp.stack(tmin_t))
    am = jnp.concatenate(ams)[:m]
    md = jnp.concatenate(mds)[:m]
    tmin_k = jnp.stack(tmins)                                    # (nmt, kg)
    # the verbatim assign_lloyd_xla one-hot update (same accumulation
    # order, so final centroids cannot drift from the unpruned backend)
    onehot = jax.nn.one_hot(am, k, dtype=x.dtype)
    sums = jax.lax.dot_general(onehot, x, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    counts = jnp.sum(onehot.astype(jnp.float32), axis=0)
    new_bounds = ops.BoundsState(
        ub=jnp.sqrt(jnp.maximum(md, 0.0)),
        assign=am,
        tmin=jnp.where(skip, tlb, tmin_k),
        c_prev=cf,
        fresh=jnp.zeros((), bool),
    )
    frac = jnp.mean(skip.astype(jnp.float32))
    return am, md, _zero(), sums, counts, new_bounds, frac


def assign_lloyd_batched(x, c: jax.Array, params=None):
    # Batched one-pass Lloyd: B independent problems through one kernel
    # launch over their row tiles (smallk epilogue per tile — batched
    # problems have small K by construction). Extended 5-tuple contract
    # with a leading B axis; a BatchPlan's problems may differ in row
    # count, (assign, min_dist) then (B, n_max), zero past each problem's
    # rows.
    am, md, sums, counts = ops.fused_lloyd_batched(x, c, params)
    return am, md, _zero(), sums, counts


@jax.jit
def assign_lloyd_batched_xla(x, c: jax.Array):
    # XLA analogue of the batched one-pass kernel (non-TPU fast path): the
    # per-problem distance GEMM, argmin and one-hot update run as batched
    # contractions over the stacked (B, N, F) / (B, K, F) operands — XLA
    # loops the problem axis outside each GEMM, so per-problem numerics
    # match the B=1 call bit-for-bit while one dispatch covers all B.
    # A BatchPlan is spread to (B, n_max, F); ragged problems get zero
    # rows past their ends, masked out of the update: the CPU path only,
    # where the stack is small.
    valid = None
    if isinstance(x, ops.BatchPlan):
        valid = None if x.stacked else x.valid()
        x = x.x
    k = c.shape[1]
    xf = x.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    cross = jnp.matmul(x, jnp.swapaxes(c, 1, 2),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)       # (B, N, K)
    d = (jnp.sum(xf * xf, axis=2, keepdims=True)
         + jnp.sum(cf * cf, axis=2)[:, None, :] - 2.0 * cross)
    am = jnp.argmin(d, axis=2).astype(jnp.int32)                 # (B, N)
    md = jnp.min(d, axis=2)
    onehot = jax.nn.one_hot(am, k, dtype=x.dtype)                # (B, N, K)
    if valid is not None:
        onehot = onehot * valid[:, :, None].astype(x.dtype)
        am, md = jnp.where(valid, am, 0), jnp.where(valid, md, 0.0)
    sums = jax.lax.dot_general(
        onehot, x, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                      # (B, K, F)
    counts = jnp.sum(onehot.astype(jnp.float32), axis=1)         # (B, K)
    return am, md, _zero(), sums, counts


@jax.jit
def assign_abft_offline(x: jax.Array, c: jax.Array):
    cross, detected = ft_matmul(x, c.T)
    d = (jnp.sum(x * x, axis=1, keepdims=True)
         + jnp.sum(c * c, axis=1)[None, :] - 2.0 * cross)
    return (jnp.argmin(d, axis=1).astype(jnp.int32), jnp.min(d, axis=1),
            detected.astype(jnp.int32))

