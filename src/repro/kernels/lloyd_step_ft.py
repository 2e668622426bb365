"""Fault-tolerant one-pass Lloyd kernel (paper §IV Fig. 6 composed with §III
Fig. 4 — the ABFT epilogue on the fused-update iteration).

``lloyd_step`` fused the centroid update's accumulation into the assignment
kernel so X is read from HBM once per iteration; ``distance_argmin_ft``
fused the paper's dual-checksum ABFT into the distance GEMM. Before this
kernel the two were mutually exclusive: enabling fault tolerance forfeited
the one-pass speedup. This kernel is their composition — both protection
layers ride the same streamed tiles:

  * **distance GEMM** (compute-bound): the e1/e2 column/row checksums of
    D = X C^T accumulate from the VMEM-resident tiles exactly as in
    ``distance_argmin_ft``; at the verification interval (last feature
    step of each (m, k) tile) an e1 residual above the dtype-aware
    threshold sends that tile, and only that one, on to location via the
    e2/e1 ratio and correction in place (``verify_and_reduce``) — the
    min/argmin epilogue and the update epilogue both run on the
    *corrected* accumulator;
  * **update epilogue** (the one-hot MXU product): alongside each row
    tile's partial per-cluster sums/counts the kernel emits their
    *expected* e1/e2 column checksums, computed from the argmin/valid
    vectors and the stashed X tiles — an arithmetic path disjoint from
    the one-hot product they verify:

        e1^T (onehot^T X) = (onehot e1)^T X = valid^T X
        e2^T (onehot^T X) = (onehot e2)^T X = (valid * (argmin+1))^T X

    The jitted tree-reduction (``ops.fused_lloyd_ft``) compares the
    observed checksums of the emitted partial blocks against these and
    *recomputes* a mismatched tile from the data plan and the corrected
    assignment — the recompute replays the kernel's own arithmetic, so a
    recovered run is bit-identical to a clean one. This supersedes the
    host-side DMR of the two-pass update for fused backends.

The injection descriptor carries two independent SEU slots — one for the
distance GEMM accumulator, one for the one-hot update product — matching
the two independently verified intervals a single Lloyd step exposes
(§II-A: at most one error per detection interval).

Like ``distance_argmin_ft`` this template keeps the generic
(revisited-output) grid for all K: the checksum scratch is already
VMEM-resident, so the small-K fast path buys nothing here. X and C tiles
may be f32, bf16 or fp16; accumulators, checksums and outputs are f32 and
the detection thresholds scale with the input dtype's rounding
(``checksum.threshold_factor``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import compiler_params, mxu_dot
from repro.kernels.distance_argmin import MIN_INIT
from repro.kernels.distance_argmin_ft import (_f32_from_bits,
                                              accumulate_checksums,
                                              inject_distance,
                                              threshold_factor,
                                              verify_and_reduce)
from repro.kernels.lloyd_step import (STASH_SLOTS, _emit_update,
                                      _stash_dma_start, _stash_dma_wait_last)

# SMEM metadata layout: [true_m] — rows >= true_m are padding and must not
# contribute to sums/counts.
META_LEN = 1

# Injection descriptor (SMEM scalars): two independent SEU slots.
#   distance slot: [0] enabled, [1] m_tile, [2] c_tile, [3] f_tile,
#                  [4] row_in_tile, [5] col_in_tile, [6] delta (f32 bits)
#   update slot:   [7] enabled, [8] m_tile, [9] cluster_row,
#                  [10] feature_col, [11] delta (f32 bits)
INJ_LEN = 12
# Two protected intervals: the distance GEMM and the update epilogue —
# one descriptor slot each. The registry's ``protected_intervals`` must
# agree with this.
INJ_SLOTS = 2


def no_injection() -> jax.Array:
    return jnp.zeros((INJ_LEN,), jnp.int32)


def _f32_bits(delta: float) -> int:
    return int(np.float32(delta).view(np.int32))


def make_injection(*, distance: Optional[tuple] = None,
                   update: Optional[tuple] = None) -> jax.Array:
    """Build a descriptor with either or both SEU slots armed.

    distance = (m_tile, c_tile, f_tile, row_in_tile, col_in_tile, delta)
    update   = (m_tile, cluster_row, feature_col, delta) — coordinates in
               the *padded* (K, F) partial-sum block of that row tile.
    """
    desc = np.zeros((INJ_LEN,), np.int32)
    if distance is not None:
        mt, ct, ft, row, col, delta = distance
        desc[0:7] = [1, mt, ct, ft, row, col, _f32_bits(delta)]
    if update is not None:
        mt, row, col, delta = update
        desc[7:12] = [1, mt, row, col, _f32_bits(delta)]
    return jnp.asarray(desc)


def _kernel(meta_ref, inj_ref, x_ref, c_ref, cn_ref,
            mind_ref, argmin_ref, det_ref, sums_ref, counts_ref,
            ucheck_ref, ccheck_ref,
            acc_ref, col1_ref, col2_ref, row1_ref, row2_ref, xbuf_ref,
            sem_ref):
    """One (bm, bk) distance tile with fused ABFT + the protected update
    epilogue.

    meta_ref  : (1,)        SMEM — [true_m]
    inj_ref   : (INJ_LEN,)  SMEM — dual-slot injection descriptor
    x_ref     : (bm, bf)    sample tile
    c_ref     : (bk, bf)    centroid tile
    cn_ref    : (1, bk)     centroid squared norms (+inf for padded slots)
    mind_ref  : (bm, 1)     running minimum of d_ij  (output, revisited)
    argmin_ref: (bm, 1)     running argmin           (output, revisited)
    det_ref   : (1, 1, 1)   corrected distance-GEMM errors in this row tile
    sums_ref  : (1, kp, fp) per-row-tile partial cluster sums (output)
    counts_ref: (1, 1, kp)  per-row-tile partial cluster counts (output)
    ucheck_ref: (1, 2, fp)  expected e1/e2 column checksums of the sums
    ccheck_ref: (1, 1, 2)   expected e1/e2 checksums of the counts
    acc/colN/rowN          : ABFT scratch as in ``distance_argmin_ft``
    xbuf_ref  : (bm, fp)    VMEM stash of the row tile's feature chunks
    sem_ref   : (2,)        DMA semaphores for the double-buffered stash
    """
    m_idx = pl.program_id(0)
    c_idx = pl.program_id(1)
    f_idx = pl.program_id(2)
    nk = pl.num_programs(1)
    nf = pl.num_programs(2)
    bm, bk = acc_ref.shape
    bf = x_ref.shape[1]

    @pl.when(jnp.logical_and(c_idx == 0, f_idx == 0))
    def _init_outputs():
        mind_ref[...] = jnp.full_like(mind_ref, MIN_INIT)
        argmin_ref[...] = jnp.zeros_like(argmin_ref)
        det_ref[...] = jnp.zeros_like(det_ref)

    @pl.when(f_idx == 0)
    def _init_scratch():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        col1_ref[...] = jnp.zeros_like(col1_ref)
        col2_ref[...] = jnp.zeros_like(col2_ref)
        row1_ref[...] = jnp.zeros_like(row1_ref)
        row2_ref[...] = jnp.zeros_like(row2_ref)

    # Stash the streamed X tile on its first visit: the update epilogue
    # reuses it from VMEM instead of a second HBM read. Async, so the copy
    # overlaps this step's MXU + checksum products (the double-buffered
    # stash shared with the unprotected kernel).
    @pl.when(c_idx == 0)
    def _stash_x():
        _stash_dma_start(x_ref, xbuf_ref, sem_ref, f_idx, bf)

    x = x_ref[...]
    c = c_ref[...]

    # --- main MXU product (native dtype in, f32 accumulate) -----------------
    acc_ref[...] += mxu_dot(x, c, (1, 1))
    accumulate_checksums(x, c, col1_ref, col2_ref, row1_ref, row2_ref)
    inject_distance(acc_ref, inj_ref, m_idx, c_idx, f_idx)

    # --- verification interval: detect (-> locate -> correct) -> reduce -----
    @pl.when(f_idx == nf - 1)
    def _verify_and_reduce():
        verify_and_reduce(acc_ref, col1_ref, col2_ref, row1_ref, row2_ref,
                          cn_ref, mind_ref, argmin_ref, det_ref, c_idx * bk,
                          threshold_factor(nf * bf, x_ref.dtype))

    # --- protected update epilogue: argmin for this row tile is final -------
    @pl.when(jnp.logical_and(c_idx == nk - 1, f_idx == nf - 1))
    def _update_epilogue():
        kp = counts_ref.shape[-1]
        fp = xbuf_ref.shape[1]
        _stash_dma_wait_last(x_ref, xbuf_ref, sem_ref, nf, bf)
        # the one-hot product itself is the unprotected kernel's epilogue,
        # shared verbatim — the bit-identity contract between this kernel,
        # the plain lloyd kernel and the recompute in
        # ops._verify_update_partials rests on one definition
        _emit_update(meta_ref, argmin_ref, sums_ref, counts_ref, xbuf_ref,
                     m_idx, bm)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0) + m_idx * bm
        valid = (rows < meta_ref[0]).astype(jnp.float32)           # (bm, 1)

        # expected checksums of the one-hot product, from the argmin/valid
        # vectors and the stashed tiles — never from the product itself.
        # They stay at full f32 precision (not the product's bf16 slices):
        # the encoder reaches kp + 1, which bf16 does not hold exactly
        amp1 = valid * (argmin_ref[...] + 1).astype(jnp.float32)   # (bm, 1)
        enc = jnp.concatenate([valid, amp1], axis=1)               # (bm, 2)
        ucheck_ref[...] = mxu_dot(
            enc, xbuf_ref[...].astype(jnp.float32), (0, 0))[None]  # (1,2,fp)
        ccheck_ref[0] = jnp.sum(enc, axis=0, keepdims=True)        # (1, 2)

        # simulated SEU in the one-hot update product — applied after the
        # invariant side is recorded (inputs are ECC's job, per §II-A)
        uhit = jnp.logical_and(inj_ref[7] > 0, m_idx == inj_ref[8])

        @pl.when(uhit)
        def _inject_update():
            krows = jax.lax.broadcasted_iota(jnp.int32, (kp, fp), 0)
            fcols = jax.lax.broadcasted_iota(jnp.int32, (kp, fp), 1)
            mask = jnp.logical_and(krows == inj_ref[9], fcols == inj_ref[10])
            udelta = _f32_from_bits(inj_ref[11], (kp, fp))
            sums_ref[...] += jnp.where(mask, udelta, 0.0)[None]


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_f", "interpret"))
def lloyd_step_ft(
    x: jax.Array,
    c: jax.Array,
    cn: jax.Array,
    meta: jax.Array,
    inj: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 128,
    block_f: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """Raw one-pass FT kernel entry. Shapes must be pre-padded to the grid.

    x (M, F) samples, c (K, F) centroids (f32/bf16/fp16), cn (1, K) f32
    centroid sq-norms with +inf in padded slots, meta (1,) int32 =
    [true_m], inj (INJ_LEN,) int32 dual-slot injection descriptor.
    Returns (min_d (M, 1), argmin (M, 1), det (M/bm, 1),
    sums (M/bm, K, F), counts (M/bm, K), ucheck (M/bm, 2, F),
    ccheck (M/bm, 2)); verify + reduce the partial blocks with
    ``ops.fused_lloyd_ft``.
    """
    m, f = x.shape
    k = c.shape[0]
    assert m % block_m == 0 and k % block_k == 0 and f % block_f == 0, (
        f"unpadded shapes {(m, k, f)} vs blocks {(block_m, block_k, block_f)}")
    num_m = m // block_m

    kernel = pl.pallas_call(
        _kernel,
        grid=(m // block_m, k // block_k, f // block_f),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_m, block_f), lambda i, j, t: (i, t)),
            pl.BlockSpec((block_k, block_f), lambda i, j, t: (j, t)),
            pl.BlockSpec((1, block_k), lambda i, j, t: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j, t: (i, 0, 0)),
            pl.BlockSpec((1, k, f), lambda i, j, t: (i, 0, 0)),
            pl.BlockSpec((1, 1, k), lambda i, j, t: (i, 0, 0)),
            pl.BlockSpec((1, 2, f), lambda i, j, t: (i, 0, 0)),
            pl.BlockSpec((1, 1, 2), lambda i, j, t: (i, 0, 0)),
        ],
        # The per-row-tile det/counts/ccheck rows carry a unit axis so each
        # block equals the array's last two dims (Mosaic's block rule); it
        # is squeezed away after the call.
        out_shape=[
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
            jax.ShapeDtypeStruct((num_m, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((num_m, k, f), jnp.float32),
            jax.ShapeDtypeStruct((num_m, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((num_m, 2, f), jnp.float32),
            jax.ShapeDtypeStruct((num_m, 1, 2), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, block_k), jnp.float32),
            pltpu.VMEM((1, block_k), jnp.float32),
            pltpu.VMEM((1, block_k), jnp.float32),
            pltpu.VMEM((block_m, 1), jnp.float32),
            pltpu.VMEM((block_m, 1), jnp.float32),
            pltpu.VMEM((block_m, f), x.dtype),   # stash in the input dtype
            pltpu.SemaphoreType.DMA((STASH_SLOTS,)),
        ],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )
    mind, am, det, sums, counts, ucheck, ccheck = kernel(meta, inj, x, c, cn)
    return mind, am, det[:, 0], sums, counts[:, 0], ucheck, ccheck[:, 0]


def _kernel_update_tile(meta_ref, am_ref, x_ref, sums_ref, counts_ref):
    """The update epilogue alone, on one row tile (row 0 of the tile is
    row 0 here; ``meta`` holds the tile's count of true rows)."""
    _emit_update(meta_ref, am_ref, sums_ref, counts_ref, x_ref, 0,
                 am_ref.shape[0])


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def recompute_update_tile(x: jax.Array, am: jax.Array, meta: jax.Array, *,
                          k: int, interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """Recompute one row tile's partial sums and counts with the kernel's
    own update epilogue — the repair of a tile whose checksums failed.

    x (bm, F) the tile's padded samples, am (bm, 1) int32 its corrected
    assignment, meta (1,) int32 = [true rows in the tile]; ``k`` is the
    padded cluster count. Running ``_emit_update`` itself, rather than an
    XLA re-derivation of it, is what makes the repaired tile bit-identical
    to an uncorrupted one on every backend. Returns (sums (1, K, F),
    counts (1, K)), shaped like one row tile's partial blocks.
    """
    bm, f = x.shape
    sums, counts = pl.pallas_call(
        _kernel_update_tile,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, 1), lambda: (0, 0)),
            pl.BlockSpec((bm, f), lambda: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, k, f), lambda: (0, 0, 0)),
            pl.BlockSpec((1, 1, k), lambda: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k, f), jnp.float32),
            jax.ShapeDtypeStruct((1, 1, k), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(meta, am, x)
    return sums, counts[:, 0]
