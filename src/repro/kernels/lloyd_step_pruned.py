"""Tile-granular triangle-inequality pruned one-pass Lloyd kernel.

Late Lloyd iterations reassign almost nothing: once clusters separate, a
row's nearest centroid rarely changes, yet the unpruned one-pass kernel
(:mod:`lloyd_step`) still pays the full distance GEMM against every
centroid tile every iteration. This variant carries Hamerly-style bounds
between iterations and skips whole ``block_k`` centroid tiles that
provably cannot change any assignment in the row tile:

  * per row ``r``: an upper bound ``ub_r`` on the Euclidean distance to
    its currently assigned centroid (refreshed exactly each computed
    iteration, grown by the assigned centroid's drift otherwise);
  * per (row tile ``i``, centroid tile ``j``): ``tmin[i, j]``, the
    minimum over valid rows of the row's Euclidean distance to its
    nearest centroid *in that tile* — a weak lower bound that holds for
    every row of the tile simultaneously, which is what makes
    tile-granular (rather than per-row) skipping sound;
  * per centroid tile ``j``: the maximum drift of its centroids since
    the bounds were recorded.

The host-side wrapper (``ops.fused_lloyd_pruned``) decays ``tmin`` by the
tile drift into a lower bound ``tlb`` and compares it against the row
tile's worst-case upper bound ``maxub[i] = max_r (ub_r +
drift[assign_r])``. A tile is skipped iff ``tlb[i, j] > maxub[i]`` (plus
a small fp-safety slack): every row's distance to every centroid of the
tile is then *strictly* greater than that row's distance to its current
centroid, so the tile can neither win the min nor tie it — the fold is
bit-identical to the unpruned kernel's by omission. The tile containing
a row's assigned centroid always satisfies ``tlb <= maxub`` and is never
skipped, so the min/argmin is always grounded.

The kernel itself receives the precomputed ``skip`` mask as a (1, 1)
block per (row tile, centroid tile) grid cell and gates the MXU product
and the min epilogue on it; the X stash and the fused one-hot update
epilogue (shared with :mod:`lloyd_step`) run unconditionally, so sums and
counts are produced exactly as before. For computed tiles the kernel
refreshes ``tmin`` from the freshly accumulated distances; for skipped
tiles the wrapper substitutes the decayed bound.

Tile granularity, not row granularity: the MXU consumes (bm, bk) tiles —
masking individual rows would still issue the full tile product, so the
only skip the TPU can actually exploit is a whole centroid tile per row
tile. That is also why bounds are reduced to per-tile scalars: the skip
decision must be uniform across the tile.

``"smallk"`` shapes (padded K == one centroid tile) cannot prune — the
sole tile always contains every assigned centroid — so the smallk
variant computes everything and only emits the ``tmin`` refresh to keep
the bounds state warm.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import compiler_params, mxu_dot
from repro.kernels.distance_argmin import MIN_INIT, fold_min, tile_min_argmin
from repro.kernels.lloyd_step import (STASH_SLOTS, _emit_update,
                                      _stash_dma_start, _stash_dma_wait_last)


def _tile_bound(meta_ref, xn_ref, local_min, m_idx, bm):
    """Euclidean group bound for one computed tile: min over *valid* rows
    of sqrt(max(partial_min + ||x||^2, 0)). Padded rows are excluded so a
    zero padding row cannot poison the bound downward (that would only
    cost prune rate, never correctness, but it costs a lot of it)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0) + m_idx * bm
    valid = rows < meta_ref[0]
    row_e = jnp.sqrt(jnp.maximum(local_min + xn_ref[...], 0.0))
    return jnp.min(jnp.where(valid, row_e, MIN_INIT), axis=0, keepdims=True)


def _kernel_pruned(meta_ref, x_ref, c_ref, cn_ref, xn_ref, skip_ref,
                   mind_ref, argmin_ref, sums_ref, counts_ref, tmin_ref,
                   acc_ref, xbuf_ref, sem_ref):
    """One (bm, bk) tile of the pruned one-pass iteration.

    meta_ref  : (1,)        SMEM — [true_m]
    x_ref     : (bm, bf)    sample tile
    c_ref     : (bk, bf)    centroid tile
    cn_ref    : (1, bk)     centroid squared norms (+inf for padded slots)
    xn_ref    : (bm, 1)     row squared norms (0 for padded rows)
    skip_ref  : (1, 1, 1, 1) i32 — 1 iff this (row tile, centroid tile)
                            cell is pruned this iteration
    mind_ref  : (bm, 1)     running minimum of d_ij  (output, revisited)
    argmin_ref: (bm, 1)     running argmin           (output, revisited)
    sums_ref  : (1, kp, fp) per-row-tile partial cluster sums (output)
    counts_ref: (1, 1, kp)  per-row-tile partial cluster counts (output)
    tmin_ref  : (1, 1, 1, 1) refreshed Euclidean group bound (output)
    acc_ref   : (bm, bk)    VMEM scratch accumulator for X C^T
    xbuf_ref  : (bm, fp)    VMEM stash of the row tile's feature chunks
    sem_ref   : (2,)        DMA semaphores for the double-buffered stash
    """
    m_idx = pl.program_id(0)
    c_idx = pl.program_id(1)
    f_idx = pl.program_id(2)
    nk = pl.num_programs(1)
    nf = pl.num_programs(2)
    bm = acc_ref.shape[0]
    bf = x_ref.shape[1]
    live = skip_ref[0, 0, 0, 0] == 0

    @pl.when(jnp.logical_and(c_idx == 0, f_idx == 0))
    def _init_outputs():
        mind_ref[...] = jnp.full_like(mind_ref, MIN_INIT)
        argmin_ref[...] = jnp.zeros_like(argmin_ref)

    @pl.when(f_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # Skipped tiles never reach the epilogue; the wrapper substitutes
        # the decayed bound, so the placeholder value is never read.
        tmin_ref[...] = jnp.full_like(tmin_ref, MIN_INIT)

    # The stash is unconditional: the fused update epilogue needs every
    # feature chunk regardless of which centroid tiles were pruned. Async,
    # overlapping whatever this step computes (even a fully pruned step
    # still pays the stash — it is the update's data, not the GEMM's).
    @pl.when(c_idx == 0)
    def _stash_x():
        _stash_dma_start(x_ref, xbuf_ref, sem_ref, f_idx, bf)

    # The entire point: no MXU product for pruned tiles.
    @pl.when(live)
    def _accumulate():
        acc_ref[...] += mxu_dot(x_ref[...], c_ref[...], (1, 1))

    @pl.when(jnp.logical_and(live, f_idx == nf - 1))
    def _min_epilogue():
        local_min, local_arg = tile_min_argmin(
            acc_ref[...], cn_ref[...], c_idx * acc_ref.shape[1])
        fold_min(mind_ref, argmin_ref, local_min, local_arg)
        tmin_ref[0, 0] = _tile_bound(meta_ref, xn_ref, local_min, m_idx, bm)

    # The update epilogue is unconditional: a skipped last tile still
    # finalizes the row tile's argmin (skipping only omits losing folds).
    @pl.when(jnp.logical_and(c_idx == nk - 1, f_idx == nf - 1))
    def _update_epilogue():
        _stash_dma_wait_last(x_ref, xbuf_ref, sem_ref, nf, bf)
        _emit_update(meta_ref, argmin_ref, sums_ref, counts_ref, xbuf_ref,
                     m_idx, bm)


def _kernel_smallk_pruned(meta_ref, x_ref, c_ref, cn_ref, xn_ref, skip_ref,
                          mind_ref, argmin_ref, sums_ref, counts_ref,
                          tmin_ref, acc_ref, xbuf_ref, sem_ref):
    """Small-K pruned path: padded K is one centroid tile, grid (M/bm,
    F/bf). A single tile always contains every row's assigned centroid,
    so it can never be skipped — the wrapper forces ``skip`` to zero and
    this kernel ignores it, computing the full smallk sweep plus the
    ``tmin`` refresh that keeps the bounds state warm."""
    del skip_ref  # single-tile shapes cannot prune (see module docstring)
    m_idx = pl.program_id(0)
    f_idx = pl.program_id(1)
    nf = pl.num_programs(1)
    bm = acc_ref.shape[0]
    bf = x_ref.shape[1]

    @pl.when(f_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _stash_dma_start(x_ref, xbuf_ref, sem_ref, f_idx, bf)

    acc_ref[...] += mxu_dot(x_ref[...], c_ref[...], (1, 1))

    @pl.when(f_idx == nf - 1)
    def _epilogue():
        local_min, local_arg = tile_min_argmin(acc_ref[...], cn_ref[...], 0)
        mind_ref[...] = local_min       # single visit: direct write
        argmin_ref[...] = local_arg
        tmin_ref[0, 0] = _tile_bound(meta_ref, xn_ref, local_min, m_idx, bm)
        _stash_dma_wait_last(x_ref, xbuf_ref, sem_ref, nf, bf)
        _emit_update(meta_ref, argmin_ref, sums_ref, counts_ref, xbuf_ref,
                     m_idx, bm)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_f", "variant", "interpret"))
def lloyd_step_pruned(
    x: jax.Array,
    c: jax.Array,
    cn: jax.Array,
    xn: jax.Array,
    meta: jax.Array,
    skip: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 128,
    block_f: int = 512,
    variant: str = "generic",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Raw pruned one-pass kernel entry. Shapes pre-padded to the grid.

    x (M, F) samples, c (K, F) centroids (f32/bf16/fp16), cn (1, K) f32
    centroid sq-norms with +inf in padded slots, xn (M, 1) f32 row
    sq-norms (0 in padded rows), meta (1,) int32 = [true_m], skip
    (M/bm, K/bk) int32 tile mask (1 = prune this cell; must be all zero
    for the ``"smallk"`` variant, whose skip shape is (M/bm, 1)).
    Returns (min_d (M, 1), argmin (M, 1), sums (M/bm, K, F), counts
    (M/bm, K), tmin (M/bm, K/bk)); tmin entries of skipped cells are a
    MIN_INIT placeholder — the caller substitutes the decayed bound.
    """
    m, f = x.shape
    k = c.shape[0]
    assert m % block_m == 0 and k % block_k == 0 and f % block_f == 0, (
        f"unpadded shapes {(m, k, f)} vs blocks {(block_m, block_k, block_f)}")
    num_m = m // block_m
    num_k = k // block_k if variant == "generic" else 1

    out_shape = [
        jax.ShapeDtypeStruct((m, 1), jnp.float32),
        jax.ShapeDtypeStruct((m, 1), jnp.int32),
        # Per-tile rows and scalars carry unit axes so every block equals
        # the array's last two dims, as Mosaic's block rule requires; they
        # are squeezed away after the call.
        jax.ShapeDtypeStruct((num_m, k, f), jnp.float32),
        jax.ShapeDtypeStruct((num_m, 1, k), jnp.float32),
        jax.ShapeDtypeStruct((num_m, num_k, 1, 1), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((block_m, block_k), jnp.float32),
        pltpu.VMEM((block_m, f), x.dtype),   # stash in the input dtype
        pltpu.SemaphoreType.DMA((STASH_SLOTS,)),
    ]

    if variant == "smallk":
        assert k == block_k, (
            f"smallk variant needs padded K ({k}) == block_k ({block_k})")
        assert skip.shape == (num_m, 1), (
            f"smallk skip shape {skip.shape} != {(num_m, 1)}")
        kernel = pl.pallas_call(
            _kernel_smallk_pruned,
            grid=(m // block_m, f // block_f),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((block_m, block_f), lambda i, t: (i, t)),
                pl.BlockSpec((block_k, block_f), lambda i, t: (0, t)),
                pl.BlockSpec((1, block_k), lambda i, t: (0, 0)),
                pl.BlockSpec((block_m, 1), lambda i, t: (i, 0)),
                pl.BlockSpec((1, 1, 1, 1), lambda i, t: (i, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_m, 1), lambda i, t: (i, 0)),
                pl.BlockSpec((block_m, 1), lambda i, t: (i, 0)),
                pl.BlockSpec((1, k, f), lambda i, t: (i, 0, 0)),
                pl.BlockSpec((1, 1, k), lambda i, t: (i, 0, 0)),
                pl.BlockSpec((1, 1, 1, 1), lambda i, t: (i, 0, 0, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=compiler_params("parallel", "arbitrary"),
            interpret=interpret,
        )
    else:
        assert variant == "generic", f"unknown kernel variant {variant!r}"
        assert skip.shape == (num_m, num_k), (
            f"skip shape {skip.shape} != {(num_m, num_k)}")
        kernel = pl.pallas_call(
            _kernel_pruned,
            grid=(m // block_m, k // block_k, f // block_f),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((block_m, block_f), lambda i, j, t: (i, t)),
                pl.BlockSpec((block_k, block_f), lambda i, j, t: (j, t)),
                pl.BlockSpec((1, block_k), lambda i, j, t: (0, j)),
                pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
                pl.BlockSpec((1, 1, 1, 1), lambda i, j, t: (i, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
                pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
                pl.BlockSpec((1, k, f), lambda i, j, t: (i, 0, 0)),
                pl.BlockSpec((1, 1, k), lambda i, j, t: (i, 0, 0)),
                pl.BlockSpec((1, 1, 1, 1), lambda i, j, t: (i, j, 0, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=compiler_params(
                "parallel", "arbitrary", "arbitrary"),
            interpret=interpret,
        )
    mind, am, sums, counts, tmin = kernel(meta, x, c, cn, xn,
                                          skip[:, :, None, None])
    return mind, am, sums, counts[:, 0], tmin[:, :, 0, 0]
