"""One-pass Lloyd iteration kernel (paper §III, Fig. 4 — fused update).

``distance_argmin`` performs the distance GEMM and the min/argmin epilogue,
but the centroid *update* still re-reads X from HBM in a second pass
(``ops.cluster_sums``). This kernel folds that second pass into the
assignment kernel's epilogue: while the feature tiles of X stream through
VMEM for the GEMM, they are stashed in a VMEM row-tile buffer; once the
argmin for a row tile is final (last centroid tile, last feature step), a
one-hot MXU product against the stashed tiles accumulates per-cluster
partial sums and counts into per-row-tile output blocks:

    sums   (num_m_tiles, K, F)   partial per-cluster feature sums
    counts (num_m_tiles, K)      partial per-cluster member counts

A small jitted tree-reduction (``ops.fused_lloyd``) collapses the partial
blocks to the (K, F) sums / (K,) counts the update needs — so X is read
from HBM once per centroid tile and never again, where the two-pass
pipeline paid a second full read of X plus an assignment round trip.

Grid and tiling match ``distance_argmin``: (M/bm, K/bk, F/bf), feature axis
fastest, MXU-aligned blocks, running min/argmin accumulated in the
revisited output block. Padded sample rows are masked out of the sums and
counts via the true row count carried in SMEM; padded centroid slots carry
+inf norms and never win the argmin.

This is the prerequisite shape for porting the §IV ABFT epilogue onto the
one-pass kernel: the checksum accumulators of ``distance_argmin_ft`` attach
to the same streamed tiles, and the update epilogue runs on the *corrected*
accumulator.

Template family (paper §III-B): the ``"smallk"`` variant drops the centroid
grid dimension when padded K fits one ``block_k`` tile — every row tile is
visited once, so min/argmin writes directly (no revisit compare) and the
one-hot update epilogue fires in the same grid step. X and C tiles may be
f32, bf16 or fp16; the stash buffer holds the input dtype (halving its VMEM
at 2-byte dtypes) while every accumulator and output stays f32.

Batched many-problem variant (:func:`lloyd_step_batched`): production
traffic is rarely one big clustering problem — it is thousands of
independent small ones (per-user embeddings, per-shard codebooks, the keys
of each attention head of a prefill batch) whose individual kernel
launches waste the MXU. The B problems' rows are packed back to back, each
padded to whole row tiles, and the grid runs over row tiles ``(T, F/bf)``.
A tile map in SMEM (scalar prefetch) names each tile's problem, which
selects its centroid block, and its valid rows, which mask the update
epilogue. Batched problems have small K by construction (padded K is a
single centroid tile), so every grid step is the ``smallk`` epilogue,
min/argmin written directly and the one-hot update emitted in the same
step. One launch amortizes B dispatches. Problems of one row count (a
stack) launch as ``lloyd_step_batched``, problems of different row counts
as :func:`lloyd_step_ragged`: one kernel under two names. Its rows may be
bf16 under f32 centroids (rows stored in bf16, f32 arithmetic): the
distance product then takes the centroids' three exact bf16 slices
(``_mosaic.mxu_dot``, split per centroid tile in the kernel: splitting
once per iteration in the wrapper measured the same on a v5e), the stash
holds bf16 and the one-hot update is one exact pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import compiler_params, mxu_dot, onehot_dot
from repro.kernels.distance_argmin import MIN_INIT, fold_min, tile_min_argmin

# SMEM metadata layout: [true_m] — rows >= true_m are padding and must not
# contribute to sums/counts.
META_LEN = 1

# Stash DMA slots: the X-tile stash is issued as an async VMEM copy so the
# current feature step's MXU product overlaps the previous chunk's store
# (the emit-pipeline idiom). Two semaphore slots, used round-robin.
STASH_SLOTS = 2


def _stash_dma_start(x_ref, xbuf_ref, sem_ref, f_idx, bf):
    """Issue this feature chunk's stash as an async copy.

    The previous chunk's copy is drained first — at most one stash is in
    flight, so the copy issued here overlaps this grid step's MXU product
    and is waited at the *next* stash (or by ``_stash_dma_wait_last``
    before the update epilogue reads the buffer). Draining f-1 before
    issuing f also keeps the revolving input block of f-1 safe to recycle
    before the pipeline lands chunk f+1 in it.
    """
    @pl.when(f_idx >= 1)
    def _drain_prev():
        pltpu.make_async_copy(
            x_ref, xbuf_ref.at[:, pl.ds((f_idx - 1) * bf, bf)],
            sem_ref.at[(f_idx - 1) % STASH_SLOTS]).wait()

    pltpu.make_async_copy(
        x_ref, xbuf_ref.at[:, pl.ds(f_idx * bf, bf)],
        sem_ref.at[f_idx % STASH_SLOTS]).start()


def _stash_dma_wait_last(x_ref, xbuf_ref, sem_ref, nf, bf):
    """Drain the final in-flight stash before an epilogue reads xbuf."""
    pltpu.make_async_copy(
        x_ref, xbuf_ref.at[:, pl.ds((nf - 1) * bf, bf)],
        sem_ref.at[(nf - 1) % STASH_SLOTS]).wait()


def _kernel(meta_ref, x_ref, c_ref, cn_ref,
            mind_ref, argmin_ref, sums_ref, counts_ref,
            acc_ref, xbuf_ref, sem_ref):
    """One (bm, bk) distance tile + the fused update epilogue.

    meta_ref  : (1,)        SMEM — [true_m]
    x_ref     : (bm, bf)    sample tile
    c_ref     : (bk, bf)    centroid tile
    cn_ref    : (1, bk)     centroid squared norms (+inf for padded slots)
    mind_ref  : (bm, 1)     running minimum of d_ij  (output, revisited)
    argmin_ref: (bm, 1)     running argmin           (output, revisited)
    sums_ref  : (1, kp, fp) per-row-tile partial cluster sums (output)
    counts_ref: (1, 1, kp)  per-row-tile partial cluster counts (output)
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

    @pl.when(jnp.logical_and(c_idx == 0, f_idx == 0))
    def _init_outputs():
        mind_ref[...] = jnp.full_like(mind_ref, MIN_INIT)
        argmin_ref[...] = jnp.zeros_like(argmin_ref)

    @pl.when(f_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Stash the streamed X tile on its first visit: the update epilogue
    # reuses it from VMEM instead of a second HBM read. The stash is an
    # async copy overlapping this step's MXU product; it is drained at the
    # next stash / before the update epilogue reads the buffer.
    @pl.when(c_idx == 0)
    def _stash_x():
        _stash_dma_start(x_ref, xbuf_ref, sem_ref, f_idx, bf)

    # MXU tile product, f32 accumulation.
    acc_ref[...] += mxu_dot(x_ref[...], c_ref[...], (1, 1))

    @pl.when(f_idx == nf - 1)
    def _min_epilogue():
        local_min, local_arg = tile_min_argmin(
            acc_ref[...], cn_ref[...], c_idx * acc_ref.shape[1])
        fold_min(mind_ref, argmin_ref, local_min, local_arg)

    # Fused update epilogue: the argmin for this row tile is final — scatter
    # the stashed X tiles into per-cluster partial sums via a one-hot MXU
    # product, masking padded sample rows.
    @pl.when(jnp.logical_and(c_idx == nk - 1, f_idx == nf - 1))
    def _update_epilogue():
        _stash_dma_wait_last(x_ref, xbuf_ref, sem_ref, nf, bf)
        _emit_update(meta_ref, argmin_ref, sums_ref, counts_ref, xbuf_ref,
                     m_idx, bm)


def _emit_update(meta_ref, argmin_ref, sums_ref, counts_ref, xbuf_ref,
                 m_idx, bm):
    """Shared one-hot update epilogue: final argmin -> per-cluster partial
    sums/counts for this row tile. The one-hot matrix is exact (0/1) in the
    stash dtype, so a 2-byte stash loses nothing; accumulation is f32. An
    f32 stash takes the product in three exact bf16 passes
    (:func:`~repro.kernels._mosaic.onehot_dot`)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0) + m_idx * bm
    valid = (rows < meta_ref[0]).astype(jnp.float32)           # (bm, 1)
    sums, counts = _onehot_update(valid, argmin_ref[...],
                                  counts_ref.shape[-1], xbuf_ref[...])
    counts_ref[0] = counts                                     # (1, kp)
    sums_ref[...] = sums[None]                                 # (1, kp, fp)


def _onehot_update(valid: jax.Array, am: jax.Array, kp: int, x: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """The one-hot product of :func:`_emit_update` on values: ``valid``
    (bm, 1) f32 0/1 row mask, ``am`` (bm, 1) final argmin, ``x`` (bm, fp)
    stashed rows. Returns (sums (kp, fp), counts (1, kp)), both f32."""
    clusters = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)
    onehot = (am == clusters).astype(jnp.float32) * valid
    counts = jnp.sum(onehot, axis=0, keepdims=True)            # (1, kp)
    return onehot_dot(onehot, x, (0, 0)), counts


def _kernel_smallk(meta_ref, x_ref, c_ref, cn_ref,
                   mind_ref, argmin_ref, sums_ref, counts_ref,
                   acc_ref, xbuf_ref, sem_ref):
    """Small-K fast path: padded K is one centroid tile, grid (M/bm, F/bf).

    Every row tile is visited exactly once, so there is no revisited
    min/argmin accumulation: the epilogue computes min/argmin from the
    VMEM-resident accumulator, writes it directly, and emits the one-hot
    update in the same grid step."""
    m_idx = pl.program_id(0)
    f_idx = pl.program_id(1)
    nf = pl.num_programs(1)
    bm = acc_ref.shape[0]
    bf = x_ref.shape[1]

    @pl.when(f_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Single centroid-tile sweep: every feature step is a first visit, so
    # every step issues its async stash (overlapping its own MXU product).
    _stash_dma_start(x_ref, xbuf_ref, sem_ref, f_idx, bf)

    acc_ref[...] += mxu_dot(x_ref[...], c_ref[...], (1, 1))

    @pl.when(f_idx == nf - 1)
    def _epilogue():
        local_min, local_arg = tile_min_argmin(acc_ref[...], cn_ref[...], 0)
        mind_ref[...] = local_min       # single visit: direct write
        argmin_ref[...] = local_arg
        _stash_dma_wait_last(x_ref, xbuf_ref, sem_ref, nf, bf)
        _emit_update(meta_ref, argmin_ref, sums_ref, counts_ref, xbuf_ref,
                     m_idx, bm)


def _kernel_batched(tile_prob_ref, tile_rows_ref, tile_slot_ref, x_ref,
                    c_ref, cn_ref, mind_ref, argmin_ref, sums_ref, counts_ref,
                    acc_ref, xbuf_ref, sem_ref):
    """One row tile of the batched grid (T, F/bf): the ``smallk``
    single-sweep epilogue on a tile of packed rows, its problem read from
    the tile map.

    Every problem's rows are padded to a whole number of row tiles, so a
    tile lies in one problem; the centroid and norm blocks follow
    ``tile_prob[t]``, and rows at or past ``tile_rows[t]`` (the problem's
    tail) are masked out of the sums and counts. The tile's partials go to
    row ``tile_slot[t]``, so that each run of problems with one tile count
    leaves them tile-major, as its tree sum reads them.

    tile_prob_ref: (T,)         SMEM (scalar prefetch) — problem of tile t
    tile_rows_ref: (T,)         SMEM (scalar prefetch) — valid rows of t
    tile_slot_ref: (T,)         SMEM (scalar prefetch) — partials row of t
    x_ref        : (bm, bf)     the tile's packed rows
    c_ref        : (1, kp, bf)  its problem's (single) centroid tile, in
                                the rows' dtype or f32 under bf16 rows
    cn_ref       : (1, 1, kp)   its problem's centroid squared norms
    mind_ref     : (1, 1, bm)   min distance, one lane-dense row per tile
    argmin_ref   : (1, 1, bm)   argmin, the same layout
    sums_ref     : (1, kp, fp)  the tile's partial cluster sums (its slot)
    counts_ref   : (1, 1, kp)   the tile's partial cluster counts (ditto)
    acc_ref      : (bm, kp)     VMEM scratch accumulator
    xbuf_ref     : (bm, fp)     VMEM stash of the row tile's chunks
    sem_ref      : (2,)         DMA semaphores for the async stash

    The min and argmin leave as rows, not (bm, 1) columns: a column block
    is stored one row per 128-lane line in HBM, 128 times its size.
    """
    t_idx = pl.program_id(0)
    f_idx = pl.program_id(1)
    nf = pl.num_programs(1)
    bm = acc_ref.shape[0]
    bf = x_ref.shape[1]

    @pl.when(f_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Single centroid-tile sweep: every feature step is a first visit, so
    # stash unconditionally (smallk rule) — async, so the copy overlaps
    # this step's MXU product.
    _stash_dma_start(x_ref, xbuf_ref, sem_ref, f_idx, bf)

    acc_ref[...] += mxu_dot(x_ref[...], c_ref[0], (1, 1))

    @pl.when(f_idx == nf - 1)
    def _epilogue():
        local_min, local_arg = tile_min_argmin(acc_ref[...], cn_ref[0], 0)
        mind_ref[0] = jnp.transpose(local_min)
        argmin_ref[0] = jnp.transpose(local_arg)
        _stash_dma_wait_last(x_ref, xbuf_ref, sem_ref, nf, bf)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        valid = (rows < tile_rows_ref[t_idx]).astype(jnp.float32)
        sums, counts = _onehot_update(valid, local_arg,
                                      counts_ref.shape[-1], xbuf_ref[...])
        counts_ref[0] = counts
        sums_ref[...] = sums[None]


def _batched_call(tile_prob, tile_rows, tile_slot, x, c, cn, block_m,
                  block_f, interpret):
    """The pallas_call of :func:`_kernel_batched`, shared by its two
    entries (not jitted itself: each entry's trace keeps its own name)."""
    m, f = x.shape
    k = c.shape[1]
    assert m % block_m == 0 and f % block_f == 0 and k % 128 == 0, (
        f"unpadded shapes {(m, k, f)} vs blocks ({block_m}, {k}, {block_f})")
    num_t = m // block_m
    assert all(a.shape == (num_t,) for a in (tile_prob, tile_rows,
                                            tile_slot)), (
        f"tile map of {tile_prob.shape} for {num_t} row tiles")

    out_shape = [
        jax.ShapeDtypeStruct((num_t, 1, block_m), jnp.float32),
        jax.ShapeDtypeStruct((num_t, 1, block_m), jnp.int32),
        jax.ShapeDtypeStruct((num_t, k, f), jnp.float32),
        # unit axis before K: see ``lloyd_step``
        jax.ShapeDtypeStruct((num_t, 1, k), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_t, f // block_f),
        in_specs=[
            pl.BlockSpec((block_m, block_f), lambda t, j, *_: (t, j)),
            pl.BlockSpec((1, k, block_f),
                         lambda t, j, tp, *_: (tp[t], 0, j)),
            pl.BlockSpec((1, 1, k), lambda t, j, tp, *_: (tp[t], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_m), lambda t, j, *_: (t, 0, 0)),
            pl.BlockSpec((1, 1, block_m), lambda t, j, *_: (t, 0, 0)),
            pl.BlockSpec((1, k, f), lambda t, j, tp, tr, ts: (ts[t], 0, 0)),
            pl.BlockSpec((1, 1, k), lambda t, j, tp, tr, ts: (ts[t], 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, k), jnp.float32),
            pltpu.VMEM((block_m, f), x.dtype),   # stash in the input dtype
            pltpu.SemaphoreType.DMA((STASH_SLOTS,)),
        ],
    )
    kernel = pl.pallas_call(
        _kernel_batched,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )
    mind, am, sums, counts = kernel(tile_prob, tile_rows, tile_slot, x, c,
                                    cn)
    return mind[:, 0], am[:, 0], sums, counts[:, 0]


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_f", "interpret"))
def lloyd_step_batched(
    tile_prob: jax.Array,
    tile_rows: jax.Array,
    tile_slot: jax.Array,
    x: jax.Array,
    c: jax.Array,
    cn: jax.Array,
    *,
    block_m: int = 256,
    block_f: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Raw batched one-pass kernel entry: B independent problems, one
    launch, their rows packed back to back in whole row tiles.

    x (T*bm, F) packed rows, each problem padded to whole row tiles;
    tile_prob (T,) int32 the problem of each row tile, tile_rows (T,)
    int32 its valid rows, tile_slot (T,) int32 the row of the partials
    it writes; c (B, K, F) per-problem centroids (f32/bf16/
    fp16) and cn (B, 1, K) f32 their squared norms (+inf in padded
    slots). Shapes must be pre-padded to the block grid; padded K must be
    a single centroid tile (the smallk condition — batched problems have
    small K by construction), so K itself is the centroid tile and there
    is no ``block_k`` knob. Returns (min_d (T, bm), argmin (T, bm),
    sums (T, K, F), counts (T, K)), the last two per-tile partials in
    ``tile_slot`` order, to be summed over each problem's tiles.

    This entry launches problems of one row count (a (B, N, F) stack);
    :func:`lloyd_step_ragged` is the same kernel under its own name for
    problems of different row counts.
    """
    return _batched_call(tile_prob, tile_rows, tile_slot, x, c, cn, block_m,
                         block_f, interpret)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_f", "interpret"))
def lloyd_step_ragged(
    tile_prob: jax.Array,
    tile_rows: jax.Array,
    tile_slot: jax.Array,
    x: jax.Array,
    c: jax.Array,
    cn: jax.Array,
    *,
    block_m: int = 256,
    block_f: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """:func:`lloyd_step_batched` for problems of different row counts:
    the same kernel and arguments, launched under this name so a trace
    tells ragged launches from stacked ones."""
    return _batched_call(tile_prob, tile_rows, tile_slot, x, c, cn, block_m,
                         block_f, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_f", "variant", "interpret"))
def lloyd_step(
    x: jax.Array,
    c: jax.Array,
    cn: jax.Array,
    meta: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 128,
    block_f: int = 512,
    variant: str = "generic",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Raw one-pass kernel entry. Shapes must be pre-padded to the block grid.

    x (M, F) samples, c (K, F) centroids (f32/bf16/fp16), cn (1, K) f32
    centroid sq-norms with +inf in padded slots, meta (1,) int32 =
    [true_m]. ``variant`` selects the template: ``"generic"`` or
    ``"smallk"`` (requires padded K == block_k). Returns
    (min_d (M, 1), argmin (M, 1), sums (M/bm, K, F), counts (M/bm, K));
    sum the partial blocks over axis 0 for the (K, F) / (K,) totals.
    """
    m, f = x.shape
    k = c.shape[0]
    assert m % block_m == 0 and k % block_k == 0 and f % block_f == 0, (
        f"unpadded shapes {(m, k, f)} vs blocks {(block_m, block_k, block_f)}")
    num_m = m // block_m

    out_shape = [
        jax.ShapeDtypeStruct((m, 1), jnp.float32),
        jax.ShapeDtypeStruct((m, 1), jnp.int32),
        jax.ShapeDtypeStruct((num_m, k, f), jnp.float32),
        # A unit axis before K: Mosaic wants a block's last two dims to be
        # (8, 128)-aligned or equal to the array's, so one row of counts per
        # row tile is a (1, 1, k) block, squeezed away after the call.
        jax.ShapeDtypeStruct((num_m, 1, k), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((block_m, block_k), jnp.float32),
        pltpu.VMEM((block_m, f), x.dtype),   # stash in the input dtype
        pltpu.SemaphoreType.DMA((STASH_SLOTS,)),
    ]

    if variant == "smallk":
        assert k == block_k, (
            f"smallk variant needs padded K ({k}) == block_k ({block_k})")
        kernel = pl.pallas_call(
            _kernel_smallk,
            grid=(m // block_m, f // block_f),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((block_m, block_f), lambda i, t: (i, t)),
                pl.BlockSpec((block_k, block_f), lambda i, t: (0, t)),
                pl.BlockSpec((1, block_k), lambda i, t: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_m, 1), lambda i, t: (i, 0)),
                pl.BlockSpec((block_m, 1), lambda i, t: (i, 0)),
                pl.BlockSpec((1, k, f), lambda i, t: (i, 0, 0)),
                pl.BlockSpec((1, 1, k), lambda i, t: (i, 0, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=compiler_params("parallel", "arbitrary"),
            interpret=interpret,
        )
    else:
        assert variant == "generic", f"unknown kernel variant {variant!r}"
        kernel = pl.pallas_call(
            _kernel,
            grid=(m // block_m, k // block_k, f // block_f),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((block_m, block_f), lambda i, j, t: (i, t)),
                pl.BlockSpec((block_k, block_f), lambda i, j, t: (j, t)),
                pl.BlockSpec((1, block_k), lambda i, j, t: (0, j)),
            ],
            out_specs=[
                pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
                pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
                pl.BlockSpec((1, k, f), lambda i, j, t: (i, 0, 0)),
                pl.BlockSpec((1, 1, k), lambda i, j, t: (i, 0, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=compiler_params(
                "parallel", "arbitrary", "arbitrary"),
            interpret=interpret,
        )
    mind, am, sums, counts = kernel(meta, x, c, cn)
    return mind, am, sums, counts[:, 0]
