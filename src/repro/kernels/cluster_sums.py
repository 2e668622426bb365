"""Sorted-window centroid update: per-cluster sums over label-sorted rows.

The two-pass update's sums, ``S_j = sum_i [a_i == j] x_i``, taken as a
one-hot product ``onehot(a)^T X`` cost 2·M·K·F flops for M·F useful adds:
a factor of K wasted. With X's rows gathered in label order (Flash-KMeans'
sort-inverse update), a tile of ``bm`` consecutive rows holds a short run
of labels, so its one-hot need only span a window of :data:`WINDOW`
clusters: ``(W, bm)·(bm, F)`` per tile, whatever K is.

Grid ``(T,)`` over row tiles, sequential (``"arbitrary"``): every tile adds
into one ``(rows, F)`` f32 output block that stays resident in VMEM for the
whole grid and is written back to HBM once. Each tile's first window start
(its first label rounded down to a multiple of 8) and its number of windows
come by scalar prefetch; a loop with that dynamic trip count walks the
windows, usually one, and adds each window's sums into the sublane slice
that starts there. A window's one-hot is built transposed, ``(W, bm)``,
against the tile's labels as a lane-dense ``(1, bm)`` row, so the product
needs no transpose. Rows past the data carry :data:`PAD_LABEL`, which no
window matches.

The product is exact per term: an f32 tile is split into three bf16 slices
and takes three bf16 MXU passes (``_mosaic.onehot_dot``), so the result
differs from the f32 reference only by the order of the f32 additions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import compiler_params, onehot_dot

# Clusters one window spans: one MXU tile of one-hot rows.
WINDOW = 128
# Label of the padded rows past the data: below every window start.
PAD_LABEL = -1


def out_rows(k: int) -> int:
    """Rows of the output block: K rounded up to the 8-row sublane tile,
    plus one window, so a window that starts at the last label's aligned
    start stays inside it."""
    return -(-k // 8) * 8 + WINDOW


def _kernel(start_ref, nwin_ref, lab_ref, x_ref, sums_ref):
    """One row tile of label-sorted rows, added window by window.

    start_ref: (T,)        SMEM (scalar prefetch) — first window of tile t,
                           a multiple of 8
    nwin_ref : (T,)        SMEM (scalar prefetch) — windows of tile t
    lab_ref  : (1, bm)     the tile's labels, ascending (PAD_LABEL past M)
    x_ref    : (bm, F)     the tile's rows, in the same order
    sums_ref : (rows, F)   f32 per-cluster sums, resident for the grid
    """
    t = pl.program_id(0)
    bm = x_ref.shape[0]

    @pl.when(t == 0)
    def _zero():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def window(w, carry):
        lo = pl.multiple_of(start_ref[t] + w * WINDOW, 8)
        clusters = jax.lax.broadcasted_iota(jnp.int32, (WINDOW, bm), 0) + lo
        onehot = (lab_ref[...] == clusters).astype(jnp.float32)   # (W, bm)
        sums_ref[pl.ds(lo, WINDOW), :] += onehot_dot(onehot, x_ref[...],
                                                     (1, 0))
        return carry

    jax.lax.fori_loop(0, nwin_ref[t], window, 0)


@functools.partial(jax.jit, static_argnames=("k", "block_m", "interpret"))
def cluster_sums(
    start: jax.Array,
    nwin: jax.Array,
    labels: jax.Array,
    x: jax.Array,
    *,
    k: int,
    block_m: int,
    interpret: bool = False,
) -> jax.Array:
    """Raw kernel entry. ``x`` (T*bm, F) rows in label order (f32, bf16 or
    fp16), ``labels`` (1, T*bm) int32 their labels, ascending, with
    :data:`PAD_LABEL` on padded rows; ``start`` (T,) int32 each tile's
    first window (its first label rounded down to a multiple of 8),
    ``nwin`` (T,) int32 its window count, enough to reach its last valid
    label. Returns the (:func:`out_rows` (k), F) f32 sums; rows at or past
    ``k`` are zero."""
    m, f = x.shape
    assert m % block_m == 0 and labels.shape == (1, m), (
        f"rows {m}, labels {labels.shape} vs block_m {block_m}")
    num_t = m // block_m
    assert start.shape == nwin.shape == (num_t,), (
        f"window tables {start.shape}, {nwin.shape} for {num_t} row tiles")
    rows = out_rows(k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_t,),
        in_specs=[
            pl.BlockSpec((1, block_m), lambda t, *_: (0, t)),
            pl.BlockSpec((block_m, f), lambda t, *_: (t, 0)),
        ],
        out_specs=pl.BlockSpec((rows, f), lambda t, *_: (0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, f), jnp.float32),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
    )(start, nwin, labels, x)
