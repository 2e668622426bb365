"""Public jit'd wrappers around the Pallas kernels.

Handles: padding to the block grid (masked so results are exact), parameter
selection via the autotune table (the paper's code-generation/selection
pipeline), interpret-mode fallback on non-TPU backends, and injection
planning helpers for fault campaigns.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core

from repro import hw, obs
from repro.dist.compression import quantize_rows as _quantize_rows
from repro.kernels import cluster_sums as _cs
from repro.kernels import distance_argmin as _da
from repro.kernels import distance_argmin_ft as _daft
from repro.kernels import distance_argmin_int8 as _dai
from repro.kernels import kmeanspp_init as _kpi
from repro.kernels import lloyd_step as _ll
from repro.kernels import lloyd_step_ft as _llft
from repro.kernels import lloyd_step_pruned as _llp
from repro.kernels import matmul_abft as _mma


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Kernel template variants (paper §III-B template family). "generic" keeps
# the centroid grid dimension and accumulates min/argmin in the revisited
# output block; "smallk" drops it when padded K fits one block_k tile.
VARIANTS = ("generic", "smallk")


def sublane_align(dtype: Any) -> int:
    """Minimum second-to-last-dimension tile multiple for a dtype: TPU
    packs 2-byte dtypes two-per-sublane (bf16/fp16 tiles need 16 rows where
    f32 needs 8) and 1-byte dtypes four-per-sublane (int8 needs 32)."""
    size = jnp.dtype(dtype).itemsize
    if size == 1:
        return 32
    return 16 if size == 2 else 8


def _itemsize(dtype: Any) -> int:
    return jnp.dtype(dtype).itemsize


@dataclasses.dataclass(frozen=True)
class KernelParams:
    """Tile parameters — the analogue of the paper's (threadblock, warp)
    CUTLASS parameter group. Thread-level tiles are Mosaic's job on TPU."""

    block_m: int = 256
    block_k: int = 128   # centroid tile (paper's Threadblock.N)
    block_f: int = 512   # contraction tile (paper's Threadblock.K)

    def vmem_bytes(self, dtype: Any = jnp.float32) -> int:
        """Working-set estimate: x + c tiles (double-buffered, input dtype)
        + f32 accumulator + f32 norm/checksum vectors."""
        b = _itemsize(dtype)
        tile = (self.block_m * self.block_f + self.block_k * self.block_f) * b
        acc = self.block_m * self.block_k * 4
        sums = 2 * (self.block_m + self.block_k) * 4
        return 2 * tile + acc + sums


DEFAULT_PARAMS = KernelParams()


def lloyd_vmem_bytes(params: KernelParams, k: int, f: int,
                     dtype: Any = jnp.float32) -> int:
    """Working-set estimate for the one-pass Lloyd kernel: the assignment
    kernel's tiles plus the stashed X row tile (input dtype) and the f32
    per-row-tile sums/counts output blocks (resident across the sweep)."""
    kp = _round_up(k, params.block_k)
    fp = _round_up(f, params.block_f)
    xbuf = params.block_m * fp * _itemsize(dtype)
    out_blocks = (kp * fp + kp) * 4
    return params.vmem_bytes(dtype) + xbuf + out_blocks


def lloyd_ft_vmem_bytes(params: KernelParams, k: int, f: int,
                        dtype: Any = jnp.float32) -> int:
    """Working-set estimate for the one-pass FT kernel: the one-pass
    kernel's footprint (``KernelParams.vmem_bytes`` already budgets the
    e1/e2 checksum vectors) plus the resident expected-checksum output
    blocks of the update epilogue."""
    fp = _round_up(f, params.block_f)
    return lloyd_vmem_bytes(params, k, f, dtype) + (2 * fp + 2) * 4


def lloyd_batched_vmem_bytes(params: KernelParams, k: int, f: int,
                             dtype: Any = jnp.float32) -> int:
    """Working-set estimate for the batched one-pass kernel: one row
    tile, and its problem's centroid tile, are resident at a time, so the
    footprint is the smallk one-pass working set with padded K as the
    single centroid tile — ``block_k`` is not a knob."""
    b = _itemsize(dtype)
    kp = _round_up(k, 128)
    fp = _round_up(f, params.block_f)
    tile = (params.block_m * params.block_f + kp * params.block_f) * b
    acc = params.block_m * kp * 4
    xbuf = params.block_m * fp * b
    out_blocks = (kp * fp + kp) * 4
    sums = 2 * (params.block_m + kp) * 4
    return 2 * tile + acc + xbuf + out_blocks + sums


def pruned_vmem_bytes(params: KernelParams, k: int, f: int,
                      dtype: Any = jnp.float32) -> int:
    """Working-set estimate for the pruned one-pass kernel: the one-pass
    footprint plus the double-buffered (bm, 1) f32 row-norm input block
    and the scalar skip/tmin blocks (a (1, 1) i32 input and a (1, 1) f32
    output per grid cell)."""
    return (lloyd_vmem_bytes(params, k, f, dtype)
            + 2 * params.block_m * 4 + 3 * 4)


def int8_vmem_bytes(params: KernelParams) -> int:
    """Working-set estimate for the int8 distance template: 1-byte x/c
    tiles plus the f32 scale vectors and centroid norms (double-buffered
    inputs), the f32/i32 min/argmin output blocks and the int32 accumulator
    scratch. Input dtype is fixed (int8 tiles, f32 epilogue operands), so
    unlike the f32 family this model takes no dtype."""
    bm, bk, bf = params.block_m, params.block_k, params.block_f
    ins = bm * bf + bk * bf + 4 * bm + 8 * bk   # x, c int8; sx, sc, cn f32
    outs = 8 * bm                               # mind f32 + argmin i32
    scr = 4 * bm * bk                           # int32 accumulator
    return 2 * ins + outs + scr


def init_vmem_bytes(params: KernelParams, f: int) -> int:
    """Working-set estimate for the fused k-means++ round kernel: one
    (bn, fp) f32 sample tile with its norms and d² vectors plus the single
    resident centroid row (double-buffered inputs), and the updated d² and
    tile-sum output blocks. Features are lane-padded and fully resident
    (F is not a grid axis), so the model depends on ``f``; ``block_k`` and
    ``block_f`` are not axes of this kernel at all."""
    bn = max(128, params.block_m)
    fp = _round_up(f, 128)
    ins = 4 * (bn * fp + bn + fp + bn)          # x tile, xn, c row, d2
    outs = 4 * (bn + 1)                         # updated d2 + tile sum
    return 2 * ins + outs


def resolve_variant(k: int, params: KernelParams,
                    variant: Optional[str] = None) -> str:
    """Template dispatch rule shared with the autotuner: the small-K fast
    path applies exactly when padded K fits one centroid tile. An explicit
    ``variant`` overrides (tests / benchmarks); ``"smallk"`` is validated
    against the tile so an impossible request fails here, not in Mosaic."""
    fits = _round_up(k, params.block_k) == params.block_k
    if variant is None:
        return "smallk" if fits else "generic"
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}, got {variant!r}")
    if variant == "smallk" and not fits:
        raise ValueError(
            f"smallk variant needs K ({k}) to fit one centroid tile "
            f"(block_k={params.block_k})")
    return variant


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class DataPlan:
    """Per-fit data plan: X padded to the block grid and its row squared
    norms, computed exactly once and reused across every Lloyd iteration
    (the seed pipeline re-padded and re-normed X inside every kernel call).

    x      : (m, f)   the original samples (update pass / reseeding)
    xp     : (mp, fp) X padded to the block grid (== x when params is None)
    xn     : (m,)     row squared norms, f32
    m, f   : true (unpadded) dimensions
    params : the KernelParams the padding was laid out for (None = no
             Pallas backend in play; xp is x unpadded)
    """

    x: jax.Array
    xp: jax.Array
    xn: jax.Array
    m: int
    f: int
    params: Optional[KernelParams]


jax.tree_util.register_pytree_node(
    DataPlan,
    lambda p: ((p.x, p.xp, p.xn), (p.m, p.f, p.params)),
    lambda aux, kids: DataPlan(kids[0], kids[1], kids[2], *aux))


def plan_data(x: jax.Array, params: Optional[KernelParams] = None) -> DataPlan:
    """Build the per-fit :class:`DataPlan` (pad + row norms, once)."""
    m, f = x.shape
    xn = jnp.sum(x.astype(jnp.float32) ** 2, axis=1)
    if params is None:
        return DataPlan(x=x, xp=x, xn=xn, m=m, f=f, params=None)
    mp = _round_up(m, params.block_m)
    fp = _round_up(f, params.block_f)
    xp = jnp.pad(x, ((0, mp - m), (0, fp - f)))
    return DataPlan(x=x, xp=xp, xn=xn, m=m, f=f, params=params)


@dataclasses.dataclass(frozen=True)
class QuantPlan:
    """Per-fit data plan for the int8 distance template: X quantized per
    row (scale = max|row|/127, the :mod:`repro.dist.compression` scheme),
    padded to the block grid, with exact f32 row norms of the *unquantized*
    samples — quantization, like padding, happens once per fit.

    The quantized values are stored in a *carrier* dtype: int8 on TPU
    (feeds the MXU int8 path directly), float32 off TPU where XLA's int8
    matmul is several times slower than f32 — the f32 carrier holds the
    same integers, and int8 dot products are bit-exact in f32 for any
    F <= 1040 (F * 127^2 < 2^24).

    x      : (m, f)   the original samples (update pass / reseeding)
    xq     : (mp, fp) quantized X in the carrier dtype, zero padded
    sx     : (mp, 1)  f32 per-row scales (1.0 in padded rows)
    xn     : (m,)     exact row squared norms of the unquantized x, f32
    m, f   : true (unpadded) dimensions
    params : the KernelParams the padding was laid out for (None = no
             Pallas backend in play; xq/sx are unpadded, for the XLA
             analogue)
    """

    x: jax.Array
    xq: jax.Array
    sx: jax.Array
    xn: jax.Array
    m: int
    f: int
    params: Optional[KernelParams]


jax.tree_util.register_pytree_node(
    QuantPlan,
    lambda p: ((p.x, p.xq, p.sx, p.xn), (p.m, p.f, p.params)),
    lambda aux, kids: QuantPlan(kids[0], kids[1], kids[2], kids[3], *aux))


def plan_data_int8(x: jax.Array, params: Optional[KernelParams] = None, *,
                   carrier: Any = None) -> QuantPlan:
    """Build the per-fit :class:`QuantPlan` (quantize + pad + norms, once).

    ``carrier=None`` picks the natural carrier for the backend: int8 on
    TPU, float32 elsewhere (see :class:`QuantPlan`). Tests pin
    ``carrier=jnp.int8`` to exercise the Pallas template in interpret mode.
    ``params=None`` skips padding (the XLA-analogue backend consumes the
    quantized rows unpadded); the resulting plan cannot feed the Pallas
    template.
    """
    if carrier is None:
        carrier = jnp.int8 if on_tpu() else jnp.float32
    m, f = x.shape
    xf = x.astype(jnp.float32)
    xn = jnp.sum(xf ** 2, axis=1)
    q, sx = _quantize_rows(xf)
    if params is None:
        return QuantPlan(x=x, xq=q.astype(carrier), sx=sx, xn=xn,
                         m=m, f=f, params=None)
    mp = _round_up(m, params.block_m)
    fp = _round_up(f, params.block_f)
    xq = jnp.pad(q.astype(carrier), ((0, mp - m), (0, fp - f)))
    sxp = jnp.pad(sx, ((0, mp - m), (0, 0)), constant_values=1.0)
    return QuantPlan(x=x, xq=xq, sx=sxp, xn=xn, m=m, f=f, params=params)


@dataclasses.dataclass(frozen=True)
class BatchPlan:
    """Per-fit data plan for B problems: their rows packed back to back
    and padded to the kernel grid, and their row squared norms, computed
    exactly once and reused across every batched Lloyd iteration.

    Problem ``b``'s rows sit in ``xp`` from ``offsets[b]``, padded with
    zero rows to a whole number of ``block``-row tiles, so every row tile
    lies in one problem. The tile map says which (``tile_prob``), how
    many of its rows are real (``tile_rows``) and where its partial sums
    go (``tile_slot``: each run of problems with one tile count keeps
    them tile-major, so its tree sum halves contiguous blocks). A
    (B, N, F) stack is the case of equal lengths: ``xp`` is then its
    padded block, reshaped. X is held once: the padded copy, or, without
    tiles (the XLA analogue's plan), the caller's rows themselves.

    xp        : (mp, fp)  packed rows, each problem padded to whole tiles
    xn        : (mp,)     row squared norms, f32 (0 in padded rows)
    tile_prob : (T,)      int32, the problem of each row tile
    tile_rows : (T,)      int32, the valid rows of each row tile
    tile_slot : (T,)      int32, the partials row of each row tile
    lengths   : the B problems' row counts (static)
    block     : rows per tile, the padding unit (1 = rows unpadded)
    f         : true feature count
    params    : the KernelParams the padding was laid out for (None = no
                Pallas backend in play: ``block`` is 1, features unpadded,
                no tile map)
    """

    xp: jax.Array
    xn: jax.Array
    tile_prob: jax.Array
    tile_rows: jax.Array
    tile_slot: jax.Array
    lengths: tuple[int, ...]
    block: int
    f: int
    params: Optional[KernelParams]

    @property
    def b(self) -> int:
        return len(self.lengths)

    @property
    def n_max(self) -> int:
        return max(self.lengths)

    @property
    def stacked(self) -> bool:
        """Every problem has the same row count."""
        return len(set(self.lengths)) == 1

    @property
    def tiles(self) -> tuple[int, ...]:
        """Row tiles of each problem."""
        return tuple(-(-n // self.block) for n in self.lengths)

    @property
    def offsets(self) -> tuple[int, ...]:
        """First row of each problem in ``xp``."""
        ends = np.cumsum([t * self.block for t in self.tiles])
        return tuple(int(e) for e in np.concatenate([[0], ends[:-1]]))

    @property
    def rows_valid(self) -> int:
        return int(sum(self.lengths))

    @property
    def rows_padded(self) -> int:
        return self.xp.shape[0] - self.rows_valid

    @property
    def x(self) -> jax.Array:
        """(B, n_max, F) the problems' rows, 0 past each problem's end: a
        stack's own (B, N, F) block."""
        return self.per_problem(self.xp)[..., :self.f]

    def valid(self) -> jax.Array:
        """(B, n_max) bool: which slots of a per-problem row are rows."""
        n = jnp.asarray(self.lengths, jnp.int32)
        return jnp.arange(self.n_max, dtype=jnp.int32)[None, :] < n[:, None]

    def per_problem(self, v: jax.Array) -> jax.Array:
        """(mp, ...) values in the packed layout -> (B, n_max, ...) per
        problem, 0 past each problem's rows. A stack is a reshape; ragged
        problems gather whole row tiles (a problem starts on a tile),
        never single values: a gather of scalars is slow on the TPU."""
        num_t, rest = v.shape[0] // self.block, v.shape[1:]
        span = max(self.tiles)
        if self.stacked:
            return v.reshape((self.b, span * self.block) + rest)[
                :, :self.n_max]
        first = jnp.asarray(self.offsets, jnp.int32) // self.block
        idx = jnp.minimum(first[:, None] + jnp.arange(span)[None, :],
                          num_t - 1)
        out = v.reshape((num_t, self.block) + rest)[idx].reshape(
            (self.b, span * self.block) + rest)[:, :self.n_max]
        valid = self.valid()
        valid = valid.reshape(valid.shape + (1,) * len(rest))
        return jnp.where(valid, out, jnp.zeros((), v.dtype))

    def packed(self, v: jax.Array) -> jax.Array:
        """(B, n_max) per-problem values -> (sum(lengths),) packed, in the
        caller's row order: one program per ``lengths``."""
        return _packed(v, lengths=self.lengths)


jax.tree_util.register_pytree_node(
    BatchPlan,
    lambda p: ((p.xp, p.xn, p.tile_prob, p.tile_rows, p.tile_slot),
               (p.lengths, p.block, p.f, p.params)),
    lambda aux, kids: BatchPlan(*kids, *aux))


@functools.partial(jax.jit, static_argnames=("lengths",))
def _packed(v: jax.Array, *, lengths: tuple[int, ...]) -> jax.Array:
    return jnp.concatenate([v[b, :n] for b, n in enumerate(lengths)])


@functools.partial(jax.jit, static_argnames=("rows", "fp"))
def _pad_stack(x: jax.Array, *, rows: int, fp: int) -> jax.Array:
    """(B, N, F) -> (B * rows, fp): each problem zero-padded to ``rows``
    rows and its features to ``fp``, flattened in one program (an eager
    reshape would copy the padded block)."""
    b, n, f = x.shape
    return jnp.pad(x, ((0, 0), (0, rows - n), (0, fp - f))).reshape(
        b * rows, fp)


@functools.partial(jax.jit, static_argnames=("f",))
def _row_norms(xp: jax.Array, *, f: int) -> jax.Array:
    """Squared norms, in f32, of the first ``f`` features of each row:
    one fused reduction, so no squares of X are held."""
    return jnp.sum(xp[:, :f].astype(jnp.float32) ** 2, axis=1)


@functools.partial(jax.jit, static_argnames=("lengths", "block", "fp"))
def _pack_rows(x: jax.Array, *, lengths: tuple[int, ...], block: int,
               fp: int) -> jax.Array:
    """Packed (sum N, F) rows -> each problem padded to whole tiles of
    ``block`` rows and features to ``fp``, zero in the padding: one
    contiguous copy per problem into a zeroed block. Each problem's
    source offset passes through an optimization barrier with the block
    written so far, so its slice is read only after the previous copy:
    left free, XLA slices many problems ahead into temporaries (0.92 GB
    at the KV-key cell's shapes on a v5e)."""
    tiles = [-(-n // block) for n in lengths]
    xp = jnp.zeros((sum(tiles) * block, fp), x.dtype)
    start = dst = 0
    for n, t in zip(lengths, tiles):
        xp, s = jax.lax.optimization_barrier((xp, jnp.int32(start)))
        rows = jnp.pad(jax.lax.dynamic_slice_in_dim(x, s, n),
                       ((0, 0), (0, fp - x.shape[1])))
        xp = jax.lax.dynamic_update_slice(xp, rows, (dst, 0))
        start, dst = start + n, dst + t * block
    return xp


# Row tiles of a ragged launch hold at least this many rows per padded
# cluster slot (up to MAX_RAGGED_BLOCK): each tile writes one (K, F)
# partial, so the partials stay under 1/16 of X's bytes, at the price of
# up to B * (block_m - 1) padded rows.
RAGGED_ROWS_PER_SLOT = 16
MAX_RAGGED_BLOCK = 4096


def ragged_params(params: KernelParams, n_max: int, k: int, f: int,
                  dtype: Any = jnp.float32) -> KernelParams:
    """The tiles of a ragged launch: ``params`` with ``block_m`` raised to
    ``RAGGED_ROWS_PER_SLOT`` rows per padded cluster slot, then clamped to
    the longest problem like any launch."""
    bm = max(params.block_m, min(RAGGED_ROWS_PER_SLOT * _round_up(k, 128),
                                 MAX_RAGGED_BLOCK))
    return clamp_params(n_max, k, f, dataclasses.replace(params, block_m=bm),
                        dtype=dtype)


def ragged_lengths(x: jax.Array, lengths) -> tuple[int, ...]:
    """Check ``lengths`` against packed rows ``x`` (sum N, F) and return
    them as a tuple of ints."""
    arr = np.asarray(lengths)
    if arr.ndim != 1 or arr.size == 0 or not np.issubdtype(arr.dtype,
                                                           np.integer):
        raise ValueError(f"lengths must be a non-empty 1-D integer array, "
                         f"got {lengths!r}")
    if x.ndim != 2:
        raise ValueError(f"ragged problems are packed (sum N, F) rows, got "
                         f"shape {x.shape}")
    out = tuple(int(n) for n in arr)
    if min(out) < 1:
        raise ValueError(f"every problem needs at least one row, got "
                         f"lengths {out}")
    if sum(out) != x.shape[0]:
        raise ValueError(f"lengths sum to {sum(out)} rows but x has "
                         f"{x.shape[0]}")
    return out


def plan_data_batched(x: jax.Array, params: Optional[KernelParams] = None,
                      lengths=None) -> BatchPlan:
    """Build the per-fit :class:`BatchPlan` (pack + row norms, once).

    ``x`` is a (B, N, F) stack, or, with ``lengths`` (B,), the rows of B
    problems of any row counts packed back to back, (sum N, F); packed
    rows of one length are that stack, reshaped. With ``params`` every
    problem is padded to whole ``block_m`` row tiles and features to
    ``block_f``: a stack by one pad of the whole block (a per-problem loop
    of pads is the dispatch overhead the batched path exists to remove),
    its row norms taken as :func:`plan_data` takes them, so each problem's
    arithmetic is the single-problem path's; ragged rows by one copy per
    problem (at most B * (block_m - 1) padded rows), their norms in one
    fused reduction. Without ``params``, rows are kept as they are."""
    if lengths is not None:
        lengths = ragged_lengths(x, lengths)
        if len(set(lengths)) == 1:
            x = x.reshape(len(lengths), lengths[0], x.shape[1])
    block = params.block_m if params is not None else 1
    f = x.shape[-1]
    fp = _round_up(f, params.block_f) if params is not None else f
    if x.ndim == 3:
        b, n, _ = x.shape
        lengths, np_ = (n,) * b, _round_up(n, block)
        xn = jnp.sum(x.astype(jnp.float32) ** 2, axis=2)
        xn = jnp.pad(xn, ((0, 0), (0, np_ - n))).reshape(-1)
        xp = _pad_stack(x, rows=np_, fp=fp)
    else:
        xp = x if params is None else _pack_rows(x, lengths=lengths,
                                                 block=block, fp=fp)
        xn = _row_norms(xp, f=f)
    tiles = [-(-n // block) for n in lengths] if params is not None else []
    tile_prob = np.repeat(np.arange(len(tiles), dtype=np.int32), tiles)
    tile_rows = np.concatenate([np.zeros((0,), np.int32)] + [
        np.minimum(block, n - block * np.arange(t)) for n, t in
        zip(lengths, tiles)]).astype(np.int32)
    return BatchPlan(xp=xp, xn=xn, tile_prob=jnp.asarray(tile_prob),
                     tile_rows=jnp.asarray(tile_rows),
                     tile_slot=jnp.asarray(_tile_slots(tiles)),
                     lengths=lengths, block=block, f=f, params=params)


def _tile_slots(tiles: list[int]) -> np.ndarray:
    """The partials row of each row tile: a run of g problems with n tiles
    each, tiles (b, j) in problem order, writes its partials as an
    (n, g) block, tile j of problem b at row j * g + b."""
    slots, start = [], 0
    for n, group in itertools.groupby(tiles):
        g = len(list(group))
        j = np.arange(g * n)
        slots.append(start + (j % n) * g + j // n)
        start += g * n
    return np.concatenate([np.zeros((0,), np.int64)] + slots).astype(
        np.int32)


def _pad_centroids_batched(c: jax.Array, k: int, kp: int,
                           fp: int) -> tuple[jax.Array, jax.Array]:
    """Pad per-problem centroids to (B, kp, fp) and build +inf-masked
    squared norms (B, 1, kp) so padded slots never win any problem's
    argmin."""
    cpad = jnp.pad(c, ((0, 0), (0, kp - c.shape[1]), (0, fp - c.shape[2])))
    cn = jnp.sum(cpad.astype(jnp.float32) ** 2, axis=2)        # (B, kp)
    slot = jnp.arange(kp)
    cn = jnp.where(slot[None, :] < k, cn, jnp.inf)[:, None, :]
    return cpad, cn


def _pad_centroids(c: jax.Array, k: int, kp: int,
                   fp: int) -> tuple[jax.Array, jax.Array]:
    """Pad centroids to (kp, fp) and build +inf-masked squared norms so
    padded centroid slots never win the argmin."""
    cpad = jnp.pad(c, ((0, kp - c.shape[0]), (0, fp - c.shape[1])))
    cn = jnp.sum(cpad.astype(jnp.float32) ** 2, axis=1)
    slot = jnp.arange(kp)
    cn = jnp.where(slot < k, cn, jnp.inf)[None, :]
    return cpad, cn


def clamp_params(m: int, k: int, f: int, params: KernelParams,
                 dtype: Any = jnp.float32) -> KernelParams:
    """Shrink blocks that exceed the (padded) problem so tiny shapes work.
    Alignment is dtype-aware: 2-byte dtypes keep 16-row sublane tiles."""
    def shrink(block: int, dim: int, align: int) -> int:
        while block > align and block > _round_up(dim, align):
            block //= 2
        return max(block, align)
    return KernelParams(
        block_m=shrink(params.block_m, m, sublane_align(dtype)),
        block_k=shrink(params.block_k, k, 128),
        block_f=shrink(params.block_f, f, 128),
    )


def _resolve_padded(x: Any, c: jax.Array, params: Optional[KernelParams],
                    kind: str) -> tuple:
    """Common front end: accept a raw X or a prebuilt :class:`DataPlan` and
    return (plan, padded centroids, masked centroid norms, params). The
    centroids are cast to the plan's dtype — the kernels' MXU product wants
    one input dtype, and X's dtype is the template's compute dtype."""
    k = c.shape[0]
    if isinstance(x, DataPlan):
        plan = x
        params = plan.params
        if params is None:
            raise ValueError(
                "DataPlan was built without KernelParams (plan_data(x) with "
                "params=None pads nothing); build it with the kernel's tile "
                "selection — plan_data(x, params) — before feeding a Pallas "
                "kernel")
    else:
        if params is None:
            from repro.api.cache import default_cache
            _, params = default_cache().lookup(x.shape[0], k, x.shape[1],
                                               kind=kind, dtype=x.dtype)
        params = clamp_params(x.shape[0], k, x.shape[1], params,
                              dtype=x.dtype)
        plan = plan_data(x, params)
    c = c.astype(plan.xp.dtype)
    kp = _round_up(k, params.block_k)
    cp, cn = _pad_centroids(c, k, kp, plan.xp.shape[1])
    return plan, cp, cn, params


def fused_assign(
    x: jax.Array,
    c: jax.Array,
    params: Optional[KernelParams] = None,
    *,
    variant: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Nearest-centroid assignment via the fused kernel.

    ``x`` may be a raw (M, F) array or a prebuilt :class:`DataPlan` (then
    ``params`` comes from the plan); f32, bf16 and fp16 inputs all lower
    (f32 accumulate). ``variant=None`` auto-selects the small-K fast path
    whenever K fits one centroid tile — the same rule the autotuner models.
    Returns (assign (M,) int32, partial min distance (M,) f32). Add
    ``sum(x**2, -1)`` for true squared distances.
    """
    if not isinstance(x, DataPlan) and x.shape[0] == 0:
        # zero-row request (serving edge case): nothing to assign, and
        # padding up to a tile would still launch a full grid — and worse,
        # a params=None call would ask the autotuner to model an M=0 shape
        return jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32)
    plan, cp, cn, params = _resolve_padded(x, c, params, "assign")
    variant = resolve_variant(c.shape[0], params, variant)
    if interpret is None:
        interpret = not on_tpu()
    mind, am = _da.distance_argmin(
        plan.xp, cp, cn, block_m=params.block_m, block_k=params.block_k,
        block_f=params.block_f, variant=variant, interpret=interpret)
    m = plan.m
    return am[:m, 0], mind[:m, 0]


def _resolve_padded_int8(x: Any, c: jax.Array,
                         params: Optional[KernelParams]) -> tuple:
    """int8 front end: accept a raw X or a prebuilt :class:`QuantPlan` and
    return (plan, quantized padded centroids, padded centroid scales,
    masked centroid norms, params). Centroids are quantized per row here —
    they move every iteration, so unlike X their quantization is per-call —
    and their squared norms come from the *unquantized* values (the
    template's norm term is exact; only the cross term is quantized)."""
    k = c.shape[0]
    if isinstance(x, QuantPlan):
        plan = x
        if plan.params is None:
            raise ValueError(
                "QuantPlan was built without KernelParams (the unpadded "
                "XLA-analogue layout); the Pallas int8 template needs a "
                "block-padded plan — build it with plan_data_int8(x, "
                "params)")
        params = plan.params
    else:
        if params is None:
            from repro.api.cache import default_cache
            _, params = default_cache().lookup(x.shape[0], k, x.shape[1],
                                               kind="int8", dtype=jnp.int8)
        params = clamp_params(x.shape[0], k, x.shape[1], params,
                              dtype=jnp.int8)
        plan = plan_data_int8(x, params)
    cf = c.astype(jnp.float32)
    kp = _round_up(k, params.block_k)
    fp = plan.xq.shape[1]
    cpad = jnp.pad(cf, ((0, kp - k), (0, fp - cf.shape[1])))
    cn = jnp.sum(cpad ** 2, axis=1)
    cn = jnp.where(jnp.arange(kp) < k, cn, jnp.inf)[None, :]
    cq, sc = _quantize_rows(cf)
    cqp = jnp.pad(cq.astype(plan.xq.dtype),
                  ((0, kp - k), (0, fp - cf.shape[1])))
    scp = jnp.pad(sc, ((0, kp - k), (0, 0)), constant_values=1.0).T  # (1,kp)
    return plan, cqp, scp, cn, params


def fused_assign_int8(
    x: jax.Array,
    c: jax.Array,
    params: Optional[KernelParams] = None,
    *,
    variant: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Nearest-centroid assignment via the int8 distance template.

    ``x`` may be a raw (M, F) array (quantized here, per row) or a prebuilt
    :class:`QuantPlan` (then ``params`` comes from the plan and the per-fit
    quantization is reused). ``c`` is the *unquantized* (K, F) centroid
    array — centroid quantization is per-call because centroids move every
    iteration. ``variant=None`` auto-selects the small-K fast path exactly
    like :func:`fused_assign`. Returns (assign (M,) int32, partial min
    distance (M,) f32); add ``sum(x**2, -1)`` for true squared distances.

    On quantization-safe data (integer entries in [-127, 127] with a
    +-127 entry per row) the argmin is bit-exact against
    :func:`fused_assign`; on float data the distance error is bounded by
    the ~1/127-per-operand quantization step (see
    :mod:`repro.kernels.distance_argmin_int8`).
    """
    if not isinstance(x, QuantPlan) and x.shape[0] == 0:
        # zero-row request: same serving edge case as fused_assign
        return jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32)
    plan, cqp, scp, cn, params = _resolve_padded_int8(x, c, params)
    variant = resolve_variant(c.shape[0], params, variant)
    if interpret is None:
        interpret = not on_tpu()
    # The Pallas template wants int8 tiles. A plan built with the f32
    # carrier (the off-TPU default, consumed by the XLA analogue backend)
    # holds int8-valued floats, so the cast back is exact.
    xq = plan.xq.astype(jnp.int8)
    cq = cqp.astype(jnp.int8)
    mind, am = _dai.distance_argmin_int8(
        xq, cq, plan.sx, scp, cn, block_m=params.block_m,
        block_k=params.block_k, block_f=params.block_f, variant=variant,
        interpret=interpret)
    m = plan.m
    return am[:m, 0], mind[:m, 0]


def _halve(a: jax.Array) -> jax.Array:
    """Balanced pairwise reduction over axis 0 (log2 depth, better fp
    behaviour than a linear fold for many partial blocks)."""
    while a.shape[0] > 1:
        half = a.shape[0] // 2
        rest = a[2 * half:]
        a = jnp.concatenate([a[:half] + a[half:2 * half], rest], axis=0)
    return a[0]


def _tree_sum(a: jax.Array) -> jax.Array:
    """:func:`_halve` in the ``partials`` scope: one problem's partial
    blocks."""
    with obs.scope("partials"):
        return _halve(a)


def fused_lloyd(
    x: jax.Array,
    c: jax.Array,
    params: Optional[KernelParams] = None,
    *,
    variant: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One-pass Lloyd step via the fused kernel: assignment plus the
    per-cluster sums/counts the centroid update needs, X read once.

    ``x`` may be a raw (M, F) array or a prebuilt :class:`DataPlan`; f32,
    bf16 and fp16 inputs all lower (f32 accumulators and outputs).
    ``variant=None`` auto-selects the small-K fast path whenever K fits one
    centroid tile. Returns (assign (M,) int32, true squared distance (M,)
    f32, sums (K, F) f32, counts (K,) f32).
    """
    plan, cp, cn, params = _resolve_padded(x, c, params, "lloyd")
    variant = resolve_variant(c.shape[0], params, variant)
    if interpret is None:
        interpret = not on_tpu()
    k, m = c.shape[0], plan.m
    meta = jnp.array([m], jnp.int32)
    mind, am, sums, counts = _ll.lloyd_step(
        plan.xp, cp, cn, meta, block_m=params.block_m,
        block_k=params.block_k, block_f=params.block_f, variant=variant,
        interpret=interpret)
    sums = _tree_sum(sums)[:k, :plan.f]
    counts = _tree_sum(counts)[:k]
    return am[:m, 0], mind[:m, 0] + plan.xn, sums, counts


# ---------------------------------------------------------------------------
# Two-pass centroid update
# ---------------------------------------------------------------------------

class ClusterSums(NamedTuple):
    """Per-cluster sums and counts of one update pass."""

    sums: jax.Array      # (K, F) f32
    counts: jax.Array    # (K,) f32, exact integers
    windows: jax.Array   # f32 scalar: 128-cluster windows the sorted kernel
    #                      ran per row tile (1.0: every tile fit one); 0.0
    #                      on the dense path


# Row tile of the sorted update: at the IVF4096 cell's Zipf(1) cluster
# sizes a 1024-row tile of label-sorted rows spans at most 26 labels (44
# if the sizes fall in label order; a numpy model of the sizes), so one
# window covers every tile.
SORTED_BLOCK_M = 1024
# Smallest K at which the sorted update (sort, gather, window kernel) beats
# the dense one-hot product, whose cost grows with K, for each X dtype the
# sorted kernel takes. TPU v5e, M = 1 M, F = 128 (chip probes, PERF.md §6):
# f32: dense 3.47, 4.48, 6.70, 12.12, 22.27 ms at K = 256, 512, 1024, 2048,
# 4096; sorted 13.3 to 13.9 ms at every K; they cross near K = 2400. At
# M = 65,536 they tie at K = 1024 (1.42 against 1.48 ms) and the sorted
# path wins at 4096 (1.76 against 2.28), so the rule holds for smaller M
# too. bf16 (the dense product in one MXU pass, not three): dense 11.54,
# 13.82, 16.41, 21.82 ms at K = 4096, 5120, 6144, 8192; sorted 14.14 to
# 14.46; they cross near K = 5300. Other dtypes take the dense product:
# Mosaic has no float16 row load for the kernel, and the estimators hand
# int8 fits an f32 X.
SORTED_MIN_K = {jnp.dtype(jnp.float32): 2560, jnp.dtype(jnp.bfloat16): 5376}


def cluster_sums_vmem_bytes(k: int, f: int, dtype: Any = jnp.float32) -> int:
    """Working-set estimate of the sorted-window kernel: double-buffered
    row and label tiles, the resident (double-buffered) f32 sums block, the
    bf16 slices of a row tile and one window's one-hot and products."""
    block_m = SORTED_BLOCK_M
    fp = _round_up(f, 128)
    rows = 2 * block_m * fp * _itemsize(dtype) + 2 * 8 * block_m * 4
    out = 2 * _cs.out_rows(k) * fp * 4
    work = (3 * block_m * fp * 2 + 2 * _cs.WINDOW * block_m * 4
            + 3 * _cs.WINDOW * fp * 4)
    return rows + out + work


def update_path(m: int, k: int, f: int, dtype: Any = jnp.float32) -> str:
    """The update the TPU runs for M rows of width F into K clusters:
    ``"sorted"`` for an X of a dtype in :data:`SORTED_MIN_K` from its
    threshold on, while the sums block fits VMEM; ``"dense"`` (the one-hot
    product) otherwise. The dense product costs 2·M·K·F flops; the sorted
    path a sort of M labels, a gather of M rows and 2·M·128·F flops,
    whatever K is."""
    min_k = SORTED_MIN_K.get(jnp.dtype(dtype))
    if (min_k is not None and k >= min_k and m >= SORTED_BLOCK_M
            and cluster_sums_vmem_bytes(k, f, dtype) <= hw.VMEM_BUDGET):
        return "sorted"
    return "dense"


def cluster_sums(x: jax.Array, am: jax.Array, k: int) -> ClusterSums:
    """Per-cluster sums and counts of ``x`` (M, F) under labels ``am``
    (M,) int32 in [0, K): the two-pass update. On the TPU the path follows
    :func:`update_path`; elsewhere the dense product runs (Pallas would
    only interpret)."""
    m, f = x.shape
    if on_tpu() and update_path(m, k, f, x.dtype) == "sorted":
        return _sorted_sums(x, am, k, interpret=False)
    return _dense_sums(x, am, k)


def _dense_sums(x: jax.Array, am: jax.Array, k: int) -> ClusterSums:
    """The one-hot product ``onehot(am)^T x`` at full precision; counts
    are the one-hot's column sums, in f32 for every input dtype."""
    onehot = jax.nn.one_hot(am, k, dtype=x.dtype)            # (M, K)
    sums = jax.lax.dot_general(onehot, x, (((0,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    counts = jnp.sum(onehot.astype(jnp.float32), axis=0)
    return ClusterSums(sums, counts, jnp.float32(0.0))


def _sort_labels(am: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """``am`` sorted stably, with the row order that sorts it. One
    operand, the packed key ``label·2^b + row``, where it fits 32 bits;
    else a two-operand sort."""
    m = am.shape[0]
    bits = max(1, (m - 1).bit_length())
    rows = jnp.arange(m, dtype=jnp.int32)
    if k << bits > 2 ** 32:
        labels, order = jax.lax.sort((am, rows), num_keys=1, is_stable=True)
        return labels, order
    key = jnp.sort((am.astype(jnp.uint32) << bits) | rows.astype(jnp.uint32))
    return ((key >> bits).astype(jnp.int32),
            (key & ((1 << bits) - 1)).astype(jnp.int32))


def _sorted_sums(x: jax.Array, am: jax.Array, k: int, *,
                 interpret: bool = False) -> ClusterSums:
    """The sorted-window update: sort the labels, count each cluster from
    the sorted labels, gather X's rows in label order and sum each row
    tile against its label windows (``kernels/cluster_sums.py``)."""
    m = x.shape[0]
    bm = min(SORTED_BLOCK_M, _round_up(m, 128))
    mp = _round_up(m, bm)
    with obs.scope("sort"):
        labels, order = _sort_labels(am, k)
        edges = jnp.searchsorted(labels, jnp.arange(k + 1, dtype=jnp.int32))
        counts = jnp.diff(edges).astype(jnp.float32)
        last = labels[jnp.minimum(jnp.arange(bm, mp + 1, bm), m) - 1]
        start = labels[::bm] // 8 * 8
        nwin = (last - start) // _cs.WINDOW + 1
        labels = jnp.pad(labels, (0, mp - m),
                         constant_values=_cs.PAD_LABEL)[None]
    with obs.scope("gather"):
        xs = x.at[jnp.pad(order, (0, mp - m))].get(
            mode="promise_in_bounds")
    sums = _cs.cluster_sums(start, nwin, labels, xs, k=k, block_m=bm,
                            interpret=interpret)
    windows = jnp.sum(nwin).astype(jnp.float32) / nwin.shape[0]
    return ClusterSums(sums[:k], counts, windows)


# Relative + absolute fp-safety slack on the tile skip test. The bounds
# are f32 and derived from rounded kernel outputs, so the raw comparison
# is not rigorously conservative at the last ulp; the slack makes a wrong
# skip require a bound error several orders of magnitude above f32
# rounding noise, while separated clusters keep margins far above it.
PRUNE_SLACK = 1e-3


@dataclasses.dataclass(frozen=True)
class BoundsState:
    """Iteration-carried Hamerly bounds for the pruned one-pass kernel.

    Registered as a pytree (all fields are leaves) so it threads through
    ``jax.lax.scan`` carries and jit boundaries like any array. The state
    is only meaningful for the (params, k, f, backend) it was built for;
    anything that moves centroids outside the kernel's own update —
    ``partial_fit`` resumption, ``from_state`` rehydration, a served
    centroid hot-swap — must replace it with a fresh state
    (:func:`init_bounds`), whose ``fresh`` flag forces the next call to
    compute every tile and reseed real bounds.

    ub     : (m,)       f32 upper bound on each row's Euclidean distance
                        to its assigned centroid
    assign : (m,)       i32 assignment the upper bounds pair with
    tmin   : (nmt, nkt) f32 per-(row tile, centroid tile) Euclidean group
                        lower bound
    c_prev : (kp, fp)   f32 copy of the padded centroids the bounds were
                        computed against (the drift reference; stored in
                        f32 *after* the compute-dtype cast so drift is
                        measured in the space the kernel sees)
    fresh  : ()         bool — True = placeholder state; the next call
                        skips nothing and seeds real bounds
    """

    ub: jax.Array
    assign: jax.Array
    tmin: jax.Array
    c_prev: jax.Array
    fresh: jax.Array


jax.tree_util.register_pytree_node(
    BoundsState,
    lambda s: ((s.ub, s.assign, s.tmin, s.c_prev, s.fresh), ()),
    lambda aux, kids: BoundsState(*kids))


def init_bounds(m: int, k: int, f: int,
                params: Optional[KernelParams] = None, *,
                dtype: Any = jnp.float32) -> BoundsState:
    """Fresh (all-invalid) :class:`BoundsState` for a pruned fit: shaped
    for the clamped tile grid of (m, k, f) so it is a valid scan carry
    from iteration zero, with ``fresh=True`` so the first call computes
    every tile."""
    if params is None:
        from repro.api.cache import default_cache
        _, params = default_cache().lookup(m, k, f, kind="pruned",
                                           dtype=dtype)
    params = clamp_params(m, k, f, params, dtype=dtype)
    mp = _round_up(m, params.block_m)
    kp = _round_up(k, params.block_k)
    fp = _round_up(f, params.block_f)
    return BoundsState(
        ub=jnp.zeros((m,), jnp.float32),
        assign=jnp.zeros((m,), jnp.int32),
        tmin=jnp.zeros((mp // params.block_m, kp // params.block_k),
                       jnp.float32),
        c_prev=jnp.zeros((kp, fp), jnp.float32),
        fresh=jnp.ones((), bool),
    )


def fused_lloyd_pruned(
    x: jax.Array,
    c: jax.Array,
    params: Optional[KernelParams] = None,
    *,
    bounds: Optional[BoundsState] = None,
    variant: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, BoundsState,
           jax.Array]:
    """One-pass Lloyd step with tile-granular triangle-inequality pruning.

    Same contract as :func:`fused_lloyd` plus an iteration-carried
    :class:`BoundsState`: each (row tile, centroid tile) cell is skipped
    when its decayed Euclidean group lower bound cannot beat the row
    tile's worst-case upper bound (``tlb > maxub`` with
    :data:`PRUNE_SLACK` safety margin). Skipping only omits folds that
    provably lose strictly, so assignments, distances, sums and counts
    are bit-identical to :func:`fused_lloyd` at the same tiles.

    ``bounds=None`` (or a ``fresh`` state) computes every tile and seeds
    the bounds — the unpruned first iteration. Single-tile shapes
    (``smallk``, or K inside one ``block_k``) can never skip.

    Returns (assign (M,) int32, true squared distance (M,) f32, sums
    (K, F) f32, counts (K,) f32, new bounds, pruned tile fraction
    (scalar f32)).
    """
    plan, cp, cn, params = _resolve_padded(x, c, params, "pruned")
    variant = resolve_variant(c.shape[0], params, variant)
    if interpret is None:
        interpret = not on_tpu()
    k, m = c.shape[0], plan.m
    mp = plan.xp.shape[0]
    kp = cp.shape[0]
    nmt = mp // params.block_m
    nkt = kp // params.block_k
    if bounds is None:
        bounds = init_bounds(m, k, plan.f, params, dtype=plan.xp.dtype)
    meta = jnp.array([m], jnp.int32)
    xnp = jnp.pad(plan.xn, (0, mp - m))[:, None]
    cpf = cp.astype(jnp.float32)
    # Decay the recorded group bounds by each tile's worst centroid drift
    # and compare against the row tile's worst adjusted upper bound. The
    # tile holding a row's assigned centroid always satisfies
    # tlb <= ub_adj <= maxub, so at least that tile survives per row and
    # the argmin stays grounded.
    drift = jnp.sqrt(jnp.sum((cpf - bounds.c_prev) ** 2, axis=1))   # (kp,)
    maxdrift = jnp.max(drift.reshape(nkt, params.block_k), axis=1)  # (nkt,)
    ub_adj = bounds.ub + drift[bounds.assign]                       # (m,)
    maxub = jnp.max(
        jnp.pad(ub_adj, (0, mp - m), constant_values=-jnp.inf)
        .reshape(nmt, params.block_m), axis=1)                      # (nmt,)
    tlb = bounds.tmin - maxdrift[None, :]                           # (nmt, nkt)
    if nkt == 1:
        # A single centroid tile contains every assigned centroid and can
        # never be skipped; forcing the mask statically keeps the smallk
        # kernel skip-free.
        skip = jnp.zeros((nmt, nkt), jnp.int32)
    else:
        can_skip = tlb > maxub[:, None] * (1.0 + PRUNE_SLACK) + PRUNE_SLACK
        skip = jnp.where(bounds.fresh, 0, can_skip.astype(jnp.int32))
    mind, am, sums, counts, tmin_k = _llp.lloyd_step_pruned(
        plan.xp, cp, cn, xnp, meta, skip, block_m=params.block_m,
        block_k=params.block_k, block_f=params.block_f, variant=variant,
        interpret=interpret)
    md = mind[:m, 0] + plan.xn
    sums_k = _tree_sum(sums)[:k, :plan.f]
    counts_k = _tree_sum(counts)[:k]
    new_bounds = BoundsState(
        ub=jnp.sqrt(jnp.maximum(md, 0.0)),
        assign=am[:m, 0],
        # skipped cells keep the decayed bound; computed cells refresh
        tmin=jnp.where(skip == 1, tlb, tmin_k),
        c_prev=cpf,
        fresh=jnp.zeros((), bool),
    )
    prune_frac = jnp.mean(skip.astype(jnp.float32))
    return am[:m, 0], md, sums_k, counts_k, new_bounds, prune_frac


def _resolve_padded_batched(x: Any, c: jax.Array,
                            params: Optional[KernelParams]) -> tuple:
    """Batched front end: accept a raw (B, N, F) stack or a prebuilt
    :class:`BatchPlan` and return (plan, padded centroids, masked centroid
    norms, params). Centroids are cast to the plan's dtype like the
    single-problem path; padded K is always one centroid tile (the batched
    template is the smallk epilogue by construction)."""
    k = c.shape[1]
    if isinstance(x, BatchPlan):
        plan = x
        params = plan.params
        if params is None:
            raise ValueError(
                "BatchPlan was built without KernelParams (plan_data_batched"
                "(x) with params=None pads nothing); build it with the "
                "kernel's tile selection — plan_data_batched(x, params) — "
                "before feeding the batched Pallas kernel")
    else:
        if params is None:
            from repro.api.cache import default_cache
            _, params = default_cache().lookup(
                x.shape[1], k, x.shape[2], kind="batched", dtype=x.dtype,
                batch=x.shape[0])
        params = clamp_params(x.shape[1], k, x.shape[2], params,
                              dtype=x.dtype)
        plan = plan_data_batched(x, params)
    c = c.astype(plan.xp.dtype)
    kp = _round_up(k, 128)
    cp, cn = _pad_centroids_batched(c, k, kp, plan.xp.shape[1])
    return plan, cp, cn, params


def fused_lloyd_batched(
    x: jax.Array,
    c: jax.Array,
    params: Optional[KernelParams] = None,
    *,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One-pass Lloyd step for B independent problems in a single launch.

    ``x`` may be a raw (B, N, F) stack or a prebuilt :class:`BatchPlan`,
    whose problems may differ in row count; ``c`` is the (B, K, F)
    per-problem centroid stack. f32, bf16 and fp16 inputs all lower (f32
    accumulators and outputs). The kernel's grid runs over the problems'
    row tiles, so one launch replaces B dispatches; per-problem arithmetic
    is identical to a loop of single-problem :func:`fused_lloyd` calls at
    the same tiles (same epilogue, same tree-reduction order). Problems of
    one row count launch ``lloyd_step_batched``, others
    ``lloyd_step_ragged`` (the same kernel). Returns (assign (B, n_max)
    int32, true squared distance (B, n_max) f32, both 0 past each
    problem's rows; sums (B, K, F) f32, counts (B, K) f32).
    """
    plan, cp, cn, params = _resolve_padded_batched(x, c, params)
    if interpret is None:
        interpret = not on_tpu()
    k = c.shape[1]
    step = _ll.lloyd_step_batched if plan.stacked else _ll.lloyd_step_ragged
    mind, am, sums, counts = step(
        plan.tile_prob, plan.tile_rows, plan.tile_slot, plan.xp, cp, cn,
        block_m=params.block_m, block_f=params.block_f, interpret=interpret)
    # each problem's row-tile partials, in the single-problem pairs
    sums = _segment_tree_sum(sums, plan.tiles)[:, :k, :plan.f]
    counts = _segment_tree_sum(counts, plan.tiles)[:, :k]
    return (plan.per_problem(am.reshape(-1)),
            plan.per_problem(mind.reshape(-1) + plan.xn), sums, counts)


def _segment_tree_sum(a: jax.Array, counts: tuple[int, ...]) -> jax.Array:
    """:func:`_tree_sum` of each problem's partial blocks, problem ``b``
    having ``counts[b]`` of them: (sum(counts), ...) -> (len(counts),
    ...). The blocks come in ``BatchPlan.tile_slot`` order: a run of g
    problems with n blocks each is an (n, g, ...) block, halved along its
    leading axis by contiguous slices, which XLA fuses into the sums with
    no copy (compile for a v5e: a problem-major run is transposed or
    copied out first)."""
    with obs.scope("partials"):
        out, start = [], 0
        for n, group in itertools.groupby(counts):
            g = len(list(group))
            out.append(_halve(a[start:start + g * n].reshape(
                (n, g) + a.shape[1:])))
            start += g * n
        return out[0] if len(out) == 1 else jnp.concatenate(out)


def _verify_update_partials(plan: Any, am: jax.Array, sums_p: jax.Array,
                            counts_p: jax.Array, ucheck: jax.Array,
                            ccheck: jax.Array, params: KernelParams,
                            interpret: bool) -> tuple:
    """Verification interval of the fused update epilogue (paper Fig. 6
    applied to the one-hot product). Compares the observed e1/e2 column
    checksums of each row tile's partial sums/counts against the expected
    ones the kernel computed from its argmin/valid vectors, and recomputes
    a mismatched tile from the data plan and the (corrected) assignment.
    The recompute runs the kernel's own update epilogue on the same
    operands, so a recovered run is bit-identical to a clean one. Under
    the §II-A SEU model at most one tile can mismatch per step; every
    mismatch is counted, the worst tile is repaired.
    """
    from repro.core.checksum import threshold_factor
    num_m, kp, fp = sums_p.shape
    bm = params.block_m
    w_k = jnp.arange(1.0, kp + 1.0, dtype=jnp.float32)
    obs1 = jnp.sum(sums_p, axis=1)                           # (num_m, fp)
    obs2 = jnp.sum(w_k[None, :, None] * sums_p, axis=1)
    res1 = jnp.abs(obs1 - ucheck[:, 0])                      # (num_m, fp)
    res2 = jnp.abs(obs2 - ucheck[:, 1])
    cres1 = jnp.abs(jnp.sum(counts_p, axis=1) - ccheck[:, 0])   # (num_m,)
    cres2 = jnp.abs(jnp.sum(w_k[None, :] * counts_p, axis=1)
                    - ccheck[:, 1])
    # contraction length is the row tile; eps tracks the stash dtype. The
    # scale comes from the expected checksums only (clean invariant side):
    # folding the possibly-corrupted partials in would let a large delta
    # inflate its own threshold (self-masking) at 2-byte dtypes. Each
    # e1/e2 pair thresholds against its own magnitude — the e2 row runs
    # ~K x larger, and a shared scale would raise the e1 detection floor
    # by that factor.
    factor = threshold_factor(bm, plan.xp.dtype)
    scale1 = jnp.maximum(jnp.max(jnp.abs(ucheck[:, 0]), axis=1), 1.0)
    scale2 = jnp.maximum(jnp.max(jnp.abs(ucheck[:, 1]), axis=1), 1.0)
    bad = ((jnp.max(res1, axis=1) > factor * scale1)
           | (jnp.max(res2, axis=1) > factor * scale2)
           | (cres1 > factor * jnp.maximum(jnp.abs(ccheck[:, 0]), 1.0))
           | (cres2 > factor * jnp.maximum(jnp.abs(ccheck[:, 1]), 1.0)))
    n_bad = jnp.sum(bad.astype(jnp.int32))

    def _recompute(operands: tuple) -> tuple:
        sums_p, counts_p = operands
        i = jnp.argmax(bad)
        x_tile = jax.lax.dynamic_slice(plan.xp, (i * bm, 0), (bm, fp))
        am_tile = jax.lax.dynamic_slice(am, (i * bm, 0), (bm, 1))
        rows_in_tile = (plan.m - i * bm)[None].astype(jnp.int32)
        # The barrier keeps XLA from fusing the writes below into the
        # kernel call: such a fusion runs under XLA's default scoped-VMEM
        # limit (16 MiB), not the kernel's own, and the update epilogue of
        # a (1024, 4096) one-hot tile does not fit it.
        new_sums, new_counts = jax.lax.optimization_barrier(
            _llft.recompute_update_tile(
                x_tile, am_tile, rows_in_tile, k=kp, interpret=interpret))
        return (jax.lax.dynamic_update_slice(sums_p, new_sums, (i, 0, 0)),
                jax.lax.dynamic_update_slice(counts_p, new_counts, (i, 0)))

    sums_p, counts_p = jax.lax.cond(
        n_bad > 0, _recompute, lambda o: o, (sums_p, counts_p))
    return sums_p, counts_p, n_bad


def fused_lloyd_ft(
    x: jax.Array,
    c: jax.Array,
    params: Optional[KernelParams] = None,
    *,
    inj: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One-pass FT Lloyd step: fused ABFT around the distance GEMM plus the
    checksum-protected update epilogue, X read from HBM once.

    ``x`` may be a raw (M, F) array or a prebuilt :class:`DataPlan`; f32,
    bf16 and fp16 inputs all lower (f32 accumulators, checksums and
    outputs). The FT template is always the generic grid (like
    ``fused_assign_ft``). ``inj`` is a dual-slot
    :func:`~repro.kernels.lloyd_step_ft.make_injection` descriptor.
    Returns (assign (M,) int32, true squared distance (M,) f32,
    sums (K, F) f32, counts (K,) f32, detected (scalar int32) — corrected
    distance-GEMM errors plus recomputed update tiles).
    """
    plan, cp, cn, params = _resolve_padded(x, c, params, "lloyd_ft")
    if interpret is None:
        interpret = not on_tpu()
    if inj is None:
        inj = _llft.no_injection()
    k, m = c.shape[0], plan.m
    meta = jnp.array([m], jnp.int32)
    mind, am, det, sums_p, counts_p, ucheck, ccheck = _llft.lloyd_step_ft(
        plan.xp, cp, cn, meta, inj, block_m=params.block_m,
        block_k=params.block_k, block_f=params.block_f, interpret=interpret)
    sums_p, counts_p, det_up = _verify_update_partials(
        plan, am, sums_p, counts_p, ucheck, ccheck, params, interpret)
    sums = _tree_sum(sums_p)[:k, :plan.f]
    counts = _tree_sum(counts_p)[:k]
    return (am[:m, 0], mind[:m, 0] + plan.xn, sums, counts,
            jnp.sum(det) + det_up)


def fused_assign_ft(
    x: jax.Array,
    c: jax.Array,
    params: Optional[KernelParams] = None,
    *,
    inj: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """FT assignment: fused ABFT detect+locate+correct inside the kernel.

    ``x`` may be a raw (M, F) array or a prebuilt :class:`DataPlan`; f32,
    bf16 and fp16 inputs all lower (checksums stay f32). The FT template is
    always the generic grid — its checksum scratch is already VMEM-resident,
    so there is no small-K variant to select. Returns (assign, partial min
    distance, corrected_error_count).
    """
    plan, cp, cn, params = _resolve_padded(x, c, params, "assign")
    if interpret is None:
        interpret = not on_tpu()
    if inj is None:
        inj = _daft.no_injection()
    mind, am, det = _daft.distance_argmin_ft(
        plan.xp, cp, cn, inj, block_m=params.block_m, block_k=params.block_k,
        block_f=params.block_f, interpret=interpret)
    m = plan.m
    return am[:m, 0], mind[:m, 0], jnp.sum(det)


def abft_matmul(
    x: jax.Array,
    y: jax.Array,
    *,
    inj: Optional[jax.Array] = None,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """ABFT GEMM D = X @ Y with in-kernel correction. Returns (D, det_count)."""
    if interpret is None:
        interpret = not on_tpu()
    m, k = x.shape
    n = y.shape[1]
    p = clamp_params(m, n, k, KernelParams(block_m, block_n, block_k))
    bm, bn, bk = p.block_m, p.block_k, p.block_f
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    yp = jnp.pad(y, ((0, kp - k), (0, np_ - n)))
    if inj is None:
        inj = _mma.no_injection()
    d, det = _mma.matmul_abft(
        xp, yp, inj, block_m=bm, block_n=bn, block_k=bk, interpret=interpret)
    return d[:m, :n], jnp.sum(det)


def plan_injection_tile(m: int, k: int, f: int, params: KernelParams,
                        row: int, col: int, f_step: int,
                        delta: float) -> jax.Array:
    """Translate a global (row, col) error position into tile coordinates."""
    params = clamp_params(m, k, f, params)
    return _daft.make_injection(
        row // params.block_m, col // params.block_k,
        f_step % max(f // params.block_f, 1),
        row % params.block_m, col % params.block_k, delta)


# ---------------------------------------------------------------------------
# Introspected kernel plans — the contract surface for repro.analysis.
# ---------------------------------------------------------------------------

# Kernel kinds with a Pallas plan. This is the canonical kind vocabulary:
# repro.core.autotune.KINDS re-exports it, so extending the family (and
# the autotune cache schema with it) is a single-point change here.
PLAN_KINDS: tuple[str, ...] = ("assign", "lloyd", "lloyd_ft", "batched",
                               "pruned", "int8", "init", "serve")

# Per-kind compute dtypes: the f32 template family lowers at every
# supported width; the int8 template is its own dtype notch (x/c tiles are
# int8 by construction, the epilogue is f32), and the fused k-means++
# round kernel runs its D² state in f32 only (seeding precision is the
# fit's floor — a half-precision CDF would bias every later iteration).
# Contract checks and the autotuner iterate this mapping instead of
# assuming one dtype set fits every kind.
PLAN_KIND_DTYPES: dict[str, tuple[str, ...]] = {
    "assign": ("float32", "bfloat16", "float16"),
    "lloyd": ("float32", "bfloat16", "float16"),
    "lloyd_ft": ("float32", "bfloat16", "float16"),
    "batched": ("float32", "bfloat16", "float16"),
    "pruned": ("float32", "bfloat16", "float16"),
    "int8": ("int8",),
    "init": ("float32",),
    # serve = the assignment kernel launched as an AOT-compiled predict
    # cell at a serving bucket shape (repro.serve). Same Pallas plan as
    # "assign"; a separate kind so bucket-shaped tile winners and the
    # per-launch dispatch cost live in their own autotune-cache namespace.
    "serve": ("float32", "bfloat16", "float16"),
}


@dataclasses.dataclass(frozen=True)
class BufferPlan:
    """One operand of a traced ``pallas_call``: per-grid-step block shape,
    dtype and memory space, recovered from the kernel jaxpr itself rather
    than re-derived from the BlockSpecs by hand — so the plan cannot drift
    from what the kernel actually allocates."""

    role: str                     # "input" | "output" | "scratch"
    memory: str                   # "vmem" | "smem"
    block_shape: tuple[int, ...]
    dtype: str

    @property
    def nbytes(self) -> int:
        n = 1
        for d in self.block_shape:
            n *= int(d)
        return n * int(jnp.dtype(self.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Grid and operand blocks of the single ``pallas_call`` behind one
    kernel entry point, obtained abstractly (``jax.make_jaxpr`` over
    ``ShapeDtypeStruct``s — no compile, no TPU)."""

    kind: str
    variant: str
    grid: tuple[int, ...]
    inputs: tuple[BufferPlan, ...]
    outputs: tuple[BufferPlan, ...]
    scratch: tuple[BufferPlan, ...]

    def vmem_bytes(self) -> int:
        """Implied footprint under the byte-model convention: VMEM input
        blocks are double-buffered, output and scratch blocks are resident
        once, SMEM operands don't count against the VMEM budget."""
        def tally(bufs: tuple[BufferPlan, ...], mult: int) -> int:
            return sum(mult * b.nbytes for b in bufs if b.memory == "vmem")
        return (tally(self.inputs, 2) + tally(self.outputs, 1)
                + tally(self.scratch, 1))


def _walk_pallas_eqns(jaxpr: jex_core.Jaxpr) -> Iterator[Any]:
    """Yield every pallas_call equation, recursing through sub-jaxprs
    (the kernel wrappers trace under a pjit equation)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            if isinstance(v, jex_core.ClosedJaxpr):
                yield from _walk_pallas_eqns(v.jaxpr)
            elif isinstance(v, jex_core.Jaxpr):
                yield from _walk_pallas_eqns(v)


def _plan_buffers(eqn: Any) -> tuple[tuple[BufferPlan, ...],
                                     tuple[BufferPlan, ...],
                                     tuple[BufferPlan, ...]]:
    gm = eqn.params["grid_mapping"]

    def buf(role: str, aval: Any, shape: Any) -> BufferPlan:
        memory = "smem" if "smem" in str(aval).lower() else "vmem"
        # JAX 0.9 block shapes hold ``Blocked`` entries, not ints
        return BufferPlan(role=role, memory=memory,
                          block_shape=tuple(int(getattr(d, "block_size", d))
                                            for d in shape),
                          dtype=jnp.dtype(aval.dtype).name)

    maps = list(gm.block_mappings)
    ins = tuple(buf("input", b.block_aval, b.block_shape)
                for b in maps[:gm.num_inputs])
    outs = tuple(buf("output", b.block_aval, b.block_shape)
                 for b in maps[gm.num_inputs:gm.num_inputs + gm.num_outputs])
    invars = eqn.params["jaxpr"].invars
    n_scr = gm.num_scratch_operands
    # DMA-semaphore scratch (the double-buffered stash handshake) has no
    # numpy dtype and occupies no VMEM bytes — it is not a buffer and is
    # excluded from the plan rather than shoehorned into one.
    def _is_sem(aval: Any) -> bool:
        try:
            jnp.dtype(aval.dtype)
        except TypeError:
            return True
        return False
    scr = tuple(buf("scratch", v.aval, v.aval.shape)
                for v in (invars[len(invars) - n_scr:] if n_scr else [])
                if not _is_sem(v.aval))
    return ins, outs, scr


def kernel_plan(kind: str, m: int, k: int, f: int,
                params: Optional[KernelParams] = None, *,
                dtype: Any = jnp.float32,
                variant: Optional[str] = None,
                batch: int = 1) -> KernelPlan:
    """Abstractly trace the kernel entry point for (kind, shape, dtype,
    variant) and return its pallas_call grid/block plan.

    Shapes are padded and params clamped exactly as the real call path
    does, so the returned plan is the plan the kernel would launch with.
    ``repro.analysis.contracts`` checks the declared VMEM byte models
    (``KernelParams.vmem_bytes`` and friends) against
    :meth:`KernelPlan.vmem_bytes` — the footprint the BlockSpecs imply.
    """
    if kind not in PLAN_KINDS:
        raise ValueError(f"kind must be one of {PLAN_KINDS}, got {kind!r}")
    if params is None:
        params = DEFAULT_PARAMS
    dt = jnp.dtype(dtype)
    p = clamp_params(m, k, f, params, dtype=dt)
    fp = _round_up(f, p.block_f)
    meta = jax.ShapeDtypeStruct((1,), jnp.int32)
    fn: Any
    args: tuple[Any, ...]
    if kind == "batched":
        tiles = batch * (_round_up(m, p.block_m) // p.block_m)
        kp = _round_up(k, 128)
        tile_map = jax.ShapeDtypeStruct((tiles,), jnp.int32)
        xs = jax.ShapeDtypeStruct((tiles * p.block_m, fp), dt)
        cs = jax.ShapeDtypeStruct((batch, kp, fp), dt)
        cn = jax.ShapeDtypeStruct((batch, 1, kp), jnp.float32)
        var = "smallk"   # the batched template is the smallk epilogue
        fn = functools.partial(_ll.lloyd_step_batched, block_m=p.block_m,
                               block_f=p.block_f, interpret=False)
        args = (tile_map, tile_map, tile_map, xs, cs, cn)
    elif kind == "init":
        # fused k-means++ round: (B, Np/bn) grid, full-F blocks; K and
        # block_k/block_f are not axes of this kernel
        bn = _kpi.clamp_init_block(m, p.block_m)
        np_ = _round_up(m, bn)
        fpl = _round_up(f, 128)
        xs = jax.ShapeDtypeStruct((batch, np_, fpl), jnp.float32)
        xn = jax.ShapeDtypeStruct((batch, np_), jnp.float32)
        cs = jax.ShapeDtypeStruct((batch, 1, fpl), jnp.float32)
        d2 = jax.ShapeDtypeStruct((batch, np_), jnp.float32)
        var = "generic"
        fn = functools.partial(_kpi.kmeanspp_round, block_n=bn,
                               interpret=False)
        args = (xs, xn, cs, d2)
    else:
        mp = _round_up(m, p.block_m)
        kp = _round_up(k, p.block_k)
        xs = jax.ShapeDtypeStruct((mp, fp), dt)
        cs = jax.ShapeDtypeStruct((kp, fp), dt)
        cn = jax.ShapeDtypeStruct((1, kp), jnp.float32)
        if kind in ("assign", "serve"):
            # a serve predict cell launches the assignment kernel at the
            # bucket shape — same plan, serving-specific tile selection
            var = resolve_variant(k, p, variant)
            fn = functools.partial(_da.distance_argmin, block_m=p.block_m,
                                   block_k=p.block_k, block_f=p.block_f,
                                   variant=var, interpret=False)
            args = (xs, cs, cn)
        elif kind == "int8":
            var = resolve_variant(k, p, variant)
            xs = jax.ShapeDtypeStruct((mp, fp), jnp.int8)
            cs = jax.ShapeDtypeStruct((kp, fp), jnp.int8)
            sx = jax.ShapeDtypeStruct((mp, 1), jnp.float32)
            sc = jax.ShapeDtypeStruct((1, kp), jnp.float32)
            fn = functools.partial(_dai.distance_argmin_int8,
                                   block_m=p.block_m, block_k=p.block_k,
                                   block_f=p.block_f, variant=var,
                                   interpret=False)
            args = (xs, cs, sx, sc, cn)
        elif kind == "pruned":
            var = resolve_variant(k, p, variant)
            xn = jax.ShapeDtypeStruct((mp, 1), jnp.float32)
            skip = jax.ShapeDtypeStruct(
                (mp // p.block_m, kp // p.block_k), jnp.int32)
            fn = functools.partial(_llp.lloyd_step_pruned, block_m=p.block_m,
                                   block_k=p.block_k, block_f=p.block_f,
                                   variant=var, interpret=False)
            args = (xs, cs, cn, xn, meta, skip)
        elif kind == "lloyd":
            var = resolve_variant(k, p, variant)
            fn = functools.partial(_ll.lloyd_step, block_m=p.block_m,
                                   block_k=p.block_k, block_f=p.block_f,
                                   variant=var, interpret=False)
            args = (xs, cs, cn, meta)
        else:                     # lloyd_ft: FT template is always generic
            var = "generic"
            inj = jax.ShapeDtypeStruct((_llft.INJ_LEN,), jnp.float32)
            fn = functools.partial(_llft.lloyd_step_ft, block_m=p.block_m,
                                   block_k=p.block_k, block_f=p.block_f,
                                   interpret=False)
            args = (xs, cs, cn, meta, inj)
    closed = jax.make_jaxpr(fn)(*args)
    eqns = list(_walk_pallas_eqns(closed.jaxpr))
    if len(eqns) != 1:
        raise RuntimeError(
            f"expected exactly one pallas_call behind kind={kind!r}, "
            f"found {len(eqns)}")
    ins, outs, scr = _plan_buffers(eqns[0])
    grid = tuple(int(g) for g in eqns[0].params["grid_mapping"].grid)
    return KernelPlan(kind=kind, variant=var, grid=grid,
                      inputs=ins, outputs=outs, scratch=scr)
