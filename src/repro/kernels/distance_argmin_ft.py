"""Fault-tolerant fused distance + argmin kernel (paper §IV, Fig. 6 — TPU).

Extends ``distance_argmin`` with the paper's dual-checksum ABFT, fully fused
into the tile loop:

  * while streaming feature tiles, the *expected* checksums of the cross
    product D = X C^T are accumulated from the inputs already resident in
    VMEM (never re-read from HBM — the TPU analogue of the paper's "no
    register reuse after cp.async" constraint):
        col1 += (e1^T X_t) C_t^T        col2 += (e2^T X_t) C_t^T
        row1 += X_t (C_t^T e1)          row2 += X_t (C_t^T e2)
    e1 = ones, e2 = [1..b] (location encoding), at tile-local indices;
  * at the verification interval (the last feature step of each (m, k)
    tile — the paper's ``k % 256 == 0`` boundary maps to the tile
    boundary on TPU), the observed e1 checksums of the accumulator are
    compared on every tile; only a tile whose residual exceeds the
    threshold goes on to the e2 residuals, which *locate* the corrupted
    element via the e2/e1 ratio, and the kernel corrects it in place. The
    fused min/argmin epilogue then runs on the *corrected* tile;
  * an optional injection descriptor adds a delta into the accumulator
    mid-stream (a simulated SEU in the MXU output), exercising the whole
    detect->locate->correct path inside one kernel launch.

Checksum arithmetic is O((bm + bk) * bf) per tile against the tile's
O(bm * bk * bf) MACs — e.g. ~1.2 % extra FLOPs at (256, 128) tiles.

X and C tiles may be f32, bf16 or fp16 (the dtype axis of the §III-B
template family); the main product accumulates in f32 and the checksums are
computed from f32 casts of the resident tiles. The detection threshold is
dtype-aware (``checksum.threshold_factor``): on backends that round the
main product's partial terms to the *input* precision, a clean bf16/fp16
tile's residual sits at bf16/fp16 rounding level, so the threshold scales
with ``max(eps_input, eps_f32)`` instead of assuming f32 everywhere. This
FT template keeps the generic (revisited-output) grid for all K: its
checksum scratch already holds everything VMEM-resident, so the small-K
fast path buys nothing here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import compiler_params, mxu_dot

from repro.kernels.distance_argmin import (MIN_INIT, fold_min,
                                           tile_min_argmin)


def threshold_factor(n: int, input_dtype) -> float:
    """Dtype-aware detection-threshold factor (lazy import: repro.core's
    package init imports the api layer, which imports this package)."""
    from repro.core.checksum import threshold_factor as _tf
    return _tf(n, input_dtype)

# Injection descriptor layout (SMEM scalars):
# [enabled, m_tile, c_tile, f_tile, row_in_tile, col_in_tile] + delta (f32).
INJ_LEN = 8
# One protected interval: the distance GEMM (detect+locate+correct in
# kernel). The registry's ``protected_intervals`` must agree with this.
INJ_SLOTS = 1


_INT_MAX = jnp.iinfo(jnp.int32).max


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _first_argmax(v, axis):
    """First index of the maximum of a (1, n) / (n, 1) vector along
    ``axis``, as a (1, 1) vector: jnp.argmax's tie rule, built from the
    min/max reductions Mosaic lowers."""
    mx = jnp.max(v, axis=axis, keepdims=True)
    return jnp.min(jnp.where(v == mx, _iota(v.shape, axis), _INT_MAX),
                   axis=axis, keepdims=True)


def _pick(v, axis, i):
    """Entry ``i`` of a (1, n) / (n, 1) vector along ``axis``, as a (1, 1)
    vector. A masked reduction: Mosaic lowers no dynamic vector slice."""
    return jnp.max(jnp.where(_iota(v.shape, axis) == i, v, -jnp.inf),
                   axis=axis, keepdims=True)


def accumulate_checksums(x, c, col1_ref, col2_ref, row1_ref, row2_ref):
    """Add one feature step's expected checksums of D = X C^T, from the
    VMEM-resident tiles (paper lines 15-24). Checksums run in f32
    regardless of the input dtype; e1 = ones, e2 = [1..b] at tile-local
    indices."""
    bm, bk = x.shape[0], c.shape[0]
    xf = x.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    w_m = (_iota((bm, 1), 0) + 1).astype(jnp.float32)        # e2 rows
    w_kc = (_iota((bk, 1), 0) + 1).astype(jnp.float32)       # e2 cols
    e1x = jnp.sum(xf, axis=0, keepdims=True)                 # (1, bf)
    e2x = jnp.sum(w_m * xf, axis=0, keepdims=True)           # (1, bf)
    ce1 = jnp.sum(cf, axis=0, keepdims=True)                 # (1, bf)
    ce2 = jnp.sum(w_kc * cf, axis=0, keepdims=True)          # (1, bf)
    col1_ref[...] += mxu_dot(e1x, cf, (1, 1))                # (1, bk)
    col2_ref[...] += mxu_dot(e2x, cf, (1, 1))                # (1, bk)
    row1_ref[...] += mxu_dot(xf, ce1, (1, 1))                # (bm, 1)
    row2_ref[...] += mxu_dot(xf, ce2, (1, 1))                # (bm, 1)


def _f32_from_bits(bits, shape):
    """An SMEM int32 scalar reinterpreted as f32, broadcast to ``shape``.
    Mosaic bitcasts vectors only, so the broadcast comes first."""
    return jax.lax.bitcast_convert_type(jnp.full(shape, bits, jnp.int32),
                                        jnp.float32)


def inject_distance(acc_ref, inj_ref, m_idx, c_idx, f_idx):
    """Simulated SEU in the accumulator (a compute-unit error): add the
    descriptor's delta at (row, col) of tile (m_tile, c_tile) at feature
    step f_tile. Slots [0..6] of the descriptor, in both FT kernels."""
    hit = jnp.logical_and(
        inj_ref[0] > 0,
        jnp.logical_and(
            jnp.logical_and(m_idx == inj_ref[1], c_idx == inj_ref[2]),
            f_idx == inj_ref[3]))

    @pl.when(hit)
    def _inject():
        shape = acc_ref.shape
        mask = jnp.logical_and(_iota(shape, 0) == inj_ref[4],
                               _iota(shape, 1) == inj_ref[5])
        delta = _f32_from_bits(inj_ref[6], shape)
        acc_ref[...] += jnp.where(mask, delta, 0.0)


def detect(acc, col1, row1, factor):
    """Detection at the verification interval of one (bm, bk) accumulator
    tile: the e1 column and row residuals against the dtype-aware
    threshold. Returns (res_col1 (1, bk), res_row1 (bm, 1), thr (1, 1),
    flag (1, 1) int32, 1 where a residual exceeds the threshold).

    ``factor`` is the dtype-aware threshold factor (a trace-time constant:
    the grid is static). The magnitude scale comes from the *expected*
    checksums — the invariant side, computed from clean inputs — never
    from the possibly-corrupted accumulator: a corrupted-side scale lets a
    large delta inflate its own threshold past itself whenever the factor
    exceeds 1 (bf16 at wide tiles), self-masking exactly the errors worth
    catching. The flag is int32 because Mosaic branches on an int32 (1, 1)
    vector's element, not a bool one's.
    """
    res_col1 = jnp.sum(acc, axis=0, keepdims=True) - col1           # (1, bk)
    res_row1 = jnp.sum(acc, axis=1, keepdims=True) - row1           # (bm, 1)
    scale = jnp.maximum(
        jnp.maximum(jnp.max(jnp.abs(col1), keepdims=True),
                    jnp.max(jnp.abs(row1), keepdims=True)), 1.0)    # (1, 1)
    thr = jnp.float32(factor) * scale
    detected = jnp.logical_or(jnp.max(jnp.abs(res_col1), keepdims=True) > thr,
                              jnp.max(jnp.abs(res_row1), keepdims=True) > thr)
    return res_col1, res_row1, thr, detected.astype(jnp.int32)


def locate_and_correct(acc, res_col1, res_row1, col2, row2, thr):
    """Location and correction of the one corrupted element of a tile that
    ``detect`` flagged. Returns the corrected tile.

    Argmax |column residual| gives j and delta; the e2/e1 ratio of the
    residuals gives i (and vice versa as fallback). The e2 residuals serve
    location alone, so they are computed here, on a flagged tile only.
    """
    bm, bk = acc.shape
    w_m = (_iota((bm, 1), 0) + 1).astype(jnp.float32)
    w_k = (_iota((1, bk), 1) + 1).astype(jnp.float32)
    res_col2 = jnp.sum(w_m * acc, axis=0, keepdims=True) - col2     # (1, bk)
    res_row2 = jnp.sum(w_k * acc, axis=1, keepdims=True) - row2     # (bm, 1)
    abs_col1, abs_row1 = jnp.abs(res_col1), jnp.abs(res_row1)

    j = _first_argmax(abs_col1, 1)
    delta_col = _pick(res_col1, 1, j)
    i_direct = _first_argmax(abs_row1, 0)
    safe = jnp.where(delta_col == 0.0, 1.0, delta_col)
    i_ratio = (jnp.round(_pick(res_col2, 1, j) / safe) - 1.0).astype(jnp.int32)
    use_ratio = jnp.abs(delta_col) > thr
    i = jnp.clip(jnp.where(use_ratio, i_ratio, i_direct), 0, bm - 1)
    delta_row = _pick(res_row1, 0, i)
    delta = jnp.where(jnp.abs(delta_col) > jnp.abs(delta_row),
                      delta_col, delta_row)
    safe_r = jnp.where(delta_row == 0.0, 1.0, delta_row)
    j_ratio = (jnp.round(_pick(res_row2, 0, i) / safe_r) - 1.0
               ).astype(jnp.int32)
    j = jnp.where(use_ratio, j, jnp.clip(j_ratio, 0, bk - 1))

    # Mosaic broadcasts a (1, 1) vector along one tile axis at a time, so
    # the correction is built as a (bm, 1) column, then spread over lanes.
    # Subtracting 0.0 leaves every other element bit-unchanged.
    on_row = _iota((bm, 1), 0) == i                                  # (bm, 1)
    on_col = _iota((1, bk), 1) == j                                  # (1, bk)
    corr = jnp.where(on_col, jnp.where(on_row, delta, 0.0), 0.0)     # (bm, bk)
    return acc - corr


def verify_and_reduce(acc_ref, col1_ref, col2_ref, row1_ref, row2_ref,
                      cn_ref, mind_ref, argmin_ref, det_ref, base_col,
                      factor):
    """Verification interval of one (bm, bk) accumulator tile, shared by
    both FT kernels: detect on every tile, locate and correct in place
    only on a flagged one (at most one tile in a detection interval holds
    an error, so the common path skips the e2 residuals and the
    correction), then the min/argmin epilogue on the corrected tile,
    folded into the running minimum. Adds the flag to ``det_ref``.

    Each step reads the tile from ``acc_ref`` afresh: a tile loaded once
    and held across the branch does not fit the vector registers, and its
    spills cost more bundles than the reloads (compile for a v5e)."""
    res_col1, res_row1, thr, flag = detect(
        acc_ref[...], col1_ref[...], row1_ref[...], factor)
    det_ref[0] += flag

    @pl.when(flag[0, 0] > 0)
    def _locate_and_correct():
        acc_ref[...] = locate_and_correct(acc_ref[...], res_col1, res_row1,
                                          col2_ref[...], row2_ref[...], thr)

    local_min, local_arg = tile_min_argmin(acc_ref[...], cn_ref[...],
                                           base_col)
    fold_min(mind_ref, argmin_ref, local_min, local_arg)


def _kernel(inj_ref, x_ref, c_ref, cn_ref,
            mind_ref, argmin_ref, det_ref,
            acc_ref, col1_ref, col2_ref, row1_ref, row2_ref):
    m_idx = pl.program_id(0)
    c_idx = pl.program_id(1)
    f_idx = pl.program_id(2)
    nf = pl.num_programs(2)
    bk = acc_ref.shape[1]
    bf = x_ref.shape[1]

    @pl.when(jnp.logical_and(c_idx == 0, f_idx == 0))
    def _init_outputs():
        # running minimum starts at +float32 max so any distance wins
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


def no_injection() -> jax.Array:
    return jnp.zeros((INJ_LEN,), jnp.int32)


def make_injection(m_tile: int, c_tile: int, f_tile: int,
                   row: int, col: int, delta: float) -> jax.Array:
    """Build an injection descriptor (delta carried bit-cast in an int32)."""
    dbits = jnp.asarray(delta, jnp.float32).view(jnp.int32)
    return jnp.array([1, m_tile, c_tile, f_tile, row, col, dbits, 0],
                     jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_f", "interpret"))
def distance_argmin_ft(
    x: jax.Array,
    c: jax.Array,
    cn: jax.Array,
    inj: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 128,
    block_f: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """FT fused kernel. Returns (min_d (M,1), argmin (M,1), det (m_tiles,1)).

    det[i] counts corrected errors in row-tile i; sum for the campaign total.
    """
    m, f = x.shape
    k = c.shape[0]
    assert m % block_m == 0 and k % block_k == 0 and f % block_f == 0
    grid = (m // block_m, k // block_k, f // block_f)

    kernel = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_m, block_f), lambda i, j, t: (i, t)),
            pl.BlockSpec((block_k, block_f), lambda i, j, t: (j, t)),
            pl.BlockSpec((1, block_k), lambda i, j, t: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
            # unit axis: the (1, 1, 1) block equals the array's last two
            # dims, as Mosaic's block rule requires; squeezed after the call
            pl.BlockSpec((1, 1, 1), lambda i, j, t: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
            jax.ShapeDtypeStruct((m // block_m, 1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, block_k), jnp.float32),
            pltpu.VMEM((1, block_k), jnp.float32),
            pltpu.VMEM((1, block_k), jnp.float32),
            pltpu.VMEM((block_m, 1), jnp.float32),
            pltpu.VMEM((block_m, 1), jnp.float32),
        ],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )
    mind, am, det = kernel(inj, x, c, cn)
    return mind, am, det[:, 0]
