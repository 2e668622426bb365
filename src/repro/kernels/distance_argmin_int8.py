"""int8 distance + nearest-centroid template (paper §III-B, one dtype notch
past the paper's fp16 floor).

The distance GEMM is the one place the template family can still shrink
its bytes and double its MXU rate: X and C are quantized **per row** with
the symmetric scheme of :mod:`repro.dist.compression` (scale =
max|row|/127, clipped away from zero; values rounded into [-127, 127]),
the tile product runs int8 x int8 -> int32 on the MXU, and the epilogue
corrects the scales in f32:

    d_ij  =  ||c_j||^2  -  2 * sx_i * sc_j * acc_ij

where ``acc`` is the exact int32 dot of the quantized rows and ``sx``/
``sc`` are the per-row scales. Two exactness properties follow:

  * the ``||c_j||^2`` term is computed from the *unquantized* centroids
    (exact, like the f32 template's) and ``||x_i||^2`` is row-constant and
    dropped from the argmin exactly as in ``distance_argmin`` — the only
    approximation lives in the cross term;
  * on *quantization-safe* data (integer entries in [-127, 127] with a
    +-127 entry per row, so every scale is exactly 1.0) the int32
    accumulator holds the same integers the f32 template accumulates, the
    scale corrections multiply by 1.0, and the argmin is **bit-exact**
    against the f32 template. That is the parity contract
    ``tests/test_int8.py`` pins; on float data the relative distance error
    is bounded by the quantization step (~1/127 per operand).

Epilogue semantics (first-min tie-break, ``MIN_INIT``) are shared with
every other template via ``tile_min_argmin`` — the scale correction is
applied to the accumulator *before* the shared reduction, so the int8
template cannot drift from the family's tie-break rules.

Grid and variants mirror :mod:`distance_argmin`: ``"generic"``
(M/bm, K/bk, F/bf) with the revisited-output min/argmin, and ``"smallk"``
(M/bm, F/bf) when padded K fits one centroid tile. The accumulator
scratch is int32; scales and outputs are f32/i32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import compiler_params
from repro.kernels.distance_argmin import MIN_INIT, fold_min, tile_min_argmin


def _scaled_acc(acc_ref, sx_ref, sc_ref):
    """Scale-correct one int32 accumulator tile into the f32 cross term:
    sx_i * sc_j * acc_ij. Exact when both scales are 1.0 (quantization-safe
    data), since the int32 values fit f32 for any feasible tile depth."""
    return sx_ref[...] * (acc_ref[...].astype(jnp.float32) * sc_ref[...])


def _kernel_int8(x_ref, c_ref, sx_ref, sc_ref, cn_ref,
                 mind_ref, argmin_ref, acc_ref):
    """One (bm, bk) int8 distance tile, accumulated over feature steps.

    x_ref   : (bm, bf) i8   quantized sample tile
    c_ref   : (bk, bf) i8   quantized centroid tile
    sx_ref  : (bm, 1)  f32  per-row sample scales
    sc_ref  : (1, bk)  f32  per-row centroid scales
    cn_ref  : (1, bk)  f32  exact centroid squared norms (+inf padded)
    mind_ref: (bm, 1)  f32  running minimum of d_ij  (output, revisited)
    argmin_ref: (bm, 1) i32 running argmin           (output, revisited)
    acc_ref : (bm, bk) i32  VMEM scratch accumulator for Xq Cq^T
    """
    c_idx = pl.program_id(1)
    f_idx = pl.program_id(2)
    nf = pl.num_programs(2)

    @pl.when(jnp.logical_and(c_idx == 0, f_idx == 0))
    def _init_outputs():
        mind_ref[...] = jnp.full_like(mind_ref, MIN_INIT)
        argmin_ref[...] = jnp.zeros_like(argmin_ref)

    @pl.when(f_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 MXU tile product, exact int32 accumulation.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], c_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(f_idx == nf - 1)
    def _epilogue():
        local_min, local_arg = tile_min_argmin(
            _scaled_acc(acc_ref, sx_ref, sc_ref), cn_ref[...],
            c_idx * acc_ref.shape[1])
        fold_min(mind_ref, argmin_ref, local_min, local_arg)


def _kernel_int8_smallk(x_ref, c_ref, sx_ref, sc_ref, cn_ref,
                        mind_ref, argmin_ref, acc_ref):
    """Small-K fast path: one centroid tile, grid (M/bm, F/bf); min/argmin
    written directly from the scale-corrected resident accumulator."""
    f_idx = pl.program_id(1)
    nf = pl.num_programs(1)

    @pl.when(f_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], c_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(f_idx == nf - 1)
    def _epilogue():
        local_min, local_arg = tile_min_argmin(
            _scaled_acc(acc_ref, sx_ref, sc_ref), cn_ref[...], 0)
        mind_ref[...] = local_min       # single visit: direct write
        argmin_ref[...] = local_arg


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_f", "variant", "interpret"))
def distance_argmin_int8(
    x: jax.Array,
    c: jax.Array,
    sx: jax.Array,
    sc: jax.Array,
    cn: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 128,
    block_f: int = 512,
    variant: str = "generic",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw int8 kernel entry. Shapes must be pre-padded to the block grid.

    x (M, F) int8 quantized samples, c (K, F) int8 quantized centroids,
    sx (M, 1) f32 per-row sample scales, sc (1, K) f32 per-row centroid
    scales, cn (1, K) f32 *exact* centroid sq-norms (from the unquantized
    centroids) with +inf in padded slots. ``variant`` selects the template:
    ``"generic"`` or ``"smallk"`` (requires padded K == block_k). Returns
    (min_d (M, 1) f32, argmin (M, 1) i32) under the same partial-distance
    contract as ``distance_argmin`` (add ``||x||^2`` for true distances).
    """
    m, f = x.shape
    k = c.shape[0]
    assert x.dtype == jnp.int8 and c.dtype == jnp.int8, (
        f"int8 template fed {x.dtype}/{c.dtype} tiles — quantize at the "
        f"plan boundary (ops.plan_data_int8)")
    assert m % block_m == 0 and k % block_k == 0 and f % block_f == 0, (
        f"unpadded shapes {(m, k, f)} vs blocks {(block_m, block_k, block_f)}")

    out_shape = [
        jax.ShapeDtypeStruct((m, 1), jnp.float32),
        jax.ShapeDtypeStruct((m, 1), jnp.int32),
    ]
    scratch = [pltpu.VMEM((block_m, block_k), jnp.int32)]

    if variant == "smallk":
        assert k == block_k, (
            f"smallk variant needs padded K ({k}) == block_k ({block_k})")
        kernel = pl.pallas_call(
            _kernel_int8_smallk,
            grid=(m // block_m, f // block_f),
            in_specs=[
                pl.BlockSpec((block_m, block_f), lambda i, t: (i, t)),
                pl.BlockSpec((block_k, block_f), lambda i, t: (0, t)),
                pl.BlockSpec((block_m, 1), lambda i, t: (i, 0)),
                pl.BlockSpec((1, block_k), lambda i, t: (0, 0)),
                pl.BlockSpec((1, block_k), lambda i, t: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_m, 1), lambda i, t: (i, 0)),
                pl.BlockSpec((block_m, 1), lambda i, t: (i, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=compiler_params("parallel", "arbitrary"),
            interpret=interpret,
        )
        return kernel(x, c, sx, sc, cn)

    assert variant == "generic", f"unknown kernel variant {variant!r}"
    kernel = pl.pallas_call(
        _kernel_int8,
        grid=(m // block_m, k // block_k, f // block_f),
        in_specs=[
            pl.BlockSpec((block_m, block_f), lambda i, j, t: (i, t)),
            pl.BlockSpec((block_k, block_f), lambda i, j, t: (j, t)),
            pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
            pl.BlockSpec((1, block_k), lambda i, j, t: (0, j)),
            pl.BlockSpec((1, block_k), lambda i, j, t: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )
    return kernel(x, c, sx, sc, cn)
