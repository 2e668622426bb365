"""Fused distance + nearest-centroid kernel (paper §III, Fig. 4 — TPU-native).

Computes, for samples X (M, F) and centroids C (K, F):

    argmin_j  ||x_i - c_j||^2   and the winning partial distance
    d_ij = ||c_j||^2 - 2 x_i . c_j      (||x_i||^2 is row-constant)

in a single pass: the GEMM (-2 X C^T), the paper's fused epilogue (thread /
threadblock min-reduction) and the cross-threadblock broadcast are all
folded into one Pallas kernel.

TPU adaptation (see docs/kernels.md):
  * the contraction (feature) axis is the innermost grid dimension with a
    VMEM scratch accumulator — the analogue of the paper's cp.async k-loop;
    Mosaic generates the HBM->VMEM double-buffered pipeline from BlockSpecs;
  * grid steps on a TensorCore are sequential, so the running min/argmin is
    accumulated directly in the revisited output block — the paper's
    lock-vector broadcast degenerates to a data dependence;
  * tiles are MXU-aligned: block_m, block_k multiples of (8, 128) lanes.

Grid: (M/bm, K/bk, F/bf), iterated row-major (feature axis fastest).

Template family (paper §III-B): two variants share this module —

  * ``"generic"`` — the grid above; min/argmin accumulated in the revisited
    output block across centroid tiles;
  * ``"smallk"``  — when padded K fits a single ``block_k`` tile the
    centroid grid dimension is dropped entirely (grid (M/bm, F/bf)): the
    min/argmin is computed once from the VMEM-resident accumulator and
    written directly, with no revisited-output compare/accumulate machinery.

Input dtype is a template axis too: X and C tiles may be f32, bf16 or fp16;
the MXU accumulator, norms and outputs are always f32
(``preferred_element_type``), matching the paper's f32-accumulate GEMMs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import compiler_params, mxu_dot

# Initial value of the running minimum: +float32 max, so the first observed
# distance always wins the compare.
MIN_INIT = float(jnp.finfo(jnp.float32).max)


def tile_min_argmin(acc, cn, base_col):
    """Min/argmin of one (bm, bk) distance tile from its f32 accumulator:
    d = cn - 2*acc, first-min (lowest-index) tie-break, ``base_col`` added
    to globalize the column index. The single definition of the epilogue
    semantics — every template variant (generic/smallk, plain/FT, with or
    without the fused update) must reduce through here so the bit-identity
    between variants holds by construction."""
    d = cn - 2.0 * acc
    local_min = jnp.min(d, axis=1, keepdims=True)
    cols = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    local_arg = jnp.min(
        jnp.where(d == local_min, cols, jnp.iinfo(jnp.int32).max),
        axis=1, keepdims=True) + base_col
    return local_min, local_arg


def fold_min(mind_ref, argmin_ref, local_min, local_arg):
    """Accumulate a tile's (min, argmin) into the revisited output block.
    Strict compare: the earlier centroid tile wins ties."""
    cur = mind_ref[...]
    take = local_min < cur
    mind_ref[...] = jnp.where(take, local_min, cur)
    argmin_ref[...] = jnp.where(take, local_arg, argmin_ref[...])


def _kernel(x_ref, c_ref, cn_ref, mind_ref, argmin_ref, acc_ref):
    """One (bm, bk) distance tile, accumulated over feature steps.

    x_ref   : (bm, bf)   sample tile
    c_ref   : (bk, bf)   centroid tile
    cn_ref  : (1, bk)    centroid squared norms (+inf for padded slots)
    mind_ref: (bm, 1)    running minimum of d_ij  (output, revisited)
    argmin_ref: (bm, 1)  running argmin           (output, revisited)
    acc_ref : (bm, bk)   VMEM scratch accumulator for X C^T
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

    # MXU tile product, f32 accumulation.
    acc_ref[...] += mxu_dot(x_ref[...], c_ref[...], (1, 1))

    @pl.when(f_idx == nf - 1)
    def _epilogue():
        local_min, local_arg = tile_min_argmin(
            acc_ref[...], cn_ref[...], c_idx * acc_ref.shape[1])
        fold_min(mind_ref, argmin_ref, local_min, local_arg)


def _kernel_smallk(x_ref, c_ref, cn_ref, mind_ref, argmin_ref, acc_ref):
    """Small-K fast path: the whole centroid set is one (bk, bf) tile, so
    the centroid grid dimension is gone — grid (M/bm, F/bf). The min/argmin
    is computed once from the VMEM-resident accumulator and written
    directly; no init-to-MIN_INIT, no revisited-output compare."""
    f_idx = pl.program_id(1)
    nf = pl.num_programs(1)

    @pl.when(f_idx == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(x_ref[...], c_ref[...], (1, 1))

    @pl.when(f_idx == nf - 1)
    def _epilogue():
        local_min, local_arg = tile_min_argmin(acc_ref[...], cn_ref[...], 0)
        mind_ref[...] = local_min       # single visit: direct write
        argmin_ref[...] = local_arg


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_f", "variant", "interpret"))
def distance_argmin(
    x: jax.Array,
    c: jax.Array,
    cn: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 128,
    block_f: int = 512,
    variant: str = "generic",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw kernel entry. Shapes must be pre-padded to the block grid.

    x (M, F) samples, c (K, F) centroids, cn (1, K) centroid sq-norms with
    +inf in padded centroid slots; any of f32/bf16/fp16 for x and c (cn is
    always f32). ``variant`` selects the template: ``"generic"`` or
    ``"smallk"`` (requires padded K == block_k). Returns
    (min_d (M, 1) f32, argmin (M, 1) i32).
    """
    m, f = x.shape
    k = c.shape[0]
    assert m % block_m == 0 and k % block_k == 0 and f % block_f == 0, (
        f"unpadded shapes {(m, k, f)} vs blocks {(block_m, block_k, block_f)}")

    out_specs_3d = lambda: [pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
                            pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0))]
    out_shape = [
        jax.ShapeDtypeStruct((m, 1), jnp.float32),
        jax.ShapeDtypeStruct((m, 1), jnp.int32),
    ]
    scratch = [pltpu.VMEM((block_m, block_k), jnp.float32)]

    if variant == "smallk":
        assert k == block_k, (
            f"smallk variant needs padded K ({k}) == block_k ({block_k})")
        kernel = pl.pallas_call(
            _kernel_smallk,
            grid=(m // block_m, f // block_f),
            in_specs=[
                pl.BlockSpec((block_m, block_f), lambda i, t: (i, t)),
                pl.BlockSpec((block_k, block_f), lambda i, t: (0, t)),
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
        return kernel(x, c, cn)

    assert variant == "generic", f"unknown kernel variant {variant!r}"
    kernel = pl.pallas_call(
        _kernel,
        grid=(m // block_m, k // block_k, f // block_f),
        in_specs=[
            pl.BlockSpec((block_m, block_f), lambda i, j, t: (i, t)),
            pl.BlockSpec((block_k, block_f), lambda i, j, t: (j, t)),
            pl.BlockSpec((1, block_k), lambda i, j, t: (0, j)),
        ],
        out_specs=out_specs_3d(),
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )
    return kernel(x, c, cn)
