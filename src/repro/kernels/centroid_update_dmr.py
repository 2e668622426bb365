"""DMR-fused centroid-update kernel (paper §I/§IV: "DMR protects the
memory-bound update phase for <1%").

The paper's argument only holds if the duplicated arithmetic shares ONE
load of the samples: at the XLA level two calls to the update read HBM
twice (2x cost for a memory-bound op). This kernel makes the claim
structural on TPU: each (bm, F) sample tile is staged into VMEM once and
accumulated into TWO independent (K, F) sum buffers + count buffers; a
mismatch between replicas flags an SEU in the accumulation arithmetic.

Grid: (M/bm,) — sequential on a TensorCore, outputs revisited.
Outputs: sums (K, F), counts (1, K), shadow sums/counts, mismatch flag.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._mosaic import compiler_params


def _kernel(x_ref, a_ref, sums_ref, counts_ref, sums2_ref, counts2_ref,
            bad_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        sums2_ref[...] = jnp.zeros_like(sums2_ref)
        counts2_ref[...] = jnp.zeros_like(counts2_ref)
        bad_ref[...] = jnp.zeros_like(bad_ref)

    x = x_ref[...]                                   # (bm, F) one VMEM load
    a = a_ref[...]                                   # (bm, 1) assignments
    k = sums_ref.shape[0]
    onehot = (a == jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], k), 1)).astype(jnp.float32)   # (bm, K)

    # primary replica
    part = jax.lax.dot_general(onehot, x, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    cnt = jnp.sum(onehot, axis=0, keepdims=True)     # (1, K)
    sums_ref[...] += part
    counts_ref[...] += cnt

    # shadow replica: same VMEM-resident tile, independent arithmetic
    # (reversed accumulation order so an MXU/VPU SEU can't hit both
    # identically; optimization_barrier-free because the buffers differ).
    part2 = jax.lax.dot_general(onehot[::-1], x[::-1],
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    cnt2 = jnp.sum(onehot[::-1], axis=0, keepdims=True)
    sums2_ref[...] += part2
    counts2_ref[...] += cnt2

    nf = pl.num_programs(0)

    @pl.when(i == nf - 1)
    def _compare():
        diff = jnp.max(jnp.abs(sums_ref[...] - sums2_ref[...]))
        dcnt = jnp.max(jnp.abs(counts_ref[...] - counts2_ref[...]))
        tol = 1e-4 * jnp.maximum(jnp.max(jnp.abs(sums_ref[...])), 1.0)
        mismatch = jnp.logical_or(diff > tol, dcnt > 0)
        bad_ref[...] = mismatch.astype(jnp.int32).reshape(1, 1)


@functools.partial(jax.jit, static_argnames=("k", "block_m", "interpret"))
def centroid_update_dmr(x: jax.Array, assign: jax.Array, k: int,
                        *, block_m: int = 1024,
                        interpret: bool = False):
    """Per-cluster sums/counts with in-kernel DMR.

    x (M, F) f32, assign (M,) int32. Returns (sums (K,F), counts (K,),
    mismatch flag). M must be padded to block_m with assign = -1 (padded
    rows match no cluster).
    """
    m, f = x.shape
    assert m % block_m == 0
    grid = (m // block_m,)
    sums, counts, sums2, counts2, bad = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, f), lambda i: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, f), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((k, f), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, f), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((k, f), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
    )(x, assign[:, None].astype(jnp.int32))
    return sums, counts[0], bad[0, 0]
