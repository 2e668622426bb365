"""General ABFT-protected GEMM kernel (paper §IV applied to plain matmul).

D = X @ Y with the dual-checksum invariant fused into the tile loop, the
same scheme as ``distance_argmin_ft`` but writing the full (corrected)
product — this is the kernel behind ``repro.ft.abft_dense`` (fault-tolerant
projections inside the LM stack) and the paper's standalone ABFT-GEMM
comparison (Wu et al. [41] baseline modernized for asynchronous-copy-era
hardware).

Grid: (M/bm, N/bn, K/bk), contraction innermost, VMEM accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import compiler_params

from repro.kernels.distance_argmin_ft import (INJ_LEN, make_injection,  # noqa: F401 — re-export
                                              no_injection,
                                              threshold_factor)


def _kernel(inj_ref, x_ref, y_ref, out_ref, det_ref,
            acc_ref, col1_ref, col2_ref, row1_ref, row2_ref):
    m_idx = pl.program_id(0)
    n_idx = pl.program_id(1)
    k_idx = pl.program_id(2)
    nk = pl.num_programs(2)
    bm, bn = acc_ref.shape
    bk = x_ref.shape[1]

    @pl.when(jnp.logical_and(n_idx == 0, k_idx == 0))
    def _init_det():
        det_ref[...] = jnp.zeros_like(det_ref)

    @pl.when(k_idx == 0)
    def _init_scratch():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        col1_ref[...] = jnp.zeros_like(col1_ref)
        col2_ref[...] = jnp.zeros_like(col2_ref)
        row1_ref[...] = jnp.zeros_like(row1_ref)
        row2_ref[...] = jnp.zeros_like(row2_ref)

    x = x_ref[...]
    y = y_ref[...]
    acc_ref[...] += jnp.dot(x, y, preferred_element_type=jnp.float32)

    w_m = jax.lax.broadcasted_iota(jnp.float32, (bm, 1), 0) + 1.0
    w_n = jax.lax.broadcasted_iota(jnp.float32, (1, bn), 1) + 1.0
    e1x = jnp.sum(x, axis=0, keepdims=True)                 # (1, bk)
    e2x = jnp.sum(w_m * x, axis=0, keepdims=True)           # (1, bk)
    ye1 = jnp.sum(y, axis=1, keepdims=True)                 # (bk, 1)
    ye2 = jnp.sum(y * w_n, axis=1, keepdims=True)           # (bk, 1)
    col1_ref[...] += jnp.dot(e1x, y, preferred_element_type=jnp.float32)
    col2_ref[...] += jnp.dot(e2x, y, preferred_element_type=jnp.float32)
    row1_ref[...] += jnp.dot(x, ye1, preferred_element_type=jnp.float32)
    row2_ref[...] += jnp.dot(x, ye2, preferred_element_type=jnp.float32)

    hit = jnp.logical_and(
        inj_ref[0] > 0,
        jnp.logical_and(
            jnp.logical_and(m_idx == inj_ref[1], n_idx == inj_ref[2]),
            k_idx == inj_ref[3]))

    @pl.when(hit)
    def _inject():
        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        mask = jnp.logical_and(rows == inj_ref[4], cols == inj_ref[5])
        delta = jax.lax.bitcast_convert_type(inj_ref[6], jnp.float32)
        acc_ref[...] += jnp.where(mask, delta, 0.0)

    @pl.when(k_idx == nk - 1)
    def _verify_and_write():
        acc = acc_ref[...]
        obs_col1 = jnp.sum(acc, axis=0, keepdims=True)
        obs_col2 = jnp.sum(w_m * acc, axis=0, keepdims=True)
        obs_row1 = jnp.sum(acc, axis=1, keepdims=True)
        obs_row2 = jnp.sum(w_n * acc, axis=1, keepdims=True)

        res_col1 = obs_col1 - col1_ref[...]
        res_col2 = obs_col2 - col2_ref[...]
        res_row1 = obs_row1 - row1_ref[...]
        res_row2 = obs_row2 - row2_ref[...]

        # static grid -> trace-time constant factor; eps is dtype-aware
        # (input rounding of the main accumulator for bf16/fp16 tiles).
        # Scale from the expected checksums (clean invariant side), never
        # the possibly-corrupted accumulator — see distance_argmin_ft.
        scale = jnp.maximum(jnp.maximum(jnp.max(jnp.abs(col1_ref[...])),
                                        jnp.max(jnp.abs(row1_ref[...]))), 1.0)
        thr = jnp.float32(threshold_factor(nk * bk, x_ref.dtype)) * scale

        detected = jnp.logical_or(jnp.max(jnp.abs(res_col1)) > thr,
                                  jnp.max(jnp.abs(res_row1)) > thr)

        j = jnp.argmax(jnp.abs(res_col1[0, :])).astype(jnp.int32)
        delta_col = res_col1[0, j]
        i_direct = jnp.argmax(jnp.abs(res_row1[:, 0])).astype(jnp.int32)
        safe = jnp.where(delta_col == 0.0, 1.0, delta_col)
        i_ratio = (jnp.round(res_col2[0, j] / safe) - 1.0).astype(jnp.int32)
        use_ratio = jnp.abs(delta_col) > thr
        i = jnp.clip(jnp.where(use_ratio, i_ratio, i_direct), 0, bm - 1)
        delta_row = res_row1[i, 0]
        delta = jnp.where(jnp.abs(delta_col) > jnp.abs(delta_row),
                          delta_col, delta_row)
        safe_r = jnp.where(delta_row == 0.0, 1.0, delta_row)
        j_ratio = (jnp.round(res_row2[i, 0] / safe_r) - 1.0).astype(jnp.int32)
        j = jnp.where(use_ratio, j, jnp.clip(j_ratio, 0, bn - 1))

        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        corrected = acc - jnp.where(
            jnp.logical_and(rows == i, cols == j), delta, 0.0)
        out_ref[...] = jnp.where(detected, corrected, acc).astype(out_ref.dtype)
        det_ref[...] += detected.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"))
def matmul_abft(
    x: jax.Array,
    y: jax.Array,
    inj: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """ABFT GEMM. Returns (D corrected (M, N), det counts (m_tiles, 1))."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    grid = (m // block_m, n // block_n, k // block_k)

    kernel = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_m, block_k), lambda i, j, t: (i, t)),
            pl.BlockSpec((block_k, block_n), lambda i, j, t: (t, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j, t: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j, t: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m // block_m, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((1, block_n), jnp.float32),
            pltpu.VMEM((1, block_n), jnp.float32),
            pltpu.VMEM((block_m, 1), jnp.float32),
            pltpu.VMEM((block_m, 1), jnp.float32),
        ],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )
    return kernel(inj, x, y)
