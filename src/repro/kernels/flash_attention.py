"""Flash-attention Pallas kernel (beyond-paper §Perf direction).

The roofline (§EXPERIMENTS) shows every train/prefill cell memory-bound,
dominated by materialized f32 score chunks (B·KV·G·Sq·Skv per layer).
Online-softmax attention never materializes the scores to HBM: per
(query-block, kv-block) tile the running max/denominator/accumulator live
in VMEM — the standard fix, here in the same BlockSpec style as the
k-means kernels so it drops into `repro.models.attention` on TPU.

Supports causal + local-window masking via absolute key positions (same
mask contract as models/attention.attend). GQA: q arrives grouped
(B, KV, G·bq?, ...) — this kernel takes q (B, H, Sq, hd), k/v
(B, KV, Skv, hd) with H = KV·G and maps h -> kv = h // G.

Grid: (B·H, Sq/bq, Skv/bk) — kv axis innermost (sequential), carrying
(m, l, acc) in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import compiler_params

NEG = -1e30


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, causal: bool, window: int):
    kv_idx = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (bq, hd)
    k = k_ref[0]                                   # (bk, hd)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    qpos = qpos_ref[...]                           # (bq, 1) int32
    kpos = kpos_ref[...]                           # (1, bk) int32
    mask = kpos >= 0
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]                            # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                         # (bq, bk)
    scale = jnp.exp(m_prev - m_new)                # (bq, 1)
    l_ref[...] = l_ref[...] * scale + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * scale + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv_idx == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "interpret"))
def flash_attention(q, k, v, q_positions, kv_positions, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False):
    """q (B, H, Sq, hd); k, v (B, KV, Skv, hd); positions absolute int32.

    Returns (B, H, Sq, hd). Shapes must be pre-padded to the blocks
    (pad keys with kv_positions = -1 -> masked out).
    """
    b, h, sq, hd = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    g = h // kvh
    assert sq % block_q == 0 and skv % block_k == 0
    qf = q.reshape(b * h, sq, hd)
    grid = (b * h, sq // block_q, skv // block_k)

    def q_map(i, j, t):
        return (i, j, 0)

    def kv_map(i, j, t):
        return ((i % h) // g + (i // h) * kvh, t, 0)

    kf = k.reshape(b * kvh, skv, hd)
    vf = v.reshape(b * kvh, skv, hd)

    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda i, j, t: (j, 0)),
            pl.BlockSpec((1, block_k), lambda i, j, t: (0, t)),
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(q_positions.astype(jnp.int32)[:, None],
      kv_positions.astype(jnp.int32)[None, :],
      qf, kf, vf)
    return out.reshape(b, h, sq, hd)
