"""Fused k-means++ D² seeding rounds (batched problems, one kernel/round).

The vmapped seeding path (``jax.vmap(init_kmeanspp)``) pays, per round and
per problem, a full elementwise ``(N, F)`` distance recompute plus
``jax.random.choice`` over N weights — and the categorical draw itself
re-materializes a cumulative distribution every round. For the B-problem
regime the batched estimator targets (many small problems), that is B
dispatches of XLA glue per round with nothing fused.

This module fuses one whole D² round into a single launch over the
``(B, N/bn)`` grid:

  * **distance update** — the cross-term form ``d² = max(‖x‖² - 2·x·c
    + ‖c‖², 0)`` against the single centroid chosen last round, folded
    into the running ``min``;
  * **per-tile partial sums** of the updated d² — the first level of the
    inverse-CDF selection tree — written alongside.

Selection then finishes on the host side of the launch in O(B·(T + bn))
instead of O(B·N): a cumulative sum over the T tile sums picks the tile,
an inner cumulative sum over that tile's bn entries picks the row
(``index = tile · bn + offset``), exactly one uniform draw per round.

**Deviation from the issue text**: the issue sketches Gumbel-top-1
sampling for the categorical draw; measured on the batched shapes it was
~5x slower than the round it replaces (a full log/noise pass over every
weight, every round). The tiled inverse-CDF above is the standard
single-uniform equivalent — identical distribution, one uniform per round
— and is what ships. Parity is pinned at the *chosen-index* level against
:func:`_round_twin`, a tile-mirrored XLA implementation of the same
round (Pallas-interpret and XLA float reductions are not bitwise
identical, so value-level parity would overconstrain the kernel).

Key protocol: ``k0, ku = split(key)``; ``randint(k0)`` picks the uniform
first centroid and ``uniform(ku, (K-1,))`` yields the K-1 round draws up
front (one uniform per round, drawn as a block so the loop body carries
no PRNG state). The stream therefore differs from ``init_kmeanspp`` —
same D² distribution, not the same samples — and reproducibility is
against *itself* per seed, plus chosen-index parity between the kernel
and the twin at a fixed ``block_n``.

Padding contract: rows are zero-padded to the tile grid and their d² is
pinned to 0.0 from the start — zero mass never advances the CDF, so a
padded row is never selected and never pollutes a tile sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._mosaic import compiler_params, mxu_dot

DEFAULT_BLOCK_N = 512
# off-TPU the tile size only shapes the two-level CDF, not a launch grid
TWIN_BLOCK_N = 128


def _round_up(v: int, b: int) -> int:
    return -(-v // b) * b


def clamp_init_block(n: int, block_n: int) -> int:
    """Row-tile size for the init round kernel: at least 128 (the d² and
    tile-sum blocks put bn on a lane-tiled axis) and no larger than the
    128-aligned problem (bigger only buys padding)."""
    return max(128, min(block_n, _round_up(n, 128)))


def _round_kernel(x_ref, xn_ref, c_ref, d2_ref, d2o_ref, ts_ref):
    """One (bn,) slice of one problem's D² round.

    x_ref  : (1, bn, fp) f32  sample tile (zero padded)
    xn_ref : (1, bn, 1)  f32  row squared norms (0 in padded rows)
    c_ref  : (1, 1, fp)  f32  the centroid chosen last round
    d2_ref : (1, bn, 1)  f32  incoming d² (0 in padded rows)
    d2o_ref: (1, bn, 1)  f32  updated d² (output)
    ts_ref : (1, 1, 1, 1) f32 tile sum of the updated d² (output)
    """
    xt = x_ref[0]                                    # (bn, fp)
    ct = c_ref[0]                                    # (1, fp)
    cross = mxu_dot(xt, ct, (1, 1))                 # (bn, 1)
    cn = jnp.sum(ct * ct)
    nd = jnp.maximum(xn_ref[0] - 2.0 * cross + cn, 0.0)
    d2 = jnp.minimum(d2_ref[0], nd)
    d2o_ref[0] = d2
    ts_ref[0, 0] = jnp.sum(d2, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeanspp_round(x: jax.Array, xn: jax.Array, c: jax.Array,
                   d2: jax.Array, *, block_n: int = DEFAULT_BLOCK_N,
                   interpret: bool = False
                   ) -> tuple[jax.Array, jax.Array]:
    """One fused D² round over the (B, Np/bn) grid.

    x (B, Np, Fp) f32 zero-padded samples, xn (B, Np) their row squared
    norms, c (B, 1, Fp) the last-chosen centroid per problem, d2 (B, Np)
    the running minimum squared distance (0.0 in padded rows). Returns
    ``(d2', tile_sums)`` with ``tile_sums`` of shape (B, Np // block_n).
    """
    b, np_, fp = x.shape
    assert np_ % block_n == 0 and fp % 128 == 0, (
        f"unpadded shapes {(np_, fp)} vs block_n={block_n}")
    t = np_ // block_n
    d2n, ts = pl.pallas_call(
        _round_kernel,
        grid=(b, t),
        in_specs=[
            pl.BlockSpec((1, block_n, fp), lambda bb, i: (bb, i, 0)),
            pl.BlockSpec((1, block_n, 1), lambda bb, i: (bb, i, 0)),
            pl.BlockSpec((1, 1, fp), lambda bb, i: (bb, 0, 0)),
            pl.BlockSpec((1, block_n, 1), lambda bb, i: (bb, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n, 1), lambda bb, i: (bb, i, 0)),
            # unit axes: the block equals the array's last two dims, as
            # Mosaic's block rule requires; squeezed after the call
            pl.BlockSpec((1, 1, 1, 1), lambda bb, i: (bb, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, t, 1, 1), jnp.float32),
        ],
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(x, xn[..., None], c, d2[..., None])
    return d2n[..., 0], ts[..., 0, 0]


def _round_twin(x: jax.Array, xn: jax.Array, c: jax.Array, d2: jax.Array,
                *, block_n: int) -> tuple[jax.Array, jax.Array]:
    """Tile-mirrored XLA twin of :func:`kmeanspp_round`: same cross-term
    distance form, same tile decomposition of the partial sums — the
    off-TPU production path and the kernel's chosen-index parity oracle."""
    cross = jnp.matmul(x, jnp.swapaxes(c, 1, 2))[:, :, 0]        # (B, Np)
    cn = jnp.sum(c * c, axis=2)                                  # (B, 1)
    nd = jnp.maximum(xn - 2.0 * cross + cn, 0.0)
    d2n = jnp.minimum(d2, nd)
    b, np_ = d2n.shape
    ts = jnp.sum(d2n.reshape(b, np_ // block_n, block_n), axis=2)
    return d2n, ts


def _select_index(d2: jax.Array, ts: jax.Array, u: jax.Array,
                  block_n: int, n: int) -> jax.Array:
    """Two-level inverse-CDF: tile from the T partial sums, row offset
    from the chosen tile's bn entries. One uniform per problem; zero-mass
    (padded or already-chosen) rows never advance the CDF."""
    if ts.shape[1] == 1:
        # single tile: the inner cumsum IS the whole CDF
        inner = jnp.cumsum(d2, axis=1)                           # (B, bn)
        tgt = u * inner[:, -1]
        off = jnp.sum((inner <= tgt[:, None]).astype(jnp.int32), axis=1)
        return jnp.minimum(off, n - 1)
    cum = jnp.cumsum(ts, axis=1)                                 # (B, T)
    target = u * cum[:, -1]                                      # (B,)
    tile = jnp.sum((cum <= target[:, None]).astype(jnp.int32), axis=1)
    tile = jnp.minimum(tile, ts.shape[1] - 1)
    prev = jnp.where(
        tile > 0,
        jnp.take_along_axis(cum, jnp.maximum(tile - 1, 0)[:, None],
                            axis=1)[:, 0],
        0.0)
    b = d2.shape[0]
    d2t = jnp.take_along_axis(d2.reshape(b, -1, block_n),
                              tile[:, None, None], axis=1)[:, 0]
    inner = jnp.cumsum(d2t, axis=1)                              # (B, bn)
    off = jnp.sum((inner <= (target - prev)[:, None]).astype(jnp.int32),
                  axis=1)
    off = jnp.minimum(off, block_n - 1)
    return jnp.minimum(tile * block_n + off, n - 1)


@functools.partial(
    jax.jit, static_argnames=("k", "block_n", "use_kernel", "interpret"))
def _init_impl(keys: jax.Array, x: jax.Array, *, k: int, block_n: int,
               use_kernel: bool, interpret: bool) -> jax.Array:
    b, n, f = x.shape
    xf = x.astype(jnp.float32)
    np_ = _round_up(n, block_n)
    # the kernel wants lane-aligned features resident; the twin runs
    # unpadded (zero feature columns add nothing but bytes)
    fp = _round_up(f, 128) if use_kernel else f
    xp = jnp.pad(xf, ((0, 0), (0, np_ - n), (0, fp - f)))
    xn = jnp.sum(xp * xp, axis=2)
    d2_0 = jnp.broadcast_to(
        jnp.where(jnp.arange(np_) < n, jnp.inf, 0.0), (b, np_))

    def _draws(key: jax.Array) -> tuple:
        k0, ku = jax.random.split(key)
        return (jax.random.randint(k0, (), 0, n),
                jax.random.uniform(ku, (k - 1,)))

    i0, us = jax.vmap(_draws)(keys)                  # (B,), (B, K-1)
    first = jnp.take_along_axis(xp, i0[:, None, None], axis=1)   # (B,1,fp)

    round_fn = (functools.partial(kmeanspp_round, block_n=block_n,
                                  interpret=interpret) if use_kernel
                else functools.partial(_round_twin, block_n=block_n))

    # the loop carries (B, K) chosen-row indices, not the centroid stack:
    # one int32 write per round beats a (B, K, F) copy, and a single
    # gather at the end materializes the centroids
    idx0 = jnp.zeros((b, k), jnp.int32).at[:, 0].set(i0)

    def body(i, carry):
        idx, d2, last = carry
        d2, ts = round_fn(xp, xn, last, d2)
        sel = _select_index(d2, ts, us[:, i - 1], block_n, n)
        nxt = jnp.take_along_axis(xp, sel[:, None, None], axis=1)
        return idx.at[:, i].set(sel), d2, nxt

    idx, _, _ = jax.lax.fori_loop(1, k, body, (idx0, d2_0, first))
    return jnp.take_along_axis(xf, idx[..., None], axis=1).astype(x.dtype)


def init_kmeanspp_fused(keys: jax.Array, x: jax.Array, k: int, *,
                        params=None, block_n: int = None,
                        use_kernel: bool = None,
                        interpret: bool = None,
                        autotune=None) -> jax.Array:
    """Fused k-means++ seeding for B stacked problems.

    keys (B, 2) per-problem PRNG keys, x (B, N, F) stacked samples.
    Returns (B, K, F) centroids in ``x.dtype``. ``use_kernel=None``
    auto-selects the Pallas round kernel on TPU and the tile-mirrored XLA
    twin elsewhere — both drive the identical round/selection protocol,
    and per seed they choose the same indices (the parity contract
    ``tests/test_seeding.py`` pins). ``block_n``/``params`` override the
    tile size (``params.block_m`` wins the autotune ``"init"``-kind
    lookup, made in ``autotune`` — default: the process cache);
    ``interpret`` only affects the kernel path.
    """
    from repro.kernels.ops import on_tpu
    b, n, f = x.shape
    if use_kernel is None:
        use_kernel = on_tpu()
    if interpret is None:
        interpret = not on_tpu()
    if block_n is None:
        if params is not None:
            block_n = params.block_m
        elif use_kernel:
            if autotune is None:
                from repro.api.cache import default_cache
                autotune = default_cache()
            _, p = autotune.lookup(n, k, f, kind="init")
            block_n = p.block_m
        else:
            # twin path: no launch grid to amortize off-TPU, so the tile
            # size only shapes the two-level CDF — small tiles keep both
            # cumsums short (XLA CPU cumsum cost grows superlinearly in
            # row length, so one long cumsum loses to tile-sum + gather)
            block_n = TWIN_BLOCK_N
    block_n = clamp_init_block(n, block_n)
    return _init_impl(keys, x, k=k, block_n=block_n,
                      use_kernel=use_kernel, interpret=interpret)
