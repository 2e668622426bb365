"""The plain reference: nearest-centroid assignment and the centroid update
in straightforward ``jax.numpy``, float32, every product at
``Precision.HIGHEST``, computed in blocks of rows so that it fits beside
the data. It imports nothing of the program under test.

It judges one Lloyd step of the program: given the centroids ``c`` the
step started from, the labels it gave, the distances it reported and the
centroids it produced, it measures how far each lies from what the step
should give. Distances carry rounding relative to ||x||^2 + ||c||^2, not
to the (much smaller) distance itself, so assignment and inertia are
judged on that scale; centroids relative to the largest |x|.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 16_384


def _blocks(n: int, block: int):
    return [(s, min(s + block, n)) for s in range(0, n, block)]


def _pad(a, rows: int):
    pad = rows - a.shape[0]
    if pad == 0:
        return a
    return jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])


@jax.jit
def _assign_block(x, c, lab, md, valid):
    xx = jnp.sum(x * x, axis=1)
    cc = jnp.sum(c * c, axis=1)
    d = xx[:, None] - 2.0 * jnp.dot(x, c.T, precision=HIGHEST) + cc[None, :]
    best = jnp.argmin(d, axis=1)
    d_lab = jnp.sum((x - c[lab]) ** 2, axis=1)
    d_best = jnp.sum((x - c[best]) ** 2, axis=1)
    scale = xx + cc[lab]
    gap = jnp.where(valid, jnp.maximum(d_lab - d_best, 0.0) / scale, 0.0)
    dist = jnp.where(valid, jnp.abs(md - d_lab) / scale, 0.0)
    return (jnp.max(gap), jnp.max(dist),
            jnp.sum(jnp.where(valid, d_lab, 0.0)),
            jnp.sum(jnp.where(valid, scale, 0.0)))


def assignment(x, c, labels, md=None, block: int = BLOCK_ROWS) -> dict:
    """How far the program's labels and distances at centroids ``c`` lie
    from the nearest centroid.

    ``label_gap``: the widest gap, over rows, between the distance to the
    given label and the distance to the nearest centroid, over the row's
    scale. ``dist_err``: the widest error of the reported distances ``md``
    over the scale (0 where ``md`` is None). ``inertia``: the exact sum of
    squared distances to the given labels; ``scale``: the sum of scales.
    """
    n = x.shape[0]
    c = jnp.asarray(c, jnp.float32)
    labels = jnp.asarray(labels, jnp.int32)
    md = jnp.zeros((n,), jnp.float32) if md is None else \
        jnp.asarray(md, jnp.float32)
    gap = dist = inertia = scale = jnp.float32(0.0)
    for s, e in _blocks(n, block):
        valid = jnp.arange(block) < (e - s)
        g, d, i, sc = _assign_block(_pad(x[s:e], block), c,
                                    _pad(labels[s:e], block),
                                    _pad(md[s:e], block), valid)
        gap, dist = jnp.maximum(gap, g), jnp.maximum(dist, d)
        inertia, scale = inertia + i, scale + sc
    gap, dist, inertia, scale = jax.device_get((gap, dist, inertia, scale))
    return {"label_gap": float(gap), "dist_err": float(dist),
            "inertia": float(inertia), "scale": float(scale)}


@functools.partial(jax.jit, static_argnums=(2,))
def _sums_block(x, lab, k, valid):
    w = valid.astype(jnp.float32)
    sums = jax.ops.segment_sum(x * w[:, None], lab, num_segments=k)
    counts = jax.ops.segment_sum(w, lab, num_segments=k)
    return sums, counts


@jax.jit
def _nearest_row_block(x, targets, valid):
    err = jnp.max(jnp.abs(x[None, :, :] - targets[:, None, :]), axis=2)
    return jnp.min(jnp.where(valid[None, :], err, jnp.inf), axis=1)


def update(x, labels, new_c, block: int = BLOCK_ROWS) -> dict:
    """How far the program's new centroids lie from the means of the rows
    it labelled. A cluster that got no row must have been moved onto a row
    of ``x`` (the empty-cluster reseed). ``update_err``: the widest
    element error over the largest |x|; ``empty``: clusters with no row."""
    n = x.shape[0]
    new_c = jnp.asarray(new_c, jnp.float32)
    k = new_c.shape[0]
    labels = jnp.asarray(labels, jnp.int32)
    sums = jnp.zeros(new_c.shape, jnp.float32)
    counts = jnp.zeros((k,), jnp.float32)
    for s, e in _blocks(n, block):
        valid = jnp.arange(block) < (e - s)
        bs, bc = _sums_block(_pad(x[s:e], block), _pad(labels[s:e], block),
                             k, valid)
        sums, counts = sums + bs, counts + bc
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    err = jnp.max(jnp.abs(new_c - means), axis=1)
    x_scale = jnp.max(jnp.abs(x))
    counts_h, err_h, x_scale = jax.device_get((counts, err, x_scale))
    empty = np.flatnonzero(counts_h == 0)
    err_h = np.array(err_h)
    rows, group = min(block, 4096), 32
    for g in range(0, empty.size, group):
        idx = empty[g:g + group]
        targets = _pad(new_c[jnp.asarray(idx)], group)
        near = jnp.full((group,), jnp.inf, jnp.float32)
        for s, e in _blocks(n, rows):
            valid = jnp.arange(rows) < (e - s)
            near = jnp.minimum(near, _nearest_row_block(
                _pad(x[s:e], rows), targets, valid))
        err_h[idx] = np.asarray(jax.device_get(near))[:idx.size]
    return {"update_err": float(np.max(err_h) / max(float(x_scale), 1e-30)),
            "empty": int(empty.size)}


def lloyd_step(x, c, labels, inertia, new_c) -> dict:
    """One whole Lloyd step of the program, judged: its labels at ``c``,
    the inertia it reported, and its new centroids."""
    a = assignment(x, c, labels)
    u = update(x, labels, new_c)
    return {"label_gap": a["label_gap"],
            "inertia_err": abs(float(inertia) - a["inertia"]) / a["scale"],
            "update_err": u["update_err"], "empty": u["empty"]}
