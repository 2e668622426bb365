"""Driver ``batched_fit``: ``repro.api.BatchedKMeans.fit`` back to back
from one init, over the sub-spaces of a product quantizer.

Set-up makes ``rows`` rows of the configuration's mixture on the device,
splits their features into ``subspaces`` stacked problems of
``sub_features`` each, draws K random rows per sub-space as the init, and
fits once, which compiles every program the window runs. The window fits
from the same init until ``--seconds`` have passed.

The check, after the window: every window fit must equal the first bit for
bit. The reference judges, in every sub-space, the last step of a window
fit and the last step of a witness fit one ``sync_every`` chunk long, so
that each compiled chunk program the window runs is compared: labels,
inertia and new centroids, the worst sub-space counting.
``BatchedKMeans.fit`` reports no per-iteration centroids, so the
centroids each judged step started from come from a witness fit of one
step fewer, through the same estimator from the same init, in
single-step chunks.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data, fits, reference

SPANS = fits.SPANS


@dataclasses.dataclass
class State:
    x: jax.Array          # (B, N, F)
    c0: jax.Array         # (B, K, F)
    est: object
    iterations: int


def setup(cell) -> State:
    from repro.api import AutotuneCache, BatchedKMeans
    cfg = cell.config
    b, f = cfg["subspaces"], cfg["sub_features"]
    if b * f != cfg["features"]:
        raise ValueError("subspaces * sub_features must equal features")
    k_data, k_init = jax.random.split(data.seed_key(cell.seed))
    rows, _ = data.mixture(k_data, cfg["rows"], cfg["features"], cfg["data"])
    x = jnp.transpose(rows.reshape(cfg["rows"], b, f), (1, 0, 2))
    c0 = jnp.stack([data.random_rows(k, x[i], cfg["clusters"])
                    for i, k in enumerate(jax.random.split(k_init, b))])
    est = BatchedKMeans(cfg["clusters"], max_iter=cfg["iterations"],
                        tol=0.0, init="random", autotune=AutotuneCache(None),
                        compute_dtype=cell.control or cfg["dtype"],
                        random_state=data.small_seed(cell.seed, 1))
    state = State(x, c0, est, cfg["iterations"])
    jax.block_until_ready(_fit(state)[0])
    return state


def _fit(state: State):
    est = state.est.fit(state.x, centroids=state.c0)
    out = (est.cluster_centers_, est.labels_,
           np.asarray(est.inertia_, np.float32))
    return out, int(np.max(est.n_iter_))


def window(state: State, seconds: float) -> dict:
    return fits.back_to_back(lambda: _fit(state), seconds,
                             metric="batched_fit_iter_ms")


def _fit_to(state: State, iterations: int, sync_every: int):
    """A witness fit of ``iterations`` from the same init through the same
    estimator, with its chunks ``sync_every`` steps long."""
    est = state.est
    saved = est.max_iter, est.sync_every
    est.max_iter, est.sync_every = iterations, sync_every
    try:
        with jax.profiler.TraceAnnotation("witness"):
            est.fit(state.x, centroids=state.c0)
    finally:
        est.max_iter, est.sync_every = saved
    return (est.cluster_centers_, est.labels_,
            np.asarray(est.inertia_, np.float32))


def check(state: State, record: dict) -> dict:
    outs = record["outputs"]
    values = {"window_fits_differ": sum(fits.differ(o, outs[0])
                                        for o in outs[1:])}
    it, sync_every = state.iterations, state.est.sync_every
    steps = []
    for n in fits.judged_lengths(it, sync_every):
        # the step under judgement runs in the window's own chunk program;
        # the centroids it started from come from single-step chunks
        out = outs[0] if n == it else _fit_to(state, n, sync_every)
        c_prev = _fit_to(state, n - 1, 1)[0] if n > 1 else state.c0
        new_c, labels, inertia = out
        steps.extend(
            reference.lloyd_step(state.x[i], c_prev[i], labels[i],
                                 inertia[i], new_c[i])
            for i in range(state.x.shape[0]))
    values.update(fits.worst(steps))
    record["log"]["reference"] = values
    record["log"]["judged_steps"] = fits.judged_lengths(it, sync_every)
    return values
