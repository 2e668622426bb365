"""Driver ``ragged_fit``: ``repro.api.BatchedKMeans.fit(x, lengths=...)``
back to back from one init, over problems of different row counts.

The configuration names a prefill batch: ``prompt_lengths`` (one per
request), and for each request ``full_layers`` x ``kv_heads`` problems
holding as many keys as the prompt has tokens. Set-up makes every
problem's keys on the device from the seed, packed request by request
(each request's problems back to back), takes K random keys of each
problem as the init, and fits once, which compiles every program the
window runs. The window fits from the same init until ``--seconds`` have
passed. The record logs what each launch processed: valid rows, padded
rows and row tiles (the estimator's counters).

The check, after the window, is ``batched_fit``'s on each problem's own
rows: every window fit must equal the first bit for bit, and the last
step of a window fit and of a witness one ``sync_every`` chunk long is
judged by the plain reference in every problem, the worst counting.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data, fits, reference

SPANS = fits.SPANS + ("kmeans.pack",)


@dataclasses.dataclass
class State:
    x: jax.Array          # (sum N, F), the problems' rows packed
    lengths: tuple        # (B,) rows of each problem
    c0: jax.Array         # (B, K, F)
    est: object
    iterations: int


def problem_lengths(cfg: dict) -> tuple[int, ...]:
    """Each request's prompt length, once per (layer, KV head)."""
    per = cfg["full_layers"] * cfg["kv_heads"]
    return tuple(int(n) for n in cfg["prompt_lengths"] for _ in range(per))


@functools.partial(jax.jit, static_argnames=("lengths", "features",
                                             "spec", "k"))
def _keys_and_init(key, *, lengths, features, spec, k):
    """Every problem's keys, a Gaussian mixture of its own with Zipf
    component sizes (``data.zipf_sizes``: fixed by the configuration, the
    seed draws the centres, the noise and the order), packed problem by
    problem; and the first K keys of each problem, which the random order
    makes K random keys. One row order is drawn for all problems by one
    sort of (B, n_max) random keys, the slots past a problem's rows last:
    a sort compiles once per shape, and for long (it is what
    ``jax.random.permutation`` does)."""
    spec = dict(spec)
    b, n_max, rows = len(lengths), max(lengths), sum(lengths)
    n = jnp.asarray(lengths, jnp.int32)
    k_centre, k_order, k_noise = jax.random.split(key, 3)
    ends = jnp.asarray(np.stack([np.cumsum(data.zipf_sizes(
        m, spec["components"], spec["zipf_s"])) for m in lengths]), jnp.int32)
    slots = jnp.arange(n_max, dtype=jnp.int32)
    comp = jax.vmap(lambda e: jnp.searchsorted(e, slots, side="right"))(ends)
    valid = slots[None, :] < n[:, None]
    order = jnp.argsort(jnp.where(
        valid, jax.random.bits(k_order, (b, n_max)) >> 1,
        jnp.uint32(2**32 - 1)),
        axis=1)
    comp = jnp.take_along_axis(comp, order, axis=1)
    prob = jnp.repeat(jnp.arange(b, dtype=jnp.int32), np.asarray(lengths),
                      total_repeat_length=rows)
    j = jnp.arange(rows, dtype=jnp.int32) - (jnp.cumsum(n) - n)[prob]
    centres = spec["centre_std"] * jax.random.normal(
        k_centre, (b, spec["components"], features), jnp.float32)
    noise = jax.random.normal(k_noise, (rows, features), jnp.float32)
    x = centres[prob, comp[prob, j]] + spec["sigma"] * noise
    first = (jnp.cumsum(n) - n)[:, None] + jnp.arange(k)[None, :]
    return x, x[first]


def setup(cell) -> State:
    from repro.api import AutotuneCache, BatchedKMeans
    cfg = cell.config
    lengths = problem_lengths(cfg)
    if sum(lengths) != cfg["rows"]:
        raise ValueError("rows must be the sum of the problems' lengths")
    if min(lengths) < cfg["clusters"]:
        raise ValueError("every problem needs at least K keys")
    x, c0 = _keys_and_init(
        data.seed_key(cell.seed), lengths=lengths, features=cfg["features"],
        spec=tuple(sorted(cfg["data"].items())), k=cfg["clusters"])
    est = BatchedKMeans(cfg["clusters"], max_iter=cfg["iterations"],
                        tol=0.0, init="random", autotune=AutotuneCache(None),
                        compute_dtype=cell.control or cfg["dtype"],
                        random_state=data.small_seed(cell.seed, 1))
    state = State(x, lengths, c0, est, cfg["iterations"])
    jax.block_until_ready(_fit(state)[0])
    return state


def _outputs(est):
    return (est.cluster_centers_, est.labels_,
            np.asarray(est.inertia_, np.float32))


def _fit(state: State):
    est = state.est.fit(state.x, lengths=state.lengths, centroids=state.c0)
    return _outputs(est), int(np.max(est.n_iter_))


def window(state: State, seconds: float) -> dict:
    record = fits.back_to_back(lambda: _fit(state), seconds,
                               metric="batched_fit_iter_ms")
    est = state.est
    record["log"].update(rows_valid=est.rows_valid_,
                         rows_padded=est.rows_padded_,
                         row_tiles=est.row_tiles_)
    return record


def _fit_to(state: State, iterations: int, sync_every: int):
    """A witness fit of ``iterations`` from the same init through the same
    estimator, with its chunks ``sync_every`` steps long."""
    est = state.est
    saved = est.max_iter, est.sync_every
    est.max_iter, est.sync_every = iterations, sync_every
    try:
        with jax.profiler.TraceAnnotation("witness"):
            est.fit(state.x, lengths=state.lengths, centroids=state.c0)
    finally:
        est.max_iter, est.sync_every = saved
    return _outputs(est)


def check(state: State, record: dict) -> dict:
    outs = record["outputs"]
    values = {"window_fits_differ": sum(fits.differ(o, outs[0])
                                        for o in outs[1:])}
    it, sync_every = state.iterations, state.est.sync_every
    starts = np.cumsum((0,) + state.lengths[:-1])
    steps = []
    for n in fits.judged_lengths(it, sync_every):
        # the step under judgement runs in the window's own chunk program;
        # the centroids it started from come from single-step chunks
        out = outs[0] if n == it else _fit_to(state, n, sync_every)
        c_prev = _fit_to(state, n - 1, 1)[0] if n > 1 else state.c0
        new_c, labels, inertia = out
        for b, (s, rows) in enumerate(zip(starts, state.lengths)):
            steps.append(reference.lloyd_step(
                state.x[s:s + rows], c_prev[b], labels[s:s + rows],
                inertia[b], new_c[b]))
    values.update(fits.worst(steps))
    record["log"]["reference"] = values
    record["log"]["judged_steps"] = fits.judged_lengths(it, sync_every)
    return values
