"""Driver ``latent_fit``: ``ragged_fit``'s fits, ``BatchedKMeans.fit(x,
lengths=...)`` back to back from one init, over rows stored in
``keys_dtype`` (bf16) at the configuration's ``dtype`` arithmetic (f32).

The configuration names a prefill batch: ``prompt_lengths``, and for each
prompt ``full_layers`` x ``kv_heads`` problems of ``features``-wide cached
vectors, as many as the prompt has tokens. Set-up makes the rows problem by
problem straight into the storage dtype, each written into one packed
buffer in place, so no f32 copy of all rows is ever made (one problem's f32
draw is the largest temporary). Each problem is a Gaussian mixture of its
own with Zipf component sizes (``data.mixture``) in random order, so its
first K rows, upcast to f32, are K random rows: the init. Then one fit,
which compiles every program the window runs. The window and its record
are ``ragged_fit``'s; the record also logs the padded feature lanes and the
bf16 passes of each distance product (the estimator's counters).

The check is ``ragged_fit``'s, with each problem's rows upcast to f32
before the reference judges them (it squares rows in their own dtype).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data, fits, reference
from chipbench.drivers import ragged_fit

SPANS = ragged_fit.SPANS
State = ragged_fit.State


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("rows", "sizes", "centre_std", "sigma"))
def _write_problem(buf, key, start, *, rows, sizes, centre_std, sigma):
    """One problem's rows, drawn in f32 and rounded to ``buf``'s dtype,
    written into ``buf`` from row ``start``."""
    x, _ = data._mixture(key, rows=rows, features=buf.shape[1], sizes=sizes,
                         centre_std=centre_std, sigma=sigma)
    return jax.lax.dynamic_update_slice(buf, x.astype(buf.dtype), (start, 0))


def make_rows(key, lengths, features: int, spec: dict, dtype):
    """(sum N, features) rows in ``dtype``, problem by problem."""
    buf = jnp.zeros((sum(lengths), features), dtype)
    starts = np.cumsum((0,) + tuple(lengths[:-1]))
    for k, s, n in zip(jax.random.split(key, len(lengths)), starts, lengths):
        sizes = tuple(int(v) for v in data.zipf_sizes(
            n, spec["components"], spec["zipf_s"]))
        buf = _write_problem(buf, k, jnp.int32(s), rows=n, sizes=sizes,
                             centre_std=float(spec["centre_std"]),
                             sigma=float(spec["sigma"]))
    return buf


def setup(cell) -> State:
    from repro.api import AutotuneCache, BatchedKMeans
    cfg = cell.config
    lengths = ragged_fit.problem_lengths(cfg)
    if sum(lengths) != cfg["rows"]:
        raise ValueError("rows must be the sum of the problems' lengths")
    if min(lengths) < cfg["clusters"]:
        raise ValueError("every problem needs at least K rows")
    x = make_rows(data.seed_key(cell.seed), lengths, cfg["features"],
                  cfg["data"], jnp.dtype(cfg["keys_dtype"]))
    k = cfg["clusters"]
    starts = np.cumsum((0,) + lengths[:-1])
    c0 = jnp.stack([x[s:s + k] for s in starts]).astype(jnp.float32)
    est = BatchedKMeans(k, max_iter=cfg["iterations"], tol=0.0,
                        init="random", autotune=AutotuneCache(None),
                        compute_dtype=cell.control or cfg["dtype"],
                        random_state=data.small_seed(cell.seed, 1))
    state = State(x, lengths, c0, est, cfg["iterations"])
    jax.block_until_ready(ragged_fit._fit(state)[0])
    return state


def window(state: State, seconds: float) -> dict:
    record = ragged_fit.window(state, seconds)
    est = state.est
    record["log"].update(features=int(state.x.shape[1]),
                         feature_lanes=getattr(est, "feature_lanes_", None),
                         distance_passes=getattr(est, "distance_passes_",
                                                 None))
    return record


def check(state: State, record: dict) -> dict:
    outs = record["outputs"]
    values = {"window_fits_differ": sum(fits.differ(o, outs[0])
                                        for o in outs[1:])}
    it, sync_every = state.iterations, state.est.sync_every
    starts = np.cumsum((0,) + state.lengths[:-1])
    steps = []
    for n in fits.judged_lengths(it, sync_every):
        # as ragged_fit.check: the judged step in its own chunk program,
        # the centroids it started from out of single-step chunks
        out = outs[0] if n == it else ragged_fit._fit_to(state, n,
                                                         sync_every)
        c_prev = ragged_fit._fit_to(state, n - 1, 1)[0] if n > 1 \
            else state.c0
        new_c, labels, inertia = out
        for b, (s, rows) in enumerate(zip(starts, state.lengths)):
            steps.append(reference.lloyd_step(
                state.x[s:s + rows].astype(jnp.float32), c_prev[b],
                labels[s:s + rows], inertia[b], new_c[b]))
    values.update(fits.worst(steps))
    record["log"]["reference"] = values
    record["log"]["judged_steps"] = fits.judged_lengths(it, sync_every)
    return values
