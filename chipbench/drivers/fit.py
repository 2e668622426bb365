"""Driver ``fit``: ``repro.api.KMeans.fit`` back to back from one init.

Set-up makes the data and the init on the device from the seed, builds the
estimator the traffic mix asks for (fault policy, SEU campaign) and fits
once, which compiles every program the window runs. The window fits from
the same init until ``--seconds`` have passed.

The check, after the window: a witness fit through the same estimator and
the same compiled programs, with ``on_iteration`` reading the centroids
the last step started from. Every window fit must equal the witness bit
for bit, and the witness's last step is judged by the plain reference:
labels, inertia and new centroids. A fit runs as ``sync_every``-step
chunks and a shorter remainder, each its own compiled program, so a
second witness as long as one chunk has its last step judged too: every
program the window runs is compared. Under an SEU campaign, each window fit
must count exactly the injected errors, and the clean protected fit (the
same estimator with the campaign removed) must end bit-equal to it.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from chipbench import data, fits, reference

SPANS = fits.SPANS


@dataclasses.dataclass
class State:
    x: jax.Array
    c0: jax.Array
    est: object
    policy: object
    clean_policy: object
    iterations: int
    injected_per_step: int        # errors the campaign injects per step


def _policy(traffic: dict, seed: int):
    from repro.api import FaultPolicy, InjectionCampaign
    if traffic["fault"] == "off":
        return FaultPolicy.off(), FaultPolicy.off()
    if traffic["fault"] != "correct":
        raise ValueError(f"unknown fault mode {traffic['fault']!r}")
    inj = traffic.get("injection")
    campaign = None if inj is None else InjectionCampaign(
        rate=inj["rate"], targets=inj["targets"],
        seed=data.small_seed(seed, 2))
    return (FaultPolicy.correct(injection=campaign), FaultPolicy.correct())


def setup(cell) -> State:
    from repro.api import AutotuneCache, KMeans
    cfg = cell.config
    k_data, k_init = jax.random.split(data.seed_key(cell.seed))
    x, _ = data.mixture(k_data, cfg["rows"], cfg["features"], cfg["data"])
    c0 = data.random_rows(k_init, x, cfg["clusters"])
    policy, clean = _policy(cell.traffic, cell.seed)
    est = KMeans(cfg["clusters"], max_iter=cfg["iterations"], tol=0.0,
                 init="random", fault=policy, autotune=AutotuneCache(None),
                 compute_dtype=cell.control or cfg["dtype"],
                 random_state=data.small_seed(cell.seed, 1))
    inj = cell.traffic.get("injection")
    state = State(x, c0, est, policy, clean, cfg["iterations"],
                  0 if inj is None else int(inj["per_step"]))
    jax.block_until_ready(_fit(state)[0])
    return state


def _fit(state: State):
    est = state.est.fit(state.x, centroids=state.c0)
    out = (est.cluster_centers_, est.labels_, np.float32(est.inertia_),
           np.int64(est.detected_errors_))
    return out, est.n_iter_


def window(state: State, seconds: float) -> dict:
    return fits.back_to_back(lambda: _fit(state), seconds)


def _judged_fit(state: State, iterations: int):
    """A witness fit of ``iterations`` through the same estimator and
    programs; returns its outputs and the centroids its last step started
    from (``on_iteration`` replays them from the chunk history)."""
    est, prev = state.est, {}

    def keep(i, centroids, *_):
        if i == iterations - 2:
            prev["c"] = np.asarray(centroids)

    est.max_iter = iterations
    try:
        with jax.profiler.TraceAnnotation("witness"):
            est.fit(state.x, centroids=state.c0, on_iteration=keep)
    finally:
        est.max_iter = state.iterations
    out = (est.cluster_centers_, est.labels_, np.float32(est.inertia_),
           np.int64(est.detected_errors_))
    return out, prev.get("c", np.asarray(state.c0))


def check(state: State, record: dict) -> dict:
    est, it = state.est, state.iterations
    witness, c_prev = _judged_fit(state, it)
    values = {"window_fits_differ": sum(
        fits.differ(out, witness) for out in record["outputs"])}
    if state.policy.injection is not None:
        want = state.iterations * state.injected_per_step
        values["detections_off"] = max(
            abs(int(out[3]) - want) for out in record["outputs"])
        est.fault = state.clean_policy
        try:
            est.fit(state.x, centroids=state.c0)
            values["ft_vs_clean"] = float(np.max(np.abs(
                np.asarray(est.cluster_centers_) - np.asarray(witness[0]))))
        finally:
            est.fault = state.policy
    steps = []
    for n in fits.judged_lengths(it, est.sync_every):
        out, c = (witness, c_prev) if n == it else _judged_fit(state, n)
        steps.append(reference.lloyd_step(state.x, c, out[1], out[2], out[0]))
    values.update(fits.worst(steps))
    record["log"]["reference"] = values
    record["log"]["judged_steps"] = fits.judged_lengths(it, est.sync_every)
    return values
