"""Driver ``serve``: online coarse assignment through ``KMeansService``.

Set-up makes the configuration's mixture on the device from the seed: its
centres, perturbed, are the served codebook, and a pool of its rows, read
to the host, is where queries come from. The codebook is wrapped in a
``KMeans`` by ``KMeans.from_state`` and served by
``KMeansService.from_estimator`` with the default bucket ladder and window
and a dispatch counter; the micro-batch loop is started, and a warm-up of
the same schedule under another offset runs before the window. Row counts
the warm-up did not meet may still compile inside the window: that is the
program's behaviour, and it is counted, not hidden.

The window offers open-loop Poisson arrivals at the mix's fixed rate:
caller threads send each request at its due time through
``KMeansService.predict``. Latency runs from the due time to the answer.
Each request's labels and distances are checked against the plain
reference after the window; a request with no answer a minute after the
close counts as failed.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data, reference

SPANS = ("request", "warmup")
ANSWER_WAIT_S = 60.0


@dataclasses.dataclass
class State:
    svc: object
    codebook: np.ndarray
    pool: np.ndarray
    mix: dict
    seed: int
    dispatches: list


def setup(cell) -> State:
    from repro.api import AutotuneCache, KMeans
    from repro.serve import KMeansService
    cfg, mix = cell.config, cell.traffic
    k_data, k_code = jax.random.split(data.seed_key(cell.seed))
    rows, centres = data.mixture(k_data, mix["query_pool_rows"],
                                 cfg["features"], cfg["data"])
    codebook = centres + mix["codebook_jitter"] * jax.random.normal(
        k_code, centres.shape, jnp.float32)
    codebook, pool = jax.device_get((codebook, rows))
    est = KMeans.from_state({
        "cluster_centers": codebook, "counts": None, "n_iter": 0,
        "inertia": None, "detected_errors": 0,
        "config": {"n_clusters": cfg["clusters"], "max_iter": 1, "tol": 0.0,
                   "init": "random", "backend": None, "batch_size": None,
                   "sync_every": 10,
                   "compute_dtype": cell.control or cfg["dtype"],
                   "predict_chunk_rows": None, "random_state": 0,
                   "params": None,
                   "fault": {"mode": "off", "update_dmr": None,
                             "injection": None}}},
        autotune=AutotuneCache(None))
    dispatches = [0]

    def count(_codebook) -> None:
        dispatches[0] += 1

    svc = KMeansService.from_estimator(est, on_dispatch=count)
    svc.start()
    state = State(svc, codebook, pool, mix, cell.seed, dispatches)
    with jax.profiler.TraceAnnotation("warmup"):
        offer(state, mix["warmup_s"], offset=1)
    return state


def offer(state: State, seconds: float, offset: int) -> dict:
    """Send the schedule for ``seconds`` and wait for every answer."""
    due, sizes = data.poisson_schedule(state.seed, state.mix, seconds,
                                       offset)
    n = len(due)
    starts = np.random.default_rng([state.seed, offset, 2]).integers(
        0, state.pool.shape[0] - sizes + 1)
    done = np.full(n, np.nan)
    late = np.zeros(n)
    answers: list = [None] * n

    def call(i: int) -> None:
        with jax.profiler.TraceAnnotation("request"):
            q = state.pool[starts[i]:starts[i] + sizes[i]]
            answers[i] = state.svc.predict(q)
        done[i] = time.perf_counter()

    d0 = state.dispatches[0]
    with concurrent.futures.ThreadPoolExecutor(state.mix["callers"]) as ex:
        t0 = time.perf_counter()
        futures = []
        for i in range(n):
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[i] = time.perf_counter() - (t0 + due[i])
            futures.append(ex.submit(call, i))
        _, pending = concurrent.futures.wait(
            futures, timeout=seconds + ANSWER_WAIT_S
            - (time.perf_counter() - t0))
        for f in futures:
            if f.done() and f.exception() is not None:
                raise f.exception()
        if pending:
            # unanswered requests stay NaN; stop the loop so no caller
            # is left waiting on a ticket after the run
            state.svc.stop()
    return {"due": t0 + due, "sizes": sizes, "starts": starts, "done": done,
            "late": late, "answers": answers, "t0": t0,
            "dispatches": state.dispatches[0] - d0}


def window(state: State, seconds: float) -> dict:
    r = offer(state, seconds, offset=0)
    state.svc.stop()
    lat = r["done"] - r["due"]
    answered = ~np.isnan(lat)
    lat_all = np.where(answered, lat, np.inf)    # a lost answer misses all
    in_window = answered & (r["done"] <= r["t0"] + seconds)
    half = r["due"] < r["t0"] + seconds / 2
    n = len(lat)
    rows = int(r["sizes"].sum())
    p = lambda a, q: float(np.percentile(a, q)) if len(a) else None
    return {
        "result": r, "attempted": n, "failed": int(n - answered.sum()),
        "dispatches": r["dispatches"], "rows": rows,
        "end_to_end": {
            "assign_p50_ms": p(lat_all, 50) * 1e3,
            "assign_p99_ms": p(lat_all, 99) * 1e3,
            "assign_rows_per_s": float(r["sizes"][in_window].sum())
            / seconds},
        "log": {"requests": n, "rows": rows, "dispatches": r["dispatches"],
                "late_p50_ms": p(r["late"], 50) * 1e3,
                "late_p99_ms": p(r["late"], 99) * 1e3,
                "late_max_ms": float(np.max(r["late"])) * 1e3,
                "p99_first_half_ms": p(lat_all[half], 99) * 1e3,
                "p99_second_half_ms": p(lat_all[~half], 99) * 1e3,
                "answered_in_window": int(in_window.sum())}}


def check(state: State, record: dict) -> dict:
    r = record["result"]
    got = [i for i, a in enumerate(r["answers"]) if a is not None]
    x = np.concatenate([state.pool[r["starts"][i]:r["starts"][i]
                                   + r["sizes"][i]] for i in got])
    labels = np.concatenate([r["answers"][i].labels for i in got])
    md = np.concatenate([r["answers"][i].sq_dists for i in got])
    a = reference.assignment(jnp.asarray(x), state.codebook, labels, md)
    values = {"unanswered": record["failed"], "label_gap": a["label_gap"],
              "dist_err": a["dist_err"]}
    record["log"]["reference"] = values
    return values
