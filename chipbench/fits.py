"""What the two fit drivers share: fits back to back over a window, and
bit-for-bit comparison of their outputs."""
from __future__ import annotations

import time

import jax
import numpy as np

SPANS = ("fit", "witness")


def back_to_back(fit, seconds: float, metric: str = "fit_iter_ms") -> dict:
    """Run ``fit()`` until one starts after ``seconds``; each returns
    (outputs, iterations) with its outputs ready on the device.
    ``metric`` (milliseconds per iteration) is the wall time from the
    window's start to the end of the last fit, over the iterations of all
    fits."""
    outs, iters = [], 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with jax.profiler.TraceAnnotation("fit"):
            out, n = fit()
            jax.block_until_ready(out)
        outs.append(out)
        iters += n
    wall = time.perf_counter() - t0
    return {"outputs": outs, "iterations": iters,
            "attempted": len(outs), "failed": 0,
            "end_to_end": {metric: wall / iters * 1e3},
            "log": {"fits": len(outs), "iterations": iters, "wall_s": wall}}


def differ(a, b) -> bool:
    """Whether two fits' outputs differ anywhere (bit for bit)."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return any(not np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(la, lb))


def worst(readings: list[dict]) -> dict:
    """The largest reading of each number over several problems."""
    return {k: max(r[k] for r in readings) for k in readings[0]}


def judged_lengths(iterations: int, sync_every: int) -> list[int]:
    """Fit lengths whose last steps, together, run in every chunk program
    a fit of ``iterations`` uses: each ``sync_every``-step chunk is one
    compiled scan and the remainder another."""
    return sorted({min(sync_every, iterations), iterations})
