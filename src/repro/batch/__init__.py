"""``repro.batch`` — many-problem K-means over stacked independent problems.

Production traffic is rarely one big clustering problem: serving millions
of users means thousands of *independent small* problems (per-user
embeddings, per-shard codebooks) whose individual kernel launches waste
the MXU. This package runs B problems, a stacked (B, N, F) block or rows
of different counts packed back to back, through the batched one-pass
Lloyd kernel (a grid over every problem's row tiles — see
``docs/kernels.md``), with per-problem seeds, inits and convergence masks
inside a single ``lax.scan``.

  * :class:`BatchedKMeans` — the stacked-problem estimator
    (``fit`` / ``predict`` / ``score`` / ``get_state`` / ``from_state``);
  * problem-axis sharding — ``repro.dist.DistributedKMeans`` accepts a
    :class:`BatchedKMeans` and shards over B instead of rows
    (embarrassingly parallel: no psum on the hot path).
"""
from repro.batch.estimator import BatchedKMeans

__all__ = ["BatchedKMeans"]
