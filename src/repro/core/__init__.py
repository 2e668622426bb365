"""FT K-means core — algorithm numerics behind the ``repro.api`` surface.

Prefer ``repro.api`` (typed ``KMeans`` + ``FaultPolicy`` + backend registry
+ injectable ``AutotuneCache``) for anything user-facing; this package holds
the pieces it composes — assignment backends (stepwise ladder §III-A),
DMR/ABFT protection (§IV), kernel search/selection (§III-B). It imports
nothing from ``repro.api``.
"""
from repro.core.kmeans import (centroid_update, init_kmeanspp, init_random,
                               protected_sums, reseed_empty)
from repro.core.fault import FaultConfig
from repro.core.ft_gemm import ft_matmul, abft_dot
from repro.core import checksum, assignment, autotune, dmr

__all__ = [
    "centroid_update", "init_kmeanspp", "init_random", "protected_sums",
    "reseed_empty", "FaultConfig", "ft_matmul", "abft_dot",
    "checksum", "assignment", "autotune", "dmr",
]
