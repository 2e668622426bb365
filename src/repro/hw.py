"""TPU v5e chip constants — the single source of truth.

Both performance models consume these numbers: the autotune selection model
(``repro.core.autotune``) and the roofline analyzer (``repro.roofline.hw``
re-exports this module). Keeping one copy means the two models cannot
drift apart on what the hardware can do.
"""

from typing import Any

PEAK_FLOPS_BF16: float = 197e12  # FLOP/s (MXU peak at 2-byte dtypes)
PEAK_FLOPS_F32: float = PEAK_FLOPS_BF16 / 2
# int8 MXU path: double the bf16 MAC rate (the systolic array packs two
# 1-byte operands per bf16 lane), accumulating in int32.
PEAK_FLOPS_INT8: float = PEAK_FLOPS_BF16 * 2
HBM_BW: float = 819e9           # bytes/s
ICI_LINK_BW: float = 50e9       # bytes/s per link
ICI_LINKS: int = 4              # v5e: 4 ICI links per chip (2D torus x2)
HBM_BYTES: int = 16 * 2**30     # 16 GiB
VMEM_BYTES: int = 128 * 2**20
# VMEM per core for kernel working sets: the budget the autotuner checks
# and the scoped-VMEM limit every kernel asks Mosaic for
# (``kernels/_mosaic.py``), three quarters of the physical 128 MiB.
VMEM_BUDGET: int = 96 * 2**20
# Fixed host-side cost of one kernel launch (runtime dispatch + grid
# setup), independent of the grid. It is invisible next to a multi-ms fit
# step but dominates small online predict cells, which is why the serving
# model (``kind="serve"`` in repro.core.autotune) adds it per launch and
# the micro-batcher exists at all.
DISPATCH_OVERHEAD_S: float = 5e-6


def peak_flops(dtype: Any) -> float:
    """MXU peak for an input dtype. Only bf16 has a native full-rate MXU
    path on v5e; fp16 is upconverted by XLA and runs at ~f32 rate (it
    still halves the HBM/VMEM bytes, which the byte models account for
    separately), f32 is half rate, and int8 doubles the bf16 rate (int32
    accumulation)."""
    import numpy as np
    name = np.dtype(dtype).name
    if name == "int8":
        return PEAK_FLOPS_INT8
    return PEAK_FLOPS_BF16 if name == "bfloat16" else PEAK_FLOPS_F32
