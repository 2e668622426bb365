"""``fit_mfu``: the whole Lloyd step's share of the chip's bf16 peak in an
unbatched fit cell (see ``chipbench/roofline.py``), beside its kernels'
rooflines; moves ``fit_iter_ms``."""
from chipbench import roofline


def read(ctx):
    return roofline.step_mfu(ctx)
