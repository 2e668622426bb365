"""``fit_mfu.batched``: the whole Lloyd step's share of the chip's bf16
peak in a batched fit cell (see ``chipbench/roofline.py``), beside its
kernel's roofline; moves ``batched_fit_iter_ms``."""
from chipbench import roofline


def read(ctx):
    return roofline.step_mfu(ctx)
