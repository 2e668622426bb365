"""``distance_argmin_roofline``: the distance_argmin kernel's share of its roofline (see
``chipbench/roofline.py`` and ``chipbench/costs/distance_argmin.py``)."""
from chipbench import roofline


def read(ctx):
    return roofline.share(ctx, "distance_argmin")
