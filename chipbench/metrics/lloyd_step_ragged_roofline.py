"""``lloyd_step_ragged_roofline``: the lloyd_step_ragged kernel's share of
its roofline (see ``chipbench/roofline.py`` and
``chipbench/costs/lloyd_step_ragged.py``)."""
from chipbench import roofline


def read(ctx):
    return roofline.share(ctx, "lloyd_step_ragged")
