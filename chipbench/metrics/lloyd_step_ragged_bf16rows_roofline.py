"""``lloyd_step_ragged_bf16rows_roofline``: the lloyd_step_ragged kernel's
share of its roofline over bf16-stored rows (see ``chipbench/roofline.py``
and ``chipbench/costs/lloyd_step_ragged_bf16rows.py``)."""
from chipbench import roofline


def read(ctx):
    return roofline.share(ctx, "lloyd_step_ragged_bf16rows")
