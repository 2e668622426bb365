"""Cost model of the ragged one-pass Lloyd kernel (``kernels/lloyd_step``,
ragged entry) over rows stored in bf16 at f32 arithmetic: one launch
assigns the N_b rows of each of B problems and sums them into their
clusters.

The algorithm's own work, at the configuration's F (576, not the 640
lanes it is padded to): for each problem 2*N_b*K*F flops for the distance
products and N_b*F adds for the sums; X read once at 2 bytes an element;
each problem's f32 C read once, the min distance and label written once
per row (4 bytes each), the f32 sums and counts written once per problem.
The feature and row padding, the per-tile partials and the three bf16
passes of each f32 product are the implementation's and are not
counted."""

PATTERN = r"^lloyd_step_ragged\b"


def cost(cell) -> tuple[float, float]:
    cfg = cell.config
    per = cfg["full_layers"] * cfg["kv_heads"]
    lengths = [n for n in cfg["prompt_lengths"] for _ in range(per)]
    b, rows = len(lengths), sum(lengths)
    k, f = cfg["clusters"], cfg["features"]
    flops = sum(2.0 * n * k * f + n * f for n in lengths)
    return flops, 2.0 * rows * f + 4.0 * (2 * b * k * f + 2 * rows + b * k)
