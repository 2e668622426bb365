"""Cost model of the ragged one-pass Lloyd kernel (``kernels/lloyd_step``,
ragged entry): one launch assigns the N_b rows of each of B problems of
different row counts and sums them into their clusters.

The algorithm's own work: for each problem 2*N_b*K*F flops for the
distance products and N_b*F adds for the sums; X read once, each
problem's C read once, the min distance and label written once per row,
the sums and counts written once per problem. The rows that pad each
problem to whole row tiles, and the per-tile partial sums, are the
implementation's, not the algorithm's, and are not counted."""

PATTERN = r"^lloyd_step_ragged\b"


def cost(cell) -> tuple[float, float]:
    cfg = cell.config
    per = cfg["full_layers"] * cfg["kv_heads"]
    lengths = [n for n in cfg["prompt_lengths"] for _ in range(per)]
    b, rows = len(lengths), sum(lengths)
    k, f = cfg["clusters"], cfg["features"]
    flops = sum(2.0 * n * k * f + n * f for n in lengths)
    return flops, 4.0 * (rows * f + 2 * b * k * f + 2 * rows + b * k)
