"""Cost model of the batched one-pass Lloyd kernel (``kernels/lloyd_step``,
batched template): one launch assigns the N rows of each of B problems and
sums them into their clusters.

The algorithm's own work per problem: 2*N*K*F flops for the distance
products and N*F adds for the sums; X read once, C read once, the min
distance and label written once per row, the sums and counts written once.
Lane padding of F to the vector width is the implementation's, not the
algorithm's, and is not counted."""

PATTERN = r"^lloyd_step_batched\b"


def cost(cell) -> tuple[float, float]:
    cfg = cell.config
    b, n, k, f = (cfg["subspaces"], cfg["rows"], cfg["clusters"],
                  cfg["sub_features"])
    return (b * (2.0 * n * k * f + n * f),
            4.0 * b * (n * f + k * f + 2 * n + k * f + k))
