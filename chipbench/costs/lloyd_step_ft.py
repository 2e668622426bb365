"""Cost model of the one-pass ABFT Lloyd kernel (``kernels/lloyd_step_ft``):
one launch assigns all M rows and sums them into their clusters.

The algorithm's own work: 2*M*K*F flops for the distance products and M*F
adds for the per-cluster sums; X read once, C read once, the min distance
and label written once per row, the sums and counts written once. The
checksums, recomputes and the one-hot form of the update are the
implementation's, not the algorithm's, and are not counted."""

PATTERN = r"^lloyd_step_ft\b"


def cost(cell) -> tuple[float, float]:
    cfg = cell.config
    m, k, f = cfg["rows"], cfg["clusters"], cfg["features"]
    return (2.0 * m * k * f + m * f,
            4.0 * (m * f + k * f + 2 * m + k * f + k))
