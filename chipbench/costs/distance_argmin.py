"""Cost model of the distance + argmin kernel (``kernels/distance_argmin``):
one launch assigns all M rows to the nearest of K centroids.

The algorithm's own work: 2*M*K*F flops for the distance products; X read
once, C read once, the min distance and the label written once per row.
Padding, re-reads of C per row tile and the MXU's precision passes are the
implementation's, not the algorithm's, and are not counted."""

PATTERN = r"^distance_argmin\b"


def cost(cell) -> tuple[float, float]:
    cfg = cell.config
    m, k, f = cfg["rows"], cfg["clusters"], cfg["features"]
    return 2.0 * m * k * f, 4.0 * (m * f + k * f + 2 * m)
