"""A kernel's share of its roofline, read from the reduced trace, and the
whole Lloyd step's share of the chip's peak."""
import sys


def share(ctx, kernel: str):
    """The least time the chip could take for the kernel's launches in the
    window, max(flops / bf16 peak, bytes / HBM bandwidth) per launch from
    the kernel's cost model, over their summed device time, in percent.
    None where the trace shows no launch of it."""
    rec = ctx["trace"]["kernels"].get(kernel)
    if not rec or not rec["launches"] or not rec["seconds"]:
        return None
    flops, nbytes = ctx["costs"][kernel].cost(ctx["cell"])
    t_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    t_bytes = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    print(f"{kernel}_roofline: {rec['launches']} launches, "
          f"{rec['seconds']} s, bound by "
          f"{'compute' if t_flops >= t_bytes else 'memory'}",
          file=sys.stderr)
    return 100.0 * rec["launches"] * max(t_flops, t_bytes) / rec["seconds"]


def step_mfu(ctx):
    """The whole Lloyd step's share of the chip's bf16 peak, in percent.

    Useful work only, 2*B*N*K*F flops per iteration (the distance products;
    checksums, recomputes, padding and the one-hot update do not count),
    over the traced run's time per iteration (the window's wall time over
    its iterations, as the cell's end-to-end metric takes it), over chips x
    peak."""
    cfg, log = ctx["cell"].config, ctx["record"]["log"]
    b = cfg.get("subspaces", 1)
    f = cfg.get("sub_features", cfg["features"])
    flops = 2.0 * b * cfg["rows"] * cfg["clusters"] * f
    iter_s = log["wall_s"] / log["iterations"]
    peak = ctx["cell"].chips * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / iter_s / peak
