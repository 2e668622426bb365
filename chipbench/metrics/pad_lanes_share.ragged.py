"""``pad_lanes_share.ragged``: the lanes that pad each row's F features to
the kernel's feature tiles, over F, in percent: MXU and HBM work the
answer does not need (11.1 % for F = 576 at 640 lanes). From the
estimator's counter ``feature_lanes_``, which ``drivers/latent_fit`` logs
beside F; None where the program has no such counter."""


def read(ctx):
    log = ctx["record"]["log"]
    if not log.get("feature_lanes") or not log.get("features"):
        return None
    return 100.0 * (log["feature_lanes"] - log["features"]) / log["features"]
