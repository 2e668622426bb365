"""``xla_ms_per_iter.fit``: device busy time outside the Pallas kernels
that have a cost model (the wrappers' padding and reductions, the XLA
update, norms), per Lloyd iteration of the window."""


def read(ctx):
    iters = ctx["record"]["iterations"]
    if not iters:
        return None
    return ctx["trace"]["other_s"] / iters * 1e3
