"""``pack_idle_ms_per_fit.ragged``: the device's idle time under the
program's ``kmeans.pack`` span (building the ragged plan, once per fit),
per fit of the window, in ms, from the trace's idle time by span. None
where the program logs no ragged counters (it has no such span)."""

SPAN = "kmeans.pack"


def read(ctx):
    log = ctx["record"]["log"]
    if not log.get("rows_valid") or not log.get("fits"):
        return None
    idle = sum(s for name, s in ctx["trace"]["idle_by_span"] if name == SPAN)
    return idle / log["fits"] * 1e3
