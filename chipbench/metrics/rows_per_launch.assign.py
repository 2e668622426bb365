"""``rows_per_launch.assign``: rows served per micro-batch dispatch, counted
through the ``on_dispatch`` seam of ``KMeansService.from_estimator``."""


def read(ctx):
    rec = ctx["record"]
    if not rec["dispatches"]:
        return None
    return rec["rows"] / rec["dispatches"]
