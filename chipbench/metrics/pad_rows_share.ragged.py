"""``pad_rows_share.ragged``: the rows that pad each problem to whole row
tiles, over the problems' own rows, per launch of the ragged kernel, in
percent: MXU and HBM work the answer does not need. From the estimator's
counters (``rows_padded_``, ``rows_valid_``), which ``drivers/ragged_fit``
logs; None where the program has no such counters."""


def read(ctx):
    log = ctx["record"]["log"]
    if not log.get("rows_valid"):
        return None
    return 100.0 * log["rows_padded"] / log["rows_valid"]
