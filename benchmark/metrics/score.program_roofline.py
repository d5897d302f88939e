"""The scoring program's share of its roofline: least time for the rows
really scored inside the traced window (their real pairs, the model once a
batch) over the program's device time (%). Memory-bound at these shapes."""

from benchmark import counts
from benchmark.metrics import _lib


def read(ctx):
    t = _lib.program_seconds(ctx, "score")
    rows = _lib.score_rows_in_trace(ctx) if t else 0
    if not rows:
        return None
    runs = max(1.0, _lib.program_runs(ctx, "score"))
    clf = ctx["cfg"]["desk"]["classifier"]
    flops, nbytes = counts.score_cost(clf, rows, ctx["pairs_per_row"])
    _, model = counts.score_cost(clf, 0, 0)
    return _lib.roofline_pct(ctx, (flops, nbytes + model * (runs - 1)), t)
