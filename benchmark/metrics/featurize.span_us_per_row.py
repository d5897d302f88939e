"""Host decode + featurize time per row from the program's own
``featurize`` spans: their durations over the rows they covered (us/row)."""

from benchmark.metrics import _spans


def read(ctx):
    spans = _spans.in_window(ctx, "featurize")
    rows = sum(_spans.detail(s).get("rows", 0.0) for s in spans)
    return 1e3 * sum(s["duration_ms"] for s in spans) / rows if rows else None
