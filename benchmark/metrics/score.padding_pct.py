"""Share of the rows the scoring program ran that were padding (%): 1 less
real over padded rows of the window's ``upload`` spans."""

from benchmark.metrics import _spans


def read(ctx):
    parts = [_spans.detail(s) for s in _spans.in_window(ctx, "upload")]
    padded = sum(p.get("padded", 0.0) for p in parts)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(p.get("rows", 0.0) for p in parts) / padded)
