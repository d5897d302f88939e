"""Host decode + featurize time per row: the benchmark's span around
``featurizer.encode_json`` over the rows it covered (us/row)."""


def read(ctx):
    lo, hi = ctx["window"]
    spans = [(dur, attrs["rows"]) for name, start, dur, attrs in ctx["spans"]
             if name == "featurize" and lo <= start < hi]
    rows = sum(r for _, r in spans)
    return 1e6 * sum(d for d, _ in spans) / rows if rows else None
