"""Median submit -> first token of the requests submitted in the window,
from the slot lane's own stamps on its tickets (ms)."""

import statistics


def read(ctx):
    lo, hi = ctx["window"]
    waits = [1e3 * (t["first_token"] - t["submitted"]) for t in ctx["tickets"]
             if t["first_token"] is not None and lo <= t["submitted"] < hi]
    return statistics.median(waits) if waits else None
