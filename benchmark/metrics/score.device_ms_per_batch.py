"""Device time of the scoring program per run of it, from the trace (ms)."""

from benchmark.metrics import _lib


def read(ctx):
    t = _lib.program_seconds(ctx, "score")
    runs = _lib.program_runs(ctx, "score") if t else 0
    return 1e3 * t / runs if runs else None
