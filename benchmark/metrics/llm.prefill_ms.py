"""Device time of the paged prefill program per prefill, inside the traced
window (ms)."""

from benchmark.metrics import _lib


def read(ctx):
    t = _lib.program_seconds(ctx, "prefill")
    runs = _lib.program_runs(ctx, "prefill") if t else 0
    return 1e3 * t / runs if runs else None
