"""Device time of the paged decode program per decode step, inside the
traced window (ms)."""

from benchmark.metrics import _lib


def read(ctx):
    t = _lib.program_seconds(ctx, "decode")
    steps = _lib.delta(ctx, "trace_start", "trace_stop", "decode_steps")
    return 1e3 * t / steps if t and steps else None
