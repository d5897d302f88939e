"""The paged prefill program's share of its roofline inside the traced
window (%): the FLOPs of each prompt's real suffix behind the cached
preamble, over its device time. Compute-bound from ~250 suffix tokens."""

from benchmark.metrics import _lib


def read(ctx):
    cost = _lib.prefill_cost(ctx, "trace_window")
    return _lib.roofline_pct(ctx, cost and cost[:2],
                             _lib.program_seconds(ctx, "prefill"))
