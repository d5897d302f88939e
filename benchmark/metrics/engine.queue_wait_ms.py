"""Median ``poll`` span of the window: how long a batch's oldest row had
been on the broker when the engine polled it (ms)."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx, "poll")
