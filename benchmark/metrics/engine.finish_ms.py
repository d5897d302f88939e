"""Median per batch of the engine's device-wait + deliver spans (ms)."""

from benchmark.metrics import _lib


def read(ctx):
    return _lib.stage_ms(ctx, ("device", "deliver"))
