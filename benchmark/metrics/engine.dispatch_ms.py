"""Median per batch of the engine's launch span: featurize + upload + launch (ms)."""

from benchmark.metrics import _lib


def read(ctx):
    return _lib.stage_ms(ctx, ("launch",))
