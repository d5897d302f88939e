"""Median per batch of the ``upload`` spans inside ``launch``: packing the
encoded rows and placing them on the device (ms)."""

from benchmark.metrics import _lib


def read(ctx):
    return _lib.stage_ms(ctx, ("upload",))
