"""Median, over the rows annotated in the window, of the ``annotate`` event
less the row's own ``explain`` end stamp: the micro-batch's barrier tail
plus delivery (ms)."""

import statistics

from benchmark.metrics import _spans


def read(ctx):
    tails = _spans.tails_ms(ctx)
    return statistics.median(tails) if tails else None
