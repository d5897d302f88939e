"""Median over the window's slot-lane iterations of ``slot_iter`` less the
``slot_fetch`` and ``prefill`` spans inside it: the host time of the loop
around each decode window, during which the chip waits (ms)."""

import statistics

from benchmark.metrics import _spans


def read(ctx):
    host = _spans.loop_host_ms(ctx)
    return statistics.median(host) if host else None
