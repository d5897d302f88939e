"""Median wait for a decode slot of the requests submitted in the window:
``slot_wait`` spans, submit -> the row's own prefill call begins (ms).
``slot.ttft_p50_ms`` is this plus the ``prefill`` span."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx, "slot_wait")
