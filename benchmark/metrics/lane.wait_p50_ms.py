"""Median wait of a flagged row on the annotation lane, enqueued in the
window: ``lane_wait`` spans, enqueue -> taken into a micro-batch (ms)."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx, "lane_wait")
