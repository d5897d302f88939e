"""Median of the window's ``slot_state_restore`` spans: the host's part of
copying the shared preamble's state snapshot into an admitted slot's block
(ms; the device copy itself is enqueued, not waited for)."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx, "slot_state_restore")
