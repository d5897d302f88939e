"""The paged decode program's share of its roofline inside the traced
window (%): weights once per step plus the K/V of the tokens the active
rows really hold (not of the padded view), over its device time.
Memory-bound at 16 slots."""

from benchmark.metrics import _lib


def read(ctx):
    return _lib.roofline_pct(
        ctx, _lib.decode_cost(ctx, "trace_start", "trace_stop", "trace_window"),
        _lib.program_seconds(ctx, "decode"))
