"""Share of slot-steps that decoded a row, over the window (%): the slot
lane's own ``occupancy`` and ``decode_steps``, differenced open to close."""

from benchmark.metrics import _lib


def read(ctx):
    steps = _lib.delta(ctx, "open", "close", "decode_steps")
    rows = _lib.row_steps(ctx, "open", "close")
    if not steps or rows is None:
        return None
    return 100.0 * rows / (steps * ctx["marks"]["open"]["slots"])
