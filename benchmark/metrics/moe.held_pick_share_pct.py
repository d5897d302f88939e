"""Share of the window's expert choices (every real token's picks, prefill
and decode, over every expert layer) that landed on an expert held here
(%): ``moe_picks_held`` over ``moe_picks``, open to close. Under even
routing it is the held share of the published experts (128 of 512: 25)."""

from benchmark.metrics import _lib


def read(ctx):
    marks = ctx["marks"]
    if "moe_picks" not in marks.get("open", {}):
        return None
    picks = _lib.delta(ctx, "open", "close", "moe_picks")
    held = _lib.delta(ctx, "open", "close", "moe_picks_held")
    return 100.0 * held / picks if picks else None
