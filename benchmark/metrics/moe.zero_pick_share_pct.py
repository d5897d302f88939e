"""Share of the window's expert choices (every real token's picks, prefill
and decode, over every expert layer) that landed on a zero-compute expert
(%): ``moe_picks_zero`` over ``moe_picks``, open to close. It is the model's
dynamic compute: a token multiplies ``top_k x (1 - share)`` real experts.
Under even routing it is the zero-compute share of the router's outputs (256
of 768: 33.3). Nothing to read for a program without the counter."""

from benchmark.metrics import _lib


def read(ctx):
    if "moe_picks_zero" not in ctx["marks"].get("open", {}):
        return None
    picks = _lib.delta(ctx, "open", "close", "moe_picks")
    zero = _lib.delta(ctx, "open", "close", "moe_picks_zero")
    return 100.0 * zero / picks if picks else None
