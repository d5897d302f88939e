"""Imbalance of the held experts' load in prefill: the busiest held expert's
tokens over the mean held expert's, both summed over the window's prefills
and expert layers (``moe_prefill_load_max`` over ``moe_prefill_load_mean``,
open to close). 1 is even; no token is dropped whatever it reads."""

from benchmark.metrics import _lib


def read(ctx):
    if "moe_prefill_load_max" not in ctx["marks"].get("open", {}):
        return None
    most = _lib.delta(ctx, "open", "close", "moe_prefill_load_max")
    mean = _lib.delta(ctx, "open", "close", "moe_prefill_load_mean")
    return most / mean if mean else None
