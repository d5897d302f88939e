"""Model FLOPs of every token prefilled and decoded in the window over the
window times the chip's peak (%)."""

from benchmark import counts
from benchmark.metrics import _lib


def read(ctx):
    dec = _lib.decode_cost(ctx, "open", "close", "window")
    pre = _lib.prefill_cost(ctx, "window")
    if dec is None and pre is None:
        return None
    flops = (dec[0] if dec else 0.0) + (pre[0] if pre else 0.0)
    peak = counts.peaks(ctx["device_kind"])["flops_per_s"]
    return 100.0 * flops / (ctx["seconds"] * peak)
