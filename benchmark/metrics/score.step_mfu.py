"""Scoring FLOPs of every row delivered in the window over the window times
the chip's peak (%). Tiny by nature: the stream is host-bound."""

from benchmark import counts


def read(ctx):
    rows = ctx["rows_delivered"]
    if not rows:
        return None
    flops, _ = counts.score_cost(ctx["cfg"]["desk"]["classifier"], rows,
                                 ctx["pairs_per_row"])
    peak = counts.peaks(ctx["device_kind"])["flops_per_s"]
    return 100.0 * flops / (ctx["seconds"] * peak)
