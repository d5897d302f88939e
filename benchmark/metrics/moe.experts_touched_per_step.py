"""Distinct held experts whose weights a decode step read, per expert
layer: ``moe_experts_touched`` over ``decode_steps`` times the expert layers,
open to close. Beside it PERF.md gives the expectation under even routing
for the rows a step held (the family's ``expected_experts_touched``)."""

from benchmark.metrics import _lib


def read(ctx):
    cfg, marks = ctx["cfg"], ctx["marks"]
    if "moe_experts_touched" not in marks.get("open", {}):
        return None
    layers = cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)
    steps = _lib.delta(ctx, "open", "close", "decode_steps")
    touched = _lib.delta(ctx, "open", "close", "moe_experts_touched")
    return touched / (steps * layers) if steps and layers else None
