"""Share of the held experts whose weights a decode step read (%):
``moe_experts_touched`` over ``moe_expert_slots`` (decode steps x expert
layers x experts held: what the first could at most have been), open to
close. It is the share of the experts' bytes a decode step reads, and it
grows with the rows a step holds: 63.5 under even routing where 16 live rows
pick 4 of 64. It needs no key of any family's configuration. Nothing to read
for a program without the second counter."""

from benchmark.metrics import _lib


def read(ctx):
    if "moe_expert_slots" not in ctx["marks"].get("open", {}):
        return None
    slots = _lib.delta(ctx, "open", "close", "moe_expert_slots")
    touched = _lib.delta(ctx, "open", "close", "moe_experts_touched")
    return 100.0 * touched / slots if slots else None
