"""The paged decode program's share of its roofline inside the traced window
(%), with the expert weights counted from the program's own counter: of each
expert layer the held experts that a step's rows really touched
(``moe_experts_touched``), where ``paged_decode_window_roofline`` counts the
expectation under even routing. A router that is not even touches fewer, so
this share is the lower of the two. Nothing to read for a model without
routed experts, or a program without the counter."""

from benchmark.metrics import _lib


def read(ctx):
    if "moe_experts_touched" not in ctx["marks"].get("trace_start", {}):
        return None
    a, b = "trace_start", "trace_stop"
    steps, rows = _lib.delta(ctx, a, b, "decode_steps"), _lib.row_steps(ctx, a, b)
    ctxlen = _lib.mean_context(ctx, "trace_window")
    touched = _lib.delta(ctx, a, b, "moe_experts_touched")
    if not steps or not rows or ctxlen is None or not touched:
        return None
    cost = ctx["family"].decode_cost(ctx["cfg"], steps, rows, ctxlen,
                                     experts_touched=touched)
    return _lib.roofline_pct(ctx, cost, _lib.program_seconds(ctx, "decode"))
