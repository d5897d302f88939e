"""The block-diffusion window program's share of its roofline inside the
traced window (%): the family's ``decode_cost`` of the forwards and live
row-forwards the slot lane counted between the profiler's marks, with the
expert weights counted from the program's own counter (of each layer the
experts the forward's positions really touched, ``moe_experts_touched``:
a block's masked positions start alike and route alike, so far fewer than
even routing of 4 x 16 positions would touch), weights once a forward, the
head where a row denoises, the K/V of the tokens the live rows hold; over
the device time of the configuration's decode program
(``paged_block_window``). Memory-bound. Nothing to read where the trace
holds no such program, or a program without the counter."""

from benchmark.metrics import _lib


def read(ctx):
    if ("paged_block_window" not in _lib.programs(ctx, "decode")
            or "moe_experts_touched" not in ctx["marks"].get("trace_start", {})):
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
