"""Tokens a live row commits a forward, for a model that generates by
diffusion over blocks: ``diffusion_tokens_committed`` (positions committed
by denoising) over ``diffusion_row_forwards`` (forwards of live rows, the
commit forwards that write a block's K/V included), open to close. With
blocks of 4, 2 denoising steps and a commit forward of its own a block
takes 3 forwards for its 4 tokens: 4/3 (a little more, as a row's last
block skips its commit forward, and a little less where the prompt's tail
fills part of the first block); a commit fused into the next block's first
denoising forward would read 2. Nothing to read for a program without the
counters, or a model that decodes a token a step."""

from benchmark.metrics import _lib


def read(ctx):
    if "diffusion_row_forwards" not in ctx["marks"].get("open", {}):
        return None
    rows = _lib.delta(ctx, "open", "close", "diffusion_row_forwards")
    tokens = _lib.delta(ctx, "open", "close", "diffusion_tokens_committed")
    return tokens / rows if rows else None
