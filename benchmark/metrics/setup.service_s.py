"""Seconds the slot service took to build: the newest ``setup_service`` span
before the window opened (decoder, the preamble's prefill, the warm-up)."""

from benchmark.metrics import _setup


def read(ctx):
    built = _setup.before_open(ctx, "setup_service")
    if not built:
        return None
    return max(built, key=lambda s: s["start"])["duration_ms"] / 1e3
