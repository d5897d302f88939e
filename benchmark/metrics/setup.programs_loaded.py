"""Executables the process obtained before the window opened: the count of
``compile`` spans, every eager op's small program among them."""

from benchmark.metrics import _setup


def read(ctx):
    return len(_setup.before_open(ctx, "compile")) or None
