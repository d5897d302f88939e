"""Seconds the process spent obtaining executables before the window
opened: the summed ``compile`` spans, loaded from the persistent cache or
built (``_setup.py`` says what "before the open" holds)."""

from benchmark.metrics import _setup


def read(ctx):
    return _setup.seconds(_setup.before_open(ctx, "compile"))
