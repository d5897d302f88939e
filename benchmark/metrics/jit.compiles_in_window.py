"""Executables obtained inside the measured window: the count of ``compile``
spans that began in it. 0 is the sound reading; each one is a request that
waited for a program."""

from benchmark.metrics import _setup


def read(ctx):
    return _setup.compiles_in_window(ctx)
