"""Seconds of classifier fits before the window opened: the summed
``setup_train`` spans (``app/train.py``, one a model)."""

from benchmark.metrics import _setup


def read(ctx):
    return _setup.seconds(_setup.before_open(ctx, "setup_train"))
