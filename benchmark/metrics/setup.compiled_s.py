"""Seconds of ``setup.program_load_s`` that the persistent cache did not
serve (``hit=0``): about 0 on a warm start, most of it on a cold one."""

from benchmark.metrics import _setup


def read(ctx):
    spans = _setup.before_open(ctx, "compile")
    if not spans:
        return None
    return _setup.seconds(_setup.built(spans)) or 0.0
