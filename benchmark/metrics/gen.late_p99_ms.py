"""p99 of how late the feeder sent the rows due in the window (ms). One
reader for ``gen.late_p99_ms.stream`` and ``gen.late_p99_ms.explain``: the
quantity is split only by the end-to-end metric it moves."""

from benchmark.metrics import _lib


def read(ctx):
    return _lib.late_p99(ctx)
