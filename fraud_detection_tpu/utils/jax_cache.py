"""The persistent XLA compilation cache — one rule, every entry point.

``serve``, ``train``, ``bench.py``, the test suite and ``chip_smoke.py`` all
call :func:`enable_persistent_compile_cache` before their first compile, so
a second process start finds the programs the first one built (the 18-layer
LLM programs and the depth-unrolled tree builders cost far more to compile
than to run).

Where the cache lives is decided from outside: if ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and this module sets no directory in code.
Otherwise the cache is ``.jax_cache/`` at the root of the checkout
(git-ignored) — a fixed path, because the path is part of what makes a
cache findable by the next process.
"""

from __future__ import annotations

import os

from fraud_detection_tpu.utils.device import on_tpu

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_persistent_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in effect."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    if on_tpu():
        # JAX's default threshold (1.0 s) would leave the serving pad
        # ladder's many sub-second programs out of the cache, and a warm
        # start on the chip would compile every rung again. The CPU keeps
        # the default: there the cache serves the test suite, whose
        # thousands of sub-second programs would each pay a serialize and a
        # write on a cold run.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
