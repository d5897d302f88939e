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

The same call registers, once a process, the listener that turns JAX's own
monitoring events into ``compile`` spans and the compile counters
(``obs/trace.py``): what each executable cost to obtain, and whether the
persistent cache served it.
"""

from __future__ import annotations

import os
import threading

from fraud_detection_tpu.obs import trace
from fraud_detection_tpu.utils.device import on_tpu

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


# JAX's events for one executable (jax/_src/compiler.py
# ``compile_or_get_cached``, wrapped by jax/_src/interpreters/pxla.py in
# ``log_elapsed_time(..., event=BACKEND_COMPILE_EVENT)``), all on the
# compiling thread: on a hit of the persistent cache the retrieval's
# duration, then around load or build alike the backend duration with
# ``fun_name=``. A request that never consulted the cache reads as a miss.
_FETCHED = "/jax/compilation_cache/cache_retrieval_time_sec"
_OBTAINED = "/jax/core/compile/backend_compile_duration"
_SUMMED = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s"}

_pending = threading.local()    # the retrieval time of a hit, until it closes
_listening = False


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _FETCHED:
        _pending.fetch = duration
    elif event == _OBTAINED:
        fetch = getattr(_pending, "fetch", None)
        _pending.fetch = None
        trace.BOOT.compiled(str(kw.get("fun_name", "?")), duration,
                            hit=fetch is not None, fetch_sec=fetch or 0.0)
    elif event in _SUMMED:
        trace.BOOT.add_seconds(_SUMMED[event], duration)


def enable_persistent_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in effect."""
    import jax

    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
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
