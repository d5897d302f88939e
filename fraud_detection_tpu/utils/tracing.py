"""The profiler hook: ``device_trace`` wraps ``jax.profiler`` so a real XLA
trace can be captured around any region with one env var
(FRAUD_TPU_PROFILE_DIR) and inspected in TensorBoard/Perfetto.

Spans and counters live in ``fraud_detection_tpu.obs`` (``obs/trace.py``
``RowTracer``): one mechanism, whose context-manager spans are also written
into a capture taken here as ``fraud/<stage>`` annotations.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Iterator, Optional


@contextmanager
def device_trace(name: str = "trace", out_dir: Optional[str] = None) -> Iterator[None]:
    """Capture a JAX/XLA profiler trace around a region.

    Active only when ``out_dir`` or FRAUD_TPU_PROFILE_DIR is set — zero cost
    otherwise, so call sites can leave it in production paths.
    """
    target = out_dir or os.getenv("FRAUD_TPU_PROFILE_DIR")
    if not target:
        yield
        return
    import jax

    with jax.profiler.trace(os.path.join(target, name)):
        yield
