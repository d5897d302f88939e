"""Where the program runs — the one module that asks JAX.

Two questions every layer used to answer for itself with a
``jax.default_backend()`` string compare:

* ``device_stamp()`` — what a result ran on. ``serve``'s stats JSON,
  ``health()["device"]``, ``train --json``, ``bench.py`` and
  ``chip_smoke.py`` all carry it, so no number can be read without its
  device.
* ``pallas_interpret()`` — whether the Pallas kernels compile (a TPU) or
  run in the interpreter (anything else; only the CPU test mesh gets
  there). Callers that must not run interpreted — the serving featurizer —
  take ``interpret`` as an argument and refuse instead.
"""

from __future__ import annotations


def device_stamp() -> dict:
    """``{"platform", "device_kind", "device_count"}`` as JAX reports them."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def on_tpu() -> bool:
    return device_stamp()["platform"] == "tpu"


def pallas_interpret() -> bool:
    """The ``interpret=`` value for a Pallas call on this process's device."""
    return not on_tpu()
