"""Slotserve — slot-based continuous-batching on-pod explanation service.

One persistent pool of KV pages under a fixed set of decode slots; newly
flagged rows admit into free slots at iteration boundaries (no fixed-batch
barrier), rows retire per-slot at EOS, and every flagged row is explained
or accounted (docs/explain_serving.md).
"""

from fraud_detection_tpu.explain.slotserve.decode import PagedSlotDecoder
from fraud_detection_tpu.explain.slotserve.service import (
    DROPPED_MARKER,
    UNAVAILABLE_MARKER,
    SlotServeService,
    make_slot_explain_hook,
)

__all__ = [
    "PagedSlotDecoder",
    "SlotServeService",
    "make_slot_explain_hook",
    "DROPPED_MARKER",
    "UNAVAILABLE_MARKER",
]
