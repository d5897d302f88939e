"""What the readers of the program's own spans share (``ctx["rowtrace"]``:
the desk's ``RowTracer`` ring as dicts cid/stage/start/duration_ms/detail,
every ``start`` on the ``time.time()`` clock and, but for the per-row
``explain`` span, the moment the span began).

Against a program that writes no such span or counter every function here
returns None.
"""

from __future__ import annotations

import bisect
import statistics
from typing import Dict, List, Optional


def in_window(ctx, stage: str) -> List[dict]:
    """The ``stage`` spans that began inside the measured window."""
    lo, hi = ctx["window"]
    return [s for s in ctx["rowtrace"]
            if s["stage"] == stage and lo <= s["start"] < hi]


def median_ms(ctx, stage: str) -> Optional[float]:
    """Median duration of the ``stage`` spans of the window; None where
    there are none or none has a duration (a span that is never fed)."""
    spans = in_window(ctx, stage)
    return (statistics.median(s["duration_ms"] for s in spans) or None
            if spans else None)


def detail(span: dict) -> Dict[str, float]:
    """``rows=80 padded=4096 bytes=65536`` -> the numbers by name."""
    out = {}
    for part in (span["detail"] or "").split():
        key, _, value = part.partition("=")
        try:
            out[key] = float(value)
        except ValueError:
            pass
    return out


def tails_ms(ctx) -> List[float]:
    """Per explained row whose annotation was stamped in the window: the
    ``annotate`` event less the end stamp of the row's own ``explain`` span
    (the one span whose ``start`` is its end): the wait for the slowest row
    of its micro-batch, plus delivery."""
    lo, hi = ctx["window"]
    ended = {s["cid"]: s["start"] for s in ctx["rowtrace"]
             if s["stage"] == "explain"
             and (s["detail"] or "").startswith("slot=")}
    return [1e3 * (s["start"] - ended[s["cid"]]) for s in ctx["rowtrace"]
            if s["stage"] == "annotate" and s["ok"] and s["cid"] in ended
            and lo <= s["start"] < hi]


def loop_host_ms(ctx) -> List[float]:
    """Per slot-lane iteration of the window: ``slot_iter`` less the
    ``slot_fetch`` and ``prefill`` spans that began inside it, which is the
    time the loop itself kept the chip waiting. The loop is one thread, so a
    span that begins inside an iteration ends inside it."""
    iters = sorted(in_window(ctx, "slot_iter"), key=lambda s: s["start"])
    if not iters:
        return []
    starts = [s["start"] for s in iters]
    waited = [0.0] * len(iters)
    for s in ctx["rowtrace"]:
        if s["stage"] in ("slot_fetch", "prefill"):
            j = bisect.bisect_right(starts, s["start"]) - 1
            if j >= 0 and s["start"] < starts[j] + iters[j]["duration_ms"] / 1e3:
                waited[j] += s["duration_ms"]
    return [it["duration_ms"] - w for it, w in zip(iters, waited)]


def steps_share_pct(ctx, key: str) -> Optional[float]:
    """Share of the window's slot-steps (decode steps x slots) that the slot
    lane's ``snapshot()`` counts under ``key``, open to close."""
    marks = ctx["marks"]
    if not all(label in marks and key in marks[label]
               for label in ("open", "close")):
        return None
    steps = marks["close"]["decode_steps"] - marks["open"]["decode_steps"]
    if steps <= 0:
        return None
    return (100.0 * (marks["close"][key] - marks["open"][key])
            / (steps * marks["open"]["slots"]))
