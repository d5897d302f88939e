"""What the per-layer readers share. A reader is a file
``benchmark/metrics/<metric name>.py`` with ``read(ctx) -> float | None``;
``ctx`` is the dict ``benchmark/run.py`` builds after a traced run:

``cfg`` ``mix`` ``device_kind`` ``seconds``   the cell and the chip
``family``        the configuration's explainer family (``explainers/<model_type>.py``):
                  ``decode_cost`` and ``prefill_cost`` here take its counts
``window``        (open, close) of the measured window, ``time.time()`` clock
``trace_window``  (start, stop) of the profiler's window, same clock
``late_ms``       feeder lateness of every row due in the window
``rowtrace``      the engine's stage spans: dicts cid/stage/start/duration_ms/detail
``spans``         the benchmark's own spans: (name, start, seconds, attrs)
``marks``         slot-lane ``snapshot()`` at "open", "close", "trace_start", "trace_stop"
``tickets``       per explain request: prompt_len, n_out, submitted, first_token (time.time() clock)
``rows_delivered``  frames stamped inside the window
``pairs_per_row``   mean (bucket, count) pairs of the mix's texts
``trace``         ``trace_reduce.reduce`` of the profiler's trace, or None

A reader that finds nothing to read returns None, never 0.
"""

from __future__ import annotations

import math
import statistics

import numpy as np
from typing import Dict, List, Optional

from benchmark import counts


def percentile(values, q: float) -> Optional[float]:
    vals = np.sort(np.asarray(values, np.float64))
    if not len(vals):
        return None
    return float(vals[max(0, math.ceil(q * len(vals) - 1e-9) - 1)])


def late_p99(ctx) -> Optional[float]:
    return percentile(ctx["late_ms"], 0.99)


def _in(ctx, start: float, which: str = "window") -> bool:
    lo, hi = ctx[which]
    return lo <= start < hi


def stage_ms(ctx, stages) -> Optional[float]:
    """Median per batch of the summed duration of ``stages``."""
    per_batch: Dict[str, float] = {}
    for s in ctx["rowtrace"]:
        if s["stage"] in stages and _in(ctx, s["start"]):
            per_batch[s["cid"]] = per_batch.get(s["cid"], 0.0) + s["duration_ms"]
    return statistics.median(per_batch.values()) if per_batch else None


def programs(ctx, role: str) -> List[str]:
    return list(ctx["cfg"]["desk"]["trace_programs"].get(role, []))


def program_seconds(ctx, role: str) -> Optional[float]:
    if not ctx["trace"]:
        return None
    t = sum(ctx["trace"]["programs"].get(p, 0.0) for p in programs(ctx, role))
    return t if t > 0 else None


def program_runs(ctx, role: str) -> float:
    return sum(ctx["trace"]["runs"].get(p, 0.0) for p in programs(ctx, role))


def delta(ctx, a: str, b: str, key: str) -> Optional[float]:
    m = ctx["marks"]
    if a not in m or b not in m:
        return None
    return float(m[b][key]) - float(m[a][key])


def row_steps(ctx, a: str, b: str) -> Optional[float]:
    """Active row-steps between two marks, from ``occupancy`` (a running
    mean over all decode steps) and ``decode_steps``."""
    m = ctx["marks"]
    if a not in m or b not in m:
        return None
    slots = m[a]["slots"]

    def occ_sum(s):
        return (s["occupancy"] or 0.0) * s["decode_steps"] * slots

    return occ_sum(m[b]) - occ_sum(m[a])


def mean_context(ctx, which: str) -> Optional[float]:
    """Mean positions a decoding row attends, over the requests in flight
    inside ``which``: its prompt plus half of what it emits."""
    lo, hi = ctx[which]
    live = [t for t in ctx["tickets"]
            if t["first_token"] is not None and t["first_token"] < hi
            and (t["done"] is None or t["done"] >= lo)]
    if not live:
        return None
    return statistics.fmean(t["prompt_len"] + t["n_out"] / 2.0 for t in live)


def decode_cost(ctx, a: str, b: str, which: str):
    steps, rows = delta(ctx, a, b, "decode_steps"), row_steps(ctx, a, b)
    ctxlen = mean_context(ctx, which)
    if not steps or not rows or ctxlen is None:
        return None
    return ctx["family"].decode_cost(ctx["cfg"], steps, rows, ctxlen)


def prefill_cost(ctx, which: str):
    lo, hi = ctx[which]
    prefix = ctx["marks"]["open"]["prefix_pages"] and ctx["prefix_len"]
    flops = nbytes = 0.0
    n = 0
    for t in ctx["tickets"]:
        if t["first_token"] is not None and lo <= t["first_token"] < hi:
            f, b = ctx["family"].prefill_cost(ctx["cfg"], prefix,
                                              t["prompt_len"] - prefix)
            flops, nbytes, n = flops + f, nbytes + b, n + 1
    return (flops, nbytes, n) if n else None


def roofline_pct(ctx, cost, seconds: Optional[float]) -> Optional[float]:
    if cost is None or not seconds:
        return None
    least, _bound = counts.roofline(cost[0], cost[1], ctx["device_kind"])
    return 100.0 * least / seconds


def score_rows_in_trace(ctx) -> float:
    return float(sum(
        int(s["detail"].split("=", 1)[1]) for s in ctx["rowtrace"]
        if s["stage"] == "poll" and _in(ctx, s["start"], "trace_window")
        and (s["detail"] or "").startswith("rows=")))
