"""What the readers of set-up's spans share (``ctx["rowtrace"]``: the ring
starts with the process's boot log, so it holds the five ``setup_*`` phases
and one ``compile`` span per executable the process obtained, detail
``fn=<name> hit=<0|1> fetch_ms=<n>``, each with the ``start`` it really had).

"Before the open" is everything of the process up to the window's open: one
process is one run, so that is the run's set-up; in a test process that ran
other cells first the sums include theirs. Only the spans' own clock and
``ctx["window"]`` are read, never a mark.

Against a program that writes no such span every function here returns None.
"""

from __future__ import annotations

from typing import List, Optional

from benchmark.metrics._spans import detail, in_window


def before_open(ctx, stage: str) -> List[dict]:
    """The ``stage`` spans that began before the measured window opened."""
    opened = ctx["window"][0]
    return [s for s in ctx["rowtrace"]
            if s["stage"] == stage and s["start"] < opened]


def seconds(spans: List[dict]) -> Optional[float]:
    """Summed duration; None where there is no span to sum."""
    return sum(s["duration_ms"] for s in spans) / 1e3 if spans else None


def built(spans: List[dict]) -> List[dict]:
    """The ``compile`` spans the persistent cache did not serve."""
    return [s for s in spans if not detail(s).get("hit")]


def compiles_in_window(ctx) -> Optional[int]:
    """``compile`` spans that began inside the window; None where the ring
    holds no span of set-up at all (a program that writes none)."""
    if not any(s["stage"].startswith(("compile", "setup_"))
               for s in ctx["rowtrace"]):
        return None
    return len(in_window(ctx, "compile"))
