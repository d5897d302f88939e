"""From a ``jax.profiler`` trace to numbers. The yardstick: later PRs may not
edit this file.

``capture`` records a trace (Python-call tracing off: it slows the host and
bloats the file), ``load`` turns the ``.xplane.pb`` into a small plain dict,
and ``reduce`` works on that dict alone, so the arithmetic is checked on a
recorded fixture (tests/benchmark/fixtures/trace_v5e.json).

What a TPU v5e trace holds (looked at by hand, PR 25): planes
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per program
run, named ``jit_<fn>(<hash>)``) and ``XLA Ops`` (one event per HLO op run,
named by its HLO text ``%fusion.3 = bf16[..]{..} fusion(..)``), and a plane
``/host:CPU`` with a line per host thread (``python3``, ``main/<tid>``,
``pjrt-tpu-tasks/<tid>`` ...). Device and host times share one clock to
about a millisecond.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import time
from typing import Dict, List, Optional, Tuple

_SUFFIX = re.compile(r"\.\d+$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_UNSAFE = re.compile(r"[^A-Za-z0-9_.:-]")
_THREAD_ID = re.compile(r"/\d+$")


class capture:
    """``with capture(dir) as cap: ...`` then ``cap.path`` is the xplane
    file and ``cap.window_s`` the traced wall time; ``cap.discard()``
    removes the files again (a trace is tens of MB)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.path: Optional[str] = None
        self.window_s = 0.0
        self._t0 = 0.0

    def start(self) -> "capture":
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._t0 = time.time()
        return self

    def stop(self) -> None:
        import jax

        self.window_s = time.time() - self._t0
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.log_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        self.path = max(found, key=os.path.getmtime) if found else None

    def discard(self) -> None:
        shutil.rmtree(self.log_dir, ignore_errors=True)

    __enter__ = start

    def __exit__(self, *exc) -> None:
        self.stop()


def load(path: str) -> dict:
    """``{"devices": {plane: {"modules": [[name, start_ns, dur_ns]..],
    "ops": [...]}}, "host": [[thread, name, start_ns, dur_ns]..]}``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = out["devices"].setdefault(plane.name,
                                            {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                thread = _THREAD_ID.sub("", line.name) or "host"
                out["host"] += [
                    [thread, e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if not e.name.startswith("$")]
    return out


def program_name(module_event: str) -> str:
    """``jit_paged_decode_window(123)`` -> ``paged_decode_window``."""
    name = module_event.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_label(hlo_text: str) -> str:
    """``%fusion.3 = bf16[16,2048]{1,0:T(8,128)} fusion(..)`` ->
    ``fusion_bf16_16_2048_``: the op without its running number, and the
    shape it produces without layouts."""
    name, _, rest = hlo_text.partition(" = ")
    name = _SUFFIX.sub("", name.lstrip("%"))
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[:i + 1]
                break
    else:
        rest = rest.split(" ", 1)[0]
    return _UNSAFE.sub("_", f"{name}_{rest}")


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _self_times(ops: List[list]) -> List[float]:
    """Each op's own time: its duration less what the ops nested inside it
    (a ``while`` around its body) cover."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [op[2] for op in ops]
    stack: List[int] = []
    for i in order:
        start, end = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack and end <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return [max(0.0, t) for t in own]


def reduce(trace: dict, window_s: float, top: int = 10) -> dict:
    """Busy time (averaged over the device planes), per-program and per-op
    device seconds, program run counts, and the longest idle gaps of the
    first device, each named by what the host was doing in its middle (or,
    where nothing was recorded there, during most of it)."""
    devices = trace["devices"]
    if not devices:
        return {"busy_s": 0.0, "window_s": window_s, "programs": {},
                "runs": {}, "ops": {}, "device_ops": [], "idle_gaps": []}
    busy_total = 0.0
    programs: Dict[str, float] = {}
    runs: Dict[str, int] = {}
    ops_s: Dict[str, float] = {}
    gaps: List[Tuple[float, float, float]] = []
    for d, (_, dev) in enumerate(sorted(devices.items())):
        mods = sorted(dev["modules"], key=lambda e: e[1])
        ops = dev["ops"] or mods
        merged = _union([(e[1], e[1] + e[2]) for e in ops])
        busy_total += sum(b - a for a, b in merged) / 1e9
        if d == 0:
            gaps = [(a2 - b1, b1, a2)
                    for (_, b1), (a2, _) in zip(merged, merged[1:])]
        for name, _, dur in mods:
            prog = program_name(name)
            runs[prog] = runs.get(prog, 0) + 1
        starts = [m[1] for m in mods]
        for op, own in zip(dev["ops"], _self_times(dev["ops"])):
            j = bisect.bisect_right(starts, op[1]) - 1
            inside = j >= 0 and op[1] < mods[j][1] + mods[j][2]
            prog = program_name(mods[j][0]) if inside else "unattributed"
            programs[prog] = programs.get(prog, 0.0) + own / 1e9
            label = f"{prog}:_{op_label(op[0])}"[:64]
            ops_s[label] = ops_s.get(label, 0.0) + own / 1e9
        if not dev["ops"]:
            for name, _, dur in mods:
                prog = program_name(name)
                programs[prog] = programs.get(prog, 0.0) + dur / 1e9
    host = trace["host"]
    named = []
    for length, lo, hi in sorted(gaps, reverse=True)[:top]:
        mid = (lo + hi) / 2.0
        holding = [h for h in host if h[2] <= mid < h[2] + h[3]]
        if holding:                       # the innermost call at the middle
            what = min(holding, key=lambda h: h[3])
        else:                             # or whatever overlaps the gap most
            over = [(min(hi, h[2] + h[3]) - max(lo, h[2]), h) for h in host]
            what = max(over, key=lambda o: o[0], default=(0, None))
            what = what[1] if what[0] > 0 else None
        label = (_UNSAFE.sub("_", f"{what[0]}:_{what[1]}")[:64]
                 if what else "host:_nothing_recorded")
        named.append([label, length / 1e9])
    n = len(devices)
    return {"busy_s": busy_total / n, "window_s": window_s,
            "programs": {k: v / n for k, v in programs.items()},
            "runs": {k: v / n for k, v in runs.items()},
            "ops": ops_s,
            "device_ops": [[k, v] for k, v in sorted(
                ops_s.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": named}
