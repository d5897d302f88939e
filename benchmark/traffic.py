"""The one load generator. A traffic mix is a JSON file of parameters under
``benchmark/traffic/``; this module turns it into a plan (which rows, how
long, due when) and paces the plan into the desk's input topic, open loop.

Equal work in every seed: the arrival offsets, the transcript lengths and the
order they come in follow from the mix file alone (its own ``draw_seed``);
``--seed`` chooses the texts (and, in the runner, all weights). Every row carries its
due time: the tick of the fixed 5 ms grid it was scheduled on, so latency
runs from when a row was due, and the feeder reports how late it really ran.

Mix file keys::

    draw_seed   seed of the arrival draw, recorded so the draw is fixed
    tick_ms     pacing grid; rows due inside a tick go out as one burst
    preroll_s   traffic before the window opens (brings queues to steady state)
    settle_s    longest wait, after the window closes, for rows due in it
    scam_share  share of rows drawn from dialogues the classifier flags
    text        {"source": "corpus"}: dialogues as the corpus makes them, or
                {"source": "joined", "length_bytes": {"<bytes>": weight, ..}}:
                same-label dialogues joined turn after turn and cut to exactly
                <bytes>; counts follow the weights by largest remainder
    pool        distinct texts (rows cycle through them); absent = one a row
    arrivals    list of {"process": "uniform"|"poisson", "rate_per_s",
                "from_s", "to_s"?} segments, offsets from the start of pre-roll;
                a rate is a number or {"of": "<config key>", "times": f}
    follow      what the run waits for after the window closes, up to
                settle_s: "frames", "annotations" or "none"
"""

from __future__ import annotations

import json
import random
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from benchmark.corpus import generate_corpus


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for key in ("draw_seed", "tick_ms", "preroll_s", "scam_share", "text",
                "arrivals"):
        if key not in mix:
            raise KeyError(f"traffic mix {path} lacks {key!r}")
    return mix


@dataclass
class Plan:
    """Which rows are due when. ``due_s`` is relative to the start of the
    pre-roll and lies on the tick grid; the window is [open_s, close_s)."""

    due_s: np.ndarray                 # (N,) float64, non-decreasing
    tick_s: float
    open_s: float
    close_s: float
    settle_s: float
    scam: np.ndarray                  # (N,) bool
    pool_of_row: np.ndarray           # (N,) index into the text pool
    pool_scam: np.ndarray             # (P,) bool
    pool_len: np.ndarray              # (P,) transcript bytes; 0 = as the corpus has it

    @property
    def in_window(self) -> np.ndarray:
        return (self.due_s >= self.open_s) & (self.due_s < self.close_s)


def _rate(value, cfg: Optional[dict]) -> float:
    """A rate is a number, or ``{"of": "<dotted key of the configuration>",
    "times": f}``: a share of a rate the configuration's file states (its
    own measured knee), so that one mix serves configurations of different
    capacity."""
    if not isinstance(value, dict):
        return float(value)
    node = cfg or {}
    for part in value["of"].split("."):
        node = node[part]
    return float(node) * float(value.get("times", 1.0))


def _offsets(arrivals: Sequence[dict], end_s: float, draw_seed: int,
             cfg: Optional[dict] = None) -> np.ndarray:
    out = []
    for k, seg in enumerate(arrivals):
        a = float(seg.get("from_s", 0.0))
        b = min(float(seg.get("to_s", end_s)), end_s)
        rate = _rate(seg["rate_per_s"], cfg)
        if b <= a or rate <= 0:
            continue
        if seg["process"] == "uniform":
            out.append(a + np.arange(int(np.floor((b - a) * rate))) / rate)
        elif seg["process"] == "poisson":
            rng = random.Random(draw_seed * 1000003 + k)
            t, seg_out = a, []
            while True:
                t += rng.expovariate(rate)
                if t >= b:
                    break
                seg_out.append(t)
            out.append(np.asarray(seg_out))
        else:
            raise ValueError(f"unknown arrival process {seg['process']!r}")
    return np.sort(np.concatenate(out)) if out else np.empty(0)


def _largest_remainder(weights: Dict[str, float], n: int) -> Dict[int, int]:
    total = float(sum(weights.values()))
    exact = {int(k): n * w / total for k, w in weights.items()}
    counts = {k: int(v) for k, v in exact.items()}
    short = n - sum(counts.values())
    for k in sorted(exact, key=lambda k: (counts[k] - exact[k], k))[:short]:
        counts[k] += 1
    return counts


def build_plan(mix: dict, seconds: float, cfg: Optional[dict] = None) -> Plan:
    """Pure arithmetic from the mix file alone: due times, lengths and the
    order the lengths come in are the same for every ``--seed`` (another
    order packs the slots differently and moved ``explanations_per_s`` by
    2 %, my chip run, PR 25). The seed chooses the texts."""
    tick = mix["tick_ms"] / 1e3
    open_s = float(mix["preroll_s"])
    close_s = open_s + float(seconds)
    off = _offsets(mix["arrivals"], close_s, int(mix["draw_seed"]), cfg)
    due = np.ceil(off / tick - 1e-9) * tick
    n = len(due)
    if n == 0:
        raise ValueError("the mix schedules no row")
    rng = random.Random(int(mix["draw_seed"]) ^ 0xA5A5)
    pool = min(int(mix.get("pool", n)), n)
    n_scam = int(round(pool * float(mix["scam_share"])))
    pool_scam = np.zeros(pool, bool)
    pool_scam[:n_scam] = True
    pool_len = np.zeros(pool, np.int64)
    if mix["text"]["source"] == "joined":
        lens: List[int] = []
        for length, count in sorted(_largest_remainder(
                mix["text"]["length_bytes"], pool).items()):
            lens += [length] * count
        rng.shuffle(lens)
        pool_len[:] = lens
    elif mix["text"]["source"] != "corpus":
        raise ValueError(f"unknown text source {mix['text']['source']!r}")
    order = list(range(pool))
    rng.shuffle(order)
    pool_of_row = np.asarray(order, np.int64)[np.arange(n) % pool]
    return Plan(due, tick, open_s, close_s, float(mix.get("settle_s", 60.0)),
                pool_scam[pool_of_row], pool_of_row, pool_scam, pool_len)


def _joined(texts: Sequence[str], start: int, length: int) -> str:
    """Dialogue ``start`` and as many of the following as it takes, turn
    after turn, cut to exactly ``length`` bytes (the corpus is ASCII)."""
    parts, have, i = [], 0, start
    while have < length:
        parts.append(texts[i % len(texts)])
        have += len(parts[-1]) + 1
        i += 1
    return "\n".join(parts)[:length]


def build_texts(plan: Plan, seed: int,
                flags: Callable[[Sequence[str]], Sequence[bool]]) -> List[str]:
    """The pool's texts from the seed. ``flags(texts)`` is the trained
    classifier's verdict: scam entries are drawn only from texts it flags
    and benign entries from texts it passes, so the flagged stream is the
    one the mix states."""
    rng = random.Random(int(seed) ^ 0x5EED)
    out: List[Optional[str]] = [None] * len(plan.pool_scam)
    for want in (True, False):
        need = [i for i in range(len(out)) if plan.pool_scam[i] == want]
        attempt = 0
        while need:
            if attempt == 8:
                raise RuntimeError(
                    f"classifier {'flags' if want else 'passes'} too few "
                    f"{'scam' if want else 'benign'} texts: {len(need)} "
                    f"entries unfilled after {attempt} draws")
            base = [d.text for d in generate_corpus(
                n=max(256, 2 * len(need)), seed=rng.getrandbits(31),
                scam_fraction=1.0 if want else 0.0)]
            cand = [(_joined(base, j, int(plan.pool_len[i]))
                     if plan.pool_len[i] else base[j % len(base)])
                    for j, i in enumerate(need)]
            verdict = flags(cand)
            left = []
            for i, text, v in zip(need, cand, verdict):
                if bool(v) == want:
                    out[i] = text
                else:
                    left.append(i)
            need, attempt = left, attempt + 1
    return out  # type: ignore[return-value]


def payload(text: str) -> bytes:
    return json.dumps({"text": text}).encode()


def row_key(i: int) -> bytes:
    return b"%d" % i


@dataclass
class Feeder:
    """Paces a plan into ``topic`` from one thread. After ``run`` returns,
    ``sent_s[i]`` is when row i really went out (relative to t0)."""

    plan: Plan
    payloads: Sequence[bytes]
    producer: object
    topic: str
    clock: Callable[[], float] = time.time
    sleep: Callable[[float], None] = time.sleep
    t0: float = 0.0
    sent_s: np.ndarray = field(default_factory=lambda: np.empty(0))

    def run(self, t0: float) -> None:
        plan, pool = self.plan, self.payloads
        self.t0 = t0
        due = plan.due_s
        sent = np.full(len(due), np.nan)
        # One burst per distinct due tick.
        starts = np.flatnonzero(np.r_[True, due[1:] != due[:-1]])
        ends = np.r_[starts[1:], len(due)]
        rows = plan.pool_of_row.tolist()
        batch = getattr(self.producer, "produce_batch", None)
        for a, b in zip(starts.tolist(), ends.tolist()):
            wait = t0 + due[a] - self.clock()
            if wait > 0:
                self.sleep(wait)
            items = [(pool[rows[i]], row_key(i)) for i in range(a, b)]
            if batch is not None:
                batch(self.topic, items)
            else:
                for value, key in items:
                    self.producer.produce(self.topic, value, key=key)
            sent[a:b] = self.clock() - t0
        self.sent_s = sent

    def late_ms(self, mask: Optional[np.ndarray] = None) -> np.ndarray:
        late = (self.sent_s - self.plan.due_s) * 1e3
        return late[mask] if mask is not None else late
