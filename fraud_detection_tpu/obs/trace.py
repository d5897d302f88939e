"""Tracecraft: end-to-end row/batch tracing for the serving pipeline.

The pipeline grew deep — admission -> batch formation -> lane ``_prepare``/
``_launch`` -> device dispatch -> delivery -> async annotation -> DLQ —
and its only windows were per-component ``health()`` aggregates: when the
headline moves, nothing says WHICH stage, which worker, which rung. This
module adds the missing attribution layer, Dapper-style but sized for a
50k rows/sec hot loop:

* A **correlation id is minted per polled batch** (``<worker>-<seq>``) and
  every row derives a stable id from it (``<batch>:<partition>:<offset>``)
  — the same coordinates DLQ/shed records already carry, so a dead-lettered
  row joins back to its spans by construction.
* **Spans are batch-granular** ("poll", "admit", "launch" with its
  children "featurize" and "upload", "device", "deliver") with
  **row-granular events** for the interesting minority (shed, dlq, flag,
  annotate): per-row spans for every clean row would cost more than the
  work they measure; per-batch spans plus row events keep the overhead
  under the tracing budget while still giving every flagged/shed/DLQ'd row
  a complete poll->terminal chain by id. A FLAGGED row's chain goes on
  past the batch's terminal, under its row id: "lane_wait" (annotation
  lane), "slot_wait" and "prefill" (slot lane), the per-row "explain" and
  the "annotate" event, tiling flag -> annotation
  (docs/observability.md). The slot lane's loop writes one chain per
  iteration (``slot-<iteration>``).
* **A span's ``start`` is when it began**, whether it was timed by a
  context manager or recorded after the fact from a measured duration.
  Spans opened as context managers are also written into the profiler's
  trace as ``fraud/<stage>`` (``jax.profiler.TraceAnnotation``), so a
  capture holds the program's spans beside the device's ops, on one clock.
* Spans buffer **batch-locally** (no shared state while the batch is in
  flight) and commit into a fixed-size ring in ONE append per batch at the
  terminal (deliver/abort). The ring drops OLDEST on overflow and counts
  the drop — it never blocks the hot path, and the counter makes the loss
  an explicit recorded fact.
* **Head sampling with forced keeps**: each batch draws its keep/discard
  fate at mint time (seeded RNG, ``sample`` fraction), but a batch that
  turns out interesting — flagged, shed, dead-lettered, breaker-tripped,
  aborted — is kept REGARDLESS of the draw. Sampling controls the clean-
  traffic volume; accountability rows are always-on.
* **Exact accounting**: every span begun is ended (context managers +
  explicit abort on the engine's failure paths), and ``begun == ended`` is
  a pinned invariant under seeded chaos and fleet worker kills
  (tests/test_obs.py).
* Per-stage wall time also feeds one :class:`LatencySketch` per stage
  (bounded memory, lossless merge), independent of sampling — the fleet
  aggregation and the bench's ``stages`` attribution block read these, so
  p50/p99 per stage covers ALL batches, not the sampled subset.
* **Set-up is on the same chain.** Before any tracer exists the process
  trains, loads weights and obtains its executables; those spans (the five
  ``setup_*`` phases and one ``compile`` per executable, fed from
  ``jax.monitoring`` by ``utils/jax_cache.py``) go to a small process-wide
  **boot log** (``BOOT``), bounded and never blocking. A :class:`RowTracer`
  starts its ring with the boot log's spans, true ``start``s and all, and
  every live tracer receives each later one (which the log keeps too, for
  a tracer built later still) — a ``compile`` span that begins after
  ``StreamingClassifier.run()`` first polled is a request that paid for a
  program (``health()["compile"]["compiles_since_serving"]``).

Thread model: a batch's trace is owned by whichever thread is driving that
batch leg (engine driver, dispatch lane, annotation lane) — legs hand off
strictly FIFO, never concurrently. Tracer-global state (the ring, the
counters, the stage sketches) is guarded by one small lock held O(1) per
BATCH, not per row or per span.
"""

from __future__ import annotations

import random
import threading
import time
import weakref
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

from fraud_detection_tpu.sched.sketch import LatencySketch

# The span vocabulary (docs/observability.md). Batch stages carry
# durations; row events are instantaneous markers with a reason.
STAGE_POLL = "poll"          # batch minted from a poll (rows, wait)
STAGE_ADMIT = "admit"        # admission + poison screen (driver)
STAGE_LAUNCH = "launch"      # featurize + upload + device launch
STAGE_DEVICE = "device"      # blocking on device results
STAGE_DELIVER = "deliver"    # produce + flush + commit
STAGE_EXPLAIN = "explain"    # one LLM explain call (annotation lane)
STAGE_FEATURIZE = "featurize"  # child of launch: host decode + featurize
STAGE_UPLOAD = "upload"      # child of launch: pack + host->device placement
STAGE_LANE_WAIT = "lane_wait"  # flagged row queued on the annotation lane
STAGE_SLOT_WAIT = "slot_wait"  # request queued for a decode slot
STAGE_PREFILL = "prefill"    # one prompt's prefill, first token on the host
# The slot lane's loop, one chain per iteration (cid ``slot-<iteration>``):
# ``slot_iter`` contains the others, in this order.
STAGE_SLOT_ITER = "slot_iter"
STAGE_SLOT_ADMIT = "slot_admit"    # queue -> free slots, prefills inside
# inside a row's ``prefill``, where the model keeps a recurrent state: the
# preamble's snapshot (or zeros) copied into the slot's block
STAGE_SLOT_STATE_RESTORE = "slot_state_restore"
STAGE_SLOT_GROW = "slot_grow"      # page tables grown to cover the window
STAGE_SLOT_LAUNCH = "slot_launch"  # arguments placed, program enqueued
STAGE_SLOT_FETCH = "slot_fetch"    # blocked until the tokens are on the host
STAGE_SLOT_EMIT = "slot_emit"      # host replay of the window's tokens
STAGE_SLOT_RETIRE = "slot_retire"  # finished rows resolved, slots freed
# Set-up (cid ``setup``), where the work happens; and one ``compile`` per
# executable the process obtains (cid ``compile-<n>``, detail ``fn= hit=
# fetch_ms=``), recorded after the fact from JAX's own monitoring events:
# ``duration`` is the whole time to obtain it, loaded or built.
STAGE_SETUP_TRAIN = "setup_train"        # one classifier's fit (app/train.py)
STAGE_SETUP_PIPELINE = "setup_pipeline"  # ServingPipeline.from_checkpoint
STAGE_SETUP_SERVICE = "setup_service"    # SlotServeService.__init__, whole
STAGE_SETUP_PREAMBLE = "setup_preamble"  # inside it: the preamble's prefill
STAGE_SETUP_WARM = "setup_warm"          # inside it: PagedSlotDecoder.warm
STAGE_COMPILE = "compile"
EVENT_SHED = "shed"          # row diverted by admission control
EVENT_DLQ = "dlq"            # row dead-lettered (malformed/poison)
EVENT_FLAG = "flag"          # row classified non-benign
EVENT_ANNOTATE = "annotate"  # row's annotation produced (or failed)
EVENT_ABORT = "abort"        # batch abandoned (crash/flush-fail replay)
EVENT_ROW = "row"            # row delivered (record mode only: the full
                             # per-batch row census a trace RECORDING needs
                             # for exact replay — scenarios/record.py)


class Span(NamedTuple):
    """One recorded span/event. ``cid`` is the batch correlation id for
    batch stages and the row id (``<batch>:<part>:<off>``) for row
    events; ``detail`` is a small JSON-safe annotation (row counts,
    shed/DLQ reason, ...). A NamedTuple, not a dataclass: row events are
    created per flagged/shed row on the hot path and construction cost is
    the tracing overhead budget's biggest line item."""

    cid: str
    stage: str
    start: float            # wall-clock seconds (time.time domain): when
                            # the span BEGAN (docs/observability.md names
                            # the one end-stamped exception)
    duration_ms: float
    ok: bool = True
    detail: Optional[str] = None

    def as_dict(self) -> dict:
        return {"cid": self.cid, "stage": self.stage,
                "start": round(self.start, 6),
                "duration_ms": round(self.duration_ms, 4),
                "ok": self.ok, "detail": self.detail}


class _RowEvents(NamedTuple):
    """A batch of row events stored COMPACT: one ring entry carrying the
    rows' (partition, offset) int pairs instead of N materialized Spans —
    at a 50% flag rate the hot path would otherwise build ~2000 Span
    objects + cid strings per micro-batch, which alone blows the 5%
    tracing-overhead budget. Expansion to Spans (cid strings included)
    happens at read time (snapshot/chain), where nobody is counting
    microseconds."""

    prefix: str             # batch correlation id
    stage: str
    pairs: tuple            # ((partition, offset), ...)
    start: float
    ok: bool = True
    detail: Optional[str] = None

    def expand(self) -> List[Span]:
        return [Span(f"{self.prefix}:{p}:{o}", self.stage, self.start,
                     0.0, self.ok, self.detail) for p, o in self.pairs]


def _weight(entry) -> int:
    return len(entry.pairs) if type(entry) is _RowEvents else 1


class SpanRing:
    """Fixed-capacity span store: drop-OLDEST on overflow, drops counted,
    O(1) per append with one small lock — appends never wait on readers
    (snapshot copies under the same lock and returns). Entries are Spans
    or compact :class:`_RowEvents` blocks; capacity, depth, and the
    recorded/dropped counters all count SPANS (a dropped block counts
    every row event it carried — overflow honesty is span-granular)."""

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity            # entries held at most
        self._buf: List[Optional[object]] = [None] * capacity
        self._next = 0          # next write slot
        self._len = 0           # entries held
        self._spans = 0         # span-weight currently held
        self.recorded = 0       # spans ever appended
        self.dropped = 0        # spans overwritten before anyone read them
        self._lock = threading.Lock()

    def extend(self, entries: Sequence[object]) -> None:
        n = 0
        with self._lock:
            for e in entries:
                w = _weight(e)
                n += w
                if self._len == self.capacity:
                    old = self._buf[self._next]
                    ow = _weight(old)
                    self.dropped += ow
                    self._spans -= ow
                else:
                    self._len += 1
                self._buf[self._next] = e
                self._spans += w
                self._next = (self._next + 1) % self.capacity
            self.recorded += n

    def __len__(self) -> int:
        """Spans currently held (expanded count, not entries)."""
        with self._lock:
            return self._spans

    def snapshot(self) -> List[Span]:
        """Oldest -> newest expanded copy of the live spans."""
        with self._lock:
            if self._len < self.capacity:
                entries = self._buf[: self._len]
            else:
                entries = self._buf[self._next:] + self._buf[: self._next]
        out: List[Span] = []
        for e in entries:
            if type(e) is _RowEvents:
                out.extend(e.expand())
            else:
                out.append(e)
        return out


class BatchTrace:
    """One polled batch's trace context: batch-local span buffer plus the
    keep/sample fate. NOT thread-safe on its own — a batch leg is owned by
    exactly one thread at a time (driver -> lane -> driver, strict FIFO),
    which is the engine's existing handoff contract."""

    __slots__ = ("tracer", "cid", "sampled", "keep", "spans", "committed")

    def __init__(self, tracer: "RowTracer", cid: str, sampled: bool):
        self.tracer = tracer
        self.cid = cid
        self.sampled = sampled
        self.keep = False           # forced keep: flagged/shed/dlq/abort
        self.spans: List[Span] = []
        self.committed = False

    # -- batch stages ---------------------------------------------------

    def span(self, stage: str, *, detail: Optional[str] = None):
        """Context manager timing one batch stage; exception-safe (the
        span ends, ok=False, and re-raises)."""
        return _SpanCtx(self.tracer, self.spans.append, self.cid, stage,
                        detail)

    def add(self, stage: str, duration_sec: float, *, ok: bool = True,
            detail: Optional[str] = None,
            start: Optional[float] = None) -> None:
        """Record an already-measured batch stage (the engine's existing
        ``dispatch_time`` style timings). ``start`` is when the stage
        began; left out, the stage is taken to have ended now."""
        t = self.tracer
        t._count_begin_end()
        self.spans.append(Span(self.cid, stage,
                               t._wall() - duration_sec if start is None
                               else start,
                               duration_sec * 1e3, ok, detail))
        t._observe_stage(stage, duration_sec)

    # -- row events -----------------------------------------------------

    def row_cid(self, msg) -> str:
        """The stable per-row correlation id: batch cid + the row's source
        coordinates (the same (partition, offset) its DLQ record carries)."""
        return f"{self.cid}:{msg.partition}:{msg.offset}"

    def event(self, stage: str, cid: str, *, ok: bool = True,
              detail: Optional[str] = None) -> None:
        """Instantaneous row-level marker; marks the batch kept (row
        events only exist for interesting rows)."""
        t = self.tracer
        t._count_begin_end()
        self.keep = True
        self.spans.append(Span(cid, stage, t._wall(), 0.0, ok, detail))

    def events_rows(self, stage: str, pairs: List[tuple], *,
                    ok: bool = True, detail: Optional[str] = None) -> None:
        """Batched row markers stored COMPACT (``pairs`` = the rows'
        (partition, offset) coordinates): one counter bump, one wall
        read, ONE ring entry for the whole list. This is the
        per-flagged-row path at 50k rows/sec — the tracing overhead
        budget lives or dies here; Span objects and cid strings only
        materialize when somebody reads the ring."""
        if not pairs:
            return
        t = self.tracer
        t._count(len(pairs))
        self.keep = True
        self.spans.append(_RowEvents(self.cid, stage, tuple(pairs),
                                     t._wall(), ok, detail))

    def shed(self, msg, reason: str) -> str:
        """Row diverted by admission control; returns the row cid so the
        DLQ record can carry it."""
        cid = self.row_cid(msg)
        self.event(EVENT_SHED, cid, ok=False, detail=reason)
        return cid

    def dlq(self, msg, reason: str) -> str:
        """Row dead-lettered (malformed / poison); returns the row cid."""
        cid = self.row_cid(msg)
        self.event(EVENT_DLQ, cid, ok=False, detail=reason)
        return cid


def _annotation(stage: str, cid: str):
    """The span's twin in the profiler's own trace (``fraud/<stage>`` on
    the calling thread's line, on the device's clock). Outside a capture
    entering one is a flag test."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation("fraud/" + stage, cid=cid)


class _SpanCtx:
    """A span open as a context manager: true start, exception-safe end,
    and a ``fraud/<stage>`` annotation in the profiler's trace while it is
    open. ``sink`` is the batch-local buffer's ``append`` (a batch leg),
    the ring's (a leg after the batch's terminal) or the boot log's (a
    phase of set-up). ``detail`` may be set while the span is open;
    ``seconds`` is its duration once it has closed."""

    __slots__ = ("tracer", "sink", "cid", "stage", "detail", "seconds",
                 "_t0", "_w0", "_ann")

    def __init__(self, tracer: "RowTracer", sink, cid: str, stage: str,
                 detail: Optional[str]):
        self.tracer = tracer
        self.sink = sink
        self.cid = cid
        self.stage = stage
        self.detail = detail

    def __enter__(self):
        self._ann = _annotation(self.stage, self.cid)
        self._ann.__enter__()
        self._w0 = self.tracer._wall()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        t = self.tracer
        t._count_begin_end()
        # A span that an exception closes names it, as the lane's failed
        # explain span always has.
        self.sink(Span(self.cid, self.stage, self._w0, dt * 1e3,
                       exc_type is None,
                       self.detail if exc_type is None else exc_type.__name__))
        t._observe_stage(self.stage, dt)
        return False


BOOT_CAPACITY = 2048    # spans the boot log keeps; the rest are counted

COMPILE_COUNTERS = ("compile_requests", "compile_cache_hits",
                    "compile_obtain_s", "compile_fetch_s", "trace_s",
                    "lower_s", "compiles_since_serving")


class _BootLog:
    """The process's record of its own set-up (module docstring): every
    ``setup_*`` and ``compile`` span up to its bound, the live tracers that
    are also handed each one as it ends, and the compile counters. One
    lock, taken once a span — a handful a process but for ``compile``,
    which JAX's compile dwarfs. To :class:`_SpanCtx` it stands where a
    tracer stands: the tracer that adopts a span does the counting."""

    def __init__(self, capacity: int = BOOT_CAPACITY):
        self.capacity = capacity
        self.spans: List[Span] = []
        self.dropped = 0
        self.serving = False    # StreamingClassifier.run() has polled
        self.counters: Dict[str, float] = dict.fromkeys(COMPILE_COUNTERS, 0)
        self._tracers: "weakref.WeakSet[RowTracer]" = weakref.WeakSet()
        self._lock = threading.Lock()

    _wall = staticmethod(time.time)

    def _count_begin_end(self) -> None:
        pass

    def _observe_stage(self, stage: str, duration_sec: float) -> None:
        pass

    def _route(self, span: Span) -> None:
        """To every live tracer, and to the log for those built later
        (lock held)."""
        for t in list(self._tracers):
            t._adopt((span,))
        if len(self.spans) < self.capacity:
            self.spans.append(span)
        else:
            self.dropped += 1

    def record(self, span: Span) -> None:
        with self._lock:
            self._route(span)

    def attach(self, tracer: "RowTracer") -> List[Span]:
        """``tracer`` takes every later span; returns the log so far."""
        with self._lock:
            self._tracers.add(tracer)
            return list(self.spans)

    def compiled(self, fn: str, duration_sec: float, *, hit: bool,
                 fetch_sec: float = 0.0) -> None:
        """One executable obtained in ``duration_sec``, ending now: from
        the persistent cache in ``fetch_sec`` (``hit``) or built. Called by
        ``utils/jax_cache.py``'s listener on the compiling thread."""
        with self._lock:
            c = self.counters
            c["compile_requests"] += 1
            c["compile_cache_hits"] += int(hit)
            c["compile_obtain_s"] += duration_sec
            c["compile_fetch_s"] += fetch_sec
            c["compiles_since_serving"] += int(self.serving)
            self._route(Span(
                f"compile-{c['compile_requests']}", STAGE_COMPILE,
                time.time() - duration_sec, duration_sec * 1e3, True,
                f"fn={'_'.join(fn.split())} hit={int(hit)} "
                f"fetch_ms={fetch_sec * 1e3:.3f}"))

    def add_seconds(self, counter: str, duration_sec: float) -> None:
        """``trace_s`` / ``lower_s``: JAX's tracing and lowering time,
        summed (they fire for every inner jit, so they get no spans)."""
        with self._lock:
            self.counters[counter] += duration_sec

    def health(self) -> Dict[str, float]:
        """The ``compile`` block of ``health()``: process-wide, since one
        process has one JAX; ``boot_dropped`` is what a tracer built from
        now on will lack. A racy copy, like the rest of ``health()``."""
        return {**self.counters, "boot_dropped": self.dropped}


BOOT = _BootLog()


def setup_span(stage: str, *, detail: Optional[str] = None) -> _SpanCtx:
    """Context manager around one phase of set-up (a ``STAGE_SETUP_*``)."""
    return _SpanCtx(BOOT, BOOT.record, "setup", stage, detail)


class RowTracer:
    """Per-worker tracing context (see module docstring). One per engine/
    fleet worker; shared across supervised incarnations so chains survive
    restarts exactly like the DLQ poison tracker does."""

    def __init__(self, *, worker: str = "w0", capacity: int = 4096,
                 sample: float = 1.0, seed: Optional[int] = None,
                 record_rows: bool = False,
                 wall: Callable[[], float] = time.time):
        if not 0.0 <= sample <= 1.0:
            raise ValueError(f"sample must be in [0, 1], got {sample}")
        if record_rows and sample < 1.0:
            # A recording exists to replay the run's EXACT row set;
            # head-sampling away clean batches would silently hole it.
            raise ValueError(
                f"record_rows needs sample=1.0 (got {sample}): a sampled "
                "recording cannot reproduce the run's row set")
        self.worker = worker
        self.sample = sample
        # Record mode (scenarios/record.py): the engine adds one compact
        # EVENT_ROW block per delivered batch carrying EVERY row's source
        # coordinates — the census a recorded trace needs for exact
        # replay. Off (the default), clean rows stay un-enumerated and
        # only the interesting minority gets row events.
        self.record_rows = bool(record_rows)
        self.ring = SpanRing(capacity)
        self._rng = random.Random(seed)
        self._wall = wall
        self._lock = threading.Lock()
        self._seq = 0
        # Exact span accounting: every begin is matched by an end (spans
        # are only ever created fully-formed, so the pair increments land
        # together — the invariant the chaos tests pin is that no path
        # creates a begun-but-never-ended span, i.e. open == 0 at rest).
        self.spans_begun = 0
        self.spans_ended = 0
        self.batches_traced = 0     # batch traces minted
        self.batches_closed = 0     # committed or aborted
        self.kept = 0               # batches whose spans entered the ring
        self.sampled_out = 0        # clean batches discarded by sampling
        self._stages: Dict[str, LatencySketch] = {}
        self._adopt(BOOT.attach(self))

    # -- internal hooks (BatchTrace) ------------------------------------

    def _count_begin_end(self) -> None:
        self._count(1)

    def _count(self, n: int) -> None:
        with self._lock:
            self.spans_begun += n
            self.spans_ended += n

    def _observe_stage(self, stage: str, duration_sec: float) -> None:
        sk = self._stages.get(stage)
        if sk is None:
            with self._lock:
                sk = self._stages.setdefault(stage, LatencySketch())
        sk.add(duration_sec)

    def _adopt(self, spans: Sequence[Span]) -> None:
        """Set-up and ``compile`` spans, timed before or outside this
        tracer: counted, kept and sketched like its own."""
        self._count(len(spans))
        self.ring.extend(spans)
        for s in spans:
            self._observe_stage(s.stage, s.duration_ms / 1e3)

    # -- engine surface -------------------------------------------------

    def batch_begin(self, n_rows: int, *,
                    poll_wait_sec: float = 0.0) -> BatchTrace:
        """Mint a batch correlation id + its trace context at poll time.
        The head-sampling draw happens HERE; interesting outcomes flip the
        batch to kept later (forced keeps are outcome-driven, the draw
        only throttles clean traffic)."""
        with self._lock:
            self._seq += 1
            seq = self._seq
            self.batches_traced += 1
            sampled = self._rng.random() < self.sample
        bt = BatchTrace(self, f"{self.worker}-{seq:x}", sampled)
        # ``poll_wait_sec``: how long the batch's oldest row had been on
        # the broker when the poll returned it, so the span starts at that
        # row's broker stamp and ends now.
        bt.add(STAGE_POLL, poll_wait_sec, detail=f"rows={n_rows}")
        return bt

    def commit(self, bt: Optional[BatchTrace]) -> None:
        """Terminal for a delivered batch: push its spans into the ring
        when kept (sampled or forced), count it out otherwise. Idempotent
        — abort-then-commit races on engine unwind paths count once."""
        if bt is None or bt.committed:
            return
        bt.committed = True
        with self._lock:
            self.batches_closed += 1
            if bt.keep or bt.sampled:
                self.kept += 1
            else:
                self.sampled_out += 1
                return
        self.ring.extend(bt.spans)

    def abort(self, bt: Optional[BatchTrace], reason: str = "abort") -> None:
        """Terminal for an abandoned batch (crash / flush failure / replay
        discard): always kept — an aborted batch is interesting by
        definition."""
        if bt is None or bt.committed:
            return
        bt.event(EVENT_ABORT, bt.cid, ok=False, detail=reason)
        self.commit(bt)

    # -- direct records (post-terminal legs: annotation lane) ------------

    def wall(self) -> float:
        """Now, on the clock every span's ``start`` is read from."""
        return self._wall()

    def span(self, cid: str, stage: str, *, detail: Optional[str] = None):
        """Context manager for a leg AFTER a batch's terminal (annotation
        lane, slot lane): like :meth:`BatchTrace.span`, committed straight
        to the ring when it closes."""
        return _SpanCtx(self, self._ring_append, cid, stage, detail)

    def _ring_append(self, span: Span) -> None:
        self.ring.extend((span,))

    def record_span(self, cid: str, stage: str, duration_sec: float, *,
                    ok: bool = True, detail: Optional[str] = None,
                    start: Optional[float] = None) -> None:
        """Record a span straight into the ring — for legs that run AFTER
        a batch's terminal commit (the annotation lane's explain calls).
        Only call for rows/legs that are always-kept (flagged rows are);
        head sampling does not apply here. ``start`` as in
        :meth:`BatchTrace.add`."""
        self._count_begin_end()
        self.ring.extend((Span(cid, stage,
                               self._wall() - duration_sec if start is None
                               else start,
                               duration_sec * 1e3, ok, detail),))
        self._observe_stage(stage, duration_sec)

    def record_event(self, cid: str, stage: str, *, ok: bool = True,
                     detail: Optional[str] = None) -> None:
        """Instantaneous direct marker (see :meth:`record_span`)."""
        self._count_begin_end()
        self.ring.extend([Span(cid, stage, self._wall(), 0.0, ok, detail)])

    # -- retrieval + export (any thread) --------------------------------

    def chain(self, cid: str) -> List[Span]:
        """Every recorded span on a correlation id's chain, oldest first.
        A ROW cid (``<batch>:<part>:<off>``) pulls its batch's stage spans
        plus the row's own events; a batch cid pulls the batch spans and
        all its rows' events."""
        batch_cid = cid.split(":", 1)[0]
        out = []
        for s in self.ring.snapshot():
            if s.cid == cid or s.cid == batch_cid or (
                    cid == batch_cid and s.cid.split(":", 1)[0] == batch_cid):
                out.append(s)
        return out

    def stage_quantiles(self) -> Dict[str, dict]:
        """Per-stage latency snapshot (ms quantiles + counts) over ALL
        batches — sampling-independent; the bench ``stages`` block and
        the fleet aggregation read this."""
        with self._lock:
            stages = dict(self._stages)
        return {name: sk.snapshot() for name, sk in sorted(stages.items())}

    def stages_wire(self) -> Dict[str, dict]:
        """Per-stage sketches in wire form (lossless bucket counts) for
        the fleet bus — the coordinator merges these exactly."""
        with self._lock:
            stages = dict(self._stages)
        return {name: sk.to_wire() for name, sk in sorted(stages.items())}

    def snapshot(self) -> dict:
        """The ``trace`` block of ``health()`` (schema pinned in
        tests/test_obs.py TRACE_BLOCK_SCHEMA, FC301-checked)."""
        with self._lock:
            begun, ended = self.spans_begun, self.spans_ended
            traced, closed = self.batches_traced, self.batches_closed
            kept, sampled_out = self.kept, self.sampled_out
        return {
            "worker": self.worker,
            "sample": self.sample,
            "spans_begun": begun,
            "spans_ended": ended,
            "spans_open": begun - ended,
            "batches_traced": traced,
            "batches_closed": closed,
            "kept": kept,
            "sampled_out": sampled_out,
            "ring_depth": len(self.ring),
            "ring_capacity": self.ring.capacity,
            "ring_recorded": self.ring.recorded,
            "ring_dropped": self.ring.dropped,
            "stages": self.stage_quantiles(),
        }


def aggregate_stage_wires(wires: Sequence[Dict[str, dict]]
                          ) -> Dict[str, LatencySketch]:
    """Merge per-worker stage-sketch wires into one sketch per stage —
    LOSSLESS (bucket counts add), so fleet-level p50/p99 per stage equals
    a single-process run over the same samples (pinned in
    tests/test_obs.py)."""
    merged: Dict[str, LatencySketch] = {}
    for wire in wires:
        if not isinstance(wire, dict):
            continue
        for stage, w in wire.items():
            sk = LatencySketch.from_wire(w)
            if sk is None:
                continue
            into = merged.get(stage)
            if into is None:
                merged[stage] = sk
            else:
                into.merge(sk)
    return merged


def fleet_stage_latency(wires: Sequence[Dict[str, dict]]) -> Dict[str, dict]:
    """The fleet view's ``stage_latency_ms`` block: merged per-stage
    quantile snapshots across every worker's published wire."""
    return {stage: sk.snapshot()
            for stage, sk in sorted(aggregate_stage_wires(wires).items())}
