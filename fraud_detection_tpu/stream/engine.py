"""Micro-batching streaming classification engine — the headline serving path.

Replaces the reference's tab-3 loop (app_ui.py:195-248), which per message ran
a full Spark job plus a synchronous LLM round-trip and a producer flush
(SURVEY.md §3.3 — the throughput ceiling this framework exists to remove).

Engine shape: drain the consumer into a micro-batch (up to ``batch_size``
messages, waiting at most ``max_wait`` for the first), JSON-decode on the
host, featurize + score the whole batch in one jitted device program, produce
classified results, THEN flush and commit offsets — at-least-once semantics
with committed progress (deliberately fixing the reference's never-committed
offsets, Q2: its restart semantics reprocessed the topic from earliest).

Malformed messages (bad JSON / missing text field) are counted and routed to
the output with an error marker instead of killing the loop (the reference
raised and died — app_ui.py:200-201).

The consume->score handoff can be delegated to an adaptive scheduler
(``scheduler=`` / sched/scheduler.py): deadline-driven dynamic batching
over a pre-warmed padding-bucket ladder, admission control with explicit
load shedding onto the DLQ lane, governor-paced polls, and per-row
enqueue->produce SLO tracking (docs/scheduling.md).
"""

from __future__ import annotations

import json
import random
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from fraud_detection_tpu.explain.prompts import label_name
from fraud_detection_tpu.models.pipeline import ServingPipeline
from fraud_detection_tpu.obs import trace as obs_trace
from fraud_detection_tpu.sched.sketch import LatencySketch
from fraud_detection_tpu.stream.broker import (CommitFailedError, Consumer,
                                               Message, Producer)
from fraud_detection_tpu.utils import get_logger
from fraud_detection_tpu.utils.device import device_stamp
from fraud_detection_tpu.utils.racecheck import ExclusiveRegion

log = get_logger("stream.engine")

# Output wire-format fast path: fixed frame, %.6f confidence (same 6-decimal
# precision as the dict path's round(confidence, 6)).
_OUT_TEMPLATE = '{"prediction": %d, "label": %s, "confidence": %.6f, "original_text": %s}'
# Raw-JSON mode emits bytes directly, splicing the input's own string literal
# (no decode/re-encode round trip — the literal is already valid JSON).
_OUT_TEMPLATE_B = _OUT_TEMPLATE.encode()
_LABEL_JSON_B = {k: json.dumps(label_name(k)).encode() for k in (0, 1)}

# Dense label->JSON table for the native frame assembler (index = label);
# grown lazily for multiclass tree pipelines. Growth builds a NEW list and
# swaps the module reference (atomic under the GIL) — never mutates the
# published list, so concurrent engines can race the swap but each always
# reads a complete, correct table.
_LABEL_TABLE = [_LABEL_JSON_B[0], _LABEL_JSON_B[1]]
_LABEL_TABLE_S = [t.decode() for t in _LABEL_TABLE]  # str twin: no per-use decode


def _label_json_table(max_label: int) -> list:
    global _LABEL_TABLE, _LABEL_TABLE_S
    table = _LABEL_TABLE
    if max_label < len(table):
        return table
    table = table + [json.dumps(label_name(i)).encode()
                     for i in range(len(table), max_label + 1)]
    # Publish the str twin FIRST: readers gate on len(_LABEL_TABLE), so the
    # twin must already cover anything the bytes table admits.
    _LABEL_TABLE_S = [t.decode() for t in table]
    _LABEL_TABLE = table
    return table


def _label_json_str(label: int) -> str:
    table = _LABEL_TABLE_S
    if label < len(table):
        return table[label]
    # Build from the grown bytes table locally — never index the global twin
    # after growth (a concurrent grower may republish between the calls).
    return _label_json_table(label)[label].decode()


def _confidence_array(preds) -> np.ndarray:
    """p(predicted class): P for label 1, 1-P otherwise. The ONE definition
    both output paths (Python template and native frames) must share —
    their whole contract is byte-identical frames."""
    return np.where(np.asarray(preds.labels) == 1, preds.probabilities,
                    1.0 - preds.probabilities)


def _malformed_wire(msg: Message) -> bytes:
    """The error frame for an undecodable message — shared by both output
    paths for the same byte-parity reason as ``_confidence_array``."""
    return json.dumps({
        "error": "malformed message", "prediction": None,
        "original": msg.value.decode("utf-8", "replace")[:500]}).encode()


def _dlq_record(msg: Message, reason: str, error: str,
                attempts: Optional[int] = None,
                trace: Optional[str] = None) -> bytes:
    """Structured dead-letter record (docs/robustness.md schema): why the
    row was diverted plus enough source coordinates to find and replay it.
    Keyed by the source message's key, so DLQ consumers can join back.
    ``trace`` is the row's correlation id when tracing is on
    (docs/observability.md): the record joins back to its span chain by
    id, not just by source coordinates."""
    rec = {
        "reason": reason,
        "error": error,
        "source": {"topic": msg.topic, "partition": msg.partition,
                   "offset": msg.offset},
        "original": msg.value.decode("utf-8", "replace")[:500],
    }
    if attempts is not None:
        rec["attempts"] = attempts
    if trace is not None:
        rec["trace"] = trace
    return json.dumps(rec).encode()


@dataclass
class StreamStats:
    processed: int = 0
    malformed: int = 0
    dead_lettered: int = 0    # rows routed to the DLQ topic (subset of processed)
    shed: int = 0             # rows shed by admission control (subset of
                              # dead_lettered: every shed row leaves a record)
    batches: int = 0
    commits_skipped: int = 0  # producer didn't drain; offsets left uncommitted
    rebalanced_commits: int = 0  # commit fenced by a group rebalance (routine)
    restarts: int = 0         # supervised engine rebuilds (run_supervised)
    elapsed: float = 0.0
    batch_latency_sum: float = 0.0
    batch_latency_max: float = 0.0
    # Per-batch latencies for percentiles. Bounded: beyond the cap, random
    # replacement keeps a uniform sample (reservoir) so a week-long run
    # doesn't grow memory while p50/p99 stay honest.
    latencies: List[float] = field(default_factory=list)
    # Per-ROW enqueue->produce latency (includes queue wait — the number a
    # caller actually experiences under load, which per-batch device latency
    # undercounts). Bounded-memory streaming sketch, mergeable across
    # supervised incarnations (sched/sketch.py).
    row_sketch: LatencySketch = field(default_factory=LatencySketch)
    _latency_cap: int = 4096
    _seen: int = 0

    def record_latency(self, dt: float) -> None:
        self.batch_latency_sum += dt
        self.batch_latency_max = max(self.batch_latency_max, dt)
        self._reservoir_add(dt)

    def _reservoir_add(self, dt: float) -> None:
        """Add a sample to the percentile reservoir WITHOUT touching the
        exact sum/max accumulators (merge path reuses this)."""
        self._seen += 1
        if len(self.latencies) < self._latency_cap:
            self.latencies.append(dt)
        else:
            j = random.randrange(self._seen)
            if j < self._latency_cap:
                self.latencies[j] = dt

    def latency_percentile(self, q: float) -> float:
        if not self.latencies:
            return 0.0
        s = sorted(self.latencies)
        return s[min(len(s) - 1, int(q / 100.0 * len(s)))]

    @property
    def msgs_per_sec(self) -> float:
        return self.processed / self.elapsed if self.elapsed > 0 else 0.0

    @property
    def mean_batch_latency(self) -> float:
        return self.batch_latency_sum / self.batches if self.batches else 0.0

    def row_latency_ms(self, q: float) -> Optional[float]:
        """Per-row enqueue->produce latency quantile in ms (None until the
        first delivered batch)."""
        sec = self.row_sketch.quantile(q)
        return None if sec is None else round(sec * 1e3, 3)

    def as_dict(self) -> dict:
        return {
            "processed": self.processed,
            "malformed": self.malformed,
            "dead_lettered": self.dead_lettered,
            "shed": self.shed,
            "batches": self.batches,
            "commits_skipped": self.commits_skipped,
            "rebalanced_commits": self.rebalanced_commits,
            "restarts": self.restarts,
            "elapsed_sec": round(self.elapsed, 4),
            "msgs_per_sec": round(self.msgs_per_sec, 1),
            "mean_batch_latency_sec": round(self.mean_batch_latency, 5),
            "p50_batch_latency_sec": round(self.latency_percentile(50), 5),
            "p99_batch_latency_sec": round(self.latency_percentile(99), 5),
            "max_batch_latency_sec": round(self.batch_latency_max, 5),
            "p50_row_latency_ms": self.row_latency_ms(0.50),
            "p99_row_latency_ms": self.row_latency_ms(0.99),
        }


class StreamingClassifier:
    """Consumer -> micro-batch -> TPU scoring -> producer, with offset commits.

    ``explain_fn`` (optional) is called per classified message with
    (text, label, confidence) and its return value attached as "analysis" —
    the hook where the LLM explanation layer (explain/) plugs in; keep it
    sampled/async for throughput, unlike the reference's blocking per-message
    DeepSeek call.
    """

    def __init__(
        self,
        pipeline: ServingPipeline,
        consumer: Consumer,
        producer: Producer,
        output_topic: str,
        *,
        batch_size: int = 1024,
        max_wait: float = 0.05,
        text_field: str = "text",
        pipeline_depth: int = 2,
        explain_fn: Optional[Callable[[str, int, float], Optional[str]]] = None,
        explain_batch_fn: Optional[Callable[[List[str], List[int], List[float]],
                                            List[Optional[str]]]] = None,
        explain_async: bool = False,
        annotations_topic: Optional[str] = None,
        annotations_producer: Optional[Producer] = None,
        annotations_queue: int = 1024,
        dlq_topic: Optional[str] = None,
        dlq_max_attempts: int = 3,
        dlq_attempts: Optional[dict] = None,
        breaker: Optional[object] = None,
        explain_service: Optional[object] = None,
        shadow: Optional[object] = None,
        learn: Optional[object] = None,
        scheduler: Optional[object] = None,
        async_dispatch: bool = False,
        rowtrace: Optional[object] = None,
        sentinel: Optional[object] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        if pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got {pipeline_depth}")
        if dlq_max_attempts < 1:
            raise ValueError(
                f"dlq_max_attempts must be >= 1, got {dlq_max_attempts}")
        if explain_async and explain_batch_fn is None:
            raise ValueError("explain_async requires explain_batch_fn")
        if explain_async and annotations_producer is None:
            # NOT defaulted to the engine's producer: flush() is how both
            # sides account delivery (engine: commit-only-if-drained;
            # lane: annotated counters), and a shared producer would let
            # either side consume the other's delivery failures — the
            # engine could commit past a lost classification record, or a
            # failed annotation could halt the classification stream.
            raise ValueError(
                "explain_async requires a dedicated annotations_producer "
                "(a second producer on the same transport)")
        if explain_async and annotations_producer is producer:
            # Same invariant, sneakier violation: handing the engine's OWN
            # producer object in cross-contaminates the accounting just the
            # same — enforce the documented contract, don't trust callers.
            raise ValueError(
                "annotations_producer is the engine's own producer object — "
                "the async lane needs a DEDICATED producer (flush() is how "
                "both sides account delivery; sharing one lets either side "
                "consume the other's failures)")
        self.pipeline = pipeline
        self.consumer = consumer
        self.producer = producer
        self.output_topic = output_topic
        self.batch_size = batch_size
        self.max_wait = max_wait
        self.text_field = text_field
        self.pipeline_depth = pipeline_depth
        self.explain_fn = explain_fn
        # Batch variant: one call per micro-batch over (texts, labels,
        # confidences) of the valid rows — amortizes an on-pod LLM's device
        # round trip over the whole batch (OnPodBackend.generate_batch)
        # where the reference paid a synchronous HTTPS call per message
        # (app_ui.py:207). Takes precedence over explain_fn when both given.
        self.explain_batch_fn = explain_batch_fn
        # Async lane (stream/annotations.py): classification frames go out
        # WITHOUT analysis (so the raw-JSON + native-frame fast paths stay
        # in play) and flagged rows annotate in the background onto a side
        # topic, bounded-queue/drop-oldest — the LLM's decode rate caps the
        # ANNOTATION rate instead of the classification rate.
        self._annotation_lane = None
        if explain_async:
            from fraud_detection_tpu.stream.annotations import (
                AsyncAnnotationLane)

            self._annotation_lane = AsyncAnnotationLane(
                explain_batch_fn, annotations_producer,
                annotations_topic or f"{output_topic}-annotations",
                max_queue=annotations_queue, rowtrace=rowtrace)
            self.explain_fn = explain_fn = None
            self.explain_batch_fn = explain_batch_fn = None
        # Optional obs.trace.RowTracer (docs/observability.md): a
        # correlation id is minted per polled batch and rides every row to
        # its terminal — batch stage spans (poll/admit/launch/device/
        # deliver) plus row events for the interesting minority (shed,
        # dlq, flag), committed to the tracer's ring at delivery. Share
        # ONE tracer across a worker's supervised incarnations (like
        # dlq_attempts) so chains survive restarts. None = zero cost.
        self._rowtrace = rowtrace
        # Dead-letter routing (docs/robustness.md): when ``dlq_topic`` is
        # set, malformed rows and rows re-delivered more than
        # ``dlq_max_attempts`` times without a successful batch go to the
        # DLQ topic as structured reason records instead of inline error
        # frames. ``dlq_attempts`` is the redelivery tracker — pass ONE dict
        # to every incarnation a supervisor builds so poison counting
        # survives restarts (a fresh dict per engine would reset the count
        # exactly when the poison row crashes the incarnation). None (the
        # default) keeps today's inline error frames for wire parity, at
        # zero per-message cost.
        self.dlq_topic = dlq_topic
        self.dlq_max_attempts = dlq_max_attempts
        self._dlq_attempts = ((dlq_attempts if dlq_attempts is not None else {})
                              if dlq_topic is not None else None)
        self._dlq_counts: dict = {}   # reason -> records delivered to the DLQ
        # Optional explain/circuit.CircuitBreakerBackend (anything with
        # ``snapshot()``) — health() surfaces its state; the engine never
        # calls it directly (the explain hook / annotation lane own calls).
        self._breaker = breaker
        # Optional explain/slotserve SlotServeService (anything with
        # ``snapshot()``): the continuous-batching explanation lane behind
        # the explain hook. Same contract as the breaker — health()
        # surfaces its slot/queue/latency block, the hook owns the calls.
        self._explain_service = explain_service
        # Optional sched/scheduler.AdaptiveScheduler: owns the consume->
        # score handoff — deadline-driven dynamic batching over the padding
        # ladder, admission control (explicit shedding to the DLQ lane),
        # governor-paced polls, and the windowed SLO tracker health()
        # surfaces. One scheduler per engine (single-driver contract). A
        # shedding policy REQUIRES a DLQ topic: shed rows are structured
        # records delivered and committed with their batch, never silent
        # drops (docs/scheduling.md).
        if (scheduler is not None and getattr(scheduler, "sheds", False)
                and dlq_topic is None):
            raise ValueError(
                "scheduler sheds (shed_policy != 'none') but no dlq_topic is "
                "set — shed rows must land as explicit DLQ records")
        self._sched = scheduler
        # Double-buffered async dispatch (sched/batcher.py DispatchLane,
        # docs/serving.md): the featurize+upload+launch leg runs on a
        # dedicated lane thread while this (driver) thread delivers the
        # previous batch — the device never waits on host featurize.
        # Delivery (_finish: produce/flush/commit) and admission stay on
        # the driver, so the commit protocol and single-driver contracts
        # are unchanged; the lane preserves strict FIFO. Off by default:
        # the lane is the serving configuration (bench + serve CLI
        # --async-dispatch), not a semantics change for library callers.
        self.async_dispatch = bool(async_dispatch)
        self._lane = None                       # live lane while run()s
        self._lane_stats: Optional[dict] = None  # last run's lane counters
        self._max_inflight = 0
        # Optional registry/shadow.ShadowScorer: each scored batch's inputs
        # + primary results are offered to the candidate's async scorer
        # (non-blocking bounded queue — registry/shadow.py). The hot loop
        # pays one ``wants()`` gate per batch while a candidate is staged,
        # nothing when idle.
        self._shadow = shadow
        # Optional learn.LearnLoop (docs/online_learning.md): each scored
        # batch's source coordinates + payload references + primary
        # results are offered to the closed-loop learner's bounded queue
        # (non-blocking, drop + count on overflow — the ShadowScorer
        # contract). Decode/encode/windowing all happen on the learn-lane
        # thread; the hot loop pays one ``wants()`` gate per batch.
        self._learn = learn
        # Optional obs.sentinel.Sentinel (anything with ``snapshot()``):
        # the alerting engine watching this worker. Same contract as the
        # breaker — health() surfaces its alert/incident block; evaluation
        # is driven externally (the serve "sentinel" thread, the scenario
        # harness's virtual-time driver), never from the hot loop. Share
        # ONE sentinel across a worker's supervised incarnations (like the
        # tracer and the DLQ poison tracker) so incident accounting
        # survives restarts.
        self._sentinel = sentinel
        # Injectable monotonic clock for health ages (tests drive it).
        self._clock = clock
        self._created_at = clock()
        self._last_batch_at: Optional[float] = None
        self._inflight_depth = 0
        self._flush_fail_streak = 0
        self.stats = StreamStats()
        self._running = False
        self._flush_failed = False
        # Raw-JSON fast path: None = untried, False = unavailable (no native
        # library / vocab featurizer), True = in use (LR and tree models
        # both ride it). The explain hooks need decoded text, so they force
        # the slow path.
        self._json_fast: Optional[bool] = (
            None if explain_fn is None and explain_batch_fn is None else False)
        # Native output-frame assembly: None = untried (probed on first use).
        self._frames_ok: Optional[bool] = None
        # The engine is single-driver by contract: stats, consumer position,
        # and in-flight state all assume one thread runs the loop. stop() is
        # the one cross-thread entry point (a bare flag write). The region
        # turns a second concurrent run()/process_batch() into an immediate
        # RaceError instead of silent stat/offset corruption.
        self._drive_region = ExclusiveRegion("StreamingClassifier.drive")
        self._stopped = False  # stop() latches this; run() then refuses

    def stop(self) -> None:
        """Request shutdown — and latch it: a stopped engine STAYS stopped.
        run() entered after stop() returns immediately instead of resetting
        the flag, which is what lets an external coordinator (serve.py's
        multi-worker Ctrl-C path) stop an engine it built but whose run()
        hasn't started yet — without the latch, run()'s entry write would
        overwrite the request and the engine would consume anyway."""
        # Deliberately lock-free: stop() must be callable from signal-adjacent
        # contexts and never block behind a batch; both flags are monotonic
        # latches whose races run() explicitly re-checks (see run()).
        self._stopped = True    # flightcheck: ignore[FC102] — documented lock-free latch
        self._running = False   # flightcheck: ignore[FC102] — documented lock-free latch

    def _decode(self, msg: Message) -> Optional[str]:
        try:
            payload = json.loads(msg.value)  # bytes accepted; skips a copy
        except ValueError:  # JSONDecodeError and UnicodeDecodeError subclass it
            return None
        text = payload.get(self.text_field) if isinstance(payload, dict) else None
        return text if isinstance(text, str) else None

    def _dispatch(self, msgs: List[Message]) -> "_InFlight":
        """Decode + featurize + launch device scoring; does NOT block on the
        device. Returns the in-flight batch handle for ``_finish``.
        Synchronous composition of the two dispatch halves — the async lane
        runs ``_prepare`` on the driver and ``_launch`` on the lane thread."""
        return self._launch(self._prepare(msgs))

    def _prepare(self, msgs: List[Message]) -> "_Prep":
        """Driver-side admission for a freshly polled batch: offset cover,
        scheduler shedding, poison screening. Always runs on the driver
        thread — admission shares region-guarded scheduler state and the
        poison tracker with the rest of the drive loop."""
        t0 = time.perf_counter()
        # Correlation id minted at poll (docs/observability.md): this
        # batch's trace context, handed through _Prep/_InFlight to every
        # later leg — admission below records shed row events into it.
        bt = None
        if self._rowtrace is not None:
            # The poll span runs from the batch's oldest broker stamp to
            # now: what a row waits for before the engine has it.
            now = self._rowtrace.wall()
            oldest = min((m.timestamp for m in msgs if m.timestamp > 0.0),
                         default=now)
            bt = self._rowtrace.batch_begin(
                len(msgs), poll_wait_sec=max(0.0, now - oldest))
        # Offsets cover the ORIGINAL batch — rows screened out below are
        # handled (their DLQ record ships with this batch) and must commit.
        offsets: dict = {}
        for m in msgs:
            key = (m.topic, m.partition)
            offsets[key] = max(offsets.get(key, 0), m.offset + 1)

        dead: Optional[List[tuple]] = None
        dead_reasons: Optional[dict] = None
        shed_n = 0
        if self._sched is not None and msgs:
            # Admission control runs FIRST, on freshly polled rows only —
            # rows already in flight are never shed, and a shed row's record
            # rides THIS batch's delivery/commit (exactly like poison/
            # malformed DLQ records), so key-set accounting stays exact.
            keep, shed_rows = self._sched.admit(
                msgs, self._sched.backlog_of(self.consumer), trace=bt)
            if shed_rows:
                dead, dead_reasons = [], {}
                for m, reason in shed_rows:
                    dead.append((_dlq_record(
                        m, reason,
                        "shed by admission control (docs/scheduling.md); "
                        "replay from the DLQ record's source coordinates",
                        trace=(bt.row_cid(m) if bt is not None else None)),
                        m.key))
                    dead_reasons[reason] = dead_reasons.get(reason, 0) + 1
                shed_n = len(shed_rows)
                msgs = keep
        if self._dlq_attempts is not None:
            if dead is None:
                dead, dead_reasons = [], {}
            msgs = self._screen_poison(msgs, dead, dead_reasons, bt)
        prep_time = time.perf_counter() - t0
        if bt is not None:
            bt.add("admit", prep_time,
                   detail=f"kept={len(msgs)} shed={shed_n}")
        return _Prep(msgs, offsets, dead, dead_reasons, shed_n,
                     prep_time, bt)

    def _launch(self, prep: "_Prep") -> "_InFlight":
        """Featurize + device dispatch for a prepared batch; does NOT block
        on the device. Runs on the driver (sync mode) or the dispatch lane's
        worker thread (``async_dispatch``) — it touches no driver-owned
        state beyond the documented monotonic fast-path latches.

        The featurize leg is multi-core on both host paths: the raw-JSON
        encode shards inside one C++ call (native/fast_featurize.cpp
        run_sharded) and the text fallback shards across the Python thread
        pool (featurize/parallel.py via ``pipeline.predict_async``) — so
        the host leg that overlaps the device wait is itself parallel, not
        one GIL-bound thread. With a device-featurizing pipeline
        (``featurize_device`` — models/pipeline.py) the leg shrinks
        further: this lane ships RAW UTF-8 BYTES (decode + memcpy) and
        tokenize/hash/count run inside the scoring program, so the only
        host work left here is JSON decode + byte packing."""
        t0 = time.perf_counter()
        bt = prep.trace
        if bt is None:
            inflight = self._score_async(prep.msgs, prep.offsets, t0)
        else:
            # The featurize+upload+launch leg, measured before prep time
            # folds in (this may run on the lane thread — the trace is
            # handed off with the batch, strictly FIFO, never shared).
            with bt.span("launch"):
                inflight = self._score_async(prep.msgs, prep.offsets, t0)
            self._trace_phases(bt, inflight.pending)
        inflight.trace = bt
        inflight.dispatch_time += prep.prep_time
        if prep.dead:
            inflight.dead = prep.dead
            inflight.dead_reasons = prep.dead_reasons
            # Screened/shed rows are OUTSIDE inflight.msgs — message
            # accounting (processed, budget) must add them back; rows
            # diverted later in _finish stay inside msgs and must not be
            # added twice.
            inflight.dead_screened = len(prep.dead)
            inflight.shed_n = prep.shed_n
        # Wall-clock receipt stamp: the enqueue->produce fallback origin for
        # transports whose messages carry no producer timestamp.
        inflight.recv_wall = time.time()
        return inflight

    def _score_async(self, msgs: List[Message], offsets: dict,
                     t0: float) -> "_InFlight":
        """Decode + featurize + upload + launch, raw-JSON path first."""
        inflight = None
        if msgs and self._json_fast is not False:
            inflight = self._dispatch_raw_json(msgs, offsets, t0)
        if inflight is None:
            texts: List[Optional[str]] = [self._decode(m) for m in msgs]
            valid_idx = [i for i, t in enumerate(texts) if t is not None]
            pending = (self.pipeline.predict_async([texts[i] for i in valid_idx])
                       if valid_idx else None)
            inflight = _InFlight(msgs, texts, valid_idx, pending, offsets,
                                 time.perf_counter() - t0)
        return inflight

    @staticmethod
    def _trace_phases(bt, pending) -> None:
        """The pipeline's own phase timings (models/pipeline.py ``Phase``)
        as child spans of the ``launch`` that just closed: ``featurize`` and
        ``upload`` with their true starts; what they leave of ``launch`` is
        its self time, the jit call."""
        phases = getattr(pending, "phases", None)
        if not phases:
            return
        shift = bt.tracer.wall() - time.perf_counter()
        for ph in phases:
            detail = f"rows={ph.rows}"
            if ph.stage == "upload":
                detail += f" padded={ph.padded} bytes={ph.nbytes}"
            bt.add(ph.stage, ph.seconds, start=ph.started + shift,
                   detail=detail)

    def _screen_poison(self, msgs: List[Message], dead: List[tuple],
                       dead_reasons: dict,
                       bt: Optional[object] = None) -> List[Message]:
        """Count this delivery against each row and divert rows whose count
        exceeded ``dlq_max_attempts`` — a row that keeps being re-delivered
        is one whose batch keeps dying (crash/flush-fail replays), and
        re-scoring it forever burns every supervisor restart. Counts clear
        on batch success (``_deliver``) and are tracked per source offset,
        so duplicates of a committed row start fresh. Granularity is the
        batch: innocent batch-mates of a poison row accumulate the same
        count and may be diverted with it — the DLQ record carries the
        attempt count so they are distinguishable downstream."""
        attempts = self._dlq_attempts
        keep: List[Message] = []
        for m in msgs:
            key = (m.topic, m.partition, m.offset)
            n = attempts[key] = attempts.get(key, 0) + 1
            if n > self.dlq_max_attempts:
                dead.append((_dlq_record(
                    m, "max_attempts_exceeded",
                    f"re-delivered {n} times without a successful batch "
                    f"(dlq_max_attempts={self.dlq_max_attempts})",
                    attempts=n,
                    trace=(bt.dlq(m, "max_attempts_exceeded")
                           if bt is not None else None)), m.key))
                dead_reasons["max_attempts_exceeded"] = (
                    dead_reasons.get("max_attempts_exceeded", 0) + 1)
            else:
                keep.append(m)
        return keep if len(keep) != len(msgs) else msgs

    def _dispatch_raw_json(self, msgs: List[Message], offsets: dict,
                           t0: float) -> Optional["_InFlight"]:
        """Try the raw-JSON path: one native pass from message bytes to hashed
        rows, no Python json.loads. Returns None to use the slow path — either
        permanently (pipeline can't do it) or for this batch only (the native
        scanner rejected a message that Python's json.loads accepts, e.g. an
        escaped key; per-message behavior must match the slow path exactly)."""
        fast = self.pipeline.predict_json_async(
            [m.value for m in msgs], self.text_field)
        if fast is None:
            self._json_fast = False
            return None
        self._json_fast = True
        pending, status, span_start, span_len, ctxs = fast
        literals: List[Optional[bytes]] = [None] * len(msgs)
        # Bulk numpy->python conversion: per-element numpy indexing costs
        # ~0.1us each and this loop runs per message at 50k+/sec.
        valid_idx = np.flatnonzero(status).tolist()
        if len(valid_idx) != len(msgs):
            for i in np.flatnonzero(status == 0).tolist():
                if self._decode(msgs[i]) is not None:
                    return None  # stricter-than-json.loads: slow path
        if ctxs is not None and self.explain_fn is None and self._native_frames():
            # Native frame assembly will splice straight from the message
            # buffers — no per-message literal slices needed at all.
            return _InFlight(msgs, literals, valid_idx, pending, offsets,
                             time.perf_counter() - t0, raw=True,
                             splice=(ctxs, span_start, span_len))
        starts = span_start.tolist()
        lens = span_len.tolist()
        for i in valid_idx:
            s = starts[i]
            literals[i] = msgs[i].value[s : s + lens[i]]
        return _InFlight(msgs, literals, valid_idx, pending, offsets,
                         time.perf_counter() - t0, raw=True)

    def _finish(self, inflight: "_InFlight") -> int:
        """Block on device results for an in-flight batch, produce outputs,
        flush, commit that batch's offsets. Returns messages handled."""
        t1 = time.perf_counter()
        msgs, texts = inflight.msgs, inflight.texts
        bt = inflight.trace
        if inflight.pending is None:
            preds = None
        elif bt is not None:
            with bt.span("device"):
                preds = inflight.pending.resolve()
        else:
            preds = inflight.pending.resolve()

        if bt is not None and preds is not None:
            self._trace_flags(inflight, preds)

        if preds is not None and self._annotation_lane is not None:
            self._submit_annotations(inflight, preds)

        if preds is not None and self._shadow is not None:
            self._submit_shadow(inflight, preds)

        if preds is not None and self._learn is not None:
            self._submit_learn(inflight, preds)

        if inflight.splice is not None and preds is not None:
            wires = self._assemble_frames_native(inflight, preds)
            return self._deliver(inflight, wires, t1)

        results: List[Optional[tuple]] = [None] * len(msgs)
        if preds is not None:
            # Bulk numpy->python conversion (tolist) and vectorized
            # confidence, not per-element int()/float()/branching: this is
            # the per-message hot loop.
            labels = preds.labels.tolist()
            confs = _confidence_array(preds).tolist()
            if inflight.raw:
                # Raw-JSON mode: predictions cover all rows positionally.
                for i in inflight.valid_idx:
                    results[i] = (labels[i], confs[i])
            else:
                for j, i in enumerate(inflight.valid_idx):
                    results[i] = (labels[j], confs[j])

        # Batch explanations: ONE hook call for the whole micro-batch's valid
        # rows (vs the per-message call below) — an on-pod LLM then explains
        # the batch in a single device program.
        analyses: Optional[List[Optional[str]]] = None
        if self.explain_batch_fn is not None:
            valid = [(i, results[i]) for i in range(len(msgs))
                     if results[i] is not None]
            batch_out = self.explain_batch_fn(
                [texts[i] for i, _ in valid],
                [r[0] for _, r in valid],
                [r[1] for _, r in valid]) if valid else []
            if len(batch_out) != len(valid):  # zip would silently drop rows
                raise ValueError(
                    f"explain_batch_fn returned {len(batch_out)} analyses "
                    f"for {len(valid)} rows")
            analyses = [None] * len(msgs)
            for (i, _), a in zip(valid, batch_out):
                analyses[i] = a

        explain = self.explain_fn is not None or analyses is not None
        wires: List[tuple] = []
        for idx, (msg, text, res) in enumerate(zip(msgs, texts, results)):
            if res is None:
                self.stats.malformed += 1
                if self.dlq_topic is not None:
                    self._dead_letter(inflight, msg, "malformed",
                                      "undecodable JSON or missing/"
                                      "non-string text field")
                    continue
                wire = _malformed_wire(msg)
            else:
                label, confidence = res  # confidence precomputed vectorized
                # Same field semantics as FraudAnalysisAgent.predict_and_get_label:
                # prediction = int class, label = display name.
                if inflight.raw:
                    # Zero-copy text: splice the input's own (already-valid)
                    # string literal into the fixed byte frame. The shared
                    # table keeps this path byte-identical to the native
                    # assembler for multiclass labels >= 2 (and amortizes
                    # their json.dumps across the hot loop).
                    label_json = _label_json_table(label)[label]
                    wire = _OUT_TEMPLATE_B % (label, label_json, confidence, text)
                elif not explain:
                    # Fast path: only the text needs JSON escaping; the frame
                    # is a fixed template (json.dumps of the full dict costs
                    # ~2.5x more and this runs per message at 30k+/sec).
                    label_json = _label_json_str(label)
                    wire = (_OUT_TEMPLATE % (label, label_json,
                                             confidence, json.dumps(text))).encode()
                else:
                    out = {
                        "prediction": label,
                        "label": label_name(label),
                        "confidence": round(confidence, 6),
                        "original_text": text,
                    }
                    analysis = (analyses[idx] if analyses is not None
                                else self.explain_fn(text, label, confidence))
                    if analysis is not None:
                        out["analysis"] = analysis
                    wire = json.dumps(out).encode()
            wires.append((wire, msg.key))
        return self._deliver(inflight, wires, t1)

    def _submit_annotations(self, inflight: "_InFlight", preds) -> None:
        """Hand this batch's flagged (non-benign) valid rows to the async
        lane. Non-blocking: the lane's bounded queue absorbs or drops;
        frames below ship regardless. Text is extracted lazily for flagged
        rows only (~5% of traffic), so the raw/native paths keep their
        zero-decode hot loop."""
        labels = np.asarray(preds.labels)
        flagged = np.flatnonzero(labels != 0)
        if flagged.size == 0:
            return
        confs = _confidence_array(preds)
        # Host conversion is BATCHED — one tolist per array over the flagged
        # subset — never per-row int(labels[i])/float(confs[i]) numpy-scalar
        # indexing (each costs ~0.5us and this loop rides every flagged
        # batch; flightcheck FC203 polices the pattern).
        flag_idx = flagged.tolist()
        flag_labels = labels[flagged].tolist()
        flag_confs = confs[flagged].tolist()
        bt = inflight.trace
        items = []
        if inflight.raw:
            # Predictions are positional over ALL rows; malformed rows hold
            # padding garbage — keep valid ones only.
            valid = frozenset(inflight.valid_idx)
            for i, label, conf in zip(flag_idx, flag_labels, flag_confs):
                if i not in valid:
                    continue
                text = self._annotation_text(inflight, i)
                if text is not None:
                    items.append((inflight.msgs[i].key, text, label, conf,
                                  bt.row_cid(inflight.msgs[i])
                                  if bt is not None else None))
        else:
            for j, label, conf in zip(flag_idx, flag_labels, flag_confs):
                i = inflight.valid_idx[j]
                items.append((inflight.msgs[i].key, inflight.texts[i],
                              label, conf,
                              bt.row_cid(inflight.msgs[i])
                              if bt is not None else None))
        if items:
            self._annotation_lane.submit(items)

    def _trace_flags(self, inflight: "_InFlight", preds) -> None:
        """Row events for this batch's flagged (non-benign) rows: flagged
        rows are ALWAYS kept by the tracer (head sampling only throttles
        clean traffic), and the event carries the row's correlation id so
        its whole poll->terminal chain is retrievable. Batched host
        conversion, like every per-row loop on this path (FC203)."""
        bt = inflight.trace
        labels = np.asarray(preds.labels)
        flagged = np.flatnonzero(labels != 0)
        if flagged.size == 0:
            return
        if not inflight.raw:
            idxs = [inflight.valid_idx[j] for j in flagged.tolist()]
        elif len(inflight.valid_idx) == len(inflight.msgs):
            idxs = flagged.tolist()     # all valid: the common case
        else:
            # Predictions are positional over ALL rows; malformed rows
            # hold padding garbage — keep valid ones only.
            valid = frozenset(inflight.valid_idx)
            idxs = [i for i in flagged.tolist() if i in valid]
        # Compact batched record (one lock, one ring entry): int pairs
        # only — cid strings materialize at read time, never here.
        msgs = inflight.msgs
        bt.events_rows("flag", [(m.partition, m.offset)
                                for m in map(msgs.__getitem__, idxs)])

    def _submit_shadow(self, inflight: "_InFlight", preds) -> None:
        """Offer this batch's valid rows + primary results to the shadow
        scorer. Non-blocking by contract (bounded queue, drop + count on
        overflow); payloads are REFERENCES (message bytes in raw mode,
        decoded texts otherwise) — the candidate decode/score happens on
        the shadow worker, never here."""
        sh = self._shadow
        if not sh.wants():
            return
        valid = inflight.valid_idx
        if not valid:
            return
        if inflight.raw:
            # Predictions are positional over ALL rows; slice to valid.
            payloads = [inflight.msgs[i].value for i in valid]
            labels = np.asarray(preds.labels)[valid]
            probs = np.asarray(preds.probabilities)[valid]
        else:
            # Predictions already cover exactly the valid rows, in order.
            payloads = [inflight.texts[i] for i in valid]
            labels, probs = preds.labels, preds.probabilities
        sh.submit(payloads, labels, probs, raw=inflight.raw,
                  text_field=self.text_field)

    def _submit_learn(self, inflight: "_InFlight", preds) -> None:
        """Offer this batch's valid rows + primary results to the learn
        loop's window (learn/loop.py). Non-blocking by contract (bounded
        queue, drop + count on overflow); payloads are REFERENCES —
        decode/encode happen on the learn lane, never here. Host
        conversion is batched (FC203), like every per-row loop on this
        path."""
        lr = self._learn
        if not lr.wants():
            return
        valid = inflight.valid_idx
        if not valid:
            return
        msgs = inflight.msgs
        coords = [(msgs[i].topic, msgs[i].partition, msgs[i].offset)
                  for i in valid]
        if inflight.raw:
            payloads = [msgs[i].value for i in valid]
            labels = np.asarray(preds.labels)[valid]
            probs = np.asarray(preds.probabilities)[valid]
        else:
            payloads = [inflight.texts[i] for i in valid]
            labels, probs = preds.labels, preds.probabilities
        lr.submit(coords, payloads, labels, probs, raw=inflight.raw,
                  version=getattr(self.pipeline, "active_version", None))

    def _dead_letter(self, inflight: "_InFlight", msg: Message, reason: str,
                     error: str, attempts: Optional[int] = None) -> None:
        """Divert one row to the DLQ: its record rides THIS batch's delivery
        (same flush/commit accounting as the output frames, so a commit can
        never advance past a lost DLQ record either)."""
        if inflight.dead is None:
            inflight.dead, inflight.dead_reasons = [], {}
        bt = inflight.trace
        inflight.dead.append((_dlq_record(
            msg, reason, error, attempts,
            trace=(bt.dlq(msg, reason) if bt is not None else None)),
            msg.key))
        inflight.dead_reasons[reason] = inflight.dead_reasons.get(reason, 0) + 1

    def _annotation_text(self, inflight: "_InFlight", i: int) -> Optional[str]:
        """Decoded text of row i in a raw-mode batch: the stored slice (or
        the native path's encode-time span) covers the complete QUOTED JSON
        string literal, so it round-trips through json.loads for exact
        unescaping."""
        lit = inflight.texts[i]
        if lit is None and inflight.splice is not None:
            _, span_start, span_len = inflight.splice
            s = int(span_start[i])
            lit = inflight.msgs[i].value[s : s + int(span_len[i])]
        if lit is None:
            return None
        if isinstance(lit, str):
            return lit
        try:
            return json.loads(lit)
        except ValueError:  # can't happen for scanner-validated literals
            return None

    def annotation_stats(self) -> Optional[dict]:
        """Async-lane counters (submitted/annotated/dropped/queue_depth),
        or None when the engine runs inline or without explanations."""
        lane = self._annotation_lane
        return lane.stats() if lane is not None else None

    def health(self) -> dict:
        """Point-in-time engine health snapshot.

        Cheap and lock-free — callable from any thread while the loop runs
        (serve.py's ``--health-file`` dumper does exactly that); values are
        racy single reads by design, a monitoring sample rather than a
        consistent transaction. Ages use the engine's injectable monotonic
        clock. ``None`` sub-objects mean the feature is off (no DLQ / no
        async lane / no breaker)."""
        now = self._clock()
        lane = self._annotation_lane
        breaker = self._breaker
        explain_service = self._explain_service
        sentinel = self._sentinel
        # Model-lifecycle block (docs/model_lifecycle.md): present when the
        # engine scores through a HotSwapPipeline (active/staged versions,
        # swap count) and/or a ShadowScorer is attached (divergence stats);
        # None for a plain static pipeline.
        snap_fn = getattr(self.pipeline, "lifecycle_snapshot", None)
        model = snap_fn() if callable(snap_fn) else None
        if self._shadow is not None:
            if model is None:
                model = {"active_version": None, "staged_version": None,
                         "swaps": 0, "last_swap_age_sec": None}
            model["shadow"] = self._shadow.snapshot()
        elif model is not None:
            model["shadow"] = None
        return {
            "running": self._running,
            "stopped": self._stopped,
            "uptime_sec": now - self._created_at,
            # Age of the last DELIVERED batch; None until the first one.
            # A growing age with running=True is the stall signal.
            "last_batch_age_sec": (None if self._last_batch_at is None
                                   else now - self._last_batch_at),
            "in_flight_depth": self._inflight_depth,
            "consecutive_flush_failures": self._flush_fail_streak,
            "processed": self.stats.processed,
            "malformed": self.stats.malformed,
            "dead_lettered": self.stats.dead_lettered,
            "shed": self.stats.shed,
            # Fence/zombie + lost-delivery counters (docs/robustness.md):
            # commits fenced by a rebalance and flushes that failed with
            # offsets held back — the sentinel's fence_events rule and
            # any external alerting read these from health, so they
            # belong in the block, not just the exit stats.
            "rebalanced_commits": self.stats.rebalanced_commits,
            "commits_skipped": self.stats.commits_skipped,
            "row_latency_ms": {"p50": self.stats.row_latency_ms(0.50),
                               "p99": self.stats.row_latency_ms(0.99)},
            "device": self._device_block(),
            "sched": (self._sched.snapshot()
                      if self._sched is not None else None),
            "dlq": (None if self.dlq_topic is None else {
                "topic": self.dlq_topic,
                "routed": dict(self._dlq_counts),
                "tracked_offsets": len(self._dlq_attempts),
            }),
            "annotations": lane.stats() if lane is not None else None,
            "breaker": (breaker.snapshot()
                        if breaker is not None and hasattr(breaker, "snapshot")
                        else None),
            # Slotserve lane (docs/explain_serving.md): slots busy/free,
            # admission queue, admitted/completed/dropped accounting,
            # expl/s, p50/p99 explain latency, kv_bytes.
            "explain": (explain_service.snapshot()
                        if explain_service is not None
                        and hasattr(explain_service, "snapshot")
                        else None),
            "model": model,
            # Closed-loop learning (learn/, docs/online_learning.md):
            # window/join accounting, retrain triggers, published and
            # promoted candidate versions.
            "learn": (self._learn.snapshot()
                      if self._learn is not None
                      and hasattr(self._learn, "snapshot")
                      else None),
            # Row-tracing accounting (obs/trace.py): span begun/ended
            # counters, ring depth/drops, per-stage latency quantiles.
            "trace": (self._rowtrace.snapshot()
                      if self._rowtrace is not None else None),
            # What this process's executables cost to obtain (obs/trace.py,
            # fed from jax.monitoring): requests, persistent-cache hits,
            # seconds, and how many came after run() first polled.
            "compile": obs_trace.BOOT.health(),
            # Alerting (obs/sentinel/, docs/observability.md): rule
            # states, firing/critical lists, incident accounting
            # (fired == resolved + still_firing), recent incidents.
            "alerts": (sentinel.snapshot()
                       if sentinel is not None
                       and hasattr(sentinel, "snapshot")
                       else None),
        }

    def _device_block(self) -> dict:
        """The ``device`` block of ``health()``: how device-resident the hot
        path is right now — dispatch-lane depth and overlap, host->device
        crossings per micro-batch, donation hits, and what is pinned in
        HBM. Pipeline counters come from the ACTIVE pipeline's DeviceStats
        (None fields when the pipeline doesn't expose them — fakes/tests);
        lane counters come from the live lane, or the last run's snapshot
        once it has stopped."""
        lane = self._lane
        ls = lane.stats() if lane is not None else (self._lane_stats or {})
        ds = getattr(self.pipeline, "device_stats", None)
        snap = ds.snapshot() if ds is not None else {}
        stamp = device_stamp()
        return {
            # Where this engine's pipeline runs, as JAX reports it.
            "platform": stamp["platform"],
            "device_kind": stamp["device_kind"],
            "device_count": stamp["device_count"],
            "async_dispatch": self.async_dispatch,
            "dispatch_depth": self.pipeline_depth,
            "max_inflight": ls.get("max_inflight", self._max_inflight),
            "lane_batches": ls.get("launched"),
            "driver_waits": ls.get("driver_waits"),
            "uploads": snap.get("uploads"),
            "upload_bytes": snap.get("upload_bytes"),
            "uploads_per_batch": snap.get("uploads_per_chunk"),
            "donation_hits": snap.get("donation_hits"),
            "pinned_bytes": snap.get("pinned_bytes"),
            "model_pins": snap.get("model_pins"),
            "int8": snap.get("int8"),
            # Mesh data-parallel scoring (parallel/serving.py): chips on
            # the data axis (0/None = single-device) and the per-chip
            # padded rungs dispatched — prewarm counts here, so a mesh
            # worker's health proves its rungs compiled before traffic.
            "mesh_devices": snap.get("mesh_devices"),
            "per_chip_rungs": snap.get("per_chip_rungs"),
            # Device-side featurization (ops/featurize_kernel.py): which
            # path featurize ran ("host" / "pallas" / "interpret"), raw
            # bytes shipped per row, and rows truncated at the byte width.
            "featurize_path": snap.get("featurize_path"),
            "bytes_in_per_row": snap.get("bytes_in_per_row"),
            "truncated_rows": snap.get("truncated_rows"),
        }

    def close_annotations(self, timeout: float = 30.0) -> bool:
        """Drain and stop the async lane (no-op inline). Call after the
        last run() when annotation completeness matters — run() itself
        leaves the lane up so repeated runs share it."""
        lane = self._annotation_lane
        return lane.close(timeout) if lane is not None else True

    def _abort_traces(self, batches, reason: str) -> None:
        """Close the traces of batches being discarded (crash / flush-fail
        replay paths): every minted batch reaches a terminal, so the
        tracer's begun==ended and traced==closed accounting stays exact
        even when the batches themselves are abandoned. Accepts _Prep and
        _InFlight alike; abort is idempotent."""
        if self._rowtrace is None:
            return
        for b in batches:
            self._rowtrace.abort(b.trace, reason)

    def _native_frames(self) -> bool:
        """Native output-frame assembly available? (cached after first ask)"""
        ok = self._frames_ok
        if ok is None:
            from fraud_detection_tpu.featurize import native as native_mod

            ok = self._frames_ok = native_mod.frames_available()
        return ok

    def _assemble_frames_native(self, inflight: "_InFlight",
                                preds) -> List[tuple]:
        """Build every output frame for a raw-mode batch in ONE C++ pass per
        chunk (format ints/floats + splice text literals straight from the
        message buffers via the encode-time spans — no per-message
        marshalling), leaving Python with a blob-slice per message.
        Byte-identical to the template path — enforced by
        tests/test_stream.py frame-parity tests."""
        msgs = inflight.msgs
        ctxs, span_start, span_len = inflight.splice
        labels = np.asarray(preds.labels, np.int32)
        confs = _confidence_array(preds).astype(np.float64)
        table = _label_json_table(int(labels.max()) if labels.size else 0)
        if len(inflight.valid_idx) != len(msgs):
            labels = labels.copy()
            mask = np.ones(len(msgs), bool)
            mask[inflight.valid_idx] = False
            labels[mask] = -1  # malformed: empty frame -> Python fallback
        from fraud_detection_tpu.featurize.native import build_frames

        wires: List[tuple] = []
        off = 0
        for arr, n_chunk in ctxs:
            hi = off + n_chunk
            blob, ends = build_frames(arr, span_start[off:hi],
                                      span_len[off:hi], labels[off:hi],
                                      confs[off:hi], table)
            start = 0
            for j, end in enumerate(ends.tolist()):
                msg = msgs[off + j]
                if end == start:  # malformed (valid frames are never empty)
                    self.stats.malformed += 1
                    if self.dlq_topic is not None:
                        self._dead_letter(inflight, msg, "malformed",
                                          "undecodable JSON or missing/"
                                          "non-string text field")
                    else:
                        wires.append((_malformed_wire(msg), msg.key))
                else:
                    wires.append((blob[start:end], msg.key))
                    start = end
            off = hi
        return wires

    def _deliver(self, inflight: "_InFlight", wires: List[tuple],
                 t1: float) -> int:
        msgs = inflight.msgs
        bt = inflight.trace
        t_del = time.perf_counter() if bt is not None else 0.0
        produce_batch = getattr(self.producer, "produce_batch", None)
        if produce_batch is not None:
            produce_batch(self.output_topic, wires)
            if inflight.dead:
                produce_batch(self.dlq_topic, inflight.dead)
        else:
            for wire, key in wires:
                self.producer.produce(self.output_topic, wire, key=key)
            if inflight.dead:
                for wire, key in inflight.dead:
                    self.producer.produce(self.dlq_topic, wire, key=key)

        # Produce-then-commit: at-least-once with durable progress (fixes Q2).
        # Commit ONLY if the producer fully drained — committing past
        # undelivered outputs would silently drop messages. Skipping the
        # commit only preserves at-least-once if we also STOP: continuing
        # would let a later batch's commit advance past this batch's offsets
        # and orphan the lost outputs. Restart re-consumes from the last
        # committed offset and re-drives this batch. Offsets are committed
        # per batch (commit_offsets), so a batch already consumed in flight
        # behind this one is never prematurely committed.
        undelivered = self.producer.flush()
        if undelivered:
            # NOT counted as processed: the batch's outputs are (partially)
            # lost and its offsets uncommitted, so a restart re-drives it —
            # counting it would let a supervisor believe the work is done.
            self.stats.commits_skipped += 1
            self._flush_fail_streak += 1
            self._flush_failed = True
            self._running = False
            if bt is not None:
                # The batch will be replayed: close the deliver leg as
                # failed and keep the whole trace (aborted batches are
                # interesting by definition).
                bt.add("deliver", time.perf_counter() - t_del, ok=False,
                       detail=f"undelivered={undelivered}")
                self._rowtrace.abort(bt, "flush_failed")
            return 0
        self._flush_fail_streak = 0
        try:
            self.consumer.commit_offsets(inflight.offsets)
        except CommitFailedError as e:
            # The group rebalanced with this batch in flight: its outputs are
            # already produced, the commit is fenced, and the partition's new
            # owner will reprocess — standard Kafka at-least-once. This is a
            # ROUTINE event for N workers in one group (every join/leave
            # re-deals partitions), so the engine carries on polling under
            # its refreshed assignment instead of dying; duplicated outputs
            # are the documented delivery semantics, not a failure.
            self.stats.rebalanced_commits += 1
            log.info("commit fenced by rebalance (batch stays at-least-once): %s", e)

        # Batch delivered: clear poison-attempt tracking for every offset
        # this batch's commit covers (fenced commits clear too — the outputs
        # stand; a new owner's replay recounts from zero, which is the
        # consecutive-failure semantics the screen wants). Keeps the tracker
        # bounded to in-flight + recently-failed rows.
        if self._dlq_attempts:
            done = inflight.offsets
            for key in [k for k in self._dlq_attempts
                        if k[2] < done.get((k[0], k[1]), 0)]:
                del self._dlq_attempts[key]
        n_dead = len(inflight.dead) if inflight.dead else 0
        if n_dead:
            self.stats.dead_lettered += n_dead
            for reason, n in inflight.dead_reasons.items():
                self._dlq_counts[reason] = self._dlq_counts.get(reason, 0) + n

        # Active processing latency: dispatch-side host work + this finish
        # leg (device wait, produce, flush, commit). Excludes time the batch
        # spent parked behind the next batch's poll — that's pipeline
        # queueing, not processing, and would inflate the number by up to
        # max_wait on a sparse stream.
        finish_dt = time.perf_counter() - t1
        dt = inflight.dispatch_time + finish_dt
        self.stats.processed += len(msgs) + inflight.dead_screened
        self.stats.shed += inflight.shed_n
        self.stats.batches += 1
        self.stats.record_latency(dt)
        if msgs:
            # Per-row enqueue->produce latency (the number a caller sees,
            # queue wait included): producer timestamp when the transport
            # carries one, else this batch's poll-receipt stamp. One
            # vectorized pass + one sketch insert per batch.
            now_wall = time.time()
            ts = np.fromiter((m.timestamp for m in msgs), np.float64,
                             len(msgs))
            lats = np.where(ts > 0.0, now_wall - ts,
                            now_wall - inflight.recv_wall)
            self.stats.row_sketch.add_many(lats)
            if self._sched is not None:
                self._sched.observe_batch(len(msgs), dt, lats)
        self._last_batch_at = self._clock()
        if bt is not None:
            if msgs and getattr(self._rowtrace, "record_rows", False):
                # Record mode (scenarios/record.py): one compact block per
                # batch carrying every delivered row's source coordinates —
                # the census an exact replay needs. Same one-entry cost
                # shape as the flag block; off unless a recording is live.
                bt.events_rows("row", [(m.partition, m.offset)
                                       for m in msgs])
            # Terminal: the deliver leg closes and the batch's spans
            # commit to the ring (kept when sampled or interesting).
            bt.add("deliver", time.perf_counter() - t_del,
                   detail=f"rows={len(wires)}")
            self._rowtrace.commit(bt)
        return len(msgs) + inflight.dead_screened

    def process_batch(self, msgs: List[Message]) -> int:
        """Score one micro-batch synchronously and emit results.

        Refuses after a failed flush (flightcheck FC403 true positive):
        unlike run(), which resets ``_flush_failed`` as a fresh-incarnation
        boundary, a caller looping process_batch would otherwise commit the
        NEXT batch's (later) offsets right past the failed batch's lost
        outputs. Rebuild the engine — or enter run(), whose reset declares
        a new incarnation — before scoring more batches."""
        with self._drive_region:
            if self._flush_failed:
                raise RuntimeError(
                    "a previous batch's producer flush failed with its "
                    "offsets uncommitted — committing a later batch would "
                    "orphan its outputs; rebuild the engine (or use run(), "
                    "which declares a fresh incarnation) to resume")
            return self._finish(self._dispatch(msgs))

    def run(self, max_messages: Optional[int] = None,
            idle_timeout: Optional[float] = None) -> StreamStats:
        """Run the loop until stopped, ``max_messages`` handled, or the input
        stays empty for ``idle_timeout`` seconds.

        Depth-K software pipeline (K = ``pipeline_depth``): up to K batches'
        device scoring is in flight while the host polls, decodes, and
        featurizes the next batch. Batches finish strictly FIFO, so offsets
        commit in order. Depth 1 recovers serial dispatch->finish; depth >= 2
        hides the device round-trip (dispatch, execute, fetch) behind the
        host work of the following batches."""
        with self._drive_region:
            if self._stopped:
                return self.stats          # stop() latched: stay stopped
            # State writes only AFTER the region admits us: a second run()
            # resetting _running/_flush_failed before its RaceError fired
            # would corrupt the active run's abort logic.
            self._running = True
            if self._stopped:
                # stop() raced between the latch check and the _running
                # write (its _running=False just got overwritten) — honor
                # it; _stopped is monotonic, so this re-check closes the
                # window (fifth-pass review).
                self._running = False
                return self.stats
            self._flush_failed = False
            # Pin the model HBM-resident off the hot path (once per model
            # version — pin_device is idempotent; hot-swap candidates
            # re-pin at stage/swap prewarm).
            pin = getattr(self.pipeline, "pin_device", None)
            if callable(pin):
                pin()
            started = time.perf_counter()
            idle_since: Optional[float] = None
            # From the first poll on, an executable obtained is one a
            # request paid for (health()["compile"]).
            obs_trace.BOOT.serving = True
            if self.async_dispatch:
                return self._run_loop_async(started, idle_since,
                                            max_messages, idle_timeout)
            in_flight: "deque[_InFlight]" = deque()
            return self._run_loop(started, idle_since, in_flight,
                                  max_messages, idle_timeout)

    def _run_loop(self, started, idle_since, in_flight, max_messages,
                  idle_timeout) -> StreamStats:
        try:
            while self._running:
                budget = self.batch_size
                if max_messages is not None:
                    consumed = self.stats.processed + sum(
                        len(f.msgs) + f.dead_screened for f in in_flight)
                    budget = min(budget, max_messages - consumed)
                if budget <= 0:
                    if in_flight:
                        self._finish(in_flight.popleft())
                        self._inflight_depth = len(in_flight)
                        continue
                    break
                if self._sched is not None:
                    # Scheduler-owned handoff: governor-paced, deadline-
                    # driven accumulation (sched/scheduler.py collect).
                    msgs = self._sched.collect(self.consumer, budget,
                                               self.max_wait)
                else:
                    msgs = self.consumer.poll_batch(budget, self.max_wait)
                if not msgs:
                    if in_flight:
                        # Drain the tail rather than idling behind it.
                        self._finish(in_flight.popleft())
                        self._inflight_depth = len(in_flight)
                        continue
                    now = time.perf_counter()
                    idle_since = idle_since or now
                    if idle_timeout is not None and now - idle_since >= idle_timeout:
                        break
                    continue
                idle_since = None
                in_flight.append(self._dispatch(msgs))
                self._max_inflight = max(self._max_inflight, len(in_flight))
                if len(in_flight) > self.pipeline_depth:
                    self._finish(in_flight.popleft())
                self._inflight_depth = len(in_flight)
        except BaseException:
            # An exception (including Ctrl-C) may have landed mid-_finish
            # after some produces succeeded. Do NOT drain newer in-flight
            # batches below: committing their (later) offsets would orphan the
            # interrupted batch's outputs. Leaving them uncommitted means a
            # restart replays them — at-least-once, as documented.
            self._abort_traces(in_flight, "engine_abort")
            in_flight.clear()
            raise
        finally:
            # Interrupt-safe: Ctrl-C lands here with correct elapsed stats.
            # Batches still in flight after a flush failure must NOT be
            # finished: committing their (later) offsets would orphan the
            # failed batch's outputs.
            while in_flight and not self._flush_failed:
                self._finish(in_flight.popleft())
            self._abort_traces(in_flight, "discarded_after_flush_failure")
            self._inflight_depth = 0
            # The loop can exit via break with the flag still set; clear it
            # so health() reports a finished engine as not running.
            self._running = False
            self.stats.elapsed = time.perf_counter() - started
        return self.stats

    def _run_loop_async(self, started, idle_since, max_messages,
                        idle_timeout) -> StreamStats:
        """The drive loop with the double-buffered dispatch lane: identical
        batch schedule and delivery invariants to ``_run_loop``, except the
        featurize+launch leg of each batch runs on the lane thread. The
        driver polls, admits, submits, and delivers; ``lane.next()`` returns
        batches strictly FIFO, so offsets commit in order exactly as in
        synchronous mode, and a lane-side failure re-raises here at the
        failed batch's position (newer batches are then discarded
        uncommitted — at-least-once replay, as documented)."""
        from fraud_detection_tpu.sched.batcher import DispatchLane

        lane = DispatchLane(self._launch, depth=self.pipeline_depth)
        self._lane = lane
        pending: "deque[_Prep]" = deque()   # submitted, not yet delivered
        discarded: list = []                # abandoned batches (traces close
                                            # after the lane thread joins)
        try:
            while self._running:
                budget = self.batch_size
                if max_messages is not None:
                    consumed = self.stats.processed + sum(
                        p.n_rows for p in pending)
                    budget = min(budget, max_messages - consumed)
                if budget <= 0:
                    if pending:
                        self._finish(lane.next())
                        pending.popleft()
                        self._inflight_depth = len(pending)
                        continue
                    break
                if self._sched is not None:
                    msgs = self._sched.collect(self.consumer, budget,
                                               self.max_wait)
                else:
                    msgs = self.consumer.poll_batch(budget, self.max_wait)
                if not msgs:
                    if pending:
                        # Drain the tail rather than idling behind it.
                        self._finish(lane.next())
                        pending.popleft()
                        self._inflight_depth = len(pending)
                        continue
                    now = time.perf_counter()
                    idle_since = idle_since or now
                    if idle_timeout is not None and now - idle_since >= idle_timeout:
                        break
                    continue
                idle_since = None
                prep = self._prepare(msgs)
                lane.submit(prep)
                pending.append(prep)
                if len(pending) > self.pipeline_depth:
                    self._finish(lane.next())
                    pending.popleft()
                self._inflight_depth = len(pending)
        except BaseException:
            # Same abort contract as the sync loop: never finish newer
            # batches past an interrupted/failed one — leave them
            # uncommitted for the restart to replay. Their traces close
            # below, AFTER lane.stop() joins the worker (the lane may
            # still be appending spans to these batches' traces here).
            discarded.extend(pending)
            pending.clear()
            raise
        finally:
            try:
                while pending and not self._flush_failed:
                    self._finish(lane.next())
                    pending.popleft()
            finally:
                lane.stop()
                discarded.extend(pending)
                self._abort_traces(discarded, "engine_abort")
                self._lane_stats = lane.stats()
                self._max_inflight = max(self._max_inflight,
                                         lane.max_inflight)
                self._lane = None
                self._inflight_depth = 0
                self._running = False
                self.stats.elapsed = time.perf_counter() - started
        return self.stats


@dataclass
class _Prep:
    """A polled micro-batch after driver-side admission (shed + poison
    screen), ready for the featurize+launch leg (``_launch``) — the unit
    the async dispatch lane carries between threads."""
    msgs: List[Message]
    offsets: dict
    dead: Optional[List[tuple]]
    dead_reasons: Optional[dict]
    shed_n: int
    prep_time: float            # driver seconds spent preparing
    trace: Optional[object] = None  # obs.trace.BatchTrace (tracing on)

    @property
    def n_rows(self) -> int:
        """Rows this batch accounts for (kept + screened/shed)."""
        return len(self.msgs) + (len(self.dead) if self.dead else 0)


@dataclass
class _InFlight:
    """A micro-batch whose device scoring has been dispatched but not resolved."""
    msgs: List[Message]
    texts: List[Optional[str]]  # decoded strs; raw mode: raw literal bytes
    valid_idx: List[int]
    pending: Optional[object]   # models.pipeline.PendingPrediction
    offsets: dict               # (topic, partition) -> next offset to commit
    dispatch_time: float        # host seconds spent in _dispatch
    raw: bool = False           # raw-JSON mode: pending covers ALL rows
                                # positionally; texts[i] is the string literal
    # Native frame-assembly context (raw mode): per-chunk marshalled message
    # arrays + the batch's span arrays; texts may then be lazily-unbuilt.
    splice: Optional[tuple] = None  # (ctxs, span_start, span_len)
    # Dead-letter rows riding this batch (DLQ mode only): (record, key)
    # wires for the DLQ topic + per-reason counts, delivered/committed with
    # the batch. None = nothing diverted (the common case costs nothing).
    dead: Optional[List[tuple]] = None
    dead_reasons: Optional[dict] = None
    dead_screened: int = 0      # dead rows NOT in msgs (poison screen + shed)
    shed_n: int = 0             # of dead_screened, rows shed by admission
    recv_wall: float = 0.0      # wall-clock poll receipt (latency fallback)
    trace: Optional[object] = None  # obs.trace.BatchTrace (tracing on)


def run_supervised(make_engine: Callable[[], StreamingClassifier], *,
                   max_restarts: int = 5,
                   backoff: float = 0.5,
                   backoff_cap: float = 30.0,
                   max_messages: Optional[int] = None,
                   idle_timeout: Optional[float] = None,
                   sleep=time.sleep,
                   jitter: bool = True,
                   rng: Optional[random.Random] = None) -> StreamStats:
    """Failure-detecting restart loop around the streaming engine.

    The reference's loop dies on the first Kafka error and, because it never
    commits offsets, restarts by re-reading the topic from the beginning
    (SURVEY.md Q2 / §5 "no elasticity"). Here the commit protocol makes a
    crash recoverable: ``make_engine`` builds a fresh engine (new consumer —
    it resumes from the group's last committed offsets), restarts use
    exponential backoff, and the backoff resets after any healthy run that
    made progress. Gives up after ``max_restarts`` consecutive failures and
    re-raises the last error (with the aggregated stats attached as
    ``.supervisor_stats`` so callers can report partial progress).

    Backoff uses FULL JITTER: each wait is uniform in [0, min(backoff *
    2^(n-1), backoff_cap)]. A broker outage fails every worker in the same
    instant; deterministic backoff would march N consumers back into the
    group coordinator in synchronized waves (each wave a rebalance storm),
    while jittered restarts spread the rejoins across the whole window.
    ``jitter=False`` restores the deterministic ceiling; ``rng`` injects a
    seeded ``random.Random`` for reproducible schedules (tests, chaos runs).

    Aggregated StreamStats across incarnations (restarts counted).
    """
    uniform = (rng.uniform if rng is not None else random.uniform)
    total = StreamStats()
    consecutive = 0
    while True:
        budget = None if max_messages is None else max_messages - total.processed
        if budget is not None and budget <= 0:
            break
        engine: Optional[StreamingClassifier] = None
        failed: Optional[BaseException] = None
        interrupted = False
        stats = StreamStats()
        try:
            # make_engine is inside the guard: with the broker down, building
            # the clients themselves can raise — that's a failed incarnation
            # (backoff + retry), not a supervisor crash.
            engine = make_engine()
            stats = engine.run(max_messages=budget, idle_timeout=idle_timeout)
        except KeyboardInterrupt:
            # Operator shutdown: report what was done, don't restart.
            if engine is not None:
                stats = engine.stats
            interrupted = True
        except Exception as e:  # noqa: BLE001 — supervisor's whole job
            if engine is not None:
                stats = engine.stats
            failed = e
        finally:
            # The supervisor owns client lifecycles: a crashed incarnation's
            # consumer must leave the group promptly (a zombie would hold its
            # partition assignment until session timeout and stall the
            # replacement), and sockets must not accumulate across restarts.
            if engine is not None:
                for client in (engine.consumer, engine.producer):
                    close = getattr(client, "close", None)
                    if close is not None:
                        try:
                            close()
                        except Exception:  # noqa: BLE001
                            pass
        _merge_stats(total, stats)
        if interrupted:
            break
        flush_failed = stats.commits_skipped > 0
        if failed is None and not flush_failed:
            break  # clean exit (idle timeout / max_messages / stop())
        if stats.processed > 0:
            consecutive = 0  # made progress: treat as a fresh incident
        consecutive += 1
        if consecutive > max_restarts:
            if failed is None:
                failed = RuntimeError(
                    f"producer flush kept failing after {max_restarts} "
                    f"restarts (last committed offsets hold; "
                    f"{total.processed} processed)")
            # Attach partial progress: the raise discards the return value,
            # and serve.py's give-up path still owes the operator a stats
            # line + final health instead of a bare traceback.
            failed.supervisor_stats = total
            raise failed
        total.restarts += 1
        delay = min(backoff * (2 ** (consecutive - 1)), backoff_cap)
        if jitter:
            delay = uniform(0.0, delay)
        try:
            sleep(delay)
        except KeyboardInterrupt:
            break  # operator shutdown during backoff: report and stop
    return total


def _merge_stats(total: StreamStats, part: StreamStats) -> None:
    total.processed += part.processed
    total.malformed += part.malformed
    total.dead_lettered += part.dead_lettered
    total.shed += part.shed
    total.batches += part.batches
    total.commits_skipped += part.commits_skipped
    total.rebalanced_commits += part.rebalanced_commits
    total.elapsed += part.elapsed
    # Sum/max merge exactly; the percentile reservoir merges by samples (an
    # incarnation that overflowed its reservoir contributes its subsample —
    # percentiles stay estimates, mean/max stay exact).
    total.batch_latency_sum += part.batch_latency_sum
    total.batch_latency_max = max(total.batch_latency_max, part.batch_latency_max)
    for dt in part.latencies:
        total._reservoir_add(dt)
    # The row-latency sketch merges losslessly (bucket counts add).
    total.row_sketch.merge(part.row_sketch)
