"""Asynchronous LLM annotation lane: classification never waits for decode.

The reference pays a BLOCKING LLM round-trip inside its per-message serve
loop (app_ui.py:195-248 — one DeepSeek HTTPS call per flagged dialogue, so
stream throughput collapses to the LLM's rate). The inline
``explain_batch_fn`` hook here already amortizes that to one on-pod device
program per micro-batch, but it still serializes CLASSIFICATION behind
DECODE: a multi-second 48-token batch generate caps the whole stream at the
annotator's ~dozen explanations/sec (measured: 5.2k msgs/s no-hook vs ~114
with the inline hook on one chip).

This lane decouples them. Flagged rows are copied into a bounded queue and
the classified frames go out IMMEDIATELY (no "analysis" field — which also
keeps the native raw-JSON frame path, disabled under inline hooks, in
play); a single worker thread hands queued rows to the hook, at most
``max_batch`` handed over and not yet delivered, and produces annotation
records to a side topic (``<output_topic>-annotations``), keyed like their
source messages so they partition identically. What "handed over" means
follows from what the hook hands back. A hook that serves rows one at a time
(``make_slot_explain_hook`` over a slot service) advertises ``submit_rows``
and returns each row's TICKET unresolved: the lane delivers a row the moment
its ticket resolves and tops the window up from the queue as places free, so
no row waits for another's decode and the slots behind the hook never run
dry while rows queue here. A hook that serves a micro-batch with one device
program (``make_stream_explain_hook`` over ``OnPodBackend``, or any plain
``(texts, labels, confs) -> analyses`` callable) resolves all its rows at the
call's return: the window empties and refills whole, i.e. the queue drains in
micro-batches of ``max_batch``. One loop serves both; the batch is its
degenerate case. When flagged rows arrive faster than the LLM can
decode — the steady state: 5% of 30k/s is ~1.5k flagged/s against ~12
explanations/s — the queue drops OLDEST first and counts it: annotating a
recent sample beats throttling classification 250x, and the drop counter
makes the sampling rate an explicit, recorded fact rather than a stall.

Consumers join annotations to classifications by message key (the
classified frame stream stays complete; annotations are best-effort
enrichment). Degraded mode matches the inline hook's: a raising backend is
logged and dropped, classification untouched.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque
from typing import Callable, Dict, List

from fraud_detection_tpu.explain.prompts import label_name
from fraud_detection_tpu.utils import get_logger

log = get_logger("stream.annotations")


class AsyncAnnotationLane:
    """Bounded background annotator feeding a side topic.

    ``explain_batch_fn``: the SAME hook shape the inline path takes
    ((texts, labels, confs) -> [analysis | None]) — e.g.
    ``make_stream_explain_hook(OnPodBackend...)``. Rows whose analysis
    comes back None produce no record (the hook's own selection policy).
    A hook that advertises ``submit_rows`` (same arguments) is called
    through it and may hand back, in a row's place, a TICKET:
    ``add_done_callback(fn)`` calls ``fn(ticket)`` once when the row has
    resolved (at once if it already has; quick, from any thread),
    ``result()`` then returns its analysis (or None) without blocking, or
    raises; an optional ``timeout`` attribute says how many seconds after
    the hand-over the lane stops waiting and takes ``result()`` as it is
    (``concurrent.futures.Future`` has the shape).

    ``max_batch``: the most rows handed to the hook and not yet delivered
    (``stats()["in_flight"]``): a batch hook's micro-batch, a ticket
    hook's window.

    ``producer``/``topic``: where annotation records go. Records are JSON:
    ``{"prediction", "label", "confidence", "analysis"}`` keyed by the
    source message's key. The producer must be the lane's OWN (a second
    client on the same transport), never shared with the engine: flush()
    is how both sides account delivery, and sharing would let either side
    consume the other's failures (StreamingClassifier enforces this).
    """

    def __init__(self, explain_batch_fn: Callable, producer, topic: str, *,
                 max_queue: int = 1024, max_batch: int = 64,
                 rowtrace=None,
                 clock: Callable[[], float] = time.perf_counter):
        if max_queue < 1 or max_batch < 1:
            raise ValueError(
                f"max_queue/max_batch must be >= 1, got {max_queue}/{max_batch}")
        self._clock = clock   # injectable: drain/close deadlines in tests
        # Optional obs.trace.RowTracer: items may carry a 5th element (the
        # row's correlation id), and the lane then records a "lane_wait"
        # span per row (enqueued here -> handed to the hook), an
        # "explain" span per backend call plus an "annotate" event per row
        # — ok=False on backend errors AND breaker fast-fails, so a flagged
        # row's chain shows exactly where its explanation died. Flagged
        # rows are always-kept by the tracer, so these record directly to
        # the ring.
        self._rowtrace = rowtrace
        self._fn = explain_batch_fn
        self._producer = producer
        self.topic = topic
        self.max_queue = max_queue
        self.max_batch = max_batch
        self._q: deque = deque()     # (item, enqueue stamp | None)
        self._cv = threading.Condition()
        self._closed = False
        # Rows handed to the hook and not yet delivered (under _cv), and
        # the tickets among them, in hand-over order: ticket -> (item,
        # deadline | None), the worker's own; ``_resolved`` (under _cv)
        # holds the tickets that have called back.
        self._in_flight = 0
        self._pending: Dict[object, tuple] = {}
        self._resolved: list = []
        # Structured drop records pending emission (built at the drop
        # site under _cv, produced by the WORKER so they ride the lane's
        # single-producer delivery accounting): (value_bytes, key, cid).
        self._drop_backlog: List[tuple] = []
        # Counters guarded by _cv's lock (submitted/dropped mutate under it);
        # annotated/errors are worker-thread-only writes, read-racy by design
        # (stats snapshots, not invariants).
        self.submitted = 0
        self.dropped = 0
        # Drop records DELIVERED to the side topic (worker-thread tally,
        # like ``annotated``): a drop-OLDEST eviction is not a bare
        # counter — it emits a structured record carrying the row's trace
        # cid, so under slotserve every flagged row is explained OR
        # accounted, join-able to ``chain(cid)``. ``dropped`` >
        # ``drop_records`` only for close()-residual discards (no worker
        # left to deliver them) or undelivered flushes — both logged.
        self.drop_records = 0
        self.annotated = 0
        self.backend_errors = 0
        # Records handed to the producer across the lane's lifetime: the
        # ``annotated`` credit is the running delivered total (produced -
        # flush()'s producer-queue depth), NOT a per-batch subtraction —
        # flush() counts the whole producer queue, so records a previous
        # failed flush left behind would otherwise be double-subtracted
        # (ADVICE round 5). Worker-thread-only, like ``annotated``.
        self.produced = 0
        self._idle = threading.Event()
        self._idle.set()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="annotation-lane")
        self._thread.start()

    def submit(self, items: List[tuple]) -> None:
        """Enqueue (key, text, label, confidence[, trace_cid]) rows;
        never blocks.

        Over capacity, the OLDEST queued rows are dropped and counted —
        under sustained overload the lane annotates a sliding recent
        sample — and each eviction leaves a STRUCTURED drop record
        (``{"dropped": true, "reason": "queue_overflow", "trace": cid}``
        keyed like the source row) for the worker to produce to the side
        topic: the sampling rate is a recorded, join-able fact per row,
        not a bare counter.
        """
        if not items:
            return
        tr = self._rowtrace
        at = tr.wall() if tr is not None else None
        with self._cv:
            if self._closed:
                return
            for it in items:
                if len(self._q) >= self.max_queue:
                    old, _at = self._q.popleft()
                    self.dropped += 1
                    self._drop_backlog.append(
                        self._drop_record(old, "queue_overflow"))
                self._q.append((it, at))
            self.submitted += len(items)
            self._idle.clear()
            self._cv.notify()

    @staticmethod
    def _drop_record(item: tuple, reason: str) -> tuple:
        """Build one structured drop record from a queued item; returns
        (value_bytes, key, cid). Schema mirrors the annotation record
        (docs/robustness.md): same key, ``analysis`` null, ``dropped``
        true, ``trace`` = the row's correlation id when the engine traces
        — a DLQ-style accounting record on the annotations topic."""
        key, _text, label, conf = item[:4]
        cid = item[4] if len(item) == 5 else None
        rec = {"prediction": label, "label": label_name(label),
               "confidence": round(conf, 6), "analysis": None,
               "dropped": True, "reason": reason}
        if cid is not None:
            rec["trace"] = cid
        return json.dumps(rec).encode(), key, cid

    def _count_error(self) -> None:
        # flightcheck: ignore[FC102] — worker-thread-only counter, read-racy by design (see __init__)
        self.backend_errors += 1

    def _ticket_done(self, ticket) -> None:
        """A handed-over row's ticket resolved (any thread: whoever
        resolved it). Notes it and wakes the worker, which delivers."""
        with self._cv:
            self._resolved.append(ticket)
            self._cv.notify()

    def _overdue(self) -> list:
        """Tickets whose wait has run out, oldest first (worker thread;
        rows are handed over in order, so the scan stops at the first
        that still has time). The clock is read only while one waits."""
        out = []
        for ticket, (_item, deadline) in self._pending.items():
            if deadline is None or self._clock() < deadline:
                break
            out.append(ticket)
        return out

    def _run(self) -> None:
        while True:
            with self._cv:
                while True:
                    overdue = self._overdue()
                    if (self._drop_backlog or self._resolved or overdue
                            or (self._q and self._in_flight < self.max_batch)):
                        break
                    # Nothing to do. With rows in flight the worker waits
                    # for their tickets (close() included: they stay its
                    # to deliver); with none, the lane is idle.
                    if not self._in_flight:
                        self._idle.set()
                        if self._closed:
                            return
                    self._cv.wait(timeout=0.2)
                drops, self._drop_backlog = self._drop_backlog, []
                done, self._resolved = self._resolved, []
                # A place frees when its row is DELIVERED: the rows in
                # ``done`` still hold theirs, so a resolved ticket admits
                # its successor on the next pass, after its record is out.
                batch = [self._q.popleft() for _ in range(
                    min(len(self._q), self.max_batch - self._in_flight))]
                self._in_flight += len(batch)
            if drops:
                # Before anything else: a drop record must not wait behind
                # a decode — its row's accounting is already due.
                try:
                    self._emit_drops(drops)
                except Exception:  # noqa: BLE001 — lane must survive anything
                    self._count_error()
                    log.exception("emitting %d drop records failed "
                                  "(counted in dropped, not drop_records)",
                                  len(drops))
            if done or overdue:
                self._settle(done + overdue)
            if batch:
                self._trace_waits(batch)
                self._hand_over([it for it, _at in batch])

    def _trace_waits(self, batch: List[tuple]) -> None:
        """A ``lane_wait`` span per traced row just taken: enqueued on the
        lane -> handed to the hook. A row waits here only for a place
        among the ``max_batch`` in flight (a batch hook: for the
        micro-batch ahead of it)."""
        tr = self._rowtrace
        if tr is None:
            return
        taken = tr.wall()
        for it, at in batch:
            if at is not None and len(it) == 5 and it[4] is not None:
                tr.record_span(it[4], "lane_wait", max(0.0, taken - at),
                               start=at)

    def _emit_drops(self, drops: List[tuple]) -> None:
        """Produce + flush the pending structured drop records (worker
        thread, the lane's own producer — same delivery accounting rule as
        annotation records: produce, then flush, then count delivered)."""
        for value, key, _cid in drops:
            self._producer.produce(self.topic, value, key=key)
        undelivered = int(self._producer.flush() or 0)
        delivered = len(drops) - min(len(drops), undelivered)
        # flightcheck: ignore[FC102] — worker-thread-only tally, read-racy by design
        self.drop_records += delivered
        if undelivered:
            log.warning("producer left %d drop records undelivered "
                        "(dropped counter stays ahead of drop_records)",
                        undelivered)
        if self._rowtrace is not None:
            for _value, _key, cid in drops:
                if cid is not None:
                    self._rowtrace.record_event(
                        cid, "annotate", ok=False,
                        detail="dropped:queue_overflow")

    def _hand_over(self, batch: List[tuple]) -> None:
        """Give the rows just taken to the hook (they are already counted
        in flight). What comes back resolved is delivered now; a ticket
        keeps its row's place until it calls back."""
        # Items are (key, text, label, conf[, cid]) — the correlation id
        # rides only when the engine traces; normalize for both shapes.
        batch = [it if len(it) == 5 else (*it, None) for it in batch]
        kept = 0
        try:
            now, now_analyses = [], []
            for item, analysis in zip(batch, self._call_hook(batch)):
                if not hasattr(analysis, "add_done_callback"):
                    now.append(item)
                    now_analyses.append(analysis)
                    continue
                timeout = getattr(analysis, "timeout", None)
                analysis.add_done_callback(self._ticket_done)
                self._pending[analysis] = (
                    item, None if timeout is None else self._clock() + timeout)
                kept += 1
            self._deliver(now, now_analyses)
        except Exception:  # noqa: BLE001 — lane must survive anything
            self._count_error()
            log.exception("annotation batch failed (%d rows dropped); "
                          "classification unaffected", len(batch) - kept)
        finally:
            with self._cv:
                self._in_flight -= len(batch) - kept

    def _call_hook(self, batch: List[tuple]) -> list:
        """One hook call over normalized rows; returns one entry a row
        (analysis, None or ticket). Raises what the hook raises, and on a
        wrong count, before any row has become a ticket."""
        _keys, texts, labels, confs, cids = map(list, zip(*batch))
        tr = self._rowtrace
        fn = getattr(self._fn, "submit_rows", self._fn)
        try:
            # The call's own span ("lane" chain), open around the hook so
            # a profiler capture shows it on this thread: a micro-batch's
            # whole decode under a batch hook, the hand-over alone under a
            # ticket hook.
            with (tr.span("lane", "explain", detail=f"rows={len(batch)}")
                  if tr is not None else contextlib.nullcontext()):
                if getattr(self._fn, "accepts_cids", False):
                    # Slotserve hooks (explain/slotserve/
                    # make_slot_explain_hook) take the rows' trace cids so
                    # each explanation's slot + latency lands on the row's
                    # own chain(cid).
                    analyses = fn(texts, labels, confs, cids=cids)
                else:
                    analyses = fn(texts, labels, confs)
        except Exception as e:
            if tr is not None:
                # The span above closed ok=False naming the exception; a
                # failed annotate event per traced row on top: breaker
                # fast-fails (BreakerOpenError) land here too, so
                # breaker-tripped rows keep a complete chain by id.
                for cid in cids:
                    if cid is not None:
                        tr.record_event(cid, "annotate", ok=False,
                                        detail=type(e).__name__)
            raise
        if len(analyses) != len(batch):  # mirrors the engine's inline check
            raise ValueError(f"explain_batch_fn returned {len(analyses)} "
                             f"analyses for {len(batch)} rows")
        return analyses

    def _settle(self, tickets: list) -> None:
        """Deliver the rows whose tickets have resolved (or run out of
        time), together: one produce, one flush. A ticket that raises
        costs its own row alone."""
        rows, analyses = [], []
        for ticket in tickets:
            item, _deadline = self._pending.pop(ticket, (None, None))
            if item is None:       # settled before: overdue, then resolved
                continue
            try:
                analysis = ticket.result()
            except Exception as e:  # noqa: BLE001 — lane must survive anything
                analysis = None
                self._count_error()
                log.exception("annotation ticket failed (its row dropped); "
                              "classification unaffected")
                if self._rowtrace is not None and item[4] is not None:
                    self._rowtrace.record_event(item[4], "annotate", ok=False,
                                                detail=type(e).__name__)
            rows.append(item)
            analyses.append(analysis)
        try:
            self._deliver(rows, analyses)
        except Exception:  # noqa: BLE001 — lane must survive anything
            self._count_error()
            log.exception("delivering %d annotations failed; "
                          "classification unaffected", len(rows))
        finally:
            with self._cv:
                self._in_flight -= len(rows)

    def _deliver(self, rows: List[tuple], analyses: list) -> None:
        """Resolved rows become their records at once: produce, flush,
        count, then each row's ``annotate`` event."""
        out = []
        out_cids = []
        for (key, _text, label, conf, cid), analysis in zip(rows, analyses):
            if analysis is None:
                continue
            rec = {"prediction": label, "label": label_name(label),
                   "confidence": round(conf, 6), "analysis": analysis}
            out.append((json.dumps(rec).encode(), key))
            out_cids.append(cid)
        if out:
            batch_produce = getattr(self._producer, "produce_batch", None)
            if batch_produce is not None:
                batch_produce(self.topic, out)
            else:
                for value, key in out:
                    self._producer.produce(self.topic, value, key=key)
            # Flush before counting: with a real Kafka producer, produce()
            # only enqueues into librdkafka — records still queued when the
            # process exits are LOST, and the drop/annotated counters are
            # the lane's recorded-fact contract. An explanation takes
            # seconds of decode, so a flush per delivery costs nothing.
            self.produced += len(out)
            undelivered = self._producer.flush()
            if undelivered:
                self._count_error()
                log.warning("producer left %d annotation records "
                            "undelivered (counted as not annotated)",
                            undelivered)
            # Running delivered tally: a later successful flush of records a
            # previous one left queued credits them then, exactly once. The
            # max() keeps the counter monotonic while the queue is deep.
            # flightcheck: ignore[FC102] — worker-thread-only tally, read-racy by design
            self.annotated = max(self.annotated,
                                 self.produced - int(undelivered))
            if self._rowtrace is not None:
                for cid in out_cids:
                    if cid is not None:
                        self._rowtrace.record_event(
                            cid, "annotate", ok=not undelivered)

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until the queue is empty, nothing is in flight and the
        worker is idle (or timeout). The lane stays usable after. True =
        fully drained.

        Bounded even against a HUNG backend: a worker stuck inside
        ``explain_batch_fn`` (or waiting on tickets that never resolve)
        never raises ``_idle``, so the wait simply expires — the caller
        gets False after ~``timeout``, never a deadlock. The deadline runs
        on the injectable ``clock``."""
        deadline = self._clock() + timeout
        while True:
            remaining = deadline - self._clock()
            if remaining <= 0:
                return False
            if self._idle.wait(timeout=min(remaining, 0.2)):
                with self._cv:
                    # Re-queued rows cleared _idle under the same lock (see
                    # submit), so observing idle + empty here is conclusive
                    # and a stale idle cannot busy-spin this loop. Pending
                    # drop records count as work: drained means every due
                    # accounting record reached the topic too, and so does
                    # a row in flight: drained means delivered.
                    if (not self._q and not self._drop_backlog
                            and not self._in_flight):
                        return True

    def close(self, timeout: float = 30.0) -> bool:
        """Drain best-effort, then stop the worker. True = clean shutdown
        (queue drained AND worker exited); False is honest about partial
        failure — rows discarded, or a worker hung in the backend (it is
        a daemon thread, so an un-joinable worker cannot block process
        exit, and a latched-closed lane drops any late submits).

        After the drain deadline the RESIDUAL QUEUE IS CLEARED under the
        lock, counting the discards as dropped, before ``_closed`` latches
        (ADVICE round 5): without this a slow worker kept draining
        multi-second LLM batches past close(), so ``annotation_stats()``
        read right after — serve.py's finish_annotations() does exactly
        that — snapshotted counters that were still mutating, and process
        exit could kill the daemon mid-flush. Clearing makes post-close
        stats quiescent up to the rows already handed to the hook
        (``in_flight``: at most ``max_batch``): those stay the worker's to
        deliver as they resolve, and it exits when none is left (bounded
        by the join below; a later close() waits for them again).

        Never blocks unboundedly: the drain phase is capped by ``timeout``
        and the join by a short window scaled to it — a backend that
        ignores interruption costs the caller ~timeout, not forever."""
        drained = self.drain(timeout)
        with self._cv:
            residual = len(self._q)
            if residual:
                self.dropped += residual
                self._q.clear()
            self._closed = True
            self._cv.notify()
        self._thread.join(timeout=min(5.0, max(0.2, timeout)))
        alive = self._thread.is_alive()
        if alive:
            log.warning("annotation worker still running after close() "
                        "(rows still in flight, or a hung backend); "
                        "daemon thread, counters may move for the rows "
                        "in flight")
        return drained and residual == 0 and not alive

    def stats(self) -> dict:
        with self._cv:
            depth = len(self._q)
            return {"submitted": self.submitted, "annotated": self.annotated,
                    "dropped": self.dropped,
                    "drop_records": self.drop_records,
                    "backend_errors": self.backend_errors,
                    "queue_depth": depth,
                    "in_flight": self._in_flight}
