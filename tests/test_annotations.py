"""Async annotation lane (stream/annotations.py): classification must never
wait for LLM decode. Covers the bounded-queue/drop-oldest contract, degraded
mode, and the engine integration — flagged rows annotate onto the side topic
while the classified frames ship analysis-free through the native fast path.
"""

import json
import queue
import threading
import time
from concurrent.futures import Future

import pytest

from fraud_detection_tpu.stream import AsyncAnnotationLane, InProcessBroker
from fraud_detection_tpu.stream import StreamingClassifier


@pytest.fixture(scope="module")
def pipeline():
    from fraud_detection_tpu.models.pipeline import synthetic_demo_pipeline

    return synthetic_demo_pipeline(batch_size=64, n=400, seed=3,
                                   num_features=2048,
                                   corpus_kwargs=dict(hard_fraction=0.0,
                                                      label_noise=0.0))


def _lane(broker, fn, **kw):
    return AsyncAnnotationLane(fn, broker.producer(), "annotations", **kw)


def test_lane_annotates_and_keys_records():
    broker = InProcessBroker(num_partitions=2)
    lane = _lane(broker, lambda t, l, c: [f"analysis {x}" for x in l])
    lane.submit([(b"k1", "text one", 1, 0.9), (b"k2", "text two", 2, 0.8)])
    assert lane.close(timeout=10.0)
    recs = broker.messages("annotations")
    assert len(recs) == 2
    by_key = {m.key: json.loads(m.value) for m in recs}
    assert by_key[b"k1"] == {"prediction": 1, "label": "Potential Scam",
                             "confidence": 0.9, "analysis": "analysis 1"}
    assert by_key[b"k2"]["prediction"] == 2
    assert lane.stats() == {"submitted": 2, "annotated": 2, "dropped": 0,
                            "drop_records": 0, "backend_errors": 0,
                            "queue_depth": 0, "in_flight": 0}


def test_lane_bounded_queue_drops_oldest():
    broker = InProcessBroker()
    gate = threading.Event()
    seen = []

    def fn(texts, labels, confs):
        gate.wait(5.0)               # hold the worker so the queue fills
        seen.extend(texts)
        return ["a"] * len(texts)

    lane = _lane(broker, fn, max_queue=4, max_batch=64)
    # One submit call is atomic vs the worker: 10 rows into a 4-slot queue
    # drops the 6 oldest.
    lane.submit([(None, f"t{i}", 1, 0.5) for i in range(10)])
    gate.set()
    assert lane.close(timeout=10.0)
    s = lane.stats()
    assert s["submitted"] == 10 and s["dropped"] == 6
    # The kept rows are the NEWEST (a sliding recent sample under overload).
    assert set(seen) <= {f"t{i}" for i in range(6, 10)}


def test_lane_batches_at_max_batch():
    broker = InProcessBroker()
    calls = []
    lane = _lane(broker, lambda t, l, c: (calls.append(len(t)),
                                          ["a"] * len(t))[1],
                 max_batch=3)
    lane.submit([(None, f"t{i}", 1, 0.5) for i in range(7)])
    assert lane.close(timeout=10.0)
    assert sum(calls) == 7
    assert max(calls) <= 3


def test_lane_survives_backend_failure():
    broker = InProcessBroker()
    state = {"n": 0}

    def fn(texts, labels, confs):
        state["n"] += 1
        if state["n"] == 1:
            raise RuntimeError("backend down")
        return ["recovered"] * len(texts)

    lane = _lane(broker, fn)
    lane.submit([(b"k1", "first", 1, 0.5)])
    lane.drain(timeout=10.0)
    lane.submit([(b"k2", "second", 1, 0.5)])
    assert lane.close(timeout=10.0)
    s = lane.stats()
    assert s["backend_errors"] == 1
    assert s["annotated"] == 1           # the failed batch's row is dropped
    assert [m.key for m in broker.messages("annotations")] == [b"k2"]


def test_lane_skips_none_analyses():
    broker = InProcessBroker()
    lane = _lane(broker, lambda t, l, c: [None if x == 0 else "flagged"
                                          for x in l])
    lane.submit([(b"a", "benign", 0, 0.1), (b"b", "scam", 1, 0.9)])
    assert lane.close(timeout=10.0)
    recs = broker.messages("annotations")
    assert [m.key for m in recs] == [b"b"]
    assert lane.stats()["annotated"] == 1


def test_lane_length_mismatch_is_backend_error():
    broker = InProcessBroker()
    lane = _lane(broker, lambda t, l, c: ["only-one"])
    lane.submit([(None, "t1", 1, 0.5), (None, "t2", 1, 0.5)])
    assert lane.close(timeout=10.0)
    assert lane.stats()["backend_errors"] == 1
    assert broker.messages("annotations") == []


def test_engine_async_annotations_end_to_end(pipeline):
    """explain_async=True: classified frames ship WITHOUT analysis (and the
    raw-JSON fast path stays in play — inline hooks disable it), flagged
    rows land on the annotations side topic keyed like their sources."""
    from fraud_detection_tpu.data import generate_corpus

    corpus = generate_corpus(n=40, seed=13, hard_fraction=0.0,
                             label_noise=0.0)
    broker = InProcessBroker(num_partitions=2)
    producer = broker.producer()
    for i, d in enumerate(corpus):
        producer.produce("customer-dialogues-raw",
                         json.dumps({"text": d.text, "id": i}).encode(),
                         key=str(i).encode())

    def explain_batch(texts, labels, confs):
        assert all(l != 0 for l in labels)     # engine pre-filters flagged
        return [f"async analysis label={l}" for l in labels]

    engine = StreamingClassifier(
        pipeline, broker.consumer(["customer-dialogues-raw"], "grp"),
        broker.producer(), "out", batch_size=16, max_wait=0.01,
        explain_batch_fn=explain_batch, explain_async=True,
        annotations_producer=broker.producer())
    stats = engine.run(max_messages=40, idle_timeout=0.2)
    assert engine.close_annotations(timeout=30.0)

    assert stats.processed == 40
    assert engine._json_fast is True          # fast path NOT disabled
    outs = {m.key: json.loads(m.value) for m in broker.messages("out")}
    assert len(outs) == 40
    assert all("analysis" not in o for o in outs.values())
    flagged = {k for k, o in outs.items() if o["prediction"] != 0}
    assert flagged                            # the corpus has scams

    recs = {m.key: json.loads(m.value) for m in
            broker.messages("out-annotations")}
    assert set(recs) == flagged               # every flagged row annotated
    for k, r in recs.items():
        assert r["prediction"] == outs[k]["prediction"]
        assert r["confidence"] == outs[k]["confidence"]
        assert r["analysis"] == f"async analysis label={r['prediction']}"
    s = engine.annotation_stats()
    assert s["annotated"] == len(flagged) and s["dropped"] == 0


def test_engine_async_requires_batch_fn(pipeline):
    broker = InProcessBroker()
    with pytest.raises(ValueError, match="explain_async"):
        StreamingClassifier(
            pipeline, broker.consumer(["t"], "g"), broker.producer(), "out",
            explain_async=True)


def test_engine_inline_has_no_lane(pipeline):
    broker = InProcessBroker()
    engine = StreamingClassifier(
        pipeline, broker.consumer(["t"], "g"), broker.producer(), "out")
    assert engine.annotation_stats() is None
    assert engine.close_annotations() is True


def test_engine_async_slow_backend_never_blocks_classification(pipeline):
    """A backend 100x slower than the stream must not throttle it: the run
    finishes at transport speed with annotations trailing/dropping, not
    serialized behind decode (the inline hook's failure mode)."""
    from fraud_detection_tpu.data import generate_corpus

    corpus = generate_corpus(n=60, seed=21, hard_fraction=0.0,
                             label_noise=0.0)
    broker = InProcessBroker()
    producer = broker.producer()
    for i, d in enumerate(corpus):
        producer.produce("customer-dialogues-raw",
                         json.dumps({"text": d.text}).encode(),
                         key=str(i).encode())

    def slow_explain(texts, labels, confs):
        time.sleep(0.25)                      # "decode" far slower than poll
        return ["slow"] * len(texts)

    engine = StreamingClassifier(
        pipeline, broker.consumer(["customer-dialogues-raw"], "grp"),
        broker.producer(), "out", batch_size=16, max_wait=0.01,
        explain_batch_fn=slow_explain, explain_async=True,
        annotations_producer=broker.producer())
    t0 = time.perf_counter()
    stats = engine.run(max_messages=60, idle_timeout=0.2)
    run_s = time.perf_counter() - t0
    assert stats.processed == 60
    assert len(broker.messages("out")) == 60
    # Inline, 60 msgs in 16-row batches would pay >= 4 * 0.25s of decode
    # inside the loop; async classification must not have waited for it.
    lane_work = engine.annotation_stats()
    assert lane_work["submitted"] > 0
    assert run_s < 0.9, f"classification waited on the annotator: {run_s:.2f}s"
    engine.close_annotations(timeout=30.0)


def test_engine_async_requires_dedicated_producer(pipeline):
    """Sharing the engine's producer would cross-contaminate flush()-based
    delivery accounting (engine: commit-only-if-drained; lane: annotated
    counters) — the constructor refuses, both when no producer is given AND
    when the engine's own producer object is passed in (ADVICE round 5: the
    documented invariant must actually be enforced)."""
    broker = InProcessBroker()
    with pytest.raises(ValueError, match="annotations_producer"):
        StreamingClassifier(
            pipeline, broker.consumer(["t"], "g"), broker.producer(), "out",
            explain_batch_fn=lambda t, l, c: [None] * len(t),
            explain_async=True)
    shared = broker.producer()
    with pytest.raises(ValueError, match="DEDICATED"):
        StreamingClassifier(
            pipeline, broker.consumer(["t"], "g"), shared, "out",
            explain_batch_fn=lambda t, l, c: [None] * len(t),
            explain_async=True, annotations_producer=shared)


def test_lane_close_bounded_and_honest_with_hung_backend():
    """A backend that hangs forever must not hang close(): the drain phase
    is capped by the timeout, the join by a short window scaled to it, and
    the result is an HONEST False (rows unprocessed, worker still stuck) —
    the caller is never deadlocked behind a dead LLM endpoint."""
    broker = InProcessBroker()
    started = threading.Event()
    release = threading.Event()        # never set during the test: a hang

    def hung_fn(texts, labels, confs):
        started.set()
        release.wait(30.0)
        return ["late"] * len(texts)

    lane = _lane(broker, hung_fn)
    lane.submit([(b"k1", "text", 1, 0.9), (b"k2", "text", 1, 0.8)])
    assert started.wait(5.0)           # the worker is now stuck in the hook
    t0 = time.perf_counter()
    ok = lane.close(timeout=0.3)
    dt = time.perf_counter() - t0
    assert ok is False                 # honest: NOT a clean drain
    assert dt < 2.0, f"close() blocked {dt:.1f}s behind a hung backend"
    assert lane._thread.is_alive()     # daemon worker still stuck — by design
    release.set()                      # unblock it for test hygiene
    lane._thread.join(timeout=5.0)


def test_lane_close_bounded_with_raising_backend_and_backlog():
    """A 100%-raising backend drains the queue through the error path:
    close() reports True (everything drained, worker exited) and every
    failed batch is counted — no deadlock, no silent loss of accounting."""
    broker = InProcessBroker()

    def bad_fn(texts, labels, confs):
        raise ConnectionError("endpoint down")

    lane = _lane(broker, bad_fn, max_batch=4)
    lane.submit([(None, f"t{i}", 1, 0.5) for i in range(12)])
    assert lane.close(timeout=10.0) is True
    assert not lane._thread.is_alive()
    s = lane.stats()
    assert s["queue_depth"] == 0 and s["annotated"] == 0
    assert s["backend_errors"] == 3    # 12 rows / max_batch 4
    assert broker.messages("annotations") == []


def test_lane_drain_deadline_uses_injected_clock():
    """drain()'s deadline runs on the injectable clock — a test can expire
    it instantly instead of sleeping through a real timeout."""
    broker = InProcessBroker()
    gate = threading.Event()
    fake_now = [0.0]

    def fast_clock():                  # every read jumps a minute forward
        fake_now[0] += 60.0
        return fake_now[0]

    def slow_fn(texts, labels, confs):
        gate.wait(10.0)
        return ["a"] * len(texts)

    lane = AsyncAnnotationLane(slow_fn, broker.producer(), "annotations",
                               clock=fast_clock)
    lane.submit([(b"k", "t", 1, 0.5)])
    t0 = time.perf_counter()
    assert lane.drain(timeout=50.0) is False
    assert time.perf_counter() - t0 < 1.0   # expired via clock, not sleeping
    gate.set()
    lane.close(timeout=10.0)           # drain verdict also rides the fast
    lane._thread.join(timeout=5.0)     # clock; just check the worker exits
    assert not lane._thread.is_alive()


def test_lane_close_discards_residual_queue_as_dropped():
    """ADVICE satellite: after the drain deadline, close() clears the
    residual queue under the lock (counting discards as dropped) before
    latching — post-close stats are quiescent, not a racing snapshot."""
    broker = InProcessBroker()
    started = threading.Event()
    release = threading.Event()

    def slow_fn(texts, labels, confs):
        started.set()
        release.wait(30.0)
        return ["late"] * len(texts)

    lane = _lane(broker, slow_fn, max_batch=2)
    lane.submit([(bytes([i]), f"t{i}", 1, 0.5) for i in range(8)])
    assert started.wait(5.0)          # worker holds a 2-row batch
    assert lane.close(timeout=0.3) is False
    s1 = lane.stats()
    assert s1["queue_depth"] == 0     # residual 6 rows cleared...
    assert s1["dropped"] == 6         # ...and counted, not silently lost
    release.set()                     # the in-flight batch may still finish
    lane._thread.join(timeout=5.0)
    # dropped/submitted/queue_depth never move again after close
    s2 = lane.stats()
    assert (s2["submitted"], s2["dropped"], s2["queue_depth"]) == (8, 6, 0)


def test_lane_annotated_credit_survives_producer_backlog():
    """ADVICE satellite: ``annotated`` is a running delivered tally
    (produced - flush()'s queue depth), so records a failed flush leaves
    behind are credited exactly once when a LATER flush delivers them —
    never double-subtracted from the next batch."""
    class BacklogProducer:
        def __init__(self):
            self.sent = []
            self.queue = 0
            self.fail_next = True

        def produce(self, topic, value, key=None):
            self.sent.append((value, key))
            self.queue += 1

        def flush(self):
            if self.fail_next:        # everything stays queued once
                self.fail_next = False
                return self.queue
            self.queue = 0
            return 0

    prod = BacklogProducer()
    lane = AsyncAnnotationLane(lambda t, l, c: ["a"] * len(t), prod, "ann")
    lane.submit([(b"k1", "one", 1, 0.5)])
    lane.drain(timeout=10.0)
    assert lane.stats()["annotated"] == 0     # first flush left it queued
    assert lane.stats()["backend_errors"] == 1
    lane.submit([(b"k2", "two", 1, 0.5)])
    assert lane.close(timeout=10.0)
    s = lane.stats()
    # Second flush delivered BOTH records: 2 produced - 0 undelivered = 2,
    # not the per-batch 1 - 0 the old subtraction would have credited on
    # top of a phantom first-batch loss.
    assert s["annotated"] == 2


def test_lane_close_is_idempotent_and_latching():
    """serve's supervised-restart path closes the replaced engine's lane and
    finish_annotations() closes every built engine again at exit — double
    close must be safe, and a closed lane must ignore late submits (a
    replaced incarnation's _finish could still be unwinding)."""
    broker = InProcessBroker()
    lane = _lane(broker, lambda t, l, c: ["a"] * len(t))
    lane.submit([(b"k", "text", 1, 0.5)])
    assert lane.close(timeout=10.0)
    assert lane.close(timeout=10.0)          # second close: clean no-op
    lane.submit([(b"late", "text", 1, 0.5)])  # latched: dropped silently
    assert lane.stats()["submitted"] == 1
    assert [m.key for m in broker.messages("annotations")] == [b"k"]


# ---------------------------------------------------------------------------
# the window: a hook that hands back tickets (ISSUE 32). Every test here
# resolves the tickets itself and waits on what the lane does next (the
# hook's next call, a row's annotate event), never on the clock.
# ---------------------------------------------------------------------------

WAIT_S = 10.0      # a bound on a wait for the worker thread, never a sleep


class TicketHook:
    """A hook that serves rows one at a time: ``submit_rows`` hands back a
    ``Future`` a row (keyed by the row's text in ``tickets``) and says on
    ``calls`` which rows each call was given and how many were in flight."""

    def __init__(self):
        self.calls = queue.Queue()
        self.tickets = {}
        self.lane = None

    def __call__(self, texts, labels, confs):
        raise AssertionError("the lane must call submit_rows")

    def submit_rows(self, texts, labels, confs):
        made = [Future() for _ in texts]
        self.tickets.update(zip(texts, made))
        self.calls.put((list(texts), self.lane.stats()["in_flight"]))
        return made

    def next_call(self):
        return self.calls.get(timeout=WAIT_S)


class RecordingProducer:
    """The lane's producer, keeping the order of its produces and flushes."""

    def __init__(self, inner):
        self.inner = inner
        self.log = []

    def produce(self, topic, value, key=None):
        self.log.append(("produce", key))
        self.inner.produce(topic, value, key=key)

    def flush(self):
        self.log.append(("flush",))
        return self.inner.flush()


def _ticket_lane(broker, **kw):
    hook = TicketHook()
    producer = RecordingProducer(broker.producer())
    hook.lane = AsyncAnnotationLane(hook, producer, "annotations", **kw)
    return hook, hook.lane, producer


def _rows(n, start=0, cid=None):
    return [(b"k%d" % i, f"t{i}", 1, 0.5) + (() if cid is None
                                              else (f"{cid}{i}",))
            for i in range(start, start + n)]


def _keys(broker):
    return [m.key for m in broker.messages("annotations")]


def test_window_holds_max_batch_and_one_resolution_admits_one_row():
    """At most ``max_batch`` rows are in flight; a resolved ticket's record
    is produced, flushed and counted while its neighbours are unresolved,
    and only then does exactly one more row take its place."""
    broker = InProcessBroker()
    hook, lane, producer = _ticket_lane(broker, max_batch=3)
    lane.submit(_rows(7))
    assert hook.next_call() == (["t0", "t1", "t2"], 3)
    assert lane.stats()["queue_depth"] == 4
    hook.tickets["t1"].set_result("one")     # the MIDDLE one: no order kept
    # The hook's next call is the lane's next act after the delivery.
    assert hook.next_call() == (["t3"], 3)
    assert _keys(broker) == [b"k1"]
    assert json.loads(broker.messages("annotations")[0].value)[
        "analysis"] == "one"
    assert producer.log == [("produce", b"k1"), ("flush",)]
    s = lane.stats()
    assert (s["annotated"], s["in_flight"], s["queue_depth"]) == (1, 3, 3)
    assert not hook.tickets["t0"].done() and not hook.tickets["t2"].done()
    seen = 3
    for i in (0, 2, 3, 4, 5, 6):             # each frees one place
        hook.tickets[f"t{i}"].set_result(f"a{i}")
        if seen < 6:
            seen += 1
            texts, in_flight = hook.next_call()
            assert texts == [f"t{seen}"] and in_flight == 3
    assert lane.close(timeout=WAIT_S)
    assert hook.calls.empty()
    assert _keys(broker) == [b"k1", b"k0", b"k2", b"k3", b"k4", b"k5", b"k6"]
    assert lane.stats() == {"submitted": 7, "annotated": 7, "dropped": 0,
                            "drop_records": 0, "backend_errors": 0,
                            "queue_depth": 0, "in_flight": 0}


def test_window_takes_rows_as_they_arrive_without_a_barrier():
    """With places free a row goes to the hook when it arrives, whatever
    is still unresolved ahead of it."""
    broker = InProcessBroker()
    hook, lane, _ = _ticket_lane(broker, max_batch=4)
    lane.submit(_rows(1))
    assert hook.next_call() == (["t0"], 1)
    lane.submit(_rows(2, start=1))           # t0 still decoding
    assert hook.next_call() == (["t1", "t2"], 3)
    assert lane.drain(timeout=0.05) is False  # in flight is not drained
    for t in ("t2", "t0", "t1"):
        hook.tickets[t].set_result(t.upper())
    assert lane.drain(timeout=WAIT_S)
    assert _keys(broker) == [b"k2", b"k0", b"k1"]
    assert lane.close(timeout=WAIT_S)


def test_window_close_with_rows_in_flight_clears_queue_and_delivers_them():
    """close() with tickets out: the residual queue is cleared and counted,
    the lane latches, and the rows already handed over are still delivered
    when they resolve; then the worker exits and stats() stand still."""
    broker = InProcessBroker()
    hook, lane, _ = _ticket_lane(broker, max_batch=2)
    lane.submit(_rows(5))
    assert hook.next_call() == (["t0", "t1"], 2)
    assert lane.close(timeout=0.2) is False   # honest: rows still out
    s = lane.stats()
    assert (s["queue_depth"], s["dropped"], s["in_flight"]) == (0, 3, 2)
    assert lane._thread.is_alive()
    lane.submit(_rows(1, start=9))            # latched: ignored
    hook.tickets["t1"].set_result("late one")
    hook.tickets["t0"].set_exception(RuntimeError("decoder died"))
    lane._thread.join(timeout=WAIT_S)
    assert not lane._thread.is_alive()
    assert hook.calls.empty()                 # nothing new was handed over
    assert _keys(broker) == [b"k1"]           # t0's error cost t0 alone
    assert lane.stats() == {"submitted": 5, "annotated": 1, "dropped": 3,
                            "drop_records": 0, "backend_errors": 1,
                            "queue_depth": 0, "in_flight": 0}
    assert lane.close(timeout=WAIT_S)         # nothing left: clean


def test_window_overflow_drop_records_go_out_while_tickets_are_in_flight():
    """The backlog stays in the lane's bounded queue: overflow evicts the
    oldest QUEUED row, and its structured drop record reaches the topic at
    once, not behind the unresolved tickets."""
    from fraud_detection_tpu.obs.trace import RowTracer

    events = queue.Queue()

    class SignalTracer(RowTracer):
        def record_event(self, cid, stage, **kw):
            super().record_event(cid, stage, **kw)
            events.put((cid, stage, kw.get("ok", True), kw.get("detail")))

    broker = InProcessBroker()
    hook, lane, _ = _ticket_lane(broker, max_batch=2, max_queue=2,
                                 rowtrace=SignalTracer(worker="w0"))
    lane.submit(_rows(2, cid="c"))
    assert hook.next_call() == (["t0", "t1"], 2)
    lane.submit(_rows(4, start=2, cid="c"))   # 2 queue, the 2 oldest drop
    dropped = [events.get(timeout=WAIT_S) for _ in range(2)]
    assert dropped == [(f"c{i}", "annotate", False, "dropped:queue_overflow")
                       for i in (2, 3)]
    s = lane.stats()
    assert (s["dropped"], s["drop_records"], s["in_flight"],
            s["queue_depth"], s["annotated"]) == (2, 2, 2, 2, 0)
    recs = [json.loads(m.value) for m in broker.messages("annotations")]
    assert [(r["dropped"], r["reason"], r["trace"]) for r in recs] == [
        (True, "queue_overflow", "c2"), (True, "queue_overflow", "c3")]
    assert hook.calls.empty()                 # the window was full throughout
    for i in range(2):
        hook.tickets[f"t{i}"].set_result("ok")
    topped_up = []          # one call or two: tickets may resolve together
    while len(topped_up) < 2:
        topped_up += hook.next_call()[0]
    assert topped_up == ["t4", "t5"]
    for i in (4, 5):
        hook.tickets[f"t{i}"].set_result("ok")
    assert lane.close(timeout=WAIT_S)
    s = lane.stats()
    assert s["submitted"] == s["annotated"] + s["dropped"] == 6


def test_window_ticket_that_outlives_its_timeout_is_taken_as_it_is():
    """A ticket may carry a ``timeout``: past it (on the lane's clock) the
    lane stops waiting and takes ``result()`` as it stands; a late
    resolution of the same ticket delivers nothing twice."""
    skew = [0.0]

    class Ticket(Future):
        timeout = 30.0

        def result(self, timeout=None):
            return super().result(0) if self.done() else "[gave up]"

    made = queue.Queue()

    def fn(texts, labels, confs):
        raise AssertionError("the lane must call submit_rows")

    def submit_rows(texts, labels, confs):
        out = [Ticket() for _ in texts]
        made.put(out)
        return out

    fn.submit_rows = submit_rows
    broker = InProcessBroker()
    lane = AsyncAnnotationLane(fn, broker.producer(), "annotations",
                               clock=lambda: time.perf_counter() + skew[0])
    lane.submit(_rows(2))
    t0, t1 = made.get(timeout=WAIT_S)
    skew[0] = 31.0                      # t0's wait has run out ...
    t1.set_result("in time")            # ... which the woken worker sees
    assert lane.drain(timeout=WAIT_S)
    assert sorted(json.loads(m.value)["analysis"]
                  for m in broker.messages("annotations")) == [
        "[gave up]", "in time"]
    t0.set_result("too late")           # settled already: ignored
    assert lane.close(timeout=WAIT_S)
    assert lane.stats() == {"submitted": 2, "annotated": 2, "dropped": 0,
                            "drop_records": 0, "backend_errors": 0,
                            "queue_depth": 0, "in_flight": 0}
    assert len(broker.messages("annotations")) == 2


def test_batch_hook_is_the_windows_degenerate_case():
    """A plain callable resolves its rows at the call's return: the lane
    hands it ``max_batch`` rows at a time, one call after the other, as it
    always did, and ``in_flight`` shows the batch while it decodes."""
    broker = InProcessBroker()
    seen = queue.Queue()
    gate = threading.Event()
    lane_box = []

    def fn(texts, labels, confs):
        seen.put((list(texts), lane_box[0].stats()["in_flight"]))
        gate.wait(WAIT_S)
        return [t.upper() for t in texts]

    lane_box.append(_lane(broker, fn, max_batch=3))
    lane = lane_box[0]
    lane.submit(_rows(7))
    assert seen.get(timeout=WAIT_S) == (["t0", "t1", "t2"], 3)
    assert seen.empty() and broker.messages("annotations") == []
    gate.set()
    assert lane.close(timeout=WAIT_S)
    assert [seen.get_nowait()[0] for _ in range(2)] == [
        ["t3", "t4", "t5"], ["t6"]]
    assert _keys(broker) == [b"k%d" % i for i in range(7)]
    assert lane.stats()["in_flight"] == 0
