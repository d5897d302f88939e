"""Slotserve invariant suite (docs/explain_serving.md).

Pins the continuous-batching lane's CLAIMS, not just its plumbing:

* **decode parity** — a row decoded through the slot pool emits exactly
  the fixed-batch path's greedy tokens, including after slot reuse (the
  cross-slot KV-contamination pin: a recycled slot must never leak a
  prior row's cache);
* **FIFO-per-row output** — ``generate_batch``/``explain_rows`` replies
  align positionally with their prompts whatever order rows retire in;
* **honest accounting** — ``admitted == completed + dropped`` always
  (queue overflow, close residue, decoder death), and every annotation-
  lane drop-OLDEST eviction leaves a STRUCTURED record carrying the
  row's trace cid, join-able to ``chain(cid)``;
* **degradation** — a dead decoder fails requests with BackendError (the
  breaker's food), the slot hook converts failures into accounted
  markers, and the lane recovers when the device comes back;
* **schema** — ``snapshot()`` is the engine's ``health()["explain"]``
  block, key set pinned here for FC301;
* **end to end** — seeded chaos + the serve CLI (``--explain-slots N``)
  + the ``campaign_explain`` game day's coverage gate.
"""

import json
import threading
import time

import numpy as np
import pytest

from fraud_detection_tpu.explain.backends import BackendError, frame_prompt
from fraud_detection_tpu.explain.circuit import (BreakerOpenError,
                                                 CircuitBreakerBackend)
from fraud_detection_tpu.explain.onpod import OnPodBackend, flatten_chat
from fraud_detection_tpu.explain.slotserve import (DROPPED_MARKER,
                                                   UNAVAILABLE_MARKER,
                                                   SlotServeService,
                                                   make_slot_explain_hook)
from fraud_detection_tpu.models import llm
from fraud_detection_tpu.stream import InProcessBroker, StreamingClassifier

pytestmark = pytest.mark.slotserve


@pytest.fixture(scope="module")
def lm():
    cfg = llm.TransformerConfig(d_model=64, n_layers=2, n_heads=4, d_ff=128,
                                max_seq=1024)
    return llm.LanguageModel.init_random(cfg, seed=3)


def make_service(lm, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_new_tokens", 24)
    kw.setdefault("prompt_width", 448)
    kw.setdefault("decode_window", 8)
    kw.setdefault("wait_timeout", 120.0)
    return SlotServeService(lm, **kw)


def prompts_varied(n, base=0):
    return [f"Analyze dialogue {base + i}: the caller claims to be the "
            "bank fraud department and demands gift cards. "
            + "Customer hesitates. " * (i % 4) for i in range(n)]


# ---------------------------------------------------------------------------
# decode parity + FIFO + slot reuse
# ---------------------------------------------------------------------------

def test_slot_outputs_match_fixed_batch_greedy(lm):
    """Greedy outputs through the slot pool == the fixed-batch decode path
    (generate_tokens_batch under OnPodBackend), positionally aligned.
    12 prompts through 4 slots forces REUSE: every slot serves ~3 rows, so
    equality here is also the cross-slot KV-contamination pin."""
    svc = make_service(lm)
    try:
        prompts = prompts_varied(12)
        got = svc.generate_batch(prompts, temperature=0.0, max_tokens=24)
        want = OnPodBackend.from_model(lm).generate_batch(
            prompts, temperature=0.0, max_tokens=24)
        assert got == list(want)
        snap = svc.snapshot()
        assert snap["admitted"] == 12
        assert snap["completed"] == 12
        assert snap["dropped"] == 0
        assert snap["truncated"] == 0
        assert snap["prefills"] == 12
        assert snap["prefills_flash"] == 0      # every suffix under 512 tokens
    finally:
        assert svc.close()


def test_slot_reuse_never_leaks_prior_kv(lm):
    """The SAME prompt decodes identically fresh and after heavy pool
    churn — a reused slot whose stale cache tail leaked into attention
    would diverge here."""
    svc = make_service(lm, slots=2)
    try:
        probe = "Analyze dialogue 999: urgent wire transfer demanded now."
        fresh = svc.generate_batch([probe], temperature=0.0, max_tokens=24)
        svc.generate_batch(prompts_varied(6, base=50), temperature=0.0,
                           max_tokens=24)       # churn both slots
        again = svc.generate_batch([probe], temperature=0.0, max_tokens=24)
        assert fresh == again
    finally:
        svc.close()


def test_explain_rows_positional_and_traced(lm):
    from fraud_detection_tpu.obs.trace import RowTracer

    tracer = RowTracer(worker="t0", sample=1.0)
    svc = make_service(lm, rowtrace=tracer)
    try:
        cids = ["t0-1:0:5", None, "t0-1:0:7"]
        out = svc.explain_rows(["scam text A", "scam text B", "scam text C"],
                               [1, 1, 1], [0.9, 0.8, 0.7], cids=cids,
                               max_tokens=8)
        assert len(out) == 3 and all(isinstance(s, str) for s in out)
        # every traced row got an "explain" span with its slot recorded
        for cid in ("t0-1:0:5", "t0-1:0:7"):
            spans = [s for s in tracer.chain(cid) if s.stage == "explain"]
            assert len(spans) == 1 and spans[0].ok
            assert "slot=" in spans[0].detail
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# admission accounting
# ---------------------------------------------------------------------------

def test_queue_overflow_drops_oldest_with_accounting(lm):
    svc = make_service(lm, slots=1, max_queue=2, max_new_tokens=8)
    try:
        reqs = [svc.submit(flatten_chat(frame_prompt(p)), max_tokens=8)
                for p in prompts_varied(8)]
        texts = [r.wait(120.0) for r in reqs]
        dropped = [t for t in texts
                   if t == DROPPED_MARKER.format(reason="queue_overflow")]
        assert dropped, "overflow should have dropped the oldest requests"
        snap = svc.snapshot()
        assert snap["admitted"] == 8
        assert snap["admitted"] == snap["completed"] + snap["dropped"]
        assert snap["dropped"] == len(dropped)
    finally:
        svc.close()


def test_close_residual_counts_dropped(lm):
    svc = make_service(lm, slots=1, max_queue=64, max_new_tokens=24)
    reqs = [svc.submit(flatten_chat(frame_prompt(p)), max_tokens=24)
            for p in prompts_varied(6)]
    # Close with a tiny drain budget: residual queue resolves as dropped.
    svc.close(timeout=0.05)
    texts = [r.wait(120.0) for r in reqs]
    assert any(t == DROPPED_MARKER.format(reason="closed") for t in texts)
    snap = svc.snapshot()
    assert snap["admitted"] == 6
    assert snap["admitted"] == snap["completed"] + snap["dropped"]
    # submissions after close are refused-as-dropped, still accounted
    late = svc.submit("late", max_tokens=4)
    assert late.wait(5.0) == DROPPED_MARKER.format(reason="closed")
    snap = svc.snapshot()
    assert snap["admitted"] == snap["completed"] + snap["dropped"]


def test_truncation_counted(lm):
    svc = make_service(lm, prompt_width=64, max_new_tokens=4)
    try:
        svc.generate_batch(["x" * 500], temperature=0.0, max_tokens=4)
        assert svc.snapshot()["truncated"] == 1
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# degradation: decoder death, breaker, marker accounting
# ---------------------------------------------------------------------------

def test_decoder_failure_fails_requests_then_recovers(lm):
    svc = make_service(lm, slots=2, max_new_tokens=8)
    try:
        real_prefill = svc._decoder.prefill

        def boom(*a, **k):
            raise RuntimeError("device lost")

        svc._decoder.prefill = boom
        with pytest.raises(BackendError, match="decoder failed"):
            svc.generate_batch(["will fail"], max_tokens=4)
        snap = svc.snapshot()
        assert snap["errors"] >= 1
        assert snap["admitted"] == snap["completed"] + snap["dropped"]
        # device comes back: the lane keeps serving
        svc._decoder.prefill = real_prefill
        out = svc.generate_batch(["recovers"], temperature=0.0, max_tokens=4)
        assert len(out) == 1 and isinstance(out[0], str)
        snap = svc.snapshot()
        assert snap["admitted"] == snap["completed"] + snap["dropped"]
    finally:
        svc.close()


def test_breaker_wraps_slotserve_and_hook_emits_markers(lm):
    clock = type("C", (), {"t": 0.0})()
    svc = make_service(lm, slots=2, max_new_tokens=8)
    try:
        svc._decoder.prefill = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("device lost"))
        breaker = CircuitBreakerBackend(svc, failure_threshold=1,
                                        probe_interval=30.0,
                                        clock=lambda: clock.t)
        hook = make_slot_explain_hook(breaker, max_tokens=4)
        # first call: real failure trips the breaker; rows get markers
        out = hook(["a", "b"], [1, 1], [0.9, 0.9], cids=[None, None])
        assert out == [UNAVAILABLE_MARKER.format(reason="BackendError")] * 2
        assert breaker.snapshot()["state"] == "open"
        # while open: fast-fail, STILL a full marker row set (accounted)
        out = hook(["c"], [1], [0.5])
        assert out == [UNAVAILABLE_MARKER.format(reason="BreakerOpenError")]
        assert breaker.snapshot()["fast_fails"] >= 1
        with pytest.raises(BreakerOpenError):
            breaker.explain_rows(["d"], [1], [0.5])
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# annotation-lane drop records (the satellite fix) + chaos coverage
# ---------------------------------------------------------------------------

def _feed(broker, n, scam_every=3):
    from tests.fixtures import BENIGN_DIALOGUE, SCAM_DIALOGUE

    prod = broker.producer()
    for i in range(n):
        text = SCAM_DIALOGUE if i % scam_every == 0 else BENIGN_DIALOGUE
        prod.produce("in", json.dumps({"text": text, "id": i}).encode(),
                     key=str(i).encode())


@pytest.fixture(scope="module")
def pipeline():
    from fraud_detection_tpu.models.pipeline import synthetic_demo_pipeline

    return synthetic_demo_pipeline(batch_size=64, n=400, seed=3,
                                   num_features=2048,
                                   corpus_kwargs=dict(hard_fraction=0.0,
                                                      label_noise=0.0))


def test_lane_drop_records_carry_trace_ids(pipeline):
    """Drop-OLDEST in the annotation lane is not a bare counter: every
    eviction lands a structured record on the side topic whose ``trace``
    id joins back to the row's span chain."""
    from fraud_detection_tpu.obs.trace import RowTracer

    tracer = RowTracer(worker="w0", sample=1.0)
    broker = InProcessBroker(num_partitions=2)
    _feed(broker, 48, scam_every=2)

    slow = threading.Event()

    def hook(texts, labels, confs, cids=None):
        slow.wait(0.25)          # a slow backend so the queue overflows
        return ["ok"] * len(texts)

    hook.accepts_cids = True
    engine = StreamingClassifier(
        pipeline, broker.consumer(["in"], "g"), broker.producer(), "out",
        batch_size=16, max_wait=0.01,
        explain_batch_fn=hook, explain_async=True,
        annotations_producer=broker.producer(), annotations_queue=4,
        rowtrace=tracer)
    engine.run(max_messages=48, idle_timeout=1.0)
    engine.close_annotations(timeout=30.0)
    stats = engine.annotation_stats()
    assert stats["dropped"] > 0
    assert stats["drop_records"] == stats["dropped"]
    assert stats["submitted"] == stats["annotated"] + stats["dropped"]
    records = [json.loads(m.value)
               for m in broker.messages("out-annotations")]
    drops = [r for r in records if r.get("dropped")]
    assert len(drops) == stats["drop_records"]
    for rec in drops:
        assert rec["reason"] == "queue_overflow"
        assert rec["analysis"] is None
        chain = tracer.chain(rec["trace"])
        stages = {s.stage for s in chain}
        # the dropped row's chain: flagged at classification, then the
        # failed-annotate marker the drop emission recorded
        assert "flag" in stages and "annotate" in stages
        assert any(s.stage == "annotate" and not s.ok
                   and "dropped" in (s.detail or "") for s in chain)


@pytest.mark.chaos
def test_chaos_every_flagged_row_explained_or_accounted(lm, pipeline):
    """Seeded broker chaos on the CLASSIFICATION path + slotserve behind
    the lane: zero lost/duplicated classifications, and the lane's
    coverage invariant holds — submitted == annotated + drop_records,
    slot accounting exact."""
    from fraud_detection_tpu.obs.trace import RowTracer
    from fraud_detection_tpu.stream.faults import FaultPlan

    tracer = RowTracer(worker="w0", sample=1.0)
    svc = make_service(lm, slots=2, max_new_tokens=6, rowtrace=tracer)
    try:
        hook = make_slot_explain_hook(svc, max_tokens=6)
        broker = InProcessBroker(num_partitions=2)
        _feed(broker, 60, scam_every=3)
        plan = FaultPlan(seed=11, duplicate_rate=0.1, corrupt_rate=0.05,
                         flush_fail_rate=0.05, max_faults=12)
        engine = StreamingClassifier(
            pipeline, plan.consumer(broker.consumer(["in"], "g")),
            plan.producer(broker.producer()), "out",
            batch_size=16, max_wait=0.01,
            explain_batch_fn=hook, explain_async=True,
            annotations_producer=broker.producer(), annotations_queue=8,
            explain_service=svc,
            dlq_topic="dlq", rowtrace=tracer)
        engine.run(max_messages=60, idle_timeout=1.0)
        engine.close_annotations(timeout=60.0)
        # classification stays exact under chaos (at-least-once)
        fed = {str(i).encode() for i in range(60)}
        out_keys = {m.key for m in broker.messages("out")}
        dlq_keys = {m.key for m in broker.messages("dlq")}
        assert fed <= (out_keys | dlq_keys)
        # the lane's coverage invariant
        stats = engine.annotation_stats()
        assert stats["submitted"] > 0
        assert stats["submitted"] == (stats["annotated"] + stats["dropped"])
        assert stats["drop_records"] == stats["dropped"]
        snap = svc.snapshot()
        assert snap["admitted"] == snap["completed"] + snap["dropped"]
        h = engine.health()
        assert h["explain"]["slots"] == 2
        assert h["trace"]["spans_open"] == 0
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# the annotation lane's window over slot tickets (ISSUE 32)
# ---------------------------------------------------------------------------

WAIT_S = 10.0      # a bound on a wait for a worker thread, never a sleep


class TicketBackend:
    """Stands where the service stands under the hook: ``submit_rows``
    hands out real slot tickets, which the test resolves itself."""

    wait_timeout = 120.0

    def __init__(self):
        import queue

        self.handed = queue.Queue()

    def explain_rows(self, *args, **kw):
        raise AssertionError("the lane must not take the blocking form")

    def submit_rows(self, texts, labels, confs, *, cids=None,
                    temperature=0.0, max_tokens=128):
        from fraud_detection_tpu.explain.slotserve.service import _SlotRequest

        out = [_SlotRequest(np.zeros(1, np.int32), max_tokens, temperature,
                            cids[i] if cids else None, 0.0)
               for i in range(len(texts))]
        self.handed.put(out)
        return out


class FlushSignal:
    """The lane's producer; says on ``flushed`` when a flush has returned."""

    def __init__(self, inner):
        import queue

        self.inner = inner
        self.flushed = queue.Queue()

    def produce(self, topic, value, key=None):
        self.inner.produce(topic, value, key=key)

    def flush(self):
        left = self.inner.flush()
        self.flushed.put(left)
        return left


def test_hook_advertises_tickets_over_the_service_only(lm):
    """Which way the lane serves a hook follows from what the hook can
    hand back: over the service, tickets; over the breaker (which forwards
    the blocking ``explain_rows`` only), every row at the call's return."""
    svc = make_service(lm, slots=2, max_new_tokens=4)
    try:
        assert callable(make_slot_explain_hook(svc).submit_rows)
        wrapped = make_slot_explain_hook(CircuitBreakerBackend(svc))
        assert not hasattr(wrapped, "submit_rows")
        assert wrapped.accepts_cids
    finally:
        svc.close()


def test_lane_delivers_each_slot_ticket_as_it_resolves():
    """Through the real hook: a served ticket becomes its row's record
    while its neighbours are unresolved; a ticket that resolves with an
    error becomes the unavailable marker of ITS row alone, a dropped one
    its drop marker; unflagged rows get no ticket and no record."""
    from fraud_detection_tpu.stream.annotations import AsyncAnnotationLane

    backend = TicketBackend()
    broker = InProcessBroker()
    producer = FlushSignal(broker.producer())
    lane = AsyncAnnotationLane(make_slot_explain_hook(backend), producer,
                               "notes")
    lane.submit([(b"k0", "a", 1, 0.9, "c0"), (b"benign", "b", 0, 0.1, "c1"),
                 (b"k2", "c", 1, 0.8, "c2"), (b"k3", "d", 1, 0.7, "c3"),
                 (b"k4", "e", 1, 0.6, "c4")])
    r0, r2, r3, r4 = backend.handed.get(timeout=WAIT_S)
    assert [r.cid for r in (r0, r2, r3, r4)] == ["c0", "c2", "c3", "c4"]

    def notes():
        return [(m.key, json.loads(m.value)["analysis"])
                for m in broker.messages("notes")]

    r2.text = "served"
    r2.resolve()
    producer.flushed.get(timeout=WAIT_S)
    assert notes() == [(b"k2", "served")]
    r3.error = RuntimeError("device lost")
    r3.resolve()
    producer.flushed.get(timeout=WAIT_S)
    assert notes()[1:] == [
        (b"k3", UNAVAILABLE_MARKER.format(reason="BackendError"))]
    assert lane.stats()["in_flight"] == 2      # r0 and r4: untouched
    r4.dropped = "queue_overflow"
    r4.resolve()
    r0.text = "first in, last out"
    r0.resolve()
    assert lane.close(timeout=WAIT_S)
    assert sorted(notes()[2:]) == [
        (b"k0", "first in, last out"),
        (b"k4", DROPPED_MARKER.format(reason="queue_overflow"))]
    assert lane.stats() == {"submitted": 5, "annotated": 4, "dropped": 0,
                            "drop_records": 0, "backend_errors": 0,
                            "queue_depth": 0, "in_flight": 0}


def test_hook_failure_at_hand_over_marks_every_picked_row():
    """``submit_rows`` itself raising is the batch failure it always was:
    an unavailable marker a picked row, none lost."""
    from fraud_detection_tpu.stream.annotations import AsyncAnnotationLane

    backend = TicketBackend()
    backend.submit_rows = lambda *a, **k: (_ for _ in ()).throw(
        BreakerOpenError("open"))
    broker = InProcessBroker()
    lane = AsyncAnnotationLane(make_slot_explain_hook(backend),
                               broker.producer(), "notes")
    lane.submit([(b"k0", "a", 1, 0.9), (b"k1", "b", 1, 0.8)])
    assert lane.close(timeout=WAIT_S)
    assert [json.loads(m.value)["analysis"]
            for m in broker.messages("notes")] == [
        UNAVAILABLE_MARKER.format(reason="BreakerOpenError")] * 2


def test_backlog_through_the_engine_never_starves_the_slots(lm, pipeline):
    """End to end at test size: 4 slots behind the lane's window of 64 and
    a backlog of flagged rows through the engine. While rows queue on the
    lane, a free slot-step is never a STARVED one (the window keeps the
    service's own queue fed); every row lands explained."""
    svc = make_service(lm, slots=4, max_new_tokens=6)
    try:
        broker = InProcessBroker(num_partitions=2)
        _feed(broker, 112, scam_every=1)
        engine = StreamingClassifier(
            pipeline, broker.consumer(["in"], "g"), broker.producer(), "out",
            batch_size=16, max_wait=0.01,
            explain_batch_fn=make_slot_explain_hook(svc, max_tokens=6),
            explain_async=True, annotations_producer=broker.producer(),
            explain_service=svc)
        lane = engine._annotation_lane
        samples = []              # taken on the lane's thread, a row each
        inner = svc.submit

        def submit(*args, **kw):
            req = inner(*args, **kw)
            snap = svc.snapshot()
            samples.append((lane.stats()["queue_depth"],
                            snap["slot_steps_starved"], snap["completed"],
                            lane.stats()["in_flight"]))
            return req

        svc.submit = submit       # looked up on the instance at call time
        engine.run(max_messages=112, idle_timeout=1.0)
        assert engine.close_annotations(timeout=120.0)
        stats = engine.annotation_stats()
        assert stats["submitted"] == stats["annotated"] == len(samples)
        assert stats["submitted"] > 64 + 16     # a backlog past the window
        assert max(s[3] for s in samples) == lane.max_batch == 64
        # Only the lane takes rows off its queue, and it samples after
        # every one: two samples in a row with rows queued = rows queued
        # all the while between them.
        backlogged = [(a, b) for a, b in zip(samples, samples[1:])
                      if a[0] > 0 and b[0] > 0]
        assert len(backlogged) >= 16
        assert all(a[1] == b[1] for a, b in backlogged)
        assert backlogged[-1][1][2] > backlogged[0][0][2]   # slots served
        snap = svc.snapshot()
        assert snap["completed"] == stats["annotated"]
        assert (snap["slot_steps_occupied"] + snap["slot_steps_starved"]
                + snap["slot_steps_backlogged"]
                == snap["decode_steps"] * snap["slots"])
        assert snap["slot_steps_backlogged"] > 0
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# health schema (FC301 contract)
# ---------------------------------------------------------------------------

SLOTSERVE_BLOCK_SCHEMA = {
    "slots": (int,),
    "busy": (int,),
    "free": (int,),
    "queue_depth": (int,),
    "admitted": (int,),
    "completed": (int,),
    "dropped": (int,),
    "errors": (int,),
    "truncated": (int,),
    "expl_per_s": (type(None), int, float),
    "latency_ms": (dict,),
    "admit_to_first_token_ms": (dict,),
    "occupancy": (type(None), int, float),
    "iterations": (int,),
    "prefills": (int,),
    # ... whose suffix took the flash kernel (ISSUE 34): 0 in a tiny service.
    "prefills_flash": (int,),
    "decode_steps": (int,),
    # decode_steps * slots, partitioned (ISSUE 26): a row decoded / the
    # slot was free with nothing queued / free with requests waiting.
    "slot_steps_occupied": (int,),
    "slot_steps_starved": (int,),
    "slot_steps_backlogged": (int,),
    "tokens_out": (int,),
    "kv_bytes": (int,),
    # The page pool (PR 19) — FC301 pins these against snapshot()'s
    # literal.
    "kv_pages": (int,),
    "page_bytes": (int,),
    "pages_free": (int,),
    "prefix_pages": (int,),
    "prefix_hits": (int,),
    "cow_copies": (int,),
    "kv_bytes_saved_vs_contiguous": (int,),
    # The model's own counters (ISSUE 29): zeros for a dense model.
    "moe_picks": (int,),
    "moe_picks_held": (int,),
    "moe_picks_zero": (int,),       # ISSUE 33: zero-compute experts only
    "moe_experts_touched": (int,),
    "moe_expert_slots": (int,),     # ISSUE 35: steps x expert layers x held
    "moe_prefill_load_max": (int,),
    "moe_prefill_load_mean": (int, float),
    # ISSUE 36: how the prefills' grouped expert product was sized
    "moe_prefill_tiles": (int,),
    "moe_prefill_tile_rows": (int,),
    "moe_prefill_experts_touched": (int,),
    "moe_prefill_picks_held": (int,),
    "state_restores": (int,),
}


def test_snapshot_schema_contract(lm):
    svc = make_service(lm, slots=2, max_new_tokens=4)
    try:
        svc.generate_batch(["one row"], temperature=0.0, max_tokens=4)
        snap = svc.snapshot()
        assert set(snap) == set(SLOTSERVE_BLOCK_SCHEMA), (
            "snapshot() keys changed — update SLOTSERVE_BLOCK_SCHEMA AND "
            f"docs/explain_serving.md (extra: "
            f"{set(snap) - set(SLOTSERVE_BLOCK_SCHEMA)}, missing: "
            f"{set(SLOTSERVE_BLOCK_SCHEMA) - set(snap)})")
        for key, types in SLOTSERVE_BLOCK_SCHEMA.items():
            assert isinstance(snap[key], types), (key, type(snap[key]))
        for sub in ("latency_ms", "admit_to_first_token_ms"):
            assert set(snap[sub]) == {"p50", "p99"}
        assert snap["expl_per_s"] is not None
        assert snap["latency_ms"]["p50"] is not None
        assert snap["admit_to_first_token_ms"]["p99"] is not None
        json.dumps(snap)
    finally:
        svc.close()


@pytest.mark.parametrize("shared_prefix", [True, False])
def test_slot_steps_partition_into_occupied_starved_backlogged(lm,
                                                               shared_prefix):
    """Every slot-step of every decode window is counted once: a row
    decoded in it, or the slot was free with the queue empty (starved), or
    free with requests waiting (backlogged). A mixed run has all three:
    12 rows over 4 slots admit 2 an iteration (free slots, rows queued),
    then one row decodes alone (free slots, nothing queued). Both
    deployments of the pool count alike: the preamble resident in 5 shared
    pages, or every page a slot's own."""
    svc = make_service(lm, slots=4, max_new_tokens=16,
                       shared_prefix=shared_prefix)
    try:
        svc.generate_batch(prompts_varied(12), temperature=0.0, max_tokens=16)
        svc.generate_batch(prompts_varied(1, base=90), temperature=0.0,
                           max_tokens=16)
        snap = svc.snapshot()
        occupied, starved, backlogged = (snap["slot_steps_" + k] for k in
                                         ("occupied", "starved", "backlogged"))
        assert occupied + starved + backlogged == snap["decode_steps"] * 4
        assert occupied > 0 and backlogged > 0
        assert starved > 0
        assert snap["occupancy"] == pytest.approx(
            occupied / (snap["decode_steps"] * 4), abs=1e-4)
        assert snap["prefix_pages"] == (5 if shared_prefix else 0)
    finally:
        assert svc.close()


def test_engine_health_explain_block(lm, pipeline):
    svc = make_service(lm, slots=2, max_new_tokens=4)
    try:
        broker = InProcessBroker()
        _feed(broker, 8, scam_every=4)
        engine = StreamingClassifier(
            pipeline, broker.consumer(["in"], "g"), broker.producer(),
            "out", batch_size=8, max_wait=0.01,
            explain_batch_fn=make_slot_explain_hook(svc, max_tokens=4),
            explain_async=True, annotations_producer=broker.producer(),
            explain_service=svc)
        engine.run(max_messages=8, idle_timeout=1.0)
        engine.close_annotations(timeout=30.0)
        h = engine.health()
        assert set(h["explain"]) == set(SLOTSERVE_BLOCK_SCHEMA)
        json.dumps(h)
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# int8 + temperature determinism
# ---------------------------------------------------------------------------

def test_int8_model_serves_through_slots(lm):
    """The PR 7 per-block quantizer composes: an int8 LanguageModel rides
    the same slot programs (Q8 weights through _mm / the int8 head)."""
    svc = make_service(lm.quantized(), slots=2, max_new_tokens=6)
    try:
        out = svc.generate_batch(["int8 row A", "int8 row B"],
                                 temperature=0.0, max_tokens=6)
        assert len(out) == 2 and all(isinstance(s, str) for s in out)
        snap = svc.snapshot()
        assert snap["completed"] == 2
    finally:
        assert svc.close()


def test_sampled_decode_deterministic_per_seed(lm):
    a = make_service(lm, slots=2, max_new_tokens=8, seed=5)
    try:
        out_a = a.generate_batch(["sample me"], temperature=0.8,
                                 max_tokens=8)
    finally:
        a.close()
    b = make_service(lm, slots=2, max_new_tokens=8, seed=5)
    try:
        out_b = b.generate_batch(["sample me"], temperature=0.8,
                                 max_tokens=8)
    finally:
        b.close()
    assert out_a == out_b


# ---------------------------------------------------------------------------
# serve CLI e2e + game day
# ---------------------------------------------------------------------------

def test_serve_cli_explain_slots_e2e(capsys):
    from fraud_detection_tpu.app.serve import main as serve_main

    rc = serve_main(["--model", "synthetic", "--demo", "120",
                     "--batch-size", "64", "--max-wait", "0.01",
                     "--explain", "onpod-demo", "--explain-slots", "2",
                     "--explain-tokens", "8", "--trace"])
    assert rc == 0
    out = capsys.readouterr().out
    stats = json.loads([l for l in out.splitlines()
                        if l.startswith("{")][0])
    snap = stats["explain"]
    assert snap["slots"] == 2
    assert snap["admitted"] == snap["completed"] + snap["dropped"]
    assert snap["completed"] > 0
    lane = stats["annotations"]
    assert lane["submitted"] == lane["annotated"] + lane["dropped"]
    assert stats["health"]["explain"]["slots"] == 2


def test_serve_cli_explain_slots_validation():
    from fraud_detection_tpu.app.serve import main as serve_main

    with pytest.raises(SystemExit, match="onpod-family"):
        serve_main(["--model", "synthetic", "--demo", "10",
                    "--explain", "canned", "--explain-slots", "2"])
    with pytest.raises(SystemExit, match="explain-slots must be"):
        serve_main(["--model", "synthetic", "--demo", "10",
                    "--explain", "onpod-demo", "--explain-slots", "-1"])


@pytest.mark.scenario
def test_campaign_explain_gameday_passes():
    from fraud_detection_tpu.scenarios.gameday import (get_scenario,
                                                       run_gameday)

    result = run_gameday(get_scenario("campaign_explain", seed=5,
                                      scale=0.25))
    assert result.ok, result.report.table()
    gates = {v.name: v for v in result.report.verdicts}
    assert gates["explain_coverage"].observed == 1.0
    assert gates["slot_accounting_exact"].ok
    ev = result.evidence
    assert ev["annotations"]["submitted"] == (
        ev["annotations"]["annotated"] + ev["annotations"]["dropped"])
    assert ev["annotations"]["drop_records"] == ev["annotations"]["dropped"]


def test_gameday_validation_rejects_bad_configs():
    from fraud_detection_tpu.scenarios.gameday import GameDay
    from fraud_detection_tpu.scenarios.traffic import SteadyLoad

    traffic = (SteadyLoad(name="s", rate=10, duration_s=1.0),)
    with pytest.raises(ValueError, match="single-engine"):
        GameDay(name="x", description="", traffic=traffic, slos=(),
                workers=2, explain_slots=4)
    with pytest.raises(ValueError, match="not both"):
        GameDay(name="x", description="", traffic=traffic, slos=(),
                breaker_threshold=2, explain_slots=4)
    with pytest.raises(ValueError, match="explain_slots must be"):
        GameDay(name="x", description="", traffic=traffic, slos=(),
                explain_slots=0)


# ---------------------------------------------------------------------------
# the tiny hybrid (tests/hybrid_tiny.py): KDA state + latent pages + experts
# through the same lane
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hybrid_runs():
    """Five prompts (shared preamble, three lengths) through two slots,
    greedy, float32, admitted behind the shared preamble (``True``) and as
    whole prompts (``False``): per run the tickets' tokens, the snapshot
    before the close and the one after."""
    import hybrid_tiny
    from fraud_detection_tpu.explain.slotserve.service import \
        shared_explain_prefix

    lm = hybrid_tiny.language_model("float32")
    # behind the shared preamble each prompt ENDS in its own words: a model
    # of random weights answers to the last tokens it read
    prompts = [shared_explain_prefix() + " Caller: read me the one-time code"
               + " now." * (40 * (i % 3)) + f" Customer {i} hesitates: {'no' * i}"
               for i in range(5)]
    runs = {"lm": lm, "prompts": prompts}
    for shared in (True, False):
        svc = make_service(lm, slots=2, max_new_tokens=8, prompt_width=832,
                           decode_window=4, shared_prefix=shared,
                           wait_timeout=600.0)
        reqs = [svc.submit(p, temperature=0.0) for p in prompts]
        for r in reqs:
            r.wait(600.0)
        snap = svc.snapshot()
        assert svc.close()
        runs[shared] = {"prompts": [np.asarray(r.tokens) for r in reqs],
                        "served": [np.asarray(r.out) for r in reqs],
                        "snapshot": snap, "after": svc.snapshot()}
    return runs


def test_hybrid_slot_window_matches_fixed_batch_greedy(hybrid_runs):
    """The slot programs (chunked prefill behind the preamble's snapshot
    into a slot's state block, stepped decode, slot reuse) emit
    ``_generate_batch_jit``'s greedy tokens."""
    lm = hybrid_runs["lm"]
    want = lm.generate_tokens_batch(
        [lm.tokenizer.encode(p) for p in hybrid_runs["prompts"]],
        max_new_tokens=8)
    for got, row in zip(hybrid_runs[True]["served"], want):
        assert got.tolist() == row[:len(got)].tolist()


def test_hybrid_shared_preamble_serves_whole_prompt_tokens(hybrid_runs):
    """Latent pages mapped copy-on-write + a state snapshot of the preamble
    restored on admission serve the tokens of whole-prompt admission."""
    for a, b in zip(hybrid_runs[True]["served"], hybrid_runs[False]["served"]):
        assert a.tolist() == b.tolist()
    assert len({tuple(a.tolist()) for a in hybrid_runs[True]["served"]}) > 1


def test_hybrid_served_tokens_are_the_references_first_choice(hybrid_runs):
    """Every token the slot lane served in float32 is the plain
    reference's best at its position, teacher-forced (gap under 1e-4: the
    float32 tolerance of tests/test_llm.py)."""
    import hybrid_tiny

    fam = hybrid_tiny.family()
    run = hybrid_runs[True]
    reqs = [{"prompt": p, "served": s}
            for p, s in zip(run["prompts"], run["served"])]
    gaps = fam.token_gaps(hybrid_tiny.SEED, hybrid_tiny.config("float32"),
                          "float32", reqs, 832 + 8)
    assert max(float(g.max()) for g in gaps) < 1e-4


@pytest.mark.parametrize("shared_prefix", [True, False])
def test_hybrid_snapshot_counts_routing_and_restores(hybrid_runs,
                                                     shared_prefix):
    snap = hybrid_runs[shared_prefix]["snapshot"]
    assert set(snap) == set(SLOTSERVE_BLOCK_SCHEMA)
    for key, types in SLOTSERVE_BLOCK_SCHEMA.items():
        assert isinstance(snap[key], types), (key, type(snap[key]))
    assert snap["completed"] == 5
    # 16 published experts, 4 held, top 4: one pick in four is held on average
    assert 0 < snap["moe_picks_held"] < snap["moe_picks"]
    assert snap["moe_picks"] % 4 == 0
    # a decode step touches at most the 4 held experts of each of 6 layers
    assert 0 < snap["moe_experts_touched"] <= 24 * snap["decode_steps"]
    assert snap["moe_prefill_load_max"] >= snap["moe_prefill_load_mean"] > 0
    # a touched expert is read at least once, a tile covers at least its picks
    assert snap["moe_prefill_tiles"] >= snap["moe_prefill_experts_touched"] > 0
    assert snap["moe_prefill_tile_rows"] >= snap["moe_prefill_picks_held"] > 0
    assert snap["moe_prefill_tile_rows"] % 16 == 0
    assert snap["moe_prefill_picks_held"] < snap["moe_picks_held"]   # + decode's
    # every admission copies the state its prefill starts from into the
    # slot's block: the preamble's snapshot where the prompt shares it, zeros
    # for a whole prompt (and once more for the warm-up's row)
    assert snap["state_restores"] == snap["prefills"] + 1
    assert snap["prefix_hits"] == (5 if shared_prefix else 0)
    json.dumps(snap)


def test_dense_snapshot_counters_stay_zero(lm):
    svc = make_service(lm, slots=2, max_new_tokens=4)
    try:
        svc.generate_batch(["one row"], temperature=0.0, max_tokens=4)
        snap = svc.snapshot()
        assert [snap[k] for k in ("moe_picks", "moe_picks_held",
                                  "moe_picks_zero", "moe_experts_touched",
                                  "moe_expert_slots", "moe_prefill_load_max",
                                  "moe_prefill_load_mean", "moe_prefill_tiles",
                                  "moe_prefill_tile_rows",
                                  "moe_prefill_experts_touched",
                                  "moe_prefill_picks_held", "state_restores")] \
            == [0] * 12
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# the host's own expert counter (ISSUE 35): any routed model reports it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tiny", ["hybrid_tiny", "longcat_tiny", "lfm2_tiny"])
def test_expert_slots_count_steps_times_layers_times_held(tiny):
    """``moe_expert_slots`` is the host's product at each decode window,
    steps run x expert layers x experts held: what ``moe_experts_touched``,
    the device's count of the experts a step really read, could at most
    have been. The lane's warm-up runs a window of its own before the
    service counts ``decode_steps``, so both are read as differences."""
    import importlib

    lm = importlib.import_module(tiny).language_model("float32")
    cfg = lm.cfg
    svc = make_service(lm, slots=2, max_new_tokens=6, prompt_width=128,
                       decode_window=4, shared_prefix=False)
    try:
        before = svc.snapshot()
        svc.generate_batch(prompts_varied(3), temperature=0.0, max_tokens=6)
        after = svc.snapshot()
    finally:
        svc.close()
    steps = after["decode_steps"] - before["decode_steps"]
    slots = after["moe_expert_slots"] - before["moe_expert_slots"]
    touched = after["moe_experts_touched"] - before["moe_experts_touched"]
    assert steps > 0 and cfg.n_expert_layers > 0
    assert slots == steps * cfg.n_expert_layers * cfg.moe.held
    assert 0 < touched <= slots
    if tiny == "lfm2_tiny":             # every expert held: every pick computed
        assert after["moe_picks_held"] == after["moe_picks"] > 0
