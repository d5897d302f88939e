"""Observability contract suite (docs/observability.md).

Pins the Tracecraft claims, not just its plumbing:

* **ring honesty** — a full ring drops OLDEST and counts every dropped
  span (compact row-event blocks count per-row), never blocks;
* **exact span accounting** — begun == ended after clean runs, seeded
  chaos, AND fleet worker kills; every minted batch reaches a terminal;
* **chains** — every flagged/shed/DLQ'd row's poll->terminal span chain
  is retrievable by its correlation id, and the DLQ record carries that
  id (the join the whole feature exists for);
* **ONE schema** — the Prometheus rendering parses and its key set is a
  superset of every ``health()`` leaf (the FC301-style exporter
  contract), and the ``trace`` block's keys are pinned for FC301 proper;
* **lossless fleet merge** — per-stage sketches merged from N workers'
  bus wires equal a single sketch over the same samples, bucket for
  bucket.
"""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

from fraud_detection_tpu.obs.metrics import (MetricsRegistry, leaf_paths,
                                             metric_name, parse_prometheus)
from fraud_detection_tpu.obs.trace import (RowTracer, Span, SpanRing,
                                           aggregate_stage_wires,
                                           fleet_stage_latency)
from fraud_detection_tpu.sched.sketch import LatencySketch
from fraud_detection_tpu.stream import InProcessBroker, StreamingClassifier
from fraud_detection_tpu.utils.atomicio import atomic_write_json

# Every tracer starts its ring with the process's boot log
# (tests/test_obs_compile.py): each test here gets an empty one.
pytestmark = [pytest.mark.obs, pytest.mark.usefixtures("boot_log")]


@pytest.fixture(scope="module")
def pipeline():
    from fraud_detection_tpu.models.pipeline import synthetic_demo_pipeline

    return synthetic_demo_pipeline(batch_size=64, n=400, seed=3,
                                   num_features=2048,
                                   corpus_kwargs=dict(hard_fraction=0.0,
                                                      label_noise=0.0))


def _feed(broker, n, topic="in", scam_every=None):
    from tests.fixtures import BENIGN_DIALOGUE, SCAM_DIALOGUE

    prod = broker.producer()
    for i in range(n):
        text = (SCAM_DIALOGUE if scam_every and i % scam_every == 0
                else BENIGN_DIALOGUE)
        prod.produce(topic, json.dumps({"text": text, "id": i}).encode(),
                     key=str(i).encode())


def _engine(broker, pipeline, tracer, **kw):
    return StreamingClassifier(
        pipeline, broker.consumer(["in"], kw.pop("group", "obs")),
        broker.producer(), "out", batch_size=kw.pop("batch_size", 32),
        max_wait=0.01, rowtrace=tracer, **kw)


# ---------------------------------------------------------------------------
# ring buffer honesty
# ---------------------------------------------------------------------------

def test_ring_overflow_drops_oldest_and_counts():
    ring = SpanRing(capacity=8)
    for i in range(11):
        ring.extend([Span(f"c{i}", "s", 0.0, 1.0)])
    assert len(ring) == 8
    assert ring.recorded == 11
    assert ring.dropped == 3
    cids = [s.cid for s in ring.snapshot()]
    assert cids == [f"c{i}" for i in range(3, 11)]   # oldest 3 gone


def test_ring_counts_compact_row_blocks_per_span():
    """A dropped compact row-event block counts every row it carried —
    overflow honesty is span-granular, not entry-granular."""
    tr = RowTracer(worker="w", capacity=2, sample=1.0, seed=0)
    for _ in range(3):
        bt = tr.batch_begin(4)          # "poll" span = 1 entry
        bt.events_rows("flag", [(0, 1), (0, 2), (0, 3)])  # 3 spans, 1 entry
        tr.commit(bt)
    # capacity 2 entries; 3 batches x 2 entries = 6 entries recorded.
    assert tr.ring.recorded == 3 * (1 + 3)
    assert tr.ring.dropped == tr.ring.recorded - len(tr.ring)
    assert tr.ring.dropped > 0
    # The survivors expand back into real spans.
    assert all(isinstance(s, Span) for s in tr.ring.snapshot())


def test_head_sampling_discards_clean_batches_keeps_interesting():
    tr = RowTracer(worker="w", sample=0.0, seed=7)   # keep NOTHING clean
    clean = tr.batch_begin(8)
    tr.commit(clean)
    shed = tr.batch_begin(8)

    class M:
        partition, offset = 0, 5

    shed.shed(M, "shed_queue_full")
    tr.commit(shed)
    snap = tr.snapshot()
    assert snap["sampled_out"] == 1 and snap["kept"] == 1
    spans = tr.ring.snapshot()
    assert all(s.cid.startswith(shed.cid) for s in spans)
    assert any(s.stage == "shed" for s in spans)


# ---------------------------------------------------------------------------
# chains: flagged / shed / DLQ rows join back by correlation id
# ---------------------------------------------------------------------------

def test_dlq_record_carries_trace_id_and_chain_is_complete(pipeline):
    """Malformed rows: the DLQ record's ``trace`` field retrieves the full
    poll->terminal chain from the tracer."""
    broker = InProcessBroker(num_partitions=3)
    _feed(broker, 20)
    bad = broker.producer()
    bad.produce("in", b"not json at all", key=b"bad0")
    bad.produce("in", b'{"nope": 1}', key=b"bad1")
    tr = RowTracer(worker="w0", sample=1.0, seed=0)
    engine = _engine(broker, pipeline, tr, dlq_topic="out-dlq")
    engine.run(max_messages=22, idle_timeout=1.0)
    recs = [json.loads(m.value) for m in broker.messages("out-dlq")]
    assert len(recs) == 2
    for rec in recs:
        cid = rec["trace"]
        assert cid.split(":")[1:] == [str(rec["source"]["partition"]),
                                      str(rec["source"]["offset"])]
        stages = [s.stage for s in tr.chain(cid)]
        assert "poll" in stages and "deliver" in stages   # poll -> terminal
        assert "dlq" in stages
        # The row event itself is on the row cid, not just the batch.
        assert any(s.cid == cid and s.stage == "dlq" for s in tr.chain(cid))


def test_shed_rows_chain_and_trace_id(pipeline):
    """Admission-shed rows: the shed record names the rule AND joins back
    to a complete chain (the event is recorded at the shed site in
    sched/admission.py)."""
    from fraud_detection_tpu.sched import AdaptiveScheduler, SchedulerConfig

    broker = InProcessBroker(num_partitions=3)
    _feed(broker, 60)
    sched = AdaptiveScheduler(
        SchedulerConfig(shed_policy="reject", max_rate=1.0, burst=30.0,
                        cost_aware=False), batch_size=32)
    tr = RowTracer(worker="w0", sample=1.0, seed=0)
    engine = _engine(broker, pipeline, tr, dlq_topic="out-dlq",
                     scheduler=sched)
    engine.run(max_messages=60, idle_timeout=1.0)
    recs = [json.loads(m.value) for m in broker.messages("out-dlq")]
    shed = [r for r in recs if r["reason"].startswith("shed_")]
    assert shed, "the rate limit never shed"
    assert engine.stats.shed == len(shed)
    for rec in shed:
        chain = tr.chain(rec["trace"])
        stages = [s.stage for s in chain]
        assert "poll" in stages and "deliver" in stages
        ev = [s for s in chain if s.cid == rec["trace"] and s.stage == "shed"]
        assert ev and ev[0].detail == rec["reason"]


def test_flagged_rows_always_kept_with_chain(pipeline):
    """Flagged rows force their batch kept even at sample=0, and each
    flagged row's chain is retrievable by its id."""
    broker = InProcessBroker(num_partitions=3)
    _feed(broker, 40, scam_every=8)          # a few flagged rows
    tr = RowTracer(worker="w0", sample=0.0, seed=0)   # keep NO clean batch
    engine = _engine(broker, pipeline, tr)
    engine.run(max_messages=40, idle_timeout=1.0)
    flags = [s for s in tr.ring.snapshot() if s.stage == "flag"]
    assert flags, "no row flagged — fixture drifted"
    n_out = len({m.key for m in broker.messages("out")})
    assert n_out == 40
    for f in flags:
        stages = {s.stage for s in tr.chain(f.cid)}
        assert {"poll", "launch", "device", "deliver"} <= stages


def test_annotation_lane_spans_ride_flagged_chains(pipeline):
    """Async-annotated flagged rows gain explain/annotate spans on the
    same correlation id; a raising backend records ok=False (the breaker's
    fast-fail lands on this same path). Two engines in turn share the hook
    and the tracer, so the second backend call (the one that dies) happens
    however the lane cut its micro-batches."""
    calls = {"n": 0}

    def hook(texts, labels, confs):
        calls["n"] += 1
        if calls["n"] == 1:
            return [f"analysis {i}" for i in range(len(texts))]
        raise RuntimeError("backend died")

    broker = InProcessBroker(num_partitions=3)
    tr = RowTracer(worker="w0", sample=1.0, seed=0)
    for part in range(2):
        _feed(broker, 16, scam_every=16)    # one flagged row: one call
        engine = StreamingClassifier(
            pipeline, broker.consumer(["in"], "obs"), broker.producer(),
            "out", batch_size=8, max_wait=0.01, rowtrace=tr,
            explain_batch_fn=hook, explain_async=True,
            annotations_producer=broker.producer())
        engine.run(max_messages=16, idle_timeout=1.0)
        assert engine.close_annotations(timeout=10.0)
        engine.consumer.close()
    assert calls["n"] == 2
    spans = tr.ring.snapshot()
    ann = [s for s in spans if s.stage == "annotate"]
    assert [s.ok for s in ann] == [True, False]
    assert ann[1].detail == "RuntimeError"
    assert {"poll", "deliver", "lane_wait"} <= {
        x.stage for x in tr.chain(ann[0].cid)}
    lane = [s for s in spans if s.stage == "explain" and s.cid == "lane"]
    assert [(s.ok, s.detail) for s in lane] == [(True, "rows=1"),
                                                (False, "RuntimeError")]
    _assert_exact_accounting(tr)


# ---------------------------------------------------------------------------
# the flagged row's chain past the batch's terminal (ISSUE 26)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def slot_lm():
    from fraud_detection_tpu.models import llm

    cfg = llm.TransformerConfig(d_model=64, n_layers=2, n_heads=4, d_ff=128,
                                max_seq=1024)
    return llm.LanguageModel.init_random(cfg, seed=3)


def _explained_desk(pipeline, slot_lm, tracer, n=48, scam_every=6):
    """Engine -> annotation lane -> paged slots at test size, run to the
    last annotation; returns the slot service's final snapshot."""
    from fraud_detection_tpu.explain.slotserve import (SlotServeService,
                                                       make_slot_explain_hook)

    svc = SlotServeService(slot_lm, slots=2, max_new_tokens=24,
                           prompt_width=960, decode_window=4, page_size=64,
                           rowtrace=tracer,
                           wait_timeout=120.0)
    try:
        broker = InProcessBroker(num_partitions=3)
        _feed(broker, n, scam_every=scam_every)
        engine = _engine(
            broker, pipeline, tracer, batch_size=8,
            explain_batch_fn=make_slot_explain_hook(svc, max_tokens=24),
            explain_async=True, annotations_producer=broker.producer(),
            explain_service=svc)
        engine.run(max_messages=n, idle_timeout=1.0)
        assert engine.close_annotations(timeout=120.0)
        return svc.snapshot()
    finally:
        svc.close()


# Two clocks meet in a chain (a span's start is time.time(), a context
# manager's duration time.perf_counter()), so "ends before the next
# begins" holds to well under this.
CLOCKS_AGREE_S = 1e-3


def _one(chain, stage, **where):
    got = [s for s in chain if s.stage == stage
           and all(getattr(s, k) == v for k, v in where.items())]
    assert len(got) == 1, (stage, [s.stage for s in chain])
    return got[0]


def test_flagged_row_chain_tiles_flag_to_annotation(pipeline, slot_lm):
    """Every explained row's chain tiles flag -> annotate: lane_wait,
    slot_wait, prefill, first token -> done (the end stamp of the per-row
    explain span) and the tail to the annotate event follow one another
    with true starts, and leave under 5 % of the interval unattributed."""
    tr = RowTracer(worker="w0", sample=1.0, seed=0, capacity=1 << 14)
    _explained_desk(pipeline, slot_lm, tr)
    _assert_exact_accounting(tr)
    assert tr.ring.dropped == 0
    annotated = [s for s in tr.ring.snapshot()
                 if s.stage == "annotate" and s.ok]
    assert len(annotated) == 8
    unattributed = interval = 0.0
    for note in annotated:
        chain = tr.chain(note.cid)
        flag = _one(chain, "flag")
        lane, slot, prefill = (_one(chain, st) for st in
                               ("lane_wait", "slot_wait", "prefill"))
        done = _one(chain, "explain", cid=note.cid)     # END-stamped
        assert done.detail.startswith("slot=")
        legs = [lane, slot, prefill]
        for a, b in zip(legs, legs[1:]):
            assert a.start + a.duration_ms / 1e3 <= b.start + CLOCKS_AGREE_S
        assert flag.start <= lane.start + CLOCKS_AGREE_S
        first_token = prefill.start + prefill.duration_ms / 1e3
        assert first_token <= done.start + CLOCKS_AGREE_S
        assert done.start <= note.start
        # The per-row explain span runs submit -> done: it ends where it
        # is stamped and began where slot_wait did.
        assert abs(done.start - done.duration_ms / 1e3
                   - slot.start) < CLOCKS_AGREE_S
        covered = (sum(s.duration_ms for s in legs) / 1e3
                   + (done.start - first_token) + (note.start - done.start))
        assert note.start > flag.start
        interval += note.start - flag.start
        unattributed += note.start - flag.start - covered
    # Over all the rows, so that one descheduled thread under a loaded
    # test run does not decide it (alone: under 1 % on every row).
    assert -0.01 < unattributed / interval < 0.05


def test_slot_loop_writes_one_chain_per_iteration(pipeline, slot_lm):
    """``slot-<iteration>``: slot_iter contains slot_admit, slot_grow,
    slot_launch, slot_fetch, slot_emit, slot_retire in that order, none
    overlapping the next, and every prefill sits inside a slot_admit."""
    tr = RowTracer(worker="w0", sample=1.0, seed=0, capacity=1 << 14)
    snap = _explained_desk(pipeline, slot_lm, tr)
    spans = tr.ring.snapshot()
    order = ["slot_admit", "slot_grow", "slot_launch", "slot_fetch",
             "slot_emit", "slot_retire"]
    iters = [s for s in spans if s.stage == "slot_iter"]
    assert len(iters) == snap["iterations"] > 0
    assert len({s.cid for s in iters}) == len(iters)
    decoded = 0
    for it in iters:
        kids = [s for s in spans if s.cid == it.cid and s is not it]
        assert [s.stage for s in kids] == [st for st in order if st in
                                           {k.stage for k in kids}]
        assert {"slot_admit", "slot_retire"} <= {s.stage for s in kids}
        decoded += any(s.stage == "slot_fetch" for s in kids)
        end = it.start + it.duration_ms / 1e3 + CLOCKS_AGREE_S
        for a, b in zip(kids, kids[1:]):
            assert a.start + a.duration_ms / 1e3 <= b.start + CLOCKS_AGREE_S
        assert it.start <= kids[0].start + CLOCKS_AGREE_S
        assert kids[-1].start + kids[-1].duration_ms / 1e3 <= end
    assert decoded > 0
    admits = [s for s in spans if s.stage == "slot_admit"]
    for p in (s for s in spans if s.stage == "prefill"):
        assert any(a.start <= p.start + CLOCKS_AGREE_S
                   and p.start + p.duration_ms / 1e3
                   <= a.start + a.duration_ms / 1e3 + CLOCKS_AGREE_S
                   for a in admits)


def test_launch_holds_featurize_and_upload_with_true_starts(pipeline):
    """The stream path: ``poll`` carries the wait of the batch's oldest
    row on the broker; ``launch`` contains its children ``featurize``
    (rows=) and ``upload`` (rows= padded= bytes=), timed where they
    happen; a clean batch costs seven spans."""
    import time

    broker = InProcessBroker(num_partitions=3)
    _feed(broker, 64)
    time.sleep(0.05)                    # the rows age on the broker
    tr = RowTracer(worker="w0", sample=1.0, seed=0)
    _engine(broker, pipeline, tr).run(max_messages=64, idle_timeout=0.5)
    _assert_exact_accounting(tr)
    spans = tr.ring.snapshot()
    batches = {s.cid for s in spans if s.stage == "poll"}
    assert batches
    for cid in batches:
        chain = [s for s in spans if s.cid == cid]
        assert sorted(s.stage for s in chain) == sorted(
            ["poll", "admit", "launch", "featurize", "upload", "device",
             "deliver"])
        poll, launch, feat, up = (_one(chain, st) for st in
                                  ("poll", "launch", "featurize", "upload"))
        assert poll.duration_ms >= 50.0
        rows = int(poll.detail.split("=")[1])
        assert feat.detail == f"rows={rows}"
        detail = dict(kv.split("=") for kv in up.detail.split())
        assert int(detail["rows"]) == rows <= int(detail["padded"])
        assert int(detail["bytes"]) > 0
        assert launch.start <= feat.start + CLOCKS_AGREE_S
        assert feat.start + feat.duration_ms / 1e3 <= up.start + CLOCKS_AGREE_S
        assert (up.start + up.duration_ms / 1e3
                <= launch.start + launch.duration_ms / 1e3 + CLOCKS_AGREE_S)
        ordered = [_one(chain, st) for st in
                   ("poll", "admit", "launch", "device", "deliver")]
        for a, b in zip(ordered, ordered[1:]):
            assert a.start <= b.start + CLOCKS_AGREE_S


def test_untraced_engine_builds_no_span_objects(pipeline, slot_lm,
                                                monkeypatch):
    """No tracer attached: the engine, the annotation lane and the slot
    lane construct no Span, open no span context and enter no profiler
    annotation, while the pipeline still hands its phase timings up. (Set-up
    writes its own few to the boot log, tracer or none — the service's
    three phases and a ``compile`` per executable: only a span of those six
    stages, or a context whose tracer is the boot log, goes uncounted.)"""
    from fraud_detection_tpu.obs import trace as trace_mod

    built = {"n": 0}
    setup = {trace_mod.STAGE_SETUP_TRAIN, trace_mod.STAGE_SETUP_PIPELINE,
             trace_mod.STAGE_SETUP_SERVICE, trace_mod.STAGE_SETUP_PREAMBLE,
             trace_mod.STAGE_SETUP_WARM, trace_mod.STAGE_COMPILE}
    # By position: Span(cid, stage, ...), _SpanCtx(tracer, ...),
    # _annotation(stage, cid); a row event is never set-up's.
    is_setups = {"Span": lambda a: a[1] in setup,
                 "_SpanCtx": lambda a: a[0] is trace_mod.BOOT,
                 "_annotation": lambda a: a[0] in setup,
                 "_RowEvents": lambda a: False}

    def counting(name, is_setups):
        real = getattr(trace_mod, name)

        def make(*a, **kw):
            built["n"] += not is_setups(a)
            return real(*a, **kw)

        monkeypatch.setattr(trace_mod, name, make)

    for name, exempt in is_setups.items():
        counting(name, exempt)
    snap = _explained_desk(pipeline, slot_lm, None, n=16, scam_every=8)
    assert snap["completed"] == 2 and snap["decode_steps"] > 0
    assert built["n"] == 0
    pending = pipeline.predict_async(["Agent: hello there friend."])
    assert [p.stage for p in pending.phases] == ["featurize", "upload"]
    pending.resolve()
    # ... and the counter does count when a tracer is attached.
    _explained_desk(pipeline, slot_lm, RowTracer(worker="w0"), n=8,
                    scam_every=8)
    assert built["n"] > 0


# ---------------------------------------------------------------------------
# exact accounting under chaos + worker death
# ---------------------------------------------------------------------------

def _assert_exact_accounting(tr):
    snap = tr.snapshot()
    assert snap["spans_begun"] == snap["spans_ended"], snap
    assert snap["spans_open"] == 0
    assert snap["batches_traced"] == snap["batches_closed"], snap
    assert snap["kept"] + snap["sampled_out"] == snap["batches_closed"]


def test_span_accounting_exact_under_seeded_chaos(pipeline):
    """begun == ended and traced == closed across a whole supervised chaos
    run — every abort path (poll errors, flush crashes, fences) closes the
    batches it abandons. One tracer spans all incarnations."""
    from fraud_detection_tpu.stream.engine import run_supervised
    from fraud_detection_tpu.stream.faults import (ChaosConsumer,
                                                   ChaosProducer, FaultPlan)

    plan = FaultPlan(seed=42, poll_error_rate=0.08, duplicate_rate=0.08,
                     corrupt_rate=0.05, flush_fail_rate=0.08,
                     flush_crash_rate=0.06, commit_fence_rate=0.08,
                     max_faults=60, sleep=lambda s: None)
    broker = InProcessBroker(num_partitions=3)
    _feed(broker, 150)
    tr = RowTracer(worker="w0", sample=1.0, seed=0)
    attempts: dict = {}

    def make_engine():
        return StreamingClassifier(
            pipeline, ChaosConsumer(broker.consumer(["in"], "chaos"), plan),
            ChaosProducer(broker.producer(), plan), "out",
            batch_size=32, max_wait=0.01, dlq_topic="out-dlq",
            dlq_attempts=attempts, rowtrace=tr)

    stats = run_supervised(make_engine, max_restarts=300, backoff=0.0,
                           idle_timeout=0.2, sleep=lambda s: None)
    assert plan.total_injected > 0 and stats.restarts > 0
    _assert_exact_accounting(tr)
    # Aborted batches are always kept: flush-failure replays left evidence.
    aborts = [s for s in tr.ring.snapshot() if s.stage == "abort"]
    if stats.commits_skipped:
        assert tr.snapshot()["kept"] > 0
        assert aborts or tr.ring.dropped > 0   # may have rolled off the ring


def test_span_accounting_exact_under_fleet_worker_kills(pipeline):
    """Fleet run with seeded whole-worker kills: every worker's tracer
    stays exact, and the coordinator's fleet view carries the merged
    per-stage latency block."""
    from fraud_detection_tpu.fleet import Fleet
    from fraud_detection_tpu.stream.faults import WorkerDeathPlan

    broker = InProcessBroker(num_partitions=4)
    _feed(broker, 400)
    fleet = Fleet.in_process(
        broker, pipeline, "in", "out", 2, batch_size=32,
        death_plan=WorkerDeathPlan(seed=11, kills=1, modes=("crash",)),
        lease_ttl=1.0, heartbeat_interval=0.02, tick_interval=0.02,
        trace=True, trace_sample=1.0, trace_seed=0)
    out = fleet.run(idle_timeout=1.0)
    assert out["errors"] == []
    assert {m.key for m in broker.messages("out")} \
        == {str(i).encode() for i in range(400)}
    assert fleet.tracers, "fleet built no tracers under trace=True"
    for tr in fleet.tracers.values():
        _assert_exact_accounting(tr)
    stage_lat = out["stage_latency_ms"]
    assert stage_lat and "deliver" in stage_lat
    assert stage_lat["deliver"]["count"] > 0


def test_coordinator_tick_merges_live_workers_stage_wires():
    """The live-fleet path: a member's bus doc carrying stage wires lands
    merged in the published fleet view."""
    from fraud_detection_tpu.fleet.bus import FleetBus
    from fraud_detection_tpu.fleet.coordinator import FleetCoordinator

    bus = FleetBus()
    coord = FleetCoordinator(["in"], 2, bus=bus)
    coord.join("w0")
    tr = RowTracer(worker="w0", sample=1.0, seed=0)
    tr._observe_stage("device", 0.004)
    bus.publish("w0", {"backlog": 0,
                       "obs": {"stages": tr.stages_wire()}})
    view = coord.tick()
    assert view["stage_latency_ms"]["device"]["count"] == 1
    assert bus.fleet_view()["stage_latency_ms"]["device"]["count"] == 1


# ---------------------------------------------------------------------------
# fleet sketch merge: lossless parity
# ---------------------------------------------------------------------------

def test_sketch_wire_roundtrip_exact():
    rng = np.random.default_rng(0)
    sk = LatencySketch()
    sk.add_many(rng.exponential(0.01, 1000))
    back = LatencySketch.from_wire(sk.to_wire())
    assert np.array_equal(back._counts, sk._counts)
    assert back.count == sk.count and back.sum == sk.sum and back.max == sk.max
    assert LatencySketch.from_wire({"v": 2}) is None
    assert LatencySketch.from_wire("junk") is None
    assert LatencySketch.from_wire({"v": 1, "idx": [999999], "counts": [1],
                                    "count": 1, "sum": 1, "max": 1}) is None


def test_fleet_sketch_merge_equals_single_process():
    """N workers' wire-published stage sketches, merged by the
    coordinator-side aggregation, equal ONE sketch fed every sample —
    bucket-exact, so fleet p50/p99 per stage is not an approximation of
    an approximation."""
    rng = np.random.default_rng(1)
    samples = [rng.exponential(0.02, 500) for _ in range(3)]
    wires = []
    for i, part in enumerate(samples):
        tr = RowTracer(worker=f"w{i}", sample=1.0, seed=0)
        tr._observe_stage("device", 0.0)  # ensure stage exists
        tr._stages["device"].add_many(part)
        wires.append(tr.stages_wire())
    merged = aggregate_stage_wires(wires)["device"]
    single = LatencySketch()
    single.add(0.0)
    single.add(0.0)
    single.add(0.0)
    for part in samples:
        single.add_many(part)
    assert np.array_equal(merged._counts, single._counts)
    assert merged.count == single.count
    view = fleet_stage_latency(wires)
    assert view["device"]["p99_ms"] == single.snapshot()["p99_ms"]


# ---------------------------------------------------------------------------
# metrics exporter: ONE schema, parseable, superset of health()
# ---------------------------------------------------------------------------

TRACE_BLOCK_SCHEMA = {
    "worker": (str,),
    "sample": (int, float),
    "spans_begun": (int,),
    "spans_ended": (int,),
    "spans_open": (int,),
    "batches_traced": (int,),
    "batches_closed": (int,),
    "kept": (int,),
    "sampled_out": (int,),
    "ring_depth": (int,),
    "ring_capacity": (int,),
    "ring_recorded": (int,),
    "ring_dropped": (int,),
    "stages": (dict,),
}


def test_trace_block_schema_contract(pipeline):
    """Pins RowTracer.snapshot()'s exact key set + types (FC301 checks the
    same contract statically)."""
    broker = InProcessBroker(num_partitions=3)
    _feed(broker, 16)
    tr = RowTracer(worker="w0", sample=1.0, seed=0)
    engine = _engine(broker, pipeline, tr, batch_size=16)
    engine.run(max_messages=16, idle_timeout=1.0)
    h = engine.health()
    snap = h["trace"]
    assert set(snap) == set(TRACE_BLOCK_SCHEMA), (
        f"trace block keys changed — update the schema test AND the "
        f"docs/pollers (extra: {set(snap) - set(TRACE_BLOCK_SCHEMA)}, "
        f"missing: {set(TRACE_BLOCK_SCHEMA) - set(snap)})")
    for key, types in TRACE_BLOCK_SCHEMA.items():
        assert isinstance(snap[key], types), (key, type(snap[key]))
    json.dumps(h)


def test_prometheus_output_parses_and_covers_every_health_key(pipeline):
    """The exporter contract: the Prometheus text parses strictly, and for
    EVERY leaf key path of the engine's health() dict the mapped metric
    name is present (lists land as <name>_count) — the exporter's key set
    is a superset of every existing health block by construction."""
    broker = InProcessBroker(num_partitions=3)
    _feed(broker, 32)
    tr = RowTracer(worker="w0", sample=1.0, seed=0)
    engine = _engine(broker, pipeline, tr, dlq_topic="out-dlq")
    engine.run(max_messages=32, idle_timeout=1.0)
    reg = MetricsRegistry()
    reg.counter("demo_events", "native instrument").inc(3)
    reg.histogram("demo_latency", "native sketch").observe_many([0.01, 0.02])
    reg.add_collector("engine", engine.health)
    text = reg.render_prometheus()
    parsed = parse_prometheus(text)      # raises on any unparseable line
    health = engine.health()
    for path in leaf_paths(health, ("engine",)):
        name = metric_name(reg.prefix, path)
        assert name in parsed or name + "_count" in parsed, (
            f"health leaf {'.'.join(path)} has no exported sample {name}")
    # Native instruments render with their conventions.
    assert parsed["fraud_demo_events_total"][0][1] == 3.0
    assert "fraud_demo_latency" in parsed          # quantile samples
    assert parsed["fraud_demo_latency_count"][0][1] == 2.0
    # JSON rendering carries the raw nested schema too.
    j = reg.render_json()
    assert j["collectors"]["engine"]["processed"] == 32
    json.dumps(j)


def test_metrics_http_endpoint_serves_both_formats(pipeline):
    from fraud_detection_tpu.obs.export import MetricsServer

    reg = MetricsRegistry()
    reg.gauge("up", fn=lambda: 1.0)
    srv = MetricsServer(reg, port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert parse_prometheus(text)["fraud_up"][0][1] == 1.0
        j = json.loads(urllib.request.urlopen(
            f"{base}/metrics.json").read().decode())
        assert j["metrics"]["fraud_up"] == 1.0
        assert reg.counter("metrics_scrapes").value == 2
    finally:
        srv.close()


def test_metrics_file_writer_formats(tmp_path):
    from fraud_detection_tpu.obs.export import write_metrics

    reg = MetricsRegistry()
    reg.gauge("up", fn=lambda: 1.0)
    prom, js = str(tmp_path / "m.prom"), str(tmp_path / "m.json")
    assert write_metrics(prom, reg) and write_metrics(js, reg)
    assert parse_prometheus(open(prom).read())["fraud_up"][0][1] == 1.0
    assert json.load(open(js))["metrics"]["fraud_up"] == 1.0


# ---------------------------------------------------------------------------
# shared atomic writer
# ---------------------------------------------------------------------------

def test_atomic_writer_never_tears_under_concurrent_writers(tmp_path):
    """Two writers hammering ONE path (the torn-read audit finding: the
    old fixed '<path>.tmp' name let writers interleave): every read must
    parse and be one writer's complete payload."""
    path = str(tmp_path / "state.json")
    stop = threading.Event()
    payloads = {w: {"writer": w, "blob": "x" * 4096} for w in ("a", "b")}

    def writer(w):
        while not stop.is_set():
            atomic_write_json(path, payloads[w])

    threads = [threading.Thread(target=writer, args=(w,)) for w in ("a", "b")]
    for t in threads:
        t.start()
    try:
        seen = set()
        reads = 0
        while reads < 300:
            try:
                doc = json.load(open(path))
            except FileNotFoundError:
                continue
            assert doc == payloads[doc["writer"]]   # complete, untorn
            seen.add(doc["writer"])
            reads += 1
    finally:
        stop.set()
        for t in threads:
            t.join()
    leftovers = [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]
    assert not leftovers, f"temp files leaked: {leftovers}"


# ---------------------------------------------------------------------------
# serve CLI e2e (the CI obs-smoke shape)
# ---------------------------------------------------------------------------

def test_serve_cli_trace_and_metrics_file(tmp_path):
    """serve --demo with tracing + metrics on: exit 0, exporter file
    parses, trace accounting exact, every engine-health leaf exported."""
    metrics = str(tmp_path / "metrics.json")
    proc = subprocess.run(
        [sys.executable, "-m", "fraud_detection_tpu.app.serve",
         "--model", "synthetic", "--demo", "200", "--batch-size", "64",
         "--trace", "--trace-sample", "1.0",
         "--metrics-file", metrics, "--dlq"],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.load(open(metrics))
    eng = doc["collectors"]["engine"]
    assert eng["processed"] == 200
    snap = eng["trace"]
    assert snap["spans_begun"] == snap["spans_ended"]
    assert snap["batches_traced"] == snap["batches_closed"] > 0
    # The stdout stats line still parses and carries the trace block.
    line = json.loads(proc.stdout.splitlines()[-2])
    assert line["health"]["trace"]["spans_open"] == 0
