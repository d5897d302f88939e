"""Adaptive serving scheduler suite: dynamic batching, admission control,
load shedding, SLO tracking (fraud_detection_tpu/sched/; docs/scheduling.md).

The acceptance invariants pinned here:

* a low-traffic trickle ships ONE partial batch at the deadline instead of
  fragmenting (or waiting for 1024 rows);
* partial batches pad to pre-warmed ladder rungs — ZERO new XLA compiles on
  the hot path, asserted via a compile-counting hook (jit cache size);
* under overload the engine sheds EXPLICITLY: every consumed row is exactly
  one of {produced, DLQ'd, shed-with-record}, shed records never cover
  committed offsets, and with the adaptive policy p99 enqueue->produce
  latency stays bounded near the target while the unscheduled engine's
  blows up with the queue;
* the same key-set accounting holds under seeded stream/faults.py chaos;
* the scheduler's single-driver contract is racecheck-enforced, and health
  snapshots from other threads never trip it.
"""

import json
import threading
import time

import numpy as np
import pytest

from fraud_detection_tpu.models.pipeline import PredictionBatch
from fraud_detection_tpu.sched import (AdaptiveScheduler, BackpressureGovernor,
                                       LatencySketch, SchedulerConfig,
                                       SloTracker, TokenBucket, default_ladder,
                                       prewarm_ladder)
from fraud_detection_tpu.sched.admission import (SHED_QUEUE,
                                                 AdmissionController)
from fraud_detection_tpu.sched.batcher import DynamicBatcher, bucket_for
from fraud_detection_tpu.stream import InProcessBroker, StreamingClassifier
from fraud_detection_tpu.utils import racecheck

pytestmark = pytest.mark.sched


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class SlowPending:
    def __init__(self, n, delay):
        self.n, self.delay = n, delay

    def resolve(self):
        if self.delay:
            time.sleep(self.delay * self.n)
        return PredictionBatch(np.zeros(self.n, np.int32),
                               np.full(self.n, 0.1, np.float32))


class SlowPipeline:
    """Pipeline stub with an injectable per-ROW device cost — gives the
    overload tests a KNOWN capacity (1/delay rows/sec, like a padded device
    program whose cost scales with rows) instead of whatever the CI host's
    jax happens to do."""

    def __init__(self, batch_size, delay=0.0):
        self.batch_size = batch_size
        self.delay = delay
        self.pad_ladder = None
        self.calls = []   # row counts per scoring call

    def predict_async(self, texts):
        self.calls.append(len(texts))
        return SlowPending(len(texts), self.delay)

    def predict_json_async(self, values, text_field="text"):
        return None      # force the engine's slow path (deterministic)

    def predict(self, texts):
        return self.predict_async(texts).resolve()


def feed(broker, n, topic="in", start=0):
    prod = broker.producer()
    for i in range(start, start + n):
        prod.produce(topic,
                     json.dumps({"text": f"ordinary dialogue {i}",
                                 "id": i}).encode(),
                     key=str(i).encode())


def make_engine(broker, pipe, group="sched", **kwargs):
    return StreamingClassifier(
        pipe, broker.consumer(["in"], group), broker.producer(), "out",
        max_wait=0.01, **kwargs)


def keys(broker, topic):
    return [m.key for m in broker.messages(topic)]


# ---------------------------------------------------------------------------
# latency sketch + SLO tracker
# ---------------------------------------------------------------------------

def test_sketch_quantiles_track_numpy_within_bucket_error():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-4.0, sigma=1.0, size=20_000)  # ~18ms median
    sk = LatencySketch()
    sk.add_many(samples)
    assert sk.count == 20_000
    for q in (0.50, 0.95, 0.99):
        got = sk.quantile(q)
        want = float(np.quantile(samples, q))
        # Log-bucketed at 7% growth; the upper-edge estimate may sit one
        # bucket high — allow 10% relative error.
        assert want <= got <= want * 1.12, (q, got, want)


def test_sketch_scalar_add_picks_add_manys_buckets():
    """``add`` (one sample, no array) and ``add_many`` are one sketch: the
    same bucket, count, sum and max for samples below, on and between the
    edges, past the last one, at zero and negative."""
    from fraud_detection_tpu.sched import sketch as sketch_mod

    rng = np.random.default_rng(5)
    samples = np.concatenate([
        10.0 ** rng.uniform(-6, 3.5, 4000), sketch_mod._EDGES[::7],
        np.nextafter(sketch_mod._EDGES[::11], np.inf),
        [0.0, -1e-6, 1e-5, 1e9]])
    one, many = LatencySketch(), LatencySketch()
    for sec in samples.tolist():
        one.add(sec)
    many.add_many(samples)
    assert np.array_equal(one._counts, many._counts)
    assert one.count == many.count == len(samples)
    assert one.max == many.max
    assert one.sum == pytest.approx(many.sum, rel=1e-12)
    assert one.to_wire()["idx"] == many.to_wire()["idx"]


def test_sketch_empty_and_merge():
    a, b = LatencySketch(), LatencySketch()
    assert a.quantile(0.99) is None
    assert a.snapshot()["p99_ms"] is None
    a.add_many([0.010] * 90)
    b.add_many([0.100] * 10)
    a.merge(b)
    assert a.count == 100
    assert a.quantile(0.5) == pytest.approx(0.010, rel=0.15)
    assert a.quantile(0.99) == pytest.approx(0.100, rel=0.15)
    assert a.max == pytest.approx(0.100)


def test_slo_tracker_windows_rotate_and_target(monkeypatch):
    clock = FakeClock()
    slo = SloTracker(target_p99_ms=50.0, window_sec=10.0, clock=clock)
    assert slo.over_target() is None          # no samples: no signal
    slo.record([0.200] * 100)                 # 200ms >> 50ms target
    assert slo.over_target() is True
    # Two full rotations later the old window has aged out entirely.
    clock.advance(11.0)
    slo.record([0.001])
    clock.advance(11.0)
    slo.record([0.001] * 100)
    assert slo.over_target() is False
    snap = slo.snapshot()
    assert snap["target_p99_ms"] == 50.0 and snap["count"] >= 100


# ---------------------------------------------------------------------------
# ladder + batcher
# ---------------------------------------------------------------------------

def test_default_ladder_shapes():
    assert default_ladder(1024) == (64, 256, 1024)
    assert default_ladder(256) == (16, 64, 256)
    assert default_ladder(16) == (16,)
    assert bucket_for(3, (64, 256, 1024)) == 64
    assert bucket_for(65, (64, 256, 1024)) == 256
    assert bucket_for(5000, (64, 256, 1024)) == 1024


def test_batcher_accumulates_trickle_until_deadline():
    """Rows arriving in two spurts inside the deadline window form ONE
    batch; the bare poll would have shipped two."""
    broker = InProcessBroker(num_partitions=1)
    feed(broker, 4)
    consumer = broker.consumer(["in"], "b")
    batcher = DynamicBatcher(deadline_ms=300.0, poll_slice=0.01)

    t = threading.Timer(0.05, lambda: feed(broker, 6, start=4))
    t.start()
    try:
        t0 = time.monotonic()
        msgs = batcher.collect(consumer, 1024, first_wait=0.05)
        elapsed = time.monotonic() - t0
    finally:
        t.join()
    assert len(msgs) == 10                 # both spurts, one batch
    assert elapsed < 5.0                   # and the deadline bounded the wait


def test_batcher_without_deadline_is_a_plain_poll():
    broker = InProcessBroker(num_partitions=1)
    feed(broker, 4)
    consumer = broker.consumer(["in"], "b2")
    msgs = DynamicBatcher(deadline_ms=None).collect(consumer, 1024, 0.05)
    assert len(msgs) == 4                  # no accumulation window


def test_engine_ships_partial_batch_at_deadline():
    """Acceptance: low traffic ships ONE partial batch at the deadline
    instead of fragmenting into per-spurt batches or waiting for 1024."""
    pipe = SlowPipeline(batch_size=1024)
    broker = InProcessBroker(num_partitions=1)
    feed(broker, 4)
    sched = AdaptiveScheduler(SchedulerConfig(batch_deadline_ms=300.0),
                              batch_size=1024)
    engine = make_engine(broker, pipe, batch_size=1024, scheduler=sched)
    t = threading.Timer(0.05, lambda: feed(broker, 6, start=4))
    t.start()
    try:
        stats = engine.run(max_messages=10, idle_timeout=2.0)
    finally:
        t.join()
    assert stats.processed == 10
    assert stats.batches == 1, "trickle fragmented instead of accumulating"
    assert len(keys(broker, "out")) == 10


# ---------------------------------------------------------------------------
# ladder pre-warm: zero compiles on the hot path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pipeline():
    from fraud_detection_tpu.models.pipeline import synthetic_demo_pipeline

    return synthetic_demo_pipeline(batch_size=64, n=300, seed=3,
                                   num_features=2048,
                                   corpus_kwargs=dict(hard_fraction=0.0,
                                                      label_noise=0.0))


def test_ladder_prewarm_keeps_hot_path_compile_free(pipeline):
    """Satellite: pre-warm the padding-bucket ladder, then run partial
    batches of every size class — the jitted scoring program's compile
    cache must not grow (the compile-counting hook)."""
    from fraud_detection_tpu.models import linear as linear_mod

    text = "hello this is a perfectly ordinary dialogue about appointments"
    ladder = default_ladder(64)            # (16, 64)
    prewarm_ladder(pipeline, ladder, texts=[text])
    try:
        compiled = linear_mod._prob_encoded._cache_size()
        for n in (1, 3, 15, 16, 17, 40, 64):
            batch = pipeline.predict([text] * n)
            assert len(batch.labels) == n
        assert linear_mod._prob_encoded._cache_size() == compiled, (
            "a partial batch compiled a fresh XLA program on the hot path")
    finally:
        pipeline.pad_ladder = None


def test_hotswap_candidates_inherit_ladder_prewarm(pipeline):
    """Satellite: the hot-swap pre-warm path warms every rung for swap
    candidates too — a swap followed by a small batch never compiles."""
    from fraud_detection_tpu.models import linear as linear_mod
    from fraud_detection_tpu.models.pipeline import synthetic_demo_pipeline
    from fraud_detection_tpu.registry.hotswap import HotSwapPipeline

    text = "hello this is a perfectly ordinary dialogue about appointments"
    hot = HotSwapPipeline(pipeline, version=1, prewarm_texts=[text])
    hot.configure_ladder(default_ladder(64), prewarm=True)
    try:
        candidate = synthetic_demo_pipeline(
            batch_size=64, n=300, seed=3, num_features=2048,
            corpus_kwargs=dict(hard_fraction=0.0, label_noise=0.0))
        hot.swap(candidate, version=2)     # pre-warms the ladder by default
        compiled = linear_mod._prob_encoded._cache_size()
        for n in (2, 17, 64):
            hot.predict([text] * n)
        assert linear_mod._prob_encoded._cache_size() == compiled
        assert candidate.pad_ladder == default_ladder(64)
    finally:
        pipeline.pad_ladder = None


# ---------------------------------------------------------------------------
# cost-aware ladder (tentpole c): geometry from the measured cost curve
# ---------------------------------------------------------------------------

def test_ladder_candidates_geometry():
    from fraud_detection_tpu.sched import ladder_candidates

    assert ladder_candidates(1024) == (64, 128, 256, 512, 1024)
    assert ladder_candidates(64) == (16, 32, 64)
    assert ladder_candidates(16) == (16,)
    # candidates are a superset of the fixed default geometry
    assert set(default_ladder(1024)) <= set(ladder_candidates(1024))


def test_cost_aware_ladder_flat_curve_collapses():
    """A flat cost curve (fixed dispatch overhead dominates) means padding
    up is free — every sub-rung is dropped."""
    from fraud_detection_tpu.sched import cost_aware_ladder

    costs = {64: 0.010, 128: 0.010, 256: 0.011, 512: 0.010, 1024: 0.011}
    assert cost_aware_ladder(costs, 1024) == (1024,)


def test_cost_aware_ladder_linear_curve_keeps_every_probe():
    from fraud_detection_tpu.sched import cost_aware_ladder

    costs = {64: 0.001, 128: 0.002, 256: 0.004, 512: 0.008, 1024: 0.016}
    assert cost_aware_ladder(costs, 1024) == (64, 128, 256, 512, 1024)


def test_cost_aware_ladder_knee_curve_keeps_the_cheap_side():
    """Flat up to 256 then linear: the flat region collapses into the 256
    rung, the steep region survives."""
    from fraud_detection_tpu.sched import cost_aware_ladder

    costs = {64: 0.004, 128: 0.004, 256: 0.004, 512: 0.008, 1024: 0.016}
    assert cost_aware_ladder(costs, 1024) == (256, 512, 1024)


def test_cost_aware_ladder_validates():
    from fraud_detection_tpu.sched import cost_aware_ladder

    with pytest.raises(ValueError, match="min_ratio"):
        cost_aware_ladder({64: 1.0}, 64, min_ratio=1.0)
    with pytest.raises(ValueError, match="costs"):
        cost_aware_ladder({}, 64)
    # batch_size absent from the probe set: largest measured rung is the top
    assert cost_aware_ladder({16: 0.1, 64: 0.4}, 1024) == (16, 64)


def test_measure_rung_costs_excludes_compile(pipeline):
    """Per-rung costs are steady-state medians: the compile-carrying first
    run is untimed, so a rung's recorded cost must be a small fraction of
    its cold wall (compiles are seconds, steady LR batches are ms)."""
    from fraud_detection_tpu.models import linear as linear_mod
    from fraud_detection_tpu.sched import measure_rung_costs

    text = "hello this is a perfectly ordinary dialogue about appointments"
    try:
        t0 = time.monotonic()
        costs = measure_rung_costs(pipeline, (16, 64), texts=[text])
        wall = time.monotonic() - t0
        assert set(costs) == {16, 64}
        for c in costs.values():
            assert 0 < c < wall / 2    # steady median ≪ total incl. compiles
        # measurement compiled the probe shapes: the hot path stays clean
        compiled = linear_mod._prob_encoded._cache_size()
        for n in (1, 15, 16, 40, 64):
            pipeline.predict([text] * n)
        assert linear_mod._prob_encoded._cache_size() == compiled
    finally:
        pipeline.pad_ladder = None


def test_scheduler_prewarm_derives_cost_aware_geometry(pipeline):
    """Default config (no explicit buckets): prewarm measures candidates,
    derives the ladder from the cost curve, records the table for health(),
    and keeps the governor floor aligned."""
    sched = AdaptiveScheduler(SchedulerConfig(), batch_size=64)
    try:
        n = sched.prewarm(pipeline)
        assert n == len(sched.buckets)
        assert set(sched.ladder_costs) == {16, 32, 64}   # candidates measured
        assert set(sched.buckets) <= {16, 32, 64}
        assert sched.buckets[-1] == 64                   # top rung pinned
        assert sched.governor.min_budget == sched.buckets[0]
        snap = sched.snapshot()
        assert set(snap["ladder_cost_ms"]) == {"16", "32", "64"}
        assert all(v > 0 for v in snap["ladder_cost_ms"].values())
        json.dumps(snap)
        # pipeline adopted the SELECTED geometry
        assert pipeline.pad_ladder == sched.buckets
    finally:
        pipeline.pad_ladder = None


def test_scheduler_prewarm_explicit_buckets_pin_geometry(pipeline):
    """Operator-pinned buckets: geometry untouched, costs still measured
    (the health table is evidence either way)."""
    sched = AdaptiveScheduler(SchedulerConfig(buckets=(16, 64)),
                              batch_size=64)
    try:
        sched.prewarm(pipeline)
        assert sched.buckets == (16, 64)
        assert set(sched.ladder_costs) == {16, 64}
    finally:
        pipeline.pad_ladder = None


def test_hotswap_reuses_measured_costs_for_candidates(pipeline):
    """Tentpole pin: a HotSwapPipeline measures ONCE on the active model;
    swap candidates inherit ladder + cached costs and only compile — no
    re-bench (configure_ladder(costs=...) + prewarm path)."""
    from fraud_detection_tpu.models.pipeline import synthetic_demo_pipeline
    from fraud_detection_tpu.registry.hotswap import HotSwapPipeline
    from fraud_detection_tpu.sched import batcher as batcher_mod

    text = "hello this is a perfectly ordinary dialogue about appointments"
    hot = HotSwapPipeline(pipeline, version=1, prewarm_texts=[text])
    sched = AdaptiveScheduler(SchedulerConfig(), batch_size=64)
    try:
        sched.prewarm(hot)
        assert hot.ladder_costs == sched.ladder_costs
        assert hot.pad_buckets == sched.buckets
        measured = []
        orig = batcher_mod.measure_rung_costs
        batcher_mod.measure_rung_costs = (
            lambda *a, **k: measured.append(1) or orig(*a, **k))
        try:
            candidate = synthetic_demo_pipeline(
                batch_size=64, n=300, seed=3, num_features=2048,
                corpus_kwargs=dict(hard_fraction=0.0, label_noise=0.0))
            hot.swap(candidate, version=2)     # prewarm compiles, no bench
        finally:
            batcher_mod.measure_rung_costs = orig
        assert measured == [], "swap candidate re-benched the ladder"
        assert candidate.pad_ladder == sched.buckets
        assert hot.ladder_costs == sched.ladder_costs  # cache survives swap
    finally:
        pipeline.pad_ladder = None


# ---------------------------------------------------------------------------
# admission control + shedding
# ---------------------------------------------------------------------------

def test_token_bucket_grant_and_drain():
    clock = FakeClock()
    bucket = TokenBucket(rate=100.0, burst=50.0, clock=clock)
    assert bucket.grant(30) == 30          # burst covers it
    assert bucket.grant(30) == 20          # only 20 tokens left
    clock.advance(0.1)                     # +10 tokens
    assert bucket.grant(30) == 10
    # drain goes into debt and reports the pacing required to repay it
    clock.advance(1.0)                     # refill to burst (50)
    assert bucket.drain(50) == 0.0
    assert bucket.drain(100) == pytest.approx(1.0)   # 100 tokens @ 100/s


def test_admission_policy_none_never_sheds_but_paces():
    clock = FakeClock()
    ctl = AdmissionController(
        "none", bucket=TokenBucket(100.0, 10.0, clock=clock))
    msgs = list(range(60))
    keep, shed = ctl.admit(msgs, backlog=10_000)
    assert keep == msgs and shed == []
    assert ctl.pending_pause() == pytest.approx(0.5)  # 50-token debt @ 100/s
    assert ctl.pending_pause() == 0.0                 # cleared on read


def test_admission_queue_watermark_sheds_proportionally():
    ctl = AdmissionController("reject", max_queue=100)
    msgs = list(range(100))
    keep, shed = ctl.admit(msgs, backlog=400)   # 75% over watermark
    assert len(shed) == 75 and len(keep) == 25
    assert all(reason == SHED_QUEUE for _, reason in shed)
    assert shed[0][0] == 25, "must shed the NEWEST rows (batch tail)"
    keep, shed = ctl.admit(msgs, backlog=50)    # under watermark: no shed
    assert len(keep) == 100 and shed == []
    assert ctl.admit([], backlog=400) == ([], [])


def test_admission_adaptive_aimd_fraction():
    from fraud_detection_tpu.stream.broker import Message

    clock = FakeClock()
    slo = SloTracker(target_p99_ms=10.0, window_sec=10.0, clock=clock)
    ctl = AdmissionController("adaptive", slo=slo)
    # timestamp 0 = unavailable: exempt from deadline shedding, so this
    # isolates the AIMD fraction.
    msgs = [Message("in", b"{}", offset=i) for i in range(100)]
    slo.record([0.200] * 50)               # far over target
    fractions = []
    for _ in range(4):
        ctl.admit(msgs, backlog=None)
        fractions.append(ctl.shed_fraction)
    assert fractions == sorted(fractions) and fractions[-1] > 0.1
    # Latency recovers -> fraction decays back to zero.
    clock.advance(11.0)
    slo.record([0.001])
    clock.advance(11.0)
    slo.record([0.001] * 500)
    for _ in range(30):
        ctl.admit(msgs, backlog=None)
    assert ctl.shed_fraction == 0.0


def test_admission_deadline_sheds_stale_rows():
    """Adaptive policy with a target: rows that already burned half the
    target queueing are shed (they cannot finish on-target), fresh rows and
    rows without timestamps are kept."""
    from fraud_detection_tpu.sched.admission import SHED_DEADLINE
    from fraud_detection_tpu.stream.broker import Message

    clock = FakeClock()
    slo = SloTracker(target_p99_ms=100.0, window_sec=10.0, clock=clock)
    now = time.time()
    ctl = AdmissionController("adaptive", slo=slo, wall=lambda: now)
    assert ctl.max_age_sec == pytest.approx(0.05)
    msgs = [Message("in", b"{}", offset=0, timestamp=now - 0.2),   # stale
            Message("in", b"{}", offset=1, timestamp=now - 0.01),  # fresh
            Message("in", b"{}", offset=2, timestamp=0.0)]         # unknown
    keep, shed = ctl.admit(msgs, backlog=None)
    assert [m.offset for m in keep] == [1, 2]
    assert [(m.offset, r) for m, r in shed] == [(0, SHED_DEADLINE)]
    assert ctl.counters[SHED_DEADLINE] == 1


def test_governor_caps_budget_from_ewma():
    gov = BackpressureGovernor(max_batch_sec=0.1, min_budget=16)
    assert gov.advise(1024) == (1024, 0.0)     # no estimate yet: no cap
    gov.observe(1000, 2.0)                     # 2ms/row
    budget, _ = gov.advise(1024)
    assert budget == 50                        # 0.1s / 2ms
    gov.observe(50, 10.0)                      # catastrophic: 200ms/row
    for _ in range(50):
        gov.observe(50, 10.0)
    budget, _ = gov.advise(1024)
    assert budget == 16                        # floored at min_budget
    assert gov.snapshot()["budget_caps"] >= 2


def test_scheduler_config_validation():
    with pytest.raises(ValueError, match="adaptive"):
        SchedulerConfig(shed_policy="adaptive")
    with pytest.raises(ValueError, match="reject"):
        SchedulerConfig(shed_policy="reject")
    with pytest.raises(ValueError, match="batch_deadline_ms"):
        SchedulerConfig(batch_deadline_ms=0)
    with pytest.raises(ValueError, match="shed_policy"):
        SchedulerConfig(shed_policy="nope")
    cfg = SchedulerConfig(target_p99_ms=400.0)
    assert cfg.resolved_max_batch_sec() == pytest.approx(0.2)


def test_engine_requires_dlq_for_shedding_scheduler():
    sched = AdaptiveScheduler(
        SchedulerConfig(shed_policy="reject", max_queue=10), batch_size=32)
    broker = InProcessBroker()
    with pytest.raises(ValueError, match="dlq"):
        make_engine(broker, SlowPipeline(32), scheduler=sched)


# ---------------------------------------------------------------------------
# overload invariants (acceptance)
# ---------------------------------------------------------------------------

def test_overload_exact_key_set_accounting():
    """Acceptance: offered load far beyond capacity, watermark shedding on —
    every consumed row is EXACTLY one of {produced, shed-with-record}, and
    shed records never cover committed-and-produced rows (no key in both
    sets, none missing, none twice)."""
    pipe = SlowPipeline(batch_size=32, delay=0.001)  # capacity 1k rows/s
    broker = InProcessBroker(num_partitions=3)
    n = 400
    feed(broker, n)                                   # all at once: >> 3x capacity
    sched = AdaptiveScheduler(
        SchedulerConfig(shed_policy="reject", max_queue=64), batch_size=32)
    engine = make_engine(broker, pipe, batch_size=32, scheduler=sched,
                         dlq_topic="out-dlq")
    stats = engine.run(max_messages=n, idle_timeout=2.0)
    out, dlq = keys(broker, "out"), keys(broker, "out-dlq")
    assert stats.shed > 0, "overload never shed"
    assert stats.shed == len(dlq)
    assert len(out) + len(dlq) == n                   # nothing lost, nothing doubled
    assert set(out) | set(dlq) == {str(i).encode() for i in range(n)}
    assert not set(out) & set(dlq), "a row was both produced and shed"
    # Shed records are structured and replayable.
    rec = json.loads(broker.messages("out-dlq")[0].value)
    assert rec["reason"] == SHED_QUEUE
    assert set(rec["source"]) == {"topic", "partition", "offset"}
    # health carries the sched block with matching counters.
    h = engine.health()
    assert h["shed"] == stats.shed
    assert h["sched"]["admission"]["shed"][SHED_QUEUE] == stats.shed
    assert stats.as_dict()["p99_row_latency_ms"] is not None


def test_overload_bounded_p99_with_adaptive_shedding():
    """Acceptance: a bursty offered load at ~3x capacity — the scheduled
    engine keeps per-row p99 enqueue->produce latency bounded near the
    target by shedding explicitly, while the bare engine's p99 grows with
    its unbounded queue."""
    delay, bs = 0.000625, 32                # capacity 1600 rows/s
    rate, seconds = 4800.0, 0.5             # offered: 3x capacity, bursty
    n = int(rate * seconds)
    target_ms = 250.0

    def run(scheduled):
        pipe = SlowPipeline(batch_size=bs, delay=delay)
        broker = InProcessBroker(num_partitions=3)
        prod = broker.producer()

        def feeder():                        # paced bursts every ~10ms
            t0 = time.perf_counter()
            chunk = max(1, int(rate * 0.01))
            for start in range(0, n, chunk):
                wait = t0 + start / rate - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                for i in range(start, min(start + chunk, n)):
                    prod.produce("in", json.dumps(
                        {"text": f"dialogue {i}", "id": i}).encode(),
                        key=str(i).encode())

        sched = None
        if scheduled:
            sched = AdaptiveScheduler(
                SchedulerConfig(shed_policy="adaptive",
                                target_p99_ms=target_ms,
                                # watermark: rows half a target window of
                                # service capacity can absorb
                                max_queue=int(target_ms / 2e3 / delay),
                                window_sec=0.2),
                batch_size=bs)
        engine = make_engine(broker, pipe, batch_size=bs, scheduler=sched,
                             dlq_topic="out-dlq" if scheduled else None)
        thread = threading.Thread(target=feeder, daemon=True)
        thread.start()
        try:
            stats = engine.run(max_messages=n, idle_timeout=2.0)
        finally:
            thread.join(10.0)
        return stats, keys(broker, "out"), keys(broker, "out-dlq")

    bare_stats, bare_out, _ = run(scheduled=False)
    sched_stats, out, dlq = run(scheduled=True)
    assert len(bare_out) == n                         # bare engine serves all...
    bare_p99 = bare_stats.as_dict()["p99_row_latency_ms"]
    sched_p99 = sched_stats.as_dict()["p99_row_latency_ms"]
    assert bare_p99 > target_ms, (
        f"overload too mild to discriminate (bare p99 {bare_p99}ms)")
    assert sched_stats.shed > 0
    assert len(out) + len(dlq) == n                   # accounting still exact
    assert sched_p99 < bare_p99, (sched_p99, bare_p99)
    # Within the configured target, with headroom for shed-decision
    # quantization (batch granularity) and CI scheduling jitter.
    assert sched_p99 <= 1.5 * target_ms, (sched_p99, bare_p99)


def test_overload_under_chaos_keeps_key_set_accounting(pipeline):
    """Satellite: seeded chaos (lossy flushes, fences, poll errors,
    duplicates, corruption) PLUS watermark shedding — at-least-once key-set
    accounting still holds: every input key lands in out or the DLQ lane,
    and no commit ever advances past a lost output."""
    from fraud_detection_tpu.stream.engine import run_supervised
    from fraud_detection_tpu.stream.faults import FaultPlan

    plan = FaultPlan(seed=11, poll_error_rate=0.06, duplicate_rate=0.06,
                     corrupt_rate=0.04, flush_fail_rate=0.06,
                     flush_crash_rate=0.04, commit_fence_rate=0.06,
                     max_faults=50, sleep=lambda s: None)
    broker = InProcessBroker(num_partitions=3)
    n = 250
    feed(broker, n)
    sched_state = {}

    def make():
        sched = sched_state.setdefault("s", AdaptiveScheduler(
            SchedulerConfig(shed_policy="reject", max_queue=48),
            batch_size=32))
        cons = plan.consumer(broker.consumer(["in"], "chaos-sched"))
        prod = plan.producer(broker.producer())
        return StreamingClassifier(pipeline, cons, prod, "out",
                                   batch_size=32, max_wait=0.01,
                                   dlq_topic="out-dlq", dlq_attempts={},
                                   scheduler=sched)

    stats = run_supervised(make, max_restarts=300, backoff=0.0,
                           idle_timeout=0.2, sleep=lambda s: None)
    assert plan.total_injected > 0, "the chaos never bit"
    assert stats.shed > 0, "the overload never shed"
    delivered = set(keys(broker, "out")) | set(keys(broker, "out-dlq"))
    want = {str(i).encode() for i in range(n)}
    assert want <= delivered, f"lost keys: {sorted(want - delivered)[:5]}"
    # No commit past a lost output (the PR-1 invariant, now with shedding).
    committed = {(t, p): off
                 for (g, t, p), off in broker._group_offsets.items()
                 if g == "chaos-sched"}
    for m in broker.messages("in"):
        if m.offset < committed.get((m.topic, m.partition), 0):
            assert m.key in delivered, (
                f"commit advanced past lost row {m.key!r}")


# ---------------------------------------------------------------------------
# per-row latency accounting
# ---------------------------------------------------------------------------

def test_row_latency_includes_queue_wait():
    """Per-row enqueue->produce latency must count time spent queued at the
    broker — the component per-batch device latency misses entirely."""
    pipe = SlowPipeline(batch_size=64, delay=0.0)
    broker = InProcessBroker(num_partitions=1)
    feed(broker, 32)
    time.sleep(0.25)                        # rows age in the queue
    engine = make_engine(broker, pipe, batch_size=64)
    stats = engine.run(max_messages=32, idle_timeout=1.0)
    d = stats.as_dict()
    assert d["p50_row_latency_ms"] >= 200, d["p50_row_latency_ms"]
    # The per-batch number stays small — the undercount this satellite fixes.
    assert d["p50_batch_latency_sec"] < 0.2
    h = engine.health()
    assert h["row_latency_ms"]["p50"] == d["p50_row_latency_ms"]
    assert h["sched"] is None               # no scheduler attached


def test_row_latency_merges_across_incarnations():
    from fraud_detection_tpu.stream.engine import StreamStats, _merge_stats

    a, b = StreamStats(), StreamStats()
    a.row_sketch.add_many([0.010] * 50)
    b.row_sketch.add_many([0.080] * 50)
    total = StreamStats()
    _merge_stats(total, a)
    _merge_stats(total, b)
    assert total.row_sketch.count == 100
    assert total.row_latency_ms(0.99) == pytest.approx(80.0, rel=0.15)


# ---------------------------------------------------------------------------
# health contract (the sched block)
# ---------------------------------------------------------------------------

SCHED_BLOCK_SCHEMA = {
    "batch_deadline_ms": (type(None), int, float),
    "buckets": (list,),
    "ladder_cost_ms": (type(None), dict),   # measured at prewarm; None before
    "slo": (dict,),
    "admission": (dict,),
    "governor": (dict,),
}

SLO_BLOCK_SCHEMA = {
    "count": (int,),
    "p50_ms": (type(None), int, float),
    "p95_ms": (type(None), int, float),
    "p99_ms": (type(None), int, float),
    "mean_ms": (type(None), int, float),
    "max_ms": (type(None), int, float),
    "target_p99_ms": (type(None), int, float),
    "window_sec": (int, float),
}

ADMISSION_BLOCK_SCHEMA = {
    "policy": (str,),
    "max_queue": (type(None), int),
    "rate_limit": (type(None), int, float),
    "tokens_available": (type(None), int, float),
    "shed_fraction": (int, float),
    "shed": (dict,),
    "backlog": (type(None), int),
}

GOVERNOR_BLOCK_SCHEMA = {
    "max_batch_sec": (type(None), int, float),
    "ewma_batch_ms": (type(None), int, float),
    "ewma_row_us": (type(None), int, float),
    "budget_caps": (int,),
    "paused_sec": (int, float),
}


def _assert_schema(obj, schema, where):
    assert set(obj) == set(schema), (
        f"{where}: keys changed — update the schema test AND docs/pollers "
        f"(extra: {set(obj) - set(schema)}, missing: {set(schema) - set(obj)})")
    for key, types in schema.items():
        assert isinstance(obj[key], types), (where, key, type(obj[key]))


def test_health_sched_block_contract():
    """Extends PR 2's health JSON schema contract: exact key set + types of
    the sched block, pinned so --health-file pollers can't silently break."""
    pipe = SlowPipeline(batch_size=32)
    broker = InProcessBroker()
    feed(broker, 40)
    sched = AdaptiveScheduler(
        SchedulerConfig(batch_deadline_ms=20.0, shed_policy="reject",
                        max_queue=1000, target_p99_ms=500.0, max_rate=1e6),
        batch_size=32)
    engine = make_engine(broker, pipe, batch_size=32, scheduler=sched,
                         dlq_topic="out-dlq")
    engine.run(max_messages=40, idle_timeout=1.0)
    h = engine.health()
    _assert_schema(h["sched"], SCHED_BLOCK_SCHEMA, "sched")
    _assert_schema(h["sched"]["slo"], SLO_BLOCK_SCHEMA, "sched.slo")
    _assert_schema(h["sched"]["admission"], ADMISSION_BLOCK_SCHEMA,
                   "sched.admission")
    _assert_schema(h["sched"]["governor"], GOVERNOR_BLOCK_SCHEMA,
                   "sched.governor")
    assert h["sched"]["slo"]["count"] == 40
    json.dumps(h)                           # JSON-serializable end to end


# ---------------------------------------------------------------------------
# threading contracts (racecheck satellite)
# ---------------------------------------------------------------------------

def test_scheduler_single_driver_contract_racechecked():
    """Two threads driving one scheduler is a documented contract violation:
    the second entry raises RaceError and the violation is recorded."""
    racecheck.clear_violations()
    sched = AdaptiveScheduler(SchedulerConfig(), batch_size=32)
    entered = threading.Event()
    release = threading.Event()

    class BlockingConsumer:
        def poll_batch(self, n, timeout):
            entered.set()
            release.wait(5.0)
            return []

    worker = threading.Thread(
        target=lambda: sched.collect(BlockingConsumer(), 32, 0.01),
        daemon=True)
    worker.start()
    assert entered.wait(5.0)
    try:
        with pytest.raises(racecheck.RaceError):
            sched.admit([object()], backlog=None)
    finally:
        release.set()
        worker.join(5.0)
    names = [v.region for v in racecheck.violations()]
    assert "AdaptiveScheduler.drive" in names
    racecheck.clear_violations()


def test_health_snapshots_never_trip_the_drive_region():
    """The supported cross-thread read: health()/snapshot() polled hard
    while the engine loop drives — zero racecheck violations."""
    racecheck.clear_violations()
    pipe = SlowPipeline(batch_size=32, delay=0.002)
    broker = InProcessBroker(num_partitions=3)
    feed(broker, 300)
    sched = AdaptiveScheduler(
        SchedulerConfig(batch_deadline_ms=5.0, shed_policy="reject",
                        max_queue=64, target_p99_ms=500.0),
        batch_size=32)
    engine = make_engine(broker, pipe, batch_size=32, scheduler=sched,
                         dlq_topic="out-dlq")
    worker = threading.Thread(
        target=lambda: engine.run(max_messages=300, idle_timeout=2.0),
        daemon=True)
    worker.start()
    deadline = time.monotonic() + 5.0
    while worker.is_alive() and time.monotonic() < deadline:
        json.dumps(engine.health())         # full snapshot path, serialized
        sched.snapshot()
    worker.join(10.0)
    assert not worker.is_alive()
    assert racecheck.violations() == [], [
        (v.region, v.holder, v.intruder) for v in racecheck.violations()]


# ---------------------------------------------------------------------------
# serve CLI surface
# ---------------------------------------------------------------------------

def test_serve_cli_scheduler_end_to_end(capsys):
    from fraud_detection_tpu.app.serve import main as serve_main

    rc = serve_main(["--model", "synthetic", "--demo", "500",
                     "--batch-size", "64", "--max-wait", "0.01",
                     "--batch-deadline-ms", "10", "--max-queue", "200",
                     "--shed-policy", "reject", "--target-p99-ms", "1000"])
    assert rc == 0
    out = capsys.readouterr().out
    stats = json.loads([l for l in out.splitlines() if l.startswith("{")][0])
    assert stats["processed"] == 500
    sched = stats["health"]["sched"]
    assert sched["admission"]["policy"] == "reject"
    # The startup measurement's geometry + cost table reach the per-worker
    # scheduler (serve.py pins measured buckets back into the config).
    assert sched["ladder_cost_ms"], "worker scheduler lost the cost table"
    assert set(sched["buckets"]) <= {int(k) for k in sched["ladder_cost_ms"]}
    assert sched["slo"]["count"] + stats["shed"] == 500
    # Exact accounting through the CLI: classified + shed covers the demo.
    assert stats["shed"] == sum(sched["admission"]["shed"].values())
    assert stats["p99_row_latency_ms"] is not None


def test_serve_cli_rejects_bad_scheduler_config():
    from fraud_detection_tpu.app.serve import main as serve_main

    with pytest.raises(SystemExit, match="scheduler"):
        serve_main(["--model", "synthetic", "--demo", "10",
                    "--shed-policy", "adaptive"])   # no target
    with pytest.raises(SystemExit, match="scheduler"):
        serve_main(["--model", "synthetic", "--demo", "10",
                    "--batch-deadline-ms", "-5"])


# ---------------------------------------------------------------------------
# bench --load-sweep (slow smoke: the full sweep takes ~15s)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bench_load_sweep_smoke(pipeline, monkeypatch):
    import bench

    monkeypatch.setenv("BENCH_SWEEP_SEC", "0.5")
    corpus = ["hello this is a perfectly ordinary dialogue"] * 50
    out = bench.load_sweep_bench(pipeline, corpus, batch_size=64, depth=2,
                                 target_p99_ms=500.0)
    assert out["capacity_est_per_s"] > 0
    assert len(out["points"]) == 7
    for p in out["points"]:
        assert p["delivered"] + p["shed"] == p["fed"]
    assert out["saturation_knee_per_s"] is not None
    json.dumps(out)
