"""Chaos suite: the engine's delivery claims under seeded fault schedules.

The engine documents at-least-once delivery with fenced commits and
supervised restarts (docs/robustness.md); the reference it replaces dies on
the first broker error (SURVEY.md §5). These tests PROVE the claims by
key-set accounting under `stream/faults.py` fault plans: every valid input
key appears in the output at least once, no commit ever advances past a
lost output, the supervisor converges, and a fixed seed reproduces the run
bit-for-bit. The circuit breaker (explain/circuit.py) is asserted both as a
deterministic state machine (injected clock) and end-to-end: a dead
explanation backend must not throttle classification.
"""

import json
import random
import threading
import time

import pytest

from fraud_detection_tpu.explain.circuit import (BreakerOpenError,
                                                 CircuitBreakerBackend)
from fraud_detection_tpu.stream import InProcessBroker, StreamingClassifier
from fraud_detection_tpu.stream.engine import run_supervised
from fraud_detection_tpu.stream.faults import (ChaosConsumer, ChaosProducer,
                                               FaultPlan)

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module")
def pipeline():
    from fraud_detection_tpu.models.pipeline import synthetic_demo_pipeline

    return synthetic_demo_pipeline(batch_size=64, n=400, seed=3,
                                   num_features=2048,
                                   corpus_kwargs=dict(hard_fraction=0.0,
                                                      label_noise=0.0))


def _feed(broker, n, topic="in"):
    prod = broker.producer()
    for i in range(n):
        prod.produce(topic,
                     json.dumps({"text": f"chaos message number {i}",
                                 "id": i}).encode(),
                     key=str(i).encode())


def _mixed_plan(seed, max_faults=60):
    """The acceptance-criteria mix: lossy flushes, flush crashes, commit
    fences, poll errors, duplicates, corruption, (zero-cost) latency
    spikes — budgeted so the supervised run provably converges."""
    return FaultPlan(seed=seed, poll_error_rate=0.08, latency_spike_rate=0.05,
                     latency_spike_sec=0.0, duplicate_rate=0.08,
                     corrupt_rate=0.05, flush_fail_rate=0.08,
                     flush_crash_rate=0.06, commit_fence_rate=0.08,
                     max_faults=max_faults, sleep=lambda s: None)


def _run_chaos(pipeline, plan, n=150, *, dlq_topic=None, dlq_attempts=None,
               max_restarts=300, group="chaos", rowtrace=None):
    broker = InProcessBroker(num_partitions=3)
    _feed(broker, n)
    producers = []

    def make_engine():
        cons = ChaosConsumer(broker.consumer(["in"], group), plan)
        prod = ChaosProducer(broker.producer(), plan)
        producers.append(prod)
        return StreamingClassifier(pipeline, cons, prod, "out",
                                   batch_size=32, max_wait=0.01,
                                   dlq_topic=dlq_topic,
                                   dlq_attempts=dlq_attempts,
                                   rowtrace=rowtrace)

    stats = run_supervised(make_engine, max_restarts=max_restarts,
                           backoff=0.0, idle_timeout=0.2,
                           sleep=lambda s: None)
    return broker, stats, producers


def _assert_delivery_invariants(broker, n, group="chaos",
                                extra_topics=("out-dlq",)):
    """Key-set accounting for the at-least-once + fenced-commit contract."""
    delivered = {m.key for m in broker.messages("out")}
    for topic in extra_topics:
        delivered |= {m.key for m in broker.messages(topic)}
    want = {str(i).encode() for i in range(n)}
    missing = want - delivered
    assert not missing, f"lost {len(missing)} input keys: {sorted(missing)[:5]}"
    # No commit ever advances past a lost output: every input message below
    # its partition's committed watermark must have been delivered.
    committed = {(t, p): off
                 for (g, t, p), off in broker._group_offsets.items()
                 if g == group}
    for m in broker.messages("in"):
        if m.offset < committed.get((m.topic, m.partition), 0):
            assert m.key in delivered, (
                f"commit advanced past lost output: {m.key!r} "
                f"({m.topic}/{m.partition}@{m.offset})")


def test_chaos_invariants_under_seeded_plan(pipeline):
    """The acceptance-criteria scenario: a seeded plan mixing every fault
    kind; the supervised engine must deliver every valid input key at least
    once, never commit past a lost output, and converge."""
    plan = _mixed_plan(seed=42)
    broker, stats, producers = _run_chaos(pipeline, plan, n=150)
    assert plan.total_injected > 0, "the chaos never bit"
    assert stats.restarts > 0, "no fault killed an incarnation"
    assert sum(len(p.lost) for p in producers) > 0, \
        "no flush fault actually lost outputs — the lossy path went untested"
    _assert_delivery_invariants(broker, 150)


def test_chaos_bit_reproducible_for_fixed_seed(pipeline):
    """Same seed, fresh broker: the delivered output stream is identical
    byte for byte (keys AND values, in produce order). Only the schedule
    drawn before the final idle drain affects outputs, and that prefix is
    fully determined by the seed."""

    def run():
        broker, _, _ = _run_chaos(pipeline, _mixed_plan(seed=1234), n=100)
        return [(m.key, m.value) for m in broker.messages("out")]

    first, second = run(), run()
    assert first == second


@pytest.mark.slow
def test_chaos_soak_many_seeds(pipeline):
    """Soak variant: several seeds at higher fault rates and bigger budget —
    the invariants hold on every schedule, not just the pinned one."""
    for seed in (1, 7, 99, 2024):
        plan = FaultPlan(seed=seed, poll_error_rate=0.15,
                         latency_spike_rate=0.1, latency_spike_sec=0.0,
                         duplicate_rate=0.12, corrupt_rate=0.08,
                         flush_fail_rate=0.12, flush_crash_rate=0.1,
                         commit_fence_rate=0.12, max_faults=150,
                         sleep=lambda s: None)
        broker, stats, _ = _run_chaos(pipeline, plan, n=300,
                                      group=f"soak{seed}")
        assert plan.total_injected > 0
        _assert_delivery_invariants(broker, 300, group=f"soak{seed}")


def test_chaos_poll_errors_alone_are_survivable(pipeline):
    """Pure transport flakiness (the TransientBrokerError class stream/kafka
    translates to) never loses or duplicates commits — only restarts."""
    plan = FaultPlan(seed=3, poll_error_rate=0.25, max_faults=20,
                     sleep=lambda s: None)
    broker, stats, _ = _run_chaos(pipeline, plan, n=80, group="pollchaos")
    assert stats.restarts > 0
    _assert_delivery_invariants(broker, 80, group="pollchaos")


# ----------------------------------------------------------------------
# dead-letter queue
# ----------------------------------------------------------------------


def test_dlq_routes_malformed_with_schema(pipeline):
    """DLQ mode: malformed rows leave the output stream and land on the DLQ
    topic as structured reason records (source coordinates + reason + the
    offending bytes), keyed like their source for joining."""
    broker = InProcessBroker(num_partitions=2)
    prod = broker.producer()
    prod.produce("in", b"not json at all", key=b"bad1")
    prod.produce("in", json.dumps({"text": 42}).encode(), key=b"bad2")
    prod.produce("in", json.dumps({"text": "hello agent calling about "
                                           "my appointment"}).encode(),
                 key=b"ok")
    engine = StreamingClassifier(
        pipeline, broker.consumer(["in"], "dlq"), broker.producer(), "out",
        batch_size=8, max_wait=0.01, dlq_topic="out-dlq")
    stats = engine.run(max_messages=3, idle_timeout=0.2)

    assert stats.processed == 3
    assert stats.malformed == 2 and stats.dead_lettered == 2
    outs = broker.messages("out")
    assert [m.key for m in outs] == [b"ok"]       # no inline error frames
    assert json.loads(outs[0].value)["prediction"] in (0, 1)
    recs = {m.key: json.loads(m.value) for m in broker.messages("out-dlq")}
    assert set(recs) == {b"bad1", b"bad2"}
    for rec in recs.values():
        assert rec["reason"] == "malformed"
        assert set(rec["source"]) == {"topic", "partition", "offset"}
        assert rec["source"]["topic"] == "in"
        assert "error" in rec and "original" in rec
    assert recs[b"bad1"]["original"] == "not json at all"
    h = engine.health()
    assert h["dlq"]["routed"] == {"malformed": 2}
    assert h["dead_lettered"] == 2


def test_dlq_poison_rows_diverted_after_max_attempts(pipeline):
    """A row that keeps killing its batch (scorer crash) must stop burning
    supervisor restarts: after dlq_max_attempts re-deliveries it is diverted
    to the DLQ with reason max_attempts_exceeded and the stream completes.
    The attempts tracker is SHARED across incarnations — per-engine state
    would reset exactly when the poison crashed the engine."""

    class _Boom:
        def resolve(self):
            raise RuntimeError("scorer crashed on poison row")

    class PoisonPipeline:
        def __init__(self, inner):
            self.inner = inner

        def predict_json_async(self, values, field):
            return None        # pin the decoded-text slow path

        def predict_async(self, texts):
            pending = self.inner.predict_async(texts)
            # Crash at resolve time (the device wait), like a real scoring
            # fault — earlier in-flight batches have already committed.
            return _Boom() if any("POISON" in t for t in texts) else pending

        def __getattr__(self, name):
            return getattr(self.inner, name)

    broker = InProcessBroker(num_partitions=1)
    prod = broker.producer()
    for i in range(10):
        text = "POISON payload" if i == 9 else f"ordinary message {i}"
        prod.produce("in", json.dumps({"text": text}).encode(),
                     key=str(i).encode())

    shared_attempts = {}
    poisoned = PoisonPipeline(pipeline)

    def make_engine():
        return StreamingClassifier(
            poisoned, broker.consumer(["in"], "poison"), broker.producer(),
            "out", batch_size=4, max_wait=0.01, dlq_topic="out-dlq",
            dlq_max_attempts=2, dlq_attempts=shared_attempts)

    stats = run_supervised(make_engine, max_restarts=10, backoff=0.0,
                           idle_timeout=0.2, sleep=lambda s: None)
    assert stats.restarts == 2     # crashed exactly max_attempts times
    recs = {m.key: json.loads(m.value) for m in broker.messages("out-dlq")}
    assert b"9" in recs
    assert recs[b"9"]["reason"] == "max_attempts_exceeded"
    assert recs[b"9"]["attempts"] == 3
    # Every input key landed somewhere — classified, or dead-lettered with
    # the poison row's batch-mates (granularity is the batch, documented).
    delivered = {m.key for m in broker.messages("out")} | set(recs)
    assert delivered == {str(i).encode() for i in range(10)}
    out_keys = {m.key for m in broker.messages("out")}
    assert len(out_keys) >= 8      # rows outside the poison batch classified
    assert stats.dead_lettered == len(recs)


def test_dlq_off_keeps_inline_error_frames(pipeline):
    """Default (no dlq_topic): wire parity with today's behavior — the
    malformed row answers on the OUTPUT topic as an inline error frame."""
    broker = InProcessBroker(num_partitions=1)
    broker.producer().produce("in", b"junk", key=b"k")
    engine = StreamingClassifier(
        pipeline, broker.consumer(["in"], "inline"), broker.producer(),
        "out", batch_size=4, max_wait=0.01)
    stats = engine.run(max_messages=1, idle_timeout=0.2)
    assert stats.malformed == 1 and stats.dead_lettered == 0
    (out,) = broker.messages("out")
    assert json.loads(out.value)["error"] == "malformed message"
    assert broker.messages("out-dlq") == []
    assert engine.health()["dlq"] is None


def test_dlq_chaos_corruption_lands_in_dlq(pipeline):
    """Corrupted deliveries under chaos are counted, dead-lettered, and the
    delivery invariants still hold over output ∪ DLQ."""
    # High rate: a 100-message run only polls a handful of batches, so a
    # modest rate can draw zero injections and test nothing.
    plan = FaultPlan(seed=11, corrupt_rate=0.7, max_faults=12,
                     sleep=lambda s: None)
    broker, stats, _ = _run_chaos(pipeline, plan, n=100, dlq_topic="out-dlq",
                                  dlq_attempts={}, group="corrupt")
    assert plan.injected.get("corrupt", 0) > 0
    assert stats.dead_lettered > 0
    recs = [json.loads(m.value) for m in broker.messages("out-dlq")]
    assert all(r["reason"] == "malformed" for r in recs)
    assert all(r["original"].startswith("\x00chaos:") for r in recs)
    _assert_delivery_invariants(broker, 100, group="corrupt")


def test_dlq_records_carry_trace_ids_under_chaos(pipeline):
    """Key-set accounting extended to correlation ids (ISSUE 10): with
    tracing on, every DLQ record minted across a whole supervised chaos
    run carries the originating row's trace id, the id encodes the same
    source coordinates the record does, and it joins back to a recorded
    poll->terminal span chain. Span accounting stays exact (begun ==
    ended) through every injected abort path."""
    from fraud_detection_tpu.obs import RowTracer

    plan = FaultPlan(seed=11, corrupt_rate=0.5, flush_fail_rate=0.05,
                     commit_fence_rate=0.05, max_faults=20,
                     sleep=lambda s: None)
    tr = RowTracer(worker="w0", sample=1.0, seed=0, capacity=65536)
    broker, stats, _ = _run_chaos(pipeline, plan, n=100, dlq_topic="out-dlq",
                                  dlq_attempts={}, group="trace",
                                  rowtrace=tr)
    recs = [json.loads(m.value) for m in broker.messages("out-dlq")]
    assert stats.dead_lettered > 0 and recs
    for rec in recs:
        cid = rec["trace"]
        assert cid.split(":")[1:] == [str(rec["source"]["partition"]),
                                      str(rec["source"]["offset"])]
        stages = [s.stage for s in tr.chain(cid)]
        assert "dlq" in stages and "poll" in stages and "deliver" in stages
    snap = tr.snapshot()
    assert snap["spans_begun"] == snap["spans_ended"]
    assert snap["batches_traced"] == snap["batches_closed"]
    _assert_delivery_invariants(broker, 100, group="trace")


# ----------------------------------------------------------------------
# supervised backoff jitter
# ----------------------------------------------------------------------


def test_supervised_backoff_full_jitter_bounds():
    """Full jitter: every wait is uniform in [0, min(backoff * 2^(n-1),
    cap)] — bounded by the deterministic schedule, never above it, and not
    degenerate (restarting workers must not stampede in synchronized
    waves). jitter=False restores the exact deterministic ceiling."""

    def dead_engine():
        raise ConnectionError("broker down")

    def run(**kw):
        sleeps = []
        with pytest.raises(ConnectionError):
            run_supervised(dead_engine, max_restarts=6, backoff=0.5,
                           backoff_cap=4.0, sleep=sleeps.append, **kw)
        return sleeps

    ceilings = [min(0.5 * 2 ** k, 4.0) for k in range(6)]
    jittered = run(rng=random.Random(7))
    assert len(jittered) == 6
    assert all(0.0 <= s <= c for s, c in zip(jittered, ceilings))
    assert len(set(jittered)) > 1, "jitter produced a degenerate schedule"
    # reproducible with the same seeded rng
    assert run(rng=random.Random(7)) == jittered
    # deterministic ceiling without jitter
    assert run(jitter=False) == ceilings


def test_supervised_give_up_attaches_partial_stats():
    """The raise path still owes the operator progress accounting: the
    aggregated stats ride the exception (serve.py's give-up message)."""

    def dead_engine():
        raise ConnectionError("broker down")

    with pytest.raises(ConnectionError) as ei:
        run_supervised(dead_engine, max_restarts=2, backoff=0.0,
                       sleep=lambda s: None)
    stats = ei.value.supervisor_stats
    assert stats.restarts == 2 and stats.processed == 0


# ----------------------------------------------------------------------
# circuit breaker
# ----------------------------------------------------------------------


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class _FlakyBackend:
    """Scriptable backend: fails while ``dead`` is True, counts calls."""

    def __init__(self):
        self.dead = True
        self.calls = 0

    def chat(self, messages, *, temperature=1.0, max_tokens=1000):
        self.calls += 1
        if self.dead:
            raise ConnectionError("endpoint down")
        return "analysis"

    def generate(self, prompt, *, temperature=1.0, max_tokens=1000,
                 system=None):
        return self.chat([{"role": "user", "content": prompt}],
                         temperature=temperature, max_tokens=max_tokens)


def test_breaker_transitions_closed_open_half_open_closed():
    """The full cycle, driven deterministically by the injected clock."""
    clock = _FakeClock()
    inner = _FlakyBackend()
    b = CircuitBreakerBackend(inner, failure_threshold=3, probe_interval=30.0,
                              clock=clock)
    assert b.state == "closed"
    for _ in range(3):
        with pytest.raises(ConnectionError):
            b.generate("x")
    assert b.state == "open" and inner.calls == 3

    # open: fast-fail without touching the backend
    with pytest.raises(BreakerOpenError):
        b.generate("x")
    assert inner.calls == 3

    # not yet probe time
    clock.t = 29.9
    with pytest.raises(BreakerOpenError):
        b.generate("x")
    assert inner.calls == 3

    # probe window: one admitted call; failure re-opens for a full interval
    clock.t = 30.0
    assert b.state == "half_open"
    with pytest.raises(ConnectionError):
        b.generate("x")
    assert inner.calls == 4 and b.state == "open"
    clock.t = 59.9
    with pytest.raises(BreakerOpenError):
        b.generate("x")

    # recovered endpoint: the next probe closes the breaker
    clock.t = 60.0
    inner.dead = False
    assert b.generate("x") == "analysis"
    assert b.state == "closed"
    assert b.generate("x") == "analysis"
    snap = b.snapshot()
    assert snap["opens"] == 1 and snap["probes"] == 2
    assert snap["fast_fails"] == 3 and snap["consecutive_failures"] == 0


def test_breaker_success_resets_consecutive_failures():
    clock = _FakeClock()
    inner = _FlakyBackend()
    b = CircuitBreakerBackend(inner, failure_threshold=3, clock=clock)
    for _ in range(2):
        with pytest.raises(ConnectionError):
            b.generate("x")
    inner.dead = False
    b.generate("x")
    inner.dead = True
    for _ in range(2):
        with pytest.raises(ConnectionError):
            b.generate("x")
    assert b.state == "closed"    # streak broken by the success


def test_breaker_generate_batch_only_if_inner_has_it():
    """make_stream_explain_hook probes generate_batch with getattr — the
    wrapper must mirror the inner backend's capabilities exactly."""
    b = CircuitBreakerBackend(_FlakyBackend(), failure_threshold=1)
    assert getattr(b, "generate_batch", None) is None

    class Batched(_FlakyBackend):
        def generate_batch(self, prompts, **kw):
            self.calls += 1
            if self.dead:
                raise ConnectionError("down")
            return ["a"] * len(prompts)

    inner = Batched()
    bb = CircuitBreakerBackend(inner, failure_threshold=1, probe_interval=5.0,
                               clock=_FakeClock())
    with pytest.raises(ConnectionError):
        bb.generate_batch(["p"])
    with pytest.raises(BreakerOpenError):
        bb.generate_batch(["p"])
    assert inner.calls == 1


def test_breaker_dead_backend_does_not_throttle_stream(pipeline):
    """Acceptance criterion: with the explanation backend failing 100%, the
    classification stream runs within 10% of the no-hook baseline — the
    breaker opens after `threshold` real failures and every later batch
    fast-fails, while the async lane keeps decode off the hot path
    entirely. Deterministic part: the dead backend is called EXACTLY
    `threshold` times (frozen clock = no probes); timing part: elapsed
    within 10% (+ a small absolute guard for CI noise on sub-second runs)."""
    from fraud_detection_tpu.data import generate_corpus
    from fraud_detection_tpu.explain.onpod import make_stream_explain_hook

    n = 2000
    corpus = generate_corpus(n=400, seed=17, hard_fraction=0.0,
                             label_noise=0.0)
    values = [json.dumps({"text": corpus[i % len(corpus)].text}).encode()
              for i in range(n)]

    def feed_and_run(explain=False, breaker=None, hook=None):
        broker = InProcessBroker(num_partitions=3)
        prod = broker.producer()
        for i, v in enumerate(values):
            prod.produce("in", v, key=str(i).encode())
        engine = StreamingClassifier(
            pipeline, broker.consumer(["in"], "deg"), broker.producer(),
            "out", batch_size=256, max_wait=0.01,
            explain_batch_fn=hook, explain_async=explain,
            annotations_producer=broker.producer() if explain else None,
            breaker=breaker)
        t0 = time.perf_counter()
        stats = engine.run(max_messages=n, idle_timeout=0.2)
        elapsed = time.perf_counter() - t0
        engine.close_annotations(timeout=10.0)
        return engine, stats, elapsed

    # warm the jit caches, then measure the no-hook baseline
    feed_and_run()
    _, base_stats, baseline = feed_and_run()
    assert base_stats.processed == n

    clock = _FakeClock()           # frozen: the breaker never half-opens
    inner = _FlakyBackend()
    breaker = CircuitBreakerBackend(inner, failure_threshold=3,
                                    probe_interval=30.0, clock=clock)
    hook = make_stream_explain_hook(breaker)
    engine, stats, elapsed = feed_and_run(explain=True, breaker=breaker,
                                          hook=hook)
    assert stats.processed == n
    # The dead endpoint cost exactly `threshold` real calls, then went to 0.
    assert inner.calls == 3
    snap = breaker.snapshot()
    assert snap["state"] == "open" and snap["fast_fails"] > 0
    assert engine.health()["breaker"]["state"] == "open"
    # Classification throughput unaffected: within 10% of no-hook (+0.25s
    # absolute slack — at these sub-second runtimes scheduler noise can
    # exceed 10% even with zero added work).
    assert elapsed <= baseline * 1.10 + 0.25, (
        f"dead backend throttled the stream: {elapsed:.3f}s vs "
        f"{baseline:.3f}s baseline")


# ----------------------------------------------------------------------
# health reporting
# ----------------------------------------------------------------------


def test_health_snapshot_fields_and_monotonic_ages(pipeline):
    clock = _FakeClock(100.0)
    broker = InProcessBroker(num_partitions=1)
    prod = broker.producer()
    for i in range(8):
        prod.produce("in", json.dumps({"text": f"message {i}"}).encode(),
                     key=str(i).encode())
    prod.produce("in", b"garbage", key=b"bad")
    engine = StreamingClassifier(
        pipeline, broker.consumer(["in"], "health"), broker.producer(),
        "out", batch_size=4, max_wait=0.01, dlq_topic="out-dlq", clock=clock)

    h0 = engine.health()
    assert h0["last_batch_age_sec"] is None     # nothing delivered yet
    assert h0["in_flight_depth"] == 0 and h0["uptime_sec"] == 0.0
    assert not h0["running"] and not h0["stopped"]

    clock.t = 105.0
    stats = engine.run(max_messages=9, idle_timeout=0.2)
    assert stats.processed == 9
    h1 = engine.health()
    assert set(h1) == {"running", "stopped", "uptime_sec",
                       "last_batch_age_sec", "in_flight_depth",
                       "consecutive_flush_failures", "processed",
                       "malformed", "dead_lettered", "shed",
                       "rebalanced_commits", "commits_skipped",
                       "row_latency_ms", "device", "sched", "dlq",
                       "annotations", "breaker", "explain", "model",
                       "learn", "trace", "compile", "alerts"}
    assert h1["shed"] == 0 and h1["sched"] is None   # no scheduler attached
    assert h1["model"] is None          # plain pipeline: no lifecycle block
    assert h1["running"] is False
    assert h1["uptime_sec"] == 5.0
    assert h1["last_batch_age_sec"] == 0.0      # delivered at t=105
    assert h1["processed"] == 9 and h1["malformed"] == 1
    assert h1["dead_lettered"] == 1
    assert h1["dlq"]["routed"] == {"malformed": 1}
    assert h1["annotations"] is None and h1["breaker"] is None

    clock.t = 111.5                              # ages grow monotonically
    h2 = engine.health()
    assert h2["uptime_sec"] == 11.5
    assert h2["last_batch_age_sec"] == 6.5
    assert h2["last_batch_age_sec"] > h1["last_batch_age_sec"]


def test_health_reports_flush_failure_streak(pipeline):
    class FailingProducer:
        def __init__(self, inner):
            self.inner = inner

        def produce(self, *a, **k):
            self.inner.produce(*a, **k)

        def flush(self, timeout=10.0):
            return 2

    broker = InProcessBroker(num_partitions=1)
    broker.producer().produce("in", json.dumps({"text": "hi"}).encode())
    engine = StreamingClassifier(
        pipeline, broker.consumer(["in"], "ffs"),
        FailingProducer(broker.producer()), "out", batch_size=4,
        max_wait=0.01)
    engine.run(max_messages=1, idle_timeout=0.2)
    h = engine.health()
    assert h["consecutive_flush_failures"] == 1
    assert h["processed"] == 0


# Exact key set of AsyncAnnotationLane.stats() — the health() "annotations"
# block. A module-level dict literal (not inline in the assert) so the
# flightcheck health-schema lint (analysis/health.py, FC301) can cross-check
# the producer against it statically.
ANNOTATION_STATS_SCHEMA = {
    "submitted": (int,),
    "annotated": (int,),
    "dropped": (int,),
    "drop_records": (int,),
    "backend_errors": (int,),
    "queue_depth": (int,),
    "in_flight": (int,),
}


def test_health_annotation_lane_counters(pipeline):
    broker = InProcessBroker(num_partitions=1)
    _feed(broker, 20)
    engine = StreamingClassifier(
        pipeline, broker.consumer(["in"], "hal"), broker.producer(), "out",
        batch_size=8, max_wait=0.01,
        explain_batch_fn=lambda t, l, c: ["a"] * len(t),
        explain_async=True, annotations_producer=broker.producer())
    engine.run(max_messages=20, idle_timeout=0.2)
    engine.close_annotations(timeout=10.0)
    h = engine.health()
    assert h["annotations"] is not None
    assert set(h["annotations"]) == set(ANNOTATION_STATS_SCHEMA)
    for key, types in ANNOTATION_STATS_SCHEMA.items():
        assert isinstance(h["annotations"][key], types), key
    assert h["annotations"]["queue_depth"] == 0
