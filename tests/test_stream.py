"""Streaming engine tests against the in-process broker (SURVEY §4 strategy #3)."""

import json

import numpy as np
import pytest

from fraud_detection_tpu.stream import InProcessBroker, StreamingClassifier


@pytest.fixture(scope="module")
def pipeline():
    from fraud_detection_tpu.models.pipeline import synthetic_demo_pipeline

    return synthetic_demo_pipeline(batch_size=64, n=400, seed=3, num_features=2048,
                                   corpus_kwargs=dict(hard_fraction=0.0,
                                                      label_noise=0.0))


def _feed(broker, dialogues, topic="customer-dialogues-raw"):
    producer = broker.producer()
    for i, (text, label) in enumerate(dialogues):
        producer.produce(topic, json.dumps({"text": text, "id": i}).encode(),
                         key=str(i).encode())


def test_end_to_end_stream_classification(pipeline):
    from fraud_detection_tpu.data import generate_corpus

    # Separable corpus: this test verifies transport plumbing, so the model's
    # accuracy vs ground truth must not be capped by corpus label noise.
    corpus = generate_corpus(n=120, seed=77, hard_fraction=0.0, label_noise=0.0)
    broker = InProcessBroker(num_partitions=3)
    _feed(broker, [(d.text, d.label) for d in corpus])

    consumer = broker.consumer(["customer-dialogues-raw"], "grp")
    engine = StreamingClassifier(
        pipeline, consumer, broker.producer(), "dialogues-classified",
        batch_size=32, max_wait=0.01)
    stats = engine.run(max_messages=120, idle_timeout=0.2)

    assert stats.processed == 120
    assert stats.malformed == 0
    out = broker.messages("dialogues-classified")
    assert len(out) == 120
    by_id = {}
    for m in out:
        payload = json.loads(m.value)
        assert payload["prediction"] in (0, 1)
        assert payload["label"] in ("Potential Scam", "Normal Conversation")
        assert 0.0 <= payload["confidence"] <= 1.0
        by_id[int(m.key)] = payload["prediction"]
    truth = {i: d.label for i, d in enumerate(corpus)}
    acc = np.mean([by_id[i] == truth[i] for i in truth])
    assert acc > 0.97, acc


def test_engine_over_mesh_backed_pipeline(pipeline):
    """The streaming engine with its scoring leg data-parallel over an
    8-device mesh (ServingPipeline(mesh=...)): same transport, same frames,
    per-message predictions identical to the single-device pipeline —
    round-4 verdict item 2(b), the production serving shape."""
    from fraud_detection_tpu.models.pipeline import ServingPipeline
    from fraud_detection_tpu.parallel import make_mesh
    from fraud_detection_tpu.data import generate_corpus

    mesh = make_mesh(n_devices=8)
    pipe_mesh = ServingPipeline(pipeline.featurizer, pipeline.model,
                                batch_size=32, mesh=mesh)
    corpus = generate_corpus(n=90, seed=5, hard_fraction=0.0, label_noise=0.0)
    broker = InProcessBroker(num_partitions=3)
    _feed(broker, [(d.text, d.label) for d in corpus])
    engine = StreamingClassifier(
        pipe_mesh, broker.consumer(["customer-dialogues-raw"], "grp-mesh"),
        broker.producer(), "dialogues-classified", batch_size=32,
        max_wait=0.01)
    stats = engine.run(max_messages=90, idle_timeout=0.5)
    assert stats.processed == 90 and stats.malformed == 0

    want = pipeline.predict([d.text for d in corpus])
    got = {int(m.key): json.loads(m.value)
           for m in broker.messages("dialogues-classified")}
    assert len(got) == 90
    for i, (lbl, p) in enumerate(zip(want.labels, want.probabilities)):
        conf = float(p) if lbl == 1 else 1.0 - float(p)
        assert got[i]["prediction"] == int(lbl)
        assert abs(got[i]["confidence"] - conf) < 1e-4


def test_malformed_messages_survive(pipeline):
    broker = InProcessBroker()
    producer = broker.producer()
    producer.produce("customer-dialogues-raw", b"not json at all")
    producer.produce("customer-dialogues-raw", json.dumps({"wrong": "field"}).encode())
    producer.produce("customer-dialogues-raw",
                     json.dumps({"text": "Agent: hello, confirming your visit."}).encode())
    consumer = broker.consumer(["customer-dialogues-raw"], "grp")
    engine = StreamingClassifier(
        pipeline, consumer, broker.producer(), "dialogues-classified",
        batch_size=16, max_wait=0.01)
    stats = engine.run(max_messages=3, idle_timeout=0.2)
    assert stats.processed == 3 and stats.malformed == 2
    out = broker.messages("dialogues-classified")
    errors = [m for m in out if json.loads(m.value).get("error")]
    assert len(errors) == 2


def test_offsets_commit_and_restart_resumes(pipeline):
    broker = InProcessBroker()
    _feed(broker, [("Agent: confirming your appointment tomorrow.", 0)] * 10)
    consumer = broker.consumer(["customer-dialogues-raw"], "grp")
    engine = StreamingClassifier(
        pipeline, consumer, broker.producer(), "out", batch_size=4, max_wait=0.01)
    engine.run(max_messages=10, idle_timeout=0.2)
    # Restart from committed offsets: nothing left to consume (unlike the
    # reference, which re-reads from earliest on every restart — Q2).
    consumer.seek_to_committed()
    assert consumer.poll(0.05) is None
    # New messages after restart are picked up.
    _feed(broker, [("Agent: your order is ready for pickup.", 0)])
    assert consumer.poll(0.1) is not None


def test_explain_hook_attached(pipeline):
    broker = InProcessBroker()
    _feed(broker, [("Agent: urgent winner congratulations verify now!", 1)])
    consumer = broker.consumer(["customer-dialogues-raw"], "grp")
    engine = StreamingClassifier(
        pipeline, consumer, broker.producer(), "out", batch_size=4, max_wait=0.01,
        explain_fn=lambda text, label, conf: f"label={label} conf~{conf:.1f}")
    engine.run(max_messages=1, idle_timeout=0.2)
    payload = json.loads(broker.messages("out")[0].value)
    assert payload["analysis"].startswith("label=")


def test_throughput_counter_sane(pipeline):
    from fraud_detection_tpu.data import generate_corpus

    corpus = generate_corpus(n=200, seed=8)
    broker = InProcessBroker()
    _feed(broker, [(d.text, d.label) for d in corpus])
    consumer = broker.consumer(["customer-dialogues-raw"], "grp")
    engine = StreamingClassifier(
        pipeline, consumer, broker.producer(), "out", batch_size=128, max_wait=0.01)
    stats = engine.run(max_messages=200, idle_timeout=0.2)
    d = stats.as_dict()
    assert d["msgs_per_sec"] > 0 and d["batches"] >= 2
    assert d["mean_batch_latency_sec"] <= d["max_batch_latency_sec"]


def test_engine_stops_when_producer_cannot_deliver(pipeline):
    """A failed flush must halt the engine with offsets uncommitted — continuing
    would commit past the lost batch on the next clean flush."""
    from fraud_detection_tpu.data import generate_corpus

    corpus = generate_corpus(n=40, seed=5)
    broker = InProcessBroker()
    _feed(broker, [(d.text, d.label) for d in corpus])

    class FailingProducer:
        def __init__(self, inner):
            self.inner = inner

        def produce(self, *a, **k):
            self.inner.produce(*a, **k)

        def flush(self, timeout=10.0):
            return 3  # pretend 3 messages failed delivery

    consumer = broker.consumer(["customer-dialogues-raw"], "failflush")
    engine = StreamingClassifier(
        pipeline, consumer, FailingProducer(broker.producer()), "out",
        batch_size=8, max_wait=0.01)
    stats = engine.run(max_messages=40, idle_timeout=0.5)
    assert stats.commits_skipped == 1  # stopped after the first failed batch
    assert stats.batches == 0          # a lost batch is NOT counted as done
    assert stats.processed == 0        # (restart re-drives it: at-least-once)
    # no offsets durably committed (owned partitions seed at the group
    # watermark, 0 here — zero means nothing committed)
    assert all(off == 0 for off in consumer.committed_offsets().values())


def test_process_batch_refuses_after_failed_flush(pipeline):
    """flightcheck FC403 regression (PR 6 true positive): process_batch
    must not score-and-commit a LATER batch after a failed flush left a
    batch's offsets uncommitted — its commit would orphan the lost
    outputs. run() stays the incarnation boundary that resets the flag."""
    from fraud_detection_tpu.data import generate_corpus

    corpus = generate_corpus(n=16, seed=7)
    broker = InProcessBroker()
    _feed(broker, [(d.text, d.label) for d in corpus])

    class FlakyProducer:
        def __init__(self, inner):
            self.inner = inner
            self.fail_next = True

        def produce(self, *a, **k):
            self.inner.produce(*a, **k)

        def flush(self, timeout=10.0):
            if self.fail_next:
                self.fail_next = False
                return 2
            return 0

    consumer = broker.consumer(["customer-dialogues-raw"], "pbflag")
    engine = StreamingClassifier(
        pipeline, consumer, FlakyProducer(broker.producer()), "out",
        batch_size=8, max_wait=0.01)
    msgs = consumer.poll_batch(8, 0.2)
    assert msgs
    assert engine.process_batch(msgs) == 0          # flush fails: nothing done
    assert engine.stats.commits_skipped == 1
    # the flag latches: the next process_batch would commit past the lost
    # batch (the producer is healthy again) — it must refuse instead.
    with pytest.raises(RuntimeError, match="flush failed"):
        engine.process_batch(msgs)
    assert all(off == 0 for off in consumer.committed_offsets().values())
    # run() declares a fresh incarnation (resets the flag) and re-drives.
    stats = engine.run(max_messages=8, idle_timeout=0.3)
    assert stats.commits_skipped == 1  # cumulative; no NEW skip this run


def test_group_offsets_survive_consumer_restart(pipeline):
    """A NEW consumer in the same group resumes from the group's committed
    offsets (broker-durable, like Kafka's __consumer_offsets)."""
    broker = InProcessBroker(num_partitions=2)
    prod = broker.producer()
    for i in range(20):
        prod.produce("t", json.dumps({"text": f"hello message {i}"}).encode(),
                     key=str(i).encode())
    c1 = broker.consumer(["t"], "g1")
    engine = StreamingClassifier(pipeline, c1, broker.producer(), "out", batch_size=8)
    engine.run(max_messages=20, idle_timeout=0.2)
    # Fresh consumer, same group: nothing left.
    c2 = broker.consumer(["t"], "g1")
    assert c2.poll_batch(20, 0.05) == []
    # Fresh group: re-reads from earliest.
    c3 = broker.consumer(["t"], "g2")
    assert len(c3.poll_batch(20, 0.05)) == 20


def test_run_supervised_restarts_after_crash(pipeline):
    """The supervisor rebuilds the engine after a crash and finishes the
    stream without dropping or duplicating committed work."""
    from fraud_detection_tpu.stream.engine import run_supervised

    broker = InProcessBroker(num_partitions=1)
    prod = broker.producer()
    for i in range(40):
        prod.produce("t", json.dumps({"text": f"message number {i}"}).encode())

    calls = {"n": 0}

    class CrashOnceProducer:
        def __init__(self, inner):
            self.inner = inner

        def produce(self, topic, value, key=None):
            self.inner.produce(topic, value, key)

        def flush(self, timeout: float = 10.0) -> int:
            calls["n"] += 1
            if calls["n"] == 2:
                raise ConnectionError("broker went away")
            return self.inner.flush(timeout)

    def make_engine():
        return StreamingClassifier(
            pipeline, broker.consumer(["t"], "sup"),
            CrashOnceProducer(broker.producer()), "out", batch_size=8)

    stats = run_supervised(make_engine, max_restarts=3, backoff=0.0,
                           max_messages=40, idle_timeout=0.2, sleep=lambda s: None)
    assert stats.restarts == 1
    assert stats.processed >= 40  # crashed batch replays: at-least-once
    outs = broker.messages("out")
    assert len(outs) >= 40
    # every input eventually classified
    import json as j
    seen = {j.loads(m.value)["original_text"] for m in outs}
    assert len(seen) == 40


def test_run_supervised_gives_up(pipeline):
    from fraud_detection_tpu.stream.engine import run_supervised

    broker = InProcessBroker(num_partitions=1)
    prod = broker.producer()
    for i in range(8):
        prod.produce("t", json.dumps({"text": "x"}).encode())

    class AlwaysFailProducer:
        def produce(self, topic, value, key=None):
            pass

        def flush(self, timeout: float = 10.0) -> int:
            return 3  # never drains

    def make_engine():
        return StreamingClassifier(
            pipeline, broker.consumer(["t"], "fail"),
            AlwaysFailProducer(), "out", batch_size=8)

    with pytest.raises(RuntimeError, match="flush kept failing"):
        run_supervised(make_engine, max_restarts=2, backoff=0.0,
                       max_messages=8, idle_timeout=0.2, sleep=lambda s: None)


def test_latency_percentiles_recorded(pipeline):
    broker = InProcessBroker(num_partitions=1)
    prod = broker.producer()
    for i in range(30):
        prod.produce("t", json.dumps({"text": f"dialogue {i}"}).encode())
    cons = broker.consumer(["t"], "lat")
    engine = StreamingClassifier(pipeline, cons, broker.producer(), "out", batch_size=10)
    stats = engine.run(max_messages=30, idle_timeout=0.2)
    assert len(stats.latencies) == stats.batches > 0
    p50, p99 = stats.latency_percentile(50), stats.latency_percentile(99)
    assert 0 < p50 <= p99 <= stats.batch_latency_max
    assert set(stats.as_dict()) >= {"p50_batch_latency_sec", "p99_batch_latency_sec"}


def test_run_supervised_closes_clients(pipeline):
    """Every incarnation's consumer must leave the group promptly (a zombie
    would hold its partitions until session timeout)."""
    from fraud_detection_tpu.stream.engine import run_supervised

    broker = InProcessBroker(num_partitions=1)
    prod = broker.producer()
    for i in range(8):
        prod.produce("t", json.dumps({"text": "hello there"}).encode())
    consumers = []

    def make_engine():
        c = broker.consumer(["t"], "closing")
        consumers.append(c)
        return StreamingClassifier(pipeline, c, broker.producer(), "out", batch_size=8)

    run_supervised(make_engine, max_messages=8, idle_timeout=0.2, sleep=lambda s: None)
    assert consumers and all(c._closed for c in consumers)


def _run_engine(pipeline, values, keys=None, force_slow=False, **kw):
    """Feed raw message bytes through a fresh engine; return (stats, outputs)."""
    broker = InProcessBroker(num_partitions=3)
    producer = broker.producer()
    for i, v in enumerate(values):
        key = keys[i] if keys else str(i).encode()
        producer.produce("in", v, key=key)
    consumer = broker.consumer(["in"], "grp")
    engine = StreamingClassifier(pipeline, consumer, broker.producer(), "out",
                                 batch_size=32, max_wait=0.01, **kw)
    if force_slow:
        engine._json_fast = False  # pin the json.loads path for comparison
    stats = engine.run(max_messages=len(values), idle_timeout=0.3)
    outs = {m.key: json.loads(m.value) for m in broker.messages("out")}
    return engine, stats, outs


def test_raw_json_fast_path_matches_slow_path(pipeline):
    """The native raw-JSON path and the Python json.loads path must emit
    semantically identical output messages (parsed equality — byte equality
    is not required: raw mode splices the input's own string literal)."""
    from fraud_detection_tpu.data import generate_corpus

    corpus = generate_corpus(n=60, seed=21)
    values = [json.dumps({"text": d.text, "id": i}).encode()
              for i, d in enumerate(corpus)]
    values[7] = b'not json'
    values[23] = b'{"text": 42}'
    values[41] = '{"text": "unicode café ☃ ok"}'.encode()

    fast_engine, fast_stats, fast = _run_engine(pipeline, values)
    if fast_engine._json_fast is not True:
        pytest.skip("native JSON path unavailable in this environment")

    slow_engine, slow_stats, slow = _run_engine(pipeline, values, force_slow=True)
    assert slow_engine._json_fast is False

    assert fast_stats.processed == slow_stats.processed == 60
    assert fast_stats.malformed == slow_stats.malformed == 2
    assert fast.keys() == slow.keys()
    for k in fast:
        f, s = fast[k], slow[k]
        assert f.get("prediction") == s.get("prediction"), k
        assert f.get("original_text") == s.get("original_text"), k
        if f.get("prediction") is not None:
            assert abs(f["confidence"] - s["confidence"]) < 1e-6, k


@pytest.mark.parametrize("model", ["dt", "xgb"])
def test_raw_json_fast_path_matches_slow_path_trees(model):
    """Tree ensembles ride the raw-JSON path too (native encode -> on-device
    scatter to dense -> traversal): outputs must match the json.loads slow
    path exactly, same as the LR pipeline."""
    from fraud_detection_tpu.data import generate_corpus
    from fraud_detection_tpu.models.pipeline import synthetic_demo_pipeline

    pipe = synthetic_demo_pipeline(batch_size=32, n=200, seed=11,
                                   num_features=2048, model=model)
    corpus = generate_corpus(n=40, seed=31)
    values = [json.dumps({"text": d.text, "id": i}).encode()
              for i, d in enumerate(corpus)]
    values[5] = b'broken'

    fast_engine, fast_stats, fast = _run_engine(pipe, values)
    if fast_engine._json_fast is not True:
        pytest.skip("native JSON path unavailable in this environment")
    slow_engine, slow_stats, slow = _run_engine(pipe, values, force_slow=True)

    assert fast_stats.processed == slow_stats.processed == 40
    assert fast_stats.malformed == slow_stats.malformed == 1
    assert fast.keys() == slow.keys()
    for k in fast:
        f, s = fast[k], slow[k]
        assert f.get("prediction") == s.get("prediction"), k
        assert f.get("original_text") == s.get("original_text"), k
        if f.get("prediction") is not None:
            assert abs(f["confidence"] - s["confidence"]) < 1e-6, k


def test_raw_json_fast_path_strict_rejection_falls_back(pipeline):
    """A message the native scanner rejects but json.loads accepts (escaped
    key) must still be scored — the engine falls back to the slow path for
    that batch instead of mis-routing it as malformed."""
    values = [
        json.dumps({"text": "hello there agent calling about your account"}).encode(),
        b'{"te\\u0078t": "prize claim urgent gift card payment now"}',
    ]
    engine, stats, outs = _run_engine(pipeline, values)
    assert stats.processed == 2
    assert stats.malformed == 0
    assert all(o["prediction"] in (0, 1) for o in outs.values())


def test_raw_json_output_preserves_exotic_text(pipeline):
    """Raw-literal splicing must round-trip escapes and unicode exactly."""
    exotic = 'tab\there "quoted" back\\slash café \U0001f600 end'
    values = [json.dumps({"text": exotic}).encode()]
    _, stats, outs = _run_engine(pipeline, values)
    assert stats.processed == 1
    (out,) = outs.values()
    assert out["original_text"] == exotic


def test_produce_batch_and_poll_batch_equivalent():
    """Broker batch ops must preserve per-partition FIFO + offset semantics."""
    broker = InProcessBroker(num_partitions=3)
    p = broker.producer()
    p.produce_batch("t", [(f"v{i}".encode(), f"k{i % 5}".encode())
                          for i in range(40)])
    assert broker.topic_size("t") == 40
    c = broker.consumer(["t"], "g")
    got = c.poll_batch(100, 0.1)
    assert len(got) == 40
    # per-partition offsets are contiguous from 0
    seen = {}
    for m in got:
        seen.setdefault(m.partition, []).append(m.offset)
    for offs in seen.values():
        assert offs == list(range(len(offs)))
    # same key -> same partition
    by_key = {}
    for m in got:
        by_key.setdefault(m.key, set()).add(m.partition)
    assert all(len(parts) == 1 for parts in by_key.values())


def test_messages_listing_is_produce_order():
    """broker.messages() must report produce order even for a batch append,
    whose messages share one timestamp (keyless round-robin spreads them
    across partitions, so timestamp+partition sorting would interleave)."""
    broker = InProcessBroker(num_partitions=3)
    p = broker.producer()
    p.produce_batch("t", [(f"b{i}".encode(), None) for i in range(9)])
    broker.append("t", b"single")
    assert [m.value for m in broker.messages("t")] == \
        [f"b{i}".encode() for i in range(9)] + [b"single"]


def _run_engine_raw(pipeline, values, disable_native_frames=False):
    """Like _run_engine but returns raw output BYTES (byte-parity checks)."""
    broker = InProcessBroker(num_partitions=3)
    producer = broker.producer()
    for i, v in enumerate(values):
        producer.produce("in", v, key=str(i).encode())
    consumer = broker.consumer(["in"], "grp")
    engine = StreamingClassifier(pipeline, consumer, broker.producer(), "out",
                                 batch_size=32, max_wait=0.01)
    if disable_native_frames:
        engine._frames_ok = False
    stats = engine.run(max_messages=len(values), idle_timeout=0.3)
    return engine, stats, {m.key: m.value for m in broker.messages("out")}


def test_native_frame_assembly_byte_parity(pipeline):
    """C++ ftok_build_frames must be byte-identical to the Python template
    path (%d / %.6f / literal splice) on every message, including routing
    malformed rows to the Python fallback frame."""
    from fraud_detection_tpu.featurize import native as native_mod

    if not native_mod.frames_available():
        pytest.skip("native frame assembly unavailable")
    from fraud_detection_tpu.data import generate_corpus

    corpus = generate_corpus(n=50, seed=77)
    values = [json.dumps({"text": d.text, "id": i}).encode()
              for i, d in enumerate(corpus)]
    values[3] = b"nope"          # malformed -> fallback frame
    values[11] = b'{"text": 9}'  # non-string field -> fallback frame

    eng_c, st_c, out_c = _run_engine_raw(pipeline, values)
    if eng_c._json_fast is not True:
        pytest.skip("native JSON path unavailable in this environment")
    assert eng_c._frames_ok is True
    eng_p, st_p, out_p = _run_engine_raw(pipeline, values,
                                         disable_native_frames=True)
    assert st_c.processed == st_p.processed == 50
    assert st_c.malformed == st_p.malformed == 2
    assert out_c == out_p


def test_build_frames_float_formatting_parity():
    """snprintf %.6f must round exactly like Python's %-formatting on
    adversarial doubles (halfway cases, extremes) — a one-ULP divergence
    here would silently break output byte parity."""
    from fraud_detection_tpu.featurize import native as native_mod

    if not native_mod.frames_available():
        pytest.skip("native frame assembly unavailable")
    import random

    from fraud_detection_tpu.stream.engine import _LABEL_JSON_B, _OUT_TEMPLATE_B

    rng = random.Random(5)
    n = 500
    confs = np.array([rng.random() for _ in range(n)], np.float64)
    confs[:8] = [0.0, 1.0, 0.5, 0.9999995, 0.1234565,
                 0.1234575, 1e-7, 0.49999999999]
    labels = np.array([rng.randint(0, 1) for _ in range(n)], np.int32)
    texts = [('"t%d"' % i).encode() for i in range(n)]
    import ctypes

    arr = (ctypes.c_char_p * n)(*texts)
    span_start = np.zeros(n, np.int32)
    span_len = np.fromiter((len(t) for t in texts), np.int32, n)
    blob, ends = native_mod.build_frames(
        arr, span_start, span_len, labels, confs,
        [_LABEL_JSON_B[0], _LABEL_JSON_B[1]])
    start = 0
    for i in range(n):
        want = _OUT_TEMPLATE_B % (labels[i], _LABEL_JSON_B[int(labels[i])],
                                  confs[i], texts[i])
        got = blob[start:ends[i]]
        start = int(ends[i])
        assert got == want, (i, got, want)


def test_run_supervised_chaos_randomized(pipeline):
    """Randomized fault injection (SURVEY.md §5 — the reference has none):
    flush crashes, undrained flushes, and poll crashes fire at random points
    across many engine incarnations. The at-least-once contract must hold —
    every input classified at least once, losses never, duplicates allowed —
    and the supervisor must actually have exercised restarts."""
    import random as _random

    from fraud_detection_tpu.stream.engine import run_supervised

    rng = _random.Random(1234)
    broker = InProcessBroker(num_partitions=3)
    prod = broker.producer()
    n = 120
    for i in range(n):
        prod.produce("t", json.dumps(
            {"text": f"chaotic message number {i}", "id": i}).encode(),
            key=str(i).encode())

    class ChaoticProducer:
        def __init__(self, inner):
            self.inner = inner

        def produce(self, topic, value, key=None):
            self.inner.produce(topic, value, key)

        def produce_batch(self, topic, items):
            self.inner.produce_batch(topic, items)

        def flush(self, timeout: float = 10.0) -> int:
            r = rng.random()
            if r < 0.15:
                raise ConnectionError("chaos: flush crashed")
            if r < 0.30:
                return 1  # undrained: triggers the abort-don't-commit path
            return self.inner.flush(timeout)

    class ChaoticConsumer:
        def __init__(self, inner):
            self.inner = inner

        def poll_batch(self, max_messages, timeout):
            if rng.random() < 0.10:
                raise TimeoutError("chaos: poll crashed")
            return self.inner.poll_batch(max_messages, timeout)

        def __getattr__(self, name):
            return getattr(self.inner, name)

    def make_engine():
        return StreamingClassifier(
            pipeline, ChaoticConsumer(broker.consumer(["t"], "chaos")),
            ChaoticProducer(broker.producer()), "out", batch_size=16)

    stats = run_supervised(make_engine, max_restarts=200, backoff=0.0,
                           max_messages=n, idle_timeout=0.2,
                           sleep=lambda s: None)
    outs = broker.messages("out")
    seen = {json.loads(m.value)["original_text"] for m in outs}
    assert len(seen) == n, f"lost {n - len(seen)} messages"
    assert stats.restarts > 0  # the chaos actually bit


def test_explain_batch_hook(pipeline):
    """The batch explanation hook runs ONCE per micro-batch over the valid
    rows (the on-pod LLM amortization seam) and its analyses land on the
    right messages; malformed rows are excluded from the hook's input."""
    from fraud_detection_tpu.data import generate_corpus

    corpus = generate_corpus(n=20, seed=13)
    broker = InProcessBroker(num_partitions=1)
    _feed(broker, [(d.text, d.label) for d in corpus])
    broker.producer().produce("customer-dialogues-raw", b"junk", key=b"bad")

    calls = []

    def explain_batch(texts, labels, confs):
        calls.append(len(texts))
        assert len(texts) == len(labels) == len(confs)
        return [f"batch analysis label={l}" for l in labels]

    consumer = broker.consumer(["customer-dialogues-raw"], "grp")
    engine = StreamingClassifier(
        pipeline, consumer, broker.producer(), "out", batch_size=32,
        max_wait=0.01, explain_batch_fn=explain_batch)
    stats = engine.run(max_messages=21, idle_timeout=0.2)
    assert stats.processed == 21 and stats.malformed == 1
    assert sum(calls) == 20 and len(calls) <= 2  # once per batch, valid rows only
    outs = [json.loads(m.value) for m in broker.messages("out")]
    analysed = [o for o in outs if "analysis" in o]
    assert len(analysed) == 20
    for o in analysed:
        assert o["analysis"] == f"batch analysis label={o['prediction']}"


def test_stop_latches_before_run(pipeline):
    """stop() on an engine whose run() hasn't started must hold: run()
    returns immediately without consuming (round-3 review: run()'s entry
    used to reset the flag, so a coordinator stopping a just-built engine —
    serve.py's multi-worker Ctrl-C — raced and lost)."""
    broker = InProcessBroker(num_partitions=1)
    prod = broker.producer()
    for i in range(10):
        prod.produce("t", json.dumps({"text": "hello there"}).encode())
    consumer = broker.consumer(["t"], "latch")
    engine = StreamingClassifier(pipeline, consumer, broker.producer(), "out",
                                 batch_size=4, max_wait=0.01)
    engine.stop()
    stats = engine.run(max_messages=10, idle_timeout=0.2)
    assert stats.processed == 0
    assert broker.messages("out") == []
    # the messages are still there for a live engine
    engine2 = StreamingClassifier(pipeline, broker.consumer(["t"], "latch2"),
                                  broker.producer(), "out", batch_size=4,
                                  max_wait=0.01)
    assert engine2.run(max_messages=10, idle_timeout=0.2).processed == 10
