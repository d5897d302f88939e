"""Model lifecycle under the streaming engine: hot swap, shadow, promotion.

Acceptance contract (ISSUE 2):
  * a hot swap under a running engine drops ZERO messages and reorders none
    (key-set delivery accounting, PR-1 chaos-invariant style), post-swap
    frames score with the new model, health() reflects the new version;
  * shadow scoring never blocks the primary path (bounded queue, drop
    counters in health()), and PromotionPolicy demonstrably rejects a
    divergent candidate and promotes an equivalent one.
"""

import json
import threading
import time

import numpy as np
import pytest

from fraud_detection_tpu.registry import (HotSwapPipeline,
                                          LifecycleController,
                                          ModelRegistry, PromotionPolicy,
                                          ShadowScorer)
from fraud_detection_tpu.models.pipeline import ServingPipeline
from fraud_detection_tpu.stream import InProcessBroker, StreamingClassifier
from tests.test_registry import const_model, make_featurizer

pytestmark = pytest.mark.lifecycle

IN_TOPIC = "customer-dialogues-raw"
OUT_TOPIC = "dialogues-classified"


def feed(broker, keys, text="hello this is a perfectly ordinary dialogue"):
    producer = broker.producer()
    for k in keys:
        producer.produce(IN_TOPIC,
                         json.dumps({"text": text, "id": k}).encode(),
                         key=str(k).encode())


def make_engine(broker, pipeline, **kwargs):
    return StreamingClassifier(
        pipeline, broker.consumer([IN_TOPIC], "lifecycle-test"),
        broker.producer(), OUT_TOPIC, max_wait=0.01, **kwargs)


def wait_until(predicate, timeout=20.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# hot swap under a running engine
# ---------------------------------------------------------------------------

def test_hot_swap_mid_stream_zero_loss_no_reorder(tmp_path):
    """Stream 300 keyed messages; publish v2 mid-run and swap it in with
    watch semantics while the engine keeps consuming. Every key delivered
    exactly once, per-partition order preserved, frames after the swap
    score with the NEW model, and health() reports the new active version."""
    feat = make_featurizer()
    registry = ModelRegistry(str(tmp_path / "registry"))
    registry.publish(feat, const_model(-8.0))   # v1: everything benign
    _, v1_pipe = registry.load(1, batch_size=32)
    hot = HotSwapPipeline(v1_pipe, version=1)
    controller = LifecycleController(registry, hot, batch_size=32)

    broker = InProcessBroker(num_partitions=3)
    engine = make_engine(broker, hot, batch_size=32)
    phase1 = list(range(150))
    phase2 = list(range(150, 300))
    feed(broker, phase1)

    thread = threading.Thread(
        target=lambda: engine.run(max_messages=300, idle_timeout=20.0),
        daemon=True)
    thread.start()
    assert wait_until(lambda: engine.stats.processed >= 150), \
        "engine never finished phase 1"

    # Publish v2 (everything scam) and adopt it exactly as `--watch` does —
    # controller tick on a non-engine thread, RCU swap between batches.
    registry.publish(feat, const_model(8.0))
    events = controller.tick()
    assert [e["event"] for e in events] == ["promote"]
    assert hot.active_version == 2 and hot.swaps == 1

    feed(broker, phase2)
    thread.join(timeout=30)
    assert not thread.is_alive()
    assert engine.stats.processed == 300

    # Key-set delivery accounting (chaos-invariant style): every input key
    # delivered exactly once — a swap must drop nothing, duplicate nothing.
    outs = broker.messages(OUT_TOPIC)
    out_keys = [m.key for m in outs]
    assert len(out_keys) == 300
    assert set(out_keys) == {str(k).encode() for k in range(300)}

    # No reordering: within each partition, output key order must equal
    # input key order (keys hash to the same partition on both topics).
    for p in range(3):
        in_order = [m.key for m in broker.messages(IN_TOPIC)
                    if m.partition == p]
        out_order = [m.key for m in outs if m.partition == p]
        assert out_order == in_order

    # Post-swap frames score with the NEW model (phase-2 keys flagged 1);
    # phase-1 frames were scored by v1 (benign 0).
    by_key = {m.key: json.loads(m.value) for m in outs}
    assert all(by_key[str(k).encode()]["prediction"] == 0 for k in phase1)
    assert all(by_key[str(k).encode()]["prediction"] == 1 for k in phase2)

    health = engine.health()
    assert health["model"]["active_version"] == 2
    assert health["model"]["swaps"] == 1
    assert health["model"]["staged_version"] is None

    # Audit trail: publish, publish, promote(direct).
    events = registry.read_audit()
    assert [e["event"] for e in events] == ["publish", "publish", "promote"]
    assert events[-1]["version"] == 2 and events[-1]["previous"] == 1


def test_prewarm_runs_before_swap():
    """swap() must score a dummy batch through the candidate BEFORE
    publishing it to readers — the XLA compile happens off the hot path."""
    feat = make_featurizer()
    v1 = ServingPipeline(feat, const_model(-8.0), batch_size=16)
    v2 = ServingPipeline(feat, const_model(8.0), batch_size=16)
    calls = []
    original = v2.predict

    def spying_predict(texts):
        calls.append(len(texts))
        return original(texts)

    v2.predict = spying_predict
    hot = HotSwapPipeline(v1, version=1)
    hot.swap(v2, version=2)
    assert calls and calls[0] > 0, "candidate was not pre-warmed"
    assert hot.active_version == 2


# ---------------------------------------------------------------------------
# shadow scoring: never blocks the primary
# ---------------------------------------------------------------------------

class SlowPipeline:
    """Candidate whose scorer is artificially slowed — the overload case the
    bounded queue exists for."""

    def __init__(self, inner, delay=0.25):
        self.inner = inner
        self.delay = delay
        self.calls = 0

    def predict(self, texts):
        self.calls += 1
        time.sleep(self.delay)
        return self.inner.predict(texts)


def test_shadow_never_blocks_primary(tmp_path):
    """With a candidate ~25x slower than a batch, the primary stream must
    finish at its own rate: the shadow queue absorbs what it can, DROPS the
    rest (counted, visible in health()), and submit never blocks."""
    feat = make_featurizer()
    primary = ServingPipeline(feat, const_model(-8.0), batch_size=32)
    hot = HotSwapPipeline(primary, version=1)
    shadow = ShadowScorer(max_queue=1)
    slow = SlowPipeline(ServingPipeline(feat, const_model(-8.0),
                                        batch_size=32), delay=0.25)
    shadow.set_candidate(slow, version=2)

    broker = InProcessBroker(num_partitions=3)
    feed(broker, range(320))
    engine = make_engine(broker, hot, batch_size=32, shadow=shadow)
    t0 = time.perf_counter()
    stats = engine.run(max_messages=320, idle_timeout=5.0)
    elapsed = time.perf_counter() - t0
    try:
        assert stats.processed == 320
        # 10 batches x 0.25s candidate delay would be >= 2.5s if the
        # primary ever waited on the shadow; generous noise margin.
        assert elapsed < 2.0, f"primary path was blocked ({elapsed:.2f}s)"
        snap = engine.health()["model"]["shadow"]
        assert snap["candidate_version"] == 2
        assert snap["dropped"] > 0, "bounded queue never dropped under overload"
        assert snap["dropped"] + snap["batches"] + snap["queue_depth"] >= 1
    finally:
        shadow.close(timeout=10.0)


def test_shadow_divergence_stats_and_errors():
    """Equivalent candidate: agreement 1.0, PSI ~0. A raising candidate
    increments the error counter and never propagates."""
    feat = make_featurizer()
    primary = ServingPipeline(feat, const_model(-8.0), batch_size=16)
    shadow = ShadowScorer(max_queue=4)
    try:
        shadow.set_candidate(primary, version=2)
        texts = ["a perfectly ordinary dialogue"] * 16
        preds = primary.predict(texts)
        assert shadow.submit(texts, preds.labels, preds.probabilities,
                             raw=False)
        assert shadow.drain(10.0)
        snap = shadow.snapshot()
        assert snap["rows"] == 16 and snap["batches"] == 1
        assert snap["agreement_rate"] == 1.0
        assert snap["mean_abs_dp"] == pytest.approx(0.0, abs=1e-9)
        assert snap["psi"] == pytest.approx(0.0, abs=1e-6)
        assert snap["flag_rate_delta"] == 0.0

        class Exploding:
            def predict(self, texts):
                raise RuntimeError("candidate broken")

        shadow.set_candidate(Exploding(), version=3)
        shadow.submit(texts, preds.labels, preds.probabilities, raw=False)
        assert shadow.drain(10.0)
        assert shadow.snapshot()["errors"] == 1
    finally:
        shadow.close(timeout=10.0)


def test_shadow_raw_payload_decoding():
    """Raw mode hands the worker message BYTES; it must decode the text
    field itself (off the hot path) and skip undecodable rows."""
    feat = make_featurizer()
    primary = ServingPipeline(feat, const_model(-8.0), batch_size=16)
    shadow = ShadowScorer(max_queue=4)
    try:
        shadow.set_candidate(primary, version=2)
        texts = ["ordinary dialogue one", "ordinary dialogue two"]
        payloads = [json.dumps({"text": t}).encode() for t in texts]
        payloads.append(b"not json at all")
        preds = primary.predict(texts + ["padding row"])
        shadow.submit(payloads, preds.labels, preds.probabilities, raw=True)
        assert shadow.drain(10.0)
        snap = shadow.snapshot()
        assert snap["rows"] == 2 and snap["agreement_rate"] == 1.0
    finally:
        shadow.close(timeout=10.0)


# ---------------------------------------------------------------------------
# promotion policy
# ---------------------------------------------------------------------------

POLICY = PromotionPolicy(min_shadow_batches=2, min_shadow_rows=20,
                         max_disagreement=0.02, max_psi=0.25,
                         max_flag_rate_delta=0.10)


def _shadow_rounds(shadow, hot, n_batches=3, n_rows=16):
    texts = ["a perfectly ordinary dialogue about appointments"] * n_rows
    for _ in range(n_batches):
        preds = hot.predict(texts)
        shadow.submit(texts, preds.labels, preds.probabilities, raw=False)
    assert shadow.drain(10.0)


def test_policy_promotes_equivalent_candidate(tmp_path):
    feat = make_featurizer()
    registry = ModelRegistry(str(tmp_path / "registry"))
    registry.publish(feat, const_model(-8.0))
    _, v1 = registry.load(1, batch_size=16)
    hot = HotSwapPipeline(v1, version=1)
    shadow = ShadowScorer(max_queue=8)
    controller = LifecycleController(registry, hot, shadow=shadow,
                                     policy=POLICY, batch_size=16)
    try:
        registry.publish(feat, const_model(-8.0))   # v2 == v1 behaviorally
        events = controller.tick()
        assert [e["event"] for e in events] == ["stage"]
        assert hot.staged_version == 2 and hot.active_version == 1

        # Not enough evidence yet: the controller must WAIT, not decide.
        assert controller.tick() == []

        _shadow_rounds(shadow, hot, n_batches=3)
        events = controller.tick()
        assert [e["event"] for e in events] == ["promote"]
        assert events[0]["mode"] == "shadow"
        assert events[0]["shadow"]["agreement_rate"] == 1.0
        assert hot.active_version == 2 and hot.staged_version is None
        assert not shadow.active
    finally:
        shadow.close(timeout=10.0)


def test_policy_rejects_divergent_candidate(tmp_path):
    feat = make_featurizer()
    registry = ModelRegistry(str(tmp_path / "registry"))
    registry.publish(feat, const_model(-8.0))
    _, v1 = registry.load(1, batch_size=16)
    hot = HotSwapPipeline(v1, version=1)
    shadow = ShadowScorer(max_queue=8)
    controller = LifecycleController(registry, hot, shadow=shadow,
                                     policy=POLICY, batch_size=16)
    try:
        registry.publish(feat, const_model(8.0))    # v2 flips every label
        controller.tick()
        _shadow_rounds(shadow, hot, n_batches=3)
        events = controller.tick()
        assert [e["event"] for e in events] == ["reject"]
        reasons = " ".join(events[0]["reasons"])
        assert "disagreement" in reasons
        assert hot.active_version == 1 and hot.staged_version is None
        assert not shadow.active
        audit = [e["event"] for e in registry.read_audit()]
        assert audit == ["publish", "publish", "stage", "reject"]
    finally:
        shadow.close(timeout=10.0)


def test_policy_health_guard_defers_promotion():
    snap = {"batches": 10, "rows": 500, "agreement_rate": 1.0, "psi": 0.0,
            "flag_rate_delta": 0.0}
    sick = {"consecutive_flush_failures": 2}
    decision = POLICY.evaluate(snap, sick)
    assert decision.action == "wait" and "unhealthy" in decision.reasons[0]
    assert POLICY.evaluate(snap, {"consecutive_flush_failures": 0}).action \
        == "promote"


def test_policy_parse():
    p = PromotionPolicy.parse(
        "min_batches=3,min_rows=50,max_disagreement=0.1,max_psi=0.5,"
        "require_healthy=false")
    assert p.min_shadow_batches == 3 and p.min_shadow_rows == 50
    assert p.max_disagreement == 0.1 and p.max_psi == 0.5
    assert p.require_healthy is False
    with pytest.raises(ValueError, match="unknown policy key"):
        PromotionPolicy.parse("max_psl=0.5")
    with pytest.raises(ValueError, match="key=value"):
        PromotionPolicy.parse("min_batches")


def test_rollback_restores_prior_version(tmp_path):
    feat = make_featurizer()
    registry = ModelRegistry(str(tmp_path / "registry"))
    registry.publish(feat, const_model(-8.0))
    registry.publish(feat, const_model(8.0))
    _, v2 = registry.load(2, batch_size=16)
    hot = HotSwapPipeline(v2, version=2)
    controller = LifecycleController(registry, hot, batch_size=16)
    assert hot.predict_one("anything")[0] == 1
    controller.rollback(1)
    assert hot.active_version == 1
    assert hot.predict_one("anything")[0] == 0
    last = registry.read_audit()[-1]
    assert last["event"] == "rollback"
    assert last["version"] == 1 and last["previous"] == 2


# ---------------------------------------------------------------------------
# health() JSON contract
# ---------------------------------------------------------------------------

ENGINE_HEALTH_SCHEMA = {
    "running": (bool,),
    "stopped": (bool,),
    "uptime_sec": (int, float),
    "last_batch_age_sec": (type(None), int, float),
    "in_flight_depth": (int,),
    "consecutive_flush_failures": (int,),
    "processed": (int,),
    "malformed": (int,),
    "dead_lettered": (int,),
    "shed": (int,),
    "rebalanced_commits": (int,),
    "commits_skipped": (int,),
    "row_latency_ms": (dict,),
    "device": (dict,),
    "sched": (type(None), dict),
    "dlq": (type(None), dict),
    "annotations": (type(None), dict),
    "breaker": (type(None), dict),
    "explain": (type(None), dict),
    "model": (type(None), dict),
    "learn": (type(None), dict),
    "trace": (type(None), dict),
    "compile": (dict,),
    "alerts": (type(None), dict),
}

DEVICE_BLOCK_SCHEMA = {
    "platform": (str,),                      # utils/device.py device_stamp
    "device_kind": (str,),
    "device_count": (int,),
    "async_dispatch": (bool,),
    "dispatch_depth": (int,),
    "max_inflight": (int,),
    "lane_batches": (type(None), int),       # None: lane never ran
    "driver_waits": (type(None), int),
    "uploads": (type(None), int),            # None: pipeline w/o DeviceStats
    "upload_bytes": (type(None), int),
    "uploads_per_batch": (type(None), int, float),
    "donation_hits": (type(None), int),
    "pinned_bytes": (type(None), int),
    "model_pins": (type(None), int),
    "int8": (type(None), bool),
    "mesh_devices": (type(None), int),       # 0/None: single-device path
    "per_chip_rungs": (type(None), list),
    "featurize_path": (type(None), str),     # host | pallas | interpret
    "bytes_in_per_row": (type(None), int, float),
    "truncated_rows": (type(None), int),
}

MODEL_BLOCK_SCHEMA = {
    "active_version": (type(None), int),
    "staged_version": (type(None), int),
    "swaps": (int,),
    "last_swap_age_sec": (type(None), int, float),
    "shadow": (type(None), dict),
}

SHADOW_BLOCK_SCHEMA = {
    "candidate_version": (type(None), int),
    "batches": (int,),
    "rows": (int,),
    "disagreed": (int,),
    "window": (dict,),
    "agreement_rate": (type(None), int, float),
    "mean_abs_dp": (type(None), int, float),
    "flag_rate_primary": (type(None), int, float),
    "flag_rate_candidate": (type(None), int, float),
    "flag_rate_delta": (type(None), int, float),
    "psi": (type(None), int, float),
    "dropped": (int,),
    "errors": (int,),
    "sampled_out": (int,),
    "queue_depth": (int,),
    "sample": (int, float),
    "window_sec": (int, float),
    "score_hist_primary": (list,),
    "score_hist_candidate": (list,),
}


def _assert_schema(obj, schema, where):
    assert set(obj) == set(schema), (
        f"{where}: health() keys changed — update the schema test AND the "
        f"docs/pollers (extra: {set(obj) - set(schema)}, "
        f"missing: {set(schema) - set(obj)})")
    for key, types in schema.items():
        assert isinstance(obj[key], types), (where, key, type(obj[key]))


def test_health_json_contract_plain_pipeline():
    """Pins the exact key set + types of health() so --health-file pollers
    and dashboards can't silently break when fields are added."""
    feat = make_featurizer()
    pipe = ServingPipeline(feat, const_model(-8.0), batch_size=16)
    broker = InProcessBroker()
    feed(broker, range(16))
    engine = make_engine(broker, pipe, batch_size=16)
    engine.run(max_messages=16, idle_timeout=2.0)
    h = engine.health()
    _assert_schema(h, ENGINE_HEALTH_SCHEMA, "engine")
    _assert_schema(h["device"], DEVICE_BLOCK_SCHEMA, "device")
    assert h["model"] is None              # plain pipeline: no model block
    json.dumps(h)                          # must be JSON-serializable


def test_health_json_contract_lifecycle_blocks():
    feat = make_featurizer()
    pipe = ServingPipeline(feat, const_model(-8.0), batch_size=16)
    hot = HotSwapPipeline(pipe, version=1)
    shadow = ShadowScorer(max_queue=4)
    try:
        shadow.set_candidate(
            ServingPipeline(feat, const_model(-8.0), batch_size=16),
            version=2)
        broker = InProcessBroker()
        feed(broker, range(16))
        engine = make_engine(broker, hot, batch_size=16, shadow=shadow)
        engine.run(max_messages=16, idle_timeout=2.0)
        assert shadow.drain(10.0)
        h = engine.health()
        _assert_schema(h, ENGINE_HEALTH_SCHEMA, "engine")
        _assert_schema(h["model"], MODEL_BLOCK_SCHEMA, "model")
        _assert_schema(h["model"]["shadow"], SHADOW_BLOCK_SCHEMA, "shadow")
        assert h["model"]["active_version"] == 1
        assert h["model"]["shadow"]["candidate_version"] == 2
        assert h["model"]["shadow"]["rows"] == 16
        json.dumps(h)
    finally:
        shadow.close(timeout=10.0)


# ---------------------------------------------------------------------------
# serve CLI surface
# ---------------------------------------------------------------------------

def test_serve_registry_watch_shadow_promote(tmp_path, capsys):
    """End-to-end CLI: serve version 1 from a registry with --watch
    --shadow --promote-policy while an equivalent v2 is already published;
    the watcher stages it on its first tick, shadow stats accumulate over
    the demo stream, the policy promotes mid-run, zero messages lost."""
    from fraud_detection_tpu.app.serve import main as serve_main

    feat = make_featurizer()
    root = str(tmp_path / "registry")
    registry = ModelRegistry(root)
    registry.publish(feat, const_model(-8.0))
    registry.publish(feat, const_model(-8.0))   # the candidate to adopt

    rc = serve_main(["--registry", root, "--model-version", "1",
                     "--demo", "30000", "--batch-size", "64",
                     "--max-wait", "0.05",
                     "--watch", "--watch-interval", "0.05",
                     "--shadow", "--promote-policy",
                     "min_batches=1,min_rows=32,max_disagreement=0.02"])
    assert rc == 0
    out = capsys.readouterr().out
    stats = json.loads([l for l in out.splitlines() if l.startswith("{")][0])
    assert stats["processed"] == 30000
    lifecycle = stats["lifecycle"]
    assert [e["event"] for e in lifecycle["events"]] == ["stage", "promote"]
    assert lifecycle["active_version"] == 2 and lifecycle["swaps"] == 1
    h = stats["health"]
    assert h["model"]["active_version"] == 2
    audit = [e["event"] for e in registry.read_audit()]
    assert audit == ["publish", "publish", "stage", "promote"]


def test_serve_registry_flag_validation(tmp_path):
    from fraud_detection_tpu.app.serve import main as serve_main

    with pytest.raises(SystemExit, match="exactly one"):
        serve_main(["--demo", "10"])
    with pytest.raises(SystemExit, match="exactly one"):
        serve_main(["--model", "synthetic", "--registry", str(tmp_path),
                    "--demo", "10"])
    with pytest.raises(SystemExit, match="need --registry"):
        serve_main(["--model", "synthetic", "--demo", "10", "--watch"])
    with pytest.raises(SystemExit, match="needs --watch"):
        serve_main(["--registry", str(tmp_path), "--demo", "10", "--shadow"])
    with pytest.raises(SystemExit, match="needs --shadow"):
        serve_main(["--registry", str(tmp_path), "--demo", "10", "--watch",
                    "--promote-policy", "min_batches=1"])
    with pytest.raises(SystemExit, match="bad --promote-policy"):
        serve_main(["--registry", str(tmp_path), "--demo", "10", "--watch",
                    "--shadow", "--promote-policy", "bogus_key=1"])
    with pytest.raises(SystemExit, match="no published versions"):
        serve_main(["--registry", str(tmp_path / "empty"), "--demo", "10"])


# ---------------------------------------------------------------------------
# shadow comparison report
# ---------------------------------------------------------------------------

def test_plot_shadow_comparison(tmp_path):
    from fraud_detection_tpu.eval.report import plot_shadow_comparison

    feat = make_featurizer()
    primary = ServingPipeline(feat, const_model(-8.0), batch_size=16)
    shadow = ShadowScorer(max_queue=4)
    try:
        shadow.set_candidate(
            ServingPipeline(feat, const_model(2.0), batch_size=16), version=2)
        texts = ["an ordinary dialogue"] * 16
        preds = primary.predict(texts)
        shadow.submit(texts, preds.labels, preds.probabilities, raw=False)
        assert shadow.drain(10.0)
        snap = shadow.snapshot()
        out = plot_shadow_comparison(snap, str(tmp_path / "shadow.png"))
        assert out is not None and (tmp_path / "shadow.png").stat().st_size > 0
        assert plot_shadow_comparison({"rows": 0}, "unused.png") is None
    finally:
        shadow.close(timeout=10.0)


# ---------------------------------------------------------------------------
# shadow soak (excluded from tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_shadow_soak_converges_and_promotes(tmp_path):
    """Long soak: watcher thread + engine streaming thousands of messages;
    shadow stats converge over many batches, the policy promotes, the swap
    lands with zero loss."""
    feat = make_featurizer()
    registry = ModelRegistry(str(tmp_path / "registry"))
    registry.publish(feat, const_model(-8.0))
    _, v1 = registry.load(1, batch_size=64)
    hot = HotSwapPipeline(v1, version=1)
    shadow = ShadowScorer(max_queue=16)
    controller = LifecycleController(
        registry, hot, shadow=shadow,
        policy=PromotionPolicy(min_shadow_batches=10, min_shadow_rows=500,
                               max_disagreement=0.02, max_psi=0.25),
        batch_size=64)
    thread, stop = controller.run_in_thread(interval=0.05)
    broker = InProcessBroker(num_partitions=3)
    engine = make_engine(broker, hot, batch_size=64, shadow=shadow)
    n = 20000
    try:
        feed(broker, range(n // 2))
        runner = threading.Thread(
            target=lambda: engine.run(max_messages=n, idle_timeout=30.0),
            daemon=True)
        runner.start()
        assert wait_until(lambda: engine.stats.processed >= n // 4)
        registry.publish(feat, const_model(-8.0))   # equivalent candidate
        feed(broker, range(n // 2, n))
        assert wait_until(lambda: hot.active_version == 2, timeout=60.0), \
            f"never promoted: {shadow.snapshot()}"
        runner.join(timeout=60)
        assert not runner.is_alive()
    finally:
        stop.set()
        thread.join(timeout=5)
        shadow.close(timeout=10.0)
    assert engine.stats.processed == n
    outs = broker.messages(OUT_TOPIC)
    assert len(outs) == n
    assert {m.key for m in outs} == {str(k).encode() for k in range(n)}
    audit = [e["event"] for e in registry.read_audit()]
    assert audit == ["publish", "publish", "stage", "promote"]
    promote = registry.read_audit()[-1]
    assert promote["shadow"]["rows"] >= 500
    assert promote["shadow"]["agreement_rate"] == 1.0
