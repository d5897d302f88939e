"""Set-up and compilation on the span chain (docs/observability.md):

* one ``compile`` span per executable JAX obtains, with what it cost and
  whether the persistent cache served it, and the process-wide counters
  beside it;
* the boot log: bounded, adopted by a tracer built late with the spans'
  own ``start``s, every later span to every live tracer;
* ``begun == ended`` with those spans counted, the per-stage sketches fed;
* the ``compile`` block of ``health()``, exported by the collector rule.
"""

import gc
import json

import numpy as np
import pytest

from fraud_detection_tpu.obs import trace
from fraud_detection_tpu.obs.metrics import (MetricsRegistry, metric_name,
                                             parse_prometheus)
from fraud_detection_tpu.obs.trace import RowTracer, Span
from fraud_detection_tpu.stream import InProcessBroker, StreamingClassifier

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def boot(boot_log):
    """What the process obtained for earlier tests, and whether an engine
    of theirs has polled, is not this test's (tests/conftest.py)."""
    return boot_log


def _fresh_jit(name):
    import jax

    def fn(x):
        return x * 3 + 1

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _detail(span):
    return dict(kv.split("=", 1) for kv in span.detail.split())


def test_a_fresh_shape_is_one_compile_span_and_one_request(boot):
    f = _fresh_jit("obs_compile_probe")
    x = np.arange(13, dtype=np.float32)      # a host array: no eager op
    f(x)
    f(x)                                     # in memory: no second request
    spans = [s for s in boot.spans if s.stage == trace.STAGE_COMPILE]
    assert len(spans) == 1
    (span,) = spans
    d = _detail(span)
    assert d["fn"] == "jit(obs_compile_probe)" and d["hit"] == "0"
    assert float(d["fetch_ms"]) == 0.0
    assert span.cid == "compile-1" and span.ok and span.duration_ms > 0
    c = trace.BOOT.health()
    assert c["compile_requests"] == 1 and c["compile_cache_hits"] == 0
    assert c["compile_obtain_s"] == pytest.approx(span.duration_ms / 1e3)
    assert c["trace_s"] > 0 and c["lower_s"] > 0   # summed, never spans
    assert {s.stage for s in boot.spans} == {trace.STAGE_COMPILE}


def test_the_persistent_cache_serves_the_second_process(boot, tmp_path):
    """A temporary cache directory and no threshold: the first request
    builds (``hit=0``), the same program after the in-memory caches are
    cleared — what a second process start is — loads (``hit=1``)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update(keys[0], str(tmp_path))
        jax.config.update(keys[1], 0.0)
        jax.config.update(keys[2], -1)
        cc.reset_cache()
        x = np.arange(17, dtype=np.float32)
        _fresh_jit("obs_cache_probe")(x)
        jax.clear_caches()
        _fresh_jit("obs_cache_probe")(x)
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
    first, second = [_detail(s) for s in boot.spans
                     if "obs_cache_probe" in s.detail]
    assert first["hit"] == "0" and float(first["fetch_ms"]) == 0.0
    assert second["hit"] == "1" and float(second["fetch_ms"]) > 0
    c = trace.BOOT.health()
    assert c["compile_cache_hits"] == 1 and c["compile_fetch_s"] > 0
    assert c["compile_requests"] == len(boot.spans)


@pytest.mark.parametrize("fn,hit,fetch,want", [
    ("jit(score)", False, 0.0, "fn=jit(score) hit=0 fetch_ms=0.000"),
    ("jit(prefill)", True, 0.0125, "fn=jit(prefill) hit=1 fetch_ms=12.500"),
    ("a name\twith  blanks", False, 0.0,
     "fn=a_name_with_blanks hit=0 fetch_ms=0.000"),
])
def test_a_compile_span_ends_now_and_says_what_it_was(boot, fn, hit, fetch,
                                                      want):
    import time

    trace.BOOT.compiled(fn, 2.0, hit=hit, fetch_sec=fetch)
    (span,) = boot.spans
    assert span.detail == want and span.duration_ms == 2000.0
    assert span.start == pytest.approx(time.time() - 2.0, abs=0.5)


def test_a_tracer_built_late_starts_with_the_boot_log(boot):
    trace.BOOT.compiled("jit(a)", 0.5, hit=True, fetch_sec=0.01)
    with trace.setup_span(trace.STAGE_SETUP_SERVICE) as span:
        with trace.setup_span(trace.STAGE_SETUP_WARM, detail="steps=4"):
            trace.BOOT.compiled("jit(b)", 0.25, hit=False)
        span.detail = "slots=2 pages=8"
    logged = list(boot.spans)
    assert [s.stage for s in logged] == ["compile", "compile", "setup_warm",
                                         "setup_service"]
    tr = RowTracer(worker="late", capacity=64)
    assert tr.ring.snapshot() == logged          # same spans, same order
    assert [s.start for s in tr.ring.snapshot()] == [s.start for s in logged]
    assert logged[3].detail == "slots=2 pages=8" and logged[3].cid == "setup"
    assert logged[3].start <= logged[2].start    # the phase holds its part
    assert span.seconds == pytest.approx(logged[3].duration_ms / 1e3)
    snap = tr.snapshot()
    assert snap["spans_begun"] == snap["spans_ended"] == 4
    assert snap["spans_open"] == 0 and snap["ring_recorded"] == 4
    assert {k: v["count"] for k, v in snap["stages"].items()} == {
        "compile": 2, "setup_service": 1, "setup_warm": 1}
    assert set(tr.stages_wire()) == set(snap["stages"])


def test_a_later_span_reaches_every_live_tracer_and_no_dead_one(boot):
    a = RowTracer(worker="a")
    b = RowTracer(worker="b")
    gone = RowTracer(worker="gone")
    ring_of_gone = gone.ring
    del gone
    gc.collect()
    trace.BOOT.compiled("jit(late)", 0.1, hit=False)
    with trace.setup_span(trace.STAGE_SETUP_PIPELINE, detail="family=LR"):
        pass
    for tr in (a, b):
        assert [s.stage for s in tr.ring.snapshot()] == ["compile",
                                                         "setup_pipeline"]
        snap = tr.snapshot()
        assert snap["spans_begun"] == snap["spans_ended"] == 2
    assert len(ring_of_gone) == 0


def test_a_tracer_built_after_another_attached_is_whole(boot):
    """The log keeps what it hands a live tracer: a second engine's, or a
    restarted worker's fresh tracer, lacks nothing emitted in between,
    and no tracer holds a span twice."""
    trace.BOOT.compiled("jit(before)", 0.1, hit=True, fetch_sec=0.01)
    first = RowTracer(worker="first")
    trace.BOOT.compiled("jit(between)", 0.1, hit=False)
    second = RowTracer(worker="second")
    trace.BOOT.compiled("jit(after)", 0.1, hit=False)
    for tr in (first, second):
        assert [s.cid for s in tr.ring.snapshot()] == [
            "compile-1", "compile-2", "compile-3"]
        snap = tr.snapshot()
        assert snap["spans_begun"] == snap["spans_ended"] == 3
    assert first.ring.snapshot() == second.ring.snapshot() == boot.spans
    assert boot.dropped == 0


def test_the_boot_log_stops_at_its_bound_and_counts_the_rest(monkeypatch):
    small = trace._BootLog(capacity=4)
    monkeypatch.setattr(trace, "BOOT", small)
    for i in range(7):
        trace.BOOT.compiled(f"jit(p{i})", 0.01, hit=False)
    assert [s.cid for s in small.spans] == [f"compile-{i}" for i in (1, 2, 3, 4)]
    assert trace.BOOT.health()["boot_dropped"] == 3
    assert trace.BOOT.health()["compile_requests"] == 7
    tr = RowTracer(worker="w", capacity=2)       # a ring smaller than the log
    assert tr.ring.recorded == 4 and tr.ring.dropped == 2
    assert tr.snapshot()["spans_begun"] == tr.snapshot()["spans_ended"] == 4
    trace.BOOT.compiled("jit(p7)", 0.01, hit=False)   # attached or not,
    assert len(small.spans) == 4 and small.dropped == 4   # the bound holds
    assert tr.ring.recorded == 5                 # ... and the tracer has it
    assert trace.BOOT_CAPACITY == 2048 == trace._BootLog().capacity


def test_a_phase_that_raises_still_ends(boot):
    with pytest.raises(KeyError):
        with trace.setup_span(trace.STAGE_SETUP_TRAIN, detail="family=lr"):
            raise KeyError("fit")
    (span,) = boot.spans
    assert span.stage == "setup_train" and span.ok is False
    assert span.detail == "KeyError"


def test_one_listener_a_process():
    from jax._src import monitoring

    from fraud_detection_tpu.utils import jax_cache

    for _ in range(3):
        jax_cache.enable_persistent_compile_cache()
    mine = [cb for cb in monitoring.get_event_duration_listeners()
            if cb is jax_cache._on_duration]
    assert len(mine) == 1


@pytest.fixture(scope="module")
def pipeline():
    from fraud_detection_tpu.models.pipeline import synthetic_demo_pipeline

    return synthetic_demo_pipeline(batch_size=16, n=200, seed=5,
                                   num_features=1024)


def _engine(pipeline, n=16, tracer=None):
    from tests.fixtures import BENIGN_DIALOGUE

    broker = InProcessBroker()
    prod = broker.producer()
    for i in range(n):
        prod.produce("in", json.dumps({"text": BENIGN_DIALOGUE}).encode(),
                     key=str(i).encode())
    return StreamingClassifier(pipeline, broker.consumer(["in"], "g"),
                               broker.producer(), "out", batch_size=16,
                               max_wait=0.01, rowtrace=tracer)


def test_health_has_the_compile_block_and_the_registry_renders_it(pipeline):
    engine = _engine(pipeline)
    trace.BOOT.compiled("jit(x)", 0.5, hit=True, fetch_sec=0.25)
    block = engine.health()["compile"]
    assert tuple(block) == trace.COMPILE_COUNTERS + ("boot_dropped",)
    assert len(trace.COMPILE_COUNTERS) == 7 and block["boot_dropped"] == 0
    assert block["compile_requests"] == 1 and block["compile_fetch_s"] == 0.25
    json.dumps(block)
    reg = MetricsRegistry()
    reg.add_collector("engine", engine.health)
    parsed = parse_prometheus(reg.render_prometheus())
    for key in block:
        name = metric_name(reg.prefix, ("engine", "compile", key))
        assert parsed[name][0][1] == block[key], name


def test_compiles_since_serving_counts_from_the_first_poll(pipeline, boot):
    engine = _engine(pipeline)
    trace.BOOT.compiled("jit(warm)", 0.1, hit=True, fetch_sec=0.05)
    assert boot.serving is False
    assert engine.health()["compile"]["compiles_since_serving"] == 0
    engine.run(max_messages=16, idle_timeout=1.0)
    assert boot.serving is True
    before = engine.health()["compile"]
    trace.BOOT.compiled("jit(on_the_serving_path)", 0.1, hit=False)
    after = engine.health()["compile"]
    assert (after["compiles_since_serving"]
            == before["compiles_since_serving"] + 1)
    assert after["compile_requests"] == before["compile_requests"] + 1


def test_a_span_adopted_after_the_fact_keeps_the_invariant_in_a_run(pipeline):
    """A compile that lands while an engine with a tracer runs: the ring
    holds it beside the batch's spans and begun == ended."""
    tr = RowTracer(worker="w0", capacity=256)
    engine = _engine(pipeline, tracer=tr)
    engine.run(max_messages=16, idle_timeout=1.0)
    trace.BOOT.compiled("jit(mid_run)", 0.01, hit=False)
    snap = tr.snapshot()
    assert snap["spans_begun"] == snap["spans_ended"] and snap["spans_open"] == 0
    assert snap["batches_traced"] == snap["batches_closed"]
    stages = [s.stage for s in tr.ring.snapshot()]
    assert "compile" in stages
    assert isinstance(tr.ring.snapshot()[-1], Span)


@pytest.mark.parametrize("running,fires", [(True, True), (False, False)])
def test_the_documented_delta_rule_reads_the_block(running, fires):
    """docs/observability.md "Alerting": ``compiles_since_serving`` rose
    while ``running`` — a request paid for a program. The rule is the
    document's JSON, read out of it."""
    import os
    import re

    from fraud_detection_tpu.obs.sentinel import AlertRule, Sentinel

    doc = open(os.path.join(os.path.dirname(__file__), os.pardir, "docs",
                            "observability.md")).read()
    text = re.search(r'\{"name": "compile_on_serving_path".*?\}', doc, re.S)
    rule = AlertRule(**json.loads(text.group(0)))
    assert rule.kind == "delta" and rule.severity == "warning"

    state = {"running": running,
             "compile": dict.fromkeys(trace.COMPILE_COUNTERS, 0)}
    sentinel = Sentinel(lambda: json.loads(json.dumps(state)), [rule])
    assert sentinel.evaluate(now=0.0) == []
    state["compile"]["compile_requests"] = 40    # set-up's: not the signal
    assert sentinel.evaluate(now=1.0) == []
    state["compile"]["compiles_since_serving"] = 1
    fired = [o["event"] for o in sentinel.evaluate(now=2.0)]
    assert fired == (["fired"] if fires else [])
