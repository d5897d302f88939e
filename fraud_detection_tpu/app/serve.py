"""Streaming serve CLI — run the micro-batching classifier against a broker.

The production counterpart of the reference's Streamlit tab-3 monitor loop
(app_ui.py:168-248), runnable headless:

    # real Kafka (reference-compatible env vars: KAFKA_BOOTSTRAP_SERVERS,
    # KAFKA_INPUT_TOPIC, KAFKA_OUTPUT_TOPIC, KAFKA_CONSUMER_GROUP, SASL vars)
    python -m fraud_detection_tpu.app.serve --model ./fraud_model --kafka

    # self-contained demo/smoke: in-process broker fed with synthetic traffic
    python -m fraud_detection_tpu.app.serve --model spark:/path/to/artifact \
        --demo 5000 --batch-size 1024

``--model`` accepts a native checkpoint dir, ``spark:<dir>`` for a Spark
PipelineModel artifact, or ``synthetic`` to train a quick LR on the synthetic
corpus at startup.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time


def start_health_writer(path, interval, current_engines, fault_plan=None):
    """Launch the ``--health-file`` dumper: every ``interval`` seconds the
    current engines' ``health()`` snapshots are written to ``path`` via an
    atomic replace (readers never see a torn file). Returns a ``finish()``
    callable that stops the thread and writes the FINAL state — call it
    after the run ends, including on failure paths, so the file on disk
    always reflects how the run finished. No-op (returns a no-op finish)
    when ``path`` is None."""
    if path is None:
        return lambda: None

    def dump():
        snap = {"time": time.time(),
                "engines": [e.health() for e in list(current_engines())
                            if e is not None]}
        if fault_plan is not None:
            snap["chaos"] = fault_plan.report()
        # Shared atomic writer (utils/atomicio.py): unique temp names per
        # writer, so a second process pointed at the same health file can
        # never tear it; failures swallowed inside (health reporting must
        # never kill serving).
        from fraud_detection_tpu.utils.atomicio import atomic_write_json

        atomic_write_json(path, snap)

    stop = threading.Event()

    def loop():
        while not stop.wait(interval):
            dump()

    thread = threading.Thread(target=loop, daemon=True, name="health-writer")
    thread.start()

    def finish():
        stop.set()
        thread.join(timeout=5.0)
        dump()

    return finish


def build_pipeline(spec: str, batch_size: int, int8: bool = False,
                   featurize_device=False, featurize_width=None):
    from fraud_detection_tpu.models.pipeline import ServingPipeline

    if spec.startswith("spark:"):
        from fraud_detection_tpu.checkpoint.spark_artifact import load_spark_pipeline

        pipe = ServingPipeline.from_spark_artifact(
            load_spark_pipeline(spec[len("spark:"):]), batch_size=batch_size)
    elif spec == "synthetic":
        from fraud_detection_tpu.models.pipeline import synthetic_demo_pipeline

        pipe = synthetic_demo_pipeline(batch_size, int8=int8)
    else:
        pipe = ServingPipeline.from_checkpoint(spec, batch_size=batch_size)
    if int8 or featurize_device:
        # Rebuild with the scoring-variant flags (docs/serving.md): int8
        # quantization and device-side featurization both derive from the
        # loaded model/featurizer, so they are constructor flags, not
        # second artifacts.
        pipe = ServingPipeline(pipe.featurizer, pipe.model,
                               batch_size=batch_size, int8=int8,
                               featurize_device=featurize_device,
                               featurize_width=featurize_width)
    return pipe


def _demo_census(broker, args) -> dict:
    """What a ``--demo N`` run left on the in-process broker, against what
    it fed (keys ``0..N-1``): record counts on the output and dead-letter
    topics, and whether every fed key came out exactly once — zero loss,
    zero duplication. The demo's own accounting, so a caller of ``main()``
    need not reach into the broker."""
    out_keys = sorted(m.key for m in broker.messages(args.output_topic)
                      if m.key is not None)
    dlq_topic = ((args.dlq_topic or f"{args.output_topic}-dlq")
                 if args.dlq else None)
    return {"fed": args.demo, "out": len(out_keys),
            "dlq": broker.topic_size(dlq_topic) if dlq_topic else 0,
            "keys_exact": out_keys == sorted(
                str(i).encode() for i in range(args.demo))}


def _judge_scenario(scenario, events, feeder, broker, args, out,
                    tracers) -> dict:
    """Evaluate a --scenario run's SLO gates from the serve-side evidence
    (broker key multisets + the exit stats/health). Scope is "serve":
    fleet-only gates (worker kills, hot swaps) report skipped — the full
    game-day runner owns those (docs/scenarios.md)."""
    from fraud_detection_tpu.scenarios import evaluate

    health = out.get("health") or {}
    dlq_topic = ((args.dlq_topic or f"{args.output_topic}-dlq")
                 if args.dlq else None)
    stats = {k: v for k, v in out.items() if isinstance(v, (int, float))}
    evidence = {
        "planned": len(events),
        "fed": feeder.fed,
        "fed_keys": [e.key.decode() for e in events],
        "out_keys": [m.key.decode()
                     for m in broker.messages(args.output_topic)
                     if m.key is not None],
        "dlq_keys": ([m.key.decode() for m in broker.messages(dlq_topic)
                      if m.key is not None] if dlq_topic else []),
        "stats": stats,
        "health": health,
        "sched": health.get("sched"),
        "breaker": health.get("breaker"),
        "chaos": out.get("chaos"),
        "traces": [t.snapshot() for t in tracers.values()],
        "tracing": bool(args.trace),
        "feeder": feeder.stats(),
        "errors": ([f"feeder: {feeder.error!r}"]
                   if feeder.error is not None else []),
    }
    evidence["shed_fraction"] = round(
        stats.get("shed", 0) / max(1, len(events)), 4)
    report = evaluate(scenario.slos, evidence, scope="serve")
    return {"name": scenario.name, "seed": scenario.seed, "ok": report.ok,
            "fed": feeder.fed, "planned": len(events),
            "verdicts": [v.as_dict() for v in report.verdicts]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default=None,
                    help="native checkpoint dir | spark:<artifact dir> | "
                         "synthetic (or use --registry)")
    ap.add_argument("--registry", default=None, metavar="DIR",
                    help="serve from a model registry "
                         "(registry/registry.py layout) instead of a fixed "
                         "--model; loads are content-hash verified "
                         "(docs/model_lifecycle.md)")
    ap.add_argument("--model-version", type=int, default=None, metavar="N",
                    help="registry version to serve (--registry; "
                         "default: latest)")
    ap.add_argument("--watch", action="store_true",
                    help="poll --registry for new versions and hot-swap "
                         "them in with zero downtime (pre-warmed RCU swap "
                         "between batches; registry/hotswap.py)")
    ap.add_argument("--watch-interval", type=float, default=2.0,
                    help="seconds between registry polls (--watch)")
    ap.add_argument("--shadow", action="store_true",
                    help="stage new versions as shadow candidates instead "
                         "of swapping immediately: each micro-batch is "
                         "also scored by the candidate asynchronously and "
                         "divergence stats accumulate in health() "
                         "(registry/shadow.py; requires --watch)")
    ap.add_argument("--shadow-sample", type=float, default=1.0,
                    help="fraction of micro-batches shadow-scored "
                         "(--shadow)")
    ap.add_argument("--shadow-queue", type=int, default=8,
                    help="bounded shadow queue depth; overflow drops + "
                         "counts, never blocks the primary (--shadow)")
    ap.add_argument("--learn", action="store_true",
                    help="close the learning loop (learn/, docs/"
                         "online_learning.md): join feedback labels "
                         "against a sliding window of scored rows, "
                         "retrain boosted trees on drift, publish to "
                         "--registry, auto-promote through the --shadow/"
                         "--promote-policy gates (requires all three)")
    ap.add_argument("--learn-feedback-topic", default=None, metavar="TOPIC",
                    help="ground-truth label topic (stream/feedback.py "
                         "records; default <input-topic>-feedback)")
    ap.add_argument("--learn-window", type=int, default=8192,
                    help="learn window capacity in rows (--learn)")
    ap.add_argument("--learn-min-rows", type=int, default=256,
                    help="labeled rows required before any retrain")
    ap.add_argument("--learn-error-threshold", type=float, default=0.15,
                    help="drift trigger: recent label-error rate of the "
                         "live model above this fires a retrain")
    ap.add_argument("--learn-rounds", type=int, default=8,
                    help="warm-start boosting rounds per windowed retrain")
    ap.add_argument("--learn-interval", type=float, default=0.0,
                    help="retrain cadence in seconds (0 = drift/row "
                         "triggers only)")
    ap.add_argument("--promote-policy", default=None, metavar="SPEC",
                    help="auto promote/reject the staged candidate, e.g. "
                         "'min_batches=5,min_rows=200,max_disagreement="
                         "0.02,max_psi=0.25' (--shadow; every transition "
                         "is audited to <registry>/audit.jsonl)")
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--max-wait", type=float, default=0.05,
                    help="micro-batch assembly deadline (seconds)")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="device batches kept in flight (hides round-trip latency)")
    ap.add_argument("--async-dispatch", action="store_true",
                    help="double-buffered dispatch lane: featurize+upload+"
                         "launch batch N+1 on a dedicated thread while this "
                         "worker delivers batch N (sched/batcher.py "
                         "DispatchLane; counters in health()['device'])")
    ap.add_argument("--int8", action="store_true",
                    help="int8 scoring variant (LogisticRegression models "
                         "only): quantized weights, exact int32 "
                         "accumulation, fp32-parity pinned by tests "
                         "(docs/serving.md)")
    ap.add_argument("--featurize-device", action="store_true",
                    help="device-side featurization (ops/featurize_kernel."
                         "py): ship raw UTF-8 bytes and run tokenize/"
                         "murmur-hash/TF counting inside the scoring "
                         "program — the host featurize leg disappears. "
                         "Requires a TPU; without one the flag is an "
                         "error, not a host fallback (docs/serving.md)")
    ap.add_argument("--featurize-width", type=int, default=None,
                    metavar="BYTES",
                    help="fixed byte width of the --featurize-device "
                         "staging tensor (default 2048); longer rows "
                         "truncate at a codepoint boundary and count in "
                         "health()['device']['truncated_rows']")
    ap.add_argument("--batch-deadline-ms", type=float, default=None,
                    help="adaptive scheduler: ship a partial micro-batch "
                         "this many ms after its first row instead of "
                         "waiting to fill --batch-size; partial batches "
                         "pad to a pre-warmed bucket ladder, so no XLA "
                         "compile ever lands on the hot path "
                         "(docs/scheduling.md)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="queue-depth high watermark (rows backlogged at "
                         "the broker): above it a shedding --shed-policy "
                         "diverts the excess to the DLQ lane as explicit "
                         "shed records")
    ap.add_argument("--shed-policy", default="none",
                    choices=["none", "reject", "adaptive"],
                    help="load shedding: 'none' never sheds (a --max-rate "
                         "then paces polls instead), 'reject' sheds over "
                         "--max-queue/--max-rate, 'adaptive' also sheds an "
                         "AIMD-controlled fraction while p99 exceeds "
                         "--target-p99-ms; shedding implies --dlq (shed "
                         "rows are records, never silent drops)")
    ap.add_argument("--target-p99-ms", type=float, default=None,
                    help="SLO target for per-row enqueue->produce p99 "
                         "latency; feeds the backpressure governor and the "
                         "'adaptive' shed policy, surfaced in health()")
    ap.add_argument("--max-rate", type=float, default=None,
                    help="token-bucket admission limit, rows/sec (paces "
                         "polls under --shed-policy none, sheds the "
                         "overflow otherwise)")
    ap.add_argument("--kafka", action="store_true",
                    help="use real Kafka via confluent_kafka + KAFKA_* env vars")
    ap.add_argument("--demo", type=int, metavar="N", default=0,
                    help="feed N synthetic messages through an in-process broker and exit")
    ap.add_argument("--input-topic", default=os.getenv("KAFKA_INPUT_TOPIC", "customer-dialogues-raw"))
    ap.add_argument("--output-topic", default=os.getenv("KAFKA_OUTPUT_TOPIC", "dialogues-classified"))
    ap.add_argument("--max-messages", type=int, default=None)
    ap.add_argument("--supervise", type=int, metavar="N", default=0,
                    help="restart the engine up to N times on crash/flush "
                         "failure (resumes from committed offsets; see "
                         "stream.engine.run_supervised)")
    ap.add_argument("--workers", type=int, default=1,
                    help="engines sharing ONE consumer group: each owns a "
                         "disjoint partition subset (the reference's "
                         "--partitions 3 scale-out unit; docs/serving.md "
                         "'Horizontal scale-out')")
    ap.add_argument("--fleet", type=int, metavar="N", default=0,
                    help="fleet serving lane (docs/fleet.md): N partition-"
                         "OWNING workers behind a lease coordinator — "
                         "revoke->drain->commit->reassign rebalance on "
                         "worker death, health on the fleet bus, shedding "
                         "coordinated on the GLOBAL backlog watermark "
                         "(demo mode; against real Kafka use --workers, "
                         "whose group assignor is broker-side)")
    ap.add_argument("--partitions", type=int, default=3,
                    help="in-process demo broker partition count (the "
                         "reference provisions --partitions 3; a fleet "
                         "scales to min(partitions, workers))")
    ap.add_argument("--fleet-health-file", default=None,
                    help="periodically dump the aggregated fleet view + "
                         "every worker's health to this path (atomic "
                         "replace; --fleet)")
    ap.add_argument("--fleet-candidates", type=int, metavar="K", default=1,
                    help="coordinator succession (docs/fleet.md "
                         "'Coordinator succession'): K candidates contend "
                         "on the leased coordinator role over the control "
                         "lane, so the fleet survives its own coordinator "
                         "dying (K >= 2 arms standby successors; 1 = the "
                         "classic single coordinator; --fleet)")
    ap.add_argument("--autoscale", action="store_true",
                    help="closed-loop autoscaling (docs/autoscaling.md): "
                         "the fleet sizes itself from its own sentinel "
                         "signals — scale-out on fleet_watermark_burn, "
                         "voluntary-leave scale-in on sustained "
                         "fleet_idle, dead capacity replaced — bounded by "
                         "--min-workers/--max-workers. Needs --fleet N "
                         "(the starting size); without --alerts there is "
                         "no signal plane and the loop only replaces "
                         "dead workers")
    ap.add_argument("--min-workers", type=int, metavar="N", default=None,
                    help="autoscale floor (default: 1; --autoscale)")
    ap.add_argument("--max-workers", type=int, metavar="N", default=None,
                    help="autoscale ceiling (default: the larger of "
                         "--fleet and --partitions — a worker past the "
                         "partition count would sit idle; --autoscale)")
    ap.add_argument("--scale-cooldown", type=float, metavar="S",
                    default=30.0,
                    help="seconds between resizes — the anti-flap window; "
                         "hysteresis credits a burn that started during "
                         "it (--autoscale)")
    ap.add_argument("--mesh", action="store_true",
                    help="mesh data-parallel scoring (parallel/serving.py "
                         "MeshServingPipeline): shard every micro-batch "
                         "across all local chips' data axis — one worker "
                         "drives the whole mesh; single-device falls back "
                         "byte-identically")
    ap.add_argument("--explain", default="off", metavar="SPEC",
                    help="attach LLM analyses to flagged messages, batched "
                         "per micro-batch: 'off' | 'canned' (offline stub) | "
                         "'onpod:<hf checkpoint dir>' (zero-egress, "
                         "checkpoint/hf_convert.py; 'onpod-int8:<dir>' adds "
                         "weight-only int8 — ~1.5x explanations/sec) | "
                         "'deepseek' (env DEEPSEEK_API_KEY, the reference's "
                         "backend)")
    ap.add_argument("--explain-tokens", type=int, default=128,
                    help="max new tokens per analysis (--explain)")
    ap.add_argument("--explain-slots", type=int, metavar="N", default=0,
                    help="serve explanations through the slot-based "
                         "continuous-batching lane with N decode slots "
                         "over one persistent pool of KV pages, the shared "
                         "explain preamble prefilled once (0 = off; needs "
                         "an onpod-family --explain backend; implies "
                         "--explain-async — docs/explain_serving.md). "
                         "Every flagged row is explained or accounted, "
                         "and health() gains the 'explain' block")
    ap.add_argument("--explain-queue", type=int, default=1024,
                    help="slotserve admission-queue bound (--explain-slots; "
                         "overflow drops OLDEST with honest accounting)")
    ap.add_argument("--explain-kv-pages", type=int, metavar="N", default=0,
                    help="cap the slot lane's page pool at N pages "
                         "(--explain-slots; "
                         "0 = slots * pages-per-slot, the zero-preemption "
                         "default; smaller pools preempt the NEWEST admit "
                         "with a kv_pages_exhausted drop record)")
    ap.add_argument("--explain-async", action="store_true",
                    help="annotate flagged rows in the background onto "
                         "--annotations-topic instead of inline: "
                         "classification never waits for LLM decode "
                         "(bounded queue, drop-oldest under overload; "
                         "stream/annotations.py)")
    ap.add_argument("--annotations-topic", default=None,
                    help="side topic for --explain-async records "
                         "(default: <output-topic>-annotations)")
    ap.add_argument("--dlq", action="store_true",
                    help="route malformed and repeatedly-failing messages "
                         "to a dead-letter topic (<output-topic>-dlq) as "
                         "structured reason records instead of inline "
                         "error frames (docs/robustness.md)")
    ap.add_argument("--dlq-topic", default=None,
                    help="dead-letter topic name (implies --dlq)")
    ap.add_argument("--dlq-max-attempts", type=int, default=3,
                    help="re-deliveries before a row is dead-lettered as "
                         "poison (--dlq; counted across --supervise restarts)")
    ap.add_argument("--breaker", type=int, metavar="N", default=0,
                    help="wrap the --explain backend in a circuit breaker "
                         "that opens after N consecutive failures (0 = off; "
                         "open = explanations fast-fail instead of paying "
                         "the backend's timeout/retry budget)")
    ap.add_argument("--breaker-probe", type=float, default=30.0,
                    help="seconds an open breaker waits before probing the "
                         "backend again (--breaker)")
    ap.add_argument("--health-file", default=None,
                    help="periodically dump an engine-health JSON snapshot "
                         "to this path (atomic replace; final state written "
                         "at exit)")
    ap.add_argument("--health-interval", type=float, default=2.0,
                    help="seconds between --health-file dumps")
    ap.add_argument("--metrics-file", default=None,
                    help="periodically dump the unified metrics exporter "
                         "to this path (atomic replace, final state at "
                         "exit, exactly like --health-file): Prometheus "
                         "text for .prom/.txt paths, JSON otherwise — "
                         "every health() key maps in, ONE schema "
                         "(docs/observability.md)")
    ap.add_argument("--metrics-interval", type=float, default=2.0,
                    help="seconds between --metrics-file dumps")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve /metrics (Prometheus text), /metrics.json, "
                         "and /healthz (readiness: 200, or 503 with the "
                         "firing rule names while a critical alert fires — "
                         "--alerts) on this local port (stdlib HTTP, "
                         "daemon thread; 0 picks a free port, printed at "
                         "startup)")
    ap.add_argument("--alerts", action="store_true",
                    help="run the sentinel alerting engine (obs/sentinel/, "
                         "docs/observability.md): the default rule pack "
                         "(shed/DLQ burn rates, breaker opens, p99 SLO "
                         "burn, dispatch stall, span leaks, fence events, "
                         "restart churn) evaluates against live engine "
                         "health; firing state rides health()['alerts'], "
                         "the exit stats JSON, /metrics, and /healthz")
    ap.add_argument("--alert-rules", default=None, metavar="FILE",
                    help="JSON alert-rule file replacing the default pack "
                         "(rule grammar: docs/observability.md); implies "
                         "--alerts")
    ap.add_argument("--alert-interval", type=float, default=1.0,
                    help="seconds between sentinel evaluations (--alerts)")
    ap.add_argument("--incident-dir", default=None, metavar="DIR",
                    help="flight-recorder output (implies --alerts): every "
                         "alert transition appends to DIR/incidents.jsonl "
                         "and a firing incident captures a bundle dir "
                         "(evidence window, metric deltas, health, "
                         "implicated trace chains)")
    ap.add_argument("--trace", action="store_true",
                    help="row/batch tracing (obs/trace.py): correlation "
                         "ids minted at poll ride every row to its "
                         "terminal; flagged/shed/DLQ rows always keep "
                         "their span chain, clean batches head-sample at "
                         "--trace-sample; per-stage p50/p99 in health() "
                         "and the exporter")
    ap.add_argument("--trace-sample", type=float, default=0.05,
                    help="fraction of CLEAN batches whose spans are kept "
                         "(--trace; interesting batches are always kept)")
    ap.add_argument("--trace-record", default=None, metavar="FILE",
                    help="record this run for replay: tracing runs in "
                         "record mode (sample forced to 1.0 + a per-batch "
                         "row census) and the SpanRing dumps to FILE as "
                         "JSONL at exit via the atomic writer; replay with "
                         "python -m fraud_detection_tpu.scenarios.replay "
                         "(docs/scenarios.md). Implies --trace; single "
                         "worker only")
    ap.add_argument("--scenario", default=None, metavar="NAME[:seed]",
                    help="drive a named scenario's seeded traffic against "
                         "this live serve run instead of the uniform "
                         "--demo preload, then gate on the scenario's "
                         "SLOs (exit 4 on violation; scenario catalog: "
                         "python -m fraud_detection_tpu.scenarios.gameday "
                         "--list). Engine config still comes from the "
                         "serve flags; fleet-only gates (worker kills, "
                         "hot swaps) report as skipped — run the full "
                         "game day via the gameday CLI. Needs --demo")
    ap.add_argument("--scenario-scale", type=float, default=1.0,
                    help="traffic-rate multiplier for --scenario (CI "
                         "smokes run < 1)")
    ap.add_argument("--scenario-time-scale", type=float, default=1.0,
                    help="timeline pacing for --scenario: 1 = the "
                         "scenario's real-time curve (default), 0 = warp "
                         "(feed as fast as the engine drains)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture jax.profiler traces: one around "
                         "prewarm/ladder measurement, one over the first "
                         "--profile-batches serving batches "
                         "(TensorBoard/Perfetto readable)")
    ap.add_argument("--profile-batches", type=int, default=50,
                    help="batches in the serving-window profiler capture "
                         "(--profile-dir)")
    ap.add_argument("--chaos", action="store_true",
                    help="demo mode only: run the in-process broker under a "
                         "seeded fault plan (poll errors, lossy flushes, "
                         "commit fences, duplicates, corruption) to "
                         "demonstrate graceful degradation; implies "
                         "supervision (stream/faults.py)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="fault-plan seed (--chaos; same seed = same "
                         "fault schedule)")
    args = ap.parse_args(argv)

    if args.kafka and args.demo:
        raise SystemExit("--kafka and --demo are mutually exclusive")
    if (args.model is None) == (args.registry is None):
        raise SystemExit("choose exactly one of --model or --registry")
    if args.registry is None and (args.model_version is not None or args.watch
                                  or args.shadow):
        raise SystemExit("--model-version/--watch/--shadow need --registry")
    if args.shadow and not args.watch:
        raise SystemExit("--shadow needs --watch (candidates arrive via "
                         "registry polling)")
    if args.promote_policy is not None and not args.shadow:
        raise SystemExit("--promote-policy needs --shadow (there is no "
                         "candidate to judge without shadow scoring)")
    if args.learn:
        # The loop's whole contract is publish -> stage -> shadow-judge ->
        # auto-promote, so every leg must be wired explicitly.
        if not (args.registry and args.watch and args.shadow
                and args.promote_policy):
            raise SystemExit(
                "--learn closes the loop through the registry lifecycle: "
                "it requires --registry, --watch, --shadow AND "
                "--promote-policy (docs/online_learning.md)")
        if args.learn_min_rows < 2 or args.learn_rounds < 1 \
                or args.learn_window < 2:
            raise SystemExit("--learn-min-rows/--learn-rounds/"
                             "--learn-window must be positive")
    if args.watch_interval <= 0:
        raise SystemExit(
            f"--watch-interval must be > 0, got {args.watch_interval}")
    if not 0.0 < args.shadow_sample <= 1.0:
        raise SystemExit(
            f"--shadow-sample must be in (0, 1], got {args.shadow_sample}")
    if args.shadow_queue < 1:
        raise SystemExit(
            f"--shadow-queue must be >= 1, got {args.shadow_queue}")
    promote_policy = None
    if args.promote_policy is not None:
        from fraud_detection_tpu.registry import PromotionPolicy

        try:
            promote_policy = PromotionPolicy.parse(args.promote_policy)
        except ValueError as e:
            raise SystemExit(f"bad --promote-policy: {e}")
    if args.int8 and args.registry:
        # Registry candidates (watch/hot-swap) are rebuilt by the watcher,
        # which would silently serve them fp32 — refuse rather than mix
        # scoring variants across swaps.
        raise SystemExit("--int8 is not supported with --registry yet "
                         "(hot-swap candidates would load fp32)")
    if args.featurize_device and args.registry:
        # Same reasoning as --int8: the watcher rebuilds candidates without
        # the flag, which would silently flip the featurize path at swap.
        raise SystemExit("--featurize-device is not supported with "
                         "--registry yet (hot-swap candidates would load "
                         "host-featurizing)")
    if args.featurize_width is not None and not args.featurize_device:
        raise SystemExit("--featurize-width needs --featurize-device")
    if args.pipeline_depth < 1:
        # Fail fast: inside --supervise this would read as a transient
        # incarnation failure and burn restarts on a pure config error.
        raise SystemExit(f"--pipeline-depth must be >= 1, got {args.pipeline_depth}")
    if args.workers < 1:
        raise SystemExit(f"--workers must be >= 1, got {args.workers}")
    if args.explain_tokens < 1:
        raise SystemExit(f"--explain-tokens must be >= 1, got {args.explain_tokens}")
    if args.explain_slots < 0:
        raise SystemExit(
            f"--explain-slots must be >= 0, got {args.explain_slots}")
    if args.explain_queue < 1:
        raise SystemExit(
            f"--explain-queue must be >= 1, got {args.explain_queue}")
    if args.explain_slots > 0:
        if not args.explain.startswith("onpod"):
            raise SystemExit(
                "--explain-slots needs an onpod-family --explain backend "
                "(onpod:<dir>, onpod-int8:<dir>, or onpod-demo) — the slot "
                "lane serves a models/llm.py model from this pod")
        # The slot lane IS the async configuration: classification never
        # waits for decode, annotations ride the side topic.
        args.explain_async = True
    if args.explain_kv_pages < 0:
        raise SystemExit(
            f"--explain-kv-pages must be >= 0, got {args.explain_kv_pages}")
    if args.explain_kv_pages > 0 and args.explain_slots < 1:
        raise SystemExit(
            "--explain-kv-pages caps the slotserve lane's page pool — it "
            "needs --explain-slots")
    if args.explain_async and args.explain == "off":
        raise SystemExit("--explain-async needs an --explain backend")
    if args.annotations_topic is not None and not args.explain_async:
        raise SystemExit("--annotations-topic only applies with "
                         "--explain-async (inline analyses ride the "
                         "output frames)")
    if args.fleet < 0:
        raise SystemExit(f"--fleet must be >= 0, got {args.fleet}")
    if args.partitions < 1:
        raise SystemExit(f"--partitions must be >= 1, got {args.partitions}")
    if args.fleet > 0:
        if not args.demo:
            raise SystemExit(
                "--fleet needs --demo N (the lease coordinator drives the "
                "in-process broker's manual-assignment mode; against real "
                "Kafka use --workers — its group assignor is broker-side)")
        if args.workers > 1:
            raise SystemExit("--fleet and --workers > 1 are mutually "
                             "exclusive (two assignment authorities)")
        if args.registry or args.explain != "off" or args.chaos:
            raise SystemExit("--fleet does not combine with --registry/"
                             "--explain/--chaos yet (docs/fleet.md)")
        if args.supervise:
            raise SystemExit("--fleet supervises itself (lease expiry + "
                             "rebalance); drop --supervise")
        if args.max_messages is not None:
            raise SystemExit("--max-messages cannot be split across a "
                             "fleet; workers drain until the group's "
                             "committed lag clears")
    if args.mesh and args.registry is not None:
        raise SystemExit("--mesh is not supported with --registry yet "
                         "(hot-swap candidates would load single-device)")
    if args.fleet_health_file is not None and args.fleet == 0:
        raise SystemExit("--fleet-health-file needs --fleet N")
    if args.fleet_candidates < 1:
        raise SystemExit(f"--fleet-candidates must be >= 1, "
                         f"got {args.fleet_candidates}")
    if args.fleet_candidates > 1 and args.fleet == 0:
        raise SystemExit("--fleet-candidates needs --fleet N")
    if (args.min_workers is not None or args.max_workers is not None) \
            and not args.autoscale:
        raise SystemExit("--min-workers/--max-workers need --autoscale")
    autoscale_config = None
    if args.autoscale:
        # Closed-loop elasticity rides the in-process fleet lane only:
        # the provisioner seam spawns THREADS against the demo broker
        # (docs/autoscaling.md "Provisioners").
        if args.fleet == 0:
            raise SystemExit("--autoscale needs --fleet N (the elastic "
                             "lane; docs/autoscaling.md)")
        lo = args.min_workers if args.min_workers is not None else 1
        hi = (args.max_workers if args.max_workers is not None
              else max(args.fleet, args.partitions))
        if lo < 1:
            raise SystemExit(f"--min-workers must be >= 1, got {lo}")
        if not lo <= args.fleet <= hi:
            raise SystemExit(
                f"--fleet {args.fleet} must sit within the autoscale "
                f"bounds [{lo}, {hi}] (--min-workers/--max-workers)")
        if args.scale_cooldown < 0:
            raise SystemExit(f"--scale-cooldown must be >= 0, "
                             f"got {args.scale_cooldown}")
        autoscale_config = dict(min_workers=lo, max_workers=hi,
                                cooldown_s=args.scale_cooldown)
    if args.workers > 1 and args.max_messages is not None:
        # Per-worker message caps can't split a global cap meaningfully —
        # refuse BEFORE the expensive pipeline build, like every other
        # config conflict above.
        raise SystemExit(
            "--max-messages cannot be split across --workers > 1; "
            "drop one of the two (workers drain until idle)")
    if args.chaos and not args.demo:
        raise SystemExit("--chaos needs --demo N (faults are injected into "
                         "the in-process broker; against real Kafka use a "
                         "real chaos tool)")
    sched_config = None
    if (args.batch_deadline_ms is not None or args.max_queue is not None
            or args.shed_policy != "none" or args.target_p99_ms is not None
            or args.max_rate is not None):
        from fraud_detection_tpu.sched import SchedulerConfig

        try:
            sched_config = SchedulerConfig(
                batch_deadline_ms=args.batch_deadline_ms,
                max_queue=args.max_queue,
                shed_policy=args.shed_policy,
                target_p99_ms=args.target_p99_ms,
                max_rate=args.max_rate)
        except ValueError as e:
            raise SystemExit(f"bad scheduler config: {e}")
        if args.shed_policy != "none":
            # Shed rows are structured DLQ records by contract — a shedding
            # scheduler without the DLQ lane would have nowhere non-silent
            # to put them.
            args.dlq = True
    if args.dlq_topic is not None:
        args.dlq = True
    if args.dlq_max_attempts < 1:
        raise SystemExit(
            f"--dlq-max-attempts must be >= 1, got {args.dlq_max_attempts}")
    if args.breaker < 0:
        raise SystemExit(f"--breaker must be >= 0, got {args.breaker}")
    if args.breaker > 0 and args.explain == "off":
        raise SystemExit("--breaker needs an --explain backend")
    if args.breaker_probe <= 0:
        raise SystemExit(
            f"--breaker-probe must be > 0, got {args.breaker_probe}")
    if args.health_interval <= 0:
        raise SystemExit(
            f"--health-interval must be > 0, got {args.health_interval}")
    if args.metrics_interval <= 0:
        raise SystemExit(
            f"--metrics-interval must be > 0, got {args.metrics_interval}")
    if args.metrics_port is not None and args.metrics_port < 0:
        raise SystemExit(
            f"--metrics-port must be >= 0, got {args.metrics_port}")
    if not 0.0 <= args.trace_sample <= 1.0:
        raise SystemExit(
            f"--trace-sample must be in [0, 1], got {args.trace_sample}")
    if args.alert_rules is not None or args.incident_dir is not None:
        args.alerts = True
    if args.alert_interval <= 0:
        raise SystemExit(
            f"--alert-interval must be > 0, got {args.alert_interval}")
    alert_rules = None
    if args.alerts:
        from fraud_detection_tpu.obs.sentinel import (default_rule_pack,
                                                      load_rules)

        if args.alert_rules is not None:
            try:
                alert_rules = load_rules(args.alert_rules)
            except (OSError, ValueError) as e:
                raise SystemExit(f"bad --alert-rules: {e}")
        else:
            alert_rules = default_rule_pack()
    if args.trace_record is not None:
        # Record mode: full sampling + the per-batch row census, one ring
        # (docs/scenarios.md "Recording a run").
        if args.workers > 1 or args.fleet > 0:
            raise SystemExit("--trace-record supports a single worker "
                             "only (one recording = one worker's ring)")
        args.trace = True
    scenario = None
    if args.scenario is not None:
        if not args.demo:
            raise SystemExit("--scenario needs --demo N (traffic is fed "
                             "into the in-process broker; N is ignored — "
                             "the scenario defines the rows)")
        if args.workers > 1 or args.fleet > 0:
            raise SystemExit("--scenario drives a single serve worker; "
                             "run multi-worker scenarios via "
                             "python -m fraud_detection_tpu.scenarios."
                             "gameday")
        if args.scenario_scale <= 0:
            raise SystemExit(f"--scenario-scale must be > 0, "
                             f"got {args.scenario_scale}")
        if args.scenario_time_scale < 0:
            raise SystemExit(f"--scenario-time-scale must be >= 0, "
                             f"got {args.scenario_time_scale}")
        from fraud_detection_tpu.scenarios import (get_scenario,
                                                   parse_scenario_ref)

        try:
            name, scenario_seed = parse_scenario_ref(args.scenario)
            scenario = get_scenario(name, scenario_seed,
                                    scale=args.scenario_scale)
        except (KeyError, ValueError) as e:
            raise SystemExit(f"bad --scenario: {e}")
    if args.profile_batches < 1:
        raise SystemExit(
            f"--profile-batches must be >= 1, got {args.profile_batches}")
    if args.chaos and args.supervise == 0:
        # Chaos without supervision dies on the first injected fault by
        # design; default to enough restarts for the demo plan's budget.
        args.supervise = 25

    from fraud_detection_tpu.stream import InProcessBroker, StreamingClassifier
    from fraud_detection_tpu.stream.kafka import kafka_available
    from fraud_detection_tpu.utils.device import device_stamp
    from fraud_detection_tpu.utils.jax_cache import (
        enable_persistent_compile_cache)

    compile_cache = enable_persistent_compile_cache()

    explain_hook = None
    breaker = None
    explain_service = None
    if args.explain != "off":
        from fraud_detection_tpu.explain import make_stream_explain_hook
        from fraud_detection_tpu.utils.config import LLMConfig

        # LLM_* parses in ONE place (LLMConfig.from_env); malformed values
        # fail like every other config error, not with a raw traceback.
        try:
            llm_cfg = LLMConfig.from_env()
        except ValueError as e:
            raise SystemExit(f"bad LLM_* environment value: {e}")
        # Temperature: an explicit LLM_TEMPERATURE wins for every backend;
        # unset, deepseek keeps the reference agent's 1.0 default
        # (utils/agent_api.py semantics) while local backends default to
        # deterministic greedy analyses.
        temp = (llm_cfg.temperature
                if args.explain == "deepseek" or "LLM_TEMPERATURE" in os.environ
                else 0.0)
        slot_lm = None     # the models/llm.py model --explain-slots serves
        if args.explain == "canned":
            from fraud_detection_tpu.explain import CannedBackend

            backend = CannedBackend(responses=[
                "(offline analysis stub — run --explain onpod:<dir> or "
                "--explain deepseek for a real model)"])
        elif args.explain == "onpod-demo":
            # Tiny random-init on-pod model: the smoke/demo backend for the
            # slot lane and CLI e2e tests — real decode path, no checkpoint
            # download, analyses are noise (it says so in the name).
            from fraud_detection_tpu.explain import OnPodBackend
            from fraud_detection_tpu.models.llm import (LanguageModel,
                                                        TransformerConfig)

            slot_lm = LanguageModel.init_random(
                TransformerConfig(d_model=128, n_layers=2, n_heads=8,
                                  d_ff=256, max_seq=2048), seed=0)
            backend = OnPodBackend.from_model(slot_lm)
        elif args.explain.startswith(("onpod:", "onpod-int8:")):
            from fraud_detection_tpu.explain import OnPodBackend

            spec, _, ckpt = args.explain.partition(":")
            if not ckpt or not os.path.isdir(ckpt):
                # clean config error, like every other bad spec on this path
                # (an EMPTY ckpt would even resolve to ./config.json)
                raise SystemExit(
                    f"--explain {spec}: checkpoint dir {ckpt!r} not found")
            try:
                from fraud_detection_tpu.checkpoint.hf_convert import (
                    load_hf_checkpoint)

                # Loaded as the model (not just a backend) so the slot
                # lane can serve the SAME params; OnPodBackend binds it
                # exactly like from_hf_checkpoint did.
                slot_lm = load_hf_checkpoint(ckpt, max_seq=4096,
                                             int8=spec == "onpod-int8")
                backend = OnPodBackend.from_model(slot_lm)
            except (OSError, ValueError, KeyError, NotImplementedError) as e:
                # A dir without config.json/safetensors/tokenizer is a config
                # error, not a crash — under --supervise a raw traceback
                # reads as a transient incarnation failure and burns restarts.
                raise SystemExit(f"--explain {spec}: cannot load {ckpt!r}: {e}")
        elif args.explain == "deepseek":
            if not llm_cfg.api_key:
                raise SystemExit("--explain deepseek needs DEEPSEEK_API_KEY")
            backend = llm_cfg.make_backend()
        else:
            raise SystemExit(f"unknown --explain spec {args.explain!r}")
        if args.explain_slots > 0:
            # Slot-based continuous batching (docs/explain_serving.md): the
            # service REPLACES the fixed-batch backend — same LLMBackend
            # surface, so the breaker below wraps it unchanged.
            from fraud_detection_tpu.explain.slotserve import SlotServeService

            try:
                backend = explain_service = SlotServeService(
                    slot_lm, slots=args.explain_slots,
                    max_queue=args.explain_queue,
                    max_new_tokens=args.explain_tokens,
                    kv_pages=args.explain_kv_pages or None)
            except ValueError as e:
                raise SystemExit(f"--explain-slots: {e}")
        if args.breaker > 0:
            # Breaker wraps the backend BEFORE the hook is built, so every
            # call path (inline hook, async lane) shares one breaker and a
            # dead endpoint fast-fails instead of stalling annotation
            # (explain/circuit.py; state surfaced via health()).
            from fraud_detection_tpu.explain import CircuitBreakerBackend

            backend = breaker = CircuitBreakerBackend(
                backend, failure_threshold=args.breaker,
                probe_interval=args.breaker_probe)
        if explain_service is not None:
            # The slot hook passes trace cids through the lane and turns
            # backend failures into accounted markers (every flagged row
            # explained or accounted — the slot lane's invariant).
            from fraud_detection_tpu.explain.slotserve import (
                make_slot_explain_hook)

            explain_hook = make_slot_explain_hook(
                backend, temperature=temp, max_tokens=args.explain_tokens)
        else:
            explain_hook = make_stream_explain_hook(
                backend, temperature=temp, max_tokens=args.explain_tokens)

    registry = None
    shadow = None
    lifecycle = None
    model_desc = args.model
    # Device-side featurization: True asks for the compiled Pallas path
    # (an error without a TPU); FRAUD_TPU_FEATURIZE_INTERPRET=1 forces
    # interpreter mode so CLI e2e tests can exercise the kernel on the CPU.
    featurize_device = False
    if args.featurize_device:
        featurize_device = ("interpret" if os.environ.get(
            "FRAUD_TPU_FEATURIZE_INTERPRET") == "1" else True)
    if args.registry is not None:
        from fraud_detection_tpu.registry import (HotSwapPipeline,
                                                  LifecycleController,
                                                  ModelRegistry, RegistryError,
                                                  RegistryIntegrityError,
                                                  ShadowScorer)

        registry = ModelRegistry(args.registry)
        try:
            mv, inner = registry.load(args.model_version,
                                      batch_size=args.batch_size)
        except (RegistryError, RegistryIntegrityError) as e:
            raise SystemExit(f"--registry: {e}")
        pipe = HotSwapPipeline(inner, version=mv.version)
        model_desc = f"registry:{args.registry}@{mv.name}"
        if args.shadow:
            shadow = ShadowScorer(max_queue=args.shadow_queue,
                                  sample=args.shadow_sample)
    else:
        from fraud_detection_tpu.featurize.device import (
            DeviceFeaturizeUnavailable)

        try:
            pipe = build_pipeline(args.model, args.batch_size, int8=args.int8,
                                  featurize_device=featurize_device,
                                  featurize_width=args.featurize_width)
        except DeviceFeaturizeUnavailable as e:
            raise SystemExit(f"--featurize-device: {e}")

    if args.mesh:
        # Mesh data-parallel scoring: shard micro-batches over every local
        # chip's data axis (parallel/serving.py). The engine's --batch-size
        # stays the GLOBAL micro-batch; each chip scores its 1/dp share.
        # On one device this constructs the plain pipeline (byte-identical
        # fallback), so --mesh is safe to leave on everywhere.
        from fraud_detection_tpu.parallel.serving import (MeshServingPipeline,
                                                          local_device_count)

        dp = local_device_count()
        pipe = MeshServingPipeline.from_pipeline(
            pipe, per_chip_batch=max(1, args.batch_size // max(1, dp)))
        model_desc = f"{model_desc} (mesh x{pipe.data_parallel or 1})"

    # Say which featurizer runs and where: the Pallas kernel, the C++
    # library, or — when g++/dlopen failed — the pure-Python tokenizer.
    if featurize_device:
        featurizer_desc = pipe.device_stats.featurize_path
    else:
        from fraud_detection_tpu.featurize import native as native_mod

        featurizer_desc = ("host-native" if native_mod.available()
                           else "host-python")
    stamp = device_stamp()
    model_desc = (f"{model_desc} featurizer={featurizer_desc} "
                  f"device={stamp['platform']}:{stamp['device_kind']}"
                  f"x{stamp['device_count']} compile_cache={compile_cache}")

    sched_ladder_costs = None
    if sched_config is not None:
        # Measure + pre-warm the padding-bucket ladder ONCE, before any
        # engine runs: candidate rungs are timed (compile excluded) and the
        # cost-aware geometry compiles here, off the hot path. A
        # HotSwapPipeline adopts ladder AND cost table for all future swap
        # candidates too (registry/hotswap.py configure_ladder). The
        # MEASURED buckets are pinned back into the config so every
        # per-worker scheduler built later agrees with the shapes the
        # pipeline actually compiled (governor floor, snapshot), and the
        # cost table is copied into each so health() carries it.
        import dataclasses

        from fraud_detection_tpu.sched import AdaptiveScheduler

        from fraud_detection_tpu.utils.tracing import device_trace

        prewarmer = AdaptiveScheduler(sched_config, args.batch_size)
        # --profile-dir: the prewarm/ladder measurement gets its own XLA
        # profiler capture (compiles + rung timing, off the hot path).
        with device_trace("prewarm", args.profile_dir):
            prewarmer.prewarm(pipe)
        sched_config = dataclasses.replace(sched_config,
                                           buckets=tuple(prewarmer.buckets))
        sched_ladder_costs = prewarmer.ladder_costs

    broker = None
    if args.kafka:
        if not kafka_available():
            raise SystemExit("confluent_kafka is not installed; cannot use --kafka")
        from fraud_detection_tpu.stream.kafka import KafkaConsumer, KafkaProducer

        make_clients = lambda: (KafkaConsumer([args.input_topic]), KafkaProducer())
        make_producer = KafkaProducer
        max_messages, idle = args.max_messages, None
    elif args.demo > 0:
        broker = InProcessBroker(num_partitions=args.partitions)
        if scenario is not None:
            # Scenario traffic (docs/scenarios.md): the seeded timeline
            # feeds the broker LIVE from the scenario-feeder thread while
            # the engine serves — shaped curves and campaign waves instead
            # of a uniform preload. Chaos (--chaos) composes on top.
            from fraud_detection_tpu.scenarios import (ScenarioClock,
                                                       TrafficFeeder,
                                                       compose)

            scenario_clock = ScenarioClock(
                scenario.seed, time_scale=args.scenario_time_scale)
            scenario_events = compose(scenario.traffic, scenario_clock)
            scenario_feeder = TrafficFeeder(
                broker.producer(), args.input_topic, scenario_events,
                scenario_clock)
            scenario_feeder.start()
            max_messages = args.max_messages
            gaps = [b - a for a, b in zip(
                [e.t for e in scenario_events],
                [e.t for e in scenario_events][1:])]
            idle = max(1.0, 2.0 * args.scenario_time_scale
                       * max(gaps, default=0.0))
        else:
            from fraud_detection_tpu.data import generate_corpus

            feeder = broker.producer()
            corpus = generate_corpus(n=min(args.demo, 2000), seed=123)
            for i in range(args.demo):
                d = corpus[i % len(corpus)]
                feeder.produce(args.input_topic,
                               json.dumps({"text": d.text, "id": i}).encode(),
                               key=str(i).encode())
            max_messages = (args.max_messages
                            if args.max_messages is not None else args.demo)
            idle = 1.0
        make_clients = lambda: (broker.consumer([args.input_topic], "serve-demo"),
                                broker.producer())
        make_producer = broker.producer
    else:
        raise SystemExit("choose --kafka or --demo N (no broker specified)")

    learn_loop = None
    if args.learn:
        # Closed learning loop (learn/, docs/online_learning.md): the
        # learn-lane thread joins feedback labels against the scored-row
        # window and publishes drift-corrected candidates into the SAME
        # registry the --watch lifecycle promotes from.
        from fraud_detection_tpu.learn import LearnConfig, LearnLoop

        feedback_topic = (args.learn_feedback_topic
                          or f"{args.input_topic}-feedback")
        if args.kafka:
            from fraud_detection_tpu.stream.kafka import KafkaConsumer

            feedback_consumer = KafkaConsumer([feedback_topic])
        else:
            feedback_consumer = broker.consumer([feedback_topic], "learn")
        learn_loop = LearnLoop(
            feedback_consumer=feedback_consumer, registry=registry,
            hotswap=pipe, shadow=shadow,
            config=LearnConfig(
                window=args.learn_window,
                min_labeled=args.learn_min_rows,
                error_threshold=args.learn_error_threshold,
                refresh_rounds=args.learn_rounds,
                interval_s=(args.learn_interval
                            if args.learn_interval > 0 else None)))

    fault_plan = None
    if args.chaos:
        # One plan shared by every incarnation: the single seeded rng stream
        # is what makes the fault schedule (and the demo) reproducible, and
        # the budget guarantees convergence once spent.
        from fraud_detection_tpu.stream.faults import FaultPlan

        fault_plan = FaultPlan.demo(seed=args.chaos_seed)
        inner_make_clients = make_clients
        make_clients = lambda: tuple(
            wrap(client) for wrap, client in
            zip((fault_plan.consumer, fault_plan.producer),
                inner_make_clients()))

    dlq_topic = None
    dlq_trackers: dict = {}
    if args.dlq:
        dlq_topic = args.dlq_topic or f"{args.output_topic}-dlq"

    # Unified metrics exporter (docs/observability.md): one registry,
    # health() mapped in as collectors, published by file and/or HTTP.
    metrics_registry = None
    metrics_server = None
    if args.metrics_file is not None or args.metrics_port is not None:
        from fraud_detection_tpu.obs import MetricsRegistry

        metrics_registry = MetricsRegistry()

    def start_metrics(healthz_fn=None):
        """Start the --metrics-file writer + --metrics-port endpoint once
        the collectors are registered; returns finish(). ``healthz_fn``
        wires the sentinel's readiness verdict into /healthz."""
        nonlocal metrics_server
        if metrics_registry is None:
            return lambda: None
        from fraud_detection_tpu.obs.export import (MetricsServer,
                                                    start_metrics_writer)

        if args.metrics_port is not None:
            metrics_server = MetricsServer(metrics_registry,
                                           args.metrics_port,
                                           healthz_fn=healthz_fn)
            print(f"metrics: http://127.0.0.1:{metrics_server.port}/metrics",
                  flush=True)
        finish_file = start_metrics_writer(args.metrics_file,
                                           args.metrics_interval,
                                           metrics_registry)

        def finish():
            finish_file()
            if metrics_server is not None:
                metrics_server.close()

        return finish

    # Row tracing (obs/trace.py): one tracer per worker, shared across a
    # worker's supervised incarnations so chains survive restarts (same
    # sharing contract as the DLQ poison tracker and the scheduler).
    trace_per_worker: dict = {}

    def rowtrace_for(worker: int):
        if not args.trace:
            return None
        from fraud_detection_tpu.obs import RowTracer

        tr = trace_per_worker.get(worker)
        if tr is None:
            record = args.trace_record is not None
            tr = trace_per_worker[worker] = RowTracer(
                worker=f"w{worker}",
                # Record mode: keep everything (sample 1.0 + row census)
                # in a ring sized for a whole demo run, so the dumped
                # recording is complete and exactly replayable.
                sample=1.0 if record else args.trace_sample,
                capacity=65536 if record else 4096,
                record_rows=record)
        return tr

    # Sentinel alerting (obs/sentinel/, docs/observability.md): one
    # sentinel per worker over a CHAIN-CUMULATIVE health source (counters
    # survive supervised restarts; supervisor.restarts feeds the
    # restart-churn rule), sharing one incident dir — all driven by the
    # single "sentinel" thread. The fleet path wires its own coordinator-
    # level sentinel through Fleet.in_process instead.
    sentinel_per_worker: dict = {}
    sentinel_sources: dict = {}

    def sentinel_for(worker: int):
        if alert_rules is None or args.fleet > 0:
            return None
        from fraud_detection_tpu.obs.sentinel import (ChainedHealthSource,
                                                      IncidentRecorder,
                                                      Sentinel)

        s = sentinel_per_worker.get(worker)
        if s is None:
            source = sentinel_sources[worker] = ChainedHealthSource()
            recorder = (IncidentRecorder(args.incident_dir,
                                         rowtrace=rowtrace_for(worker))
                        if args.incident_dir is not None else None)
            s = sentinel_per_worker[worker] = Sentinel(
                source, alert_rules, recorder=recorder,
                worker=f"w{worker}")
        return s

    def sentinels_healthz():
        """Aggregate readiness across every worker's sentinel: not ready
        while ANY critical alert fires anywhere."""
        firing = []
        for s in sentinel_per_worker.values():
            firing.extend(s.critical_firing())
        return (not firing, sorted(set(firing)))

    if explain_service is not None and args.trace and args.workers == 1:
        # Completed explanations land per-row "explain" spans (slot id +
        # admit wait) on the single worker's chains. Multi-worker runs keep
        # lane-level spans only: one service serves every worker, and a
        # row's span must not land on another worker's tracer.
        explain_service.set_rowtrace(rowtrace_for(0))

    if args.fleet > 0:
        # Fleet serving lane (docs/fleet.md): N partition-owning workers
        # under the lease coordinator, health on the fleet bus, shedding on
        # the global backlog watermark. Drains until the group's committed
        # lag clears, then exits with the merged fleet stats.
        from fraud_detection_tpu.fleet import Fleet

        fleet_sentinel_kw = {}
        if args.alerts:
            # Coordinator-level fleet rules + per-worker engine sentinels
            # riding the bus (docs/observability.md "Fleet alerting").
            from fraud_detection_tpu.obs.sentinel import (IncidentRecorder,
                                                          fleet_rule_pack)

            fleet_sentinel_kw = dict(
                sentinel_rules=(alert_rules if args.alert_rules is not None
                                else fleet_rule_pack()),
                sentinel_recorder=(
                    IncidentRecorder(args.incident_dir)
                    if args.incident_dir is not None else None))
        fleet = Fleet.in_process(
            broker, pipe, args.input_topic, args.output_topic, args.fleet,
            batch_size=args.batch_size, max_wait=args.max_wait,
            pipeline_depth=args.pipeline_depth,
            async_dispatch=args.async_dispatch,
            sched_config=sched_config, dlq_topic=dlq_topic,
            health_file=args.fleet_health_file,
            candidates=args.fleet_candidates,
            autoscale=autoscale_config,
            trace=args.trace, trace_sample=args.trace_sample,
            **fleet_sentinel_kw)
        if metrics_registry is not None:
            metrics_registry.add_collector("fleet", fleet.fleet_health)
        finish_metrics = start_metrics(
            healthz_fn=(fleet.sentinel.healthz
                        if fleet.sentinel is not None else None))
        print(f"serving: model={model_desc} in={args.input_topic} "
              f"out={args.output_topic} batch={args.batch_size} "
              f"fleet={args.fleet} partitions={args.partitions}", flush=True)
        try:
            out = fleet.run(idle_timeout=1.0)
        finally:
            finish_metrics()
        out["device"] = stamp
        print(json.dumps(out))
        n_out = broker.topic_size(args.output_topic)
        print(f"classified messages on {args.output_topic}: {n_out}")
        return 1 if out["errors"] else 0

    engines_built = []   # LIVE engines only — replaced ones are harvested
    # Aggregated lane counters of engines already replaced+closed: replaced
    # engines are dropped from engines_built (holding every dead incarnation
    # — consumer/producer references included — for the process lifetime was
    # a slow leak under --kafka --supervise N; ADVICE round 5), so their
    # contribution to the exit stats lives here instead.
    annotations_harvested = {"submitted": 0, "annotated": 0, "dropped": 0,
                             "drop_records": 0, "backend_errors": 0}
    sched_per_worker: dict = {}

    def make_engine(replacing=None, worker=0):
        """Build an engine; ``replacing`` is the previous incarnation on a
        supervised-restart path — its async lane is stopped first (briefly
        drained) so restarts don't accumulate worker threads, each pinning
        a producer; its lane counters are harvested into the exit aggregate
        and the dead engine is dropped from ``engines_built``. The DLQ
        poison tracker is shared across one WORKER's incarnations (so
        counts survive restarts) but never across workers: they own
        disjoint partitions, and a cross-thread dict would race a worker's
        cleanup iteration against another's inserts. The adaptive
        scheduler follows the same per-worker sharing: one scheduler per
        worker keeps the SLO window and EWMAs warm across supervised
        restarts (incarnations of one worker run sequentially, so the
        single-driver contract holds), never across workers (collect/admit
        state is single-driver by contract)."""
        if replacing is not None:
            replacing.close_annotations(timeout=5.0)
            harvested = replacing.annotation_stats()
            if harvested:
                for k in annotations_harvested:
                    annotations_harvested[k] += harvested.get(k, 0)
            try:
                engines_built.remove(replacing)
            except ValueError:
                pass
        dlq_attempts = (dlq_trackers.setdefault(worker, {})
                        if args.dlq else None)
        scheduler = None
        if sched_config is not None:
            from fraud_detection_tpu.sched import AdaptiveScheduler

            scheduler = sched_per_worker.get(worker)
            if scheduler is None:
                scheduler = AdaptiveScheduler(sched_config, args.batch_size)
                # The startup measurement's per-rung cost table (None when
                # measurement was skipped) — workers report it in health().
                scheduler.ladder_costs = (dict(sched_ladder_costs)
                                          if sched_ladder_costs else None)
                sched_per_worker[worker] = scheduler
        c, p = make_clients()
        e = StreamingClassifier(pipe, c, p, args.output_topic,
                                batch_size=args.batch_size, max_wait=args.max_wait,
                                pipeline_depth=args.pipeline_depth,
                                explain_batch_fn=explain_hook,
                                explain_async=args.explain_async,
                                annotations_topic=args.annotations_topic,
                                annotations_producer=(
                                    make_producer() if args.explain_async
                                    else None),
                                dlq_topic=dlq_topic,
                                dlq_max_attempts=args.dlq_max_attempts,
                                dlq_attempts=dlq_attempts,
                                breaker=breaker,
                                explain_service=explain_service,
                                shadow=shadow,
                                learn=learn_loop,
                                scheduler=scheduler,
                                async_dispatch=args.async_dispatch,
                                rowtrace=rowtrace_for(worker),
                                sentinel=sentinel_for(worker))
        engines_built.append(e)
        source = sentinel_sources.get(worker)
        if source is not None:
            # Fold the replaced incarnation's counters into the chain-
            # cumulative alerting source (obs/sentinel/engine.py).
            source.attach(e)
        return e

    def start_alerting():
        """Build every worker's sentinel and start the ONE "sentinel"
        evaluation thread; returns finish() (no-op without --alerts)."""
        if alert_rules is None:
            return lambda: None
        from fraud_detection_tpu.obs.sentinel import start_sentinel

        return start_sentinel([sentinel_for(i)
                               for i in range(args.workers)],
                              args.alert_interval)

    def alerts_out():
        """The exit-stats 'alerts' block: one snapshot (single worker) or
        a per-worker list."""
        if alert_rules is None or not sentinel_per_worker:
            return None
        snaps = [sentinel_per_worker[w].snapshot()
                 for w in sorted(sentinel_per_worker)]
        return snaps[0] if args.workers == 1 else snaps

    def finish_annotations():
        """Drain every LIVE engine's async lane; aggregated counters for
        the stats JSON include the already-harvested replaced incarnations
        (None when running inline). The slotserve service (if any) closes
        AFTER the lanes drained — lane workers block inside explain_rows,
        so lane-drained implies slot-lane idle."""
        if not args.explain_async:
            return None
        agg = dict(annotations_harvested)
        for e in engines_built:
            e.close_annotations(timeout=30.0)
            s = e.annotation_stats() or {}
            for k in agg:
                agg[k] += s.get(k, 0)
        if explain_service is not None:
            explain_service.close(timeout=30.0)
        return agg

    watch_stop = None
    if args.watch:
        # One watcher for the whole process (all workers share ``pipe``, so
        # a swap lands everywhere at once): poll the registry, verify + pre-
        # warm new versions, swap or stage+judge per the flags. Runs on a
        # daemon thread; tick() failures log and never kill serving.
        lifecycle = LifecycleController(
            registry, pipe, shadow=shadow, policy=promote_policy,
            batch_size=args.batch_size,
            health_fn=lambda: (engines_built[-1].health()
                               if engines_built else None),
            on_transition=(learn_loop.on_transition
                           if learn_loop is not None else None))
        if learn_loop is not None:
            learn_loop.bind_controller(lifecycle)
        _watch_thread, watch_stop = lifecycle.run_in_thread(
            args.watch_interval)

    def finish_lifecycle():
        """Stop the learn lane + watcher + shadow worker; returns the
        audit-event list for the stats JSON (None when not serving from a
        registry). The learn lane closes FIRST (a retrain mid-flight
        finishes and its publish is still picked up by the final watcher
        state below)."""
        if learn_loop is not None:
            learn_loop.close(timeout=30.0)
        if watch_stop is not None:
            watch_stop.set()
            _watch_thread.join(timeout=5.0)
        if shadow is not None:
            shadow.close(timeout=5.0)
        if registry is None:
            return None
        out = {"active_version": pipe.active_version,
               "staged_version": pipe.staged_version,
               "swaps": pipe.swaps,
               "events": lifecycle.events if lifecycle is not None else []}
        if learn_loop is not None:
            out["learn"] = learn_loop.snapshot()
        return out

    print(f"serving: model={model_desc} in={args.input_topic} out={args.output_topic} "
          f"batch={args.batch_size} workers={args.workers}", flush=True)
    if args.workers > 1:
        # Horizontal scale-out: N engines, ONE group — the broker (in-process
        # or Kafka) deals each a disjoint partition subset; a worker's exit
        # rebalances ONLY its partitions to the survivors (balanced-sticky
        # assignor — uninvolved survivors keep theirs, so their in-flight
        # commits are not fenced and the merged counts carry no rebalance
        # duplicates on the common exit path). Workers share the
        # pipeline (scoring is jitted + thread-safe; the engine serializes
        # its own consumer). --max-messages was already rejected up top.
        from fraud_detection_tpu.stream.engine import (StreamStats,
                                                       _merge_stats,
                                                       run_supervised)

        results = [None] * args.workers
        errors = [None] * args.workers
        live = [None] * args.workers     # current engine, for Ctrl-C stop
        finish_health = start_health_writer(
            args.health_file, args.health_interval, lambda: live, fault_plan)
        if metrics_registry is not None:
            # One collector, every live worker's full health() — flattened
            # with an index label per worker at render time.
            metrics_registry.add_collector(
                "engine", lambda: [e.health() for e in live if e is not None])
        finish_metrics = start_metrics(
            healthz_fn=sentinels_healthz if args.alerts else None)
        finish_sentinel = start_alerting()
        from fraud_detection_tpu.obs.export import start_profile_window

        finish_profile = start_profile_window(
            args.profile_dir, args.profile_batches,
            lambda: sum(e.stats.batches for e in live if e is not None))
        # Cooperative shutdown: KeyboardInterrupt only reaches the MAIN
        # thread, so a supervised worker in its backoff sleep would rebuild
        # and keep consuming after the operator's Ctrl-C stopped its dead
        # incarnation. The event closes that race — an engine built after
        # shutdown is stopped before it runs, so its run() returns
        # immediately and the supervisor unwinds through its own
        # close-the-consumer path.
        shutdown = threading.Event()

        # Demo path (in-process broker, topic pre-loaded): construct every
        # worker's engine BEFORE any engine consumes — group members join at
        # consumer CONSTRUCTION, so this settles the group at its final
        # generation first. Staggered joins let worker 0 drain the whole
        # topic in one batch and then have its commit correctly fenced by
        # the late joiners' rebalance: at-least-once duplicates a settled
        # group never produces (Kafka deployments avoid the same pathology
        # by starting all consumers before traffic). --kafka keeps lazy
        # construction INSIDE the supervisor — client-construction failures
        # must stay retryable incarnations (engine.py run_supervised), and
        # one worker's failure must not abort its siblings.
        prebuilt = [make_engine(worker=i) if broker is not None else None
                    for i in range(args.workers)]

        def run_worker(i: int) -> None:
            def make():
                if prebuilt[i] is not None:
                    live[i], prebuilt[i] = prebuilt[i], None
                else:
                    live[i] = make_engine(replacing=live[i], worker=i)
                if shutdown.is_set():
                    live[i].stop()
                return live[i]

            try:
                if args.supervise > 0:
                    results[i] = run_supervised(
                        make, max_restarts=args.supervise,
                        max_messages=None, idle_timeout=idle)
                else:
                    engine = make()
                    try:
                        results[i] = engine.run(max_messages=None,
                                                idle_timeout=idle)
                    finally:
                        engine.consumer.close()
            except BaseException as e:  # noqa: BLE001 — surfaced via exit code
                errors[i] = e
                # Immediately, not at shutdown: with --kafka the survivors
                # run indefinitely and a silent 1/N capacity loss would
                # otherwise only surface at Ctrl-C.
                print(f"worker {i} died: {e!r} (survivors keep their "
                      f"partitions; exit code will be nonzero)",
                      file=sys.stderr, flush=True)

        threads = [threading.Thread(target=run_worker, args=(i,), daemon=True)
                   for i in range(args.workers)]
        for t in threads:
            t.start()
        try:
            for t in threads:
                t.join()
        except KeyboardInterrupt:
            # Graceful drain: stop every live engine (its run() returns and
            # the worker's close/supervisor path leaves the group — killing
            # daemon threads abruptly would strand partitions on zombie
            # members until the session timeout).
            shutdown.set()
            for engine in live:
                if engine is not None:
                    engine.stop()
            for t in threads:
                t.join(timeout=30)
        total = StreamStats()
        for r in results:
            if r is not None:
                _merge_stats(total, r)
        done = [r for r in results if r is not None]
        # _merge_stats SUMS elapsed (right for run_supervised's sequential
        # incarnations, wrong for parallel threads — it would report the
        # aggregate rate divided by N); workers overlap, so wall-clock is
        # the slowest worker. restarts isn't merged there either (the
        # supervisor increments it outside _merge_stats).
        total.elapsed = max((r.elapsed for r in done), default=0.0)
        total.restarts = sum(r.restarts for r in done)
        merged = {**total.as_dict(), "device": stamp,
                  "workers": args.workers,
                  "per_worker_processed": [r.processed if r else None
                                           for r in results],
                  "health": [e.health() if e is not None else None
                             for e in live]}
        if fault_plan is not None:
            merged["chaos"] = fault_plan.report()
        annotations = finish_annotations()
        if annotations is not None:
            merged["annotations"] = annotations
        if explain_service is not None:
            # Post-drain snapshot: the in-run health captures above may
            # predate the final lane drain.
            merged["explain"] = explain_service.snapshot()
        lifecycle_out = finish_lifecycle()
        if lifecycle_out is not None:
            merged["lifecycle"] = lifecycle_out
        profile = finish_profile()
        if profile is not None:
            merged["profile"] = profile
        finish_sentinel()
        alerts = alerts_out()
        if alerts is not None:
            merged["alerts"] = alerts
        finish_metrics()
        finish_health()
        print(json.dumps(merged))
        if args.demo:
            n_out = broker.topic_size(args.output_topic)
            print(f"classified messages on {args.output_topic}: {n_out}")
        failures = [e for e in errors if e is not None]
        if failures:
            print(f"{len(failures)} worker(s) failed; first: {failures[0]!r}",
                  file=sys.stderr)
            return 1
        return 0
    finish_health = start_health_writer(
        args.health_file, args.health_interval,
        lambda: engines_built[-1:], fault_plan)
    if metrics_registry is not None:
        metrics_registry.add_collector(
            "engine", lambda: (engines_built[-1].health()
                               if engines_built else None))
    finish_metrics = start_metrics(
        healthz_fn=sentinels_healthz if args.alerts else None)
    finish_sentinel = start_alerting()
    from fraud_detection_tpu.obs.export import start_profile_window

    finish_profile = start_profile_window(
        args.profile_dir, args.profile_batches,
        lambda: engines_built[-1].stats.batches if engines_built else 0)
    gave_up = None
    if args.supervise > 0:
        # The supervisor builds and closes every consumer/producer itself
        # (including on Ctrl-C, where it returns the aggregated stats).
        from fraud_detection_tpu.stream.engine import StreamStats, run_supervised

        try:
            stats = run_supervised(
                lambda: make_engine(
                    replacing=engines_built[-1] if engines_built else None),
                max_restarts=args.supervise,
                max_messages=max_messages, idle_timeout=idle)
        except Exception as e:  # noqa: BLE001 — give-up surfaced as exit code
            # The supervisor exhausted max_restarts: report the partial
            # progress it attached plus final health, exit non-zero — an
            # orchestrator reading exit codes must never see success on a
            # stream that died (mirrors the multi-worker path's contract).
            gave_up = e
            stats = getattr(e, "supervisor_stats", None) or StreamStats()
            print(f"supervised run gave up after {args.supervise} restarts: "
                  f"{e!r} (offsets stay at the last commit; a restarted "
                  f"serve resumes there)", file=sys.stderr, flush=True)
    else:
        engine = make_engine()
        try:
            stats = engine.run(max_messages=max_messages, idle_timeout=idle)
        except KeyboardInterrupt:
            engine.stop()
            stats = engine.stats
        finally:
            engine.consumer.close()
    out = stats.as_dict()
    out["device"] = stamp
    out["featurizer"] = featurizer_desc
    if engines_built:
        # The run is over and the dispatch lane has stopped: whether the
        # last engine's raw-JSON encode and C++ frame assembly stayed on
        # (None = never asked — e.g. a device-featurized pipeline).
        out["fast_paths"] = {"native_json": engines_built[-1]._json_fast,
                             "native_frames": engines_built[-1]._frames_ok}
    out["health"] = engines_built[-1].health() if engines_built else None
    if fault_plan is not None:
        out["chaos"] = fault_plan.report()
    annotations = finish_annotations()
    if annotations is not None:
        out["annotations"] = annotations
    if explain_service is not None:
        # Post-drain snapshot (the health block above may predate it).
        out["explain"] = explain_service.snapshot()
    lifecycle_out = finish_lifecycle()
    if lifecycle_out is not None:
        out["lifecycle"] = lifecycle_out
    profile = finish_profile()
    if profile is not None:
        out["profile"] = profile
    finish_sentinel()
    alerts = alerts_out()
    if alerts is not None:
        out["alerts"] = alerts
    finish_metrics()
    finish_health()
    if args.trace_record is not None and trace_per_worker:
        # Atomic JSONL dump of the ring at exit (scenarios/record.py):
        # the run is now a replayable regression input.
        from fraud_detection_tpu.scenarios import dump_tracer

        header = dump_tracer(trace_per_worker[0], args.trace_record,
                             now=time.time())
        out["trace_record"] = {"path": args.trace_record,
                               "spans": header["spans"],
                               "complete": header["complete"]}
    scenario_failed = False
    if scenario is not None:
        scenario_feeder.join(timeout=120.0)
        out["scenario"] = _judge_scenario(
            scenario, scenario_events, scenario_feeder, broker, args, out,
            trace_per_worker)
        scenario_failed = not out["scenario"]["ok"]
        if scenario_failed:
            print(f"scenario {scenario.name!r} FAILED its SLO gates "
                  f"(exit 4): "
                  f"{[v['name'] for v in out['scenario']['verdicts'] if not v['ok'] and not v['skipped']]}",
                  file=sys.stderr, flush=True)
    if args.demo and scenario is None:
        out["demo"] = _demo_census(broker, args)
    print(json.dumps(out))
    if args.demo:
        n_out = broker.topic_size(args.output_topic)
        print(f"classified messages on {args.output_topic}: {n_out}")
    if gave_up is not None:
        return 3
    return 4 if scenario_failed else 0


if __name__ == "__main__":
    sys.exit(main())
