#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

Drives what a user pays for — a trained classifier, the Kafka-shaped stream
scored by it, flagged rows explained by the on-pod LLM — once, end to end,
at the full width of the shapes the repo ships, through the entry points a
user would call (``app.train.main``, ``app.serve.main``, and the library
surface ``serve`` itself wires for the explain lane), sequentially in ONE
process (a chip belongs to one process at a time; each phase frees its
buffers before the next). Then every Pallas kernel runs COMPILED against
its in-repo reference, and on a host with four or more chips the mesh paths
run too. Weights are random from a seed; depth may be cut, width never.

The command takes no option and reads no environment variable that lets it
pass without a TPU: the device gate runs first and exits non-zero naming
the platform. A failed phase is an exception and a non-zero exit, never a
field. The last two lines of stdout are one JSON object each: the report
(device, each phase's status and counts, set-up time, versions, ``"claim":
null``), then the verdict the driver reads, with exactly these keys::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The phases are plain functions over a ``Sizes`` value, so
tests/test_chip_smoke.py calls them at toy sizes on the CPU.
"""

from __future__ import annotations

import contextlib
import gc
import io
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np


class SmokeFailure(AssertionError):
    """A phase produced something wrong."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Sizes:
    """Every shape the smoke runs at. The defaults are the shipped widths:
    the artifact's 10,000 hashed features at depth 5, bench.py's headline
    serving shape (batch 4096, pipeline depth 4, 20,000 preloaded messages),
    and the only LLM shape the repo supports (bench.py GEMMA2B_HF_CONFIG)."""

    # train
    n_corpus: int = 1600
    num_features: int = 10000
    max_depth: int = 5
    n_rounds: int = 3
    # serve
    batch: int = 4096
    pipeline_depth: int = 4
    lr_msgs: int = 20000
    xgb_msgs: int = 8192
    sample: int = 256             # seeded rows checked against NumPy
    # explain
    llm: dict = field(default_factory=lambda: dict(
        vocab_size=256000, d_model=2048, n_heads=8, n_layers=18, d_ff=16384,
        n_kv_heads=1, head_dim_override=256, activation="gelu",
        embed_scale=2048 ** 0.5, max_seq=2048))
    llm_dtype: str = "bfloat16"
    slots: int = 8
    new_tokens: int = 64
    explain_msgs: int = 96        # a third are scams: >= 16 flagged rows
    min_flagged: int = 16
    long_prompt: int = 640        # >= 512 tokens: the flash kernel's regime
    flash_t: int = 512
    # kernels (bench.py pallas_parity_check has the histogram shapes)
    hist: tuple = (4096, 256, 32, 8, 3)      # rows, features, bins, nodes, stats
    attn: tuple = (1, 2048, 8, 1, 256)       # B, T, H, Hkv, head
    feat_rows: int = 256
    feat_width: int = 2048
    feat_tokens: int = 256
    # mesh (>= 4 chips): full LLM width, depth cut
    mesh_llm_layers: int = 2
    mesh_rows: int = 2048


FULL = Sizes()


# ---------------------------------------------------------------------------
# device gate and set-up accounting
# ---------------------------------------------------------------------------

def gate() -> dict:
    """Exit non-zero at once unless JAX's first device is a TPU."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX reports platform {platform!r} "
              f"({jax.devices()[0].device_kind}); nothing was run",
              file=sys.stderr)
        raise SystemExit(2)
    from fraud_detection_tpu.utils.device import device_stamp

    return device_stamp()


class CompileCounter:
    """Counts compile requests and persistent-cache hits (jax.monitoring):
    requests - hits is what was compiled fresh in this process."""

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self) -> tuple:
        return self.requests, self.hits

    def since(self, mark: tuple) -> dict:
        req, hits = self.requests - mark[0], self.hits - mark[1]
        return {"compile_requests": req, "cache_hits": hits,
                "fresh_compiles": req - hits}


def verdict(stamp: dict) -> dict:
    """The last stdout line of a run that passed: exactly the keys the
    driver's chip check reads, the device as JAX reports it."""
    return {"ok": True,
            "device": {"platform": stamp["platform"],
                       "kind": stamp["device_kind"],
                       "count": stamp["device_count"]}}


def versions() -> dict:
    import jax
    import jaxlib

    out = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        import libtpu

        out["libtpu"] = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        out["libtpu"] = None
    return out


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _call_cli(main, argv) -> tuple:
    """Run a CLI ``main(argv)`` in-process, echoing its stdout; returns
    ``(rc, stdout_lines)``."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    text = buf.getvalue()
    sys.stdout.write(text)
    sys.stdout.flush()
    return rc, text.splitlines()


def _stats_line(lines) -> dict:
    """serve prints its stats as ONE JSON line; take the last such line."""
    for line in reversed(lines):
        if line.startswith("{"):
            return json.loads(line)
    raise SmokeFailure("serve printed no stats JSON line")


def _seeded_texts(n: int, seed: int):
    from fraud_detection_tpu.data import generate_corpus

    return [d.text for d in generate_corpus(n=n, seed=seed)]


def _sigmoid(margin: np.ndarray) -> np.ndarray:
    """1 / (1 + e^-m) without overflowing exp on a large negative margin."""
    e = np.exp(-np.abs(margin))
    return np.where(margin >= 0, 1.0 / (1.0 + e), e / (1.0 + e))


def _numpy_probabilities(pipe, texts) -> np.ndarray:
    """p(class 1) for ``texts`` in host NumPy: the host featurizer's ids and
    counts, then the model's arithmetic — a dot for LR, a node walk for
    boosted trees. No JAX below the featurizer."""
    from fraud_detection_tpu.models.linear import LogisticRegression

    enc = pipe.featurizer.encode(texts)
    ids = np.asarray(enc.ids).astype(np.int64)
    counts = np.asarray(enc.counts).astype(np.float32)
    if isinstance(pipe.model, LogisticRegression):
        w = np.asarray(pipe.fused_model.weights, np.float32)
        b = np.float32(np.asarray(pipe.fused_model.intercept))
        margin = (w[ids] * counts).sum(axis=1, dtype=np.float32) + b
        return _sigmoid(margin.astype(np.float64))
    ens = pipe.model
    require(ens.kind == "xgboost", f"unexpected tree kind {ens.kind!r}")
    idf = np.asarray(pipe.featurizer.idf_array(), np.float32)
    feature, threshold = np.asarray(ens.feature), np.asarray(ens.threshold)
    left, right = np.asarray(ens.left), np.asarray(ens.right)
    leaf, weights = np.asarray(ens.leaf), np.asarray(ens.tree_weights)
    margin = np.full(len(texts), ens.bias, np.float64)
    for r in range(len(texts)):
        dense = np.zeros(idf.shape[0], np.float32)
        np.add.at(dense, ids[r], counts[r])
        dense *= idf
        for t in range(feature.shape[0]):
            node = 0
            while left[t, node] >= 0:
                go_left = dense[feature[t, node]] <= threshold[t, node]
                node = left[t, node] if go_left else right[t, node]
            margin[r] += weights[t] * leaf[t, node, 0]
    return _sigmoid(margin)


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def phase_train(workdir: str, sizes: Sizes, stamp: dict) -> dict:
    """``train`` at the shipped artifact's width; leaves servable LR and XGB
    checkpoints in ``workdir``."""
    from fraud_detection_tpu.app import train
    from fraud_detection_tpu.models.train_trees import TreeTrainConfig
    from fraud_detection_tpu.utils.device import pallas_interpret

    lr_dir, xgb_dir = (os.path.join(workdir, m) for m in ("lr", "xgb"))
    report = os.path.join(workdir, "train_metrics.json")
    rc, _ = _call_cli(train.main, [
        "--data", "synthetic", "--n", str(sizes.n_corpus),
        "--models", "lr,xgb", "--num-features", str(sizes.num_features),
        "--max-depth", str(sizes.max_depth), "--n-rounds", str(sizes.n_rounds),
        "--save", f"lr={lr_dir}", "--save", f"xgb={xgb_dir}",
        "--json", "--metrics-out", report])
    require(rc == 0, f"train exited {rc}")
    with open(report) as f:
        rep = json.load(f)
    meta, metrics = rep["meta"], rep["metrics"]
    on_chip = stamp["platform"] == "tpu"
    for key in ("platform", "device_kind", "device_count"):
        require(meta[key] == stamp[key],
                f"train ran on {meta[key]!r}, the gate saw {stamp[key]!r}")
    # The tree trainers' kernels compile exactly when there is a TPU.
    require(TreeTrainConfig().use_pallas is on_chip
            and meta["use_pallas"] is on_chip
            and pallas_interpret() is (not on_chip),
            f"histogram/split kernels: use_pallas={meta['use_pallas']} "
            f"interpret={pallas_interpret()} on {stamp['platform']}")
    acc = {}
    for model in ("lr", "xgb"):
        test = metrics[model]["Test"]
        require(all(v is None or np.isfinite(v) for k, v in test.items()
                    if k != "confusion"), f"{model}: non-finite metric {test}")
        require(test["accuracy"] >= 0.8,
                f"{model}: test accuracy {test['accuracy']:.3f} < 0.8 on the "
                "synthetic corpus")
        acc[model] = round(test["accuracy"], 4)
        require(os.path.isdir(lr_dir if model == "lr" else xgb_dir),
                f"{model}: no checkpoint written")
    return {"ok": True, "models": {"lr": lr_dir, "xgb": xgb_dir},
            "use_pallas": meta["use_pallas"], "test_accuracy": acc,
            "train_seconds": meta["train_seconds"]}


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def phase_serve(model_dir: str, n_msgs: int, sizes: Sizes, stamp: dict,
                *, mesh: bool = False) -> dict:
    """``serve --demo`` from a saved checkpoint at the bench's headline
    shape, then a seeded sample scored against NumPy."""
    from fraud_detection_tpu.app import serve
    from fraud_detection_tpu.models.pipeline import (ServingPipeline,
                                                     donation_effective)

    argv = ["--model", model_dir, "--demo", str(n_msgs),
            "--batch-size", str(sizes.batch),
            "--pipeline-depth", str(sizes.pipeline_depth),
            "--async-dispatch", "--dlq"]
    rc, lines = _call_cli(serve.main, argv + (["--mesh"] if mesh else []))
    require(rc == 0, f"serve exited {rc}")
    out = _stats_line(lines)
    dev, demo = out["health"]["device"], out["demo"]
    require(out["device"] == stamp and all(dev[k] == stamp[k] for k in stamp),
            f"serve ran on {out['device']}, the gate saw {stamp}")
    require(out["processed"] == n_msgs == demo["out"] and demo["keys_exact"],
            f"fed {n_msgs}: processed {out['processed']}, out {demo['out']}, "
            f"keys_exact {demo['keys_exact']}")
    require(demo["dlq"] == 0 and out["dead_lettered"] == 0
            and out["malformed"] == 0 and out["shed"] == 0,
            f"DLQ not empty: {demo} / {out['dead_lettered']}")
    require(out["restarts"] == 0, f"supervised restarts: {out['restarts']}")
    require(dev["async_dispatch"] and dev["uploads_per_batch"] == 1.0,
            f"uploads_per_batch {dev['uploads_per_batch']} != 1.0")
    require(dev["model_pins"] >= 1, f"model_pins {dev['model_pins']}")
    # Staging buffers are donated exactly where the runtime consumes a
    # donation (the pipeline's own probe): every chunk, or none.
    consumed = donation_effective()
    require((dev["donation_hits"] > 0) is consumed,
            f"donation_hits {dev['donation_hits']} over {out['batches']} "
            f"batches with donation_effective() == {consumed}")
    require(dev["featurize_path"] == "host"
            and out["featurizer"] == "host-native"
            and out["fast_paths"] == {"native_json": True,
                                      "native_frames": True},
            f"native fast paths fell off: {out['featurizer']} "
            f"{out['fast_paths']}")
    if mesh:
        require(dev["mesh_devices"] == stamp["device_count"],
                f"mesh_devices {dev['mesh_devices']} != {stamp['device_count']}")

    pipe = ServingPipeline.from_checkpoint(model_dir, batch_size=sizes.sample)
    texts = _seeded_texts(sizes.sample, seed=2024)
    got = pipe.predict(texts)
    want = _numpy_probabilities(pipe, texts)
    diff = float(np.max(np.abs(got.probabilities - want)))
    require(np.isfinite(got.probabilities).all() and diff < 1e-4,
            f"probabilities differ from the NumPy reference by {diff:.3g}")
    require(0 < int(got.labels.sum()) < len(texts),
            f"degenerate labels: {int(got.labels.sum())} of {len(texts)} flagged")
    return {"ok": True, "messages": n_msgs, "batches": out["batches"],
            "uploads_per_batch": dev["uploads_per_batch"],
            "donation_hits": dev["donation_hits"],
            "donation_consumed": consumed,
            "model_pins": dev["model_pins"],
            "mesh_devices": dev["mesh_devices"],
            "max_abs_diff_vs_numpy": diff,
            "sample_probabilities": [float(p) for p in got.probabilities]}


# ---------------------------------------------------------------------------
# phase: explain
# ---------------------------------------------------------------------------

def _llm_config(sizes: Sizes, **overrides):
    import jax.numpy as jnp

    from fraud_detection_tpu.models.llm import TransformerConfig

    return TransformerConfig(**{**sizes.llm, "dtype": getattr(jnp, sizes.llm_dtype),
                                **overrides})


def phase_explain(lr_dir: str, sizes: Sizes) -> dict:
    """The explain lane as ``serve`` wires it (app/serve.py "--explain-slots"):
    a paged ``SlotServeService`` attached to a ``StreamingClassifier`` by
    ``make_slot_explain_hook`` with ``explain_async=True`` — every flagged
    row explained or accounted. Then the fixed-batch backend on a long
    prompt, and the flash kernel's logits against the XLA attention path."""
    import jax
    import jax.numpy as jnp

    from fraud_detection_tpu.data import generate_corpus
    from fraud_detection_tpu.explain import OnPodBackend
    from fraud_detection_tpu.explain.slotserve import (SlotServeService,
                                                       make_slot_explain_hook)
    from fraud_detection_tpu.models import llm
    from fraud_detection_tpu.models.pipeline import ServingPipeline
    from fraud_detection_tpu.stream import InProcessBroker, StreamingClassifier

    cfg = _llm_config(sizes)
    t0 = time.perf_counter()
    lm = llm.LanguageModel.init_random(cfg, seed=0)
    jax.block_until_ready(lm.params)
    init_s = time.perf_counter() - t0
    n_params = int(sum(np.prod(a.shape) for a in lm.params.values()))

    # A stream with a known share of scams, keyed like serve --demo.
    corpus = generate_corpus(n=4 * sizes.explain_msgs, seed=77)
    scams = [d.text for d in corpus if d.label == 1]
    benign = [d.text for d in corpus if d.label == 0]
    texts = [scams[i // 3 % len(scams)] if i % 3 == 0
             else benign[i % len(benign)] for i in range(sizes.explain_msgs)]
    broker = InProcessBroker(num_partitions=3)
    feeder = broker.producer()
    for i, text in enumerate(texts):
        feeder.produce("in", json.dumps({"text": text, "id": i}).encode(),
                       key=str(i).encode())

    pipe = ServingPipeline.from_checkpoint(lr_dir, batch_size=sizes.batch)
    svc = SlotServeService(lm, slots=sizes.slots,
                           max_new_tokens=sizes.new_tokens)
    try:
        engine = StreamingClassifier(
            pipe, broker.consumer(["in"], "smoke-explain"), broker.producer(),
            "out", batch_size=sizes.batch, pipeline_depth=sizes.pipeline_depth,
            explain_batch_fn=make_slot_explain_hook(
                svc, temperature=0.0, max_tokens=sizes.new_tokens),
            explain_async=True, annotations_producer=broker.producer(),
            explain_service=svc, dlq_topic="out-dlq", async_dispatch=True)
        try:
            stats = engine.run(max_messages=len(texts), idle_timeout=1.0)
        finally:
            engine.consumer.close()
        require(engine.close_annotations(timeout=900.0),
                "annotation lane did not drain")
        lane = engine.annotation_stats()
        snap = svc.snapshot()       # before close() returns the prefix pages
    finally:
        closed = svc.close(timeout=120.0)
    leaked = svc._decoder.leaked_pages
    flagged = sum(1 for m in broker.messages("out")
                  if json.loads(m.value)["prediction"] != 0)
    notes = [json.loads(m.value) for m in broker.messages("out-annotations")]
    require(stats.processed == len(texts) and broker.topic_size("out-dlq") == 0,
            f"processed {stats.processed} of {len(texts)}, "
            f"dlq {broker.topic_size('out-dlq')}")
    require(flagged >= sizes.min_flagged,
            f"only {flagged} rows flagged; need >= {sizes.min_flagged} so "
            "slots are reused")
    require(closed and snap["errors"] == 0 and snap["dropped"] == 0
            and snap["admitted"] == snap["completed"] == flagged,
            f"slot lane accounting: flagged {flagged}, {snap}")
    require(snap["prefix_pages"] > 0 and snap["prefix_hits"] == snap["admitted"],
            f"shared preamble missed: prefix_hits {snap['prefix_hits']} of "
            f"{snap['admitted']} admits ({snap['prefix_pages']} prefix pages)")
    require(leaked == 0, f"{leaked} KV pages leaked")
    require(len(notes) == flagged and lane["annotated"] == flagged
            and lane["dropped"] == 0 and lane["backend_errors"] == 0,
            f"annotations {len(notes)} != flagged {flagged}: {lane}")
    require(all(isinstance(n["analysis"], str)
                and not n["analysis"].startswith("[explanation ")
                for n in notes), "an annotation carries a failure marker")
    require(snap["tokens_out"] > flagged, f"tokens_out {snap['tokens_out']}")

    # Fixed-batch backend, one long prompt (the transcript-sized regime).
    long_prompt = ("Caller: this is the bank fraud department, read me the "
                   "one-time code now. Customer: are you really the bank? "
                   * 40)[:sizes.long_prompt]
    reply = OnPodBackend.from_model(lm).generate_batch(
        [long_prompt], temperature=0.0, max_tokens=8)
    require(len(reply) == 1 and isinstance(reply[0], str),
            f"generate_batch returned {reply!r}")

    # The compiled flash kernel inside the full model (forward dispatches to
    # it from T >= 512) against the chunked XLA attention: next-token logits.
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, 255, size=(1, sizes.flash_t)), jnp.int32)
    flash = np.asarray(llm.forward(lm.params, toks, cfg,
                                   logits_last_only=True)[0], np.float32)
    plain = np.asarray(llm.forward(lm.params, toks, cfg, use_flash=False,
                                   logits_last_only=True)[0], np.float32)
    require(flash.shape == (1, 1, cfg.vocab_size) and np.isfinite(flash).all(),
            f"flash logits shape {flash.shape} or non-finite")
    rel = float(np.linalg.norm(flash - plain) / np.linalg.norm(plain))
    require(rel < 5e-2, f"flash vs XLA logits: relative L2 error {rel:.3g}")

    out = {"ok": True, "params": n_params, "n_layers": cfg.n_layers,
           "d_model": cfg.d_model, "vocab": cfg.vocab_size,
           "dtype": sizes.llm_dtype, "init_s": round(init_s, 1),
           "flagged": flagged, "admitted": snap["admitted"],
           "completed": snap["completed"], "dropped": snap["dropped"],
           "prefix_hits": snap["prefix_hits"], "prefix_pages": snap["prefix_pages"],
           "kv_pages": snap["kv_pages"], "leaked_pages": leaked,
           "tokens_out": snap["tokens_out"], "annotations": len(notes),
           "flash_vs_xla_rel_l2": rel}
    del lm, svc, engine, pipe
    gc.collect()
    return out


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

def phase_kernels(sizes: Sizes, *, interpret: bool = False) -> dict:
    """Each Pallas kernel against its in-repo reference at production tiles.
    ``interpret`` is for the CPU test; ``main()`` always compiles."""
    import jax.numpy as jnp

    from fraud_detection_tpu.featurize.device import DeviceFeaturizer
    from fraud_detection_tpu.featurize.tfidf import HashingTfIdfFeaturizer
    from fraud_detection_tpu.models import llm
    from fraud_detection_tpu.models.pipeline import (ServingPipeline,
                                                     unpack_packed_host)
    from fraud_detection_tpu.models.train_linear import fit_logistic_regression
    from fraud_detection_tpu.models.train_trees import (TreeTrainConfig,
                                                        _xgb_gain,
                                                        fit_decision_tree)
    from fraud_detection_tpu.ops.attention import flash_attention
    from fraud_detection_tpu.ops.histogram import (
        best_splits, histogram_reference, node_feature_bin_histogram,
        node_feature_bin_histogram_multi)

    out = {"ok": True, "interpret": interpret}
    rng = np.random.default_rng(0)
    n, f, nb, nodes, k = sizes.hist
    bins = jnp.asarray(rng.integers(0, nb, (n, f), dtype=np.int32))
    local = jnp.asarray(rng.integers(0, nodes + 1, (n,), dtype=np.int32))

    # histogram, f32 statistics: two bf16 MXU passes vs the XLA segment-sum
    stats = jnp.asarray(rng.normal(0, 1, (n, k)).astype(np.float32))
    got = node_feature_bin_histogram(bins, local, stats, n_nodes=nodes,
                                     n_bins=nb, interpret=interpret)
    want = histogram_reference(bins, local, stats, n_nodes=nodes, n_bins=nb)
    diff = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    require(diff <= 1e-3 * max(scale, 1.0),
            f"histogram vs XLA reference: max|diff| {diff:.3g} at scale {scale:.3g}")
    out["histogram_max_abs_diff"] = diff

    # histogram, exact_int8: class one-hots x Poisson-like weights, two trees
    onehot = jnp.asarray(np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)])
    weights = jnp.asarray(rng.integers(0, 5, (2, n)).astype(np.float32))
    locals2 = jnp.stack([local, jnp.roll(local, 7)])
    got8 = node_feature_bin_histogram_multi(
        bins, locals2, weights, onehot, n_nodes=nodes, n_bins=nb,
        interpret=interpret, exact_int8=True)
    for t in range(2):
        want8 = histogram_reference(bins, locals2[t], onehot * weights[t][:, None],
                                    n_nodes=nodes, n_bins=nb)
        require(bool(jnp.array_equal(got8[t], want8)),
                f"exact_int8 histogram (tree {t}) is not exact: max|diff| "
                f"{float(jnp.max(jnp.abs(got8[t] - want8))):.3g}")
    out["histogram_int8_exact"] = True

    # split-gain scan vs the XLA formulation on the same stats
    hist = jnp.abs(want) + 0.01
    totals = hist[:, 0].sum(axis=1)
    bf, bb, _ = best_splits(hist, totals, criterion="xgb", n_bins=nb,
                            feature_tile=128, interpret=interpret)
    gain = _xgb_gain(jnp.cumsum(hist, axis=2), totals[:, None, None, :],
                     1.0, 1e-6)[:, :, : nb - 1]
    ref = np.asarray(gain.reshape(nodes, -1)).argmax(axis=1)
    require((np.asarray(bf) == ref // (nb - 1)).all()
            and (np.asarray(bb) == ref % (nb - 1)).all(),
            "best_splits disagrees with the XLA gain argmax")
    out["best_splits_exact"] = True

    # the same kernels inside a trainer's program: the gini path runs the
    # exact_int8 histogram with its contract diagnostic (a host callback)
    Xt = rng.normal(size=(n, f)).astype(np.float32)
    tree = fit_decision_tree(
        Xt, (Xt[:, 3] > 0).astype(np.int32),
        config=TreeTrainConfig(max_depth=sizes.max_depth, use_pallas=True))
    require(int(np.asarray(tree.feature)[0, 0]) == 3,
            f"decision tree split the root on feature "
            f"{int(np.asarray(tree.feature)[0, 0])}, the label is feature 3")
    out["gini_trainer_root_feature"] = 3

    # flash attention vs materialized scores, MQA at head 256
    b, t, h, hkv, d = sizes.attn
    q, kk, v = (jnp.asarray(rng.normal(size=s).astype(np.float32)).astype(jnp.bfloat16)
                for s in ((b, t, h, d), (b, t, hkv, d), (b, t, hkv, d)))
    fa = flash_attention(q, kk, v, interpret=interpret)
    tril = jnp.tril(jnp.ones((t, t), bool))
    ref_a = llm._attend(q, jnp.repeat(kk, h // hkv, axis=2),
                        jnp.repeat(v, h // hkv, axis=2), tril)
    err = float(jnp.max(jnp.abs(fa.astype(jnp.float32) - ref_a.astype(jnp.float32))))
    require(err <= 3e-2, f"flash_attention vs _attend: max|diff| {err:.3g}")
    out["flash_max_abs_diff"] = err

    # featurize scan kernel vs the host featurizer: identical ids and counts
    texts = _seeded_texts(sizes.feat_rows, seed=123)
    feat = HashingTfIdfFeaturizer(num_features=sizes.num_features)
    feat.fit_idf(texts)
    dev = DeviceFeaturizer(feat, width=sizes.feat_width, tokens=sizes.feat_tokens,
                           interpret=True if interpret else None)
    staged, truncated = dev.pack(texts, len(texts))
    ids_d, cnt_d = unpack_packed_host(np.asarray(dev.encode_packed(staged)))
    host = feat.encode(dev.decode_truncated(texts), batch_size=len(texts),
                       max_tokens=dev.tokens)
    bad = int(np.sum(np.any(ids_d != np.asarray(host.ids), axis=1)
                     | np.any(cnt_d != np.asarray(host.counts), axis=1)))
    require(bad == 0, f"featurize kernel differs from the host featurizer on "
                      f"{bad} of {len(texts)} rows")
    out["featurize"] = {"path": dev.path, "rows": len(texts),
                        "truncated_rows": truncated, "mismatched_rows": 0}

    # ...and fused into the scoring program (serve --featurize-device)
    y = np.asarray([i % 2 for i in range(len(texts))], np.float32)
    model = fit_logistic_regression(
        np.asarray(feat.featurize_dense(texts)), y, max_iter=5)
    host_pipe = ServingPipeline(feat, model, batch_size=len(texts))
    dev_pipe = ServingPipeline(
        feat, model, batch_size=len(texts),
        featurize_device="interpret" if interpret else True,
        featurize_width=sizes.feat_width, featurize_tokens=sizes.feat_tokens)
    cut = dev.decode_truncated(texts)
    fused = float(np.max(np.abs(dev_pipe.predict(texts).probabilities
                                - host_pipe.predict(cut).probabilities)))
    require(fused < 1e-5, f"fused featurize+score differs from host by {fused:.3g}")
    out["featurize"]["fused_max_abs_diff"] = fused
    return out


# ---------------------------------------------------------------------------
# phase: mesh (four chips)
# ---------------------------------------------------------------------------

def phase_mesh(models: dict, sizes: Sizes, stamp: dict,
               lr_reference: list) -> dict:
    """Every chip of the host: ``serve --mesh`` equal to the one-chip serve,
    a tensor-parallel decode through ``shard_params``, and two boosting
    rounds with cross-chip histograms."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from fraud_detection_tpu.models import llm
    from fraud_detection_tpu.models.pipeline import ServingPipeline
    from fraud_detection_tpu.models.train_trees import (TreeTrainConfig,
                                                        fit_gradient_boosting)
    from fraud_detection_tpu.parallel import make_mesh
    from fraud_detection_tpu.parallel.mesh import shard_rows
    from fraud_detection_tpu.parallel.serving import MeshServingPipeline

    n_dev = stamp["device_count"]
    served = phase_serve(models["lr"], sizes.xgb_msgs, sizes, stamp, mesh=True)
    require(served["mesh_devices"] == n_dev, f"serve --mesh used "
            f"{served['mesh_devices']} of {n_dev} devices")
    base = ServingPipeline.from_checkpoint(models["lr"], batch_size=sizes.sample)
    meshed = MeshServingPipeline.from_pipeline(
        base, per_chip_batch=max(1, sizes.sample // n_dev))
    require(meshed.mesh is not None and meshed.data_parallel == n_dev,
            f"mesh dropped: data_parallel {meshed.data_parallel}")
    probe = shard_rows(np.zeros((n_dev * 2, 4), np.float32), meshed.mesh)
    require(len(probe.sharding.device_set) == n_dev
            and len({s.device for s in probe.addressable_shards}) == n_dev,
            "rows are not sharded over distinct devices")
    texts = _seeded_texts(sizes.sample, seed=2024)
    got = meshed.predict(texts).probabilities
    diff = float(np.max(np.abs(got - np.asarray(lr_reference, np.float32))))
    require(diff <= 1e-6, f"mesh serving differs from one chip by {diff:.3g}")

    # tensor-parallel decode: model-axis-sharded params against unsharded
    cfg = _llm_config(sizes, n_layers=sizes.mesh_llm_layers)
    tp_mesh = Mesh(np.asarray(jax.devices()[:4]), (llm.MODEL_AXIS,))
    lm = llm.LanguageModel.init_random(cfg, seed=0)
    lm_tp = llm.LanguageModel(cfg, llm.shard_params(lm.params, cfg, tp_mesh))
    wq = lm_tp.params["l0.wq"]
    require(len(wq.sharding.device_set) == 4
            and wq.addressable_shards[0].data.shape[1] == cfg.n_heads // 4,
            f"wq not head-sharded over 4 chips: {wq.sharding}")
    prompt = np.random.default_rng(3).integers(0, 255, size=48)
    one = lm.generate_tokens(prompt, max_new_tokens=8)
    tp = lm_tp.generate_tokens(prompt, max_new_tokens=8)
    toks = jnp.asarray(prompt[None, :], jnp.int32)
    l_one = np.asarray(llm.forward(lm.params, toks, cfg, use_flash=False,
                                   logits_last_only=True)[0], np.float32)
    l_tp = np.asarray(llm.forward(lm_tp.params, toks, cfg, use_flash=False,
                                  logits_last_only=True)[0], np.float32)
    rel = float(np.linalg.norm(l_tp - l_one) / np.linalg.norm(l_one))
    require(tp.shape == one.shape and rel < 5e-2,
            f"tp=4 logits differ from one chip: relative L2 {rel:.3g}")
    del lm, lm_tp
    gc.collect()

    # two boosting rounds, rows sharded over the data axis (psum histograms)
    rng = np.random.default_rng(9)
    X = rng.normal(size=(sizes.mesh_rows, 256)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    cfg_t = TreeTrainConfig(max_depth=3, criterion="xgb", use_pallas=False)
    single = fit_gradient_boosting(X, y, n_rounds=2, config=cfg_t)
    sharded = fit_gradient_boosting(X, y, n_rounds=2, config=cfg_t,
                                    mesh=make_mesh())
    require(np.array_equal(np.asarray(single.feature), np.asarray(sharded.feature))
            and np.allclose(np.asarray(single.leaf), np.asarray(sharded.leaf),
                            rtol=1e-3, atol=1e-5),
            "mesh boosting built different trees than one chip")
    return {"ok": True, "devices": n_dev, "serve_mesh_devices": served["mesh_devices"],
            "serve_max_abs_diff_vs_one_chip": diff, "tp4_logits_rel_l2": rel,
            "tp4_tokens_equal": bool(np.array_equal(one, tp)),
            "mesh_boosting_rounds": 2}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

# What a phase hands to a later phase, not to the report.
_HANDOFF = ("models", "sample_probabilities")


def run(sizes: Sizes, stamp: dict, counter: CompileCounter) -> dict:
    phases: dict = {}
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")

    def timed(name, fn, *args, **kw):
        mark, t0 = counter.mark(), time.perf_counter()
        print(f"--- chip_smoke: {name}", flush=True)
        res = fn(*args, **kw)
        phases[name] = {
            **{k: v for k, v in res.items() if k not in _HANDOFF},
            "wall_s": round(time.perf_counter() - t0, 1),
            **counter.since(mark)}
        return res

    try:
        models = timed("train", phase_train, workdir, sizes, stamp)["models"]
        lr = timed("serve_lr", phase_serve, models["lr"], sizes.lr_msgs,
                   sizes, stamp)
        timed("serve_xgb", phase_serve, models["xgb"], sizes.xgb_msgs,
              sizes, stamp)
        timed("kernels", phase_kernels, sizes)
        timed("explain", phase_explain, models["lr"], sizes)
        if stamp["device_count"] >= 4:
            timed("mesh", phase_mesh, models, sizes, stamp,
                  lr["sample_probabilities"])
        else:
            print(f"--- chip_smoke: mesh phase does not apply "
                  f"({stamp['device_count']} chip)", flush=True)
            phases["mesh"] = {"ok": None, "skipped": "needs >= 4 chips, have "
                              f"{stamp['device_count']}"}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return phases


def main() -> int:
    t_start = time.perf_counter()
    stamp = gate()

    from fraud_detection_tpu.featurize import native
    from fraud_detection_tpu.utils.jax_cache import (
        CACHE_ENV, enable_persistent_compile_cache)

    cache_dir = enable_persistent_compile_cache()
    counter = CompileCounter()
    cache_entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"chip_smoke: platform={stamp['platform']} "
          f"device_kind={stamp['device_kind']!r} "
          f"device_count={stamp['device_count']} versions={versions()} "
          f"compile_cache={cache_dir} "
          f"({'from ' + CACHE_ENV if os.environ.get(CACHE_ENV) else 'in-checkout default'}, "
          f"{cache_entries} entries)", flush=True)

    # The C++ featurizer is built from the committed source, here and now.
    for stale in (native._LIB, native._LIB + ".key"):
        with contextlib.suppress(FileNotFoundError):
            os.unlink(stale)
    require(native.available(), "native featurizer did not build/load "
            "(g++ or dlopen failed); the Python tokenizer would take over")

    phases = run(FULL, stamp, counter)
    total = counter.since((0, 0))
    report = {
        **verdict(stamp),
        "phases": phases,
        "setup": {"wall_s": round(time.perf_counter() - t_start, 1),
                  "compile_cache": cache_dir,
                  "cache_entries_at_start": cache_entries,
                  "cache": "warm" if cache_entries else "cold", **total},
        "versions": versions(),
        "claim": None,
    }
    print(json.dumps(report), flush=True)
    print(json.dumps(verdict(stamp)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
