"""The system under test, built from a configuration file: the *desk* as
``serve --explain-slots N --explain-paged`` wires it (app/serve.py) — one
``StreamingClassifier`` over an in-process broker, scoring with the trained
classifier and explaining flagged rows through the paged slot lane via
``make_slot_explain_hook`` and the asynchronous annotation lane. This module
and the explainer family files (``explainers/<model_type>.py``, whose
``build`` makes the model the slot lane serves) are the only ones of the
benchmark that import the program.

Everything here is set-up: the classifier is trained through the normal
``train`` entry on the benchmark's own corpus, the explainer's weights are
made from the seed by the family's ``make_params`` and handed to its
``build``, and ``warm`` drives every shape the cell's traffic will touch.
"""

from __future__ import annotations

import csv
import json
import os
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmark.corpus import generate_corpus

IN_TOPIC, OUT_TOPIC, DLQ_TOPIC = "calls", "scored", "scored-dlq"
NOTES_TOPIC = OUT_TOPIC + "-annotations"


def train_classifier(spec: dict, seed: int, workdir: str) -> str:
    """``python -m fraud_detection_tpu.app.train`` in-process on the
    benchmark's corpus (written as the CSV the CLI reads); returns the
    checkpoint directory."""
    import contextlib
    import io

    from fraud_detection_tpu.app import train

    data = os.path.join(workdir, "corpus.csv")
    with open(data, "w", newline="", encoding="utf-8") as f:
        w = csv.writer(f)
        w.writerow(["dialogue", "labels"])
        for d in generate_corpus(n=int(spec["train_rows"]),
                                 seed=int(seed) & 0x7FFFFFFF):
            w.writerow([d.text, d.label])
    family = spec["family"]
    out = os.path.join(workdir, family)
    argv = ["--data", data, "--seed", str(int(seed) & 0x7FFFFFFF),
            "--models", family, "--num-features", str(spec["num_features"]),
            "--save", f"{family}={out}", "--json"]
    if family == "xgb":
        argv += ["--n-rounds", str(spec["n_rounds"]),
                 "--max-depth", str(spec["max_depth"])]
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = train.main(argv)
    if rc != 0 or not os.path.isdir(out):
        raise RuntimeError(f"train exited {rc}: {buf.getvalue()[-2000:]}")
    return out


class Desk:
    """One serve process's worth of objects, and the thread its engine
    runs on. ``family`` is the configuration's explainer family
    (``run.load_family``)."""

    def __init__(self, cfg: dict, seed: int, workdir: str, family, *,
                 traced: bool = False):
        import jax.numpy as jnp

        from fraud_detection_tpu.explain.slotserve import (
            SlotServeService, make_slot_explain_hook)
        from fraud_detection_tpu.models.pipeline import ServingPipeline
        from fraud_detection_tpu.stream import (InProcessBroker,
                                                StreamingClassifier)

        desk = cfg["desk"]
        eng, exp = desk["engine"], desk["explain"]
        self.cfg, self.seed = cfg, int(seed)
        self.spans: List[tuple] = []        # (name, start_s, dur_s, attrs)
        self.requests: list = []            # slot tickets, in submit order
        self.checkpoint = train_classifier(desk["classifier"], seed, workdir)
        self.pipe = ServingPipeline.from_checkpoint(
            self.checkpoint, batch_size=eng["batch_size"])
        # ``weights`` other than the stated dtype is the family's own lower
        # precision (benchmark/control.py, or a configuration that states it).
        self.lm = family.build(
            cfg, family.make_params(seed, cfg,
                                    jnp.dtype(cfg["torch_dtype"]).type),
            exp.get("weights", cfg["torch_dtype"]))
        self.svc = SlotServeService(
            self.lm, slots=exp["slots"], max_queue=exp["max_queue"],
            max_new_tokens=exp["max_new_tokens"],
            prompt_width=exp["prompt_width"], paged=exp["paged"],
            page_size=exp["page_size"], temperature=exp["temperature"])
        self._wrap_submit()
        self.prefix_len = 0
        if self.svc.snapshot()["prefix_pages"]:
            from fraud_detection_tpu.explain.slotserve.service import (
                shared_explain_prefix)

            self.prefix_len = len(self.lm.tokenizer.encode(
                shared_explain_prefix()))
        self.rowtrace = None
        if traced:
            from fraud_detection_tpu.obs.trace import RowTracer

            self.rowtrace = RowTracer(worker="desk", capacity=1 << 18)
            self.svc.set_rowtrace(self.rowtrace)
            self._wrap_featurize()
        self.broker = InProcessBroker(num_partitions=eng["partitions"])
        self.engine = StreamingClassifier(
            self.pipe, self.broker.consumer([IN_TOPIC], "desk"),
            self.broker.producer(), OUT_TOPIC,
            batch_size=eng["batch_size"], max_wait=eng["max_wait_s"],
            pipeline_depth=eng["pipeline_depth"],
            explain_batch_fn=make_slot_explain_hook(
                self.svc, temperature=exp["temperature"],
                max_tokens=exp["max_new_tokens"]),
            explain_async=True, annotations_producer=self.broker.producer(),
            annotations_queue=exp["annotations_queue"],
            explain_service=self.svc,
            dlq_topic=DLQ_TOPIC if eng["dlq"] else None,
            async_dispatch=eng["async_dispatch"], rowtrace=self.rowtrace)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    # -- the benchmark's own taps ----------------------------------------

    def _wrap_submit(self) -> None:
        """Keep every slot ticket ``submit`` hands out: the prompt tokens,
        the served tokens and the lane's own stamps ride on it."""
        inner = self.svc.submit

        def submit(*args, **kw):
            req = inner(*args, **kw)
            self.requests.append(req)
            return req

        self.svc.submit = submit

    def _wrap_featurize(self) -> None:
        """A span around the host decode+featurize call (traced runs)."""
        feat = self.pipe.featurizer
        inner = feat.encode_json

        def encode_json(values, *args, **kw):
            t0 = time.time()
            out = inner(values, *args, **kw)
            self.spans.append(("featurize", t0, time.time() - t0,
                               {"rows": len(values)}))
            return out

        feat.encode_json = encode_json

    # -- set-up ----------------------------------------------------------

    def flags(self, texts: Sequence[str]) -> np.ndarray:
        return np.asarray(self.pipe.predict(list(texts)).labels) == 1

    def warm(self, payloads: Sequence[bytes], scam_texts: Sequence[str]) -> dict:
        """Drive every shape the traffic can touch: the scoring program at
        each pair-count rung up to the widest payload's, and the paged
        prefill at each suffix bucket of the scam texts (one token each).
        Also says how many (bucket, count) pairs a payload holds on average."""
        from benchmark.traffic import payload

        width, pairs = 16, 0.0
        if payloads:
            head = list(payloads[:4096])
            enc = self.pipe.featurizer.encode_json(head, "text")
            width = int(enc[0].ids.shape[1]) if enc is not None else 256
            if enc is not None:
                pairs = float(np.count_nonzero(np.asarray(enc[0].counts))
                              / len(head))
        rungs, rung = [], 16
        while rung <= width:
            words = " ".join(_word(i) for i in range(rung * 3 // 4))
            out = self.pipe.predict_json_async([payload(words)], "text")
            if out is not None:
                out[0].resolve()
            rungs.append(rung)
            rung *= 2
        seen: Dict[int, str] = {}
        for text in scam_texts:
            seen.setdefault(len(text), text)
        if seen:
            self.svc.explain_rows(list(seen.values()), [1] * len(seen),
                                  [0.99] * len(seen), max_tokens=1)
        buckets = sorted({-(-len(r.tokens) // 64) for r in self.requests})
        self.requests.clear()
        return {"score_rungs": rungs, "prefill_rows": len(seen),
                "prompt_pages": buckets, "pairs_per_row": pairs}

    # -- the run ---------------------------------------------------------

    def emitted(self) -> np.ndarray:
        """Tokens each slot ticket has emitted so far, in submit order."""
        return np.asarray([len(r.out) for r in list(self.requests)], np.int64)

    def start(self) -> None:
        def drive():
            try:
                self.engine.run()
            except BaseException as e:  # noqa: BLE001 — reported by stop()
                self._error = e

        self._thread = threading.Thread(target=drive, name="desk-engine",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> dict:
        """Stop the engine, then the lanes; returns the lanes' last words.
        Queued explain work is dropped (accounted), rows in slots finish."""
        self.engine.stop()
        if self._thread is not None:
            self._thread.join(timeout=120.0)
            if self._thread.is_alive():
                raise RuntimeError("the engine did not stop within 120 s")
        self.engine.consumer.close()
        self.engine.close_annotations(timeout=0.2)
        self.svc.close(timeout=0.2)          # drop what is queued ...
        snap = self.svc.snapshot()
        closed = self.svc.close(timeout=120.0)   # ... finish what is in slots
        self.engine.close_annotations(timeout=30.0)   # ... and note them
        if self._error is not None:
            raise RuntimeError("the engine died") from self._error
        # At quiescence every page is back on the free list, the shared
        # preamble's too (both 0 where the lane is not paged).
        after = self.svc.snapshot()
        return {"snapshot": snap, "closed": bool(closed),
                "leaked_pages": int(after["kv_pages"] - after["pages_free"]),
                "lane": self.engine.annotation_stats(),
                "stats": self.engine.stats.as_dict(),
                "health_device": self.engine.health().get("device", {})}

    def release(self) -> None:
        """Drop every reference to device state, so the reference pass
        finds the chip's memory free."""
        for name in ("engine", "svc", "lm", "pipe", "broker", "rowtrace"):
            setattr(self, name, None)


def _word(i: int) -> str:
    """A distinct lowercase word per index (letters only: digits are
    cleaned away by the featurizer)."""
    out = "q"
    while True:
        out += chr(ord("a") + i % 26)
        i //= 26
        if i == 0:
            return out


def messages(broker, topic: str):
    """(keys, stamps, values) of a topic, as the broker holds them."""
    msgs = broker.messages(topic)
    return ([m.key for m in msgs],
            np.asarray([m.timestamp for m in msgs], np.float64),
            [m.value for m in msgs])


def device_stamp() -> dict:
    import jax

    dev = jax.devices()
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def memory_in_use() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.devices())


def load_config(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
