"""Serving pipeline: text in, (label, probability) out — the agent-facing API.

TPU-native replacement for the reference's serve path
(``DeepSeekClassificationAgent.predict_and_get_label``,
/root/reference/utils/agent_api.py:155-175), which ran a full 5-stage Spark job
per single-row DataFrame. Here the host tokenizes/hashes a whole micro-batch
and one jitted program scores it; for logistic models the features are never
materialized (gather/segment-sum fast path, models/linear.py).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fraud_detection_tpu.checkpoint.spark_artifact import SparkPipelineArtifact
from fraud_detection_tpu.featurize.text import StopWordFilter
from fraud_detection_tpu.featurize.tfidf import (
    HashingTfIdfFeaturizer,
    VocabTfIdfFeaturizer,
)
from fraud_detection_tpu.models import linear as linear_mod
from fraud_detection_tpu.models import trees as trees_mod
from fraud_detection_tpu.models.linear import LogisticRegression
from fraud_detection_tpu.models.trees import TreeEnsemble


@dataclass
class PredictionBatch:
    labels: np.ndarray          # (N,) int32 — 1 = scam
    probabilities: np.ndarray   # (N,) float32 — p(class=1)

    def __iter__(self):
        return iter(zip(self.labels.tolist(), self.probabilities.tolist()))


_DONATION_EFFECTIVE: Optional[bool] = None


def donation_effective() -> bool:
    """Does this backend CONSUME donated input buffers? Probed once per
    process with a tiny program shaped like the serving case (int16 staging
    buffer in, f32 out — sizes never alias). Platforms that implement
    donation free the input at dispatch (the HBM win the serving path
    wants); CPU jax currently keeps the buffer and warns, so the pipeline
    routes through the non-donating twins there and ``donation_hits``
    honestly stays 0."""
    global _DONATION_EFFECTIVE
    if _DONATION_EFFECTIVE is None:
        import warnings

        # flightcheck: ignore[FC201] — one-shot probe; cached in _DONATION_EFFECTIVE
        probe = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32), axis=-1),
                        donate_argnums=(0,))
        x = jnp.zeros((2, 2, 4), jnp.int16)
        jax.block_until_ready(x)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            jax.block_until_ready(probe(x))
        _DONATION_EFFECTIVE = bool(x.is_deleted())
    return _DONATION_EFFECTIVE


def _pack_encoded(enc) -> Optional[np.ndarray]:
    """Stack an EncodedBatch into ONE (B, 2, L) int16 staging array so the
    micro-batch crosses host->device as a single transfer (ids in plane 0,
    uint16 counts bit-cast into plane 1; linear.unpack_rows restores them
    exactly). None when the featurizer widened ids past int16 (num_features
    > 32767) — that configuration keeps the two-array upload."""
    ids = np.asarray(enc.ids)
    counts = np.asarray(enc.counts)
    if ids.dtype != np.int16 or counts.dtype != np.uint16:
        return None
    return np.stack([ids, counts.view(np.int16)], axis=1)


def unpack_packed_host(packed: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host inverse of ``_pack_encoded``: (B, 2, L) int16 -> (int16 ids,
    uint16 counts). The device featurize path's parity surface
    (featurize/device.py) round-trips through this."""
    packed = np.asarray(packed)
    return packed[:, 0, :], packed[:, 1, :].view(np.uint16)


class DeviceStats:
    """Per-pipeline device-path counters (the ``device`` block of engine
    health): host->device crossings, donation hits, and what is pinned
    HBM-resident. Single-writer — the dispatching thread — with racy reads
    from health pollers by design (a monitoring sample, like StreamStats)."""

    __slots__ = ("uploads", "upload_bytes", "chunks", "donated",
                 "pinned_bytes", "pins", "int8", "mesh_devices", "_rungs",
                 "featurize_path", "feat_bytes_in", "feat_rows",
                 "truncated_rows")

    def __init__(self, int8: bool = False, mesh_devices: int = 0):
        self.uploads = 0        # host->device transfer events
        self.upload_bytes = 0
        self.chunks = 0         # micro-batch chunks dispatched
        self.donated = 0        # chunks dispatched through a donating program
        self.pinned_bytes = 0   # model-side bytes made device-resident
        self.pins = 0           # pin_device() calls (1/version; re-pin on swap)
        self.int8 = int8
        # Mesh data-parallel scoring (parallel/serving.py): chips on the
        # mesh's data axis (0 = single-device path), and every distinct
        # padded row count dispatched — prewarm populates it, so health
        # shows which per-chip rungs are compiled BEFORE traffic arrives.
        self.mesh_devices = mesh_devices
        self._rungs: set = set()
        # Device-side featurization (ops/featurize_kernel.py): which path
        # featurize actually RUNS ("host" = the classic C++/Python leg,
        # "pallas" = compiled kernel, "interpret" = interpreter mode), raw
        # bytes shipped instead of packed ids+counts, and rows whose UTF-8
        # exceeded the byte width (truncated at a codepoint boundary —
        # counted, never silent).
        self.featurize_path = "host"
        self.feat_bytes_in = 0
        self.feat_rows = 0
        self.truncated_rows = 0

    def record_chunk(self, nbytes: int, transfers: int = 1,
                     rows: Optional[int] = None) -> None:
        self.chunks += 1
        self.uploads += transfers
        self.upload_bytes += nbytes
        if rows:
            self._rungs.add(rows)   # set.add is atomic; snapshot copies

    def record_featurize(self, nbytes: int, rows: int, truncated: int) -> None:
        """One device-featurized chunk: raw bytes in, rows covered, rows
        byte-truncated (single-writer, like record_chunk)."""
        self.feat_bytes_in += nbytes
        self.feat_rows += rows
        self.truncated_rows += truncated

    def per_chip_rungs(self) -> list:
        """Distinct padded row counts dispatched, PER CHIP on the data
        axis (== the global rungs on the single-device path)."""
        dp = max(1, self.mesh_devices)
        return sorted({-(-r // dp) for r in self._rungs})

    def snapshot(self) -> dict:
        chunks = self.chunks
        return {
            "uploads": self.uploads,
            "upload_bytes": self.upload_bytes,
            "chunks": chunks,
            "uploads_per_chunk": (round(self.uploads / chunks, 3)
                                  if chunks else None),
            "donation_hits": self.donated,
            "pinned_bytes": self.pinned_bytes,
            "model_pins": self.pins,
            "int8": self.int8,
            "mesh_devices": self.mesh_devices,
            "per_chip_rungs": self.per_chip_rungs(),
            "featurize_path": self.featurize_path,
            "bytes_in_per_row": (round(self.feat_bytes_in / self.feat_rows, 1)
                                 if self.feat_rows else None),
            "truncated_rows": self.truncated_rows,
        }


class Phase(NamedTuple):
    """One host phase of one chunk's dispatch, timed where it happens:
    ``featurize`` (decode + tokenize + hash + count) or ``upload`` (pack +
    host->device placement). A traced engine records these as the children
    of its ``launch`` span (stream/engine.py); the jit call is what is left
    of ``launch``."""

    stage: str
    started: float          # time.perf_counter() when the phase began
    seconds: float
    rows: int               # real rows of the chunk
    padded: int = 0         # rows the device program runs (upload only)
    nbytes: int = 0         # bytes placed on the device (upload only)


class PendingPrediction:
    """Unresolved device results from ``ServingPipeline.predict_async``.

    Holds per-chunk (probability, valid_count) device arrays whose host copy
    was already initiated asynchronously at dispatch; ``resolve()`` blocks on
    the device and returns host numpy arrays. Only p(class=1) crosses the
    device->host link — labels come from the identical ``p > threshold``
    comparison on the host (for trees, argmax over the normalized binary
    proba reduces to the same comparison)."""

    def __init__(self, parts: List[Tuple[object, int]], threshold: float = 0.5,
                 argmax: bool = False,
                 phases: Optional[List[Phase]] = None):
        self._parts = parts
        self.threshold = threshold
        self.argmax = argmax  # parts hold full (B, C) probas (multiclass trees)
        self.phases: List[Phase] = phases or []

    def resolve(self) -> PredictionBatch:
        if not self._parts:
            return PredictionBatch(np.empty(0, np.int32), np.empty(0, np.float32))
        host = np.concatenate([np.asarray(p)[:n] for p, n in self._parts])
        if self.argmax:
            labels = np.argmax(host, axis=-1).astype(np.int32)
            probs = host[:, 1].astype(np.float32)
        else:
            probs = host
            labels = (probs > np.float32(self.threshold)).astype(np.int32)
        return PredictionBatch(labels, probs)


class ServingPipeline:
    """Featurizer + classifier bound together behind ``predict(texts)``.

    Use ``from_spark_artifact`` to serve the reference's shipped model with
    bit-parity semantics, or construct directly from a native featurizer +
    model pair trained by this framework.
    """

    def __init__(self, featurizer: HashingTfIdfFeaturizer,
                 model: "LogisticRegression | TreeEnsemble",
                 fold_idf: bool = True, batch_size: int = 256, mesh=None,
                 int8: bool = False, featurize_device=False,
                 featurize_width: Optional[int] = None,
                 featurize_tokens: Optional[int] = None):
        self.featurizer = featurizer
        self.batch_size = batch_size
        self.mesh = mesh  # data-parallel serving: rows sharded on "data"
        # Padding-bucket ladder (sched/batcher.py): when set (ascending
        # rungs, e.g. (64, 256, 1024)), a partial chunk pads to the smallest
        # rung that fits instead of to batch_size — small batches pay small
        # device programs, and the rung set is the FIXED menu of compiled
        # shapes (pre-warmed at startup so the hot path never compiles).
        # None keeps the single batch_size shape of the bare pipeline.
        self.pad_ladder: Optional[Tuple[int, ...]] = None
        self.model = model
        if isinstance(model, LogisticRegression):
            # Fold IDF into the weights so the sparse fast path sees raw counts.
            self._fused_model: Optional[LogisticRegression] = (
                model.fold_idf(featurizer.idf_array()) if fold_idf else model)
        else:
            # Trees branch on absolute feature values: needs the dense TF-IDF
            # matrix (one scatter + traversal, still one device program).
            self._fused_model = None
        self._tree_idf = None  # device IDF cache for the tree fast path
        # int8 scoring variant (docs/serving.md): symmetric per-block
        # quantization of the fused weights (models/linear.py
        # quantize_weights). Rides the packed upload path; fp32 parity
        # pinned in tests/test_device_path.py.
        self.int8 = bool(int8)
        self._q8 = None
        if self.int8:
            if self._fused_model is None:
                raise ValueError(
                    "int8 scoring requires a LogisticRegression pipeline — "
                    "tree ensembles serve fp32 (their traversal compares "
                    "thresholds, not dot products)")
            self._q8 = linear_mod.quantize_weights(self._fused_model)
        if mesh is not None:
            dp = int(dict(mesh.shape).get("data", 1))
        else:
            dp = 0
        self.device_stats = DeviceStats(int8=self.int8, mesh_devices=dp)
        # Device-side featurization (ops/featurize_kernel.py + featurize/
        # device.py): the host ships a fixed-width raw-byte tensor and ONE
        # jitted program runs tokenize/murmur-hash/count/pack + scoring —
        # the featurize leg leaves the host CPU entirely. ``featurize_device``
        # accepts False, True (compiled Pallas; raises
        # DeviceFeaturizeUnavailable where there is no TPU or the
        # featurizer cannot be represented) or "interpret" (interpreter
        # mode, for parity tests on the CPU mesh).
        self._dev_feat = None
        if featurize_device:
            from fraud_detection_tpu.featurize.device import DeviceFeaturizer

            self._dev_feat = DeviceFeaturizer(
                featurizer,
                **({"width": featurize_width}
                   if featurize_width is not None else {}),
                **({"tokens": featurize_tokens}
                   if featurize_tokens is not None else {}),
                interpret=(True if featurize_device == "interpret"
                           else None))
            self.device_stats.featurize_path = self._dev_feat.path
        # Donate per-batch staging buffers into the scoring program when the
        # platform consumes them (probed once; False on CPU).
        self._donate = donation_effective()
        self._pinned_version: Optional[object] = None

    def _pad_rows(self, n: int) -> int:
        """Row-padding target for an n-row chunk: the smallest ladder rung
        that fits (ladder configured), else batch_size (the bare contract)."""
        ladder = self.pad_ladder
        if ladder:
            for b in ladder:
                if n <= b:
                    return b
        return self.batch_size

    @property
    def fused_model(self) -> LogisticRegression:
        """The serving model with IDF folded into the weights (raw-count input)."""
        if self._fused_model is None:
            raise TypeError("fused sparse scoring only applies to LogisticRegression")
        return self._fused_model

    @classmethod
    def from_checkpoint(cls, path: str, batch_size: int = 256,
                        mesh=None) -> "ServingPipeline":
        """Load a native checkpoint directory (checkpoint/native.py layout)."""
        from fraud_detection_tpu.checkpoint.native import load_checkpoint
        from fraud_detection_tpu.obs.trace import (STAGE_SETUP_PIPELINE,
                                                   setup_span)

        with setup_span(STAGE_SETUP_PIPELINE) as span:
            featurizer, model = load_checkpoint(path)
            span.detail = f"family={type(model).__name__}"
            return cls(featurizer, model, batch_size=batch_size, mesh=mesh)

    @classmethod
    def from_spark_artifact(cls, artifact: SparkPipelineArtifact,
                            batch_size: int = 256,
                            mesh=None) -> "ServingPipeline":
        """Serve any reference artifact shape: the shipped HashingTF +
        LogisticRegression pipeline (SURVEY.md §2.2) AND the training
        script's CountVectorizer + tree pipelines
        (fraud_detection_spark.py:47-91, saved at :389-393 — quirk Q1)."""
        from fraud_detection_tpu.checkpoint.spark_artifact import RegexTokenizerStage

        for s in artifact.stages:
            if isinstance(s, RegexTokenizerStage):
                raise NotImplementedError(
                    "artifact uses RegexTokenizer; only plain Tokenizer semantics "
                    f"are implemented (pattern={s.pattern!r}, gaps={s.gaps})")
        htf = artifact.hashing_tf
        cv = artifact.count_vectorizer
        idf_stage = artifact.idf
        lr = artifact.logistic_regression
        tree = artifact.tree_ensemble
        sw = artifact.stopwords
        stop = StopWordFilter(sw.stopwords, sw.case_sensitive) if sw else StopWordFilter()
        idf = None if idf_stage is None else idf_stage.idf.astype(np.float32)
        if htf is not None:
            featurizer: HashingTfIdfFeaturizer = HashingTfIdfFeaturizer(
                num_features=htf.num_features, idf=idf, binary_tf=htf.binary,
                stop_filter=stop, remove_stopwords=sw is not None)
        elif cv is not None:
            featurizer = VocabTfIdfFeaturizer(
                vocabulary=cv.vocabulary, min_tf=cv.min_tf, idf=idf,
                binary_tf=cv.binary, stop_filter=stop,
                remove_stopwords=sw is not None)
        else:
            raise ValueError(
                "artifact has no HashingTF or CountVectorizerModel stage "
                f"(got {[type(s).__name__ for s in artifact.stages]})")
        if lr is not None:
            model: "LogisticRegression | TreeEnsemble" = LogisticRegression.from_arrays(
                lr.coefficients, lr.intercept, threshold=lr.threshold)
        elif tree is not None:
            model = trees_mod.from_spark_stage(tree)
        else:
            raise ValueError(
                "artifact has no LogisticRegression or tree classifier stage "
                f"(got {[type(s).__name__ for s in artifact.stages]})")
        return cls(featurizer, model, fold_idf=True, batch_size=batch_size,
                   mesh=mesh)

    def predict_json_async(self, values: Sequence[bytes], text_field: str = "text"
                           ) -> Optional[Tuple["PendingPrediction", np.ndarray,
                                               np.ndarray, np.ndarray,
                                               Optional[list]]]:
        """Raw-JSON fast path: score Kafka message bytes without Python-side
        json.loads (featurize/tfidf.py ``encode_json`` — one native pass from
        message bytes to hashed sparse rows).

        Returns ``(pending, status, span_start, span_len, splice_ctxs)``
        where the pending prediction covers ALL rows positionally (row i =
        values[i]; status 0 rows are all-padding and score as garbage the
        caller must discard), or None when unavailable (no native library or
        vocabulary featurizer). Tree models ride the same native encode: the
        hashed sparse rows scatter to dense TF-IDF and traverse the ensemble
        in one device program (matching the reference's primary trained
        family, fraud_detection_spark.py:56-91 / Q1). The spans locate each
        message's raw string literal for zero-copy output framing
        (stream/engine.py); ``splice_ctxs`` is a list of per-chunk
        ``(marshalled char*[] array, chunk_len)`` for native frame assembly
        (``featurize/native.py build_frames``), or None when any chunk's
        context is unavailable."""
        if self._dev_feat is not None:
            # Device-side featurization owns the hot path: the engine's
            # slow path decodes JSON and predict_async ships raw bytes —
            # the native host tokenize/hash pass this method fronts is the
            # very work the kernel deleted.
            return None
        encode_json = getattr(self.featurizer, "encode_json", None)
        if encode_json is None:
            return None
        pop_ctx = getattr(self.featurizer, "pop_json_splice_ctx", lambda: None)
        is_tree = self._fused_model is None
        tree_binary = is_tree and self._tree_is_binary()
        parts: List[Tuple[object, int]] = []
        phases: List[Phase] = []
        stats: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        ctxs: Optional[List[Tuple[object, int]]] = []
        for start in range(0, len(values), self.batch_size):
            chunk = values[start : start + self.batch_size]
            t0 = time.perf_counter()
            out = encode_json(chunk, text_field,
                              batch_size=self._pad_rows(len(chunk)),
                              keep_splice_ctx=True)
            if out is None:
                return None
            phases.append(Phase("featurize", t0, time.perf_counter() - t0,
                                len(chunk)))
            enc, status, span_start, span_len = out
            ctx = pop_ctx()
            if ctx is None:
                ctxs = None
            elif ctxs is not None:
                ctxs.append((ctx, len(chunk)))
            placed = self._upload(enc, len(chunk), phases)
            if is_tree:
                parts.append((self._dispatch_tree(placed, tree_binary),
                              len(chunk)))
            else:
                parts.append((self._dispatch_fused(placed), len(chunk)))
            stats.append((status, span_start, span_len))
        pending = PendingPrediction(
            parts,
            threshold=0.5 if is_tree else self._fused_model.threshold,
            argmax=is_tree and not tree_binary, phases=phases)
        if not stats:
            empty = np.empty(0, np.int32)
            return pending, empty, empty, empty, ctxs
        return (pending,
                np.concatenate([s[0] for s in stats]),
                np.concatenate([s[1] for s in stats]),
                np.concatenate([s[2] for s in stats]),
                ctxs)

    def _tree_is_binary(self) -> bool:
        """Binary trees: p(class=1) > 0.5 equals argmax over the normalized
        proba (ties -> class 0 both ways), so a 1-D fetch is exact."""
        return isinstance(self.model, TreeEnsemble) and (
            self.model.kind in ("gbt", "xgboost")  # boosted margins are binary
            or self.model.leaf.shape[-1] == 2)

    def pin_device(self) -> dict:
        """Make every model-side constant device-resident NOW, off the hot
        path: fused LR weights (int8 codes + scale when enabled), tree
        ensemble arrays, and the TF-IDF idf vector. Called once per model
        version — at engine start, at bench warm, and by HotSwapPipeline's
        prewarm so every swap/stage candidate RE-pins before it goes active
        — never per batch. Idempotent per pipeline; returns the pin stats."""
        ds = self.device_stats
        if self._pinned_version is not None:
            return {"pinned_bytes": ds.pinned_bytes, "model_pins": ds.pins}
        arrs = [a for a in jax.tree_util.tree_leaves(
                    self._fused_model if self._fused_model is not None
                    else self.model)
                if isinstance(a, jax.Array)]
        if self._fused_model is None and self._tree_idf is None:
            self._tree_idf = self.featurizer.idf_array()
        if self._tree_idf is not None:
            arrs.append(self._tree_idf)
        if self._q8 is not None:
            arrs.extend(self._q8)
        if self._dev_feat is not None:
            # The stop table is a model-side constant of the device
            # featurize program: uploaded once, pinned with the weights.
            arrs.append(self._dev_feat.stop_table())
        jax.block_until_ready(arrs)
        ds.pinned_bytes = int(sum(a.size * a.dtype.itemsize for a in arrs))
        ds.pins += 1
        self._pinned_version = object()
        return {"pinned_bytes": ds.pinned_bytes, "model_pins": ds.pins}

    def _device_rows(self, ids, counts):
        """Fallback placement for one encoded chunk when the packed staging
        layout doesn't apply (ids widened to int32): two device arrays,
        plain single-chip or row-sharded over the serving mesh's "data"
        axis. The SAME jitted scoring programs serve both — jit follows
        input shardings and GSPMD adds the final gather, so mesh-backed
        streaming (engine -> data-parallel scoring) is a placement decision,
        not a second code path. shard_rows pads rows to a data-axis
        multiple; PendingPrediction already slices every chunk back to its
        real count."""
        ids = np.asarray(ids)
        counts = np.asarray(counts)
        self.device_stats.record_chunk(ids.nbytes + counts.nbytes,
                                       transfers=2, rows=ids.shape[0])
        if self.mesh is None:
            return jnp.asarray(ids), jnp.asarray(counts)
        from fraud_detection_tpu.parallel.mesh import shard_rows

        return shard_rows(ids, self.mesh), shard_rows(counts, self.mesh)

    def _device_packed(self, packed: np.ndarray):
        """Place one packed (B, 2, L) staging buffer: ONE host->device
        transfer per micro-batch chunk (the accounting the bench's
        ``device`` block commits)."""
        self.device_stats.record_chunk(packed.nbytes, transfers=1,
                                       rows=packed.shape[0])
        if self.mesh is None:
            return jnp.asarray(packed)
        from fraud_detection_tpu.parallel.mesh import shard_rows

        return shard_rows(packed, self.mesh)

    def _upload(self, enc, rows: int,
                phases: Optional[List[Phase]] = None) -> tuple:
        """Pack and place one encoded chunk (``rows`` real rows), timed as
        the chunk's ``upload`` phase. Returns ``(device buffer(s), packed)``:
        the one packed staging buffer, or the ``(ids, counts)`` pair where
        the packed layout does not apply."""
        t0 = time.perf_counter()
        packed = _pack_encoded(enc)
        if packed is None:
            dev = self._device_rows(enc.ids, enc.counts)
            padded = int(dev[0].shape[0])
            nbytes = sum(int(a.size * a.dtype.itemsize) for a in dev)
        else:
            dev = self._device_packed(packed)
            padded, nbytes = packed.shape[0], packed.nbytes
        if phases is not None:
            phases.append(Phase("upload", t0, time.perf_counter() - t0, rows,
                                padded, nbytes))
        return dev, packed is not None

    def _dispatch_fused(self, placed: tuple) -> object:
        """Launch fused sparse LR scoring for one placed chunk
        (``_upload``) and start the async device->host fetch; shared by
        both predict paths. The chunk rides the packed single-buffer
        upload, donated into the scoring program where the platform
        consumes donations; int8 pipelines score through the quantized
        program on the same staging buffer."""
        dev, packed = placed
        if not packed:
            ids, counts = dev
            p = linear_mod.prob_encoded_arrays(self._fused_model, ids, counts)
        elif self._q8 is not None:
            p = linear_mod.prob_packed_q8(
                self._q8[0], self._q8[1], self._fused_model.intercept,
                dev, donate=self._donate)
        else:
            p = linear_mod.prob_packed(self._fused_model, dev,
                                       donate=self._donate)
        if packed and self._donate:
            self.device_stats.donated += 1
        copy_async = getattr(p, "copy_to_host_async", None)
        if copy_async is not None:
            copy_async()  # start the device->host fetch behind the dispatch
        return p

    def _dispatch_tree(self, placed: tuple, binary: bool) -> object:
        """Launch the scatter-free ensemble traversal for one placed chunk
        (``_upload``) and start the async device->host fetch."""
        if self._tree_idf is None:
            # One upload, reused every chunk (pin_device does this off the
            # hot path; this is the fallback for unpinned pipelines).
            self._tree_idf = self.featurizer.idf_array()
        dev, packed = placed
        if not packed:
            ids, counts = dev
            p = _tree_prob_encoded(self.model, ids, counts, self._tree_idf,
                                   binary)
        else:
            p = _tree_prob_packed(self.model, dev, self._tree_idf, binary,
                                  donate=self._donate)
            if self._donate:
                self.device_stats.donated += 1
        copy_async = getattr(p, "copy_to_host_async", None)
        if copy_async is not None:
            copy_async()  # start the device->host fetch behind the dispatch
        return p

    def _dispatch_bytes(self, texts: Sequence[str], rows: int,
                        tree_binary: bool) -> object:
        """Device-featurized dispatch for one chunk: pack raw UTF-8 bytes
        (the host's entire featurize leg — a memcpy), upload the ONE
        staging tensor, and launch the fused featurize+score program. The
        byte tensor is donated where the platform consumes donations, like
        every other staging buffer."""
        dev = self._dev_feat
        staged, truncated = dev.pack(texts, batch_size=rows)
        ds = self.device_stats
        ds.record_featurize(staged.nbytes, len(texts), truncated)
        ds.record_chunk(staged.nbytes, transfers=1, rows=rows)
        if self.mesh is None:
            staged_dev = jnp.asarray(staged)
        else:
            from fraud_detection_tpu.parallel.mesh import shard_rows

            staged_dev = shard_rows(staged, self.mesh)
        stop_tbl = dev.stop_table()
        if self._fused_model is None:
            if self._tree_idf is None:
                self._tree_idf = self.featurizer.idf_array()
            fn = (_tree_prob_bytes_donating if self._donate
                  else _tree_prob_bytes_plain)
            p = fn(self.model, stop_tbl, staged_dev, self._tree_idf,
                   tree_binary, spec=dev.spec)
        elif self._q8 is not None:
            fn = (_prob_bytes_q8_donating if self._donate
                  else _prob_bytes_q8_plain)
            p = fn(self._q8[0], self._q8[1], self._fused_model.intercept,
                   stop_tbl, staged_dev, spec=dev.spec)
        else:
            fn = _prob_bytes_donating if self._donate else _prob_bytes_plain
            p = fn(self._fused_model, stop_tbl, staged_dev, spec=dev.spec)
        if self._donate:
            ds.donated += 1
        copy_async = getattr(p, "copy_to_host_async", None)
        if copy_async is not None:
            copy_async()  # start the device->host fetch behind the dispatch
        return p

    def predict_async(self, texts: Sequence[str]) -> "PendingPrediction":
        """Featurize + dispatch device scoring WITHOUT blocking on results.

        Returns a handle whose ``resolve()`` materializes the PredictionBatch.
        JAX dispatch is asynchronous, so the caller can overlap host work
        (decode/produce of neighboring batches) with device execution — the
        lever that hides the per-call device round-trip latency in the
        streaming engine. The host featurize leg itself fans out for large
        chunks: ``featurizer.encode`` shards across the thread pool
        (featurize/parallel.py), so at ``pipeline_depth >= 2`` the engine
        overlaps a PARALLEL featurize with the in-flight batches' device
        wait instead of a single-threaded one."""
        parts: List[Tuple[object, int]] = []
        phases: List[Phase] = []
        threshold = 0.5
        argmax = False
        # Multiclass trees need the full (B, C) proba + host argmax — still
        # a single device->host fetch per chunk.
        tree_binary = self._tree_is_binary()
        for start in range(0, len(texts), self.batch_size):
            chunk = list(texts[start : start + self.batch_size])
            n = len(chunk)
            if self._dev_feat is not None:
                # Device-side featurization: raw bytes are the crossing;
                # tokenize/hash/count run inside the scoring program.
                parts.append((self._dispatch_bytes(chunk, self._pad_rows(n),
                                                   tree_binary), n))
                if self._fused_model is not None:
                    threshold = self._fused_model.threshold
                else:
                    argmax = not tree_binary
                continue
            t0 = time.perf_counter()
            enc = self.featurizer.encode(chunk, batch_size=self._pad_rows(n))
            phases.append(Phase("featurize", t0, time.perf_counter() - t0, n))
            placed = self._upload(enc, n, phases)
            if self._fused_model is not None:
                parts.append((self._dispatch_fused(placed), n))
                threshold = self._fused_model.threshold
                continue
            # Trees ride the same scatter-free encoded traversal (and packed
            # upload) as the raw-JSON path — the old densify-then-traverse
            # formulation paid a (B, F) XLA scatter plus a second upload
            # per chunk for bit-identical probabilities.
            parts.append((self._dispatch_tree(placed, tree_binary), n))
            argmax = not tree_binary
        return PendingPrediction(parts, threshold=threshold, argmax=argmax,
                                 phases=phases)

    def predict(self, texts: Sequence[str]) -> PredictionBatch:
        """Score texts in fixed-size micro-batches (pads the tail batch)."""
        return self.predict_async(texts).resolve()

    def predict_one(self, text: str) -> Tuple[int, float]:
        """Single-dialogue convenience (the reference's per-click path)."""
        batch = self.predict([text])
        return int(batch.labels[0]), float(batch.probabilities[0])

    def predict_encoded(self, ids: np.ndarray,
                        counts: np.ndarray) -> PredictionBatch:
        """Score ALREADY-ENCODED rows: (B, L) hashed feature ids + term
        counts, exactly the packed form the featurizer emits and the learn
        window retains (learn/store.py). The shadow replay path scores a
        staged candidate on the window's rows through this — the rows'
        text was deliberately never kept, and re-featurizing is both
        impossible and unnecessary: padding slots (id 0, count 0) are
        inert on every scoring path, so the stored arrays score exactly
        as the original batch did. Rides the same dispatch entries
        (packed upload, fused LR / encoded tree traversal) as live
        serving; rows chunk and pad to the pipeline's compiled shapes."""
        from fraud_detection_tpu.featurize.tfidf import EncodedBatch

        ids = np.asarray(ids)
        counts = np.asarray(counts)
        if ids.shape != counts.shape or ids.ndim != 2:
            raise ValueError(
                f"ids {ids.shape} / counts {counts.shape} must be equal "
                "2-D (B, L) arrays")
        tree_binary = self._tree_is_binary()
        parts: List[Tuple[object, int]] = []
        threshold = 0.5
        argmax = False
        for start in range(0, ids.shape[0], self.batch_size):
            chunk_ids = ids[start : start + self.batch_size]
            chunk_counts = counts[start : start + self.batch_size]
            n = chunk_ids.shape[0]
            rows = self._pad_rows(n)
            if rows != n:
                chunk_ids = np.concatenate(
                    [chunk_ids, np.zeros((rows - n, ids.shape[1]),
                                         ids.dtype)])
                chunk_counts = np.concatenate(
                    [chunk_counts, np.zeros((rows - n, counts.shape[1]),
                                            counts.dtype)])
            placed = self._upload(
                EncodedBatch(ids=chunk_ids, counts=chunk_counts), n)
            if self._fused_model is not None:
                parts.append((self._dispatch_fused(placed), n))
                threshold = self._fused_model.threshold
            else:
                parts.append((self._dispatch_tree(placed, tree_binary), n))
                argmax = not tree_binary
        return PendingPrediction(parts, threshold=threshold,
                                 argmax=argmax).resolve()


@partial(jax.jit, static_argnames=("binary",))
def _tree_prob_encoded(ensemble: TreeEnsemble, ids, counts, idf, binary: bool):
    """Hashed sparse rows -> scatter-free ensemble traversal, ONE compiled
    program (the tree analogue of linear.prob_encoded, for the raw-JSON fast
    path). The traversal reads each node's split-feature value directly from
    the row's term list (models/trees.py _leaf_indices_encoded) — the old
    densify-then-gather formulation paid a (B, 10000) XLA scatter per chunk,
    the single most expensive op on the tree serving path."""
    proba = trees_mod.predict_proba_encoded(ensemble, ids, counts, idf)
    return proba[:, 1] if binary else proba


def _tree_prob_packed_impl(ensemble: TreeEnsemble, packed, idf, binary: bool):
    # Scopes name the two parts in a profiler capture (op metadata only).
    with jax.named_scope("score.unpack"):
        ids, counts = linear_mod.unpack_rows(packed)
    with jax.named_scope("score.traverse"):
        proba = trees_mod.predict_proba_encoded(ensemble, ids, counts, idf)
        return proba[:, 1] if binary else proba


_tree_prob_packed_plain = jax.jit(_tree_prob_packed_impl,
                                  static_argnames=("binary",))
_tree_prob_packed_donating = jax.jit(_tree_prob_packed_impl,
                                     static_argnames=("binary",),
                                     donate_argnums=(1,))


def _tree_prob_packed(ensemble: TreeEnsemble, packed, idf, binary: bool,
                      donate: bool = False):
    """Packed-staging-buffer twin of ``_tree_prob_encoded`` (one upload per
    chunk; buffer donated where the platform consumes donations)."""
    fn = _tree_prob_packed_donating if donate else _tree_prob_packed_plain
    return fn(ensemble, packed, idf, binary)


# ---------------------------------------------------------------------------
# Device-side featurization scoring entries (ops/featurize_kernel.py): the
# staging buffer is the raw-byte tensor itself — featurize (Pallas scan +
# count/pack) and scoring fuse into ONE jitted program per model family, so
# bytes -> probability never touches the host in between. Each entry has a
# donating twin for the byte tensor (argument 2 throughout), same policy as
# the packed entries above.
# ---------------------------------------------------------------------------


def _prob_bytes_impl(model: LogisticRegression, stop_tbl, staged, *, spec):
    from fraud_detection_tpu.ops.featurize_kernel import featurize_bytes

    packed, _ = featurize_bytes(staged, stop_tbl, spec=spec)
    ids, counts = linear_mod.unpack_rows(packed)
    gathered = model.weights[ids]
    m = jnp.sum(gathered * counts, axis=-1) + model.intercept
    return jax.nn.sigmoid(m)


_prob_bytes_plain = jax.jit(_prob_bytes_impl, static_argnames=("spec",))
_prob_bytes_donating = jax.jit(_prob_bytes_impl, static_argnames=("spec",),
                               donate_argnums=(2,))


def _prob_bytes_q8_impl(w_q, scales, intercept, stop_tbl, staged, *, spec):
    from fraud_detection_tpu.ops.featurize_kernel import featurize_bytes

    packed, _ = featurize_bytes(staged, stop_tbl, spec=spec)
    return linear_mod._prob_packed_q8_impl(w_q, scales, intercept, packed)


_prob_bytes_q8_plain = jax.jit(_prob_bytes_q8_impl, static_argnames=("spec",))
_prob_bytes_q8_donating = jax.jit(_prob_bytes_q8_impl,
                                  static_argnames=("spec",),
                                  donate_argnums=(4,))


def _tree_prob_bytes_impl(ensemble: TreeEnsemble, stop_tbl, staged, idf,
                          binary: bool, *, spec):
    from fraud_detection_tpu.ops.featurize_kernel import featurize_bytes

    packed, _ = featurize_bytes(staged, stop_tbl, spec=spec)
    return _tree_prob_packed_impl(ensemble, packed, idf, binary)


_tree_prob_bytes_plain = jax.jit(_tree_prob_bytes_impl,
                                 static_argnames=("binary", "spec"))
_tree_prob_bytes_donating = jax.jit(_tree_prob_bytes_impl,
                                    static_argnames=("binary", "spec"),
                                    donate_argnums=(2,))


def synthetic_demo_pipeline(batch_size: int = 256, *, n: int = 800, seed: int = 7,
                            num_features: int = 10000,
                            model: str = "lr",
                            corpus_kwargs: dict | None = None,
                            mesh=None, int8: bool = False,
                            featurize_device=False) -> ServingPipeline:
    """Train a quick model on the synthetic corpus — the shared demo/bench
    fallback pipeline (one recipe, used by bench.py and app/serve.py).
    ``model``: "lr" (default) | "dt" | "rf" | "xgb". ``corpus_kwargs`` is
    forwarded to generate_corpus (e.g. hard_fraction/label_noise=0 for the
    separable corpus transport tests train against)."""
    from fraud_detection_tpu.data import generate_corpus
    from fraud_detection_tpu.models.train_linear import fit_logistic_regression
    from fraud_detection_tpu.models.train_trees import (
        fit_decision_tree, fit_gradient_boosting, fit_random_forest)

    corpus = generate_corpus(n=n, seed=seed, **(corpus_kwargs or {}))
    feat = HashingTfIdfFeaturizer(num_features=num_features)
    feat.fit_idf([d.text for d in corpus])
    X = np.asarray(feat.featurize_dense([d.text for d in corpus]))
    y = np.asarray([d.label for d in corpus], np.float32)
    if model == "lr":
        clf = fit_logistic_regression(X, y, max_iter=50)
    elif model == "dt":
        clf = fit_decision_tree(X, y)
    elif model == "rf":
        clf = fit_random_forest(X, y, n_trees=20)
    elif model == "xgb":
        clf = fit_gradient_boosting(X, y, n_rounds=20)
    else:
        raise ValueError(f"unknown demo model {model!r}")
    return ServingPipeline(feat, clf, batch_size=batch_size, mesh=mesh,
                           int8=int8, featurize_device=featurize_device)
