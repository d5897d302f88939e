"""Logistic-regression scorer, TPU-native.

Replaces Spark's ``LogisticRegressionModel.transform`` (the final stage of the
shipped serving pipeline, dialogue_classification_model/stages/4_LogisticRegression_*)
with two jitted paths:

  * dense:  margin = X @ w + b over a (B, F) TF-IDF matrix — one MXU matvec.
  * sparse fused: for hashed-TF rows the margin is a gather + segment-sum over
    the padded EncodedBatch — features are never materialized. ``idf * w`` is
    folded into one effective weight vector at model-build time, so serve-time
    work per token is a single gather-accumulate. This is the fast path that
    replaces the reference's per-row 5-stage Spark job (utils/agent_api.py:139-158).

Spark semantics replicated: rawPrediction = [-m, m], probability = sigmoid(m),
prediction = 1 iff p > threshold (threshold 0.5 in the shipped artifact).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from fraud_detection_tpu.featurize.tfidf import EncodedBatch


@jax.tree_util.register_dataclass
@dataclass
class LogisticRegression:
    """Binary logistic regression parameters as a jax pytree.

    ``weights`` are in *feature* space (post-IDF). For fused sparse scoring over
    hashed term counts, use ``effective_weights = idf * weights`` (precomputed
    via ``fold_idf``).
    """

    weights: jax.Array            # (F,) float32
    intercept: jax.Array          # () float32
    threshold: float = 0.5

    @classmethod
    def from_arrays(cls, weights, intercept, threshold: float = 0.5) -> "LogisticRegression":
        return cls(
            weights=jnp.asarray(np.asarray(weights, np.float32)),
            intercept=jnp.asarray(np.float32(intercept)),
            threshold=float(threshold),
        )

    def fold_idf(self, idf) -> "LogisticRegression":
        """Fold an IDF vector into the weights (for raw term-count inputs)."""
        return LogisticRegression(
            weights=self.weights * jnp.asarray(idf, self.weights.dtype),
            intercept=self.intercept,
            threshold=self.threshold,
        )


def margin_dense(model: LogisticRegression, x: jax.Array) -> jax.Array:
    """(B, F) dense features -> (B,) raw margin."""
    return x @ model.weights + model.intercept


# ---------------------------------------------------------------------------
# Packed-buffer serving entries (models/pipeline.py device-resident hot path):
# the host stacks an EncodedBatch's int16 ids and uint16 counts into ONE
# (B, 2, L) int16 staging array, so a micro-batch costs exactly one
# host->device transfer; the program unpacks on-device (a reshape + bitcast,
# free next to the gather). Each entry has a donating twin — when the
# platform consumes donated buffers (models/pipeline.py donation_effective),
# the per-batch input buffer is handed to XLA at dispatch instead of waiting
# for Python refcounting to release it.
# ---------------------------------------------------------------------------


def unpack_rows(packed: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(B, 2, L) int16 -> (ids int32 (B, L), counts float32 (B, L)).

    counts travel as uint16 bits inside the int16 container; the bitcast
    restores them exactly (values up to 65535, matching EncodedBatch)."""
    ids = packed[:, 0, :].astype(jnp.int32)
    counts = jax.lax.bitcast_convert_type(packed[:, 1, :], jnp.uint16)
    return ids, counts.astype(jnp.float32)


def _prob_packed_impl(model: LogisticRegression, packed: jax.Array):
    # Scopes name the two parts in a profiler capture (op metadata only).
    with jax.named_scope("score.unpack"):
        ids, counts = unpack_rows(packed)
    with jax.named_scope("score.gather_dot"):
        gathered = model.weights[ids]                   # (B, L)
        m = jnp.sum(gathered * counts, axis=-1) + model.intercept
        return jax.nn.sigmoid(m)


_prob_packed = jax.jit(_prob_packed_impl)
_prob_packed_donated = jax.jit(_prob_packed_impl, donate_argnums=(1,))


def prob_packed(model: LogisticRegression, packed: jax.Array,
                donate: bool = False) -> jax.Array:
    """Packed-buffer variant of ``prob_encoded_arrays`` (idf must be folded
    into the weights). ``donate=True`` dispatches through the donating
    program — the caller must not touch ``packed`` afterwards."""
    fn = _prob_packed_donated if donate else _prob_packed
    return fn(model, packed)


# ---------------------------------------------------------------------------
# int8 scoring variant: symmetric per-BLOCK weight quantization. The gather
# reads int8 codes (a quarter of the fp32 weight bytes out of HBM) plus one
# f32 scale per 128-weight block; per-block scales matter because TF-IDF LR
# weights carry a few huge outliers — a single per-tensor scale quantized
# everything else to mush (max |Δp| ~0.38 on the shipped artifact; blocks
# bring it under ~1e-2). Quantization error comes from the one weight
# rounding, nothing else; fp32 parity is pinned in tests/test_device_path.py.
# ---------------------------------------------------------------------------

_Q8_BLOCK = 128


def quantize_weights(model: LogisticRegression,
                     block: int = _Q8_BLOCK) -> tuple[jax.Array, jax.Array]:
    """(int8 codes (ceil(F/block)*block,), f32 per-block scales (nb,)) with
    w[i] ~= scales[i // block] * w_q[i]. Codes stay padded to a whole number
    of blocks so consumers recover ``block`` from the two shapes."""
    w = model.weights
    f = w.shape[0]
    nb = -(-f // block)
    wp = jnp.pad(w, (0, nb * block - f)).reshape(nb, block)
    absmax = jnp.maximum(jnp.max(jnp.abs(wp), axis=1), 1e-12)
    scales = (absmax / 127.0).astype(jnp.float32)
    w_q = jnp.clip(jnp.round(wp / scales[:, None]),
                   -127, 127).astype(jnp.int8).reshape(-1)
    return w_q, scales


def _prob_packed_q8_impl(w_q: jax.Array, scales: jax.Array,
                         intercept: jax.Array, packed: jax.Array):
    block = w_q.shape[0] // scales.shape[0]     # static under jit
    ids = packed[:, 0, :].astype(jnp.int32)
    counts = jax.lax.bitcast_convert_type(packed[:, 1, :], jnp.uint16)
    per_term = (w_q[ids].astype(jnp.float32) * scales[ids // block]
                * counts.astype(jnp.float32))
    return jax.nn.sigmoid(jnp.sum(per_term, axis=-1) + intercept)


_prob_packed_q8 = jax.jit(_prob_packed_q8_impl)
_prob_packed_q8_donated = jax.jit(_prob_packed_q8_impl, donate_argnums=(3,))


def prob_packed_q8(w_q: jax.Array, scales: jax.Array, intercept: jax.Array,
                   packed: jax.Array, donate: bool = False) -> jax.Array:
    """int8 packed-buffer scoring (see ``quantize_weights``)."""
    fn = _prob_packed_q8_donated if donate else _prob_packed_q8
    return fn(w_q, scales, intercept, packed)


def margin_encoded(model: LogisticRegression, ids: jax.Array, counts: jax.Array) -> jax.Array:
    """Fused sparse scoring over padded (B, L) bucket ids / counts.

    ``model.weights`` must already include the IDF factor (see ``fold_idf``);
    padding rows have count 0 so they contribute nothing.
    """
    gathered = model.weights[ids.astype(jnp.int32)]          # (B, L)
    return jnp.sum(gathered * counts.astype(model.weights.dtype),
                   axis=-1) + model.intercept


@partial(jax.jit, static_argnames=())
def _predict_dense(model: LogisticRegression, x: jax.Array):
    m = margin_dense(model, x)
    p = jax.nn.sigmoid(m)
    return (p > model.threshold).astype(jnp.int32), p


@jax.jit
def _predict_encoded(model: LogisticRegression, ids: jax.Array, counts: jax.Array):
    m = margin_encoded(model, ids, counts)
    p = jax.nn.sigmoid(m)
    return (p > model.threshold).astype(jnp.int32), p


@jax.jit
def _prob_encoded(model: LogisticRegression, ids: jax.Array, counts: jax.Array):
    return jax.nn.sigmoid(margin_encoded(model, ids, counts))


def prob_encoded(model: LogisticRegression, batch: EncodedBatch) -> jax.Array:
    """Single-output serving path: (B,) p(class=1) only.

    Fetching one array instead of (labels, probs) halves device->host
    round-trips; labels are derived on the host with the identical
    ``p > threshold`` comparison (thresholding commutes with the fetch)."""
    return _prob_encoded(model, jnp.asarray(batch.ids), jnp.asarray(batch.counts))


def predict_dense(model: LogisticRegression, x) -> tuple[jax.Array, jax.Array]:
    """Dense path: returns (predictions int32 (B,), probability of class 1 (B,))."""
    return _predict_dense(model, jnp.asarray(x))


def predict_encoded(model: LogisticRegression, batch: EncodedBatch) -> tuple[jax.Array, jax.Array]:
    """Fused sparse path over an EncodedBatch (idf must be folded into weights)."""
    return _predict_encoded(model, jnp.asarray(batch.ids), jnp.asarray(batch.counts))


def prob_encoded_arrays(model: LogisticRegression, ids: jax.Array,
                        counts: jax.Array) -> jax.Array:
    """Device-array variant of ``prob_encoded`` for callers that place the
    encoded rows themselves (e.g. the mesh-backed ServingPipeline, which
    row-shards them first — jit follows the input shardings, so the same
    compiled program serves single-chip and data-parallel)."""
    return _prob_encoded(model, ids, counts)


def predict_encoded_mesh(model: LogisticRegression, batch: EncodedBatch,
                         mesh) -> tuple[np.ndarray, np.ndarray]:
    """Data-parallel serving over a device mesh: the encoded batch's rows are
    sharded on the mesh "data" axis (weights replicated), each device scores
    its shard with the same fused gather-accumulate as ``prob_encoded``, and
    ONE gather returns the full probability vector — the horizontal-serving
    shape of BASELINE's v5e-8 north star (N chips scoring one micro-batch;
    the reference scales the same way with N Spark consumers on its
    3-partition topic). Rows are zero-padded to a data-axis multiple on the
    host; padded rows cost sigmoid(intercept) each and are sliced off before
    returning. Returns host (pred, prob) at the real row count."""
    from fraud_detection_tpu.parallel.mesh import shard_rows

    n = batch.ids.shape[0]
    ids = shard_rows(np.asarray(batch.ids), mesh)
    counts = shard_rows(np.asarray(batch.counts), mesh)
    prob = np.asarray(_prob_encoded(model, ids, counts))[:n]
    return (prob > model.threshold).astype(np.int32), prob
