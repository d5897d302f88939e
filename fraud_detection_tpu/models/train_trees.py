"""Histogram-based tree training, TPU-native.

Replaces the reference's three tree trainers — Spark MLlib DecisionTree
(maxDepth=5, gini), RandomForest (100 trees, depth 5, featureSubsetStrategy
"auto") and SparkXGBClassifier (100 rounds, depth 5, second-order boosting
with Rabit allreduce) — fraud_detection_spark.py:56-91 — with one engine:

  * Features are quantile-binned once (Spark's own maxBins=32 discretization).
  * Trees grow level-wise in heap layout (node i -> children 2i+1, 2i+2) with
    a FIXED depth bound, so the entire builder is one jit: per level, a
    per-(node, feature, bin) statistics histogram via segment-sum, a cumsum
    gain scan over bins, and a masked argmax pick the splits; rows then
    re-route by gathering their node's split. No data-dependent control flow
    anywhere — XLA sees dense scatter/cumsum/argmax over static shapes.
  * Split criteria are pluggable over the same histograms: weighted-gini
    impurity decrease (Spark DT/RF semantics) and second-order logloss gain
    (XGBoost semantics: G^2/(H+lambda) with leaf value -G/(H+lambda)).
  * Random forest = the same builder looped per chunk inside one program
    over Poisson(1) bootstrap row weights with per-node Bernoulli feature
    masks (expected size sqrt(F), approximating Spark's exact sqrt subset -
    documented deviation).
  * Boosting = the builder called per round on (grad, hess) stats.

On single-TPU runs the per-level histogram and gain scan default to the
Pallas MXU kernels (ops/histogram.py); trainer loops keep per-round state on
device so wall-clock is not dominated by host round-trips.

Distribution: with inputs sharded over the mesh "data" axis, the per-level
segment-sums reduce across chips (XLA inserts the psum) — exactly the
gradient-histogram allreduce XGBoost does over Rabit, riding ICI instead
(the Pallas path is forced off under a mesh: pallas_call has no SPMD
partitioning rule — see resolve_config).
"""

from __future__ import annotations

import math
import weakref
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fraud_detection_tpu.models.trees import TreeEnsemble
from fraud_detection_tpu.utils.device import on_tpu, pallas_interpret


# ---------------------------------------------------------------------------
# Quantile binning
# ---------------------------------------------------------------------------

def quantile_bin_edges(X: np.ndarray, n_bins: int = 32) -> np.ndarray:
    """Per-feature quantile edges, (F, n_bins - 1), host-side numpy.

    Mirrors Spark's maxBins quantile discretization. Duplicate edges (heavy
    zero-inflation in TF-IDF columns) are fine: bins collapse and those split
    candidates simply tie.
    """
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    return np.quantile(np.asarray(X, np.float32), qs, axis=0).T.astype(np.float32)


def bin_rows_host(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Host-side twin of ``apply_bins`` returning int8 bin ids.

    bin = #(edges < x) for both (``searchsorted(..., side="left")`` counts
    strictly-smaller sorted edges), so uploading these bins and training on
    them is bit-identical to uploading floats and binning on device — at a
    quarter of the bytes (int8 vs f32): at 100k x 2048 the f32 upload is
    819MB against 205MB of bins. n_bins <= 128 keeps int8 exact; the trainers widen to
    int32 on device."""
    if edges.shape[1] > 127:
        raise ValueError(
            f"{edges.shape[1]} edges per feature exceeds int8 range "
            "(n_bins must be <= 128 for host binning)")
    if not np.isfinite(X).all():
        # searchsorted sorts NaN above every edge (top bin) while apply_bins
        # counts `edges < NaN` as 0 (bottom bin) — refuse rather than let
        # the two documented-equivalent paths train different models.
        raise ValueError("bin_rows_host requires finite input "
                         "(NaN/inf bin differently on host and device)")
    out = np.empty(X.shape, np.int8)
    for f in range(X.shape[1]):
        out[:, f] = np.searchsorted(edges[f], X[:, f], side="left")
    return out


@jax.jit
def apply_bins(X: jax.Array, edges: jax.Array) -> jax.Array:
    """(N, F) values -> (N, F) int32 bin ids; bin = #(edges < x) so that
    ``x <= edges[b]  <=>  bin(x) <= b`` (keeps serve-time ``x <= threshold``
    traversal bit-consistent with train-time binning).

    Computed as an unrolled compare-accumulate over the (static, <= 31) edge
    columns rather than a binary search: ``searchsorted``'s data-dependent
    gathers are hostile to the VPU (seconds at 100k x 2048 on TPU), while
    the compares fuse into one elementwise HBM sweep."""
    bins = jnp.zeros(X.shape, jnp.int32)
    for j in range(edges.shape[1]):
        bins = bins + (X > edges[None, :, j]).astype(jnp.int32)
    return bins


# ---------------------------------------------------------------------------
# Split criteria over (left, right) stat blocks
# ---------------------------------------------------------------------------

def _gini_impurity(stats: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """stats (..., K) class counts -> (impurity, total_count)."""
    n = stats.sum(-1)
    p = stats / jnp.maximum(n[..., None], 1e-12)
    return 1.0 - jnp.sum(p * p, axis=-1), n


def _gini_gain(left: jax.Array, total: jax.Array) -> jax.Array:
    """Weighted impurity decrease for every (node, feature, bin) candidate.

    left: (L, F, B, K) cumulative class counts for rows with bin <= b;
    total: (L, 1, 1, K). Returns (L, F, B) gain; empty-child candidates -inf.
    """
    right = total - left
    gi_p, n_p = _gini_impurity(total)
    gi_l, n_l = _gini_impurity(left)
    gi_r, n_r = _gini_impurity(right)
    n_safe = jnp.maximum(n_p, 1e-12)
    gain = gi_p - (n_l * gi_l + n_r * gi_r) / n_safe
    valid = (n_l > 0) & (n_r > 0)
    return jnp.where(valid, gain, -jnp.inf)


def _xgb_gain(left: jax.Array, total: jax.Array, lam: float, min_child_weight: float) -> jax.Array:
    """Second-order gain: stats K=3 are (grad, hess, count)."""
    right = total - left
    gl, hl = left[..., 0], left[..., 1]
    gr, hr = right[..., 0], right[..., 1]
    gp, hp = total[..., 0], total[..., 1]
    score = lambda g, h: (g * g) / (h + lam)
    gain = 0.5 * (score(gl, hl) + score(gr, hr) - score(gp, hp))
    valid = (hl >= min_child_weight) & (hr >= min_child_weight) & \
            (left[..., 2] > 0) & (right[..., 2] > 0)
    return jnp.where(valid, gain, -jnp.inf)




def _feature_mask(mask_keys_level, width: int, f: int, f_padded: int):
    """Per-node Bernoulli feature subsets (expected size sqrt(F)), batched
    over a leading tree axis: mask_keys_level (T, key) -> (T, width, f_padded).

    The draw runs over the TRUE feature count ``f`` (the subset probability
    and the PRNG stream must not depend on tile-alignment padding); padded
    feature columns are masked False so they can never be selected."""
    p_keep = jnp.sqrt(jnp.float32(f)) / f
    mask = jax.vmap(
        lambda key: jax.random.bernoulli(key, p_keep, (width, f))
    )(mask_keys_level)
    # Bias-free fallback: a node that drew an empty subset (probability
    # ~(1-p)^F, astronomically rare) considers all features.
    empty = ~mask.any(axis=2)
    mask = mask | empty[:, :, None]
    if f_padded != f:
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, f_padded - f)))
    return mask



def _node_totals(stats, seg_node, width: int, batch_factor: int = 1):
    """Per-node stat totals as a one-hot matmul instead of segment_sum:
    XLA lowers segment_sum to a serial scatter-add (~10ms for 100k rows on
    TPU) while the (L+1, N) @ (N, K) contraction is trivial MXU work.
    HIGHEST precision keeps f32-faithful accumulation: exact for the integer
    gini stats, ulp-level for xgb grad/hess. The overflow segment (rows with
    seg_node == width) is computed and sliced away, same as the scatter
    formulation.

    The dense one-hot transient is (width+1, N) f32 — fine at the default
    depth 5 (width <= 32) but growing as 2^depth * N; above a ~256MB
    threshold (e.g. depth 10 at 1M rows would be ~4GB) this falls back to
    the segment_sum formulation it replaced, trading the MXU win for
    bounded memory. ``batch_factor``: callers that vmap this over a tree
    chunk pass the chunk width so the threshold sees the REAL materialized
    size (T, width+1, N), not the per-tree slice."""
    n = stats.shape[0]
    if batch_factor * (width + 1) * n * 4 > _DENSE_TRANSIENT_LIMIT:
        return jax.ops.segment_sum(stats, seg_node, num_segments=width + 1)[:-1]
    onehot = (seg_node[None, :] == jnp.arange(width + 1)[:, None]).astype(
        stats.dtype)                                       # (L+1, N)
    return jax.lax.dot_general(
        onehot, stats, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)[:-1]          # (L, K)


def _child_totals(hist, totals, best_f, best_b, do_split):
    """Next level's per-node totals from this level's histogram: the left
    child's stats are the cumulative histogram of the parent's chosen
    feature at the chosen bin; the right child's are the complement. Heap
    order interleaves (left, right) per parent; children of non-split
    parents get zeros (no rows ever route there — matches the scanned
    totals). Supports an optional leading tree axis.

    hist (..., L, F, NB, K); totals (..., L, K); best_f/best_b/do_split
    (..., L) -> (..., 2L, K)."""
    # One-hot contractions instead of take_along_axis: TPU lowers these
    # small-table gathers to kCustom scans over the full (.., L, F, NB, K)
    # slab (~6ms/level profiled); the masked reductions are single
    # vectorized passes.
    f = hist.shape[-3]
    onehot_f = (best_f[..., None] == jnp.arange(f)).astype(hist.dtype)
    hist_f = jnp.einsum("...lfbk,...lf->...lbk", hist, onehot_f,
                        precision=jax.lax.Precision.HIGHEST)
    cum_f = jnp.cumsum(hist_f, axis=-2)
    nb = hist.shape[-2]
    onehot_b = (best_b[..., None] == jnp.arange(nb)).astype(hist.dtype)
    left = jnp.einsum("...lbk,...lb->...lk", cum_f, onehot_b,
                      precision=jax.lax.Precision.HIGHEST)   # (..., L, K)
    right = totals - left
    pair = jnp.stack([left, right], axis=-2)              # (..., L, 2, K)
    pair = pair * do_split[..., None, None]
    shape = pair.shape[:-3] + (2 * pair.shape[-3], pair.shape[-1])
    return pair.reshape(shape)


def _select_splits(hist, totals, mask, cfg: TreeTrainConfig):
    """XLA split selection for one level, batched over a leading tree axis.

    hist (T, L, F, NB, K) statistics; totals (T, L, K); mask (T, L, F) bool
    feature subsets or None. Returns (best_f, best_b, best_gain), each
    (T, L) — flat first-occurrence argmax over (F, NB-1) per node.
    """
    nb = cfg.n_bins
    # Inclusive bin prefix as an upper-triangular matmul: jnp.cumsum lowers
    # to a log-step scan (~log2(NB) full passes over the (T, L, F, NB, K)
    # slab per level), while the (NB, NB) contraction is one MXU pass —
    # the same formulation the Pallas gain kernel uses in-tile. HIGHEST
    # precision keeps the f32 count/grad accumulation exact at these
    # magnitudes (a default bf16 dot would round counts above 2^8).
    tri = (jnp.arange(nb)[:, None] <= jnp.arange(nb)[None, :]).astype(hist.dtype)
    cum = jnp.einsum("tlfbk,bc->tlfck", hist, tri,
                     precision=jax.lax.Precision.HIGHEST)
    total_b = totals[:, :, None, None, :]
    if cfg.criterion == "gini":
        gain = _gini_gain(cum, total_b)                   # (T, L, F, NB)
    else:
        gain = _xgb_gain(cum, total_b, cfg.reg_lambda, cfg.min_child_weight)
    gain = gain[..., : nb - 1]                            # last bin: no right side
    if mask is not None:
        gain = jnp.where(mask[:, :, :, None], gain, -jnp.inf)
    t, width = gain.shape[:2]
    flat = gain.reshape(t, width, -1)
    best = jnp.argmax(flat, axis=2)
    best_gain = jnp.take_along_axis(flat, best[:, :, None], axis=2)[:, :, 0]
    return ((best // (nb - 1)).astype(jnp.int32),
            (best % (nb - 1)).astype(jnp.int32), best_gain)


#: Dense-transient budget shared by _route_rows and _node_totals guards.
_DENSE_TRANSIENT_LIMIT = 256 * 1024 * 1024


def _route_rows(bins, local, seg_valid, node, best_f, best_b, do_split,
                width: int, dense_limit: int = _DENSE_TRANSIENT_LIMIT):
    """Row re-routing for one level, batched over a leading tree axis:
    gather each row's node's chosen split, compare bin ids, descend.
    Rows whose node became a leaf stop descending and drop out of deeper
    histograms (their prediction lives at the marked leaf).
    local/seg_valid/node (T, N); best_f/best_b/do_split (T, L).
    Returns (node, active), each (T, N)."""
    row_local = jnp.clip(local, 0, width - 1)
    # Per-NODE column extraction instead of a per-row feature gather: every
    # row at node l reads the same split column best_f[t, l], so ONE
    # (N, F) @ (F, T*L) one-hot matmul pulls all needed bin columns (exact:
    # bin ids < 32 are exact in bf16 operands / f32 accumulation) and a
    # vectorized one-hot select picks each row's own node column. The
    # row-wise take_along_axis this replaces lowered to a serialized TPU
    # gather — ~25ms per level at bench shape, the forest builder's single
    # largest op (profiled r5); the matmul reads bins once at ~1ms.
    t, n = local.shape
    if t * n * width * 4 > dense_limit:
        # Same 256MB dense-transient guard as _node_totals: deep/wide
        # configs fall back to the row-wise gathers (slower, O(T*N) memory —
        # no (T, N, width) one-hot anywhere on this branch).
        row_b = jnp.take_along_axis(best_b, row_local, axis=1)
        row_split = jnp.take_along_axis(do_split, row_local, axis=1)
        row_f = jnp.take_along_axis(best_f, row_local, axis=1)
        row_bin = jax.vmap(
            lambda rf: jnp.take_along_axis(bins, rf[:, None], axis=1)[:, 0]
        )(row_f).astype(jnp.float32)
    else:
        # sel: each row's one-hot over this level's nodes — drives the
        # per-node column select AND the small-table lookups (row_b,
        # row_split), which as take_along_axis lowered to ~5ms kCustom
        # gathers over (T, N) on TPU (profiled r5).
        sel = row_local[:, :, None] == jnp.arange(width)[None, None, :]
        row_b = jnp.sum(jnp.where(sel, best_b[:, None, :], 0), axis=2)
        row_split = jnp.any(sel & do_split[:, None, :], axis=2)
        f = bins.shape[1]
        onehot_f = (best_f.reshape(-1)[None, :]
                    == jnp.arange(f)[:, None]).astype(jnp.bfloat16)  # (F, T*L)
        cols = jax.lax.dot_general(
            bins.astype(jnp.bfloat16), onehot_f, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                      # (N, T*L)
        cols = cols.reshape(n, *best_f.shape).transpose(1, 0, 2)
        row_bin = jnp.sum(jnp.where(sel, cols, 0.0), axis=2)         # (T, N)
    go_left = row_bin <= row_b.astype(row_bin.dtype)
    new_node = jnp.where(go_left, 2 * node + 1, 2 * node + 2)
    node = jnp.where(seg_valid & row_split, new_node, node)
    return node, seg_valid & row_split


# ---------------------------------------------------------------------------
# Single-tree level-wise builder (jit-unrolled over levels)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TreeTrainConfig:
    max_depth: int = 5            # Spark maxDepth=5 (fraud_detection_spark.py:62,72,81)
    n_bins: int = 32              # Spark default maxBins
    min_info_gain: float = 0.0
    criterion: str = "gini"       # "gini" | "xgb"
    reg_lambda: float = 1.0       # xgb: L2 on leaf values and split gain
    min_child_weight: float = 1e-6
    learning_rate: float = 0.3    # xgb: leaf-value shrinkage (eta)
    # Pallas histogram + gain-scan kernels (ops/histogram.py) for the
    # no-feature-mask path (DT/boosting). None = auto: compiled kernels on
    # TPU, XLA segment-sum elsewhere (interpret-mode Pallas is only for
    # tests). Resolved to a concrete bool at construction so jit static
    # hashing and resume fingerprints see a deterministic value.
    use_pallas: Optional[bool] = None

    def __post_init__(self):
        if self.use_pallas is None:
            object.__setattr__(self, "use_pallas", on_tpu())


def _build_tree(bins, stats, row_weights, feature_mask_keys, cfg: TreeTrainConfig,
                true_features: Optional[int] = None):
    """Grow one tree. All shapes static; python loop over levels unrolls in jit.

    bins: (N, F) int32; stats: (N, K) per-row statistics (class one-hots for
    gini; grad/hess/count for xgb), already multiplied by bootstrap weights;
    row_weights: (N,) 0/1-ish activity weights; feature_mask_keys: PRNG key
    per level for Bernoulli feature subsets, or None for all features.

    ``true_features``: the pre-padding feature count — the Bernoulli
    feature-subset draw must not see tile-alignment padding (subset
    probability and PRNG stream follow the real F).

    Returns flat arrays (M,) feature/threshold-bin/left/right + (M, K) stats.
    """
    n, f = bins.shape
    f_true = f if true_features is None else true_features
    k = stats.shape[-1]
    nb = cfg.n_bins
    depth = cfg.max_depth
    m = 2 ** (depth + 1) - 1

    feature = jnp.full((m,), -1, jnp.int32)
    split_bin = jnp.zeros((m,), jnp.int32)
    left_child = jnp.full((m,), -1, jnp.int32)
    right_child = jnp.full((m,), -1, jnp.int32)
    node_stats = jnp.zeros((m, k), stats.dtype)

    stats = stats * row_weights[:, None]
    node = jnp.zeros((n,), jnp.int32)  # heap position per row
    active = row_weights > 0

    # Gini statistics are one-hot class counts times small-integer weights:
    # the histogram runs as ONE exact int8 MXU pass (vs two bf16 passes for
    # float grad/hess stats), and node totals are DERIVED instead of scanned:
    # level 0's from the histogram (feature 0's bins partition the root's
    # rows), deeper levels' from the parent's cumulative stats at its chosen
    # split (left child = cum[f*, b*]; right = parent - left) — sibling
    # arithmetic that removes every per-level segment-sum sweep including
    # the leaf level's. All quantities are exact integers, so the derived
    # totals are bit-equal to the XLA path's scanned ones.
    exact = bool(cfg.use_pallas) and cfg.criterion == "gini"
    carried = None   # exact path: totals for this level, derived at l-1

    for level in range(depth + 1):
        offset = 2 ** level - 1
        width = 2 ** level
        local = node - offset
        seg_valid = active & (local >= 0) & (local < width)
        # Inactive rows route to an overflow segment that is sliced away.
        seg_node = jnp.where(seg_valid, local, width)

        if level == depth:
            # Deepest level grows no splits: only the leaf totals are needed
            # — derived on the exact path, one cheap scan on the float path.
            totals = (carried if exact and carried is not None
                      else _node_totals(stats, seg_node, width))
            node_stats = node_stats.at[offset : offset + width].set(totals)
            break

        if cfg.use_pallas:
            # The Pallas MXU histogram serves every trainer — feature masks
            # only affect SPLIT SELECTION, not the statistics, so the forest
            # path reuses the same kernel and applies its mask on the gains.
            from fraud_detection_tpu.ops.histogram import (
                best_splits, node_feature_bin_histogram)

            hist = node_feature_bin_histogram(
                bins, jnp.where(seg_valid, local, width), stats,
                n_nodes=width, n_bins=nb, interpret=pallas_interpret(),
                exact_int8=exact)
        else:
            def hist_one_feature(fbins):
                seg = jnp.where(seg_valid, local * nb + fbins, width * nb)
                return jax.ops.segment_sum(stats, seg, num_segments=width * nb + 1)[:-1]
            hist = jax.vmap(hist_one_feature, in_axes=1)(bins)      # (F, L*NB, K)
            hist = hist.reshape(f, width, nb, k).transpose(1, 0, 2, 3)  # (L,F,NB,K)

        if exact:
            totals = (hist[:, 0].sum(axis=1) if carried is None else carried)
        else:
            totals = _node_totals(stats, seg_node, width)
        node_stats = node_stats.at[offset : offset + width].set(totals)

        if cfg.use_pallas and feature_mask_keys is None:
            best_f, best_b, best_gain = best_splits(
                hist, totals, criterion=cfg.criterion, n_bins=nb,
                reg_lambda=cfg.reg_lambda, min_child_weight=cfg.min_child_weight,
                interpret=pallas_interpret())
        else:
            mask = (None if feature_mask_keys is None
                    else _feature_mask(feature_mask_keys[level][None], width,
                                       f_true, f))
            bf, bb, bg = _select_splits(hist[None], totals[None], mask, cfg)
            best_f, best_b, best_gain = bf[0], bb[0], bg[0]
        do_split = best_gain > cfg.min_info_gain

        pos = offset + jnp.arange(width)
        feature = feature.at[pos].set(jnp.where(do_split, best_f, -1))
        split_bin = split_bin.at[pos].set(best_b)
        left_child = left_child.at[pos].set(jnp.where(do_split, 2 * pos + 1, -1))
        right_child = right_child.at[pos].set(jnp.where(do_split, 2 * pos + 2, -1))

        if exact:
            carried = _child_totals(hist, totals, best_f, best_b, do_split)

        node1, active1 = _route_rows(
            bins, local[None], seg_valid[None], node[None],
            best_f[None], best_b[None], do_split[None], width)
        node, active = node1[0], active1[0]

    # ``node`` is each ACTIVE row's final leaf heap position — the boosting
    # round reuses it instead of re-traversing (a per-row gather walk).
    # Weight-0 rows (tile padding, mesh padding) never route and stay at 0;
    # their margins are inert (stats are weight-zeroed before every
    # histogram), so this costs nothing downstream.
    return feature, split_bin, left_child, right_child, node_stats, node


@partial(jax.jit, static_argnames=("cfg", "use_feature_mask", "true_features"))
def _build_tree_jit(bins, stats, row_weights, mask_keys, cfg: TreeTrainConfig,
                    use_feature_mask: bool, true_features: Optional[int] = None):
    keys = mask_keys if use_feature_mask else None
    return _build_tree(bins, stats, row_weights, keys, cfg, true_features)[:5]


@partial(jax.jit, static_argnames=("cfg", "use_feature_mask", "true_features"))
def _build_tree_chunk(bins, stats, row_weights, mask_keys,
                      cfg: TreeTrainConfig, use_feature_mask: bool,
                      true_features: Optional[int] = None):
    """A chunk of independent trees in ONE program.

    Pallas path: all trees per level go through ONE fused multi-tree
    histogram kernel — the trees share ``bins``, so the kernel's dominant
    cost (the multihot build) is paid once per cell instead of per tree, and
    the fused dot fills MXU lanes a single tree leaves idle.

    XLA path: looped (not vmapped) single-tree builds — vmapping the
    segment-sum histogram multiplies its working set by the chunk size and
    OOMs HBM at bench scale.

    Per-tree PRNG keys come from the caller, so the chunking strategy never
    changes results."""
    if cfg.use_pallas:
        return _build_forest_chunk_pallas(
            bins, stats, row_weights,
            mask_keys if use_feature_mask else None, cfg, true_features)
    outs = [
        _build_tree(bins, stats, row_weights[i],
                    mask_keys[i] if use_feature_mask else None, cfg,
                    true_features)[:5]     # drop the per-row leaf positions
        for i in range(row_weights.shape[0])
    ]
    return tuple(jnp.stack(parts) for parts in zip(*outs))


def _build_forest_chunk_pallas(bins, stats, row_weights, mask_keys,
                               cfg: TreeTrainConfig,
                               true_features: Optional[int] = None):
    """Batched level-wise builder: every per-row/per-node array carries a
    leading tree axis, and the per-level histogram is one
    ``node_feature_bin_histogram_multi`` call for the whole chunk. Math is
    identical to looping ``_build_tree`` per tree (same per-element f32
    products, same hi/lo bf16 rounding, same masked-gain argmaxes) — the
    interpret-mode parity test asserts structural equality."""
    from fraud_detection_tpu.ops.histogram import (
        node_feature_bin_histogram_multi)

    t, n = row_weights.shape
    f = bins.shape[1]
    k = stats.shape[-1]
    nb = cfg.n_bins
    depth = cfg.max_depth
    m = 2 ** (depth + 1) - 1

    feature = jnp.full((t, m), -1, jnp.int32)
    split_bin = jnp.zeros((t, m), jnp.int32)
    left_child = jnp.full((t, m), -1, jnp.int32)
    right_child = jnp.full((t, m), -1, jnp.int32)
    node_stats = jnp.zeros((t, m, k), stats.dtype)

    node = jnp.zeros((t, n), jnp.int32)
    active = row_weights > 0
    # Gini chunks (the forest's only criterion) qualify for the exact int8
    # MXU pass: one-hot class stats x Poisson weights, products < 128.
    exact = cfg.criterion == "gini"

    def seg_totals(locals_masked, width):
        # per-tree totals via the one-hot matmul (segment_sum scatters are
        # ~10ms per call at bench scale; this is trivial MXU work)
        return jax.vmap(
            lambda loc, w: _node_totals(stats * w[:, None], loc, width,
                                        batch_factor=t)
        )(locals_masked, row_weights)                           # (T, L, K)

    carried = None   # exact path: this level's totals, derived at l-1

    for level in range(depth + 1):
        offset = 2 ** level - 1
        width = 2 ** level
        local = node - offset                                   # (T, N)
        seg_valid = active & (local >= 0) & (local < width)
        locals_masked = jnp.where(seg_valid, local, width)

        if level == depth:
            # Leaves only: derived totals (exact path) skip the final scan.
            totals = (carried if exact and carried is not None
                      else seg_totals(locals_masked, width))
            node_stats = node_stats.at[:, offset : offset + width].set(totals)
            break

        hist = node_feature_bin_histogram_multi(
            bins, locals_masked, row_weights, stats,
            n_nodes=width, n_bins=nb, interpret=pallas_interpret(),
            exact_int8=exact)
        if exact:
            totals = (hist[:, :, 0].sum(axis=2) if carried is None
                      else carried)                             # (T, L, K)
        else:
            totals = seg_totals(locals_masked, width)
        node_stats = node_stats.at[:, offset : offset + width].set(totals)

        mask = (None if mask_keys is None
                else _feature_mask(mask_keys[:, level], width,
                                   f if true_features is None else true_features,
                                   f))
        best_f, best_b, best_gain = _select_splits(hist, totals, mask, cfg)
        do_split = best_gain > cfg.min_info_gain

        pos = offset + jnp.arange(width)
        feature = feature.at[:, pos].set(jnp.where(do_split, best_f, -1))
        split_bin = split_bin.at[:, pos].set(best_b)
        left_child = left_child.at[:, pos].set(
            jnp.where(do_split, 2 * pos + 1, -1))
        right_child = right_child.at[:, pos].set(
            jnp.where(do_split, 2 * pos + 2, -1))

        if exact:
            carried = _child_totals(hist, totals, best_f, best_b, do_split)

        node, active = _route_rows(bins, local, seg_valid, node,
                                   best_f, best_b, do_split, width)

    return feature, split_bin, left_child, right_child, node_stats


# Poisson(1) inverse CDF, support 0..12: P(k > 12) ~ 6e-11 is below f32
# uniform resolution, so searchsorted(u) IS the exact Poisson(1) quantile
# function at the precision the draw sees.
_POISSON1_CDF = np.cumsum(
    [math.exp(-1.0) / math.factorial(k) for k in range(13)]).astype(np.float32)


def _poisson1(key, shape) -> jax.Array:
    """Poisson(1) bootstrap weights via inverse-CDF lookup.

    ``jax.random.poisson``'s general-rate rejection sampler costs ~69ms per
    (8, 100k) draw on v5e — 8.6ms/tree of the forest's device critical path
    (a third of the fused chunk program itself). At rate 1 the distribution
    has 13 reachable outcomes, so one uniform draw + a 13-entry searchsorted
    replaces it, trivially within the exact-int8 histogram contract (max
    weight 13 << 127). NOTE: this changes the bootstrap PRNG stream —
    same-seed forests differ from builds before this change, and the
    resume fingerprint's ``bootstrap_sampler`` key refuses pre-change
    snapshots."""
    u = jax.random.uniform(key, shape)
    # Vectorized quantile: count CDF entries below u (a 13-wide broadcast
    # compare-sum; jnp.searchsorted's default method lowers to a serial
    # scan, which benchmarked SLOWER than the rejection sampler).
    cdf = jnp.asarray(_POISSON1_CDF)
    return jnp.sum(u[..., None] > cdf, axis=-1).astype(jnp.float32)


def _edges_to_thresholds(edges: np.ndarray, feature: np.ndarray, split_bin: np.ndarray):
    """Map (feature, bin) splits to serve-time thresholds: edges[f][b]."""
    thr = np.zeros(feature.shape, np.float32)
    valid = feature >= 0
    thr[valid] = edges[feature[valid], split_bin[valid]]
    return thr


# ---------------------------------------------------------------------------
# Public trainers
# ---------------------------------------------------------------------------

def resolve_tree_chunk(cfg: TreeTrainConfig, num_classes: int = 2) -> int:
    """Default trees-per-program for the forest builder — THE one place the
    chunk rule lives (bench.py's roofline accounting imports it too).

    Fused-kernel VMEM: the accumulator block is (chunk * num_classes *
    2^depth) rows x (feature_tile * n_bins) lanes of f32; 512 rows (= 8
    trees * 2 classes * depth-5 leaves, the measured budget) is the ceiling,
    so the chunk shrinks with class count and depth. The XLA loop path uses
    4 (compile time grows with the unroll)."""
    return (max(1, 512 // (num_classes * 2 ** cfg.max_depth))
            if cfg.use_pallas else 4)


def resolve_config(config: Optional[TreeTrainConfig], mesh,
                 **defaults) -> TreeTrainConfig:
    """Trainer-entry config resolution. With a mesh, the Pallas path is
    forced OFF: pallas_call has no SPMD partitioning rule, so GSPMD would
    either fail to lower or gather the full row set onto every chip — the
    distributed histogram design is the segment-sum whose psum XLA inserts."""
    cfg = config or TreeTrainConfig(**defaults)
    if mesh is not None and cfg.use_pallas:
        cfg = TreeTrainConfig(**{**cfg.__dict__, "use_pallas": False})
    return cfg


def _drain_lists_to_host(lists, n_host: int) -> int:
    """device_get the tail (>= n_host) of each accumulator list in one
    transfer; returns the new host watermark."""
    pulled = jax.device_get([lst[n_host:] for lst in lists])
    for lst, new in zip(lists, pulled):
        lst[n_host:] = new
    return len(lists[0])


# Device bin matrices are immutable, so their (min, max) is FETCHED once per
# array — but the RANGE CHECK still runs per fit, against that fit's n_bins
# (a cached pass/fail would silently skip validation when a later fit uses a
# smaller n_bins). jax arrays are unhashable, so the cache is id-keyed with
# a weakref.finalize that evicts the id when the array is collected (before
# CPython can recycle it).
_VALIDATED_BIN_RANGE: dict = {}   # id(array) -> (lo, hi)


def _cache_bins_range(x, lo: int, hi: int) -> None:
    if id(x) in _VALIDATED_BIN_RANGE:
        return  # one finalizer per array, not one per fit
    try:
        weakref.finalize(x, _VALIDATED_BIN_RANGE.pop, id(x), None)
    except TypeError:
        return  # not weakref-able: fetch on every call instead
    _VALIDATED_BIN_RANGE[id(x)] = (lo, hi)


def _prepare_inputs(X, y, num_classes, cfg, edges, mesh):
    """Shared prep: binning, per-row class stats, activity weights.

    ``X`` may be float features (binned here, on device) OR integer bin ids
    from ``bin_rows_host`` — the pre-binned path requires ``edges`` (they
    define the serve-time thresholds and can't be recovered from bins) and
    skips ``apply_bins``, so the caller uploads int8 instead of f32.

    With a mesh, rows are padded to a data-axis multiple and sharded; padded
    rows get weight 0 so every histogram they touch sees nothing. The
    per-level segment-sums then reduce across chips (XLA-inserted psum) —
    the distributed gradient-histogram allreduce.
    """
    from fraud_detection_tpu.parallel import mesh as mesh_lib

    if not hasattr(X, "shape"):  # plain sequences stay accepted
        X = np.asarray(X, np.float32)
    prebinned = np.issubdtype(np.dtype(X.dtype), np.integer)
    if prebinned and edges is None:
        raise ValueError(
            "integer X means pre-binned input (bin_rows_host), which requires "
            "the matching edges= — thresholds cannot be recovered from bins")
    n = X.shape[0]
    if not prebinned and (edges is None or mesh is not None):
        # Quantiles are host-side; the mesh path shards from host rows.
        X = np.asarray(X, np.float32)
    y = np.asarray(y)
    if edges is None:
        edges = quantile_bin_edges(X, cfg.n_bins)
    if mesh is not None:
        Xd = mesh_lib.shard_rows(np.asarray(X), mesh)
        yd = mesh_lib.shard_rows(np.asarray(y, np.float32), mesh)
        weights = mesh_lib.shard_rows(np.ones(n, np.float32), mesh)
    else:
        # No host round-trip when the caller already staged X on device with
        # precomputed edges (transfer can dwarf training on a remote host).
        Xd = X if prebinned else jnp.asarray(X, dtype=jnp.float32)
        yd = jnp.asarray(np.asarray(y, np.float32))
        weights = jnp.ones((n,), jnp.float32)
    if prebinned:
        bins = jnp.asarray(Xd).astype(jnp.int32)
        # Integer dtype is the pre-binned signal, so validate the claim: a
        # raw integer FEATURE matrix routed here would silently index
        # histograms with garbage (clamped out-of-range ids), not error.
        # Host inputs validate in numpy; device inputs pay ONE stacked fetch
        # (not two int() syncs) — and only ONCE per array: the matrix is
        # immutable on device, so a repeat fit does not wait on it again.
        if isinstance(X, np.ndarray):
            lo, hi = int(X.min()), int(X.max())
        elif id(X) in _VALIDATED_BIN_RANGE:
            lo, hi = _VALIDATED_BIN_RANGE[id(X)]  # fetched once; checked below
        else:
            lo, hi = (int(v) for v in
                      jax.device_get(jnp.stack([bins.min(), bins.max()])))
        if lo < 0 or hi >= cfg.n_bins:
            raise ValueError(
                f"pre-binned X has ids in [{lo}, {hi}] but n_bins={cfg.n_bins}; "
                "integer X must contain bin_rows_host output, not raw features")
        if not isinstance(X, np.ndarray):
            _cache_bins_range(X, lo, hi)
    else:
        bins = apply_bins(Xd, jnp.asarray(edges))
    if mesh is None:
        # Pre-pad rows/features to the Pallas tile grid ONCE: the kernel
        # wrapper otherwise re-pads (a full-matrix HBM copy) on every one of
        # the depth x rounds histogram calls. Padded rows carry weight 0 (so
        # every histogram sees nothing); padded features produce all-rows-in-
        # bin-0 columns whose split candidates are all invalid (empty right
        # child), so first-occurrence argmax never selects them. Applied on
        # the XLA path too (not just use_pallas): the forest PRNG draw
        # shapes follow the padded row/feature counts, and the two paths
        # must consume identical streams to build identical forests.
        from fraud_detection_tpu.ops.histogram import FEATURE_TILE, ROW_TILE

        n_rows, n_feat = bins.shape
        pad_n = (-n_rows) % ROW_TILE
        pad_f = (-n_feat) % FEATURE_TILE
        if pad_n or pad_f:
            bins = jnp.pad(bins, ((0, pad_n), (0, pad_f)))
            yd = jnp.pad(yd, (0, pad_n))
            weights = jnp.pad(weights, (0, pad_n))
    stats = jax.nn.one_hot(yd.astype(jnp.int32), num_classes, dtype=jnp.float32)
    return edges, bins, yd, stats, weights, n


def fit_decision_tree(
    X, y, *, num_classes: int = 2, config: Optional[TreeTrainConfig] = None,
    edges: Optional[np.ndarray] = None, mesh=None,
) -> TreeEnsemble:
    """Gini decision tree (Spark DecisionTreeClassifier semantics, maxBins binning)."""
    cfg = resolve_config(config, mesh)
    edges, bins, _, stats, weights, _ = _prepare_inputs(X, y, num_classes, cfg, edges, mesh)
    dummy_keys = jax.random.split(jax.random.PRNGKey(0), cfg.max_depth + 1)
    out = _build_tree_jit(bins, stats, weights, dummy_keys, cfg, False)
    # ONE batched transfer instead of five sequential np.asarray pulls,
    # each a host<->device round-trip of its own.
    feat, sbin, left, right, node_stats = jax.device_get(out)
    return _assemble(
        [feat], [sbin], [left], [right], [node_stats],
        edges, np.ones(1), "decision_tree", cfg)


def fit_random_forest(
    X, y, *, n_trees: int = 100, num_classes: int = 2, seed: int = 42,
    config: Optional[TreeTrainConfig] = None, tree_chunk: Optional[int] = None,
    feature_subset: bool = True, edges: Optional[np.ndarray] = None, mesh=None,
    checkpoint_dir: Optional[str] = None, checkpoint_every: int = 10,
) -> TreeEnsemble:
    """Random forest: Poisson(1) bootstrap + per-node feature subsets.

    Spark parity notes (RandomForestClassifier, numTrees=100, depth 5,
    featureSubsetStrategy "auto" -> sqrt): bootstrap matches Spark's Poisson
    resampling; the feature subset is Bernoulli with expected size sqrt(F)
    rather than an exact sqrt(F)-subset (vectorization-friendly deviation,
    same expectation).

    ``checkpoint_dir`` snapshots every ``checkpoint_every`` trees (and at
    completion) and resumes by skipping completed chunks
    (checkpoint/train_state.py). Per-chunk PRNG keys are
    ``fold_in(root, start)`` — a pure function of (seed, start) — so resumed
    forests are bit-identical to uninterrupted ones.

    ``tree_chunk`` defaults per path: VMEM-bounded on the fused Pallas
    builder (bigger fusions amortize the shared multihot, but the kernel's
    accumulator scales with chunk * classes * 2^depth), 4 on the XLA loop
    (compile time grows with the unroll). The chunk size shapes the
    bootstrap PRNG draw, so it is part of the resume fingerprint — resuming
    a snapshot taken under a different default requires passing that
    ``tree_chunk`` explicitly (the train CLI exposes ``--tree-chunk``).
    """
    cfg = resolve_config(config, mesh)
    if tree_chunk is None:
        tree_chunk = resolve_tree_chunk(cfg, num_classes)
    edges, bins, _, stats, base_weights, n = _prepare_inputs(
        X, y, num_classes, cfg, edges, mesh)
    n_padded = bins.shape[0]

    root = jax.random.PRNGKey(seed)
    build = _build_tree_chunk

    fingerprint = None
    if checkpoint_dir is not None:
        from fraud_detection_tpu.checkpoint import train_state as ts

        if checkpoint_every < 1:
            raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
        # bootstrap_rows: the Poisson draw runs over the PADDED row count,
        # so the padded shape is part of the PRNG stream identity — a
        # snapshot from a run with different padding must refuse to resume.
        # bootstrap_sampler: the weight PRNG stream's identity — r5 swapped
        # jax.random.poisson for the inverse-CDF sampler, so a pre-swap
        # snapshot must refuse to resume (a mixed-stream forest would not
        # be bit-identical to an uninterrupted same-seed build).
        extra = {"seed": seed, "tree_chunk": tree_chunk,
                 "feature_subset": feature_subset, "num_classes": num_classes,
                 "bootstrap_rows": n_padded,
                 "bootstrap_sampler": "poisson1-icdf",
                 **ts.mesh_extra(mesh)}
        fingerprint = ts.data_fingerprint(
            cfg.__dict__, edges, n, y=np.asarray(y), extra=extra)

    feats, sbins, lefts, rights, all_stats = [], [], [], [], []
    trees_done = 0
    if checkpoint_dir is not None:
        snap = ts.load_for(checkpoint_dir, "random_forest", fingerprint)
        if snap is not None:
            progress, arrays = snap
            trees_done = min(progress, n_trees)
            if trees_done < n_trees:
                # Snap the resume point to the original chunk grid: chunk PRNG
                # keys are fold_in(root, start) with start a multiple of
                # tree_chunk, so an off-grid tail (a completed run's final
                # partial chunk being extended) must be dropped and rebuilt
                # for the extension to stay bit-identical to a fresh run.
                trees_done = (trees_done // tree_chunk) * tree_chunk
            feats.append(arrays["feature"][:trees_done])
            sbins.append(arrays["split_bin"][:trees_done])
            lefts.append(arrays["left"][:trees_done])
            rights.append(arrays["right"][:trees_done])
            all_stats.append(arrays["node_stats"][:trees_done])

    # Chunk outputs stay ON DEVICE until a snapshot or the end — a host
    # round-trip per chunk would dominate wall-clock when the host is far
    # from the device (the per-chunk arrays are a few KB).
    n_host = len(feats)  # chunks already on host (resume load)
    acc_lists = [feats, sbins, lefts, rights, all_stats]

    def drain_to_host() -> None:
        nonlocal n_host
        n_host = _drain_lists_to_host(acc_lists, n_host)

    last_saved = trees_done
    for start in range(trees_done, n_trees, tree_chunk):
        need = min(tree_chunk, n_trees - start)
        key = jax.random.fold_in(root, start)
        wkey, mkey = jax.random.split(key)
        # Always draw/build the FULL chunk: a ragged tail would compile a
        # second program shape (which costs far more than the few discarded
        # trees); extras are sliced away. Same rule on resume, so resumed
        # forests stay bit-identical to uninterrupted ones.
        weights = _poisson1(wkey, (tree_chunk, n_padded))
        weights = weights * base_weights[None, :]  # zero out mesh padding rows
        mask_keys = jax.random.split(mkey, tree_chunk * (cfg.max_depth + 1)).reshape(
            tree_chunk, cfg.max_depth + 1, -1)
        f_, b_, l_, r_, s_ = build(bins, stats, weights, mask_keys, cfg,
                                   feature_subset, edges.shape[0])
        if need != tree_chunk:
            f_, b_, l_, r_, s_ = (f_[:need], b_[:need], l_[:need],
                                  r_[:need], s_[:need])
        feats.append(f_); sbins.append(b_)
        lefts.append(l_); rights.append(r_)
        all_stats.append(s_)
        done = start + need
        # Snapshot on the cadence (each save rewrites the full accumulated
        # state, so per-chunk saves would cost O(n_trees^2) bytes) and at
        # completion (the seed for extending the forest later).
        if checkpoint_dir is not None and (
                done - last_saved >= checkpoint_every or done == n_trees):
            drain_to_host()
            ts.save_train_state(
                checkpoint_dir, "random_forest", done, fingerprint,
                {"feature": np.concatenate(feats), "split_bin": np.concatenate(sbins),
                 "left": np.concatenate(lefts), "right": np.concatenate(rights),
                 "node_stats": np.concatenate(all_stats)})
            last_saved = done
    drain_to_host()
    cat = lambda xs: list(np.concatenate(xs, axis=0))
    return _assemble(cat(feats), cat(sbins), cat(lefts), cat(rights), cat(all_stats),
                     edges, np.ones(n_trees), "random_forest", cfg)


def fit_gradient_boosting(
    X, y, *, n_rounds: int = 100, config: Optional[TreeTrainConfig] = None,
    edges: Optional[np.ndarray] = None, base_score: Optional[float] = None,
    mesh=None, checkpoint_dir: Optional[str] = None, checkpoint_every: int = 10,
) -> TreeEnsemble:
    """XGBoost-style second-order boosting (binary logloss).

    Matches SparkXGBClassifier's configuration surface (n_estimators=100,
    max_depth=5; eta/lambda live on TreeTrainConfig — learning_rate 0.3 and
    reg_lambda 1.0 defaults as in XGBoost); each round fits a regression tree
    on (grad, hess) histograms — the distributed histogram reduction is the
    psum the engine inserts when rows are sharded, standing in for Rabit
    allreduce.

    ``checkpoint_dir`` enables mid-training snapshots every
    ``checkpoint_every`` rounds (checkpoint/train_state.py — the reference
    has no training resume, SURVEY.md §5). Resume is bit-identical: the
    margin is replayed from the saved trees in round order, so the ensemble
    equals an uninterrupted run's. A snapshot taken under a different
    config/data refuses to load.
    """
    cfg = resolve_config(config, mesh, criterion="xgb")
    if cfg.criterion != "xgb":
        cfg = TreeTrainConfig(**{**cfg.__dict__, "criterion": "xgb"})
    if base_score is None:
        # Class-prior log-odds: keeps margins calibrated for rows that match
        # few features (short/empty texts) instead of defaulting to 0.
        prior = float(np.clip(np.mean(np.asarray(y, np.float64)), 1e-6, 1 - 1e-6))
        base_score = float(np.log(prior / (1.0 - prior)))
    edges, bins, yf, _, weights, n = _prepare_inputs(X, y, 2, cfg, edges, mesh)
    n_padded = bins.shape[0]

    margin = jnp.full((n_padded,), base_score, jnp.float32)
    feats, sbins, lefts, rights, leaf_vals = [], [], [], [], []

    fingerprint = None
    if checkpoint_dir is not None:
        from fraud_detection_tpu.checkpoint import train_state as ts

        if checkpoint_every < 1:
            raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
        # gain_scan: r5 changed the float-prefix summation order (cumsum ->
        # triangular matmul) — grad/hess gains can tie-break differently, so
        # pre-change boosting snapshots must refuse to resume (a mixed-math
        # ensemble would not be bit-identical to an uninterrupted run).
        extra = {"base_score": base_score, "gain_scan": "tri-matmul",
                 **ts.mesh_extra(mesh)}
        fingerprint = ts.data_fingerprint(
            cfg.__dict__, edges, n, y=np.asarray(y), extra=extra)

    start_round = 0
    if checkpoint_dir is not None:
        snap = ts.load_for(checkpoint_dir, "gradient_boosting", fingerprint)
        if snap is not None:
            progress, arrays = snap
            # Clamp: a snapshot from a longer run must not overfill a shorter
            # one (tree count would exceed n_rounds and its tree_weights).
            progress = min(progress, n_rounds)
            for r in range(progress):
                f_ = arrays["feature"][r]; b_ = arrays["split_bin"][r]
                l_ = arrays["left"][r]; r__ = arrays["right"][r]
                v_ = arrays["leaf_values"][r]
                feats.append(f_); sbins.append(b_)
                lefts.append(l_); rights.append(r__)
                leaf_vals.append(v_[:, None])
                # Replay the margin in round order — same float additions as
                # the original incremental updates, so resume is bit-exact.
                row_leaf = _row_leaves(bins, jnp.asarray(f_), jnp.asarray(b_),
                                       jnp.asarray(l_), jnp.asarray(r__),
                                       cfg.max_depth)
                margin = _update_margin(margin, row_leaf, jnp.asarray(v_))
            start_round = progress

    def snapshot(rounds_done: int) -> None:
        ts.save_train_state(
            checkpoint_dir, "gradient_boosting", rounds_done, fingerprint,
            {"feature": np.stack(feats), "split_bin": np.stack(sbins),
             "left": np.stack(lefts), "right": np.stack(rights),
             "leaf_values": np.stack([v[:, 0] for v in leaf_vals])})

    # One fused program per round, and per-tree arrays stay ON DEVICE until a
    # snapshot or the end: a host round-trip per round would dominate
    # wall-clock (the tiny (63,) tree arrays cost more in sync latency than
    # the whole histogram pass costs in compute).
    n_host = len(feats)  # rounds already materialized on host (resume replay)
    acc_lists = [feats, sbins, lefts, rights, leaf_vals]

    def drain_to_host() -> None:
        nonlocal n_host
        n_host = _drain_lists_to_host(acc_lists, n_host)

    for r in range(start_round, n_rounds):
        f_, b_, l_, r_, values, values2, row_leaf = _boost_round(
            margin, bins, yf, weights, cfg)
        # The update runs as the SAME separate program the resume replay
        # uses: fusing it into _boost_round lets XLA contract the gather-add
        # differently (fma) and break bit-identical resume.
        margin = _update_margin(margin, row_leaf, values)
        feats.append(f_); sbins.append(b_)
        lefts.append(l_); rights.append(r_)
        leaf_vals.append(values2)
        # Snapshot on the cadence AND at completion (a finished run's snapshot
        # is the seed for extending training to more rounds later).
        if checkpoint_dir is not None and (
                (r + 1) % checkpoint_every == 0 or r + 1 == n_rounds):
            drain_to_host()
            snapshot(r + 1)

    drain_to_host()
    return _assemble(feats, sbins, lefts, rights, leaf_vals,
                     edges, np.ones(n_rounds), "xgboost", cfg, bias=base_score)


@jax.jit
def _update_margin(margin, row_node, values):
    return margin + values[row_node]


#: Row-count padding ladder for the windowed refresh trainer: every retrain
#: pads its window to the smallest rung that fits, so repeated retrains of
#: drifting window sizes reuse the SAME compiled program shapes (XLA
#: compiles stay off the learn lane's steady state, the same bucket
#: discipline the serving ladder applies to micro-batches).
REFRESH_ROW_BUCKETS: Tuple[int, ...] = (512, 1024, 2048, 4096, 8192,
                                        16384, 32768)


def refresh_row_bucket(n: int,
                       buckets: Tuple[int, ...] = REFRESH_ROW_BUCKETS) -> int:
    """Smallest configured rung >= n (the top rung caps: larger windows
    must be subsampled by the caller, never silently grown into a fresh
    compile shape per retrain)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def refresh_gradient_boosting(
    ensemble: TreeEnsemble, X, y, *, n_rounds: int = 8,
    config: Optional[TreeTrainConfig] = None,
    row_buckets: Tuple[int, ...] = REFRESH_ROW_BUCKETS,
    sample_weight: Optional[np.ndarray] = None,
) -> Tuple[TreeEnsemble, dict]:
    """Warm-started incremental boosting: keep every tree of ``ensemble``
    and fit ``n_rounds`` NEW regression trees on the recent window's
    (grad, hess) statistics, starting from the live model's margins.

    This is the learn loop's retrain primitive (learn/loop.py,
    docs/online_learning.md): the window is small (thousands of rows), the
    existing trees already explain the stationary part of the traffic, and
    the new rounds only have to explain what DRIFTED — the gradients of
    rows the live model already scores correctly are near zero, so the new
    trees spend their splits on the drifted region. Each round rides the
    same fused ``_boost_round`` program (device histogram kernels on TPU,
    segment-sum elsewhere) as offline training.

    Shapes are BUCKETED: the window pads (weight-0 rows) to the smallest
    ``row_buckets`` rung that fits, so a steady retrain cadence reuses one
    compiled program instead of compiling per window size; windows larger
    than the top rung keep their most recent rows. Returns
    ``(new_ensemble, info)`` — info carries the padded rung, per-round
    shapes, and the window metadata the registry manifest records.
    """
    if ensemble.kind != "xgboost":
        raise ValueError(
            f"refresh_gradient_boosting warm-starts xgboost ensembles; got "
            f"kind {ensemble.kind!r} (gini forests have no additive margin "
            "to resume from — retrain those offline)")
    cfg = resolve_config(config, None, criterion="xgb")
    if cfg.criterion != "xgb":
        cfg = TreeTrainConfig(**{**cfg.__dict__, "criterion": "xgb"})
    if cfg.max_depth != ensemble.max_depth:
        # Node-array layouts must agree for the concat below; a different
        # depth would also silently change the candidate's latency class.
        cfg = TreeTrainConfig(**{**cfg.__dict__,
                                 "max_depth": ensemble.max_depth})
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float32)
    if X.ndim != 2 or X.shape[0] != y.shape[0]:
        raise ValueError(f"X {X.shape} / y {y.shape} mismatch")
    if X.shape[0] < 2:
        raise ValueError("refresh needs at least 2 labeled rows")
    bucket = refresh_row_bucket(X.shape[0], tuple(row_buckets))
    if X.shape[0] > bucket:
        # Over the top rung: keep the most RECENT rows (callers pass the
        # window oldest-first) — the window semantics, made explicit.
        X, y = X[-bucket:], y[-bucket:]
        if sample_weight is not None:
            sample_weight = sample_weight[-bucket:]
    n = X.shape[0]
    weights = (np.ones(n, np.float32) if sample_weight is None
               else np.asarray(sample_weight, np.float32))

    # Window-local quantile edges from the REAL rows (pads excluded): the
    # new trees' thresholds come from the drifted window's distribution.
    edges = quantile_bin_edges(X, cfg.n_bins)

    pad = bucket - n
    if pad:
        X = np.concatenate([X, np.zeros((pad, X.shape[1]), np.float32)])
        y = np.concatenate([y, np.zeros(pad, np.float32)])
        weights = np.concatenate([weights, np.zeros(pad, np.float32)])

    # Warm start: the live ensemble's margins on the window (padded rows
    # get the margin of an all-zero row — inert under weight 0).
    from fraud_detection_tpu.models import trees as trees_mod

    margin = trees_mod.predict_margin(ensemble, jnp.asarray(X))

    bins = apply_bins(jnp.asarray(X), jnp.asarray(edges))
    # Tile-align once, like _prepare_inputs (the Pallas wrapper would
    # otherwise re-pad the matrix on every level of every round).
    from fraud_detection_tpu.ops.histogram import FEATURE_TILE, ROW_TILE

    pad_n = (-bins.shape[0]) % ROW_TILE
    pad_f = (-bins.shape[1]) % FEATURE_TILE
    if pad_n or pad_f:
        bins = jnp.pad(bins, ((0, pad_n), (0, pad_f)))
        y = np.concatenate([y, np.zeros(pad_n, np.float32)])
        weights = np.concatenate([weights, np.zeros(pad_n, np.float32)])
        margin = jnp.pad(margin, (0, pad_n))
    yd = jnp.asarray(y)
    wd = jnp.asarray(weights)

    feats, sbins, lefts, rights, leaf_vals = [], [], [], [], []
    for _ in range(n_rounds):
        f_, b_, l_, r_, values, values2, row_leaf = _boost_round(
            margin, bins, yd, wd, cfg)
        margin = _update_margin(margin, row_leaf, values)
        feats.append(f_); sbins.append(b_)
        lefts.append(l_); rights.append(r_)
        leaf_vals.append(values2)
    jax.device_get(margin)  # one sync: rounds above stayed on device
    new = _assemble(feats, sbins, lefts, rights, leaf_vals, edges,
                    np.ones(n_rounds), "xgboost", cfg, bias=ensemble.bias)

    refreshed = TreeEnsemble(
        feature=jnp.concatenate([ensemble.feature, new.feature]),
        threshold=jnp.concatenate([ensemble.threshold, new.threshold]),
        left=jnp.concatenate([ensemble.left, new.left]),
        right=jnp.concatenate([ensemble.right, new.right]),
        leaf=jnp.concatenate([ensemble.leaf, new.leaf]),
        tree_weights=jnp.concatenate([ensemble.tree_weights,
                                      new.tree_weights]),
        kind="xgboost", max_depth=ensemble.max_depth, bias=ensemble.bias)
    info = {
        "window_rows": n,
        "padded_rows": bucket,
        "rounds": n_rounds,
        "base_trees": int(ensemble.num_trees),
        "total_trees": int(refreshed.num_trees),
        "n_bins": cfg.n_bins,
        "max_depth": cfg.max_depth,
    }
    return refreshed, info


@partial(jax.jit, static_argnames=("cfg",))
def _boost_round(margin, bins, yf, weights, cfg: TreeTrainConfig):
    """One boosting round as a single program: gradients, tree build, leaf
    values, row routing. Fusing these keeps dispatches per round to two
    (this + ``_update_margin``) — per-launch overhead is material when the
    host is far from the device."""
    p = jax.nn.sigmoid(margin)
    g, h = p - yf, p * (1.0 - p)
    stats = jnp.stack([g, h, jnp.ones_like(g)], axis=1)
    # The builder's final routing state IS each row's leaf position —
    # re-traversing with _row_leaves costs a per-row gather walk per round
    # (TPU serializes row-wise gathers; ~the same pathology removed from
    # _route_rows in r5). The resume REPLAY still uses _row_leaves (only
    # the trees are on disk); weight-0 padding rows are the one divergence
    # (builder leaves them at the root) and their margins are inert.
    f_, b_, l_, r_, s_, row_leaf = _build_tree(bins, stats, weights, None, cfg)
    values = -s_[:, 0] / (s_[:, 1] + cfg.reg_lambda) * cfg.learning_rate
    # values twice: flat for the margin update, (M, 1) for the snapshot
    # accumulator — shaping in-program avoids a per-round dispatch.
    return f_, b_, l_, r_, values, values[:, None], row_leaf


@partial(jax.jit, static_argnames=("max_depth",))
def _row_leaves(bins, feature, split_bin, left, right, max_depth: int):
    """Leaf heap-position per row, in bin space (train-time traversal)."""

    def body(_, node):
        f = feature[node]
        is_leaf = left[node] < 0
        row_bin = jnp.take_along_axis(bins, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
        nxt = jnp.where(row_bin <= split_bin[node], left[node], right[node])
        return jnp.where(is_leaf, node, nxt)

    n = bins.shape[0]
    return jax.lax.fori_loop(0, max_depth, body, jnp.zeros((n,), jnp.int32))


def _assemble(feats, sbins, lefts, rights, payloads, edges, tree_weights,
              kind: str, cfg: TreeTrainConfig, bias: float = 0.0) -> TreeEnsemble:
    """Stack per-tree flat arrays into a TreeEnsemble with real thresholds."""
    feature = np.stack(feats).astype(np.int32)
    split_bin = np.stack(sbins).astype(np.int32)
    thresholds = np.stack([
        _edges_to_thresholds(edges, f, b) for f, b in zip(feature, split_bin)])
    return TreeEnsemble(
        feature=jnp.asarray(feature),
        threshold=jnp.asarray(thresholds),
        left=jnp.asarray(np.stack(lefts).astype(np.int32)),
        right=jnp.asarray(np.stack(rights).astype(np.int32)),
        leaf=jnp.asarray(np.stack(payloads).astype(np.float32)),
        tree_weights=jnp.asarray(np.asarray(tree_weights, np.float32)),
        kind=kind,
        max_depth=cfg.max_depth,
        bias=bias,
    )
