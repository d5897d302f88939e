"""Pallas TPU kernels for histogram tree building.

The tree trainer's hot op is the per-level (node, feature, bin) statistics
histogram over the sharded row set (models/train_trees.py:158-162 — the
XLA path vmaps a segment-sum over all 10k features). On TPU the idiomatic
formulation is a matmul, not a scatter: for a row tile,

    hist[f*NB+b, l*K+k] = sum_r  onehot(bins[r,f]==b) * onehot(node[r]==l) * stats[r,k]
                        =        multihot_bins^T  @  (node_onehot (x) stats)

— one (F_t*NB, R) @ (R, L*K) contraction per (feature-tile, row-tile) grid
cell, accumulated over row tiles in VMEM. The scatter becomes MXU work at
full systolic utilization; this is the same reformulation the reference's
XGBoost applies on GPU with atomics, done the TPU way (BASELINE.json:
"histogram build ... becomes Pallas kernels").

The split-gain scan (cumsum over bins + impurity gain + argmax — the
per-level decision) ships here too as a fused VPU kernel.

Both kernels take ``interpret=`` so the CPU test mesh exercises them; the
trainers pass ``utils.device.pallas_interpret()``.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Default tile grid — OWNED here; the trainers' pre-padding imports these so
# the aligned no-copy fast path can never silently drift from the kernel.
ROW_TILE = 256
FEATURE_TILE = 128


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# ---------------------------------------------------------------------------
# histogram kernel
# ---------------------------------------------------------------------------

def node_feature_bin_histogram(
    bins: jax.Array,      # (N, F) int32 bin ids
    local: jax.Array,     # (N,) int32 node position within the level; >= n_nodes = skip
    stats: jax.Array,     # (N, K) f32 per-row statistics (weights folded in)
    *,
    n_nodes: int,
    n_bins: int,
    row_tile: int = ROW_TILE,
    feature_tile: int = FEATURE_TILE,
    interpret: bool = False,
    exact_int8: bool = False,
) -> jax.Array:
    """(n_nodes, F, n_bins, K) statistics histogram via the Pallas kernel —
    the T=1 case of ``node_feature_bin_histogram_multi`` (unit weights are
    exact, so delegating costs one multiply by 1.0 and keeps a single
    kernel to maintain)."""
    hist = node_feature_bin_histogram_multi(
        bins, local[None, :], jnp.ones((1, local.shape[0]), jnp.float32),
        stats, n_nodes=n_nodes, n_bins=n_bins, row_tile=row_tile,
        feature_tile=feature_tile, interpret=interpret,
        exact_int8=exact_int8)
    return hist[0]


def _hist_kernel_multi(bins_ref, b_of_c_ref, locals_ref, weights_ref,
                       stats_ref, out_ref, *, n_bins: int, n_nodes: int,
                       k: int, n_trees: int, exact_int8: bool):
    """One (feature-tile, row-tile) cell for T trees sharing ``bins``:
    out += [node (x) stats (x) weights]^T @ multihot.

    Mosaic constraints + MXU economics shape this kernel:

    * No minor-dim reshape exists, so the flat bucket axis uses the
      (bin, feature-in-tile) order that ``pltpu.repeat`` (tile-concat
      semantics) produces directly — column c <-> (b = c // Ft, f = c % Ft)
      — and the khatri-rao node (x) stats matrix is built by sublane-axis
      concatenation instead of a 3D reshape. The host wrapper untangles.
    * ``b_of_c`` (the bin id of each flat column — identical for every tile)
      arrives as a (1, C) input instead of a per-cell iota+divide.
    * The dot runs TRANSPOSED — (T*K*L, R) @ (R, C) — so the 4096-wide
      bucket axis lands on lanes: the MXUs parallelize over lanes, and
      T*K*L on lanes would leave most idle. Fusing T trees builds the
      expensive multihot (the kernel's dominant cost) ONCE per cell instead
      of per tree, and fills MXU lanes a single tree leaves idle at shallow
      levels. Output rows: t*(K*L) + kk*L + l.
    * ``exact_int8`` (class-count statistics — gini DT/RF): stats, weights,
      multihot and the khatri-rao matrix are all small non-negative ints, so
      the whole contraction runs as ONE int8 MXU pass accumulating int32 —
      bit-exact (stronger than any float formulation) at the MXU's double
      int8 rate. The f32 path splits stats hi/lo into two bf16 passes (~16
      mantissa bits, accumulated in f32): single-pass bf16 rounds to 8 bits
      — enough error (~1e-2 relative) to flip split argmaxes vs the XLA
      path — while HIGHEST costs 6 passes for precision the argmax doesn't
      need. The 0/1 multihot is exact in bf16.
    """
    r_idx = pl.program_id(1)

    @pl.when(r_idx == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins = bins_ref[:]                         # (R, Ft) int32
    R, Ft = bins.shape
    bins_rep = pltpu.repeat(bins, n_bins, axis=1)                  # (R, C)
    eq = bins_rep == b_of_c_ref[:]
    node_iota = jax.lax.broadcasted_iota(jnp.int32, (n_nodes, R), 0)
    dims = (((1,), (0,)), ((), ()))

    # Khatri-rao build runs in f32 on both paths (Mosaic has no int8
    # elementwise multiply; f32 is exact for the int path's magnitudes).
    parts = []
    for t in range(n_trees):
        local_t = locals_ref[t : t + 1, :]                         # (1, R)
        w_t = weights_ref[t : t + 1, :]                            # (1, R)
        onehot_t = (node_iota == local_t).astype(jnp.float32)      # (L, R)
        for kk in range(k):
            parts.append(onehot_t * (stats_ref[kk : kk + 1, :] * w_t))
    ns = jnp.concatenate(parts, axis=0)                            # (T*K*L, R)

    if exact_int8:
        # stats*w <= 127 (one-hot class counts x Poisson weights) — the
        # trainer guarantees the range, so the int8 cast is exact and the
        # contraction is ONE int8 MXU pass accumulating exact int32. The
        # clip saturates (instead of silently wrapping to negative counts)
        # if a future caller breaks the contract; the jitted wrapper
        # additionally reports the violation (jax.debug.print).
        out_ref[:] += jax.lax.dot_general(
            jnp.clip(ns, 0.0, 127.0).astype(jnp.int8), eq.astype(jnp.int8),
            dims, preferred_element_type=jnp.int32)
        return

    multihot = eq.astype(jnp.bfloat16)
    ns_hi = ns.astype(jnp.bfloat16)
    ns_lo = (ns - ns_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    acc = jax.lax.dot_general(ns_hi, multihot, dims,
                              preferred_element_type=jnp.float32)
    acc = acc + jax.lax.dot_general(ns_lo, multihot, dims,
                                    preferred_element_type=jnp.float32)
    out_ref[:] += acc


@partial(jax.jit, static_argnames=("n_nodes", "n_bins", "row_tile",
                                   "feature_tile", "interpret", "exact_int8"))
def node_feature_bin_histogram_multi(
    bins: jax.Array,      # (N, F) int32 bin ids, SHARED by all trees
    locals_: jax.Array,   # (T, N) int32 per-tree node position; >= n_nodes = skip
    weights: jax.Array,   # (T, N) f32 per-tree bootstrap weights
    stats: jax.Array,     # (N, K) f32 per-row statistics (weights NOT folded)
    *,
    n_nodes: int,
    n_bins: int,
    row_tile: int = ROW_TILE,
    feature_tile: int = FEATURE_TILE,
    interpret: bool = False,
    exact_int8: bool = False,
) -> jax.Array:
    """(T, n_nodes, F, n_bins, K) histograms for a chunk of trees sharing
    one binned matrix — the forest trainer's per-level hot op.

    ``exact_int8``: caller promises stats and weights are non-negative
    integers with per-row products < 128 (class one-hots x Poisson bootstrap
    weights — the gini trainers). The kernel then runs ONE int8 MXU pass
    with exact int32 accumulation instead of two bf16 passes: ~2x faster and
    bit-exact. Output is f32 either way (exact for the int path: every count
    is far below 2^24)."""
    n, f = bins.shape
    t, k = locals_.shape[0], stats.shape[-1]
    n_pad = _round_up(max(n, 1), row_tile)
    f_pad = _round_up(max(f, 1), feature_tile)
    bins = bins.astype(jnp.int32)  # dtype contract independent of alignment
    if n_pad == n and f_pad == f:
        # Aligned input: skip the pad — the zeros+set below copies the FULL
        # (N, F) matrix (GBs of pure HBM copy per level at bench scale), so
        # the trainers pre-pad once and hit this branch every level.
        bins_p = bins
    else:
        bins_p = jnp.zeros((n_pad, f_pad), jnp.int32)
        bins_p = bins_p.at[:n, :f].set(bins)
    locals_p = jnp.full((t, n_pad), n_nodes, jnp.int32).at[:, :n].set(locals_)
    weights_p = jnp.zeros((t, n_pad), jnp.float32).at[:, :n].set(
        weights.astype(jnp.float32))
    stats_p = jnp.zeros((k, n_pad), jnp.float32).at[:, :n].set(
        stats.T.astype(jnp.float32))
    b_of_c = (jnp.arange(feature_tile * n_bins, dtype=jnp.int32)
              // feature_tile)[None, :]

    if exact_int8:
        # Loud contract check: the int8 MXU path is exact only for
        # stats*weight products in [0, 127]. The exact per-row bound
        # max_r(max_k stats[k,r] * max_t w[t,r]) is as cheap as the global
        # maxima and never false-positives across rows; negatives violate
        # the non-negativity half of the contract (the kernel clip would
        # silently zero them). Violations print a diagnostic (the kernel
        # saturates to [0, 127] rather than wrapping).
        bound = jnp.max(jnp.max(stats_p, axis=0) * jnp.max(weights_p, axis=0))
        negative = jnp.minimum(jnp.min(stats_p), jnp.min(weights_p))
        # Negated-complement predicates so NaN operands (which compare False
        # both ways) trip the diagnostic instead of slipping past it.
        jax.lax.cond(
            ~(bound <= 127.0) | ~(negative >= 0.0),
            lambda b, neg: jax.debug.print(
                "ops.histogram exact_int8 contract violated: per-row "
                "stats*weight bound {b}, min operand {neg} — products are "
                "clipped to [0, 127] (use the bf16 path for unbounded or "
                "signed stats)", b=b, neg=neg),
            lambda b, neg: None, bound, negative)

    grid = (f_pad // feature_tile, n_pad // row_tile)
    out = pl.pallas_call(
        partial(_hist_kernel_multi, n_bins=n_bins, n_nodes=n_nodes, k=k,
                n_trees=t, exact_int8=exact_int8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, feature_tile), lambda fi, ri: (ri, fi),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, feature_tile * n_bins), lambda fi, ri: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((t, row_tile), lambda fi, ri: (0, ri),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((t, row_tile), lambda fi, ri: (0, ri),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, row_tile), lambda fi, ri: (0, ri),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((t * k * n_nodes, feature_tile * n_bins),
                               lambda fi, ri: (0, fi),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (t * k * n_nodes, f_pad * n_bins),
            jnp.int32 if exact_int8 else jnp.float32),
        interpret=interpret,
    )(bins_p, b_of_c, locals_p, weights_p, stats_p)

    # Untangle: row = t*(K*L) + kk*L + l, col = tile*(NB*Ft) + b*Ft + f_in
    # -> (T, L, F, NB, K).
    n_tiles = f_pad // feature_tile
    hist = out.reshape(t, k, n_nodes, n_tiles, n_bins, feature_tile)
    hist = hist.transpose(0, 2, 3, 5, 4, 1).reshape(
        t, n_nodes, f_pad, n_bins, k)
    return hist[:, :, :f].astype(jnp.float32)


def histogram_reference(bins, local, stats, *, n_nodes: int, n_bins: int) -> jax.Array:
    """XLA segment-sum formulation (models/train_trees.py:158-162 shape)."""
    valid = local < n_nodes
    seg_local = jnp.where(valid, local, n_nodes)

    def one_feature(fbins):
        seg = jnp.where(valid, seg_local * n_bins + fbins, n_nodes * n_bins)
        return jax.ops.segment_sum(stats, seg, num_segments=n_nodes * n_bins + 1)[:-1]

    hist = jax.vmap(one_feature, in_axes=1)(bins)       # (F, L*NB, K)
    f = bins.shape[1]
    return hist.reshape(f, n_nodes, n_bins, -1).transpose(1, 0, 2, 3)


# ---------------------------------------------------------------------------
# split-gain scan kernel
# ---------------------------------------------------------------------------

def _gain_kernel(hist_ref, total_ref, best_idx_ref, best_gain_ref, *,
                 n_bins: int, n_stats: int, criterion: str, reg_lambda: float,
                 min_child_weight: float):
    """One (node, feature-tile) cell: cumulative-left stats, impurity gain,
    argmax over the tile's (Ft, NB-1) candidates.

    All intermediates are 2D (Ft, NB) per statistic — Mosaic has no
    minor-dim reshape, so the K statistics arrive pre-sliced on a leading
    axis and the bin-cumsum is an upper-triangular matmul (MXU work; exact
    for the 0/1 and small-count magnitudes involved). Totals ride in SMEM as
    scalars. The per-tile argmax is recovered as min(position where gain ==
    max), matching XLA's first-occurrence argmax tie rule in row-major
    order; the host wrapper reduces across tiles (features are tiled so huge
    F doesn't overflow VMEM — the whole (F, NB, K) slab at F=10000 needs
    >30MB of intermediates).
    """
    f_idx = pl.program_id(1)
    nb = n_bins
    # inclusive prefix over bins: left = hist @ upper_tri  (NB, NB)
    tri_r = jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
    tri_c = jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 1)
    tri = (tri_r <= tri_c).astype(jnp.float32)

    left = []
    total = []
    for kk in range(n_stats):
        h = hist_ref[0, kk].astype(jnp.float32)          # (F, NB)
        left.append(jax.lax.dot_general(
            h, tri, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32))
        total.append(total_ref[0, 0, kk])                # SMEM scalar
    right = [t - l for l, t in zip(left, total)]

    if criterion == "gini":
        def gini_sum(stats_2d):
            cnt = stats_2d[0]
            sq = stats_2d[0] * stats_2d[0]
            for s in stats_2d[1:]:
                cnt = cnt + s
                sq = sq + s * s
            return cnt - sq / jnp.maximum(cnt, 1e-12), cnt
        g_l, n_l = gini_sum(left)
        g_r, n_r = gini_sum(right)
        cnt_p = total[0]
        sq_p = total[0] * total[0]
        for t in total[1:]:
            cnt_p = cnt_p + t
            sq_p = sq_p + t * t
        g_p = cnt_p - sq_p / jnp.maximum(cnt_p, 1e-12)
        gain = (g_p - g_l - g_r) / jnp.maximum(cnt_p, 1e-12)
        valid = (n_l > 0) & (n_r > 0)
    else:  # xgb second-order gain; stats layout (grad, hess, count)
        gl, hl, cl = left[0], left[1], left[2]
        gr, hr, cr = right[0], right[1], right[2]
        gp, hp = total[0], total[1]
        score = lambda g, h: (g * g) / (h + reg_lambda)
        gain = 0.5 * (score(gl, hl) + score(gr, hr) - score(gp, hp))
        valid = (hl >= min_child_weight) & (hr >= min_child_weight) & \
                (cl > 0) & (cr > 0)

    f = gain.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (f, nb), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (f, nb), 0)
    in_range = col < nb - 1                              # last bin: no right side
    gain = jnp.where(valid & in_range, gain, -jnp.inf)
    best = jnp.max(gain)
    pos = row * (nb - 1) + col                           # tile-local position
    pos = jnp.where((gain == best) & in_range, pos, jnp.int32(2**30))
    best_idx_ref[0, 0, f_idx] = jnp.min(pos)
    best_gain_ref[0, 0, f_idx] = best


@partial(jax.jit, static_argnames=("criterion", "n_bins", "reg_lambda",
                                   "min_child_weight", "feature_tile",
                                   "interpret"))
def best_splits(
    hist: jax.Array,       # (L, F, NB, K)
    totals: jax.Array,     # (L, K)
    *,
    criterion: str = "gini",
    n_bins: int = 32,
    reg_lambda: float = 1.0,
    min_child_weight: float = 1e-6,
    feature_tile: int = 1024,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per node: (best_feature, best_bin, best_gain) fused on the VPU.

    Features are processed in tiles of ``feature_tile``; each grid cell emits
    its tile's (first-occurrence) best, and a cheap XLA reduction combines
    tiles — argmax over tile bests picks the lowest tile on ties, which
    together with the in-tile min-position rule reproduces XLA's flat
    row-major first-occurrence argmax exactly.
    """
    L, F, NB, K = hist.shape
    ft = min(feature_tile, F)
    f_pad = _round_up(F, ft)
    hist_k = hist.transpose(0, 3, 1, 2)                  # (L, K, F, NB)
    if f_pad != F:
        # Padded features carry all-zero stats: empty children/hessians make
        # every candidate invalid (-inf), so padding never wins.
        hist_k = jnp.pad(hist_k, ((0, 0), (0, 0), (0, f_pad - F), (0, 0)))
    n_tiles = f_pad // ft
    totals3 = totals.reshape(L, 1, K)
    idx_t, gain_t = pl.pallas_call(
        partial(_gain_kernel, n_bins=NB, n_stats=K, criterion=criterion,
                reg_lambda=reg_lambda, min_child_weight=min_child_weight),
        grid=(L, n_tiles),
        in_specs=[
            pl.BlockSpec((1, K, ft, NB), lambda l, fi: (l, 0, fi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, K), lambda l, fi: (l, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, n_tiles), lambda l, fi: (l, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, n_tiles), lambda l, fi: (l, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, 1, n_tiles), jnp.int32),
            jax.ShapeDtypeStruct((L, 1, n_tiles), jnp.float32),
        ],
        interpret=interpret,
    )(hist_k, totals3)
    idx_t = idx_t[:, 0, :]                               # (L, T) tile-local pos
    gain_t = gain_t[:, 0, :]                             # (L, T)
    t_star = jnp.argmax(gain_t, axis=1)                  # ties -> lowest tile
    best_gain = jnp.take_along_axis(gain_t, t_star[:, None], 1)[:, 0]
    idx = jnp.take_along_axis(idx_t, t_star[:, None], 1)[:, 0]
    best_f = t_star.astype(jnp.int32) * ft + (idx // (NB - 1)).astype(jnp.int32)
    return best_f, (idx % (NB - 1)).astype(jnp.int32), best_gain
