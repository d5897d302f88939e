"""Pallas kernel tests (interpret mode on the CPU mesh): histogram and
gain-scan kernels must agree with the XLA formulations to the kernel's
designed precision (the histogram accumulates f32 stats split into hi/lo
bf16 MXU passes — ~16 mantissa bits per term), and trees built through the
Pallas path must match trees built through the XLA path."""

import jax.numpy as jnp
import numpy as np
import pytest

from fraud_detection_tpu.ops import (
    best_splits,
    histogram_reference,
    node_feature_bin_histogram,
)


@pytest.fixture(scope="module")
def hist_case():
    rng = np.random.default_rng(0)
    n, f, nb, L, k = 300, 40, 8, 4, 3
    bins = jnp.asarray(rng.integers(0, nb, (n, f)), jnp.int32)
    local = jnp.asarray(rng.integers(0, L + 1, (n,)), jnp.int32)  # L = inactive
    stats = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    return bins, local, stats, L, nb


def test_histogram_kernel_matches_reference(hist_case):
    bins, local, stats, L, nb = hist_case
    got = node_feature_bin_histogram(bins, local, stats, n_nodes=L, n_bins=nb,
                                     row_tile=64, feature_tile=16, interpret=True)
    want = histogram_reference(bins, local, stats, n_nodes=L, n_bins=nb)
    assert got.shape == want.shape
    # hi/lo bf16 split: ~2^-16 relative per term; cancelling sums can show a
    # larger RELATIVE error on near-zero cells, so tolerance is scale-based.
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3 * scale)


def test_histogram_kernel_ragged_sizes():
    """N and F not multiples of the tiles: padding must not leak into bins."""
    rng = np.random.default_rng(1)
    n, f, nb, L = 127, 13, 4, 2
    bins = jnp.asarray(rng.integers(0, nb, (n, f)), jnp.int32)
    local = jnp.asarray(rng.integers(0, L, (n,)), jnp.int32)
    stats = jnp.asarray(np.ones((n, 1), np.float32))
    got = node_feature_bin_histogram(bins, local, stats, n_nodes=L, n_bins=nb,
                                     row_tile=32, feature_tile=8, interpret=True)
    want = histogram_reference(bins, local, stats, n_nodes=L, n_bins=nb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    # every row lands exactly once per feature
    assert np.allclose(np.asarray(got).sum(axis=(0, 2, 3)), n)


@pytest.mark.parametrize("criterion", ["gini", "xgb"])
def test_gain_scan_matches_xla(criterion):
    from fraud_detection_tpu.models.train_trees import _gini_gain, _xgb_gain

    rng = np.random.default_rng(2)
    L, F, NB, K = 4, 24, 8, 3
    if criterion == "gini":
        hist = jnp.asarray(rng.integers(0, 10, (L, F, NB, K)).astype(np.float32))
    else:
        g = rng.normal(size=(L, F, NB, 1)).astype(np.float32)
        h = rng.uniform(0.1, 1.0, (L, F, NB, 1)).astype(np.float32)
        c = rng.integers(1, 5, (L, F, NB, 1)).astype(np.float32)
        hist = jnp.asarray(np.concatenate([g, h, c], axis=-1))
    # Per-node totals the way the builder computes them: one feature's bins.
    totals = hist[:, 0].sum(axis=1)

    cum = jnp.cumsum(hist, axis=2)
    total_b = totals[:, None, None, :]
    if criterion == "gini":
        gain = _gini_gain(cum, total_b)
    else:
        gain = _xgb_gain(cum, total_b, 1.0, 1e-6)
    gain = gain[:, :, : NB - 1]
    flat = np.asarray(gain.reshape(L, -1))
    want_best = flat.argmax(axis=1)
    want_gain = flat[np.arange(L), want_best]

    bf, bb, bg = best_splits(hist, totals, criterion=criterion, n_bins=NB,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(bf), want_best // (NB - 1))
    np.testing.assert_array_equal(np.asarray(bb), want_best % (NB - 1))
    np.testing.assert_allclose(np.asarray(bg), want_gain, rtol=1e-5, atol=1e-6)


def test_gain_scan_tiled_features_matches_flat():
    """feature_tile < F (with ragged padding) must reproduce the flat
    first-occurrence argmax exactly — the two-stage tile reduction is the
    VMEM guard for 10k-feature pipelines."""
    from fraud_detection_tpu.models.train_trees import _xgb_gain

    rng = np.random.default_rng(5)
    L, F, NB = 3, 50, 8
    hist = jnp.asarray(np.concatenate(
        [rng.normal(size=(L, F, NB, 1)),
         rng.uniform(0.1, 1, (L, F, NB, 1)),
         rng.integers(1, 5, (L, F, NB, 1))], axis=-1).astype(np.float32))
    totals = hist[:, 0].sum(axis=1)
    bf, bb, bg = best_splits(hist, totals, criterion="xgb", n_bins=NB,
                             feature_tile=16, interpret=True)  # 4 tiles, ragged
    cum = jnp.cumsum(hist, axis=2)
    gain = _xgb_gain(cum, totals[:, None, None, :], 1.0, 1e-6)[:, :, : NB - 1]
    flat = np.asarray(gain.reshape(L, -1))
    want = flat.argmax(axis=1)
    np.testing.assert_array_equal(np.asarray(bf), want // (NB - 1))
    np.testing.assert_array_equal(np.asarray(bb), want % (NB - 1))
    np.testing.assert_allclose(np.asarray(bg), flat[np.arange(L), want],
                               rtol=1e-4, atol=1e-5)


def test_tree_built_with_pallas_matches_xla_path():
    from fraud_detection_tpu.models import trees as trees_mod
    from fraud_detection_tpu.models.train_trees import TreeTrainConfig, fit_decision_tree

    rng = np.random.default_rng(3)
    X = rng.normal(size=(400, 24)).astype(np.float32)
    y = ((X[:, 3] > 0.2) ^ (X[:, 10] < -0.1)).astype(np.float32)

    base = fit_decision_tree(X, y, config=TreeTrainConfig(max_depth=4))
    pall = fit_decision_tree(X, y, config=TreeTrainConfig(max_depth=4, use_pallas=True))

    np.testing.assert_array_equal(np.asarray(base.feature), np.asarray(pall.feature))
    np.testing.assert_array_equal(np.asarray(base.left), np.asarray(pall.left))
    np.testing.assert_allclose(np.asarray(base.threshold), np.asarray(pall.threshold),
                               rtol=1e-6, atol=1e-6)
    p_base = trees_mod.predict(base, jnp.asarray(X))[1]
    p_pall = trees_mod.predict(pall, jnp.asarray(X))[1]
    np.testing.assert_allclose(np.asarray(p_base), np.asarray(p_pall), rtol=1e-6)


def test_boosting_with_pallas_matches_xla_path():
    from fraud_detection_tpu.models import trees as trees_mod
    from fraud_detection_tpu.models.train_trees import (
        TreeTrainConfig, fit_gradient_boosting)

    rng = np.random.default_rng(4)
    X = rng.normal(size=(300, 16)).astype(np.float32)
    y = (X[:, 1] + 0.5 * X[:, 7] > 0).astype(np.float32)

    kw = dict(n_rounds=5)
    base = fit_gradient_boosting(
        X, y, config=TreeTrainConfig(max_depth=3, criterion="xgb"), **kw)
    pall = fit_gradient_boosting(
        X, y, config=TreeTrainConfig(max_depth=3, criterion="xgb", use_pallas=True), **kw)
    p_base = trees_mod.predict(base, jnp.asarray(X))[1]
    p_pall = trees_mod.predict(pall, jnp.asarray(X))[1]
    np.testing.assert_allclose(np.asarray(p_base), np.asarray(p_pall),
                               rtol=1e-4, atol=1e-5)


def test_multi_tree_histogram_matches_single():
    """The fused multi-tree kernel must equal per-tree single calls (same
    math, multihot built once) — weights folded in-kernel."""
    from fraud_detection_tpu.ops import node_feature_bin_histogram_multi

    rng = np.random.default_rng(8)
    n, f, nb, L, k, T = 300, 40, 8, 4, 2, 3
    bins = jnp.asarray(rng.integers(0, nb, (n, f)), jnp.int32)
    locals_ = jnp.asarray(rng.integers(0, L + 1, (T, n)), jnp.int32)
    weights = jnp.asarray(rng.poisson(1.0, (T, n)).astype(np.float32))
    stats = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    multi = node_feature_bin_histogram_multi(
        bins, locals_, weights, stats, n_nodes=L, n_bins=nb,
        row_tile=64, feature_tile=16, interpret=True)
    assert multi.shape == (T, L, f, nb, k)
    for t in range(T):
        single = node_feature_bin_histogram(
            bins, locals_[t], stats * weights[t][:, None], n_nodes=L,
            n_bins=nb, row_tile=64, feature_tile=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(multi[t]), np.asarray(single),
                                      err_msg=f"tree {t}")


def test_forest_chunk_pallas_matches_per_tree_loop():
    """fit_random_forest through the fused Pallas chunk builder must produce
    the same forest as the XLA per-tree loop (same PRNG stream; argmaxes on
    well-separated gains survive the kernel's bf16-split precision)."""
    from fraud_detection_tpu.models import trees as trees_mod
    from fraud_detection_tpu.models.train_trees import (
        TreeTrainConfig, fit_random_forest)

    rng = np.random.default_rng(12)
    X = rng.normal(size=(500, 24)).astype(np.float32)
    y = ((X[:, 2] > 0.1) ^ (X[:, 11] < -0.2)).astype(np.int32)
    kw = dict(n_trees=6, tree_chunk=3, seed=9)
    base = fit_random_forest(X, y, config=TreeTrainConfig(max_depth=4), **kw)
    pall = fit_random_forest(
        X, y, config=TreeTrainConfig(max_depth=4, use_pallas=True), **kw)
    np.testing.assert_array_equal(np.asarray(base.feature), np.asarray(pall.feature))
    np.testing.assert_array_equal(np.asarray(base.left), np.asarray(pall.left))
    p_base = trees_mod.predict(base, jnp.asarray(X))[1]
    p_pall = trees_mod.predict(pall, jnp.asarray(X))[1]
    np.testing.assert_allclose(np.asarray(p_base), np.asarray(p_pall),
                               rtol=1e-4, atol=1e-5)
