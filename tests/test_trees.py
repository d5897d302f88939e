"""Tree ensemble tests: traversal semantics, Spark-stage decoding, trainers."""

import numpy as np
import pytest

import jax.numpy as jnp

from fraud_detection_tpu.checkpoint.spark_artifact import TreeEnsembleStage, TreeNode
from fraud_detection_tpu.models.trees import (
    TreeEnsemble,
    feature_importances,
    from_spark_stage,
    predict,
    predict_proba,
)
from fraud_detection_tpu.models.train_trees import (
    TreeTrainConfig,
    apply_bins,
    fit_decision_tree,
    fit_gradient_boosting,
    fit_random_forest,
    quantile_bin_edges,
)


def _manual_stump() -> TreeEnsemble:
    # Single tree: root splits on feature 1 at 0.5; left leaf class 0 (3:1),
    # right leaf class 1 (1:9).
    return TreeEnsemble(
        feature=jnp.array([[1, -1, -1]], jnp.int32),
        threshold=jnp.array([[0.5, 0.0, 0.0]], jnp.float32),
        left=jnp.array([[1, -1, -1]], jnp.int32),
        right=jnp.array([[2, -1, -1]], jnp.int32),
        leaf=jnp.array([[[0, 0], [3, 1], [1, 9]]], jnp.float32),
        tree_weights=jnp.ones((1,)),
        kind="decision_tree",
        max_depth=1,
    )


def test_stump_traversal_boundary():
    ens = _manual_stump()
    x = jnp.array([[9.0, 0.5], [9.0, 0.50001], [9.0, -1.0]], jnp.float32)
    pred, p1 = predict(ens, x)
    # Spark semantics: go left iff value <= threshold (0.5 goes left).
    assert np.asarray(pred).tolist() == [0, 1, 0]
    np.testing.assert_allclose(np.asarray(p1), [0.25, 0.9, 0.25], rtol=1e-6)


def test_random_forest_averaging_semantics():
    # Two stumps voting differently: Spark averages per-tree probabilities.
    base = _manual_stump()
    ens = TreeEnsemble(
        feature=jnp.concatenate([base.feature, base.feature]),
        threshold=jnp.asarray([[0.5, 0, 0], [2.0, 0, 0]], jnp.float32),
        left=jnp.concatenate([base.left, base.left]),
        right=jnp.concatenate([base.right, base.right]),
        leaf=jnp.asarray([[[0, 0], [3, 1], [1, 9]],
                          [[0, 0], [1, 1], [0, 1]]], jnp.float32),
        tree_weights=jnp.ones((2,)),
        kind="random_forest",
        max_depth=1,
    )
    x = jnp.array([[0.0, 1.0]], jnp.float32)  # tree1: right leaf; tree2: left leaf
    proba = predict_proba(ens, x)
    expected_p1 = (0.9 + 0.5) / 2
    np.testing.assert_allclose(np.asarray(proba)[0, 1], expected_p1, rtol=1e-6)


def test_gbt_margin_semantics():
    ens = TreeEnsemble(
        feature=jnp.array([[0, -1, -1]], jnp.int32),
        threshold=jnp.array([[0.0, 0, 0]], jnp.float32),
        left=jnp.array([[1, -1, -1]], jnp.int32),
        right=jnp.array([[2, -1, -1]], jnp.int32),
        leaf=jnp.array([[[0.0], [-0.7], [0.7]]], jnp.float32),
        tree_weights=jnp.asarray([0.5]),
        kind="gbt",
        max_depth=1,
    )
    x = jnp.array([[1.0], [-1.0]], jnp.float32)
    proba = predict_proba(ens, x)
    # Spark GBT: p1 = sigmoid(2 * margin), margin = 0.5 * (+-0.7)
    expected = 1 / (1 + np.exp(-2 * 0.5 * 0.7))
    np.testing.assert_allclose(np.asarray(proba)[:, 1], [expected, 1 - expected], rtol=1e-5)


def _spark_like_stage() -> TreeEnsembleStage:
    # Spark preorder ids: root 0, children 1,2; node 1 splits into 3,4.
    nodes = [
        TreeNode(id=0, prediction=1, impurity=0.5, impurity_stats=np.array([10.0, 10.0]),
                 gain=0.3, left=1, right=2, split_feature=2, split_threshold=1.5),
        TreeNode(id=1, prediction=0, impurity=0.4, impurity_stats=np.array([8.0, 4.0]),
                 gain=0.2, left=3, right=4, split_feature=0, split_threshold=-0.5),
        TreeNode(id=2, prediction=1, impurity=0.1, impurity_stats=np.array([2.0, 6.0]),
                 gain=-1.0, left=-1, right=-1, split_feature=-1, split_threshold=0.0),
        TreeNode(id=3, prediction=0, impurity=0.0, impurity_stats=np.array([8.0, 0.0]),
                 gain=-1.0, left=-1, right=-1, split_feature=-1, split_threshold=0.0),
        TreeNode(id=4, prediction=1, impurity=0.0, impurity_stats=np.array([0.0, 4.0]),
                 gain=-1.0, left=-1, right=-1, split_feature=-1, split_threshold=0.0),
    ]
    return TreeEnsembleStage(
        kind="decision_tree", trees=[nodes], tree_weights=np.ones(1),
        num_features=3, num_classes=2, features_col="features", label_col="label")


def test_from_spark_stage_roundtrip():
    ens = from_spark_stage(_spark_like_stage())
    assert ens.max_depth == 2
    x = jnp.array([
        [-1.0, 0.0, 1.0],   # f2<=1.5 -> node1; f0<=-0.5 -> node3: class 0 (8:0)
        [0.0, 0.0, 1.0],    # node1; f0>-0.5 -> node4: class 1 (0:4)
        [0.0, 0.0, 2.0],    # f2>1.5 -> node2: class 1 (2:6)
    ], jnp.float32)
    pred, p1 = predict(ens, x)
    assert np.asarray(pred).tolist() == [0, 1, 1]
    np.testing.assert_allclose(np.asarray(p1), [0.0, 1.0, 0.75], atol=1e-6)


def test_feature_importances_gain_weighted():
    imp = feature_importances(_spark_like_stage(), 3)
    assert imp.shape == (3,)
    assert imp.sum() == pytest.approx(1.0)
    assert imp[2] > imp[0] > 0 and imp[1] == 0.0  # f2: gain .3 x 20; f0: .2 x 12


def test_binning_roundtrip_consistency():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(500, 3)).astype(np.float32)
    edges = quantile_bin_edges(X, 32)
    assert edges.shape == (3, 31)
    bins = np.asarray(apply_bins(jnp.asarray(X), jnp.asarray(edges)))
    # Contract: x <= edges[b] <=> bin(x) <= b (traversal/binning consistency).
    for f in range(3):
        for b in [0, 10, 30]:
            if b < 31:
                lhs = X[:, f] <= edges[f, b] if b < edges.shape[1] else np.ones(500, bool)
                rhs = bins[:, f] <= b
                np.testing.assert_array_equal(lhs, rhs)


def test_decision_tree_learns_separable():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(600, 5)).astype(np.float32)
    y = (X[:, 3] > 0.2).astype(np.int64)
    ens = fit_decision_tree(X, y, config=TreeTrainConfig(max_depth=3))
    pred, _ = predict(ens, jnp.asarray(X))
    acc = np.mean(np.asarray(pred) == y)
    assert acc > 0.97, acc
    # The root must split on the informative feature.
    assert int(np.asarray(ens.feature)[0, 0]) == 3


def test_decision_tree_close_to_sklearn():
    from sklearn.tree import DecisionTreeClassifier

    rng = np.random.default_rng(2)
    X = rng.normal(size=(800, 8)).astype(np.float32)
    logits = 1.5 * X[:, 0] - 2.0 * X[:, 5] + X[:, 2] * X[:, 0]
    y = (logits + rng.normal(0, 0.5, 800) > 0).astype(np.int64)
    ours = fit_decision_tree(X, y, config=TreeTrainConfig(max_depth=5))
    pred, _ = predict(ours, jnp.asarray(X))
    acc_ours = np.mean(np.asarray(pred) == y)
    sk = DecisionTreeClassifier(max_depth=5, random_state=0).fit(X, y)
    acc_sk = sk.score(X, y)
    assert acc_ours > acc_sk - 0.05, (acc_ours, acc_sk)


def test_random_forest_beats_single_tree():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(500, 12)).astype(np.float32)
    logits = X[:, 0] - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
    y = (logits + rng.normal(0, 0.8, 500) > 0).astype(np.int64)
    Xte = rng.normal(size=(500, 12)).astype(np.float32)
    yte = (Xte[:, 0] - Xte[:, 1] + 0.5 * Xte[:, 2] * Xte[:, 3] > 0).astype(np.int64)

    dt = fit_decision_tree(X, y, config=TreeTrainConfig(max_depth=4))
    rf = fit_random_forest(X, y, n_trees=24, seed=0,
                           config=TreeTrainConfig(max_depth=4), tree_chunk=8)
    acc = lambda m: np.mean(np.asarray(predict(m, jnp.asarray(Xte))[0]) == yte)
    assert rf.num_trees == 24
    assert acc(rf) >= acc(dt) - 0.02, (acc(rf), acc(dt))
    assert acc(rf) > 0.75


def test_gradient_boosting_converges():
    rng = np.random.default_rng(4)
    X = rng.normal(size=(400, 6)).astype(np.float32)
    y = ((X[:, 1] > 0) ^ (X[:, 4] > 0)).astype(np.int64)  # XOR: needs depth
    ens = fit_gradient_boosting(
        X, y, n_rounds=30,
        config=TreeTrainConfig(max_depth=3, criterion="xgb", learning_rate=0.3))
    pred, p1 = predict(ens, jnp.asarray(X))
    acc = np.mean(np.asarray(pred) == y)
    assert acc > 0.95, acc


def test_mesh_tree_training_matches_single_device():
    from fraud_detection_tpu.parallel import make_mesh

    rng = np.random.default_rng(7)
    X = rng.normal(size=(301, 6)).astype(np.float32)  # odd n exercises padding
    y = (X[:, 1] - X[:, 4] > 0).astype(np.int64)
    cfg = TreeTrainConfig(max_depth=4)
    single = fit_decision_tree(X, y, config=cfg)
    sharded = fit_decision_tree(X, y, config=cfg, mesh=make_mesh())
    # Identical data + deterministic splits => identical trees.
    np.testing.assert_array_equal(np.asarray(single.feature), np.asarray(sharded.feature))
    np.testing.assert_allclose(np.asarray(single.threshold), np.asarray(sharded.threshold))
    np.testing.assert_allclose(np.asarray(single.leaf), np.asarray(sharded.leaf), rtol=1e-5)

    gbt_single = fit_gradient_boosting(X, y, n_rounds=5, config=cfg)
    gbt_sharded = fit_gradient_boosting(X, y, n_rounds=5, config=cfg, mesh=make_mesh())
    xs = jnp.asarray(X)
    np.testing.assert_allclose(
        np.asarray(predict_proba(gbt_single, xs)),
        np.asarray(predict_proba(gbt_sharded, xs)), atol=1e-4)


def test_all_tree_models_on_synthetic_corpus():
    from fraud_detection_tpu.data import generate_corpus, train_val_test_split
    from fraud_detection_tpu.featurize.tfidf import HashingTfIdfFeaturizer

    corpus = generate_corpus(n=600, seed=11)
    train, _, test = train_val_test_split(corpus, seed=42)
    feat = HashingTfIdfFeaturizer(num_features=2048)
    feat.fit_idf([d.text for d in train])
    Xtr = np.asarray(feat.featurize_dense([d.text for d in train]))
    ytr = np.asarray([d.label for d in train])
    Xte = np.asarray(feat.featurize_dense([d.text for d in test]))
    yte = np.asarray([d.label for d in test])

    cfg = TreeTrainConfig(max_depth=5)
    dt = fit_decision_tree(Xtr, ytr, config=cfg)
    rf = fit_random_forest(Xtr, ytr, n_trees=16, tree_chunk=4, config=cfg)
    xgb = fit_gradient_boosting(Xtr, ytr, n_rounds=20,
                                config=TreeTrainConfig(max_depth=5, criterion="xgb"))
    for name, m in [("dt", dt), ("rf", rf), ("xgb", xgb)]:
        pred, _ = predict(m, jnp.asarray(Xte))
        acc = np.mean(np.asarray(pred) == yte)
        assert acc > 0.9, (name, acc)


def test_serving_pipeline_multiclass_tree_uses_argmax():
    """ServingPipeline labels for a >2-class ensemble must match device argmax
    (the binary p1>0.5 shortcut is invalid there — review regression)."""
    from fraud_detection_tpu.featurize.tfidf import HashingTfIdfFeaturizer
    from fraud_detection_tpu.models.pipeline import ServingPipeline

    rng = np.random.default_rng(5)
    # Alphabetic-only vocab: the Spark-parity text prep strips digits, so
    # names like "w0" would all collapse to the single token "w" (idf 0).
    syll = ["ka", "lo", "mi", "ne", "pu", "ri", "so", "ta", "vu", "ze"]
    vocab = [a + b for a in syll for b in syll][:30]
    texts, labels = [], []
    for i in range(240):
        c = i % 3
        words = rng.choice(vocab[c * 10:(c + 1) * 10], size=20)
        texts.append(" ".join(words))
        labels.append(c)
    feat = HashingTfIdfFeaturizer(num_features=512)
    feat.fit_idf(texts)
    X = np.asarray(feat.featurize_dense(texts))
    y = np.asarray(labels)

    dt = fit_decision_tree(X, y, num_classes=3, config=TreeTrainConfig(max_depth=5))
    pipe = ServingPipeline(feat, dt, batch_size=64)
    got = pipe.predict(texts)
    want, _ = predict(dt, jnp.asarray(X))
    np.testing.assert_array_equal(got.labels, np.asarray(want))
    assert np.mean(got.labels == y) > 0.9


def test_prebinned_int8_training_matches_float_path():
    """bin_rows_host + int8 upload is the quarter-of-the-bytes training path:
    host bins must equal device apply_bins
    bit-for-bit, trainers must accept the int8 matrix with edges and build
    the identical model, and pre-binned input without edges must refuse."""
    import jax.numpy as jnp

    from fraud_detection_tpu.models.train_trees import (
        apply_bins, bin_rows_host, fit_decision_tree, fit_gradient_boosting,
        quantile_bin_edges)

    rng = np.random.default_rng(5)
    X = rng.normal(0, 1, (400, 24)).astype(np.float32)
    X[rng.uniform(size=X.shape) < 0.6] = 0.0        # TF-IDF-ish zero inflation
    y = (X[:, 0] + 0.2 * rng.normal(size=400) > 0).astype(np.int32)
    edges = quantile_bin_edges(X, 32)

    bins8 = bin_rows_host(X, edges)
    assert bins8.dtype == np.int8
    np.testing.assert_array_equal(
        np.asarray(apply_bins(jnp.asarray(X), jnp.asarray(edges))), bins8)

    for fit in (fit_decision_tree,
                lambda a, b, edges: fit_gradient_boosting(a, b, n_rounds=3,
                                                          edges=edges)):
        m_f32 = fit(X, y, edges=edges)
        m_int8 = fit(bins8, y, edges=edges)
        for field_name in ("feature", "threshold", "left", "right", "leaf"):
            np.testing.assert_array_equal(
                np.asarray(getattr(m_f32, field_name)),
                np.asarray(getattr(m_int8, field_name)), err_msg=field_name)

    with pytest.raises(ValueError, match="pre-binned"):
        fit_decision_tree(bins8, y)


def test_prebinned_guards_reject_garbage():
    """The integer-dtype pre-binned signal is validated, not trusted: raw
    integer features (out-of-range ids) raise instead of silently indexing
    histograms with garbage, and host binning refuses edge counts beyond
    int8 (round-3 review findings)."""
    from fraud_detection_tpu.models.train_trees import (
        bin_rows_host, fit_decision_tree, quantile_bin_edges)

    rng = np.random.default_rng(0)
    X = rng.normal(0, 1, (100, 8)).astype(np.float32)
    edges = quantile_bin_edges(X, 32)
    raw_counts = rng.integers(0, 500, (100, 8)).astype(np.int32)  # NOT bins
    with pytest.raises(ValueError, match="bin_rows_host output"):
        fit_decision_tree(raw_counts, (X[:, 0] > 0).astype(int), edges=edges)

    wide = np.tile(np.linspace(0, 1, 200, dtype=np.float32)[:, None], (1, 8))
    with pytest.raises(ValueError, match="int8 range"):
        bin_rows_host(X, quantile_bin_edges(wide, 256))


def test_cached_bin_range_rechecks_against_each_fits_n_bins():
    """The validation cache stores the fetched (lo, hi), NOT a pass verdict:
    refitting the same device array under a smaller n_bins must still raise
    (sixth-pass review — a cached pass silently re-opened the garbage-
    histogram hole the validation exists to close)."""
    import jax.numpy as jnp

    from fraud_detection_tpu.models.train_trees import (
        TreeTrainConfig, bin_rows_host, fit_decision_tree, quantile_bin_edges)

    rng = np.random.default_rng(2)
    X = rng.normal(0, 1, (300, 16)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32)
    edges32 = quantile_bin_edges(X, 32)
    dev = jnp.asarray(bin_rows_host(X, edges32))       # ids up to 31
    fit_decision_tree(dev, y, edges=edges32)           # validates, caches range
    small = TreeTrainConfig(n_bins=16)
    with pytest.raises(ValueError, match="n_bins=16"):
        fit_decision_tree(dev, y, edges=edges32[:, :15], config=small)


def test_encoded_traversal_matches_dense_path():
    """predict_proba_encoded (the scatter-free serving path) must agree with
    predict_proba on the densified rows for every ensemble kind — same split
    comparisons, so identical leaf routing."""
    from fraud_detection_tpu.data import generate_corpus
    from fraud_detection_tpu.featurize.tfidf import HashingTfIdfFeaturizer
    from fraud_detection_tpu.models.trees import predict_proba, predict_proba_encoded

    corpus = generate_corpus(n=300, seed=21)
    texts = [d.text for d in corpus]
    y = np.asarray([d.label for d in corpus])
    feat = HashingTfIdfFeaturizer(num_features=1024)
    feat.fit_idf(texts)
    X = np.asarray(feat.featurize_dense(texts))
    enc = feat.encode(texts)
    idf = feat.idf_array()

    cfg = TreeTrainConfig(max_depth=4)
    models = [
        fit_decision_tree(X, y, config=cfg),
        fit_random_forest(X, y, n_trees=6, tree_chunk=3, config=cfg),
        fit_gradient_boosting(X, y, n_rounds=6,
                              config=TreeTrainConfig(max_depth=4, criterion="xgb")),
    ]
    for m in models:
        dense = np.asarray(predict_proba(m, jnp.asarray(X)))
        sparse = np.asarray(predict_proba_encoded(
            m, jnp.asarray(enc.ids), jnp.asarray(enc.counts), jnp.asarray(idf)))
        np.testing.assert_allclose(sparse, dense, rtol=1e-5, atol=1e-6,
                                   err_msg=m.kind)


def test_poisson1_inverse_cdf_distribution():
    """The forest's bootstrap sampler (inverse-CDF Poisson(1)) matches the
    true pmf: one uniform + 13-entry searchsorted replaced
    jax.random.poisson's rejection loops (~30x faster at bench shapes)."""
    import math

    import jax

    from fraud_detection_tpu.models.train_trees import _poisson1

    w = np.asarray(_poisson1(jax.random.PRNGKey(0), (200_000,)))
    assert w.min() >= 0 and w.max() <= 13
    assert abs(w.mean() - 1.0) < 0.01
    assert abs(w.var() - 1.0) < 0.02
    for k, p in ((0, math.exp(-1)), (1, math.exp(-1)), (2, math.exp(-1) / 2)):
        assert abs((w == k).mean() - p) < 0.005


def test_route_rows_fallback_matches_matmul_branch():
    """Both REAL branches of _route_rows — the one-hot matmul and the
    256MB-guarded gather fallback (forced via dense_limit=0) — must agree
    exactly, including ties, inactive rows, and no-split nodes. Bench
    shapes only ever run the matmul branch, so this is the fallback's one
    execution in the suite."""
    from fraud_detection_tpu.models import train_trees as tt

    rng = np.random.default_rng(11)
    t, n, f, width = 3, 257, 64, 8
    bins = jnp.asarray(rng.integers(0, 32, (n, f), dtype=np.int32))
    local = jnp.asarray(rng.integers(-1, width + 1, (t, n), dtype=np.int32))
    seg_valid = (jnp.asarray(rng.uniform(size=(t, n)) < 0.8)
                 & (local >= 0) & (local < width))
    node = jnp.asarray(rng.integers(0, 2 * width, (t, n), dtype=np.int32))
    best_f = jnp.asarray(rng.integers(0, f, (t, width), dtype=np.int32))
    best_b = jnp.asarray(rng.integers(0, 31, (t, width), dtype=np.int32))
    do_split = jnp.asarray(rng.uniform(size=(t, width)) < 0.7)

    args = (bins, local, seg_valid, node, best_f, best_b, do_split, width)
    node_mm, act_mm = tt._route_rows(*args)
    node_gather, act_gather = tt._route_rows(*args, dense_limit=0)
    np.testing.assert_array_equal(np.asarray(node_mm), np.asarray(node_gather))
    np.testing.assert_array_equal(np.asarray(act_mm), np.asarray(act_gather))


def test_node_totals_fallback_matches_dense():
    """_node_totals' segment_sum fallback (above the dense-transient
    threshold) must equal the dense matmul path bit-for-bit on integer
    stats."""
    from fraud_detection_tpu.models import train_trees as tt

    rng = np.random.default_rng(5)
    n, width, k = 4096, 16, 2
    stats = jnp.asarray(rng.integers(0, 4, (n, k)).astype(np.float32))
    seg = jnp.asarray(rng.integers(0, width + 1, (n,), dtype=np.int32))
    dense = tt._node_totals(stats, seg, width)
    # batch_factor large enough to trip the fallback at these shapes
    fallback = tt._node_totals(stats, seg, width, batch_factor=10**6)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(fallback))
