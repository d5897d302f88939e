"""Training driver CLI — the reference's ``main()`` rebuilt for TPU.

Mirrors /root/reference/fraud_detection_spark.py:326-405: load + clean the
dialogue corpus, 70/10/20 seeded split, train the classifier zoo (decision
tree, random forest, gradient boosting — plus logistic regression, the model
family the shipped serving artifact actually uses), evaluate every model on
validation and test with the same metric set (accuracy / weighted P / R / F1 /
AUC / confusion), print a report, and save the selected model as a native
checkpoint servable by ``ServingPipeline.from_checkpoint``.

Unlike the reference (no CLI flags anywhere — SURVEY.md §5), everything is
flag-driven:

    python -m fraud_detection_tpu.app.train --data synthetic --n 1600 \
        --models dt,rf,xgb,lr --save dt=fraud_model_dt --num-features 10000
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Tuple

import numpy as np


def load_corpus(args) -> List[Tuple[str, int]]:
    """Returns [(dialogue, label)]. CSV schema matches the reference dataset:
    columns ``dialogue`` and ``labels`` in {0, 1} (fraud_detection_spark.py:32-41)."""
    if args.data == "synthetic":
        from fraud_detection_tpu.data import generate_corpus

        return [(d.text, d.label) for d in generate_corpus(n=args.n, seed=args.seed)]
    import csv as csv_mod

    from fraud_detection_tpu.data import clean_rows, load_dialogue_csv

    if args.data.startswith(("http://", "https://")):
        rows = load_dialogue_csv(args.data)
    else:
        if not os.path.exists(args.data):
            raise SystemExit(f"CSV {args.data} not found")
        with open(args.data, newline="", encoding="utf-8") as fh:
            raw = list(csv_mod.DictReader(fh))
        if raw and "dialogue" not in raw[0]:
            raise SystemExit(
                f"CSV {args.data} missing 'dialogue' column (has {list(raw[0])})")
        # CLI conveniences on top of the strict reference chain: accept a
        # singular 'label' header and float-style labels ("1.0").
        for r in raw:
            if "labels" not in r and "label" in r:
                r["labels"] = r["label"]
            lab = (r.get("labels") or "").strip()
            try:
                val = float(lab)
            except ValueError:
                continue
            if val in (0.0, 1.0):
                r["labels"] = str(int(val))
        rows = clean_rows(raw)
    if not rows:
        raise SystemExit(
            f"CSV {args.data}: no usable rows — labels must be 0/1 "
            "(column 'labels' or 'label') and clean_text non-empty "
            "(fraud_detection_spark.py:40-45 semantics)")
    return [(r.dialogue, r.label) for r in rows]


def _ckpt_subdir(args, model_name: str):
    """Per-model snapshot directory under --checkpoint-dir (None when off)."""
    if args.checkpoint_dir is None:
        return None
    return os.path.join(args.checkpoint_dir, model_name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--data", default="synthetic",
                    help="'synthetic' or a CSV path with dialogue/labels columns")
    ap.add_argument("--n", type=int, default=1600, help="synthetic corpus size")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--models", default="dt,rf,xgb,lr",
                    help="comma list from {dt,rf,xgb,lr}")
    ap.add_argument("--num-features", type=int, default=10000)
    ap.add_argument("--featurizer", choices=("hashing", "count"), default="hashing",
                    help="'hashing' = HashingTF(num-features) like the shipped "
                         "artifact; 'count' = CountVectorizer(vocab-size) like "
                         "the reference training script (fraud_detection_spark.py:51)")
    ap.add_argument("--vocab-size", type=int, default=20000,
                    help="vocabulary cap for --featurizer count")
    ap.add_argument("--max-depth", type=int, default=5)
    ap.add_argument("--n-trees", type=int, default=100)
    ap.add_argument("--n-rounds", type=int, default=100)
    ap.add_argument("--tree-chunk", type=int, default=None,
                    help="forest trees built per program (default: auto per "
                         "backend); pass the original value when resuming a "
                         "checkpoint taken under a different default")
    ap.add_argument("--save", action="append", default=[],
                    help="model=dir pairs, e.g. dt=./fraud_model_dt (repeatable); "
                         "model=spark:<dir> exports the Spark PipelineModel "
                         "layout instead of the native format")
    ap.add_argument("--publish", action="append", default=[],
                    help="model=registry-root pairs (repeatable): publish "
                         "the trained model as the next version of a model "
                         "registry — atomic, content-hashed, with this "
                         "run's metrics in the manifest; a serve --registry "
                         "--watch picks it up live "
                         "(docs/model_lifecycle.md)")
    ap.add_argument("--mesh", action="store_true",
                    help="train data-parallel over all available devices")
    ap.add_argument("--json", action="store_true", help="emit metrics as JSON")
    ap.add_argument("--metrics-out", metavar="FILE", default=None,
                    help="write the full metric report (all models x splits "
                         "+ run metadata) as JSON to FILE — the repo's "
                         "analogue of the reference's Tables II-VI "
                         "(reports/report-paper.pdf)")
    ap.add_argument("--plots", metavar="DIR", default=None,
                    help="write metric-comparison + confusion-matrix PNGs here "
                         "(fraud_detection_spark.py:125-222 equivalents)")
    ap.add_argument("--associations", type=int, metavar="N", default=0,
                    help="word-association analysis over the top N features "
                         "per model (side-vocabulary inversion of hashed "
                         "features — SURVEY.md Q11)")
    ap.add_argument("--checkpoint-dir", metavar="DIR", default=None,
                    help="mid-training snapshot directory for the iterative "
                         "trainers (rf/xgb): snapshots land in DIR/<model>, "
                         "and an interrupted run resumes bit-identically "
                         "(the reference has no training resume, SURVEY §5)")
    ap.add_argument("--checkpoint-every", type=int, metavar="K", default=10,
                    help="snapshot cadence: boosting rounds / forest trees "
                         "(default 10)")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from fraud_detection_tpu.utils.device import device_stamp
    from fraud_detection_tpu.utils.jax_cache import (
        enable_persistent_compile_cache)

    enable_persistent_compile_cache()

    from fraud_detection_tpu.data import train_val_test_split
    from fraud_detection_tpu.eval import evaluate_classification
    from fraud_detection_tpu.featurize.tfidf import HashingTfIdfFeaturizer
    from fraud_detection_tpu.models import trees as trees_mod
    from fraud_detection_tpu.models.linear import predict_dense
    from fraud_detection_tpu.models.train_linear import fit_logistic_regression
    from fraud_detection_tpu.models.train_trees import (
        TreeTrainConfig, fit_decision_tree, fit_gradient_boosting, fit_random_forest)
    from fraud_detection_tpu.obs.trace import STAGE_SETUP_TRAIN, setup_span

    chosen = [m.strip() for m in args.models.split(",") if m.strip()]
    save_pairs = []
    for pair in args.save:  # validate before any training time is spent
        name, _, out_dir = pair.partition("=")
        target = out_dir[len("spark:"):] if out_dir.startswith("spark:") else out_dir
        if not target or name not in chosen:
            raise SystemExit(
                f"--save expects model=dir or model=spark:dir with the model in "
                f"--models (got {pair!r}, models: {chosen})")
        save_pairs.append((name, out_dir))
    publish_pairs = []
    for pair in args.publish:
        name, _, root = pair.partition("=")
        if not root or name not in chosen:
            raise SystemExit(
                f"--publish expects model=registry-root with the model in "
                f"--models (got {pair!r}, models: {chosen})")
        publish_pairs.append((name, root))

    corpus = load_corpus(args)
    train, val, test = train_val_test_split(corpus, seed=args.seed)
    print(f"Training samples: {len(train)}\nValidation samples: {len(val)}"
          f"\nTest samples: {len(test)}")

    if args.featurizer == "count":
        from fraud_detection_tpu.featurize.tfidf import VocabTfIdfFeaturizer

        feat = VocabTfIdfFeaturizer.fit_vocabulary(
            [t for t, _ in train], vocab_size=args.vocab_size)
    else:
        feat = HashingTfIdfFeaturizer(num_features=args.num_features)
    feat.fit_idf([t for t, _ in train])
    to_xy = lambda split: (
        np.asarray(feat.featurize_dense([t for t, _ in split])),
        np.asarray([l for _, l in split]))
    Xtr, ytr = to_xy(train)
    sets = {"Validation": to_xy(val), "Test": to_xy(test)}

    mesh = None
    if args.mesh:
        from fraud_detection_tpu.parallel import make_mesh

        mesh = make_mesh()
        print(f"mesh: {dict(mesh.shape)}")

    cfg = TreeTrainConfig(max_depth=args.max_depth)
    trained = {}
    timings: Dict[str, float] = {}
    for name in chosen:
        with setup_span(STAGE_SETUP_TRAIN,
                        detail=f"family={name} rows={len(ytr)}") as fit:
            if name == "dt":
                trained[name] = fit_decision_tree(Xtr, ytr, config=cfg, mesh=mesh)
            elif name == "rf":
                trained[name] = fit_random_forest(
                    Xtr, ytr, n_trees=args.n_trees, seed=args.seed, config=cfg, mesh=mesh,
                    tree_chunk=args.tree_chunk,
                    checkpoint_dir=_ckpt_subdir(args, name),
                    checkpoint_every=args.checkpoint_every)
            elif name == "xgb":
                trained[name] = fit_gradient_boosting(
                    Xtr, ytr, n_rounds=args.n_rounds, mesh=mesh,
                    config=TreeTrainConfig(max_depth=args.max_depth, criterion="xgb"),
                    checkpoint_dir=_ckpt_subdir(args, name),
                    checkpoint_every=args.checkpoint_every)
            elif name == "lr":
                trained[name] = fit_logistic_regression(
                    Xtr, ytr.astype(np.float32), mesh=mesh)
            else:
                raise SystemExit(f"unknown model {name!r} (choose from dt,rf,xgb,lr)")
        timings[name] = round(fit.seconds, 3)
        print(f"trained {name} in {timings[name]:.2f}s")

    def scores(model, X):
        if hasattr(model, "tree_weights"):
            return trees_mod.predict(model, jnp.asarray(X))
        return predict_dense(model, X)

    all_metrics: Dict[str, Dict[str, Dict[str, float]]] = {}
    all_reports: Dict[str, Dict[str, object]] = {}
    for name, model in trained.items():
        all_metrics[name] = {}
        all_reports[name] = {}
        for split_name, (X, y) in sets.items():
            pred, p1 = scores(model, X)
            rep = evaluate_classification(y, np.asarray(pred), np.asarray(p1))
            all_metrics[name][split_name] = rep.as_dict()
            all_reports[name][split_name] = rep
            if not args.json:
                print(f"\n=== {name} / {split_name} ===")
                for k, v in rep.as_dict().items():
                    print(f"  {k}: {v:.4f}")
                print(f"  confusion: {rep.confusion.tolist()}")
    if args.json:
        print(json.dumps({"device": device_stamp(), **all_metrics}, indent=2))
    if args.metrics_out:
        import math as math_mod

        from fraud_detection_tpu.models.train_trees import resolve_config

        def de_nan(v):
            # Undefined metrics (single-class AUC) must serialize as null:
            # bare NaN is outside the JSON spec and breaks non-Python readers.
            return None if isinstance(v, float) and math_mod.isnan(v) else v

        meta = {
            "data": args.data, "n": len(corpus), "seed": args.seed,
            "featurizer": args.featurizer,
            "max_depth": args.max_depth, "n_trees": args.n_trees,
            "n_rounds": args.n_rounds,
            "splits": {"train": len(train), "val": len(val),
                       "test": len(test)},
            **device_stamp(),
            "mesh": dict(mesh.shape) if mesh is not None else None,
            "train_seconds": timings,
        }
        if any(m in chosen for m in ("dt", "rf", "xgb")):
            # the EFFECTIVE tree-kernel path (a mesh forces the XLA path);
            # meaningless — and omitted — for LR-only runs
            meta["use_pallas"] = bool(resolve_config(cfg, mesh).use_pallas)
        if args.featurizer == "count":
            meta["vocab_size"] = args.vocab_size
        else:
            meta["num_features"] = args.num_features
        report = {
            "meta": meta,
            "metrics": {
                name: {split: dict(
                           {k: de_nan(v) for k, v in m.items()},
                           confusion=all_reports[name][split]
                           .confusion.tolist())
                       for split, m in per_split.items()}
                for name, per_split in all_metrics.items()
            },
        }
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as fh:
            json.dump(report, fh, indent=2, allow_nan=False)
        print(f"metrics report -> {args.metrics_out}")

    if args.plots:
        from fraud_detection_tpu.eval.report import (
            plot_confusion_matrices, plot_metrics_comparison)

        os.makedirs(args.plots, exist_ok=True)
        p = plot_metrics_comparison(
            all_reports, os.path.join(args.plots, "metrics_comparison.png"))
        cms = plot_confusion_matrices(
            all_reports, os.path.join(args.plots, "confusion_matrices"))
        print(f"plots: {p} + {len(cms)} confusion-matrix figures")

    if args.associations:
        from fraud_detection_tpu.eval import SideVocabulary, analyze_word_associations
        from fraud_detection_tpu.eval.word_associations import model_feature_importances

        train_texts = [t for t, _ in train]
        train_labels = [l for _, l in train]
        vocab = SideVocabulary(feat).add_corpus(train_texts)
        for name, model in trained.items():
            imps = model_feature_importances(model, Xtr, ytr)
            assocs = analyze_word_associations(
                model, feat, train_texts, train_labels,
                top_n=args.associations, vocab=vocab, importances=imps)
            print(f"\n=== word associations: {name} ===")
            for a in assocs:
                print(f"  {a.word:<20} importance={a.importance:.4f} "
                      f"scam_ratio={a.scam_ratio:.3f} "
                      f"({a.scam_docs} scam / {a.non_scam_docs} non-scam)")
            if args.plots:
                from fraud_detection_tpu.eval.report import plot_word_associations

                plot_word_associations(
                    assocs, os.path.join(args.plots, f"word_associations_{name}.png"),
                    model_name=name)

    from fraud_detection_tpu.checkpoint.native import save_checkpoint

    for name, out_dir in save_pairs:
        if out_dir.startswith("spark:"):
            from fraud_detection_tpu.checkpoint import save_spark_pipeline

            save_spark_pipeline(out_dir[len("spark:"):], feat, trained[name])
            print(f"saved {name} -> {out_dir[len('spark:'):]} (Spark PipelineModel layout)")
        else:
            save_checkpoint(out_dir, feat, trained[name])
            print(f"saved {name} -> {out_dir}")

    for name, root in publish_pairs:
        from fraud_detection_tpu.registry import ModelRegistry

        registry = ModelRegistry(root)
        mv = registry.publish(
            feat, trained[name],
            metrics=all_metrics.get(name),
            extra={"trained_with": {"model": name, "data": args.data,
                                    "seed": args.seed,
                                    "featurizer": args.featurizer}})
        print(f"published {name} -> {root} as {mv.name} "
              f"(parent: {mv.manifest['parent']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
