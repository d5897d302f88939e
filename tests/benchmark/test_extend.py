"""A later PR adds a configuration, a traffic mix, a cell, a per-layer
metric and an explainer family as new files and new entries of
BENCHMARK.json only: nothing that is there is edited. Shown on a copy of the
tree's BENCHMARK.json whose `paths` gain one directory."""

import copy
import json
import os
import time

import pytest

from conftest import CHIP, FIXTURES, REPO

from benchmark import check, run

READER = '''"""Rows the engine polled inside the window (a dummy metric)."""


def read(ctx):
    lo, hi = ctx["window"]
    rows = sum(int(s["detail"].split("=")[1]) for s in ctx["rowtrace"]
               if s["stage"] == "poll" and lo <= s["start"] < hi)
    return rows or None
'''


def test_add_config_mix_cell_and_metric_as_files_only(spec, tmp_path):
    root = tmp_path / "checkout"
    extra = root / "later_pr"
    for sub in ("configs", "traffic", "metrics"):
        (extra / sub).mkdir(parents=True)
    os.symlink(os.path.join(REPO, "benchmark"), root / "benchmark")
    with open(os.path.join(FIXTURES, "configs", "tiny-desk.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "later-desk"
    cfg["desk"]["sustained_rows_per_s"] = 300
    (extra / "configs" / "later-desk.json").write_text(json.dumps(cfg))
    with open(os.path.join(FIXTURES, "traffic", "tiny-stream.json")) as f:
        mix = json.load(f)
    mix["name"] = "later-mix"
    (extra / "traffic" / "later-mix.json").write_text(json.dumps(mix))
    (extra / "metrics" / "later.rows_polled.py").write_text(READER)

    new = copy.deepcopy(spec)
    cell = "later-desk.later-mix"
    new["paths"].append("later_pr")
    new["configs"].append({"name": "later-desk", "source": "test",
                           "file": "later_pr/configs/later-desk.json",
                           "reduced": [], "why": "a later PR's configuration"})
    new["workloads"].append({"name": cell, "config": "later-desk",
                             "traffic": "later-mix", "chips": 1,
                             "why": "a later PR's cell"})
    for m in new["end_to_end"]:
        if m["name"] in ("dialogues_per_s", "row_latency_p95_ms"):
            m["workloads"].append(cell)
    new["per_layer"].append({"name": "later.rows_polled", "unit": "rows",
                             "better": "higher", "source": "program_span",
                             "layer": "engine", "moves": "dialogues_per_s",
                             "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    loaded = run.load_spec(str(root))
    found, cfg2, mix2 = run.find_cell(loaded, cell, root=str(root))
    assert cfg2["name"] == "later-desk" and mix2["name"] == "later-mix"
    e2e, layer = run.cell_metrics(loaded, cell)
    assert {m["name"] for m in e2e} == {"dialogues_per_s", "row_latency_p95_ms",
                                        "setup_s"}
    assert [m["name"] for m in layer] == ["later.rows_polled"]
    # The cells that were there keep exactly their metrics.
    def names(s, cell_name):
        return [[m["name"] for m in part] for part in run.cell_metrics(s, cell_name)]

    for w in spec["workloads"]:
        assert names(loaded, w["name"]) == names(spec, w["name"])
    line = run.run_cell(loaded, found, cfg2, mix2, seed=11, seconds=2.0,
                        trace=True, t_start=time.time(), root=str(root),
                        scratch=str(tmp_path), device_kind=CHIP)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"later.rows_polled"}
    assert line["metrics"]["later.rows_polled"] == {
        "value": float(line["attempted"]), "unit": "rows"}


# ---------------------------------------------------------------------------
# an explainer family as files only
# ---------------------------------------------------------------------------

FAMILY = os.path.join(FIXTURES, "explainers", "toygelu.py")
SILU_REFERENCE = '''

_gelu_gaps = token_gaps


def token_gaps(seed, cfg, dtype_name, requests, pad_to):
    import jax

    return _gelu_gaps(seed, cfg, dtype_name, requests, pad_to, act=jax.nn.silu)
'''
# What a configuration states for its own family (here from CPU runs of this
# very fixture, 48 served tokens each: float32 on both sides read 0.0 on four
# seeds; of the int8 path's four, the two that flipped a token off a near tie
# are below).
STATED = {"token_gap_sq": {
    "limit": 1e-6, "sound": [0.0, 0.0, 0.0, 0.0], "control": [9.68e-4, 2.05e-3],
    "why": "float32 program against float32 reference at test size"}}


def _family_checkout(spec, tmp_path, *, family_source=None, model_type="toygelu",
                     check=STATED):
    """A copy of the tree's BENCHMARK.json whose ``paths`` gain ``later_pr``,
    which holds a family file, a configuration that names it and states its
    own limit, a campaign mix and their cell; ``benchmark/`` is a link to the
    tree's, so nothing under it can have been edited."""
    root = tmp_path / "checkout"
    extra = root / "later_pr"
    for sub in ("configs", "traffic", "explainers"):
        (extra / sub).mkdir(parents=True)
    os.symlink(os.path.join(REPO, "benchmark"), root / "benchmark")
    with open(FAMILY) as f:
        (extra / "explainers" / "toygelu.py").write_text(
            f.read() + (family_source or ""))
    with open(os.path.join(FIXTURES, "configs", "tiny-desk.json")) as f:
        cfg = json.load(f)
    cfg.update(name="later-gelu-desk", model_type=model_type,
               hidden_act="gelu_pytorch_tanh", tie_word_embeddings=True)
    if check is not None:
        cfg["check"] = check
    (extra / "configs" / "later-gelu-desk.json").write_text(json.dumps(cfg))
    with open(os.path.join(FIXTURES, "traffic", "tiny-campaign.json")) as f:
        mix = json.load(f)
    mix["name"] = "later-campaign"
    (extra / "traffic" / "later-campaign.json").write_text(json.dumps(mix))

    new = copy.deepcopy(spec)
    cell = "later-gelu-desk.later-campaign"
    new["paths"].append("later_pr")
    new["configs"].append({"name": "later-gelu-desk", "source": "test",
                           "file": "later_pr/configs/later-gelu-desk.json",
                           "reduced": [], "why": "a later PR's explainer"})
    new["workloads"].append({"name": cell, "config": "later-gelu-desk",
                             "traffic": "later-campaign", "chips": 1,
                             "why": "a later PR's cell"})
    for m in new["end_to_end"] + new["per_layer"]:
        if m["name"] in ("explanations_per_s", "explain.step_mfu"):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    loaded = run.load_spec(str(root))
    return (str(root), loaded) + run.find_cell(loaded, cell, root=str(root))


def _run(root, loaded, cell, cfg, mix, tmp_path, seed=2**31 + 28, **kw):
    return run.run_cell(loaded, cell, cfg, mix, seed=seed, seconds=3.0,
                        t_start=time.time(), root=root, scratch=str(tmp_path),
                        device_kind=CHIP, **kw)


def test_add_an_explainer_family_as_files_only(spec, tmp_path):
    root, loaded, cell, cfg, mix = _family_checkout(spec, tmp_path)
    family = run.load_family(loaded, cfg, root)
    assert family.__file__.startswith(os.path.join(root, "later_pr"))
    # The harness's own reference refuses this model on both keys.
    from benchmark import reference
    with pytest.raises(ValueError, match="untied"):
        reference.make_llm_params(1, cfg, "float32")
    with pytest.raises(ValueError, match="SiLU"):
        reference.llm_token_gaps(1, cfg, "float32", [], 8)
    line = _run(root, loaded, cell, cfg, mix, tmp_path, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["info"]["numbers"]["tokens_compared"] > 0
    # The limit is the one the configuration states, not check.LIMITS'.
    assert line["compared"]["token_gap_sq"] == [0.0, 1e-6]
    assert check.LIMITS["token_gap_sq"] == 6.5e-4
    # ... and the counts are the family's: the MFU reader found them.
    assert line["metrics"]["explain.step_mfu"]["value"] > 0
    import jax
    params = family.make_params(3, cfg, "float32")
    assert "lm_head" not in params
    assert sum(p.size for p in jax.tree_util.tree_leaves(params)) \
        == family.param_count(cfg)


def test_the_wrong_family_reference_is_not_correct(spec, tmp_path):
    """The same family with its reference's activation swapped for SiLU."""
    root, loaded, cell, cfg, mix = _family_checkout(
        spec, tmp_path, family_source=SILU_REFERENCE)
    line = _run(root, loaded, cell, cfg, mix, tmp_path, trace=False)
    value, limit = line["compared"]["token_gap_sq"]
    assert line["correct"] is False and value > limit == 1e-6
    assert line["compared"]["notes_unaccounted"] == [0, 0]


def test_the_family_control_is_judged_by_the_stated_limit(spec, tmp_path):
    """benchmark/control.py's mode: the family's own lower precision, held
    to the limit its configuration states."""
    root, loaded, cell, cfg, mix = _family_checkout(spec, tmp_path)
    cfg["desk"]["explain"]["weights"] = "int8"
    line = _run(root, loaded, cell, cfg, mix, tmp_path, seed=5, trace=False,
                control=True)
    value, limit = line["control"]["compared"]["token_gap_sq"]
    assert line["control"]["correct"] is False
    assert value > 100 * limit and limit == 1e-6


def _without(key):
    entry = dict(STATED["token_gap_sq"])
    del entry[key]
    return {"token_gap_sq": entry}


@pytest.mark.parametrize("kw,error,said", [
    ({"model_type": "never-heard-of"}, SystemExit,
     r"searched \['benchmark/explainers', 'tests/benchmark/explainers', "
     r"'later_pr/explainers'\] for never-heard-of\.py"),
    ({"family_source": "\ndel prefill_cost\n"}, SystemExit,
     r"lacks \['prefill_cost'\]"),
    ({"check": _without("why")}, ValueError, r"lacks \['why'\]"),
    ({"check": _without("control")}, ValueError, r"lacks \['control'\]"),
    ({"check": {"confidence_gap": STATED["token_gap_sq"]}}, ValueError,
     r"\['token_gap_sq'\] only, not 'confidence_gap'"),
    ({"check": {"token_gap_sq": dict(STATED["token_gap_sq"], limit=0.5)}},
     ValueError, "does not stand between"),
], ids=["unknown-model-type", "family-lacks-a-function", "limit-without-why",
        "limit-without-control", "another-limit-stated",
        "limit-outside-its-readings"])
def test_what_a_family_or_its_limit_may_not_do(spec, tmp_path, kw, error, said):
    root, loaded, cell, cfg, mix = _family_checkout(spec, tmp_path, **kw)
    with pytest.raises(error, match=said):
        _run(root, loaded, cell, cfg, mix, tmp_path, trace=False)
