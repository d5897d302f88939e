"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as new files and new entries of BENCHMARK.json only: nothing that is
there is edited. Shown on a copy of the tree's BENCHMARK.json whose `paths`
gain one directory."""

import copy
import json
import os
import time

from conftest import CHIP, FIXTURES, REPO

from benchmark import run

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
