"""The runner end to end on the CPU at tiny sizes: every mix through the one
entry, the result line's keys, the device gate, and `correct` turning false
when the timed path is broken underneath."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
EXTRA_KEYS = {"breakdown", "info", "compared"}


def _check_line(line, spec, cell_traffic, trace):
    from benchmark import run

    assert LINE_KEYS <= set(line) <= LINE_KEYS | EXTRA_KEYS
    assert list(line)[-1] == "compared"          # the numbers come last
    assert json.loads(json.dumps(line)) == line  # one JSON object, finite
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    cell = next(w for w in spec["workloads"] if w["traffic"] == cell_traffic)
    e2e, layer = run.cell_metrics(spec, cell["name"])
    names = {m["name"] for m in (layer if trace else e2e)}
    assert set(line["metrics"]) <= names
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    for value, limit in line["compared"].values():
        assert value <= limit
    return names


@pytest.mark.parametrize("mix,kind,config", [
    ("tiny-campaign", "campaign", "tiny-desk"),
    ("tiny-steady", "steady", "tiny-desk"),
    ("tiny-stream", "stream-quiet", "tiny-desk"),
    ("tiny-stream", "stream-quiet", "tiny-desk-xgb"),
])
def test_each_mix_end_to_end(run_tiny, spec, mix, kind, config):
    line = run_tiny(mix, config=config, kind=kind)
    names = _check_line(line, spec, kind, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == names         # every end-to-end metric
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["attempted"] > 0
    if kind == "stream-quiet":
        assert line["info"]["lane_admitted"] == 0
        assert line["metrics"]["dialogues_per_s"]["value"] > 0
    else:
        assert {"token_gap_sq", "notes_unaccounted",
                "idf_gap"} <= set(line["compared"])
        assert line["info"]["numbers"]["tokens_compared"] > 0
        assert line["compared"]["token_gap_sq"][0] == 0.0     # float32 both
        assert line["metrics"]["explanations_per_s"]["value"] > 0


@pytest.mark.parametrize("mix,kind", [("tiny-steady", "steady"),
                                      ("tiny-stream", "stream-quiet")])
def test_traced_run_reports_per_layer_metrics(run_tiny, spec, mix, kind):
    line = run_tiny(mix, kind=kind, trace=True, seconds=3.0)
    _check_line(line, spec, kind, trace=True)
    assert line["correct"] is True
    # What needs no device trace is read on the CPU too; what needs one is
    # left out of the line, never reported as 0.
    want = {"steady": {"slot.occupancy", "slot.ttft_p50_ms",
                       "gen.late_p99_ms.explain", "explain.step_mfu"},
            "stream-quiet": {"gen.late_p99_ms.stream", "engine.dispatch_ms",
                             "engine.finish_ms", "featurize.us_per_row",
                             "score.step_mfu"}}[kind]
    assert want <= set(line["metrics"])
    assert all(m["value"] != 0 for m in line["metrics"].values())
    assert "paged_decode_window_roofline" not in line["metrics"]


def _alter_token(desk):
    """A served token altered where it is produced."""
    step = desk.svc._decoder.step

    def bad_step(*args, **kw):
        out, lens, steps_run, n_act = step(*args, **kw)
        out = np.array(out)
        out[:, 0] = (out[:, 0] + 7) % 250
        return out, lens, steps_run, n_act

    desk.svc._decoder.step = bad_step


def _alter_answer(desk):
    """An answer altered where it is produced: every probability the
    scoring program returns is pulled a thousandth toward 0.5."""
    from fraud_detection_tpu.models import pipeline

    resolve = pipeline.PendingPrediction.resolve

    def bad_resolve(self):
        got = resolve(self)
        return pipeline.PredictionBatch(
            got.labels, got.probabilities + (0.5 - got.probabilities) * 1e-3)

    desk._restore = (pipeline.PendingPrediction, resolve)
    pipeline.PendingPrediction.resolve = bad_resolve


def _never_deliver(desk):
    """The lane decodes, and its annotations never reach the topic."""
    producer = desk.engine._annotation_lane._producer
    producer.produce_batch = lambda topic, items: None
    producer.produce = lambda topic, value, key=None: None


@pytest.mark.parametrize("fault,mix,kind,number", [
    (_alter_token, "tiny-campaign", "campaign", "token_gap_sq"),
    (_alter_answer, "tiny-stream", "stream-quiet", "confidence_gap"),
    (_never_deliver, "tiny-campaign", "campaign", "notes_unaccounted"),
    (_never_deliver, "tiny-steady", "steady", "notes_unaccounted"),
])
def test_a_broken_timed_path_is_not_correct(run_tiny, fault, mix, kind, number):
    from fraud_detection_tpu.models import pipeline

    resolve = pipeline.PendingPrediction.resolve
    try:
        line = run_tiny(mix, kind=kind, fault=fault)
    finally:
        pipeline.PendingPrediction.resolve = resolve
    assert line["correct"] is False
    value, limit = line["compared"][number]
    assert value > limit
    if fault is _never_deliver:     # what was never delivered counts nothing
        assert line["metrics"]["explanations_per_s"]["value"] == 0
        assert line["failed"] >= value


def test_a_backlog_cut_off_at_the_close_is_accounted(run_tiny):
    """Above capacity the rows still queued when the run ends get no record;
    the annotation lane counts each as discarded, and the two agree."""
    def slow(desk):
        import time
        step = desk.svc._decoder.step

        def slow_step(*args, **kw):
            time.sleep(0.05)
            return step(*args, **kw)

        desk.svc._decoder.step = slow_step

    line = run_tiny("tiny-campaign", kind="campaign", fault=slow, seconds=2.0,
                    rate_times=40.0)
    numbers = line["info"]["numbers"]
    assert numbers["notes_silent"] > 0
    assert numbers["notes_silent"] == numbers["notes_discarded"]
    assert line["compared"]["notes_unaccounted"] == [0, 0]
    assert line["correct"] is True


def test_control_mode_judges_the_lower_precision(run_tiny):
    """benchmark/control.py's mode at test size: the explainer served
    through the program's own int8 path, and the classifier's reference in
    bfloat16 put in the program's place, go through the same verdict."""
    from benchmark import check

    line = run_tiny("tiny-campaign", kind="campaign", control=True,
                    explain_weights="int8")
    assert list(line)[-1] == "compared"
    numbers, control = line["info"]["numbers"], line["control"]
    for name in ("confidence_gap", "label_mismatch"):
        assert control["compared"][name][0] == numbers["control_" + name]
        assert control["compared"][name][1] == line["compared"][name][1]
    # The int8 program serves other tokens than float32 arithmetic puts
    # first (the sound run of this size reads exactly 0.0, above), so at a
    # limit fit for this size the control is not correct; the cell's own
    # limit and the readings it stands between are the chip's (PERF.md).
    served = line["compared"]["token_gap_sq"][0]
    assert served > 0 and control["compared"]["token_gap_sq"][0] == served
    tight = dict(check.LIMITS, token_gap_sq=1e-12)
    assert check.control_verdict(
        {**{k: v[0] for k, v in line["compared"].items()}, **numbers},
        tight)["correct"] is False


def test_no_tpu_no_result():
    """On a machine without a TPU the command prints no result and fails."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = "desk-lr-internlm2-1.8b.campaign"
    got = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert got.returncode != 0 and got.stdout.strip() == ""
    assert "TPU" in got.stderr
