"""Admission sized by what a boundary frees, on a lane whose rows take a
window count that does not divide the slots' turnover: generation by
diffusion over blocks (the tiny ``sdar_moe`` configuration of
``tests/benchmark/fixtures``, read as it is). A block of 4 takes 3 forwards,
so a row's 8 tokens end mid-window and a 4-slot lane frees 1 to 4 slots a
boundary. Under a standing queue every boundary fills every slot it freed,
more than 2 rows a window on average, and a row serves the same tokens
whether it was admitted in a cohort or alone."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

pytestmark = pytest.mark.slotserve

SEED = 2**31 + 5
SLOTS, MAX_NEW, WINDOW, ROWS = 4, 8, 5, 16


def language_model():
    import jax.numpy as jnp

    from benchmark import run

    fam = run._load_file(os.path.join(REPO, "benchmark", "explainers",
                                      "sdar_moe.py"), "bench_explainer_sdar_moe")
    with open(os.path.join(REPO, "tests", "benchmark", "fixtures", "configs",
                           "tiny-desk-sdar.json")) as f:
        cfg = json.load(f)
    return fam.build(cfg, fam.make_params(SEED, cfg, jnp.float32), "float32")


def prompts():
    from fraud_detection_tpu.explain.slotserve.service import (
        shared_explain_prefix)

    return [shared_explain_prefix() + "Caller: read me the one-time code"
            + " now." * (3 * i) + f" Customer {i} hesitates: {'no' * i}"
            for i in range(ROWS)]


@pytest.fixture(scope="module")
def served():
    """The rows queued at once (``cohort``: the lane's condition held while
    they are submitted, so its first boundary sees them all) and the same
    rows one at a time into an idle lane (``alone``): per run the served
    tokens, the snapshot and every ``slot_admit`` span's (rows, free,
    queued)."""
    from fraud_detection_tpu.explain.slotserve.service import SlotServeService
    from fraud_detection_tpu.obs.trace import RowTracer

    lm = language_model()
    out = {}
    for how in ("cohort", "alone"):
        svc = SlotServeService(lm, slots=SLOTS, max_new_tokens=MAX_NEW,
                               prompt_width=832, decode_window=WINDOW,
                               wait_timeout=600.0)
        tr = RowTracer(worker="w0", sample=1.0, seed=0, capacity=1 << 14)
        svc.set_rowtrace(tr)
        try:
            if how == "cohort":
                with svc._cv:
                    reqs = [svc.submit(p, temperature=0.0) for p in prompts()]
                for r in reqs:
                    r.wait(600.0)
            else:
                reqs = []
                for p in prompts():
                    reqs.append(svc.submit(p, temperature=0.0))
                    reqs[-1].wait(600.0)
            snap = svc.snapshot()
        finally:
            assert svc.close()
        rounds = []
        for s in tr.ring.snapshot():
            if s.stage == "slot_admit":
                fields = dict(kv.split("=") for kv in s.detail.split())
                rounds.append(tuple(int(fields[k])
                                    for k in ("rows", "free", "queued")))
        out[how] = {"served": [np.asarray(r.out).tolist() for r in reqs],
                    "snapshot": snap, "rounds": rounds}
    return out


def test_every_window_begins_with_every_slot_busy(served):
    """While at least as many rows queue as slots are free, the boundary
    fills every free slot: each window under the standing queue starts
    with all 4 slots busy. The lane slides: boundaries free 1 to 4 slots,
    not a fixed count."""
    run = served["cohort"]
    rounds, snap = run["rounds"], run["snapshot"]
    assert rounds[0] == (SLOTS, SLOTS, ROWS)
    standing = [r for r in rounds if r[2] >= r[1]]
    assert len(standing) >= 5
    for rows, free, queued in standing:
        assert rows == free
    assert len({free for _, free, _ in standing[1:]}) > 1
    assert snap["admit_gated"] == 0
    assert snap["prefills"] == sum(r[0] for r in rounds) == ROWS
    assert snap["admit_rounds"] == sum(r[0] > 0 for r in rounds)
    assert snap["diffusion_tokens_committed"] > snap["diffusion_row_forwards"]


def test_more_than_two_rows_a_window_under_the_standing_queue(served):
    """Rows of 8 tokens in blocks of 4 at 3 forwards a block, windows of 5:
    more than 2 slots free a boundary on average, and the lane admits every
    one, where a cap of 2 would have admitted 2 a window at most."""
    standing = [r for r in served["cohort"]["rounds"] if r[2] >= r[1]]
    assert sum(rows for rows, _, _ in standing) > 2 * len(standing)


def test_a_row_serves_the_same_tokens_in_a_cohort_or_alone(served):
    """Greedy, same weights and seed: what a row serves does not depend on
    which rows were prefilled beside it or which slot it took."""
    cohort, alone = served["cohort"], served["alone"]
    assert cohort["served"] == alone["served"]
    assert all(len(s) == MAX_NEW for s in cohort["served"])
    assert len({tuple(s) for s in cohort["served"]}) > 1
    assert all(r[0] <= 1 for r in alone["rounds"])
    assert alone["snapshot"]["admit_rounds"] == ROWS
