"""The generator: equal work across seeds, due stamps on the tick grid,
lateness, rates taken from the configuration, texts the classifier agrees
with."""

import glob
import json
import os

import numpy as np
import pytest

from conftest import FIXTURES, REPO

from benchmark import traffic

MIXES = sorted(glob.glob(os.path.join(REPO, "benchmark", "traffic", "*.json")))
CFG = {"desk": {"sustained_rows_per_s": 2000}}


@pytest.mark.parametrize("path", MIXES, ids=[os.path.basename(p) for p in MIXES])
def test_every_seed_carries_equal_work(path):
    mix = traffic.load_mix(path)
    a = traffic.build_plan(mix, 10, cfg=CFG)
    b = traffic.build_plan(json.loads(json.dumps(mix)), 10, cfg=CFG)
    assert np.array_equal(a.due_s, b.due_s)                  # same arrivals
    assert np.array_equal(a.pool_len, b.pool_len)            # same lengths, same order
    assert np.array_equal(a.pool_of_row, b.pool_of_row)
    longer = traffic.build_plan(mix, 20, cfg=CFG)            # a prefix of a longer run
    assert np.array_equal(longer.due_s[:len(a.due_s)], a.due_s)
    ticks = a.due_s / a.tick_s
    assert np.allclose(ticks, np.round(ticks))               # on the grid
    assert np.all(np.diff(a.due_s) >= 0)
    assert a.in_window.sum() > 0 and a.open_s == mix["preroll_s"]


def test_campaign_front_and_length_classes():
    mix = traffic.load_mix(os.path.join(REPO, "benchmark", "traffic", "campaign.json"))
    plan = traffic.build_plan(mix, 45)
    assert (plan.due_s < 1.0 - 1e-9).sum() == 192            # the front
    assert len(plan.due_s) == 192 + int(64 * 3.07)
    assert plan.scam.all()
    lengths = set(plan.pool_len.tolist())
    assert lengths == {int(k) for k in mix["text"]["length_bytes"]}
    assert len(lengths) <= 8 and max(lengths) + 935 <= 2048  # fits the prompt width


def test_rate_from_the_configuration():
    mix = traffic.load_mix(os.path.join(REPO, "benchmark", "traffic", "stream-quiet.json"))
    slow = traffic.build_plan(mix, 2, cfg={"desk": {"sustained_rows_per_s": 1000}})
    fast = traffic.build_plan(mix, 2, cfg={"desk": {"sustained_rows_per_s": 4000}})
    assert len(fast.due_s) == 4 * len(slow.due_s)
    assert len(slow.due_s) == int((mix["preroll_s"] + 2) * 550)


def test_texts_follow_the_classifier_and_the_lengths():
    mix = traffic.load_mix(os.path.join(FIXTURES, "traffic", "tiny-campaign.json"))
    mix = dict(mix, scam_share=0.5)
    plan = traffic.build_plan(mix, 4)
    calls = []

    def flags(texts):                       # flags about half the candidates
        calls.append(len(texts))
        return [sum(map(ord, t)) % 2 == 0 for t in texts]

    texts = traffic.build_texts(plan, 9, flags)
    assert len(calls) > 2                   # redrawn until the verdicts fit
    assert [len(t) for t in texts] == plan.pool_len.tolist()
    assert texts == traffic.build_texts(plan, 9, flags)      # from the seed
    assert texts != traffic.build_texts(plan, 10, flags)


def test_feeder_paces_and_reports_lateness():
    mix = traffic.load_mix(os.path.join(FIXTURES, "traffic", "tiny-stream.json"))
    plan = traffic.build_plan(mix, 1, cfg={"desk": {"sustained_rows_per_s": 800}})
    now = [100.0]
    sent = []

    class Producer:
        def produce_batch(self, topic, items):
            now[0] += 0.002                  # each burst costs 2 ms
            sent.extend(items)

    def sleep(dt):
        now[0] += dt + 0.001                 # and every sleep overshoots 1 ms

    feeder = traffic.Feeder(plan, [b"p%d" % i for i in range(64)], Producer(),
                            "in", clock=lambda: now[0], sleep=sleep)
    feeder.run(t0=100.0)
    assert [k for _, k in sent] == [traffic.row_key(i) for i in range(len(plan.due_s))]
    assert sent[0][0] == b"p%d" % plan.pool_of_row[0]
    late = feeder.late_ms()
    assert np.all(late >= 2.0 - 1e-6) and np.all(late < 10.0)
    assert len(feeder.late_ms(plan.in_window)) == plan.in_window.sum()
    assert json.loads(traffic.payload('a "b"\n'))["text"] == 'a "b"\n'
