"""The per-layer metrics that read the program's own spans and counters
(ISSUE 26): each reader on a hand-built ``ctx``, silent (None, never 0)
against a program that writes no such span, and all nine present in the tiny
traced cells."""

import numpy as np
import pytest

from benchmark import run

WINDOW = (1000.0, 1045.0)


def span(cid, stage, start, ms, detail=None, ok=True):
    return {"cid": cid, "stage": stage, "start": start, "duration_ms": ms,
            "ok": ok, "detail": detail}


def hand_ctx():
    """Two stream batches, two explained rows and two slot-loop iterations
    inside the window, one of each before it."""
    rows = [
        # the stream path: 80 and 40 real rows padded to 4,096
        span("desk-1", "poll", 1001.0, 8.0, "rows=80"),
        span("desk-1", "launch", 1001.01, 3.0),
        span("desk-1", "featurize", 1001.01, 0.8, "rows=80"),
        span("desk-1", "upload", 1001.011, 1.5,
             "rows=80 padded=4096 bytes=1048576"),
        span("desk-2", "poll", 1002.0, 12.0, "rows=40"),
        span("desk-2", "featurize", 1002.01, 0.4, "rows=40"),
        span("desk-2", "upload", 1002.011, 2.5,
             "rows=40 padded=4096 bytes=1048576"),
        span("desk-0", "poll", 990.0, 500.0, "rows=9"),
        span("desk-0", "featurize", 990.0, 90.0, "rows=9"),
        span("desk-0", "upload", 990.0, 90.0, "rows=9 padded=16 bytes=64"),
        # the explain path: rows a and b, b's explain END-stamped at 1031
        span("desk-1:0:5", "lane_wait", 1003.0, 10_000.0),
        span("desk-1:0:5", "slot_wait", 1013.0, 3_000.0, "slot=1"),
        span("desk-1:0:5", "explain", 1029.0, 16_000.0, "slot=1 tokens=128"),
        span("desk-1:0:5", "annotate", 1032.0, 0.0),
        span("desk-1:0:9", "lane_wait", 1003.0, 12_000.0),
        span("desk-1:0:9", "slot_wait", 1015.0, 5_000.0, "slot=0"),
        span("desk-1:0:9", "explain", 1031.0, 16_000.0, "slot=0 tokens=128"),
        span("desk-1:0:9", "annotate", 1032.0, 0.0),
        span("lane", "explain", 1013.0, 19_000.0, "rows=2"),
        span("desk-0:0:1", "lane_wait", 980.0, 99_000.0),
        span("desk-0:0:1", "slot_wait", 981.0, 99_000.0, "slot=0"),
        # the slot loop: 600 ms iterations of which fetch + prefill wait
        # on the device for 570 and 580
        span("slot-a", "slot_iter", 1010.0, 600.0),
        span("slot-a", "slot_fetch", 1010.1, 520.0),
        span("desk-1:0:5", "prefill", 1010.01, 50.0),
        span("slot-b", "slot_iter", 1010.6, 600.0),
        span("slot-b", "slot_fetch", 1010.65, 530.0),
        span("desk-1:0:9", "prefill", 1010.61, 50.0),
        span("slot-9", "slot_iter", 999.0, 900.0),
        span("slot-9", "slot_fetch", 999.1, 100.0),
    ]
    marks = {
        "open": {"slots": 16, "decode_steps": 1_000, "occupancy": 0.5,
                 "slot_steps_occupied": 8_000, "slot_steps_starved": 6_000,
                 "slot_steps_backlogged": 2_000},
        "close": {"slots": 16, "decode_steps": 2_000, "occupancy": 0.6,
                  "slot_steps_occupied": 19_200, "slot_steps_starved": 8_400,
                  "slot_steps_backlogged": 4_400},
    }
    return {"window": WINDOW, "rowtrace": rows, "marks": marks}


WANT = {
    "lane.wait_p50_ms": 11_000.0,
    "lane.tail_p50_ms": 2_000.0,                  # (3,000 + 1,000) / 2
    "slot.queue_wait_p50_ms": 4_000.0,
    "slot.starved_pct": 100.0 * 2_400 / 16_000,
    "slot.host_ms_per_window": 25.0,              # (30 + 20) / 2
    "engine.queue_wait_ms": 10.0,
    "engine.upload_ms": 2.0,
    "featurize.span_us_per_row": 1e3 * 1.2 / 120,
    "score.padding_pct": 100.0 * (1 - 120 / 8_192),
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_built_ctx(spec, name):
    read = run.load_reader(spec, name)
    assert read(hand_ctx()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_is_silent_against_the_parent_program(spec, name):
    """A program without this PR's spans and counters: ``poll`` never fed
    (0 ms), ``explain`` and ``annotate`` as they were. Only the tail, which
    needs no new span, has something to read."""
    ctx = hand_ctx()
    old = {"poll", "launch", "explain", "annotate"}
    ctx["rowtrace"] = [dict(s, duration_ms=0.0) if s["stage"] == "poll" else s
                       for s in ctx["rowtrace"] if s["stage"] in old]
    ctx["marks"] = {k: {f: v for f, v in m.items()
                        if not f.startswith("slot_steps_")}
                    for k, m in ctx["marks"].items()}
    got = run.load_reader(spec, name)(ctx)
    assert got == (pytest.approx(2_000.0) if name == "lane.tail_p50_ms"
                   else None)


def test_the_new_entries_are_appended_and_name_their_cells(spec):
    """Each entry is present, with these cells among its ``workloads``: by
    name, never by position, so later entries and cells may follow."""
    names = [m["name"] for m in spec["per_layer"]]
    assert set(WANT) <= set(names)
    by = {m["name"]: m for m in spec["per_layer"]}
    cell = "desk-lr-internlm2-1.8b."
    for name in WANT:
        m = by[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["better"] == "lower"
        cells = set(m["workloads"])
        e2e = {e["name"]: e for e in spec["end_to_end"]}[m["moves"]]
        assert cells <= set(e2e.get("workloads", m["workloads"]))
        if name.startswith(("engine.", "featurize.", "score.")):
            assert cell + "stream-quiet" in cells
        elif m["moves"] == "explanations_per_s":
            assert {cell + "campaign", cell + "steady"} <= cells
        else:
            assert cell + "steady" in cells
    assert {by[n]["layer"] for n in ("lane.wait_p50_ms",
                                     "lane.tail_p50_ms")} == {"annotation lane"}


@pytest.mark.parametrize("mix,kind", [("tiny-steady", "steady"),
                                      ("tiny-stream", "stream-quiet"),
                                      ("tiny-campaign", "campaign")])
def test_traced_tiny_cells_report_the_new_metrics(run_tiny, spec, mix, kind):
    line = run_tiny(mix, kind=kind, trace=True, seconds=3.0)
    assert line["correct"] is True
    cell = next(w["name"] for w in spec["workloads"] if w["traffic"] == kind)
    want = {m["name"] for m in spec["per_layer"]
            if m["name"] in WANT and cell in m["workloads"]}
    assert want and want <= set(line["metrics"])
    for name in want:       # present and finite: 0 is slot.starved_pct's best
        value = line["metrics"][name]["value"]
        assert np.isfinite(value), (name, value)
    if kind != "stream-quiet":
        # occupancy + starved + backlogged = 100 (slot.occupancy is read
        # from a mean the program rounds to four places)
        assert (line["metrics"]["slot.occupancy"]["value"]
                + line["metrics"]["slot.starved_pct"]["value"]) <= 100.1
    else:
        assert line["metrics"]["score.padding_pct"]["value"] < 100.0
        # the program's featurize span and the benchmark's own wrapper
        # time the same call
        assert line["metrics"]["featurize.span_us_per_row"]["value"] == \
            pytest.approx(line["metrics"]["featurize.us_per_row"]["value"],
                          rel=0.25)
