"""The per-layer metrics of set-up: the six readers over a synthetic ring,
the six entries of ``per_layer`` they are written for, and one traced tiny
run that prints them from the program's own ``setup_*`` and ``compile``
spans.

``BENCHMARK.json`` does not list the six yet: ``tests/benchmark/test_lfm2.py``
pins the list's last entry, so they cannot be appended, and an entry put
ahead of it reads to the driver as an edit of the entries that were there,
which only a ``benchmark`` PR may make (PERF.md section 7). ``ENTRIES`` is
what that PR inserts; until then, and after, the tests here lay whichever
of them the file lacks over the spec, ahead of its last entry."""

import copy
import math

import pytest

from benchmark import run

OPEN, CLOSE = 1000.0, 1045.0

INTERNLM2 = ["desk-lr-internlm2-1.8b.campaign",
             "desk-lr-internlm2-1.8b.stream-quiet",
             "desk-lr-internlm2-1.8b.steady"]
HYBRID = "desk-lr-ling-3.0-flash.campaign-1.35x"
SETUP = ("setup.program_load_s", "setup.programs_loaded", "setup.compiled_s",
         "setup.train_s", "setup.service_s")
WINDOW = "jit.compiles_in_window"


def _entry(name, unit, moves, cells):
    return {"name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": "set-up", "moves": moves,
            "workloads": list(cells)}


ENTRIES = [_entry(n, "programs" if n == "setup.programs_loaded" else "s",
                  "setup_s", INTERNLM2 + [HYBRID]) for n in SETUP]
ENTRIES.append(_entry(WINDOW, "programs", "explanations_per_s",
                      [INTERNLM2[0], HYBRID]))


def _listed(spec):
    """``spec`` with the entries it lacks inserted ahead of its last."""
    have = {m["name"] for m in spec["per_layer"]}
    out = copy.deepcopy(spec)
    out["per_layer"][-1:-1] = [e for e in ENTRIES if e["name"] not in have]
    return out


def _span(stage, start, ms, detail=None, cid="setup"):
    return {"cid": cid, "stage": stage, "start": start, "duration_ms": ms,
            "ok": True, "detail": detail}


def _compile(n, start, ms, hit, fn="jit(f)"):
    return _span("compile", start, ms, cid=f"compile-{n}",
                 detail=f"fn={fn} hit={hit} fetch_ms={ms / 2 * hit:.3f}")


def _ctx(spans):
    return {"rowtrace": spans, "window": (OPEN, CLOSE)}


def _read(spec, name, spans):
    return run.load_reader(spec, name)(_ctx(spans))


# A process's set-up (two fits, a pipeline, a service that was rebuilt),
# its executables, and what the window and the time after it held.
RING = [
    _compile(1, 900.0, 1500.0, 0, "jit(fit)"),
    _span("setup_train", 899.0, 4000.0, "family=lr rows=1120"),
    _span("setup_train", 904.0, 2500.0, "family=xgb rows=1120"),
    _span("setup_pipeline", 907.0, 30.0, "family=LogisticRegression"),
    _compile(2, 910.0, 4000.0, 1, "jit(paged_slot_prefill)"),
    _span("setup_preamble", 909.9, 4100.0, "tokens=293"),
    _compile(3, 915.0, 250.0, 0, "jit(paged_decode_window)"),
    _span("setup_warm", 914.5, 900.0, "steps=16"),
    _span("setup_service", 909.0, 7000.0, "slots=16 pages=544"),
    _span("setup_service", 930.0, 9000.0, "slots=16 pages=544"),
    _compile(4, 950.0, 3000.0, 1),
    _span("poll", 990.0, 1.0, "rows=8", cid="desk-1"),
    _compile(5, OPEN, 700.0, 0),                 # the open itself: inside
    _compile(6, 1020.0, 40.0, 1),
    _span("slot_iter", 1021.0, 200.0, cid="slot-9"),
    _compile(7, CLOSE, 60.0, 0),                 # the close itself: after
    _span("setup_train", 1050.0, 1000.0, "family=lr rows=8"),
]


@pytest.mark.parametrize("name,want", [
    ("setup.program_load_s", 1.5 + 4.0 + 0.25 + 3.0),
    ("setup.programs_loaded", 4),
    ("setup.compiled_s", 1.5 + 0.25),
    ("setup.train_s", 4.0 + 2.5),
    ("setup.service_s", 9.0),
    (WINDOW, 2),
])
def test_the_readers_take_spans_by_their_clock(spec, name, want):
    assert _read(spec, name, RING) == pytest.approx(want)
    assert _read(spec, name, RING[::-1]) == pytest.approx(want)   # not by order


def test_a_warm_start_reads_zero_compiled_not_nothing(spec):
    warm = [_compile(1, 900.0, 4000.0, 1), _compile(2, 905.0, 300.0, 1)]
    assert _read(spec, "setup.compiled_s", warm) == 0.0
    assert _read(spec, "setup.program_load_s", warm) == pytest.approx(4.3)
    assert _read(spec, WINDOW, warm) == 0


@pytest.mark.parametrize("name", SETUP + (WINDOW,))
def test_a_program_that_writes_no_such_span_reads_nothing(spec, name):
    """The parent commit's ring: batch and slot spans only."""
    ring = [s for s in RING
            if s["stage"] not in ("compile",) and "setup" not in s["stage"]]
    assert ring and _read(spec, name, ring) is None
    assert _read(spec, name, []) is None


def test_phases_without_a_compile_span_still_count_the_window(spec):
    ring = [s for s in RING if s["stage"] != "compile"]
    for name in ("setup.program_load_s", "setup.programs_loaded",
                 "setup.compiled_s"):
        assert _read(spec, name, ring) is None
    assert _read(spec, "setup.train_s", ring) == pytest.approx(6.5)
    assert _read(spec, WINDOW, ring) == 0        # it writes them; none began


def test_the_entries_name_their_cells_and_what_they_move(spec):
    listed = _listed(spec)
    by = {m["name"]: m for m in listed["per_layer"]}
    for want in ENTRIES:
        assert by[want["name"]] == want          # as proposed, once listed
    for name in SETUP:
        m = by[name]
        assert m["workloads"] == INTERNLM2 + [HYBRID]
        assert (m["moves"], m["layer"], m["source"], m["better"]) == (
            "setup_s", "set-up", "program_span", "lower")
    w = by[WINDOW]
    assert w["workloads"] == [INTERNLM2[0], HYBRID]
    assert (w["moves"], w["unit"]) == ("explanations_per_s", "programs")
    # every listed cell reports the end-to-end metric its entry moves, and
    # run.py hands the cell the entry
    for m in [by[n] for n in SETUP + (WINDOW,)]:
        for cell in m["workloads"]:
            e2e, layer = run.cell_metrics(listed, cell)
            assert m["moves"] in {e["name"] for e in e2e}
            assert m in layer
    # the routed cells whose tests pin their sets of metrics list none
    for cell in ("desk-lr-longcat-flash-chat.campaign-1.35x-longcat",
                 "desk-lr-lfm2-24b-a2b.campaign-1.35x-lfm2"):
        assert not [n for n in SETUP + (WINDOW,) if cell in by[n]["workloads"]]
    # inserted, not appended: what the file ends with it still ends with,
    # every entry it had is there in its order, and no name comes twice
    names = [m["name"] for m in listed["per_layer"]]
    assert len(names) == len(set(names))
    assert listed["per_layer"][-1] == spec["per_layer"][-1]
    assert [m for m in listed["per_layer"]
            if m["name"] not in SETUP + (WINDOW,)] == [
                m for m in spec["per_layer"]
                if m["name"] not in SETUP + (WINDOW,)]
    assert {k: v for k, v in listed.items() if k != "per_layer"} == {
        k: v for k, v in spec.items() if k != "per_layer"}


def test_a_traced_tiny_campaign_prints_them(spec, run_tiny, boot_log,
                                            monkeypatch):
    """The process's own set-up, from an empty boot log and with nothing
    compiled in memory: the fit, the service and the executables of this
    run are what the line reads."""
    import jax

    monkeypatch.setitem(spec, "per_layer", _listed(spec)["per_layer"])
    jax.clear_caches()
    line = run_tiny("tiny-campaign", kind="campaign", trace=True, seconds=3.0)
    assert line["correct"] is True
    got = {n: line["metrics"][n]["value"] for n in SETUP + (WINDOW,)}
    assert all(math.isfinite(v) for v in got.values())
    assert all(got[n] > 0 for n in SETUP), got
    assert got[WINDOW] >= 0
    assert got["setup.compiled_s"] <= got["setup.program_load_s"]
    assert got["setup.programs_loaded"] == int(got["setup.programs_loaded"])
    assert line["metrics"]["setup.programs_loaded"]["unit"] == "programs"
