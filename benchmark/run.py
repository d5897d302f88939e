#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on: builds
the desk from the cell's configuration file (benchmark/desk.py), warms every
shape, paces the cell's traffic mix into it (benchmark/traffic.py) through a
pre-roll and a measured window, computes the end-to-end metrics from its own
due stamps and the broker's delivery stamps, compares what the timed path
produced with the plain references (benchmark/check.py) and prints one JSON
object as its last line. ``--trace 1`` profiles a slice of the window and
reports the per-layer metrics instead, each read by its own file under
benchmark/metrics/.

Exits non-zero, printing no result, without a TPU or with fewer chips than
the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.metrics._lib import percentile  # noqa: E402

MISSING_MS = 1e9          # a row that never came: late beyond any limit
TRACE_SECONDS = 3.0       # profiled slice of the window (--trace 1)
TRACE_STOP_WAIT_S = 200.0  # the profiler's stop has taken up to 110 s
CHECK_FRAMES = 256        # output frames compared with the reference
CHECK_REQUESTS = 16       # explained requests compared (128 tokens each)


def log(msg: str) -> None:
    print(f"[bench {time.time() - T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------

def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: dict, name: str, root: str = ROOT):
    """(cell, config dict, mix dict) of a workload, each from its own file."""
    from benchmark import desk, traffic

    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = desk.load_config(os.path.join(root, entry["file"]))
    mix_path = next((os.path.join(root, p, "traffic", cell["traffic"] + ".json")
                     for p in spec["paths"] if os.path.exists(os.path.join(
                         root, p, "traffic", cell["traffic"] + ".json"))), None)
    if mix_path is None:
        raise SystemExit(f"no traffic file for mix {cell['traffic']!r}")
    return cell, cfg, traffic.load_mix(mix_path)


def cell_metrics(spec: dict, cell: str):
    """(end-to-end names, per-layer entries) this cell reports."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def _load_file(path: str, module_name: str):
    mod_spec = importlib.util.spec_from_file_location(
        module_name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(spec: dict, name: str, root: str = ROOT):
    """``read`` of ``metrics/<name>.py``. A quantity split by the end-to-end
    metric it moves (``gen.late_p99_ms.stream``, ``gen.late_p99_ms.explain``)
    may share one reader named without the last part (``gen.late_p99_ms.py``)."""
    for stem in (name, name.rpartition(".")[0]):
        for p in spec["paths"]:
            path = os.path.join(root, p, "metrics", stem + ".py")
            if stem and os.path.exists(path):
                return _load_file(path, "bench_metric_" + stem).read
    raise SystemExit(f"no reader file metrics/{name}.py under {spec['paths']}")


FAMILY_FUNCTIONS = ("build", "make_params", "token_gaps", "decode_cost",
                    "prefill_cost", "param_count")


def load_family(spec: dict, cfg: dict, root: str = ROOT):
    """The explainer family a configuration names: the module
    ``explainers/<model_type>.py`` under the first directory of ``paths``
    that holds one. It brings the model the desk serves, its weights from
    the seed, its plain reference and its counts (the contract is the
    header of ``benchmark/explainers/internlm2.py``)."""
    model_type = cfg.get("model_type")
    dirs = [os.path.join(p, "explainers") for p in spec["paths"]]
    for d in dirs:
        path = os.path.join(root, d, f"{model_type}.py")
        if model_type and os.path.exists(path):
            family = _load_file(path, f"bench_explainer_{model_type}")
            missing = [f for f in FAMILY_FUNCTIONS if not hasattr(family, f)]
            if missing:
                raise SystemExit(f"explainer family file {path} lacks {missing}")
            return family
    raise SystemExit(f"no explainer family file for model_type {model_type!r}: "
                     f"searched {dirs} for {model_type}.py")


# ---------------------------------------------------------------------------
# end-to-end arithmetic (the benchmark's own stamps and the broker's)
# ---------------------------------------------------------------------------

def latencies_ms(due_abs: np.ndarray, rows: np.ndarray, keys, stamps) -> np.ndarray:
    """Due -> first delivery stamp of each of ``rows``; MISSING_MS where no
    record carries the row's key."""
    first = np.full(len(due_abs), np.inf)
    idx = np.asarray(keys, np.int64)
    np.minimum.at(first, idx, np.asarray(stamps, np.float64))
    lat = (first[rows] - due_abs[rows]) * 1e3
    return np.where(np.isfinite(lat), lat, MISSING_MS)


def end_to_end(plan, t0: float, frames, notes, explained: float,
               seconds: float) -> dict:
    """Every end-to-end number the harness knows, over all the work and all
    the time of the window. ``frames``/``notes``: (int keys, stamps) of the
    output and annotation topics; ``explained``: explanations produced
    inside the window (``explained_in_window``)."""
    lo, hi = t0 + plan.open_s, t0 + plan.close_s
    due_abs = t0 + plan.due_s
    win = np.flatnonzero(plan.in_window)
    out = {}
    f_keys, f_stamps = frames
    inside = (f_stamps >= lo) & (f_stamps < hi)
    out["dialogues_per_s"] = float(np.sum(inside)) / seconds
    if len(win):
        out["row_latency_p95_ms"] = percentile(
            latencies_ms(due_abs, win, f_keys, f_stamps), 0.95)
    n_keys, n_stamps = notes
    out["explanations_per_s"] = float(explained) / seconds
    flagged = win[plan.scam[win]]
    if len(flagged):
        out["explain_latency_p90_ms"] = percentile(
            latencies_ms(due_abs, flagged, n_keys, n_stamps), 0.90)
    return out


def explained_in_window(tickets, delivered, at_open, at_close,
                        max_new: int) -> float:
    """Explanations of the window: each request whose annotation was
    delivered (``delivered[i]``: a real record with the served text under
    its row's key on the annotations topic, by the end of the run) counts by
    the share of its own tokens that it emitted between the window's open
    and close (``at_open``/``at_close``: tokens per ticket at those two
    moments), so one that straddles an edge counts in part and every other
    one counts 1. The completions of the window, without the steps of a
    whole-request count (~1 % each at 100 requests a window). A request that
    decodes and is never delivered counts nothing."""
    if at_open is None or at_close is None:
        return 0.0
    total = 0.0
    for i, req in enumerate(tickets):
        if req.error is not None or i >= len(at_close) or not delivered[i]:
            continue
        if req.dropped is not None and req.dropped != "closed":
            continue
        final = len(req.out) if req.text is not None else max_new
        before = int(at_open[i]) if i < len(at_open) else 0
        total += (int(at_close[i]) - before) / max(1, final)
    return total


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Marks(threading.Thread):
    """At fixed times of the run, off the feeder's thread: slot-lane
    snapshots, and the profiler's start and stop. The stop runs on a thread
    of its own (``stopper``): over a slice of many device events it takes
    minutes, and the marks after it are still taken on time."""

    def __init__(self, desk, t0: float, schedule, cap=None):
        super().__init__(name="bench-marks", daemon=True)
        self.desk, self.t0, self.cap = desk, t0, cap
        self.schedule = sorted(schedule)
        self.marks, self.stamps, self.emitted = {}, {}, {}
        self.stopper = None

    def run(self) -> None:
        for at, label in self.schedule:
            wait = self.t0 + at - time.time()
            if wait > 0:
                time.sleep(wait)
            if label == "trace_start" and self.cap is not None:
                self.cap.start()
            self.stamps[label] = time.time()
            self.marks[label] = self.desk.svc.snapshot()
            self.emitted[label] = self.desk.emitted()
            if label == "trace_stop" and self.cap is not None:
                self.stopper = Stopper(self.cap)
                self.stopper.start()


class Stopper(threading.Thread):
    """``cap.stop()``, timed (``seconds``)."""

    def __init__(self, cap):
        super().__init__(name="bench-trace-stop", daemon=True)
        self.cap, self.seconds = cap, None

    def run(self) -> None:
        began = time.time()
        self.cap.stop()
        self.seconds = time.time() - began


def stopped_trace(stopper, timeout: float = TRACE_STOP_WAIT_S):
    """The capture's file once the profiler's stop has returned; None,
    logged, where it has not within ``timeout`` seconds or never began."""
    if stopper is None:
        log("the profiler was never stopped; the line has no device trace")
        return None
    stopper.join(timeout=timeout)
    if stopper.is_alive():
        log(f"the profiler had not stopped {timeout:g} s after the run began "
            f"waiting for it; the line has no device trace")
        return None
    log(f"the profiler's stop took {stopper.seconds:.1f} s")
    return stopper.cap.path


def _settled(desk_mod, desk, plan, follow: str) -> bool:
    win = np.flatnonzero(plan.in_window)
    if follow == "none" or not len(win):
        return True
    last = int(win[-1])
    if desk.broker.topic_size(desk_mod.OUT_TOPIC) <= last:
        return False
    if follow == "annotations":
        want = int(np.sum(plan.scam[:last + 1]))
        return desk.broker.topic_size(desk_mod.NOTES_TOPIC) >= want
    return True


def run_cell(spec: dict, cell: dict, cfg: dict, mix: dict, *, seed: int,
             seconds: float, trace: bool, t_start: float = T_START,
             root: str = ROOT, scratch: str = None, fault=None,
             device_kind: str = None, control: bool = False) -> dict:
    """Everything after the look for a chip. ``fault(desk)`` lets a test
    break the timed path underneath before the traffic starts, and
    ``device_kind`` lets it name a chip for the readers' arithmetic.
    ``control`` (benchmark/control.py) judges the lower precision too: the
    classifier's reference in bfloat16 put in the program's place, beside
    whatever lower precision ``cfg`` has the explainer's family serve."""
    import jax

    from benchmark import (check, corpus, desk as desk_mod, reference,
                           trace_reduce, traffic)

    e2e_spec, layer_spec = cell_metrics(spec, cell["name"])
    family = load_family(spec, cfg, root)
    limits = check.stated_limits(cfg)
    workdir = tempfile.mkdtemp(prefix="bench-", dir=scratch)
    cap = None
    try:
        # ---- set-up -----------------------------------------------------
        log(f"cell {cell['name']} seed {seed} seconds {seconds} trace {int(trace)}")
        desk = desk_mod.Desk(cfg, seed, workdir, family, traced=trace)
        log("desk built (classifier trained, explainer resident)")
        plan = traffic.build_plan(mix, seconds, cfg)
        texts = traffic.build_texts(plan, seed, desk.flags)
        payloads = [traffic.payload(t) for t in texts]
        scam_texts = [t for t, s in zip(texts, plan.pool_scam) if s]
        warmed = desk.warm(payloads, scam_texts)
        pairs = warmed.pop("pairs_per_row")
        log(f"warmed {warmed}; {len(plan.due_s)} rows planned, "
            f"{int(plan.in_window.sum())} due in the window")
        if fault is not None:
            fault(desk)
        compiles = {"n": 0}

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                compiles["n"] += 1

        jax.monitoring.register_event_listener(on_event)
        gc_was = gc.get_threshold()
        gc.collect()
        gc.freeze()
        # The in-process broker keeps every message of the run as a Python
        # object; full collections over them are a cost no deployment has.
        gc.set_threshold(700, 10, 1_000_000)
        desk.start()
        feeder = traffic.Feeder(plan, payloads, desk.broker.producer(),
                                desk_mod.IN_TOPIC)
        t0 = time.time() + 0.1
        setup_s = t0 + plan.open_s - t_start
        schedule = [(plan.open_s, "open"), (plan.close_s, "close")]
        if trace:
            cap = trace_reduce.capture(os.path.join(workdir, "trace"))
            t_lo = plan.open_s + min(1.0, 0.1 * seconds)
            schedule += [(t_lo, "trace_start"),
                         (min(plan.close_s, t_lo + TRACE_SECONDS), "trace_stop")]
        marks = Marks(desk, t0, schedule, cap)
        marks.start()
        # ---- pre-roll and window ----------------------------------------
        feeder.run(t0)
        at_close = compiles["n"]
        marks.join(timeout=60.0)
        stopper = marks.stopper
        deadline = time.time() + plan.settle_s
        while (not _settled(desk_mod, desk, plan, mix.get("follow", "frames"))
               and time.time() < deadline):
            time.sleep(0.05)
        log("window closed and settled; stopping the desk")
        end = desk.stop()
        peak = desk_mod.memory_peak_bytes()
        # ---- what was delivered -----------------------------------------
        f_keys, f_stamps, f_vals = desk_mod.messages(desk.broker, desk_mod.OUT_TOPIC)
        n_keys, n_stamps, n_vals = desk_mod.messages(desk.broker, desk_mod.NOTES_TOPIC)
        dlq = desk.broker.topic_size(desk_mod.DLQ_TOPIC)
        f_keys = np.asarray([int(k) for k in f_keys], np.int64)
        notes = [json.loads(v) for v in n_vals]
        real = np.asarray([check.is_explanation(n) for n in notes], bool)
        all_note_keys = np.asarray([int(k) for k in n_keys], np.int64)
        n_keys, n_stamps = all_note_keys[real], n_stamps[real]
        row_of = _rows_of_tickets(desk.requests, texts, plan)
        said = {int(k): n["analysis"] for k, n, r in
                zip(all_note_keys.tolist(), notes, real) if r}
        delivered = [row is not None and r.text is not None
                     and said.get(row) == r.text
                     for r, row in zip(desk.requests, row_of)]
        explained = explained_in_window(
            desk.requests, delivered, marks.emitted.get("open"),
            marks.emitted.get("close"),
            cfg["desk"]["explain"]["max_new_tokens"])
        values = end_to_end(plan, t0, (f_keys, f_stamps), (n_keys, n_stamps),
                            explained, seconds)
        values["setup_s"] = setup_s
        win = np.flatnonzero(plan.in_window)
        log(f"lane admitted {end['snapshot']['admitted']} rows; frames "
            f"{len(f_keys)}, notes {len(notes)} ({int(real.sum())} real), "
            f"dlq {dlq}; compiles inside the run {at_close}; "
            f"feeder late p99 {np.percentile(feeder.late_ms(plan.in_window), 99):.2f} ms")
        # ---- correct ----------------------------------------------------
        numbers = check.accounting_numbers(win.tolist(), f_keys.tolist(), dlq)
        numbers.update(check.lane_numbers(end, notes))
        tickets, finished = _tickets(desk)
        follows = mix.get("follow", "frames") == "annotations"
        numbers.update(check.notes_numbers(
            np.flatnonzero(plan.scam).tolist(),
            win[plan.scam[win]].tolist() if follows else [],
            all_note_keys.tolist(), real.tolist(),
            sum(1 for i, _, _ in finished if not delivered[i]),
            end["lane"] or {}))
        spec_c = cfg["desk"]["classifier"]
        art = reference.ClassifierArtifact(
            desk.checkpoint, reference.training_texts(
                [d.text for d in corpus.generate_corpus(
                    n=int(spec_c["train_rows"]), seed=int(seed) & 0x7FFFFFFF)],
                int(seed) & 0x7FFFFFFF, float(spec_c.get("train_fraction", 0.7))))
        numbers.update(art.featurizer_numbers())
        want = _pick_rows(plan, win, texts, art, seed)
        frame_of = {}
        for k, v in zip(f_keys.tolist(), f_vals):
            if k in want:
                frame_of.setdefault(k, v)
        sent = {k: texts[int(plan.pool_of_row[k])] for k in frame_of}
        numbers.update(check.classifier_numbers(art, frame_of, sent))
        ctx = None
        if trace:
            ctx = {"cfg": cfg, "family": family, "mix": mix,
                   "seconds": seconds,
                   "device_kind": device_kind or jax.devices()[0].device_kind,
                   "window": (t0 + plan.open_s, t0 + plan.close_s),
                   "trace_window": (marks.stamps.get("trace_start", 0.0),
                                    marks.stamps.get("trace_stop", 0.0)),
                   "late_ms": feeder.late_ms(plan.in_window),
                   "rowtrace": [s.as_dict() for s in desk.rowtrace.ring.snapshot()],
                   "spans": list(desk.spans), "marks": marks.marks,
                   "tickets": tickets, "pairs_per_row": pairs,
                   "prefix_len": desk.prefix_len,
                   "rows_delivered": int(np.sum(
                       (f_stamps >= t0 + plan.open_s) & (f_stamps < t0 + plan.close_s)))}
        picked = _pick_requests(finished, row_of, texts, plan, seed)
        pad_to = cfg["desk"]["explain"]["prompt_width"] + \
            cfg["desk"]["explain"]["max_new_tokens"]
        desk.release()
        del desk, feeder, marks
        gc.unfreeze()
        gc.set_threshold(*gc_was)
        gc.collect()
        if control:     # benchmark/control.py: the lower precision's readings
            numbers.update({"control_" + k: v for k, v in check.classifier_numbers(
                art, frame_of, sent, "bfloat16",
                reference_as_program=True).items()})
        if picked:
            t_ref = time.time()
            log(f"reference over {len(picked)} requests; device memory in "
                f"use {desk_mod.memory_in_use() / 1e9:.2f} GB")
            numbers.update(check.explainer_numbers(seed, cfg, picked, pad_to,
                                                   family.token_gaps))
            log(f"reference took {time.time() - t_ref:.1f} s")
        result = check.verdict(numbers, limits)
        # ---- the line ---------------------------------------------------
        device = desk_mod.device_stamp()
        device["memory_peak_bytes"] = peak
        units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
        metrics = {}
        breakdown = None
        if trace:
            reduced = None
            path = stopped_trace(stopper)
            if path:
                reduced = trace_reduce.reduce(trace_reduce.load(path),
                                              cap.window_s)
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                breakdown = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
            ctx["trace"] = reduced
            for m in layer_spec:
                value = load_reader(spec, m["name"], root)(ctx)
                if value is not None and value == value:
                    metrics[m["name"]] = {"value": float(value), "unit": units[m["name"]]}
        else:
            for m in e2e_spec:
                if m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": units[m["name"]]}
        failed = (int(numbers["rows_unaccounted"]) + int(numbers["bad_notes"])
                  + int(numbers["notes_unaccounted"]))
        line = {"correct": result["correct"], "attempted": int(len(win)),
                "failed": failed, "metrics": metrics, "device": device}
        if breakdown is not None:
            line["breakdown"] = breakdown
        line["info"] = {"cell": cell["name"], "seed": seed, "seconds": seconds,
                        "compiles_in_run": at_close, "warmed": warmed,
                        "lane_admitted": end["snapshot"]["admitted"],
                        "numbers": {k: v for k, v in numbers.items()
                                    if k not in check.LIMITS}}
        if control:
            line["control"] = check.control_verdict(numbers, limits)
        line["compared"] = result["compared"]
        return line
    finally:
        if cap is not None:
            cap.discard()
        shutil.rmtree(workdir, ignore_errors=True)


def _pick_rows(plan, win, texts, art, seed: int) -> set:
    """Rows whose frames are compared: half drawn from the seed, half the
    rows that carry the texts the reference is least sure of (confidence
    nearest 0.5) — a frame whose confidence is 1.000000 says nothing about
    the arithmetic behind it, and some seeds' classifiers are that sure of
    nearly every text."""
    from benchmark import check, reference

    drawn = {int(win[i]) for i in check.sample_indices(
        len(win), CHECK_FRAMES // 2, seed)}
    first_row = {}
    for row in win.tolist():
        first_row.setdefault(int(plan.pool_of_row[row]), row)
    pool = sorted(first_row)
    _, conf = reference.classifier_confidences(art, [texts[p] for p in pool])
    unsure = np.argsort(conf, kind="stable")[:CHECK_FRAMES - len(drawn)]
    return drawn | {first_row[pool[int(j)]] for j in unsure}


def _tickets(desk):
    """The slot tickets as plain dicts on the ``time.time()`` clock, and the
    finished ones with their tokens."""
    shift = time.time() - time.perf_counter()
    tickets, finished = [], []
    ended = {}
    if desk.rowtrace is not None:
        ended = {s.cid: s.start for s in desk.rowtrace.ring.snapshot()
                 if s.stage == "explain" and (s.detail or "").startswith("slot=")}
    for i, r in enumerate(desk.requests):
        done = r.done.is_set() and r.text is not None
        tickets.append({
            "prompt_len": int(len(r.tokens)), "n_out": int(len(r.out)),
            "submitted": r.submitted_at + shift,
            "first_token": (None if r.first_token_at is None
                            else r.first_token_at + shift),
            "done": ended.get(r.cid)})
        if done and r.dropped is None and r.error is None:
            finished.append((i, np.asarray(r.tokens), np.asarray(r.out)))
    return tickets, finished


def _rows_of_tickets(requests, texts, plan):
    """The row each slot ticket explains: the flagged row whose transcript
    its prompt carries (the k-th ticket that carries a text stands for the
    k-th row sent with it); None where it carries none that was sent."""
    rows_of_text = {}
    for row in np.flatnonzero(plan.scam).tolist():
        rows_of_text.setdefault(texts[int(plan.pool_of_row[row])], []).append(row)
    sent = sorted(((t.encode(), t) for t in rows_of_text),
                  key=lambda bt: -len(bt[0]))
    taken, out = {}, []
    for r in requests:
        body = bytes(int(t) for t in r.tokens if 0 <= int(t) < 256)
        text = next((t for b, t in sent if b in body), None)
        k = taken.get(text, 0)
        taken[text] = k + 1
        rows = rows_of_text.get(text, [])
        out.append(rows[k] if k < len(rows) else None)
    return out


def _pick_requests(finished, row_of, texts, plan, seed: int):
    """A sample of finished greedy requests drawn from the seed, the longest
    prompt among them, each with the transcript its row carried."""
    from benchmark import check

    if not finished:
        return []
    longest = max(range(len(finished)), key=lambda j: len(finished[j][1]))
    picked = check.sample_indices(len(finished), CHECK_REQUESTS, seed,
                                  always=[longest])
    out = []
    for j in picked:
        i, prompt, served = finished[j]
        row = row_of[i]
        out.append({"prompt": prompt, "served": served,
                    "text": (None if row is None
                             else texts[int(plan.pool_of_row[row])])})
    return out


# ---------------------------------------------------------------------------
# command line
# ---------------------------------------------------------------------------

def open_cell(workload: str):
    """(spec, cell, cfg, mix) of a workload once the machine is seen to
    hold the chips it asks for; exits with code 2, nothing run, otherwise."""
    # The compile cache lives at a fixed path inside the checkout unless the
    # machine names one; the program's own entry points read the same variable.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    spec = load_spec()
    cell, cfg, mix = find_cell(spec, workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX reports {len(devices)} x {devices[0].platform!r} "
              f"({devices[0].device_kind}); nothing was run", file=sys.stderr)
        raise SystemExit(2)
    from benchmark import counts

    counts.peaks(devices[0].device_kind)        # an unknown chip is an error
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return spec, cell, cfg, mix


def report(line: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error, the result as the last line of standard output."""
    if "control" in line:
        for name, (value, limit) in line["control"]["compared"].items():
            print(f"control compared {name}: {value!r} (limit {limit!r})",
                  file=sys.stderr)
        print(f"control correct: {line['control']['correct']}", file=sys.stderr)
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(argv=None, offered_rate: float = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, cfg, mix = open_cell(args.workload)
    if offered_rate is not None:            # benchmark/sweep.py
        for seg in mix["arrivals"]:
            seg["rate_per_s"] = offered_rate
    report(run_cell(spec, cell, cfg, mix, seed=args.seed,
                    seconds=args.seconds, trace=bool(args.trace),
                    scratch=os.environ.get("TMPDIR") or None))
    return 0


if __name__ == "__main__":
    sys.exit(main())
