"""The comparison that decides ``correct``. The yardstick: later PRs may not
edit this file. It compares what the timed path produced, at the timed
sizes, with the plain references of ``benchmark/reference.py`` and of the
configuration's explainer family (``explainers/<model_type>.py``); each
number compared stands beside its limit in the result.

Numbers (limits and the readings they were set from are in PERF.md; a
configuration file may state ``token_gap_sq``'s for its own family, with its
readings: ``stated_limits``):

``rows_unaccounted``    rows due in the window with no frame on the output
                        topic, a frame twice, or a dead-letter record (0).
``text_mismatch``       sampled frames whose ``original_text`` is not the
                        text sent under that key (0).
``label_mismatch``      sampled frames whose label differs from the
                        reference's while the reference's own confidence is
                        clear of the threshold by the confidence limit (0).
``confidence_gap``      widest |frame confidence - reference confidence|
                        over the sampled frames.
``stoplist_mismatch``   words by which the served featurizer's stop list
                        differs from the benchmark's own copy (0).
``idf_gap``             widest |served IDF - the IDF the reference fits
                        again from the benchmark's corpus|.
``lane_unaccounted``    slot-lane and annotation-lane accounting: admitted
                        minus completed minus dropped, errors, truncated
                        prompts, leaked pages, failure markers (0).
``notes_unaccounted``   the configuration's guarantee "every flagged row is
                        explained or accounted": flagged rows with two
                        records on the annotations topic, records under a
                        key that was not flagged, requests the slots
                        finished whose row carries no real annotation with
                        the served text, rows with no record beyond those
                        the annotation lane counts as discarded at the
                        shutdown, and, where the mix follows annotations,
                        rows due in the window without a real one (0).
``prompt_mismatch``     sampled explanations whose prompt tokens do not
                        carry the transcript sent under that key (0).
``token_gap_sq``        over the sampled requests' served (greedy) tokens
                        that are not the reference's first choice: the mean
                        gap by which such a token's logit lies below the
                        reference's best, squared (0 where every token is
                        the best). A token is served off the best where the
                        program's logit noise exceeds the reference's margin
                        there, so the mean gap of those tokens follows the
                        noise's width whatever the seed's share of near
                        ties, and its square the noise's variance, which is
                        what a lower precision adds to.

Printed and not compared: that mean gap itself (``token_gap_off_best``),
the mean gap over all served tokens (``token_gap_mean``), the share of
served tokens off the reference's first choice (``tokens_off_best``) and the
widest single gap (``token_gap_max``). The last three follow the seed's share
of near ties as much as the precision: the program's own int8 path reads
none of them at three times the sound runs' largest (PERF.md section 6).
"""

from __future__ import annotations

import json
import random
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmark import reference

LIMITS = {
    "rows_unaccounted": 0,
    "text_mismatch": 0,
    "label_mismatch": 0,
    "confidence_gap": 1e-4,
    "stoplist_mismatch": 0,
    "idf_gap": 1e-6,
    "lane_unaccounted": 0,
    "notes_unaccounted": 0,
    "prompt_mismatch": 0,
    # Over 2,048 served tokens a run: the bf16 program read at most 3.45e-4
    # (a mean gap of 0.0186) on 20 seeds, the program's own int8 path at
    # least 1.106e-3 (0.0333) on 8 (PERF.md section 6).
    "token_gap_sq": 6.5e-4,
}


# The one limit a configuration file may state for itself, with the keys
# it has to bring: an explainer family's logit noise is its own (a routed
# model's steps at every flipped expert choice), so its limit is set between
# its own sound runs and its own control, and says so where it is stated.
STATABLE = ("token_gap_sq",)
STATED_KEYS = ("limit", "sound", "control", "why")


def stated_limits(cfg: dict) -> Dict[str, float]:
    """``LIMITS``, with the configuration's ``"check": {"token_gap_sq":
    {"limit": x, "sound": [...], "control": [...], "why": "..."}}`` merged
    over it. ``sound`` and ``control`` are the readings the limit was set
    from (the program as stated; the family's lower precision), and the limit
    has to stand between them. Any other name, a missing key or a limit
    outside its own readings is an error."""
    limits = dict(LIMITS)
    for name, entry in (cfg.get("check") or {}).items():
        if name not in STATABLE:
            raise ValueError(f"a configuration may state a limit for "
                             f"{list(STATABLE)} only, not {name!r}")
        missing = [k for k in STATED_KEYS if not entry.get(k)]
        if missing:
            raise ValueError(f"the stated limit of {name!r} lacks {missing}: "
                             f"it needs {list(STATED_KEYS)}")
        limit = float(entry["limit"])
        if not max(entry["sound"]) < limit < min(entry["control"]):
            raise ValueError(
                f"the stated limit {limit!r} of {name!r} does not stand "
                f"between its sound readings (largest {max(entry['sound'])!r})"
                f" and its control's (smallest {min(entry['control'])!r})")
        limits[name] = limit
    return limits


def sample_indices(n: int, k: int, seed: int, always: Sequence[int] = ()) -> List[int]:
    """``k`` of ``range(n)`` drawn from the seed, ``always`` among them."""
    rng = random.Random(int(seed) ^ 0xC0FFEE)
    picked = list(dict.fromkeys(int(i) for i in always))
    taken = set(picked)
    rest = [i for i in range(n) if i not in taken]
    rng.shuffle(rest)
    return (picked + rest)[:max(k, len(picked))]


def classifier_numbers(art: "reference.ClassifierArtifact",
                       frames: Dict[int, bytes],
                       sent_text: Dict[int, str], precision: str = "float32",
                       reference_as_program: bool = False) -> Dict[str, float]:
    """Compare sampled output frames with the plain reference. With
    ``reference_as_program`` the frames' own numbers are replaced by the
    reference's at ``precision`` — the control."""
    rows = sorted(frames)
    parsed = [json.loads(frames[i]) for i in rows]
    texts = [p.get("original_text") for p in parsed]
    text_mismatch = sum(1 for i, t in zip(rows, texts) if t != sent_text[i])
    sent = [sent_text[i] for i in rows]
    ref_labels, ref_conf = reference.classifier_confidences(art, sent)
    if reference_as_program:
        labels, conf = reference.classifier_confidences(art, sent, precision)
    else:
        labels = np.asarray([p.get("prediction", -1) for p in parsed])
        conf = np.asarray([float(p.get("confidence", -1.0)) for p in parsed])
    gap = np.abs(conf - ref_conf)
    # A label may flip only where the reference itself sits on the threshold.
    clear = np.abs(ref_conf - 0.5) > LIMITS["confidence_gap"]
    same = labels == ref_labels
    gap = np.where(same, gap, np.abs(conf - (1.0 - ref_conf)))
    return {"text_mismatch": int(text_mismatch),
            "label_mismatch": int(np.sum(~same & clear)),
            "confidence_gap": float(np.max(gap)) if len(gap) else 0.0,
            "frames_compared": len(rows)}


def is_explanation(note: dict) -> bool:
    """A real explanation: text from the model, not a drop record and not a
    drop or failure marker."""
    text = note.get("analysis")
    return (isinstance(text, str) and not note.get("dropped")
            and not text.startswith("[explanation "))


def lane_numbers(end: dict, notes: Sequence[dict]) -> Dict[str, float]:
    snap, lane = end["snapshot"], end["lane"] or {}
    bad_notes = sum(1 for n in notes if not is_explanation(n)
                    and n.get("analysis") != "[explanation dropped: closed]")
    return {"bad_notes": bad_notes, "lane_unaccounted": int(
        abs(snap["admitted"] - snap["completed"] - snap["dropped"]
            - snap["busy"] - snap["queue_depth"])
        + snap["errors"] + snap["truncated"] + end["leaked_pages"]
        + int(lane.get("backend_errors", 0)) + bad_notes)}


def notes_numbers(flagged: Sequence[int], must_be_real: Sequence[int],
                  note_keys: Sequence[int], note_real: Sequence[bool],
                  finished_undelivered: int, lane: dict) -> Dict[str, float]:
    """``flagged``: keys of every flagged row sent; ``must_be_real``: those
    the mix follows to their annotation; ``note_keys``/``note_real``: the
    records of the annotations topic; ``finished_undelivered``: requests the
    slots served to the end whose row has no real annotation with the served
    text; ``lane``: the annotation lane's own counters."""
    flagged_set = set(int(k) for k in flagged)
    seen: Dict[int, int] = {}
    real = set()
    for k, r in zip(note_keys, note_real):
        seen[int(k)] = seen.get(int(k), 0) + 1
        if r:
            real.add(int(k))
    twice = sum(1 for k, c in seen.items() if c > 1)
    stray = sum(1 for k in seen if k not in flagged_set)
    silent = sum(1 for k in flagged_set if k not in seen)
    discarded = int(lane.get("dropped", 0)) - int(lane.get("drop_records", 0))
    unreal = sum(1 for k in must_be_real if int(k) not in real)
    return {"notes_unaccounted": int(twice + stray + abs(silent - discarded)
                                     + int(finished_undelivered) + unreal),
            "notes_silent": silent, "notes_discarded": discarded}


def explainer_numbers(seed: int, cfg: dict, requests: Sequence[dict],
                      pad_to: int, token_gaps) -> Dict[str, float]:
    """``requests``: ``{"prompt", "served", "text"}`` of sampled finished
    rows; ``text`` is the sent transcript the prompt carries, None if it
    carries none. ``token_gaps`` is the plain reference of the
    configuration's explainer family (``run.load_family``). Returns
    ``token_gap_sq`` (compared), and for the record the mean gap it squares,
    the mean gap over all tokens, the share of served tokens that are not
    the reference's first choice, the widest single gap and how many tokens
    were compared."""
    gap = np.concatenate(token_gaps(
        seed, cfg, cfg["torch_dtype"], requests, pad_to))
    off = gap[gap > 0]
    off_mean = float(np.mean(off)) if len(off) else 0.0
    return {"prompt_mismatch": sum(1 for r in requests if r["text"] is None),
            "token_gap_sq": off_mean * off_mean,
            "token_gap_off_best": off_mean,
            "token_gap_mean": float(np.mean(gap)),
            "tokens_off_best": float(np.mean(gap > 0)),
            "token_gap_max": float(np.max(gap)),
            "tokens_compared": int(len(gap))}


def control_verdict(numbers: Dict[str, float],
                    limits: Optional[Dict[str, float]] = None) -> Dict[str, object]:
    """The verdict with every ``control_<name>`` reading (the classifier's
    reference in bfloat16) put in the place of the program's ``<name>``:
    what ``correct`` says of the lower precision. The explainer's control
    is served by the program itself, through the lower precision of its
    family file's ``build`` (benchmark/control.py), so its numbers stand in
    the program's place already."""
    swapped = dict(numbers)
    for k, v in numbers.items():
        if k.startswith("control_"):
            swapped[k[len("control_"):]] = v
    return verdict(swapped, limits)


def verdict(numbers: Dict[str, float],
            limits: Optional[Dict[str, float]] = None) -> Dict[str, object]:
    """``{"correct": bool, "compared": {name: [value, limit]}}`` over the
    numbers that have a limit; a NaN never passes. ``limits`` is
    ``stated_limits`` of the cell's configuration, or a test's at sizes
    whose readings differ from the cells'."""
    limits = LIMITS if limits is None else limits
    compared = {k: [numbers[k], limits[k]] for k in limits if k in numbers}
    ok = bool(compared) and all(
        (v == v) and v <= lim for v, lim in compared.values())
    return {"correct": ok, "compared": compared}


def accounting_numbers(due_rows: Sequence[int], out_keys: Sequence[int],
                       dlq_count: int) -> Dict[str, float]:
    got = np.bincount(np.asarray(out_keys, np.int64),
                      minlength=(max(due_rows) + 1 if len(due_rows) else 1))
    due = np.asarray(due_rows, np.int64)
    wrong = int(np.sum(got[due] != 1)) if len(due) else 0
    return {"rows_unaccounted": wrong + int(dlq_count)}
