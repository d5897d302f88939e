"""The explainer family ``bailing_hybrid`` and its one configuration: counts
against hand-worked values for the cut of Ling-3.0-flash, the family file's
contract, and the tiny hybrid desk run end to end on the CPU (sound run
correct, the family's int8 control not, every new per-layer metric read)."""

import ast
import json
import os

import pytest

from conftest import REPO

from benchmark import check, run

FAMILY = os.path.join(REPO, "benchmark", "explainers", "bailing_hybrid.py")
CELL = "desk-lr-ling-3.0-flash.campaign-1.35x"
NEW_METRICS = ("moe.held_pick_share_pct", "moe.experts_touched_per_step",
               "moe.prefill_load_max_over_mean", "slot.state_restore_ms")


@pytest.fixture(scope="module")
def family():
    return run._load_file(FAMILY, "bench_explainer_bailing_hybrid")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "desk-lr-ling-3.0-flash.json")) as f:
        return json.load(f)


def test_the_cut_of_ling_by_hand(family, cfg):
    D, H, d = 2560, 32, 128
    expert = 3 * D * 768                                     # 5,898,240
    # KDA: q, k, v and the full-rank decay projection to 32 x 128, out;
    # beta and the head gate (2 x 2560 x 32), three 4-tap filters, A_log,
    # dt_bias, the head norm
    kda = 5 * D * H * d + 2 * D * H + 3 * 4 * H * d + H + H * d + d
    assert kda == 52_646_048
    # MLA: q to 32 x 192, kv_a to 576, the latent's norm, kv_b 512 -> 32 x 256,
    # the head gate, out from 32 x 128
    mla = D * H * 192 + D * 576 + 512 + 512 * H * 256 + D * H + H * 128 * D
    assert mla == 31_965_696
    dense = 3 * D * 6144
    routed = 128 * expert + D * 512 + 512 + expert           # + router, bias, shared
    assert family._layer_params(cfg, "kda", "dense") == kda + dense
    assert family._layer_params(cfg, "mla", "experts") == mla + routed
    assert family._layer_params(cfg, "kda", "experts", held=False) == \
        kda + routed - 128 * expert
    assert family.layer_kinds(cfg) == (
        [("kda", "dense")] * 2 + [("kda", "experts")] * 3
        + [("mla", "experts")] + [("kda", "experts")] * 2)
    total = (7 * kda + mla + 2 * dense + 6 * routed + 8 * 2 * D   # block norms
             + 2 * 39296 * D + D)                                 # vocab slice
    assert family.param_count(cfg) == total == 5_269_204_064
    assert cfg["assumed"]["parameters"] == total
    assert family.latent_bytes_per_token(cfg) == 1152
    assert family._state_bytes(cfg, 2) == H * d * d * 4 + 3 * 3 * H * d * 2


def test_decode_and_prefill_costs_by_hand(family, cfg):
    D, H, d, V = 2560, 32, 128, 39296
    expert = 3 * D * 768
    unrouted = sum(family._layer_params(cfg, m, f, held=False) + 2 * D
                   for m, f in family.layer_kinds(cfg))
    # 12 rows a step: each misses a given expert with chance 63/64
    touched = 128 * (1 - (63 / 64) ** 12)
    assert family.expected_experts_touched(cfg, 12) == pytest.approx(touched)
    assert 22.0 < touched < 22.1
    steps, rows, ctx = 16, 16 * 12, 1800.0
    flops, nbytes = family.decode_cost(cfg, steps, rows, ctx)
    state = 2 * (H * d * d * 4 + 9 * H * d * 2)               # read and written
    want_bytes = (steps * 2 * (unrouted + V * D + D + 6 * touched * expert)
                  + rows * (7 * state + 1152 * (ctx + 2)))
    assert nbytes == pytest.approx(want_bytes)
    assert 3.2e9 < nbytes / steps < 3.4e9                     # the issue's 3.3 GB a step
    # with the program's own count (21 experts a layer-step, as an uneven
    # router gives) in the expectation's place: only the expert bytes move
    flops_m, nbytes_m = family.decode_cost(cfg, steps, rows, ctx,
                                           experts_touched=steps * 6 * 21.0)
    assert flops_m == flops
    assert nbytes - nbytes_m == pytest.approx(
        steps * 2 * 6 * (touched - 21.0) * expert)
    # a token: 2 flops a weight it multiplies (2 of its 8 picks are held on
    # average), the head, 7 KDA recurrences of 7 flops a state entry, one
    # absorbed attention over the 576-wide latents and 512-wide values
    token = (2 * (unrouted - 8 * 2 * D + 6 * 2 * expert + V * D)
             + 7 * 7 * H * d * d + 2 * H * (576 + 512) * (ctx + 1))
    # (block norms multiply nothing)
    assert flops == pytest.approx(rows * token)
    f, b = family.prefill_cost(cfg, prefix_len=293, suffix_len=1400)
    ctx_sum = 1400 * 293 + 1400 * 1401 / 2
    want = (1400 * (2 * (unrouted - 8 * 2 * D + 6 * 2 * expert)
                    + 7 * 7 * H * d * d)
            + 2 * 512 * H * 256 * 1693 + 2 * H * (192 + 128) * ctx_sum
            + 2 * V * D)
    assert f == pytest.approx(want)
    assert 1.25e9 < f / 1400 < 1.30e9                         # ~1.27 GFLOP a token
    all_touched = 128 * (1 - (63 / 64) ** 1400)
    assert b == pytest.approx(
        2 * (unrouted + V * D + D + 6 * all_touched * expert)
        + 1152 * 1693 + 7 * state)


def test_family_imports_the_program_in_build_alone():
    tree = ast.parse(open(FAMILY).read())
    where = []
    for fn in [n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)]:
        for node in ast.walk(fn):
            names = ([a.name for a in node.names] if isinstance(node, ast.Import)
                     else [node.module or ""] if isinstance(node, ast.ImportFrom)
                     else [])
            if any(n.startswith("fraud_detection_tpu") for n in names):
                where.append(fn.name)
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert not any("fraud_detection_tpu" in ast.dump(n) for n in top)
    assert where == ["build"]
    family = run._load_file(FAMILY, "bench_explainer_bailing_hybrid")
    assert all(hasattr(family, f) for f in run.FAMILY_FUNCTIONS)


def test_configuration_states_its_cut_and_its_limit(spec, cfg):
    entry = next(c for c in spec["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 42, "num_experts": 512,
                                "vocab_size": 157184}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) \
        == (8, 128, 39296)
    assert cfg["expert_share"] == {"first": 0, "chips_sharing_a_layer": 4}
    assert "4 chips share each layer" in cfg["deployment"]
    # no width is cut
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["head_dim"],
            cfg["kv_lora_rank"], cfg["intermediate_size"],
            cfg["num_experts_per_tok"]) == (2560, 768, 128, 512, 6144, 8)
    base = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "desk-lr-internlm2-1.8b.json")))
    for part in ("classifier", "engine", "guarantees"):
        assert cfg["desk"][part] == base["desk"][part]
    for key in ("slots", "paged", "page_size", "prompt_width",
                "max_new_tokens", "temperature"):
        assert cfg["desk"]["explain"][key] == base["desk"]["explain"][key]
    limits = check.stated_limits(cfg)
    stated = cfg["check"]["token_gap_sq"]
    assert limits["token_gap_sq"] == stated["limit"]
    assert len(stated["sound"]) >= 12 and len(stated["control"]) >= 3
    assert max(stated["sound"]) < stated["limit"] < min(stated["control"])
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        cfg["name"], "campaign-1.35x", 1)
    mix = json.load(open(os.path.join(REPO, "benchmark", "traffic",
                                      "campaign-1.35x.json")))
    # 1.35 x the knee, as a number (the stub configuration of
    # test_traffic.py lacks the knee's key; the tiny mix keeps the relative form)
    assert mix["arrivals"][1]["rate_per_s"] == pytest.approx(
        1.35 * cfg["desk"]["sustained_explanations_per_s"], rel=2e-3)


def test_tiny_hybrid_cell_is_correct_and_reads_every_new_metric(run_tiny, spec):
    # 10 s: with the other test files running beside it a 3 s window may
    # close before one decode window has come back, and the counters'
    # readers then find nothing between the marks
    line = run_tiny("tiny-campaign-rel", config="tiny-desk-hybrid",
                    kind="campaign-1.35x", trace=True, seconds=10.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["info"]["cell"] == CELL
    assert line["info"]["numbers"]["tokens_compared"] > 0
    assert line["compared"]["token_gap_sq"][0] < 1e-8         # float32: the best
    for name in NEW_METRICS + ("slot.occupancy", "slot.host_ms_per_window"):
        value = line["metrics"][name]["value"]
        assert value == value and value != 0, (name, value)
    # 4 of 16 experts held: a quarter of the picks under even routing
    assert 5.0 < line["metrics"]["moe.held_pick_share_pct"]["value"] < 60.0
    assert 0 < line["metrics"]["moe.experts_touched_per_step"]["value"] <= 4
    assert line["metrics"]["moe.prefill_load_max_over_mean"]["value"] >= 1.0
    listed = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert set(NEW_METRICS) <= listed


def test_tiny_hybrid_int8_control_is_not_correct(run_tiny):
    line = run_tiny("tiny-campaign-rel", config="tiny-desk-hybrid",
                    kind="campaign-1.35x", explain_weights="int8",
                    control=True, seconds=3.0)
    assert line["control"]["correct"] is False
    value, limit = line["control"]["compared"]["token_gap_sq"]
    assert value > limit


def test_new_readers_find_nothing_in_a_program_without_the_counters(spec):
    """Laid over the parent's checkout the readers return None, not 0."""
    marks = {k: {"slots": 2, "decode_steps": 10 * i, "occupancy": 0.5}
             for i, k in enumerate(("open", "trace_start", "trace_stop", "close"))}
    ctx = {"cfg": {"num_hidden_layers": 8, "first_k_dense_replace": 2},
           "marks": marks, "rowtrace": [], "window": (0.0, 1.0)}
    for name in NEW_METRICS + ("paged_decode_window_touched_roofline",):
        assert run.load_reader(spec, name)(ctx) is None


def test_decode_roofline_from_the_programs_own_count(spec, family, cfg):
    """The second decode share counts the experts the steps really touched:
    with the counter at the even-routing expectation it equals the first,
    with fewer touched it is lower (a CPU trace names no program, so the
    tiny traced run reads neither: the context here is made by hand)."""
    steps, slots, occ = 160, 16, 0.75
    even = steps * 6 * family.expected_experts_touched(cfg, slots * occ)

    def ctx_with(touched):
        marks = {"trace_start": {"slots": slots, "decode_steps": 0,
                                 "occupancy": occ, "moe_experts_touched": 0},
                 "trace_stop": {"slots": slots, "decode_steps": steps,
                                "occupancy": occ, "moe_experts_touched": touched}}
        return {"cfg": cfg, "family": family, "marks": marks,
                "device_kind": "TPU v5 lite", "trace_window": (0.0, 3.0),
                "tickets": [{"prompt_len": 1700, "n_out": 128,
                             "first_token": 1.0, "done": None}],
                "trace": {"programs": {"paged_decode_window": 1.2}}}

    both = [run.load_reader(spec, n) for n in (
        "paged_decode_window_roofline", "paged_decode_window_touched_roofline")]
    first, second = (r(ctx_with(even)) for r in both)
    assert 0 < first < 100 and second == pytest.approx(first)
    fewer = both[1](ctx_with(0.85 * even))
    assert 0.9 * first < fewer < first
    listed = {m["name"]: m for m in spec["per_layer"]}
    assert listed["paged_decode_window_touched_roofline"]["workloads"] == [CELL]
