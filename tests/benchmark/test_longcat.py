"""The explainer family ``longcat_flash`` and its one configuration: counts
against hand-worked values for the cut of LongCat-Flash-Chat, the family
file's contract, and the tiny shortcut-connected desk run end to end on the
CPU (sound run correct, the family's int8 control not, every metric listed
for the new cell read)."""

import ast
import json
import os

import pytest

from conftest import REPO

from benchmark import check, run

FAMILY = os.path.join(REPO, "benchmark", "explainers", "longcat_flash.py")
CONFIG = "desk-lr-longcat-flash-chat"
CELL = CONFIG + ".campaign-1.35x-longcat"
MIX = "campaign-1.35x-longcat"
# every per-layer metric that lists the cell and reads something on the CPU
# (a CPU trace names no program: the two rooflines, the two device times and
# explain.step_mfu's decode half need the chip)
COUNTER_METRICS = ("moe.zero_pick_share_pct", "moe.held_pick_share_pct",
                   "moe.prefill_load_max_over_mean", "slot.occupancy",
                   "slot.starved_pct", "slot.host_ms_per_window")
LISTED = COUNTER_METRICS + (
    "llm.decode_step_ms", "llm.prefill_ms", "paged_decode_window_roofline",
    "paged_slot_prefill_roofline", "explain.step_mfu")


@pytest.fixture(scope="module")
def family():
    return run._load_file(FAMILY, "bench_explainer_longcat_flash")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cut_of_longcat_by_hand(family, cfg):
    D, H = 6144, 64
    # one attention: q down to 1,536 and its norm, up to 64 x 192; kv_a to
    # 576 and the latent's norm; kv_b 512 -> 64 x 256; out from 64 x 128
    attention = (D * 1536 + 1536 + 1536 * H * 192 + D * 576 + 512
                 + 512 * H * 256 + 8192 * D)
    assert attention == 90_572_800
    mlp = 3 * D * 12288
    assert mlp == 226_492_416
    router = D * 768 + 768
    assert router == 4_719_360
    expert = 3 * D * 2048
    assert expert == 37_748_736
    outside = 2 * attention + 2 * mlp + router + 4 * D
    assert outside == 638_874_368
    assert family._layer_params(cfg, held=False) + 4 * D == outside
    assert family._layer_params(cfg) + 4 * D == outside + 16 * expert
    assert family._expert_params(cfg) == expert
    assert 4 * (outside + 16 * expert) == 4_971_416_576
    total = 4 * (outside + 16 * expert) + 2 * 16384 * D + D
    assert family.param_count(cfg) == total == 5_172_749_312
    assert cfg["assumed"]["parameters"] == total
    assert family.router_width(cfg) == 768
    assert family.latent_bytes_per_token(cfg) == 1152
    assert family.mla_scales(cfg) == (2.0, 12 ** 0.5)
    # five layers, or 32 experts held, would not leave the lane its room
    assert (total + outside + 16 * expert) * 2 > 12.8e9
    assert (total + 4 * 16 * expert) * 2 > 15.1e9


def test_decode_and_prefill_costs_by_hand(family, cfg):
    D, H, V = 6144, 64, 16384
    expert = 3 * D * 2048
    outside = 638_874_368                                  # with its 4 norms
    # 16 rows a step: each misses a given held expert with chance 1 - 12/768
    touched = 16 * (1 - (1 - 12 / 768) ** 16)
    assert family.expected_experts_touched(cfg, 16) == pytest.approx(touched)
    assert 3.5 < touched < 3.6
    steps, rows, ctx = 16, 16 * 16, 1800.0
    flops, nbytes = family.decode_cost(cfg, steps, rows, ctx)
    want_bytes = (steps * 2 * (4 * outside + V * D + D + 4 * touched * expert)
                  + rows * 8 * 1152 * (ctx + 2))
    assert nbytes == pytest.approx(want_bytes)
    # the issue's 5.3 GB outside the experts, ~1.1 GB of touched experts and
    # 0.3 GB of latents a step
    assert 5.3e9 < steps * 2 * (4 * outside + V * D + D) / steps < 5.35e9
    assert 1.0e9 < 2 * 4 * touched * expert < 1.1e9
    assert 0.25e9 < 16 * 8 * 1152 * (ctx + 2) < 0.3e9
    assert 6.6e9 < nbytes / steps < 6.8e9
    # the program's own count in the expectation's place moves the expert
    # bytes alone
    flops_m, nbytes_m = family.decode_cost(cfg, steps, rows, ctx,
                                           experts_touched=steps * 4 * 3.0)
    assert flops_m == flops
    assert nbytes - nbytes_m == pytest.approx(
        steps * 2 * 4 * (touched - 3.0) * expert)
    # a token: 2 flops a weight it multiplies outside the norms (a quarter
    # of a pick lands on a held expert a layer: 12 x 16 / 768), a
    # multiply-add of the layer's input for its zero-compute picks, the
    # head, eight absorbed attentions over the 576-wide latents and 512-wide
    # values
    token = (2 * (4 * (outside - 4 * D) + 4 * 0.25 * expert + 4 * D + V * D)
             + 8 * 2 * H * (576 + 512) * (ctx + 1))
    assert flops == pytest.approx(rows * token)
    f, b = family.prefill_cost(cfg, prefix_len=293, suffix_len=1400)
    ctx_sum = 1400 * 293 + 1400 * 1401 / 2
    want = (1400 * 2 * (4 * (outside - 4 * D) + 4 * 0.25 * expert + 4 * D)
            + 8 * (2 * 512 * H * 256 * 1693 + 2 * H * (192 + 128) * ctx_sum)
            + 2 * V * D)
    assert f == pytest.approx(want)
    assert 5.1e9 < f / 1400 < 5.7e9                        # ~5.5 GFLOP a token
    all_touched = 16 * (1 - (1 - 12 / 768) ** 1400)
    assert all_touched > 15.99
    assert b == pytest.approx(
        2 * (4 * outside + V * D + D + 4 * all_touched * expert)
        + 8 * 1152 * 1693)


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
    family = run._load_file(FAMILY, "bench_explainer_longcat_flash")
    assert all(hasattr(family, f) for f in run.FAMILY_FUNCTIONS)


def test_configuration_states_its_cut_and_its_limit(spec, cfg):
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) < 200
    assert cfg["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                "vocab_size": 131072}
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) \
        == (4, 16, 16384)
    assert cfg["expert_share"] == {"first": 0, "chips_sharing_a_layer": 32}
    assert "32 chips share each layer" in cfg["deployment"]
    # every number of the catalog's row under its own key, but the three cut
    published = {
        "attention_bias": False, "hidden_size": 6144, "ffn_hidden_size": 12288,
        "expert_ffn_hidden_size": 2048, "num_attention_heads": 64,
        "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
        "rope_theta": 10000000, "attention_method": "MLA",
        "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12}
    assert {k: cfg[k] for k in published} == published
    base = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "desk-lr-internlm2-1.8b.json")))
    for part in ("classifier", "engine", "guarantees", "trace_programs"):
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
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    mix = json.load(open(os.path.join(REPO, "benchmark", "traffic",
                                      MIX + ".json")))
    hybrid = json.load(open(os.path.join(REPO, "benchmark", "traffic",
                                         "campaign-1.35x.json")))
    for key in ("draw_seed", "tick_ms", "preroll_s", "scam_share", "text",
                "follow"):
        assert mix[key] == hybrid[key]
    assert mix["arrivals"][0] == hybrid["arrivals"][0]
    # 1.35 x the knee, as a number
    assert mix["arrivals"][1]["rate_per_s"] == pytest.approx(
        1.35 * cfg["desk"]["sustained_explanations_per_s"], rel=2e-3)


def test_the_cell_is_listed_where_the_issue_says(spec):
    by = {m["name"]: m for m in spec["per_layer"]}
    listed = {n for n, m in by.items() if CELL in m["workloads"]}
    assert listed == set(LISTED)
    assert by["moe.zero_pick_share_pct"]["workloads"] == [CELL]
    assert spec["per_layer"][-1]["name"] == "moe.zero_pick_share_pct"
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"explanations_per_s", "setup_s"}


def test_tiny_longcat_cell_is_correct_and_reads_its_metrics(run_tiny, spec):
    # 10 s: see test_hybrid.py (a 3 s window may close before one decode
    # window has come back)
    line = run_tiny("tiny-campaign-rel", config="tiny-desk-longcat",
                    kind=MIX, trace=True, seconds=10.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["info"]["cell"] == CELL
    assert line["info"]["numbers"]["tokens_compared"] > 0
    assert line["compared"]["token_gap_sq"][0] < 1e-8         # float32: the best
    for name in COUNTER_METRICS:
        value = line["metrics"][name]["value"]
        assert value == value, (name, value)
        if name != "slot.starved_pct":
            assert value != 0, (name, value)
    # 8 of 24 router outputs are zero-compute, 4 of 24 are held experts
    assert 10.0 < line["metrics"]["moe.zero_pick_share_pct"]["value"] < 60.0
    assert 3.0 < line["metrics"]["moe.held_pick_share_pct"]["value"] < 40.0
    assert line["metrics"]["moe.prefill_load_max_over_mean"]["value"] >= 1.0
    assert set(line["metrics"]) <= set(LISTED)


def test_tiny_longcat_int8_control_is_not_correct(run_tiny):
    line = run_tiny("tiny-campaign-rel", config="tiny-desk-longcat",
                    kind=MIX, explain_weights="int8", control=True,
                    seconds=3.0)
    assert line["control"]["correct"] is False
    value, limit = line["control"]["compared"]["token_gap_sq"]
    assert value > limit


def test_zero_pick_reader_finds_nothing_without_the_counter(spec):
    """Laid over the parent's checkout the reader returns None, not 0."""
    marks = {k: {"slots": 2, "decode_steps": 10 * i, "occupancy": 0.5,
                 "moe_picks": 100 * i, "moe_picks_held": 10 * i}
             for i, k in enumerate(("open", "trace_start", "trace_stop", "close"))}
    ctx = {"cfg": {}, "marks": marks, "rowtrace": [], "window": (0.0, 1.0)}
    read = run.load_reader(spec, "moe.zero_pick_share_pct")
    assert read(ctx) is None
    for i, k in enumerate(marks):
        marks[k]["moe_picks_zero"] = 33 * i
    assert read(ctx) == pytest.approx(33.0)
