"""The explainer family ``lfm2_moe`` and its one configuration: counts against
hand-worked values for the stage of LFM2-24B-A2B, the family file's contract,
and the tiny convolution-attention desk run end to end on the CPU (sound run
correct, the family's int8 control not, every counter metric listed for the
new cell read)."""

import ast
import json
import os

import pytest

from conftest import REPO

from benchmark import check, run

FAMILY = os.path.join(REPO, "benchmark", "explainers", "lfm2_moe.py")
CONFIG = "desk-lr-lfm2-24b-a2b"
CELL = CONFIG + ".campaign-1.35x-lfm2"
MIX = "campaign-1.35x-lfm2"
# every per-layer metric that lists the cell and reads something on the CPU
# (a CPU trace names no program: the two rooflines, the two device times and
# explain.step_mfu's decode half need the chip)
COUNTER_METRICS = ("moe.decode_touched_pct", "moe.held_pick_share_pct",
                   "moe.prefill_load_max_over_mean", "slot.occupancy",
                   "slot.starved_pct", "slot.host_ms_per_window",
                   "slot.state_restore_ms")
LISTED = COUNTER_METRICS + (
    "llm.decode_step_ms", "llm.prefill_ms", "paged_decode_window_roofline",
    "paged_slot_prefill_roofline", "explain.step_mfu")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def family():
    return run._load_file(FAMILY, "bench_explainer_lfm2_moe")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_stage_of_lfm2_by_hand(family, cfg):
    D, V = 2048, 65536
    conv = D * 3 * D + 3 * D + D * D       # in-projection, three taps, out
    assert conv == 16_783_360
    # q and o at 32 x 64, k and v at 8 x 64, the two norms of 64
    attention = 2 * D * 2048 + 2 * D * 512 + 128
    assert attention == 10_485_888
    mlp = 3 * D * 11776
    assert mlp == 72_351_744
    expert = 3 * D * 1536
    assert expert == 9_437_184 == family._expert_params(cfg)
    experts = 64 * expert + D * 64 + 64                    # router and its bias
    assert experts == 604_110_912
    kinds = family.layer_kinds(cfg)
    assert kinds == [("conv", "dense"), ("conv", "dense"),
                     ("attention", "experts"), ("conv", "experts"),
                     ("conv", "experts"), ("conv", "experts"),
                     ("attention", "experts"), ("conv", "experts"),
                     ("conv", "experts"), ("conv", "experts")]
    assert family._layer_params(cfg, "conv", "dense") + 2 * D == 89_139_200
    assert family._layer_params(cfg, "attention", "experts") + 2 * D \
        == 614_600_896
    assert family._layer_params(cfg, "conv", "experts") + 2 * D == 620_898_368
    assert family._layer_params(cfg, "conv", "experts", held=False) \
        == conv + D * 64 + 64
    total = (2 * 89_139_200 + 2 * 614_600_896 + 6 * 620_898_368
             + V * D + D)
    assert family.param_count(cfg) == total == 5_267_090_176
    assert cfg["assumed"]["parameters"] == total
    assert family.head_dim(cfg) == 64
    assert family.kv_bytes_per_token(cfg) == 4096 // 2    # a layer; two page
    assert family.state_bytes(cfg) == 8 * 2 * D * 2       # 64 KB a slot
    # a third period of four would not leave the lane its room
    assert (total + 614_600_896 + 3 * 620_898_368) * 2 > 12.9e9


def test_decode_and_prefill_costs_by_hand(family, cfg):
    D, V, H, d = 2048, 65536, 32, 64
    conv, attention, mlp, expert = 16_783_360, 10_485_888, 72_351_744, 9_437_184
    router = D * 64 + 64
    # what every step reads whatever is routed, with the tied head: 0.87 GB
    outside = (8 * conv + 2 * attention + 2 * mlp + 8 * router + 10 * 2 * D
               + V * D + D)
    assert family._step_params(cfg) == outside
    assert 0.86e9 < 2 * outside < 0.88e9
    # 16 rows a step make 64 draws of one expert in 64
    touched = 64 * (1 - (63 / 64) ** 64)
    assert family.expected_experts_touched(cfg, 16) == pytest.approx(touched)
    assert 40.5 < touched < 40.7
    steps, rows, ctx = 16, 16 * 16, 1800.0
    flops, nbytes = family.decode_cost(cfg, steps, rows, ctx)
    want_bytes = (steps * 2 * (outside + 8 * touched * expert)
                  + rows * (2 * 2048 * (ctx + 2) + 2 * 65536))
    assert nbytes == pytest.approx(want_bytes)
    assert 6.0e9 < 2 * 8 * touched * expert < 6.2e9        # the experts' bytes
    assert 7.0e9 < nbytes / steps < 7.3e9                  # ~7.1 GB a step
    assert 0.84 < 2 * 8 * touched * expert / (nbytes / steps) < 0.88
    # the program's own count in the expectation's place moves the expert
    # bytes alone
    flops_m, nbytes_m = family.decode_cost(cfg, steps, rows, ctx,
                                           experts_touched=steps * 8 * 30.0)
    assert flops_m == flops
    assert nbytes - nbytes_m == pytest.approx(
        steps * 2 * 8 * (touched - 30.0) * expert)
    # a token: 2 flops a weight it multiplies outside the block norms (four
    # picks a layer, all computed; the filters' taps a multiply-add each),
    # the head, two attentions over 64-wide keys and values
    token_weights = (8 * conv + 2 * attention + 2 * mlp + 8 * router
                     + 8 * 4 * expert)
    token = 2 * token_weights + 2 * V * D + 2 * 4 * H * d * (ctx + 1)
    assert flops == pytest.approx(rows * token)
    assert 1.20e9 < 2 * token_weights < 1.21e9             # 1.2 GFLOP a token
    f, b = family.prefill_cost(cfg, prefix_len=293, suffix_len=1400)
    ctx_sum = 1400 * 293 + 1400 * 1401 / 2
    assert f == pytest.approx(1400 * 2 * token_weights
                              + 2 * 4 * H * d * ctx_sum + 2 * V * D)
    assert 1.6e12 < f < 1.8e12                             # ~1.7 TFLOP a prompt
    all_touched = 64 * (1 - (63 / 64) ** 5600)
    assert all_touched > 63.99
    assert b == pytest.approx(2 * (outside + 8 * all_touched * expert)
                              + 2 * 2048 * 1693 + 2 * 65536)
    # each touched expert's weights once: memory-bound on the v5e
    assert b / 819e9 > f / 197e12


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
    family = run._load_file(FAMILY, "bench_explainer_lfm2_moe")
    assert all(hasattr(family, f) for f in run.FAMILY_FUNCTIONS)


def test_configuration_states_its_cut_and_its_limit(spec, cfg):
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) < 200
    assert cfg["published"] == {"num_hidden_layers": 40}
    assert cfg["num_hidden_layers"] == 10
    assert cfg["expert_share"] == {"first": 0, "chips_sharing_a_layer": 1}
    assert "one chip shares each layer; stage 0 of four" in cfg["deployment"]
    # every published width and count unchanged
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 8, "intermediate_size": 11776,
        "moe_intermediate_size": 1536, "num_experts": 64,
        "num_experts_per_tok": 4, "conv_L_cache": 3, "conv_bias": False,
        "vocab_size": 65536, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_dense_layers": 2, "routed_scaling_factor": 1,
        "use_expert_bias": True, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe",
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert {k: cfg[k] for k in published} == published
    assert cfg["head_dim"] == 64 and "head_dim" in cfg["assumed"]
    # the published list of 40 kept whole; the stage serves its first ten
    assert len(cfg["layer_types"]) == 40
    assert cfg["layer_types"][:10] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]
    if os.path.exists(CATALOG):    # every key of the catalog's row but the cut
        row = next(json.loads(line) for line in open(CATALOG)
                   if '"name": "LFM2-24B-A2B"' in line)
        assert row["source_url"] == cfg["source"]
        assert {k: cfg[k] for k in row["config"] if k != "num_hidden_layers"} \
            == {k: v for k, v in row["config"].items()
                if k != "num_hidden_layers"}
        assert row["config"]["num_hidden_layers"] == 40
    for key in ("head_dim", "tie_word_embeddings", "router", "streams",
                "rotary", "torch_dtype", "hidden_act", "bias", "tokenizer",
                "weights"):
        assert cfg["assumed"][key], key
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
    assert by["moe.decode_touched_pct"]["workloads"] == [CELL]
    assert by["moe.decode_touched_pct"]["better"] == "lower"
    assert by["moe.decode_touched_pct"]["layer"] == "model step"
    assert spec["per_layer"][-1]["name"] == "moe.decode_touched_pct"
    assert spec["workloads"][-1]["name"] == CELL
    assert spec["configs"][-1]["name"] == CONFIG
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"explanations_per_s", "setup_s"}
    # its list is pinned by another family's test; its reader counts expert
    # layers by the hybrid's keys; no zero-compute outputs here
    for name in ("paged_decode_window_touched_roofline",
                 "moe.experts_touched_per_step", "moe.zero_pick_share_pct"):
        assert CELL not in by[name]["workloads"]


def test_tiny_lfm2_cell_is_correct_and_reads_its_metrics(run_tiny, spec):
    # 10 s: see test_hybrid.py (a 3 s window may close before one decode
    # window has come back)
    line = run_tiny("tiny-campaign-rel", config="tiny-desk-lfm2",
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
    # all 8 experts held: every pick is computed; 2 slots' 8 draws of one in
    # 8 touch ~5 of them a step
    assert line["metrics"]["moe.held_pick_share_pct"]["value"] == 100.0
    assert 12.5 <= line["metrics"]["moe.decode_touched_pct"]["value"] <= 100.0
    assert line["metrics"]["moe.prefill_load_max_over_mean"]["value"] >= 1.0
    assert set(line["metrics"]) <= set(LISTED)


def test_tiny_lfm2_int8_control_is_not_correct(run_tiny):
    line = run_tiny("tiny-campaign-rel", config="tiny-desk-lfm2",
                    kind=MIX, explain_weights="int8", control=True,
                    seconds=3.0)
    assert line["control"]["correct"] is False
    value, limit = line["control"]["compared"]["token_gap_sq"]
    assert value > limit


def test_touched_reader_finds_nothing_without_the_counter(spec):
    """Laid over the parent's checkout the reader returns None, not 0."""
    marks = {k: {"slots": 2, "decode_steps": 10 * i, "occupancy": 0.5,
                 "moe_picks": 100 * i, "moe_experts_touched": 127 * i}
             for i, k in enumerate(("open", "trace_start", "trace_stop", "close"))}
    ctx = {"cfg": {}, "marks": marks, "rowtrace": [], "window": (0.0, 1.0)}
    read = run.load_reader(spec, "moe.decode_touched_pct")
    assert read(ctx) is None
    for i, k in enumerate(marks):
        marks[k]["moe_expert_slots"] = 200 * i
    assert read(ctx) == pytest.approx(63.5)
