"""The explainer family ``kimi_linear`` and its one configuration: counts
against hand-worked values for the cut of Kimi-Linear-48B-A3B, the family
file's contract, the tiny unbounded-gate hybrid against its plain float32
reference (cached decode, the slot lane's pages and state, the expert
shares), and the tiny desk run end to end on the CPU (sound run correct, the
family's int8 control not, every counter metric listed for the new cell
read)."""

import ast
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FIXTURES, REPO

from benchmark import check, run
from fraud_detection_tpu.models import llm

FAMILY = os.path.join(REPO, "benchmark", "explainers", "kimi_linear.py")
CONFIG = "desk-lr-kimi-linear-48b-a3b"
CELL = CONFIG + ".campaign-1.35x-kimi"
MIX = "campaign-1.35x-kimi"
SEED = 2**31 + 5
# every per-layer metric that lists the cell and reads something on the CPU
# (a CPU trace names no program: the three rooflines, the two device times
# and explain.step_mfu's decode half need the chip)
COUNTER_METRICS = ("kda.deep_decay_pct", "moe.decode_touched_pct",
                   "moe.held_pick_share_pct", "moe.experts_touched_per_step",
                   "moe.prefill_load_max_over_mean", "slot.occupancy",
                   "slot.starved_pct", "slot.host_ms_per_window",
                   "slot.state_restore_ms")
LISTED = COUNTER_METRICS + (
    "llm.decode_step_ms", "llm.prefill_ms", "paged_decode_window_roofline",
    "paged_decode_window_touched_roofline", "paged_slot_prefill_roofline",
    "explain.step_mfu", "setup.program_load_s", "setup.programs_loaded",
    "setup.compiled_s", "setup.train_s", "setup.service_s",
    "jit.compiles_in_window")


@pytest.fixture(scope="module")
def family():
    return run._load_file(FAMILY, "bench_explainer_kimi_linear")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def tiny(dtype="float32", **changes):
    """The tiny configuration (tests/benchmark/fixtures): 5 layers as the
    cut's (KDA with the dense MLP; KDA, KDA, latent attention, KDA over
    experts), 2 KDA heads of 16 (the gates' rank), 4 of 16 experts held."""
    with open(os.path.join(FIXTURES, "configs", "tiny-desk-kimi.json")) as f:
        c = json.load(f)
    c["torch_dtype"] = dtype
    c.update(changes)
    return c


def tiny_model(family, dtype="float32", weights=None, **changes):
    c = tiny(dtype, **changes)
    return family.build(c, family.make_params(SEED, c, jnp.dtype(dtype).type),
                        weights or dtype)


def test_the_cut_of_kimi_by_hand(family, cfg):
    D, H, d = 2304, 32, 128
    # KDA: q, k, v to 32 x 128, three 4-tap filters, f_a 2304 -> 128 and f_b
    # 128 -> 4096 (the decay), beta, g_a and g_b (the output gate), A_log,
    # dt_bias, the head norm, out
    kda = (3 * D * H * d + 3 * 4 * H * d + D * d + d * H * d + D * H
           + D * d + d * H * d + H + H * d + d + H * d * D)
    assert kda == 39_514_272
    # MLA: q to 32 x 192, kv_a to 576, the latent's norm, kv_b 512 -> 32 x 256,
    # out from 32 x 128; no query latent, no gate
    mla = D * H * 192 + D * 576 + 512 + 512 * H * 256 + H * 128 * D
    assert mla == 29_114_880
    dense = 3 * D * 9216
    assert dense == 63_700_992
    expert = 3 * D * 1024
    assert expert == 7_077_888 == family._expert_params(cfg)
    routed = 128 * expert + D * 256 + 256 + expert       # + router, bias, shared
    assert routed == 913_637_632
    assert family._layer_params(cfg, "kda", "dense") == kda + dense
    assert family._layer_params(cfg, "mla", "experts") == mla + routed
    assert family._layer_params(cfg, "kda", "experts", held=False) == \
        kda + routed - 128 * expert
    assert family.layer_kinds(cfg) == [
        ("kda", "dense"), ("kda", "experts"), ("kda", "experts"),
        ("mla", "experts"), ("kda", "experts")]
    assert kda + dense + 2 * D == 103_219_872
    assert kda + routed + 2 * D == 953_156_512
    assert mla + routed + 2 * D == 942_757_120
    assert 2 * 81920 * D == 377_487_360
    total = (4 * kda + mla + dense + 4 * routed + 5 * 2 * D   # block norms
             + 2 * 81920 * D + D)                              # vocab slice
    assert family.param_count(cfg) == total == 4_282_936_192
    assert cfg["assumed"]["parameters"] == total
    assert family.latent_bytes_per_token(cfg) == 1152
    assert family._state_bytes(cfg, 2) == H * d * d * 4 + 3 * 3 * H * d * 2
    # a sixth layer (KDA) would tilt the period and leave the lane 1 GB less
    assert (total + kda + routed + 2 * D) * 2 > 10.4e9


def test_decode_and_prefill_costs_by_hand(family, cfg):
    D, H, d, V = 2304, 32, 128, 81920
    expert = 3 * D * 1024
    unrouted = sum(family._layer_params(cfg, m, f, held=False) + 2 * D
                   for m, f in family.layer_kinds(cfg))
    # 16 rows a step: each misses a given expert with chance 1 - 8/256
    touched = 128 * (1 - (1 - 8 / 256) ** 16)
    assert family.expected_experts_touched(cfg, 16) == pytest.approx(touched)
    assert 50.9 < touched < 51.1                              # ~40 % of 128
    steps, rows, ctx = 16, 16 * 16, 1800.0
    flops, nbytes = family.decode_cost(cfg, steps, rows, ctx)
    state = 2 * (H * d * d * 4 + 9 * H * d * 2)               # read and written
    want_bytes = (steps * 2 * (unrouted + V * D + D + 4 * touched * expert)
                  + rows * (4 * state + 1152 * (ctx + 2)))
    assert nbytes == pytest.approx(want_bytes)
    assert 2.85e9 < 2 * 4 * touched * expert < 2.95e9        # the experts' bytes
    assert 4.0e9 < nbytes / steps < 4.2e9                     # ~4.1 GB a step
    flops_m, nbytes_m = family.decode_cost(cfg, steps, rows, ctx,
                                           experts_touched=steps * 4 * 40.0)
    assert flops_m == flops
    assert nbytes - nbytes_m == pytest.approx(
        steps * 2 * 4 * (touched - 40.0) * expert)
    # a token: 2 flops a weight it multiplies (4 of its 8 picks are held on
    # average), the head, 4 KDA recurrences of 7 flops a state entry, one
    # absorbed attention over the 576-wide latents and 512-wide values
    token = (2 * (unrouted - 5 * 2 * D + 4 * 4 * expert + V * D)
             + 4 * 7 * H * d * d + 2 * H * (576 + 512) * (ctx + 1))
    assert flops == pytest.approx(rows * token)
    f, b = family.prefill_cost(cfg, prefix_len=293, suffix_len=1400)
    ctx_sum = 1400 * 293 + 1400 * 1401 / 2
    diag = 2 * 4 * H * d * 17 / 2                # a token's pairs in its block
    want = (1400 * (2 * (unrouted - 5 * 2 * D + 4 * 4 * expert)
                    + 4 * (7 * H * d * d + diag))
            + 2 * 512 * H * 256 * 1693 + 2 * H * (192 + 128) * ctx_sum
            + 2 * V * D)
    assert f == pytest.approx(want)
    all_touched = 128 * (1 - (1 - 8 / 256) ** 1400)
    assert b == pytest.approx(
        2 * (unrouted + V * D + D + 4 * all_touched * expert)
        + 1152 * 1693 + 4 * state)


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
    family = run._load_file(FAMILY, "bench_explainer_kimi_linear")
    assert all(hasattr(family, f) for f in run.FAMILY_FUNCTIONS)


def test_configuration_states_its_cut_and_its_limit(spec, cfg):
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) < 200
    assert cfg["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) \
        == (5, 128, 81920)
    assert cfg["expert_share"] == {"first": 0, "chips_sharing_a_layer": 2}
    assert "2 chips share each layer" in cfg["deployment"]
    # every key of the published config.json, as published, but the three cut
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts_per_token": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "routed_scaling_factor": 2.446,
        "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_topk": True, "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    for key in ("tokenizer", "weights", "torch_dtype", "A_log", "dt_bias",
                "kda_gate", "kda_filter", "output_gate", "mla", "router",
                "head_dim", "mtp"):
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
    assert len(stated["sound"]) >= 6 and len(stated["control"]) >= 3
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


def test_the_cell_is_listed_in_every_metric_it_reads(spec):
    by = {m["name"]: m for m in spec["per_layer"]}
    listed = {n for n, m in by.items() if CELL in m["workloads"]}
    assert set(LISTED) <= listed
    deep = by["kda.deep_decay_pct"]
    assert (deep["layer"], deep["moves"], deep["source"], deep["unit"]) == (
        "model step", "explanations_per_s", "program_counter", "%")
    assert CONFIG in [c["name"] for c in spec["configs"]]
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"explanations_per_s", "setup_s"}
    # no zero-compute outputs here
    assert CELL not in by["moe.zero_pick_share_pct"]["workloads"]


# ---------------------------------------------------------------------------
# the tiny model against its plain float32 reference
# ---------------------------------------------------------------------------

def _cached_logits(lm, toks, n_prefill):
    """Logits of every position through the cache: one prefill of
    ``n_prefill`` tokens (the chunked recurrence, expanded latent
    attention), then one token a step (stepped, absorbed)."""
    B, T = toks.shape
    cfg = lm.cfg
    prefill = jax.jit(lambda p, t, c: llm.forward(
        p, t, cfg, kv_cache=c, cache_len=jnp.int32(0)))
    step = jax.jit(lambda p, t, pos, c: llm.forward(
        p, t, cfg, positions=jnp.full((B, 1), pos), kv_cache=c, cache_len=pos))
    lg, cache = prefill(lm.params, jnp.asarray(toks[:, :n_prefill]),
                        llm.init_cache(cfg, B, T))
    out = [np.asarray(lg)]
    for t in range(n_prefill, T):
        lg, cache = step(lm.params, jnp.asarray(toks[:, t:t + 1]),
                         jnp.int32(t), cache)
        out.append(np.asarray(lg))
    return np.concatenate(out, axis=1)


@pytest.fixture(scope="module")
def errors(family):
    """|program logits - reference logits| at every position of two
    120-token rows (100 prefilled, 20 decoded through the cache), by
    (dtype, weights)."""
    toks = np.random.default_rng(5).integers(0, 258, (2, 120)).astype(np.int32)
    memo = {}

    def get(dtype, weights=None):
        key = (dtype, weights or dtype)
        if key not in memo:
            ref = np.asarray(family.reference_logits(SEED, tiny(dtype), dtype,
                                                     toks))
            got = _cached_logits(tiny_model(family, dtype, weights), toks, 100)
            memo[key] = np.abs(got - ref)
        return memo[key]

    return get


# Tolerances of the program against the reference, logits of scale ~0.8:
# * float32, widest error 1e-4: the two differ in the order of float32 sums
#   only (chunked against token-by-token recurrence, absorbed against
#   expanded latent attention, experts by sorted tiles against one by one);
#   measured 1.6e-5.
# * bfloat16, MEDIAN error 0.018: bfloat16 rounding of every matmul's
#   operands (measured 0.0124). The widest error says nothing here (1.8): a
#   routed model's logits step wherever rounding flips an expert choice the
#   float32 reference does not.
# The weight-only int8 path of the same dtype fails each: float32 compute
# reads a widest error of 2.0, bfloat16 a median of 0.028.
F32_MAX, BF16_MEDIAN = 1e-4, 0.018


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_cached_decode_matches_reference(errors, dtype):
    err = errors(dtype)
    if dtype == "float32":
        assert err.max() < F32_MAX
    else:
        assert np.median(err) < BF16_MEDIAN


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_int8_path_fails_the_tolerance(errors, dtype):
    err = errors(dtype, "int8")
    if dtype == "float32":
        assert err.max() > 100 * F32_MAX
    else:
        assert np.median(err) > BF16_MEDIAN


def test_slot_lane_serves_the_references_first_choice(family):
    """Through the slot lane, as the desk serves it: a chunked prefill behind
    the preamble's latent pages and state snapshot, then decode windows
    through the pages and each slot's state block. Every token served in
    float32 is the plain reference's best at its position, teacher-forced
    (gap under 1e-4: the float32 tolerance above), and admission of whole
    prompts serves the same tokens. The snapshot counts the unbounded gate's
    decays, a share of them deep."""
    from fraud_detection_tpu.explain.slotserve.service import (
        SlotServeService, shared_explain_prefix)

    lm = tiny_model(family)
    prompts = [shared_explain_prefix() + " Caller: read me the one-time code"
               + " now." * (40 * (i % 3)) + f" Customer {i} hesitates: {'no' * i}"
               for i in range(4)]
    served = {}
    for shared in (True, False):
        svc = SlotServeService(lm, slots=2, max_new_tokens=8, prompt_width=832,
                               decode_window=4, shared_prefix=shared,
                               wait_timeout=600.0)
        reqs = [svc.submit(p, temperature=0.0) for p in prompts]
        for r in reqs:
            r.wait(600.0)
        snap = svc.snapshot()
        assert svc.close()
        served[shared] = [(np.asarray(r.tokens), np.asarray(r.out)) for r in reqs]
        assert snap["completed"] == 4
        assert 0 < snap["kda_decays_deep"] < snap["kda_decays"]
        assert snap["kda_decays"] % (2 * 16) == 0          # heads x head width
        assert 0 < snap["moe_picks_held"] < snap["moe_picks"]
    for (_, a), (_, b) in zip(served[True], served[False]):
        assert a.tolist() == b.tolist()
    gaps = family.token_gaps(SEED, tiny(), "float32",
                             [{"prompt": p, "served": s}
                              for p, s in served[True]], 832 + 8)
    assert max(float(g.max()) for g in gaps) < 1e-4


def test_the_shares_add_up_to_the_whole_layer(family):
    """The four shares' routed parts (each chip computes the picks that land
    on its own 4 of the 16 experts) plus the shared expert counted once are
    the uncut reference's whole expert layer. Float32, tolerance 2e-5 on
    outputs of scale ~1."""
    whole = tiny(num_experts=16)
    layer = 1                                   # the first expert layer (KDA)
    key = jax.random.fold_in(family._root_key(SEED), layer)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 50, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        flat = family._rms(x, whole["rms_norm_eps"]).reshape(-1, 32)
        want = family.expert_layer(key, whole, "kda", jnp.float32, flat)
    total, held_picks, shared = 0.0, 0, None
    for first in (0, 4, 8, 12):
        lm = tiny_model(family, expert_share={"first": first,
                                              "chips_sharing_a_layer": 4})
        assert lm.cfg.moe.held_start == first and lm.cfg.moe.held == 4
        y, stats = llm._experts_ffn(lm.params, lm.cfg, layer, x, jax.nn.silu,
                                    None)
        shared = llm._dense_mlp(lm.params, lm.cfg, layer, x, jax.nn.silu,
                                ("moe_sg", "moe_su", "moe_sd")) - x
        total = total + (y - x - shared)        # this share's routed part
        held_picks += int(stats["picks_held"])
        assert int(stats["picks"]) == 50 * 4
    assert held_picks == 50 * 4                 # every pick lands on one share
    np.testing.assert_allclose(np.asarray(total + shared)[0], np.asarray(want),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# the desk at the tiny size
# ---------------------------------------------------------------------------

def test_tiny_kimi_cell_is_correct_and_reads_its_metrics(run_tiny, spec):
    # 10 s: see test_hybrid.py (a 3 s window may close before one decode
    # window has come back)
    line = run_tiny("tiny-campaign-rel", config="tiny-desk-kimi",
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
    # 4 of 16 experts held: a quarter of the picks under even routing
    assert 5.0 < line["metrics"]["moe.held_pick_share_pct"]["value"] < 60.0
    assert 0 < line["metrics"]["kda.deep_decay_pct"]["value"] < 100.0
    assert line["metrics"]["moe.prefill_load_max_over_mean"]["value"] >= 1.0
    # these among those printed, and nothing printed that is not listed
    assert set(COUNTER_METRICS) <= set(line["metrics"]) <= {
        m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}


def test_tiny_kimi_int8_control_is_not_correct(run_tiny):
    line = run_tiny("tiny-campaign-rel", config="tiny-desk-kimi",
                    kind=MIX, explain_weights="int8", control=True,
                    seconds=3.0)
    assert line["control"]["correct"] is False
    value, limit = line["control"]["compared"]["token_gap_sq"]
    assert value > limit


def test_deep_decay_reader_finds_nothing_without_the_counters(spec):
    """Laid over the parent's checkout the reader returns None, not 0; with
    the counters at zero (a bounded gate) it returns None too."""
    marks = {k: {"slots": 2, "decode_steps": 10 * i, "occupancy": 0.5}
             for i, k in enumerate(("open", "trace_start", "trace_stop", "close"))}
    ctx = {"cfg": {}, "marks": marks, "rowtrace": [], "window": (0.0, 1.0)}
    read = run.load_reader(spec, "kda.deep_decay_pct")
    assert read(ctx) is None
    for i, k in enumerate(marks):
        marks[k]["kda_decays"] = marks[k]["kda_decays_deep"] = 0
    assert read(ctx) is None
    for i, k in enumerate(marks):
        marks[k]["kda_decays"], marks[k]["kda_decays_deep"] = 4096 * i, 1024 * i
    assert read(ctx) == pytest.approx(25.0)
