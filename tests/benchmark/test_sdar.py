"""The explainer family ``sdar_moe`` and its one configuration: counts
against hand-worked values for the cut of SDAR-30B-A3B-Chat, the family
file's contract, generation by diffusion over blocks through the slot lane
against the plain float32 reference (block-causal prefill, every denoising
forward's logits through the pages, the schedule, EOS inside a block, the
prefix cut at a block boundary, the mask id), the tiny desk run end to end
on the CPU, and the other five families' slot programs free of what
generation by diffusion adds."""

import ast
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FIXTURES, REPO

from benchmark import check, run
from fraud_detection_tpu.models import block_diffusion, llm

FAMILY = os.path.join(REPO, "benchmark", "explainers", "sdar_moe.py")
CONFIG = "desk-lr-sdar-30b-a3b-chat"
CELL = CONFIG + ".campaign-1.35x-sdar"
MIX = "campaign-1.35x-sdar"
SEED = 2**31 + 5
# every per-layer metric that lists the cell and reads something on the CPU
# (a CPU trace names no program: the two rooflines, the two device times and
# explain.step_mfu's decode half need the chip)
COUNTER_METRICS = ("diffusion.tokens_per_row_forward", "moe.decode_touched_pct",
                   "moe.prefill_load_max_over_mean", "slot.occupancy",
                   "slot.starved_pct", "slot.host_ms_per_window")
LISTED = COUNTER_METRICS + (
    "llm.decode_step_ms", "llm.prefill_ms", "paged_block_window_roofline",
    "paged_slot_prefill_roofline", "explain.step_mfu", "setup.program_load_s",
    "setup.programs_loaded", "setup.compiled_s", "setup.train_s",
    "setup.service_s", "jit.compiles_in_window")


@pytest.fixture(scope="module")
def family():
    return run._load_file(FAMILY, "bench_explainer_sdar_moe")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def tiny(dtype="float32", **changes):
    """The tiny configuration (tests/benchmark/fixtures): 2 layers of GQA (4
    query heads over 2 key-value heads of 16) over 8 experts, 2 a token;
    blocks of 4, 2 denoising steps, mask id 299 of 300."""
    with open(os.path.join(FIXTURES, "configs", "tiny-desk-sdar.json")) as f:
        c = json.load(f)
    c["torch_dtype"] = dtype
    for key, value in changes.items():
        if key in c["block_diffusion"]:
            c["block_diffusion"][key] = value
        else:
            c[key] = value
    return c


def tiny_model(family, dtype="float32", weights=None, **changes):
    c = tiny(dtype, **changes)
    return family.build(c, family.make_params(SEED, c, jnp.dtype(dtype).type),
                        weights or dtype)


PROMPTS = ["Caller: read me the one-time code" + " now." * (7 * i)
           + f" Customer {i} hesitates: {'no' * i}" for i in range(5)]


def framed(i):
    from fraud_detection_tpu.explain.slotserve.service import (
        shared_explain_prefix)

    return shared_explain_prefix() + PROMPTS[i]


def serve(lm, prompts, shared=True, max_new=10, window=5, temperature=0.0):
    """Every prompt through the slot lane, as the desk serves it; returns
    the tickets and the last snapshot."""
    from fraud_detection_tpu.explain.slotserve.service import SlotServeService

    svc = SlotServeService(lm, slots=2, max_new_tokens=max_new,
                           prompt_width=832, decode_window=window,
                           shared_prefix=shared, wait_timeout=600.0)
    reqs = [svc.submit(p, temperature=temperature) for p in prompts]
    for r in reqs:
        r.wait(600.0)
    snap = svc.snapshot()
    assert svc.close()
    return reqs, snap


# ---------------------------------------------------------------------------
# the cut and the counts, by hand
# ---------------------------------------------------------------------------

def test_the_cut_of_sdar_by_hand(family, cfg):
    D, H, Hkv, d, E, F, V = 2048, 32, 4, 128, 128, 768, 151936
    # q to 32 x 128, k and v to 4 x 128, o from 32 x 128; the two head norms
    attention = D * H * d + 2 * D * Hkv * d + H * d * D + 2 * d
    assert attention == 18_874_624
    router = D * E + E                              # + the selection bias
    assert attention + router == 19_136_896        # 19.1 M a layer
    expert = 3 * D * F
    assert expert == 4_718_592 == family._expert_params(cfg)
    assert E * expert == 603_979_776               # 1.21 GB in bf16
    layer = attention + router + E * expert + 2 * D
    assert family._layer_params(cfg) + 2 * D == layer == 623_120_768
    assert family._layer_params(cfg, held=False) == attention + router
    heads = 2 * V * D
    assert heads == 622_329_856                     # embedding + untied head
    total = 6 * layer + heads + D
    assert family.param_count(cfg) == total == 4_361_056_512
    assert cfg["assumed"]["parameters"] == total
    assert 8.7e9 < 2 * total < 8.75e9
    # 6 layers x k and v of 4 heads x 128 in bf16: 12 KB a position; 544
    # pages of 64 positions
    assert 6 * family.kv_bytes_per_token(cfg) == 12_288
    assert 0.42e9 < 544 * 64 * 6 * family.kv_bytes_per_token(cfg) < 0.43e9
    assert family.layer_kinds(cfg) == [("attention", "experts")] * 6
    assert family.diffusion(cfg) == {"block": 4, "steps": 2, "mask_id": 151669}


def test_decode_and_prefill_costs_by_hand(family, cfg):
    D, H, d, V = 2048, 32, 128, 151936
    expert = 3 * D * 768
    unrouted = 6 * (family._layer_params(cfg, held=False) + 2 * D) + D
    # 16 rows of 4 positions a forward: each misses an expert with 1 - 8/128
    touched = 128 * (1 - (1 - 8 / 128) ** 64)
    assert family.expected_experts_touched(cfg, 64) == pytest.approx(touched)
    assert 125.8 < touched < 126.0
    assert family.denoise_share(cfg) == pytest.approx(2 / 3)
    steps, rows, ctx = 30, 30 * 16, 1800.0
    flops, nbytes = family.decode_cost(cfg, steps, rows, ctx)
    heads = steps * (1 - (1 / 3) ** 16)            # all rows commit: never
    kv = 6 * 2 * 4 * d * 2
    want_bytes = (2 * (steps * unrouted + heads * V * D
                       + steps * 6 * touched * expert)
                  + rows * kv * (ctx + 4 + 4 / 3))
    assert nbytes == pytest.approx(want_bytes)
    # ~8.3 GB a forward: 7.13 GB of experts, 0.62 GB of head, 0.35 GB of the
    # rows' K/V, 0.23 GB of attention and router weights
    assert 8.2e9 < nbytes / steps < 8.5e9
    flops_m, nbytes_m = family.decode_cost(cfg, steps, rows, ctx,
                                           experts_touched=steps * 6 * 100.0)
    assert flops_m == flops
    assert nbytes - nbytes_m == pytest.approx(
        steps * 2 * 6 * (touched - 100.0) * expert)
    # a position: 2 flops a weight it multiplies (its 8 picks), attention
    # over the context and its block, the head on two forwards of three
    position = (2 * 6 * (family._layer_params(cfg, held=False) + 8 * expert)
                + 6 * 4 * H * d * (ctx + 4) + (2 / 3) * 2 * V * D)
    assert flops == pytest.approx(rows * 4 * position)
    # 293 + 1,203 tokens: the preamble's 292 cached, whole blocks to 1,496,
    # 1,204 positions prefilled (301 blocks) causal by blocks
    f, b = family.prefill_cost(cfg, prefix_len=293, suffix_len=1203)
    ctx_sum = 1204 * 292 + 16 * 301 * 302 / 2
    want = (1204 * 2 * 6 * (family._layer_params(cfg, held=False) + 8 * expert)
            + 6 * 4 * H * d * ctx_sum + 2 * V * D)
    assert f == pytest.approx(want)
    all_touched = 128 * (1 - (1 - 8 / 128) ** 1204)
    assert b == pytest.approx(2 * (unrouted + V * D + 6 * all_touched * expert)
                              + kv * (292 + 1204))


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
    assert set(where) == {"build"}
    family = run._load_file(FAMILY, "bench_explainer_sdar_moe")
    assert all(hasattr(family, f) for f in run.FAMILY_FUNCTIONS)


def test_configuration_states_its_cut_and_its_limit(spec, cfg):
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) < 200
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["num_hidden_layers"] == 6
    assert "8 stages of 6 layers" in cfg["deployment"]
    # every key of the published config.json, as published, but the cut one
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: cfg[k] for k in published} == published
    for key in ("block_length", "denoising_steps", "remasking_strategy",
                "mask_token_id", "qk_norm", "weights", "tokenizer", "window",
                "torch_dtype", "router", "attention", "prompt", "generation"):
        assert cfg["assumed"][key], key
    base = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "desk-lr-kimi-linear-48b-a3b.json")))
    for part in ("classifier", "engine", "guarantees"):
        assert cfg["desk"][part] == base["desk"][part]
    for key in ("slots", "paged", "page_size", "prompt_width",
                "max_new_tokens", "temperature"):
        assert cfg["desk"]["explain"][key] == base["desk"]["explain"][key]
    assert cfg["desk"]["trace_programs"]["decode"] == ["paged_block_window"]
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
    for name, layer, source in (
            ("diffusion.tokens_per_row_forward", "model step", "program_counter"),
            ("paged_block_window_roofline", "kernels", "device_trace")):
        m = by[name]
        assert (m["layer"], m["moves"], m["source"], m["workloads"]) == (
            layer, "explanations_per_s", source, [CELL])
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"explanations_per_s", "setup_s"}
    # the autoregressive window's roofline would misname this program
    assert CELL not in by["paged_decode_window_roofline"]["workloads"]


@pytest.mark.parametrize("name", ["diffusion.tokens_per_row_forward",
                                  "paged_block_window_roofline"])
def test_new_readers_find_nothing_on_a_program_without_them(spec, name):
    """Laid over the parent's checkout (no counters, no such program) each
    new reader returns None, not 0."""
    marks = {k: {"slots": 2, "decode_steps": 10 * i, "occupancy": 0.5}
             for i, k in enumerate(("open", "trace_start", "trace_stop", "close"))}
    ctx = {"cfg": {"desk": {"trace_programs": {"decode": ["paged_decode_window"]}}},
           "marks": marks, "rowtrace": [], "window": (0.0, 1.0),
           "trace_window": (0.0, 1.0), "trace": None, "tickets": []}
    read = run.load_reader(spec, name)
    assert read(ctx) is None
    if name == "diffusion.tokens_per_row_forward":
        for i, k in enumerate(marks):
            marks[k]["diffusion_row_forwards"] = 30 * i
            marks[k]["diffusion_tokens_committed"] = 40 * i
        assert read(ctx) == pytest.approx(4 / 3)


# ---------------------------------------------------------------------------
# the program's pieces
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    # (started masked, step, confidences, masked, committed)
    (4, 0, [0.2, 0.9, 0.5, 0.9], [1, 1, 1, 1], [0, 1, 0, 1]),
    (4, 1, [0.2, 0.9, 0.5, 0.9], [1, 0, 1, 0], [1, 0, 1, 0]),
    (3, 0, [0.7, 0.3, 0.6, 0.1], [0, 1, 1, 1], [0, 1, 1, 0]),
    (3, 1, [0.7, 0.3, 0.6, 0.1], [0, 0, 0, 1], [0, 0, 0, 1]),
    (1, 0, [0.4, 0.4, 0.4, 0.4], [0, 0, 0, 1], [0, 0, 0, 1]),
    (4, 0, [0.5, 0.5, 0.5, 0.5], [1, 1, 1, 1], [1, 1, 0, 0]),
    # a committed position's confidence, however high, takes no place
    (2, 0, [0.99, 0.98, 0.2, 0.3], [0, 0, 1, 1], [0, 0, 0, 1]),
    (2, 1, [0.99, 0.98, 0.9, 0.3], [0, 0, 1, 0], [0, 0, 1, 0]),
])
def test_the_rules_commit_what_they_should(family, case):
    """Program and reference alike: the schedule's count of the masked
    positions, most confident first and ties to the lower position."""
    n_new, step, conf, masked, want = case
    d = block_diffusion.BlockDiffusionConfig(mask_id=7)
    blocks = jnp.where(jnp.asarray(masked, bool), 7, 3)[None]
    got = block_diffusion.commit_positions(
        d, blocks, jnp.asarray([conf], jnp.float32),
        jnp.asarray([n_new]), jnp.asarray([step]))
    assert np.asarray(got[0]).astype(int).tolist() == want
    k = n_new // 2 + int(step < n_new % 2)
    ref = family.commit_order(np.asarray(conf), np.asarray(masked, bool), k)
    assert sorted(ref.tolist()) == np.flatnonzero(want).tolist()


@pytest.mark.parametrize("conf,masked,count,want", [
    # confidences within a hair of each other: the larger first, however
    # close, and the reference takes no other order
    ([0.0084, 0.00841, 0.0081, 0.0082], [1, 1, 1, 1], 2, [1, 0]),
    # clear ranks
    ([0.5, 0.1, 0.2, 0.3], [1, 1, 1, 1], 3, [0, 3, 2]),
    # an exact tie goes to the lower position
    ([0.3, 0.6, 0.3, 0.3], [1, 0, 1, 1], 2, [0, 2]),
    # nothing to commit
    ([0.3, 0.3, 0.3, 0.3], [0, 0, 0, 0], 2, []),
])
def test_reference_commits_by_its_own_ranking(family, conf, masked, count,
                                              want):
    got = family.commit_order(np.asarray(conf), np.asarray(masked, bool), count)
    assert got.tolist() == want


def test_the_family_serves_the_static_schedule_only(family):
    c = tiny(remasking_strategy="low_confidence_dynamic")
    with pytest.raises(ValueError, match="low_confidence_static"):
        family.diffusion(c)


def test_the_mask_id_is_never_chosen():
    logits = jnp.asarray([[0.0, 5.0, 9.0, 1.0], [2.0, 0.0, 9.0, 9.5]])
    ids, conf = block_diffusion.choose(logits, jnp.zeros(2), jax.random.PRNGKey(0),
                                       mask_id=2)
    assert ids.tolist() == [1, 3]
    want = 1.0 / np.sum(np.exp(np.asarray([[0.0, 5.0, 1.0], [2.0, 0.0, 9.5]])
                               - np.asarray([[5.0], [9.5]])), -1)
    np.testing.assert_allclose(np.asarray(conf), want, rtol=1e-6)
    drawn, p = block_diffusion.choose(jnp.tile(logits, (50, 1)), jnp.ones(100),
                                      jax.random.PRNGKey(3), mask_id=2)
    assert 2 not in np.asarray(drawn).tolist()
    assert np.all((np.asarray(p) > 0) & (np.asarray(p) <= 1))


def test_block_causal_flash_kernel_equals_masked_scores():
    """The flash kernel causal by blocks of 4 (interpret mode) against
    materialized scores under the same mask, a suffix at offset 8."""
    from fraud_detection_tpu.ops.attention import flash_attention

    r = np.random.default_rng(0)
    q = jnp.asarray(r.standard_normal((1, 24, 4, 64)), jnp.float32)
    k = jnp.asarray(r.standard_normal((1, 40, 2, 64)), jnp.float32)
    v = jnp.asarray(r.standard_normal((1, 40, 2, 64)), jnp.float32)
    pos = 8 + jnp.arange(24)
    mask = jnp.arange(40)[None, :] <= llm._sees_to(pos, 4)[:, None]
    want = llm._attend(q, k, v, mask)
    got = flash_attention(q, k, v, q_offset=8, block=4, blk_q=8, blk_k=8,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    with pytest.raises(ValueError, match="power of two"):
        flash_attention(q, k, v, q_offset=6, block=4, interpret=True)


# ---------------------------------------------------------------------------
# the tiny model against its plain float32 reference
# ---------------------------------------------------------------------------

def test_block_causal_forward_matches_reference(family):
    """The plain forward of the program, causal by blocks, against the
    reference's full forward (float32: the two differ in the order of sums
    only; logits of scale ~1, widest error measured 2e-6)."""
    lm = tiny_model(family)
    toks = np.random.default_rng(5).integers(0, 258, (2, 90)).astype(np.int32)
    got, _ = jax.jit(lambda p, t: llm.forward(p, t, lm.cfg))(
        lm.params, jnp.asarray(toks))
    ref = family.reference_logits(SEED, tiny(), "float32", toks)
    assert np.abs(np.asarray(got) - np.asarray(ref)).max() < 1e-4
    # and it is not the causal forward: the block's later positions count
    causal = family.reference_logits(
        SEED, tiny(), "float32", toks,
        sees=np.broadcast_to(np.tril(np.ones((90, 90), bool)), (2, 90, 90)))
    assert np.abs(np.asarray(causal) - np.asarray(ref)).max() > 1e-2


@pytest.fixture(scope="module")
def probed(family):
    """Every denoising forward's logits through the pages, as the lane
    computes them, beside the reference's at the same block state: one
    prompt admitted behind the preamble's pages, then one forward at a time
    through ``paged_block_window`` (so commits write the pages as served),
    each block state read before the forward advances it."""
    from fraud_detection_tpu.explain.slotserve.decode import PagedSlotDecoder
    from fraud_detection_tpu.explain.slotserve.service import (
        shared_explain_prefix)

    lm = tiny_model(family)
    dec = PagedSlotDecoder(lm, 2, prompt_width=832, max_new_tokens=12,
                           prefix_text=shared_explain_prefix())
    toks, _ = dec.encode_prompt(framed(2))
    assert len(toks) % 4 == 3                    # a tail of 3 in the first block
    dec.prefill(1, toks, 0.0, 0)
    B, D = 4, lm.cfg.d_model
    base = dec.committed_len(len(toks))

    @jax.jit
    def logits_of(params, pages, tables, blocks, lens):
        view = llm._gather_view(pages, tables)
        x, _, _ = block_diffusion._block_forward(
            params, lm.cfg, view, blocks, lens, jnp.ones(2, bool))
        return llm._logits_head(x.reshape(-1, D), params, lm.cfg).reshape(
            2, B, -1)

    lens = np.zeros(2, np.int32)
    lens[1] = base
    active = np.asarray([False, True])
    out, pairs = [], []
    while active[1]:
        dec.grow_for_window(1, int(lens[1]), 1)
        blocks = dec._blocks.copy()
        if (blocks[1] == 299).any():
            got = logits_of(lm.params, dec.pages, jnp.asarray(dec._tables),
                            jnp.asarray(blocks), jnp.asarray(lens))[1]
            pairs.append((int(lens[1]), blocks[1].copy(), np.asarray(got)))
        served, counts, lens, _, _ = dec.block_step(
            lens, active, np.asarray([0, 12 - len(out)], np.int32),
            np.zeros(2, np.float32), 0, 1)
        out = (out + served[1, :counts[1]].tolist())[:12]   # the budget
        if counts[1] and (12 - len(out) <= 0 or 257 in out):
            active[1] = False
    # the reference at each state: the prompt's whole blocks and every block
    # committed since, then the block in flight (masks and all)
    whole = np.concatenate([toks, out])
    refs = []
    for at, block, _ in pairs:
        seq = np.concatenate([whole[:at], block])[None]
        refs.append(np.asarray(family.reference_logits(
            SEED, tiny(), "float32", seq))[0, at:])
    dec.close()
    return pairs, refs, toks, out


def test_every_denoising_forward_through_the_pages_matches_reference(probed):
    """Float32: the paged lane's logits at every denoising state equal the
    reference's full forward of the same sequence (order of sums only; of
    scale ~1, widest error measured 3e-6)."""
    pairs, refs, toks, out = probed
    assert len(pairs) >= 6          # 1 for the tail's block, then 2 a block
    for (_, block, got), ref in zip(pairs, refs):
        assert np.abs(got - ref).max() < 1e-4
    assert len(out) == 12 and 299 not in out


def test_lane_serves_the_references_trajectory(family, probed):
    """What the lane served, replayed by the reference's own diffusion
    trajectory (``token_gaps``), is its best everywhere in float32, and a
    token from a trajectory the reference did not take is not."""
    _, _, toks, out = probed
    gaps = family.token_gaps(SEED, tiny(), "float32",
                             [{"prompt": toks, "served": np.asarray(out)}],
                             832 + 12)
    assert gaps[0].max() <= family.REGRET_FLOOR
    wrong = np.asarray(out).copy()
    wrong[5] = (wrong[5] + 1) % 258
    gaps = family.token_gaps(SEED, tiny(), "float32",
                             [{"prompt": toks, "served": wrong}], 832 + 12)
    assert gaps[0][5] > 1e-3


def test_prefix_cut_at_a_block_boundary_serves_what_no_sharing_serves(family):
    """The preamble is shared to its last whole block (292 of 293 tokens:
    its last token shares a block with the transcript); admission of whole
    prompts serves the same tokens, and every token served is the
    reference's best along its own trajectory."""
    lm = tiny_model(family)
    prompts = [framed(i) for i in range(4)]
    served = {}
    for shared in (True, False):
        reqs, snap = serve(lm, prompts, shared=shared)
        assert snap["completed"] == 4
        assert snap["prefix_hits"] == (4 if shared else 0)
        served[shared] = [(np.asarray(r.tokens), np.asarray(r.out)) for r in reqs]
        if shared:
            assert snap["prefix_pages"] == 5          # 292 positions of 64
            assert snap["diffusion_blocks_committed"] > 0
            assert (snap["diffusion_tokens_committed"]
                    > snap["diffusion_row_forwards"])
    for (_, a), (_, b) in zip(served[True], served[False]):
        assert a.tolist() == b.tolist()
    gaps = family.token_gaps(SEED, tiny(), "float32",
                             [{"prompt": p, "served": s}
                              for p, s in served[True]], 832 + 12)
    assert max(float(g.max()) for g in gaps) <= family.REGRET_FLOOR


def test_eos_inside_a_block_stops_the_row(family):
    """With the head's EOS row set to a copy of a token the lane serves
    mid-block, the lane serves EOS there and nothing after it, and the
    reference replays the row's trajectory (positions of the block past EOS
    were committed but not served)."""
    lm = tiny_model(family)
    reqs, _ = serve(lm, [framed(3)], max_new=12)
    out = reqs[0].out
    token = out[2]                               # inside the first block
    head = lm.params["lm_head"]
    lm.params["lm_head"] = head.at[257].set(head[token] * 1.01)
    reqs, snap = serve(lm, [framed(3)], max_new=12)
    got = reqs[0].out
    assert got[-1] == 257 and 257 not in got[:-1]
    assert len(got) < 12 and snap["completed"] == 1
    assert got[:got.index(257)] == out[:got.index(257)]
    c = tiny()
    gaps = family.token_gaps(SEED, c, "float32",
                             [{"prompt": reqs[0].tokens,
                               "served": np.asarray(got)}], 832 + 12)
    lm.params["lm_head"] = head
    assert len(gaps[0]) == len(got)


def test_the_mask_id_is_never_served(family):
    """With the mask id's head row the largest by far, every position's
    argmax would be it: the lane serves other ids only."""
    lm = tiny_model(family)
    head = lm.params["lm_head"]
    lm.params["lm_head"] = head.at[299].set(head[100] * 50.0)
    reqs, _ = serve(lm, [framed(0), framed(1)], max_new=8)
    lm.params["lm_head"] = head
    for r in reqs:
        assert len(r.out) == 8 and 299 not in r.out


def test_a_temperature_draws_and_serves_valid_ids(family):
    lm = tiny_model(family)
    reqs, snap = serve(lm, [framed(0), framed(4)], max_new=8, temperature=1.0)
    for r in reqs:
        assert len(r.out) == 8 and all(0 <= t < 299 for t in r.out)
    assert snap["tokens_out"] == 16


def test_block_window_names_its_parts(family):
    lm = tiny_model(family)
    c = lm.cfg
    pages = llm.init_kv_pages(c, 8, 64)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)     # noqa: E731
    text = block_diffusion.paged_block_window.lower(
        lm.params, i32(2, 4), i32(2), i32(2), i32(2), jnp.ones(2, bool),
        i32(2), c, pages, i32(2, 4), jnp.zeros(2), jax.random.PRNGKey(0),
        4).as_text(debug_info=True)
    for scope in ("diffusion.denoise", "diffusion.select", "diffusion.commit",
                  "moe.experts", "attn.qk_norm"):
        assert f"/{scope}" in text, scope


# ---------------------------------------------------------------------------
# the other families' programs hold nothing of generation by diffusion
# ---------------------------------------------------------------------------

OTHER_FAMILIES = {"internlm2": "tiny-desk", "bailing_hybrid": "tiny-desk-hybrid",
                  "longcat_flash": "tiny-desk-longcat", "lfm2_moe": "tiny-desk-lfm2",
                  "kimi_linear": "tiny-desk-kimi"}


@pytest.mark.parametrize("name", sorted(OTHER_FAMILIES))
def test_other_families_slot_programs_hold_no_diffusion(name):
    """A model that generates token by token (``cfg.diffusion`` None) lowers
    its decode window and suffix prefill with no scope of the block window,
    and its prefill's mask is the causal one: ``_sees_to`` at block 1 is
    each position itself."""
    with open(os.path.join(FIXTURES, "configs", OTHER_FAMILIES[name] + ".json")) as f:
        c = json.load(f)
    fam = run._load_file(os.path.join(REPO, "benchmark", "explainers",
                                      name + ".py"), "bench_explainer_" + name)
    lm = fam.build(c, fam.make_params(SEED, c, jnp.float32), "float32")
    m = lm.cfg
    assert m.diffusion is None and llm._block(m) == 1
    pages = llm.init_kv_pages(m, 8, 16)
    state = llm.init_state(m, 2) or None
    i32 = lambda *s: jnp.zeros(s, jnp.int32)     # noqa: E731
    key = jax.random.PRNGKey(0)
    decode = llm.paged_decode_window.lower(
        lm.params, i32(2), i32(2), jnp.ones(2, bool), i32(2), m, pages,
        i32(2, 4), jnp.zeros(2), key, 4, state)
    prefill = llm.paged_slot_prefill.lower(
        lm.params, i32(1, 32), jnp.int32(40), m, pages, i32(4),
        jnp.float32(0), key, 16, state, jnp.int32(0) if state else None)
    for program in (decode, prefill):
        text = program.as_text(debug_info=True)
        assert "/attn." in text                      # scopes are named here
        for scope in ("denoise", "select", "commit"):
            assert f"/diffusion.{scope}" not in text
    pos = jnp.arange(70)
    assert np.array_equal(np.asarray(llm._sees_to(pos, llm._block(m))),
                          np.asarray(pos))


def test_flash_kernel_at_block_one_is_the_causal_kernel():
    """``block`` 1 lowers the kernel the callers that name no block get, and
    a block of 4 reaches the kernel (another program)."""
    from fraud_detection_tpu.ops.attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 32, 4, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 48, 2, 64), jnp.float32)

    def text(**block):
        return flash_attention.lower(q, kv, kv, q_offset=16, blk_q=8, blk_k=8,
                                     interpret=True, **block).as_text()

    assert text(block=1) == text()
    assert text(block=4) != text()


# ---------------------------------------------------------------------------
# the desk at the tiny size
# ---------------------------------------------------------------------------

def test_tiny_sdar_cell_is_correct_and_reads_its_metrics(run_tiny, spec):
    # 10 s: see test_hybrid.py (a 3 s window may close before one decode
    # window has come back)
    line = run_tiny("tiny-campaign-rel", config="tiny-desk-sdar",
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
    # blocks of 4 in 3 forwards, 8 new tokens a row: more than one a forward
    assert 1.0 < line["metrics"]["diffusion.tokens_per_row_forward"]["value"] <= 4.0
    assert set(COUNTER_METRICS) <= set(line["metrics"]) <= {
        m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}


def test_tiny_sdar_int8_control_is_not_correct(run_tiny):
    line = run_tiny("tiny-campaign-rel", config="tiny-desk-sdar",
                    kind=MIX, explain_weights="int8", control=True,
                    seconds=3.0)
    assert line["control"]["correct"] is False
    value, limit = line["control"]["compared"]["token_gap_sq"]
    assert value > limit
