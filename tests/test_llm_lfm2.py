"""The convolution-attention hybrid's layer kinds (("conv", "dense" |
"experts"): a gated short convolution that keeps its filter's tail as the
row's state; ("attention", "experts") with per-head q/k norms ahead of RoPE
at four query heads a key-value head; a sigmoid router without groups whose
chosen weights are normalised with 1e-6 in the denominator, every expert
held): the tiny explainer of tests/lfm2_tiny.py against its family's plain
float32 reference (benchmark/explainers/lfm2_moe.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fraud_detection_tpu.explain.slotserve.decode import PagedSlotDecoder
from fraud_detection_tpu.explain.slotserve.service import shared_explain_prefix
from fraud_detection_tpu.models import llm
from fraud_detection_tpu.models.llm import MODEL_AXIS

import lfm2_tiny
from test_llm import _cached_logits, model_mesh
from test_paged_kv import analysis_prompts


@pytest.fixture(scope="module")
def lm():
    return lfm2_tiny.language_model("float32")


@pytest.fixture(scope="module")
def errors():
    """|program logits - reference logits| at every position of two
    120-token rows (100 prefilled, 20 decoded through the cache and the
    filters' tails), by (dtype, weights)."""
    fam = lfm2_tiny.family()
    toks = np.random.default_rng(5).integers(0, 258, (2, 120)).astype(np.int32)
    memo = {}

    def get(dtype, weights=None):
        key = (dtype, weights or dtype)
        if key not in memo:
            ref = np.asarray(fam.reference_logits(
                lfm2_tiny.SEED, lfm2_tiny.config(dtype), dtype, toks))
            got = _cached_logits(lfm2_tiny.language_model(dtype, weights),
                                 toks, 100)
            memo[key] = np.abs(got - ref)
        return memo[key]

    return get


# Tolerances of the program against the reference, logits of scale ~1.0:
# * float32, widest error 2e-5: the two differ in the order of float32 sums
#   only (the filter over a window against shifted products, grouped
#   against repeated heads, experts by sorted tiles against one by one);
#   measured 4.2e-6.
# * bfloat16, MEDIAN error 0.025: bfloat16 rounding of every matmul's
#   operands (measured median 0.0156). The widest error says nothing here
#   (1.2 either way): a routed model's logits step wherever rounding flips
#   an expert choice the float32 reference does not, and here every flipped
#   pick is computed.
# The weight-only int8 path of the same dtype fails each: float32 compute
# reads a widest error of 1.03, bfloat16 a median of 0.049.
F32_MAX, BF16_MEDIAN = 2e-5, 0.025


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


def test_quantize_and_shardings_name_every_new_leaf():
    lm = lfm2_tiny.language_model("float32", "int8")
    q8 = {n for n, w in lm.params.items() if isinstance(w, llm.Q8)}
    full = {n.split(".", 1)[-1] for n in set(lm.params) - q8}
    # full precision on purpose: norms, the filter, the router and its bias
    assert full == {"ln1", "ln2", "ln_f", "q_norm", "k_norm", "conv_w",
                    "moe_router", "moe_bias"}
    assert {n.split(".", 1)[-1] for n in q8} == {
        "embed", "conv_win", "conv_wout", "wq", "wk", "wv", "wo", "w_gate",
        "w_up", "w_down", "moe_wg", "moe_wu", "moe_wd"}
    assert lm.params["l0.conv_win"].scale.shape == (1, 3, 64)   # per stream
    assert lm.params["l0.conv_wout"].scale.shape == (1, 64)     # and channel
    assert lm.params["l2.moe_wg"].scale.shape == (8, 1, 16)     # per expert
    # the tied head has no leaf of its own; the dense layers no router; the
    # one attention layer alone the two norms
    assert "lm_head" not in lm.params and "l1.moe_router" not in lm.params
    assert [n for n in lm.params if n.endswith("q_norm")] == ["l2.q_norm"]
    lm = lfm2_tiny.language_model("float32")
    mesh = model_mesh(2)
    sh = llm.param_shardings(lm.cfg, mesh)
    assert set(sh) == set(lm.params) == set(
        llm.init_params(jax.random.PRNGKey(0), lm.cfg))
    placed = llm.shard_params(lm.params, lm.cfg, mesh)
    P = jax.sharding.PartitionSpec
    # channels over the model axis up to the output projection
    assert placed["l0.conv_win"].sharding.spec == P(None, None, MODEL_AXIS)
    assert placed["l0.conv_w"].sharding.spec == P(None, MODEL_AXIS)
    assert placed["l0.conv_wout"].sharding.spec == P(MODEL_AXIS, None)
    assert placed["l2.q_norm"].sharding.spec == P()
    assert placed["l2.wk"].sharding.spec == P(None, MODEL_AXIS, None)
    assert placed["l3.moe_wd"].sharding.spec == P(None, MODEL_AXIS, None)
    # the state is by name: one tail a convolution layer, nothing else
    assert {n: a.shape for n, a in llm.init_state(lm.cfg, 3).items()} == {
        f"l{l}.tail": (3, 2, 64) for l in (0, 1, 3, 4, 5)}
    assert set(llm.init_kv_pages(lm.cfg, 4, 64)) == {"l2.k", "l2.v"}


def _slot_tails(dec, slot):
    return {n: np.asarray(a[slot], np.float32) for n, a in dec.state.items()}


def test_the_state_is_the_sequences(lm):
    """One prompt three ways: prefilled whole (right-padded to its bucket),
    as the preamble's snapshot + its suffix, and one token at a time. The
    same final filter tails, the same logits at the last token (so the same
    first token), the same decode behind them. Float32, 2e-5."""
    cfg = lm.cfg
    prompt = analysis_prompts(1)[0]
    shared = PagedSlotDecoder(lm, 2, prompt_width=1088, max_new_tokens=8,
                              prefix_text=shared_explain_prefix())
    whole = PagedSlotDecoder(lm, 2, prompt_width=1088, max_new_tokens=8)
    toks, _ = shared.encode_prompt(prompt)
    assert len(toks) % shared.prompt_bucket                # padding in the bucket
    first = [d.prefill(1, toks, 0.0, 0) for d in (shared, whole)]
    assert (shared.prefix_hits, shared.cow_copies, shared.state_restores) == (1, 1, 1)
    assert (whole.prefix_hits, whole.cow_copies, whole.state_restores) == (0, 0, 1)
    # the preamble's snapshot holds filter tails and nothing else
    assert set(shared._prefix_state) == {f"l{l}.tail" for l in (0, 1, 3, 4, 5)}

    # one token at a time through forward's cache (T == 1: the tail shifts)
    step = jax.jit(lambda p, t, pos, c: llm.forward(
        p, t, cfg, positions=jnp.full((1, 1), pos), kv_cache=c, cache_len=pos))
    cache = llm.init_cache(cfg, 1, len(toks))
    for t, tok in enumerate(toks.tolist()):
        logits, cache = step(lm.params, jnp.asarray([[tok]]), jnp.int32(t), cache)
    # ... and the whole prompt in one stateless pass
    full, _ = llm.forward(lm.params, jnp.asarray(toks)[None], cfg)
    np.testing.assert_allclose(np.asarray(logits[0, 0]), np.asarray(full[0, -1]),
                               atol=2e-5)
    assert first[0] == first[1] == int(jnp.argmax(full[0, -1]))
    for name, want in _slot_tails(whole, 1).items():
        np.testing.assert_allclose(_slot_tails(shared, 1)[name], want, atol=2e-5)
        np.testing.assert_allclose(np.asarray(cache[name][0]), want, atol=2e-5)
        assert np.abs(want).max() > 1e-3
    outs = []
    for d in (shared, whole):
        assert d.grow_for_window(1, len(toks), 4)
        out, *_ = d.step(np.asarray([0, first[0]], np.int32),
                         np.asarray([0, len(toks)], np.int32),
                         np.asarray([False, True]), np.asarray([0, 4], np.int32),
                         np.zeros(2, np.float32), 0, 4)
        outs.append(out[1].tolist())
        d.close()
        assert d.leaked_pages == 0
    assert outs[0] == outs[1]


def test_padding_leaves_the_tail_alone(lm):
    """``_conv_mix`` over a padded input: the tail going out is the two
    gated inputs ahead of the position after the last real token, whatever
    lies in the padding, and equals the tail after the real tokens alone."""
    cfg = lm.cfg
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((2, 12, cfg.d_model)), jnp.float32)
    x = jnp.zeros_like(h)
    tail0 = jnp.asarray(rng.standard_normal((2, 2, cfg.d_model)), jnp.float32)
    lens = np.asarray([12, 7])
    real = jnp.arange(12)[None, :] < jnp.asarray(lens)[:, None]
    y, tail = llm._conv_mix(lm.params, cfg, 0, x, h, tail0, real)
    for b, n in enumerate(lens):
        y_b, tail_b = llm._conv_mix(lm.params, cfg, 0, x[b:b + 1, :n],
                                    h[b:b + 1, :n], tail0[b:b + 1], None)
        np.testing.assert_allclose(np.asarray(tail[b]), np.asarray(tail_b[0]),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(y[b, :n]), np.asarray(y_b[0]),
                                   atol=1e-5)
    # a row of padding alone hands its tail on untouched
    _, kept = llm._conv_mix(lm.params, cfg, 0, x, h, tail0, jnp.zeros_like(real))
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(tail0))


def test_qk_norms_on_and_off_differ_and_grouped_equals_expanded(lm):
    cfg, l = lm.cfg, 2
    assert cfg.qk_norm and cfg.n_heads // cfg.kv_heads == 4
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((2, 9, cfg.d_model)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(9), (2, 9))
    params = dict(lm.params)
    params["l2.q_norm"] = jnp.asarray(rng.uniform(0.5, 2.0, cfg.head_dim),
                                      jnp.float32)
    q, k, v = llm._qkv(params, cfg, l, h, pos)
    off = dataclasses.replace(cfg, qk_norm=False)
    q0, k0, v0 = llm._qkv(params, off, l, h, pos)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v0))
    assert np.abs(np.asarray(q) - np.asarray(q0)).max() > 0.1
    assert np.abs(np.asarray(k) - np.asarray(k0)).max() > 0.1
    # the norm by hand: unit RMS a head times its gamma, then the rotation
    raw = jnp.einsum("btD,Dhd->bthd", h, params["l2.wq"])
    unit = raw * jax.lax.rsqrt(jnp.mean(raw * raw, -1, keepdims=True) + cfg.rms_eps)
    np.testing.assert_allclose(
        np.asarray(q), np.asarray(llm.rope(unit * params["l2.q_norm"], pos,
                                           cfg.rope_theta)), atol=1e-5)
    # four query heads on each key-value head: contracting against the
    # narrow k/v is attention over the heads repeated
    mask = jnp.tril(jnp.ones((9, 9), bool))
    grouped = llm._attend(q, k, v, mask)
    expanded = llm._attend(q, jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2),
                           mask)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(expanded),
                               atol=1e-6)
    # a model without the field has no such leaf and lowers as it did
    assert "q_norm" not in llm._layer_shapes(off, "attention", "experts")


def test_router_picks_by_score_plus_bias_and_weighs_by_score_with_the_epsilon():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((8, 10)), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.5, 0.5, 10), jnp.float32)
    m = llm.MoEConfig(n_experts=10, top_k=3, n_group=1, topk_group=1,
                      routed_scale=1.0, norm_eps=1e-6)
    idx, w = llm.moe_route(router, bias, x, m)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, router, precision="highest")),
                   np.float64)
    want = np.argsort(-(s + np.asarray(bias)), -1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want, -1))
    # ... which is not the choice by the bare scores for every token
    assert not np.array_equal(np.sort(want, -1),
                              np.sort(np.argsort(-s, -1)[:, :3], -1))
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # the epsilon is there: scores small enough that it shows
    tiny = jnp.full((8, 10), -3.0, jnp.float32) * jnp.abs(router)
    small = jnp.abs(x) + 1.0
    _, w_eps = llm.moe_route(tiny, bias, small, m)
    _, w_bare = llm.moe_route(tiny, bias, small,
                              dataclasses.replace(m, norm_eps=0.0))
    np.testing.assert_allclose(np.asarray(w_bare).sum(-1), 1.0, rtol=1e-6)
    assert np.all(np.asarray(w_eps).sum(-1) < np.asarray(w_bare).sum(-1))


def test_the_shares_add_up_to_the_whole_layer(lm):
    """Two halves of the experts (``held_start`` 0 and 4, ``held_count`` 4:
    each computes the picks that land on its own experts, routing over all
    eight) sum to the whole layer, which is the reference's. Float32,
    tolerance 2e-5 on outputs of scale ~1."""
    fam = lfm2_tiny.family()
    cfg, l = lm.cfg, 3
    assert (cfg.moe.held_start, cfg.moe.held, cfg.moe.n_experts) == (0, 8, 8)
    u = jnp.asarray(np.random.default_rng(1).standard_normal((1, 50, 64)),
                    jnp.float32)
    whole, stats = llm._expert_branch(lm.params, cfg, l, u, None)
    assert int(stats["picks"]) == int(stats["picks_held"]) == 50 * 4
    key = jax.random.fold_in(fam._root_key(lfm2_tiny.SEED), l)
    with jax.default_matmul_precision("highest"):
        want = fam.expert_layer(key, lfm2_tiny.config("float32"), jnp.float32,
                                "conv", u.reshape(-1, 64)).reshape(u.shape)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=2e-5)
    total, held_picks = 0.0, 0
    for first in (0, 4):
        half = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held_start=first, held_count=4))
        params = dict(lm.params)
        for name in ("moe_wg", "moe_wu", "moe_wd"):
            params[f"l{l}.{name}"] = lm.params[f"l{l}.{name}"][first:first + 4]
        part, stats = llm._expert_branch(params, half, l, u, None)
        with jax.default_matmul_precision("highest"):
            ref = fam.expert_layer(key, lfm2_tiny.config("float32"),
                                   jnp.float32, "conv", u.reshape(-1, 64),
                                   first, 4).reshape(u.shape)
        np.testing.assert_allclose(np.asarray(part), np.asarray(ref), atol=2e-5)
        assert np.abs(np.asarray(part)).max() > 0.05
        total = total + part
        held_picks += int(stats["picks_held"])
        assert int(stats["picks"]) == 50 * 4
    assert held_picks == 50 * 4              # every pick lands on one half
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-5)


@pytest.mark.parametrize("kinds,sized", [
    ((("conv", "dense"),), {}),                            # no cfg.conv
    ((("scan", "dense"),), {"conv": llm.ConvConfig()}),    # no such mixer
])
def test_a_conv_layer_needs_its_config(kinds, sized):
    with pytest.raises(ValueError, match="needs cfg.conv|MIXERS"):
        llm.TransformerConfig(n_layers=1, layer_kinds=kinds, **sized)
