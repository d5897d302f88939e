"""The shortcut-connected routed layer kinds (("mla", "dense+experts") then
("mla", "dense+join"): two latent attentions with a query latent and both
latent scales, two dense MLPs, an expert branch that joins one sub-layer
later, a softmax router over routed and zero-compute outputs): the tiny
explainer of tests/longcat_tiny.py against its family's plain float32
reference (benchmark/explainers/longcat_flash.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fraud_detection_tpu.models import llm
from fraud_detection_tpu.models.llm import MODEL_AXIS

import longcat_tiny
from test_llm import _cached_logits, model_mesh


@pytest.fixture(scope="module")
def errors():
    """|program logits - reference logits| at every position of two
    120-token rows (100 prefilled, 20 decoded through the cache), by
    (dtype, weights)."""
    fam = longcat_tiny.family()
    toks = np.random.default_rng(5).integers(0, 258, (2, 120)).astype(np.int32)
    memo = {}

    def get(dtype, weights=None):
        key = (dtype, weights or dtype)
        if key not in memo:
            ref = np.asarray(fam.reference_logits(
                longcat_tiny.SEED, longcat_tiny.config(dtype), dtype, toks))
            got = _cached_logits(longcat_tiny.language_model(dtype, weights),
                                 toks, 100)
            memo[key] = np.abs(got - ref)
        return memo[key]

    return get


# Tolerances of the program against the reference, logits of scale ~0.8:
# * float32, widest error 2e-5: the two differ in the order of float32 sums
#   only (absorbed against expanded latent attention, experts by sorted
#   tiles against one by one); measured 2.3e-6.
# * bfloat16, MEDIAN error 0.008: bfloat16 rounding of every matmul's
#   operands (measured median 0.0055). The widest error says nothing here
#   (0.54 either way): a routed model's logits step wherever rounding flips
#   an expert choice the float32 reference does not.
# The weight-only int8 path of the same dtype fails each: float32 compute
# reads a widest error of 0.54, bfloat16 a median of 0.0124.
F32_MAX, BF16_MEDIAN = 2e-5, 0.008


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
    lm = longcat_tiny.language_model("float32", "int8")
    q8 = {n for n, w in lm.params.items() if isinstance(w, llm.Q8)}
    full = {n.split(".", 1)[-1] for n in set(lm.params) - q8}
    # full precision on purpose: norms, the router and its bias
    assert full == {"ln1", "ln2", "ln_f", "mla_kvnorm", "mla_qnorm",
                    "moe_router", "moe_bias"}
    assert {n.split(".", 1)[-1] for n in q8} == {
        "embed", "lm_head", "mla_wqa", "mla_wqb", "mla_wkva", "mla_wkvb",
        "mla_wo", "w_gate", "w_up", "w_down", "moe_wg", "moe_wu", "moe_wd"}
    assert lm.params["l0.mla_wqa"].scale.shape == (1, 24)
    assert lm.params["l1.mla_wqb"].scale.shape == (1, 4, 12)
    assert lm.params["l0.moe_wg"].scale.shape == (4, 1, 16)     # per expert
    # the branch lives in the first sub-layer of a layer alone, no layer has
    # a one-matrix query, a head gate or a shared expert
    assert "l0.moe_router" in lm.params and "l1.moe_router" not in lm.params
    assert lm.params["l2.moe_router"].shape == (32, 24)          # 16 + 8
    assert not [n for n in lm.params
                if n.endswith((".mla_wq", ".mla_wz", ".moe_sg"))]
    lm = longcat_tiny.language_model("float32")
    mesh = model_mesh(2)
    sh = llm.param_shardings(lm.cfg, mesh)
    assert set(sh) == set(lm.params) == set(
        llm.init_params(jax.random.PRNGKey(0), lm.cfg))
    placed = llm.shard_params(lm.params, lm.cfg, mesh)
    P = jax.sharding.PartitionSpec
    assert placed["l0.mla_wqa"].sharding.spec == P()
    assert placed["l3.mla_wqb"].sharding.spec == P(None, MODEL_AXIS, None)
    assert placed["l2.moe_wd"].sharding.spec == P(None, MODEL_AXIS, None)
    assert placed["l1.w_gate"].sharding.spec == P(None, MODEL_AXIS)


def test_absorbed_decode_equals_expanded_with_query_latent_and_scales():
    """One query a row against the cached latents, absorbed (decode) against
    expanded (prefill's path), with the query through its own latent and
    both scales on (the cached latent carries kv_scale). Float32, 1e-5."""
    lm = longcat_tiny.language_model("float32")
    cfg, l = lm.cfg, 1
    assert cfg.mla.q_rank == 24 and cfg.mla.q_scale == pytest.approx(
        (32 / 24) ** 0.5) and cfg.mla.kv_scale == pytest.approx(2 ** 0.5)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((3, 1, cfg.d_model)), jnp.float32)
    lat = jnp.asarray(rng.standard_normal((3, 40, 1, cfg.mla.latent_dim)),
                      jnp.float32)
    valid = jnp.arange(40)[None, None, :] <= jnp.asarray([39, 7, 20])[:, None, None]
    q, own = llm._mla_project(lm.params, cfg, l, h, jnp.asarray([[39], [7], [20]]))
    a = llm._mla_absorbed(lm.params, cfg, l, q, lat, valid)
    b = llm._mla_expanded(lm.params, cfg, l, q, lat, valid)
    assert a.shape == b.shape == (3, 1, cfg.n_heads, cfg.mla.v_dim)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # the scales are where MLAConfig says: on the query, and on the latent
    # the cache holds (its norm's unit RMS times kv_scale)
    plain = llm.LanguageModel(llm.TransformerConfig(**{
        **cfg.__dict__, "mla": llm.MLAConfig(**{
            **cfg.mla.__dict__, "q_scale": 1.0, "kv_scale": 1.0})}), lm.params)
    q1, own1 = llm._mla_project(plain.params, plain.cfg, l, h,
                                jnp.asarray([[39], [7], [20]]))
    np.testing.assert_allclose(np.asarray(q), np.asarray(q1) * cfg.mla.q_scale,
                               rtol=1e-6)
    r = cfg.mla.kv_rank
    np.testing.assert_allclose(np.asarray(own[..., :r]),
                               np.asarray(own1[..., :r]) * cfg.mla.kv_scale,
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(own[..., r:]),
                                  np.asarray(own1[..., r:]))


def test_the_shares_add_up_to_the_whole_layer():
    """The four shares' routed parts (each chip computes the picks that land
    on its own 4 of the 16 routed experts), with the zero-compute part and
    the two dense MLPs counted once, are the uncut reference's whole layer.
    Float32, tolerance 2e-5 on outputs of scale ~1."""
    fam = longcat_tiny.family()
    whole = longcat_tiny.config("float32", n_routed_experts=16)
    key = jax.random.fold_in(fam._root_key(longcat_tiny.SEED), 0)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 50, 32)),
                    jnp.float32)
    _, layer, _ = fam._reference_fns(whole, "float32")
    want = np.asarray(layer(key, x))
    act = jax.nn.silu

    def sub_layers(lm, branch_of):
        """Layer 0 through the program's own blocks (no cache), the expert
        branch replaced by ``branch_of(u)``."""
        cfg, p = lm.cfg, lm.params
        pos = jnp.arange(50)[None]
        mask = jnp.tril(jnp.ones((50, 50), bool))
        y, branch = x, None
        for l in (0, 1):
            h = llm.rms_norm(y, p[f"l{l}.ln1"], cfg.rms_eps)
            q, lat = llm._mla_project(p, cfg, l, h, pos)
            y = llm._mla_out(p, cfg, l, y, h,
                             llm._mla_expanded(p, cfg, l, q, lat, mask))
            if l == 0:
                branch = branch_of(llm.rms_norm(y, p["l0.ln2"], cfg.rms_eps))
            y = llm._dense_mlp(p, cfg, l, y, act)
        return y + branch

    def first_attention(lm):
        cfg, p = lm.cfg, lm.params
        h = llm.rms_norm(x, p["l0.ln1"], cfg.rms_eps)
        q, lat = llm._mla_project(p, cfg, 0, h, jnp.arange(50)[None])
        h1 = llm._mla_out(p, cfg, 0, x, h, llm._mla_expanded(
            p, cfg, 0, q, lat, jnp.tril(jnp.ones((50, 50), bool))))
        return llm.rms_norm(h1, p["l0.ln2"], cfg.rms_eps)

    total, held_picks, zero_picks, zero_part = 0.0, 0, set(), None
    for first in (0, 4, 8, 12):
        lm = longcat_tiny.language_model(
            "float32", expert_share={"first": first, "chips_sharing_a_layer": 4})
        assert lm.cfg.moe.held_start == first and lm.cfg.moe.held == 4
        assert lm.cfg.moe.n_router == 24 and lm.cfg.moe.n_zero == 8
        u = first_attention(lm)              # every share's alike
        both, stats = llm._expert_branch(lm.params, lm.cfg, 0, u, None)
        # what this share's own experts give, by the reference; the rest of
        # the program's branch is the zero-compute part, every share's alike
        with jax.default_matmul_precision("highest"):
            routed = fam.expert_branch(key, whole, jnp.float32,
                                       u.reshape(-1, 32), first, 4,
                                       zero_part=False).reshape(u.shape)
        zero = np.asarray(both - routed)
        zero_part = zero if zero_part is None else zero_part
        np.testing.assert_allclose(zero, zero_part, atol=2e-5)
        total = total + routed
        held_picks += int(stats["picks_held"])
        zero_picks.add(int(stats["picks_zero"]))
        assert int(stats["picks"]) == 50 * 4
    # every pick lands on one share's experts or on a zero-compute output,
    # which every share counts alike
    assert len(zero_picks) == 1 and held_picks + zero_picks.pop() == 50 * 4
    assert np.abs(zero_part).max() > 0.1 and np.abs(np.asarray(total)).max() > 0.1
    got = sub_layers(lm, lambda u: total + jnp.asarray(zero_part))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_a_token_of_zero_compute_picks_alone_reads_no_expert():
    """With the correction bias pushing every choice onto the zero-compute
    outputs, a token reads no expert (``experts_touched`` 0, no held pick)
    and still gets ``(sum of its weights) x u``."""
    lm = longcat_tiny.language_model("float32")
    cfg = lm.cfg
    params = dict(lm.params)
    params["l0.moe_bias"] = jnp.where(jnp.arange(24) >= 16, 10.0, 0.0)
    u = jnp.asarray(np.random.default_rng(2).standard_normal((2, 6, 32)),
                    jnp.float32)
    out, stats = llm._expert_branch(params, cfg, 0, u, None)
    assert int(stats["experts_touched"]) == int(stats["picks_held"]) == 0
    assert int(stats["picks_zero"]) == int(stats["picks"]) == 2 * 6 * 4
    p = jax.nn.softmax(jnp.einsum("btd,de->bte", u, params["l0.moe_router"],
                                  precision="highest"), -1)
    w = 6.0 * jnp.sum(jax.lax.top_k(p[..., 16:], 4)[0], -1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(w * u), rtol=1e-5,
                               atol=1e-6)
    # rows that are not live are routed nowhere and counted nowhere
    live = jnp.asarray([[True] * 6, [False] * 6])
    _, stats = llm._expert_branch(params, cfg, 0, u, live)
    assert int(stats["picks_zero"]) == int(stats["picks"]) == 6 * 4


def test_router_kinds():
    """``moe_route``: softmax over all outputs, no groups, raw scores times
    the scale; against the sigmoid, group-limited, normalised kind."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((5, 8)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((8, 12)), jnp.float32)
    bias = jnp.zeros((12,), jnp.float32)
    m = llm.MoEConfig(n_experts=8, n_zero=4, top_k=3, n_group=1, topk_group=1,
                      routed_scale=6.0, score="softmax", norm_topk=False)
    idx, w = llm.moe_route(router, bias, x, m)
    p = np.asarray(jax.nn.softmax(jnp.dot(x, router, precision="highest"), -1))
    want = np.argsort(-p, -1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want, -1))
    np.testing.assert_allclose(np.sort(np.asarray(w), -1),
                               np.sort(6.0 * np.take_along_axis(p, want, -1), -1),
                               rtol=1e-6)
    sig = llm.MoEConfig(n_experts=12, top_k=3, n_group=1, topk_group=1,
                        routed_scale=2.5)
    _, w = llm.moe_route(router, bias, x, sig)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)
    with pytest.raises(ValueError, match="router score"):
        llm.TransformerConfig(n_layers=1, layer_kinds=(("attention", "experts"),),
                              moe=llm.MoEConfig(score="tanh"))


@pytest.mark.parametrize("kinds", [
    (("mla", "dense+experts"),),                              # never joined
    (("mla", "dense+join"), ("mla", "dense+experts")),        # joined first
    (("mla", "dense+experts"), ("mla", "dense+experts"), ("mla", "dense+join")),
    (("mla", "dense+fork"),),
])
def test_a_branch_needs_its_join(kinds):
    with pytest.raises(ValueError, match="dense\\+experts|dense\\+join|unknown"):
        llm.TransformerConfig(n_layers=len(kinds), layer_kinds=kinds,
                              mla=llm.MLAConfig(), moe=llm.MoEConfig())
