"""The device programs name their parts (``jax.named_scope``,
docs/observability.md "Names on the device") and the names are metadata only:
with every scope taken away the lowered program is the same text and the
outputs are the same bits.
"""

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fraud_detection_tpu.models import linear, llm, pipeline, trees

LAYER_SCOPES = {"attn.qkv", "attn.scores", "attn.values", "attn.out", "mlp",
                "lm_head", "sample", "kv.gather_pages", "kv.scatter_pages"}

CFG = llm.TransformerConfig(vocab_size=300, d_model=64, n_heads=4, n_layers=2,
                            d_ff=128, max_seq=256, n_kv_heads=2)


def _llm_inputs():
    params = llm.init_params(jax.random.PRNGKey(0), CFG)
    pages = llm.init_kv_pages(CFG, 12, 16)
    tables = jnp.asarray(np.arange(12).reshape(3, 4), jnp.int32)
    return params, pages, tables, jax.random.PRNGKey(1)


def _decode_case():
    params, pages, tables, key = _llm_inputs()
    args = (params, jnp.asarray([5, 6, 7], jnp.int32),
            jnp.asarray([10, 20, 3], jnp.int32),
            jnp.asarray([True, True, False]), jnp.asarray([8, 8, 0], jnp.int32),
            CFG, pages, tables, jnp.zeros(3, jnp.float32), key, 4)
    return (llm.paged_decode_window, (5, 10), args,
            LAYER_SCOPES | {"kv.append"})


def _prefill_case():
    params, pages, tables, key = _llm_inputs()
    tokens = jnp.asarray(np.arange(32).reshape(1, 32) % 250, jnp.int32)
    args = (params, tokens, jnp.int32(40), CFG, pages, tables[0],
            jnp.float32(0.0), key, 16)
    return llm.paged_slot_prefill, (3, 8), args, LAYER_SCOPES


# What the layer kinds beside ("attention", "dense") add (ISSUE 29).
HYBRID_SCOPES = {"moe.route", "moe.experts", "moe.shared", "kda.conv",
                 "kda.gate", "mla.latent", "mla.attend"}
NEW_SCOPES = HYBRID_SCOPES | {"kda.chunk", "kda.step", "mla.absorb",
                              "state.restore"}
HYBRID = llm.TransformerConfig(
    vocab_size=300, d_model=32, n_heads=2, n_layers=3, d_ff=64, max_seq=256,
    tie_embeddings=False,
    layer_kinds=(("kda", "dense"), ("mla", "experts"), ("kda", "experts")),
    mla=llm.MLAConfig(kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8),
    kda=llm.KDAConfig(n_heads=2, head_dim=16, chunk=16),
    moe=llm.MoEConfig(n_experts=8, top_k=2, n_group=2, topk_group=1,
                      d_expert=16, d_shared=16, routed_scale=2.5,
                      held_start=2, held_count=4))


def _hybrid_inputs():
    params = llm.init_params(jax.random.PRNGKey(0), HYBRID)
    pages = llm.init_kv_pages(HYBRID, 12, 16)
    tables = jnp.asarray(np.arange(12).reshape(3, 4), jnp.int32)
    return params, pages, tables, jax.random.PRNGKey(1), llm.init_state(HYBRID, 3)


def _hybrid_decode_case():
    params, pages, tables, key, state = _hybrid_inputs()
    args = (params, jnp.asarray([5, 6, 7], jnp.int32),
            jnp.asarray([10, 20, 3], jnp.int32),
            jnp.asarray([True, True, False]), jnp.asarray([8, 8, 0], jnp.int32),
            HYBRID, pages, tables, jnp.zeros(3, jnp.float32), key, 4, state)
    return (llm.paged_decode_window, (5, 10), args,
            LAYER_SCOPES - {"attn.scores", "attn.values"} | HYBRID_SCOPES
            | {"kv.append", "kda.step", "mla.absorb"})


def _hybrid_prefill_case():
    params, pages, tables, key, state = _hybrid_inputs()
    tokens = jnp.asarray(np.arange(32).reshape(1, 32) % 250, jnp.int32)
    args = (params, tokens, jnp.int32(40), HYBRID, pages, tables[0],
            jnp.float32(0.0), key, 16, state, jnp.int32(1))
    return (llm.paged_slot_prefill, (3, 8), args,
            LAYER_SCOPES | HYBRID_SCOPES | {"kda.chunk"})


# What the shortcut-connected routed layer kinds add (ISSUE 33): two latent
# attentions with a query latent and no output gate, two dense MLPs, an expert
# branch with zero-compute outputs that joins one sub-layer later.
SHORTCUT_SCOPES = {"moe.zero", "moe.join", "mla.q_latent"}
SHORTCUT = llm.TransformerConfig(
    vocab_size=300, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_seq=256,
    tie_embeddings=False,
    layer_kinds=(("mla", "dense+experts"), ("mla", "dense+join")),
    mla=llm.MLAConfig(kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8, q_rank=24,
                      q_scale=(32 / 24) ** 0.5, kv_scale=2 ** 0.5,
                      out_gate=False),
    moe=llm.MoEConfig(n_experts=8, n_zero=4, top_k=3, n_group=1, topk_group=1,
                      d_expert=16, d_shared=0, routed_scale=6.0,
                      score="softmax", norm_topk=False, held_start=2,
                      held_count=4))


def _shortcut_inputs():
    params = llm.init_params(jax.random.PRNGKey(0), SHORTCUT)
    pages = llm.init_kv_pages(SHORTCUT, 12, 16)
    tables = jnp.asarray(np.arange(12).reshape(3, 4), jnp.int32)
    return params, pages, tables, jax.random.PRNGKey(1)


def _shortcut_decode_case():
    params, pages, tables, key = _shortcut_inputs()
    args = (params, jnp.asarray([5, 6, 7], jnp.int32),
            jnp.asarray([10, 20, 3], jnp.int32),
            jnp.asarray([True, True, False]), jnp.asarray([8, 8, 0], jnp.int32),
            SHORTCUT, pages, tables, jnp.zeros(3, jnp.float32), key, 4)
    return (llm.paged_decode_window, (5, 10), args,
            LAYER_SCOPES - {"attn.scores", "attn.values"} | SHORTCUT_SCOPES
            | {"kv.append", "moe.route", "moe.experts", "mla.latent",
               "mla.attend", "mla.absorb"})


def _shortcut_prefill_case():
    params, pages, tables, key = _shortcut_inputs()
    tokens = jnp.asarray(np.arange(32).reshape(1, 32) % 250, jnp.int32)
    args = (params, tokens, jnp.int32(40), SHORTCUT, pages, tables[0],
            jnp.float32(0.0), key, 16)
    return (llm.paged_slot_prefill, (3, 8), args,
            LAYER_SCOPES | SHORTCUT_SCOPES
            | {"moe.route", "moe.experts", "mla.latent", "mla.attend"})


# What the convolution-attention hybrid's layer kinds add (ISSUE 35): a gated
# short convolution that keeps its filter's tail, attention with per-head q/k
# norms at four query heads a key-value head, every expert held.
CONV_SCOPES = {"conv.in", "conv.filter", "conv.out", "attn.qk_norm"}
CONVMIX = llm.TransformerConfig(
    vocab_size=300, d_model=32, n_heads=4, n_layers=3, d_ff=64, max_seq=256,
    n_kv_heads=1, head_dim_override=8, rms_eps=1e-5, qk_norm=True,
    layer_kinds=(("conv", "dense"), ("attention", "experts"),
                 ("conv", "experts")),
    conv=llm.ConvConfig(taps=3),
    moe=llm.MoEConfig(n_experts=8, top_k=2, n_group=1, topk_group=1,
                      d_expert=16, d_shared=0, norm_eps=1e-6, held_start=0,
                      held_count=8))


def _convmix_inputs():
    params = llm.init_params(jax.random.PRNGKey(0), CONVMIX)
    pages = llm.init_kv_pages(CONVMIX, 12, 16)
    tables = jnp.asarray(np.arange(12).reshape(3, 4), jnp.int32)
    return params, pages, tables, jax.random.PRNGKey(1), llm.init_state(CONVMIX, 3)


def _convmix_decode_case():
    params, pages, tables, key, state = _convmix_inputs()
    args = (params, jnp.asarray([5, 6, 7], jnp.int32),
            jnp.asarray([10, 20, 3], jnp.int32),
            jnp.asarray([True, True, False]), jnp.asarray([8, 8, 0], jnp.int32),
            CONVMIX, pages, tables, jnp.zeros(3, jnp.float32), key, 4, state)
    return (llm.paged_decode_window, (5, 10), args,
            LAYER_SCOPES | CONV_SCOPES | {"kv.append", "moe.route",
                                          "moe.experts"})


def _convmix_prefill_case():
    params, pages, tables, key, state = _convmix_inputs()
    tokens = jnp.asarray(np.arange(32).reshape(1, 32) % 250, jnp.int32)
    args = (params, tokens, jnp.int32(40), CONVMIX, pages, tables[0],
            jnp.float32(0.0), key, 16, state, jnp.int32(1))
    return (llm.paged_slot_prefill, (3, 8), args,
            LAYER_SCOPES | CONV_SCOPES | {"moe.route", "moe.experts"})


def _packed_rows():
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 500, (8, 16)).astype(np.int16)
    counts = rng.integers(0, 5, (8, 16)).astype(np.uint16).view(np.int16)
    return jnp.asarray(np.stack([ids, counts], axis=1))


def _lr_case():
    model = linear.LogisticRegression.from_arrays(
        np.linspace(-1, 1, 500).astype(np.float32), -0.3)
    return (linear._prob_packed, (), (model, _packed_rows()),
            {"score.unpack", "score.gather_dot"})


def _tree_case():
    rng = np.random.default_rng(1)
    T, M = 3, 7          # three complete trees of depth 2
    inner = np.arange(M) < 3
    ens = trees.TreeEnsemble(
        feature=jnp.asarray(np.where(inner, rng.integers(0, 500, (T, M)), -1),
                            jnp.int32),
        threshold=jnp.asarray(rng.random((T, M)), jnp.float32),
        left=jnp.asarray(np.where(inner, 2 * np.arange(M) + 1, -1)[None]
                         .repeat(T, 0), jnp.int32),
        right=jnp.asarray(np.where(inner, 2 * np.arange(M) + 2, -1)[None]
                          .repeat(T, 0), jnp.int32),
        leaf=jnp.asarray(rng.random((T, M, 2)), jnp.float32),
        tree_weights=jnp.ones(T, jnp.float32), kind="random_forest",
        max_depth=2)
    idf = jnp.asarray(rng.random(500), jnp.float32)
    return (pipeline._tree_prob_packed_plain, (3,),
            (ens, _packed_rows(), idf, True),
            {"score.unpack", "score.traverse"})


@pytest.mark.parametrize("case", [_decode_case,
                                  _prefill_case, _lr_case, _tree_case,
                                  _hybrid_decode_case, _hybrid_prefill_case,
                                  _shortcut_decode_case, _shortcut_prefill_case,
                                  _convmix_decode_case, _convmix_prefill_case])
def test_scopes_are_named_and_change_no_number(case, monkeypatch):
    fn, static, args, scopes = case()
    named = fn.lower(*args)
    text = named.as_text(debug_info=True)
    missing = {s for s in scopes if f"/{s}" not in text}
    assert not missing, f"scopes not in the lowered text: {missing}"
    got = fn(*args)

    # The same Python function traced with every scope a no-op, under a
    # wrapper of its own: jit keeps its traces by the function it wraps.
    def unscoped(*a):
        return fn.__wrapped__(*a)

    unscoped.__name__ = fn.__wrapped__.__name__
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = jax.jit(unscoped, static_argnums=static)
    bare = plain.lower(*args)
    assert not any(f"/{s}" in bare.as_text(debug_info=True) for s in scopes)
    assert bare.as_text() == named.as_text()
    want = plain(*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)


@pytest.mark.parametrize("case,view", [
    (_decode_case, (3, 64)),              # 3 slots, 4 pages of 16
    (_prefill_case, (1, 64)),             # one row, 4 pages of 16
])
def test_slot_programs_attend_the_narrow_kv(case, view):
    """CFG is grouped (4 query heads over 2 kv heads): the slot programs hand
    ``_attend`` the cache as stored, so the lowered program carries no
    ``attn.expand_kv`` and builds nothing of shape (B, S, n_heads, head_dim)
    — the copy the padded view once paid for in every layer of every step."""
    fn, _, args, _ = case()
    text = fn.lower(*args).as_text(debug_info=True)
    assert "attn.expand_kv" not in text
    B, S = view
    assert f"tensor<{B}x{S}x{CFG.kv_heads}x{CFG.head_dim}x" in text
    assert f"tensor<{B}x{S}x{CFG.n_heads}x{CFG.head_dim}x" not in text


@pytest.mark.parametrize("case", [_decode_case, _prefill_case])
def test_dense_programs_carry_none_of_the_hybrid_scopes(case):
    """A model of ("attention", "dense") layers lowers to the program it
    always was: no scope of another layer kind, no counters, no state."""
    fn, _, args, _ = case()
    text = fn.lower(*args).as_text(debug_info=True)
    assert not [s for s in NEW_SCOPES if f"/{s}" in text]
    out = fn(*args)
    assert out[-1] is None                       # no expert counters
    if fn is llm.paged_decode_window:
        assert out[-2] == {}                     # no recurrent state


def _long_prefill_case(cfg, state=None):
    """A suffix of 512 tokens behind a prefix of 16: long enough for the flash
    kernel (``llm.prefill_takes_flash``), 33 pages of 16."""
    params = llm.init_params(jax.random.PRNGKey(0), cfg)
    pages = llm.init_kv_pages(cfg, 34, 16)
    tokens = jnp.asarray(np.arange(512).reshape(1, 512) % 250, jnp.int32)
    args = (params, tokens, jnp.int32(500), cfg, pages,
            jnp.arange(1, 34, dtype=jnp.int32), jnp.float32(0.0),
            jax.random.PRNGKey(1), 16)
    if state is not None:
        args += (state, jnp.int32(1))
    return args


@pytest.mark.parametrize("tiny,state", [(CFG, False), (HYBRID, True),
                                        (SHORTCUT, False), (CONVMIX, True)],
                         ids=["dense", "hybrid", "shortcut", "convmix"])
def test_long_suffix_prefill_attends_under_attn_flash(tiny, state, monkeypatch):
    """At 512 suffix tokens and heads 64 wide the prefill's attention is the
    flash kernel under a scope of its own: no ``attn.scores`` / ``attn.values``
    (no (H, Ts, S) array) is left in the program; the short cases above keep
    theirs, and so does the same length at the tiny models' narrow heads;
    compiled for a TPU the kernel is a ``tpu_custom_call``."""
    wide = (dict(head_dim_override=64) if tiny.mla is None else
            dict(mla=dataclasses.replace(tiny.mla, nope_dim=48, rope_dim=16)))
    cfg = dataclasses.replace(tiny, max_seq=1024, **wide)
    short = llm.paged_slot_prefill.lower(*_long_prefill_case(
        dataclasses.replace(tiny, max_seq=1024),
        llm.init_state(tiny, 3) if state else None)).as_text(debug_info=True)
    assert "/attn.flash" not in short and "/attn.scores" in short   # heads of 8-16
    args = _long_prefill_case(cfg, llm.init_state(cfg, 3) if state else None)
    text = llm.paged_slot_prefill.lower(*args).as_text(debug_info=True)
    assert "/attn.flash" in text
    assert "/attn.scores" not in text and "/attn.values" not in text
    assert "tpu_custom_call" not in text         # interpreted on the CPU
    from fraud_detection_tpu.utils import device

    monkeypatch.setattr(device, "pallas_interpret", lambda: False)
    llm.paged_slot_prefill.clear_cache()
    try:
        on_chip = llm.paged_slot_prefill.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    finally:
        llm.paged_slot_prefill.clear_cache()
    assert "/attn.flash" in on_chip and "tpu_custom_call" in on_chip
    assert "/attn.scores" not in on_chip


@pytest.mark.parametrize("case", [_decode_case, _hybrid_decode_case,
                                  _shortcut_decode_case, _convmix_decode_case])
def test_decode_window_holds_no_kernel_call(case, monkeypatch):
    """The decode window keeps ``_attend`` / the absorbed latent attention:
    lowered for a TPU it carries no ``tpu_custom_call`` and no ``attn.flash``
    in any of the four tiny configurations."""
    from fraud_detection_tpu.utils import device

    monkeypatch.setattr(device, "pallas_interpret", lambda: False)
    fn, _, args, _ = case()
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text(
        debug_info=True)
    assert "tpu_custom_call" not in text and "/attn.flash" not in text


def test_hybrid_prefill_holds_no_triangular_solve():
    """The chunk's unit-lower system is sub-block substitution and block
    products (``kda_chunked``): the prefill program carries no triangular
    solve under any of its spellings (``stablehlo.triangular_solve``, XLA's
    ``triangular-solve``, jax's ``_solve_triangular``, the CPU's ``trsm``
    custom call), and ``kda.chunk`` still names what replaced it."""
    fn, _, args, _ = _hybrid_prefill_case()
    lowered = fn.lower(*args)
    assert "/kda.chunk" in lowered.as_text(debug_info=True)
    # without the locations: they would carry this test's own name
    assert not re.search(r"triangular[_-]solve|solve_triangular|trsm",
                         lowered.as_text())


def test_state_restore_is_named():
    state = llm.init_state(HYBRID, 3)
    snapshot = llm.init_state(HYBRID, 1)
    text = llm.restore_slot_state.lower(state, snapshot, jnp.int32(2)).as_text(
        debug_info=True)
    assert "/state.restore" in text
    assert set(state) == {"l0.S", "l0.tail", "l2.S", "l2.tail"}


@pytest.mark.parametrize("case", [_decode_case, _prefill_case,
                                  _hybrid_decode_case, _hybrid_prefill_case])
def test_other_programs_carry_none_of_the_shortcut_scopes(case):
    """The dense decoder and the hybrid lower to the programs they were: no
    query latent, no zero-compute part, no branch to join, and no
    ``picks_zero`` among the hybrid's counters."""
    fn, _, args, _ = case()
    text = fn.lower(*args).as_text(debug_info=True)
    assert not [s for s in SHORTCUT_SCOPES if f"/{s}" in text]
    cfg = next(a for a in args if isinstance(a, llm.TransformerConfig))
    assert "picks_zero" not in llm.moe_stat_names(cfg)


def test_shortcut_programs_count_the_zero_compute_picks():
    """Both slot programs hand back five counters (``moe_stat_names``), the
    fifth the picks on zero-compute outputs; the shortcut model keeps no
    recurrent state, and ``moe.shared`` is in neither program."""
    assert llm.moe_stat_names(SHORTCUT) == llm.MOE_STATS + ("picks_zero",)
    for case in (_shortcut_decode_case, _shortcut_prefill_case):
        fn, _, args, _ = case()
        assert "/moe.shared" not in fn.lower(*args).as_text(debug_info=True)
        out = fn(*args)
        stats = dict(zip(llm.moe_stat_names(SHORTCUT), np.asarray(out[-1])))
        assert stats["picks"] > 0 and stats["picks"] % 3 == 0
        assert 0 < stats["picks_zero"] < stats["picks"]
        assert stats["picks_held"] + stats["picks_zero"] <= stats["picks"]
        assert out[-2] == {}


@pytest.mark.parametrize("case", [_decode_case, _prefill_case,
                                  _hybrid_decode_case, _hybrid_prefill_case,
                                  _shortcut_decode_case, _shortcut_prefill_case])
def test_other_programs_carry_none_of_the_conv_scopes(case):
    """The dense decoder, the hybrid and the shortcut-connected model lower
    to the programs they were: no gated convolution, no q/k norm."""
    fn, _, args, _ = case()
    text = fn.lower(*args).as_text(debug_info=True)
    assert not [s for s in CONV_SCOPES if f"/{s}" in text]


def test_convmix_programs_keep_filter_tails_and_hold_every_expert():
    """Both slot programs of the convolution-attention hybrid carry the four
    new scopes (the case list above) and none of the other kinds'; the state
    that rides them is one filter tail a convolution layer; with every expert
    held each pick is a held pick; ``moe.shared`` is in neither program."""
    assert set(llm.init_state(CONVMIX, 3)) == {"l0.tail", "l2.tail"}
    assert llm.moe_stat_names(CONVMIX) == llm.MOE_STATS
    others = (NEW_SCOPES | SHORTCUT_SCOPES) - {"moe.route", "moe.experts"}
    for case in (_convmix_decode_case, _convmix_prefill_case):
        fn, _, args, _ = case()
        text = fn.lower(*args).as_text(debug_info=True)
        assert not [s for s in others if f"/{s}" in text]
        assert "/attn.qkv/attn.qk_norm" in text       # inside the projection's
        out = fn(*args)
        stats = dict(zip(llm.moe_stat_names(CONVMIX), np.asarray(out[-1])))
        assert stats["picks"] > 0 and stats["picks_held"] == stats["picks"]
        assert set(out[-2]) == {"l0.tail", "l2.tail"}
        assert out[-2]["l0.tail"].shape == (3, 2, 32)
