"""Tests for the on-pod LLM: ring attention exactness, tensor-parallel parity,
KV-cache decode consistency, generation API (SURVEY §4 strategy #5 — all
multi-chip paths run on the virtual 8-device CPU mesh from conftest)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from fraud_detection_tpu.models import llm
from fraud_detection_tpu.models.llm import (
    ByteTokenizer,
    LanguageModel,
    MODEL_AXIS,
    SEQ_AXIS,
    TransformerConfig,
    _attend,
    forward,
    init_cache,
    init_params,
    ring_attention,
    shard_params,
)

CFG = TransformerConfig(d_model=64, n_heads=8, n_layers=2, d_ff=128, max_seq=256)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def seq_mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), (SEQ_AXIS,))


def model_mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), (MODEL_AXIS,))


# ---------------------------------------------------------------------------
# ring attention == dense causal attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", [32, 64])
def test_ring_attention_matches_dense(T):
    B, H, d = 2, 4, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)

    causal = jnp.tril(jnp.ones((T, T), bool))
    dense = _attend(q / 1.0, k, v, causal)  # _attend applies 1/sqrt(d) inside

    ring = ring_attention(q, k, v, seq_mesh(8))
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_under_jit_with_sharded_inputs():
    mesh = seq_mesh(8)
    B, T, H, d = 1, 64, 4, 16
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
               for _ in range(3))
    out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh))(q, k, v)
    dense = _attend(q, k, v, jnp.tril(jnp.ones((T, T), bool)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_forward_ring_mode_matches_plain(params):
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 256, (2, 64)), jnp.int32)
    plain, _ = forward(params, tokens, CFG)
    ringed, _ = forward(params, tokens, CFG, seq_mesh=seq_mesh(8))
    np.testing.assert_allclose(np.asarray(ringed), np.asarray(plain),
                               rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# tensor parallelism
# ---------------------------------------------------------------------------

def test_tp_sharded_forward_matches_single_device(params):
    mesh = model_mesh(8)
    sharded = shard_params(params, CFG, mesh)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 256, (2, 16)), jnp.int32)
    want, _ = forward(params, tokens, CFG)
    got = jax.jit(lambda p, t: forward(p, t, CFG)[0])(sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-4, atol=3e-4)
    # head-dim sharding actually happened
    sh = sharded["l0.wq"].sharding
    assert sh.spec == jax.sharding.PartitionSpec(None, MODEL_AXIS, None)


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------

def test_incremental_decode_matches_full_forward(params):
    """Prefill+step logits must equal full-sequence forward at each position."""
    rng = np.random.default_rng(4)
    T = 12
    tokens = jnp.asarray(rng.integers(0, 256, (1, T)), jnp.int32)
    full, _ = forward(params, tokens, CFG)

    cache = init_cache(CFG, 1, T)
    # prefill the first 6, then decode one at a time
    pre, cache = forward(params, tokens[:, :6], CFG,
                         positions=jnp.arange(6)[None], kv_cache=cache,
                         cache_len=jnp.int32(0))
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :6]),
                               rtol=2e-4, atol=2e-4)
    for t in range(6, T):
        step, cache = forward(params, tokens[:, t : t + 1], CFG,
                              positions=jnp.asarray([[t]]), kv_cache=cache,
                              cache_len=jnp.int32(t))
        np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(full[:, t]),
                                   rtol=2e-4, atol=2e-4, err_msg=f"pos {t}")


# ---------------------------------------------------------------------------
# generation API
# ---------------------------------------------------------------------------

def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer(CFG)
    ids = tok.encode("hello wörld")
    assert ids[0] == CFG.BOS
    assert tok.decode(ids[1:]) == "hello wörld"
    assert tok.decode(list(ids[1:]) + [CFG.EOS, 65, 66]) == "hello wörld"


def test_generate_deterministic_greedy():
    lm = LanguageModel.init_random(CFG, seed=1)
    a = lm.generate_tokens(lm.tokenizer.encode("hi"), max_new_tokens=8, temperature=0.0)
    b = lm.generate_tokens(lm.tokenizer.encode("hi"), max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (8,)
    assert all(0 <= t < CFG.vocab_size for t in a.tolist())


def test_generate_prompt_padding_invariant():
    """Bucketed prompt padding must not change greedy output."""
    lm = LanguageModel.init_random(CFG, seed=1)
    t1 = lm.generate_tokens(lm.tokenizer.encode("abcdefg"), max_new_tokens=6)
    t2 = lm.generate_tokens(np.asarray(lm.tokenizer.encode("abcdefg"), np.int32),
                            max_new_tokens=6)
    np.testing.assert_array_equal(t1, t2)
    # different prompt length -> different padding bucket, still deterministic
    short = lm.generate_tokens(lm.tokenizer.encode("ab"), max_new_tokens=4)
    assert short.shape == (4,)


def test_generate_text_and_onpod_backend():
    from fraud_detection_tpu.explain.onpod import OnPodBackend

    lm = LanguageModel.init_random(CFG, seed=2)
    text = lm.generate_text("explain", max_new_tokens=12)
    assert isinstance(text, str)
    be = OnPodBackend.from_model(lm)
    out = be.generate("why scam?", temperature=0.0, max_tokens=12)
    assert isinstance(out, str)


def test_tp_generation_runs():
    mesh = model_mesh(8)
    lm = LanguageModel.init_random(CFG, seed=3, mesh=mesh)
    toks = lm.generate_tokens(lm.tokenizer.encode("x"), max_new_tokens=4)
    assert toks.shape == (4,)


def test_ring_attention_key_chunked_matches_dense():
    """Force the within-step key-chunk loop (key_chunk < T_loc) — the
    memory-bounded path long shards take — and require exact agreement
    with dense causal attention, including indivisible chunk sizes whose
    final overhang chunk is sentinel-masked."""
    mesh = seq_mesh(8)
    B, T, H, d = 1, 128, 2, 16    # T_loc = 16 per device
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
               for _ in range(3))
    dense = _attend(q, k, v, jnp.tril(jnp.ones((T, T), bool)))
    for key_chunk in (4, 5, 7, 16):  # 5, 7: overhang chunks (16 % c != 0)
        ring = ring_attention(q, k, v, mesh, key_chunk=key_chunk)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"key_chunk={key_chunk}")


def test_batched_generation_matches_single(params):
    """Batched decode over UNEVEN prompt lengths (left-pad + per-row
    validity masking) must reproduce each prompt's B=1 greedy generation —
    any cross-row cache contamination or off-by-one in the masking shows
    up as a divergent token here."""
    lm = LanguageModel(CFG, params)
    prompts = ["Agent: hello",
               "Customer: I was told I won a big prize yesterday",
               "A"]
    tok_prompts = [lm.tokenizer.encode(p) for p in prompts]
    batched = lm.generate_tokens_batch(tok_prompts, max_new_tokens=12)
    for i, tp in enumerate(tok_prompts):
        single = lm.generate_tokens(tp, max_new_tokens=12)
        np.testing.assert_array_equal(batched[i], single,
                                      err_msg=prompts[i])


def test_generation_freezes_after_eos(params):
    """Once a row samples EOS the early-stop decode freezes it: every
    later slot holds EOS (the while_loop exits when all rows are done).
    High-temperature sampling draws EOS naturally within a few seeds."""
    lm = LanguageModel(CFG, params)
    enc = lm.tokenizer.encode("hello there")
    for seed in range(40):
        toks = lm.generate_tokens(enc, max_new_tokens=24,
                                  temperature=3.0, seed=seed)
        hits = np.where(toks == CFG.EOS)[0]
        if len(hits) and hits[0] < 16:
            first = int(hits[0])
            assert (toks[first:] == CFG.EOS).all(), toks
            break
    else:
        raise AssertionError("no early EOS drawn in 40 seeds at temp 3.0")


def test_ulysses_attention_matches_dense():
    """All-to-all sequence parallelism: heads re-shard across the seq axis,
    full local attention per head group, re-shard back — must equal dense
    causal attention exactly (it IS dense attention, relaid out)."""
    from fraud_detection_tpu.models.llm import ulysses_attention

    mesh = seq_mesh(8)
    B, T, H, d = 2, 64, 8, 16
    rng = np.random.default_rng(21)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
               for _ in range(3))
    dense = _attend(q, k, v, jnp.tril(jnp.ones((T, T), bool)))
    out = ulysses_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(q[:, :, :6], k[:, :, :6], v[:, :, :6], mesh)


def test_forward_ulysses_mode_matches_plain(params):
    tokens = jnp.asarray(np.random.default_rng(6).integers(0, 256, (2, 64)),
                         jnp.int32)
    plain, _ = forward(params, tokens, CFG)
    sp, _ = forward(params, tokens, CFG, seq_mesh=seq_mesh(8),
                    sp_impl="ulysses")
    np.testing.assert_allclose(np.asarray(sp), np.asarray(plain),
                               rtol=3e-4, atol=3e-4)


def test_chunked_causal_attention_matches_dense():
    """Pure-XLA memory-efficient attention: forward AND gradient must match
    the materialized path (it's the differentiable long-context path
    training and TP take). Ragged tails included."""
    from fraud_detection_tpu.models.llm import chunked_causal_attention

    B, T, H, d = 2, 100, 3, 16   # ragged vs both chunk sizes
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
               for _ in range(3))
    causal = jnp.tril(jnp.ones((T, T), bool))
    dense = _attend(q, k, v, causal)
    out = chunked_causal_attention(q, k, v, q_chunk=32, key_chunk=48)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)

    def loss_chunked(q, k, v):
        return jnp.sum(chunked_causal_attention(q, k, v, q_chunk=32,
                                                key_chunk=48) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_attend(q, k, v, causal) ** 2)

    g_c = jax.grad(loss_chunked, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_c, g_d, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_long_seq_training_step_uses_chunked_path(params):
    """forward(use_flash=False) at T >= _FLASH_MIN_T must route through the
    chunked path and stay differentiable end to end (a smoke grad step)."""
    tokens = jnp.asarray(
        np.random.default_rng(8).integers(0, 256, (1, 576)), jnp.int32)

    def loss(p):
        logits, _ = forward(p, tokens, CFG, use_flash=False)
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    g = jax.grad(loss)(params)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in g.values())


def test_stochastic_sampling_batch_composition_invariant(params):
    """At temperature > 0, row r's sampled tokens are a function of
    (seed, step, r) only — co-batching more prompts (which changes the
    power-of-two batch bucket) must not change an earlier row's stream
    (round-2 advisor finding: a (B, V)-shaped noise draw broke this)."""
    lm = LanguageModel(CFG, params)
    tok = lm.tokenizer.encode("Customer: I was told I won a prize")
    alone = lm.generate_tokens_batch([tok], max_new_tokens=10,
                                     temperature=1.0, seed=5)
    extras = [lm.tokenizer.encode(p) for p in ("Agent: hi", "B", "CC")]
    cobatched = lm.generate_tokens_batch([tok] + extras, max_new_tokens=10,
                                         temperature=1.0, seed=5)
    np.testing.assert_array_equal(alone[0], cobatched[0])
    # and the single-prompt wrapper is the same stream
    single = lm.generate_tokens(tok, max_new_tokens=10, temperature=1.0, seed=5)
    np.testing.assert_array_equal(single, alone[0])


def test_auto_flash_dispatch_is_differentiable():
    """Long-sequence auto-dispatch takes the Pallas flash kernel, whose
    backward is rerouted through chunked_causal_attention by custom_vjp —
    external callers differentiating forward() without use_flash=False must
    get real gradients matching the pure-XLA path (round-2 advisor finding:
    this used to raise an opaque Pallas AD error)."""
    from fraud_detection_tpu.models.llm import causal_attention

    B, T, H, d = 1, 512, 2, 8  # T >= _FLASH_MIN_T triggers auto flash
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)

    loss_auto = lambda q, k, v: jnp.sum(causal_attention(q, k, v) ** 2)
    loss_ref = lambda q, k, v: jnp.sum(
        causal_attention(q, k, v, use_flash=False) ** 2)
    g_auto = jax.grad(loss_auto, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for ga, gr in zip(g_auto, g_ref):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)


def test_int8_weight_only_quantization(params):
    """Weight-only int8 (decode is weight-streaming bound; this halves the
    streamed bytes): quantized logits track full-precision closely, greedy
    decode runs end to end through the same generate paths, and
    tensor-parallel sharding of quantized params refuses loudly (per-leaf
    scale shardings are not implemented)."""
    from fraud_detection_tpu.models.llm import (LanguageModel, Q8,
                                                quantize_params, shard_params)

    lm = LanguageModel(CFG, params)
    qlm = lm.quantized()
    # structure: matmul weights quantized per output channel, norms intact
    assert isinstance(qlm.params["l0.wq"], Q8)
    assert qlm.params["l0.wq"].q.dtype == jnp.int8
    assert qlm.params["l0.wq"].scale.shape == (1,) + qlm.params["l0.wq"].q.shape[1:]
    assert not isinstance(qlm.params["l0.ln1"], Q8)
    q_bytes = sum(l.size * l.dtype.itemsize
                  for l in jax.tree_util.tree_leaves(qlm.params))
    f_bytes = sum(l.size * l.dtype.itemsize
                  for l in jax.tree_util.tree_leaves(lm.params))
    assert q_bytes < 0.45 * f_bytes  # f32 test params: int8 is ~4x smaller

    toks = jnp.asarray(np.arange(24, dtype=np.int32)[None, :] % 250)
    full = np.asarray(forward(params, toks, CFG)[0])
    quant = np.asarray(forward(qlm.params, toks, CFG)[0])
    # per-channel int8 keeps logits tightly correlated with full precision
    corr = np.corrcoef(full.ravel(), quant.ravel())[0, 1]
    assert corr > 0.999, corr
    # greedy decode through the standard path (jit boundary crosses Q8 pytree)
    text = qlm.generate_text("hello urgent prize", max_new_tokens=8)
    assert isinstance(text, str)
    # embed kept full-precision on request
    half = lm.quantized(include_embed=False)
    assert not isinstance(half.params["embed"], Q8)


def test_int8_tensor_parallel_both_orders(params):
    """int8 x TP composes in BOTH orders (round-4 verdict item 1): the Q8
    q-leaf follows the weight's Megatron spec, the scale its output-channel
    restriction, and an 8-way tp forward matches the single-device quantized
    forward bit-for-bit in f32 logits (same math, same reduction order per
    shard up to GSPMD's deterministic collectives — tolerance covers that)."""
    from fraud_detection_tpu.models.llm import (LanguageModel, Q8,
                                                quantize_params, shard_params)

    mesh = model_mesh(8)
    toks = jnp.asarray(np.arange(24, dtype=np.int32)[None, :] % 250)
    qparams = quantize_params(params)
    want = np.asarray(forward(qparams, toks, CFG)[0])

    # quantize -> shard
    q_then_s = shard_params(qparams, CFG, mesh)
    wq = q_then_s["l0.wq"]
    assert isinstance(wq, Q8) and wq.q.dtype == jnp.int8
    assert not wq.q.sharding.is_fully_replicated          # heads sharded
    got1 = np.asarray(jax.jit(lambda p, t: forward(p, t, CFG)[0])(q_then_s, toks))
    np.testing.assert_allclose(got1, want, rtol=2e-5, atol=2e-5)

    # shard -> quantize (the onpod from_hf_checkpoint(int8=True, mesh=...)
    # order: quantization runs on already-placed params)
    s_then_q = quantize_params(shard_params(params, CFG, mesh))
    got2 = np.asarray(jax.jit(lambda p, t: forward(p, t, CFG)[0])(s_then_q, toks))
    np.testing.assert_allclose(got2, want, rtol=2e-5, atol=2e-5)

    # generation end to end on the tp mesh
    qlm = LanguageModel(CFG, q_then_s)
    toks_out = qlm.generate_tokens(qlm.tokenizer.encode("urgent"), max_new_tokens=4)
    assert toks_out.shape == (4,)


def test_logits_last_only_matches_full_forward(params):
    """The decode prefill's last-position-only mode is exactly the full
    forward's final position (full-sequence logits at B=64 x ~1000-token
    prompts would materialize ~63GB — the OOM the mode exists to avoid)."""
    toks = jnp.asarray(np.arange(20, dtype=np.int32)[None, :] % 250)
    full, _ = forward(params, toks, CFG)
    last, _ = forward(params, toks, CFG, logits_last_only=True)
    assert last.shape == (1, 1, CFG.vocab_size)
    np.testing.assert_allclose(np.asarray(last[:, 0]), np.asarray(full[:, -1]),
                               rtol=1e-5, atol=1e-5)


def test_int8_tensor_parallel_mqa_kv_replicated():
    """int8 x TP at the Gemma-2B serving shape: MQA (one kv head) keeps
    wk/wv REPLICATED while wq shards over heads — the Q8 leaves must follow
    the same split (replicated q+scale for kv, head-sharded for q), and the
    tp(8) forward must match the single-device quantized forward."""
    from fraud_detection_tpu.models.llm import (Q8, init_params,
                                                quantize_params, shard_params)

    cfg = TransformerConfig(d_model=64, n_heads=8, n_layers=2, d_ff=128,
                            max_seq=256, n_kv_heads=1, head_dim_override=16)
    params = init_params(jax.random.PRNGKey(4), cfg)
    mesh = model_mesh(8)
    toks = jnp.asarray(np.arange(24, dtype=np.int32)[None, :] % 250)

    qparams = quantize_params(params)
    want = np.asarray(forward(qparams, toks, cfg)[0])
    sharded = shard_params(qparams, cfg, mesh)
    wk = sharded["l0.wk"]
    assert isinstance(wk, Q8)
    assert wk.q.sharding.is_fully_replicated          # MQA: kv replicated
    assert wk.scale.sharding.is_fully_replicated
    assert not sharded["l0.wq"].q.sharding.is_fully_replicated
    got = np.asarray(jax.jit(lambda p, t: forward(p, t, cfg)[0])(sharded, toks))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_quantize_params_host_matches_device():
    """Host (numpy) and device (XLA) quantization are the SAME function:
    load_hf_checkpoint(int8=True) ships host-quantized weights and must
    land bit-identical to an after-load ``.quantized()`` — int8 codes
    exactly equal, scales exactly equal (both run f32 math with
    round-half-even, per the quantize_params_host contract)."""
    from fraud_detection_tpu.models.llm import (Q8, quantize_params,
                                                quantize_params_host)

    params = init_params(jax.random.PRNGKey(11), CFG)
    params_np = {k: np.asarray(v) for k, v in params.items()}

    dev = quantize_params(params)
    host = quantize_params_host(params_np)
    assert dev.keys() == host.keys()
    for name in dev:
        d, h = dev[name], host[name]
        assert isinstance(d, Q8) == isinstance(h, Q8), name
        if isinstance(d, Q8):
            assert np.asarray(h.q).dtype == np.int8
            np.testing.assert_array_equal(np.asarray(d.q), h.q, err_msg=name)
            np.testing.assert_array_equal(
                np.asarray(d.scale), h.scale, err_msg=name)
        else:
            np.testing.assert_array_equal(np.asarray(d), np.asarray(h),
                                          err_msg=name)

    # include_embed=False propagates the same way on both paths.
    dev_half = quantize_params(params, include_embed=False)
    host_half = quantize_params_host(params_np, include_embed=False)
    assert not isinstance(dev_half["embed"], Q8)
    assert not isinstance(host_half["embed"], Q8)


def test_flash_gqa_narrow_kv_gradients_match_expanded():
    """Differentiating the auto-dispatched flash path with NARROW GQA kv
    must produce dk/dv at the narrow width, equal to the expanded-kv
    gradients summed over each head group (the vjp of the expansion).
    Pins _flash_diff_bwd's rep != 1 branch — forward parity alone would
    not catch a dropped group-sum or wrong repeat axis."""
    from fraud_detection_tpu.models.llm import causal_attention

    B, T, H, Hkv, d = 1, 640, 4, 2, 16   # T >= _FLASH_MIN_T: flash dispatch
    rng = jax.random.PRNGKey(7)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (B, T, H, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, T, Hkv, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, T, Hkv, d), jnp.float32)

    def loss_narrow(q_, k_, v_):
        return causal_attention(q_, k_, v_).astype(jnp.float32).sum()

    def loss_expanded(q_, k_, v_):
        ke, ve = (jnp.repeat(t, H // Hkv, axis=2) for t in (k_, v_))
        return causal_attention(q_, ke, ve).astype(jnp.float32).sum()

    gq, gk, gv = jax.grad(loss_narrow, argnums=(0, 1, 2))(q, k, v)
    assert gk.shape == k.shape and gv.shape == v.shape
    eq, ek, ev = jax.grad(loss_expanded, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(eq),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(ek),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(ev),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hkv", [1, 2])
def test_ring_attention_narrow_kv_matches_dense(hkv):
    """GQA/MQA kv ride the ring at NARROW width (1/rep of the ICI bytes per
    rotation) and expand per arrival — must equal dense attention over the
    expanded kv exactly as the full-width ring does. Covers both the
    single-pass and key-chunked step bodies."""
    from fraud_detection_tpu.models.llm import _expand_kv_heads

    B, T, H, d = 2, 64, 4, 16
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, hkv, d)), jnp.float32)
    ke, ve = (_expand_kv_heads(t, H // hkv) for t in (k, v))
    dense = _attend(q, ke, ve, jnp.tril(jnp.ones((T, T), bool)))

    ring = ring_attention(q, k, v, seq_mesh(8))
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
    chunked = ring_attention(q, k, v, seq_mesh(8), key_chunk=3)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_narrow_kv_matches_dense():
    """Ulysses expands narrow kv at entry (its all-to-all splits the head
    axis) — same result as pre-expanded kv."""
    from fraud_detection_tpu.models.llm import _expand_kv_heads, ulysses_attention

    B, T, H, d = 2, 64, 8, 16
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, 2, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, 2, d)), jnp.float32)
    ke, ve = (_expand_kv_heads(t, 4) for t in (k, v))
    dense = _attend(q, ke, ve, jnp.tril(jnp.ones((T, T), bool)))
    out = ulysses_attention(q, k, v, seq_mesh(8))
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("T", [1, 5], ids=["decode", "prefill"])
@pytest.mark.parametrize("per_row_mask", [False, True],
                         ids=["shared_mask", "row_mask"])
@pytest.mark.parametrize("rep", [1, 2, 4, 8])
def test_attend_narrow_kv_matches_expanded(rep, per_row_mask, T, dtype, tol):
    """``_attend`` contracts grouped query heads against the kv as stored:
    query head h reads kv head h // rep, which is ``_expand_kv_heads``'s
    order, so narrow kv must give what the expanded copy gives — MHA
    (rep 1) and MQA (rep == H) included, under the slot decode's per-row
    (B, T, S) mask and the prefill's shared (T, S) mask alike."""
    from fraud_detection_tpu.models.llm import _expand_kv_heads

    B, S, H, d = 3, 24, 8, 16
    rng = np.random.default_rng(100 + rep)
    q = jnp.asarray(rng.normal(size=(B, T, H, d)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(B, S, H // rep, d)), dtype)
            for _ in range(2))
    # row t sees keys [0, S - T + t]: never a fully-masked row
    mask = jnp.arange(S)[None, :] <= (S - T + jnp.arange(T))[:, None]
    if per_row_mask:  # each batch row holds its own prefix length
        held = jnp.asarray([S, S - 7, T])
        mask = mask[None] & (jnp.arange(S)[None, None, :]
                             < held[:, None, None])
    got = _attend(q, k, v, mask)
    want = _attend(q, _expand_kv_heads(k, rep), _expand_kv_heads(v, rep),
                   mask)
    assert got.shape == (B, T, H, d) and got.dtype == want.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# layer kinds beside ("attention", "dense"): the tiny hybrid (tests/hybrid_tiny.py)
# against its family's plain float32 reference
# ---------------------------------------------------------------------------

def _cached_logits(lm, toks, n_prefill):
    """Logits of every position through the cache: one prefill of
    ``n_prefill`` tokens, then one token a step (chunked KDA then stepped,
    expanded MLA then absorbed, the grouped expert product at both sizes)."""
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
def hybrid_errors():
    """|program logits - reference logits| at every position of two
    120-token rows (100 prefilled, 20 decoded through the cache), by
    (dtype, weights)."""
    import hybrid_tiny

    fam = hybrid_tiny.family()
    toks = np.random.default_rng(5).integers(0, 258, (2, 120)).astype(np.int32)
    memo = {}

    def errors(dtype, weights=None):
        key = (dtype, weights or dtype)
        if key not in memo:
            ref = np.asarray(fam.reference_logits(
                hybrid_tiny.SEED, hybrid_tiny.config(dtype), dtype, toks))
            got = _cached_logits(hybrid_tiny.language_model(dtype, weights),
                                 toks, 100)
            memo[key] = np.abs(got - ref)
        return memo[key]

    return errors


# Tolerances of the program against the reference, logits of scale ~4:
# * float32, widest error 1e-4: the two differ in the order of float32 sums
#   only (chunked against token-by-token recurrence, absorbed against
#   expanded latent attention, experts by sorted tiles against one by one),
#   over 8 layers: measured 0.9e-5 to 1.3e-5 on three seeds.
# * bfloat16, MEDIAN error 0.055: bfloat16 rounding of every matmul's
#   operands (measured median 0.036-0.038 on three seeds). The widest error
#   says nothing here (1.4-3.0): a routed model's logits step wherever
#   rounding flips an expert choice the float32 reference does not, and at 16
#   experts of width 16 one flipped expert is a large share of a layer.
# The weight-only int8 path of the same dtype fails each: float32 compute
# reads a widest error of 1.6-3.2, bfloat16 a median of 0.061-0.076.
HYBRID_F32_MAX, HYBRID_BF16_MEDIAN = 1e-4, 0.055


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_hybrid_prefill_then_cached_decode_matches_reference(hybrid_errors, dtype):
    err = hybrid_errors(dtype)
    if dtype == "float32":
        assert err.max() < HYBRID_F32_MAX
    else:
        assert np.median(err) < HYBRID_BF16_MEDIAN


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_hybrid_int8_path_fails_the_tolerance(hybrid_errors, dtype):
    err = hybrid_errors(dtype, "int8")
    if dtype == "float32":
        assert err.max() > 100 * HYBRID_F32_MAX
    else:
        assert np.median(err) > HYBRID_BF16_MEDIAN


def test_hybrid_quantize_covers_every_new_matrix():
    import hybrid_tiny

    lm = hybrid_tiny.language_model("float32", "int8")
    q8 = {n for n, w in lm.params.items() if isinstance(w, llm.Q8)}
    full = {n.split(".", 1)[-1] for n in set(lm.params) - q8}
    # full precision on purpose: norms, router and its bias, filters, decay
    assert full == {"ln1", "ln2", "ln_f", "mla_kvnorm", "kda_onorm",
                    "moe_router", "moe_bias", "kda_conv_q", "kda_conv_k",
                    "kda_conv_v", "kda_A_log", "kda_dt_bias"}
    held, d, f = 4, 32, 16
    assert lm.params["l2.moe_wg"].scale.shape == (held, 1, f)   # per expert,
    assert lm.params["l2.moe_wd"].scale.shape == (held, 1, d)   # per channel
    assert lm.params["l5.mla_wkvb"].scale.shape == (1, 4, 16)


@pytest.mark.parametrize("chunk,T,case", [
    (16, 40, "random"), (32, 70, "random"), (64, 64, "random"),
    (64, 200, "published"), (64, 150, "correlated"), (32, 70, "correlated"),
    (64, 150, "correlated-floor"), (64, 150, "beta0")])
def test_kda_chunked_equals_stepped(chunk, T, case):
    """The chunked (WY / UT) form of the recurrence is the token-by-token
    step, decays from the strongest allowed (-5 a token: 1/Gamma overflows
    float32 within one chunk unless taken per sub-block) to none, padding
    positions (g = 0, beta = 0) leaving the state alone. Tolerance 2e-5 on
    outputs of scale ~0.1: float32 sums in another order. ``published`` is
    one row at the configuration's head width, T no multiple of the chunk;
    ``correlated`` is what one template's prompts give the chunk's triangular
    system (every key one unit vector plus 1e-2 of noise, beta in [0.9, 1):
    its strictly lower part sits near beta, which a substitution solves and a
    product of its powers does not), without decay and with every decay at
    the floor; ``beta0`` makes the system the identity."""
    rng = np.random.default_rng(chunk + T)
    B, H, d = (1, 2, 128) if case == "published" else (2, 3, 16)
    q, k, v = (rng.standard_normal((B, T, H, d)).astype(np.float32) for _ in range(3))
    if case.startswith("correlated"):
        k = (rng.standard_normal(d) + 1e-2 * k).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * 4
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -5.0 * rng.random((B, T, H, d)).astype(np.float32) ** 3
    g[:, :, 0] = -5.0
    beta = rng.random((B, T, H)).astype(np.float32)
    g[-1, 10:14], beta[-1, 10:14] = 0.0, 0.0
    if case.startswith("correlated"):
        g[:] = -5.0 if case == "correlated-floor" else 0.0
        beta = (0.9 + 0.1 * beta).astype(np.float32)
    if case == "beta0":
        beta[:] = 0.0
    S0 = rng.standard_normal((B, H, d, d)).astype(np.float32)
    o, S = llm.kda_chunked(*(jnp.asarray(a) for a in (q, k, v, g, beta, S0)), chunk)
    Sw, ow = jnp.asarray(S0), []
    for t in range(T):
        o_t, Sw = llm.kda_step(*(jnp.asarray(a[:, t]) for a in (q, k, v, g, beta)), Sw)
        ow.append(o_t)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), np.stack(ow, 1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(Sw), atol=2e-5)
    if case == "beta0":                   # nothing is written: S0 decayed, read
        decayed = S0[:, None] * np.exp(np.cumsum(g, 1))[..., None]
        np.testing.assert_allclose(
            np.asarray(o), np.einsum("bthkv,bthk->bthv", decayed, q), atol=2e-5)


def test_mla_absorbed_decode_equals_expanded():
    """One query a row against the cached latents: ``kv_b`` folded into the
    query and applied after the sum (decode) against K and V expanded per
    cached token (prefill's path). Float32, tolerance 1e-5."""
    import hybrid_tiny

    lm = hybrid_tiny.language_model("float32")
    cfg, l = lm.cfg, 5
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((3, 1, cfg.d_model)), jnp.float32)
    lat = jnp.asarray(rng.standard_normal((3, 40, 1, cfg.mla.latent_dim)),
                      jnp.float32)
    valid = jnp.arange(40)[None, None, :] <= jnp.asarray([39, 7, 20])[:, None, None]
    q, _ = llm._mla_project(lm.params, cfg, l, h, jnp.asarray([[39], [7], [20]]))
    a = llm._mla_absorbed(lm.params, cfg, l, q, lat, valid)
    b = llm._mla_expanded(lm.params, cfg, l, q, lat, valid)
    assert a.shape == b.shape == (3, 1, cfg.n_heads, cfg.mla.v_dim)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_expert_shares_add_up_to_the_whole_layer():
    """The four shares' routed parts (each chip computes the picks that land
    on its own 4 of the 16 experts) plus the shared expert counted once are
    the uncut reference's whole expert layer. Float32, tolerance 2e-5 on
    outputs of scale ~1."""
    import hybrid_tiny

    fam = hybrid_tiny.family()
    whole = hybrid_tiny.config("float32", num_experts=16)
    layer = 2                                   # the first expert layer (KDA)
    key = jax.random.fold_in(fam._root_key(hybrid_tiny.SEED), layer)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 50, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        w = fam._ref_weights(key, whole, "kda", "experts", jnp.float32)
        flat = fam._rms(x, whole["rms_norm_eps"]).reshape(-1, 32)
        want = fam._ref_experts(key, whole, "kda", jnp.float32, flat,
                                fam._ref_choice(w, whole, flat)) \
            + (jax.nn.silu(flat @ w["moe_sg"]) * (flat @ w["moe_su"])) @ w["moe_sd"]
    total, held_picks = 0.0, 0
    for first in (0, 4, 8, 12):
        lm = hybrid_tiny.language_model(
            "float32", expert_share={"first": first, "chips_sharing_a_layer": 4})
        assert lm.cfg.moe.held_start == first and lm.cfg.moe.held == 4
        y, stats = llm._experts_ffn(lm.params, lm.cfg, layer, x, jax.nn.silu, None)
        shared = llm._dense_mlp(lm.params, lm.cfg, layer, x, jax.nn.silu,
                                ("moe_sg", "moe_su", "moe_sd")) - x
        total = total + (y - x - shared)        # this share's routed part
        held_picks += int(stats["picks_held"])
        assert int(stats["picks"]) == 50 * 4
    assert held_picks == 50 * 4                 # every pick lands on one share
    np.testing.assert_allclose(np.asarray(total + shared)[0], np.asarray(want),
                               atol=2e-5)


def test_hybrid_param_shardings_name_every_leaf():
    import hybrid_tiny

    lm = hybrid_tiny.language_model("float32")
    mesh = model_mesh(2)
    sh = llm.param_shardings(lm.cfg, mesh)
    assert set(sh) == set(lm.params)
    placed = llm.shard_params(lm.params, lm.cfg, mesh)
    P = jax.sharding.PartitionSpec
    assert placed["l2.moe_wg"].sharding.spec == P(None, None, MODEL_AXIS)
    assert placed["l5.mla_wkva"].sharding.spec == P()
    assert placed["l2.kda_wq"].sharding.spec == P(None, MODEL_AXIS, None)


# ---------------------------------------------------------------------------
# the grouped expert product, sized by its load (ISSUE 36): moe_held_experts
# (tiles over the held picks, each token's float32 sum) against a plain
# reference; the tile rule at the published shapes; no (N x K, D) tensor
# ---------------------------------------------------------------------------

def _plain_experts(wg, wu, wd, xf, local, held, w, dtype):
    """Every held expert applied to EVERY token, the picks it did not get
    masked out; each expert's output rounded to ``dtype`` as the program
    rounds it, weighted and summed in float32."""
    def matrix(stack, e):
        if isinstance(stack, llm.Q8):
            return stack.q[e].astype(jnp.float32) * stack.scale[e]
        return stack[e].astype(jnp.float32)

    x = xf.astype(jnp.float32)
    total = jnp.zeros(xf.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        for e in range((wg.q if isinstance(wg, llm.Q8) else wg).shape[0]):
            hid = (jax.nn.silu((x @ matrix(wg, e)).astype(dtype))
                   * (x @ matrix(wu, e)).astype(dtype))
            out = (hid.astype(jnp.float32) @ matrix(wd, e)).astype(dtype)
            w_e = jnp.sum(jnp.where(held & (local == e), w, 0.0), axis=1)
            total = total + out.astype(jnp.float32) * w_e[:, None]
    return total


def _all_held(rng, n, k, e, r):
    return np.stack([rng.permutation(e)[:k] for _ in range(n)])


def _over_the_router(rng, n, k, e, r):
    return np.stack([rng.permutation(r)[:k] for _ in range(n)])


def _one_expert_past_two_tiles(rng, n, k, e, r):
    """Every token's first pick is expert 1; expert 3 is nobody's."""
    others = np.asarray([0, 2] + list(range(e, r)))
    return np.stack([np.concatenate([[1], rng.permutation(others)[:k - 1]])
                     for _ in range(n)])


def _nothing_held(rng, n, k, e, r):
    return np.stack([e + rng.permutation(r - e)[:k] for _ in range(n)])


# name: (N, K, held E, router outputs R, picks, dtype, int8 stacks, live rows)
HELD_EXPERT_CASES = {
    "all-held-n16": (16, 4, 8, 8, _all_held, "float32", False, None),
    "all-held-n40": (40, 4, 8, 8, _all_held, "float32", False, None),
    "all-held-n300-tile128": (300, 4, 8, 8, _all_held, "float32", False, None),
    "quarter-held-n16": (16, 4, 4, 16, _over_the_router, "float32", False, None),
    "quarter-held-n40": (40, 4, 4, 16, _over_the_router, "float32", False, None),
    "quarter-held-n300": (300, 4, 4, 16, _over_the_router, "float32", False, None),
    # 64 routed experts of which 2 are held, then 32 zero-compute outputs
    "one-in-48-n16": (16, 12, 2, 96, _over_the_router, "float32", False, None),
    "one-in-48-n40": (40, 12, 2, 96, _over_the_router, "float32", False, None),
    "one-in-48-n300": (300, 12, 2, 96, _over_the_router, "float32", False, None),
    "one-expert-past-two-tiles": (300, 4, 4, 64, _one_expert_past_two_tiles,
                                  "float32", False, None),
    "nothing-held": (40, 4, 4, 16, _nothing_held, "float32", False, None),
    # every pick held where the shapes expect an eighth: several blocks
    "eight-times-the-expected-load": (300, 12, 12, 96, _all_held, "float32",
                                      False, None),
    "padding-rows-not-live": (40, 4, 4, 16, _over_the_router, "float32", False, 29),
    "int8-stacks": (40, 4, 8, 8, _all_held, "float32", True, None),
    "int8-stacks-quarter-held-n300": (300, 4, 4, 16, _over_the_router,
                                      "float32", True, None),
    "bfloat16-all-held-n300": (300, 4, 8, 8, _all_held, "bfloat16", False, None),
    "bfloat16-one-in-48-n300": (300, 12, 2, 96, _over_the_router, "bfloat16",
                                False, None),
}


@pytest.mark.parametrize("case", sorted(HELD_EXPERT_CASES))
def test_held_experts_match_every_expert_on_every_token(case):
    """Output within the dtype's tolerance of the plain reference (values of
    scale ~1: float32 2e-5, the order of a token's float32 additions;
    bfloat16 0.03, a product's accumulation order before its rounding),
    ``counts`` the held picks an expert got, and the tile steps
    ``sum(ceil(counts / tile))``: none for an expert nobody chose."""
    N, K, E, R, picks, dtype, int8, live_rows = HELD_EXPERT_CASES[case]
    D, F = 32, 16
    dtype = jnp.dtype(dtype).type
    rng = np.random.default_rng(sorted(HELD_EXPERT_CASES).index(case))
    stacks = {name: jnp.asarray(rng.standard_normal(shape) / math.sqrt(shape[1]),
                                dtype)
              for name, shape in (("moe_wg", (E, D, F)), ("moe_wu", (E, D, F)),
                                  ("moe_wd", (E, F, D)))}
    if int8:
        stacks = llm.quantize_params(stacks)
    wg, wu, wd = stacks["moe_wg"], stacks["moe_wu"], stacks["moe_wd"]
    xf = jnp.asarray(rng.standard_normal((N, D)), dtype)
    idx = jnp.asarray(picks(rng, N, K, E, R), jnp.int32)
    w = jnp.asarray(rng.uniform(0.05, 1.0, (N, K)), jnp.float32)
    held = idx < E
    if live_rows is not None:
        held &= (jnp.arange(N) < live_rows)[:, None]
    got, counts, tiles = jax.jit(
        lambda *a: llm.moe_held_experts(*a, dtype, R))(wg, wu, wd, xf, idx, held, w)
    assert got.dtype == jnp.float32 and got.shape == (N, D)
    want = _plain_experts(wg, wu, wd, xf, idx, held, w, dtype)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 if dtype == jnp.float32 else 0.03)
    load = np.bincount(np.asarray(idx)[np.asarray(held)], minlength=E)
    assert np.asarray(counts).tolist() == load.tolist()
    tile = llm.moe_tile_rows(N, K, R)
    assert int(tiles) == int(np.sum(-(-load // tile)))
    held_np = np.asarray(held)
    if case == "nothing-held":
        assert int(tiles) == 0 and not np.asarray(got).any()
    else:
        assert np.abs(np.asarray(got)).max() > 0.05
    if case == "one-expert-past-two-tiles":
        assert load[1] == N > 2 * tile and load[3] == 0 < load[0]
    if case.startswith(("quarter-held", "one-in-48")):
        assert (~held_np.any(axis=1)).any()     # a token with no held pick ...
        assert not np.asarray(got)[~held_np.any(axis=1)].any()   # ... gets 0
    if case == "eight-times-the-expected-load":
        assert held_np.sum() > 2 * llm.moe_block_rows(N, K, R, E, tile)
    if live_rows is not None:
        assert not np.asarray(got)[live_rows:].any()


def _published_routing(name):
    """(top_k, router outputs) of a benchmark configuration as published,
    and the prompt width it is served at."""
    import json, os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    if cfg["model_type"] == "longcat_flash":
        top_k = cfg["moe_topk"]
        router = cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]
    else:
        top_k = cfg["num_experts_per_tok"]
        router = cfg.get("published", {}).get("num_experts", cfg["num_experts"])
    return top_k, router, cfg["desk"]["explain"]["prompt_width"]


@pytest.mark.parametrize("name,routing,bucket_tile", [
    ("desk-lr-ling-3.0-flash", (8, 512), 64),
    ("desk-lr-longcat-flash-chat", (12, 768), 64),
    ("desk-lr-lfm2-24b-a2b", (4, 64), 128),
])
def test_tile_rows_at_the_published_shapes(name, routing, bucket_tile):
    """The tile is a function of the bucket's rows, ``top_k`` and the
    router's width alone: 16 for a decode step's rows; 64 where a held
    expert's mean load is a fraction of 64 (the hybrid's and LongCat's
    17-27 rows at the prompts' buckets, every 320-token preamble's 20);
    128 for LFM2's 66-107."""
    top_k, router, width = _published_routing(name)
    assert (top_k, router) == routing and width == 2048
    for rows in (1, 2, 16):
        assert llm.moe_tile_rows(rows, top_k, router) == 16
    assert llm.moe_tile_rows(320, top_k, router) == 64
    for rows in (1088, 1728):
        assert llm.moe_tile_rows(rows, top_k, router) == bucket_tile
    # a block step covers twice the held picks the shapes expect, and for no
    # configuration every pick of a bucket unless every pick is held
    held = {"desk-lr-ling-3.0-flash": 128, "desk-lr-longcat-flash-chat": 16,
            "desk-lr-lfm2-24b-a2b": 64}[name]
    for rows in (1088, 1728):
        block = llm.moe_block_rows(rows, top_k, router, held, bucket_tile)
        assert 2 * rows * top_k * held / router <= block or block >= rows * top_k
        assert (block < rows * top_k) == (held < router)


def test_expert_branch_lowers_without_a_row_for_every_pick():
    """At a one-in-48-held shape the lowered ``_expert_branch`` holds no
    tensor of N x K (or more) rows by D, whatever its type: the gather of
    every pick, the un-sort of every pick's output and the float32 product
    over (N, K, D) are not in the program."""
    import re

    N, K, D = 300, 12, 40
    cfg = TransformerConfig(
        vocab_size=300, d_model=D, n_heads=2, n_layers=1, d_ff=64, max_seq=512,
        layer_kinds=(("attention", "experts"),),
        moe=llm.MoEConfig(n_experts=64, n_zero=32, top_k=K, n_group=1,
                          topk_group=1, d_expert=24, d_shared=0,
                          score="softmax", norm_topk=False, held_start=0,
                          held_count=2))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    h2 = jax.ShapeDtypeStruct((1, N, D), cfg.dtype)
    live = jax.ShapeDtypeStruct((1, N), jnp.bool_)
    text = jax.jit(lambda p, h, lv: llm._expert_branch(p, cfg, 0, h, lv)
                   ).lower(params, h2, live).as_text()
    shapes = {tuple(int(d) for d in m.group(1).split("x"))
              for m in re.finditer(r"tensor<(\d+(?:x\d+)*)x[a-z]+\d+>", text)}
    assert (N, D) in {s[-2:] for s in shapes if len(s) >= 2}    # the parse works
    wide = [s for s in shapes if len(s) >= 2 and s[-1] == D
            and math.prod(s[:-1]) >= N * K]
    assert not wide, wide
    assert (N, K) in shapes and D not in (N, K)
