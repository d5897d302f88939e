"""Flash-attention kernel parity (ops/attention.py vs models/llm.py _attend).

The kernel's contract is numerical equivalence with the materialized-score
path — same inputs, same causal mask — to f32 round-off. Runs in interpret
mode on the CPU test mesh; chip_smoke.py runs the compiled kernel on a TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fraud_detection_tpu.models import llm
from fraud_detection_tpu.ops.attention import flash_attention


def _ref(q, k, v):
    causal = jnp.tril(jnp.ones((q.shape[1], q.shape[1]), bool))
    return llm._attend(q, k, v, causal)


@pytest.mark.parametrize("shape", [
    (2, 384, 3, 64),    # T not a block multiple, d < 128 (padding paths)
    (1, 256, 2, 128),   # exact tiles
    (1, 131, 1, 32),    # ragged everything
])
def test_flash_matches_attend(shape):
    B, T, H, d = shape
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    k = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    v = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    got = flash_attention(q, k, v, interpret=True)
    want = _ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def _offset_mask(T, S, q_offset):
    return jnp.arange(S)[None, :] <= q_offset + jnp.arange(T)[:, None]


@pytest.mark.parametrize("case", [
    # (T, H, Hkv, d, dv, q_offset, columns past q_offset + T, blocks)
    pytest.param((256, 2, 2, 128, 128, 0, 0, 0), id="square-offset-0"),
    pytest.param((192, 2, 2, 64, 64, 37, 27, 128), id="offset-garbage-tail"),
    pytest.param((256, 4, 2, 32, 32, 128, 64, 128), id="gqa-2"),
    pytest.param((128, 2, 2, 192, 128, 64, 0, 128), id="mha-d192-dv128"),
    pytest.param((200, 1, 1, 64, 64, 100, 20, 128), id="ragged-T"),
    pytest.param((384, 1, 1, 64, 32, 293, 27, (128, 256)), id="blk_q-ne-blk_k"),
])
def test_flash_suffix_matches_attend_under_the_offset_mask(case):
    """What a suffix prefill is: T queries at the static ``q_offset`` against
    S >= q_offset + T keys, values of a width of their own. Query row j
    attends columns <= q_offset + j, and what lies past q_offset + T is never
    read: filling it with other garbage changes no bit."""
    T, H, Hkv, d, dv, q_offset, tail, blocks = case
    blk_q, blk_k = blocks if isinstance(blocks, tuple) else (blocks, blocks)
    S = q_offset + T + tail
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(1, T, H, d)).astype(np.float32))
    k = rng.normal(size=(1, S, Hkv, d)).astype(np.float32)
    v = rng.normal(size=(1, S, Hkv, dv)).astype(np.float32)
    k[:, q_offset + T:], v[:, q_offset + T:] = 1e4, -1e4
    got = flash_attention(q, jnp.asarray(k), jnp.asarray(v), q_offset=q_offset,
                          blk_q=blk_q, blk_k=blk_k, interpret=True)
    want = llm._attend(q, jnp.asarray(k), jnp.asarray(v),
                       _offset_mask(T, S, q_offset))
    assert got.shape == (1, T, H, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    if tail:
        k[:, q_offset + T:], v[:, q_offset + T:] = -7.0, 3e3
        again = flash_attention(q, jnp.asarray(k), jnp.asarray(v),
                                q_offset=q_offset, blk_q=blk_q, blk_k=blk_k,
                                interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(again))


def test_flash_over_several_major_key_blocks(monkeypatch):
    """Keys past what one head keeps resident come as further major blocks
    (the grid's innermost axis); the accumulators carry across them and a
    block above a query block's diagonal is skipped whole. Two chunks of 128
    a block here: five blocks for 600 + 640 columns."""
    from fraud_detection_tpu.ops import attention

    monkeypatch.setattr(attention, "_MAJOR_KEYS", 256)
    T, q_offset = 640, 600
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.normal(size=(1, T, 2, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, q_offset + T, 1, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, q_offset + T, 1, 64)).astype(np.float32))
    flash = jax.jit(attention.flash_attention.__wrapped__, static_argnames=(
        "q_offset", "blk_q", "blk_k", "interpret"))   # a trace of this limit
    got = flash(q, k, v, q_offset=q_offset, blk_q=128, blk_k=128,
                interpret=True)
    want = llm._attend(q, k, v, _offset_mask(T, q_offset + T, q_offset))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_refuses_a_view_shorter_than_its_queries_see():
    q = jnp.zeros((1, 128, 1, 32))
    with pytest.raises(ValueError, match="need 192 keys"):
        flash_attention(q, q, q, q_offset=64, interpret=True)


def test_flash_matches_attend_bf16():
    rng = np.random.default_rng(9)
    shape = (1, 256, 2, 64)
    q = jnp.asarray(rng.normal(size=shape)).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=shape)).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=shape)).astype(jnp.bfloat16)
    got = flash_attention(q, k, v, interpret=True)
    want = _ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_forward_uses_flash_above_threshold(monkeypatch):
    """The full-sequence forward must produce the same logits whether the
    flash kernel or the materialized path runs — proven by flipping the
    dispatch threshold around one T."""
    cfg = llm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=2, d_ff=64, max_seq=640)
    params = llm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, size=(1, 576)), jnp.int32)

    monkeypatch.setattr(llm, "_FLASH_MIN_T", 10_000)  # force materialized
    ref_logits, _ = llm.forward(params, tokens, cfg)
    monkeypatch.setattr(llm, "_FLASH_MIN_T", 1)       # force flash
    flash_logits, _ = llm.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(flash_logits),
                               np.asarray(ref_logits), atol=5e-4, rtol=5e-4)


def test_flash_gqa_native_kv_matches_expanded():
    """GQA/MQA kv at native width through the kernel's head-group index map
    must equal the expanded-kv computation exactly (same blocks, same
    accumulation order — the expansion only changes WHERE K/V bytes come
    from, not the math)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fraud_detection_tpu.models.llm import _attend
    from fraud_detection_tpu.ops.attention import flash_attention

    B, T, H, Hkv, d = 2, 192, 4, 1, 32
    rng = jax.random.PRNGKey(5)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (B, T, H, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, T, Hkv, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, T, Hkv, d), jnp.float32)
    ke, ve = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))

    native = flash_attention(q, k, v, interpret=True)
    expanded = flash_attention(q, ke, ve, interpret=True)
    np.testing.assert_array_equal(np.asarray(native), np.asarray(expanded))

    tril = jnp.tril(jnp.ones((T, T), bool))
    np.testing.assert_allclose(np.asarray(native),
                               np.asarray(_attend(q, ke, ve, tril)),
                               rtol=2e-5, atol=2e-5)

    # GQA with 2 groups exercises a non-trivial b%H//rep map.
    k2 = jax.random.normal(jax.random.fold_in(rng, 3), (B, T, 2, d), jnp.float32)
    v2 = jax.random.normal(jax.random.fold_in(rng, 4), (B, T, 2, d), jnp.float32)
    ke2, ve2 = (jnp.repeat(t, 2, axis=2) for t in (k2, v2))
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k2, v2, interpret=True)),
        np.asarray(flash_attention(q, ke2, ve2, interpret=True)))
