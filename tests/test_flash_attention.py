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
