"""The device programs name their parts (``jax.named_scope``,
docs/observability.md "Names on the device") and the names are metadata only:
with every scope taken away the lowered program is the same text and the
outputs are the same bits.
"""

import contextlib

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
            CFG, pages, tables, jnp.zeros(3, jnp.float32), key, 4, 60)
    return (llm.paged_decode_window, (5, 10, 11), args,
            LAYER_SCOPES | {"kv.append"})


def _prefill_case():
    params, pages, tables, key = _llm_inputs()
    tokens = jnp.asarray(np.arange(32).reshape(1, 32) % 250, jnp.int32)
    args = (params, tokens, jnp.int32(40), CFG, pages, tables[0],
            jnp.float32(0.0), key, 16)
    return llm.paged_slot_prefill, (3, 8), args, LAYER_SCOPES


def _contiguous_decode_case():
    _, _, a, _ = _decode_case()       # the same window over a contiguous pool
    args = a[:6] + (llm.init_cache(CFG, 3, a[11]),) + a[8:11]
    return (llm.slot_decode_window, (5, 9), args,
            LAYER_SCOPES - {"kv.gather_pages", "kv.scatter_pages"}
            | {"kv.append"})


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


@pytest.mark.parametrize("case", [_decode_case, _contiguous_decode_case,
                                  _prefill_case, _lr_case, _tree_case])
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
    (_decode_case, (3, 60)),              # 3 slots, view_len 60
    (_contiguous_decode_case, (3, 60)),
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
