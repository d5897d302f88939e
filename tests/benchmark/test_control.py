"""The controls come out as not correct: the plain reference, computed one
precision below what the configuration states and put in the program's
place, fails the comparison (here at sizes a test run can hold; on the chip
at the cells' own sizes, PERF.md has the readings)."""

import json
import os

import numpy as np
import pytest

from conftest import FIXTURES

from benchmark import check, corpus, reference


@pytest.mark.parametrize("config", ["tiny-desk", "tiny-desk-xgb"])
def test_classifier_in_bfloat16_is_not_correct(config, tmp_path):
    from benchmark import desk

    cfg = desk.load_config(os.path.join(FIXTURES, "configs", config + ".json"))
    spec = cfg["desk"]["classifier"]
    ckpt = desk.train_classifier(spec, seed=5, workdir=str(tmp_path))
    trained_on = reference.training_texts(
        [d.text for d in corpus.generate_corpus(n=spec["train_rows"], seed=5)],
        5, 0.7)
    art = reference.ClassifierArtifact(ckpt, trained_on)
    # The reference's own stop list and refitted IDF are the served ones.
    assert art.featurizer_numbers() == {"stoplist_mismatch": 0, "idf_gap": 0.0}
    assert check.verdict(art.featurizer_numbers())["correct"] is True
    texts = [d.text for d in corpus.generate_corpus(n=128, seed=6)]
    sent = dict(enumerate(texts))
    frames = {i: json.dumps({"original_text": t}).encode()
              for i, t in sent.items()}
    sound = check.classifier_numbers(art, frames, sent, "float32",
                                     reference_as_program=True)
    assert check.verdict(sound)["correct"] is True
    assert sound["confidence_gap"] == 0.0 and sound["frames_compared"] == 128
    control = check.classifier_numbers(art, frames, sent, "bfloat16",
                                       reference_as_program=True)
    got = check.verdict(control)
    assert got["correct"] is False
    assert control["confidence_gap"] > 3 * check.LIMITS["confidence_gap"]
    # An IDF fitted on other rows than the configuration states (here: the
    # whole corpus, not its training split), or held in bfloat16, is seen.
    whole = reference.ClassifierArtifact(
        ckpt, [d.text for d in corpus.generate_corpus(n=spec["train_rows"], seed=5)])
    assert whole.featurizer_numbers()["idf_gap"] > 0.1
    import ml_dtypes
    rounded = art.served_idf.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert np.max(np.abs(rounded - art.idf)) > 1e3 * check.LIMITS["idf_gap"]


def test_explainer_numbers_on_random_tokens_are_not_correct():
    cfg = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
           "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 4096,
           "rms_norm_eps": 1e-5, "rope_theta": 1e6, "hidden_act": "silu",
           "tie_word_embeddings": False, "torch_dtype": "float32"}
    rng = np.random.default_rng(0)
    # Ten requests of uneven length: two blocks of the reference, the
    # second one short.
    requests = [{"prompt": rng.integers(0, 258, 40 - i), "text": "x",
                 "served": rng.integers(0, 4096, 24 + i)} for i in range(10)]
    got = check.explainer_numbers(3, cfg, requests, 128,
                                  reference.llm_token_gaps)
    assert got["tokens_compared"] == sum(24 + i for i in range(10))
    assert got["prompt_mismatch"] == 0
    assert got["token_gap_max"] >= got["token_gap_off_best"] >= got["token_gap_mean"] > 0
    assert got["token_gap_sq"] == got["token_gap_off_best"] ** 2
    # Random tokens, put in the program's place, fail the cells' limit.
    assert check.verdict(got)["correct"] is False
    assert check.verdict({"token_gap_sq": float("nan")})["correct"] is False


def test_reference_blocks_agree_with_one_request_at_a_time():
    cfg = {"hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 300,
           "rms_norm_eps": 1e-5, "rope_theta": 1e6, "hidden_act": "silu",
           "tie_word_embeddings": False}
    rng = np.random.default_rng(1)
    requests = [{"prompt": rng.integers(0, 258, 30 + 3 * i),
                 "served": rng.integers(0, 300, 6)} for i in range(10)]
    together = reference.llm_token_gaps(9, cfg, "float32", requests, 64)
    for req, got in zip(requests, together):
        alone = reference.llm_token_gaps(9, cfg, "float32", [req], 64)[0]
        np.testing.assert_allclose(got, alone, atol=1e-5)


def test_weights_are_the_same_numbers_whole_and_layer_by_layer():
    import jax
    import jax.numpy as jnp

    cfg = {"hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "num_hidden_layers": 3, "vocab_size": 300,
           "tie_word_embeddings": False}
    seed = 2**31 + 12345
    whole = reference.make_llm_params(seed, cfg, jnp.bfloat16)
    root = reference._root_key(seed)
    for l in range(3):
        layer = reference._layer_weights(jax.random.fold_in(root, l), cfg,
                                         jnp.bfloat16)
        for name, w in layer.items():
            assert whole[f"l{l}.{name}"].dtype == jnp.bfloat16
            assert jnp.array_equal(whole[f"l{l}.{name}"], w)
    embed, head = reference._embed_weights(jax.random.fold_in(root, 3), cfg,
                                           jnp.bfloat16)
    assert jnp.array_equal(whole["embed"], embed)
    assert jnp.array_equal(whole["lm_head"], head)
    other = reference.make_llm_params(seed - 2**31, cfg, jnp.bfloat16)
    assert not jnp.array_equal(whole["embed"], other["embed"])   # high bits count


# (token_gap_mean, tokens_off_best) of every chip run on record (PR 25, one
# TPU v5e, 2,048 served tokens each): what token_gap_sq's limit was set from.
SOUND_BF16 = [
    (0.0006102, 0.040527), (0.0002511, 0.019531), (0.0006038, 0.043945),
    (0.0006895, 0.037109), (0.0003065, 0.022461), (0.0006913, 0.049316),
    (0.0006137, 0.040527), (0.0004723, 0.028320), (0.0009067, 0.056152),
    (0.0001699, 0.012207), (0.0003535, 0.022461), (0.0005401, 0.036621),
    (0.0006868, 0.044434), (0.0007844, 0.046387), (0.0005628, 0.034180),
    (0.0007998, 0.048340), (0.0008414, 0.054199), (0.0007595, 0.044434),
    (0.0008355, 0.057617), (0.0001587, 0.012207)]
PROGRAM_INT8 = [
    (0.0043405, 0.116211), (0.0039644, 0.062500), (0.0039658, 0.115234),
    (0.0045285, 0.123535), (0.0047175, 0.119141), (0.0024851, 0.074707),
    (0.0031872, 0.094238), (0.0051390, 0.109863)]


def test_the_explainer_limit_stands_between_the_recorded_readings():
    def sq(mean, share):            # mean over all tokens / share off best
        return (mean / share) ** 2

    lower = max(sq(*r) for r in SOUND_BF16)
    upper = min(sq(*r) for r in PROGRAM_INT8)
    limit = check.LIMITS["token_gap_sq"]
    assert upper >= 3.0 * lower
    assert 1.5 * lower < limit < upper / 1.5
    # The mean over all tokens, which the share of near ties moves as much
    # as the precision does, no longer separates the two by three times.
    assert min(m for m, _ in PROGRAM_INT8) < 3.0 * max(m for m, _ in SOUND_BF16)


def test_the_internlm2_family_binds_these_weights_and_this_reference(spec):
    """Through ``explainers/internlm2.py`` the weights of a seed are bitwise
    ``reference.make_llm_params``'s and the gaps ``reference.llm_token_gaps``'s
    (the cases of the two tests above)."""
    import jax.numpy as jnp

    from benchmark import run

    cfg = {"model_type": "internlm2", "hidden_size": 32, "intermediate_size": 64,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "num_hidden_layers": 3, "vocab_size": 300, "rms_norm_eps": 1e-5,
           "rope_theta": 1e6, "hidden_act": "silu", "tie_word_embeddings": False}
    family = run.load_family(spec, cfg)
    seed = 2**31 + 12345
    mine = family.make_params(seed, cfg, jnp.bfloat16)
    theirs = reference.make_llm_params(seed, cfg, jnp.bfloat16)
    assert set(mine) == set(theirs)
    for name, w in theirs.items():
        assert mine[name].dtype == w.dtype and jnp.array_equal(mine[name], w)
    rng = np.random.default_rng(1)
    requests = [{"prompt": rng.integers(0, 258, 30 + 3 * i),
                 "served": rng.integers(0, 300, 6)} for i in range(10)]
    for got, want in zip(family.token_gaps(9, cfg, "float32", requests, 64),
                         reference.llm_token_gaps(9, cfg, "float32", requests, 64)):
        np.testing.assert_array_equal(got, want)
    # The limit it is held to is check.LIMITS': its files state none.
    assert check.stated_limits(cfg) == check.LIMITS
    assert check.stated_limits(cfg) is not check.LIMITS


def test_the_internlm2_family_builds_what_the_desk_built(spec):
    """``build`` hands the slot lane the program's model at the stated
    dtype, its own int8 path as the lower precision, a tokenizer on both."""
    import jax.numpy as jnp

    from benchmark import desk, run

    cfg = desk.load_config(os.path.join(FIXTURES, "configs", "tiny-desk.json"))
    family = run.load_family(spec, cfg)
    params = family.make_params(4, cfg, jnp.float32)
    stated = family.build(cfg, params, cfg["torch_dtype"])
    lower = family.build(cfg, params, "int8")
    assert stated.params is params and stated.cfg.dtype == jnp.float32
    assert (stated.cfg.n_layers, stated.cfg.kv_heads, stated.cfg.d_ff) == (2, 2, 64)
    assert not stated.cfg.tie_embeddings and stated.cfg.activation == "silu"
    assert type(lower.params["l0.wq"]).__name__ == "Q8"
    assert list(stated.tokenizer.encode("a")) == [256, 97] \
        == list(lower.tokenizer.encode("a"))
