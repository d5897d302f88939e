"""On-pod LLM trainer (models/train_llm.py): learning, sharding, resume.

Runs on the 8-virtual-device CPU mesh from conftest. Tiny configs keep the
compiles fast; the contracts are what matter — loss goes down, the dp x tp
sharded step preserves parameter layouts, and checkpoint resume continues
bit-identically.
"""

import jax
import numpy as np
import pytest

from fraud_detection_tpu.models.llm import MODEL_AXIS, TransformerConfig
from fraud_detection_tpu.models.train_llm import (
    LLMTrainConfig,
    batch_for_step,
    fit_language_model,
    pack_corpus,
)

TINY = TransformerConfig(d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=128)

CORPUS = [
    "agent: hello this is the prize department calling about your winnings",
    "customer: i did not enter any lottery please remove me from your list",
    "agent: we just need a small processing fee paid with gift cards today",
    "customer: that sounds like a scam i am hanging up now goodbye",
] * 8


def test_pack_and_batch_are_deterministic():
    stream = pack_corpus(CORPUS, TINY)
    assert stream.dtype == np.int32 and stream.size > 100
    tcfg = LLMTrainConfig(batch_size=4, seq_len=32, seed=3)
    b1 = batch_for_step(stream, 7, tcfg)
    b2 = batch_for_step(stream, 7, tcfg)
    np.testing.assert_array_equal(b1, b2)
    assert b1.shape == (4, 33)
    assert not np.array_equal(b1, batch_for_step(stream, 8, tcfg))


def test_loss_decreases_single_device():
    tcfg = LLMTrainConfig(steps=30, batch_size=4, seq_len=32,
                          learning_rate=1e-2, warmup_steps=5, seed=1)
    lm, losses = fit_language_model(CORPUS, TINY, tcfg)
    assert len(losses) == 30
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])
    out = lm.generate_text("agent: hello", max_new_tokens=8)
    assert isinstance(out, str)


def test_dp_tp_mesh_training_step_keeps_shardings():
    import jax
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("data", MODEL_AXIS))
    tcfg = LLMTrainConfig(steps=4, batch_size=4, seq_len=16, seed=2)
    lm, losses = fit_language_model(CORPUS, TINY, tcfg, mesh=mesh)
    assert all(np.isfinite(losses))
    # TP matrices stay model-axis sharded through the optimizer update.
    from fraud_detection_tpu.models.llm import param_shardings

    sh = param_shardings(TINY, mesh)
    for name in ("l0.wq", "l1.w_down"):
        assert lm.params[name].sharding.is_equivalent_to(
            sh[name], lm.params[name].ndim), name


def test_remat_matches_no_remat():
    tcfg = LLMTrainConfig(steps=6, batch_size=2, seq_len=16, seed=4)
    _, base = fit_language_model(CORPUS, TINY, tcfg)
    tcfg_r = LLMTrainConfig(steps=6, batch_size=2, seq_len=16, seed=4, remat=True)
    _, remat = fit_language_model(CORPUS, TINY, tcfg_r)
    np.testing.assert_allclose(base, remat, rtol=1e-5)


def test_resume_is_bit_identical(tmp_path):
    # decay_steps pinned so the 6-step "interrupted" run and the 12-step
    # resume share the exact LR schedule at every step index.
    tcfg = LLMTrainConfig(steps=12, batch_size=2, seq_len=16, decay_steps=12,
                          learning_rate=3e-3, warmup_steps=2, seed=5)
    full, _ = fit_language_model(CORPUS, TINY, tcfg)

    ckpt = str(tmp_path / "lm")
    half = LLMTrainConfig(**{**tcfg.__dict__, "steps": 6})
    fit_language_model(CORPUS, TINY, half, checkpoint_dir=ckpt, checkpoint_every=3)
    resumed, tail_losses = fit_language_model(
        CORPUS, TINY, tcfg, checkpoint_dir=ckpt, checkpoint_every=3)
    assert len(tail_losses) == 6  # only the remaining steps ran
    for k in full.params:
        np.testing.assert_array_equal(np.asarray(full.params[k]),
                                      np.asarray(resumed.params[k]), err_msg=k)


def test_resume_refuses_different_corpus(tmp_path):
    tcfg = LLMTrainConfig(steps=4, batch_size=2, seq_len=16, seed=6)
    ckpt = str(tmp_path / "lm2")
    fit_language_model(CORPUS, TINY, tcfg, checkpoint_dir=ckpt, checkpoint_every=2)
    with pytest.raises(ValueError, match="different setup"):
        fit_language_model(CORPUS[:8] + ["totally different text"], TINY,
                           LLMTrainConfig(**{**tcfg.__dict__, "steps": 8}),
                           checkpoint_dir=ckpt)


def test_resume_refuses_overtrained_snapshot(tmp_path):
    """Requesting FEWER steps than the snapshot has trained must raise, not
    silently return the over-trained model (AdamW state can't be rolled
    back, unlike boosting rounds)."""
    tcfg = LLMTrainConfig(steps=6, batch_size=2, seq_len=16, decay_steps=6,
                          warmup_steps=2, seed=7)
    ckpt = str(tmp_path / "lm3")
    fit_language_model(CORPUS, TINY, tcfg, checkpoint_dir=ckpt,
                       checkpoint_every=3)
    with pytest.raises(ValueError, match="already trained"):
        fit_language_model(CORPUS, TINY,
                           LLMTrainConfig(**{**tcfg.__dict__, "steps": 3}),
                           checkpoint_dir=ckpt)


def test_resume_refuses_different_mesh(tmp_path):
    """An off-mesh snapshot must not resume on a mesh: data-parallel gradient
    psum reduction order depends on topology (same guard as the tree
    trainers)."""
    from fraud_detection_tpu.parallel.mesh import make_mesh

    tcfg = LLMTrainConfig(steps=4, batch_size=2, seq_len=16, decay_steps=4,
                          warmup_steps=1, seed=8)
    ckpt = str(tmp_path / "lm4")
    fit_language_model(CORPUS, TINY, tcfg, checkpoint_dir=ckpt,
                       checkpoint_every=2)
    with pytest.raises(ValueError, match="different setup"):
        fit_language_model(CORPUS, TINY,
                           LLMTrainConfig(**{**tcfg.__dict__, "steps": 8}),
                           mesh=make_mesh(n_devices=2),
                           checkpoint_dir=ckpt)


def test_too_small_corpus_raises():
    with pytest.raises(ValueError, match="smaller than one"):
        fit_language_model(["hi"], TINY,
                           LLMTrainConfig(steps=1, batch_size=2, seq_len=128))


def test_window_sampling_reaches_stream_tail():
    """The final window (ending on the stream's last token) must be drawable —
    the off-by-one that dropped it would under-train the corpus tail."""
    stream = pack_corpus(CORPUS, TINY)
    tcfg = LLMTrainConfig(batch_size=64, seq_len=32, seed=0)
    tail = stream[-(tcfg.seq_len + 1):]
    for s in range(200):
        batch = batch_for_step(stream, s, tcfg)
        if any(np.array_equal(row, tail) for row in batch):
            return
    pytest.fail("no sampled window ever ended on the stream's last token")


def test_dp_sp_mesh_training_step():
    """Sequence-parallel fine-tuning: one step over a (data=2, seq=4) mesh —
    ring attention inside the jitted train step, gradients flowing back
    through the ppermute rotation — must reproduce the single-device loss
    trajectory."""
    import jax
    import numpy as np

    from fraud_detection_tpu.models.llm import SEQ_AXIS, TransformerConfig
    from fraud_detection_tpu.models.train_llm import (DATA_AXIS,
                                                      LLMTrainConfig,
                                                      fit_language_model)
    from jax.sharding import Mesh

    texts = [f"agent hello customer {i} this is a training transcript " * 3
             for i in range(20)]
    cfg = TransformerConfig(d_model=32, n_heads=4, n_layers=1, d_ff=64,
                            max_seq=128)
    tcfg = LLMTrainConfig(steps=3, batch_size=4, seq_len=32, seed=5)

    _, base_losses = fit_language_model(texts, cfg, tcfg)

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, (DATA_AXIS, SEQ_AXIS))
    _, sp_losses = fit_language_model(texts, cfg, tcfg, mesh=mesh)

    np.testing.assert_allclose(sp_losses, base_losses, rtol=3e-4, atol=3e-4)


def test_sp_seq_len_divisibility_rejected():
    from fraud_detection_tpu.models.llm import SEQ_AXIS, TransformerConfig
    from fraud_detection_tpu.models.train_llm import (DATA_AXIS,
                                                      LLMTrainConfig,
                                                      fit_language_model)
    from jax.sharding import Mesh
    import jax
    import numpy as np

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                (DATA_AXIS, SEQ_AXIS))
    with pytest.raises(ValueError, match="seq_len"):
        fit_language_model(
            ["some text to train on " * 10],
            TransformerConfig(d_model=32, n_heads=4, n_layers=1, d_ff=64,
                              max_seq=128),
            LLMTrainConfig(steps=1, batch_size=2, seq_len=30), mesh=mesh)
