"""Device-side featurization (ops/featurize_kernel.py + featurize/device.py):
the Pallas byte-scan kernel must be BYTE-IDENTICAL to the host featurizer —
clean/tokenize/stop-filter/murmur-hash/count, packed layout included — and
the serving integration must keep every scoring path's outputs exact while
shipping raw bytes as the only host->device crossing.

Kernel tests run in interpret mode on the CPU mesh, unconditionally: a
kernel the installed JAX cannot run is a failure, not a skip.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fraud_detection_tpu.featurize.device import (
    DeviceFeaturizer,
    DeviceFeaturizeUnavailable,
    pack_bytes,
    pack_staged,
)
from fraud_detection_tpu.featurize.hashing import HashingTF, spark_hash_bucket
from fraud_detection_tpu.featurize.tfidf import (
    HashingTfIdfFeaturizer,
    VocabTfIdfFeaturizer,
)
from fraud_detection_tpu.models.pipeline import (
    ServingPipeline,
    synthetic_demo_pipeline,
    unpack_packed_host,
)


def _python_twin(feat: HashingTfIdfFeaturizer,
                 legacy: bool = False) -> HashingTfIdfFeaturizer:
    """Pure-Python host reference (the native C++ path implements only the
    standard hash, so legacy-mode references MUST bypass it)."""
    twin = HashingTfIdfFeaturizer(
        num_features=feat.num_features, idf=feat.idf,
        binary_tf=feat.binary_tf, stop_filter=feat.stop_filter,
        remove_stopwords=feat.remove_stopwords)
    if legacy:
        twin._hashing = HashingTF(feat.num_features, binary=feat.binary_tf,
                                  legacy=True)
    twin._native_tried, twin._native = True, None
    return twin


def _device_pairs(dev, texts, batch_size):
    staged, _ = dev.pack(texts, batch_size)
    packed = np.asarray(dev.encode_packed(staged))
    return unpack_packed_host(packed)


def _assert_device_matches_host(dev, host, texts, batch_size=None):
    b = batch_size or len(texts)
    ids_d, cnt_d = _device_pairs(dev, texts, b)
    want = host.encode(dev.decode_truncated(texts), batch_size=b,
                       max_tokens=dev.tokens)
    np.testing.assert_array_equal(ids_d, np.asarray(want.ids))
    np.testing.assert_array_equal(cnt_d, np.asarray(want.counts))


# ---------------------------------------------------------------------------
# the clean_text parity table
# ---------------------------------------------------------------------------

def test_special_lower_table_is_exhaustive():
    """Re-derive, over ALL of Unicode, every codepoint whose ``str.lower()``
    contains a char in [a-z ] — the kernel's byte-classing special cases.
    Pins SPECIAL_LOWER so a Unicode-table change in a future Python can't
    silently break device/host parity."""
    from fraud_detection_tpu.ops import featurize_kernel as fk

    keep = set("abcdefghijklmnopqrstuvwxyz ")
    found = {}
    for cp in range(0x80, 0x110000):
        if 0xD800 <= cp <= 0xDFFF:
            continue
        kept = [c for c in chr(cp).lower() if c in keep]
        if kept:
            found[cp] = "".join(kept)
    want = {int.from_bytes(b"", "big"): None}  # placate linters; rebuilt below
    want = {}
    for seq, ch in fk.SPECIAL_LOWER:
        want[seq.decode("utf-8")] = chr(ch)
    assert {chr(cp): s for cp, s in found.items()} == want


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

ADVERSARIAL = [
    "hello world hello",
    "",
    "   ",
    "the a an and of urgent urgent account",    # default stop words
    "İstanbul K 42 --- !!!",                    # the two special codepoints
    "a  b   c",                                 # interior empty tokens
    "tab\tand\nnewline stay joined",            # \t\n strip -> tokens JOIN
    "ALL CAPS MiXeD",
    "ß é ü ñ",                                  # strip to spaces only
    "x" * 90,                                   # one token past the pack width
    "z 9 9 9",                                  # digits strip -> empty fields
    "trailing spaces   ",
    "🚀 emoji 🚀🚀 between 🚀",
    "a" * 12 + " " + "b" * 13,                  # pack-width boundary tokens
]


def test_kernel_matches_host_on_adversarial_corpus():
    feat = HashingTfIdfFeaturizer(num_features=1000)
    dev = DeviceFeaturizer(feat, width=128, tokens=16, interpret=True)
    _assert_device_matches_host(dev, _python_twin(feat), ADVERSARIAL)


@pytest.mark.parametrize("legacy", [False, True])
@pytest.mark.parametrize("binary", [False, True])
def test_kernel_fuzz_parity_all_hash_modes(legacy, binary):
    """Seeded fuzz over the tricky alphabet in every (legacy, binary)
    combination — the packed arrays must be byte-identical to the pure-
    Python reference, padding rows and truncation included."""
    import random

    rng = random.Random(1234 + 2 * legacy + binary)
    alphabet = list("abcXYZ  \t\n0!-'") + ["İ", "K", "ß", "é", "🚀"]
    feat = HashingTfIdfFeaturizer(num_features=997, binary_tf=binary)
    if legacy:
        feat._hashing = HashingTF(997, binary=binary, legacy=True)
    dev = DeviceFeaturizer(feat, width=64, tokens=8, interpret=True)
    twin = _python_twin(feat, legacy=legacy)
    for trial in range(12):
        texts = ["".join(rng.choice(alphabet)
                         for _ in range(rng.randrange(0, 90)))
                 for _ in range(5)]
        if trial % 4 == 0:
            texts[0] = ""           # genuine empty row next to padding rows
        _assert_device_matches_host(dev, twin, texts, batch_size=8)


def test_empty_text_vs_padding_row():
    """A real "" tokenizes to [""] and counts one empty-token bucket (Java
    split semantics) on BOTH paths; padding rows beyond len(texts) must
    stay all-zero. The two are distinguished by the -1 length sentinel."""
    feat = HashingTfIdfFeaturizer(num_features=1000)
    dev = DeviceFeaturizer(feat, width=32, tokens=8, interpret=True)
    ids, cnt = _device_pairs(dev, [""], 4)
    empty_bucket = spark_hash_bucket("", 1000)
    assert ids[0, 0] == empty_bucket and cnt[0, 0] == 1
    assert not cnt[1:].any()
    host = _python_twin(feat).encode([""], batch_size=4, max_tokens=8)
    np.testing.assert_array_equal(ids, np.asarray(host.ids))
    np.testing.assert_array_equal(cnt, np.asarray(host.counts))


def test_high_count_rows():
    feat = HashingTfIdfFeaturizer(num_features=1000)
    dev = DeviceFeaturizer(feat, width=2048, tokens=8, interpret=True)
    texts = ["spam " * 300, "spam eggs " * 100]
    _assert_device_matches_host(dev, _python_twin(feat), texts)


def test_overflow_truncation_matches_host_rule():
    """More unique buckets than token slots: the device applies the HOST
    truncation rule (keep top counts, ties toward the lower bucket id) —
    pinned against host encode at the same max_tokens."""
    import random

    rng = random.Random(7)
    words = ["w" + chr(97 + i) + chr(97 + j)
             for i in range(8) for j in range(5)]
    texts = [" ".join(rng.choice(words)
                      for _ in range(120)) for _ in range(4)]
    feat = HashingTfIdfFeaturizer(num_features=1000)
    dev = DeviceFeaturizer(feat, width=512, tokens=8, interpret=True)
    ids_d, cnt_d = _device_pairs(dev, texts, 4)
    assert (np.count_nonzero(cnt_d, axis=1) == 8).all()   # genuinely overflowed
    want = _python_twin(feat).encode(texts, batch_size=4, max_tokens=8)
    np.testing.assert_array_equal(ids_d, np.asarray(want.ids))
    np.testing.assert_array_equal(cnt_d, np.asarray(want.counts))


def test_truncation_honesty():
    """Byte-width truncation cuts at a CODEPOINT boundary, is counted, and
    the device result equals the host featurizer run on the truncated
    text — truncation changes the input, never the semantics."""
    text = "hello " * 10 + "ééé"         # multi-byte tail straddles the cut
    feat = HashingTfIdfFeaturizer(num_features=1000)
    for width in (61, 62, 63, 64):
        byts, lengths, truncated = pack_bytes([text], width)
        assert truncated == 1
        decoded = bytes(byts[0, : lengths[0]]).decode("utf-8")  # must not raise
        dev = DeviceFeaturizer(feat, width=width, tokens=16, interpret=True)
        assert dev.decode_truncated([text]) == [decoded]
        _assert_device_matches_host(dev, _python_twin(feat), [text])


def test_pack_staged_roundtrip_lengths():
    staged, truncated = pack_staged(["ab", "", "c" * 50], 32, batch_size=4)
    assert staged.shape == (4, 36) and truncated == 1
    lens = staged[:, 32:].copy().view("<i4").ravel()
    assert list(lens) == [2, 0, 32, -1]   # text, empty, truncated, PADDING


def test_non_negative_mod_parity_on_negative_hashes():
    """jnp floor-mod == Spark nonNegativeMod for signed 32-bit hashes."""
    from fraud_detection_tpu.featurize.hashing import non_negative_mod

    vals = np.array([-2147483648, -10007, -1, 0, 1, 9999, 2147483647],
                    np.int32)
    got = np.asarray(jnp.remainder(jnp.asarray(vals), jnp.int32(10000)))
    want = [non_negative_mod(int(v), 10000) for v in vals]
    assert got.tolist() == want


# ---------------------------------------------------------------------------
# stop table
# ---------------------------------------------------------------------------

def test_stop_table_build_and_refusal():
    from fraud_detection_tpu.ops.featurize_kernel import (build_stop_table,
                                                          pack_token)

    tbl, empty_is_stop = build_stop_table(["the", "don't", "a", ""])
    assert empty_is_stop
    # "don't" can never equal a cleaned [a-z]* token: dropped, exact.
    present = {tuple(r) for r in tbl[tbl[:, 2] >= 0].tolist()}
    assert present == {pack_token("the"), pack_token("a")}
    # A pure-alpha word longer than the pack width WOULD alias: refuse.
    assert build_stop_table(["abcdefghijklm"]) is None
    assert build_stop_table(list("abc")) is not None


def test_stopword_removal_exact_on_device():
    """Every default stop word must vanish on device exactly as on host —
    including 'i' reached via İ and one-char words."""
    feat = HashingTfIdfFeaturizer(num_features=1000)
    stop_words = feat.stop_filter.words
    assert len(stop_words) == 181
    dev = DeviceFeaturizer(feat, width=2048, tokens=64, interpret=True)
    # Apostrophe stop words ("don't") clean to NON-stop tokens ("dont") and
    # are legitimately kept by both paths; only the pure-alpha ones vanish.
    alpha_stops = [w for w in stop_words
                   if all("a" <= c <= "z" for c in w)]
    assert len(alpha_stops) > 100
    texts = [" ".join(alpha_stops),                # pure-alpha: no tokens
             " ".join(stop_words),                 # apostrophe variants stay
             "İ myself and ourselves keep nothing but fraud",
             "notastopword the notastopword"]
    _assert_device_matches_host(dev, _python_twin(feat), texts)
    ids, cnt = _device_pairs(dev, texts[:1], 1)
    assert not cnt.any()


def test_device_featurizer_refuses_unrepresentable_configs():
    with pytest.raises(DeviceFeaturizeUnavailable, match="vocabulary"):
        DeviceFeaturizer(VocabTfIdfFeaturizer(vocabulary=["a", "b"]),
                         interpret=True)
    with pytest.raises(DeviceFeaturizeUnavailable, match="int16"):
        DeviceFeaturizer(HashingTfIdfFeaturizer(num_features=40000),
                         interpret=True)
    from fraud_detection_tpu.featurize.text import StopWordFilter

    long_stop = HashingTfIdfFeaturizer(
        num_features=100, stop_filter=StopWordFilter(["abcdefghijklmnop"]))
    with pytest.raises(DeviceFeaturizeUnavailable, match="stop list"):
        DeviceFeaturizer(long_stop, interpret=True)


# ---------------------------------------------------------------------------
# serving pipeline integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def demo():
    from fraud_detection_tpu.data import generate_corpus

    pipe = synthetic_demo_pipeline(batch_size=32, n=200, seed=7)
    texts = [d.text for d in generate_corpus(n=96, seed=5)]
    return pipe, texts


def test_pipeline_parity_lr(demo):
    host, texts = demo
    dev = ServingPipeline(host.featurizer, host.model, batch_size=32,
                          featurize_device="interpret")
    assert dev.device_stats.featurize_path == "interpret"
    ph, pd = host.predict(texts), dev.predict(texts)
    np.testing.assert_array_equal(ph.labels, pd.labels)
    assert float(np.abs(ph.probabilities - pd.probabilities).max()) < 1e-6
    snap = dev.device_stats.snapshot()
    assert snap["uploads_per_chunk"] == 1.0          # ONE crossing per chunk
    assert snap["featurize_path"] == "interpret"
    assert snap["truncated_rows"] == 0
    assert snap["bytes_in_per_row"] is not None


def test_pipeline_parity_int8(demo):
    host, texts = demo
    q8h = ServingPipeline(host.featurizer, host.model, batch_size=32,
                          int8=True)
    q8d = ServingPipeline(host.featurizer, host.model, batch_size=32,
                          int8=True, featurize_device="interpret")
    ph, pd = q8h.predict(texts), q8d.predict(texts)
    np.testing.assert_array_equal(ph.labels, pd.labels)
    assert float(np.abs(ph.probabilities - pd.probabilities).max()) < 1e-6


def test_pipeline_parity_tree(demo):
    _, texts = demo
    host = synthetic_demo_pipeline(batch_size=32, n=200, seed=7, model="dt")
    dev = ServingPipeline(host.featurizer, host.model, batch_size=32,
                          featurize_device="interpret")
    ph, pd = host.predict(texts), dev.predict(texts)
    np.testing.assert_array_equal(ph.labels, pd.labels)
    assert float(np.abs(ph.probabilities - pd.probabilities).max()) < 1e-6


def test_pipeline_refuses_device_featurize_off_tpu(demo):
    """featurize_device=True (compiled) without a TPU is an error naming the
    platform — never a quiet host fallback."""
    host, _ = demo
    with pytest.raises(DeviceFeaturizeUnavailable, match="needs a TPU.*cpu"):
        ServingPipeline(host.featurizer, host.model, batch_size=32,
                        featurize_device=True)


def test_pin_device_includes_stop_table(demo):
    host, _ = demo
    plain = ServingPipeline(host.featurizer, host.model, batch_size=32)
    dev = ServingPipeline(host.featurizer, host.model, batch_size=32,
                          featurize_device="interpret")
    assert (dev.pin_device()["pinned_bytes"]
            >= plain.pin_device()["pinned_bytes"]
            + dev._dev_feat.stop_table_np.nbytes)


def test_mesh_pipeline_parity(demo):
    from fraud_detection_tpu.parallel.serving import MeshServingPipeline

    host, texts = demo
    mesh_pipe = MeshServingPipeline(host.featurizer, host.model,
                                    per_chip_batch=8,
                                    featurize_device="interpret")
    assert mesh_pipe.device_stats.featurize_path == "interpret"
    ph, pd = host.predict(texts), mesh_pipe.predict(texts)
    np.testing.assert_array_equal(ph.labels, pd.labels)
    assert float(np.abs(ph.probabilities - pd.probabilities).max()) < 1e-6
    snap = mesh_pipe.device_stats.snapshot()
    assert snap["mesh_devices"] == jax.local_device_count()
    assert snap["featurize_path"] == "interpret"


def test_mesh_from_pipeline_carries_featurize_config(demo):
    from fraud_detection_tpu.parallel.serving import MeshServingPipeline

    host, _ = demo
    dev = ServingPipeline(host.featurizer, host.model, batch_size=32,
                          featurize_device="interpret", featurize_width=512,
                          featurize_tokens=64)
    mesh_pipe = MeshServingPipeline.from_pipeline(dev, per_chip_batch=8)
    assert mesh_pipe._dev_feat is not None
    assert mesh_pipe._dev_feat.width == 512
    assert mesh_pipe._dev_feat.tokens == 64


# ---------------------------------------------------------------------------
# streaming engine integration
# ---------------------------------------------------------------------------

def _run_engine(pipe, texts, topic, **kw):
    from fraud_detection_tpu.stream import InProcessBroker, StreamingClassifier

    broker = InProcessBroker()
    producer = broker.producer()
    for i, t in enumerate(texts):
        producer.produce("in", json.dumps({"text": t}).encode(),
                         key=str(i).encode())
    engine = StreamingClassifier(
        pipe, broker.consumer(["in"], "g"), broker.producer(), topic,
        batch_size=32, max_wait=0.05, **kw)
    engine.run(max_messages=len(texts), idle_timeout=3.0)
    out = broker.consumer([topic], "reader").poll_batch(10_000, 0.2)
    return sorted((m.key, m.value) for m in out), engine


def test_engine_wire_parity_and_health(demo):
    host, texts = demo
    dev_pipe = ServingPipeline(host.featurizer, host.model, batch_size=32,
                               featurize_device="interpret")
    want, _ = _run_engine(host, texts, "out-host")
    got, engine = _run_engine(dev_pipe, texts, "out-dev")
    assert got == want and len(got) == len(texts)
    block = engine.health()["device"]
    assert block["featurize_path"] == "interpret"
    assert block["truncated_rows"] == 0
    assert block["bytes_in_per_row"] == pytest.approx(
        (dev_pipe._dev_feat.width + 4) * 32 * 3 / len(texts))
    assert block["uploads_per_batch"] == 1.0


def test_serve_cli_featurize_device(monkeypatch, capsys):
    """serve --featurize-device e2e (interpret forced via env on CPU): exit
    0, every demo message classified, and the final health's device block
    says which featurize path ran with the raw-bytes accounting."""
    from fraud_detection_tpu.app.serve import main as serve_main

    monkeypatch.setenv("FRAUD_TPU_FEATURIZE_INTERPRET", "1")
    rc = serve_main(["--model", "synthetic", "--demo", "48",
                     "--batch-size", "16", "--max-wait", "0.01",
                     "--featurize-device", "--featurize-width", "512"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "featurizer=interpret" in out
    stats = json.loads([l for l in out.splitlines() if l.startswith("{")][0])
    assert stats["processed"] == 48
    block = stats["health"]["device"]
    assert block["featurize_path"] == "interpret"
    assert block["bytes_in_per_row"] == 516.0
    assert block["uploads_per_batch"] == 1.0
    assert block["truncated_rows"] >= 0


def test_serve_cli_featurize_width_requires_flag():
    from fraud_detection_tpu.app.serve import main as serve_main

    with pytest.raises(SystemExit, match="featurize-device"):
        serve_main(["--model", "synthetic", "--demo", "8",
                    "--featurize-width", "512"])


def test_engine_async_dispatch_lane_ships_bytes(demo):
    """The dispatch lane's _launch leg with device featurization: byte-
    identical output, strict FIFO, and the lane's upload accounting shows
    raw bytes (one crossing per batch)."""
    host, texts = demo
    dev_pipe = ServingPipeline(host.featurizer, host.model, batch_size=32,
                               featurize_device="interpret")
    want, _ = _run_engine(host, texts, "out-sync")
    got, engine = _run_engine(dev_pipe, texts, "out-async",
                              async_dispatch=True, pipeline_depth=2)
    assert got == want
    block = engine.health()["device"]
    assert block["async_dispatch"] is True and block["lane_batches"] >= 3
    assert block["featurize_path"] == "interpret"
    assert block["uploads_per_batch"] == 1.0
