"""Property-based parity: the device featurize kernel vs the host path.

Hypothesis explores what the fixed-seed fuzzes in test_featurize_device.py
can't: arbitrary unicode (astral planes, the İ/Kelvin special cases,
combining marks), pathological whitespace runs, width-L boundaries — in
both murmur tail variants and both TF modes. The property is always the
same: the device kernel's packed buckets/counts must be byte-identical to
``HashingTF``/``HashingTfIdfFeaturizer`` over the byte-truncated input.
"""

import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed in this environment")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from tests.test_featurize_device import _python_twin  # noqa: E402

from fraud_detection_tpu.featurize.device import DeviceFeaturizer  # noqa: E402
from fraud_detection_tpu.featurize.hashing import HashingTF  # noqa: E402
from fraud_detection_tpu.featurize.tfidf import (  # noqa: E402
    HashingTfIdfFeaturizer,
)
from fraud_detection_tpu.models.pipeline import unpack_packed_host  # noqa: E402

# Biased toward the tricky regions: case flips, token-joining strippables,
# space runs, the two lowercase-to-ascii codepoints, combining marks,
# astral-plane symbols — and enough plain letters to form real tokens.
_text = st.text(
    alphabet=st.one_of(
        st.sampled_from(list("abcz ABCZ  '-.,09\t\n") + ["İ", "K", "ß", "é"]),
        st.characters(min_codepoint=0x20, max_codepoint=0x2FFF),
        st.characters(min_codepoint=0x1F300, max_codepoint=0x1F6FF),
    ),
    max_size=80)


def _build(legacy: bool, binary: bool):
    feat = HashingTfIdfFeaturizer(num_features=1000, binary_tf=binary)
    if legacy:
        feat._hashing = HashingTF(1000, binary=binary, legacy=True)
    dev = DeviceFeaturizer(feat, width=64, tokens=8, interpret=True)
    return dev, _python_twin(feat, legacy=legacy)


def _scoring_pair():
    from fraud_detection_tpu.models.pipeline import (ServingPipeline,
                                                     synthetic_demo_pipeline)

    host = synthetic_demo_pipeline(batch_size=8, n=120, seed=11,
                                   num_features=1000)
    dev = ServingPipeline(host.featurizer, host.model, batch_size=8,
                          featurize_device="interpret", featurize_width=64,
                          featurize_tokens=16)
    return host, dev


# One device featurizer per mode, built once (jit caches per spec+shape).
_MODES = {(lg, bn): _build(lg, bn)
          for lg in (False, True) for bn in (False, True)}
_SCORING = _scoring_pair()


@settings(max_examples=60, deadline=None)
@given(st.lists(_text, min_size=1, max_size=6),
       st.booleans(), st.booleans())
def test_device_kernel_property_parity(texts, legacy, binary):
    """Buckets, counts and layout byte-identical to the host featurizer —
    over the byte-truncated input (width 64 truncates some examples on
    purpose: truncation must change the INPUT, never the semantics)."""
    dev, twin = _MODES[(legacy, binary)]
    staged, _ = dev.pack(texts, batch_size=8)
    ids_d, cnt_d = unpack_packed_host(np.asarray(dev.encode_packed(staged)))
    want = twin.encode(dev.decode_truncated(texts), batch_size=8,
                       max_tokens=dev.tokens)
    np.testing.assert_array_equal(ids_d, np.asarray(want.ids))
    np.testing.assert_array_equal(cnt_d, np.asarray(want.counts))


@settings(max_examples=40, deadline=None)
@given(_text)
def test_device_idf_scoring_property_parity(text):
    """End-to-end with IDF in play: the fused bytes->featurize->score
    program must agree with host featurize + the same scoring program on
    the byte-truncated input (labels identical, |Δp| < 1e-6)."""
    host, dev = _SCORING
    truncated = dev._dev_feat.decode_truncated([text])
    ph = host.predict(truncated)
    pd = dev.predict([text])
    assert ph.labels[0] == pd.labels[0]
    assert abs(float(ph.probabilities[0]) - float(pd.probabilities[0])) < 1e-6
