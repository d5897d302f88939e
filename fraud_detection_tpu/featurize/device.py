"""Device-side featurization: host byte packing + the serving-facing probe.

``ops/featurize_kernel.py`` owns the device program (Pallas scan kernel +
XLA count/pack). This module owns everything around it:

* :func:`pack_bytes` — the host's ENTIRE remaining featurize work: UTF-8
  encode + memcpy into a fixed-width ``(B, W)`` uint8 tensor with per-row
  byte lengths. Rows longer than ``W`` truncate at a CODEPOINT boundary
  (never mid-sequence) and are counted — truncation honesty is a counter
  (``DeviceStats.truncated_rows``), not a silent divergence, and the
  truncation semantics are pinned: featurizing the truncated bytes on
  device equals running the host featurizer on the truncated text.
* :class:`DeviceFeaturizer` — validates that a host featurizer's exact
  semantics are expressible on device (hashing featurizer, representable
  stop list, int16-range feature space) and builds the stop table and
  static spec. ``path`` is ``"pallas"`` on a TPU, ``"interpret"`` when a
  test asks for the interpreter by argument; anything else raises
  :class:`DeviceFeaturizeUnavailable` — asking for the device featurizer
  where it cannot run is an error, never a quiet host fallback.

The serving integration lives in models/pipeline.py
(``ServingPipeline(featurize_device=...)``): the byte tensor becomes the
only host->device crossing, featurize + scoring fuse under one jit, and
the dispatch lane's ``_launch`` leg ships raw bytes instead of running
tokenize/hash on the host.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from fraud_detection_tpu.featurize.hashing import spark_hash_bucket
from fraud_detection_tpu.featurize.tfidf import HashingTfIdfFeaturizer
from fraud_detection_tpu.utils.device import device_stamp

DEFAULT_WIDTH = 2048
DEFAULT_TOKENS = 256


class DeviceFeaturizeUnavailable(RuntimeError):
    """The device featurize path cannot represent this configuration, or
    this process has no TPU to compile it for."""


def truncation_cut(data: bytes, width: int) -> int:
    """Largest cut <= width that does not split a UTF-8 sequence."""
    cut = width
    while cut > 0 and (data[cut] & 0xC0) == 0x80:
        cut -= 1
    return cut


def pack_bytes(texts: Sequence[str], width: int,
               batch_size: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Texts -> ((B, width) uint8, (B,) int32 lengths, truncated_rows).

    A straight UTF-8 encode + memcpy per row — no tokenization, hashing or
    regex work; this is the host featurize leg after the kernel takes the
    rest. Rows beyond ``len(texts)`` carry length -1: PADDING, not the
    empty string — a real ``""`` (length 0) tokenizes to ``[""]`` and
    counts one empty-token bucket (Java split semantics, on both paths),
    while a padding row must featurize to nothing, exactly like the host
    encoder's all-zero padding rows. The -1 suppresses the kernel's
    end-of-text marker entirely.
    """
    b = batch_size if batch_size is not None else len(texts)
    if len(texts) > b:
        raise ValueError(f"{len(texts)} texts > batch_size {b}")
    out = np.zeros((b, width), np.uint8)
    lengths = np.full(b, -1, np.int32)
    truncated = 0
    for i, t in enumerate(texts):
        data = t.encode("utf-8")
        if len(data) > width:
            data = data[: truncation_cut(data, width)]
            truncated += 1
        n = len(data)
        out[i, :n] = np.frombuffer(data, np.uint8)
        lengths[i] = n
    return out, lengths, truncated


def pack_staged(texts: Sequence[str], width: int,
                batch_size: Optional[int] = None
                ) -> Tuple[np.ndarray, int]:
    """Texts -> ((B, width+4) uint8 staging tensor, truncated_rows): the
    byte tensor with each row's length in its last four columns (little-
    endian), so the whole micro-batch is ONE host->device transfer
    (``ops/featurize_kernel.split_staged`` is the device inverse)."""
    byts, lengths, truncated = pack_bytes(texts, width, batch_size)
    staged = np.empty((byts.shape[0], width + 4), np.uint8)
    staged[:, :width] = byts
    staged[:, width:] = lengths.astype("<i4").view(np.uint8).reshape(-1, 4)
    return staged, truncated


class DeviceFeaturizer:
    """The device twin of a :class:`HashingTfIdfFeaturizer`.

    Construction VALIDATES exactness — any configuration the kernel cannot
    reproduce bit-for-bit raises :class:`DeviceFeaturizeUnavailable` with
    the reason (vocabulary featurizers, stop words longer than the identity
    pack, feature spaces past int16) — and resolves the execution path:

    * ``interpret=False`` — compiled Pallas; requires a TPU.
    * ``interpret=True``  — interpreter mode (the CPU test mesh).
    * ``interpret=None``  — compiled on a TPU, otherwise refuse: an
      interpreted kernel on the serving path would be slower than the host
      leg it replaces.
    """

    def __init__(self, featurizer: HashingTfIdfFeaturizer, *,
                 width: int = DEFAULT_WIDTH, tokens: int = DEFAULT_TOKENS,
                 interpret: Optional[bool] = None):
        from fraud_detection_tpu.ops import featurize_kernel as fk

        if type(featurizer) is not HashingTfIdfFeaturizer:
            raise DeviceFeaturizeUnavailable(
                f"{type(featurizer).__name__} featurizes through an explicit "
                "vocabulary; the device kernel implements the hashing path")
        if featurizer.num_features > np.iinfo(np.int16).max:
            raise DeviceFeaturizeUnavailable(
                f"num_features={featurizer.num_features} exceeds the int16 "
                "packed staging layout")
        if width < 8:
            raise ValueError(f"width must be >= 8 bytes, got {width}")
        if tokens < 1:
            raise ValueError(f"tokens must be >= 1, got {tokens}")
        stop_words = (featurizer.stop_filter.words
                      if featurizer.remove_stopwords else [])
        built = fk.build_stop_table(stop_words)
        if built is None:
            raise DeviceFeaturizeUnavailable(
                "stop list contains a pure-[a-z] word longer than the "
                "identity pack — exact device-side removal is impossible")
        table, empty_is_stop = built
        legacy = bool(getattr(featurizer.hashing_tf, "legacy", False))
        if interpret is None:
            stamp = device_stamp()
            if stamp["platform"] != "tpu":
                raise DeviceFeaturizeUnavailable(
                    f"device featurization needs a TPU, this process runs on "
                    f"{stamp['platform']} ({stamp['device_kind']}); pass "
                    "interpret=True to run the kernel interpreted for parity "
                    "testing")
            interpret = False
        self.featurizer = featurizer
        self.width = int(width)
        self.tokens = int(tokens)
        self.stop_table_np = table
        self.spec = fk.FeaturizeSpec(
            num_features=featurizer.num_features,
            n_slots=int(tokens),
            binary=bool(featurizer.binary_tf),
            legacy=legacy,
            empty_bucket=spark_hash_bucket("", featurizer.num_features,
                                           legacy),
            empty_is_stop=empty_is_stop,
            interpret=bool(interpret),
        )
        self._stop_dev = None           # uploaded once, on first use

    @property
    def path(self) -> str:
        """Which device path this featurizer runs: ``pallas`` (compiled) or
        ``interpret``."""
        return "interpret" if self.spec.interpret else "pallas"

    def stop_table(self):
        """Device copy of the stop table — uploaded ONCE and cached (the
        same model-constant discipline as ``idf_array``); pinned HBM-
        resident by ``ServingPipeline.pin_device``."""
        if self._stop_dev is None:
            import jax.numpy as jnp

            self._stop_dev = jnp.asarray(self.stop_table_np)
        return self._stop_dev

    def pack(self, texts: Sequence[str], batch_size: Optional[int] = None
             ) -> Tuple[np.ndarray, int]:
        """Texts -> ((B, width+4) uint8 staging tensor, truncated_rows) —
        the micro-batch's ONE host->device transfer."""
        return pack_staged(texts, self.width, batch_size)

    def encode_packed(self, staged):
        """Standalone device featurize: (B, W+4) staging tensor -> packed
        (B, 2, L) int16 device array (tests / benches; serving fuses this
        with the scoring program instead — models/pipeline.py)."""
        from fraud_detection_tpu.ops import featurize_kernel as fk

        packed, _ = fk.featurize_bytes_jit(staged, self.stop_table(),
                                           spec=self.spec)
        return packed

    def encode(self, texts: Sequence[str],
               batch_size: Optional[int] = None):
        """Texts -> host EncodedBatch via the DEVICE path (parity surface:
        directly comparable with ``HashingTfIdfFeaturizer.encode``)."""
        from fraud_detection_tpu.featurize.tfidf import EncodedBatch
        from fraud_detection_tpu.models.pipeline import unpack_packed_host

        staged, _ = self.pack(texts, batch_size)
        packed = np.asarray(self.encode_packed(staged))
        ids, counts = unpack_packed_host(packed)
        return EncodedBatch(ids=ids, counts=counts)

    def decode_truncated(self, texts: Sequence[str]) -> List[str]:
        """What each text becomes after byte-width truncation — the exact
        input whose HOST featurization the device path must match (the
        truncation-honesty contract)."""
        out = []
        for t in texts:
            data = t.encode("utf-8")
            if len(data) > self.width:
                data = data[: truncation_cut(data, self.width)]
            out.append(data.decode("utf-8"))
        return out
