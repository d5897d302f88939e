"""Pallas TPU kernel for device-side featurization: raw UTF-8 bytes in,
packed (B, 2, L) ids/counts staging layout out.

The serving hot path's last host-side compute is the featurize leg —
clean/tokenize/murmur-hash/count (featurize/text.py + featurize/hashing.py,
~130–200k rows/sec of host CPU at bench scale against a device ladder with
far more capacity). This module moves that leg on-device: the host ships a
fixed-width ``(B, W)`` uint8 byte tensor (a straight memcpy of each
dialogue's UTF-8 bytes — no tokenization, no hashing, no regex on host) and
ONE jitted device program reproduces the exact Spark-parity pipeline:

  * **clean_text** — lowercase + strip every char not in ``[a-z ]``. Byte
    classing is embarrassingly parallel XLA (``byte_classes``). Exactly two
    codepoints outside ASCII lowercase into ``[a-z ]`` under Python's
    ``str.lower`` (U+0130 → 'i', U+212A → 'k' — re-derived over all of
    Unicode by tests/test_featurize_device.py), so multi-byte sequences
    reduce to two pattern matches; every other non-ASCII byte strips, which
    is byte-for-byte what the host regex does after ``.lower()``.
  * **tokenize** — Spark ``Tokenizer``/Java ``split("\\s")`` semantics
    (interior/leading empty tokens kept, trailing dropped, ``"" → [""]``).
    Runs in the Pallas scan kernel: one pass over byte positions, rows
    vectorized across the VPU, emitting a finalized token at every
    field boundary.
  * **murmur3_x86_32** — exact ``spark_hash_bucket`` semantics including
    the legacy sign-extended-tail variant, streamed byte-by-byte through
    the same scan (state: h1, pending tail word, byte count).
  * **stop words** — exact membership against the featurizer's stop list.
    Cleaned tokens are ``[a-z]*``, so a token of ≤ ``_STOP_PACK_CHARS``
    chars is IDENTIFIED by its packed 5-bit char words + length; the scan
    emits those alongside the hash and the XLA post-pass probes a
    direct-mapped table (``build_stop_table``, collision-free by
    construction). Stop words that cannot match any cleaned token (non
    ``[a-z]`` chars) are dropped from the table host-side; a pure-alpha
    stop word longer than the pack width makes the device path refuse
    rather than silently diverge.
  * **count + pack** — bucket = nonNegativeMod(signed hash, F), per-row
    unique-bucket counting via sort + segment-sum, host truncation rule
    (keep top counts, ties toward the LOWER bucket id) when a row has more
    unique buckets than ``n_slots``, then the same packed ``(B, 2, L)``
    int16 staging layout ``models/pipeline._pack_encoded`` produces — so
    every downstream scoring path (fused LR, int8, trees) is unchanged.

IDF scaling already lives on device (folded into LR weights /
``idf_array`` for trees), so with this kernel the packed staging buffer —
and upstream of it, the raw byte tensor — is the only host artifact on the
scoring path.

Tests run the kernel under ``interpret=True`` on the CPU mesh (parity with
the host featurizer, byte for byte); on a TPU it compiles through Mosaic.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fraud_detection_tpu.featurize.hashing import SPARK_HASHING_TF_SEED

# Character classes produced by byte_classes: 1..26 = 'a'..'z', the rest
# as named below. Everything stripped by clean_text is NOP.
CLS_NOP = 0
CLS_SPACE = 27
CLS_END = 28

#: The only codepoints whose ``str.lower()`` contains chars in ``[a-z ]``
#: (pinned by an exhaustive re-derivation in tests/test_featurize_device.py).
#: İ (U+0130) lowercases to "i" + combining dot — the 'i' survives the
#: strip; K (U+212A, Kelvin) lowercases to 'k'. Their UTF-8 encodings.
SPECIAL_LOWER = ((b"\xc4\xb0", ord("i")), (b"\xe2\x84\xaa", ord("k")))

# Stop-word identity pack: cleaned tokens are [a-z]*, so 5 bits/char and
# two 30-bit words identify any token up to 12 chars exactly (length is
# compared too). The longest word in Spark's default English list is 10.
_STOP_PACK_CHARS = 12
_STOP_TABLE_MAX = 1 << 16

# Kernel tile geometry. Mosaic indexes a ref dynamically only on its leading
# (untiled) dims, so the scan runs POSITION-MAJOR: streams are
# (positions, row-groups, 128 lanes), one step of the scan reads/writes
# ``ref[j]`` — a whole (row-groups, 128) slab — and every batch row owns one
# lane of it. SUBLANES row groups make each state vector a full (8, 128)
# vreg; COL_TILE positions per grid step bound VMEM at 5 streams x 2 buffers
# x COL_TILE x 4 KiB = 5 MiB, under the 16 MiB scoped default.
LANES = 128
SUBLANES = 8
COL_TILE = 128

_MASK32 = 0xFFFFFFFF


class FeaturizeSpec(NamedTuple):
    """Static (hashable) configuration of the device featurize program —
    everything that changes the compiled kernel, as jit static args."""

    num_features: int
    n_slots: int            # token slots L in the packed output
    binary: bool            # HashingTF(binary=True): presence, not counts
    legacy: bool            # murmur legacy sign-extended-tail variant
    empty_bucket: int       # spark_hash_bucket("") — the "" token's bucket
    empty_is_stop: bool     # "" present in the stop list
    interpret: bool = False


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# ---------------------------------------------------------------------------
# murmur3 x86_32 primitives (int32 vector ops — usable inside the kernel)
# ---------------------------------------------------------------------------
#
# Everything is int32: two's-complement multiply/add/xor/shift-left are the
# same bits as the uint32 reference, and the unsigned right shift is spelled
# ``shift_right_logical`` — the kernel needs no unsigned vector type.

def _i32(c: int):
    """A 32-bit constant given as unsigned, as the int32 with the same bits."""
    return jnp.int32(c - (1 << 32) if c >= (1 << 31) else c)


def _srl(x, n: int):
    return jax.lax.shift_right_logical(x, jnp.int32(n))


def _rotl(x, r: int):
    return (x << r) | _srl(x, 32 - r)


def _mix_k1(k1):
    return _rotl(k1 * _i32(0xCC9E2D51), 15) * _i32(0x1B873593)


def _mix_h1(h1, k1):
    return _rotl(h1 ^ k1, 13) * jnp.int32(5) + _i32(0xE6546B64)


def _fmix(h1, length):
    h1 = h1 ^ length
    h1 = h1 ^ _srl(h1, 16)
    h1 = h1 * _i32(0x85EBCA6B)
    h1 = h1 ^ _srl(h1, 13)
    h1 = h1 * _i32(0xC2B2AE35)
    return h1 ^ _srl(h1, 16)


# ---------------------------------------------------------------------------
# clean_text as byte classing (XLA, embarrassingly parallel)
# ---------------------------------------------------------------------------

def byte_classes(byts: jax.Array, lengths: jax.Array) -> jax.Array:
    """(B, W) uint8 + (B,) lengths -> (B, W+1) int32 char classes.

    Implements clean_text byte-exactly: ASCII A-Z lowercases, a-z and space
    keep, everything else strips — except the two SPECIAL_LOWER sequences,
    whose lead byte emits the surviving ASCII letter (their continuation
    bytes are >= 0x80 and strip like any other). Position ``lengths[r]``
    carries CLS_END (the scan's flush trigger); the column always exists
    because the class tensor is one wider than the byte tensor.
    """
    b = byts.astype(jnp.int32)
    nxt1 = jnp.pad(b[:, 1:], ((0, 0), (0, 1)))
    nxt2 = jnp.pad(b[:, 2:], ((0, 0), (0, 2)))
    upper = (b >= 65) & (b <= 90)
    lower = (b >= 97) & (b <= 122)
    cls = jnp.where(upper, b - 64, jnp.where(lower, b - 96, CLS_NOP))
    cls = jnp.where(b == 32, CLS_SPACE, cls)
    (s_i, ch_i), (s_k, ch_k) = SPECIAL_LOWER
    cls = jnp.where((b == s_i[0]) & (nxt1 == s_i[1]), ch_i - 96, cls)
    cls = jnp.where((b == s_k[0]) & (nxt1 == s_k[1]) & (nxt2 == s_k[2]),
                    ch_k - 96, cls)
    cls = jnp.pad(cls, ((0, 0), (0, 1)))
    pos = jnp.arange(cls.shape[1], dtype=jnp.int32)[None, :]
    ln = lengths.astype(jnp.int32)[:, None]
    return jnp.where(pos < ln, cls, jnp.where(pos == ln, CLS_END, CLS_NOP))


# ---------------------------------------------------------------------------
# the scan kernel: tokenize + murmur + stop-key pack, one pass over bytes
# ---------------------------------------------------------------------------

# Scan state carried across byte positions (and, in VMEM scratch, across
# the column tiles of one row tile), in this order.
_STATE = ("h1", "k1", "nb", "w0", "w1", "pend", "emp", "kept")
_EMP = _STATE.index("emp")


def _scan_kernel(cls_ref, h_ref, w0_ref, w1_ref, tl_ref, emp_ref, st_ref, *,
                 legacy: bool):
    """One (row tile, column tile) cell: sequential scan over the tile's
    byte positions, batch rows vectorized across (row-group, lane).

    Per step, every row advances its token state by one char class: letters
    stream into the murmur word accumulator and the 5-bit identity pack;
    a space or the end flush the current field. Emissions land at the
    CURRENT position (each position closes at most one field), so the output
    streams have the input's shape with no data-dependent scatter: ``tl``
    >= 0 marks a real token (its byte length), -1 an empty slot.

    Java-split semantics ride two per-row counters: ``pend`` accumulates
    empty fields whose interior-ness is unknown until a later non-empty
    field confirms it (trailing empties die in ``pend``), and ``emp`` is
    the confirmed empty-token count — plus the ``"" -> [""]`` rule when the
    cleaned row kept no chars at all.

    The grid runs column tiles innermost, so ``st_ref`` hands the state from
    one column tile of a row tile to the next.
    """
    ncols = cls_ref.shape[0]
    zero = jnp.zeros(cls_ref.shape[1:], jnp.int32)
    seed_v = jnp.full_like(zero, SPARK_HASHING_TF_SEED)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        for i, name in enumerate(_STATE):
            st_ref[i] = seed_v if name == "h1" else zero

    def step(j, st):
        h1, k1, nb, w0, w1, pend, emp, kept = st
        c = cls_ref[j]
        is_let = (c >= 1) & (c <= 26)
        is_space = c == CLS_SPACE
        is_end = c == CLS_END

        # letter: stream the byte into murmur (body words complete every
        # 4th byte) and the identity pack (first _STOP_PACK_CHARS chars).
        vb = jnp.where(is_let, c + 96, 0)
        k1n = jnp.where(is_let, k1 | (vb << ((nb & 3) * 8)), k1)
        word_full = is_let & ((nb & 3) == 3)
        h1n = jnp.where(word_full, _mix_h1(h1, _mix_k1(k1n)), h1)
        k1n = jnp.where(word_full, zero, k1n)
        cw = jnp.where(is_let, c, 0)
        w0n = jnp.where(is_let & (nb < 6),
                        w0 | (cw << (5 * jnp.minimum(nb, 6))), w0)
        w1n = jnp.where(is_let & (nb >= 6) & (nb < _STOP_PACK_CHARS),
                        w1 | (cw << (5 * jnp.clip(nb - 6, 0, 6))), w1)
        nbn = jnp.where(is_let, nb + 1, nb)

        # boundary: this position closes a field. Non-empty -> finalize the
        # hash and emit; empty at a space -> one more pending empty field;
        # empty at the end -> trailing, dropped.
        emit = (is_space | is_end) & (nbn > 0)
        tail_n = nbn & 3
        if legacy:
            # hashUnsafeBytes: each tail byte gets a FULL mix round. Token
            # bytes are 'a'..'z' (< 0x80), so Java's sign extension is the
            # identity here.
            hfin = h1n
            for t in range(3):
                byte_t = _srl(k1n, 8 * t) & 0xFF
                hfin = jnp.where(tail_n > t, _mix_h1(hfin, _mix_k1(byte_t)),
                                 hfin)
        else:
            # hashUnsafeBytes2: the pending tail word mixes in once
            # (mix_k1(0) == 0, so the aligned case is the same expression).
            hfin = h1n ^ _mix_k1(k1n)
        hout = _fmix(hfin, nbn)

        h_ref[j] = jnp.where(emit, hout, 0)
        w0_ref[j] = jnp.where(emit, w0n, 0)
        w1_ref[j] = jnp.where(emit, w1n, 0)
        tl_ref[j] = jnp.where(emit, nbn, -1)

        empn = jnp.where(emit, emp + pend, emp)
        pendn = jnp.where(emit, zero, pend)
        pendn = jnp.where(is_space & (nbn == 0), pendn + 1, pendn)
        keptn = kept | (is_let | is_space).astype(jnp.int32)
        # cleaned row kept NOTHING: Java split("") returns [""] — exactly
        # one empty token, regardless of pending state.
        empn = jnp.where(is_end & (keptn == 0), jnp.ones_like(empn), empn)

        boundary = is_space | is_end
        return (jnp.where(boundary, seed_v, h1n),
                jnp.where(boundary, zero, k1n),
                jnp.where(boundary, zero, nbn),
                jnp.where(boundary, zero, w0n),
                jnp.where(boundary, zero, w1n),
                pendn, empn, keptn)

    final = jax.lax.fori_loop(
        0, ncols, step, tuple(st_ref[i] for i in range(len(_STATE))))
    for i, v in enumerate(final):
        st_ref[i] = v
    # The block is resident across the row tile's column steps; the last
    # step's write is the one that reaches HBM.
    emp_ref[...] = final[_EMP]


def tokenize_hash(classes: jax.Array, *, legacy: bool = False,
                  interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                             jax.Array]:
    """Run the scan kernel over a (B, C) class tensor.

    Returns per-position streams ``(h_raw, w0, w1, tok_len)`` — each
    (B, C) int32, ``tok_len`` < 0 where no token ends — plus the per-row
    confirmed empty-token count (B, 1). The kernel sees the tensor
    position-major, (C, B/128, 128): rows pad to whole tiles of up to
    SUBLANES lane groups, positions pad to COL_TILE with CLS_NOP (a no-op
    for the scan).
    """
    b, c = classes.shape
    groups = min(SUBLANES, _round_up(max(b, 1), LANES) // LANES)
    b_pad = _round_up(max(b, 1), groups * LANES)
    c_pad = _round_up(c, COL_TILE)
    cls = jnp.zeros((b_pad, c_pad), jnp.int32).at[:b, :c].set(
        classes.astype(jnp.int32))
    cls = cls.T.reshape(c_pad, b_pad // LANES, LANES)
    stream_spec = pl.BlockSpec((COL_TILE, groups, LANES),
                               lambda i, j: (j, i, 0),
                               memory_space=pltpu.VMEM)
    stream_shape = jax.ShapeDtypeStruct(cls.shape, jnp.int32)
    outs = pl.pallas_call(
        partial(_scan_kernel, legacy=legacy),
        grid=(b_pad // (groups * LANES), c_pad // COL_TILE),
        in_specs=[stream_spec],
        out_specs=[stream_spec] * 4 + [
            pl.BlockSpec((groups, LANES), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM)],
        out_shape=[stream_shape] * 4 + [
            jax.ShapeDtypeStruct((b_pad // LANES, LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((len(_STATE), groups, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cls)
    h, w0, w1, tl = (x.reshape(c_pad, b_pad).T[:b, :c] for x in outs[:4])
    return h, w0, w1, tl, outs[4].reshape(b_pad, 1)[:b]


# ---------------------------------------------------------------------------
# stop-word table (host build + device probe share one hash)
# ---------------------------------------------------------------------------

def _probe_mix(w0: int, w1: int, ln: int) -> int:
    """The direct-map probe hash, in wrap-around uint32 arithmetic. The
    device twin below must stay expression-identical."""
    h = (w0 * 0x9E3779B1 + w1 * 0x85EBCA6B + ln * 0xC2B2AE35) & _MASK32
    h ^= h >> 15
    h = (h * 0x2C1B3C6D) & _MASK32
    h ^= h >> 12
    return h


def _probe_mix_device(w0, w1, ln):
    w0u = w0.astype(jnp.uint32)
    w1u = w1.astype(jnp.uint32)
    lnu = ln.astype(jnp.uint32)
    h = (w0u * jnp.uint32(0x9E3779B1) + w1u * jnp.uint32(0x85EBCA6B)
         + lnu * jnp.uint32(0xC2B2AE35))
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x2C1B3C6D)
    return h ^ (h >> 12)


def pack_token(word: str) -> Optional[Tuple[int, int, int]]:
    """(w0, w1, len) identity key of a cleaned token, or None when the word
    can never equal a cleaned token (chars outside [a-z]) — such stop words
    are unmatchable on the host path too, so dropping them is exact."""
    if any(not ("a" <= ch <= "z") for ch in word):
        return None
    w0 = w1 = 0
    for i, ch in enumerate(word[:_STOP_PACK_CHARS]):
        v = ord(ch) - 96
        if i < 6:
            w0 |= v << (5 * i)
        else:
            w1 |= v << (5 * (i - 6))
    return w0, w1, len(word)


def build_stop_table(words) -> Optional[Tuple[np.ndarray, bool]]:
    """Direct-mapped (size, 3) int32 stop table [w0, w1, len] + the
    empty-token flag, or None when the list cannot be represented exactly
    (a pure-[a-z] word longer than the pack width — the device path refuses
    such a list rather than diverge silently).

    Size doubles until every eligible word lands in its own slot (the probe
    is just a hash; collisions are resolved by growing, so the table is
    collision-free by construction and one gather + compare per token is an
    EXACT membership test). Empty slots carry len = -1, matching no token.
    """
    empty_is_stop = False
    keys = []
    for w in words:
        if w == "":
            empty_is_stop = True
            continue
        key = pack_token(w)
        if key is None:
            continue                    # unmatchable on host too: exact drop
        if len(w) > _STOP_PACK_CHARS:
            return None                 # would ALIAS 12-char prefixes: refuse
        keys.append(key)
    size = 64
    while size <= _STOP_TABLE_MAX:
        slots = {}
        for key in keys:
            idx = _probe_mix(*key) & (size - 1)
            if idx in slots and slots[idx] != key:
                break
            slots[idx] = key
        else:
            tbl = np.full((size, 3), -1, np.int32)
            for idx, (w0, w1, ln) in slots.items():
                tbl[idx] = (w0, w1, ln)
            return tbl, empty_is_stop
        size *= 2
    return None


# ---------------------------------------------------------------------------
# count + pack (XLA post-pass, same jitted program)
# ---------------------------------------------------------------------------

def assemble_packed(h_raw, w0, w1, tok_len, empty_cnt, stop_table,
                    *, spec: FeaturizeSpec
                    ) -> Tuple[jax.Array, jax.Array]:
    """Token streams -> packed (B, 2, n_slots) int16 staging layout.

    Stop-word filter (exact table probe), bucket = nonNegativeMod(signed
    hash, F), per-row unique-bucket counts via sort + segment-sum, the host
    truncation rule past ``n_slots``, ids ascending with zero padding —
    the exact layout ``_pack_encoded`` ships. Also returns the per-row
    unique-bucket count (pre-truncation); serving callers drop it and jit
    DCE removes the extra outputs.
    """
    b, n = h_raw.shape
    f = spec.num_features
    sent = jnp.int32(f)                 # sorts past every real bucket

    idx = (_probe_mix_device(w0, w1, tok_len)
           & jnp.uint32(stop_table.shape[0] - 1)).astype(jnp.int32)
    probe = stop_table[idx]             # (B, N, 3) gather
    is_tok = tok_len >= 0
    is_stop = (is_tok & (probe[..., 0] == w0) & (probe[..., 1] == w1)
               & (probe[..., 2] == tok_len))
    keep = is_tok & ~is_stop

    bucket = jnp.remainder(h_raw, jnp.int32(f))    # floor-mod == nonNegativeMod
    stream = jnp.where(keep, bucket, sent)
    weight = keep.astype(jnp.int32)

    # The empty token "" rides as one extra (bucket, multiplicity) slot.
    emp = (jnp.zeros_like(empty_cnt) if spec.empty_is_stop
           else empty_cnt.astype(jnp.int32))
    stream = jnp.concatenate(
        [stream, jnp.where(emp > 0, jnp.int32(spec.empty_bucket), sent)],
        axis=1)
    weight = jnp.concatenate([weight, emp], axis=1)

    order = jnp.argsort(stream, axis=1)
    sb = jnp.take_along_axis(stream, order, axis=1)
    sw = jnp.take_along_axis(weight, order, axis=1)
    first = jnp.concatenate(
        [jnp.ones((b, 1), bool), sb[:, 1:] != sb[:, :-1]], axis=1)
    seg = jnp.cumsum(first.astype(jnp.int32), axis=1) - 1
    n_seg = n + 2                       # n+1 slots -> at most n+1 segments
    flat = (seg + jnp.arange(b, dtype=jnp.int32)[:, None] * n_seg).reshape(-1)
    counts = jnp.zeros((b * n_seg,), jnp.int32).at[flat].add(
        sw.reshape(-1)).reshape(b, n_seg)
    ids = jnp.zeros((b * n_seg,), jnp.int32).at[flat].max(
        sb.reshape(-1)).reshape(b, n_seg)
    valid = (ids < f) & (counts > 0)
    counts = jnp.where(valid, counts, 0)
    n_unique = jnp.sum(valid, axis=1)

    # Host truncation rule (featurize/tfidf._fill_python_rows): keep the
    # top-count buckets, ties resolving toward the LOWER bucket id — ids
    # are bucket-ascending here, so a stable sort on -count is exactly it.
    sel = jnp.argsort(-counts, axis=1, stable=True)[:, : spec.n_slots]
    sel_ids = jnp.take_along_axis(ids, sel, axis=1)
    sel_cnt = jnp.take_along_axis(counts, sel, axis=1)
    resort = jnp.argsort(jnp.where(sel_cnt > 0, sel_ids, sent), axis=1)
    out_ids = jnp.take_along_axis(sel_ids, resort, axis=1)
    out_cnt = jnp.take_along_axis(sel_cnt, resort, axis=1)
    out_ids = jnp.where(out_cnt > 0, out_ids, 0)
    if spec.binary:
        out_cnt = jnp.minimum(out_cnt, 1)
    out_cnt = jnp.minimum(out_cnt, 65535)
    if spec.n_slots > out_ids.shape[1]:     # tiny W: pad up to the contract
        pad = spec.n_slots - out_ids.shape[1]
        out_ids = jnp.pad(out_ids, ((0, 0), (0, pad)))
        out_cnt = jnp.pad(out_cnt, ((0, 0), (0, pad)))
    packed = jnp.stack(
        [out_ids.astype(jnp.int16),
         jax.lax.bitcast_convert_type(out_cnt.astype(jnp.uint16), jnp.int16)],
        axis=1)
    return packed, n_unique


def split_staged(staged: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(B, W+4) uint8 staging tensor -> ((B, W) bytes, (B,) int32 lengths).

    The per-row byte length rides little-endian in the LAST four columns so
    a micro-batch is ONE host->device transfer (the same single-crossing
    discipline as ``_pack_encoded``); 0xFFFFFFFF (-1) marks a padding row
    (featurize/device.py ``pack_staged``)."""
    byts = staged[:, :-4]
    tail = staged[:, -4:].astype(jnp.int32)
    lengths = (tail[:, 0] | (tail[:, 1] << 8) | (tail[:, 2] << 16)
               | (tail[:, 3] << 24))
    return byts, lengths


def featurize_bytes(staged: jax.Array, stop_table: jax.Array, *,
                    spec: FeaturizeSpec) -> Tuple[jax.Array, jax.Array]:
    """The full device featurize program: (B, W+4) uint8 staging tensor ->
    (packed (B, 2, n_slots) int16, per-row unique count). Composes under an
    outer jit with the packed scoring entries (models/pipeline.py), so
    bytes -> features -> probability is ONE device program."""
    byts, lengths = split_staged(staged)
    classes = byte_classes(byts, lengths)
    h, w0, w1, tl, emp = tokenize_hash(
        classes, legacy=spec.legacy, interpret=spec.interpret)
    return assemble_packed(h, w0, w1, tl, emp, stop_table, spec=spec)


featurize_bytes_jit = jax.jit(featurize_bytes, static_argnames=("spec",))
