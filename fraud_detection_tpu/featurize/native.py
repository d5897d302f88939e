"""ctypes loader for the native featurizer (native/fast_featurize.cpp).

Builds the shared library on demand with g++ (no pip/pybind dependency —
plain C ABI + ctypes), caches it next to the source keyed on the source's
content, and degrades to None when no toolchain is available so the
pure-Python path keeps working (``serve`` prints which featurizer it runs).
The Python featurizer (featurize/tfidf.py) auto-uses this when loadable;
parity is enforced by tests/test_native_featurize.py comparing both paths
byte-for-byte.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "native", "fast_featurize.cpp")
_LIB = os.path.join(os.path.dirname(_SRC), "libfastfeat.so")
_lock = threading.Lock()
_lib: "ctypes.CDLL | None" = None
_lib_failed = False


_BASE_FLAGS = ["-std=c++17", "-shared", "-fPIC", "-pthread"]

# Sanitizer build variants (docs/static_analysis.md "Sanitizer builds"):
# the multi-thread ftok_shard_* ABI runs N pool threads over one shared
# handle, and "simple by design" only stays true under a REAL race/memory
# detector. -O1 keeps stacks honest; recovery is off so the first finding
# fails the run. The instrumented .so must be loaded into a process that
# PRELOADS the matching runtime (LD_PRELOAD=libasan.so/libtsan.so —
# native/san_driver.py and the CI `sanitizers` job do this).
_SAN_VARIANTS = {
    "asan": ["-O1", "-g", "-fno-omit-frame-pointer",
             "-fsanitize=address,undefined", "-fno-sanitize-recover=all"],
    "tsan": ["-O1", "-g", "-fno-omit-frame-pointer", "-fsanitize=thread"],
}
_SAN_RUNTIMES = {"asan": "libasan.so", "tsan": "libtsan.so"}


def _build_key(opt_flags) -> str:
    """What a built library is a function of: the source's CONTENT and the
    compiler flags. A library is reused only when the key recorded beside it
    matches — never on file times, which a copied tree does not keep."""
    digest = hashlib.sha256()
    with open(_SRC, "rb") as f:
        digest.update(f.read())
    digest.update("\0".join([*opt_flags, *_BASE_FLAGS]).encode())
    return digest.hexdigest()


def _compile(out: str, opt_flags) -> Optional[str]:
    key, key_path = _build_key(opt_flags), out + ".key"
    try:
        with open(key_path) as f:
            if f.read() == key and os.path.isfile(out):
                return out
    except OSError:
        pass
    tmp = None
    try:
        # build to a temp name then atomic-rename: concurrent processes race
        # safely; the key lands after the library it describes.
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=os.path.dirname(out))
        os.close(fd)
        subprocess.run(
            ["g++", *opt_flags, *_BASE_FLAGS, _SRC, "-o", tmp],
            check=True, capture_output=True, timeout=240)
        os.replace(tmp, out)
        with open(tmp, "w") as f:
            f.write(key)
        os.replace(tmp, key_path)
        return out
    except (OSError, subprocess.SubprocessError):
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass
        return None


def _build() -> Optional[str]:
    return _compile(_LIB, ["-O3"])


def variant_lib_path(variant: str) -> str:
    return os.path.join(os.path.dirname(_SRC), f"libfastfeat_{variant}.so")


def build_variant(variant: Optional[str]) -> Optional[str]:
    """Build (or reuse) a sanitizer-instrumented library variant; None when
    the toolchain can't. ``variant`` in {"asan", "tsan"}; None/"plain"
    falls through to the production -O3 build."""
    if not variant or variant == "plain":
        return _build()
    if variant not in _SAN_VARIANTS:
        raise ValueError(f"unknown sanitizer variant {variant!r} "
                         f"(known: {sorted(_SAN_VARIANTS)})")
    return _compile(variant_lib_path(variant), _SAN_VARIANTS[variant])


def sanitizer_runtime(variant: str) -> Optional[str]:
    """Absolute path of the sanitizer runtime to LD_PRELOAD for ``variant``
    (gcc's bundled libasan/libtsan), or None when the toolchain lacks it."""
    name = _SAN_RUNTIMES.get(variant)
    if name is None:
        return None
    try:
        out = subprocess.run(["gcc", f"-print-file-name={name}"],
                             capture_output=True, text=True,
                             timeout=30).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return None
    return out if os.path.isabs(out) and os.path.isfile(out) else None


def load_library() -> Optional[ctypes.CDLL]:
    """The process-wide native library, built+loaded lazily; None if unavailable."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        if os.environ.get("FRAUD_TPU_NO_NATIVE"):
            _lib_failed = True
            return None
        # FRAUD_TPU_NATIVE_VARIANT=asan|tsan loads the sanitizer-
        # instrumented build instead — the caller must have LD_PRELOADed
        # the matching runtime BEFORE the process started (san_driver.py);
        # without it the instrumented .so aborts at dlopen.
        path = build_variant(os.environ.get("FRAUD_TPU_NATIVE_VARIANT"))
        if path is None:
            _lib_failed = True
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            _lib_failed = True
            return None
        lib.ftok_create.restype = ctypes.c_void_p
        lib.ftok_create.argtypes = [ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                                    ctypes.c_int, ctypes.c_int, ctypes.c_int]
        lib.ftok_destroy.argtypes = [ctypes.c_void_p]
        lib.ftok_hash_bucket.restype = ctypes.c_int
        lib.ftok_hash_bucket.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.ftok_encode_begin.restype = ctypes.c_int
        lib.ftok_encode_begin.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_char_p), ctypes.c_int]
        lib.ftok_encode_fill.argtypes = [
            ctypes.c_void_p,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            ctypes.c_int, ctypes.c_int]
        try:  # stale prebuilt .so without the JSON path: degrade, don't fail
            lib.ftok_encode_json_begin.restype = ctypes.c_int
            lib.ftok_encode_json_begin.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_char_p),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")]
            lib._has_json = True
        except AttributeError:
            lib._has_json = False
        try:  # direct wire-dtype fill (int16 ids / uint16 counts)
            lib.ftok_encode_fill16.argtypes = [
                ctypes.c_void_p,
                np.ctypeslib.ndpointer(np.int16, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.uint16, flags="C_CONTIGUOUS"),
                ctypes.c_int, ctypes.c_int]
            lib._has_fill16 = True
        except AttributeError:
            lib._has_fill16 = False
        try:  # stateless batch-shard encode (featurize/parallel.py drives it)
            lib.ftok_shard_begin.restype = ctypes.c_void_p
            lib.ftok_shard_begin.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")]
            lib.ftok_shard_fill.argtypes = [
                ctypes.c_void_p,
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
                ctypes.c_int, ctypes.c_int]
            lib.ftok_shard_fill16.argtypes = [
                ctypes.c_void_p,
                np.ctypeslib.ndpointer(np.int16, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.uint16, flags="C_CONTIGUOUS"),
                ctypes.c_int, ctypes.c_int]
            lib.ftok_shard_destroy.argtypes = [ctypes.c_void_p]
            lib._has_shards = True
        except AttributeError:
            lib._has_shards = False
        try:  # stateless raw-JSON batch-shard encode (Python-side fan-out)
            lib.ftok_shard_json_begin.restype = ctypes.c_void_p
            lib.ftok_shard_json_begin.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")]
            lib._has_json_shards = True
        except AttributeError:
            lib._has_json_shards = False
        try:  # batch output-frame assembly (stateless)
            lib.ftok_build_frames.restype = ctypes.c_longlong
            lib.ftok_build_frames.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
                ctypes.POINTER(ctypes.c_char_p),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                ctypes.c_int, ctypes.c_int,
                ctypes.c_char_p, ctypes.c_longlong,
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")]
            lib._has_frames = True
        except AttributeError:
            lib._has_frames = False
        _lib = lib
        return _lib


class NativeFeaturizer:
    """One native handle: stopword set + hashing config bound at creation."""

    def __init__(self, stopwords: Sequence[str], num_features: int,
                 binary: bool, remove_stopwords: bool):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native featurizer library unavailable")
        self._lib = lib
        arr = (ctypes.c_char_p * len(stopwords))(
            *[s.encode("utf-8") for s in stopwords])
        self._handle = lib.ftok_create(arr, len(stopwords), num_features,
                                       int(binary), int(remove_stopwords))
        self._call_lock = threading.Lock()  # begin/fill state is per-handle
        # Race tripwire (utils/racecheck.py): begin/fill share handle state,
        # so interleaved pairs from two threads corrupt rows. _call_lock
        # prevents that today; the checker wraps the ABI calls themselves
        # (``_begin`` / ``_fill``) so a future path using those helpers
        # without the lock trips it instead of corrupting rows.
        from fraud_detection_tpu.utils.racecheck import PairedCallChecker

        self._pair_check = PairedCallChecker(name="NativeFeaturizer")

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.ftok_destroy(handle)
            self._handle = None

    def hash_bucket(self, term: str) -> int:
        return self._lib.ftok_hash_bucket(self._handle, term.encode("utf-8"))

    def supports_json(self) -> bool:
        return bool(getattr(self._lib, "_has_json", False))

    def _begin(self, lib_begin, *args) -> int:
        """All C-ABI ``*_begin`` calls route through here so the race
        tripwire (utils/racecheck.py) wraps the shared-handle-state calls
        themselves — a future code path that reaches the ABI without
        ``_call_lock`` trips the checker instead of corrupting rows."""
        self._pair_check.begin()
        return lib_begin(self._handle, *args)

    def _fill(self, rows: int, length: int, want16: bool
              ) -> Tuple[np.ndarray, np.ndarray]:
        """Drain handle row state into padded arrays. ``want16`` (and library
        support) emits the device wire dtypes (int16 ids / uint16 counts,
        clipped) directly from C++, skipping a Python astype+copy of both
        (B, L) arrays; callers gate want16 on num_features <= int16 max."""
        try:
            if want16 and getattr(self._lib, "_has_fill16", False):
                ids = np.empty((rows, length), np.int16)
                counts = np.empty((rows, length), np.uint16)
                self._lib.ftok_encode_fill16(self._handle, ids, counts, rows, length)
            else:
                ids = np.empty((rows, length), np.int32)
                counts = np.empty((rows, length), np.float32)
                self._lib.ftok_encode_fill(self._handle, ids, counts, rows, length)
        finally:
            self._pair_check.finish()
        return ids, counts

    def encode(self, texts: Sequence[str], rows: int,
               max_tokens: Optional[int], pad_len,
               want16: bool = False) -> Tuple[np.ndarray, np.ndarray]:
        """Padded (rows, L) ids/counts — same contract as the Python encode."""
        # NULs would truncate the C string; clean() strips them anyway, and
        # they are not token separators, so removal preserves parity.
        # surrogatepass: json.loads legally yields lone surrogates (\ud800);
        # the C++ permissive decoder strips those codepoints exactly like the
        # Python clean regex strips the surrogate char.
        buf: List[bytes] = [
            t.encode("utf-8", "surrogatepass").replace(b"\x00", b"") for t in texts]
        arr = (ctypes.c_char_p * len(buf))(*buf)
        with self._call_lock:
            # Outer finally: an exception between begin and fill (e.g. a
            # raising pad_len) must not leave the checker poisoned with a
            # stale pending entry (finish is idempotent; _fill also finishes).
            try:
                width = self._begin(self._lib.ftok_encode_begin, arr, len(buf))
                length = max_tokens if max_tokens is not None else pad_len(max(width, 1))
                return self._fill(rows, length, want16)
            finally:
                self._pair_check.finish()

    # ---------------- stateless shard API (thread-pool featurization) ------

    def supports_shards(self) -> bool:
        """True when the loaded library has the stateless batch-shard entry
        points (ftok_shard_*). Shard calls never touch the handle's begin/
        fill row state, so they need no ``_call_lock`` — N threads may drive
        N shards of one batch concurrently over this one handle."""
        return bool(getattr(self._lib, "_has_shards", False))

    @staticmethod
    def sanitize(text: str) -> bytes:
        """The encode() wire prep (NUL-strip + surrogatepass), shared so the
        sharded path feeds the C ABI byte-identical inputs."""
        return text.encode("utf-8", "surrogatepass").replace(b"\x00", b"")

    def shard_begin(self, texts: Sequence[bytes]) -> Tuple[int, int]:
        """Encode one shard (phase 1): tokenize+hash ``texts`` (already
        ``sanitize``d bytes) into a heap-owned shard object. Returns
        ``(shard_handle, width)``; the text buffers may be dropped as soon
        as this returns (rows store bucket ids, not byte references)."""
        arr = (ctypes.c_char_p * len(texts))(*texts)
        width = np.zeros(1, np.int32)
        shard = self._lib.ftok_shard_begin(self._handle, arr, len(texts), width)
        return shard, int(width[0])

    def shard_fill_into(self, shard: int, ids: np.ndarray, counts: np.ndarray,
                        rows: int, length: int) -> None:
        """Phase 2: write one shard's padded rows into a C-contiguous
        row-slice of the caller's preallocated output arrays (zero-copy
        assembly — no per-shard arrays, no concatenate)."""
        if ids.dtype == np.int16:
            self._lib.ftok_shard_fill16(shard, ids, counts, rows, length)
        else:
            self._lib.ftok_shard_fill(shard, ids, counts, rows, length)

    def shard_destroy(self, shard: int) -> None:
        if shard:
            self._lib.ftok_shard_destroy(shard)

    def supports_json_shards(self) -> bool:
        """True when the library has the stateless raw-JSON shard entry
        point (``ftok_shard_json_begin``) — like the text shards, it never
        touches the handle's begin/fill row state, so N threads may encode
        N message shards concurrently over this one handle."""
        return bool(getattr(self._lib, "_has_json_shards", False))

    def shard_json_begin(self, msgs_ptr, lens: np.ndarray, n: int,
                         key: bytes, status: np.ndarray,
                         span_start: np.ndarray,
                         span_len: np.ndarray) -> Tuple[int, int]:
        """Raw-JSON shard encode (phase 1): parse+extract+tokenize ``n``
        messages starting at ``msgs_ptr`` (a sub-pointer into the batch's
        one marshalled ``char*[]``), writing this shard's slice of the
        status/span arrays. Returns ``(shard_handle, width)``; fill with
        ``shard_fill_into`` exactly like a text shard."""
        width = np.zeros(1, np.int32)
        shard = self._lib.ftok_shard_json_begin(
            self._handle, msgs_ptr, lens, n, key, len(key),
            status, span_start, span_len, width)
        return shard, int(width[0])

    def encode_json(self, values: Sequence[bytes], key: bytes, rows: int,
                    max_tokens: Optional[int], pad_len,
                    want16: bool = False) -> Tuple[
                        np.ndarray, np.ndarray, np.ndarray, np.ndarray,
                        np.ndarray, object]:
        """Raw-JSON batch encode: one native pass extracts the string field
        ``key`` from each JSON message, cleans+tokenizes+hashes it.

        Returns (ids, counts, status, span_start, span_len, splice_ctx):
        padded (rows, L) arrays where malformed messages (status 0) are
        all-padding rows, plus the raw string literal's byte span (including
        quotes) inside each message for zero-copy splicing into output
        frames. ``splice_ctx`` is the marshalled ``char*[n]`` message array —
        hand it (with the spans) to ``build_frames`` to assemble output
        frames without re-marshalling the batch; pointers stay valid only
        while the caller keeps the message bytes alive. Explicit lengths
        are passed, so embedded NULs in message bytes are handled exactly
        (json.loads would reject them inside strings as raw control chars)."""
        if not getattr(self._lib, "_has_json", False):
            raise RuntimeError("native library predates the JSON encode path")
        n = len(values)
        arr = (ctypes.c_char_p * n)(*values)
        lens = np.fromiter((len(v) for v in values), np.int32, n)
        status = np.zeros(n, np.int32)
        span_start = np.zeros(n, np.int32)
        span_len = np.zeros(n, np.int32)
        with self._call_lock:
            try:
                width = self._begin(self._lib.ftok_encode_json_begin,
                                    arr, lens, n, key, len(key),
                                    status, span_start, span_len)
                length = max_tokens if max_tokens is not None else pad_len(max(width, 1))
                ids, counts = self._fill(rows, length, want16)
            finally:
                self._pair_check.finish()
        return ids, counts, status, span_start, span_len, arr


def frames_available() -> bool:
    lib = load_library()
    return bool(lib is not None and getattr(lib, "_has_frames", False))


def build_frames(msgs_arr, span_start: np.ndarray, span_len: np.ndarray,
                 labels: np.ndarray, confs: np.ndarray,
                 label_jsons: Sequence[bytes]) -> Tuple[bytes, np.ndarray]:
    """Assemble the engine's classified-output wire frames in one native pass.

    ``msgs_arr`` is the SAME ctypes ``char*[n]`` array a prior
    ``encode_json`` marshalled (returned as its splice context — so this
    call does zero per-message Python->C conversion); ``span_start`` /
    ``span_len`` locate each message's raw string literal (with quotes) to
    splice. ``labels`` (n,) int32 — rows whose label falls outside
    ``[0, len(label_jsons))`` (e.g. -1 for malformed) come back as EMPTY
    frames for the caller's Python fallback; ``confs`` (n,) float64.
    Returns ``(blob, ends)``: frame i is ``blob[ends[i-1]:ends[i]]``.
    The message bytes the array points into must still be alive (the engine
    holds them via its in-flight batch).
    """
    lib = load_library()
    n = len(span_start)
    ljs = (ctypes.c_char_p * len(label_jsons))(*label_jsons)
    ljlens = np.fromiter((len(s) for s in label_jsons), np.int32,
                         len(label_jsons))
    ends = np.empty(n, np.int64)
    # Mirrors the C++ per-row bound: 96 fixed + label json + text literal.
    cap = int(span_len.sum()) + n * (96 + int(ljlens.max(initial=0)))
    buf = ctypes.create_string_buffer(cap)
    total = lib.ftok_build_frames(msgs_arr, span_start, span_len, labels,
                                  confs, ljs, ljlens, len(label_jsons),
                                  n, buf, cap, ends)
    if total < 0:  # cannot happen while cap mirrors the C++ bound
        raise RuntimeError("frame buffer overflow")
    return ctypes.string_at(buf, total), ends


def available() -> bool:
    return load_library() is not None
