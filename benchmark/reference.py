"""Plain references, and the weights both sides are given. The yardstick:
later PRs may not edit this file, and it imports nothing of the program.

* ``make_llm_params``: the explainer's random weights, made on the device
  from the seed in ONE jitted call, in the serving dtype, in the parameter
  layout the program's ``LanguageModel`` takes. The reference makes the same
  numbers again for itself, layer by layer, from the same per-layer keys.
* ``llm_token_gaps``: the decoder's forward pass in straightforward float32
  ``jax.numpy`` at ``highest`` matmul precision (no cache, no paging, no
  kernels; whole sequences, a few requests side by side), teacher-forced
  over a prompt and the tokens the program served for it. It returns, per
  served token, how far that token's logit lies below the reference's best.
  Its control is the program itself with its own lower precision switched
  on (``LanguageModel.quantized()``, benchmark/control.py).
* ``classifier_confidences``: clean -> tokenize -> stop words -> murmur3
  bucket -> counts -> TF-IDF -> LR dot or boosted-tree walk, in Python and
  NumPy. The stop list is the benchmark's own copy and the IDF is fitted
  again from the benchmark's corpus; only the fitted weights or trees come
  from the artifact the desk serves (manifest.json + arrays.npz): a served
  input, as a checkpoint's weights are, not something this file checks.

Departures from the published InternLM2 description, both only a fixed
permutation of random weights: projections are stored (in, heads, head_dim),
and rotary pairs are the interleaved (2i, 2i+1) lanes as the program's are,
where the published code rotates half-blocks.
"""

from __future__ import annotations

import json
import math
import os
import random
import re
from typing import Dict, List, Sequence

import numpy as np

# ---------------------------------------------------------------------------
# explainer weights from the seed
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: dict) -> Dict[str, tuple]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads", h)
    hd = cfg.get("head_dim", d // h)
    f = cfg["intermediate_size"]
    return {"wq": (d, h, hd), "wk": (d, hkv, hd), "wv": (d, hkv, hd),
            "wo": (h, hd, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


def _fan_in(name: str, shape: tuple) -> int:
    return shape[0] * shape[1] if name == "wo" else shape[0]


def _root_key(seed: int):
    import jax

    # Seeds run to a little over 2**31: fold the high bits in, so no seed
    # is cut to 32 signed bits on the way.
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _layer_weights(key, cfg: dict, dtype):
    """One layer's seven matrices, N(0, 1/fan_in), rounded to ``dtype``."""
    import jax

    out = {}
    for i, (name, shape) in enumerate(_leaf_shapes(cfg).items()):
        w = jax.random.normal(jax.random.fold_in(key, i), shape, "float32")
        out[name] = (w / math.sqrt(_fan_in(name, shape))).astype(dtype)
    return out


def _embed_weights(key, cfg: dict, dtype):
    import jax

    v, d = cfg["vocab_size"], cfg["hidden_size"]
    embed = jax.random.normal(jax.random.fold_in(key, 0), (v, d),
                              "float32").astype(dtype)
    head = (jax.random.normal(jax.random.fold_in(key, 1), (v, d), "float32")
            / math.sqrt(d)).astype(dtype)
    return embed, head


def make_llm_params(seed: int, cfg: dict, dtype) -> dict:
    """All of the explainer's weights in one jitted call (untied head)."""
    import jax
    import jax.numpy as jnp

    if cfg.get("tie_word_embeddings", False):
        raise ValueError("the benchmark's weight maker builds an untied head")
    n = cfg["num_hidden_layers"]

    @jax.jit
    def make(root):
        p = {}
        p["embed"], p["lm_head"] = _embed_weights(
            jax.random.fold_in(root, n), cfg, dtype)
        ones = jnp.ones((cfg["hidden_size"],), dtype)
        for l in range(n):
            for name, w in _layer_weights(jax.random.fold_in(root, l), cfg,
                                          dtype).items():
                p[f"l{l}.{name}"] = w
            p[f"l{l}.ln1"] = p[f"l{l}.ln2"] = ones
        p["ln_f"] = ones
        return p

    return make(_root_key(seed))


# ---------------------------------------------------------------------------
# explainer reference forward
# ---------------------------------------------------------------------------

def _rms(x, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (T, H, d); lanes (2i, 2i+1) rotate by position * theta^(-2i/d)."""
    import jax.numpy as jnp

    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


_FNS: Dict[tuple, tuple] = {}


def _reference_fns(cfg: dict, dtype_name: str):
    """(embed, layer, logits) jitted for this config. Every function makes
    its own weights from the key it is given, upcast to float32 from the
    serving dtype's values, so nothing wide stays on the device."""
    memo = (json.dumps({k: v for k, v in cfg.items() if k != "desk"},
                       sort_keys=True, default=str), dtype_name)
    if memo not in _FNS:
        _FNS[memo] = _build_reference_fns(cfg, dtype_name)
    return _FNS[memo]


def _build_reference_fns(cfg: dict, dtype_name: str):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    h = cfg["num_attention_heads"]
    rep = h // cfg.get("num_key_value_heads", h)
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the plain reference implements SiLU-gated MLPs")

    @jax.jit
    def embed(key, tokens):
        table, _ = _embed_weights(key, cfg, dtype)
        return table[tokens].astype(jnp.float32)

    @jax.jit
    def layer(key, x):
        """x: (B, T, D), a block of requests; the weights are made once."""
        with jax.default_matmul_precision("highest"):
            w = {n: a.astype(jnp.float32)
                 for n, a in _layer_weights(key, cfg, dtype).items()}
            t = x.shape[1]
            hn = _rms(x, eps)
            rope = jax.vmap(lambda a: _rope(a, theta))
            q = rope(jnp.einsum("btD,Dhd->bthd", hn, w["wq"]))
            k = rope(jnp.einsum("btD,Dhd->bthd", hn, w["wk"]))
            v = jnp.einsum("btD,Dhd->bthd", hn, w["wv"])
            k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
            s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(q.shape[-1])
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s,
                          -jnp.inf)
            a = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
            x = x + jnp.einsum("bthd,hdD->btD", a, w["wo"])
            h2 = _rms(x, eps)
            mlp = jax.nn.silu(h2 @ w["w_gate"]) * (h2 @ w["w_up"])
            return x + mlp @ w["w_down"]

    @jax.jit
    def logits(key, x):
        with jax.default_matmul_precision("highest"):
            _, head = _embed_weights(key, cfg, dtype)
            return _rms(x, eps) @ head.astype(jnp.float32).T

    return embed, layer, logits


REFERENCE_BLOCK = 8       # requests a reference pass holds at once


def llm_token_gaps(seed: int, cfg: dict, dtype_name: str,
                   requests: Sequence[dict], pad_to: int) -> List[np.ndarray]:
    """For each request ``{"prompt": int tokens, "served": int tokens}`` the
    gap of every served token: the reference's best logit at its position
    minus the logit of the token that was served there (0 where it is the
    best). The requests go through in blocks of ``REFERENCE_BLOCK``, layer
    by layer, each padded to ``pad_to`` positions so that one compilation
    serves them all; a layer's weights are made once a block."""
    import jax
    import jax.numpy as jnp

    n = cfg["num_hidden_layers"]
    root = _root_key(seed)
    keys = [jax.random.fold_in(root, l) for l in range(n + 1)]
    embed, layer, logits = _reference_fns(cfg, dtype_name)
    block = min(REFERENCE_BLOCK, max(1, len(requests)))
    span = max((len(r["served"]) for r in requests), default=0)
    out = []
    for lo in range(0, len(requests), block):
        part = list(requests[lo:lo + block])
        toks = np.zeros((block, pad_to), np.int32)
        served = np.zeros((block, span), np.int32)
        at = np.zeros((block, span), np.int32)    # positions that predict
        for b, req in enumerate(part):
            prompt = np.asarray(req["prompt"], np.int32)
            out_b = np.asarray(req["served"], np.int32)
            seq = np.concatenate([prompt, out_b[:-1]])
            if len(seq) > pad_to:
                raise ValueError(
                    f"sequence of {len(seq)} exceeds pad_to {pad_to}")
            toks[b, :len(seq)] = seq
            served[b, :len(out_b)] = out_b
            at[b] = np.minimum(len(prompt) - 1 + np.arange(span),
                               len(seq) - 1)
        x = embed(keys[n], jnp.asarray(toks))
        for l in range(n):
            x = layer(keys[l], x)
        ref = logits(keys[n], x[jnp.arange(block)[:, None], jnp.asarray(at)])
        gap = np.asarray(
            jnp.max(ref, -1) - jnp.take_along_axis(
                ref, jnp.asarray(served)[..., None], -1)[..., 0], np.float64)
        out += [gap[b, :len(req["served"])] for b, req in enumerate(part)]
    return out


# ---------------------------------------------------------------------------
# classifier reference (host featurize + LR / boosted trees)
# ---------------------------------------------------------------------------

_NON_ALPHA_SPACE = re.compile(r"[^a-z ]")
_WS = re.compile(r"[ \t\n\x0b\f\r]")
_M = 0xFFFFFFFF


def _murmur3_32(data: bytes, seed: int) -> int:
    def mix_k(k):
        k = (k * 0xCC9E2D51) & _M
        k = ((k << 15) | (k >> 17)) & _M
        return (k * 0x1B873593) & _M

    h = seed & _M
    aligned = len(data) & ~3
    for i in range(0, aligned, 4):
        h ^= mix_k(int.from_bytes(data[i:i + 4], "little"))
        h = ((h << 13) | (h >> 19)) & _M
        h = (h * 5 + 0xE6546B64) & _M
    k = 0
    for j, b in enumerate(data[aligned:]):
        k ^= b << (8 * j)
    h ^= mix_k(k)
    h ^= len(data)
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & _M
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & _M
    return h ^ (h >> 16)


def _bucket(term: str, num_features: int) -> int:
    """Spark ml HashingTF: murmur3_x86_32(utf8, seed 42) as a signed int,
    non-negative mod."""
    h = _murmur3_32(term.encode("utf-8"), 42)
    if h >= 1 << 31:
        h -= 1 << 32
    return h % num_features          # Python's % is already non-negative


def default_stopwords() -> List[str]:
    """Spark's public default English list (181 words), the benchmark's own
    copy: ``benchmark/stopwords.txt``."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "stopwords.txt")
    with open(path, encoding="utf-8") as f:
        return [w for w in f.read().splitlines() if w]


def training_texts(texts: Sequence[str], seed: int,
                   fraction: float) -> List[str]:
    """The rows the classifier is fitted on, as the configuration states
    the split: a ``random.Random(seed)`` shuffle of the corpus, the first
    ``fraction`` of it."""
    idx = list(range(len(texts)))
    random.Random(int(seed)).shuffle(idx)
    return [texts[i] for i in idx[:int(round(fraction * len(texts)))]]


class ClassifierArtifact:
    """The reference's featurizer (its own stop list; the IDF fitted again
    from ``train_texts``, Spark ``IDF.fit``: ln((docs + 1) / (docFreq + 1)))
    beside the fitted model read straight from the artifact's two files.
    The fitted weights or trees are a served input, taken as they are; the
    artifact's own stop list and IDF are only compared (``served_stop``,
    ``served_idf``), never used."""

    def __init__(self, path: str, train_texts: Sequence[str]):
        with open(os.path.join(path, "manifest.json")) as f:
            self.meta = json.load(f)
        with np.load(os.path.join(path, "arrays.npz")) as z:
            self.arrays = {k: z[k] for k in z.files}
        feat = self.meta["featurizer"]
        if feat.get("kind", "hashing") != "hashing" or feat.get("binary_tf"):
            raise ValueError("the plain reference covers the hashing "
                             "term-frequency featurizer only")
        self.num_features = int(feat["num_features"])
        self.stop = frozenset(w.lower() for w in default_stopwords())
        self.served_stop = (frozenset(w.lower() for w in feat["stopwords"])
                            if feat.get("remove_stopwords", True)
                            else frozenset())
        self.served_idf = np.asarray(self.arrays["featurizer.idf"], np.float32)
        self._buckets: Dict[str, int] = {}
        doc_freq = np.zeros(self.num_features, np.int64)
        for t in train_texts:
            doc_freq[list(self.counts(t))] += 1
        self.idf = np.log((len(train_texts) + 1.0)
                          / (doc_freq + 1.0)).astype(np.float32)

    def counts(self, text: str) -> Dict[int, float]:
        clean = _NON_ALPHA_SPACE.sub("", text.lower())
        toks = _WS.split(clean) if clean else [""]
        while toks and toks[-1] == "" and clean:
            toks.pop()                      # Java split drops trailing ""
        row: Dict[int, float] = {}
        for tok in toks:
            if tok.lower() in self.stop:
                continue
            b = self._buckets.get(tok)
            if b is None:
                b = self._buckets[tok] = _bucket(tok, self.num_features)
            row[b] = row.get(b, 0.0) + 1.0
        return row

    def featurizer_numbers(self) -> Dict[str, float]:
        """The served featurizer's tables against the reference's own."""
        return {"stoplist_mismatch": len(self.stop ^ self.served_stop),
                "idf_gap": (float(np.max(np.abs(self.idf - self.served_idf)))
                            if self.idf.shape == self.served_idf.shape
                            else float("inf"))}


def _sigmoid(m: np.ndarray) -> np.ndarray:
    e = np.exp(-np.abs(m))
    return np.where(m >= 0, 1.0 / (1.0 + e), e / (1.0 + e))


def _round_to(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float32":
        return np.asarray(x, np.float32)
    if precision == "bfloat16":
        import ml_dtypes

        return np.asarray(x, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def classifier_confidences(artifact: ClassifierArtifact, texts: Sequence[str],
                           precision: str = "float32"):
    """(labels, confidences) as the desk's output frames state them: label 1
    when p(scam) passes the threshold, confidence = p of the stated label.
    ``precision="bfloat16"`` is the control: the model's numbers (weights,
    IDF, thresholds, leaves) rounded to bfloat16 first."""
    kind = artifact.meta["model_kind"]
    a = artifact.arrays
    idf = _round_to(artifact.idf, precision)
    rows = [artifact.counts(t) for t in texts]
    if kind == "logistic_regression":
        w = _round_to(_round_to(a["model.weights"], precision) * idf,
                      precision)
        b = float(_round_to(a["model.intercept"], precision))
        margin = np.asarray([
            float(np.sum(np.asarray([w[i] * c for i, c in r.items()],
                                    np.float32), dtype=np.float32)) + b
            for r in rows], np.float64)
        threshold = float(artifact.meta["model"].get("threshold", 0.5))
    elif kind == "tree_ensemble":
        if artifact.meta["model"]["kind"] != "xgboost":
            raise ValueError("the plain reference walks boosted trees only")
        feature, left, right = a["model.feature"], a["model.left"], a["model.right"]
        thr = _round_to(a["model.threshold"], precision)
        leaf = _round_to(a["model.leaf"], precision)
        tw = _round_to(a["model.tree_weights"], precision)
        margin = np.full(len(rows), float(artifact.meta["model"]["bias"]),
                         np.float64)
        for r, row in enumerate(rows):
            dense = {i: np.float32(c) * idf[i] for i, c in row.items()}
            for t in range(feature.shape[0]):
                node = 0
                while left[t, node] >= 0:
                    x = dense.get(int(feature[t, node]), np.float32(0.0))
                    node = (left[t, node] if x <= thr[t, node]
                            else right[t, node])
                margin[r] += float(tw[t]) * float(leaf[t, node, 0])
        threshold = 0.5
    else:
        raise ValueError(f"unknown model kind {kind!r}")
    p = _sigmoid(margin)
    labels = (p > threshold).astype(np.int64)
    return labels, np.where(labels == 1, p, 1.0 - p)
