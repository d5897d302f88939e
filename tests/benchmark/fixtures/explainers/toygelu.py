"""A toy explainer family for tests/benchmark/test_extend.py, as a later PR
would bring one: a tied-head dense decoder with a tanh-GELU-gated MLP. The
program runs it today (``TransformerConfig(activation="gelu",
tie_embeddings=True)``); ``benchmark/reference.py`` refuses both keys. The
contract is the header of ``benchmark/explainers/internlm2.py``; everything
the family needs is in this file, and nothing of ``benchmark/`` is imported.
"""

import math

import numpy as np


def _dims(cfg: dict):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads", h)
    return (d, h, hkv, cfg.get("head_dim", d // h), cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["vocab_size"])


# -- weights from the seed ---------------------------------------------------

def _key(seed: int, index: int):
    """Key of layer ``index`` (the embedding is index ``num_hidden_layers``);
    seeds run past 2**31, so the high bits are folded in."""
    import jax

    seed = int(seed)
    root = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.random.fold_in(root, index)


def _layer(key, cfg: dict, dtype) -> dict:
    import jax

    d, h, hkv, hd, f, _, _ = _dims(cfg)
    shapes = {"wq": (d, h, hd), "wk": (d, hkv, hd), "wv": (d, hkv, hd),
              "wo": (h, hd, d), "w_gate": (d, f), "w_up": (d, f),
              "w_down": (f, d)}
    out = {}
    for i, (name, shape) in enumerate(shapes.items()):
        fan_in = shape[0] * shape[1] if name == "wo" else shape[0]
        w = jax.random.normal(jax.random.fold_in(key, i), shape, "float32")
        out[name] = (w / math.sqrt(fan_in)).astype(dtype)
    return out


def _embed(key, cfg: dict, dtype):
    import jax

    d, _, _, _, _, _, v = _dims(cfg)
    return (jax.random.normal(key, (v, d), "float32") / math.sqrt(d)).astype(dtype)


def make_params(seed: int, cfg: dict, dtype) -> dict:
    import jax
    import jax.numpy as jnp

    n = cfg["num_hidden_layers"]

    @jax.jit
    def make():
        ones = jnp.ones((cfg["hidden_size"],), dtype)
        p = {"embed": _embed(_key(seed, n), cfg, dtype), "ln_f": ones}
        for l in range(n):
            for name, w in _layer(_key(seed, l), cfg, dtype).items():
                p[f"l{l}.{name}"] = w
            p[f"l{l}.ln1"] = p[f"l{l}.ln2"] = ones
        return p

    return make()


# -- the model the slot lane serves -----------------------------------------

def build(cfg: dict, params: dict, weights: str):
    import jax.numpy as jnp

    from fraud_detection_tpu.models.llm import LanguageModel, TransformerConfig

    if cfg["hidden_act"] != "gelu_pytorch_tanh" or not cfg["tie_word_embeddings"]:
        raise ValueError("the toygelu family is tied-head and tanh-GELU-gated")
    d, h, hkv, hd, f, n, v = _dims(cfg)
    lm = LanguageModel(TransformerConfig(
        vocab_size=v, d_model=d, n_heads=h, n_layers=n, d_ff=f,
        max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        dtype=jnp.dtype(cfg["torch_dtype"]).type, n_kv_heads=hkv,
        head_dim_override=hd, activation="gelu", tie_embeddings=True,
        rms_eps=float(cfg["rms_norm_eps"])), params)
    return lm.quantized() if weights == "int8" else lm


# -- the plain reference ----------------------------------------------------

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


def token_gaps(seed: int, cfg: dict, dtype_name: str, requests, pad_to: int,
               act=None):
    """Whole sequences in float32 at ``highest``, one request at a time,
    each padded to ``pad_to`` so that one compilation serves them all."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    _, h, hkv, hd, _, n, _ = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    act = act or (lambda a: jax.nn.gelu(a, approximate=True))

    @jax.jit
    def logits(tokens):
        with jax.default_matmul_precision("highest"):
            table = _embed(_key(seed, n), cfg, dtype).astype(jnp.float32)
            x = table[tokens]
            t = x.shape[0]
            for l in range(n):
                w = {k: a.astype(jnp.float32)
                     for k, a in _layer(_key(seed, l), cfg, dtype).items()}
                hn = _rms(x, eps)
                q = _rope(jnp.einsum("tD,Dhd->thd", hn, w["wq"]), theta)
                k = _rope(jnp.einsum("tD,Dhd->thd", hn, w["wk"]), theta)
                v = jnp.einsum("tD,Dhd->thd", hn, w["wv"])
                k, v = (jnp.repeat(a, h // hkv, axis=1) for a in (k, v))
                s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hd)
                s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s,
                              -jnp.inf)
                a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
                x = x + jnp.einsum("thd,hdD->tD", a, w["wo"])
                h2 = _rms(x, eps)
                x = x + (act(h2 @ w["w_gate"]) * (h2 @ w["w_up"])) @ w["w_down"]
            return _rms(x, eps) @ table.T

    out = []
    for req in requests:
        prompt = np.asarray(req["prompt"], np.int32)
        served = np.asarray(req["served"], np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        toks = np.zeros((pad_to,), np.int32)
        toks[:len(seq)] = seq
        ref = np.asarray(logits(jnp.asarray(toks)),
                         np.float64)[len(prompt) - 1:len(seq)]
        out.append(ref.max(-1) - ref[np.arange(len(served)), served])
    return out


# -- counts -----------------------------------------------------------------

def _layer_params(cfg: dict) -> int:
    d, h, hkv, hd, f, _, _ = _dims(cfg)
    return d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * f


def param_count(cfg: dict) -> int:
    d, _, _, _, _, n, v = _dims(cfg)
    return n * (_layer_params(cfg) + 2 * d) + v * d + d      # one table: tied


def _token_flops(cfg: dict, context: float) -> float:
    d, h, _, hd, _, n, v = _dims(cfg)
    return 2.0 * n * _layer_params(cfg) + 4.0 * n * h * hd * context + 2.0 * v * d


def _weight_bytes(cfg: dict, itemsize: int) -> int:
    d, _, _, _, _, n, v = _dims(cfg)
    return (n * _layer_params(cfg) + v * d) * itemsize


def _kv_bytes(cfg: dict, itemsize: int) -> int:
    _, _, hkv, hd, _, n, _ = _dims(cfg)
    return 2 * hkv * hd * itemsize * n


def decode_cost(cfg: dict, steps: float, row_steps: float,
                mean_context: float, itemsize: int = 2):
    return (row_steps * _token_flops(cfg, mean_context),
            steps * _weight_bytes(cfg, itemsize)
            + row_steps * _kv_bytes(cfg, itemsize) * (mean_context + 1))


def prefill_cost(cfg: dict, prefix_len: int, suffix_len: int,
                 itemsize: int = 2):
    d, h, _, hd, _, n, v = _dims(cfg)
    ctx_sum = suffix_len * prefix_len + suffix_len * (suffix_len + 1) / 2.0
    return (2.0 * n * _layer_params(cfg) * suffix_len
            + 4.0 * n * h * hd * ctx_sum + 2.0 * v * d,
            _weight_bytes(cfg, itemsize)
            + _kv_bytes(cfg, itemsize) * (prefix_len + suffix_len))
