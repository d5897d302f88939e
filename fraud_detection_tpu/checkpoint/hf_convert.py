"""Pretrained-checkpoint converter: HF safetensors -> models/llm.py pytree.

BASELINE.json config 5 names a Gemma-class on-pod explanation model; the
reference reaches its LLM over HTTPS (/root/reference/utils/agent_api.py:36,
deepseek_chat_ui.py:7-12). This module makes the zero-egress replacement
real: given a locally downloaded HuggingFace checkpoint directory
(config.json + *.safetensors [+ tokenizer files]), it produces the exact
parameter pytree `models/llm.forward` consumes.

Three deliberate design points:

* **No safetensors dependency.** The format is 8 bytes of header length +
  JSON header + raw little-endian tensor bytes; `read_safetensors` /
  `write_safetensors` implement it directly over numpy (bfloat16 via
  ml_dtypes, which JAX already ships).
* **RoPE basis permutation.** HF Llama/Gemma checkpoints pair dimension i
  with i + d/2 ("rotate_half"); our `rope` pairs (2i, 2i+1). The converter
  permutes the head_dim axis of wq/wk so our interleaved rotation computes
  the identical attention scores — a basis change, not an approximation
  (dot products are invariant under the shared permutation; v/wo untouched).
* **Architecture quirks become config or weights, not code.** Gemma's
  (1 + w) RMSNorm is folded into the stored gammas; its sqrt(D) embedding
  scale and GeGLU activation are `TransformerConfig` fields; GQA/MQA widths
  land in `n_kv_heads`; untied output heads become an explicit "lm_head"
  param.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, Optional

import numpy as np

from fraud_detection_tpu.models.llm import Params, TransformerConfig

# safetensors dtype tag -> numpy dtype (bfloat16 via ml_dtypes, a jax dep)
def _np_dtypes():
    import ml_dtypes

    return {
        "F64": np.float64, "F32": np.float32, "F16": np.float16,
        "BF16": ml_dtypes.bfloat16,
        "I64": np.int64, "I32": np.int32, "I16": np.int16, "I8": np.int8,
        "U8": np.uint8, "BOOL": np.bool_,
    }


def read_safetensors(path: str) -> Dict[str, np.ndarray]:
    """Parse one .safetensors file into {name: array}.

    The data region is memory-mapped and each tensor is a VIEW into the
    mapped pages — a multi-GB shard costs address space, not resident RAM,
    until a tensor is actually touched (and only that tensor's pages)."""
    dtypes = _np_dtypes()
    with open(path, "rb") as f:
        header_len = int.from_bytes(f.read(8), "little")
        header = json.loads(f.read(header_len))
    base = 8 + header_len
    data = np.memmap(path, dtype=np.uint8, mode="r")
    out: Dict[str, np.ndarray] = {}
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        start, end = meta["data_offsets"]
        arr = data[base + start : base + end].view(dtypes[meta["dtype"]])
        out[name] = arr.reshape(meta["shape"])
    return out


def write_safetensors(path: str, tensors: Dict[str, np.ndarray]) -> None:
    """Write {name: array} as a .safetensors file (test/round-trip support)."""
    rev = {np.dtype(v): k for k, v in _np_dtypes().items()}
    header: Dict[str, dict] = {}
    offset = 0
    blobs = []
    for name, arr in tensors.items():
        arr = np.ascontiguousarray(arr)
        blob = arr.tobytes()
        header[name] = {"dtype": rev[arr.dtype], "shape": list(arr.shape),
                        "data_offsets": [offset, offset + len(blob)]}
        offset += len(blob)
        blobs.append(blob)
    hdr = json.dumps(header).encode()
    with open(path, "wb") as f:
        f.write(len(hdr).to_bytes(8, "little"))
        f.write(hdr)
        for blob in blobs:
            f.write(blob)


def read_checkpoint_tensors(ckpt_dir: str) -> Dict[str, np.ndarray]:
    """All tensors of a checkpoint dir, following the sharding index when
    present (model.safetensors.index.json -> weight_map)."""
    index = os.path.join(ckpt_dir, "model.safetensors.index.json")
    if os.path.exists(index):
        with open(index) as f:
            weight_map: Dict[str, str] = json.load(f)["weight_map"]
        out: Dict[str, np.ndarray] = {}
        for fname in sorted(set(weight_map.values())):
            out.update(read_safetensors(os.path.join(ckpt_dir, fname)))
        return out
    single = os.path.join(ckpt_dir, "model.safetensors")
    if os.path.exists(single):
        return read_safetensors(single)
    cands = [f for f in os.listdir(ckpt_dir) if f.endswith(".safetensors")]
    if len(cands) == 1:
        return read_safetensors(os.path.join(ckpt_dir, cands[0]))
    raise FileNotFoundError(
        f"no model.safetensors(.index.json) in {ckpt_dir!r} (found {cands})")


def config_from_hf(hf: dict, *, max_seq: int = 4096,
                   dtype=None) -> TransformerConfig:
    """Map an HF config.json dict onto TransformerConfig.

    Handles the Llama family (llama/mistral/qwen2/deepseek) and Gemma; other
    model types raise so a silent architecture mismatch can't ship.
    """
    import jax.numpy as jnp

    mtype = hf.get("model_type", "llama")
    # Only architectures convert_hf_state can FULLY map are allowed: qwen2
    # (mandatory q/k/v biases), gemma2 (extra feedforward norms + logit
    # softcapping) and deepseek_v2 (MLA attention) would fail late or — worse
    # — numerically wrong, so they are rejected up front.
    if mtype not in ("llama", "mistral", "deepseek", "gemma"):
        raise NotImplementedError(
            f"model_type {mtype!r} is not a supported architecture "
            "(Llama-family and Gemma-1 checkpoints map onto models/llm.py)")
    act = hf.get("hidden_act", "silu")
    if act in ("silu", "swish"):
        activation = "silu"
    elif act in ("gelu", "gelu_new", "gelu_pytorch_tanh"):
        activation = "gelu"
    else:
        raise NotImplementedError(f"hidden_act {act!r} unsupported")
    d_model = int(hf["hidden_size"])
    n_heads = int(hf["num_attention_heads"])
    gemma = mtype.startswith("gemma")
    head_dim = hf.get("head_dim")
    return TransformerConfig(
        vocab_size=int(hf["vocab_size"]),
        d_model=d_model,
        n_heads=n_heads,
        n_layers=int(hf["num_hidden_layers"]),
        d_ff=int(hf["intermediate_size"]),
        max_seq=max_seq,
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        dtype=dtype if dtype is not None else jnp.bfloat16,
        n_kv_heads=int(hf.get("num_key_value_heads", n_heads)),
        head_dim_override=None if head_dim is None else int(head_dim),
        activation=activation,
        embed_scale=math.sqrt(d_model) if gemma else 1.0,
        tie_embeddings=bool(hf.get("tie_word_embeddings", gemma)),
        rms_eps=float(hf.get("rms_norm_eps", 1e-6)),
    )


def _rope_permutation(d: int) -> np.ndarray:
    """Index map half-split -> interleaved: new[2i]=old[i], new[2i+1]=old[i+d/2]."""
    perm = np.empty(d, np.int64)
    perm[0::2] = np.arange(d // 2)
    perm[1::2] = np.arange(d // 2) + d // 2
    return perm


def convert_hf_state(state: Dict[str, np.ndarray],
                     cfg: TransformerConfig) -> Params:
    """HF Llama/Gemma-layout state dict -> models/llm.py parameter pytree
    (numpy; caller device_puts / shards). Rejects unexpected extras like
    attention biases instead of silently dropping them."""
    h, hkv, d, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_model
    perm = _rope_permutation(d)
    gemma = cfg.embed_scale != 1.0

    def take(name: str) -> np.ndarray:
        # Stays in the checkpoint's dtype (often bf16 memmap views): peak
        # host RAM ~1x the converted tensor, not a float32 blow-up.
        try:
            return np.asarray(state.pop(name))
        except KeyError:
            raise KeyError(f"checkpoint is missing tensor {name!r}") from None

    def norm(w: np.ndarray) -> np.ndarray:
        # Gemma stores gamma - 1 (applies x * (1 + w)); fold the offset in
        # (computed in f32 so bf16 gammas near -1 don't lose bits).
        return (w.astype(np.float32) + 1.0).astype(w.dtype) if gemma else w

    p: Params = {"embed": take("model.embed_tokens.weight")}
    if not cfg.tie_embeddings:
        p["lm_head"] = take("lm_head.weight")
    else:
        state.pop("lm_head.weight", None)  # some exports duplicate the tie
    for l in range(cfg.n_layers):
        pre = f"model.layers.{l}."
        # HF projections are (out, in); ours are input-major
        wq = take(pre + "self_attn.q_proj.weight").T.reshape(D, h, d)
        wk = take(pre + "self_attn.k_proj.weight").T.reshape(D, hkv, d)
        p[f"l{l}.wq"] = wq[:, :, perm]
        p[f"l{l}.wk"] = wk[:, :, perm]
        p[f"l{l}.wv"] = take(pre + "self_attn.v_proj.weight").T.reshape(D, hkv, d)
        p[f"l{l}.wo"] = take(pre + "self_attn.o_proj.weight").T.reshape(h, d, D)
        p[f"l{l}.w_gate"] = take(pre + "mlp.gate_proj.weight").T
        p[f"l{l}.w_up"] = take(pre + "mlp.up_proj.weight").T
        p[f"l{l}.w_down"] = take(pre + "mlp.down_proj.weight").T
        p[f"l{l}.ln1"] = norm(take(pre + "input_layernorm.weight"))
        p[f"l{l}.ln2"] = norm(take(pre + "post_attention_layernorm.weight"))
    p["ln_f"] = norm(take("model.norm.weight"))
    if state:
        raise NotImplementedError(
            "unconverted tensors remain (unsupported architecture details, "
            f"e.g. attention biases): {sorted(state)[:8]}")
    return p


# ---------------------------------------------------------------------------
# converted-layout cache
# ---------------------------------------------------------------------------
#
# The HF->pytree conversion transposes/reshapes every projection out of the
# memmapped shards (non-contiguous host copies of the full multi-GB state)
# before anything reaches the device. That cost is pure waste after the first
# load, so the converted tensors are written ONCE — contiguous, already in
# models/llm.py layout — next to the HF dir, keyed by a fingerprint of the
# source (config bytes + shard names/sizes/mtimes). Warm loads memmap the
# cache and go straight to device upload.

_CACHE_NAME = "converted.fraud_tpu_cache"  # not .safetensors: must never be
#                                            picked up as a checkpoint shard

#: Bump whenever convert_hf_state's OUTPUT changes (layout, permutation,
#: gamma folding, ...) — part of the cache validity check, so an old cache
#: can never serve a new converter's layout.
_CONVERTER_VERSION = 1


def _converted_cache_paths(ckpt_dir: str, *, create: bool = False,
                           variant: str = ""):
    """(tensor_file, meta_file) for the converted cache — next to the HF dir
    when writable, under ~/.cache/fraud_tpu_converted/<dirhash> otherwise.
    ``create`` makes the fallback directory (write path only; read-side
    queries must not mutate the filesystem). ``variant`` names an alternate
    converted layout ("q8": host-quantized int8 — half the bytes to read
    AND upload on the warm path)."""
    import hashlib

    stem, dot, ext = _CACHE_NAME.partition(".")
    name = f"{stem}_{variant}{dot}{ext}" if variant else _CACHE_NAME
    if os.access(ckpt_dir, os.W_OK):
        base = os.path.join(ckpt_dir, name)
    else:
        tag = hashlib.sha256(
            os.path.abspath(ckpt_dir).encode()).hexdigest()[:16]
        d = os.path.join(os.path.expanduser("~/.cache/fraud_tpu_converted"),
                         tag)
        if create:
            os.makedirs(d, exist_ok=True)
        base = os.path.join(d, name)
    return base, base + ".json"


def _source_fingerprint(ckpt_dir: str) -> str:
    """Hash of everything the conversion reads: config.json bytes plus the
    (name, size, mtime_ns) of every safetensors shard."""
    import hashlib

    h = hashlib.sha256()
    with open(os.path.join(ckpt_dir, "config.json"), "rb") as f:
        h.update(f.read())
    for fn in sorted(os.listdir(ckpt_dir)):
        if fn.endswith(".safetensors"):
            st = os.stat(os.path.join(ckpt_dir, fn))
            h.update(f"{fn}:{st.st_size}:{st.st_mtime_ns}".encode())
    return h.hexdigest()


def _valid_cache_file(ckpt_dir: str, variant: str = "",
                      require: Optional[dict] = None) -> Optional[str]:
    """Path of a valid converted cache (fingerprint AND converter version
    match, tensor file present), else None. The ONE validity check — used by
    both ``load_hf_checkpoint`` and ``has_converted_cache`` so the bench's
    cold/warm labeling can't drift from what the loader actually does.
    ``require``: extra meta key/values that must match exactly (the q8
    variant's codes bake in the compute dtype, so its loader requires
    ``{"quant_dtype": ...}`` — a bf16-quantized cache must never serve an
    f32 load)."""
    cache_f, meta_f = _converted_cache_paths(ckpt_dir, variant=variant)
    try:
        with open(meta_f) as f:
            meta = json.load(f)
        if (meta.get("fingerprint") == _source_fingerprint(ckpt_dir)
                and meta.get("converter_version") == _CONVERTER_VERSION
                and all(meta.get(k) == v for k, v in (require or {}).items())
                and os.path.exists(cache_f)):
            return cache_f
    except (OSError, ValueError):
        pass
    return None


def has_converted_cache(ckpt_dir: str, variant: str = "",
                        quant_dtype=None) -> bool:
    """True when a valid converted cache exists — the bench uses this to
    label its load timing cold vs warm. ``variant="q8"`` asks about the
    host-quantized cache the ``int8=True`` load path keeps; pass the
    load's ``quant_dtype`` (model dtype) to ask the loader's EXACT
    question — a q8 cache bakes its compute dtype into the codes, so
    without it this is a presence check that a differently-typed load
    would still reject and rebuild."""
    require = ({"quant_dtype": np.dtype(quant_dtype).name}
               if quant_dtype is not None else None)
    return _valid_cache_file(ckpt_dir, variant, require) is not None


class HFTokenizerAdapter:
    """Wrap a transformers tokenizer behind the ByteTokenizer protocol
    (encode -> int32 ids with BOS, clamped to max_seq; decode stops at EOS).
    transformers is a local-files-only dependency here — nothing is fetched."""

    def __init__(self, tok, max_seq: int = 4096):
        self.tok = tok
        self.max_seq = max_seq

    @classmethod
    def from_dir(cls, ckpt_dir: str, max_seq: int = 4096) -> "HFTokenizerAdapter":
        from transformers import AutoTokenizer

        return cls(AutoTokenizer.from_pretrained(ckpt_dir, local_files_only=True),
                   max_seq=max_seq)

    def encode(self, text: str) -> np.ndarray:
        ids = self.tok.encode(text)
        if self.tok.bos_token_id is not None and (
                not ids or ids[0] != self.tok.bos_token_id):
            ids = [self.tok.bos_token_id] + ids
        # Same bound ByteTokenizer enforces: an unclamped 50k-token
        # transcript would size the KV cache and prefill quadratically.
        return np.asarray(ids[: self.max_seq - 2], np.int32)

    def decode(self, tokens) -> str:
        ids = []
        for t in np.asarray(tokens).tolist():
            if t == self.tok.eos_token_id:
                break
            ids.append(int(t))
        return self.tok.decode(ids, skip_special_tokens=True)


_Q8_KEY, _Q8_SCALE_KEY = "::q8", "::q8_scale"   # "::" never occurs in param names


def _flatten_q8(params: Dict[str, object]) -> Dict[str, np.ndarray]:
    """{name: ndarray | Q8} -> flat safetensors-writable {name: ndarray}."""
    from fraud_detection_tpu.models.llm import Q8

    out: Dict[str, np.ndarray] = {}
    for k, v in params.items():
        if isinstance(v, Q8):
            out[k + _Q8_KEY] = np.asarray(v.q)
            out[k + _Q8_SCALE_KEY] = np.asarray(v.scale)
        else:
            out[k] = v
    return out


def _unflatten_q8(tensors: Dict[str, np.ndarray]) -> Dict[str, object]:
    """Inverse of ``_flatten_q8`` (raises KeyError on a q8 half-pair —
    caught by the loader's corrupt-cache fallback)."""
    from fraud_detection_tpu.models.llm import Q8

    out: Dict[str, object] = {}
    for k, v in tensors.items():
        if k.endswith(_Q8_SCALE_KEY):
            continue
        elif k.endswith(_Q8_KEY):
            name = k[: -len(_Q8_KEY)]
            out[name] = Q8(q=v, scale=tensors[name + _Q8_SCALE_KEY])
        else:
            out[k] = v
    return out


def load_hf_checkpoint(ckpt_dir: str, *, max_seq: int = 4096, dtype=None,
                       mesh=None, tokenizer: Optional[object] = None,
                       use_cache: bool = True, int8: bool = False,
                       load_info: Optional[dict] = None):
    """Directory of a downloaded HF checkpoint -> ready LanguageModel.

    Plugs straight into the explanation layer:
    ``OnPodBackend.from_model(load_hf_checkpoint(dir))`` replaces the
    reference's DeepSeek HTTPS round-trip with on-pod serving.

    ``use_cache``: reuse (and on a miss, write) the converted-layout cache —
    warm loads skip the transpose-heavy conversion entirely and memmap
    straight into the device upload.

    ``int8``: weight-only quantization ON THE HOST, before upload — the
    model arrives identical to ``load_hf_checkpoint(dir).quantized()``
    (same rounding contract, pinned by test) but ships HALF the bytes
    through the host-to-device transfer of a cold start. Keeps its own converted cache variant ("q8", int8 + scales), so
    warm int8 loads also READ half the bytes; an int8 miss still reuses a
    valid bf16 cache (host quantize, no reconverting).

    ``load_info``: caller-supplied dict that receives what ACTUALLY
    happened — ``source`` ("q8_cache" | "bf16_cache" | "hf_shards": the
    tier the weights came from, recorded at the branch that served them,
    never re-derived by callers) — so the bench's artifact attribution is
    ground truth, not a pre-check that can drift from the loader.
    """
    import jax.numpy as jnp

    from fraud_detection_tpu.models.llm import (
        LanguageModel, Q8, quantize_params_host, shard_params)

    info = load_info if load_info is not None else {}
    with open(os.path.join(ckpt_dir, "config.json")) as f:
        cfg = config_from_hf(json.load(f), max_seq=max_seq, dtype=dtype)
    variant = "q8" if int8 else ""
    require = ({"quant_dtype": np.dtype(cfg.dtype).name} if int8 else None)
    params_np = None
    if use_cache:
        valid = _valid_cache_file(ckpt_dir, variant, require)
        if valid is not None:
            try:
                raw = read_safetensors(valid)
                params_np = _unflatten_q8(raw) if int8 else raw
                info["source"] = "q8_cache" if int8 else "bf16_cache"
            except (OSError, ValueError, KeyError):
                params_np = None
    if params_np is None:
        if use_cache and int8:
            # int8 miss, bf16 cache hit: skip the transpose-heavy
            # reconversion, just host-quantize the cached layout.
            bf16_cache = _valid_cache_file(ckpt_dir)
            if bf16_cache is not None:
                try:
                    params_np = read_safetensors(bf16_cache)
                    info["source"] = "bf16_cache"
                except (OSError, ValueError):
                    params_np = None
        if params_np is None:
            params_np = convert_hf_state(read_checkpoint_tensors(ckpt_dir),
                                         cfg)
            info["source"] = "hf_shards"
        if int8:
            params_np = quantize_params_host(params_np,
                                             compute_dtype=cfg.dtype)
        if use_cache:
            cache_f, meta_f = _converted_cache_paths(ckpt_dir, create=True,
                                                     variant=variant)
            try:
                # Tensors first, meta (the validity marker) last and
                # atomically — a kill mid-write can't leave a valid-looking
                # cache.
                write_safetensors(
                    cache_f + ".tmp",
                    _flatten_q8(params_np) if int8 else params_np)
                os.replace(cache_f + ".tmp", cache_f)
                tmp = meta_f + ".tmp"
                meta = {"fingerprint": _source_fingerprint(ckpt_dir),
                        "converter_version": _CONVERTER_VERSION}
                if int8:
                    meta.update(require)
                with open(tmp, "w") as f:
                    json.dump(meta, f)
                os.replace(tmp, meta_f)
            except OSError:
                # Unwritable/full disk: the cache is an optimization only —
                # but partial multi-GB files must not pin the disk space.
                # cache_f itself is dead weight too when the meta marker
                # write failed (nothing will ever validate it).
                for leftover in (cache_f + ".tmp", meta_f + ".tmp", cache_f):
                    try:
                        os.unlink(leftover)
                    except OSError:
                        pass
    def _materialize(v: np.ndarray) -> np.ndarray:
        # Memmap-backed tensors (the cached path) materialize to RAM first:
        # uploading straight from the memmap page-faults its way through
        # the transfer 4KB at a time instead of one sequential disk read.
        base = v
        while isinstance(base, np.ndarray):
            if isinstance(base, np.memmap):
                return np.array(v)
            base = base.base
        return v

    def _to_device(v):
        if isinstance(v, Q8):
            # int8 payload + f32 scale upload at their own widths — the
            # whole point of quantize-before-upload; never cast to
            # cfg.dtype.
            return Q8(q=jnp.asarray(_materialize(v.q)),
                      scale=jnp.asarray(_materialize(v.scale), jnp.float32))
        return jnp.asarray(_materialize(v), cfg.dtype)

    params = {k: _to_device(v) for k, v in params_np.items()}
    if mesh is not None:
        params = shard_params(params, cfg, mesh)
    if tokenizer == "byte":
        tokenizer = None  # explicit opt-in to the byte-level fallback
    elif tokenizer is None:
        # NEVER fall back to ByteTokenizer silently: byte ids against a
        # learned 32k+ vocab generate fluent-looking garbage with no error.
        try:
            tokenizer = HFTokenizerAdapter.from_dir(ckpt_dir, max_seq=max_seq)
        except Exception as e:
            raise ValueError(
                f"could not load a tokenizer from {ckpt_dir!r} ({e}); pass "
                "tokenizer=<object with encode/decode> or tokenizer='byte' "
                "to explicitly use the byte-level tokenizer") from e
    return LanguageModel(cfg, params, tokenizer=tokenizer)
