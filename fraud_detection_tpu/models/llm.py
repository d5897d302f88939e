"""On-pod explanation LLM: a TPU-native decoder-only transformer.

BASELINE.json config 5 asks for the DeepSeek HTTPS round-trip
(/root/reference/utils/agent_api.py:36,66) to be replaceable by a model served
from the same pod as the classifier. This module is that model: a standard
pre-norm decoder (RMSNorm / RoPE multi-head attention / SwiGLU), written as
pure-functional JAX over a params pytree so the same forward runs

  * single-chip (tests, small models) — long sequences dispatch to the
    Pallas flash-attention kernel (``ops/attention.py``: blockwise online
    softmax, O(T·d) memory, both matmuls on the MXU),
  * tensor-parallel over a mesh "model" axis — head-sharded attention and
    hidden-sharded MLP with GSPMD inserting the all-reduces (the Megatron
    column/row-parallel layout expressed as shardings, not explicit
    collectives), and
  * sequence-parallel for long transcripts via **ring attention**
    (``ring_attention``): each device holds a sequence shard, K/V blocks
    rotate around the ring with ``ppermute`` while a flash-style online
    softmax accumulates — exact attention, memory O(T/n) per chip, ICI
    traffic fully overlapped block math.

The byte-level tokenizer keeps the model self-contained (no vocab downloads,
zero egress); real pretrained weights convert into this exact pytree layout
via ``checkpoint/hf_convert.py`` (HF safetensors -> Params, incl. GQA/MQA,
untied heads, and Gemma's norm/scale/GeGLU quirks — verified against an
independent numpy forward in tests/test_hf_convert.py).
``LanguageModel.generate_text`` plugs into the explanation layer through
``explain.onpod.OnPodBackend.from_model`` /
``OnPodBackend.from_hf_checkpoint``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MODEL_AXIS = "model"
SEQ_AXIS = "seq"
DATA_AXIS = "data"  # batch axis on 2-D (data, seq) / (data, model) meshes

Params = Dict[str, jax.Array]


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 258          # 256 bytes + BOS + EOS
    d_model: int = 256
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 1024
    max_seq: int = 2048
    rope_theta: float = 10000.0
    dtype: jnp.dtype = jnp.float32  # bfloat16 on real TPU runs
    # --- pretrained-checkpoint surface (checkpoint/hf_convert.py) ---
    n_kv_heads: Optional[int] = None   # < n_heads = GQA; 1 = MQA (Gemma-2B)
    head_dim_override: Optional[int] = None  # Gemma: head_dim != D/H
    activation: str = "silu"           # "silu" | "gelu" (Gemma's GeGLU tanh)
    embed_scale: float = 1.0           # Gemma scales embeddings by sqrt(D)
    tie_embeddings: bool = True        # False = separate "lm_head" param
    rms_eps: float = 1e-6

    @property
    def head_dim(self) -> int:
        return (self.head_dim_override if self.head_dim_override is not None
                else self.d_model // self.n_heads)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    BOS: int = field(default=256, init=False)
    EOS: int = field(default=257, init=False)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    """Random-init parameter pytree. Layout (per layer l):
    wq (D, H, d), wk/wv (D, Hkv, d), wo (H, d, D), w_gate/w_up (D, F),
    w_down (F, D), ln1/ln2 (D,), plus embed (V, D) and ln_f (D,). The output
    head ties embed unless cfg.tie_embeddings=False adds "lm_head" (V, D)."""
    keys = jax.random.split(rng, cfg.n_layers * 7 + 2)
    scale = 1.0 / math.sqrt(cfg.d_model)
    p: Params = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model)) * scale
                  ).astype(cfg.dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(
            keys[-1], (cfg.vocab_size, cfg.d_model)) * scale).astype(cfg.dtype)
    h, hkv, d = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    for l in range(cfg.n_layers):
        k = keys[1 + l * 7 : 1 + (l + 1) * 7]
        p[f"l{l}.wq"] = (jax.random.normal(k[0], (cfg.d_model, h, d)) * scale).astype(cfg.dtype)
        p[f"l{l}.wk"] = (jax.random.normal(k[1], (cfg.d_model, hkv, d)) * scale).astype(cfg.dtype)
        p[f"l{l}.wv"] = (jax.random.normal(k[2], (cfg.d_model, hkv, d)) * scale).astype(cfg.dtype)
        p[f"l{l}.wo"] = (jax.random.normal(k[3], (h, d, cfg.d_model)) * scale).astype(cfg.dtype)
        p[f"l{l}.w_gate"] = (jax.random.normal(k[4], (cfg.d_model, cfg.d_ff)) * scale).astype(cfg.dtype)
        p[f"l{l}.w_up"] = (jax.random.normal(k[5], (cfg.d_model, cfg.d_ff)) * scale).astype(cfg.dtype)
        p[f"l{l}.w_down"] = (jax.random.normal(k[6], (cfg.d_ff, cfg.d_model)) * scale).astype(cfg.dtype)
        p[f"l{l}.ln1"] = jnp.ones(cfg.d_model, cfg.dtype)
        p[f"l{l}.ln2"] = jnp.ones(cfg.d_model, cfg.dtype)
    p["ln_f"] = jnp.ones(cfg.d_model, cfg.dtype)
    return p


def param_shardings(cfg: TransformerConfig, mesh: Mesh) -> Dict[str, NamedSharding]:
    """Megatron TP layout as shardings: attention sharded over heads, MLP over
    the hidden dim; norms/embeddings replicated. GSPMD derives the matching
    activation collectives (all-reduce after row-parallel wo / w_down)."""
    s: Dict[str, NamedSharding] = {}
    rep = NamedSharding(mesh, P())
    for name in ("embed", "ln_f"):
        s[name] = rep
    if not cfg.tie_embeddings:
        s["lm_head"] = rep
    # GQA: when the kv-head count doesn't divide over the model axis (MQA has
    # a single kv head), replicate k/v — the Megatron convention.
    kv_spec = (P(None, MODEL_AXIS, None)
               if cfg.kv_heads % mesh.shape[MODEL_AXIS] == 0 else P())
    for l in range(cfg.n_layers):
        s[f"l{l}.wq"] = NamedSharding(mesh, P(None, MODEL_AXIS, None))
        s[f"l{l}.wk"] = NamedSharding(mesh, kv_spec)
        s[f"l{l}.wv"] = NamedSharding(mesh, kv_spec)
        s[f"l{l}.wo"] = NamedSharding(mesh, P(MODEL_AXIS, None, None))
        s[f"l{l}.w_gate"] = NamedSharding(mesh, P(None, MODEL_AXIS))
        s[f"l{l}.w_up"] = NamedSharding(mesh, P(None, MODEL_AXIS))
        s[f"l{l}.w_down"] = NamedSharding(mesh, P(MODEL_AXIS, None))
        s[f"l{l}.ln1"] = rep
        s[f"l{l}.ln2"] = rep
    return s


def _scale_sharding(weight_sh: NamedSharding, scale_shape) -> NamedSharding:
    """Sharding for a Q8 scale: the weight's spec restricted to the dims the
    scale keeps. Scales carry singleton input dims (quantize_params reduces
    with keepdims), so only the weight's OUTPUT dims can be sharded — e.g.
    wq (D, H, d) @ P(None, model, None) gives its (1, H, d) scale
    P(None, model, None), while wo (H, d, D) @ P(model, None, None) gives
    its (1, 1, D) scale full replication."""
    spec = list(weight_sh.spec) + [None] * (len(scale_shape) - len(weight_sh.spec))
    restricted = tuple(None if scale_shape[i] == 1 else spec[i]
                       for i in range(len(scale_shape)))
    return NamedSharding(weight_sh.mesh, P(*restricted))


def shard_params(params: Params, cfg: TransformerConfig, mesh: Mesh) -> Params:
    """Place params (full-precision OR int8-quantized) on the mesh in the
    Megatron TP layout. Q8 leaves shard componentwise: q follows the
    weight's spec, the per-output-channel scale follows on its non-singleton
    dims (``_scale_sharding``) — quantize-then-shard and shard-then-quantize
    both land on this exact placement."""
    sh = param_shardings(cfg, mesh)
    out: Params = {}
    for k, v in params.items():
        if isinstance(v, Q8):
            out[k] = Q8(q=jax.device_put(v.q, sh[k]),
                        scale=jax.device_put(
                            v.scale, _scale_sharding(sh[k], v.scale.shape)))
        else:
            out[k] = jax.device_put(v, sh[k])
    return out


# ---------------------------------------------------------------------------
# int8 weight-only quantization (decode is weight-streaming bound: bf16
# decode on the 2B model measures ~81-83% of HBM peak at 256-token
# samples, so halving the weight bytes is the one lever that moves
# single-stream tokens/sec — measured 1.77x, 135.7 -> 240.7 tok/s)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass
class Q8:
    """Per-output-channel int8 weight: ``w ≈ q * scale``.

    ``scale`` keeps q's rank with singleton input dims. Consumers (``_mm``)
    feed ``q`` to the dot through a bare int8->dtype convert and apply the
    scale to the dot's OUTPUT — constant along every contracted dim, so the
    move is exact, and the HBM read stays int8-wide without relying on XLA
    to fuse an operand-side convert*scale chain."""

    q: jax.Array          # int8, the weight's shape
    scale: jax.Array      # f32, singleton along the weight's INPUT dims


#: weight name suffix -> axes reduced for the absmax (the INPUT dims).
_QUANT_REDUCE_AXES = {
    "wq": (0,), "wk": (0,), "wv": (0,),      # (D, h, d): in = D
    "wo": (0, 1),                            # (h, d, D): in = (h, d)
    "w_gate": (0,), "w_up": (0,),            # (D, F): in = D
    "w_down": (0,),                          # (F, D): in = F
    "embed": (1,), "lm_head": (1,),          # (V, D): per-row (gather + head)
}


def quantize_params(params: Params, *, include_embed: bool = True) -> Params:
    """bf16/f32 params -> weight-only int8 with per-output-channel scales.

    Norm gammas stay full precision (tiny, numerically load-bearing).
    ``include_embed=False`` keeps the embedding/output head unquantized
    (it is ~20% of Gemma-2B's bytes; quantizing it costs ~1/127-per-channel
    relative error on logits too, not just activations)."""
    out: Params = {}
    for name, w in params.items():
        suffix = name.rsplit(".", 1)[-1]
        axes = _QUANT_REDUCE_AXES.get(suffix)
        if axes is None or (suffix in ("embed", "lm_head") and not include_embed):
            out[name] = w
            continue
        # Sharded inputs quantize in place: the elementwise q keeps the
        # weight's sharding, and the keepdims absmax reduction lands the
        # scale exactly on _scale_sharding's layout (reduced input dims
        # become singletons; surviving output dims keep their spec) — GSPMD
        # inserts the cross-shard max where an input dim was sharded.
        wf = jnp.asarray(w).astype(jnp.float32)
        absmax = jnp.max(jnp.abs(wf), axis=axes, keepdims=True)
        scale = jnp.maximum(absmax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
        out[name] = Q8(q=q, scale=scale)
    return out


def quantize_params_host(params: dict, *, include_embed: bool = True,
                         compute_dtype=None) -> dict:
    """``quantize_params`` in host numpy, for quantize-BEFORE-upload loads.

    A cold model load reads and uploads every weight byte (5GB of bf16 at
    2B widths), so an int8 serving config wants the weights quantized on
    the host and HALF the bytes shipped —
    not a bf16 upload followed by on-device ``quantize_params``. Same
    contract as the device version (f32 math, keepdims absmax, round-half-
    even, ±127 clip; both numpy and XLA follow IEEE semantics for these
    ops), pinned by tests/test_llm.py's host-vs-device equality test.

    ``compute_dtype``: the model dtype an after-load ``quantize_params``
    would have seen — weights round-trip through it before quantizing, so
    an f32/f16 checkpoint loaded at bf16 quantizes the same rounded values
    on both paths (checkpoint dtype and model dtype differ routinely; both
    numpy/ml_dtypes and XLA cast round-to-nearest-even).

    Takes and returns numpy leaves ({name: ndarray | Q8-of-ndarray});
    callers upload with Q8-aware device placement (checkpoint/hf_convert.py)
    or ``shard_params``."""
    out: dict = {}
    for name, w in params.items():
        suffix = name.rsplit(".", 1)[-1]
        axes = _QUANT_REDUCE_AXES.get(suffix)
        if axes is None or (suffix in ("embed", "lm_head") and not include_embed):
            out[name] = w
            continue
        wf = np.asarray(w)
        if compute_dtype is not None:
            wf = wf.astype(np.dtype(compute_dtype))
        wf = wf.astype(np.float32)
        absmax = np.max(np.abs(wf), axis=axes, keepdims=True)
        scale = np.maximum(absmax, np.float32(1e-8)) / np.float32(127.0)
        q = np.clip(np.round(wf / scale), -127, 127).astype(np.int8)
        out[name] = Q8(q=q, scale=scale)
    return out


def _mm(sub: str, x: jax.Array, w, dtype) -> jax.Array:
    """Einsum against a possibly-quantized weight. An int8 weight enters the
    dot as a bare int8->dtype convert — the HBM read stays int8-wide — and
    its per-output-channel scale multiplies the dot's OUTPUT instead of the
    operand: mathematically identical (the scale is constant along every
    contracted dim), and it removes any reliance on XLA fusing a
    convert*scale*convert chain into the operand load (an operand-side
    dequant leaves a full-width scaled weight on the critical path whenever
    that fusion declines). Scales keep singleton input dims, so they
    broadcast directly against the output's trailing dims for every layer
    weight; the (V, 1) head layout is handled at the logits call site."""
    if isinstance(w, Q8):
        out = jnp.einsum(sub, x, w.q.astype(dtype))
        return (out * w.scale).astype(dtype)
    return jnp.einsum(sub, x, w)


def _embed_rows(emb, tokens: jax.Array, dtype) -> jax.Array:
    """Embedding gather, dequantizing only the gathered rows when int8."""
    if isinstance(emb, Q8):
        return (emb.q[tokens].astype(jnp.float32)
                * emb.scale[tokens]).astype(dtype)
    return emb[tokens].astype(dtype)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Plain RMSNorm. Gemma's (1 + w) convention is folded into gamma at
    checkpoint-conversion time (checkpoint/hf_convert.py), not special-cased
    here."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., T, H, d); positions: (..., T)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., T, d/2)
    cos = jnp.cos(angles)[..., :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[..., :, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def _attend(q, k, v, mask) -> jax.Array:
    """Plain masked attention. q: (B,T,H,d), k/v: (B,S,Hkv,d), H % Hkv == 0:
    query head h reads kv head h // rep (``jnp.repeat(kv, rep, axis=2)``'s
    order) by contracting against the NARROW k/v — no (B,S,H,d) copy is
    built; MHA is rep == 1, MQA one group. mask (T,S) shared across the
    batch or (B,T,S) per-row (batched decode with uneven prompt lengths)."""
    B, T, H, d = q.shape
    g = k.shape[2]
    with jax.named_scope("attn.scores"):
        # heads first: the scores leave the contraction as (B, g, rep, T, S),
        # which IS (B, H, T, S) — the small q is transposed, never they
        qh = q.reshape(B, T, g, H // g, d).transpose(0, 2, 3, 1, 4)
        scores = jnp.einsum("bgrtd,bsgd->bgrts", qh, k).reshape(B, H, T, -1)
        scores = scores.astype(jnp.float32) / math.sqrt(d)
        mask_b = mask[None] if mask.ndim == 2 else mask  # -> (B|1, T, S)
        scores = jnp.where(mask_b[:, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    with jax.named_scope("attn.values"):
        out = jnp.einsum("bgrts,bsgd->bgrtd",
                         probs.reshape(B, g, H // g, T, -1), v)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, d)


# Below this the materialized-score path is cheaper to compile and its
# O(T^2) scores are small; above it the blockwise paths keep memory bounded.
_FLASH_MIN_T = 512


def _chunked_key_pass(qf, q_pos, k_pad, v_pad, *, chunk: int, n_chunks: int,
                      base_pos, valid_len: int, far, carry, scale: float,
                      remat: bool):
    """Online-softmax accumulation over the key chunks of ONE padded block —
    the inner loop both the ring step and the single-device chunked path
    share (one copy of the sentinel/masking convention). ``base_pos`` is
    the block's global position offset; overhang keys (j >= valid_len) get
    the ``far`` sentinel the causal test rejects. With ``remat`` each
    chunk's probabilities are recomputed in backward instead of saved —
    without it, reverse-mode AD stores every (q, k)-chunk softmax block and
    the memory win evaporates exactly at long-context training sizes."""
    update = (jax.checkpoint(_online_softmax_update) if remat
              else _online_softmax_update)

    def body(c, inner):
        m, l, acc = inner
        k_c = jax.lax.dynamic_slice_in_dim(k_pad, c * chunk, chunk, 1)
        v_c = jax.lax.dynamic_slice_in_dim(v_pad, c * chunk, chunk, 1)
        j = c * chunk + jnp.arange(chunk)
        k_pos = jnp.where(j < valid_len, base_pos + j, far)
        return update(qf, k_c, v_c, q_pos, k_pos, m, l, acc, scale)

    return jax.lax.fori_loop(0, n_chunks, body, carry)


def chunked_causal_attention(q, k, v, q_chunk: int = 512,
                             key_chunk: int = 1024) -> jax.Array:
    """Memory-efficient causal attention in pure XLA: a static loop over
    query chunks, online softmax over key chunks — peak score memory
    O(q_chunk * key_chunk) per head instead of O(T^2), in backward too
    (chunk updates are rematerialized). Unlike the Pallas flash kernel this
    is reverse-differentiable and GSPMD-partitionable (plain einsums shard
    over heads under tensor parallelism), so it is the long-sequence path
    TRAINING and TP use. Each query chunk only visits key chunks at or
    below the diagonal (the loop bound is static per chunk), so no FLOPs
    go to fully-masked blocks. Ragged tails are handled like the ring's:
    padded keys carry a sentinel position; padded queries are sliced away.
    """
    B, T, H, d = q.shape
    scale = 1.0 / math.sqrt(d)
    qc = min(q_chunk, T)
    kc = min(key_chunk, T)
    n_q = -(-T // qc)
    n_k = -(-T // kc)
    q_pad = jnp.pad(q, ((0, 0), (0, n_q * qc - T), (0, 0), (0, 0)))
    k_pad = jnp.pad(k, ((0, 0), (0, n_k * kc - T), (0, 0), (0, 0)))
    v_pad = jnp.pad(v, ((0, 0), (0, n_k * kc - T), (0, 0), (0, 0)))
    far = T + 1  # sentinel: beyond every real query position

    outs = []
    for qi in range(n_q):  # static: per-chunk causal bounds, differentiable
        q_c = jax.lax.dynamic_slice_in_dim(q_pad, qi * qc, qc, 1)
        qf = q_c.astype(jnp.float32)
        q_pos = qi * qc + jnp.arange(qc)
        carry = (jnp.full((B, H, qc), -jnp.inf, jnp.float32),
                 jnp.zeros((B, H, qc), jnp.float32),
                 jnp.zeros((B, H, qc, d), jnp.float32))
        # key chunks entirely above the diagonal contribute nothing
        n_k_i = min(n_k, -(-(qi * qc + qc) // kc))
        _, l, acc = _chunked_key_pass(
            qf, q_pos, k_pad, v_pad, chunk=kc, n_chunks=n_k_i, base_pos=0,
            valid_len=T, far=far, carry=carry, scale=scale, remat=True)
        out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,H,qc,d)
        outs.append(out.transpose(0, 2, 1, 3))                # (B,qc,H,d)

    out = jnp.concatenate(outs, axis=1)
    return out[:, :T].astype(q.dtype)


def _expand_kv_heads(t: jax.Array, rep: int) -> jax.Array:
    """GQA/MQA kv -> full query-head width (HF repeat_kv semantics). The
    ONE expansion idiom, for the paths that need full width: ulysses (its
    all-to-all splits heads), the ring step, the chunked XLA path (flash
    backward included). ``_attend`` and the flash kernel read kv narrow."""
    if rep == 1:
        return t
    with jax.named_scope("attn.expand_kv"):
        return jnp.repeat(t, rep, axis=2)


@jax.custom_vjp
def _flash_attention_diff(q, k, v):
    """Flash forward with a differentiable backward: ``pallas_call`` defines
    no VJP, so the backward pass re-derives gradients through
    ``chunked_causal_attention`` (the exact same function, computed in
    bounded-memory XLA). External callers differentiating an auto-dispatched
    long-sequence ``forward()`` therefore get real gradients instead of an
    opaque Pallas AD error (round-2 advisor finding). k/v may be at their
    narrow GQA width (the kernel maps heads to groups; no expansion is
    materialized) — the backward expands inside the vjp, whose repeat
    transpose sums dk/dv over each group."""
    from fraud_detection_tpu.ops.attention import flash_attention
    from fraud_detection_tpu.utils.device import pallas_interpret

    return flash_attention(q, k, v, interpret=pallas_interpret())


def _flash_diff_fwd(q, k, v):
    return _flash_attention_diff(q, k, v), (q, k, v)


def _flash_diff_bwd(res, g):
    q, k, v = res
    rep = q.shape[2] // k.shape[2]

    def ref(q_, k_, v_):
        return chunked_causal_attention(q_, _expand_kv_heads(k_, rep),
                                        _expand_kv_heads(v_, rep))

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


_flash_attention_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def causal_attention(q, k, v, use_flash: Optional[bool] = None) -> jax.Array:
    """Full-sequence causal attention, dispatched by length and context:

    * short sequences — materialized scores (cheapest to compile);
    * long + ``use_flash`` allowed — the Pallas flash kernel
      (ops/attention.py), wrapped so its backward runs through the chunked
      XLA path (differentiable even under auto-dispatch);
    * long + ``use_flash=False`` (training, tensor parallelism) —
      ``chunked_causal_attention``: same bounded memory, one fused
      forward+backward program, and GSPMD shards its einsums over heads
      (``pallas_call`` has no partitioning rule, so the flash path would
      all-gather head-sharded activations).

    ``use_flash``: None = auto by length; model-axis-sharded callers must
    pass False.

    k/v may arrive at their narrow GQA/MQA width (fewer heads than q):
    the flash path and the short path (``_attend``) consume them natively;
    only the chunked path expands here. Every branch sees identical math."""
    long_seq = q.shape[1] >= _FLASH_MIN_T
    if use_flash is None:
        use_flash = long_seq
    if use_flash:
        return _flash_attention_diff(q, k, v)
    if long_seq:
        rep = q.shape[2] // k.shape[2]
        return chunked_causal_attention(q, _expand_kv_heads(k, rep),
                                        _expand_kv_heads(v, rep))
    causal = jnp.tril(jnp.ones((q.shape[1], q.shape[1]), bool))
    return _attend(q, k, v, causal)


# ---------------------------------------------------------------------------
# ring attention (sequence parallelism)
# ---------------------------------------------------------------------------

def _online_softmax_update(qf, k_part, v_part, q_pos, k_pos, m, l, acc,
                           scale: float):
    """One online-softmax accumulation against a slice of keys/values —
    the shared inner math of the ring step and its key-chunked variant."""
    scores = jnp.einsum("bthd,bshd->bhts", qf, k_part.astype(jnp.float32)) * scale
    causal = q_pos[:, None] >= k_pos[None, :]                # (T, S_part)
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    blk_max = jnp.max(scores, axis=-1)                       # (B,H,T)
    m_new = jnp.maximum(m, blk_max)
    # guard fully-masked rows (no valid key yet in this slice)
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(jnp.isneginf(scores), 0.0, p)
    correction = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = l * correction + jnp.sum(p, axis=-1)
    acc_new = (acc * correction[..., None]
               + jnp.einsum("bhts,bshd->bthd", p, v_part.astype(jnp.float32))
                 .transpose(0, 2, 1, 3))
    return m_new, l_new, acc_new


# Peak-memory knob for the ring step: scores materialize (B, H, T_loc,
# chunk) instead of (B, H, T_loc, T_loc) — without it a 4k-per-device shard
# costs 512MB of f32 scores per head-8 step, defeating the ring's O(T/n)
# memory promise on exactly the long-transcript workloads it exists for.
_RING_KEY_CHUNK = 2048


def _ring_attention_sharded(q, k, v, *, axis_name: str, blocks_per_ring: int,
                            scale: float, key_chunk: int = _RING_KEY_CHUNK,
                            batch_axis: Optional[str] = None):
    """Per-shard body (runs under shard_map): exact causal attention with K/V
    blocks rotating around the ring, flash-style online softmax; within a
    step, keys are processed in ``key_chunk`` slices so score memory stays
    O(T_loc * key_chunk).

    q: (B, T_loc, H, d) — this device's sequence shard; k/v may be at
    their NARROW GQA/MQA width (B, T_loc, Hkv, d): blocks transit the ring
    narrow — 1/rep of the ICI bytes per rotation (8x less for Gemma-2B's
    MQA) — and expand to query width only on arrival, for the local
    chunk attend. Device r owns global positions [r*T_loc, (r+1)*T_loc).
    """
    if key_chunk < 1:
        raise ValueError(f"key_chunk must be >= 1, got {key_chunk}")
    idx = jax.lax.axis_index(axis_name)
    B, T, H, d = q.shape
    rep = H // k.shape[2]
    qf = q.astype(jnp.float32)
    # Ceil-division chunking (T is static): the last chunk may overhang the
    # block; overhang keys are masked out via a sentinel position, so any
    # T_loc — prime lengths included — keeps chunk ~= key_chunk instead of
    # degrading to tiny divisors.
    chunk = min(T, key_chunk)
    n_chunks = -(-T // chunk)
    pad = n_chunks * chunk - T
    # Sentinel above every real global position: the causal mask rejects it.
    far = blocks_per_ring * T + 1

    def step(s, carry):
        k_blk, v_blk, m, l, acc = carry
        # after s rotations device idx holds the block produced by idx - s
        src = (idx - s) % blocks_per_ring
        q_pos = idx * T + jnp.arange(T)
        # Expand AFTER transit: the block rode the ring at narrow width.
        k_full = _expand_kv_heads(k_blk, rep)
        v_full = _expand_kv_heads(v_blk, rep)
        if n_chunks == 1:
            k_pos = src * T + jnp.arange(T)
            m, l, acc = _online_softmax_update(
                qf, k_full, v_full, q_pos, k_pos, m, l, acc, scale)
        else:
            k_pad = jnp.pad(k_full, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_pad = jnp.pad(v_full, ((0, 0), (0, pad), (0, 0), (0, 0)))
            m, l, acc = _chunked_key_pass(
                qf, q_pos, k_pad, v_pad, chunk=chunk, n_chunks=n_chunks,
                base_pos=src * T, valid_len=T, far=far, carry=(m, l, acc),
                scale=scale, remat=False)
        k_next = jax.lax.ppermute(
            k_blk, axis_name, [(i, (i + 1) % blocks_per_ring) for i in range(blocks_per_ring)])
        v_next = jax.lax.ppermute(
            v_blk, axis_name, [(i, (i + 1) % blocks_per_ring) for i in range(blocks_per_ring)])
        return k_next, v_next, m, l, acc

    # The accumulators become device-varying on the first iteration, so
    # their carry types must be marked varying over the ring axis up front.
    vary = (axis_name,) if batch_axis is None else (axis_name, batch_axis)
    mark = partial(jax.lax.pcast, axis_name=vary, to="varying")
    m0 = mark(jnp.full((B, H, T), -jnp.inf, jnp.float32))
    l0 = mark(jnp.zeros((B, H, T), jnp.float32))
    acc0 = mark(jnp.zeros((B, H, T, d), jnp.float32))
    _, _, m, l, acc = jax.lax.fori_loop(
        0, blocks_per_ring, step, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]             # (B,H,T,d)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)         # (B,T,H,d)


def _ulysses_sharded(q, k, v, *, axis_name: str, causal_mask):
    """Per-shard body: all-to-all heads<->sequence, local full attention,
    all-to-all back. q/k/v arrive (B, T/n, H, d); after the first collective
    each device holds ALL T positions for H/n heads."""
    def to_heads(x):   # (B, T/n, H, d) -> (B, T, H/n, d)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def to_seq(x):     # (B, T, H/n, d) -> (B, T/n, H, d)
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    attn = _attend(to_heads(q), to_heads(k), to_heads(v), causal_mask)
    return to_seq(attn)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                      axis_name: str = SEQ_AXIS,
                      batch_axis: Optional[str] = None) -> jax.Array:
    """All-to-all sequence parallelism (the Ulysses layout) — the second SP
    strategy next to ``ring_attention``. Two collectives per call re-shard
    heads<->sequence so every device runs plain full causal attention for
    its H/n head group over the WHOLE sequence: cheaper in ICI traffic than
    the ring's n-step rotation when heads divide evenly and the full (T, T)
    score block for H/n heads fits on a device; the ring (with key
    chunking) remains the memory-bounded choice for extreme T.

    q/k/v: (B, T, H, d) global; T and H must divide by the axis size.
    Narrow GQA/MQA k/v are accepted and expanded HERE: the head<->sequence
    all-to-all splits the head axis, which needs full query width (the
    ring, which never reshards heads, ships kv narrow instead).
    """
    n = mesh.shape[axis_name]
    B, T, H, d = q.shape
    k = _expand_kv_heads(k, H // k.shape[2])
    v = _expand_kv_heads(v, H // v.shape[2])
    if T % n or H % n:
        raise ValueError(
            f"ulysses_attention needs T ({T}) and H ({H}) divisible by the "
            f"'{axis_name}' axis size ({n}); use ring_attention otherwise")
    causal = jnp.tril(jnp.ones((T, T), bool))
    body = partial(_ulysses_sharded, axis_name=axis_name, causal_mask=causal)
    spec = P(batch_axis, axis_name, None, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   axis_name: str = SEQ_AXIS,
                   key_chunk: int = _RING_KEY_CHUNK,
                   batch_axis: Optional[str] = None) -> jax.Array:
    """Exact causal attention with the sequence sharded over ``axis_name``.

    q: (B, T, H, d) global; k/v may be at their narrow GQA/MQA width
    (B, T, Hkv, d) — they rotate the ring NARROW (1/rep of the ICI bytes;
    8x less for MQA) and expand per arrival. T must divide by the axis
    size. ``key_chunk`` bounds per-step score memory (see
    ``_RING_KEY_CHUNK``).
    ``batch_axis``: on a 2-D (data, seq) mesh, also shard the batch dim —
    without it the shard_map spec would silently REPLICATE the batch across
    the data axis (an all-gather of every dp-sharded activation).
    """
    n = mesh.shape[axis_name]
    scale = 1.0 / math.sqrt(q.shape[-1])
    body = partial(_ring_attention_sharded, axis_name=axis_name,
                   blocks_per_ring=n, scale=scale, key_chunk=key_chunk,
                   batch_axis=batch_axis)
    spec = P(batch_axis, axis_name, None, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def forward(params: Params, tokens: jax.Array, cfg: TransformerConfig,
            *, positions: Optional[jax.Array] = None,
            kv_cache: Optional[Dict[str, jax.Array]] = None,
            cache_len: Optional[jax.Array] = None,
            valid_from: Optional[jax.Array] = None,
            seq_mesh: Optional[Mesh] = None,
            sp_impl: str = "ring",
            use_flash: Optional[bool] = None,
            logits_last_only: bool = False) -> Tuple[jax.Array, Optional[Dict]]:
    """Logits for a token batch (B, T) -> (B, T, V).

    ``logits_last_only``: emit logits for the LAST position only —
    (B, 1, V). The decode prefill uses this: full-sequence logits cost
    B*T*V f32 (a 64-row batch of ~1000-token transcripts would materialize
    ~63GB and OOM the chip) and T times the output-head FLOPs, while
    sampling only ever reads position -1.

    Three modes:
      * full-sequence (kv_cache None, seq_mesh None): causal attention —
        the flash kernel for long sequences (``use_flash`` None = auto;
        pass False when params are model-axis sharded, see
        ``causal_attention``);
      * sequence-parallel (seq_mesh given): exact attention with T sharded
        over the mesh "seq" axis (prefill/scoring of long transcripts);
        ``sp_impl`` picks the strategy — "ring" (K/V rotation, memory-
        bounded) or "ulysses" (two all-to-alls, head-partitioned);
      * incremental (kv_cache given): T == 1 decode step against the cache;
        returns the updated cache. ``valid_from`` (B,) marks each row's
        first REAL cache slot — left-padded batched decode masks everything
        before it (uneven prompt lengths share one cache layout).
    """
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = _embed_rows(params["embed"], tokens, cfg.dtype)
    if cfg.embed_scale != 1.0:  # Gemma scales embeddings by sqrt(D)
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    new_cache: Optional[Dict[str, jax.Array]] = {} if kv_cache is not None else None
    act = jax.nn.silu if cfg.activation == "silu" else partial(
        jax.nn.gelu, approximate=True)

    for l in range(cfg.n_layers):
        h = rms_norm(x, params[f"l{l}.ln1"], cfg.rms_eps)
        q = _mm("btD,Dhd->bthd", h, params[f"l{l}.wq"], cfg.dtype)
        k = _mm("btD,Dhd->bthd", h, params[f"l{l}.wk"], cfg.dtype)
        v = _mm("btD,Dhd->bthd", h, params[f"l{l}.wv"], cfg.dtype)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

        if kv_cache is not None:
            # decode: append this step's k/v at cache_len, attend over prefix
            # (cache stays at Hkv width — _attend reads it as stored)
            ck = jax.lax.dynamic_update_slice(
                kv_cache[f"l{l}.k"], k, (0, cache_len, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                kv_cache[f"l{l}.v"], v, (0, cache_len, 0, 0))
            new_cache[f"l{l}.k"], new_cache[f"l{l}.v"] = ck, cv
            S = ck.shape[1]
            # causal within the appended block: row t sees keys <= cache_len+t
            valid = jnp.arange(S)[None, :] <= (cache_len + jnp.arange(T))[:, None]
            if valid_from is not None:  # (B,): left-pad slots are not real
                # Keep each query's OWN slot visible even in the pad region:
                # a fully-masked row softmaxes to NaN, and NaN values poison
                # later layers through 0-weighted (0 * NaN) attention sums.
                # Pad-query outputs are garbage-but-finite and never read.
                own = (jnp.arange(S)[None, :]
                       == (cache_len + jnp.arange(T))[:, None])  # (T, S)
                valid = ((valid[None]
                          & (jnp.arange(S)[None, None, :]
                             >= valid_from[:, None, None]))
                         | own[None])
            attn = _attend(q, ck, cv, valid)
        elif seq_mesh is not None:
            # On a (data, seq) training mesh the batch dim rides the data
            # axis through the SP body; a pure-seq serving mesh has none.
            # kv pass at native GQA width: the ring ships them narrow over
            # ICI (1/rep of the bytes per rotation) and expands on arrival;
            # ulysses expands at entry (its all-to-all splits heads).
            b_axis = DATA_AXIS if DATA_AXIS in seq_mesh.axis_names else None
            sp = (ulysses_attention if sp_impl == "ulysses"
                  else ring_attention)
            attn = sp(q, k, v, seq_mesh, batch_axis=b_axis)
        else:
            # kv at native GQA width: only the chunked branch expands it
            attn = causal_attention(q, k, v, use_flash)

        x = x + _mm("bthd,hdD->btD", attn, params[f"l{l}.wo"], cfg.dtype)
        h2 = rms_norm(x, params[f"l{l}.ln2"], cfg.rms_eps)
        gate = act(_mm("btD,DF->btF", h2, params[f"l{l}.w_gate"], cfg.dtype))
        up = _mm("btD,DF->btF", h2, params[f"l{l}.w_up"], cfg.dtype)
        x = x + _mm("btF,FD->btD", gate * up, params[f"l{l}.w_down"], cfg.dtype)

    x = rms_norm(x, params["ln_f"], cfg.rms_eps)
    if logits_last_only:
        x = x[:, -1:]
    head = params["lm_head"] if not cfg.tie_embeddings else params["embed"]
    if isinstance(head, Q8):
        # (V, 1) per-row scale applied to the f32 logits, same output-side
        # move as _mm — the int8 head streams at int8 width.
        logits = (jnp.einsum("btD,VD->btV", x, head.q.astype(cfg.dtype))
                  .astype(jnp.float32) * head.scale[:, 0])
    else:
        logits = jnp.einsum("btD,VD->btV", x, head).astype(jnp.float32)
    return logits, new_cache


def init_cache(cfg: TransformerConfig, batch: int, max_len: int) -> Dict[str, jax.Array]:
    return {f"l{l}.{t}": jnp.zeros((batch, max_len, cfg.kv_heads, cfg.head_dim), cfg.dtype)
            for l in range(cfg.n_layers) for t in ("k", "v")}


# ---------------------------------------------------------------------------
# slot decode (continuous batching: explain/slotserve/)
#
# The fixed-batch decode below (`_generate_batch_jit`) runs B prompts behind
# ONE barrier: every row pays device steps until the SLOWEST row finishes,
# and a new request waits for the whole batch to drain. These two functions
# are the iteration-level alternative (Orca, OSDI '22): one PERSISTENT
# (slots, S, Hkv, d) KV pool where each row owns a slot, a prompt prefills
# into a free slot at any iteration boundary, and one decode step advances
# every busy slot — per-slot lengths, per-slot retirement, no barrier. The
# host-side slot/queue management lives in explain/slotserve/; these are the
# only device programs it runs (exactly one decode compile for the pool, one
# prefill compile per prompt bucket).
# ---------------------------------------------------------------------------


# The slot programs name their parts for the profiler (``jax.named_scope``:
# op metadata only, no op or number changes): a device op of a capture then
# says which part of the layer it belongs to, whatever its shape. The names
# are listed in docs/observability.md.


def _logits_head(x: jax.Array, params: Params, cfg: TransformerConfig) -> jax.Array:
    """Output-head logits for (N, D) features — the Q8 per-row-scale move
    `forward` applies, shared by the slot prefill/decode entries."""
    head = params["lm_head"] if not cfg.tie_embeddings else params["embed"]
    with jax.named_scope("lm_head"):
        if isinstance(head, Q8):
            return (jnp.einsum("nD,VD->nV", x, head.q.astype(cfg.dtype))
                    .astype(jnp.float32) * head.scale[:, 0])
        return jnp.einsum("nD,VD->nV", x, head).astype(jnp.float32)


def _qkv(params: Params, cfg: TransformerConfig, l: int, h: jax.Array,
         positions: jax.Array):
    """Layer ``l``'s q/k/v projections with rotary positions applied."""
    with jax.named_scope("attn.qkv"):
        q = _mm("btD,Dhd->bthd", h, params[f"l{l}.wq"], cfg.dtype)
        k = _mm("btD,Dhd->bthd", h, params[f"l{l}.wk"], cfg.dtype)
        v = _mm("btD,Dhd->bthd", h, params[f"l{l}.wv"], cfg.dtype)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn_out_mlp(params: Params, cfg: TransformerConfig, l: int,
                  x: jax.Array, attn: jax.Array, act) -> jax.Array:
    """Layer ``l`` after attention: output projection, then the gated MLP,
    each on its residual."""
    with jax.named_scope("attn.out"):
        x = x + _mm("bthd,hdD->btD", attn, params[f"l{l}.wo"], cfg.dtype)
    with jax.named_scope("mlp"):
        h2 = rms_norm(x, params[f"l{l}.ln2"], cfg.rms_eps)
        gate = act(_mm("btD,DF->btF", h2, params[f"l{l}.w_gate"], cfg.dtype))
        up = _mm("btD,DF->btF", h2, params[f"l{l}.w_up"], cfg.dtype)
        return x + _mm("btF,FD->btD", gate * up, params[f"l{l}.w_down"],
                       cfg.dtype)


@partial(jax.jit, static_argnames=("cfg",))
def slot_prefill(params: Params, tokens: jax.Array, length: jax.Array,
                 cfg: TransformerConfig, kv_cache: Dict[str, jax.Array],
                 slot: jax.Array, temperature: jax.Array,
                 rng: jax.Array):
    """Prefill ONE prompt into row ``slot`` of a pooled slot cache.

    ``tokens``: (1, Tp) RIGHT-padded (Tp is the prompt bucket — compile
    count is bounded by the bucket ladder, and ``slot``/``length`` are
    traced so admitting into any slot reuses the same program).
    Padding-region k/v DO land in cache rows [length, Tp) — they are
    garbage, but every later read masks to [0, len] and decode overwrites
    them in order, so they are never attended. Returns
    ``(first_token scalar int32, new_cache)`` — the first sampled token is
    part of the row's output (same convention as ``_generate_batch_jit``:
    sample from the prefill logits, then feed tokens back one step at a
    time)."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = _embed_rows(params["embed"], tokens, cfg.dtype)
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    act = jax.nn.silu if cfg.activation == "silu" else partial(
        jax.nn.gelu, approximate=True)
    new_cache: Dict[str, jax.Array] = {}
    for l in range(cfg.n_layers):
        h = rms_norm(x, params[f"l{l}.ln1"], cfg.rms_eps)
        q, k, v = _qkv(params, cfg, l, h, positions)
        # Write this prompt's k/v into the slot's cache rows. Right-padded
        # overhang is masked by length everywhere downstream.
        new_cache[f"l{l}.k"] = jax.lax.dynamic_update_slice(
            kv_cache[f"l{l}.k"], k, (slot, 0, 0, 0))
        new_cache[f"l{l}.v"] = jax.lax.dynamic_update_slice(
            kv_cache[f"l{l}.v"], v, (slot, 0, 0, 0))
        # Causal attention over the prompt itself (padded queries attend
        # real+pad keys at or below their position — garbage-but-finite,
        # and only the length-1 position is ever read).
        attn = causal_attention(q, k, v, use_flash=False)
        x = _attn_out_mlp(params, cfg, l, x, attn, act)
    x = rms_norm(x, params["ln_f"], cfg.rms_eps)
    # Logits at the LAST REAL position only (length-1; right padding means
    # it is not at Tp-1) — full (Tp, V) logits would pay T times the head.
    x_last = jax.lax.dynamic_slice_in_dim(x[0], length - 1, 1, 0)  # (1, D)
    logits = _logits_head(x_last, params, cfg)                     # (1, V)
    tok = _sample_token(temperature, logits, rng)
    return tok[0], new_cache


def _slot_step_math(params: Params, cfg: TransformerConfig,
                    kv_cache: Dict[str, jax.Array], tokens: jax.Array,
                    lens: jax.Array, temperature: jax.Array,
                    step_key: jax.Array) -> Tuple[jax.Array, Dict]:
    """The shared single-step math of the slot pool: feed (B,) tokens,
    scatter their k/v at per-slot index ``lens[b]``, attend each row over
    its own prefix [0, lens[b]], sample (B,) next tokens (per-slot
    temperature: greedy rows argmax, sampled rows draw from
    (key, row) — a slot's stream never depends on its neighbors)."""
    B = tokens.shape[0]
    positions = lens[:, None]                                   # (B, 1)
    x = _embed_rows(params["embed"], tokens[:, None], cfg.dtype)
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    act = jax.nn.silu if cfg.activation == "silu" else partial(
        jax.nn.gelu, approximate=True)
    rows = jnp.arange(B)
    new_cache: Dict[str, jax.Array] = {}
    for l in range(cfg.n_layers):
        h = rms_norm(x, params[f"l{l}.ln1"], cfg.rms_eps)
        q, k, v = _qkv(params, cfg, l, h, positions)
        # Per-slot append: row b writes at its own lens[b] (a scatter —
        # the whole point of slots is rows sitting at different lengths).
        with jax.named_scope("kv.append"):
            ck = kv_cache[f"l{l}.k"].at[rows, lens].set(k[:, 0])
            cv = kv_cache[f"l{l}.v"].at[rows, lens].set(v[:, 0])
        new_cache[f"l{l}.k"], new_cache[f"l{l}.v"] = ck, cv
        S = ck.shape[1]
        # Row b attends its own prefix [0, lens[b]] (the appended token's
        # own slot included — never a fully-masked row, so no NaN).
        valid = (jnp.arange(S)[None, None, :]
                 <= lens[:, None, None])                        # (B, 1, S)
        attn = _attend(q, ck, cv, valid)
        x = _attn_out_mlp(params, cfg, l, x, attn, act)
    x = rms_norm(x, params["ln_f"], cfg.rms_eps)[:, 0]          # (B, D)
    logits = _logits_head(x, params, cfg)                       # (B, V)
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, -1)
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        row_keys = jax.vmap(partial(jax.random.fold_in, step_key))(rows)
        drawn = jax.vmap(lambda k_, lg: jax.random.categorical(k_, lg, -1))(
            row_keys, scaled)
        tok = jnp.where(temperature <= 1e-6, greedy,
                        drawn).astype(jnp.int32)
    return tok, new_cache


def _slot_window_loop(params: Params, tokens: jax.Array, lens: jax.Array,
                      active: jax.Array, remaining: jax.Array,
                      cfg: TransformerConfig,
                      kv_cache: Dict[str, jax.Array],
                      temperature: jax.Array, rng: jax.Array,
                      steps: int):
    """The fused multi-step decode loop over a (B, S, Hkv, d) cache layout —
    shared VERBATIM by the contiguous pool (`slot_decode_window`) and the
    paged pool (`paged_decode_window`, which gathers its pages into exactly
    this layout first). One body means the two paths are bit-equal by
    construction, not by test luck."""
    B = tokens.shape[0]
    out0 = jnp.full((B, steps), cfg.EOS, jnp.int32)

    def cond(carry):
        i, _, _, act, _, _, _, _ = carry
        return (i < steps) & jnp.any(act)

    def body(carry):
        i, last, lens_c, act_c, rem, cache, out, n_act = carry
        tok, cache = _slot_step_math(params, cfg, cache, last, lens_c,
                                     temperature,
                                     jax.random.fold_in(rng, i))
        # Rows active this step wrote their fed token's k/v at lens.
        lens_c = lens_c + act_c.astype(jnp.int32)
        n_act = n_act + jnp.sum(act_c.astype(jnp.int32))
        tok = jnp.where(act_c, tok, jnp.int32(cfg.EOS))
        out = jax.lax.dynamic_update_slice(out, tok[:, None], (0, i))
        rem = rem - act_c.astype(jnp.int32)
        act_c = act_c & (tok != cfg.EOS) & (rem > 0)
        return i + 1, tok, lens_c, act_c, rem, cache, out, n_act

    carry = (jnp.int32(0), tokens, lens, active, remaining, kv_cache, out0,
             jnp.int32(0))
    i, _, new_lens, _, _, new_cache, out, n_act = jax.lax.while_loop(
        cond, body, carry)
    return out, new_lens, i, n_act, new_cache


@partial(jax.jit, static_argnames=("cfg", "steps"))
def slot_decode_window(params: Params, tokens: jax.Array, lens: jax.Array,
                       active: jax.Array, remaining: jax.Array,
                       cfg: TransformerConfig,
                       kv_cache: Dict[str, jax.Array],
                       temperature: jax.Array, rng: jax.Array,
                       steps: int):
    """Up to ``steps`` fused decode iterations for the WHOLE slot pool —
    iteration-level scheduling with the per-token dispatch amortized
    (multi-step scheduling: admissions land at window boundaries, which
    is the continuous-batching granularity knob).

    ``tokens``: (B,) last sampled token per slot (written this window);
    ``lens``: (B,) valid cache length per slot; ``active``: (B,) bool —
    inactive slots compute garbage into index ``lens[b]`` (free slots
    keep lens 0) which the next prefill overwrites, and always emit EOS;
    ``remaining``: (B,) per-slot token budget left. A row that samples
    EOS or exhausts its budget FREEZES for the rest of the window (emits
    EOS, writes nothing further) — exactly the `_generate_batch_jit`
    freeze rule — and the loop exits early once every row froze.

    Returns ``(out (B, steps) EOS-padded, new_lens, steps_run,
    active_row_steps, new_cache)``; the host appends each row's tokens
    column-by-column under the same freeze rule, so host and device agree
    bit-for-bit, and steps_run/active_row_steps feed the occupancy
    accounting."""
    return _slot_window_loop(params, tokens, lens, active, remaining, cfg,
                             kv_cache, temperature, rng, steps)


# ---------------------------------------------------------------------------
# paged slot decode (PagedAttention-style KV pool: explain/slotserve/)
#
# The pooled cache above still reserves a worst-case (slots, S, Hkv, d)
# region per slot. The paged layout below replaces it with a flat pool of
# fixed-size KV blocks — per layer/tensor (num_pages, page, Hkv, d) — plus a
# per-slot PAGE TABLE of page ids. Device programs see only gathers and
# scatters by page id (no data-dependent shapes; table shapes are static),
# and the page tables themselves mutate on the HOST side of the iteration
# boundary, so the compiled programs stay shape-stable across any
# allocation pattern. Shared-prefix caching falls out of the indirection:
# several tables may point at the same refcounted read-only pages holding
# the explain template's preamble k/v, prefilled once (PagedAttention /
# RadixAttention, applied to the slot pool). Allocation policy — refcounts,
# copy-on-write, exhaustion preemption — lives with the host-side allocator
# in explain/slotserve/decode.py; nothing here allocates.
# ---------------------------------------------------------------------------


def init_kv_pages(cfg: TransformerConfig, num_pages: int,
                  page_size: int) -> Dict[str, jax.Array]:
    """The paged twin of ``init_cache``: a flat block pool per layer/tensor.
    Page ids index the leading axis; a slot's logical position p lives at
    ``(table[p // page_size], p % page_size)``."""
    return {f"l{l}.{t}": jnp.zeros(
                (num_pages, page_size, cfg.kv_heads, cfg.head_dim), cfg.dtype)
            for l in range(cfg.n_layers) for t in ("k", "v")}


@partial(jax.jit, donate_argnums=(0,))
def copy_kv_page(kv_pages: Dict[str, jax.Array], src: jax.Array,
                 dst: jax.Array) -> Dict[str, jax.Array]:
    """Copy-on-write device copy: page ``src`` -> page ``dst`` across every
    layer/tensor. Traced page ids — one compile covers every COW."""
    return {name: arr.at[dst].set(arr[src]) for name, arr in kv_pages.items()}


def _gather_view(kv_pages: Dict[str, jax.Array],
                 tables: jax.Array) -> Dict[str, jax.Array]:
    """Materialize the contiguous-layout view of ``tables`` (B, n_view):
    (B, n_view*page, Hkv, d) per layer/tensor. Unallocated table slots hold
    filler id 0 — their gathered content is stale pool data, which the
    decode/prefill masks (never attended) and the scatter-back never
    targets (write positions are always table-covered by the allocator)."""
    out = {}
    with jax.named_scope("kv.gather_pages"):
        for name, arr in kv_pages.items():
            num_pages, page, hkv, d = arr.shape
            g = arr[tables]                              # (B, n_view, P, ...)
            out[name] = g.reshape(tables.shape[0], tables.shape[1] * page,
                                  hkv, d)
    return out


@partial(jax.jit, static_argnames=("cfg", "prefix_len"))
def paged_slot_prefill(params: Params, tokens: jax.Array, length: jax.Array,
                       cfg: TransformerConfig,
                       kv_pages: Dict[str, jax.Array], table_row: jax.Array,
                       temperature: jax.Array, rng: jax.Array,
                       prefix_len: int):
    """Prefill ONE prompt suffix into the pages of ``table_row``.

    ``tokens``: (1, Ts) RIGHT-padded suffix — with shared-prefix caching the
    first ``prefix_len`` positions of the row are already resident (read-only
    preamble pages every table points at), so only the transcript suffix is
    computed; ``prefix_len == 0`` is the plain no-sharing path. ``length`` is
    the FULL prompt length (prefix + real suffix), matching the contiguous
    ``slot_prefill`` convention so the sampled-token position is identical.

    ``table_row``: (n_view,) page ids covering at least
    ``prefix_len + Ts`` positions. Suffix k/v scatter into the row's own
    pages; the prefix region is only gathered (COW in the allocator
    guarantees a table never points a WRITE position at a shared page).
    ``prefix_len`` is static: one shared preamble per service -> one
    compile per suffix bucket, same bound as the contiguous ladder.

    Bit-equality with ``slot_prefill``: suffix activations are position-
    wise identical; attention reads [cached prefix k/v ; this suffix's
    k/v] under the same causal mask (row j attends positions <=
    prefix_len + j), and the masked tail pads with exact zeros — the
    zero-pad width invariance the slot tests pin."""
    B, Ts = tokens.shape
    page = next(iter(kv_pages.values())).shape[1]
    positions = jnp.broadcast_to(prefix_len + jnp.arange(Ts), (B, Ts))
    x = _embed_rows(params["embed"], tokens, cfg.dtype)
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    act = jax.nn.silu if cfg.activation == "silu" else partial(
        jax.nn.gelu, approximate=True)
    # Static per-suffix-position page/offset mapping: position prefix_len+j
    # lives at (table_row[(prefix_len+j)//page], (prefix_len+j)%page).
    pos = prefix_len + jnp.arange(Ts)
    pids = table_row[pos // page]                        # (Ts,) traced ids
    offs = pos % page
    # Row j attends every resident position at or below its own.
    kv_mask = (jnp.arange(table_row.shape[0] * page)[None, :]
               <= pos[:, None])                          # (Ts, Tkv)
    new_pages: Dict[str, jax.Array] = dict(kv_pages)
    for l in range(cfg.n_layers):
        h = rms_norm(x, params[f"l{l}.ln1"], cfg.rms_eps)
        q, k, v = _qkv(params, cfg, l, h, positions)
        # Scatter the suffix k/v into the row's own pages (pad-region
        # overhang included — garbage-but-private, masked downstream and
        # overwritten in order by decode, same as the contiguous path).
        with jax.named_scope("kv.scatter_pages"):
            pk = new_pages[f"l{l}.k"].at[pids, offs].set(k[0])
            pv = new_pages[f"l{l}.v"].at[pids, offs].set(v[0])
        new_pages[f"l{l}.k"], new_pages[f"l{l}.v"] = pk, pv
        # Gather the row's resident view: prefix pages + the suffix just
        # written. (B=1: table_row[None] is the one-row table.)
        view = _gather_view({"k": pk, "v": pv}, table_row[None])
        attn = _attend(q, view["k"], view["v"], kv_mask)
        x = _attn_out_mlp(params, cfg, l, x, attn, act)
    x = rms_norm(x, params["ln_f"], cfg.rms_eps)
    # Logits at the last REAL position, suffix-local index length-1-prefix.
    x_last = jax.lax.dynamic_slice_in_dim(
        x[0], length - 1 - prefix_len, 1, 0)                       # (1, D)
    logits = _logits_head(x_last, params, cfg)                     # (1, V)
    tok = _sample_token(temperature, logits, rng)
    return tok[0], new_pages


@partial(jax.jit, static_argnames=("cfg", "steps", "view_len"))
def paged_decode_window(params: Params, tokens: jax.Array, lens: jax.Array,
                        active: jax.Array, remaining: jax.Array,
                        cfg: TransformerConfig,
                        kv_pages: Dict[str, jax.Array], tables: jax.Array,
                        temperature: jax.Array, rng: jax.Array,
                        steps: int, view_len: int):
    """`slot_decode_window` over the paged pool: gather every slot's pages
    into the contiguous (B, view_len, Hkv, d) layout, run the IDENTICAL
    fused window loop (``_slot_window_loop``), then scatter each row's
    newly written positions [lens, new_lens) back to its pages.

    ``view_len`` is the contiguous pool's max_len: the gathered view is
    SLICED to it (the last page may overhang when max_len is not
    page-aligned), so the window loop runs at exactly the contiguous
    attention width — bit-equal by construction, not by reduction-order
    luck.

    ``tables``: (B, n_view) page ids; the allocator guarantees every active
    row's table covers [0, lens + steps) before the call, so scatter-back
    positions are always table-resident. Frozen/inactive rows write
    in-window garbage at their frozen ``lens`` exactly like the contiguous
    path — it is NOT scattered back (the next admit/step overwrites it
    before any attend, so dropping it preserves bit-equality)."""
    B = tokens.shape[0]
    page = next(iter(kv_pages.values())).shape[1]
    n_view = tables.shape[1]
    num_pages = next(iter(kv_pages.values())).shape[0]
    if not 0 < view_len <= n_view * page:
        raise ValueError(f"view_len {view_len} outside (0, "
                         f"{n_view * page}]")
    view = {name: arr[:, :view_len]
            for name, arr in _gather_view(kv_pages, tables).items()}
    out, new_lens, i, n_act, new_view = _slot_window_loop(
        params, tokens, lens, active, remaining, cfg, view, temperature,
        rng, steps)
    # Scatter-back: row b wrote view positions [lens[b], new_lens[b]).
    rows = jnp.arange(B)
    pos = lens[:, None] + jnp.arange(steps)[None, :]               # (B, W)
    valid = pos < new_lens[:, None]
    pidx = jnp.minimum(pos // page, n_view - 1)
    pids = jnp.take_along_axis(tables, pidx, axis=1)
    # Invalid entries get an out-of-range page id: JAX scatter DROPS
    # out-of-bounds writes, so masked positions never touch the pool.
    pids = jnp.where(valid, pids, num_pages)
    offs = pos % page
    pos_c = jnp.minimum(pos, view_len - 1)
    new_pages: Dict[str, jax.Array] = {}
    with jax.named_scope("kv.scatter_pages"):
        for name, arr in kv_pages.items():
            vals = new_view[name][rows[:, None], pos_c]    # (B, W, Hkv, d)
            new_pages[name] = arr.at[pids, offs].set(vals)
    return out, new_lens, i, n_act, new_pages


# ---------------------------------------------------------------------------
# generation
# ---------------------------------------------------------------------------

def _sample_token(temperature, logits_1, step_key):
    """Greedy below the temperature epsilon, categorical above — the ONE
    sampling rule the decode path uses. Each row draws from its own key,
    ``fold_in(step_key, row)``, so a row's sample depends only on
    (seed, step, row) — NOT on how many prompts are co-batched (batch-size
    bucketing pads B; a (B, V)-shaped draw would change with the padding)."""
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits_1, -1)
        scaled = logits_1 / jnp.maximum(temperature, 1e-6)
        row_keys = jax.vmap(partial(jax.random.fold_in, step_key))(
            jnp.arange(logits_1.shape[0]))
        drawn = jax.vmap(lambda k, lg: jax.random.categorical(k, lg, -1))(
            row_keys, scaled)
        return jnp.where(temperature <= 1e-6, greedy,
                         drawn).astype(jnp.int32)


@partial(jax.jit, static_argnames=("cfg", "max_new"))
def _generate_batch_jit(params: Params, prompt: jax.Array, prompt_len: jax.Array,
                        row_real: jax.Array, cfg: TransformerConfig,
                        max_new: int, temperature: jax.Array, rng: jax.Array):
    """Batched decode for UNEVEN prompt lengths. prompt: (B, Tp) LEFT-padded
    so every row's last real token sits at Tp-1 — all rows then share one
    scalar write position per step, while ``valid_from`` masks each row's
    left-pad slots out of attention and RoPE positions stay per-row real
    (negative on pads, which the mask discards). Returns (B, max_new).
    Row b's greedy output matches the B=1 path on the same prompt —
    tests/test_llm.py::test_batched_generation_matches_single."""
    B, Tp = prompt.shape
    max_len = Tp + max_new
    cache = init_cache(cfg, B, max_len)
    valid_from = Tp - prompt_len                               # (B,)
    positions = jnp.arange(Tp)[None, :] - valid_from[:, None]  # real idx; <0 on pads
    logits, cache = forward(params, prompt, cfg, positions=positions,
                            kv_cache=cache, cache_len=jnp.int32(0),
                            valid_from=valid_from, logits_last_only=True)
    last = logits[:, -1]                                       # every row ends at Tp-1
    sample = partial(_sample_token, temperature)
    out0 = jnp.full((B, max_new), cfg.EOS, jnp.int32)

    # while_loop, not scan: once every row has emitted EOS the loop exits —
    # short answers stop paying per-step forwards (unemitted slots stay EOS,
    # which the tokenizers already treat as end-of-text).
    def cond(carry):
        _, _, i, done, _ = carry
        return (i < max_new) & ~jnp.all(done)

    def body(carry):
        cache, last_logits, i, done, out = carry
        # Per-step key derived by counter from the closed-over rng, per-row
        # keys inside _sample_token: output stream for row r is a pure
        # function of (seed, step, r).
        sub = jax.random.fold_in(rng, i)
        tok = sample(last_logits, sub)                         # (B,)
        tok = jnp.where(done, cfg.EOS, tok)                    # freeze done rows
        out = jax.lax.dynamic_update_slice(out, tok[:, None], (0, i))
        done = done | (tok == cfg.EOS)
        pos = prompt_len + i                                   # (B,) real position
        logits, cache = forward(params, tok[:, None], cfg,
                                positions=pos[:, None],
                                kv_cache=cache, cache_len=Tp + i,
                                valid_from=valid_from)
        return cache, logits[:, 0], i + 1, done, out

    # Batch-bucketing dummy rows start DONE — waiting on a garbage row that
    # may never sample EOS would defeat the early exit for every batch whose
    # real size isn't a power of two.
    carry = (cache, last, jnp.int32(0), ~row_real, out0)
    *_, out = jax.lax.while_loop(cond, body, carry)
    return out  # (B, max_new); rows past their EOS hold EOS


class ByteTokenizer:
    """Self-contained byte-level tokenizer (no external vocab)."""

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg

    def encode(self, text: str) -> np.ndarray:
        data = text.encode("utf-8")[: self.cfg.max_seq - 2]
        return np.asarray([self.cfg.BOS] + list(data), np.int32)

    def decode(self, tokens) -> str:
        out = bytearray()
        for t in np.asarray(tokens).tolist():
            if t == self.cfg.EOS:
                break
            if 0 <= t < 256:
                out.append(t)
        return out.decode("utf-8", "replace")


@dataclass
class LanguageModel:
    """Params + config + tokenizer behind a text-in/text-out API."""

    cfg: TransformerConfig
    params: Params
    tokenizer: ByteTokenizer = None

    def __post_init__(self):
        if self.tokenizer is None:
            self.tokenizer = ByteTokenizer(self.cfg)

    @classmethod
    def init_random(cls, cfg: Optional[TransformerConfig] = None, seed: int = 0,
                    mesh: Optional[Mesh] = None) -> "LanguageModel":
        cfg = cfg or TransformerConfig()
        params = init_params(jax.random.PRNGKey(seed), cfg)
        if mesh is not None:
            params = shard_params(params, cfg, mesh)
        return cls(cfg, params)

    def quantized(self, *, include_embed: bool = True) -> "LanguageModel":
        """Weight-only int8 copy (see ``quantize_params``): same API, same
        KV cache, ~half the weight bytes per decode step."""
        return LanguageModel(self.cfg,
                             quantize_params(self.params,
                                             include_embed=include_embed),
                             tokenizer=self.tokenizer)

    def generate_tokens(self, prompt_tokens: np.ndarray, *, max_new_tokens: int = 64,
                        temperature: float = 0.0, seed: int = 0) -> np.ndarray:
        """Single-prompt decode — the B=1 case of ``generate_tokens_batch``
        (one decode program to maintain; the batch path's left-pad masking
        degenerates to a no-op at B=1)."""
        return self.generate_tokens_batch(
            [np.asarray(prompt_tokens)], max_new_tokens=max_new_tokens,
            temperature=temperature, seed=seed)[0]

    def generate_tokens_batch(self, prompts, *, max_new_tokens: int = 64,
                              temperature: float = 0.0,
                              seed: int = 0) -> np.ndarray:
        """Decode a batch of UNEVEN-length prompts in one device program
        (one prefill + one early-exit decode loop — a single dispatch and
        fetch for the whole batch). Prompts are left-padded to a shared bucket; per-row validity
        masking keeps each row's context exactly its own prompt. Sampling is
        batch-composition invariant: row r's tokens depend only on
        (seed, step, r), not on how many prompts are co-batched. Returns
        (B, max_new_tokens)."""
        n = len(prompts)
        if n == 0:
            return np.zeros((0, max_new_tokens), np.int32)
        # Bucket BOTH dims: prompt length to a multiple of 8 and batch size
        # to a power of two (dummy rows, sliced away) — a live stream's
        # per-batch valid-row count jitters, and each distinct (B, Tp) would
        # otherwise recompile the whole decode scan.
        b_pad = 1 << (n - 1).bit_length()
        lens_list = [len(p) for p in prompts] + [1] * (b_pad - n)
        lens = np.asarray(lens_list, np.int32)
        pad = 8 * ((int(lens.max()) + 7) // 8)
        prompt = np.zeros((b_pad, pad), np.int32)
        for i, p in enumerate(prompts):
            prompt[i, pad - len(p):] = p        # LEFT-padded
        row_real = np.arange(b_pad) < n
        toks = _generate_batch_jit(self.params, jnp.asarray(prompt),
                                   jnp.asarray(lens), jnp.asarray(row_real),
                                   self.cfg, int(max_new_tokens),
                                   jnp.float32(temperature),
                                   jax.random.PRNGKey(seed))
        return np.asarray(toks)[:n]

    def generate_text(self, prompt: str, *, temperature: float = 0.0,
                      max_new_tokens: int = 256, mesh: Optional[Mesh] = None,
                      seed: int = 0) -> str:
        del mesh  # params are already placed; kept for OnPodBackend signature
        toks = self.generate_tokens(self.tokenizer.encode(prompt),
                                    max_new_tokens=max_new_tokens,
                                    temperature=temperature, seed=seed)
        return self.tokenizer.decode(toks)

    def generate_text_batch(self, prompts, *, temperature: float = 0.0,
                            max_new_tokens: int = 256, seed: int = 0):
        """Batch text-in/text-out: explain MANY flagged dialogues per device
        round trip (the reference pays one synchronous DeepSeek HTTPS call
        per message — app_ui.py:207)."""
        toks = self.generate_tokens_batch(
            [self.tokenizer.encode(p) for p in prompts],
            max_new_tokens=max_new_tokens, temperature=temperature, seed=seed)
        return [self.tokenizer.decode(t) for t in toks]
