"""The explainer family ``lfm2_moe``: a convolution-attention hybrid with
routed experts told by the keys of ``LiquidAI/LFM2-24B-A2B``'s ``config.json``
— a gated short convolution in three layers of four, grouped-query attention
with per-head q/k norms in the fourth (``layer_types``), a dense MLP in the
first ``num_dense_layers`` layers and routed experts after. The contract with
the harness is the header of ``explainers/internlm2.py``; everything of it is
in this file, and only ``build`` imports the program.

**The stage.** A configuration of this family is ONE pipeline stage of a
deployment in which one chip shares each layer: the first
``num_hidden_layers`` entries of ``layer_types`` (the file keeps the published
list whole), every layer whole where it lives, all ``num_experts`` experts of
an expert layer and the whole vocabulary held. The expert layer is still told
which experts it holds (experts 0 .. ``num_experts`` of ``num_experts``) and
routes over all of them; here the share is the whole. So that the lane emits
tokens, the final norm and the tied head read this stage's output, in the
program and in the reference alike.

**The equations** (``eps`` = ``norm_eps``, ``D`` = ``hidden_size``; weights
N(0, 1/fan_in) from the seed, rounded to the serving dtype; projections are
stored (in, heads, head_dim) and rotary pairs are the interleaved lanes
(2i, 2i+1), both only a fixed permutation of random weights). Every layer is
pre-norm on one residual, ``n_op`` and ``n_ffn`` two RMS norms:

    h = x + Mix(n_op x)          y = h + FFN(n_ffn h)

* Gated short convolution (``conv``; ``conv_L_cache`` taps, ``conv_bias``
  false): ``[B | C | X] = W_in n`` (three streams of D, in that order);
  ``u_t = B_t * X_t``; ``c_t = sum_j w_j * u_(t-(taps-1)+j)`` (``w_j`` one
  scalar a channel a tap; ``u`` before the sequence's first token is 0);
  ``Mix = W_out (C_t * c_t)``. No activation, no bias, no norm inside.
* Attention (``full_attention``): ``q = W_q n`` (H x d), ``k = W_k n``,
  ``v = W_v n`` (Hkv x d each); ``q <- RMSNorm_d(q) * g_q``,
  ``k <- RMSNorm_d(k) * g_k`` per head (eps), then RoPE
  (``rope_parameters.rope_theta``) on both; scores ``q.k / sqrt(d)``, causal,
  float32 softmax, each group of H / Hkv query heads on its one key-value
  head; ``Mix = W_o o``. No bias.
* Dense MLP: ``W_2 (SiLU(W_1 n) * W_3 n)``, width ``intermediate_size``.
* Expert layer: ``s = sigmoid(W_r n)`` in float32 over ``num_experts``
  outputs; the choice is the ``num_experts_per_tok`` largest of ``s + b``
  (``use_expert_bias``; no groups); ``w_e = s_e / (sum_chosen s + 1e-6)``
  (``norm_topk_prob``), times ``routed_scaling_factor``;
  ``FFN = sum_chosen w_e W_2,e (SiLU(W_1,e n) * W_3,e n)``, width
  ``moe_intermediate_size``. No shared expert.
* Embedding, final RMSNorm, head tied to the embedding (N(0, 1/D) rows).

Departures from the published model, each under ``assumed`` in the
configuration file: the tokenizer is the repo's byte tokenizer, the weights
are random, and what the catalog's row does not carry (``head_dim``, the tied
head, the router's ``1e-6``, the stream order, SiLU, bfloat16) is the
family's convention.
"""

from __future__ import annotations

import json
import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import numpy as np

# the benchmark's own seed key, RMS norm and interleaved RoPE: one copy,
# shared with the other families' references
from benchmark.reference import _rms, _root_key, _rope

REFERENCE_BLOCK = 4       # requests a reference pass holds at once (32 heads'
#                           float32 scores of 2,176 positions: 0.6 GB a request)
REGRET_FLOOR = 1e-9       # least regret of a served token (see token_gaps)
ROUTER_NORM_EPS = 1e-6    # added to the chosen scores' sum (the family's code)


# ---------------------------------------------------------------------------
# shapes (no program import): what each layer holds, by its kind
# ---------------------------------------------------------------------------

def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_kinds(cfg: dict) -> List[Tuple[str, str]]:
    """(mixer, ffn) of every layer held: the first ``num_hidden_layers``
    entries of ``layer_types``; a dense MLP in the first
    ``num_dense_layers``, experts after."""
    mixers = {"conv": "conv", "full_attention": "attention"}
    return [(mixers[t], "dense" if i < cfg["num_dense_layers"] else "experts")
            for i, t in enumerate(
                cfg["layer_types"][:cfg["num_hidden_layers"]])]


def _mixer_leaves(cfg: dict, mixer: str) -> List[tuple]:
    D, H, Hkv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], head_dim(cfg))
    if mixer == "conv":
        taps = cfg["conv_L_cache"]
        return [("conv_win", (D, 3, D), "normal", D),
                ("conv_w", (taps, D), "normal", taps),
                ("conv_wout", (D, D), "normal", D)]
    return [("wq", (D, H, d), "normal", D), ("wk", (D, Hkv, d), "normal", D),
            ("wv", (D, Hkv, d), "normal", D), ("wo", (H, d, D), "normal", H * d),
            ("q_norm", (d,), "ones", 1), ("k_norm", (d,), "ones", 1)]


def _ffn_leaves(cfg: dict, ffn: str) -> List[tuple]:
    D = cfg["hidden_size"]
    if ffn == "dense":
        F = cfg["intermediate_size"]
        return [("w_gate", (D, F), "normal", D), ("w_up", (D, F), "normal", D),
                ("w_down", (F, D), "normal", F)]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    return [("moe_router", (D, E), "normal", D),
            ("moe_bias", (E,), "zeros_f32", 1),
            ("moe_wg", (E, D, F), "experts", D),
            ("moe_wu", (E, D, F), "experts", D),
            ("moe_wd", (E, F, D), "experts", F)]


def layer_leaves(cfg: dict, mixer: str, ffn: str) -> List[tuple]:
    """One layer's weights in the order their keys are drawn, the mixer's
    and then the feed-forward's: (name, shape, how made, fan_in). A shape
    that starts with the expert count is one matrix per expert, each from
    its own key (the expert's published index folded in)."""
    return _mixer_leaves(cfg, mixer) + _ffn_leaves(cfg, ffn)


# ---------------------------------------------------------------------------
# the model the slot lane serves (the only importer of the program)
# ---------------------------------------------------------------------------

def build(cfg: dict, params: dict, weights: str):
    import jax.numpy as jnp

    from fraud_detection_tpu.models import llm

    if cfg["conv_bias"] or not cfg["use_expert_bias"] \
            or not cfg.get("tie_word_embeddings", True):
        raise ValueError("this family serves a filter without bias, a router "
                         "with its selection bias and a tied head")
    tcfg = llm.TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"], max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        dtype=jnp.dtype(cfg["torch_dtype"]).type,
        n_kv_heads=cfg["num_key_value_heads"], head_dim_override=head_dim(cfg),
        activation=cfg.get("hidden_act", "silu"), tie_embeddings=True,
        rms_eps=float(cfg["norm_eps"]), layer_kinds=tuple(layer_kinds(cfg)),
        conv=llm.ConvConfig(taps=cfg["conv_L_cache"]), qk_norm=True,
        moe=llm.MoEConfig(
            n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
            n_group=1, topk_group=1, d_expert=cfg["moe_intermediate_size"],
            d_shared=0, routed_scale=float(cfg["routed_scaling_factor"]),
            score="sigmoid", norm_topk=bool(cfg["norm_topk_prob"]),
            norm_eps=ROUTER_NORM_EPS, held_start=0,
            held_count=cfg["num_experts"]))
    if weights == "int8":
        # Leaf by leaf, each full-width leaf dropped as its int8 copy
        # lands: both copies whole would not fit the chip beside each other.
        for name in list(params):
            params[name] = llm.quantize_params({name: params[name]})[name]
    return llm.LanguageModel(tcfg, params)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _leaf(key, shape: tuple, made: str, fan_in: int, dtype):
    """One leaf from its key, rounded to ``dtype`` (traced inside a jit)."""
    import jax
    import jax.numpy as jnp

    if made == "ones":
        return jnp.ones(shape, dtype)
    if made == "zeros_f32":
        return jnp.zeros(shape, "float32")
    if made == "experts":           # expert e's matrix from fold_in(key, e)
        w = jax.vmap(lambda e: jax.random.normal(
            jax.random.fold_in(key, e), shape[1:], "float32"))(
                jnp.arange(shape[0]))
    else:
        w = jax.random.normal(key, shape, "float32")
    return (w / math.sqrt(fan_in)).astype(dtype)


def _embedding(key, cfg: dict, dtype):
    """The (V, D) table, N(0, 1/D): the tied head's rows too."""
    return _leaf(key, (cfg["vocab_size"], cfg["hidden_size"]), "normal",
                 cfg["hidden_size"], dtype)


def make_params(seed: int, cfg: dict, dtype) -> dict:
    """The explainer's weights on the device, one jitted call a leaf (an
    expert stack's float32 draft is 0.8 GB), in the layout ``build`` takes."""
    import jax
    import jax.numpy as jnp

    n = cfg["num_hidden_layers"]
    root = _root_key(seed)
    make = jax.jit(_leaf, static_argnums=(1, 2, 3, 4))
    p = {}
    ones = jnp.ones((cfg["hidden_size"],), dtype)
    for l, kind in enumerate(layer_kinds(cfg)):
        key = jax.random.fold_in(root, l)
        for j, (name, shape, made, fan_in) in enumerate(layer_leaves(cfg, *kind)):
            p[f"l{l}.{name}"] = make(jax.random.fold_in(key, j), shape, made,
                                     fan_in, dtype)
        p[f"l{l}.ln1"] = p[f"l{l}.ln2"] = ones
    p["embed"] = jax.jit(lambda k: _embedding(k, cfg, dtype))(
        jax.random.fold_in(root, n))
    p["ln_f"] = ones
    return p


# ---------------------------------------------------------------------------
# the plain reference (float32, "highest"; no cache, no state, no kernels)
# ---------------------------------------------------------------------------

def _ref_weights(key, cfg: dict, mixer: str, ffn: str,
                 names: Sequence[str], dtype) -> dict:
    """The named leaves of one layer again from the layer's key, upcast to
    float32 from the serving dtype's values."""
    import jax
    import jax.numpy as jnp

    return {name: _leaf(jax.random.fold_in(key, j), shape, made, fan_in,
                        dtype).astype(jnp.float32)
            for j, (name, shape, made, fan_in)
            in enumerate(layer_leaves(cfg, mixer, ffn)) if name in names}


def _ref_conv(w: dict, cfg: dict, hn):
    """hn (B,T,D) normed -> Mix(hn): the filter as shifted products over the
    whole sequence, zeros ahead of its first token."""
    import jax.numpy as jnp

    T, taps = hn.shape[1], cfg["conv_L_cache"]
    bcx = jnp.einsum("btD,DsC->btsC", hn, w["conv_win"])
    u = bcx[:, :, 0] * bcx[:, :, 2]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(w["conv_w"][j] * padded[:, j:j + T] for j in range(taps))
    return (bcx[:, :, 1] * c) @ w["conv_wout"]


def _ref_attention(w: dict, cfg: dict, hn):
    """hn (B,T,D) normed -> Mix(hn): full causal attention, each query head
    on its group's one key-value head."""
    import jax
    import jax.numpy as jnp

    T = hn.shape[1]
    eps, theta = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    rep = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    rope = jax.vmap(lambda a: _rope(a, theta))
    q = rope(_rms(jnp.einsum("btD,Dhd->bthd", hn, w["wq"]), eps) * w["q_norm"])
    k = rope(_rms(jnp.einsum("btD,Dhd->bthd", hn, w["wk"]), eps) * w["k_norm"])
    v = jnp.einsum("btD,Dhd->bthd", hn, w["wv"])
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    return jnp.einsum("bthd,hdD->btD", o, w["wo"])


def _ref_choice(w: dict, cfg: dict, u):
    """u (N,D) -> (N, experts): the weight of every expert in each token's
    sum, zero where it was not chosen."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(u @ w["moe_router"])
    rank = jnp.argsort(jnp.argsort(-(s + w["moe_bias"]), -1, stable=True), -1)
    chosen = jnp.where(rank < cfg["num_experts_per_tok"], s, 0.0)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + ROUTER_NORM_EPS)
    return cfg["routed_scaling_factor"] * chosen


def _ref_experts(key, cfg: dict, dtype, mixer: str, u, weight, first: int,
                 count: int):
    """Sum over experts ``first .. first + count`` of ``weight[:, e] *
    E_e(u)``, one expert at a time: each expert's matrices made from its own
    keys (``mixer`` is the layer's, which places them among its leaves),
    applied to every token, and masked by the weights (zero where the token
    did not choose it)."""
    import jax
    import jax.numpy as jnp

    leaves = {name: (j, shape, fan_in) for j, (name, shape, made, fan_in)
              in enumerate(layer_leaves(cfg, mixer, "experts"))
              if made == "experts"}

    def mat(name, e):
        j, shape, fan_in = leaves[name]
        k = jax.random.fold_in(jax.random.fold_in(key, j), e)
        return (jax.random.normal(k, shape[1:], "float32")
                / math.sqrt(fan_in)).astype(dtype).astype(jnp.float32)

    def one(e_local, y):
        e = first + e_local
        hid = jax.nn.silu(u @ mat("moe_wg", e)) * (u @ mat("moe_wu", e))
        return y + jax.lax.dynamic_slice_in_dim(weight, e, 1, 1) * (
            hid @ mat("moe_wd", e))

    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))


def expert_layer(key, cfg: dict, dtype, mixer: str, u, first: int = 0,
                 count: int = None):
    """``FFN(u)`` of an expert layer for u (N,D) normed, as the share that
    holds experts ``first .. first + count`` computes it (all of them by
    default); ``mixer`` is the layer's."""
    w = _ref_weights(key, cfg, mixer, "experts", ("moe_router", "moe_bias"),
                     dtype)
    return _ref_experts(key, cfg, dtype, mixer, u, _ref_choice(w, cfg, u),
                        first, cfg["num_experts"] if count is None else count)


_FNS: Dict[tuple, tuple] = {}


def _reference_fns(cfg: dict, dtype_name: str):
    memo = (json.dumps({k: v for k, v in cfg.items() if k != "desk"},
                       sort_keys=True, default=str), dtype_name)
    if memo not in _FNS:
        _FNS[memo] = _build_reference_fns(cfg, dtype_name)
    return _FNS[memo]


def _build_reference_fns(cfg: dict, dtype_name: str):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    eps = cfg["norm_eps"]
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the plain reference implements SiLU-gated MLPs")

    @jax.jit
    def embed(key, tokens):
        return _embedding(key, cfg, dtype)[tokens].astype(jnp.float32)

    # One jitted piece a sub-block, so that no more than one sub-block's
    # float32 weights stand beside the activations.
    @partial(jax.jit, static_argnums=(2, 3))
    def mix(key, x, mixer, ffn):
        with jax.default_matmul_precision("highest"):
            names = [leaf[0] for leaf in _mixer_leaves(cfg, mixer)]
            w = _ref_weights(key, cfg, mixer, ffn, names, dtype)
            fn = _ref_conv if mixer == "conv" else _ref_attention
            return x + fn(w, cfg, _rms(x, eps))

    @partial(jax.jit, static_argnums=(2, 3))
    def ffn_of(key, x, mixer, ffn):
        with jax.default_matmul_precision("highest"):
            u = _rms(x, eps)
            if ffn == "dense":
                w = _ref_weights(key, cfg, mixer, ffn,
                                 ("w_gate", "w_up", "w_down"), dtype)
                return x + (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) \
                    @ w["w_down"]
            return x + expert_layer(key, cfg, dtype, mixer,
                                    u.reshape(-1, u.shape[-1])).reshape(u.shape)

    def layer(key, x, kind):
        return ffn_of(key, mix(key, x, *kind), *kind)

    @jax.jit
    def logits(key, x):
        with jax.default_matmul_precision("highest"):
            return _rms(x, eps) @ _embedding(key, cfg, dtype).astype(
                jnp.float32).T

    return embed, layer, logits


def reference_logits(seed: int, cfg: dict, dtype_name: str, tokens, at=None):
    """The reference's logits for a block of token rows (B,T): at every
    position, or at the positions ``at`` (B,S) of each row."""
    import jax
    import jax.numpy as jnp

    n = cfg["num_hidden_layers"]
    root = _root_key(seed)
    embed, layer, logits = _reference_fns(cfg, dtype_name)
    x = embed(jax.random.fold_in(root, n), jnp.asarray(tokens))
    for l, kind in enumerate(layer_kinds(cfg)):
        x = layer(jax.random.fold_in(root, l), x, kind)
    if at is not None:
        x = x[jnp.arange(x.shape[0])[:, None], jnp.asarray(at)]
    return logits(jax.random.fold_in(root, n), x)


def token_gaps(seed: int, cfg: dict, dtype_name: str,
               requests: Sequence[dict], pad_to: int) -> List[np.ndarray]:
    """For each request ``{"prompt": int tokens, "served": int tokens}`` the
    regret of every served token: the reference's best logit at its position
    minus the logit of the token that was served there, teacher-forced over
    the prompt and what the program served (prefill, then decode through the
    pages and the filters' tails), and never under ``REGRET_FLOOR``. As for
    the other routed families the floor makes ``token_gap_sq`` the square of
    the mean regret over EVERY served token (``check.explainer_numbers``
    takes its mean over the values above zero): a routed model in bfloat16
    flips an expert choice somewhere in its layers for a share of the tokens
    whatever the arithmetic does, and here every flipped pick is computed,
    so how many served tokens are off the best and by how much both follow
    the noise, and their product tells the family's lower precision from
    the stated one.
    Whole sequences, ``REFERENCE_BLOCK`` requests side by side, each padded
    to ``pad_to`` positions behind its real tokens (causal layers: padding
    after a position does not reach it)."""
    import jax.numpy as jnp

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
        ref = reference_logits(seed, cfg, dtype_name, toks, at)
        gap = np.asarray(
            jnp.max(ref, -1) - jnp.take_along_axis(
                ref, jnp.asarray(served)[..., None], -1)[..., 0], np.float64)
        out += [np.maximum(gap[b, :len(req["served"])], REGRET_FLOOR)
                for b, req in enumerate(part)]
    return out


# ---------------------------------------------------------------------------
# operations and bytes the algorithm needs (shapes alone)
# ---------------------------------------------------------------------------

def _numel(shape) -> int:
    return int(np.prod(shape))


def _layer_params(cfg: dict, mixer: str, ffn: str, *, held: bool = True) -> int:
    """A layer's weights without its two block norms; ``held=False`` leaves
    the experts' stacks out (what every token multiplies whatever it chose,
    plus the small vectors)."""
    return sum(_numel(shape) for _, shape, made, _ in layer_leaves(cfg, mixer, ffn)
               if held or made != "experts")


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _n_layers(cfg: dict, kind: str) -> int:
    """Layers of a mixer ("conv" | "attention") or a feed-forward
    ("experts") among those held."""
    return sum(1 for pair in layer_kinds(cfg) if kind in pair)


def param_count(cfg: dict) -> int:
    """Every weight held: layers, their two block norms, the final norm and
    the embedding (the tied head is the embedding)."""
    D = cfg["hidden_size"]
    return (sum(_layer_params(cfg, *kind) + 2 * D for kind in layer_kinds(cfg))
            + cfg["vocab_size"] * D + D)


def expected_experts_touched(cfg: dict, rows: float) -> float:
    """Distinct experts that ``rows`` tokens touch in one layer under even
    routing, each of a token's ``num_experts_per_tok`` picks counted as a
    draw of its own (so slightly under the distinct-pick count: the lower)."""
    E = cfg["num_experts"]
    return E * (1.0 - (1.0 - 1.0 / E) ** (cfg["num_experts_per_tok"] * rows))


def _step_params(cfg: dict) -> int:
    """Weights every step reads whatever is routed: every layer outside its
    experts' stacks with its two norms, the tied head and the final norm."""
    D = cfg["hidden_size"]
    return (sum(_layer_params(cfg, *kind, held=False) + 2 * D
                for kind in layer_kinds(cfg)) + cfg["vocab_size"] * D + D)


def _token_flops(cfg: dict) -> float:
    """FLOPs one token costs outside attention over the context and the
    head: 2 a weight it multiplies — every layer outside the experts' stacks
    (the filter's taps among them: a multiply-add a channel a tap) and its
    ``num_experts_per_tok`` picks, all computed here."""
    picks = (cfg["num_experts_per_tok"] * _expert_params(cfg)
             * _n_layers(cfg, "experts"))
    return 2.0 * (sum(_layer_params(cfg, *kind, held=False)
                      for kind in layer_kinds(cfg)) + picks)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """What one attention layer caches of a token: k and v."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def state_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What the convolution layers keep of a row: ``conv_L_cache - 1``
    gated inputs of D channels each."""
    return (_n_layers(cfg, "conv") * (cfg["conv_L_cache"] - 1)
            * cfg["hidden_size"] * itemsize)


def decode_cost(cfg: dict, steps: float, row_steps: float,
                mean_context: float, itemsize: int = 2,
                experts_touched: float = None) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``steps`` decode steps in which ``row_steps`` rows
    decoded, each holding ``mean_context`` tokens on average. A step reads
    once: every layer's weights outside the experts' stacks, the tied head,
    and of each expert layer the experts its rows touch: ``experts_touched``,
    the program's own count summed over these steps and the layers
    (``moe_experts_touched``), or without it the expected distinct count
    under even routing (``expected_experts_touched``). A row-step reads the
    k/v of the tokens it holds in each attention layer and writes one, reads
    and writes the filters' tails; its FLOPs are ``_token_flops``, the head
    and the attention."""
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    n_attn, n_exp = _n_layers(cfg, "attention"), _n_layers(cfg, "experts")
    rows = row_steps / steps if steps else 0.0
    if experts_touched is None:
        experts_touched = steps * n_exp * expected_experts_touched(cfg, rows)
    attend = 4.0 * H * head_dim(cfg) * (mean_context + 1)
    flops = row_steps * (_token_flops(cfg) + 2.0 * V * D + n_attn * attend)
    nbytes = ((steps * _step_params(cfg) + experts_touched * _expert_params(cfg))
              * itemsize
              + row_steps * (n_attn * kv_bytes_per_token(cfg, itemsize)
                             * (mean_context + 2)
                             + 2 * state_bytes(cfg, itemsize)))
    return flops, nbytes


def prefill_cost(cfg: dict, prefix_len: int, suffix_len: int,
                 itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) to prefill the REAL ``suffix_len`` tokens behind
    ``prefix_len`` cached ones: every suffix token costs ``_token_flops``;
    each attention layer attends causally over what is resident; the head
    runs once. Bytes: every weight once — of the experts' stacks the experts
    the suffix touches, each ONCE (a program that reads a busy expert once a
    tile of rows pays for that itself) — the prefix's k/v read and the
    suffix's written, the filters' tails read and written."""
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    n_attn, n_exp = _n_layers(cfg, "attention"), _n_layers(cfg, "experts")
    ctx_sum = suffix_len * prefix_len + suffix_len * (suffix_len + 1) / 2.0
    attend = 4.0 * H * head_dim(cfg) * ctx_sum
    flops = suffix_len * _token_flops(cfg) + n_attn * attend + 2.0 * V * D
    weights = (_step_params(cfg)
               + n_exp * expected_experts_touched(cfg, suffix_len)
               * _expert_params(cfg))
    nbytes = (weights * itemsize
              + n_attn * kv_bytes_per_token(cfg, itemsize)
              * (prefix_len + suffix_len) + 2 * state_bytes(cfg, itemsize))
    return flops, nbytes
