"""The explainer family ``bailing_hybrid``: a hybrid routed decoder told by
the HF-style keys of ``inclusionAI/Ling-3.0-flash``'s ``config.json`` — per
layer a KDA recurrence or (one layer in ``layer_group_size``) latent
attention, and a dense MLP (the first ``first_k_dense_replace`` layers) or
routed experts with one shared expert. The contract with the harness is the
header of ``explainers/internlm2.py``; everything of it is in this file, and
only ``build`` imports the program.

**The share.** A configuration of this family is ONE chip's share of a
deployment in which several chips divide each layer: ``num_experts`` is the
number of routed experts HELD here (experts ``expert_share.first`` ..
``+ num_experts`` of ``published.num_experts``), ``vocab_size`` the rows of
the vocabulary held (rows 0 .. vocab_size - 1). The router keeps the
published width and ``num_experts_per_tok``; a token's choice and weights
are made over all published experts, and only picks on held experts are
computed. What the absent experts would add is left out, here and in the
program alike, and that partial sum goes on to the next layer.

**The equations** (``eps`` = ``rms_norm_eps``; pre-norm residual blocks
``h = x + Mix(norm(x))``, ``y = h + FFN(norm(h))``; weights N(0, 1/fan_in)
from the seed, rounded to the serving dtype; projections are stored
(in, heads, head_dim) and rotary pairs are the interleaved lanes (2i, 2i+1),
both only a fixed permutation of random weights):

* KDA (H heads x d): ``q, k, v = SiLU(conv(W x))``, the filter causal and
  depth-wise, ``y_t = sum_j w[j] x_(t-(n-1)+j)`` over n = 4 taps; q and k
  L2-normalised per head (``a / sqrt(sum a^2 + 1e-6)``), q times d^-1/2.
  ``g_t = kda_lower_bound * sigmoid(exp(A_log_h) (W_g x + dt_bias))`` per
  channel, in [-5, 0]; ``beta_t = sigmoid(W_beta x)`` per head. State per
  head, float32, d x d: ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_(t-1)
  + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``. Out:
  ``W_o [RMSNorm_head(o_t) * sigmoid(W_z x)_head]``.
* MLA: ``q = W_q x`` (H x (nope + rope)); ``[c | k_r] = W_kva x``,
  ``c <- RMSNorm(c)``; ``[k_n | v] = W_kvb c``; RoPE on q's rope part and on
  the one shared k_r; scores ``(q_n k_n + q_r k_r) / sqrt(nope + rope)``,
  float32 softmax; out ``W_o [o * sigmoid(W_z x)_head]``.
* Experts: ``s = sigmoid(W_r x)`` over all published experts; the choice on
  ``s + b``: ``n_group`` groups scored by the sum of their two best, the best
  ``topk_group`` kept, then the best ``num_experts_per_tok`` among them;
  ``w_e = routed_scaling_factor * s_e / sum_chosen s``;
  ``y = sum_(e chosen and held) w_e E_e(x) + E_shared(x)``,
  ``E(x) = W_d (SiLU(W_g x) * W_u x)``.
* Final RMSNorm, untied head over the held vocabulary rows.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

# the benchmark's own seed key, embedding and head, RMS norm and interleaved
# RoPE: one copy, shared with the dense family's reference
from benchmark.reference import _embed_weights, _rms, _root_key, _rope

REFERENCE_BLOCK = 4       # requests a reference pass holds at once
REGRET_FLOOR = 1e-9       # least regret of a served token (see token_gaps)
L2_EPS = 1e-6


# ---------------------------------------------------------------------------
# shapes (no program import): what each layer holds, by its kind
# ---------------------------------------------------------------------------

def layer_kinds(cfg: dict) -> List[Tuple[str, str]]:
    """(mixer, ffn) of every layer held: latent attention where
    ``(i + 1) % layer_group_size == 0``, else KDA; a dense MLP in the first
    ``first_k_dense_replace`` layers, experts after."""
    return [("mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
             "dense" if i < cfg["first_k_dense_replace"] else "experts")
            for i in range(cfg["num_hidden_layers"])]


def router_width(cfg: dict) -> int:
    return int(cfg["published"]["num_experts"])


def layer_leaves(cfg: dict, mixer: str, ffn: str) -> List[tuple]:
    """One layer's weights in the order their keys are drawn:
    (name, shape, how made, fan_in). A shape that starts with the held
    expert count is one matrix per expert, each from its own key (the
    expert's published index folded in)."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    out: List[tuple] = []
    if mixer == "kda":
        d, taps = cfg["head_dim"], cfg["short_conv_kernel_size"]
        for n in ("kda_wq", "kda_wk", "kda_wv", "kda_wg"):
            out.append((n, (D, H, d), "normal", D))
        for n in ("kda_conv_q", "kda_conv_k", "kda_conv_v"):
            out.append((n, (taps, H, d), "normal", taps))
        out += [("kda_A_log", (H,), "zeros", 1),
                ("kda_dt_bias", (H, d), "decay_bias", 1),
                ("kda_wbeta", (D, H), "normal", D),
                ("kda_wz", (D, H), "normal", D),
                ("kda_onorm", (d,), "ones", 1),
                ("kda_wo", (H, d, D), "normal", H * d)]
    else:
        r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
        rope, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
        out += [("mla_wq", (D, H, nope + rope), "normal", D),
                ("mla_wkva", (D, r + rope), "normal", D),
                ("mla_kvnorm", (r,), "ones", 1),
                ("mla_wkvb", (r, H, nope + dv), "normal", r),
                ("mla_wz", (D, H), "normal", D),
                ("mla_wo", (H, dv, D), "normal", H * dv)]
    if ffn == "dense":
        F = cfg["intermediate_size"]
        out += [("w_gate", (D, F), "normal", D), ("w_up", (D, F), "normal", D),
                ("w_down", (F, D), "normal", F)]
    else:
        E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
        Fs = cfg["moe_shared_expert_intermediate_size"] * cfg["num_shared_experts"]
        out += [("moe_router", (D, router_width(cfg)), "normal", D),
                ("moe_bias", (router_width(cfg),), "zeros_f32", 1),
                ("moe_wg", (E, D, F), "experts", D),
                ("moe_wu", (E, D, F), "experts", D),
                ("moe_wd", (E, F, D), "experts", F),
                ("moe_sg", (D, Fs), "normal", D), ("moe_su", (D, Fs), "normal", D),
                ("moe_sd", (Fs, D), "normal", Fs)]
    return out


# ---------------------------------------------------------------------------
# the model the slot lane serves (the only importer of the program)
# ---------------------------------------------------------------------------

def build(cfg: dict, params: dict, weights: str):
    import jax.numpy as jnp

    from fraud_detection_tpu.models import llm

    share = cfg["expert_share"]
    tcfg = llm.TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"], max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        dtype=jnp.dtype(cfg["torch_dtype"]).type,
        head_dim_override=cfg["head_dim"], activation=cfg["hidden_act"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        layer_kinds=tuple(layer_kinds(cfg)),
        mla=llm.MLAConfig(kv_rank=cfg["kv_lora_rank"],
                          nope_dim=cfg["qk_nope_head_dim"],
                          rope_dim=cfg["qk_rope_head_dim"],
                          v_dim=cfg["v_head_dim"]),
        kda=llm.KDAConfig(n_heads=cfg["num_attention_heads"],
                          head_dim=cfg["head_dim"],
                          conv_taps=cfg["short_conv_kernel_size"],
                          lower_bound=float(cfg["kda_lower_bound"])),
        moe=llm.MoEConfig(
            n_experts=router_width(cfg), top_k=cfg["num_experts_per_tok"],
            n_group=cfg["n_group"], topk_group=cfg["topk_group"],
            d_expert=cfg["moe_intermediate_size"],
            d_shared=(cfg["moe_shared_expert_intermediate_size"]
                      * cfg["num_shared_experts"]),
            routed_scale=float(cfg["routed_scaling_factor"]),
            held_start=int(share["first"]), held_count=cfg["num_experts"]))
    if weights == "int8":
        # Leaf by leaf, each full-width leaf dropped as its int8 copy
        # lands: both copies whole would not fit the chip beside each other.
        for name in list(params):
            params[name] = llm.quantize_params({name: params[name]})[name]
    return llm.LanguageModel(tcfg, params)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _leaf(key, shape: tuple, made: str, fan_in: int, dtype, first: int = 0):
    """One leaf from its key, rounded to ``dtype`` (traced inside a jit)."""
    import jax
    import jax.numpy as jnp

    if made == "ones":
        return jnp.ones(shape, dtype)
    if made in ("zeros", "zeros_f32"):
        return jnp.zeros(shape, "float32" if made == "zeros_f32" else dtype)
    if made == "decay_bias":        # a decay of a few tokens .. several hundred
        return jax.random.uniform(key, shape, "float32", -8.0, -2.0).astype(dtype)
    if made == "experts":           # expert e's matrix from fold_in(key, e)
        one = lambda e: jax.random.normal(  # noqa: E731
            jax.random.fold_in(key, e), shape[1:], "float32")
        w = jax.vmap(one)(first + jnp.arange(shape[0]))
    else:
        w = jax.random.normal(key, shape, "float32")
    return (w / math.sqrt(fan_in)).astype(dtype)


def make_params(seed: int, cfg: dict, dtype) -> dict:
    """The explainer's weights on the device, one jitted call a leaf (an
    expert stack's float32 draft is a gigabyte; a layer's at once would put
    set-up's peak over the window's), in the layout ``build`` takes."""
    import jax
    import jax.numpy as jnp

    if cfg["tie_word_embeddings"]:
        raise ValueError("this family's weight maker builds an untied head")
    n = cfg["num_hidden_layers"]
    root = _root_key(seed)
    first = int(cfg["expert_share"]["first"])
    make = jax.jit(_leaf, static_argnums=(1, 2, 3, 4, 5))
    p = {}
    ones = jnp.ones((cfg["hidden_size"],), dtype)
    for l, (mixer, ffn) in enumerate(layer_kinds(cfg)):
        key = jax.random.fold_in(root, l)
        for i, (name, shape, made, fan_in) in enumerate(
                layer_leaves(cfg, mixer, ffn)):
            p[f"l{l}.{name}"] = make(jax.random.fold_in(key, i), shape, made,
                                     fan_in, dtype, first)
        p[f"l{l}.ln1"] = p[f"l{l}.ln2"] = ones
    p["embed"], p["lm_head"] = jax.jit(
        lambda k: _embed_weights(k, cfg, dtype))(jax.random.fold_in(root, n))
    p["ln_f"] = ones
    return p


# ---------------------------------------------------------------------------
# the plain reference (float32, "highest"; no cache, no chunks, no kernels)
# ---------------------------------------------------------------------------

def _ref_weights(key, cfg: dict, mixer: str, ffn: str, dtype) -> dict:
    """A layer's weights again from its key, upcast to float32 from the
    serving dtype's values; the expert stacks are left to ``_ref_experts``,
    which makes one expert at a time."""
    import jax
    import jax.numpy as jnp

    return {name: _leaf(jax.random.fold_in(key, i), shape, made, fan_in,
                        dtype).astype(jnp.float32)
            for i, (name, shape, made, fan_in) in enumerate(
                layer_leaves(cfg, mixer, ffn)) if made != "experts"}


def _ref_kda(w: dict, cfg: dict, hn):
    """hn (B,T,D) normed -> the mixer's output (B,T,D). The recurrence runs
    token by token."""
    import jax
    import jax.numpy as jnp

    B, T, _ = hn.shape
    d = cfg["head_dim"]
    taps = cfg["short_conv_kernel_size"]

    def filtered(name):
        x = jnp.einsum("btD,Dhd->bthd", hn, w["kda_w" + name])
        xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
        f = w["kda_conv_" + name]
        return jax.nn.silu(sum(f[j] * xp[:, j:j + T] for j in range(taps)))

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

    q, k, v = unit(filtered("q")) * d ** -0.5, unit(filtered("k")), filtered("v")
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["kda_A_log"])[:, None]
        * (jnp.einsum("btD,Dhd->bthd", hn, w["kda_wg"]) + w["kda_dt_bias"]))
    beta = jax.nn.sigmoid(jnp.einsum("btD,Dh->bth", hn, w["kda_wbeta"]))

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x                    # (B,H,d) .. (B,H)
        S = jnp.exp(g_t)[..., None] * S
        S = S - b_t[..., None, None] * k_t[..., None] * jnp.einsum(
            "bhk,bhkv->bhv", k_t, S)[..., None, :]
        S = S + b_t[..., None, None] * k_t[..., None] * v_t[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, q.shape[2], d, d), jnp.float32),
                        seq)
    o = _rms(jnp.moveaxis(o, 0, 1), cfg["rms_norm_eps"]) * w["kda_onorm"]
    z = jax.nn.sigmoid(jnp.einsum("btD,Dh->bth", hn, w["kda_wz"]))
    return jnp.einsum("bthd,hdD->btD", o * z[..., None], w["kda_wo"])


def _ref_mla(w: dict, cfg: dict, hn):
    import jax
    import jax.numpy as jnp

    T = hn.shape[1]
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    theta = float(cfg["rope_theta"])
    rope = jax.vmap(lambda a: _rope(a, theta))
    q = jnp.einsum("btD,Dhd->bthd", hn, w["mla_wq"])
    q_n, q_r = q[..., :nope], rope(q[..., nope:])
    ckr = hn @ w["mla_wkva"]
    c = _rms(ckr[..., :r], cfg["rms_norm_eps"]) * w["mla_kvnorm"]
    k_r = rope(ckr[:, :, None, r:])[:, :, 0]                       # (B,T,rope)
    kv = jnp.einsum("btc,chd->bthd", c, w["mla_wkvb"])
    k_n, v = kv[..., :nope], kv[..., nope:]
    s = (jnp.einsum("bthd,bshd->bhts", q_n, k_n)
         + jnp.einsum("bthd,bsd->bhts", q_r, k_r)) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    z = jax.nn.sigmoid(jnp.einsum("btD,Dh->bth", hn, w["mla_wz"]))
    return jnp.einsum("bthd,hdD->btD", o * z[..., None], w["mla_wo"])


def _ref_choice(w: dict, cfg: dict, x):
    """x (N,D) -> (N, published experts): the weight of every expert in each
    token's sum, zero where it was not chosen."""
    import jax
    import jax.numpy as jnp

    N = x.shape[0]
    G, keep_g, K = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ w["moe_router"])
    sel = (s + w["moe_bias"]).reshape(N, G, -1)
    two_best = jnp.sum(jnp.sort(sel, -1)[..., -2:], -1)             # (N,G)
    rank_g = jnp.argsort(jnp.argsort(-two_best, -1, stable=True), -1)
    open_ = jnp.where((rank_g < keep_g)[..., None], sel, -jnp.inf).reshape(N, -1)
    rank_e = jnp.argsort(jnp.argsort(-open_, -1, stable=True), -1)
    chosen = rank_e < K
    total = jnp.sum(jnp.where(chosen, s, 0.0), -1, keepdims=True)
    return jnp.where(chosen, cfg["routed_scaling_factor"] * s / total, 0.0)


def _ref_experts(key, cfg: dict, mixer: str, dtype, x, weight):
    """Sum over the HELD experts of weight[:, e] * E_e(x), one expert at a
    time: each expert's matrices made from its own keys, applied to every
    token, and masked by the weights (zero where the token did not choose
    it). ``weight`` (N, published experts)."""
    import jax
    import jax.numpy as jnp

    first = int(cfg["expert_share"]["first"])
    leaves = {name: (i, shape, fan_in) for i, (name, shape, made, fan_in)
              in enumerate(layer_leaves(cfg, mixer, "experts"))
              if made == "experts"}

    def mat(name, e):
        i, shape, fan_in = leaves[name]
        k = jax.random.fold_in(jax.random.fold_in(key, i), e)
        return (jax.random.normal(k, shape[1:], "float32")
                / math.sqrt(fan_in)).astype(dtype).astype(jnp.float32)

    def one(e_local, y):
        e = first + e_local
        hid = jax.nn.silu(x @ mat("moe_wg", e)) * (x @ mat("moe_wu", e))
        return y + jax.lax.dynamic_slice_in_dim(weight, e, 1, 1) * (
            hid @ mat("moe_wd", e))

    return jax.lax.fori_loop(0, cfg["num_experts"], one, jnp.zeros_like(x))


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
    eps = cfg["rms_norm_eps"]
    if cfg["hidden_act"] != "silu":
        raise ValueError("the plain reference implements SiLU-gated MLPs")

    @jax.jit
    def embed(key, tokens):
        table, _ = _embed_weights(key, cfg, dtype)
        return table[tokens].astype(jnp.float32)

    def layer(mixer, ffn):
        @jax.jit
        def run(key, x):
            with jax.default_matmul_precision("highest"):
                w = _ref_weights(key, cfg, mixer, ffn, dtype)
                mix = _ref_kda if mixer == "kda" else _ref_mla
                x = x + mix(w, cfg, _rms(x, eps))
                h2 = _rms(x, eps)
                if ffn == "dense":
                    mlp = jax.nn.silu(h2 @ w["w_gate"]) * (h2 @ w["w_up"])
                    return x + mlp @ w["w_down"]
                flat = h2.reshape(-1, h2.shape[-1])
                routed = _ref_experts(key, cfg, mixer, dtype, flat,
                                      _ref_choice(w, cfg, flat))
                shared = (jax.nn.silu(flat @ w["moe_sg"])
                          * (flat @ w["moe_su"])) @ w["moe_sd"]
                return x + (routed + shared).reshape(x.shape)

        return run

    layers = {kind: layer(*kind) for kind in set(layer_kinds(cfg))}

    @jax.jit
    def logits(key, x):
        with jax.default_matmul_precision("highest"):
            _, head = _embed_weights(key, cfg, dtype)
            return _rms(x, eps) @ head.astype(jnp.float32).T

    return embed, layers, logits


def reference_logits(seed: int, cfg: dict, dtype_name: str, tokens, at=None):
    """The reference's logits for a block of token rows (B,T): at every
    position, or at the positions ``at`` (B,S) of each row."""
    import jax
    import jax.numpy as jnp

    n = cfg["num_hidden_layers"]
    root = _root_key(seed)
    embed, layers, logits = _reference_fns(cfg, dtype_name)
    x = embed(jax.random.fold_in(root, n), jnp.asarray(tokens))
    for l, kind in enumerate(layer_kinds(cfg)):
        x = layers[kind](jax.random.fold_in(root, l), x)
    if at is not None:
        x = x[jnp.arange(x.shape[0])[:, None], jnp.asarray(at)]
    return logits(jax.random.fold_in(root, n), x)


def token_gaps(seed: int, cfg: dict, dtype_name: str,
               requests: Sequence[dict], pad_to: int) -> List[np.ndarray]:
    """For each request ``{"prompt": int tokens, "served": int tokens}`` the
    regret of every served token: the reference's best logit at its position
    minus the logit of the token that was served there, teacher-forced over
    the prompt and what the program served, and never under ``REGRET_FLOOR``.
    The floor is what makes this family's ``token_gap_sq`` the square of the
    mean regret over EVERY served token: ``check.explainer_numbers`` takes
    its mean over the values above zero, which for a dense model are the
    tokens off the reference's first choice, and here are all of them. A
    routed model in bf16 flips an expert choice of about every second token
    somewhere in its layers (PERF.md section 6, PR 29), so a sixth of the
    served tokens are off the best whatever the arithmetic does, and both
    how many are and by how much follow the noise: their product, the mean
    over all tokens, tells the family's lower precision from the stated one
    where the mean over the off-best tokens alone does not.
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
    """A layer's weights without the two block norms; ``held=False`` leaves
    the routed experts' stacks out (what every token multiplies whatever it
    chose, plus the small vectors)."""
    return sum(_numel(shape) for _, shape, made, _ in layer_leaves(cfg, mixer, ffn)
               if held or made != "experts")


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def param_count(cfg: dict) -> int:
    """Every weight held: layers, block norms, final norm, embedding and
    head over the held vocabulary rows."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    heads = V * D * (1 if cfg["tie_word_embeddings"] else 2)
    return sum(_layer_params(cfg, m, f) + 2 * D
               for m, f in layer_kinds(cfg)) + heads + D


def _held_share(cfg: dict) -> float:
    """Chance that one of a token's picks, even over the published experts,
    lands on an expert held here."""
    return cfg["num_experts"] / router_width(cfg)


def expected_experts_touched(cfg: dict, rows: float) -> float:
    """Distinct held experts that ``rows`` tokens touch in one expert layer
    under even routing: a token picks ``num_experts_per_tok`` distinct ones
    of the published count, so it misses a given expert with chance
    1 - k/n."""
    miss = 1.0 - cfg["num_experts_per_tok"] / router_width(cfg)
    return cfg["num_experts"] * (1.0 - miss ** rows)


def _token_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies outside attention over the context: every
    layer without its routed stacks, plus its expected picks on held
    experts."""
    picks_held = cfg["num_experts_per_tok"] * _held_share(cfg)
    total = 0.0
    for m, f in layer_kinds(cfg):
        total += _layer_params(cfg, m, f, held=False)
        if f == "experts":
            total += picks_held * _expert_params(cfg)
    return total


def _kda_token_flops(cfg: dict) -> float:
    """The recurrence itself, one token, all heads: decay, S^T k, the rank-1
    update, S^T q over a d x d state (7 flops an entry)."""
    return 7.0 * cfg["num_attention_heads"] * cfg["head_dim"] ** 2


def _state_bytes(cfg: dict, itemsize: int) -> int:
    """One KDA layer's per-row state: the float32 d x d state of every head
    and the filter's tail (taps - 1 inputs of q, k, v)."""
    H, d = cfg["num_attention_heads"], cfg["head_dim"]
    return H * d * d * 4 + (cfg["short_conv_kernel_size"] - 1) * 3 * H * d * itemsize


def latent_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """What one latent-attention layer caches of a token."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def decode_cost(cfg: dict, steps: float, row_steps: float,
                mean_context: float, itemsize: int = 2,
                experts_touched: float = None) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``steps`` decode steps in which ``row_steps`` rows
    decoded, each holding ``mean_context`` tokens on average. A step reads
    once: every layer's weights outside the routed stacks, the head, and of
    each expert layer the held experts its rows touch: ``experts_touched``,
    the program's own count summed over these steps and the expert layers
    (``moe_experts_touched``), or without it the expected distinct count
    under even routing (``expected_experts_touched``: the prediction; a
    router that is not even touches fewer). A row-step
    reads and writes each KDA layer's state whole whatever the context, reads
    the latents of the tokens it holds in each MLA layer (absorbed: scores
    and values against the latent, ``kv_b`` folded into q and applied to the
    sum) and writes one; its FLOPs are 2 per weight it multiplies — picks on
    held experts only — plus the recurrence and the attention."""
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    kinds = layer_kinds(cfg)
    n_kda = sum(1 for m, _ in kinds if m == "kda")
    n_mla = sum(1 for m, _ in kinds if m == "mla")
    n_exp = sum(1 for _, f in kinds if f == "experts")
    rows = row_steps / steps if steps else 0.0
    if experts_touched is None:
        experts_touched = steps * n_exp * expected_experts_touched(cfg, rows)
    step_params = (sum(_layer_params(cfg, m, f, held=False) + 2 * D
                       for m, f in kinds) + V * D + D)
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    attend = 2.0 * H * ((r + rope) + r) * (mean_context + 1)
    flops = row_steps * (2.0 * (_token_matmul_params(cfg) + V * D)
                         + n_kda * _kda_token_flops(cfg) + n_mla * attend)
    nbytes = ((steps * step_params + experts_touched * _expert_params(cfg))
              * itemsize
              + row_steps * (n_kda * 2 * _state_bytes(cfg, itemsize)
                             + n_mla * latent_bytes_per_token(cfg, itemsize)
                             * (mean_context + 2)))
    return flops, nbytes


def prefill_cost(cfg: dict, prefix_len: int, suffix_len: int,
                 itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) to prefill the REAL ``suffix_len`` tokens behind
    ``prefix_len`` cached ones: every suffix token multiplies its layers'
    weights (picks on held experts only) and runs the recurrence; an MLA
    layer expands K and V of everything resident and attends causally; the
    head runs once. Bytes: every weight once (of the routed stacks the
    experts the suffix touches), the prefix's latents read and the suffix's
    written, each KDA layer's state read and written once."""
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    kinds = layer_kinds(cfg)
    n_kda = sum(1 for m, _ in kinds if m == "kda")
    n_mla = sum(1 for m, _ in kinds if m == "mla")
    n_exp = sum(1 for _, f in kinds if f == "experts")
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    ctx_sum = suffix_len * prefix_len + suffix_len * (suffix_len + 1) / 2.0
    expand = 2.0 * r * H * (nope + dv) * (prefix_len + suffix_len)
    attend = 2.0 * H * ((nope + rope) + dv) * ctx_sum
    flops = (suffix_len * (2.0 * _token_matmul_params(cfg)
                           + n_kda * _kda_token_flops(cfg))
             + n_mla * (expand + attend) + 2.0 * V * D)
    weights = (sum(_layer_params(cfg, m, f, held=False) + 2 * D
                   for m, f in kinds) + V * D + D
               + n_exp * expected_experts_touched(cfg, suffix_len)
               * _expert_params(cfg))
    nbytes = (weights * itemsize
              + n_mla * latent_bytes_per_token(cfg, itemsize)
              * (prefix_len + suffix_len)
              + n_kda * 2 * _state_bytes(cfg, itemsize))
    return flops, nbytes
