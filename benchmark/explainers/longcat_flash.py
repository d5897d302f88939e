"""The explainer family ``longcat_flash``: a shortcut-connected routed decoder
told by the keys of ``meituan-longcat/LongCat-Flash-Chat``'s ``config.json``
— per layer two latent-attention sub-layers and two dense MLPs, and an
expert branch that starts at the first MLP's input and joins the residual one
sub-layer later, behind the second MLP; the router's outputs are the routed
experts and, after them, zero-compute (identity) experts. The contract with
the harness is the header of ``explainers/internlm2.py``; everything of it is
in this file, and only ``build`` imports the program.

**The share.** A configuration of this family is ONE chip's share of a
deployment in which several chips divide each layer: ``n_routed_experts`` is
the number of routed experts HELD here (experts ``expert_share.first`` ..
``+ n_routed_experts`` of ``published.n_routed_experts``), ``vocab_size`` the
rows of the vocabulary held (rows 0 .. vocab_size - 1). The router keeps its
published width (routed + zero-compute outputs) and ``moe_topk``; a token's
choice and weights are made over all of them, only picks on held experts are
multiplied, and the zero-compute picks are computed for every row in full (an
identity reads no weights, so the chip a token lives on does it, once). What
the absent experts would add is left out, here and in the program alike, and
that partial sum goes on to the next layer. Latent attention and the dense
MLPs are whole.

**The equations** (``eps`` = ``rms_norm_eps``, ``D`` = ``hidden_size``;
weights N(0, 1/fan_in) from the seed, rounded to the serving dtype;
projections are stored (in, heads, head_dim) and rotary pairs are the
interleaved lanes (2i, 2i+1), both only a fixed permutation of random
weights). One layer, ``A0, A1`` its attentions, ``M0, M1`` its dense MLPs
(``ffn_hidden_size``, SiLU-gated), ``E`` its expert branch, ``n0 .. n3`` four
RMS norms:

    h1 = x  + A0(n0 x)          u  = n1 h1
    s  = E(u)                   h2 = h1 + M0(u)
    h3 = h2 + A1(n2 h2)         y  = h3 + M1(n3 h3) + s

* Latent attention ``A(x)``: ``cq = RMSNorm(W_qa x)`` (``q_lora_rank``);
  ``q = W_qb cq`` (H x (nope + rope)), times ``(D / q_lora_rank)^1/2``
  (``mla_scale_q_lora``); ``[c | k_r] = W_kva x``, ``c <- RMSNorm(c)`` times
  ``(D / kv_lora_rank)^1/2`` (``mla_scale_kv_lora``: on the latent ahead of
  ``W_kvb``, so keys and values both carry it, and so does the cache);
  ``[k_n | v] = W_kvb c``; RoPE on q's rope part and on the one shared k_r;
  scores ``(q_n k_n + q_r k_r) / sqrt(nope + rope)``, causal, float32
  softmax; out ``W_o o``: no bias, no output gate.
* Expert branch ``E(u)``: ``p = softmax(W_r u)`` in float32 over ALL router
  outputs (published routed + ``zero_expert_num``); the choice is the
  ``moe_topk`` largest of ``p + b`` (no groups); ``w_e = routed_scaling_factor
  * p_e`` for the chosen, not normalised; ``E(u) = sum_(e chosen, routed,
  held) w_e W_d,e (SiLU(W_g,e u) * W_u,e u) + (sum_(e chosen, zero-compute)
  w_e) u`` (``zero_expert_type`` identity). No shared expert.
* Embedding, final RMSNorm, untied head over the held vocabulary rows.

Departures from the published model, each under ``assumed`` in the
configuration file: the multi-token-prediction module is not loaded, the
tokenizer is the repo's byte tokenizer, the weights are random.
"""

from __future__ import annotations

import json
import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import numpy as np

# the benchmark's own seed key, embedding and head, RMS norm and interleaved
# RoPE: one copy, shared with the other families' references
from benchmark.reference import _embed_weights, _rms, _root_key, _rope

REFERENCE_BLOCK = 2       # requests a reference pass holds at once (64 heads'
#                           float32 scores of 2,176 positions: 1.2 GB a request)
REGRET_FLOOR = 1e-9       # least regret of a served token (see token_gaps)


# ---------------------------------------------------------------------------
# shapes (no program import)
# ---------------------------------------------------------------------------

def router_width(cfg: dict) -> int:
    return int(cfg["published"]["n_routed_experts"]) + int(cfg["zero_expert_num"])


def _attention_leaves(cfg: dict) -> List[tuple]:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    return [("mla_wqa", (D, rq), "normal", D),
            ("mla_qnorm", (rq,), "ones", 1),
            ("mla_wqb", (rq, H, nope + rope), "normal", rq),
            ("mla_wkva", (D, r + rope), "normal", D),
            ("mla_kvnorm", (r,), "ones", 1),
            ("mla_wkvb", (r, H, nope + dv), "normal", r),
            ("mla_wo", (H, dv, D), "normal", H * dv)]


def _mlp_leaves(cfg: dict) -> List[tuple]:
    D, F = cfg["hidden_size"], cfg["ffn_hidden_size"]
    return [("w_gate", (D, F), "normal", D), ("w_up", (D, F), "normal", D),
            ("w_down", (F, D), "normal", F)]


def _branch_leaves(cfg: dict) -> List[tuple]:
    D, E, F = cfg["hidden_size"], cfg["n_routed_experts"], cfg["expert_ffn_hidden_size"]
    return [("moe_router", (D, router_width(cfg)), "normal", D),
            ("moe_bias", (router_width(cfg),), "zeros_f32", 1),
            ("moe_wg", (E, D, F), "experts", D),
            ("moe_wu", (E, D, F), "experts", D),
            ("moe_wd", (E, F, D), "experts", F)]


def layer_leaves(cfg: dict) -> List[tuple]:
    """One layer's weights in the order their keys are drawn: (sub-layer 0
    or 1, name, shape, how made, fan_in). The program serves a layer as two
    sub-layers (an attention and a dense MLP each; the first starts the
    expert branch, the second joins it). A shape that starts with the held
    expert count is one matrix per expert, each from its own key (the
    expert's published index folded in)."""
    first = _attention_leaves(cfg) + _mlp_leaves(cfg) + _branch_leaves(cfg)
    second = _attention_leaves(cfg) + _mlp_leaves(cfg)
    return [(0,) + leaf for leaf in first] + [(1,) + leaf for leaf in second]


def mla_scales(cfg: dict) -> Tuple[float, float]:
    D = cfg["hidden_size"]
    return (math.sqrt(D / cfg["q_lora_rank"]) if cfg["mla_scale_q_lora"] else 1.0,
            math.sqrt(D / cfg["kv_lora_rank"]) if cfg["mla_scale_kv_lora"] else 1.0)


# ---------------------------------------------------------------------------
# the model the slot lane serves (the only importer of the program)
# ---------------------------------------------------------------------------

def build(cfg: dict, params: dict, weights: str):
    import jax.numpy as jnp

    from fraud_detection_tpu.models import llm

    if cfg["zero_expert_type"] != "identity" or cfg["attention_bias"]:
        raise ValueError("this family serves identity zero-compute experts "
                         "and attention without bias")
    q_scale, kv_scale = mla_scales(cfg)
    tcfg = llm.TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_layers=2 * cfg["num_layers"],
        d_ff=cfg["ffn_hidden_size"], max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        dtype=jnp.dtype(cfg["torch_dtype"]).type,
        activation=cfg["hidden_act"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        layer_kinds=(("mla", "dense+experts"), ("mla", "dense+join"))
        * cfg["num_layers"],
        mla=llm.MLAConfig(kv_rank=cfg["kv_lora_rank"],
                          nope_dim=cfg["qk_nope_head_dim"],
                          rope_dim=cfg["qk_rope_head_dim"],
                          v_dim=cfg["v_head_dim"], q_rank=cfg["q_lora_rank"],
                          q_scale=q_scale, kv_scale=kv_scale, out_gate=False),
        moe=llm.MoEConfig(
            n_experts=int(cfg["published"]["n_routed_experts"]),
            n_zero=int(cfg["zero_expert_num"]), top_k=cfg["moe_topk"],
            n_group=1, topk_group=1, d_expert=cfg["expert_ffn_hidden_size"],
            d_shared=0, routed_scale=float(cfg["routed_scaling_factor"]),
            score="softmax", norm_topk=False,
            held_start=int(cfg["expert_share"]["first"]),
            held_count=cfg["n_routed_experts"]))
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
    if made == "zeros_f32":
        return jnp.zeros(shape, "float32")
    if made == "experts":           # expert e's matrix from fold_in(key, e)
        one = lambda e: jax.random.normal(  # noqa: E731
            jax.random.fold_in(key, e), shape[1:], "float32")
        w = jax.vmap(one)(first + jnp.arange(shape[0]))
    else:
        w = jax.random.normal(key, shape, "float32")
    return (w / math.sqrt(fan_in)).astype(dtype)


def make_params(seed: int, cfg: dict, dtype) -> dict:
    """The explainer's weights on the device, one jitted call a leaf (an
    expert stack's float32 draft is 0.8 GB), in the layout ``build`` takes:
    layer i's two sub-layers are the program's layers 2i and 2i + 1."""
    import jax
    import jax.numpy as jnp

    if cfg["tie_word_embeddings"]:
        raise ValueError("this family's weight maker builds an untied head")
    n = cfg["num_layers"]
    root = _root_key(seed)
    first = int(cfg["expert_share"]["first"])
    make = jax.jit(_leaf, static_argnums=(1, 2, 3, 4, 5))
    p = {}
    ones = jnp.ones((cfg["hidden_size"],), dtype)
    for i in range(n):
        key = jax.random.fold_in(root, i)
        for j, (sub, name, shape, made, fan_in) in enumerate(layer_leaves(cfg)):
            p[f"l{2 * i + sub}.{name}"] = make(
                jax.random.fold_in(key, j), shape, made, fan_in, dtype, first)
        for l in (2 * i, 2 * i + 1):
            p[f"l{l}.ln1"] = p[f"l{l}.ln2"] = ones
    p["embed"], p["lm_head"] = jax.jit(
        lambda k: _embed_weights(k, cfg, dtype))(jax.random.fold_in(root, n))
    p["ln_f"] = ones
    return p


# ---------------------------------------------------------------------------
# the plain reference (float32, "highest"; no cache, no chunks, no kernels)
# ---------------------------------------------------------------------------

def _ref_weights(key, cfg: dict, sub: int, names: Sequence[str], dtype) -> dict:
    """The named leaves of one sub-layer again from the layer's key, upcast
    to float32 from the serving dtype's values."""
    import jax
    import jax.numpy as jnp

    return {name: _leaf(jax.random.fold_in(key, j), shape, made, fan_in,
                        dtype).astype(jnp.float32)
            for j, (s, name, shape, made, fan_in) in enumerate(layer_leaves(cfg))
            if s == sub and name in names}


def _ref_attention(w: dict, cfg: dict, hn):
    """hn (B,T,D) normed -> A(hn) (B,T,D)."""
    import jax
    import jax.numpy as jnp

    T = hn.shape[1]
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    theta, eps = float(cfg["rope_theta"]), cfg["rms_norm_eps"]
    q_scale, kv_scale = mla_scales(cfg)
    rope = jax.vmap(lambda a: _rope(a, theta))
    cq = _rms(hn @ w["mla_wqa"], eps) * w["mla_qnorm"]
    q = jnp.einsum("btr,rhd->bthd", cq, w["mla_wqb"]) * q_scale
    q_n, q_r = q[..., :nope], rope(q[..., nope:])
    ckr = hn @ w["mla_wkva"]
    c = _rms(ckr[..., :r], eps) * w["mla_kvnorm"] * kv_scale
    k_r = rope(ckr[:, :, None, r:])[:, :, 0]                       # (B,T,rope)
    kv = jnp.einsum("btc,chd->bthd", c, w["mla_wkvb"])
    k_n, v = kv[..., :nope], kv[..., nope:]
    s = (jnp.einsum("bthd,bshd->bhts", q_n, k_n)
         + jnp.einsum("bthd,bsd->bhts", q_r, k_r)) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    return jnp.einsum("bthd,hdD->btD", o, w["mla_wo"])


def _ref_choice(w: dict, cfg: dict, u):
    """u (N,D) -> (N, router outputs): the weight of every router output in
    each token's sum, zero where it was not chosen."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(u @ w["moe_router"], -1)
    rank = jnp.argsort(jnp.argsort(-(p + w["moe_bias"]), -1, stable=True), -1)
    return jnp.where(rank < cfg["moe_topk"],
                     cfg["routed_scaling_factor"] * p, 0.0)


def _ref_experts(key, cfg: dict, dtype, u, weight, first: int, count: int):
    """Sum over routed experts ``first .. first + count`` of
    ``weight[:, e] * E_e(u)``, one expert at a time: each expert's matrices
    made from its own keys, applied to every token, and masked by the
    weights (zero where the token did not choose it)."""
    import jax
    import jax.numpy as jnp

    leaves = {name: (j, shape, fan_in) for j, (_, name, shape, made, fan_in)
              in enumerate(layer_leaves(cfg)) if made == "experts"}

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


def expert_branch(key, cfg: dict, dtype, u, first: int, count: int,
                  zero_part: bool = True):
    """``E(u)`` for u (N,D) as the share that holds routed experts ``first ..
    first + count`` computes it: their part of the routed sum and, with
    ``zero_part``, the zero-compute part in full."""
    import jax.numpy as jnp

    w = _ref_weights(key, cfg, 0, ("moe_router", "moe_bias"), dtype)
    weight = _ref_choice(w, cfg, u)
    out = _ref_experts(key, cfg, dtype, u, weight, first, count)
    if zero_part:
        n_routed = int(cfg["published"]["n_routed_experts"])
        out = out + jnp.sum(weight[:, n_routed:], -1, keepdims=True) * u
    return out


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
    first, count = int(cfg["expert_share"]["first"]), cfg["n_routed_experts"]
    attention_names = [leaf[0] for leaf in _attention_leaves(cfg)]
    mlp_names = [leaf[0] for leaf in _mlp_leaves(cfg)]

    @jax.jit
    def embed(key, tokens):
        table, _ = _embed_weights(key, cfg, dtype)
        return table[tokens].astype(jnp.float32)

    # One jitted piece a sub-block, so that no more than one sub-block's
    # float32 weights (0.9 GB for a dense MLP) stand beside the activations.
    @partial(jax.jit, static_argnums=(2,))
    def attend(key, x, sub):
        with jax.default_matmul_precision("highest"):
            w = _ref_weights(key, cfg, sub, attention_names, dtype)
            return x + _ref_attention(w, cfg, _rms(x, eps))

    @partial(jax.jit, static_argnums=(2,))
    def mlp(key, x, sub):
        """x -> (x + M(n x), n x)."""
        with jax.default_matmul_precision("highest"):
            w = _ref_weights(key, cfg, sub, mlp_names, dtype)
            u = _rms(x, eps)
            return x + (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) \
                @ w["w_down"], u

    @jax.jit
    def branch(key, u):
        with jax.default_matmul_precision("highest"):
            return expert_branch(key, cfg, dtype, u.reshape(-1, u.shape[-1]),
                                 first, count).reshape(u.shape)

    def layer(key, x):
        h1 = attend(key, x, 0)
        h2, u = mlp(key, h1, 0)
        s = branch(key, u)
        h3 = attend(key, h2, 1)
        y, _ = mlp(key, h3, 1)
        return y + s

    @jax.jit
    def logits(key, x):
        with jax.default_matmul_precision("highest"):
            _, head = _embed_weights(key, cfg, dtype)
            return _rms(x, eps) @ head.astype(jnp.float32).T

    return embed, layer, logits


def reference_logits(seed: int, cfg: dict, dtype_name: str, tokens, at=None):
    """The reference's logits for a block of token rows (B,T): at every
    position, or at the positions ``at`` (B,S) of each row."""
    import jax
    import jax.numpy as jnp

    n = cfg["num_layers"]
    root = _root_key(seed)
    embed, layer, logits = _reference_fns(cfg, dtype_name)
    x = embed(jax.random.fold_in(root, n), jnp.asarray(tokens))
    for i in range(n):
        x = layer(jax.random.fold_in(root, i), x)
    if at is not None:
        x = x[jnp.arange(x.shape[0])[:, None], jnp.asarray(at)]
    return logits(jax.random.fold_in(root, n), x)


def token_gaps(seed: int, cfg: dict, dtype_name: str,
               requests: Sequence[dict], pad_to: int) -> List[np.ndarray]:
    """For each request ``{"prompt": int tokens, "served": int tokens}`` the
    regret of every served token: the reference's best logit at its position
    minus the logit of the token that was served there, teacher-forced over
    the prompt and what the program served (prefill, then decode through the
    pages), and never under ``REGRET_FLOOR``. As for the other routed family
    (``bailing_hybrid.py``) the floor makes ``token_gap_sq`` the square of
    the mean regret over EVERY served token (``check.explainer_numbers``
    takes its mean over the values above zero): a routed model in bfloat16
    flips an expert choice somewhere in its layers for a share of the tokens
    whatever the arithmetic does, so how many served tokens are off the best
    and by how much both follow the noise, and their product tells the
    family's lower precision from the stated one.
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


def _layer_params(cfg: dict, *, held: bool = True) -> int:
    """A layer's weights without its four block norms; ``held=False`` leaves
    the routed experts' stacks out (what every token multiplies whatever it
    chose, plus the small vectors)."""
    return sum(_numel(shape) for _, _, shape, made, _ in layer_leaves(cfg)
               if held or made != "experts")


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def param_count(cfg: dict) -> int:
    """Every weight held: layers, their four block norms, final norm,
    embedding and head over the held vocabulary rows."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    heads = V * D * (1 if cfg["tie_word_embeddings"] else 2)
    return cfg["num_layers"] * (_layer_params(cfg) + 4 * D) + heads + D


def _held_share(cfg: dict) -> float:
    """Chance that one of a token's picks, even over the router's outputs,
    lands on an expert held here."""
    return cfg["n_routed_experts"] / router_width(cfg)


def expected_experts_touched(cfg: dict, rows: float) -> float:
    """Distinct held experts that ``rows`` tokens touch in one layer under
    even routing: a token picks ``moe_topk`` distinct ones of the router's
    outputs, so it misses a given expert with chance 1 - k/n."""
    miss = 1.0 - cfg["moe_topk"] / router_width(cfg)
    return cfg["n_routed_experts"] * (1.0 - miss ** rows)


def _token_flops(cfg: dict) -> float:
    """FLOPs one token costs outside attention over the context: 2 a weight
    it multiplies — every layer without its routed stacks, plus its expected
    picks on held experts — and for its zero-compute picks nothing but the
    one multiply-add of the layer's input (2 a channel)."""
    picks_held = cfg["moe_topk"] * _held_share(cfg)
    per_layer = (_layer_params(cfg, held=False)
                 + picks_held * _expert_params(cfg) + cfg["hidden_size"])
    return 2.0 * cfg["num_layers"] * per_layer


def latent_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """What one latent-attention sub-layer caches of a token."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def _step_params(cfg: dict) -> int:
    """Weights every step reads whatever is routed: every layer outside its
    routed stacks with its four norms, the head and the final norm."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    return cfg["num_layers"] * (_layer_params(cfg, held=False) + 4 * D) + V * D + D


def decode_cost(cfg: dict, steps: float, row_steps: float,
                mean_context: float, itemsize: int = 2,
                experts_touched: float = None) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``steps`` decode steps in which ``row_steps`` rows
    decoded, each holding ``mean_context`` tokens on average. A step reads
    once: every layer's weights outside the routed stacks, the head, and of
    each layer the held experts its rows touch: ``experts_touched``, the
    program's own count summed over these steps and the layers
    (``moe_experts_touched``), or without it the expected distinct count
    under even routing (``expected_experts_touched``). A row-step reads the
    latents of the tokens it holds in each of the two latent-attention
    sub-layers of every layer (absorbed: scores and values against the
    latent) and writes one; its FLOPs are ``_token_flops``, the head and the
    attention. A zero-compute pick reads nothing."""
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    n, n_mla = cfg["num_layers"], 2 * cfg["num_layers"]
    rows = row_steps / steps if steps else 0.0
    if experts_touched is None:
        experts_touched = steps * n * expected_experts_touched(cfg, rows)
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    attend = 2.0 * H * ((r + rope) + r) * (mean_context + 1)
    flops = row_steps * (_token_flops(cfg) + 2.0 * V * D + n_mla * attend)
    nbytes = ((steps * _step_params(cfg) + experts_touched * _expert_params(cfg))
              * itemsize
              + row_steps * n_mla * latent_bytes_per_token(cfg, itemsize)
              * (mean_context + 2))
    return flops, nbytes


def prefill_cost(cfg: dict, prefix_len: int, suffix_len: int,
                 itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) to prefill the REAL ``suffix_len`` tokens behind
    ``prefix_len`` cached ones: every suffix token costs ``_token_flops``
    (picks on held experts only, a multiply-add for its zero-compute picks);
    each latent-attention sub-layer expands K and V of everything resident
    and attends causally; the head runs once. Bytes: every weight once (of
    the routed stacks the experts the suffix touches), the prefix's latents
    read and the suffix's written."""
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    n, n_mla = cfg["num_layers"], 2 * cfg["num_layers"]
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    ctx_sum = suffix_len * prefix_len + suffix_len * (suffix_len + 1) / 2.0
    expand = 2.0 * r * H * (nope + dv) * (prefix_len + suffix_len)
    attend = 2.0 * H * ((nope + rope) + dv) * ctx_sum
    flops = (suffix_len * _token_flops(cfg) + n_mla * (expand + attend)
             + 2.0 * V * D)
    weights = (_step_params(cfg)
               + n * expected_experts_touched(cfg, suffix_len)
               * _expert_params(cfg))
    nbytes = (weights * itemsize
              + n_mla * latent_bytes_per_token(cfg, itemsize)
              * (prefix_len + suffix_len))
    return flops, nbytes
