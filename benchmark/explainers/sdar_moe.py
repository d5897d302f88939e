"""The explainer family ``sdar_moe``: a routed decoder that generates by
diffusion over blocks, told by the keys of ``JetLM/SDAR-30B-A3B-Chat``'s
``config.json`` (Qwen3-MoE's decoder layer, which SDAR's modeling keeps) and
by the block-diffusion settings under the configuration's
``block_diffusion`` key, which the catalog row does not carry. The contract
with the harness is the header of ``explainers/internlm2.py``; everything
of it is in this file, and only ``build`` imports the program.

**The stage.** A configuration of this family is ONE pipeline stage of a
deployment whose stages hold ``num_hidden_layers`` layers each, every layer
whole: all ``num_experts`` experts, and the whole vocabulary in the
embedding and the untied head (held here because the lane samples here).

**The equations** (``eps`` = ``rms_norm_eps``, ``D`` = ``hidden_size``,
``d`` = ``head_dim``; weights N(0, 1/fan_in) from the seed, rounded to the
serving dtype, the embedding N(0, 1); projections are stored (in, heads, d)
and rotary pairs are the interleaved lanes (2i, 2i+1), a fixed permutation
of random weights). Every layer, pre-norm on one residual:

    h = x + Attn(RMSNorm(x))          out = h + MoE(RMSNorm(h))

* Attention: ``q = W_q n`` (H x d), ``k = W_k n``, ``v = W_v n`` (Hkv x d),
  no bias; ``q <- RMSNorm_d(q) * g_q``, ``k <- RMSNorm_d(k) * g_k`` per head
  (Qwen3's, no config key) ahead of RoPE over all d lanes (``rope_theta``,
  no scaling); scores ``q.k / sqrt(d)``, float32 softmax, each group of
  H / Hkv query heads on its one key-value head, **causal by blocks**: the
  query at position i sees key j iff ``j // B <= i // B``; ``W_o o``.
* Experts: ``s = softmax(W_r n)`` over all ``num_experts`` in float32; the
  ``num_experts_per_tok`` largest, renormalised over their sum
  (``norm_topk_prob``); ``MoE = sum_e w_e W_d,e(SiLU(W_g,e n) * W_u,e n)``,
  width ``moe_intermediate_size``; no shared expert.
* Final RMSNorm, untied head over all ``vocab_size`` ids.

**Generation** (SDAR's block diffusion; ``block_diffusion`` in the file):
blocks of B = ``block_length`` positions. The prompt's whole blocks are
prefilled; its last ``len mod B`` tokens stand committed at the head of the
first block, whose other positions hold ``mask_token_id``. A denoising step
runs the block against everything before it and itself, takes each
position's argmax (never the mask id) and its float32 softmax probability
as confidence, and commits, most confident first (ties to the lower
position), ``n // steps`` of the n positions that started masked, one more
in the first ``n % steps`` steps (``low_confidence_static``, the one
schedule the family serves). A block with no mask left is served; a row
stops after the block that serves EOS or its budget.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

# the benchmark's own seed key and RMS norm: one copy, shared with the other
# families' references
from benchmark.reference import _rms, _root_key

REFERENCE_BLOCK = 4       # requests a reference pass holds at once (32 heads'
#                           float32 scores of ~2,300 positions: 0.7 GB a request)
REGRET_FLOOR = 1e-9       # least regret of a served token (see token_gaps)
QK_GAIN = 2.0             # the q and k norms' gammas (see make_params)


# ---------------------------------------------------------------------------
# shapes (no program import)
# ---------------------------------------------------------------------------

def diffusion(cfg: dict) -> dict:
    """The block-diffusion settings: block length, denoising steps a block
    and the mask id, under the low-confidence static schedule."""
    d = cfg["block_diffusion"]
    if d["remasking_strategy"] != "low_confidence_static":
        raise ValueError("this family serves the low_confidence_static "
                         f"schedule, not {d['remasking_strategy']!r}")
    return {"block": int(d["block_length"]), "steps": int(d["denoising_steps"]),
            "mask_id": int(d["mask_token_id"])}


def layer_kinds(cfg: dict) -> List[Tuple[str, str]]:
    """Every layer held is attention over experts: ``decoder_sparse_step``
    1 and no ``mlp_only_layers``."""
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("this family serves an expert layer in every layer")
    return [("attention", "experts")] * cfg["num_hidden_layers"]


def layer_leaves(cfg: dict) -> List[tuple]:
    """One layer's weights in the order their keys are drawn: (name, shape,
    how made, fan_in). A shape that starts with the expert count is one
    matrix per expert, each from its own key (the expert's index folded
    in)."""
    D, H, Hkv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    return [("wq", (D, H, d), "normal", D), ("wk", (D, Hkv, d), "normal", D),
            ("wv", (D, Hkv, d), "normal", D), ("wo", (H, d, D), "normal", H * d),
            ("q_norm", (d,), "qk_gain", 1), ("k_norm", (d,), "qk_gain", 1),
            ("moe_router", (D, E), "normal", D),
            ("moe_bias", (E,), "zeros_f32", 1),
            ("moe_wg", (E, D, F), "experts", D),
            ("moe_wu", (E, D, F), "experts", D),
            ("moe_wd", (E, F, D), "experts", F)]


# ---------------------------------------------------------------------------
# the model the slot lane serves (the only importer of the program)
# ---------------------------------------------------------------------------

def build(cfg: dict, params: dict, weights: str):
    import jax.numpy as jnp

    from fraud_detection_tpu.models import llm
    from fraud_detection_tpu.models.block_diffusion import BlockDiffusionConfig

    if (cfg["attention_bias"] or cfg["tie_word_embeddings"]
            or cfg["use_sliding_window"] or cfg["rope_scaling"] is not None):
        raise ValueError("this family serves attention without bias, window "
                         "or rope scaling, and an untied head")
    tcfg = llm.TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"], max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        dtype=jnp.dtype(cfg["torch_dtype"]).type,
        n_kv_heads=cfg["num_key_value_heads"], head_dim_override=cfg["head_dim"],
        activation=cfg["hidden_act"], tie_embeddings=False,
        rms_eps=float(cfg["rms_norm_eps"]), layer_kinds=tuple(layer_kinds(cfg)),
        qk_norm=True,
        moe=llm.MoEConfig(
            n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
            n_group=1, topk_group=1, d_expert=cfg["moe_intermediate_size"],
            d_shared=0, routed_scale=1.0, score="softmax",
            norm_topk=bool(cfg["norm_topk_prob"]), held_start=0,
            held_count=cfg["num_experts"]),
        diffusion=BlockDiffusionConfig(**diffusion(cfg)))
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
    if made == "qk_gain":
        return jnp.full(shape, QK_GAIN, dtype)
    if made == "zeros_f32":
        return jnp.zeros(shape, "float32")
    if made == "experts":           # expert e's matrix from fold_in(key, e)
        w = jax.vmap(lambda e: jax.random.normal(
            jax.random.fold_in(key, e), shape[1:], "float32"))(
                jnp.arange(shape[0]))
    else:
        w = jax.random.normal(key, shape, "float32")
    return (w / math.sqrt(fan_in)).astype(dtype)


def _table(key, cfg: dict, which: int, dtype):
    """The embedding (``which`` 0, N(0, 1)) or the head (1, N(0, 1/D)),
    (V, D) each."""
    import jax

    D = cfg["hidden_size"]
    return _leaf(jax.random.fold_in(key, which), (cfg["vocab_size"], D),
                 "normal", 1 if which == 0 else D, dtype)


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
    for l in range(n):
        key = jax.random.fold_in(root, l)
        for j, (name, shape, made, fan_in) in enumerate(layer_leaves(cfg)):
            p[f"l{l}.{name}"] = make(jax.random.fold_in(key, j), shape, made,
                                     fan_in, dtype)
        p[f"l{l}.ln1"] = p[f"l{l}.ln2"] = ones
    for name, which in (("embed", 0), ("lm_head", 1)):
        p[name] = jax.jit(lambda k: _table(k, cfg, which, dtype))(
            jax.random.fold_in(root, n))
    p["ln_f"] = ones
    return p


# ---------------------------------------------------------------------------
# the plain reference (float32, "highest"; no cache, no kernels, no pages)
# ---------------------------------------------------------------------------

def _ref_weights(key, cfg: dict, names: Sequence[str], dtype) -> dict:
    """The named leaves of one layer again from the layer's key, upcast to
    float32 from the serving dtype's values."""
    import jax
    import jax.numpy as jnp

    return {name: _leaf(jax.random.fold_in(key, j), shape, made, fan_in,
                        dtype).astype(jnp.float32)
            for j, (name, shape, made, fan_in) in enumerate(layer_leaves(cfg))
            if name in names}


def _rope(x, pos, theta: float):
    """x (R,T,H,d) at positions pos (R,T): lanes (2i, 2i+1) turn by
    position * theta^(-2i/d)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _ref_attention(w: dict, cfg: dict, hn, pos, sees, ctx_k, ctx_v):
    """hn (R,T,D) normed at positions pos (R,T) -> (Attn(hn), its k, its v):
    every query head on its group's key-value head, over the keys of
    ``ctx_k`` / ``ctx_v`` (R,S,Hkv,d: positions computed before, S may be 0)
    and then hn's own, query t on those ``sees`` (R,T,S+T) allows."""
    import jax
    import jax.numpy as jnp

    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    rep = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    q = _rope(_rms(jnp.einsum("btD,Dhd->bthd", hn, w["wq"]), eps) * w["q_norm"],
              pos, theta)
    k = _rope(_rms(jnp.einsum("btD,Dhd->bthd", hn, w["wk"]), eps) * w["k_norm"],
              pos, theta)
    v = jnp.einsum("btD,Dhd->bthd", hn, w["wv"])
    keys = jnp.repeat(jnp.concatenate([ctx_k, k], 1), rep, axis=2)
    vals = jnp.repeat(jnp.concatenate([ctx_v, v], 1), rep, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, keys) / math.sqrt(q.shape[-1])
    s = jnp.where(sees[:, None], s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), vals)
    return jnp.einsum("bthd,hdD->btD", o, w["wo"]), k, v


def _ref_choice(w: dict, cfg: dict, u):
    """u (N,D) -> (N, experts): the weight of every expert in each token's
    sum, zero where it was not chosen."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.softmax(u @ w["moe_router"], -1)
    rank = jnp.argsort(jnp.argsort(-(s + w["moe_bias"]), -1, stable=True), -1)
    chosen = jnp.where(rank < cfg["num_experts_per_tok"], s, 0.0)
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    return chosen


def expert_layer(key, cfg: dict, dtype, u):
    """``MoE(u)`` for u (N,D) normed: every expert applied to every token,
    one expert at a time from its own keys, masked by the weights (zero
    where the token did not choose it)."""
    import jax
    import jax.numpy as jnp

    weight = _ref_choice(_ref_weights(key, cfg, ("moe_router", "moe_bias"),
                                      dtype), cfg, u)
    leaves = {name: (j, shape, fan_in) for j, (name, shape, made, fan_in)
              in enumerate(layer_leaves(cfg)) if made == "experts"}

    def mat(name, e):
        j, shape, fan_in = leaves[name]
        k = jax.random.fold_in(jax.random.fold_in(key, j), e)
        return (jax.random.normal(k, shape[1:], "float32")
                / math.sqrt(fan_in)).astype(dtype).astype(jnp.float32)

    def one(e, y):
        hid = jax.nn.silu(u @ mat("moe_wg", e)) * (u @ mat("moe_wu", e))
        return y + jax.lax.dynamic_slice_in_dim(weight, e, 1, 1) * (
            hid @ mat("moe_wd", e))

    return jax.lax.fori_loop(0, cfg["num_experts"], one, jnp.zeros_like(u))


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
        raise ValueError("the plain reference implements SiLU-gated experts")

    @jax.jit
    def embed(key, tokens):
        return _table(key, cfg, 0, dtype)[tokens].astype(jnp.float32)

    # One jitted piece a sub-block, so that no more than one sub-block's
    # float32 weights stand beside the activations.
    @jax.jit
    def attend(key, x, pos, sees, ctx_k, ctx_v):
        with jax.default_matmul_precision("highest"):
            w = _ref_weights(key, cfg, ("wq", "wk", "wv", "wo", "q_norm",
                                        "k_norm"), dtype)
            out, k, v = _ref_attention(w, cfg, _rms(x, eps), pos, sees,
                                       ctx_k, ctx_v)
            return x + out, k, v

    @jax.jit
    def experts(key, x):
        with jax.default_matmul_precision("highest"):
            u = _rms(x, eps)
            return x + expert_layer(key, cfg, dtype, u.reshape(
                -1, u.shape[-1])).reshape(u.shape)

    @jax.jit
    def logits(key, x):
        with jax.default_matmul_precision("highest"):
            return _rms(x, eps) @ _table(key, cfg, 1, dtype).astype(
                jnp.float32).T

    return embed, attend, experts, logits


def block_causal(n: int, block: int) -> np.ndarray:
    """(n, n) bool: position i sees j iff j // block <= i // block."""
    b = np.arange(n) // block
    return b[None, :] <= b[:, None]


def _layers(seed: int, cfg: dict, dtype_name: str, tokens, positions, sees,
            ctx=None, at=None):
    """Tokens (R,T) at ``positions`` through every layer, each attending the
    layer's ``ctx`` (k, v of positions computed before; none by default)
    and its own positions as ``sees`` (R,T,S+T) allows. Returns (logits at
    the columns ``at`` (R,A) of each row, or at every one; each layer's
    (k, v))."""
    import jax
    import jax.numpy as jnp

    R = tokens.shape[0]
    n = cfg["num_hidden_layers"]
    root = _root_key(seed)
    embed, attend, experts, logits = _reference_fns(cfg, dtype_name)
    x = embed(jax.random.fold_in(root, n), jnp.asarray(tokens))
    pos, sees = jnp.asarray(positions, jnp.int32), jnp.asarray(sees)
    if ctx is None:
        none = jnp.zeros((R, 0, cfg["num_key_value_heads"], cfg["head_dim"]),
                         jnp.float32)
        ctx = [(none, none)] * n
    kv = []
    for l in range(n):
        key = jax.random.fold_in(root, l)
        x, k, v = attend(key, x, pos, sees, *ctx[l])
        x = experts(key, x)
        kv.append((k, v))
    if at is not None:
        x = x[jnp.arange(R)[:, None], jnp.asarray(at)]
    return logits(jax.random.fold_in(root, n), x), kv


def reference_logits(seed: int, cfg: dict, dtype_name: str, tokens,
                     positions=None, sees=None, at=None):
    """The reference's logits for token rows (R,T): at every position, or
    at the columns ``at`` (R,S) of each row. ``positions`` (R,T) default to
    the columns, ``sees`` (R,T,T) to causal by blocks."""
    tokens = np.asarray(tokens)
    R, T = tokens.shape
    if positions is None:
        positions = np.broadcast_to(np.arange(T), (R, T))
    if sees is None:
        sees = np.broadcast_to(block_causal(T, diffusion(cfg)["block"]),
                               (R, T, T))
    return _layers(seed, cfg, dtype_name, tokens, positions, sees, at=at)[0]


def commit_order(conf: np.ndarray, masked: np.ndarray,
                 count: int) -> np.ndarray:
    """Positions of one block a denoising step commits: the ``count`` most
    confident masked ones, ties to the lower position."""
    where = np.flatnonzero(masked)
    return where[np.lexsort((where, -conf[where]))][:count]


def _trajectories(requests: Sequence[dict], d: dict, pad_to: int,
                  n_blocks: int):
    """Per request, the x_t (+) x_0 layout: the clean prompt and served
    tokens in ``pad_to`` columns, causal by blocks among themselves, and a
    noisy copy of each generated block beside them at the block's own
    positions, seeing the clean positions before its block and itself,
    whole (no clean position sees a noisy one). Returns (clean tokens
    (R,pad_to), the noisy copies' positions (R,N) and what they see
    (R,N,pad_to+N), and per request (tail, served))."""
    B = d["block"]
    R, N = len(requests), n_blocks * d["block"]
    clean = np.zeros((R, pad_to), np.int32)
    positions = np.zeros((R, N), np.int32)
    sees = np.zeros((R, N, pad_to + N), bool)
    meta = []
    for r, req in enumerate(requests):
        prompt = np.asarray(req["prompt"], np.int32)
        served = np.asarray(req["served"], np.int32)
        seq = np.concatenate([prompt, served])
        if len(seq) > pad_to or len(prompt) % B + len(served) > N:
            raise ValueError(f"request of {len(seq)} tokens exceeds the "
                             f"reference's {pad_to} + {n_blocks} blocks")
        clean[r, :len(seq)] = seq
        tail = len(prompt) % B
        base = len(prompt) - tail
        positions[r] = base + np.arange(N)
        for b in range(n_blocks):
            rows = slice(b * B, (b + 1) * B)
            sees[r, rows, :base + b * B] = True
            sees[r, rows, pad_to + b * B:pad_to + (b + 1) * B] = True
        meta.append((tail, served))
    return clean, positions, sees, meta


def token_gaps(seed: int, cfg: dict, dtype_name: str,
               requests: Sequence[dict], pad_to: int) -> List[np.ndarray]:
    """For each request ``{"prompt": int tokens, "served": int tokens}`` the
    regret of every served token along a diffusion trajectory of the
    reference, teacher-forced: beside the clean sequence (prompt and served
    tokens, causal by blocks) a noisy copy of every generated block at
    denoising step s, each seeing the clean blocks before it and itself
    (SDAR's x_t (+) x_0 training layout), one forward a step for all of a
    request's blocks. At step s each block commits the schedule's count of
    its masked positions, the served token placed at a served position (the
    reference's own argmax at a position past what was served: EOS or the
    budget); the regret of a served token is the reference's best logit
    (never the mask id) minus the served token's, at the step where its
    position is committed, and never under ``REGRET_FLOOR``. Which positions
    a step commits is the reference's own confidence ranking alone
    (``commit_order``), as the program's schedule ranks its own.

    As for the other routed families the floor makes ``token_gap_sq`` the
    square of the mean regret over EVERY served token
    (``check.explainer_numbers`` takes its mean over the values above zero):
    a routed model in bfloat16 flips expert choices somewhere for a share of
    the tokens whatever the arithmetic does, and a bfloat16 program ranks
    confidences that lie close by its own rounding.
    ``REFERENCE_BLOCK`` requests side by side."""
    d = diffusion(cfg)
    B, mask_id = d["block"], d["mask_id"]
    span = max((len(r["served"]) for r in requests), default=0)
    max_new = cfg.get("desk", {}).get("explain", {}).get("max_new_tokens", span)
    n_blocks = -(-(B - 1 + max(span, max_new)) // B)
    group = min(REFERENCE_BLOCK, max(1, len(requests)))
    out = []
    for lo in range(0, len(requests), group):
        part = list(requests[lo:lo + group])
        part += [{"prompt": [0], "served": []}] * (group - len(part))
        clean, positions, sees, meta = _trajectories(part, d, pad_to, n_blocks)
        # the clean positions' k and v once: no clean position sees a noisy
        # one, so every step's forward of the noisy copies attends the same
        _, ctx = _layers(seed, cfg, dtype_name, clean,
                         np.broadcast_to(np.arange(pad_to), clean.shape),
                         np.broadcast_to(block_causal(pad_to, B),
                                         (group, pad_to, pad_to)),
                         at=np.zeros((group, 1), np.int32))
        noisy = np.full((group, n_blocks * B), mask_id, np.int32)
        n_new = np.full((group, n_blocks), B, np.int32)
        for r, (tail, _) in enumerate(meta):
            noisy[r, :tail] = clean[r, len(part[r]["prompt"]) - tail:
                                    len(part[r]["prompt"])]
            n_new[r, 0] = B - tail
        regret = [np.full(len(s), np.nan) for _, s in meta]
        for step in range(d["steps"]):
            lg = np.asarray(_layers(seed, cfg, dtype_name, noisy, positions,
                                    sees, ctx)[0], np.float64)
            lg[..., mask_id] = -np.inf
            best = lg.max(-1)
            conf = 1.0 / np.exp(lg - best[..., None]).sum(-1)
            masked = noisy == mask_id
            for r, (tail, served) in enumerate(meta):
                for b in range(-(-(tail + len(served)) // B)):
                    cols = slice(b * B, (b + 1) * B)
                    count = (n_new[r, b] // d["steps"]
                             + int(step < n_new[r, b] % d["steps"]))
                    for c in commit_order(conf[r, cols], masked[r, cols],
                                          count):
                        col = b * B + int(c)
                        i = col - tail                  # index among served
                        if i < len(served):
                            noisy[r, col] = served[i]
                            regret[r][i] = best[r, col] - lg[r, col, served[i]]
                        else:
                            noisy[r, col] = int(np.argmax(lg[r, col]))
        for r in range(len(requests[lo:lo + group])):
            if np.isnan(regret[r]).any():
                raise ValueError("a served position the schedule never "
                                 "committed")
            out.append(np.maximum(regret[r], REGRET_FLOOR))
    return out


# ---------------------------------------------------------------------------
# operations and bytes the algorithm needs (shapes alone)
# ---------------------------------------------------------------------------

def _numel(shape) -> int:
    return int(np.prod(shape))


def _layer_params(cfg: dict, *, held: bool = True) -> int:
    """A layer's weights without its two block norms; ``held=False`` leaves
    the experts' stacks out (what every position multiplies whatever it
    chose, plus the small vectors)."""
    return sum(_numel(shape) for _, shape, made, _ in layer_leaves(cfg)
               if held or made != "experts")


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def param_count(cfg: dict) -> int:
    """Every weight held: layers, their two block norms, the final norm, the
    embedding and the untied head."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    return (cfg["num_hidden_layers"] * (_layer_params(cfg) + 2 * D)
            + 2 * V * D + D)


def expected_experts_touched(cfg: dict, rows: float) -> float:
    """Distinct experts that ``rows`` positions touch in one layer under
    even routing: a position picks ``num_experts_per_tok`` distinct ones of
    ``num_experts``, so it misses a given expert with chance 1 - k/E."""
    E = cfg["num_experts"]
    return E * (1.0 - (1.0 - cfg["num_experts_per_tok"] / E) ** rows)


def _step_params(cfg: dict) -> int:
    """Weights every forward reads whatever is routed, but the head: every
    layer outside its experts' stacks with its two norms, the final norm."""
    D = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * (_layer_params(cfg, held=False)
                                       + 2 * D) + D


def _position_flops(cfg: dict) -> float:
    """FLOPs one position costs outside attention over the context and the
    head: 2 a weight it multiplies, every layer outside the experts' stacks
    and its ``num_experts_per_tok`` picks."""
    return 2.0 * cfg["num_hidden_layers"] * (
        _layer_params(cfg, held=False)
        + cfg["num_experts_per_tok"] * _expert_params(cfg))


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """What one layer caches of a position: k and v."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def denoise_share(cfg: dict) -> float:
    """Share of a full block's forwards that denoise (and read the head):
    as many as the schedule has steps that commit something, then the one
    commit forward."""
    d = diffusion(cfg)
    n = min(d["steps"], d["block"])
    return n / (n + 1.0)


def decode_cost(cfg: dict, steps: float, row_steps: float,
                mean_context: float, itemsize: int = 2,
                experts_touched: float = None) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``steps`` forwards of the pool in which
    ``row_steps`` rows ran a block of B positions each, every row holding
    ``mean_context`` tokens on average. A forward reads once: every layer's
    weights outside the experts' stacks, and of each layer the experts its
    B x rows positions touch: ``experts_touched``, a count summed over these
    forwards and the layers, or without it the expected distinct count under
    even routing (``expected_experts_touched``), and the head wherever one
    of its rows denoises (every forward but a vanishing share at the pool's
    rows: all of them commit at once with chance (1 - ``denoise_share``) to
    the power of the rows). A row-forward's positions read the k/v of the
    row's tokens and their own block's in each layer, and a commit writes
    the block's; their FLOPs are ``_position_flops``, the attention over
    the context and the block, and the head where the row denoises."""
    d = diffusion(cfg)
    B = d["block"]
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    n = cfg["num_hidden_layers"]
    rows = row_steps / steps if steps else 0.0
    if experts_touched is None:
        experts_touched = steps * n * expected_experts_touched(cfg, B * rows)
    share = denoise_share(cfg)
    heads = steps * (1.0 - (1.0 - share) ** rows) if steps else 0.0
    keys = mean_context + B
    attend = 4.0 * H * cfg["head_dim"] * keys
    flops = row_steps * B * (_position_flops(cfg) + n * attend
                             + share * 2.0 * V * D)
    nbytes = ((steps * _step_params(cfg) + heads * V * D
               + experts_touched * _expert_params(cfg)) * itemsize
              + row_steps * n * kv_bytes_per_token(cfg, itemsize)
              * (keys + (1.0 - share) * B))
    return flops, nbytes


def prefill_cost(cfg: dict, prefix_len: int, suffix_len: int,
                 itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) to prefill a prompt of ``prefix_len + suffix_len``
    tokens whose first ``prefix_len`` are the shared preamble: the program
    caches the preamble's whole blocks and prefills the prompt's whole
    blocks past them, causal by blocks (its last partial block seeds the
    first generated block, which ``decode_cost`` counts). Every prefilled
    position costs ``_position_flops``; position i of the prompt attends
    every position of the blocks up to its own; the head runs once. Bytes:
    every weight once, of the experts' stacks the experts the suffix
    touches, each ONCE; the cached k/v read and the suffix's written."""
    B = diffusion(cfg)["block"]
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    n = cfg["num_hidden_layers"]
    cached = prefix_len // B * B
    blocks = ((prefix_len + suffix_len) // B * B - cached) // B
    real = blocks * B
    ctx_sum = real * cached + B * B * blocks * (blocks + 1) / 2.0
    flops = (real * _position_flops(cfg)
             + n * 4.0 * H * cfg["head_dim"] * ctx_sum + 2.0 * V * D)
    weights = (_step_params(cfg) + V * D
               + n * expected_experts_touched(cfg, real) * _expert_params(cfg))
    nbytes = (weights * itemsize
              + n * kv_bytes_per_token(cfg, itemsize) * (cached + real))
    return flops, nbytes
