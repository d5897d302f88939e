"""Generation by diffusion over blocks (SDAR's block diffusion) in the slot
lane: the one device program that advances a pool of rows a block at a time.

A model that generates this way (``TransformerConfig.diffusion``) attends
causally by blocks everywhere: a position sees every earlier block and the
whole of its own (``llm._sees_to``). A row's prompt is prefilled in whole
blocks by ``llm.paged_slot_prefill``; its last ``len mod block`` tokens, if
any, stand already committed at the head of its first generation block,
whose other positions start as ``mask_id``. Each forward of
:func:`paged_block_window` then runs every live row's block of ``block``
positions against the row's pages and, bidirectionally, against itself:

* a row whose block still holds a mask **denoises**: the head reads the
  block's positions, each gets an id (greedy: the argmax; with a
  temperature: a draw) and a confidence (that id's float32 softmax
  probability; ``mask_id`` is never chosen), and the masked positions are
  ranked by confidence (ties to the lower position) and the first as many
  as the schedule allows are committed (``commit_count``);
* a row whose block holds no mask **commits** the block: the forward's K/V
  of its positions are final, they go to the row's pages, and the next block
  starts as all masks. It reads no logits.

A block is served as soon as its last mask is committed, 1 to ``block``
tokens at once (the prompt's tail is not served), and a row stops after the
block that serves EOS or reaches its budget: the commit forward of that
block is never run. What a window leaves in flight (a block's ids, its
denoising step, how many of its positions started masked) rides across the
window boundary in the arrays the program takes and gives back.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from fraud_detection_tpu.models import llm

# what the program counts, packed in this order beside the model's own
# counters: row-forwards of live rows (commit forwards included), positions
# committed by denoising, blocks whose K/V a commit forward wrote
DIFFUSION_STATS = ("row_forwards", "tokens_committed", "blocks_committed")


@dataclass(frozen=True)
class BlockDiffusionConfig:
    """``block`` positions a block (a power of two), ``steps`` denoising
    steps a block (the low-confidence schedule: step s of a block that
    started with n masks commits ``n // steps`` positions, one more for the
    first ``n % steps`` steps, the most confident first), and the id a
    masked position holds, which is never served."""

    block: int = 4
    steps: int = 2
    mask_id: int = 258

    def __post_init__(self):
        if self.block < 1 or self.block & (self.block - 1):
            raise ValueError(f"block must be a power of two, got {self.block}")
        if self.steps < 1:
            raise ValueError(f"steps must be >= 1, got {self.steps}")


def check_model(cfg: llm.TransformerConfig) -> None:
    """A model this module serves: every layer attends (no layer keeps a
    row state or a latent cache), and the mask id is a row of its head."""
    d = cfg.diffusion
    if d is None:
        raise ValueError("the model does not generate by diffusion over "
                         "blocks (cfg.diffusion is None)")
    if any(mixer != "attention" for mixer, _ in cfg.kinds):
        raise ValueError("generation by diffusion over blocks serves "
                         "attention layers only")
    if not cfg.EOS < d.mask_id < cfg.vocab_size:
        raise ValueError(f"mask id {d.mask_id} must be a held id past the "
                         f"byte tokenizer's ({cfg.EOS})")


def window_positions(d: BlockDiffusionConfig, steps: int) -> int:
    """Positions a window of ``steps`` forwards can commit to a row's pages
    at most: a commit forward follows at least one denoising forward of its
    block, but a window may open on one."""
    return (steps + 1) // 2 * d.block


def commit_count(d: BlockDiffusionConfig, n_new: jax.Array,
                 step: jax.Array) -> jax.Array:
    """Positions denoising step ``step`` commits in a block that started
    with ``n_new`` masks (the low-confidence static schedule)."""
    return n_new // d.steps + (step < n_new % d.steps).astype(jnp.int32)


def choose(logits: jax.Array, temperature: jax.Array, key: jax.Array,
           mask_id: int):
    """(ids, confidences) of N positions from their (N, V) float32 logits:
    where ``temperature`` (N,) is 0 the argmax and its softmax probability,
    elsewhere a draw from softmax(logits / temperature), position n from
    ``fold_in(key, n)``, and the drawn id's probability under it. The mask
    id is never chosen."""
    logits = logits.at[:, mask_id].set(-jnp.inf)
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    top = jnp.max(logits, -1, keepdims=True)
    sure = 1.0 / jnp.sum(jnp.exp(logits - top), -1)
    hot = temperature > 1e-6

    def drawn(_):
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        keys = jax.vmap(partial(jax.random.fold_in, key))(
            jnp.arange(logits.shape[0]))
        ids = jax.vmap(lambda k, lg: jax.random.categorical(k, lg, -1))(
            keys, scaled).astype(jnp.int32)
        p = jnp.take_along_axis(jax.nn.softmax(scaled, -1), ids[:, None],
                                -1)[:, 0]
        return jnp.where(hot, ids, greedy), jnp.where(hot, p, sure)

    return jax.lax.cond(jnp.any(hot), drawn, lambda _: (greedy, sure), None)


def commit_positions(d: BlockDiffusionConfig, blocks: jax.Array,
                     conf: jax.Array, n_new: jax.Array,
                     step: jax.Array) -> jax.Array:
    """(S, block) bool: the masked positions a denoising forward commits,
    ranked by ``conf`` (most confident first, ties to the lower position),
    as many as ``commit_count``."""
    masked = blocks == d.mask_id
    k = commit_count(d, n_new, step)[:, None]
    c = jnp.where(masked, conf, -jnp.inf)
    j = jnp.arange(blocks.shape[1])
    ahead = ((c[:, None, :] > c[:, :, None])
             | ((c[:, None, :] == c[:, :, None]) & (j[None, None, :] < j[:, None])))
    rank = jnp.sum(ahead & masked[:, None, :], -1)
    return masked & (rank < k)


def _block_forward(params: llm.Params, cfg: llm.TransformerConfig,
                   view: Dict[str, jax.Array], blocks: jax.Array,
                   lens: jax.Array, live: jax.Array):
    """Every row's block (S, block) at positions [lens, lens + block) through
    the layers: its K/V written into the gathered view there, its queries on
    the view's positions below lens + block (every earlier block, and its
    own whole). Returns (normed features (S, block, D), view, counters)."""
    S, B = blocks.shape
    positions = lens[:, None] + jnp.arange(B)[None, :]
    rows = jnp.arange(S)[:, None]
    x = llm._embed_rows(params["embed"], blocks, cfg.dtype)
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    act = llm._act(cfg)
    live_pos = jnp.broadcast_to(live[:, None], (S, B))
    stats = llm._zero_stats(cfg)
    view = dict(view)
    for l in range(cfg.n_layers):
        h = llm.rms_norm(x, params[f"l{l}.ln1"], cfg.rms_eps)
        q, k, v = llm._qkv(params, cfg, l, h, positions)
        with jax.named_scope("kv.append"):
            view[f"l{l}.k"] = view[f"l{l}.k"].at[rows, positions].set(k)
            view[f"l{l}.v"] = view[f"l{l}.v"].at[rows, positions].set(v)
        span = view[f"l{l}.k"].shape[1]
        sees = jnp.broadcast_to(
            jnp.arange(span)[None, None, :] < (lens + B)[:, None, None],
            (S, B, span))
        x = llm._attn_out(params, cfg, l, x, llm._attend(
            q, view[f"l{l}.k"], view[f"l{l}.v"], sees))
        x, stats, _ = llm._ffn(params, cfg, l, x, act, live_pos, stats)
    return llm.rms_norm(x, params["ln_f"], cfg.rms_eps), view, stats


def _window_loop(params, cfg: llm.TransformerConfig, view, blocks, lens,
                 step, n_new, active, remaining, temperature, rng, steps: int):
    """Up to ``steps`` forwards of the pool (:func:`paged_block_window`
    documents the arguments). Returns (out, counts, blocks, lens, step,
    n_new, forwards run, live row-forwards, view, model counters, diffusion
    counters)."""
    d = cfg.diffusion
    S, B = blocks.shape
    width = steps * B
    slot_of = jnp.arange(B)[None, :]
    temps = jnp.repeat(temperature, B)

    def cond(carry):
        return (carry[0] < steps) & jnp.any(carry[5])

    def body(carry):
        (i, blocks, lens, step, n_new, act, rem, view, out, count, n_act,
         stats, dstats) = carry
        masked = blocks == d.mask_id
        denoise = act & jnp.any(masked, -1)
        commit_fwd = act & ~denoise
        with jax.named_scope("diffusion.denoise"):
            x, view, new = _block_forward(params, cfg, view, blocks, lens, act)
            stats = {k: stats[k] + new[k] for k in stats}
        with jax.named_scope("diffusion.select"):
            ids, conf = jax.lax.cond(
                jnp.any(denoise),
                lambda xf: choose(llm._logits_head(xf, params, cfg), temps,
                                  jax.random.fold_in(rng, i), d.mask_id),
                lambda xf: (jnp.zeros((S * B,), jnp.int32),
                            jnp.zeros((S * B,), jnp.float32)),
                x.reshape(S * B, -1))
            ids, conf = ids.reshape(S, B), conf.reshape(S, B)
            commit = commit_positions(d, blocks, conf, n_new, step) \
                & denoise[:, None]
            blocks = jnp.where(commit, ids, blocks)
        with jax.named_scope("diffusion.commit"):
            step = step + denoise.astype(jnp.int32)
            # a block whose last mask is committed is served whole: its
            # positions that started masked, in order, at the row's cursor
            final = denoise & ~jnp.any(blocks == d.mask_id, -1)
            first = B - n_new
            src = jnp.minimum(first[:, None] + slot_of, B - 1)
            toks = jnp.take_along_axis(blocks, src, axis=1)
            at = jnp.where(final[:, None] & (slot_of < n_new[:, None]),
                           count[:, None] + slot_of, width)
            out = out.at[jnp.arange(S)[:, None], at].set(toks, mode="drop")
            count = count + jnp.where(final, n_new, 0)
            served = slot_of < jnp.minimum(n_new, rem)[:, None]
            eos = jnp.any(served & (toks == cfg.EOS), -1)
            rem = rem - jnp.where(final, n_new, 0)
            # the row stops after the block that serves EOS or its budget
            live_next = act & ~(final & (eos | (rem <= 0)))
            # a commit forward wrote its block's K/V: the next block opens
            lens = lens + jnp.where(commit_fwd, B, 0)
            blocks = jnp.where(commit_fwd[:, None], d.mask_id, blocks)
            step = jnp.where(commit_fwd, 0, step)
            n_new = jnp.where(commit_fwd, B, n_new)
            n_live = jnp.sum(act.astype(jnp.int32))
            dstats = dstats + jnp.stack([
                n_live, jnp.sum(commit.astype(jnp.int32)),
                jnp.sum(commit_fwd.astype(jnp.int32))])
        return (i + 1, blocks, lens, step, n_new, live_next, rem, view, out,
                count, n_act + n_live, stats, dstats)

    carry = (jnp.int32(0), blocks, lens, step, n_new, active, remaining, view,
             jnp.full((S, width), cfg.EOS, jnp.int32), jnp.zeros((S,), jnp.int32),
             jnp.int32(0), llm._zero_stats(cfg),
             jnp.zeros((len(DIFFUSION_STATS),), jnp.int32))
    (i, blocks, lens, step, n_new, _, _, view, out, count, n_act, stats,
     dstats) = jax.lax.while_loop(cond, body, carry)
    return (out, count, blocks, lens, step, n_new, i, n_act, view,
            llm._pack_stats(stats), dstats)


@partial(jax.jit, static_argnames=("cfg", "steps"))
def paged_block_window(params: llm.Params, blocks: jax.Array,
                       lens: jax.Array, step: jax.Array, n_new: jax.Array,
                       active: jax.Array, remaining: jax.Array,
                       cfg: llm.TransformerConfig,
                       kv_pages: Dict[str, jax.Array], tables: jax.Array,
                       temperature: jax.Array, rng: jax.Array, steps: int):
    """Up to ``steps`` forwards of the WHOLE slot pool, each a block of
    ``cfg.diffusion.block`` positions a row (module docstring): the block
    diffusion counterpart of ``llm.paged_decode_window``, over the same
    pages and tables.

    Per slot: ``blocks`` (S, block) the block in flight (committed ids and
    mask ids), ``lens`` the positions whose K/V are in the row's pages (the
    block sits at [lens, lens + block)), ``step`` its denoising steps so
    far, ``n_new`` its positions that started masked, ``active`` the rows
    that run, ``remaining`` their budget of tokens still to serve,
    ``temperature`` 0 for greedy. The allocator guarantees each live row's
    table covers ``lens + window_positions(steps)`` (the last page's
    overhang past the pool's length is masked like any position not yet
    written).

    Returns ``(out (S, steps * block) the tokens served in position order,
    EOS past each row's count, counts (S,), blocks, lens, step, n_new,
    forwards run, live row-forwards, pages, the model's counters (None for
    a model that counts nothing), the DIFFUSION_STATS counters)``; the host
    serves each row's ``out[:count]`` up to EOS or its budget, which is
    where the program stopped the row."""
    S = blocks.shape[0]
    num_pages, page = next(iter(kv_pages.values())).shape[:2]
    n_view = tables.shape[1]
    lens0 = lens
    (out, count, blocks, lens, step, n_new, i, n_act, view, stats,
     dstats) = _window_loop(params, cfg, llm._gather_view(kv_pages, tables),
                            blocks, lens, step, n_new, active, remaining,
                            temperature, rng, steps)
    # the committed blocks' K/V, view positions [lens0, lens), to the pages
    span = window_positions(cfg.diffusion, steps)
    pos = lens0[:, None] + jnp.arange(span)[None, :]
    pids = jnp.take_along_axis(tables, jnp.minimum(pos // page, n_view - 1),
                               axis=1)
    pids = jnp.where(pos < lens[:, None], pids, num_pages)     # dropped
    pos_c = jnp.minimum(pos, n_view * page - 1)
    new_pages = {}
    with jax.named_scope("diffusion.commit"):
        for name, arr in kv_pages.items():
            new_pages[name] = arr.at[pids, pos % page].set(
                view[name][jnp.arange(S)[:, None], pos_c])
    return (out, count, blocks, lens, step, n_new, i, n_act, new_pages, stats,
            dstats)
