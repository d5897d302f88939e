"""Pallas TPU flash attention for the on-pod LLM's single-chip path.

``models/llm.py _attend`` materializes the full (B, H, T, S) score matrix —
fine for short prompts, O(T^2) memory for long transcripts (the workload
SURVEY.md §5 long-context calls out). This kernel is the standard
flash-attention reformulation on TPU: block over (query, key) tiles, keep a
running row max / normalizer / output accumulator in VMEM scratch, and never
materialize scores — memory O(T * d) while both matmuls (q·k^T and p·v) run
on the MXU. The cross-chip analogue (sequence-parallel ring attention,
``models/llm.py ring_attention``) uses the same online-softmax algebra with
K/V blocks arriving over ICI instead of from HBM.

Causal-only by design: the decoder has no non-causal path, and causality is
what lets sequence padding ride for free (padded key columns sit above the
diagonal for every real query row, so the mask discards them).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fraud_detection_tpu.ops.histogram import _round_up

_NEG = -1e30  # mask value: exp(s - m) underflows to exactly 0, no inf-inf NaNs


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, blk_q: int, blk_k: int, n_k: int):
    """One (batch*head, q-block, k-block) cell. The grid runs k innermost, so
    the scratch accumulators carry across k blocks of one q block; the causal
    gate skips cells entirely above the diagonal (their K/V blocks still DMA,
    but the matmuls — the dominant cost — are skipped)."""
    qi = pl.program_id(1)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(si * blk_k <= qi * blk_q + (blk_q - 1))
    def _block():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (blk_q, blk_k)
        rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = si * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, _NEG)

        m_prev = m_ref[:, 0:1]                                 # (blk_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                                 # masked -> 0
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_ref[:, 0:1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(si == n_k - 1)
    def _emit():
        o_ref[0] = (acc_ref[:] / l_ref[:, 0:1]).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("blk_q", "blk_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    blk_q: int = 0, blk_k: int = 0,
                    interpret: bool = False) -> jax.Array:
    """Causal flash attention. q: (B, T, H, d); k/v: (B, T, Hkv, d) with
    H % Hkv == 0 — GQA/MQA kv stay at their NATIVE width and the kernel's
    index map hands each query head its group's K/V block, so nothing
    expands: on Gemma-2B (MQA, H=8, Hkv=1) the pre-r5 caller-side
    ``jnp.repeat`` materialized and streamed 8x the K/V bytes. Hkv == H
    recovers plain MHA. Returns (B, T, H, d). Matches
    ``_attend(q, expand(k), expand(v), tril)`` to f32 round-off; enforced
    by tests/test_flash_attention.py.

    ``blk_q``/``blk_k`` default (0) to shape-aware auto-selection: 512x512
    for T >= 512, else 128x128. Each query block re-streams ALL of K/V
    through VMEM, so K/V DMA scales as (T/blk_q)*T — on the 2B serving
    config the 128x128 default measured 16.0k prefill tok/s at T=8192
    (45.6% MFU) vs 26.8-27.5k at 512-wide blocks (76-78% MFU), with
    T=2048 improving 22.9k -> 27.9k too (device sweep, r5). 512x512 keeps
    the f32 score tile + accumulators comfortably inside VMEM (~3MB).
    Ragged T guard: wide blocks also widen t_pad, and padded q-blocks run
    both matmuls before being sliced off — so auto-selection takes the
    largest block adding at most ~12.5% padding over the 128-granularity
    floor (T=4000 -> 512 via 1.6% waste; T=640 stays 128, where 512
    would pad 60%)."""
    B, T, H, d = q.shape
    h_kv = k.shape[2]
    if H % h_kv or v.shape[2] != h_kv:
        raise ValueError(f"kv heads {k.shape[2]}/{v.shape[2]} must divide "
                         f"query heads {H}")
    rep = H // h_kv
    if not blk_q or not blk_k:
        floor = _round_up(T, 128)
        auto = next(b for b in (512, 256, 128)
                    if _round_up(T, b) * 8 <= floor * 9)
        blk_q = blk_q or auto
        blk_k = blk_k or auto
    scale = 1.0 / math.sqrt(d)
    d_pad = _round_up(d, 128)
    t_pad = _round_up(T, max(blk_q, blk_k))

    def prep(x):
        h = x.shape[2]
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(B * h, T, d)
        return jnp.pad(x, ((0, 0), (0, t_pad - T), (0, d_pad - d)))

    qf, kf, vf = prep(q), prep(k), prep(v)
    n_q, n_k = t_pad // blk_q, t_pad // blk_k

    def kv_row(b, qi, si):
        # grid row b = bi * H + hi over (B*H); its kv row is
        # bi * Hkv + hi // rep over (B*Hkv).
        return (b // H) * h_kv + (b % H) // rep, si, 0

    out = pl.pallas_call(
        partial(_flash_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k, n_k=n_k),
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, d_pad), lambda b, qi, si: (b, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_k, d_pad), kv_row,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_k, d_pad), kv_row,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d_pad), lambda b, qi, si: (b, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B * H, t_pad, d_pad), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),   # running row max
            pltpu.VMEM((blk_q, 128), jnp.float32),   # running normalizer
            pltpu.VMEM((blk_q, d_pad), jnp.float32), # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)

    out = out[:, :T, :d].reshape(B, H, T, d)
    return jnp.transpose(out, (0, 2, 1, 3))
