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

Two callers: ``forward()``'s whole-sequence attention (square: the queries
are the keys' own positions) and the slot lane's suffix prefill
(``llm.paged_slot_prefill``: ``Ts`` queries at the static offset of the
cached preamble against the row's gathered view, rectangular, and for latent
attention keys wider than values).
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


def _flash_kernel(q_ref, k_ref, vt_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, blk_q: int, chunk: int, n_chunks: int,
                  n_major: int, q_offset: int):
    """One (batch*head, q-block, major key block) cell; the grid runs the
    major blocks innermost, so the scratch accumulators carry across them.

    Everything is held TRANSPOSED, keys down the sublanes and queries along
    the lanes: scores (chunk, blk_q), the accumulator (dv, blk_q), the running
    max and normalizer one lane-dense row (1, blk_q). A row statistic is then
    a few vregs (not blk_q/8 of them, one lane each), the max and the sum over
    keys are elementwise across vregs, and both products are plain MXU forms
    (k . q^T and v^T . p). What one key chunk costs beside its score tile is
    small, so chunks can be narrow and the causal edge is followed closely.

    Query row ``i`` of the block sees key columns ``<= q_offset + i``. A major
    block is ``n_chunks`` chunks of ``chunk`` keys, resident in VMEM; the
    kernel loops over the chunks every row sees (no mask), then over those the
    diagonal crosses (masked), and never touches the ones above it. A major
    block wholly above the diagonal runs no chunk and, its index map holding
    the block already resident, costs no DMA."""
    qi = pl.program_id(1)
    mi = pl.program_id(2)
    first_row_sees = q_offset + qi * blk_q          # last column row 0 sees
    last_row_sees = first_row_sees + (blk_q - 1)

    @pl.when(mi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                               # (blk_q, d)
    base = mi * n_chunks                       # this block's first chunk

    def accumulate(c, masked: bool):
        s = jax.lax.dot_general(
            k_ref[0, c], q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (chunk, blk_q)
        if masked:
            ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                     - jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            s = jnp.where(ahead >= (base + c) * chunk - first_row_sees, s, _NEG)
        m_prev = m_ref[0:1]                                    # (1, blk_q)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)                                 # masked -> 0
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_ref[0:1] + jnp.sum(p, axis=0, keepdims=True),
            l_ref.shape)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            vt_ref[0, c], p.astype(vt_ref.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (dv, blk_q)

    # Chunks [0, seen) of this block lie under every row's limit, chunks
    # [seen, crossed) hold the diagonal, the rest no row sees.
    seen = jnp.clip((first_row_sees + 1) // chunk - base, 0, n_chunks)
    crossed = jnp.clip(last_row_sees // chunk + 1 - base, 0, n_chunks)
    jax.lax.fori_loop(0, seen, lambda c, _: accumulate(c, False), None)
    jax.lax.fori_loop(seen, crossed, lambda c, _: accumulate(c, True), None)

    @pl.when(mi == n_major - 1)
    def _emit():
        o_ref[0] = (acc_ref[:] / l_ref[0:1]).astype(o_ref.dtype)


# Keys a major block holds at most: K and V^T of one head stay resident in
# VMEM (two buffers each) while a query block loops over their chunks.
_MAJOR_KEYS = 2048


@partial(jax.jit, static_argnames=("q_offset", "blk_q", "blk_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    q_offset: int = 0, blk_q: int = 0, blk_k: int = 0,
                    interpret: bool = False) -> jax.Array:
    """Causal flash attention. q: (B, T, H, d) at the static position
    ``q_offset``: query row j attends key columns ``<= q_offset + j`` of
    k (B, S, Hkv, d) / v (B, S, Hkv, dv), ``S >= q_offset + T`` — the whole
    sequence against itself (``q_offset`` 0, S == T), or a suffix against
    [what is cached ; itself] (the slot prefill). Columns past
    ``q_offset + T`` are seen by no row and never read. dv is free of d
    (latent attention: keys 192 wide, values 128). H % Hkv == 0 — GQA/MQA kv
    stay at their NATIVE width and the kernel's index map hands each query
    head its group's K/V block, so nothing expands: on Gemma-2B (MQA, H=8,
    Hkv=1) the pre-r5 caller-side ``jnp.repeat`` materialized and streamed
    8x the K/V bytes. Hkv == H recovers plain MHA. Returns (B, T, H, dv).
    Matches ``_attend(q, k, v, offset-causal mask)`` to f32 round-off;
    enforced by tests/test_flash_attention.py.

    ``blk_q`` queries a block, ``blk_k`` keys a chunk of the kernel's inner
    loop; 0 picks from the shapes (``_auto_blocks``)."""
    B, T, H, d = q.shape
    S, h_kv, dv = k.shape[1], k.shape[2], v.shape[3]
    if H % h_kv or v.shape[2] != h_kv:
        raise ValueError(f"kv heads {k.shape[2]}/{v.shape[2]} must divide "
                         f"query heads {H}")
    if q_offset < 0 or S < q_offset + T or v.shape[1] != S:
        raise ValueError(f"{T} queries at offset {q_offset} need "
                         f"{q_offset + T} keys and values, got "
                         f"{S}/{v.shape[1]}")
    rep = H // h_kv
    auto_q, auto_k = _auto_blocks(T)
    blk_q, chunk = blk_q or auto_q, blk_k or auto_k
    scale = 1.0 / math.sqrt(d)
    s_seen = q_offset + T                    # columns some real row attends
    t_pad = _round_up(T, blk_q)
    n_chunks = min(-(-s_seen // chunk), max(1, _MAJOR_KEYS // chunk))
    s_pad = _round_up(s_seen, n_chunks * chunk)
    n_q, n_major = t_pad // blk_q, s_pad // (n_chunks * chunk)
    d_pad, dv_pad = _round_up(d, 128), _round_up(dv, 128)

    def heads_first(x, n, n_pad, w_pad):       # (B,n,h,w) -> (B*h, n_pad, w_pad)
        h, w = x.shape[2], x.shape[3]
        x = jnp.transpose(x[:, :n], (0, 2, 1, 3)).reshape(B * h, n, w)
        return jnp.pad(x, ((0, 0), (0, n_pad - n), (0, w_pad - w)))

    qf = heads_first(q, T, t_pad, d_pad)
    kf = heads_first(k, s_seen, s_pad, d_pad).reshape(
        B * h_kv, s_pad // chunk, chunk, d_pad)
    vt = jnp.transpose(
        heads_first(v, s_seen, s_pad, dv_pad).reshape(
            B * h_kv, s_pad // chunk, chunk, dv_pad), (0, 1, 3, 2))

    def kv_block(b, qi, mi):
        # grid row b = bi * H + hi over (B*H); its kv row is
        # bi * Hkv + hi // rep over (B*Hkv). Past the last major block the
        # query block sees, the index stays there: an unchanged block is not
        # fetched again, so the cells the kernel skips move no bytes.
        last = jnp.minimum((q_offset + qi * blk_q + blk_q - 1)
                           // (n_chunks * chunk), n_major - 1)
        return (b // H) * h_kv + (b % H) // rep, jnp.minimum(mi, last), 0, 0

    out = pl.pallas_call(
        partial(_flash_kernel, scale=scale, blk_q=blk_q, chunk=chunk,
                n_chunks=n_chunks, n_major=n_major, q_offset=q_offset),
        grid=(B * H, n_q, n_major),
        in_specs=[
            pl.BlockSpec((1, blk_q, d_pad), lambda b, qi, mi: (b, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_chunks, chunk, d_pad), kv_block,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_chunks, dv_pad, chunk), kv_block,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, dv_pad, blk_q), lambda b, qi, mi: (b, 0, qi),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B * H, dv_pad, t_pad), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((8, blk_q), jnp.float32),       # running row max
            pltpu.VMEM((8, blk_q), jnp.float32),       # running normalizer
            pltpu.VMEM((dv_pad, blk_q), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vt)

    out = out[:, :dv, :T].reshape(B, H, dv, T)
    return jnp.transpose(out, (0, 3, 1, 2))


def _auto_blocks(T: int):
    """(queries a block, keys a chunk) for T queries, from a sweep on the v5e
    at the explain cells' shapes (16 heads over 8 kv heads of 128; 64 and 32
    heads of 192 with values of 128; T 1,088 to 1,728 behind 293 cached
    positions; PERF.md section 6, PR 34). Padded query rows run both products
    before they are sliced off, so the block is the largest of 512, 256, 128
    that pads T by at most ~12.5 % over its 128-granularity floor (T 1,408 ->
    512; T 1,088 and 1,728 -> 256; T 640 stays 128). Chunks of 512 keys cost
    least per key wherever T is long enough to have them: a chunk's fixed
    cost, the accumulator's rescale, is set against more columns, and that
    outweighs what a wider chunk computes above the diagonal. A short T keeps
    128."""
    floor = _round_up(T, 128)
    blk_q = next(b for b in (512, 256, 128)
                 if _round_up(T, b) * 8 <= floor * 9)
    return blk_q, (512 if T >= 512 else 128)
