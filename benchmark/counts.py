"""Peaks of the chips the benchmark knows, and the operations and bytes that
the algorithms it times need. The yardstick: later PRs may not edit this file.

Every count is worked out from shapes alone (no program import): the least
work the *algorithm* needs, not what an implementation happens to move. A
roofline share is ``least_time / device_time``; it cannot pass 100 % unless a
count here is too high or the device time leaves out part of the work.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

# Published peaks per chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; an unknown kind is an error, never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"add it to benchmark/counts.py DEVICE_PEAKS with its "
                       f"source") from None


def roofline(flops: float, nbytes: float, device_kind: str) -> Tuple[float, str]:
    """(least seconds the chip could take, which peak bounds it)."""
    p = peaks(device_kind)
    t_f, t_b = flops / p["flops_per_s"], nbytes / p["bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")


# ---------------------------------------------------------------------------
# decoder-only transformer (HF-style config keys)
# ---------------------------------------------------------------------------

def _dims(cfg: dict) -> Tuple[int, int, int, int, int, int, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads", h)
    hd = cfg.get("head_dim", d // h)
    return (d, h, hkv, hd, cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


def llm_layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that every token multiplies (norms left out)."""
    d, h, hkv, hd, f, _, _ = _dims(cfg)
    return d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * f


def llm_param_count(cfg: dict) -> int:
    d, _, _, _, _, n, v = _dims(cfg)
    heads = v * d * (1 if cfg.get("tie_word_embeddings", False) else 2)
    return n * (llm_layer_matmul_params(cfg) + 2 * d) + heads + d


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token over every layer, at the serving dtype."""
    _, _, hkv, hd, _, n, _ = _dims(cfg)
    return 2 * hkv * hd * itemsize * n


def llm_token_flops(cfg: dict, context: int, *, head: bool = True) -> float:
    """Model FLOPs to process ONE token that attends ``context`` positions:
    2 per weight it multiplies, plus QK^T and PV over the context."""
    d, h, _, hd, _, n, v = _dims(cfg)
    flops = 2.0 * n * llm_layer_matmul_params(cfg)
    flops += 4.0 * n * h * hd * context
    if head:
        flops += 2.0 * v * d
    return flops


def decode_window_cost(cfg: dict, step_contexts: Sequence[Sequence[int]],
                       itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) one fused decode window needs. ``step_contexts[s]``
    lists, for step ``s``, the context length of each row that really
    decodes in it. Per step the weights stream once (whatever the batch) and
    each active row reads the K/V of the tokens it really holds and writes
    one token's; padded view positions and idle slots need nothing."""
    d, _, _, _, _, n, v = _dims(cfg)
    w_bytes = (n * llm_layer_matmul_params(cfg) + v * d) * itemsize
    kv = kv_bytes_per_token(cfg, itemsize)
    flops = nbytes = 0.0
    for ctxs in step_contexts:
        if not ctxs:
            continue
        nbytes += w_bytes
        for c in ctxs:
            flops += llm_token_flops(cfg, c)
            nbytes += kv * (c + 1)
    return flops, nbytes


def decode_aggregate_cost(cfg: dict, steps: float, row_steps: float,
                          mean_context: float, itemsize: int = 2
                          ) -> Tuple[float, float]:
    """``decode_window_cost`` from totals: ``steps`` decode steps in which
    ``row_steps`` rows decoded, each attending ``mean_context`` positions on
    average (both costs are linear in the context)."""
    d, _, _, _, _, n, v = _dims(cfg)
    w_bytes = (n * llm_layer_matmul_params(cfg) + v * d) * itemsize
    return (row_steps * llm_token_flops(cfg, mean_context),
            steps * w_bytes
            + row_steps * kv_bytes_per_token(cfg, itemsize) * (mean_context + 1))


def prefill_cost(cfg: dict, prefix_len: int, suffix_len: int,
                 itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) to prefill the REAL ``suffix_len`` tokens of a prompt
    whose first ``prefix_len`` positions are already cached: every suffix
    token multiplies the layer weights and attends everything at or below
    it; the head runs for the last position only. Bytes: weights once, the
    prefix K/V read, the suffix K/V written."""
    d, h, _, hd, _, n, v = _dims(cfg)
    ctx_sum = suffix_len * prefix_len + suffix_len * (suffix_len + 1) / 2.0
    flops = (2.0 * n * llm_layer_matmul_params(cfg) * suffix_len
             + 4.0 * n * h * hd * ctx_sum + 2.0 * v * d)
    nbytes = ((n * llm_layer_matmul_params(cfg) + v * d) * itemsize
              + kv_bytes_per_token(cfg, itemsize) * (prefix_len + suffix_len))
    return flops, nbytes


# ---------------------------------------------------------------------------
# scoring program (featurized rows -> p(scam))
# ---------------------------------------------------------------------------

def score_cost(classifier: dict, rows: int, pairs_per_row: float
               ) -> Tuple[float, float]:
    """(FLOPs, bytes) to score ``rows`` featurized rows that hold
    ``pairs_per_row`` (bucket id, count) pairs each, by what the algorithm
    needs: the pairs in (int16 id + uint16 count), the model once, one f32
    out per row. LR: one multiply-add per pair against the IDF-folded
    weight, then a sigmoid. Boosted trees: IDF scaling per pair, then one
    compare per level per tree and one add per tree."""
    feats = classifier["num_features"]
    io_bytes = rows * (pairs_per_row * 4 + 4)
    if classifier["family"] == "lr":
        return rows * (2.0 * pairs_per_row + 4.0), io_bytes + feats * 4
    if classifier["family"] == "xgb":
        trees, depth = classifier["n_rounds"], classifier["max_depth"]
        nodes = trees * (2 ** (depth + 1) - 1)
        flops = rows * (pairs_per_row + trees * (depth + 1.0) + 4.0)
        return flops, io_bytes + feats * 4 + nodes * 20
    raise KeyError(f"no scoring count for classifier family "
                   f"{classifier['family']!r}")
