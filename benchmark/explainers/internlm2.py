"""The explainer family ``internlm2``: one dense decoder (MHA/GQA/MQA,
SiLU-gated MLP, untied head) told by HF-style config keys. A family file is
one family's whole contract with the harness, found by the configuration's
``model_type`` (``run.load_family``): five functions over the configuration
dict. This one binds what the benchmark had before families were a seam, the
arithmetic stays where its tests find it (``reference.py``, ``counts.py``).

``build(cfg, params, weights)``    what ``SlotServeService`` serves; it
        carries ``tokenizer``. ``weights`` is ``desk.explain.weights`` (the
        stated ``torch_dtype`` unless the configuration, or
        benchmark/control.py, names the family's lower precision: here
        "int8", the program's weight-only ``quantized()`` path). The only
        place the program is imported.
``make_params(seed, cfg, dtype)``  the weights from the seed, on the device
        in one jitted call, in the layout ``build`` takes.
``token_gaps(seed, cfg, dtype_name, requests, pad_to)``  the plain float32
        reference: per request, the gap of every served token below the
        reference's best logit at its position.
``decode_cost(cfg, steps, row_steps, mean_context, itemsize=2)``,
``prefill_cost(cfg, prefix_len, suffix_len, itemsize=2)``  (FLOPs, bytes)
        the algorithm needs: what a roofline share and ``explain.step_mfu``
        are read against.
``param_count(cfg)``               every weight, norms included.
"""

from benchmark import counts, reference


def llm_config(cfg: dict):
    """The program's ``TransformerConfig`` for an HF-style config dict."""
    import jax.numpy as jnp

    from fraud_detection_tpu.models.llm import TransformerConfig

    return TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"], max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        dtype=jnp.dtype(cfg["torch_dtype"]).type,
        n_kv_heads=cfg.get("num_key_value_heads"),
        head_dim_override=cfg.get("head_dim"),
        activation=cfg.get("hidden_act", "silu"),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        rms_eps=float(cfg["rms_norm_eps"]))


def build(cfg: dict, params: dict, weights: str):
    from fraud_detection_tpu.models.llm import LanguageModel

    lm = LanguageModel(llm_config(cfg), params)
    return lm.quantized() if weights == "int8" else lm


make_params = reference.make_llm_params
token_gaps = reference.llm_token_gaps
decode_cost = counts.decode_aggregate_cost
prefill_cost = counts.prefill_cost
param_count = counts.llm_param_count
