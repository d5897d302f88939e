"""The tiny convolution-attention hybrid the CPU tests share: 6 layers (conv,
conv, attention, conv, conv, conv: 2 dense MLPs, then 4 expert layers), GQA
with 8 query heads on 2 key-value heads of 8 lanes and q/k norms, 3 filter
taps, 8 experts all held, top 4, sigmoid router with the 1e-6 —
``tests/benchmark/fixtures/configs/tiny-desk-lfm2.json`` served through its
family file (``benchmark/explainers/lfm2_moe.py``), whose plain float32
reference the tests compare the program with."""

import copy
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SEED = 2**31 + 5


def config(dtype: str = "float32", **changes) -> dict:
    with open(os.path.join(REPO, "tests", "benchmark", "fixtures", "configs",
                           "tiny-desk-lfm2.json")) as f:
        cfg = json.load(f)
    cfg["torch_dtype"] = dtype
    cfg.update(copy.deepcopy(changes))
    return cfg


def family():
    from benchmark import run

    return run._load_file(os.path.join(REPO, "benchmark", "explainers",
                                       "lfm2_moe.py"),
                          "bench_explainer_lfm2_moe")


def language_model(dtype: str = "float32", weights: str = None, seed: int = SEED,
                   **changes):
    """The program's model for the tiny configuration, weights from ``seed``
    as the family makes them (``weights="int8"``: its lower precision)."""
    import jax.numpy as jnp

    fam, cfg = family(), config(dtype, **changes)
    params = fam.make_params(seed, cfg, jnp.dtype(dtype).type)
    return fam.build(cfg, params, weights or dtype)
