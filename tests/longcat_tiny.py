"""The tiny shortcut-connected explainer the CPU tests share: 2 layers (4
sub-layers: two latent attentions and two dense MLPs a layer, the expert
branch joining one sub-layer later), 16 routed experts of which 4 are held,
8 zero-compute experts behind them (24 router outputs), top 4, softmax router
— ``tests/benchmark/fixtures/configs/tiny-desk-longcat.json`` served through
its family file (``benchmark/explainers/longcat_flash.py``), whose plain
float32 reference the tests compare the program with."""

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
                           "tiny-desk-longcat.json")) as f:
        cfg = json.load(f)
    cfg["torch_dtype"] = dtype
    cfg.update(copy.deepcopy(changes))
    return cfg


def family():
    from benchmark import run

    return run._load_file(os.path.join(REPO, "benchmark", "explainers",
                                       "longcat_flash.py"),
                          "bench_explainer_longcat_flash")


def language_model(dtype: str = "float32", weights: str = None, seed: int = SEED,
                   **changes):
    """The program's model for the tiny configuration, weights from ``seed``
    as the family makes them (``weights="int8"``: its lower precision)."""
    import jax.numpy as jnp

    fam, cfg = family(), config(dtype, **changes)
    params = fam.make_params(seed, cfg, jnp.dtype(dtype).type)
    return fam.build(cfg, params, weights or dtype)
