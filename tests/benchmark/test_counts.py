"""Operation and byte counts against hand-worked values for InternLM2-1.8B,
and the table of peaks."""

import json
import os

import pytest

from conftest import REPO

from benchmark import counts


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "desk-lr-internlm2-1.8b.json")) as f:
        return json.load(f)


def test_internlm2_1_8b_by_hand(cfg):
    # per layer: wq 2048*2048 + wk,wv 2*2048*1024 + wo 2048*2048 + 3*2048*8192
    layer = 4194304 + 4194304 + 4194304 + 50331648
    assert counts.llm_layer_matmul_params(cfg) == layer == 62914560
    # 24 layers (+2 norms each) + embedding + untied head + final norm
    assert counts.llm_param_count(cfg) == 24 * (layer + 4096) + 2 * 92544 * 2048 + 2048
    assert counts.llm_param_count(cfg) == 1889110016 == cfg["assumed"]["parameters"]
    # K and V: 2 * 8 heads * 128 * 2 bytes * 24 layers
    assert counts.kv_bytes_per_token(cfg) == 98304
    # one token at context 1000: 2 flops a weight + 4*H*d*ctx a layer + head
    want = 2 * 24 * layer + 4 * 24 * 16 * 128 * 1000 + 2 * 92544 * 2048
    assert counts.llm_token_flops(cfg, 1000) == want == 3595567104


def test_decode_window_and_prefill_costs(cfg):
    w_bytes = (24 * 62914560 + 92544 * 2048) * 2
    flops, nbytes = counts.decode_window_cost(cfg, [[1000, 1500], [], [1001]])
    assert nbytes == 2 * w_bytes + 98304 * (1001 + 1501 + 1002)
    assert flops == sum(counts.llm_token_flops(cfg, c) for c in (1000, 1500, 1001))
    agg = counts.decode_aggregate_cost(cfg, steps=2, row_steps=3,
                                       mean_context=(1000 + 1500 + 1001) / 3)
    assert agg == pytest.approx((flops, nbytes))
    f, b = counts.prefill_cost(cfg, prefix_len=293, suffix_len=1000)
    ctx_sum = 1000 * 293 + 1000 * 1001 / 2
    assert f == 2 * 24 * 62914560 * 1000 + 4 * 24 * 16 * 128 * ctx_sum + 2 * 92544 * 2048
    assert b == w_bytes + 98304 * 1293


def test_score_costs_and_peaks(cfg):
    lr = cfg["desk"]["classifier"]
    flops, nbytes = counts.score_cost(lr, rows=4096, pairs_per_row=60)
    assert flops == 4096 * 124 and nbytes == 4096 * 244 + 40000
    xgb = dict(lr, family="xgb", n_rounds=100, max_depth=5)
    flops, nbytes = counts.score_cost(xgb, rows=1, pairs_per_row=60)
    assert flops == 60 + 600 + 4 and nbytes == 244 + 40000 + 100 * 63 * 20
    least, bound = counts.roofline(197e12, 819e9 / 2, "TPU v5 lite")
    assert (least, bound) == (1.0, "compute")
    assert counts.roofline(1.0, 819e9, "TPU v5 lite") == (1.0, "memory")
    with pytest.raises(KeyError, match="no peaks recorded"):
        counts.peaks("cpu")


# ---------------------------------------------------------------------------
# the same counts through the configuration's family file
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def family(spec, cfg):
    from benchmark import run

    return run.load_family(spec, cfg)


def test_the_family_file_is_found_by_model_type(family, cfg):
    assert cfg["model_type"] == "internlm2"
    assert family.__file__ == os.path.join(REPO, "benchmark", "explainers",
                                           "internlm2.py")


@pytest.mark.parametrize("name,args,through", [
    ("decode_cost", (2, 3, (1000 + 1500 + 1001) / 3), "decode_aggregate_cost"),
    ("decode_cost", (211.0, 2505.0, 1735.5, 1), "decode_aggregate_cost"),
    ("prefill_cost", (293, 1000), "prefill_cost"),
    ("prefill_cost", (0, 1991, 1), "prefill_cost"),
    ("param_count", (), "llm_param_count"),
])
def test_family_counts_are_those_of_counts_py(family, cfg, name, args, through):
    assert getattr(family, name)(cfg, *args) == getattr(counts, through)(cfg, *args)
    if name == "param_count":
        assert family.param_count(cfg) == 1889110016


def test_readers_take_their_counts_from_the_family():
    """``_lib.decode_cost`` and ``_lib.prefill_cost`` hand the family what
    the marks and the tickets say, and return what it says."""
    from types import SimpleNamespace

    from benchmark.metrics import _lib

    asked = []
    fam = SimpleNamespace(
        decode_cost=lambda *a: asked.append(("decode",) + a) or (1.0, 2.0),
        prefill_cost=lambda *a: asked.append(("prefill",) + a) or (10.0, 20.0))
    mark = {"slots": 2, "prefix_pages": 5, "occupancy": 0.0, "decode_steps": 0}
    ctx = {"cfg": {"name": "x"}, "family": fam, "prefix_len": 293,
           "window": (100.0, 110.0),
           "marks": {"open": mark,
                     "close": dict(mark, occupancy=0.75, decode_steps=40)},
           "tickets": [
               {"prompt_len": 1400, "n_out": 100, "first_token": 101.0, "done": 105.0},
               {"prompt_len": 1600, "n_out": 100, "first_token": 104.0, "done": None},
               {"prompt_len": 1500, "n_out": 0, "first_token": None, "done": None}]}
    assert _lib.decode_cost(ctx, "open", "close", "window") == (1.0, 2.0)
    assert _lib.prefill_cost(ctx, "window") == (20.0, 40.0, 2)
    assert asked == [("decode", ctx["cfg"], 40.0, 60.0, 1550.0),
                     ("prefill", ctx["cfg"], 293, 1107),
                     ("prefill", ctx["cfg"], 293, 1307)]
