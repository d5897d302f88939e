"""The trace reducer on a small trace recorded on a TPU v5e (the fixture),
and its arithmetic on hand-made events."""

import json
import os

import pytest

from conftest import FIXTURES

from benchmark import trace_reduce


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(FIXTURES, "trace_v5e.json")) as f:
        return json.load(f)


def test_names():
    assert trace_reduce.program_name("jit_paged_decode_window(1469535)") == \
        "paged_decode_window"
    assert trace_reduce.program_name("my_module") == "my_module"
    text = ("%convolution_tanh_fusion.3 = bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)} "
            "fusion(bf16[2048,2048]{1,0:T(8,128)(2,1)} %x.1), kind=kOutput")
    assert trace_reduce.op_label(text) == "convolution_tanh_fusion_bf16_2048_2048_"
    tup = ("%copy-start = (bf16[16,8]{1,0:T(8,128)(2,1)S(1)}, bf16[16,8]{1,0}, "
           "u32[]{:S(2)}) copy-start(bf16[16,8]{1,0} %w.1)")
    assert trace_reduce.op_label(tup) == "copy-start__bf16_16_8___bf16_16_8___u32___"
    assert trace_reduce.op_label("%broadcast_in_dim.693.remat = bf16[16,2]{1,0} x()") \
        == "broadcast_in_dim.693.remat_bf16_16_2_"


def test_recorded_v5e_trace(recorded):
    got = trace_reduce.reduce(recorded, window_s=0.2)
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    # The recorded ops do not overlap: busy time is their sum.
    assert got["busy_s"] == pytest.approx(sum(o[2] for o in ops) / 1e9)
    assert got["window_s"] == 0.2
    assert got["runs"] == {"my_step": 5, "dynamic_slice": 5}
    fusion = "my_step:_convolution_tanh_fusion_bf16_2048_2048_"
    assert got["ops"][fusion] == pytest.approx(
        (90852 + 89956 + 90141 + 90984 + 91115) / 1e9)
    assert got["device_ops"][0][0] == fusion
    assert got["programs"]["my_step"] == pytest.approx(
        (14 + 2 + 90852 + 89956 + 90141 + 90984 + 13 + 12473 + 91115) / 1e9)
    assert got["programs"]["dynamic_slice"] == pytest.approx((13 + 10946 + 318) / 1e9)
    # The longest gap lies between the first step and the slice after it
    # (43.03 ms -> 122.05 ms); of what the fixture keeps of the host, the
    # benchmark's own annotation (asleep inside it) overlaps it most.
    label, seconds = got["idle_gaps"][0]
    assert seconds == pytest.approx((122050299 - (42943944 + 90984)) / 1e9)
    assert label == "python3:_bench_iter"
    assert len(got["idle_gaps"]) <= 10 and len(got["device_ops"]) <= 10


def test_nested_ops_count_their_own_time_only():
    trace = {"devices": {"/device:TPU:0": {
        "modules": [["jit_loop(1)", 0.0, 1000.0], ["jit_loop(1)", 5000.0, 1000.0]],
        "ops": [["%while.1 = s32[] while(s32[] %a)", 0.0, 1000.0],
                ["%fusion.1 = f32[8]{0} fusion()", 100.0, 300.0],
                ["%fusion.2 = f32[8]{0} fusion()", 500.0, 400.0],
                ["%fusion.9 = f32[8]{0} fusion()", 5000.0, 1000.0]]}},
        "host": [["python3", "np.asarray(jax.Array)", 900.0, 4200.0],
                 ["python3", "PjitFunction(loop)", 0.0, 6000.0]]}
    got = trace_reduce.reduce(trace, window_s=1e-5)
    assert got["busy_s"] == pytest.approx(2000 / 1e9)        # union, not sum
    assert got["ops"]["loop:_while_s32__"] == pytest.approx(300 / 1e9)
    assert got["ops"]["loop:_fusion_f32_8_"] == pytest.approx(1700 / 1e9)
    assert got["programs"] == {"loop": pytest.approx(2000 / 1e9)}
    assert got["idle_gaps"] == [["python3:_np.asarray_jax.Array_",
                                 pytest.approx(4000 / 1e9)]]


def test_two_chips_average_and_no_device():
    dev = {"modules": [["jit_f(1)", 0.0, 100.0]], "ops": []}
    two = {"devices": {"/device:TPU:0": dev,
                       "/device:TPU:1": {"modules": [["jit_f(1)", 0.0, 300.0]],
                                         "ops": []}}, "host": []}
    got = trace_reduce.reduce(two, window_s=1.0)
    assert got["busy_s"] == pytest.approx(200 / 1e9)
    assert got["programs"] == {"f": pytest.approx(200 / 1e9)}
    none = trace_reduce.reduce({"devices": {}, "host": []}, window_s=1.0)
    assert none["busy_s"] == 0.0 and none["device_ops"] == []
