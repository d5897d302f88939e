"""Shared pieces of the benchmark's CPU tests: tiny configurations and mixes
(tests/benchmark/fixtures), and one way to run a cell without a chip."""

import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
CHIP = "TPU v5 lite"          # names a chip for the readers' arithmetic only


def tiny_cell(spec, traffic_kind):
    return next(w for w in spec["workloads"] if w["traffic"] == traffic_kind)


@pytest.fixture(scope="session")
def spec():
    from benchmark import run

    return run.load_spec()


@pytest.fixture(scope="session")
def run_tiny(spec, tmp_path_factory):
    """run_tiny(mix, config="tiny-desk", kind=..., **kw) -> the result line
    of ``run.run_cell`` on the CPU at the fixtures' sizes."""
    from benchmark import desk, run, traffic

    def go(mix, config="tiny-desk", kind="campaign", seed=2**31 + 77,
           seconds=3.0, trace=False, rate_times=1.0, explain_weights=None,
           **kw):
        cfg = desk.load_config(os.path.join(FIXTURES, "configs", config + ".json"))
        mixd = traffic.load_mix(os.path.join(FIXTURES, "traffic", mix + ".json"))
        if explain_weights is not None:
            cfg["desk"]["explain"]["weights"] = explain_weights
        for seg in mixd["arrivals"]:
            if rate_times != 1.0:
                seg["rate_per_s"] = seg["rate_per_s"] * rate_times
        return run.run_cell(
            spec, tiny_cell(spec, kind), cfg, mixd, seed=seed, seconds=seconds,
            trace=trace, t_start=time.time(), device_kind=CHIP,
            scratch=str(tmp_path_factory.mktemp("bench")), **kw)

    return go
