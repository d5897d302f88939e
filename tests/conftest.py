"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths
(pjit/shard_map over a Mesh) are exercised without TPU hardware. These env
vars must be set before jax is imported anywhere in the test process.
"""

import os

# The suite always runs on the CPU, with 8 virtual devices standing in for a
# multi-chip host; subprocesses a test starts inherit both settings. CPU
# compiles dominate the suite's wall-clock and a CPU run proves correctness,
# never speed, so LLVM runs unoptimized: 20-28% off the compile-heavy files
# (test_llm 129 s -> 92 s), which is what keeps tier-1 inside its timeout.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
if "xla_backend_optimization_level" not in flags:
    flags += (" --xla_backend_optimization_level=0"
              " --xla_llvm_disable_expensive_passes=true")
os.environ["XLA_FLAGS"] = flags.strip()

# Persistent compilation cache: the tree trainers unroll depth-wise programs
# whose CPU compiles dominate suite wall-clock; repeat runs — including the
# driver's — hit the cache instead (the one rule in utils/jax_cache.py).
import jax  # noqa: E402

from fraud_detection_tpu.utils.jax_cache import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()

import gc  # noqa: E402

import pytest  # noqa: E402


def _compiled_code_piled_up() -> bool:
    """XLA:CPU maps every compiled program's code into the process (~15
    mappings each) and JAX keeps them all alive. Two costs grow with the
    pile: a hard one — at ``vm.max_map_count`` (65,530 here) LLVM fails with
    "Cannot allocate memory" and the next compile segfaults, which the suite
    reached at 92 % — and a soft one well before it: every later compile
    and trace gets slower (tests/test_llm.py: 89 s alone, 126 s half-way
    through an uncleared suite). A sixth of the limit is roughly what one
    heavy module leaves behind."""
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            limit = int(f.read())
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f) > limit // 6
    except (OSError, ValueError):
        return False                  # no /proc: nothing to bound


@pytest.fixture(autouse=True, scope="module")
def _bound_compiled_code_maps():
    """After a test module, drop JAX's compiled programs if they have piled
    up (they recompile, or reload from the persistent cache, on next use).
    Small modules in a row still share their programs. Tier-1 on this
    sandbox: 770-781 s clearing only near the limit, 705 s clearing after
    every module, 656 s with this rule."""
    yield
    if _compiled_code_piled_up():
        jax.clear_caches()
        gc.collect()


@pytest.fixture
def boot_log(monkeypatch):
    """A boot log of the test's own (obs/trace.py): every tracer starts its
    ring with the process's, so a test that counts a fresh tracer's spans,
    or reads set-up's, must not find what earlier tests compiled."""
    from fraud_detection_tpu.obs import trace

    fresh = trace._BootLog()
    monkeypatch.setattr(trace, "BOOT", fresh)
    return fresh


REFERENCE_ARTIFACT = "/root/reference/dialogue_classification_model"


@pytest.fixture(scope="session")
def reference_artifact_path():
    if not os.path.isdir(REFERENCE_ARTIFACT):
        pytest.skip("reference Spark artifact not available")
    return REFERENCE_ARTIFACT
