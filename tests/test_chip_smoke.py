"""chip_smoke.py on the CPU: the phase functions at toy sizes (control flow,
counts and parity — never a rate), the device gate, the compile-cache rule
and the content-keyed native build."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from fraud_detection_tpu.featurize import native  # noqa: E402
from fraud_detection_tpu.utils import jax_cache  # noqa: E402
from fraud_detection_tpu.utils.device import device_stamp  # noqa: E402

TOY = chip_smoke.Sizes(
    n_corpus=400, num_features=512, max_depth=2, n_rounds=2,
    batch=64, pipeline_depth=2, lr_msgs=200, xgb_msgs=128, sample=32,
    llm=dict(vocab_size=258, d_model=32, n_heads=4, n_layers=1, d_ff=64,
             n_kv_heads=1, head_dim_override=8, activation="gelu",
             embed_scale=5.0, max_seq=2048),
    llm_dtype="float32", slots=2, new_tokens=4, explain_msgs=24,
    min_flagged=4, long_prompt=520, flash_t=512,
    hist=(256, 128, 8, 2, 3), attn=(1, 128, 2, 1, 32),
    feat_rows=8, feat_width=128, feat_tokens=16,
    mesh_llm_layers=1, mesh_rows=256)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    workdir = str(tmp_path_factory.mktemp("chip_smoke"))
    stamp = device_stamp()
    res = chip_smoke.phase_train(workdir, TOY, stamp)
    assert res["use_pallas"] is False          # XLA histograms off-TPU
    return res["models"], stamp


def test_serve_phase_lr_and_xgb(trained):
    models, stamp = trained
    for kind, n in (("lr", TOY.lr_msgs), ("xgb", TOY.xgb_msgs)):
        res = chip_smoke.phase_serve(models[kind], n, TOY, stamp)
        assert res["messages"] == n and res["uploads_per_batch"] == 1.0
        assert res["donation_hits"] == 0 and not res["donation_consumed"]
        assert res["max_abs_diff_vs_numpy"] < 1e-4


def test_explain_phase_accounts_every_flagged_row(trained):
    models, _ = trained
    res = chip_smoke.phase_explain(models["lr"], TOY)
    assert res["flagged"] >= TOY.min_flagged > TOY.slots   # slots reused
    assert res["admitted"] == res["completed"] == res["annotations"]
    assert res["prefix_hits"] == res["admitted"] and res["leaked_pages"] == 0


def test_kernel_phase_interpreted():
    res = chip_smoke.phase_kernels(TOY, interpret=True)
    assert res["histogram_int8_exact"] and res["best_splits_exact"]
    assert res["featurize"] == {"path": "interpret", "rows": TOY.feat_rows,
                                "truncated_rows": TOY.feat_rows,
                                "mismatched_rows": 0,
                                "fused_max_abs_diff": 0.0}


def test_mesh_phase_on_the_virtual_devices(trained):
    models, stamp = trained
    assert stamp["device_count"] >= 4          # conftest: 8 virtual devices
    lr = chip_smoke.phase_serve(models["lr"], TOY.lr_msgs, TOY, stamp)
    res = chip_smoke.phase_mesh(models, TOY, stamp, lr["sample_probabilities"])
    assert res["serve_mesh_devices"] == stamp["device_count"]
    assert res["tp4_tokens_equal"]


def test_a_wrong_result_is_an_exception_not_a_field(trained):
    models, stamp = trained
    elsewhere = dict(stamp, device_kind="some other chip")
    with pytest.raises(chip_smoke.SmokeFailure, match="the gate saw"):
        chip_smoke.phase_serve(models["lr"], 64, TOY, elsewhere)


def test_last_stdout_line_is_the_drivers_verdict(tmp_path, monkeypatch, capsys):
    """The chip check reads the LAST line and refuses any key beyond
    ok / device{platform, kind, count}; the report is the line before it."""
    stamp = device_stamp()
    monkeypatch.setattr(chip_smoke, "gate", lambda: stamp)
    monkeypatch.setattr(chip_smoke, "run", lambda *a: {"train": {"ok": True}})
    monkeypatch.setattr(native, "_LIB", str(tmp_path / "libfastfeat.so"))
    monkeypatch.setattr(native, "available", lambda: True)
    assert chip_smoke.main() == 0
    report, last = (json.loads(line)
                    for line in capsys.readouterr().out.splitlines()[-2:])
    assert last == {"ok": True, "device": {
        "platform": stamp["platform"], "kind": stamp["device_kind"],
        "count": stamp["device_count"]}}
    assert isinstance(last["device"]["count"], int)
    assert report["phases"] == {"train": {"ok": True}}
    assert report["claim"] is None and report["device"] == last["device"]


def _run(code_or_argv, env_extra, cwd=REPO):
    env = {k: v for k, v in os.environ.items()
           if k != jax_cache.CACHE_ENV and not k.startswith("XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_extra)
    argv = (code_or_argv if isinstance(code_or_argv, list)
            else [sys.executable, "-c", code_or_argv])
    return subprocess.run(argv, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=300)


def test_bare_command_refuses_the_cpu():
    proc = _run([sys.executable, os.path.join(REPO, "chip_smoke.py")], {})
    assert proc.returncode not in (0, None)
    assert "platform 'cpu'" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


_CACHE_PROBE = """
import json, jax
calls = []
real = jax.config.update
jax.config.update = lambda name, value: (calls.append(name), real(name, value))[1]
from fraud_detection_tpu.utils.jax_cache import enable_persistent_compile_cache
path = enable_persistent_compile_cache()
jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(7.0)).block_until_ready()
print(json.dumps({"path": path, "calls": calls,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def test_cache_dir_from_the_environment_is_not_set_in_code(tmp_path):
    placed = str(tmp_path / "placed_cache")
    proc = _run(_CACHE_PROBE, {
        jax_cache.CACHE_ENV: placed,
        # off-TPU the cache keeps JAX's 1 s threshold; let the probe's tiny
        # program through so "files land there" is observable
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["path"] == out["config"] == placed
    assert "jax_compilation_cache_dir" not in out["calls"]
    assert os.listdir(placed)                  # the compile landed there


def test_cache_dir_defaults_to_the_fixed_path_in_the_checkout():
    proc = _run(_CACHE_PROBE, {})
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    fixed = os.path.join(REPO, ".jax_cache")
    assert out["path"] == out["config"] == jax_cache.DEFAULT_CACHE_DIR == fixed
    tracked = subprocess.run(["git", "check-ignore", "-q", fixed], cwd=REPO)
    assert tracked.returncode in (0, 128)      # ignored (128: not a git checkout)


def test_native_library_rebuilds_on_content_not_mtime(tmp_path, monkeypatch):
    if not native.available():
        pytest.skip("native toolchain unavailable")
    src = tmp_path / "fast_featurize.cpp"   # any source will do: the rule
    src.write_bytes(b'extern "C" int ftok_probe() { return 1; }\n')
    monkeypatch.setattr(native, "_SRC", str(src))
    lib = str(tmp_path / "libfastfeat.so")
    flags = ["-O0"]

    def state():
        with open(lib + ".key") as f:
            return f.read(), os.stat(lib).st_mtime_ns

    assert native._compile(lib, flags) == lib
    built = state()
    assert native._compile(lib, flags) == lib
    assert state() == built                    # same content: reused

    stamp = os.stat(src)
    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    os.utime(src, ns=(stamp.st_atime_ns, stamp.st_mtime_ns))
    os.utime(lib)                              # library NEWER than the source
    touched = state()
    assert native._compile(lib, flags) == lib
    edited = state()                           # content changed: rebuilt
    assert edited[0] != built[0] and edited[1] != touched[1]
    assert native._compile(lib, ["-O1"]) == lib
    assert state()[0] != edited[0]             # flags are part of the key
