"""The entry points refuse what they cannot do instead of hiding the device:
no TPU is not the CPU, an unknown chip has no assumed peak, and the compile
cache lives where it is told or at one fixed path."""
import os
import subprocess
import sys
import types

import jax
import pytest

import bench
from paddle_tpu.framework import device
from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_set_device_tpu_raises_without_a_tpu():
    before = device.current_device()
    with pytest.raises(RuntimeError, match="no tpu device"):
        device.set_device("tpu")
    assert device.current_device() is before


def test_set_device_index_past_the_end_raises():
    n = len(jax.devices("cpu"))
    with pytest.raises(RuntimeError, match=f"cpu has {n} device"):
        device.set_device(f"cpu:{n}")
    assert device.set_device("cpu:0") is jax.devices("cpu")[0]


def _fake_jax(kind):
    return types.SimpleNamespace(
        devices=lambda: [types.SimpleNamespace(device_kind=kind)])


def test_peak_flops_raises_on_unknown_device_kind():
    with pytest.raises(ValueError, match="TPU v9"):
        bench._peak_flops(_fake_jax("TPU v9"), True)
    with pytest.raises(ValueError, match="TPU v9"):
        bench._hbm_bytes_per_s(_fake_jax("TPU v9"), True)


def test_peaks_come_from_one_table():
    assert bench._peak_flops(_fake_jax("TPU v5 lite"), True) == (
        "TPU v5 lite", 197e12)
    assert bench._hbm_bytes_per_s(_fake_jax("TPU v5 lite"), True) == 819e9
    # longest prefix: the bare "TPU v5" row is the v5p
    assert bench._peak_flops(_fake_jax("TPU v5p"), True)[1] == 459e12
    # the CPU rehearsal path has no peak at all, not a guessed one
    assert bench._peak_flops(_fake_jax("cpu"), False) == ("cpu", None)


@pytest.mark.parametrize("argv", [["--device", "tpu"], []],
                         ids=["device-tpu", "default"])
def test_bench_without_a_chip_fails_loudly(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench.py", "--preset", "tiny", *argv],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_chip_smoke_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_defaults_to_the_fixed_in_repo_path(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable_compile_cache() == os.path.join(
        REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == compile_cache.REPO_CACHE_DIR
    # on the CPU backend it stays off (tests/conftest.py says why)
    assert not jax.config.jax_enable_compilation_cache


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                               cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory
    assert jax.config.jax_compilation_cache_dir == before
