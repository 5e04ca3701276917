"""The chip path's guards, checked without a chip: the compilation cache
goes where it is told and nowhere else, the sweep memory budget refuses to
guess for an accelerator, and chip_smoke.py fails without a TPU."""

import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import chip_smoke
from repro import compile_cache
from repro.core import stream as xstream

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**kw):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_CACHE_DIR}
    env.update(JAX_PLATFORMS="cpu", **kw)
    return env


def test_cache_dir_from_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_cache_dir_default_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
    path = compile_cache.compile_cache_dir()
    assert path == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_entries_land_in_env_dir_only(tmp_path):
    cache = tmp_path / "cache"
    before = (sorted(os.listdir(compile_cache.REPO_CACHE_DIR))
              if os.path.isdir(compile_cache.REPO_CACHE_DIR) else None)
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(5)).block_until_ready()\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=tmp_path,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache),
                 PYTHONPATH=os.path.join(ROOT, "src")))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(cache)
    assert os.listdir(cache)
    after = (sorted(os.listdir(compile_cache.REPO_CACHE_DIR))
             if os.path.isdir(compile_cache.REPO_CACHE_DIR) else None)
    assert after == before
    assert sorted(os.listdir(tmp_path)) == ["cache"]


def _fake_device(stats):
    return SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                           memory_stats=lambda: stats)


def test_memory_budget_refuses_accelerator_without_limit(monkeypatch):
    monkeypatch.delenv(xstream.ENV_MEM_MB, raising=False)
    monkeypatch.setattr(xstream.jax, "devices",
                        lambda: [_fake_device({})])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        xstream.memory_budget_bytes()
    monkeypatch.setattr(xstream.jax, "devices",
                        lambda: [_fake_device(None)])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        xstream.memory_budget_bytes()
    # an explicit budget still wins
    assert xstream.memory_budget_bytes(3) == 3 * 2**20


def test_memory_budget_from_accelerator_limit(monkeypatch):
    monkeypatch.delenv(xstream.ENV_MEM_MB, raising=False)
    monkeypatch.setattr(xstream.jax, "devices",
                        lambda: [_fake_device({"bytes_limit": 1000})])
    assert xstream.memory_budget_bytes() == int(
        xstream.DEVICE_MEM_FRACTION * 1000)


def test_memory_budget_cpu_default(monkeypatch):
    monkeypatch.delenv(xstream.ENV_MEM_MB, raising=False)
    assert xstream.memory_budget_bytes() == int(
        xstream.DEFAULT_MEM_MB * 2**20)


@pytest.mark.parametrize("alone", [False, True], ids=["repo", "alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:                      # a directory holding nothing else
        with open(script) as f:
            (tmp_path / "chip_smoke.py").write_text(f.read())
        script = str(tmp_path / "chip_smoke.py")
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, timeout=300, cwd=tmp_path,
                         env=_env(JAX_COMPILATION_CACHE_DIR=str(
                             tmp_path / "cache")))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_first_difference_names_config_and_field():
    n = 6
    a = SimpleNamespace(**{f: None for f in chip_smoke.RESULT_FIELDS})
    a.completed = np.arange(n, dtype=np.int32)
    a.spin_cpu = np.linspace(0, 1, n, dtype=np.float32)
    b = SimpleNamespace(**vars(a))
    assert chip_smoke.first_difference(a, b) is None
    b.spin_cpu = a.spin_cpu.copy()
    b.spin_cpu[3] = np.nextafter(b.spin_cpu[3], np.float32(2))
    msg = chip_smoke.first_difference(a, b)
    assert msg.startswith("config 3 field spin_cpu")
    # -0.0 == 0.0 numerically, but not bit for bit
    b.spin_cpu = a.spin_cpu.copy()
    b.spin_cpu[0] = -0.0
    assert chip_smoke.first_difference(a, b).startswith("config 0 ")
