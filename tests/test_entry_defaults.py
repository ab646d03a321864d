"""What the entry points decide for themselves: the interpret-mode default,
the persistent compilation cache, a backend-free import, and the chip
smoke test's refusal to fall back to the CPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache
from repro.kernels.common import default_interpret

ROOT = Path(__file__).resolve().parents[1]


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.CACHE_ENV}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu", **extra)
    return env


def test_default_interpret_is_decided_per_call(monkeypatch):
    assert default_interpret() is True                 # the CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert default_interpret() is False


def test_import_initializes_no_backend():
    code = ("import repro, repro.kernels, repro.serve.vision, "
            "repro.models.mbconv, repro.core.autotune, repro.compat\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    subprocess.run([sys.executable, "-c", code], env=_env(), check=True,
                   timeout=120)


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_a_fixed_checkout_path(monkeypatch,
                                                         cache_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert Path(first) == ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first


def test_compile_cache_leaves_the_environment_setting(monkeypatch,
                                                      cache_config,
                                                      tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # untouched


def test_chip_smoke_refuses_the_cpu():
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=_env(), capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
