"""Where the persistent compile cache lives (utils/cache.py).

``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and the program
sets no directory in code. Unset: ``<checkout>/.jax_cache``, the same
path on every invocation (the path is part of the cache key).
"""

import os
import subprocess
import sys

import jax
import pytest

from deepspeech_tpu.utils import cache
from deepspeech_tpu.utils.envscrub import scrubbed_cpu_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT = os.path.join(REPO, ".jax_cache")
DIR_OPTION = "jax_compilation_cache_dir"


@pytest.fixture()
def updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.delenv("DS2_COMPILE_CACHE", raising=False)
    return calls


def _child(code: str, env_dir: "str | None") -> str:
    env = {k: v for k, v in os.environ.items()
           if k != cache.CACHE_DIR_ENV}
    if env_dir is not None:
        env[cache.CACHE_DIR_ENV] = env_dir
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip()


def test_env_set_sets_no_directory_in_code(updates, monkeypatch, tmp_path):
    monkeypatch.setenv(cache.CACHE_DIR_ENV, str(tmp_path))
    assert cache.enable_compilation_cache() is True
    assert cache.resolve_cache_dir() == str(tmp_path)
    assert DIR_OPTION not in [name for name, _ in updates]
    # The write thresholds are still the program's to set.
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in updates


def test_env_set_jax_reports_the_env_path(tmp_path):
    """jax reads the variable at import, so ask a fresh interpreter."""
    got = _child(
        "import jax; from deepspeech_tpu.utils import cache; "
        "assert cache.enable_compilation_cache(); "
        "print(jax.config.jax_compilation_cache_dir)", str(tmp_path))
    assert got == str(tmp_path)


def test_env_unset_is_the_checkout_dir_on_every_call(updates, monkeypatch):
    monkeypatch.delenv(cache.CACHE_DIR_ENV, raising=False)
    assert cache.resolve_cache_dir() == DEFAULT
    assert cache.resolve_cache_dir() == DEFAULT
    assert cache.enable_compilation_cache() is True
    assert (DIR_OPTION, DEFAULT) in updates


def test_env_unset_is_the_same_dir_in_another_process():
    code = ("import jax; from deepspeech_tpu.utils import cache; "
            "cache.enable_compilation_cache(); "
            "print(cache.resolve_cache_dir()); "
            "print(jax.config.jax_compilation_cache_dir)")
    first, second = _child(code, None), _child(code, None)
    assert first == second == f"{DEFAULT}\n{DEFAULT}"


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_usage_sidecar_follows_the_cache_dir(monkeypatch, env_dir):
    if env_dir is None:
        monkeypatch.delenv(cache.CACHE_DIR_ENV, raising=False)
    else:
        monkeypatch.setenv(cache.CACHE_DIR_ENV, env_dir)
    assert cache.usage_sidecar_path() == os.path.join(
        env_dir or DEFAULT, cache.USAGE_SIDECAR)


def test_opt_out_touches_nothing(updates, monkeypatch):
    monkeypatch.setenv("DS2_COMPILE_CACHE", "0")
    assert cache.enable_compilation_cache() is False
    assert updates == []


def test_cpu_children_keep_the_cache_location():
    env = scrubbed_cpu_env(REPO, 4, base={
        cache.CACHE_DIR_ENV: "/some/dir", "JAX_PLATFORMS": "tpu",
        "JAX_TRACEBACK_FILTERING": "off", "XLA_FLAGS": "--x",
        "TPU_WORKER_HOSTNAMES": "h", "PYTHONPATH": "/elsewhere"})
    assert env[cache.CACHE_DIR_ENV] == "/some/dir"
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=4"
    assert "JAX_TRACEBACK_FILTERING" not in env
    assert "TPU_WORKER_HOSTNAMES" not in env
    assert env["PYTHONPATH"] == os.pathsep.join([REPO, "/elsewhere"])
