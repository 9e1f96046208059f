"""Entry-point plumbing: the persistent compile cache's fixed directory and
the serve CLI's choice of configuration."""

import os
import subprocess
import sys

import pytest

from repro import configs
from repro.launch import cache, serve

_COMPILE = """
import sys
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if sys.argv[1] == "compile":
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.block_until_ready(jax.jit(lambda x: jnp.cos(x) * 3)(jnp.ones(7)))
"""


def _run(arg, **extra_env):
    env = {k: os.environ[k] for k in ("PATH", "HOME", "TMPDIR")
           if k in os.environ}
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu", **extra_env)
    proc = subprocess.run([sys.executable, "-c", _COMPILE, arg],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout.split()


def test_compile_cache_uses_env_dir_only(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets nothing else and
    compiled programs land there."""
    d = str(tmp_path / "cc")
    returned, configured = _run("compile", JAX_COMPILATION_CACHE_DIR=d)
    assert returned == configured == d
    assert any(name.endswith("-cache") for name in os.listdir(d))


def test_compile_cache_defaults_to_checkout_dir():
    """Without the variable the cache is <checkout>/.jax_cache — a fixed
    path, so a later run finds what an earlier one cached — ignored by
    git."""
    returned, configured = _run("config-only")
    expect = os.path.join(os.getcwd(), ".jax_cache")
    assert returned == configured == str(cache.CHECKOUT_CACHE_DIR) == expect
    with open(".gitignore") as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("argv,expect", [
    ([], configs.get_reduced("qwen2-1.5b")),
    (["--arch", "qwen2-1.5b"], configs.get("qwen2-1.5b")),
    (["--arch", "qwen2-1.5b", "--reduced"], configs.get_reduced("qwen2-1.5b")),
    (["--arch", "starcoder2-3b", "--reduced"],
     configs.get_reduced("starcoder2-3b")),
])
def test_serve_cli_config(monkeypatch, argv, expect):
    """``--arch`` serves the published widths, ``--reduced`` the tiny
    preset, and no ``--arch`` the reduced qwen2-1.5b (what CI serves)."""
    got = {}

    def fake_build(cfg, **kwargs):
        got["cfg"] = cfg
        return "program"

    monkeypatch.setattr(serve, "build_program", fake_build)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(serve.lp, "launch_and_wait", lambda *a, **k: None)
    serve.main(argv)
    assert got["cfg"] == expect
