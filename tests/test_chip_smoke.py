"""The chip smoke's contract off the chip, and the compile-cache helper."""
import json
import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_smoke_refuses_to_run_without_a_tpu(tmp_path):
    """No accelerator: non-zero exit, and no result line on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=tmp_path,
    )
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    for line in r.stdout.splitlines():
        try:
            assert "ok" not in json.loads(line)
        except json.JSONDecodeError:
            pass


def test_compile_cache_honours_env_else_fixed_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set
    monkeypatch.delenv(compile_cache.ENV)
    try:
        got = compile_cache.enable()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
