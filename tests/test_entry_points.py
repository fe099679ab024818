"""The chip entry point and the compile-cache rule.

``chip_smoke.py`` is the proof that the system runs on a TPU, so off the
chip it must fail loudly: a CPU backend, or a directory that holds the
script and nothing else of the repository, exits non-zero and prints no
``ok`` line.  ``enable_compile_cache`` keeps JAX's persistent cache where
``JAX_COMPILATION_CACHE_DIR`` says, and otherwise at ``<repo>/.jax_cache``.
"""
import os
import shutil
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run_smoke(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=300, cwd=cwd, env=env)


def test_chip_smoke_refuses_the_cpu(tmp_path):
    r = _run_smoke(os.path.join(ROOT, "chip_smoke.py"), str(tmp_path))
    assert r.returncode != 0, r.stdout + r.stderr
    assert '"ok"' not in r.stdout, r.stdout
    assert "no TPU" in r.stderr, r.stderr


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run_smoke(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert r.returncode != 0, r.stdout + r.stderr
    assert '"ok"' not in r.stdout, r.stdout


def test_compile_cache_directory(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", before)
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before  # JAX's own
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
