"""Entry-point plumbing: the compile-cache helper and chip_smoke.py.

``chip_smoke.py`` is the quickest proof that the served path runs on a
TPU; off the chip it must fail and never print its ok line.  Its
``--tiny`` rehearsal drives the same phases on the CPU.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import serve

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert serve.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = serve.configure_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert serve.configure_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def _smoke(script: Path, tmp_path: Path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, str(script), *args], env=env,
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_off_tpu(tmp_path, where):
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    r = _smoke(script, tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    if where == "checkout":
        assert "FAIL: no TPU" in r.stdout


def test_chip_smoke_tiny_rehearsal(tmp_path):
    r = _smoke(REPO / "chip_smoke.py", tmp_path, "--tiny")
    log = r.stdout + r.stderr
    assert r.returncode == 1, log
    assert r.stdout.count(" ok\n") == 2, log     # both stage splits
    assert "completed=16/16 finite_logits=16/16" in r.stdout, log
    assert "FAIL: rehearsal on cpu passed" in r.stdout, log
    assert '"ok"' not in r.stdout
