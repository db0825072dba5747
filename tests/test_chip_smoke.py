"""``chip_smoke.py`` off the chip: it must refuse, and its compile cache
goes where the environment says.  What it does ON the chip only a chip
run shows (CHANGES.md quotes one)."""

import os
import subprocess
import sys

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_to_run_without_a_tpu():
    """No CPU branch: under JAX_PLATFORMS=cpu the script exits non-zero
    and prints no result line — a CPU run can never pass for a chip
    run."""
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert result.returncode != 0
    assert '"ok"' not in result.stdout, result.stdout
    assert "not a TPU" in result.stderr


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache
    is ``<checkout>/.jax_cache`` — one place, never both."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert chip_smoke.compile_cache_dir() == os.path.join(REPO,
                                                          ".jax_cache")
