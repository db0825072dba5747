"""``chip_smoke.py`` off the chip: it must refuse, and its compile cache
goes where the environment says.  What it does ON the chip only a chip
run shows (CHANGES.md quotes one).  And the docs that send a reader to
it, or to any other file of the checkout, name files that exist."""

import glob
import os
import re
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


_TOP_LEVEL = ("tests/", "bin/", "benchmark/", "examples/", "docs/",
              "horovod_tpu/", "csrc/")
# the reader's own training script, by the docs' convention
_READERS_SCRIPTS = {"train.py", "train_3d.py"}


def _stale_names(text, built):
    """Back-quoted paths under this checkout's top-level directories,
    and the scripts of ``python <script>.py`` commands in code spans or
    fenced blocks, that do not exist.  Bare names (``runner.py``) and
    ``.rst`` files (``docs/running.rst``) are left alone: they are the
    upstream's; ``built`` is made by a build, not committed."""
    stale = []
    for span in re.findall(r"`([^`\n]+)`", text):
        path = re.split(r"::|:\d", span.strip())[0]
        if (path.startswith(_TOP_LEVEL) and not path.endswith(".rst")
                and path.rstrip("/") not in built
                and not re.search(r"[*<>$\s]", path)
                and not os.path.exists(os.path.join(REPO, path))):
            stale.append(path)
    code = re.findall(r"```.*?```|`[^`\n]+`", text, flags=re.S)
    for script in re.findall(r"\bpython3? +(\S+\.py)\b", "\n".join(code)):
        if (script not in _READERS_SCRIPTS
                and not os.path.exists(os.path.join(REPO, script))):
            stale.append(f"python {script}")
    return stale


def test_docs_name_only_files_that_exist():
    """``README.md`` and ``docs/*.md`` send the reader only to files of
    this checkout: a deleted script or test leaves no sentence behind."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        built = {line.strip().rstrip("/") for line in f}
    stale = {}
    for doc in ["README.md", *sorted(glob.glob("docs/*.md", root_dir=REPO))]:
        with open(os.path.join(REPO, doc)) as f:
            found = _stale_names(f.read(), built)
        if found:
            stale[doc] = found
    assert not stale, stale
