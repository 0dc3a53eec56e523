"""Repo hygiene guards: no build artifacts in the tree, and .gitignore
keeps covering the artifact patterns so they can't sneak back in."""
import pathlib
import shutil
import subprocess

import pytest

_ROOT = pathlib.Path(__file__).resolve().parent.parent

# Patterns .gitignore must carry — dropping one silently reopens the
# door to committed bytecode/caches.
_REQUIRED_IGNORES = ("__pycache__/", "*.pyc", ".pytest_cache/",
                     "artifacts/", ".jax_cache/")


def _tracked_files():
    if shutil.which("git") is None:
        pytest.skip("git not available")
    proc = subprocess.run(["git", "ls-files"], cwd=_ROOT,
                          capture_output=True, text=True)
    if proc.returncode != 0:
        pytest.skip(f"not a git checkout: {proc.stderr.strip()}")
    return proc.stdout.splitlines()


def test_no_bytecode_or_caches_tracked():
    offenders = [f for f in _tracked_files()
                 if "__pycache__" in f or f.endswith((".pyc", ".pyo"))
                 or ".pytest_cache" in f]
    assert not offenders, \
        f"build artifacts tracked in git: {offenders[:10]}"


def test_gitignore_covers_artifact_patterns():
    gitignore = (_ROOT / ".gitignore").read_text().splitlines()
    patterns = {line.strip() for line in gitignore
                if line.strip() and not line.startswith("#")}
    missing = [p for p in _REQUIRED_IGNORES if p not in patterns]
    assert not missing, f".gitignore lost required patterns: {missing}"
