"""Tier-1-adjacent guards.

1. No direct jax shard_map imports outside ``utils/jax_compat.py``:
   ``shard_map`` has moved between jax releases before, and each move
   broke collection of every file that imported it directly — one
   import point keeps the next move a one-file change.
2. ``pytest --collect-only`` must report zero errors: a collection error
   silently removes an entire file's tests from the tier-1 count.
"""
from __future__ import annotations

import re
import subprocess
import sys
from pathlib import Path


REPO = Path(__file__).resolve().parent.parent
SHIM = "dlnetbench_tpu/utils/jax_compat.py"

_DIRECT_IMPORT = re.compile(
    r"^\s*(from\s+jax\s+import\s+.*\bshard_map\b"
    r"|from\s+jax\.experimental\.shard_map\s+import"
    r"|from\s+jax\.experimental\s+import\s+.*\bshard_map\b)",
    re.MULTILINE)


def _repo_py_files():
    for sub in ("dlnetbench_tpu", "tests", "examples"):
        yield from (REPO / sub).rglob("*.py")


def test_no_direct_shard_map_imports():
    offenders = []
    for path in _repo_py_files():
        rel = path.relative_to(REPO).as_posix()
        if rel == SHIM:
            continue
        if _DIRECT_IMPORT.search(path.read_text()):
            offenders.append(rel)
    assert not offenders, (
        f"direct jax shard_map imports outside {SHIM}: {offenders} — "
        f"import it from dlnetbench_tpu.utils.jax_compat instead")


def test_collection_is_clean():
    """Zero collection errors — one silently removes a whole file's
    tests from every tier-1 run."""
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/", "-q", "--collect-only",
         "-p", "no:cacheprovider"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": str(REPO),
             "HOME": str(Path.home())},
    )
    tail = "\n".join(proc.stdout.splitlines()[-10:])
    assert proc.returncode == 0, f"collect-only failed:\n{tail}\n{proc.stderr[-2000:]}"
    assert "error" not in tail.lower(), f"collection errors:\n{tail}"
