"""Tier-1-adjacent guards.

1. No direct jax shard_map imports outside ``utils/jax_compat.py``:
   ``shard_map`` has moved between jax releases before, and each move
   broke collection of every file that imported it directly — one
   import point keeps the next move a one-file change.
2. ``pytest --collect-only`` must report zero errors: a collection error
   silently removes an entire file's tests from the tier-1 count.
3. The package's layers import downward only: ``utils`` -> ``core`` /
   ``metrics`` / ``parallel`` -> ``ops`` -> ``models`` -> ``serving``.
   What still points up is listed here with its reason.
4. Only the ``topo`` fixture of ``tests/chip_compile_support.py``
   describes a TPU topology: that call loads the TPU's library, and
   every xdist worker imports every test file.
"""
from __future__ import annotations

import ast
import re
import subprocess
import sys
from pathlib import Path

import pytest


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


# the lower layers, and the upper ones none of them may import
LOWER = ("utils", "core", "metrics", "parallel", "ops", "tuning")
UPPER = ("models", "serving", "proxies", "faults", "analysis")

# (file, upper package) -> why it still points up.  Each is a debt in
# ROADMAP.md; an entry that matches no import fails the test too.
UPWARD_EXCEPTIONS = {
    ("dlnetbench_tpu/tuning/__main__.py", "serving"):
        "the tune CLI is an entry point: it times serving's paged "
        "attention site to fill the DB",
    ("dlnetbench_tpu/tuning/__main__.py", "models"):
        "the tune CLI is an entry point: it times the SPMD step's "
        "gradient-bucket knob to fill the DB",
    ("dlnetbench_tpu/metrics/emit.py", "proxies"):
        "the record emitter takes a ProxyResult: the type belongs "
        "beside the record schema",
    ("dlnetbench_tpu/metrics/emit.py", "analysis"):
        "records are stamped with their attribution as they are emitted",
    ("dlnetbench_tpu/metrics/merge.py", "analysis"):
        "merged records are attributed again after the merge",
}


def _upward_imports(package: str) -> set:
    """{(file, upper package)} over every import statement of the
    package's files, at module level or inside a function."""
    found = set()
    for path in (REPO / "dlnetbench_tpu" / package).rglob("*.py"):
        rel = path.relative_to(REPO).as_posix()
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom):
                assert not node.level, f"{rel}:{node.lineno}: relative import"
                names = [f"{node.module}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            else:
                continue
            for name in names:
                top, _, rest = name.partition(".")
                upper = rest.split(".")[0]
                if top == "dlnetbench_tpu" and upper in UPPER:
                    found.add((rel, upper))
    return found


@pytest.mark.parametrize("package", LOWER)
def test_lower_layers_do_not_import_upward(package):
    found = _upward_imports(package)
    listed = {k for k in UPWARD_EXCEPTIONS
              if k[0].startswith(f"dlnetbench_tpu/{package}/")}
    assert found - listed == set(), (
        f"{package}/ imports a layer above it: {sorted(found - listed)}; "
        f"move the shared piece down or hand it in as an argument")
    assert listed - found == set(), (
        f"exceptions that match no import any more, delete them: "
        f"{sorted(listed - found)}")


def _names_topology_desc(node) -> bool:
    return "get_topology_desc" in (
        getattr(node, "attr", None), getattr(node, "id", None),
        *(a.name for a in getattr(node, "names", ())
          if isinstance(node, ast.ImportFrom)))


def test_no_tier1_file_describes_a_topology_outside_its_fixture():
    """``get_topology_desc`` loads the TPU's library into the process.
    Named at a module's top level it would load it into every worker
    that imports the file; named inside the one fixture, only a case
    that asks for ``topo`` does."""
    found = set()
    for path in (REPO / "tests").rglob("*.py"):
        tree = ast.parse(path.read_text())
        inside = {id(n): fn for fn in ast.walk(tree)
                  if isinstance(fn, ast.FunctionDef)
                  and any("fixture" in ast.unparse(d)
                          for d in fn.decorator_list)
                  for n in ast.walk(fn)}
        for node in ast.walk(tree):
            if _names_topology_desc(node):
                fn = inside.get(id(node))
                found.add((path.relative_to(REPO).as_posix(),
                           fn.name if fn else None))
    assert found == {("tests/chip_compile_support.py", "topo")}


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
