"""One cell traced with the program's own tracer on: the step's device
time by the program's scopes, and the program's spans.

    python3 benchmarks/scope_dump.py <out.json> --workload <cell> --seed <n> --seconds <s>

Runs ``run.py --trace 1`` itself (``run.main``), with ``spans.enable()``
called first, so that the executor registers the step's op->scope table
with the program's tracer.  The per-layer metrics whose spec in
``layer_metrics/`` names this cell and which ``BENCHMARK.json`` does not
list yet (PERF.md section 7 item 0 says what listing them needs) are
read where the listed ones are: by ``harness.read_layer_metrics`` from
the ``ctx`` that ``run.traced_metrics`` builds, with the tracer's export
under ``record["program_trace"]``, and they come out on the run's own
result line; one with nothing to read fails the run as a listed one
does.  ``<out.json>`` gets them again with the time of every scope, the
operations with most time in an execution under their scope, and the
program's spans.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import harness, run, trace_reduce as tr  # noqa: E402
from benchmarks.readers import module_ms, scope_ms  # noqa: E402

MODULE = "train_k"


def unlisted(cell_name: str) -> list:
    """A ``per_layer`` entry for each spec in ``layer_metrics/`` that
    names the cell and that ``BENCHMARK.json`` does not list."""
    listed = {m["name"] for m in harness.load_json(
        harness.ROOT / "BENCHMARK.json")["per_layer"]}
    out = []
    for path in sorted((harness.HERE / "layer_metrics").glob("*.json")):
        spec = harness.load_json(path)
        if path.stem not in listed and cell_name in spec["cells"]:
            out.append({"name": path.stem, "unit": spec["unit"]})
    return out


@contextlib.contextmanager
def reading_unlisted(export):
    """While inside, ``harness.read_layer_metrics`` also reads the
    cell's unlisted metrics, with ``export()`` (the program tracer's)
    where ``runners/train.py`` will put it.  Yields a dict that gets the
    ``ctx`` the harness read from and the ``metrics`` it read."""
    seen: dict = {}
    listed_only = harness.read_layer_metrics

    def read_layer_metrics(cell, ctx):
        ctx["record"]["program_trace"] = export()
        seen["ctx"] = ctx
        seen["metrics"] = listed_only(dataclasses.replace(
            cell, per_layer=cell.per_layer + unlisted(cell.name)), ctx)
        return seen["metrics"]

    harness.read_layer_metrics = read_layer_metrics
    try:
        yield seen
    finally:
        harness.read_layer_metrics = listed_only


def run_with_program_tracer(argv: list):
    """(exit code of ``run.main(argv)``, what the harness read and from
    which ``ctx`` if the run was traced, what the program's tracer
    exported) with the tracer enabled around the whole run."""
    from dlnetbench_tpu.metrics import spans
    tracer = spans.enable()
    try:
        with reading_unlisted(tracer.export) as seen:
            rc = run.main(argv)
    finally:
        spans.disable()
    return rc, seen, tracer.export()


def by_op(ctx: dict, key) -> dict:
    """{key(event name): median ms of a whole execution spent in the
    device operations of that key}."""
    runs = module_ms.executions(ctx, MODULE)
    events: dict = {}
    for e in ctx["devices"][0]["ops"] if runs else ():
        events.setdefault(key(e[0]), []).append(e)
    return {k: 1e3 * statistics.median(
                tr.busy_seconds(tr.clip(evs, a, b)) for a, b in runs)
            for k, evs in events.items()}


def scope_lookup(ctx: dict):
    scope_of = scope_ms.table(ctx, MODULE) or {}
    return lambda name: scope_of.get(scope_ms.instruction(name), "unknown")


def by_scope(ctx: dict) -> dict:
    """Median ms an execution spends in each scope the table names, and
    in instructions it does not know (``unknown``)."""
    return by_op(ctx, scope_lookup(ctx))


def top_ops(ctx: dict, n: int = 25) -> list:
    """[[scope, short name, median ms an execution]] of the operations
    with most time in a whole execution."""
    ms = by_op(ctx, lambda name: name)
    scope = scope_lookup(ctx)
    return [[scope(name), tr.short_name(name), ms[name]]
            for name in sorted(ms, key=ms.get, reverse=True)[:n]]


def report(cell: str, seen: dict, program: dict) -> dict:
    got = {"workload": cell, "metrics": seen.get("metrics"),
           "spans": program["spans"]}
    if "ctx" in seen:
        got.update(scope_ms=by_scope(seen["ctx"]),
                   top_ops=top_ops(seen["ctx"]))
    return got


if __name__ == "__main__":
    out, argv = sys.argv[1], sys.argv[2:]
    rc, seen, program = run_with_program_tracer([*argv, "--trace", "1"])
    got = report(argv[argv.index("--workload") + 1], seen, program)
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    Path(out).write_text(json.dumps(got, indent=1))
    print(json.dumps({"line": "scope_dump",
                      "scope_ms": got.get("scope_ms")}), flush=True)
    raise SystemExit(rc)
