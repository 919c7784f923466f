"""A number of the step's build from the program's own record of it
(``core/executor.builds()``: one record for every program the executor
built in this process, filled where the build happens, tracer or not):
the last record whose ``module`` is ``module``, its ``fields`` summed,
times ``scale``.  The log is the process's, not the step's, so it is
still there after the runner has freed the step.  A program that keeps
no such log, a log without such a record, or a record without a number
under one of the fields gives nothing to read.

    python3 benchmarks/readers/build_record.py <out.json> --workload <cell> --seed <n> --seconds <s> --trace <0|1>

is one run of ``run.py`` followed by the metrics of this reader and the
whole log, as one more line and in ``<out.json>``: how they are read on
the chip until ``BENCHMARK.json`` lists them (PERF.md section 7 item 0).
"""
from __future__ import annotations


def log():
    """The process's build records in build order, or None where the
    program has no such log."""
    try:
        from dlnetbench_tpu.core import executor
        return executor.builds()
    except (ImportError, AttributeError):
        return None


def read(ctx, params):
    found = [r for r in log() or () if r.get("module") == params["module"]]
    if not found:
        return None
    values = [found[-1].get(f) for f in params["fields"]]
    if not all(isinstance(v, (int, float)) for v in values):
        return None
    return sum(values) * params.get("scale", 1.0)


if __name__ == "__main__":
    import json
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    from benchmarks import harness, run

    out, argv = Path(sys.argv[1]), sys.argv[2:]
    rc = run.main(argv)
    specs = {p.stem: harness.load_json(p) for p in sorted(
        (harness.HERE / "layer_metrics").glob("*.json"))}
    got = {"line": "build_record",
           "metrics": {name: read({}, s["params"])
                       for name, s in specs.items()
                       if s["reader"] == "build_record"},
           "builds": list(log() or ())}
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(got, indent=1))
    print(json.dumps(got), flush=True)
    raise SystemExit(rc)
