"""The reader of the program's build record (``readers/build_record``)
and the four metrics of ``setup_s`` that read it: on a hand-made log,
and on the log this process keeps after a rehearsal of a cell of each
runner family."""
import importlib
import json
import math
import os
import re
import subprocess
import sys

import pytest

from benchmarks import harness, run
from benchmarks.readers import build_record

MANIFEST = harness.load_json(harness.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = ("setup_before_build_s", "step_trace_lower_s",
           "step_executable_s", "step_code_mb")
REHEARSED = ("minerva7b_train", "phi4miniflash_train_s8k")


def spec(metric):
    return harness.load_json(harness.HERE / "layer_metrics"
                             / f"{metric}.json")


def read(metric):
    s = spec(metric)
    return importlib.import_module(
        f"benchmarks.readers.{s['reader']}").read({}, s["params"])


def record(module="jit_train_k", **over):
    return {"fn": module[4:], "module": module, "began_at_s": 20.0,
            "trace_s": 1.5, "lower_s": 2.5, "executable_s": 3.0,
            "cache": "hit", "cache_retrieval_s": 2.75,
            "backend_compile_s": 0.0, "code_bytes": 41_000_000,
            "op_scopes_s": 0.0, "analyses_s": 0.25, **over}


WANT = {"setup_before_build_s": 31.0, "step_trace_lower_s": 4.0 + 0.5,
        "step_executable_s": 9.0, "step_code_mb": 64.0}


@pytest.mark.parametrize("metric", METRICS)
def test_reads_the_last_record_of_the_step(metric, monkeypatch):
    """Of two builds of the step (a control's, then the run's own) the
    later one; other programs' records (the reference's, a clone's) are
    not the step's."""
    monkeypatch.setattr(build_record, "log", lambda: (
        record(), record("jit_sgd_steps", began_at_s=25.0),
        record(began_at_s=31.0, trace_s=4.0, lower_s=0.5,
               executable_s=9.0, code_bytes=64_000_000),
        record("jit_copy", began_at_s=50.0)))
    assert read(metric) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("log", [
    (), None, (record("jit_decode_step"),),
    (record(code_bytes=None, began_at_s=None, trace_s=None,
            executable_s="3"),)],
    ids=["empty", "no_log", "no_such_module", "no_number"])
def test_nothing_to_read_is_none_and_never_raises(metric, log,
                                                  monkeypatch):
    monkeypatch.setattr(build_record, "log", lambda: log)
    assert read(metric) is None


def test_a_program_without_the_log_gives_none(monkeypatch):
    from dlnetbench_tpu.core import executor
    monkeypatch.delattr(executor, "builds")
    assert build_record.log() is None
    assert all(read(m) is None for m in METRICS)


@pytest.mark.parametrize("metric", METRICS)
def test_spec_is_the_entry_it_is_ready_to_become(metric):
    """The manifest's own rules (``test_bench_manifest.py``) on the
    entry PERF.md section 7 item 0 gives for the metric; until a PR that
    may lists it, the spec names no cell, so that ``scope_dump`` and the
    harness leave it alone."""
    s = spec(metric)
    assert s["reader"] == "build_record" and callable(build_record.read)
    assert (s["layer"], s["moves"]) == ("executor", "setup_s")
    assert s["layer"] in {m["layer"] for m in MANIFEST["per_layer"]}
    assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", metric)
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", s["unit"])
    assert s["params"]["module"] == "jit_train_k"
    assert set(s["params"]["fields"]) <= set(record())
    listed = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    if listed:
        assert listed[0]["workloads"] == s["cells"] == CELLS
        assert (listed[0]["better"], listed[0]["source"]) == \
            ("lower", "program_counter")
    else:
        assert s["cells"] == []


@pytest.fixture(scope="module", params=REHEARSED)
def rehearsed(request):
    """The process's log after one untraced rehearsal of the cell: the
    records the rehearsal added."""
    before = len(build_record.log())
    rc = run.main(["--workload", request.param, "--seed", "5",
                   "--seconds", "0.5", "--trace", "0",
                   "--rehearse-cpu", "1"])
    assert rc != 0                       # a rehearsal is no measurement
    return build_record.log()[before:]


@pytest.mark.parametrize("metric", METRICS)
def test_reads_the_log_a_rehearsal_leaves(rehearsed, metric):
    """With the tracer off and the step freed, each of the four reads a
    finite number from the process's own log, the step's record."""
    steps = [r for r in rehearsed if r["module"] == "jit_train_k"]
    assert len(steps) == 1 and set(steps[0]) == set(record())
    value = read(metric)
    assert math.isfinite(value) and value >= 0
    if metric in ("step_trace_lower_s", "step_executable_s"):
        assert value > 0
    if metric == "setup_before_build_s":
        assert value == steps[-1]["began_at_s"] > 0
    assert steps[-1]["op_scopes_s"] == 0.0      # no tracer was on


def test_the_tool_prints_the_four_and_the_log(tmp_path):
    """``python3 benchmarks/readers/build_record.py <out> ...`` is one
    run of ``run.py`` and then the metrics and the log, on the last line
    and in the file; here a rehearsal, so the exit code is ``run.py``'s
    non-zero."""
    out = tmp_path / "build.json"
    got = subprocess.run(
        [sys.executable, str(harness.HERE / "readers" / "build_record.py"),
         str(out), "--workload", "minerva7b_train", "--seed", "5",
         "--seconds", "0.5", "--trace", "0", "--rehearse-cpu", "1"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert got.returncode == 1, got.stderr[-2000:]
    last = json.loads(got.stdout.splitlines()[-1])
    assert last == json.loads(out.read_text())
    assert last["line"] == "build_record" and set(last["metrics"]) == \
        set(METRICS)
    assert all(math.isfinite(v) for v in last["metrics"].values())
    assert "jit_train_k" in [r["module"] for r in last["builds"]]
