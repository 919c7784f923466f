"""The readers of the program's scopes and spans (``scope_ms``,
``scope_other``, ``span_s``) against the hand-built trace kept beside
them (``scope_fixture.json``: events named as the TPU trace names them,
the program's table, two whole executions and two cut short), and
``scope_dump.py``, which has the harness read them until
``BENCHMARK.json`` lists them."""
import dataclasses
import importlib
import json

import pytest

from benchmarks import harness, run, scope_dump
from benchmarks.readers import scope_ms

FIX = harness.load_json(harness.HERE / "scope_fixture.json")
WANT = FIX["expect"]
MANIFEST = harness.load_json(harness.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NEW = ["attn_ms", "mlp_ms", "moe_experts_ms", "moe_route_ms",
       "head_loss_ms", "scope_other_pct", "step_build_s"]
KIND = {"minerva7b_train": "dense", "mixtral8x7b_train": "moe"}
READERS = ("scope_ms", "scope_other", "span_s")


def spec(metric):
    return harness.load_json(harness.HERE / "layer_metrics"
                             / f"{metric}.json")


def trace():
    return {"devices": {0: {"ops": [tuple(e) for e in FIX["ops"]],
                            "modules": [tuple(e)
                                        for e in FIX["modules"]]}},
            "host": [tuple(e) for e in FIX["host"]]}


def program(kind="dense"):
    got = json.loads(json.dumps(FIX["program_trace"]))
    if kind == "moe":
        got["op_scopes"] = FIX["op_scopes_moe"]
    return got


def ctx(kind="dense"):
    """A reader's ``ctx`` as ``run.traced_metrics`` builds it, with the
    program's export where the runner will put it."""
    return {"record": {"program_trace": program(kind)},
            "devices": [trace()["devices"][0]],
            "window": tuple(FIX["window"])}


def read(metric, c):
    s = spec(metric)
    return importlib.import_module(
        f"benchmarks.readers.{s['reader']}").read(c, s["params"])


@pytest.mark.parametrize("kind,metric", [
    (k, m) for k in ("dense", "moe") for m in sorted(WANT[k])])
def test_reader_against_the_fixture(kind, metric):
    assert read(metric, ctx(kind)) == pytest.approx(WANT[kind][metric])


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_disjoint_scopes_and_the_rest_sum_to_the_step(kind):
    got = {m: read(m, ctx(kind)) for m in WANT[kind]}
    step = WANT["step_device_ms"]
    parts = sum(v for m, v in got.items() if m.endswith("_ms"))
    assert parts + got["scope_other_pct"] / 100 * step == \
        pytest.approx(step)


def test_overlapping_events_of_one_scope_count_once():
    per = scope_ms.scope_seconds(ctx(), "train_k", {"attn"})
    assert [s for s, _ in per] == pytest.approx([1.5, 1.7])
    assert [busy for _, busy in per] == pytest.approx([3.0, 3.0])


def test_executions_cut_short_by_the_trace_are_left_out():
    assert len(scope_ms.scope_seconds(ctx(), "train_k", {"attn"})) == 2


def test_the_table_is_found_by_the_modules_name():
    assert "fusion.3" in scope_ms.table(ctx(), "train_k")
    assert scope_ms.table(ctx(), "decode_step") is None


@pytest.mark.parametrize("name,want", [
    ("%fusion.54 = bf16[4096,51200]{1,0:T(8,128)(2,1)} fusion(bf16[4096]"
     " %p)", "fusion.54"),
    ("%flash_bwd_dkv.2 = (bf16[2,8]{1,0}, bf16[2,8]{1,0}) custom-call("
     "bf16[2,8] %copy.3)", "flash_bwd_dkv.2"),
    ("%copy-done.15 = bf16[2]{0} copy-done(%copy-start.15)",
     "copy-done.15"),
    ("bench_step", None)])
def test_instruction_name_of_an_event(name, want):
    assert scope_ms.instruction(name) == want


def test_a_table_that_matches_nothing_reads_all_other():
    c = ctx()
    c["record"]["program_trace"]["op_scopes"] = {
        "jit_train_k": {"fusion.1000": "attn"}}
    assert read("scope_other_pct", c) == pytest.approx(100.0)
    assert read("attn_ms", c) is None


def no_program_trace():
    c = ctx()
    c["record"] = {}
    return c


def no_executions():
    c = ctx()
    c["devices"][0]["modules"] = []
    return c


def nothing_of_the_scope_or_span():
    c = ctx()
    c["record"]["program_trace"] = {
        "op_scopes": {"jit_train_k": {"fusion.1": "embed"}},
        "spans": [{"name": "compile", "ts_us": 0.0, "dur_us": 1.0,
                   "attrs": {"fn": "clone"}}]}
    return c


# the span needs no trace, and all the time in other scopes is a reading
@pytest.mark.parametrize("metric,broken", [
    (m, b) for m in NEW for b in (no_program_trace, no_executions,
                                  nothing_of_the_scope_or_span)
    if (m, b) not in (("step_build_s", no_executions),
                      ("scope_other_pct", nothing_of_the_scope_or_span))])
def test_nothing_to_read_is_none(metric, broken):
    assert read(metric, broken()) is None


@pytest.mark.parametrize("metric", NEW)
def test_listed_for_a_cell_and_nothing_to_read_fails_the_run(metric):
    """What the harness does with such a None once ``BENCHMARK.json``
    lists the metric: the run fails, the line is not shortened."""
    s = spec(metric)
    cell = harness.load_cell(s["cells"][0])
    cell.per_layer = [{"name": metric, "unit": s["unit"]}]
    with pytest.raises(harness.BenchError, match="found nothing to read"):
        harness.read_layer_metrics(cell, no_program_trace())


@pytest.mark.parametrize("metric", NEW)
def test_spec_file(metric):
    s = spec(metric)
    assert s["reader"] in READERS
    assert s["cells"] and set(s["cells"]) <= set(CELLS)
    assert s["moves"] in {m["name"] for m in MANIFEST["end_to_end"]
                          if set(s["cells"])
                          <= harness.metric_cells(m, MANIFEST)}
    assert s["layer"] in {m["layer"] for m in MANIFEST["per_layer"]}
    if "scopes" in s["params"]:
        from dlnetbench_tpu.metrics import spans
        assert set(s["params"]["scopes"]) <= set(spans.SCOPES)


def test_other_takes_every_scope_a_ms_metric_reads():
    read_by_ms = {sc for m in NEW if spec(m)["reader"] == "scope_ms"
                  for sc in spec(m)["params"]["scopes"]}
    assert set(spec("scope_other_pct")["params"]["scopes"]) == read_by_ms


def unlisted_of(cell):
    listed = {m["name"] for m in MANIFEST["per_layer"]}
    return {m for m in NEW if cell in spec(m)["cells"]} - listed


@pytest.mark.parametrize("cell", CELLS)
def test_unlisted_are_the_cells_specs_the_manifest_lacks(cell):
    got = scope_dump.unlisted(cell)
    assert {e["name"] for e in got} == unlisted_of(cell)
    assert all(e["unit"] == spec(e["name"])["unit"] for e in got)


def traced(cell, export):
    """``run.traced_metrics`` on the fixture's trace with the cell's
    listed metrics left out (the fixture holds nothing for them), as
    ``scope_dump`` has the harness read the unlisted ones."""
    bare = dataclasses.replace(harness.load_cell(cell), per_layer=[])
    outcome = {"record": {}, "cache": {"hits": 0, "misses": 0},
               "device": {"platform": "cpu", "kind": "cpu"}}
    with scope_dump.reading_unlisted(export) as seen:
        metrics = run.traced_metrics(bare, outcome, trace(), 1)[0]
    return metrics, seen


@pytest.mark.parametrize("cell", CELLS)
def test_scope_dump_reads_through_the_harness(cell):
    listed_only = harness.read_layer_metrics
    metrics, seen = traced(cell, lambda: program(KIND[cell]))
    assert harness.read_layer_metrics is listed_only
    want = WANT[KIND[cell]]
    assert metrics is seen["metrics"] and set(metrics) == unlisted_of(cell)
    assert {m: v["value"] for m, v in metrics.items()} == \
        {m: pytest.approx(want[m]) for m in metrics}
    got = scope_dump.report(cell, seen, program(KIND[cell]))
    assert sum(got["scope_ms"].values()) == \
        pytest.approx(WANT["step_device_ms"])
    assert got["scope_ms"]["attn"] == pytest.approx(1600.0)
    if KIND[cell] == "dense":
        assert got["scope_ms"]["unknown"] == pytest.approx(250.0)
    assert [s["name"] for s in got["spans"]] == ["compile", "compile"]
    scope, name, ms = got["top_ops"][0]
    assert (scope, name) == ("attn", "fusion.1 bf16[8,128]")
    assert ms == pytest.approx(1100.0)      # 1.0 s and 1.2 s


@pytest.mark.parametrize("cell", CELLS)
def test_scope_dump_fails_the_run_on_a_program_without_scopes(cell):
    with pytest.raises(harness.BenchError, match="found nothing to read"):
        traced(cell, lambda: {"spans": [], "op_scopes": {}})


@pytest.mark.parametrize("cell", CELLS)
def test_run_with_the_programs_tracer_at_rehearsal_sizes(cell, capsys):
    """The whole runner with ``spans.enable()`` called first: the
    executor hands the step's table and its ``compile`` span to the
    tracer, and the tracer is off again afterwards."""
    from dlnetbench_tpu.metrics import spans
    rc, seen, got = scope_dump.run_with_program_tracer(
        ["--workload", cell, "--seed", "5", "--seconds", "0.5",
         "--trace", "0", "--rehearse-cpu", "1"])
    capsys.readouterr()
    assert rc != 0 and not spans.is_enabled()   # a rehearsal
    assert seen == {}                           # and not traced
    table = got["op_scopes"]["jit_train_k"]
    scopes = set(table.values())
    assert {"attn", "head_loss", "optimizer", "embed"} <= scopes
    assert ("mlp" in scopes) == (KIND[cell] == "dense")
    assert ("moe.experts" in scopes) == (KIND[cell] == "moe")
    c = {"record": {"program_trace": got}}
    assert read("step_build_s", c) > 0
    # the program's spans of a train run: builds, and nothing a step
    assert {s["name"] for s in got["spans"]} == {"compile"}
