"""The reader of the program's phases (``readers/phase_ms.py``) against
the hand-built trace kept beside it (``phase_fixture.json``: a loop's
event over body events of two phases, two whole executions and two cut
short), what it reads from a program that names no phase (the committed
scope fixtures of the five cells that list its metrics), the four
entries of the manifest, and the script's scope x phase table."""
import importlib
import json

import pytest

from benchmarks import harness, run, trace_reduce as tr
from benchmarks.readers import phase_ms

FIX = harness.load_json(harness.HERE / "phase_fixture.json")
WANT = FIX["expect"]
MANIFEST = harness.load_json(harness.ROOT / "BENCHMARK.json")
METRICS = ["step_forward_ms", "step_recompute_ms", "step_backward_ms",
           "step_unphased_pct"]
PHASE_OF = dict(zip(METRICS, phase_ms.PHASES))
CELLS = {"phi4miniflash_train_s8k": "scope_fixture_hybrid.json",
         "kimivl_a3b_train_s8k": "scope_fixture_latent_moe.json",
         "qwen3next_a3b_train_s16k": "scope_fixture_linear_moe.json",
         "lfm2_8b_a1b_train_s8k": "scope_fixture_conv_moe.json",
         "smallthinker_21b_a3b_train_s16k": "scope_fixture_swa_moe.json"}
NS = 1e-9


def spec(metric):
    return harness.load_json(harness.HERE / "layer_metrics"
                             / f"{metric}.json")


def ctx_of(fix):
    """A reader's ``ctx`` as ``run.traced_metrics`` builds it on a
    fixture's trace, the program's export where the runner puts it."""
    return {"record": {**json.loads(json.dumps(fix.get("record", {}))),
                       "program_trace": json.loads(json.dumps(
                           fix["program_trace"]))},
            "devices": [{"ops": [tuple(e) for e in fix["ops"]],
                         "modules": [tuple(e) for e in fix["modules"]]}],
            "window": tuple(fix["window"]), "peaks": fix.get("peaks"),
            "cache": {"hits": 1, "misses": 0}}


def ctx():
    return ctx_of(FIX)


def read(metric, c):
    s = spec(metric)
    return importlib.import_module(
        f"benchmarks.readers.{s['reader']}").read(c, s["params"])


@pytest.mark.parametrize("metric", METRICS)
def test_reader_against_the_fixture(metric):
    assert read(metric, ctx()) == pytest.approx(WANT[metric])


def test_phases_and_scopes_together_pick_one_cell_of_the_table():
    params = {"module": "train_k", "phases": ["backward"],
              "scopes": ["head_loss"]}
    assert phase_ms.read(ctx(), params) == pytest.approx(
        WANT["table_ms"]["head_loss"]["backward"])
    params["phases"] = ["forward", "backward"]
    assert phase_ms.read(ctx(), params) == pytest.approx(525.0 + 315.0)
    assert phase_ms.read(ctx(), {**params, "share": True}) == pytest.approx(
        100 * 0.8 / 2.7)


def test_the_phases_partition_an_executions_busy_time():
    """A loop's event lies over body events of two phases: each instant
    has one owner, so the four phases' times sum to the busy union to
    the nanosecond, where a union a phase would count the loop's 0.8 s
    whole under ``forward`` and 0.3 s of it again under ``backward``."""
    c = ctx()
    per = phase_ms.by_scope_and_phase(c, "train_k")
    ops = c["devices"][0]["ops"]
    runs = [(1.0, 4.0), (5.0, 8.3)]
    assert len(per) == 2
    for i, (run_, (a, b)) in enumerate(zip(per, runs)):
        by_phase = {p: sum(s for (_, phase), s in run_.items()
                           if phase == p) for p in phase_ms.PHASES}
        busy = tr.busy_seconds(tr.clip(ops, a, b))
        assert abs(sum(by_phase.values()) - busy) < NS
        assert busy == pytest.approx(WANT["busy_seconds"][i])
        for p in phase_ms.PHASES:
            assert by_phase[p] == pytest.approx(WANT["seconds"][p][i])
    # the loop itself owns only what neither body event covers
    owned = phase_ms.owned_by_operation(c, "train_k")
    loop = [next(s for n, s in run_.items() if n.startswith("%while.2"))
            for run_ in owned]
    assert loop == pytest.approx(WANT["loop_owns_seconds"])


@pytest.mark.parametrize("events,want", [
    # a body inside its loop, with room before, between and after
    ([("loop", 0.0, 10.0), ("a", 1.0, 2.0), ("b", 4.0, 5.0)],
     {"loop": 3.0, "a": 2.0, "b": 5.0}),
    # three deep
    ([("outer", 0.0, 8.0), ("inner", 1.0, 6.0), ("op", 2.0, 1.0)],
     {"outer": 2.0, "inner": 5.0, "op": 1.0}),
    # begun together: the shorter is inside the longer
    ([("loop", 0.0, 4.0), ("first", 0.0, 1.0)],
     {"loop": 3.0, "first": 1.0}),
    # a body that fills its loop leaves it nothing
    ([("loop", 0.0, 2.0), ("a", 0.0, 1.0), ("a", 1.0, 1.0)], {"a": 2.0}),
    # side by side, a gap between, out of order
    ([("b", 3.0, 1.0), ("a", 0.0, 2.0)], {"a": 2.0, "b": 1.0}),
    # not nested (no trace holds this): still every instant once
    ([("a", 0.0, 10.0), ("b", 5.0, 7.0)], {"a": 5.0, "b": 7.0}),
    ([], {})])
def test_an_instant_belongs_to_the_event_that_began_last(events, want):
    got = phase_ms.owned_seconds(events)
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(tr.busy_seconds(events))


def test_an_operation_the_tables_do_not_know_is_unknown_and_unphased():
    per = phase_ms.by_scope_and_phase(ctx(), "train_k")
    assert [run_[("unknown", "none")] for run_ in per] \
        == pytest.approx([0.2, 0.22])


# ------------------------------------- a program that names no phase
def without_phases():
    c = ctx()
    del c["record"]["program_trace"]["op_phases"]
    return c


def phases_of_another_module():
    c = ctx()
    c["record"]["program_trace"]["op_phases"] = {
        "jit_decode_step": {"fusion.1": "forward"}}
    return c


@pytest.mark.parametrize("older", [without_phases,
                                   phases_of_another_module])
def test_a_table_of_scopes_without_phases_reads_0_0_0_100(older):
    """The parent's program: time in operations the program names as a
    phase is nil, and all of it is unphased.  A reading, not a fault."""
    got = [read(m, older()) for m in METRICS]
    assert got == [0.0, 0.0, 0.0, 100.0]
    cell = harness.load_cell("qwen3next_a3b_train_s16k")
    cell.per_layer = [m for m in cell.per_layer if m["name"] in METRICS]
    line = harness.read_layer_metrics(cell, older())
    assert {k: v["value"] for k, v in line.items()} \
        == dict(zip(METRICS, got))
    assert [v["unit"] for v in line.values()] == ["ms", "ms", "ms", "%"]


def no_program_trace():
    c = ctx()
    c["record"] = {}
    return c


def no_table_of_the_module():
    c = ctx()
    c["record"]["program_trace"]["op_scopes"] = {
        "jit_decode_step": {"fusion.1": "attn"}}
    return c


def no_whole_execution():
    c = ctx()
    c["devices"][0]["modules"] = [m for m in c["devices"][0]["modules"]
                                  if m[1] in (0.0, 9.5)]    # cut short
    return c


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("broken", [no_program_trace,
                                    no_table_of_the_module,
                                    no_whole_execution])
def test_nothing_to_read_is_none_and_fails_the_run(metric, broken):
    assert read(metric, broken()) is None
    cell = harness.load_cell(spec(metric)["cells"][0])
    cell.per_layer = [m for m in cell.per_layer if m["name"] == metric]
    assert len(cell.per_layer) == 1
    with pytest.raises(harness.BenchError, match="found nothing to read"):
        harness.read_layer_metrics(cell, broken())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_committed_scope_fixture_still_reads_the_cells_whole_line(cell):
    """The fixtures the cells' own tests read were written before the
    program named a phase (no ``op_phases`` in them): every listed
    metric of the cell still reads, the four new ones 0 / 0 / 0 / 100,
    and the older ones what they read without the new entries."""
    fix = harness.load_json(harness.HERE / CELLS[cell])
    assert "op_phases" not in fix["program_trace"]
    loaded = harness.load_cell(cell)
    got = harness.read_layer_metrics(loaded, ctx_of(fix))
    assert list(got) == [m["name"] for m in loaded.per_layer]
    assert list(got)[-4:] == METRICS
    assert [got[m]["value"] for m in METRICS] == [0.0, 0.0, 0.0, 100.0]
    older = harness.load_cell(cell)
    older.per_layer = [m for m in older.per_layer
                       if m["name"] not in METRICS]
    assert harness.read_layer_metrics(older, ctx_of(fix)) \
        == {k: v for k, v in got.items() if k not in METRICS}


# ------------------------------------------------------ the manifest
def test_the_four_entries_stand_last_and_list_the_five_cells():
    last = MANIFEST["per_layer"][-4:]
    assert [m["name"] for m in last] == METRICS
    assert MANIFEST["per_layer"][-5]["name"] \
        == "device_idle_pct.sparse_linear"
    for m in last:
        assert m["workloads"] == list(CELLS)
        assert (m["layer"], m["source"], m["moves"], m["better"]) == (
            "model step", "device_trace", "train_tokens_per_s", "lower")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert [m["unit"] for m in last] == ["ms", "ms", "ms", "%"]


@pytest.mark.parametrize("metric", METRICS)
def test_spec_file(metric):
    s = spec(metric)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert s["cells"] == entry["workloads"] == list(CELLS)
    assert (s["layer"], s["unit"], s["moves"]) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert s["reader"] == "phase_ms"
    params = {k: v for k, v in s["params"].items() if k != "note"}
    want = {"module": "train_k", "phases": [PHASE_OF[metric]]}
    if metric == "step_unphased_pct":
        want["share"] = True
    assert params == want


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_only_the_five_cells_gain_the_phase_metrics(cell):
    """Laguna's and MiniCPM-SALA's own tests pin their per-layer sets
    exactly and ``runners/train.py`` exports no ``program_trace``: the
    other four cells' lines are what they were."""
    names = [m["name"] for m in harness.load_cell(cell).per_layer]
    assert [n for n in names if n in METRICS] \
        == (METRICS if cell in CELLS else [])


# -------------------------------------------------------- the script
def test_scope_by_phase_table_of_the_fixture():
    got = phase_ms.scope_phase_table(ctx(), scopes=("attn", "mlp"))
    assert list(got["table"])[:4] == ["attn", "mlp", "other", "unknown"]
    for scope, row in got["table"].items():
        assert row == pytest.approx(WANT["table_ms"].get(
            scope, dict.fromkeys(phase_ms.PHASES, 0.0))), scope
    assert got["phase_ms"] == pytest.approx(
        {"forward": 945.0, "recompute": 420.0, "backward": 945.0,
         "none": 525.0})
    assert got["busy_ms"] == pytest.approx(2835.0)
    assert sum(got["phase_ms"].values()) == pytest.approx(got["busy_ms"])
    assert got["kernels"] == WANT["kernels"]
    (scope, name, ms), = got["recompute_top"]
    assert (scope, name) == ("attn", "flash_fwd.5 bf16[128,64] pallas")
    assert ms == pytest.approx(420.0)
    assert [(scope, name) for scope, name, _ in got["unphased_top"]] == [
        ("optimizer", "fusion.7 bf16[128,64]"),
        ("unknown", "copy.8 bf16[128,64]")]
    assert [ms for _, _, ms in got["unphased_top"]] \
        == pytest.approx([315.0, 210.0])
    assert json.loads(json.dumps(got)) == got


@pytest.mark.parametrize("broken", [no_program_trace, no_whole_execution])
def test_no_table_without_the_programs_or_a_whole_execution(broken):
    assert phase_ms.scope_phase_table(broken()) is None


def test_the_script_writes_the_table_of_the_ctx_the_harness_read(
        monkeypatch, tmp_path, capsys):
    """``main`` is a traced run under the program's tracer
    (``scope_dump.run_with_program_tracer``) and then the table of the
    ``ctx`` the harness read from, the tracer's export where the runner
    puts it, as one more line and in the file; the run's exit code is
    the script's, and a run that read no metric writes a bare line."""
    from dlnetbench_tpu.metrics import spans
    listed = harness.read_layer_metrics
    # a cell with no spec that the manifest lacks: ``scope_dump`` reads
    # those too
    bare = harness.load_cell("lfm2_8b_a1b_train_s8k")
    bare.per_layer = []

    def traced_run(argv):
        assert spans.is_enabled() and argv[-2:] == ["--trace", "1"]
        spans.current().register_op_scopes(
            "jit_train_k", *(FIX["program_trace"][k]["jit_train_k"]
                             for k in ("op_scopes", "op_phases")))
        harness.read_layer_metrics(bare, no_program_trace())
        return 7
    monkeypatch.setattr(run, "main", traced_run)
    out = tmp_path / "deep" / "x.json"
    argv = ["--workload", "lfm2_8b_a1b_train_s8k", "--seed", "5"]
    assert phase_ms.main(out, argv) == 7
    assert not spans.is_enabled() and harness.read_layer_metrics is listed
    got = json.loads(out.read_text())
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == got
    assert (got["line"], got["workload"]) == ("phase_ms", "lfm2_8b_a1b_train_s8k")
    assert set(spans.SCOPES) < set(got["table"]) and got["spans"] == []
    assert got["phase_ms"]["recompute"] == pytest.approx(420.0)
    assert got["table"]["attn"] == pytest.approx(WANT["table_ms"]["attn"])
    monkeypatch.setattr(run, "main", lambda argv: 1)   # read no metric
    assert phase_ms.main(out, argv) == 1
    assert json.loads(out.read_text()) == {"line": "phase_ms",
                                           "workload": "lfm2_8b_a1b_train_s8k"}
