"""The manifest and the files it finds by name."""
import importlib
import json
import re
from pathlib import Path

import pytest

from benchmarks import harness

ROOT = harness.ROOT
BENCH = ROOT / "benchmarks"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
WIDTHS = re.compile(r"_size$|latent|proj|_dim$|_rank$|expan|"
                    r"experts_per_tok")


def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024


@pytest.mark.parametrize("name", sorted(
    {m["name"] for m in METRICS} | set(CELLS)
    | {c["name"] for c in MANIFEST["configs"]}
    | {w["traffic"] for w in MANIFEST["workloads"]}))
def test_name_is_of_the_allowed_characters(name):
    assert NAME.match(name)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in MANIFEST["end_to_end"]:
        allowed |= {"bound"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        allowed |= {"layer", "moves"}
        assert metric["moves"] in {m["name"]
                                   for m in MANIFEST["end_to_end"]}
    assert set(metric) <= allowed
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


def test_no_two_entries_share_a_name():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_four_chip_cells_within_the_quarter():
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_file(config):
    assert config["source"].startswith("https://")
    assert config["file"].startswith("benchmarks/configs/")
    body = json.loads((ROOT / config["file"]).read_text())
    assert body["source"] == config["source"]
    assert not any(WIDTHS.search(k) for k in config["reduced"])
    assert set(config["reduced"]) == set(body["reduced"])
    assert set(body["reduced"]) <= set(body["published"])
    for key in config["reduced"]:
        assert body[key] != body["published"][key]
    assert any(w["config"] == config["name"]
               for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.load_cell(cell)
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    assert c.workload["config"] == entry["config"]
    assert c.workload["traffic"] == entry["traffic"]
    assert c.workload["chips"] == entry["chips"] in (1, 4)
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert c.workload["why"] and c.workload["who"]
    assert harness.runner_for(c.workload["runner"]).run
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    assert {m["moves"] for m in c.per_layer} <= names


@pytest.mark.parametrize("cell", CELLS)
def test_fixed_horizon_is_whole_turns_of_the_feed_inside_the_bounds_reading(
        cell):
    """Wherever ``cycle_steps`` stands, a cycle is a whole number of
    turns of the feed, so that ``feed()``'s count of steps meets the same
    batches in the same order in every cycle, and no longer than the
    timed steps over which ``moe_slots_from`` says the bound on an
    expert's rows was read; its ``cycle_why`` stands beside it.  A cell
    without the key has neither, nor a rehearsal of one."""
    c = harness.load_cell(cell)
    small = harness.rehearsal(c)
    if "cycle_steps" not in c.workload:
        assert "cycle_why" not in c.workload
        assert "cycle_steps" not in small.workload
        return
    for sized in (c, small):
        cycle = sized.workload["cycle_steps"]
        assert isinstance(cycle, int) and cycle > 0
        assert cycle % sized.traffic["pool_batches"] == 0
    read_over = re.search(r"(\d+) timed steps", c.workload["moe_slots_from"])
    assert c.workload["cycle_steps"] <= int(read_over.group(1)) == 51
    why = c.workload["cycle_why"]
    assert f"{c.workload['cycle_steps']} " in why and "pool_batches" in why
    assert small.workload["cycle_steps"] < c.workload["cycle_steps"]


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_layer_metric_file_and_reader(metric):
    spec = json.loads((BENCH / "layer_metrics"
                       / f"{metric['name']}.json").read_text())
    for key in ("layer", "unit", "moves"):
        assert spec[key] == metric[key]
    assert spec.get("cells") == metric.get("workloads")
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    assert callable(reader.read)
    if "cost" in spec.get("params", {}):
        cost = importlib.import_module(
            f"benchmarks.costs.{spec['params']['cost']}")
        assert callable(cost.cost)


@pytest.mark.parametrize("cell", CELLS)
def test_listed_metric_with_nothing_to_read_is_a_fault(cell):
    """A trace in which the step program or a kernel family is renamed
    or gone gives no shorter line: the run fails."""
    ctx = {"record": {}, "devices": [{"ops": [], "modules": []}],
           "window": (0.0, 1.0), "peaks": None,
           "cache": {"hits": 0, "misses": 0}}
    with pytest.raises(harness.BenchError, match="found nothing to read"):
        harness.read_layer_metrics(harness.load_cell(cell), ctx)


def test_peaks_table():
    p = harness.peaks("TPU v5 lite")
    assert p["flops_per_s"] == {"bfloat16": 197e12, "int8": 393e12}
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("stats,want", [
    ({"peak_bytes_in_use": 5, "peak_bytes_reserved": 6}, 11),
    ({"peak_bytes_in_use": 5}, 5), ({}, 0)])
def test_memory_peak_counts_the_programs_reserved_temporaries(stats, want):
    assert harness._peak(stats) == want


def test_unknown_workload_is_refused():
    with pytest.raises(harness.BenchError):
        harness.load_cell("no_such_cell")


def test_files_under_paths_are_named_from_allowed_characters():
    for base in MANIFEST["paths"]:
        for f in (ROOT / base).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert re.match(r"^[A-Za-z0-9_.\-/]+$",
                            str(f.relative_to(ROOT))), f
