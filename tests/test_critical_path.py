"""Cross-rank critical-path blame (analysis/critical_path.py).

The ISSUE 14 blame acceptance, end to end: four "ranks" run the same
measured step loop as four rank-scoped views of one FaultPlan
(``FaultInjector(plan, world, rank=r)`` — the multi-controller
emulation), each measuring its own steps; the merged per-rank timelines
must attribute >= 80% of the fault window's excess step time to the
injected rank, and a clean run must attribute no rank above the noise
band.  The steps are read on a clock the test owns (``owned_clock``:
the injector's sleep and the step's own work advance it, the host's
load does not); the same loop on the wall clock is in the ``slow`` lane.
"""
from __future__ import annotations

import json
import random
import time

import pytest

from dlnetbench_tpu.analysis.critical_path import (blame_columns,
                                                   blame_from_matrix,
                                                   blame_report,
                                                   matrix_from_flights,
                                                   step_matrix)
from dlnetbench_tpu.faults.inject import FaultInjector
from dlnetbench_tpu.faults.plan import FaultEvent, FaultPlan

pytestmark = pytest.mark.telemetry

WARM, RUNS, WORLD = 2, 10, 4
DELAY_US = 4000.0
WIN = (WARM + 3, WARM + 7)  # plan-step units (warmup included)


def _measured_rank_rows(plan: FaultPlan, clock) -> list[dict]:
    """Per-rank step timelines as one process per rank would measure
    them: each rank runs the same step loop under ITS OWN rank-scoped
    injector, and reads each step on ``clock`` (``perf_counter`` and
    ``sleep``: the ``owned_clock`` fixture, or ``time`` for the wall)."""
    rows = []
    for r in range(WORLD):
        inj = FaultInjector(plan, world=WORLD, rank=r, sleep=clock.sleep)
        work = random.Random(r)     # a ~0.3 ms step, 5 % of noise
        walls = []
        for _ in range(WARM + RUNS):
            t0 = clock.perf_counter()
            inj.before_step()
            clock.sleep(300e-6 * work.uniform(0.95, 1.05))
            walls.append(round((clock.perf_counter() - t0) * 1e6, 1))
        rows.append({"rank": r, "device_id": r, "process_index": r,
                     "hostname": f"host{r}", "runtimes": walls[WARM:]})
    return rows


def _record(rows: list[dict], plan: FaultPlan | None) -> dict:
    g: dict = {"model": "busywork", "world_size": WORLD}
    if plan is not None and plan.events:
        g["fault_plan"] = plan.to_dict()
    return {"section": "dp", "version": 2, "process": 0, "global": g,
            "mesh": {}, "num_runs": RUNS,
            "warmup_times": [0.0] * WARM, "ranks": rows}


def _blame_lands_on_the_injected_rank(clock):
    plan = FaultPlan(events=[FaultEvent(
        kind="delay", ranks=[2], iteration=WIN[0], until=WIN[1],
        magnitude_us=DELAY_US)]).validate()
    rec = _record(_measured_rank_rows(plan, clock), plan)
    rep = blame_report(rec)
    assert rep["clock_alignment"] == "collective-fence"
    win = rep["window"]
    # sample units: plan window rebased by the warmup length
    assert win["sample_range"] == [WIN[0] - WARM, WIN[1] - WARM]
    assert win["top_rank"] == 2
    assert win["top_frac"] >= 0.8
    # the injected sleep dominates the window's excess
    assert win["excess_us"] >= 0.5 * DELAY_US * (WIN[1] - WIN[0])
    assert rep["suspects"] == [2]
    cols = blame_columns(rec)
    assert cols["blame_rank"] == "2" and cols["blame_frac"] >= 0.8


def test_straggler_blame_lands_on_injected_rank(owned_clock):
    """ISSUE 14 acceptance: >= 80% of the fault window's excess lands
    on the injected rank, which is also the only suspect."""
    _blame_lands_on_the_injected_rank(owned_clock)


@pytest.mark.slow
def test_straggler_blame_lands_on_injected_rank_by_the_wall_clock():
    _blame_lands_on_the_injected_rank(time)


def test_clean_run_blames_no_rank_above_noise(owned_clock):
    rec = _record(_measured_rank_rows(FaultPlan(), owned_clock), None)
    rep = blame_report(rec)
    assert rep["suspects"] == []
    assert "window" not in rep
    cols = blame_columns(rec)
    assert cols["blame_rank"] == "-"


def test_single_controller_record_degrades_to_no_signal():
    """Rank rows sharing ONE clock (the python single-controller
    duplication) have zero per-rank signal — blame must say so, never
    fabricate a verdict.  The gate holds on the WINDOW path too: a
    faulted single-controller record (fault_plan present, identical
    rows) must not crown rank 0 with a 0%-blame verdict."""
    import math

    shared = [300.0, 305.0, 310.0, 303.0]
    rows = [{"rank": r, "runtimes": list(shared)} for r in range(4)]
    rec = {"section": "dp", "global": {"model": "m"}, "num_runs": 4,
           "warmup_times": [], "ranks": rows}
    rep = blame_report(rec)
    assert rep["suspects"] == []
    cols = blame_columns(rec)
    assert cols["blame_rank"] == "-" and math.isnan(cols["blame_frac"])
    faulted = json.loads(json.dumps(rec))
    faulted["global"]["fault_plan"] = FaultPlan(events=[FaultEvent(
        kind="delay", ranks=[1], iteration=1, until=3,
        magnitude_us=1000.0)]).validate().to_dict()
    cols = blame_columns(faulted)
    assert cols["blame_rank"] == "-" and math.isnan(cols["blame_frac"])


def test_phase_blame_names_the_grown_timer():
    """Per-phase decomposition: the straggler's excess shows up in the
    phase timer that actually grew (here a synthetic comm leg)."""
    base = [100.0] * 6
    mat = [list(base) for _ in range(3)]
    comm = {r: [20.0] * 6 for r in range(3)}
    for i in (2, 3):
        mat[1][i] += 500.0
        comm[1][i] += 500.0
    phases = {r: {"comm_time": comm[r], "compute_time": [80.0] * 6}
              for r in range(3)}
    rep = blame_from_matrix([0, 1, 2], mat, window=(2, 4),
                            phases=phases)
    assert rep["window"]["top_rank"] == 1
    assert rep["phases"]["comm_time"] == pytest.approx(1000.0)
    assert rep["phases"]["compute_time"] == pytest.approx(0.0)


def test_energy_axis_rides_the_report():
    rows = [{"rank": r, "runtimes": [100.0, 101.0],
             "energy_consumed": [0.5 + r, 0.5 + r]} for r in range(2)]
    rec = {"section": "dp", "global": {"model": "m"}, "num_runs": 2,
           "warmup_times": [], "ranks": rows}
    rep = blame_report(rec)
    assert rep["energy_j"] == {"0": 1.0, "1": 3.0}


def test_matrix_from_flights_merges_rank_rings():
    """Per-rank flight dumps (python FlightRecorder or the native
    TelemetryRing's record block) merge on step keys; only the common
    step window survives (rings may roll past each other)."""
    dumps = []
    for r in range(2):
        samples = [{"rank": r, "step": s, "t_s": 0.01 * s,
                    "step_wall_us": 100.0 + r * 10 + s}
                   for s in range(2 + r, 8)]  # rank 1 lost steps 2
        dumps.append({"trigger": "stall", "samples": samples})
    ranks, mat = matrix_from_flights(dumps)
    assert ranks == [0, 1]
    assert len(mat[0]) == len(mat[1]) == 5  # steps 3..7
    assert mat[0][0] == pytest.approx(103.0)
    assert mat[1][0] == pytest.approx(113.0)


def test_step_matrix_truncates_to_common_length():
    rows = [{"rank": 0, "runtimes": [1.0, 2.0, 3.0]},
            {"rank": 1, "runtimes": [1.0, 2.0]}]
    ranks, mat = step_matrix({"ranks": rows, "global": {}})
    assert ranks == [0, 1] and all(len(m) == 2 for m in mat)
    with pytest.raises(ValueError, match="no per-rank"):
        step_matrix({"ranks": [], "global": {}, "section": "x"})


def test_report_cli_end_to_end(tmp_path, capsys, owned_clock):
    """python -m dlnetbench_tpu.analysis.critical_path report — the
    committed telemetry fixture through load -> merge-shape -> report,
    both human and --json forms."""
    from pathlib import Path

    from dlnetbench_tpu.analysis import critical_path as cp

    plan = FaultPlan(events=[FaultEvent(
        kind="delay", ranks=[2], iteration=WIN[0], until=WIN[1],
        magnitude_us=DELAY_US)]).validate()
    rec = _record(_measured_rank_rows(plan, owned_clock), plan)
    path = tmp_path / "runs.jsonl"
    path.write_text(json.dumps(rec) + "\n")
    assert cp.main(["report", str(path)]) == 0
    out = capsys.readouterr().out
    assert "critical path: dp/busywork" in out
    assert "top rank 2" in out
    assert cp.main(["report", "--json", "--section", "dp",
                    str(path)]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["window"]["top_rank"] == 2
    # usage errors are tidy, not tracebacks
    assert cp.main([]) == 2
    assert cp.main(["report"]) == 2
    empty = tmp_path / "none.jsonl"
    empty.write_text(json.dumps({"section": "serving", "global": {},
                                 "ranks": []}) + "\n")
    assert cp.main(["report", str(empty)]) == 1
    assert Path(path).exists()
