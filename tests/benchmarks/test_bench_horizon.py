"""A cell's fixed training horizon (``cycle_steps`` in its workload
file, ``TrainCell.horizon`` and ``window``): the window trains the same
steps from the checked weights over and over, so what a window reads of
the router's load does not depend on how many steps fit in it; a cell
without the key runs the window it ran before; and the fault the key
cures, shown on the same rehearsal window with the key taken out."""
import dataclasses
import json
import math

import jax
import pytest

from benchmarks import harness, run
from benchmarks.runners import train, train_latent_moe, train_linear_moe

CELL = "qwen3next_a3b_train_s16k"
SEED = 2**31 + 17
COUNTERS = ("max_load", "routed", "past_bound")


def cell_with(name=CELL, **over):
    """The cell at its rehearsal sizes with ``over`` laid over its
    workload; a value of None takes the key out."""
    cell = harness.rehearsal(harness.load_cell(name))
    workload = {k: v for k, v in {**cell.workload, **over}.items()
                if v is not None}
    return dataclasses.replace(cell, workload=workload)


def drive(cell, make=train_linear_moe.LinearMoeCell, seconds=1.5):
    """Set-up and one window, as ``run`` drives them: the cell, what the
    window returns and the timed steps' counters."""
    tc = make(cell, SEED, lambda _: None)
    tc.first_steps()
    tc.horizon()
    win = tc.window(seconds, harness.TraceWindow(False, 0.0))
    counted = ({k: v[tc.check_steps:] for k, v in tc.counted().items()}
               if hasattr(tc, "counted") else {})
    return tc, win, counted


def failed_steps(win, counted):
    """The runners' rule, a step at a time: a row past the bound or a
    loss that is not finite."""
    return [bool(past) or not math.isfinite(loss)
            for loss, past in zip(win["losses"], counted["past_bound"])]


# ------------------------------------------- (a) the cycle repeats
@pytest.mark.parametrize("moe_slots", [128, 24],
                         ids=["sound_bound", "bound_under_the_load"])
def test_fixed_horizon_repeats_its_cycle_exactly(moe_slots):
    """Three cycles and more of a rehearsal window: every loss and every
    counter of a step equals that of the step one cycle earlier, so the
    share of failed steps over whole cycles is one cycle's (under a
    bound below the load, where it is not nought)."""
    cell = cell_with(moe_slots=moe_slots)
    cycle = cell.workload["cycle_steps"]
    tc, win, counted = drive(cell)
    steps = len(win["losses"])
    assert steps >= 3 * cycle and win["restores"] == steps // cycle
    assert tc.steps_done == tc.check_steps + steps     # the feed's own count
    for series in (win["losses"], *(counted[k] for k in COUNTERS)):
        assert len(series) == steps
        assert series[cycle:] == series[:-cycle]
    assert len(set(win["losses"][:cycle])) == cycle    # it does train
    failed = failed_steps(win, counted)
    whole = steps - steps % cycle
    assert sum(failed[:whole]) * cycle == sum(failed[:cycle]) * whole
    assert (sum(failed) > 0) == (moe_slots == 24)


def test_restore_writes_the_kept_weights_into_the_donated_buffers():
    cell = cell_with()
    tc = train_linear_moe.LinearMoeCell(cell, SEED, lambda _: None)
    tc.first_steps()
    assert tc.kept is None and tc.horizon() == cell.workload["cycle_steps"]
    kept, restore = tc.kept, tc.restore
    assert tc.horizon() and tc.kept is kept and tc.restore is restore
    want = jax.device_get(tc.params)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: (a == b).all(), want, jax.device_get(kept)))
    tc.call()
    trained = tc.params
    fresh = tc.restore(trained, tc.kept)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(trained))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(tc.kept))
    for got, k, w in zip(*map(jax.tree.leaves, (fresh, tc.kept, want))):
        assert got.unsafe_buffer_pointer() != k.unsafe_buffer_pointer()
        assert (jax.device_get(got) == w).all()
    tc.free()
    assert tc.kept is None and tc.restore is None


def test_window_line_carries_the_horizon(capsys):
    run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "1",
              "--trace", "0", "--rehearse-cpu", "1"])
    got = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("{")]
    window = next(g for g in got if g["line"] == "window")
    cycle = harness.load_cell(CELL).workload["rehearsal"]["cycle_steps"]
    assert window["cycle_steps"] == cycle
    assert window["restores"] == window["steps"] // cycle >= 3
    assert window["moe_max_load_first_cycle"] == window["moe_max_load"]
    assert window["cycles_repeat"] is True
    # the restore is compiled and the copy kept before set-up ends
    ages = {g["line"]: g["t"] for g in got}
    assert ages["compiled"] <= ages["set-up"] <= ages["host"]


# ------------------------------- (b) a cell without the key, as before
@pytest.mark.parametrize("name,make", [
    ("minerva7b_train", train.TrainCell),
    ("kimivl_a3b_train_s8k", train_latent_moe.LatentMoeCell),
    (CELL, train_linear_moe.LinearMoeCell)],
    ids=["dense", "latent_moe", "linear_moe_key_taken_out"])
def test_window_without_the_key_is_the_hand_run_loop(name, make):
    """No weights are kept, no restore is compiled or run, and the
    window's losses are those of as many ``call()``s by hand from the
    same seed."""
    cell = cell_with(name, cycle_steps=None)
    assert "cycle_steps" not in cell.workload
    tc, win, _ = drive(cell, make, seconds=0.4)
    assert tc.horizon() == 0 and win["restores"] == 0
    assert tc.kept is None and tc.restore is None
    steps = len(win["losses"])
    assert steps >= 3 and tc.steps_done == tc.check_steps + steps
    by_hand = make(cell, SEED, lambda _: None)
    by_hand.first_steps()
    assert [float(by_hand.call()[0]) for _ in range(steps)] == win["losses"]


# ------------------------------------- (d) the fault, with the key out
def test_without_the_horizon_a_longer_window_passes_the_first_cycles_bound():
    """Plain SGD on memorised batches concentrates the router (here at
    lr 2.0, as far as the load needs to grow at the rehearsal size).
    With the key taken out and a bound just above one cycle's largest
    load, the first cycle holds and later steps put rows past the bound:
    the more steps a window takes, the more of them fail.  With the key,
    under the same bound and lr, no step of any cycle fails.  (The bound
    is part of the program, and at this lr another program's rounding
    moves the loads by a row or two within a cycle: so the bound is the
    first, from one above the sound run's largest, under which the first
    cycle holds.)"""
    over, seconds = {"lr": 2.0}, 4.0
    cycle = cell_with().workload["cycle_steps"]
    _, free, loads = drive(cell_with(**over, cycle_steps=None),
                           seconds=seconds)
    assert free["restores"] == 0 and len(free["losses"]) >= 12 * cycle
    largest = max(loads["max_load"][:cycle])
    assert max(loads["max_load"][cycle:]) > largest + 2, (
        "the load does not grow here: pin instead that the counters do "
        "not repeat")
    assert loads["max_load"][cycle:] != loads["max_load"][:-cycle]
    for bound in range(largest + 1, largest + 6):
        _, win, counted = drive(
            cell_with(**over, moe_slots=bound, cycle_steps=None),
            seconds=seconds)
        failed = failed_steps(win, counted)
        if not any(failed[:cycle]):
            break
    else:
        pytest.fail("no bound near the load holds the first cycle")
    assert len(failed) >= 12 * cycle and any(failed[cycle:])
    _, win, counted = drive(cell_with(**over, moe_slots=bound),
                            seconds=seconds)
    assert win["restores"] >= 12 and not any(failed_steps(win, counted))
    assert max(counted["max_load"]) <= bound
