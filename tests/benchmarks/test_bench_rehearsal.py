"""The runner end to end at the cells' tiny rehearsal sizes on the CPU
(the explicit ``--rehearse-cpu 1`` path), the controls that have to come
out as not correct, and a run with the timed path broken underneath."""
import json

import pytest

from benchmarks import harness, run
from benchmarks.runners import train

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]
TRAIN = [c for c in CELLS if c.endswith("_train")]


def lines(capsys):
    out = capsys.readouterr().out
    return [json.loads(ln) for ln in out.splitlines()
            if ln.startswith("{")]


def rehearse(cell, capsys, seed=2**31 + 17, seconds="1"):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   seconds, "--trace", "0", "--rehearse-cpu", "1"])
    got = lines(capsys)
    return rc, got, next(g for g in got
                         if g["line"].startswith("rehearsal result"))


@pytest.mark.parametrize("cell", CELLS)
def test_run_fails_without_a_tpu_and_prints_no_result(cell, capsys):
    rc = run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0
    assert "correct" not in captured.out
    assert "no TPU" in captured.err


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_end_to_end(cell, capsys):
    rc, got, result = rehearse(cell, capsys)
    assert rc != 0                       # a rehearsal is no measurement
    start = next(g for g in got if g["line"] == "start")
    assert start["device"]["platform"] == "cpu" and start["rehearsal"]
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    names = {m["name"] for m in harness.load_cell(cell).end_to_end}
    assert set(result["metrics"]) == names
    compared = [g for g in got if g["line"] == "compared"]
    assert compared and all(g["value"] <= g["limit"] for g in compared)


def test_numbers_compared_stand_last_on_the_line_and_on_stderr(capsys):
    """Each number compared beside its limit, as the last key of the
    result's line and as the last lines of standard error before the
    rehearsal's own refusal (a driver's record of a run that is not
    correct keeps the end of each)."""
    run.main(["--workload", "minerva7b_train", "--seed", "9", "--seconds",
              "0.5", "--trace", "0", "--rehearse-cpu", "1"])
    captured = capsys.readouterr()
    got = [json.loads(ln) for ln in captured.out.splitlines()
           if ln.startswith("{")]
    result = got[-1]        # the log's line: the result and its age
    assert list(result)[-2:] == ["compared", "t"]
    rows = {g["name"]: {"value": g["value"], "limit": g["limit"]}
            for g in got if g["line"] == "compared"}
    assert result["compared"] == rows and set(rows) == {
        "loss_gap", "grad_norm_gap", "delta_norm_gap"}
    err = captured.err.splitlines()
    assert err[-1].startswith("benchmarks/run.py: rehearsal")
    assert err[-1 - len(rows):-1] == [
        f"compared {k} {v['value']!r} limit {v['limit']!r}"
        for k, v in rows.items()]


@pytest.mark.parametrize("cell", TRAIN)
def test_train_control_int8_reference_is_not_correct(cell):
    rows = train.readings(harness.rehearsal(harness.load_cell(cell)), 7,
                          lambda _: None, "reference_int8")
    assert any(value > limit for _, value, limit, _ in rows)


def test_train_control_int8_program_is_not_correct():
    cell = harness.rehearsal(harness.load_cell("minerva7b_train"))
    rows = train.readings(cell, 7, lambda _: None, "program")
    assert any(value > limit for _, value, limit, _ in rows)


@pytest.mark.parametrize("cell", TRAIN)
def test_step_that_returns_its_state_unchanged_is_not_correct(
        cell, capsys, monkeypatch):
    def call(self):
        import jax
        import jax.numpy as jnp
        _, losses = self.step(jax.tree.map(jnp.copy, self.params),
                              self.feed())
        self.steps_done += 1
        return losses
    monkeypatch.setattr(train.TrainCell, "call", call)
    _, got, result = rehearse(cell, capsys)
    assert result["correct"] is False
    bad = {g["name"] for g in got
           if g["line"] == "compared" and not g["ok"]}
    assert "delta_norm_gap" in bad


def test_part_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    feed = train.TrainCell.feed

    def half(self):
        tokens = feed(self)
        return tokens.at[1:].set(tokens[:1])    # row 0 in every row
    monkeypatch.setattr(train.TrainCell, "feed", half)
    _, _, result = rehearse("minerva7b_train", capsys)
    assert result["correct"] is False


class FakeLoss:
    """A step's loss that is ready only once ``done`` holds it."""

    def __init__(self, i, done):
        self.i, self.done = i, done

    def is_ready(self):
        return self.i in self.done

    def __getitem__(self, _):
        return float(self.i)


@pytest.mark.parametrize("in_flight", [1, 3, 8])
def test_window_queues_steps_and_counts_every_one(in_flight, monkeypatch):
    """The window's loop on a step that only counts: never more than
    ``steps_in_flight`` dispatched and not yet known to have ended, the
    oldest waited for first, every dispatched step counted."""
    import jax
    tc = object.__new__(train.TrainCell)
    tc.cell = harness.rehearsal(harness.load_cell("minerva7b_train"))
    tc.in_flight, calls, done, queued = in_flight, [], set(), []

    def call():
        calls.append(FakeLoss(len(calls), done))
        queued.append(len(calls) - len(done))
        return calls[-1]
    tc.call = call
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: done.add(x.i))
    win = tc.window(0.05, harness.TraceWindow(False, 0.0))
    assert max(queued) == min(in_flight, len(calls))
    assert win["losses"] == [float(i) for i in range(len(calls))]
    assert len(win["step_ends_s"]) == len(win["dispatch_s"]) == len(calls)
    assert win["step_ends_s"] == sorted(win["step_ends_s"])


def test_window_fills_the_queue_again_behind_a_late_notice(monkeypatch):
    """A wait that returns late, with every queued step ended meanwhile
    (the chip ran on): all of them are known at once, and the loop goes
    on offering steps until the window's end, not for a shorter time."""
    import time

    import jax
    tc = object.__new__(train.TrainCell)
    tc.cell = harness.rehearsal(harness.load_cell("minerva7b_train"))
    tc.in_flight, calls, done = 4, [], set()

    def call():
        calls.append(FakeLoss(len(calls), done))
        return calls[-1]

    def wait(x):
        time.sleep(0.002)
        if x.i == 10:                        # the late notice
            time.sleep(0.1)
            done.update(range(len(calls)))
        done.add(x.i)
    tc.call = call
    monkeypatch.setattr(jax, "block_until_ready", wait)
    win = tc.window(0.3, harness.TraceWindow(False, 0.0))
    ends = win["step_ends_s"]
    assert ends[13] - ends[10] < 0.002       # known at once
    assert ends[-1] >= 0.29                  # the window kept its length
    assert win["losses"] == [float(i) for i in range(len(calls))]


@pytest.mark.parametrize("in_flight", [1, 3, 8])
def test_restore_is_queued_right_behind_every_cycle_th_step(in_flight,
                                                            monkeypatch):
    """The window's loop on a step that only counts: the restore is
    dispatched straight after the step that ends a cycle, before the
    host waits for any older step, it is given the trained weights and
    the kept ones, what it returns is what the next step trains, and it
    is no step: not timed, not counted."""
    import dataclasses

    import jax
    tc = object.__new__(train.TrainCell)
    cell = harness.rehearsal(harness.load_cell("minerva7b_train"))
    tc.cell = dataclasses.replace(
        cell, workload={**cell.workload, "cycle_steps": 5})
    tc.in_flight, tc.params, tc.kept = in_flight, "seeded", "kept"
    events, done = [], set()

    def call():
        events.append(("step", tc.params))
        tc.params = f"trained {len(events)}"
        return FakeLoss(sum(e[0] == "step" for e in events) - 1, done)

    def restore(trained, kept):
        events.append(("restore", trained, kept))
        return "fresh"
    tc.call, tc.restore = call, restore
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (events.append(("wait",)), done.add(x.i)))
    win = tc.window(0.05, harness.TraceWindow(False, 0.0))
    steps = [i for i, e in enumerate(events) if e[0] == "step"]
    assert len(steps) >= 15 and win["restores"] == len(steps) // 5 \
        == sum(e[0] == "restore" for e in events)
    assert win["losses"] == [float(i) for i in range(len(steps))]
    assert len(win["step_ends_s"]) == len(win["dispatch_s"]) == len(steps)
    for n, at in enumerate(steps, 1):
        after = events[at + 1] if at + 1 < len(events) else ("end",)
        if n % 5 == 0:
            assert after == ("restore", f"trained {at + 1}", "kept")
            if n < len(steps):
                assert events[steps[n]] == ("step", "fresh")
        else:
            assert after[0] != "restore"


@pytest.mark.parametrize("experts", [1, 4])
def test_layerwise_backward_equals_autodiff_of_the_whole_loss(experts):
    """The reference's layer-at-a-time backpropagation against
    ``jax.grad`` of the same loss as one function."""
    import functools

    import jax
    import numpy as np

    from benchmarks import reference, weights
    arch = weights.arch_of({
        "hidden_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 64,
        "vocab_size": 64, "num_hidden_layers": 2,
        "torch_dtype": "float32",
        **({"num_local_experts": experts, "num_experts_per_tok": 2}
           if experts > 1 else {})})
    p = reference.unstack(weights.make_params(arch, 3))
    tokens = weights.make_token_pool(3, 1, 2, 17, 64)[0]
    loss, grads = reference.LayerwiseGrad(arch)(p, tokens)
    want_loss, want = jax.value_and_grad(functools.partial(
        reference.loss_fn, arch=arch))(p, tokens)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=1e-7)
