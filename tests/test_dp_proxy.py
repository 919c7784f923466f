"""End-to-end DP proxy test on the 8-device virtual CPU mesh: schedule ->
jitted shard_map step -> harness -> JSON record -> DataFrame (the minimum
slice of SURVEY.md §7.2 step 3)."""
import json

import jax.numpy as jnp
import pytest

from dlnetbench_tpu.core.model_stats import load_model_stats
from dlnetbench_tpu.metrics.emit import emit_result, result_to_record
from dlnetbench_tpu.metrics.parser import get_metrics_dataframe, load_records
from dlnetbench_tpu.parallel.mesh import make_flat_mesh
from dlnetbench_tpu.proxies import dp as dp_proxy
from dlnetbench_tpu.proxies.base import (ProxyConfig, StepBundle,
                                         estimate_runs, run_proxy)

TINY = dict(size_scale=1e-5, time_scale=2e-4)


@pytest.fixture(scope="module")
def dp_result(eight_devices):
    stats = load_model_stats("gpt2_l_16_bfloat16")
    cfg = ProxyConfig(warmup=1, runs=3, **TINY)
    mesh = make_flat_mesh(4)
    bundle = dp_proxy.build(stats, num_buckets=4, cfg=cfg, mesh=mesh)
    return run_proxy("dp", bundle, cfg), bundle


def test_dp_runs_and_times(dp_result):
    result, bundle = dp_result
    assert result.num_runs == 3
    assert len(result.timers_us["runtimes"]) == 3
    assert all(t > 0 for t in result.timers_us["runtimes"])
    assert "barrier_time" in result.timers_us
    assert "comm_time" in result.timers_us
    assert all(t >= 0 for t in result.timers_us["barrier_time"])


def test_dp_overlap_fraction_measured(dp_result):
    """With both A/B legs measured, run_proxy reports the per-chain
    measured overlap fraction (metrics/stats.overlap_fraction) — one
    dimensionless sample per run, consistent with the timers it was
    derived from."""
    result, _ = dp_result
    ov = result.timers_us["overlap_fraction"]
    assert len(ov) == 3
    from dlnetbench_tpu.metrics.stats import overlap_fraction
    expect = overlap_fraction(result.timers_us["runtimes"],
                              result.timers_us["compute_time"],
                              result.timers_us["comm_time"])
    for got, want in zip(ov, expect):
        assert got == pytest.approx(want, abs=1e-3)


def test_dp_step_correctness(dp_result):
    """The allreduce must actually sum across the 4 ranks: buffers start at
    zero, so outputs stay zero — then rerun the comm-only step on ones via
    the bundle's full step, checking the burn didn't corrupt buffers."""
    _, bundle = dp_result
    outs = bundle.full()
    state = outs[0]
    assert jnp.all(jnp.isfinite(state.astype(jnp.float32)))
    for o in outs[1:]:
        assert float(jnp.max(jnp.abs(o))) == 0.0  # 4 * zeros = zeros


def test_dp_meta(dp_result):
    result, _ = dp_result
    g = result.global_meta
    assert g["proxy"] == "dp" and g["world_size"] == 4
    assert len(g["bucket_bytes"]) == 4
    # true schedule sizes preserved alongside scaled buffers
    assert sum(g["schedule_bucket_bytes"]) == pytest.approx(
        load_model_stats("gpt2_l_16_bfloat16").model_bytes, rel=0.01)


def test_emit_and_parse_roundtrip(dp_result, tmp_path):
    result, _ = dp_result
    out = tmp_path / "runs.jsonl"
    emit_result(result, path=str(out))
    emit_result(result, path=str(out))  # two records, same section

    recs = load_records(out, "dp")
    assert len(recs) == 2
    assert recs[0]["global"]["model"] == "gpt2_l_16_bfloat16"
    assert len(recs[0]["ranks"]) == 4

    df = get_metrics_dataframe(out, "dp")
    # rows = records x ranks x runs
    assert len(df) == 2 * 4 * 3
    assert {"runtime", "barrier_time", "rank", "run", "model"} <= set(df.columns)
    assert (df["runtime"] > 0).all()


def test_record_validation_catches_missing_rank(dp_result):
    from dlnetbench_tpu.metrics.parser import validate_record
    result, _ = dp_result
    rec = result_to_record(result)
    rec["ranks"] = rec["ranks"][:-1]
    with pytest.raises(ValueError, match="rank set"):
        validate_record(rec)


def test_estimate_runs():
    # mean of warmups after skipping first 2 = 0.1 -> 10 runs for 1s
    assert estimate_runs([5.0, 3.0, 0.1, 0.1], 1.0) == 10
    assert estimate_runs([0.5], 1.0) == 2       # falls back to last sample
    assert estimate_runs([0.1, 0.1, 0.0], 1.0) == 1


def test_cli_dp(tmp_path, eight_devices, capsys):
    from dlnetbench_tpu.cli import main
    out = tmp_path / "cli.jsonl"
    rc = main(["dp", "--model", "gpt2_l_16_bfloat16", "--num_buckets", "2",
               "-w", "1", "-r", "2", "--devices", "2",
               "--size_scale", "1e-5", "--time_scale", "1e-4",
               "--out", str(out)])
    assert rc == 0
    rec = json.loads(out.read_text().strip())
    assert rec["section"] == "dp" and rec["global"]["world_size"] == 2
    assert len(rec["ranks"][0]["runtimes"]) == 2


def test_cli_device_list_selection(tmp_path, eight_devices):
    """--devices accepts an arbitrary index list (reference -d 0,2,3,
    utils.hpp:62-71), not just a first-N count."""
    from dlnetbench_tpu.cli import main
    out = tmp_path / "cli.jsonl"
    rc = main(["dp", "--model", "gpt2_l_16_bfloat16", "--num_buckets", "2",
               "-w", "1", "-r", "1", "--devices", "1,3,5",
               "--size_scale", "1e-5", "--time_scale", "1e-4",
               "--no_topology", "--out", str(out)])
    assert rc == 0
    rec = json.loads(out.read_text().strip())
    assert rec["global"]["world_size"] == 3
    assert [r["device_id"] for r in rec["ranks"]] == [1, 3, 5]


def test_cli_device_list_rejects_bad_specs(eight_devices, capsys):
    from dlnetbench_tpu.cli import main
    for spec in ("0,2,99", "0,0", "abc", "0-3"):
        with pytest.raises(SystemExit):
            main(["dp", "--model", "gpt2_l_16_bfloat16", "--num_buckets",
                  "2", "--devices", spec, "--no_topology"])
        capsys.readouterr()


def test_cli_buffer_dtype_stats(eight_devices, tmp_path):
    """--buffer_dtype stats follows the stat file's Dtype (the reference's
    compile-time bf16/fp8 selection as a runtime switch): bfloat16 buffers
    halve the reported bucket bytes vs float32."""
    import json
    from dlnetbench_tpu.cli import main

    recs = {}
    for bd in ("float32", "stats"):
        out = tmp_path / f"{bd}.jsonl"
        rc = main(["dp", "--model", "gpt2_l_16_bfloat16", "--num_buckets",
                   "2", "--platform", "cpu", "-r", "1", "-w", "1",
                   "--size_scale", "1e-5", "--time_scale", "1e-4",
                   "--no_topology", "--buffer_dtype", bd,
                   "--out", str(out)])
        assert rc == 0
        recs[bd] = json.loads(out.read_text().strip())
    f32 = recs["float32"]["global"]["bucket_bytes"]
    bf16 = recs["stats"]["global"]["bucket_bytes"]  # stat file is bfloat16
    assert [b // 2 for b in f32] == list(bf16)


def _barrier_samples_ms(clock) -> list:
    """``barrier_time`` of three runs whose full and compute legs both
    drift by 10 ms a call, read on ``clock``.  Call counts include one
    warmup (full) / compile (compute) call each, so measured pairs are
    (20, 18), (30, 28), (40, 38) ms: matched subtraction gives 2 ms for
    every run, while subtracting the MEAN compute (28 ms) would give
    [0, 2, 12] ms."""
    calls = {"full": 0, "comp": 0}

    def full():
        clock.sleep(0.010 + 0.010 * calls["full"])
        calls["full"] += 1

    def compute():
        clock.sleep(0.008 + 0.010 * calls["comp"])
        calls["comp"] += 1

    bundle = StepBundle(full=full, compute=compute, comm=None,
                        global_meta={"proxy": "t", "world_size": 1})
    cfg = ProxyConfig(warmup=1, runs=3, measure_energy=False)
    res = run_proxy("t", bundle, cfg, clock=clock.perf_counter)
    return [t / 1000 for t in res.timers_us["barrier_time"]]


def test_barrier_time_uses_matched_compute_samples(owned_clock):
    """VERDICT r1 #6: barrier_time[i] must be full[i] - compute[i] with an
    ADJACENT (A/B-interleaved) compute sample, not full[i] minus an
    averaged compute time — drifting per-run durations would otherwise
    leak compute variance into the exposed-comm signal.  On a clock only
    the two legs advance, the matched differences are the 2 ms exactly."""
    assert _barrier_samples_ms(owned_clock) == pytest.approx([2.0] * 3)


@pytest.mark.slow
def test_barrier_time_uses_matched_compute_samples_by_the_wall_clock():
    """The same legs asleep on the host's clock.  The mean-subtraction
    bug's signature is the SPREAD ([0, 2, 12]), so the top sample and
    the median carry the guard; a single low sample is tolerated (a
    sleep pair can inflate unevenly under load)."""
    import statistics
    import time
    barrier_ms = _barrier_samples_ms(time)
    assert len(barrier_ms) == 3
    assert max(barrier_ms) < 6.0, (
        f"barrier_time {barrier_ms} — matched samples give ~2 ms each; "
        "a spread like [0, 2, 12] means a mean-compute subtraction")
    assert 1.0 < statistics.median(barrier_ms) < 6.0, (
        f"barrier_time {barrier_ms} — matched samples give ~2 ms each")
    assert sum(1 for b in barrier_ms if b <= 1.0) <= 1, (
        f"barrier_time {barrier_ms} — more than one collapsed sample is "
        "a subtraction bug, not host jitter")
