"""Fault-injection & elastic degradation (dlnetbench_tpu/faults/):
plan round-trip, step-boundary injection, the three degradation
policies around the dp proxy on the virtual mesh, record provenance,
and the analysis layer's straggler/recovery columns."""
from __future__ import annotations

import dataclasses
import json

import pytest

from dlnetbench_tpu.faults.inject import FaultInjector, RankFailure
from dlnetbench_tpu.faults.plan import FaultEvent, FaultPlan


# --------------------------------------------------------------- plan
def test_plan_roundtrip_and_native_args(tmp_path):
    plan = FaultPlan(events=[
        FaultEvent(kind="delay", ranks=[2], iteration=1, until=5,
                   magnitude_us=2000.0),
        FaultEvent(kind="crash", ranks=[3], iteration=4),
    ], policy="shrink").validate()
    text = plan.dumps()
    back = FaultPlan.loads(text)
    assert back.to_dict() == plan.to_dict()
    # @file form
    p = tmp_path / "plan.json"
    p.write_text(text)
    assert FaultPlan.loads(f"@{p}").to_dict() == plan.to_dict()
    argv = plan.native_args()
    assert argv[0] == "--fault" and json.loads(argv[1]) == plan.to_dict()
    assert argv[2:] == ["--fault_policy", "shrink"]
    assert plan.crash_victims() == [3]
    assert plan.survivors(6) == [0, 1, 2, 4, 5]
    assert plan.first_crash_iteration() == 4
    assert plan.fault_window() == (1, -1) or plan.fault_window() == (1, None)


def test_plan_validation_rejects_bad_plans():
    with pytest.raises(ValueError, match="kind"):
        FaultPlan(events=[FaultEvent(kind="meteor")]).validate()
    with pytest.raises(ValueError, match="policy"):
        FaultPlan(policy="hope").validate()
    with pytest.raises(ValueError, match="drop rate"):
        FaultPlan(events=[FaultEvent(kind="drop", rate=1.0)]).validate()
    with pytest.raises(ValueError, match="partition"):
        FaultPlan(events=[FaultEvent(kind="partition")]).validate()


# ----------------------------------------------------------- injector
def test_injector_delay_window_and_counters():
    plan = FaultPlan(events=[FaultEvent(
        kind="delay", ranks=[1], iteration=1, until=3,
        magnitude_us=1000.0)]).validate()
    inj = FaultInjector(plan)
    slept = [inj.before_step() for _ in range(4)]
    # live at iterations 1 and 2 only
    assert slept[0] == 0.0 and slept[3] == 0.0
    assert slept[1] == slept[2] == 1000.0
    assert inj.injected_delay_us == 2000.0
    assert inj.iteration == 4


def test_injector_jitter_is_seeded_and_bounded():
    plan = FaultPlan(events=[FaultEvent(
        kind="jitter", iteration=0, magnitude_us=500.0,
        seed=7)]).validate()
    a = FaultInjector(plan)
    b = FaultInjector(plan)
    sa = [a.before_step() for _ in range(3)]
    sb = [b.before_step() for _ in range(3)]
    assert sa == sb  # deterministic replay
    assert all(0.0 <= v < 500.0 for v in sa)


def test_injector_crash_fires_exactly_at_trigger():
    plan = FaultPlan(events=[FaultEvent(kind="crash", ranks=[2],
                                        iteration=2)]).validate()
    inj = FaultInjector(plan)
    inj.before_step()
    inj.before_step()
    with pytest.raises(RankFailure) as ei:
        inj.before_step()
    assert ei.value.rank == 2 and ei.value.iteration == 2
    # the trigger fires once: the counter moved past it
    inj.before_step()


def test_collectives_fault_hook():
    """The pre-collective hook fires per wrapper invocation (once per
    TRACE for jitted programs — the documented semantics) and clears
    cleanly."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from dlnetbench_tpu.parallel import collectives
    from dlnetbench_tpu.utils.jax_compat import shard_map

    calls = []
    collectives.set_fault_hook(lambda op, axis: calls.append((op, axis)))
    try:
        mesh = Mesh(jax.devices()[:2], ("x",))
        prog = jax.jit(shard_map(
            lambda v: collectives.allreduce(v, "x"), mesh=mesh,
            in_specs=P("x"), out_specs=P()))
        out = prog(jnp.ones((2,), jnp.float32))
        assert float(out[0]) == 2.0
        assert calls == [("allreduce", "x")]  # once, at trace time
        prog(jnp.ones((2,), jnp.float32))
        assert len(calls) == 1  # compiled re-run: no host hook
    finally:
        collectives.set_fault_hook(None)
    collectives._maybe_fault("allreduce", "x")
    assert len(calls) == 1  # cleared


# ------------------------------------------------- policies (dp proxy)
def _dp_bundle(cfg, devices, dtype=None):
    import jax.numpy as jnp

    from dlnetbench_tpu.core.model_stats import load_model_stats
    from dlnetbench_tpu.parallel.mesh import make_flat_mesh
    from dlnetbench_tpu.proxies import dp as dp_proxy

    return dp_proxy.build(load_model_stats("gpt2_l_16_bfloat16"), 2, cfg,
                          mesh=make_flat_mesh(devices=devices),
                          dtype=dtype or jnp.float32)


@pytest.fixture(scope="module")
def proxy_cfg():
    from dlnetbench_tpu.proxies.base import ProxyConfig
    return ProxyConfig(warmup=1, runs=4, size_scale=1e-4, time_scale=1e-3,
                       measure_comm_only=False, measure_compute_only=False,
                       measure_energy=False)


def _straggler_plan_and_config(proxy_cfg):
    plan = FaultPlan(events=[FaultEvent(kind="delay", ranks=[1],
                                        iteration=3,
                                        magnitude_us=20000.0)]).validate()
    return plan, dataclasses.replace(proxy_cfg, runs=4)


def test_straggler_delay_rides_the_runtime_samples(eight_devices, proxy_cfg,
                                                   owned_clock):
    """An injected per-step delay must inflate the timed runtime (the
    sleep lands INSIDE the chain) and be accounted in the
    fault_delay_us timer.  The samples are read on a clock that only
    the injector's sleep advances: a faulted run is the 20 ms exactly,
    a clean one nothing."""
    from dlnetbench_tpu.faults.policy import run_faulted
    from dlnetbench_tpu.proxies.base import run_proxy

    plan, cfg = _straggler_plan_and_config(proxy_cfg)
    bundle = _dp_bundle(cfg, eight_devices)
    res = run_faulted("dp", bundle, cfg, plan)
    g = res.global_meta
    assert g["fault_policy"] == "fail_fast"
    assert g["fault_plan"]["events"][0]["kind"] == "delay"
    assert g["fault_injected_delay_us"] >= 2 * 20000.0
    fd = res.timers_us["fault_delay_us"]
    assert len(fd) == cfg.runs
    # window starts at step 3 = measured run 2 (after the 1-step warmup)
    assert fd[0] == fd[1] == 0.0 and fd[2] >= 19999 and fd[3] >= 19999
    # what ``run_faulted`` runs under this plan, with the injector's
    # sleep and the samples on the test's own clock
    injector = FaultInjector(plan, world=8, sleep=owned_clock.sleep)
    res = run_proxy("dp", bundle,
                    dataclasses.replace(cfg, fault_injector=injector),
                    clock=owned_clock.perf_counter)
    assert res.timers_us["runtimes"] == pytest.approx([0, 0, 20000, 20000])
    assert res.timers_us["fault_delay_us"] == [0, 0, 20000, 20000]


@pytest.mark.slow
def test_straggler_delay_rides_the_runtime_samples_by_the_wall_clock(
        eight_devices, proxy_cfg):
    """The faulted samples carry the sleep over the IN-RECORD clean
    baseline (runs 0-1, adjacent in time: cross-run medians would be at
    the mercy of host drift)."""
    import statistics

    from dlnetbench_tpu.faults.policy import run_faulted
    plan, cfg = _straggler_plan_and_config(proxy_cfg)
    rt = run_faulted("dp", _dp_bundle(cfg, eight_devices), cfg,
                     plan).timers_us["runtimes"]
    assert (statistics.median(rt[2:]) - statistics.median(rt[:2])
            >= 15000)


def test_crash_fail_fast_propagates(eight_devices, proxy_cfg):
    from dlnetbench_tpu.faults.policy import run_faulted

    plan = FaultPlan(events=[FaultEvent(kind="crash", ranks=[2],
                                        iteration=2)]).validate()
    bundle = _dp_bundle(proxy_cfg, eight_devices)
    with pytest.raises(RankFailure, match="rank 2"):
        run_faulted("dp", bundle, proxy_cfg, plan)


def test_crash_retry_recovers_on_same_world(eight_devices, proxy_cfg):
    from dlnetbench_tpu.faults.policy import run_faulted

    plan = FaultPlan(events=[FaultEvent(kind="crash", ranks=[2],
                                        iteration=2)],
                     policy="retry").validate()
    bundle = _dp_bundle(proxy_cfg, eight_devices)
    res = run_faulted("dp", bundle, proxy_cfg, plan)
    g = res.global_meta
    assert g["fault_retries"] == 1
    assert g["recovery_ms"] > 0 and g["detection_ms"] >= 0
    assert "degraded_world" not in g
    assert res.num_runs == proxy_cfg.runs
    assert len(res.timers_us["runtimes"]) == proxy_cfg.runs


def test_crash_shrink_finishes_on_survivors(eight_devices, proxy_cfg):
    """The elastic-degradation acceptance path on the python tier: the
    run finishes on the survivor mesh, the record declares
    degraded_world with ORIGINAL rank ids, detection/recovery are
    stamped, and the emitted record validates + parses."""
    from dlnetbench_tpu.faults.policy import run_faulted
    from dlnetbench_tpu.metrics.emit import result_to_record
    from dlnetbench_tpu.metrics.parser import records_to_dataframe, \
        validate_record

    plan = FaultPlan(events=[FaultEvent(kind="crash", ranks=[2],
                                        iteration=3)],
                     policy="shrink").validate()
    bundle = _dp_bundle(proxy_cfg, eight_devices)

    def rebuild(survivors):
        return _dp_bundle(proxy_cfg, [eight_devices[i] for i in survivors])

    res = run_faulted("dp", bundle, proxy_cfg, plan, rebuild=rebuild)
    g = res.global_meta
    assert g["degraded_world"] == [0, 1, 3, 4, 5, 6, 7]
    assert g["world_size"] == 8
    assert g["recovery_ms"] > 0 and g["detection_ms"] >= 0
    assert res.num_runs == proxy_cfg.runs

    rec = result_to_record(res)
    assert [row["rank"] for row in rec["ranks"]] == [0, 1, 3, 4, 5, 6, 7]
    validate_record(rec)
    df = records_to_dataframe([rec])
    assert len(df) == 7 * proxy_cfg.runs
    assert (df["runtime"] > 0).all()


def test_shrink_without_rebuild_or_bad_trigger_rejected(proxy_cfg):
    import dataclasses

    from dlnetbench_tpu.faults.policy import run_faulted

    class FakeBundle:
        global_meta = {"world_size": 4}

    plan = FaultPlan(events=[FaultEvent(kind="crash", ranks=[1],
                                        iteration=0)],
                     policy="shrink").validate()
    with pytest.raises(ValueError, match="warmup"):
        run_faulted("dp", FakeBundle(), proxy_cfg, plan, rebuild=lambda s: s)
    plan2 = FaultPlan(events=[FaultEvent(kind="crash", ranks=[1],
                                         iteration=2)],
                      policy="shrink").validate()
    cfg = dataclasses.replace(proxy_cfg, reps_per_fence=4)
    with pytest.raises(ValueError, match="reps_per_fence"):
        run_faulted("dp", FakeBundle(), cfg, plan2, rebuild=lambda s: s)
    # run-count estimation could move the measured region past the
    # trigger, letting the crash escape the policy — rejected up front
    cfg2 = dataclasses.replace(proxy_cfg, min_exectime_s=1.0)
    with pytest.raises(ValueError, match="min_exectime"):
        run_faulted("dp", FakeBundle(), cfg2, plan2, rebuild=lambda s: s)


def test_preempt_rejoin_plan_validation_and_queries():
    """The elastic schema (ISSUE 7): preempt needs explicit ranks and
    policy shrink; rejoin must follow its preempt; the eviction-window
    queries and the fault window close at the rejoin."""
    with pytest.raises(ValueError, match="ranks"):
        FaultPlan(events=[FaultEvent(kind="preempt", iteration=3)],
                  policy="shrink").validate()
    with pytest.raises(ValueError, match="shrink"):
        FaultPlan(events=[FaultEvent(kind="preempt", ranks=[1],
                                     iteration=3)]).validate()
    with pytest.raises(ValueError, match="nobody left"):
        FaultPlan(events=[FaultEvent(kind="rejoin", ranks=[1],
                                     iteration=5)],
                  policy="shrink").validate()
    with pytest.raises(ValueError, match="does not follow"):
        FaultPlan(events=[
            FaultEvent(kind="preempt", ranks=[1], iteration=5),
            FaultEvent(kind="rejoin", ranks=[1], iteration=4),
        ], policy="shrink").validate()

    plan = FaultPlan(events=[
        FaultEvent(kind="preempt", ranks=[2], iteration=4,
                   magnitude_us=20000.0),
        FaultEvent(kind="rejoin", ranks=[2], iteration=8),
    ], policy="shrink").validate()
    assert plan.preempt_victims() == [2]
    assert plan.first_preempt_iteration() == 4
    assert plan.rejoin_iteration() == 8
    assert not plan.evicted(2, 3)
    assert plan.evicted(2, 4) and plan.evicted(2, 7)
    assert not plan.evicted(2, 8)  # back in the world
    assert not plan.evicted(0, 5)  # survivors were never out
    # window closes at rejoin + 1: the rejoin step pays the grow
    # re-split and must not pass as clean
    assert plan.fault_window() == (4, 9)
    # round-trips through the shared wire format
    assert FaultPlan.loads(plan.dumps()).to_dict() == plan.to_dict()
    # the segmented python tier needs a degraded step between the two
    with pytest.raises(ValueError, match="preempt \\+ 2"):
        FaultPlan(events=[
            FaultEvent(kind="preempt", ranks=[2], iteration=4),
            FaultEvent(kind="rejoin", ranks=[2], iteration=5),
        ], policy="shrink").validate().check_config(
            ProxyConfigStub())


class ProxyConfigStub:
    warmup = 1
    runs = 8
    reps_per_fence = 1
    min_exectime_s = 0.0


def test_preempt_restore_rejoin_end_to_end(eight_devices, proxy_cfg,
                                           tmp_path):
    """The acceptance arc on the python tier: preempt -> grace-window
    drain -> restore-from-latest -> shrink -> rejoin restores the FULL
    world (degraded_world cleared), with checkpoint costs, lost work,
    and goodput stamped — and the record parses clean."""
    import dataclasses

    from dlnetbench_tpu.faults.policy import CheckpointPolicy, run_faulted
    from dlnetbench_tpu.metrics.emit import result_to_record
    from dlnetbench_tpu.metrics.parser import records_to_dataframe, \
        validate_record

    cfg = dataclasses.replace(proxy_cfg, runs=8)
    plan = FaultPlan(events=[
        FaultEvent(kind="preempt", ranks=[2], iteration=4,
                   magnitude_us=50000.0),
        FaultEvent(kind="rejoin", ranks=[2], iteration=7),
    ], policy="shrink").validate()
    bundle = _dp_bundle(cfg, eight_devices)

    def rebuild(ranks):
        return _dp_bundle(cfg, [eight_devices[i] for i in ranks])

    res = run_faulted("dp", bundle, cfg, plan, rebuild=rebuild,
                      checkpoint=CheckpointPolicy(
                          dir=tmp_path / "ck", every=2, mode="stall",
                          backend="npz"))
    g = res.global_meta
    # the world grew back: NO degraded_world, full rank coverage
    assert "degraded_world" not in g
    assert g["fault_rejoin_step"] == 7
    assert g["rejoin_ms"] > 0
    assert g["world_size"] == 8
    # checkpoint accounting: periodic saves happened, the eviction
    # restored from the latest, and the redone work is priced
    assert g["checkpoint_saves"] >= 1
    assert g["checkpoint_ms"] > 0 and g["checkpoint_stall_ms"] > 0
    assert g["checkpoint_backend"] == "npz"
    assert g["restore_ms"] > 0
    assert 0 <= g["lost_steps"] < cfg.runs
    assert g["goodput"] > 0
    assert g["goodput_useful_steps"] == cfg.runs - g["lost_steps"]
    assert g["detection_ms"] >= 0 and g["recovery_ms"] > 0
    assert res.num_runs == cfg.runs

    rec = result_to_record(res)
    validate_record(rec)
    assert [row["rank"] for row in rec["ranks"]] == list(range(8))
    df = records_to_dataframe([rec])
    assert len(df) == 8 * cfg.runs


def test_preempt_without_rejoin_stays_degraded(eight_devices, proxy_cfg):
    """A plan that never grows back degrades to the end like shrink —
    degraded_world keeps the survivor set."""
    from dlnetbench_tpu.faults.policy import run_faulted

    plan = FaultPlan(events=[
        FaultEvent(kind="preempt", ranks=[2], iteration=3,
                   magnitude_us=1000.0),
    ], policy="shrink").validate()
    bundle = _dp_bundle(proxy_cfg, eight_devices)

    def rebuild(ranks):
        return _dp_bundle(proxy_cfg, [eight_devices[i] for i in ranks])

    res = run_faulted("dp", bundle, proxy_cfg, plan, rebuild=rebuild)
    g = res.global_meta
    assert g["degraded_world"] == [0, 1, 3, 4, 5, 6, 7]
    assert "fault_rejoin_step" not in g
    assert g["goodput"] > 0  # the arc still yields its bottom line


def test_checkpoint_policy_requires_declared_state(eight_devices,
                                                   proxy_cfg, tmp_path):
    """A bundle without StepBundle.state cannot honestly price
    checkpointing — refused up front, never priced at zero bytes."""
    import dataclasses

    from dlnetbench_tpu.faults.policy import CheckpointPolicy, run_faulted

    plan = FaultPlan(events=[FaultEvent(kind="crash", ranks=[2],
                                        iteration=3)],
                     policy="shrink").validate()
    bundle = dataclasses.replace(_dp_bundle(proxy_cfg, eight_devices),
                                 state=None)
    with pytest.raises(ValueError, match="checkpointable state"):
        run_faulted("dp", bundle, proxy_cfg, plan,
                    rebuild=lambda s: s,
                    checkpoint=CheckpointPolicy(dir=tmp_path / "ck"))


def test_parallel_stragglers_gate_on_max_not_sum():
    """Delays on DIFFERENT ranks run in parallel: the per-step injected
    figure (amplification denominator) is the max over target ranks,
    plus everyone-targeted events that stack on every rank."""
    plan = FaultPlan(events=[
        FaultEvent(kind="delay", ranks=[1], magnitude_us=100.0),
        FaultEvent(kind="delay", ranks=[2], magnitude_us=100.0),
        FaultEvent(kind="delay", magnitude_us=10.0),  # every rank
    ]).validate()
    assert plan.delay_per_step_us() == 110.0      # max(100, 100) + 10
    assert plan.delay_per_step_us(rank=1) == 110.0
    assert plan.delay_per_step_us(rank=3) == 10.0

    from dlnetbench_tpu.analysis.bandwidth import straggler_amplification
    rec = _faulted_record(runtimes=[1000.0, 1000.0, 1110.0, 1110.0])
    rec["global"]["fault_plan"]["events"] = [
        {"kind": "delay", "ranks": [0], "iteration": 3,
         "magnitude_us": 100.0},
        {"kind": "delay", "ranks": [1], "iteration": 3,
         "magnitude_us": 100.0},
        {"kind": "delay", "iteration": 3, "magnitude_us": 10.0},
    ]
    # 110 us inflation / max-based 110 us = 1.0 (a summed 210 us
    # denominator would misreport 0.52)
    assert straggler_amplification(rec) == pytest.approx(1.0)


def test_fault_window_respects_reps_per_fence():
    """With reps_per_fence = K each runtime sample covers K measured
    steps: a chain with ANY faulted step must group as faulted, and
    the measured fault_delay_us timer (already per-iteration) is the
    amplification denominator for such records."""
    from dlnetbench_tpu.analysis.bandwidth import effective_bandwidth, \
        straggler_amplification

    # 8 measured steps as 2 chains of 4; delay live from step 5 on
    # (warmup 1 -> measured steps 4..) — only chain 1 intersects
    rec = _faulted_record(iteration=5, runtimes=[1000.0, 6000.0],
                          reps_per_fence=4)
    rec["num_runs"] = 2
    for row in rec["ranks"]:
        row["fault_delay_us"] = [0.0, 5000.0]
    bw = effective_bandwidth([rec])
    assert list(bw[bw["run"] == 0]["bound"].unique()) == ["exact"]
    assert list(bw[bw["run"] == 1]["bound"].unique()) == ["faulted"]
    # (6000 - 1000) / measured 5000 per-iteration injection = 1.0
    assert straggler_amplification(rec) == pytest.approx(1.0)


# ------------------------------------------------------ analysis layer
def _faulted_record(kind="delay", iteration=3, until=-1, magnitude=20000.0,
                    runtimes=None, warmup=1, **extra_globals):
    events = [{"kind": kind, "ranks": [1], "iteration": iteration,
               **({"until": until} if until >= 0 else {}),
               **({"magnitude_us": magnitude}
                  if kind in ("delay", "jitter") else {})}]
    runtimes = runtimes or [1000.0, 1000.0, 21000.0, 21000.0]
    return {
        "section": "dp", "version": 2, "process": 0,
        "global": {"proxy": "dp", "model": "m", "world_size": 2,
                   "fault_plan": {"policy": "fail_fast", "events": events},
                   "fault_policy": "fail_fast",
                   "comm_model": {"runtimes": [
                       {"kind": "allreduce", "group": 2,
                        "bytes": 1_000_000}]},
                   **extra_globals},
        "mesh": {"platform": "cpu"},
        "num_runs": len(runtimes),
        "warmup_times": [1.0] * warmup,
        "ranks": [{"rank": r, "device_id": r, "process_index": 0,
                   "hostname": "h", "runtimes": list(runtimes)}
                  for r in range(2)],
    }


def test_bandwidth_suppresses_faulted_runs_and_reports_amplification():
    from dlnetbench_tpu.analysis.bandwidth import bandwidth_summary, \
        effective_bandwidth, straggler_amplification

    rec = _faulted_record()
    bw = effective_bandwidth([rec])
    # steps 0..: warmup 1 -> measured run window starts at run 2
    clean = bw[bw["run"] < 2]
    faulted = bw[bw["run"] >= 2]
    assert (clean["bound"] == "exact").all()
    assert (faulted["bound"] == "faulted").all()
    assert faulted["busbw_GBps"].isna().all()
    assert clean["busbw_GBps"].notna().all()
    # (21000 - 1000) us inflation / 20000 us injected = 1.0
    amp = straggler_amplification(rec)
    assert amp == pytest.approx(1.0)
    summary = bandwidth_summary([rec])
    srow = summary[summary["bound"] == "faulted"].iloc[0]
    assert srow["straggler_amp"] == pytest.approx(1.0)

    # crash records have no comparable baseline: amplification is NaN
    import math
    crash = _faulted_record(kind="crash", detection_ms=5.0,
                            recovery_ms=7.0)
    assert math.isnan(straggler_amplification(crash))
    bw2 = bandwidth_summary([crash])
    assert (bw2["detection_ms"].dropna() == 5.0).all()
    assert (bw2["recovery_ms"].dropna() == 7.0).all()


def test_bandwidth_elastic_recovery_columns():
    """checkpoint_ms / restore_ms / lost_steps / goodput ride every
    bandwidth row of a record that measured them, NaN otherwise; the
    preempt window's runs still get busbw refused."""
    from dlnetbench_tpu.analysis.bandwidth import bandwidth_summary, \
        effective_bandwidth

    rec = _faulted_record(checkpoint_ms=12.5, restore_ms=3.25,
                          lost_steps=2, goodput=6.125)
    rec["global"]["fault_plan"] = {
        "policy": "shrink",
        "events": [{"kind": "preempt", "ranks": [1], "iteration": 3,
                    "magnitude_us": 20000.0},
                   {"kind": "rejoin", "ranks": [1], "iteration": 5}]}
    bw = effective_bandwidth([rec])
    for col, want in (("checkpoint_ms", 12.5), ("restore_ms", 3.25),
                      ("lost_steps", 2.0), ("goodput", 6.125)):
        assert (bw[col] == want).all()
    # warmup 1: plan steps 3..5 (+1 for the rejoin step) = runs 2..4
    faulted = bw[bw["bound"] == "faulted"]
    assert sorted(faulted["run"].unique()) == [2, 3]
    assert faulted["busbw_GBps"].isna().all()
    summary = bandwidth_summary([rec])
    assert (summary["goodput"].dropna() == 6.125).all()

    clean = _faulted_record()
    bw2 = effective_bandwidth([clean])
    for col in ("checkpoint_ms", "restore_ms", "lost_steps", "goodput"):
        assert bw2[col].isna().all()


def test_clean_records_unaffected_by_fault_columns():
    from dlnetbench_tpu.analysis.bandwidth import effective_bandwidth

    rec = _faulted_record()
    del rec["global"]["fault_plan"]
    del rec["global"]["fault_policy"]
    bw = effective_bandwidth([rec])
    assert (bw["bound"] == "exact").all()
    assert bw["busbw_GBps"].notna().all()
    assert bw["straggler_amp"].isna().all()
