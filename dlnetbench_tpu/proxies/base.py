"""Proxy harness: warmup, run estimation, timed runs, loop mode.

Reproduces the reference's measurement skeleton (reference
cpp/data_parallel/dp.cpp:234-264):

  barrier -> warmup loop (default 3) -> [estimate runs from warmup times,
  skipping the first 2, when min_exectime is set] -> clear timers ->
  timed runs (default 5) -> emit.

Where the reference brackets host-blocking collective calls with wall
timers, a TPU program is one async device launch, so per-collective cost is
measured by *decomposition* (SURVEY.md §7.3 hard-part 1): each proxy
provides up to three jitted variants of its step —

  full      the real schedule (compute overlapped with collectives)
  compute   collectives stripped (burn chains only)
  comm      compute stripped (collectives only)

All are timed whole-program with ``block_until_ready`` fencing.  Then

  runtime        = t(full)                      per iteration
  exposed comm   = max(0, t(full) - t(compute)) the reference's "barrier"
                   timer: communication not hidden by compute (dp.cpp:191)
  wire comm      = t(comm)                      fenced lower bound of the
                   collective cost without contention from compute
  overlap        = (t(compute) + t(comm) - t(full)) / min(...)
                   the measured comm–compute overlap fraction
                   (metrics/stats.overlap_fraction): 1.0 = the shorter
                   leg fully hidden, 0.0 = serialized, negative =
                   interference

Loop mode (reference ``-DPROXY_LOOP`` binaries, dp.cpp:251-256) re-runs the
full step forever to generate sustained background load for interference
studies.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections.abc import Callable

import jax

from dlnetbench_tpu.metrics import spans, telemetry
from dlnetbench_tpu.utils.timing import (dispatch_fence_s, time_callable,
                                         time_chain)

DEFAULT_WARMUP = 3   # reference dp.cpp:65
DEFAULT_RUNS = 5     # reference dp.cpp:66


@dataclasses.dataclass
class ProxyConfig:
    warmup: int = DEFAULT_WARMUP
    runs: int = DEFAULT_RUNS
    min_exectime_s: float = 0.0    # reference -m flag -> estimate_runs
    loop: bool = False             # reference PROXY_LOOP
    size_scale: float = 1.0        # shrink buffers for dev machines
    time_scale: float = 1.0        # shrink burn durations for dev machines
    measure_comm_only: bool = True
    measure_compute_only: bool = True
    measure_energy: bool = True    # reference PROXY_ENERGY_PROFILING
    # K-chained fencing: K dispatches per host fence, so dispatch + fence
    # latency amortize over K iterations instead of biasing every sample
    # (utils/timing.py time_chain); 1 = the reference's fence-per-rep
    reps_per_fence: int = 1
    # faults.inject.FaultInjector (or None): step-boundary fault
    # injection — the FULL step callable is wrapped so delay/jitter
    # sleeps land INSIDE the timed window (a straggler must inflate the
    # runtime sample, exactly as the native tier's in-step injection
    # does) and scripted RankFailures fire at their trigger iteration.
    # The compute/comm A/B legs stay unwrapped: they are the CLEAN
    # decomposition baseline, and only full-step invocations advance
    # the plan's iteration counter (native step-count parity).
    fault_injector: object | None = None
    # utils.watchdog.StepWatchdog (or None): arms around every fenced
    # chain and beats a per-phase heartbeat, stamped into the record
    # (watchdog_heartbeat_age_s) so post-mortems of hung runs show
    # where progress stopped.
    watchdog: object | None = None


@dataclasses.dataclass
class StepBundle:
    """What a proxy's ``build()`` returns."""
    full: Callable          # () -> outputs (closed over device buffers)
    compute: Callable | None
    comm: Callable | None
    global_meta: dict       # model/grid/message-size metadata for the emitter
    # named comm-only sub-schedules timed into "<name>_time" timers — the
    # per-collective parity channel (reference fsdp.cpp:61-66 allgather/
    # reduce_scatter timers, hybrid_3d.cpp:65-68 pp/dp/tp_comm timers)
    variants: dict | None = None
    # pytree of the proxy's device buffers for the checkpoint path
    # (faults/policy.py run_faulted + utils/checkpoint.py
    # SnapshotCheckpointer).  The executor donates private CLONES, so
    # these originals stay readable; proxies replay stateless schedules,
    # which means the save/restore COST is real (the bytes a training
    # state of this proxy's size moves) while the values never change —
    # documented in docs/RESILIENCE.md.
    state: object | None = None


def estimate_runs(warmup_times_s: list[float], min_exectime_s: float,
                  skip: int = 2) -> int:
    """Runs needed so total measured time reaches ``min_exectime_s``, from
    the mean warm-up iteration time excluding the first ``skip`` iterations
    (reference cpp/utils.hpp:121-135 — including its intent, not its
    divide-by-the-wrong-count bug, SURVEY.md §7.4)."""
    usable = warmup_times_s[skip:] or warmup_times_s[-1:]
    mean = sum(usable) / len(usable)
    if mean <= 0:
        return 1
    return max(1, math.ceil(min_exectime_s / mean))


@dataclasses.dataclass
class ProxyResult:
    name: str
    global_meta: dict
    timers_us: dict          # timer name -> list of per-iteration us
    warmup_times_us: list
    num_runs: int

    def mean_us(self, timer: str) -> float:
        vals = self.timers_us.get(timer, [])
        return sum(vals) / len(vals) if vals else 0.0


def _chain_sizes(runs: int, k: int) -> list[int]:
    """Partition ``runs`` iterations into fence chains of (at most) ``k``."""
    if k <= 1:
        return [1] * runs
    sizes = [k] * (runs // k)
    if runs % k:
        sizes.append(runs % k)
    return sizes


def run_proxy(name: str, bundle: StepBundle, cfg: ProxyConfig,
              energy_sampler=None, clock=time.perf_counter) -> ProxyResult:
    # ``clock`` reads every timer of the result (a test hands in one it
    # advances itself)
    # fault injection (faults/inject.py): wrap the FULL step so the
    # injected sleeps land inside every timed window and crash triggers
    # count warmup + measured invocations, matching the native tier
    injector = cfg.fault_injector
    if injector is not None:
        base_full = bundle.full

        def full_step():
            injector.before_step()
            return base_full()
    else:
        full_step = bundle.full
    wd = cfg.watchdog

    # warmup; reference dp.cpp:234-244.  Bundles are AOT-compiled at
    # build time (core/executor.py), so these samples measure EXECUTION
    # only — compile time can no longer pollute estimate_runs through
    # the warmup mean the way a first-call jit compile did.
    with spans.span("warmup", proxy=name, reps=max(cfg.warmup, 1)):
        warmup_s = time_callable(full_step, reps=max(cfg.warmup, 1),
                                 clock=clock)
    if wd is not None:
        wd.beat("warmup")
    if telemetry.is_enabled():
        # flight-recorder context (ISSUE 14): warmup samples give the
        # anomaly dumps a pre-measurement baseline.  Step indices count
        # every harness step warmup included — the fault plan's units.
        # A fresh run over a live recorder re-baselines the step-time
        # detector (an in-process sweep's next config is not an anomaly
        # against the previous config's walls).
        telemetry.current().reset_walls("proxy")
        for w, t in enumerate(warmup_s):
            telemetry.record_step("proxy", step=w, phase="warmup",
                                  step_wall_us=round(t * 1e6, 1))

    runs = cfg.runs
    if cfg.min_exectime_s > 0:
        runs = estimate_runs(warmup_s, cfg.min_exectime_s)

    if cfg.loop:  # reference PROXY_LOOP, dp.cpp:251-256
        while True:
            full_step()

    if energy_sampler is None and cfg.measure_energy:
        with spans.span("calibrate", what="energy_sampler"):
            from dlnetbench_tpu.metrics.energy import detect_sampler
            energy_sampler = detect_sampler()
    if energy_sampler is not None:
        # which sensor produced energy_consumed — misattribution (wrong
        # hwmon device) must be visible in the record, not silent
        bundle.global_meta["energy_source"] = getattr(
            energy_sampler, "source", type(energy_sampler).__name__)

    # Interleaved A/B measurement: each full run is paired with an
    # immediately adjacent compute-only run, so barrier_time[i] =
    # full[i] - compute[i] uses a MATCHED sample — run-to-run compute
    # variance (clock drift, co-tenancy) hits both sides of the
    # subtraction instead of leaking into the exposed-comm signal the way
    # a full[i] - mean(compute) estimate would.  The reference gets this
    # for free by bracketing WaitAll inside the same iteration
    # (dp.cpp:191); the decomposition channel has to earn it.
    measure_compute = cfg.measure_compute_only and bundle.compute is not None
    if measure_compute:
        with spans.span("warmup", proxy=name, variant="compute"):
            time_callable(bundle.compute, reps=1)  # warm outside A/B loop

    # fence chains: with reps_per_fence = K each chain is K back-to-back
    # dispatches fenced ONCE, and contributes one per-iteration sample
    # (time_chain's elapsed/K) — the A/B pairing below is then
    # chain-vs-chain, still matched in time
    chains = _chain_sizes(runs, max(cfg.reps_per_fence, 1))
    bundle.global_meta["reps_per_fence"] = max(cfg.reps_per_fence, 1)
    # one empty dispatch plus fence is the HOST-overhead floor every
    # chain pays once (it stays in the samples): stamped so the
    # attribution engine's ``host`` fraction can cite a measured
    # dispatch/fence figure instead of guessing
    bundle.global_meta["host_rtt_us"] = round(dispatch_fence_s() * 1e6, 1)

    timers: dict[str, list] = {}
    full_s: list[float] = []
    comp_s: list[float] = []
    energy_j: list[float] = []
    fault_us: list[float] = []
    with spans.span("timed", proxy=name, variant="full+compute",
                    runs=runs, chains=len(chains)):
        for ci, k in enumerate(chains):
            # Energy brackets ONLY the fenced full chain (reference
            # per-rank energy_consumed arrays, plots/parser.py:172),
            # reported per iteration.  The fence inside time_chain
            # guarantees the device work finished before the closing
            # read.
            if energy_sampler is not None:
                e0 = energy_sampler.read_joules()
            inj0 = injector.injected_delay_us if injector is not None else 0.0
            if wd is not None:
                with wd:
                    t_full = time_chain(full_step, k=k, clock=clock)
                wd.beat(f"chain_{ci}")
            else:
                t_full = time_chain(full_step, k=k, clock=clock)
            if injector is not None:
                # injected latency attributable to this chain, per
                # iteration — lets analyses subtract the scripted delay
                # from the observed inflation (straggler amplification)
                fault_us.append(
                    (injector.injected_delay_us - inj0) / k)
            if energy_sampler is not None:
                energy_j.append(max(0.0,
                                    energy_sampler.read_joules() - e0) / k)
            full_s.append(t_full)
            if measure_compute:
                comp_s.append(time_chain(bundle.compute, k=k, clock=clock))
            if telemetry.is_enabled():
                # one ring sample per fenced chain: the measured
                # per-iteration wall plus the axes the flight dump
                # needs to explain it (energy per step where a sampler
                # exists — the ISSUE 14 satellite; the injected delay
                # so a straggler window self-identifies; the matched
                # compute leg).  Step index = warmup + iterations so
                # far (fault-plan units).
                step_ix = max(cfg.warmup, 1) + sum(chains[:ci]) + k - 1
                fields = {"phase": "timed",
                          "step_wall_us": round(t_full * 1e6, 1),
                          "chain_k": k}
                if measure_compute:
                    fields["compute_us"] = round(comp_s[-1] * 1e6, 1)
                if energy_sampler is not None and energy_j:
                    fields["energy_j"] = round(energy_j[-1], 6)
                if injector is not None and fault_us:
                    fields["fault_delay_us"] = round(fault_us[-1], 1)
                telemetry.record_step("proxy", step=step_ix, **fields)
                telemetry.observe_step_wall("proxy", t_full * 1e6,
                                            step=step_ix)
    timers["runtimes"] = [t * 1e6 for t in full_s]
    if injector is not None:
        timers["fault_delay_us"] = [round(v, 1) for v in fault_us]
    if energy_sampler is not None:
        timers["energy_consumed"] = energy_j
        # stop any background polling now that the measured phase is over
        # (restartable: the cached sampler revives on its next read)
        from dlnetbench_tpu.metrics.energy import close_sampler
        close_sampler(energy_sampler)
    if measure_compute:
        timers["compute_time"] = [t * 1e6 for t in comp_s]
        timers["barrier_time"] = [max(0.0, f - c) * 1e6
                                  for f, c in zip(full_s, comp_s)]

    if cfg.measure_comm_only and bundle.comm is not None:
        with spans.span("timed", proxy=name, variant="comm"):
            time_callable(bundle.comm, reps=1)  # warm
            comm_s = [time_chain(bundle.comm, k=k, clock=clock)
                      for k in chains]
        timers["comm_time"] = [t * 1e6 for t in comm_s]
        if measure_compute:
            # measured comm–compute overlap per chain (the A/B
            # decomposition answering SURVEY §7.3 hard-part 1
            # quantitatively): 1.0 = shorter leg fully hidden, 0.0 =
            # serialized, negative = interference.  Dimensionless —
            # rides the record like a timer and surfaces as the
            # ``overlap`` column in analysis/bandwidth.py summaries.
            from dlnetbench_tpu.metrics.stats import overlap_fraction
            timers["overlap_fraction"] = [
                round(v, 4) for v in overlap_fraction(full_s, comp_s,
                                                      comm_s)]

    if cfg.measure_comm_only and bundle.variants:
        for vname, vfn in bundle.variants.items():
            with spans.span("timed", proxy=name, variant=vname):
                time_callable(vfn, reps=1)  # warm
                v_s = [time_chain(vfn, k=k, clock=clock) for k in chains]
            timers[f"{vname}_time"] = [t * 1e6 for t in v_s]

    if wd is not None:
        # last-progress heartbeat ages at emission time: a completed
        # run shows tiny ages everywhere; a post-mortem of a hung run
        # (record emitted by a supervisor) shows WHERE progress stopped
        wd.stamp(bundle.global_meta)
    return ProxyResult(
        name=name,
        global_meta=bundle.global_meta,
        timers_us=timers,
        warmup_times_us=[t * 1e6 for t in warmup_s],
        num_runs=runs,
    )
