"""Python-tier fault injection: step-boundary sleeps + scripted crashes.

The python tier is single-controller: one process drives the whole
device mesh, and a proxy's step is one async device launch
(proxies/base.py).  Where the native tier can delay ONE rank inside a
rendezvous, the honest injection point here is the step boundary — a
host-side sleep before the dispatch IS what a straggler looks like to a
fenced harness (the collective gates on the slowest rank, so a delay on
any target rank inflates the whole step), and a scripted
``RankFailure`` at the trigger iteration is the controller-visible form
of a rank death.

``FaultInjector`` plugs into ``ProxyConfig.fault_injector``
(proxies/base.run_proxy calls ``before_chain`` ahead of every timed
fence chain and warmup pass); ``faults.policy.run_faulted`` catches the
``RankFailure`` and applies the degradation policy.

``parallel.collectives`` additionally exposes a module-level hook
(``set_fault_hook``) invoked at every collective wrapper call — for
EAGER callers and tests.  Inside a jitted/shard_mapped program the
wrapper runs at trace time only, so per-collective injection cannot
reach a compiled step; that is by design and documented
(docs/RESILIENCE.md): per-iteration injection is the measurable channel
on this tier.
"""
from __future__ import annotations

import random
import time

from dlnetbench_tpu.faults.plan import FaultPlan


class RankFailure(RuntimeError):
    """A fault-plan scripted rank death (python tier)."""

    def __init__(self, rank: int, iteration: int):
        super().__init__(f"rank {rank} crashed by fault plan "
                         f"(iteration {iteration})")
        self.rank = rank
        self.iteration = iteration


class RankPreempted(RuntimeError):
    """A fault-plan scripted grace-window eviction (python tier): the
    SIGTERM-notice shape — unlike RankFailure the departure is
    announced, and ``grace_us`` is the drain budget the policy layer
    may spend on a final checkpoint save before the devices are gone
    (faults/policy.py run_faulted)."""

    def __init__(self, rank: int, iteration: int, grace_us: float = 0.0):
        super().__init__(f"rank {rank} preempted by fault plan "
                         f"(iteration {iteration}, grace "
                         f"{grace_us / 1e3:.1f} ms)")
        self.rank = rank
        self.iteration = iteration
        self.grace_us = grace_us


class FaultInjector:
    """Applies a plan's step-boundary events; one per measured run.

    The controller plays every rank, so a delay targeting ANY rank
    gates the step (collective semantics) and a crash targeting any
    rank surfaces as that rank's RankFailure.  ``iteration`` counts
    every harness step (warmup included), matching the native tier.

    The single-controller default plays EVERY rank (``rank=None``).
    ``rank=r`` scopes the injector to one rank's view — only events
    targeting ``r`` fire — which is how a multi-controller run (one
    process per rank, each measuring its own clock) injects: each
    process constructs ``FaultInjector(plan, world, rank=its_rank)``
    and the straggler's delay lands on exactly the scripted rank's
    timeline (the per-rank step series analysis/critical_path.py
    assigns blame from).
    """

    def __init__(self, plan: FaultPlan, world: int | None = None,
                 rank: int | None = None, sleep=time.sleep):
        self.plan = plan
        self.sleep = sleep  # seconds; a test's own clock advances here
        self.world = world  # needed to name a partition's far side
        self.rank = rank    # None = controller plays every rank
        self.iteration = 0
        self.injected_delay_us = 0.0
        self.crash_raised_at = 0.0  # monotonic stamp for detection_ms
        # one independent stream PER EVENT (keyed by position, seeded
        # by (seed, index)): two events sharing a seed value must not
        # interleave draws from one stream, or adding an unrelated
        # event would change another event's injected delays and break
        # the deterministic-replay contract
        self._rng = [random.Random((e.seed << 20) ^ (i + 1))
                     for i, e in enumerate(plan.events)]

    def before_step(self) -> float:
        """Apply one step's worth of faults; returns the injected sleep
        in microseconds (already slept).  Raises RankFailure at a crash
        (or controller-losing partition) trigger."""
        it = self.iteration
        self.iteration += 1
        sleep_us = 0.0
        for ei, e in enumerate(self.plan.events):
            if not e.live_at(it):
                continue
            if self.rank is not None and not e.targets(self.rank):
                # rank-scoped view (multi-controller emulation): this
                # rank's timeline only carries events aimed at it
                continue
            if e.kind == "delay" and e.where == "step":
                sleep_us += e.magnitude_us
            elif e.kind == "jitter" and e.where == "step":
                sleep_us += self._rng[ei].uniform(0, e.magnitude_us)
            elif e.kind == "crash" and it == e.iteration:
                self._sleep(sleep_us)
                self.crash_raised_at = time.monotonic()
                raise RankFailure(min(e.ranks) if e.ranks else 0, it)
            elif e.kind == "preempt" and it == e.iteration:
                # announced eviction: the policy layer catches this and
                # spends the grace window on a drain save; 'rejoin'
                # events never raise — they only mark the step index at
                # which the policy layer grows the world back
                self._sleep(sleep_us)
                self.crash_raised_at = time.monotonic()
                raise RankPreempted(min(e.ranks), it,
                                    grace_us=e.magnitude_us)
            elif e.kind == "partition" and it == e.iteration and e.group:
                # the side WITHOUT rank 0 is lost to the controller —
                # surfaces like a crash of those ranks.  When rank 0
                # sits inside the group the lost side is the
                # complement, which needs the world size to name.
                if 0 not in e.group:
                    far = sorted(e.group)
                elif self.world is None:
                    raise ValueError(
                        "fault plan: a partition whose group contains "
                        "rank 0 loses the complement side — construct "
                        "FaultInjector(plan, world=N) to enumerate it")
                else:
                    far = [r for r in range(self.world)
                           if r not in e.group]
                if far:
                    self._sleep(sleep_us)
                    self.crash_raised_at = time.monotonic()
                    raise RankFailure(far[0], it)
        self._sleep(sleep_us)
        return sleep_us

    def before_chain(self, reps: int) -> float:
        """One fence chain = ``reps`` back-to-back step dispatches
        (utils/timing.time_chain); apply each rep's step faults."""
        total = 0.0
        for _ in range(max(reps, 1)):
            total += self.before_step()
        return total

    def _sleep(self, us: float) -> None:
        if us > 0:
            self.sleep(us / 1e6)
            self.injected_delay_us += us
