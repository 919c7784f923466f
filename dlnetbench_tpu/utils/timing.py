"""Wall-clock timing of jitted programs.

Device execution is async: a jitted call returns before the device finishes
(SURVEY.md §5.1).  Every measurement here fences on the outputs with
``jax.block_until_ready`` — the TPU analogue of the reference's
host-blocking timer brackets (reference CCUTILS_MPI_TIMER_START/STOP,
cpp/data_parallel/dp.cpp:102-104) — applied around the *whole program*,
never inside it, so on-device overlap is preserved.

A sample is the host clock from dispatch to the end of the fence; nothing
is subtracted from it.  What one dispatch plus fence costs with no device
work behind it is measured separately (``dispatch_fence_s``) and stamped
into records as ``host_rtt_us``: the floor the attribution engine's host
share cites.
"""
from __future__ import annotations

import os
import statistics
import time

import jax
import jax.numpy as jnp

from dlnetbench_tpu.metrics import spans

_DISPATCH_FENCE_S: float | None = None
_IMPORTED_AT = time.perf_counter()


def process_age_s() -> float:
    """Seconds since this process started, by the kernel's clock: what
    the interpreter, the imports and the device's bring-up took is in
    it.  Since this module's import where ``/proc`` cannot say."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED_AT


def dispatch_fence_s() -> float:
    """Cost of one empty dispatch plus fence (cached): a scalar add on a
    fresh value, blocked on.  The minimum of five probes after one
    warm-up (which pays the compile)."""
    global _DISPATCH_FENCE_S
    if _DISPATCH_FENCE_S is None:
        base = jnp.zeros(())
        jax.block_until_ready(base + 0)
        samples = []
        for i in range(1, 6):
            t0 = time.perf_counter()
            jax.block_until_ready(base + i)
            samples.append(time.perf_counter() - t0)
        _DISPATCH_FENCE_S = min(samples)
    return _DISPATCH_FENCE_S


def _fence(res, k: int) -> None:
    """Wait for everything ``res`` depends on.  The span tagging the
    fence on a traced timeline is gated on ``is_enabled`` so an untraced
    run's timed window pays NOTHING here — not even the attrs dict a
    ``span(**kwargs)`` call would build."""
    if spans.is_enabled():
        with spans.span("fence", k=k):
            jax.block_until_ready(res)
    else:
        jax.block_until_ready(res)


def time_callable(fn, *args, reps: int = 1, clock=time.perf_counter,
                  **kwargs) -> list[float]:
    """Run ``fn(*args)`` ``reps`` times, fencing each run; returns seconds
    per run by ``clock``.  Caller is responsible for warmup
    (compilation)."""
    out = []
    for _ in range(reps):
        t0 = clock()
        _fence(fn(*args, **kwargs), k=1)
        out.append(clock() - t0)
    return out


def time_chain(fn, *args, k: int = 1, clock=time.perf_counter,
               **kwargs) -> float:
    """Run ``fn(*args)`` ``k`` times back-to-back with ONE fence after the
    last call; returns per-iteration seconds (elapsed / k).

    The per-rep fencing of ``time_callable`` charges every sample one
    dispatch plus fence of host latency, and leaves the device idle
    between reps — a constant bias that dwarfs sub-millisecond programs.
    Chaining k dispatches under one fence amortizes it to 1/k per
    iteration.  The async runtime queues the k launches; each program
    consumes the carried state of the previous call (the executor
    rebinds donated carries), so the device executes them strictly in
    sequence and the chain elapsed time is k honest iterations.  Caller
    is responsible for warmup (compilation)."""
    if k <= 1:
        return time_callable(fn, *args, clock=clock, **kwargs)[0]
    t0 = clock()
    res = None
    for _ in range(k):
        res = fn(*args, **kwargs)
    # the per-chain fence is span-tagged (traced runs only) so the
    # merged timeline shows the host blocked-on-device tail distinct
    # from the dispatch burst
    _fence(res, k=k)
    return (clock() - t0) / k


def median_us(samples_s: list[float]) -> float:
    return statistics.median(samples_s) * 1e6
