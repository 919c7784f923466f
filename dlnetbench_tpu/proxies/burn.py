"""Calibrated on-device compute burn — the ``usleep`` replacement.

The reference simulates compute by host-sleeping for roofline-derived
durations between collective calls (reference cpp/data_parallel/dp.cpp:93,
98).  Inside an XLA program a host sleep is impossible — and sleeping on the
host *between* device dispatches would serialize against the async runtime
and destroy the comm/compute overlap the benchmark exists to measure
(SURVEY.md §7.1 Tier A note).  Instead we burn device cycles with a chained
matmul loop on a small VMEM-resident matrix:

    state <- tanh(state @ state / n)      x iters   (MXU work, bounded values)

The per-iteration cost is calibrated once per (device kind, shape, dtype)
by differencing two loop lengths (cancelling dispatch and loop overheads),
then any requested microsecond budget maps to a static trip count.  The
chain is strictly sequential (each iteration consumes the previous state),
so XLA cannot shrink or parallelize it, and ``tie``-ing a collective's
operand to the chain state reproduces the reference's issue-order semantics.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from dlnetbench_tpu.utils.timing import dispatch_fence_s, time_callable

# 256x256 bf16: two MXU tiles wide — big enough to exercise the MXU,
# small enough to live in VMEM and calibrate in milliseconds.
DEFAULT_SHAPE = (256, 256)
DEFAULT_DTYPE = jnp.bfloat16


def make_state(shape=DEFAULT_SHAPE, dtype=DEFAULT_DTYPE):
    """Deterministic, well-conditioned initial burn state in (-1, 1)."""
    n, m = shape
    i = jnp.arange(n, dtype=jnp.float32)[:, None]
    j = jnp.arange(m, dtype=jnp.float32)[None, :]
    return jnp.sin(i * 0.7 + j * 1.3).astype(dtype) * 0.5


def burn(state, iters: int):
    """Advance the burn chain ``iters`` times (static count).  Returns the
    new state; consuming it (or ``tie``-ing to it) orders work after the
    burn."""
    if iters <= 0:
        return state
    scale = 1.0 / state.shape[-1]

    def body(_, s):
        p = jnp.dot(s, s, preferred_element_type=jnp.float32)
        return jnp.tanh(p * scale).astype(s.dtype)

    return lax.fori_loop(0, iters, body, state, unroll=False)


def burn_if(state, iters: int, active):
    """Advance the chain ``iters`` times when ``active`` (a traced bool —
    typically derived from a mesh axis index), else do ~0 work: the
    rank-predicated burn that lets one SPMD program express stage-gated
    pipeline compute (GPipe fill/drain ticks where idle stages
    participate in the hop but not the burn).  Expressed as ``lax.cond``
    around a STATIC-count loop rather than a dynamic trip count: a
    while-loop bound derived from ``axis_index`` leaves a PartitionId
    in the loop condition that XLA's SPMD partitioner rejects
    (UNIMPLEMENTED on this toolchain), while a conditional's idle branch
    still costs only the predicate check."""
    if iters <= 0:
        return state

    return lax.cond(active,
                    functools.partial(burn, iters=iters),
                    lambda s: s,
                    state)


@dataclasses.dataclass(frozen=True)
class BurnCalibration:
    ns_per_iter: float
    shape: tuple
    dtype: str
    device_kind: str

    def iters_for_us(self, us: float) -> int:
        if us <= 0:
            return 0
        return max(1, round(us * 1000.0 / self.ns_per_iter))

    def us_for_iters(self, iters: int) -> float:
        return iters * self.ns_per_iter / 1000.0


# the shorter calibration probe must outlast one dispatch plus fence by
# this factor, and the probes stop growing at this many iterations
_PROBE_OVER_DISPATCH = 4.0
_MAX_PROBE_ITERS = 1 << 20


def _calibrate_on_device(shape, dtype_name, device, n_lo, n_hi):
    """ns per burn iteration from the difference of two loop lengths.

    The difference cancels dispatch and fence only while both probes
    keep the device busy longer than the host takes to dispatch and
    fence: a probe shorter than that finishes under the host's own
    latency and reads as the host.  So the pair grows fourfold until the
    shorter probe outlasts ``_PROBE_OVER_DISPATCH`` dispatch floors (on
    a v5e one iteration is about a microsecond and the floor most of a
    millisecond: 64 and 256 iterations read 2.2x too fast there)."""
    dtype = jnp.dtype(dtype_name)
    with jax.default_device(device):
        state = jax.device_put(make_state(shape, dtype), device)
        floor_s = _PROBE_OVER_DISPATCH * dispatch_fence_s()
        while True:
            lo = jax.jit(functools.partial(burn, iters=n_lo))
            hi = jax.jit(functools.partial(burn, iters=n_hi))
            lo(state).block_until_ready()  # compile
            hi(state).block_until_ready()
            t_lo = min(time_callable(lo, state, reps=5))
            t_hi = min(time_callable(hi, state, reps=5))
            if t_lo >= floor_s or n_hi >= _MAX_PROBE_ITERS:
                break
            n_lo, n_hi = 4 * n_lo, 4 * n_hi
        ns = (t_hi - t_lo) * 1e9 / (n_hi - n_lo)
        if ns <= 0:  # timer noise on very fast devices: widen the gap
            t_hi = min(time_callable(
                jax.jit(functools.partial(burn, iters=n_hi * 8)), state, reps=3))
            ns = max((t_hi - t_lo) * 1e9 / (n_hi * 8 - n_lo), 1.0)
    return BurnCalibration(ns_per_iter=ns, shape=shape, dtype=str(dtype_name),
                           device_kind=device.device_kind)


_CAL_CACHE: dict = {}

# part of every persisted entry's name: a calibration made by an older
# method must not answer (bump when ``_calibrate_on_device`` changes
# what it measures; 2 = probes grown past the dispatch floor)
_CAL_METHOD = 2


def _persist_name(key) -> str:
    return ":".join(map(str, (*key, f"m{_CAL_METHOD}")))


def _persist_path():
    """Calibration rides in the same cache dir as compiled executables
    (core/executor.enable_persistent_cache): a warm sweep re-run should
    skip the ~2.4 s calibration the same way it skips recompiles.
    Returns None when no entry point has placed the cache."""
    d = jax.config.jax_compilation_cache_dir
    if not d or not jax.config.jax_enable_compilation_cache:
        return None
    from pathlib import Path
    return Path(d) / "burn_calibration.json"


def _load_persisted(path, key) -> BurnCalibration | None:
    import json
    # TypeError included: a cache file holding valid JSON that is not a
    # dict (hand edit, torn write) must fall back to measuring, not
    # crash every run until someone deletes the file
    try:
        entry = json.loads(path.read_text())[_persist_name(key)]
        return BurnCalibration(ns_per_iter=float(entry), shape=key[0],
                               dtype=key[1], device_kind=key[2])
    except (OSError, KeyError, TypeError, ValueError):
        return None


def _store_persisted(path, key, cal: BurnCalibration) -> None:
    import json
    import os
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        try:
            data = json.loads(path.read_text())
        except (OSError, ValueError):
            data = {}
        if not isinstance(data, dict):
            data = {}
        data[_persist_name(key)] = cal.ns_per_iter
        # per-process + random tmp name: id() repeats across processes
        # (same heap layout), and two concurrent sweep points sharing a
        # tmp path could rename a torn file into place
        tmp = path.with_suffix(
            f".{os.getpid()}-{os.urandom(4).hex()}.tmp")
        tmp.write_text(json.dumps(data))
        tmp.replace(path)  # atomic: readers never see a torn file
    except OSError:
        pass  # persistence is an optimization, never a failure


def calibrate(shape=DEFAULT_SHAPE, dtype=DEFAULT_DTYPE,
              device=None) -> BurnCalibration:
    """Measure ns/iteration of the burn chain on the current default device.
    Differenced between two trip counts so dispatch/compile overheads cancel
    (the same discipline as the reference's warm-up skipping, reference
    cpp/utils.hpp:121-123).  Cached in-process per (shape, dtype, device
    kind) — one ``build()`` per grid point must not re-pay it — and
    persisted beside the compile cache, where one is in use, so re-runs
    start warm."""
    device = device or jax.devices()[0]
    key = (tuple(shape), jnp.dtype(dtype).name, device.device_kind)
    if key not in _CAL_CACHE:
        persist = _persist_path()
        cal = _load_persisted(persist, key) if persist else None
        if cal is None:
            cal = _calibrate_on_device(tuple(shape), jnp.dtype(dtype).name,
                                       device, 64, 256)
            if persist:
                _store_persisted(persist, key, cal)
        _CAL_CACHE[key] = cal
    return _CAL_CACHE[key]
