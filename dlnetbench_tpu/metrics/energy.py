"""Energy sampling — the rebuild's ``power_profiler`` equivalent.

The reference optionally links a vendor power profiler
(``-DPROXY_ENERGY_PROFILING -lpower_profiler``, reference
Makefile.flags.mk:119-124) sampling at ``POWER_SAMPLING_RATE_MS 5``
(dp.cpp:67) and emits per-rank ``energy_consumed`` arrays
(plots/parser.py:172) that feed the runtime-energy Pareto analysis.

TPU chips expose no public per-chip energy counter through JAX/PJRT, so
this is a *host-side* pluggable sampler chain, best available source wins:

  * RaplSampler   — Linux RAPL cumulative counters
                    (/sys/class/powercap/intel-rapl*/energy_uj) with
                    wraparound handling.  Real measured joules for the
                    CPU-mesh runs and the host share of TPU runs.
  * HwmonSampler  — /sys/class/hwmon power_input (uW) integrated by a
                    5 ms background thread (the reference's sampling rate).
  * none          — energy is simply absent from the emitted record (the
                    reference behaves the same when built without the
                    profiler).

``run_proxy`` brackets each timed run with ``read_joules()`` and emits the
per-run deltas as ``energy_consumed``, keeping the reference's record
schema so the Pareto plots work unchanged.

Continuous telemetry (ISSUE 14): the same per-chain deltas also feed
the flight-recorder ring per step (``energy_j`` on each ``proxy``
sample — proxies/base.py gates on ``telemetry.is_enabled()``), so
anomaly flight dumps show the energy trend into the event and the
critical-path report carries a per-rank energy axis
(``analysis/critical_path.py`` sums the ``energy_consumed`` timer over
the analysis window) wherever a sampler exists.
"""
from __future__ import annotations

import glob
import threading
import time

POWER_SAMPLING_RATE_MS = 5   # reference dp.cpp:67


class TpuChipSampler:
    """Best-effort TPU *chip* energy counter.

    TPU chips expose no public per-chip energy counter through JAX/PJRT
    today, but that is a probed fact, not an assumption — this sampler
    attempts every plausible channel and reports what it found:

      1. PJRT/libtpu device attributes via jax: any attribute whose
         name mentions energy/power on a TPU device (none exist in
         current libtpu releases; ``probe_notes`` records the attribute
         names actually seen so a future libtpu that adds one is
         noticed, not silently missed).
      2. sysfs hwmon: a device whose ``name`` mentions tpu, with
         cumulative ``energy*_input`` channels (microjoules).
      3. the accel class: ``/sys/class/accel/accel*/device/energy_uj``.

    When a counter exists the record's ``energy_source`` reads ``tpu``
    and the figures are chip-side.  When every probe misses (the
    current state on Cloud TPU images — docs/PERF.md documents the dead
    end), the chain falls through to the HOST samplers below, whose
    axes are host-side by construction and labeled so."""

    def __init__(self, hwmon_root: str = "/sys/class/hwmon",
                 accel_root: str = "/sys/class/accel"):
        self.probe_notes: list[str] = []
        self._files: list[str] = []
        # 1. PJRT device attributes (only meaningful on a TPU backend;
        # cheap and exception-safe elsewhere)
        try:
            import jax
            devs = jax.devices()
            if devs and devs[0].platform == "tpu":
                attrs = [a for a in dir(devs[0]) if not a.startswith("_")]
                hits = [a for a in attrs
                        if "energy" in a.lower() or "power" in a.lower()]
                if hits:
                    self.probe_notes.append(
                        f"pjrt device attributes matched: {hits}")
                else:
                    self.probe_notes.append(
                        f"pjrt tpu device exposes no energy/power "
                        f"attribute ({len(attrs)} attributes probed)")
        except Exception:
            self.probe_notes.append("jax/pjrt probe unavailable")
        # 2. tpu-named hwmon with cumulative energy channels
        for path in sorted(glob.glob(f"{hwmon_root}/hwmon*")):
            try:
                with open(f"{path}/name") as f:
                    name = f.read().strip()
            except OSError:
                continue
            if "tpu" not in name.lower():
                continue
            chans = sorted(glob.glob(f"{path}/energy*_input"))
            if chans:
                self._files.extend(chans)
                self.probe_notes.append(
                    f"hwmon {name}: {len(chans)} energy channel(s)")
            else:
                self.probe_notes.append(
                    f"hwmon {name}: present but no energy*_input")
        # 3. accel-class cumulative counters
        for path in sorted(glob.glob(f"{accel_root}/accel*/device/energy_uj")):
            self._files.append(path)
            self.probe_notes.append(f"accel counter: {path}")
        if not self._files:
            self.probe_notes.append("no TPU chip energy counter found")
        self.source = "tpu"
        self._last: list[float] = []
        self._acc = 0.0
        if self._files:
            self._last = [self._read_raw(i)
                          for i in range(len(self._files))]

    @property
    def available(self) -> bool:
        return bool(self._files)

    def _read_raw(self, i: int) -> float:
        with open(self._files[i]) as f:
            return float(f.read())

    def read_joules(self) -> float:
        """Monotonic cumulative joules summed over chip counters
        (counters are cumulative uJ; a wrapped/reset counter drops that
        sample rather than going backwards)."""
        for i in range(len(self._files)):
            cur = self._read_raw(i)
            delta = cur - self._last[i]
            if delta > 0:
                self._acc += delta
            self._last[i] = cur
        return self._acc / 1e6


class RaplSampler:
    """Cumulative joules from Linux RAPL package domains."""

    def __init__(self, root: str = "/sys/class/powercap"):
        packages, psys = [], []
        for path in sorted(glob.glob(f"{root}/intel-rapl:*")):
            # top-level zones only: subzones (intel-rapl:0:0) are already
            # included in their parent's counter
            if path.rsplit("/", 1)[-1].count(":") != 1:
                continue
            try:
                with open(f"{path}/energy_uj") as f:
                    float(f.read())
                try:
                    with open(f"{path}/name") as f:
                        zone = f.read().strip()
                except OSError:
                    zone = "package-?"
                try:
                    with open(f"{path}/max_energy_range_uj") as f:
                        rng = float(f.read())
                except OSError:
                    rng = 0.0   # unknown range: drop wrapped samples
                entry = (f"{path}/energy_uj", rng)
                # psys already contains the packages — never sum both
                (psys if zone == "psys" else packages).append(entry)
            except (OSError, ValueError):
                continue
        self._domains = psys if psys else packages
        self._last: list[float] = []
        self._acc = 0.0
        if self._domains:
            self._last = [self._read_raw(i)
                          for i in range(len(self._domains))]

    @property
    def available(self) -> bool:
        return bool(self._domains)

    def _read_raw(self, i: int) -> float:
        with open(self._domains[i][0]) as f:
            return float(f.read())

    def read_joules(self) -> float:
        """Monotonic cumulative joules across packages (wraparound-safe)."""
        for i, (_, rng) in enumerate(self._domains):
            cur = self._read_raw(i)
            delta = cur - self._last[i]
            if delta < 0:  # counter wrapped; drop the sample if the
                delta = delta + rng if rng > 0 else 0.0  # range is unknown
            self._acc += delta
            self._last[i] = cur
        return self._acc / 1e6


class HwmonSampler:
    """Integrate instantaneous /sys/class/hwmon power (uW) in a background
    thread at the reference's 5 ms sampling period."""

    def __init__(self, root: str = "/sys/class/hwmon"):
        # channels from ONE hwmon device only — summing across devices
        # double-counts when aggregate (battery/ACPI) and component (CPU
        # package) sensors coexist.  DLNB_HWMON_DEVICE selects by name.
        import os
        want = os.environ.get("DLNB_HWMON_DEVICE", "")
        by_dev: dict[str, list[str]] = {}
        names: dict[str, str] = {}
        for path in sorted(glob.glob(f"{root}/hwmon*/power*_input")):
            dev = path.rsplit("/", 2)[-2]
            try:
                with open(path) as f:
                    float(f.read())
                by_dev.setdefault(dev, []).append(path)
                try:
                    with open(f"{root}/{dev}/name") as f:
                        names[dev] = f.read().strip()
                except OSError:
                    names[dev] = dev
            except (OSError, ValueError):
                continue
        if want:
            # explicit selection: no match means unavailable, never a
            # silent fallback to some other sensor
            chosen = next((d for d, n in names.items() if want in n), None)
            if chosen is None and by_dev:
                import sys
                print(f"[energy] DLNB_HWMON_DEVICE={want!r} matches none of "
                      f"{sorted(names.values())}; hwmon sampling disabled",
                      file=sys.stderr)
        else:
            # unconfigured: prefer CPU-package-like sensors — the
            # alphabetically-first device could be a battery, NVMe or
            # wifi sensor, silently attributing energy to the wrong part
            preferred = ("cpu", "package", "core", "soc", "rapl")
            chosen = next((d for d in sorted(by_dev)
                           if any(p in names[d].lower() for p in preferred)),
                          next(iter(sorted(by_dev)), None))
        self._inputs = by_dev.get(chosen, []) if chosen else []
        # surfaced in the emitted record (energy_source) so a
        # misattributed sensor is visible, not silent
        self.source = f"hwmon:{names[chosen]}" if self._inputs else ""
        self._joules = 0.0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    @property
    def available(self) -> bool:
        return bool(self._inputs)

    def _ensure_running(self):
        """Lazy-start (or restart after close) the integration thread —
        the 5 ms poller only spins while a measurement is in progress."""
        if self._inputs and (self._thread is None
                             or not self._thread.is_alive()):
            self._stop.clear()
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    def _loop(self):
        prev = time.monotonic()
        while not self._stop.is_set():
            time.sleep(POWER_SAMPLING_RATE_MS / 1e3)
            now = time.monotonic()
            watts = 0.0
            for path in self._inputs:
                try:
                    with open(path) as f:
                        watts += float(f.read()) / 1e6
                except (OSError, ValueError):
                    continue
            with self._lock:
                self._joules += watts * (now - prev)
            prev = now

    def read_joules(self) -> float:
        self._ensure_running()
        with self._lock:
            return self._joules

    def close(self):
        """Stop the integration thread; a later read_joules restarts it.
        Joins before returning so a read that follows immediately sees a
        dead thread and restarts cleanly (otherwise it could observe the
        stopping-but-alive thread, skip the restart, and integrate
        nothing for the whole next measured phase)."""
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=1.0)


_CACHED = None
_PROBED = False


def detect_sampler():
    """Best available energy source, or None (cached per process).
    Chip-side beats host-side: a real TPU chip counter (energy_source
    ``tpu``) wins over host RAPL/hwmon — on current images the TPU
    probe is a documented dead end (docs/PERF.md) and the chain falls
    through to the host counters, whose records say ``rapl``/``hwmon:*``
    so their figures are never read as chip energy."""
    global _CACHED, _PROBED
    if _PROBED:
        return _CACHED
    _PROBED = True
    tpu = TpuChipSampler()
    if tpu.available:
        _CACHED = tpu
        return _CACHED
    rapl = RaplSampler()
    if rapl.available:
        rapl.source = "rapl"
        _CACHED = rapl
        return _CACHED
    hw = HwmonSampler()
    if hw.available:
        # safety net: never leave the poller spinning past process end
        # even if a caller forgets close_sampler()
        import atexit
        atexit.register(hw.close)
        _CACHED = hw
        return _CACHED
    return None


def close_sampler(sampler) -> None:
    """Release a sampler's background resources after a measured phase
    (restartable — the cached sampler keeps working for later runs)."""
    close = getattr(sampler, "close", None)
    if close is not None:
        close()
