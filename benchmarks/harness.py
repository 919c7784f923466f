"""What every runner shares: the manifest and its files found by name,
the device check, the compile cache and its counter, the traced
sub-window, and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent


class BenchError(Exception):
    """The run cannot give a result (exit non-zero, print none)."""


def process_age_s() -> float:
    """Seconds since this process started, by the kernel's clock."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED_AT


_IMPORTED_AT = time.perf_counter()


def host_pressure() -> dict:
    """What the host took from this run so far: seconds the hypervisor
    gave this machine's cores to others (``steal``, all cores summed)
    and how often this process was switched out against its will.  A
    one-chip machine shares its host; a stall that the host's scheduler
    caused shows here (the stalls of PR 23 did not: PERF.md)."""
    import resource
    steal = 0.0
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        steal = int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        pass
    return {"steal_s": steal,
            "involuntary_switches":
                resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw}


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file, as it is run
    workload: dict        # benchmarks/workloads/<cell>.json
    traffic: dict         # benchmarks/traffic/<traffic>.json
    end_to_end: list      # metric entries this cell reports
    per_layer: list


def metric_cells(entry: dict, manifest: dict, moved=None) -> set:
    """The cells a metric entry is reported in."""
    if "workloads" in entry:
        return set(entry["workloads"])
    if moved is not None:
        return metric_cells(moved, manifest)
    return {w["name"] for w in manifest["workloads"]}


def load_cell(name: str, root: Path = ROOT) -> Cell:
    manifest = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in manifest["configs"] if c["name"] == w["config"])
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    bench = root / manifest["paths"][0]
    return Cell(
        name=name, chips=w["chips"], config_name=w["config"],
        config=load_json(root / cfg["file"]),
        workload=load_json(bench / "workloads" / f"{name}.json"),
        traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in manifest["end_to_end"]
                    if name in metric_cells(m, manifest)],
        per_layer=[m for m in manifest["per_layer"]
                   if name in metric_cells(m, manifest, e2e[m["moves"]])])


def rehearsal(cell: Cell) -> Cell:
    """The cell at its tiny CPU sizes: the ``rehearsal`` blocks of its
    files laid over them."""
    def over(d):
        return {**d, **d.get("rehearsal", {})}
    return dataclasses.replace(cell, config=over(cell.config),
                               workload=over(cell.workload),
                               traffic=over(cell.traffic))


def device_block(chips: int, rehearse: bool) -> dict:
    """The devices as jax reports them; refuses what cannot measure."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" and not rehearse:
        raise BenchError(f"jax found no TPU (devices: {dev}); a cell is "
                         f"measured on the chip only")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chip(s), jax has "
                         f"{len(devs)}")
    if not rehearse:
        peaks(dev["kind"])
    return dev


def peaks(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json "
                         f"({sorted(table)}): no peak, no roofline")
    return table[kind]


def _peak(stats: dict) -> int:
    """The TPU runtime keeps a loaded program's temporaries as a
    reservation beside the arrays in use, so the peak is both."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def memory_stats(chips: int) -> dict:
    """jax's allocator counters of the fullest chip used."""
    import jax
    return max((d.memory_stats() or {} for d in jax.devices()[:chips]),
               key=_peak)


def memory_peak_bytes(chips: int) -> int:
    return _peak(memory_stats(chips))


class CacheCounter:
    """jax's persistent-cache events of this process."""

    def __init__(self):
        self.counts = {"hits": 0, "misses": 0}

    def __call__(self, event: str, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            kind = event.rsplit("_", 1)[1]
            if kind in self.counts:
                self.counts[kind] += 1

    def install(self):
        import jax
        jax.monitoring.register_event_listener(self)
        return self


def enable_cache() -> str:
    """The program's one place for the compile cache: the directory the
    environment names, else ``<checkout>/.jax_cache``."""
    from dlnetbench_tpu.core.executor import enable_persistent_cache
    return enable_persistent_cache()


class TraceWindow:
    """A profiler trace over part of the measured window, written under
    the checkout's scratch directory and removed after it is read
    (``keep``: left for ``trace_dump.py`` to describe)."""

    def __init__(self, enabled: bool, seconds: float, keep: bool = False):
        self.enabled = enabled
        self.seconds = seconds
        self.keep = keep
        self.dir = ROOT / ".bench_trace" / f"pid{os.getpid()}"
        self.active = False
        self.started_at = None
        self.done = False

    def maybe_start(self, elapsed_s: float, after_s: float) -> None:
        if (self.enabled and not self.active and not self.done
                and elapsed_s >= after_s):
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True)
            jax.profiler.start_trace(str(self.dir))
            self.active = True
            self.started_at = time.perf_counter()
            self._window = jax.profiler.TraceAnnotation("bench_window")
            self._window.__enter__()

    def maybe_stop(self, force: bool = False) -> None:
        if self.active and (force or time.perf_counter()
                            - self.started_at >= self.seconds):
            import jax
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False
            self.done = True

    def annotate(self, name: str):
        if self.active:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def load(self):
        """The reduced trace, or None when nothing was traced."""
        if not self.done:
            return None
        from benchmarks import trace_reduce
        try:
            return trace_reduce.load_xplane(
                trace_reduce.find_xplane(str(self.dir)))
        finally:
            if not self.keep:
                shutil.rmtree(self.dir, ignore_errors=True)


def runner_for(kind: str):
    return importlib.import_module(f"benchmarks.runners.{kind}")


def read_layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric of the cell through its own reader.  A
    reader that finds nothing to read returns None; ``BENCHMARK.json``
    lists the cell for the metric, so that is a fault of the run (a
    kernel or program renamed or gone), never a shorter line."""
    out = {}
    for entry in cell.per_layer:
        spec = load_json(HERE / "layer_metrics" / f"{entry['name']}.json")
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['reader']}")
        value = reader.read(ctx, spec.get("params", {}))
        if value is None:
            raise BenchError(
                f"per-layer metric {entry['name']!r} found nothing to "
                f"read in {cell.name} (reader {spec['reader']}, "
                f"{spec.get('params', {})})")
        out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out
