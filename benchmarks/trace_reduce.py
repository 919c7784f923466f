"""From a profiler trace to the device's numbers.

Two halves.  ``load_xplane`` reads the ``.xplane.pb`` that
``jax.profiler.trace`` writes (with nothing but jax) into plain event
lists.  The rest are pure functions on ``(name, start_s, dur_s)`` tuples
and are checked against the hand-built list in ``trace_fixture.json``:
the busy union (overlapping and nested intervals counted once), the idle
share, time by name, the top operations and the longest idle gaps named
by the host annotation that covers them.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals) -> list:
    """Sorted disjoint ``(start, end)`` covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(events, t0: float, t1: float) -> list:
    """Events cut to the window [t0, t1]; those outside it dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_seconds(events) -> float:
    return sum(e - s for s, e in union((s, s + d) for _, s, d in events))


def idle_share(events, t0: float, t1: float) -> float:
    """1 - busy/window over [t0, t1]."""
    return 1.0 - busy_seconds(clip(events, t0, t1)) / (t1 - t0)


def time_by_name(events) -> dict:
    """Union-busy seconds of each name (a name's nested or repeated
    intervals are counted once where they overlap)."""
    by: dict = {}
    for name, s, d in events:
        by.setdefault(name, []).append((s, s + d))
    return {n: sum(e - s for s, e in union(iv)) for n, iv in by.items()}


def top_ops(events, n: int = 10) -> list:
    """[[name, seconds], ...] of the ``n`` names with most device time."""
    by = time_by_name(events)
    return [[k, by[k]] for k in sorted(by, key=by.get, reverse=True)[:n]]


def gaps(events, t0: float, t1: float) -> list:
    """Idle ``(start, end)`` intervals of [t0, t1]."""
    out, at = [], t0
    for s, e in union((s, s + d) for _, s, d in clip(events, t0, t1)):
        if s > at:
            out.append((at, s))
        at = e
    if t1 > at:
        out.append((at, t1))
    return out


def name_gap(gap, host_events, default: str) -> str:
    """The host annotation that covers most of ``gap``, else
    ``default``."""
    g0, g1 = gap
    best, cover = default, 0.0
    for name, s, d in host_events:
        c = min(g1, s + d) - max(g0, s)
        if c > cover:
            best, cover = name, c
    return best


def idle_gaps(events, host_events, t0: float, t1: float,
              default: str = "unattributed", n: int = 10) -> list:
    """[[what the host was doing, seconds], ...]: idle time summed by
    the annotation that names each gap, longest first."""
    by: dict = {}
    for g in gaps(events, t0, t1):
        k = name_gap(g, host_events, default)
        by[k] = by.get(k, 0.0) + g[1] - g[0]
    return [[k, by[k]] for k in sorted(by, key=by.get, reverse=True)[:n]]


def short_name(name: str, limit: int = 80) -> str:
    """An operation's name as a breakdown shows it.  The TPU trace names
    an operation by its whole HLO line (``%fusion.54 = bf16[4096,51200]
    {...} fusion(...)``): keep the instruction's name, its first result
    shape and, for a Pallas kernel, the fact."""
    m = re.match(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?", name)
    if not m:
        return name[:limit]
    out = m.group(1) + (f" {m.group(2)}" if m.group(2) else "")
    if "tpu_custom_call" in name:
        out += " pallas"
    return out[:limit]


def matching(events, pattern: str) -> list:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


# ------------------------------------------------------------ xplane
def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    """{"devices": {id: {"ops": [...], "modules": [...]}}, "host":
    [...]}: device operations and whole-program executions of each TPU
    plane, and every host-thread event, as ``(name, start_s, dur_s)`` on
    the profiler's one clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key] = [(ev.name, ev.start_ns * 1e-9,
                             ev.duration_ns * 1e-9) for ev in line.events]
            out["devices"][int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    for ev in line.events)
    return out


def describe_xplane(path: str, top: int = 25) -> dict:
    """What a trace holds, for reading one by hand: planes, their lines,
    event counts and the names with most time on each line."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                   for ev in line.events]
            lines.append({"line": line.name, "events": len(evs),
                          "top": top_ops(evs, top)})
        planes.append({"plane": plane.name, "lines": lines})
    return {"path": path, "planes": planes}
