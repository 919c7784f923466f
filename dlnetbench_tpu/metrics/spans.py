"""Host-side span tracing — the observability spine of the harness.

Every emitted number in this repo is a *host* wall-clock measurement of
an async device program; the device side already has a self-describing
trace channel (``jax.profiler`` -> ``metrics/profiling.py``), but the
host side — build, compile, warmup, calibration, fence waits, per-config
sweep points — lived only in session logs.  This module gives the host
side the same artifact-grade story:

* ``span("name", key=value)`` is a context manager timing a region on
  the process-wide monotonic clock (``time.perf_counter``), nestable
  across threads (each thread keeps its own depth stack).
* Tracing is OFF by default and the disabled path is near-zero cost:
  ``span()`` returns a shared no-op singleton — no span object is
  allocated, no clock is read, nothing is recorded.  (A keyword-attrs
  call still builds its kwargs dict, so the hot measurement sites in
  ``utils/timing.py`` additionally gate on ``is_enabled()`` — a timed
  fence window in an untraced run pays nothing at all.)
* ``write_chrome_trace`` exports the collected spans as Chrome-trace
  ("Trace Event Format") complete events and MERGES them with the
  device-op events the JAX profiler emitted for the same run, so ONE
  ``trace.json`` (loadable in Perfetto / chrome://tracing) shows where
  wall-clock went: host track on top (compile vs warmup vs timed vs
  fence), per-device tracks below, collective ops colored by kind via
  ``profiling.classify_op``.

* While a ``jax.profiler`` trace runs, an enabled span is also a
  ``jax.profiler.TraceAnnotation``: it lies on the host line of the same
  ``.xplane.pb`` as the device's operations, on the profiler's one
  clock, its numeric and string attrs as the event's stats.  A gap on
  the device can then be put down to the span that covers it.
* ``SCOPES`` is the fixed vocabulary of ``jax.named_scope`` names the
  model step wears (``scope(name)``); ``core/executor.py`` maps each
  compiled instruction back to one of them (``op_scopes``) and to the
  pass that runs it (``op_phases``: one of ``PHASES``), and a tracer
  keeps the tables of the steps compiled while it was enabled
  (``Tracer.export``).

The tracer is deliberately NOT a per-collective measurement channel —
that is the decomposition harness (proxies/base.py) and the device
trace (metrics/profiling.py).  Spans attribute *phases* of the harness
itself, the layer neither channel covers.
"""
from __future__ import annotations

import json
import threading
import time
from pathlib import Path

# ---------------------------------------------------------------------
# The model step's layers, by name.  Declared here once: the models wear
# them through ``scope()``, the executor's op->scope table and the
# benchmark's per-layer readers take them from here.
SCOPES = ("embed", "attn", "attn.window", "attn.full", "attn.gate",
          "attn.select", "attn.sparse", "mlp",
          "moe.router", "moe.dispatch", "moe.experts", "moe.combine",
          "moe.shared", "ssm", "ssm.scan", "gmu", "linattn", "linattn.rule",
          "conv", "conv.gate", "head_loss", "optimizer")
OTHER_SCOPE = "other"   # an instruction under none of them
# The passes of a train step an instruction can belong to: the forward
# pass, a checkpointed layer's forward run again inside the backward
# pass, and the backward pass itself.
PHASES = ("forward", "recompute", "backward")
NO_PHASE = "none"       # an instruction of none of them (the optimizer)


def scope(name: str):
    """``jax.named_scope`` of one vocabulary name: metadata on the
    operations traced under it, nothing at run time."""
    if name not in SCOPES:
        raise ValueError(f"scope {name!r} is not in spans.SCOPES {SCOPES}")
    import jax
    return jax.named_scope(name)


# ---------------------------------------------------------------------
# Tracer core.

class _NullSpan:
    """Shared disabled-mode span: entering/exiting does nothing and the
    module hands out this one instance for every disabled ``span()``
    call — the per-span allocation count when disabled is zero."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """One live span: records start on __enter__, appends a finished
    record to its tracer on __exit__.  Exceptions propagate (the span
    still closes, marked ``error``) so a failing phase stays visible in
    the timeline instead of vanishing with its context."""
    __slots__ = ("_tracer", "name", "attrs", "_t0", "_depth", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict | None):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self._depth = self._tracer._push(self)
        # the same region on the profiler's clock (a no-op flag test
        # while no profiler trace runs)
        self._ann = self._tracer._annotation(
            self.name, **{k: v for k, v in (self.attrs or {}).items()
                          if isinstance(v, (int, float, str))})
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        tr = self._tracer
        tr._pop()
        if exc_type is not None:
            attrs = dict(self.attrs or {})
            attrs["error"] = exc_type.__name__
            self.attrs = attrs
        tr._record(self.name, self._t0, t1, self._depth, self.attrs)
        return False


class Tracer:
    """Collects finished spans as plain dicts (name, ts/dur in us on the
    tracer's own origin, thread id, nesting depth, attrs).  Thread-safe;
    one tracer per measured run is the intended shape."""

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.origin = time.perf_counter()
        self.spans: list[dict] = []
        # module name -> {instruction name: scope} of the steps compiled
        # while this tracer was current (core/executor.py registers them)
        self.op_scopes: dict[str, dict[str, str]] = {}
        # module name -> {instruction name: phase} of the same steps
        self.op_phases: dict[str, dict[str, str]] = {}
        self._lock = threading.Lock()
        # tid -> stack of OPEN spans, readable from other threads: the
        # watchdog's stall handler fires on a Timer thread and must see
        # where the measuring thread currently is (the span stack is
        # the postmortem breadcrumb the stall message dumps)
        self._active: dict[int, list[_Span]] = {}

    # -- called by _Span --------------------------------------------
    def _push(self, span: _Span) -> int:
        with self._lock:
            stack = self._active.setdefault(threading.get_ident(), [])
            stack.append(span)
            return len(stack) - 1

    def _pop(self) -> None:
        with self._lock:
            stack = self._active.get(threading.get_ident())
            if stack:
                stack.pop()

    def active_stacks(self) -> dict[int, list[str]]:
        """Snapshot of every thread's open-span stack (outermost first)."""
        with self._lock:
            return {tid: [span.name for span in stack]
                    for tid, stack in self._active.items() if stack}

    def _record(self, name: str, t0: float, t1: float, depth: int,
                attrs: dict | None) -> None:
        rec = {
            "name": name,
            "ts_us": (t0 - self.origin) * 1e6,
            "dur_us": (t1 - t0) * 1e6,
            "tid": threading.get_ident(),
            "depth": depth,
        }
        if attrs:
            rec["attrs"] = attrs
        with self._lock:
            self.spans.append(rec)

    # -- public ------------------------------------------------------
    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs or None)

    def mark(self, name: str, **attrs) -> None:
        """A fact with no duration (a choice made while a step is
        traced): one more entry of the list ``name`` in the attrs of
        the innermost span open on this thread, a build's ``compile``
        as a rule; a span of that name where none is open."""
        with self._lock:
            stack = self._active.get(threading.get_ident())
            if stack:
                span = stack[-1]
                if span.attrs is None:
                    span.attrs = {}
                span.attrs.setdefault(name, []).append(attrs)
                return
        now = time.perf_counter()
        self._record(name, now, now, 0, attrs)

    def register_op_scopes(self, module: str, table: dict,
                           phases: dict | None = None) -> None:
        with self._lock:
            self.op_scopes[module] = table
            if phases is not None:
                self.op_phases[module] = phases

    def export(self) -> dict:
        """What a run's reader takes at its end, as plain data: the
        finished spans, the op->scope tables and the op->phase tables."""
        with self._lock:
            return {"spans": [dict(s) for s in self.spans],
                    "op_scopes": {m: dict(t)
                                  for m, t in self.op_scopes.items()},
                    "op_phases": {m: dict(t)
                                  for m, t in self.op_phases.items()}}


# Module-level current tracer.  ``None`` means disabled — the common
# case — and the ``span()`` fast path below is one global load, one
# ``is None`` test, one return of the shared singleton.
_TRACER: Tracer | None = None


def enable() -> Tracer:
    """Install (and return) a fresh tracer as the process tracer.
    Subsequent ``span()`` calls record into it."""
    global _TRACER
    _TRACER = Tracer()
    return _TRACER


def disable() -> Tracer | None:
    """Stop tracing; returns the tracer that was active (with its
    collected spans) so callers can export after the measured region."""
    global _TRACER
    t, _TRACER = _TRACER, None
    return t


def current() -> Tracer | None:
    return _TRACER


def is_enabled() -> bool:
    return _TRACER is not None


def span(name: str, **attrs):
    """Time a region when tracing is enabled; free when it is not."""
    t = _TRACER
    if t is None:
        return NULL_SPAN
    return t.span(name, **attrs)


def mark(name: str, **attrs) -> None:
    """``Tracer.mark`` on the process tracer; free when there is none."""
    t = _TRACER
    if t is not None:
        t.mark(name, **attrs)


def active_stacks() -> dict[int, list[str]]:
    """Every thread's currently-open span stack ({} when tracing is
    off) — the watchdog's stall-time breadcrumb channel."""
    t = _TRACER
    return t.active_stacks() if t is not None else {}


# ---------------------------------------------------------------------
# Chrome-trace / Perfetto export.

HOST_PID = 0          # host spans live on one process track
_DEVICE_PID_BASE = 1  # device events keep their own pids shifted up

# chrome://tracing reserved color names per collective kind — Perfetto
# falls back to hashing the name, so the kind also rides in args.kind
_KIND_CNAME = {
    "allreduce": "thread_state_running",
    "allgather": "thread_state_runnable",
    "reduce_scatter": "thread_state_iowait",
    "alltoall": "rail_animation",
    "permute": "rail_response",
    "send_recv": "rail_idle",
}


def host_events(tracer: Tracer, *, pid: int = HOST_PID) -> list[dict]:
    """Tracer spans -> Chrome complete ('X') events on the host track."""
    events: list[dict] = [
        {"ph": "M", "pid": pid, "name": "process_name",
         "args": {"name": "host (harness phases)"}},
        {"ph": "M", "pid": pid, "name": "process_sort_index",
         "args": {"sort_index": -1}},  # host track above device tracks
    ]
    for s in tracer.spans:
        ev = {
            "ph": "X",
            "pid": pid,
            "tid": s["tid"],
            "name": s["name"],
            "ts": s["ts_us"],
            "dur": s["dur_us"],
        }
        args = dict(s.get("attrs") or {})
        args["depth"] = s["depth"]
        ev["args"] = args
        events.append(ev)
    return events


def _colored_device_events(device_events: list[dict],
                           align_to_us: float | None) -> list[dict]:
    """Shift device events onto the host timeline and color collectives.

    The device trace's timestamps are on the profiler's own epoch; only
    their relative layout is meaningful here, so the earliest device
    event is aligned to ``align_to_us`` on the host clock (the start of
    the span that bracketed the profiled iteration when the caller
    knows it, else 0).  Pids are shifted past the host pid so the
    tracks never collide."""
    from dlnetbench_tpu.metrics.profiling import classify_op

    if not device_events:
        return []
    t_min = min(float(e.get("ts", 0.0)) for e in device_events)
    shift = (align_to_us if align_to_us is not None else 0.0) - t_min
    out = []
    for e in device_events:
        ev = dict(e)
        ev.pop("_thread", None)  # loader annotation, not trace data
        ev["ts"] = float(e.get("ts", 0.0)) + shift
        ev["pid"] = int(e.get("pid", 0)) + _DEVICE_PID_BASE
        kind = classify_op(str(e.get("name", "")))
        if kind is not None:
            ev["cname"] = _KIND_CNAME[kind]
            args = dict(ev.get("args") or {})
            args["kind"] = kind
            ev["args"] = args
        out.append(ev)
    return out


def write_chrome_trace(path: str | Path, tracer: Tracer | None,
                       device_events: list[dict] | None = None,
                       align_span: str | None = "profile",
                       extra_events: list[dict] | None = None) -> dict:
    """Write ONE merged Chrome trace: host spans + device-op events.

    ``align_span`` names the host span whose start the earliest device
    event is pinned to (the span that wrapped the profiled iteration);
    when absent the device timeline starts at host ts 0.
    ``extra_events`` are appended verbatim — the attribution counter
    tracks and record-derived per-rank tracks ride this channel.
    Returns the trace dict that was written (callers/tests can inspect
    it without re-reading the file)."""
    events: list[dict] = []
    align_to = None
    if tracer is not None:
        events.extend(host_events(tracer))
        if align_span is not None:
            for s in tracer.spans:
                if s["name"] == align_span:
                    align_to = s["ts_us"]
                    break
    if device_events:
        events.extend(_colored_device_events(device_events, align_to))
    if extra_events:
        events.extend(extra_events)
    trace = {"traceEvents": events, "displayTimeUnit": "ms"}
    path = Path(path)
    with open(path, "w") as f:
        json.dump(trace, f)
    return trace


# ---------------------------------------------------------------------
# Record-derived tracks: per-rank timers + attribution counters.
#
# The merged host+device timeline above covers the python tier, whose
# process runs the tracer.  Native-tier runs emit only their JSON
# record — but that record carries everything a timeline needs:
# per-rank per-run timer samples, their band summaries, and (post
# merge) the attribution block.  These exporters turn a record into
# Chrome/Perfetto counter + duration tracks so ``--trace-out`` (via
# ``metrics.merge --trace-out``) is useful for native runs too.

ATTRIBUTION_PID = 50       # attribution counter track
TELEMETRY_PID = 60         # flight-recorder counter tracks (ISSUE 14)
_RECORD_PID_BASE = 100     # per-rank record tracks start here


def attribution_counter_events(attr: dict, *, dur_us: float = 1.0,
                               pid: int = ATTRIBUTION_PID) -> list[dict]:
    """Counter tracks for an ``attribution`` block's fractions: one
    Chrome 'C' series per resource over [0, dur_us], so Perfetto shows
    the compute/hbm/comm/host split next to the timelines it explains.
    The ``bound`` verdict rides the track name."""
    fractions = (attr or {}).get("fractions")
    if not fractions:
        return []
    name = f"attribution (bound: {attr.get('bound', '?')})"
    events: list[dict] = [
        {"ph": "M", "pid": pid, "name": "process_name",
         "args": {"name": name}},
        {"ph": "M", "pid": pid, "name": "process_sort_index",
         "args": {"sort_index": 40}},
    ]
    for ts in (0.0, max(dur_us, 1.0)):
        events.append({"ph": "C", "pid": pid, "name": "fractions",
                       "ts": ts, "args": {k: round(float(v), 4)
                                          for k, v in fractions.items()}})
    return events


def telemetry_counter_events(block: dict, anomalies: dict | None = None,
                             *, pid: int = TELEMETRY_PID) -> list[dict]:
    """Flight-recorder samples -> Perfetto counter tracks: every
    numeric field of the telemetry samples becomes one 'C' series over
    the samples' own ``t_s`` clock (us on the trace timeline), and each
    anomaly event lands as a global instant ('i', scope process) at its
    trigger time, named by its trigger kind.  Accepts a record's
    ``global.telemetry`` block (tail samples), a flight dump payload
    (full ring), or a live ``FlightRecorder.telemetry_block()``."""
    samples = (block or {}).get("samples") or (block or {}).get("last") \
        or []
    events: list[dict] = []
    if samples:
        events += [
            {"ph": "M", "pid": pid, "name": "process_name",
             "args": {"name": "telemetry (flight recorder)"}},
            {"ph": "M", "pid": pid, "name": "process_sort_index",
             "args": {"sort_index": 45}},
        ]
        for s in samples:
            ts = float(s.get("t_s", 0.0)) * 1e6
            for k, v in s.items():
                if k in ("t_s", "source", "step") \
                        or not isinstance(v, (int, float)):
                    continue
                events.append({"ph": "C", "pid": pid, "name": k,
                               "ts": ts, "args": {"value": float(v)}})
    for ev in ((anomalies or {}).get("events") or []):
        events.append({"ph": "i", "pid": pid, "tid": 0, "s": "p",
                       "name": f"anomaly: {ev.get('trigger', '?')}",
                       "ts": float(ev.get("t_s", 0.0)) * 1e6,
                       "args": {k: v for k, v in ev.items()
                                if k != "detail"}})
    return events


def record_track_events(record: dict,
                        pid_base: int = _RECORD_PID_BASE) -> list[dict]:
    """Per-rank tracks from a run record (either tier): each rank
    becomes one process track whose 'runtimes' samples lay out runs as
    duration events end-to-end, every other timer rides as a counter
    series sampled per run, and the schema-v2 band summaries annotate
    the track as instant events (args = the {value, best, band, n}
    dict).  The record's attribution block (stamped by emit, or
    mirrored at merge time for native records) is appended as a counter
    track spanning the laid-out run window."""
    events: list[dict] = []
    rows = record.get("ranks") or []
    max_end = 0.0
    for i, row in enumerate(rows):
        pid = pid_base + i
        rank = row.get("rank", i)
        events.append({"ph": "M", "pid": pid, "name": "process_name",
                       "args": {"name": f"rank {rank} "
                                        f"({record.get('section', '?')})"}})
        events.append({"ph": "M", "pid": pid, "name": "process_sort_index",
                       "args": {"sort_index": 50 + i}})
        runtimes = [float(v) for v in row.get("runtimes") or []]
        # runs laid out end-to-end on the rank's own clock: ts of run j
        # is the sum of runs 0..j-1 (wall-adjacent, gaps unknowable)
        starts = []
        t = 0.0
        for v in runtimes:
            starts.append(t)
            t += v
        max_end = max(max_end, t)
        for j, (ts, dur) in enumerate(zip(starts, runtimes)):
            events.append({"ph": "X", "pid": pid, "tid": 0,
                           "name": f"run {j}", "ts": ts, "dur": dur,
                           "args": {"us": dur}})
        for timer, vals in row.items():
            # skip structural list fields (chip coords are not a timer
            # series) alongside the runtimes already laid out above
            if timer in ("runtimes", "coords") or not isinstance(vals,
                                                                 list):
                continue
            for j, v in enumerate(vals):
                if j >= len(starts):
                    break
                try:
                    fv = float(v)
                except (TypeError, ValueError):
                    break
                events.append({"ph": "C", "pid": pid, "name": timer,
                               "ts": starts[j], "args": {"value": fv}})
        for timer, summary in (row.get("summary") or {}).items():
            events.append({"ph": "i", "pid": pid, "tid": 0, "s": "p",
                           "name": f"{timer} band", "ts": 0.0,
                           "args": dict(summary)})
    attr = (record.get("global") or {}).get("attribution")
    if attr:
        events.extend(attribution_counter_events(attr, dur_us=max_end))
    tele = (record.get("global") or {}).get("telemetry")
    anom = (record.get("global") or {}).get("anomalies")
    if tele or anom:
        events.extend(telemetry_counter_events(tele or {}, anom))
    return events
