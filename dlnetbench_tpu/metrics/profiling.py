"""Profiler-trace-derived per-collective timing.

SURVEY.md §7.3 hard-part 1: the reference times each collective by
bracketing a host-blocking call — on TPU, fencing every collective would
destroy the compute/comm overlap being measured.  The harness therefore
measures by schedule *decomposition* (proxies/base.py); this module is the
independent cross-check channel: run ONE schedule iteration under the JAX
profiler, parse the Chrome-trace it emits, and report per-collective
device-op durations (count / total / mean per collective kind).  The two
channels bound the truth from different sides — decomposition gives
end-to-end exposed cost including queueing, the trace gives pure device
occupancy of each collective op.

Works on every backend (CPU-mesh traces name ops ``psum.N`` etc.; TPU
traces ``all-reduce.N`` / ``collective-permute.N`` / fusions) with no
TensorFlow dependency — the trace.json.gz is stdlib-parseable.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
import tempfile
from pathlib import Path

import jax

# HLO/op-name fragments -> collective kind (lowercased substring match)
COLLECTIVE_PATTERNS: dict[str, tuple[str, ...]] = {
    "allreduce": ("all-reduce", "all_reduce", "allreduce", "psum"),
    "allgather": ("all-gather", "all_gather", "allgather"),
    "reduce_scatter": ("reduce-scatter", "reduce_scatter", "psum-scatter",
                       "psum_scatter"),
    "alltoall": ("all-to-all", "all_to_all", "alltoall"),
    "permute": ("collective-permute", "collective_permute", "ppermute"),
    "send_recv": ("send-done", "recv-done", "send.", "recv."),
}
# reduce_scatter names contain "psum" -> check more specific kinds first
_KIND_ORDER = ("reduce_scatter", "allgather", "alltoall", "permute",
               "send_recv", "allreduce")


def classify_op(name: str) -> str | None:
    """Collective kind for a trace-event name, or None."""
    n = name.lower()
    if n.startswith("end: "):   # async completion markers, not the op
        return None
    for kind in _KIND_ORDER:
        if any(p in n for p in COLLECTIVE_PATTERNS[kind]):
            return kind
    return None


def load_trace_events(trace_dir: str | Path) -> list[dict]:
    """All complete ('X') events from a Chrome trace.

    Accepts either a directory (the layout ``jax.profiler.trace``
    writes — the newest ``*.trace.json.gz`` under it is read) or a
    single trace file, plain ``.json`` or gzipped — which is how the
    merged host+device timelines ``metrics.spans.write_chrome_trace``
    emits round-trip through the same loader.

    Each event is annotated with its lane's thread name (``_thread``,
    resolved from the trace's metadata events) when the trace carries
    one: the occupancy functions below use it to keep host-lane events
    out of the device buckets.  Merged/synthetic traces without thread
    metadata get no annotation."""
    p = Path(trace_dir)
    if p.is_file():
        opener = gzip.open if p.name.endswith(".gz") else open
        with opener(p) as f:
            trace = json.load(f)
    else:
        paths = sorted(glob.glob(f"{trace_dir}/**/*.trace.json.gz",
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no trace.json.gz under {trace_dir}")
        with gzip.open(paths[-1]) as f:
            trace = json.load(f)
    raw = trace.get("traceEvents", [])
    threads = {(e.get("pid"), e.get("tid")): (e.get("args") or {}).get("name")
               for e in raw
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    out = []
    for e in raw:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        t = threads.get((e.get("pid"), e.get("tid")))
        out.append({**e, "_thread": t} if t is not None else e)
    return out


# XLA HLO op names are bare identifiers (fusion.12, copy.3,
# while.1.remat) — no spaces, paths, parens or $-prefixes; this shape
# test drops runtime bookkeeping ("ThreadpoolListener::StartRegion",
# "ThunkExecutor::Execute (wait for completion)") and most host python
# spans ("$profiler.py:226 trace", "PjitFunction(<lambda>)"), which
# share the raw trace's event stream.
_XLA_OP_RE = re.compile(r"^[A-Za-z0-9_.\-]+$")

# SOME host events are bare identifiers too — compiler passes ("dce",
# "algsimp", "backend_compile") whenever a compile lands inside the
# profiled window, argument bookkeeping ("ParseArguments") — but they
# all run on the python dispatch thread, while XLA executor ops run on
# the runtime's own pools (tf_XLAEigen/..., tf_XLATfrtCpuClient/...,
# device lanes on TPU).  The ``_thread`` annotation from
# ``load_trace_events`` separates them where the shape test cannot.
_HOST_THREAD = "python"

# the CPU thunk executor emits a "call" event whose duration encloses
# the child ops it dispatches on the same lane — counting it would
# double-count every child
_WRAPPER_OPS = frozenset({"call"})


def _device_op_name(e: dict) -> str | None:
    """The event's op name when it is device occupancy, else None."""
    if e.get("_thread") == _HOST_THREAD:
        return None
    name = str(e.get("name", ""))
    if name in _WRAPPER_OPS or not _XLA_OP_RE.match(name):
        return None
    return name


def collective_stats(events: list[dict]) -> dict[str, dict]:
    """Per-collective-kind device-occupancy summary (durations in us).

    Ops ``classify_op`` cannot name — fusions, convolutions, copies,
    anything XLA renamed — are NOT dropped: device ops
    (``_device_op_name``) bucket under ``other`` so occupancy
    *fractions* computed from this summary (the attribution engine
    divides a kind's total by the sum over all kinds) are conservative.
    Silently dropping them made every collective look like a larger
    share of device time than it was.  Host-lane events (python spans,
    compiler passes when a compile lands in the window), thunk wrapper
    events, and async completion markers (``end: ...``, which duplicate
    the op they close) stay excluded."""
    by_kind: dict[str, list[float]] = {}
    for e in events:
        name = _device_op_name(e)
        if name is None:
            continue
        kind = classify_op(name) or "other"
        by_kind.setdefault(kind, []).append(float(e["dur"]))
    return {
        kind: {
            "count": len(durs),
            "total_us": sum(durs),
            "mean_us": sum(durs) / len(durs),
            "max_us": max(durs),
        }
        for kind, durs in sorted(by_kind.items())
    }


def top_device_ops(events: list[dict], k: int = 5) -> list[dict]:
    """Top-k device ops by total duration (name-aggregated): the
    per-op channel ``cli.py --profile`` stamps as ``device_top_ops``
    and the attribution engine prefers for its ``top_ops`` field.
    Host-lane events, thunk wrappers, and async completion markers are
    excluded like in ``collective_stats``."""
    totals: dict[str, list[float]] = {}
    for e in events:
        name = _device_op_name(e)
        if name is None:
            continue
        totals.setdefault(name, []).append(float(e["dur"]))
    ranked = sorted(totals.items(), key=lambda kv: -sum(kv[1]))[:max(k, 0)]
    return [{"op": name, "total_us": round(sum(durs), 1),
             "count": len(durs)} for name, durs in ranked]


def profile_collectives(fn, *args, trace_dir: str | Path | None = None,
                        **kwargs) -> dict[str, dict]:
    """Run ``fn`` once under the profiler; return ``collective_stats``.

    ``fn`` should be compiled already (profile the steady state, not
    tracing/compilation).  ``trace_dir`` defaults to a fresh temp dir.
    """
    from dlnetbench_tpu.utils.timing import time_callable

    d = str(trace_dir) if trace_dir else tempfile.mkdtemp(prefix="dlnb_prof_")
    with jax.profiler.trace(d):
        # time_callable fences, so the device work finishes before
        # the profiler context closes and the trace is whole
        time_callable(fn, *args, reps=1, **kwargs)
    return collective_stats(load_trace_events(d))


# ---------------------------------------------------------------------
# Structural overlap analysis.  Whether two collectives CAN ride the
# links together is a property of the program's dataflow: XLA may only
# overlap ops with no dependency path between them.  A CPU-mesh trace
# cannot show device-channel overlap (host thunks timeshare cores), so
# the schedulability check is done on the jaxpr — 1F1B's steady up/down
# hop pairs must be mutually independent, GPipe's hops must chain.

def _iter_subjaxprs(jaxpr):
    """The jaxpr and every nested sub-jaxpr (pjit / shard_map / scan...)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _iter_subjaxprs(inner)


def permute_dependencies(fn, *args) -> tuple[int, set[tuple[int, int]]]:
    """Trace ``fn`` and analyze its ``ppermute`` ops' mutual dataflow.

    Returns ``(n_permutes, deps)`` where ``deps`` holds ordered pairs
    ``(i, j)``: the j-th permute (program order) transitively consumes the
    i-th's output, so the two can never be in flight together.  Pairs
    absent from ``deps`` are schedulable concurrently by XLA — the 1F1B
    overlap property is ``(i, i+1) not in deps`` for its steady pairs.

    An AOT-compiled program (core/executor.py CompiledProgram) is opaque
    to ``make_jaxpr``; its kept python callable + argument buffers are
    traced instead.
    """
    if not args and hasattr(fn, "traceable"):
        args = fn.example_args
        fn = fn.traceable
    closed = jax.make_jaxpr(fn)(*args)
    # find the (deepest) jaxpr level that actually contains the permutes
    level = None
    for j in _iter_subjaxprs(closed.jaxpr):
        if any(e.primitive.name == "ppermute" for e in j.eqns):
            level = j
            break
    if level is None:
        return 0, set()

    producer: dict = {}            # var -> eqn index
    depsets: list[set] = []        # eqn index -> transitive eqn deps
    permute_eqns: list[int] = []
    for idx, eqn in enumerate(level.eqns):
        deps: set = set()
        for v in eqn.invars:
            if hasattr(v, "count") and v in producer:  # Var, not Literal
                p = producer[v]
                deps.add(p)
                deps |= depsets[p]
        depsets.append(deps)
        for v in eqn.outvars:
            producer[v] = idx
        if eqn.primitive.name == "ppermute":
            permute_eqns.append(idx)

    pairs: set[tuple[int, int]] = set()
    for j_pos, j_eqn in enumerate(permute_eqns):
        for i_pos, i_eqn in enumerate(permute_eqns[:j_pos]):
            if i_eqn in depsets[j_eqn]:
                pairs.add((i_pos, j_pos))
    return len(permute_eqns), pairs
