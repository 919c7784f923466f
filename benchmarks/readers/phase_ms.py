"""Median device time (ms) of one execution of a compiled program spent
in some of the step's phases: inside each whole execution of the module
matching ``module`` (``module_ms.executions``), the time of the device
operations whose instruction name the program's own table
(``core/executor.hlo_op_phases``, exported by its tracer under
``record["program_trace"]["op_phases"]``) gives one of ``phases``
(``forward``, ``recompute``, ``backward`` or ``none``) and, where
``scopes`` is given, whose scope (``op_scopes``) is one of them.
``"share": true`` gives 100 x that time over the execution's busy time.

**The phases partition an execution's busy time.**  The trace holds a
loop's event and the events of its body, one inside the other; an
instant that several events cover belongs to the one that began last
(the shortest of those that began together): the body's operation, not
the loop.  So every busy instant has one owner, a loop keeps only what
none of its body's events cover, and the times of the four phases sum
to the busy time (a scope's ``scope_ms`` is a union and holds its loops
whole: the two agree wherever a loop's body is of the loop's scope).

What it reads where: no ``program_trace``, no table of scopes of the
module, or no whole execution gives nothing to read (None: a fault, as
for ``scope_ms``).  A table of scopes **without** a table of phases (a
program older than the phase table) names no phase: every operation's
phase is ``none``, a time reads 0.0 and the share of ``none`` reads
100.0.  That is what the metric is defined as, "time in operations the
program names as recomputation", and no sentinel.  An operation the
table of phases does not know is ``none`` too.

    python3 benchmarks/readers/phase_ms.py <out.json> --workload <cell> --seed <n> --seconds <s>

is one run of ``run.py --trace 1`` with the program's tracer on,
followed by the **scope x phase table** of the step as one more line and
in ``<out.json>``: ms an execution of every scope of ``spans.SCOPES``
(and ``other``, and ``unknown`` for an operation the table of scopes
does not hold) by the four phases, the ten longest ``recompute``
operations and the ten longest of no phase under their scope, the
Pallas kernels of each phase by name, and the program's spans (a build's ``compile`` span carries what
the two tables cost it, ``op_scopes_s``).  This is how a cell that lists none of the phase metrics, or any
question about one scope, is read.
"""
from __future__ import annotations

import re
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:      # run as a script
    sys.path.insert(0, str(ROOT))

from benchmarks import trace_reduce as tr  # noqa: E402
from benchmarks.readers import module_ms, scope_ms  # noqa: E402

PHASES = ("forward", "recompute", "backward", "none")
NO_PHASE = PHASES[-1]
UNKNOWN_SCOPE = "unknown"


def owned_seconds(events) -> dict:
    """{name: seconds of which an event of that name is the owner} of
    ``(name, start, duration)`` events: each instant belongs to the
    covering event that began last.  The values sum to the busy union."""
    out: dict = {}
    open_: list = []    # (name, end) of the events begun and not ended
    at = float("-inf")

    def close(until):
        """Give the time up to ``until`` to whoever owns it."""
        nonlocal at
        while open_ and open_[-1][1] <= until:
            name, end = open_.pop()
            if end > at:
                out[name] = out.get(name, 0.0) + end - at
                at = end
        if open_ and until > at:
            out[open_[-1][0]] = out.get(open_[-1][0], 0.0) + until - at
        at = max(at, until)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        open_.append((name, start + dur))
    close(float("inf"))
    return out


def tables(ctx, module: str):
    """({instruction: scope}, {instruction: phase}) of the traced
    module: the second empty for a program that names no phase, both
    None for one without a table of scopes."""
    scope_of = scope_ms.table(ctx, module)
    if scope_of is None:
        return None, None
    for name, phases in (scope_ms.program_trace(ctx).get("op_phases")
                         or {}).items():
        if re.search(module, name):
            return scope_of, phases
    return scope_of, {}


def owned_by_operation(ctx, module: str) -> list | None:
    """[{event name: seconds owned}] of each whole execution, or None
    without one."""
    runs = module_ms.executions(ctx, module)
    if not runs:
        return None
    ops = ctx["devices"][0]["ops"]
    return [owned_seconds(tr.clip(ops, a, b)) for a, b in runs]


def grouped(owned: dict, key) -> dict:
    out: dict = {}
    for name, seconds in owned.items():
        out[key(name)] = out.get(key(name), 0.0) + seconds
    return out


def keyed_by(scope_of: dict, phase_of: dict):
    """event name -> (scope, phase) by the program's two tables."""
    def key(name):
        inst = scope_ms.instruction(name)
        return (scope_of.get(inst, UNKNOWN_SCOPE),
                phase_of.get(inst, NO_PHASE))
    return key


def by_scope_and_phase(ctx, module: str) -> list | None:
    """[{(scope, phase): seconds owned}] of each whole execution, or
    None without an execution or without the program's table of
    scopes."""
    scope_of, phase_of = tables(ctx, module)
    per = owned_by_operation(ctx, module)
    if per is None or scope_of is None:
        return None
    return [grouped(run, keyed_by(scope_of, phase_of)) for run in per]


def read(ctx, params):
    per = by_scope_and_phase(ctx, params["module"])
    if per is None:
        return None
    phases, scopes = set(params["phases"]), params.get("scopes")
    spent = [sum(s for (scope, phase), s in run.items()
                 if phase in phases and (scopes is None or scope in scopes))
             for run in per]
    if not params.get("share"):
        return statistics.median(spent) * 1e3
    busy = [sum(run.values()) for run in per]
    if not all(b > 0 for b in busy):
        return None
    return 100.0 * statistics.median(s / b for s, b in zip(spent, busy))


# ------------------------------------------------------------ the script
def scope_phase_table(ctx, module: str = "train_k", scopes=()) -> dict | None:
    """What the script writes: ``table`` {scope: {phase: median ms an
    execution}} over ``scopes``, ``other``, ``unknown`` and whatever
    else the program's table names, ``phase_ms`` the columns' sums and
    ``busy_ms`` the execution's, ``recompute_top`` and ``unphased_top``
    the ten operations of phase ``recompute`` and of none with most time
    as [scope, short name, ms], and ``kernels`` {phase: {Pallas
    kernel's name without its number: how many of its instructions the
    first whole execution ran}}."""
    scope_of, phase_of = tables(ctx, module)
    per_op = owned_by_operation(ctx, module)
    if per_op is None or scope_of is None:
        return None
    key = keyed_by(scope_of, phase_of)
    per = [grouped(run, key) for run in per_op]

    def median_ms(runs, pick):
        return 1e3 * statistics.median(
            sum(s for k, s in run.items() if pick(k)) for run in runs)

    def top(phase, n=10):
        ms = {name: median_ms(per_op, lambda k: k == name)
              for name in {name for run in per_op for name in run}
              if key(name)[1] == phase}
        return [[key(name)[0], tr.short_name(name), ms[name]]
                for name in sorted(ms, key=ms.get, reverse=True)[:n]]
    kernels: dict = {p: {} for p in PHASES}
    for name in per_op[0]:
        if "tpu_custom_call" in name:
            kind = re.sub(r"\.\d+$", "", scope_ms.instruction(name) or name)
            row = kernels[key(name)[1]]
            row[kind] = row.get(kind, 0) + 1
    rows = dict.fromkeys([*scopes, "other", UNKNOWN_SCOPE,
                          *(s for run in per for s, _ in run)])
    return {
        "table": {s: {p: median_ms(per, lambda k: k == (s, p))
                      for p in PHASES} for s in rows},
        "phase_ms": {p: median_ms(per, lambda k: k[1] == p) for p in PHASES},
        "busy_ms": median_ms(per, lambda k: True),
        "recompute_top": top("recompute"),
        "unphased_top": top(NO_PHASE),
        "kernels": kernels}


def main(out: Path, argv: list) -> int:
    """One traced run with the program's tracer on around it
    (``scope_dump.run_with_program_tracer``: a runner that never turns
    it on exports its tables too), then the table of the ``ctx`` the
    harness read the run's metrics from."""
    import json

    from benchmarks import scope_dump
    from dlnetbench_tpu.metrics import spans
    rc, seen, program = scope_dump.run_with_program_tracer(
        [*argv, "--trace", "1"])
    got = {"line": "phase_ms",
           "workload": argv[argv.index("--workload") + 1]}
    if "ctx" in seen:
        got.update(scope_phase_table(seen["ctx"], scopes=spans.SCOPES) or {},
                   spans=program["spans"])
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(got, indent=1))
    print(json.dumps(got), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main(Path(sys.argv[1]), sys.argv[2:]))
