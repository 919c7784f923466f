"""Median device time (ms) of one execution of a compiled program spent
in some of the program's scopes: the busy union, inside each whole
execution of the module matching ``module``, of the device operations
whose instruction name (the token after ``%`` and before `` =`` in the
event's name) the program's own table gives one of ``scopes``.

The table is the program's (``core/executor.hlo_op_scopes``, exported by
its tracer under ``record["program_trace"]["op_scopes"]``), never a
pattern on operand shapes: a program without it gives nothing to read.
"""
import re
import statistics

from benchmarks import trace_reduce as tr
from benchmarks.readers import module_ms

INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


def program_trace(ctx) -> dict:
    """What the program's tracer exported for this run, or {}."""
    return ctx["record"].get("program_trace") or {}


def table(ctx, module: str):
    """{instruction name: scope} of the traced module, or None."""
    for name, scopes in (program_trace(ctx).get("op_scopes") or {}).items():
        if re.search(module, name):
            return scopes
    return None


def instruction(event_name: str):
    m = INSTRUCTION.match(event_name)
    return m.group(1) if m else None


def scope_seconds(ctx, module: str, scopes) -> list | None:
    """[(seconds in ``scopes``, busy seconds)] of each whole execution,
    or None without an execution or without the program's table."""
    runs = module_ms.executions(ctx, module)
    scope_of = table(ctx, module)
    if not runs or scope_of is None:
        return None
    ops = ctx["devices"][0]["ops"]
    mine = [e for e in ops if scope_of.get(instruction(e[0])) in scopes]
    return [(tr.busy_seconds(tr.clip(mine, a, b)),
             tr.busy_seconds(tr.clip(ops, a, b))) for a, b in runs]


def read(ctx, params):
    per = scope_seconds(ctx, params["module"], set(params["scopes"]))
    if per is None:
        return None
    spent = statistics.median(s for s, _ in per)
    return spent * 1e3 if spent > 0 else None
