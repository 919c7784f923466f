"""A kernel family's share of its roofline, the kernels found by NAME:
the least time the chip could take for the family's work in one step
(the larger of operations over the peak rate and bytes over the memory
bandwidth, counted from shapes by the cost function named in ``cost``,
which counts the whole step's calls) over the device time the trace
shows for the operations whose instruction is one of ``kernels`` (the
``name=`` of a ``pl.pallas_call``; the trace numbers them ``name.N``),
inside whole executions of the program matching ``module``.  A program
whose kernels are not named so gives nothing to read."""
import importlib
import re

from benchmarks import trace_reduce as tr
from benchmarks.readers import module_ms, scope_ms


def kernel_of(event_name: str):
    """``%flash_fwd.12 = ...`` -> ``flash_fwd``."""
    inst = scope_ms.instruction(event_name)
    return re.sub(r"\.\d+$", "", inst) if inst else None


def read(ctx, params):
    runs = module_ms.executions(ctx, params["module"])
    names = set(params["kernels"])
    ops = [e for e in ctx["devices"][0]["ops"] if kernel_of(e[0]) in names]
    if not runs or not ops or ctx["peaks"] is None:
        return None
    spent = sum(tr.busy_seconds(tr.clip(ops, a, b)) for a, b in runs)
    if spent <= 0:
        return None
    rec = ctx["record"]
    cost = importlib.import_module(
        f"benchmarks.costs.{params['cost']}").cost(**rec["arch"], **rec)
    peaks = ctx["peaks"]
    least = max(cost["flops"] / peaks["flops_per_s"][params["dtype"]],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(runs) / spent
