"""A scope's share of its roofline: the least time the chip could take
for the work of one step under ``scope`` (the larger of operations over
the peak rate and bytes over the memory bandwidth, counted from shapes
by the cost function named in ``cost``, which counts the whole step's
calls) over the device time the trace shows for the operations the
program's own table puts under that scope, whichever implementation ran
them, inside whole executions of the program matching ``module``.  An
operation under an outer and an inner scope belongs to the inner one
(``core/executor.scope_of_op_name``) and is counted once."""
import importlib

from benchmarks.readers import scope_ms


def read(ctx, params):
    if ctx["peaks"] is None:
        return None
    per = scope_ms.scope_seconds(ctx, params["module"],
                                 set(params["scopes"]))
    if per is None:
        return None
    spent = sum(s for s, _ in per)
    if spent <= 0:
        return None
    rec = ctx["record"]
    cost = importlib.import_module(
        f"benchmarks.costs.{params['cost']}").cost(**rec["arch"], **rec)
    peaks = ctx["peaks"]
    least = max(cost["flops"] / peaks["flops_per_s"][params["dtype"]],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(per) / spent
