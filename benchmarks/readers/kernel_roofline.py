"""A kernel family's share of its roofline: the least time the chip
could take for the work (the larger of operations over the peak rate
and bytes over the memory bandwidth, counted from shapes by the cost
function named in ``cost``) over the device time the trace shows for the
operations whose names match ``pattern``, inside whole executions of the
program matching ``module``."""
import importlib

from benchmarks import trace_reduce as tr
from benchmarks.readers import module_ms


def read(ctx, params):
    runs = module_ms.executions(ctx, params["module"])
    ops = tr.matching(ctx["devices"][0]["ops"], params["pattern"])
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
    calls = len(runs) * rec["arch"]["num_layers"]
    return 100.0 * least * calls / spent
