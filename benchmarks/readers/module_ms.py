"""Median device time (ms) of one compiled program's executions that lie
wholly inside the traced window: the union of the device operations
inside each execution of a module whose name matches ``pattern``."""
import statistics

from benchmarks import trace_reduce as tr

# a cut-short execution and the trace's outermost operation agree to
# nanoseconds; a whole one has a microsecond of other work beyond it
EDGE_S = 1e-6


def executions(ctx, pattern):
    """(start, end) of the whole executions in the window.  A program
    running when the trace began or ended is recorded cut short, from
    the trace's first operation on or up to its last, so only an
    execution that lies inside the span of the recorded operations
    counts as whole."""
    t0, t1 = ctx["window"]
    dev = ctx["devices"][0]
    first = min((s for _, s, _ in dev["ops"]), default=t0) + EDGE_S
    last = max((s + d for _, s, d in dev["ops"]), default=t1) - EDGE_S
    return [(s, s + d) for _, s, d in tr.matching(dev["modules"], pattern)
            if s >= t0 and s > first and s + d < last and s + d <= t1]


def read(ctx, params):
    runs = executions(ctx, params["pattern"])
    if not runs:
        return None
    ops = ctx["devices"][0]["ops"]
    busy = [tr.busy_seconds(tr.clip(ops, a, b)) for a, b in runs]
    return statistics.median(busy) * 1e3
