"""Share (%) of a compiled program's device time spent in none of
``scopes``: whatever the per-scope metrics (``scope_ms``) do not read,
and any operation the program's table does not know.  The reading's own
check: a table that does not match the trace's names reads 100."""
import statistics

from benchmarks.readers import scope_ms


def read(ctx, params):
    per = scope_ms.scope_seconds(ctx, params["module"],
                                 set(params["scopes"]))
    if per is None or not all(busy > 0 for _, busy in per):
        return None
    return 100.0 * statistics.median((busy - s) / busy for s, busy in per)
