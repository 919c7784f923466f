"""100 * a counter of the program over a total, both from the run's
``record`` (what the runner took from the step's own outputs): the
median of the window's per-step readings under ``counter`` (a path of
keys) over the number under ``of``.  A record without either gives
nothing to read."""
import statistics


def _at(record, path):
    for key in path:
        if not isinstance(record, dict) or key not in record:
            return None
        record = record[key]
    return record


def read(ctx, params):
    readings = _at(ctx["record"], params["counter"])
    total = _at(ctx["record"], params["of"])
    if not readings or not total:
        return None
    return 100.0 * statistics.median(readings) / total
