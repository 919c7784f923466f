"""Duration (s) of one of the program's own spans (``metrics/spans.py``,
exported by its tracer under ``record["program_trace"]["spans"]``): the
last finished span named ``span`` whose attrs hold ``attrs``."""
from benchmarks.readers import scope_ms


def read(ctx, params):
    want = params.get("attrs", {})
    found = [s for s in scope_ms.program_trace(ctx).get("spans") or []
             if s["name"] == params["span"]
             and all((s.get("attrs") or {}).get(k) == v
                     for k, v in want.items())]
    return found[-1]["dur_us"] * 1e-6 if found else None
