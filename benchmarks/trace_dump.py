"""What a profiler trace holds, for reading one by hand before writing a
reader against it: runs one cell traced, keeps the trace, writes what
its planes, lines and longest-running names are, and removes it.

    python3 benchmarks/trace_dump.py <out.json> --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import harness, run, trace_reduce  # noqa: E402

if __name__ == "__main__":
    out, argv = sys.argv[1], sys.argv[2:]
    rc = run.main([*argv, "--trace", "1"], keep_trace=True)
    kept = harness.ROOT / ".bench_trace" / f"pid{os.getpid()}"
    try:
        desc = trace_reduce.describe_xplane(
            trace_reduce.find_xplane(str(kept)))
        Path(out).write_text(json.dumps(desc, indent=1))
    finally:
        shutil.rmtree(kept, ignore_errors=True)
    raise SystemExit(rc)
