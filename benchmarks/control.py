"""Readings of what ``correct`` compares, over several seeds in one
process: the sound program, or a control that has to come out as not
correct.  Not part of a benchmark run; the limits in the workload files
were set from what this prints on the chip.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 \
        [--control reference_int8|program] [--rehearse-cpu 1]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import harness  # noqa: E402


def log(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--rehearse-cpu", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.rehearse_cpu:
        cell = harness.rehearsal(cell)
    device = harness.device_block(cell.chips, bool(args.rehearse_cpu))
    if not args.rehearse_cpu:
        harness.enable_cache()
    runner = harness.runner_for(cell.workload["runner"])
    worst: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        rows = runner.readings(cell, seed, log, args.control)
        for name, value, limit, where in rows:
            log({"line": "reading", "seed": seed, "control": args.control,
                 "name": name, "value": value, "limit": limit,
                 "at": where, "ok": value <= limit})
            worst.setdefault(name, []).append(value)
    log({"line": "summary", "workload": cell.name, "device": device,
         "control": args.control,
         "largest": {k: max(v) for k, v in worst.items()},
         "smallest": {k: min(v) for k, v in worst.items()}})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
