"""Two sets of runs of one cell with the same seeds, and each metric's
spread as the contract measures it (the distance between the quartiles
over the median, the wider of the two sets).  The parent never touches
jax: every run is a process of its own, one after another.

    python3 benchmarks/spread.py --workload <cell> --seeds 1,2,3,4,5,6 \
        --seconds 45 [--sets 2] [--out chiprun_out/<cell>.jsonl]
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from benchmarks.stats import spread as quartile_spread  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    sets, all_ok = [], True
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            proc = subprocess.run(
                [sys.executable, str(HERE / "run.py"), "--workload",
                 args.workload, "--seed", str(seed), "--seconds",
                 str(args.seconds), "--trace", "0"],
                capture_output=True, text=True)
            lines = proc.stdout.strip().splitlines()
            if args.out:        # the whole run, for reading a far-off one
                Path(f"{args.out}.set{k}.seed{seed}.log").write_text(
                    proc.stdout + proc.stderr[-4000:])
            if proc.returncode or not lines:
                print(proc.stdout[-2000:], proc.stderr[-2000:],
                      file=sys.stderr)
                return 1
            result = json.loads(lines[-1])
            compared = [json.loads(ln) for ln in lines
                        if ln.startswith('{"line": "compared"')]
            row = {"set": k, "seed": seed, "correct": result["correct"],
                   "failed": result["failed"],
                   "memory_peak_bytes":
                       result["device"]["memory_peak_bytes"],
                   **{n: m["value"] for n, m in result["metrics"].items()},
                   **{c["name"]: c["value"] for c in compared}}
            all_ok &= bool(result["correct"]) and not result["failed"]
            print(json.dumps(row), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
            rows.append(row)
        sets.append(rows)
    metrics = [n for n in sets[0][0]
               if n not in ("set", "seed", "correct", "failed")]
    summary = {"workload": args.workload, "seconds": args.seconds,
               "runs_in_a_set": len(seeds), "all_correct": all_ok}
    for name in metrics:
        per_set = [[r[name] for r in rows] for rows in sets]
        summary[name] = {
            "medians": [statistics.median(v) for v in per_set],
            "spreads": ([quartile_spread(v) for v in per_set]
                        if len(seeds) >= 2 else None),
            "min": min(min(v) for v in per_set),
            "max": max(max(v) for v in per_set)}
    print(json.dumps({"line": "spread", **summary}), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps({"line": "spread", **summary}) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
