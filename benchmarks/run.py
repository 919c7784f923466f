"""One run of one cell:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by name, warms the cell's own shapes (set-up),
measures for ``--seconds``, decides ``correct`` against the plain
reference, and prints one JSON object as the last line of its output.
It fails (non-zero, no result) without a TPU, with fewer chips than the
cell asks for, or on a device kind ``peaks.json`` does not list.
``--rehearse-cpu 1`` runs the same code at the cells' tiny rehearsal
sizes wherever jax runs, prints the device it ran on, and still exits
non-zero: a rehearsal is no measurement.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import harness  # noqa: E402


def log(obj: dict) -> None:
    """An earlier line of the output, stamped with the process's age so
    that a slow set-up shows which part was slow."""
    print(json.dumps({**obj, "t": round(harness.process_age_s(), 3)}),
          flush=True)


def traced_metrics(cell, outcome: dict, trace: dict, chips: int):
    """The per-layer metrics, the device's busy seconds and the
    breakdown of a traced run."""
    from benchmarks import trace_reduce as tr
    window = [e for e in trace["host"] if e[0] == "bench_window"]
    if not window:
        raise harness.BenchError("the trace holds no bench_window span")
    t0 = window[0][1]
    t1 = t0 + window[0][2]
    devices = [trace["devices"][i] for i in sorted(trace["devices"])][:chips]
    if not devices:
        raise harness.BenchError("the trace holds no TPU plane")
    busy = [tr.busy_seconds(tr.clip(d["ops"], t0, t1)) for d in devices]
    host = [e for e in trace["host"] if e[0].startswith("bench_")
            and e[0] != "bench_window"]
    ctx = {"record": outcome["record"], "devices": devices,
           "window": (t0, t1), "cache": outcome["cache"],
           "peaks": harness.peaks(outcome["device"]["kind"])
           if outcome["device"]["platform"] == "tpu" else None}
    metrics = harness.read_layer_metrics(cell, ctx)
    ops = tr.clip(devices[0]["ops"], t0, t1)
    breakdown = {
        "device_ops": [[tr.short_name(n), t]
                       for n, t in tr.top_ops(ops, 10)],
        "idle_gaps": tr.idle_gaps(
            ops, host, t0, t1,
            default=cell.workload.get("gap_default", "unattributed"))}
    return metrics, sum(busy) / len(busy), t1 - t0, breakdown


def main(argv=None, keep_trace: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    rehearse = bool(args.rehearse_cpu)
    try:
        cell = harness.load_cell(args.workload)
        if rehearse:
            cell = harness.rehearsal(cell)
        log({"line": "begin"})
        device = harness.device_block(cell.chips, rehearse)
        log({"line": "start", "workload": cell.name, "seed": args.seed,
             "device": device, "rehearsal": rehearse,
             "compile_cache_dir": (None if rehearse
                                   else harness.enable_cache())})
        cache = harness.CacheCounter().install()
        tracer = harness.TraceWindow(
            bool(args.trace), cell.workload.get("trace_seconds", 3.0),
            keep_trace)
        runner = harness.runner_for(cell.workload["runner"])
        outcome = runner.run({"cell": cell, "seed": args.seed,
                              "seconds": args.seconds, "tracer": tracer,
                              "log": log, "rehearse": rehearse})
        outcome["device"] = device
        outcome["cache"] = dict(cache.counts)
        correct = True
        compared = {}
        for name, value, limit, where in outcome["checks"]:
            ok = value <= limit
            correct &= ok
            # a gap that is not finite as a word: the line stays JSON
            compared[name] = {"value": value if math.isfinite(value)
                              else repr(value), "limit": limit}
            log({"line": "compared", "name": name, "value": value,
                 "limit": limit, "at": where, "ok": ok})
        log({"line": "compile_cache", **cache.counts})
        device = {**device,
                  "memory_peak_bytes": outcome["memory_peak_bytes"]}
        result = {"correct": bool(correct),
                  "attempted": outcome["attempted"],
                  "failed": outcome["failed"]}
        if args.trace:
            trace = tracer.load()
            if trace is None:
                raise harness.BenchError("--trace 1 and nothing traced")
            metrics, busy_s, window_s, breakdown = traced_metrics(
                cell, outcome, trace, cell.chips)
            device.update(busy_s=busy_s, window_s=window_s)
            result.update(metrics=metrics, device=device,
                          breakdown=breakdown)
        else:
            units = {m["name"]: m["unit"] for m in cell.end_to_end}
            result.update(
                metrics={k: {"value": v, "unit": units[k]}
                         for k, v in outcome["end_to_end"].items()
                         if k in units},
                device=device)
        # each number compared beside its limit: last on the result's
        # line and the last lines of standard error, which is what a
        # driver's record keeps of a run that is not correct
        result["compared"] = compared
        for name, row in compared.items():
            print(f"compared {name} {row['value']!r} limit "
                  f"{row['limit']!r}", file=sys.stderr)
    except harness.BenchError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 1
    if rehearse:
        log({"line": "rehearsal result (no measurement)", **result})
        print("benchmarks/run.py: rehearsal on "
              f"{device['platform']}: no result line", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
