#!/usr/bin/env python3
"""North-star pod study — every proxy workload on llama3_70b + mixtral,
one command producing the effective-bandwidth table and the three plot
families (SURVEY.md §7.2 step 7: effective bus GB/s and iteration time
per collective).

The reference runs this as a SLURM grid (sbatchman) over
dp/fsdp/hybrid_3d/hybrid_3d_moe and parses the job outputs back into
DataFrames (reference plots/parser.py:213-256).  Here the same study is
one script with no scheduler:

    python examples/pod_study.py --out_dir /tmp/pod_study

runs all 7 proxies (dp, fsdp, hybrid_2d/3d/3d-moe, ring_attention,
ulysses) on an 8-device virtual CPU mesh at reduced buffer/time scale,
then prints per-collective effective bandwidth and writes
scaling / barrier-scatter / Pareto PNGs plus bandwidth_summary.csv.

On a real TPU pod slice, drop the shrink factors and let the runtime's
devices be the mesh:

    python examples/pod_study.py --platform tpu --full_scale \
        --devices 16 --out_dir ~/pod_study_v5p

Every point is a fresh subprocess (compilation caches and backend state
cannot leak between grid points), tagged with ``proxy=<name>`` so the
combined records file remains one flat, parseable study.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

# runnable from a clone without installation
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from dlnetbench_tpu.utils.net import free_port  # noqa: E402

DENSE = "llama3_70b_16_bfloat16"
MOE = "mixtral_8x7b_16_bfloat16"


def build_plan(models: list[str], devices: int) -> list[tuple[str, dict]]:
    """(proxy, flags) for every study point.

    Grid shapes mirror the reference's study configurations scaled to the
    available world size: dp scaling over world sizes and bucket counts
    (reference plots/plot_dp.py:29, :80), fsdp with hybrid sharding
    (sharding_factor x replicas = world, reference
    cpp/data_parallel/fsdp.cpp:217), the three hybrids on stagexdp(xtp/ep)
    grids (reference cpp/hybrid_parallel/*.cpp), and the two
    sequence-parallel extensions on sp x dp grids.
    """
    half = max(devices // 2, 1)
    quarter = max(devices // 4, 1)
    plan: list[tuple[str, dict]] = []

    for model in models:
        # dp runtime scaling over world sizes (last point = full world)
        w = devices
        worlds = []
        while w >= 2:
            worlds.append(w)
            w //= 2
        for w in sorted(worlds):
            plan.append(("dp", {"model": model, "num_buckets": 4, "d": w}))
        # dp bucket study at full world (barrier-scatter axis)
        for nb in (2, 8):
            plan.append(("dp", {"model": model, "num_buckets": nb,
                                "d": devices}))
        plan.append(("fsdp", {"model": model, "num_units": 8,
                              "sharding_factor": half}))
        # pipeline-schedule comparison: reference GPipe vs the rebuild's
        # 1F1B and ZB-H1 extras, same grid and microbatch totals
        for sch in ("gpipe", "1f1b", "zb"):
            plan.append(("hybrid_2d", {"model": model, "num_stages": 4,
                                       "num_microbatches": 8,
                                       "dp": quarter, "schedule": sch}))
        plan.append(("hybrid_3d", {"model": model, "num_stages": 2,
                                   "num_microbatches": 8, "tp": 2,
                                   "dp": quarter}))
        if model == MOE:
            plan.append(("hybrid_3d_moe", {"model": model, "num_stages": 2,
                                           "num_microbatches": 8,
                                           "num_expert_shards": 2,
                                           "dp": quarter}))
        plan.append(("ring_attention", {"model": model, "sp": 4,
                                        "dp": quarter, "max_layers": 2}))
        plan.append(("ulysses", {"model": model, "sp": 4, "dp": quarter,
                                 "max_layers": 2}))
    return plan


def run_plan(plan, args, records: Path) -> int:
    env = dict(os.environ)
    if args.platform == "cpu" and not env.get("XLA_FLAGS"):
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    repo = str(Path(__file__).resolve().parent.parent)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo, env.get("PYTHONPATH")) if p)

    from dlnetbench_tpu.utils.native_build import native_bin as _locate
    if args.tier == "native":
        # always (re)build: incremental ninja is a no-op when current,
        # and a silently stale cached binary would poison the study
        try:
            native_bin = _locate(repo)
        except Exception as e:
            raise SystemExit(f"--tier native could not build: {e}")
    else:
        native_bin = _locate(repo, build=False)

    failed = 0
    for i, (proxy, flags) in enumerate(plan):
        desc = " ".join(f"{k}={v}" for k, v in flags.items())
        flags = dict(flags)
        if args.tier == "native":
            # same study on the C++ tier: per-proxy binary, explicit
            # --world (the python tier infers it from the device mesh;
            # the dp scaling axis "d" IS the world)
            world = flags.pop("d", args.devices)
            argv = [str(native_bin / proxy),
                    "--model", flags.pop("model"),
                    "--world", str(world), "--out", str(records),
                    "--runs", str(args.runs), "--warmup", "1",
                    "--no_topology", "--base_path", repo]
        else:
            argv = [sys.executable, "-m", "dlnetbench_tpu.cli", proxy,
                    "--out", str(records), "--platform", args.platform,
                    "-r", str(args.runs), "-w", "1", "--no_topology",
                    "--tag", f"proxy={proxy}"]
        if not args.full_scale:
            argv += ["--size_scale", str(args.size_scale),
                     "--time_scale", str(args.time_scale)]
        for k, v in flags.items():
            argv += [f"--{k}", str(v)]
        print(f"[{i + 1}/{len(plan)}] {proxy} {desc}", flush=True)
        if args.tier == "native" and args.backend == "pjrt-hier":
            rc = _run_hier_point(argv, world, records, env, args.procs)
        else:
            rc = subprocess.run(argv, env=env,
                                stdout=subprocess.DEVNULL).returncode
        if rc != 0:
            print(f"  FAILED rc={rc}", file=sys.stderr)
            failed += 1
    return failed


def _run_hier_point(argv: list[str], world, records: Path, env,
                    nprocs: int = 2) -> int:
    """One study point over the hierarchical ICI x DCN fabric: --procs
    OS processes, each driving its own executor (libtpu when usable,
    host otherwise) over world/procs ranks, combined over the TCP mesh;
    their per-process records are merged into the study's record stream
    (the reference's multi-node operating mode, dp.cpp:166-189).
    Returns a nonzero code for ANY per-point failure (signal death,
    timeout, bad records) so run_plan's per-point FAILED accounting
    sees it."""
    if int(world) < nprocs:
        # uneven worlds are fine (the fabric's balanced layout gives the
        # first world%procs processes one extra rank); only a process
        # with NO rank to host is impossible
        print(f"  skipped (world {world} < {nprocs} processes)",
              file=sys.stderr)
        return 0
    # strip the single-record --out; each process writes its own file
    base = [a for j, a in enumerate(argv)
            if argv[j - 1] != "--out" and a != "--out"]
    parts = [records.parent / f".hier_p{r}.jsonl" for r in range(nprocs)]
    # the freshly-probed port can be stolen before rank 0 binds it
    # (TOCTOU) — retry on a fresh port, same discipline as the tcp
    # fabric tests
    for attempt in range(3):
        for p in parts:
            p.unlink(missing_ok=True)
        port = free_port()
        procs = [subprocess.Popen(
            base + ["--backend", "pjrt", "--procs", str(nprocs),
                    "--rank", str(r),
                    "--coordinator", f"127.0.0.1:{port}", "--out",
                    str(parts[r])],
            env=env, stdout=subprocess.DEVNULL) for r in range(nprocs)]
        rcs = []
        for p in procs:
            try:
                rcs.append(p.wait(timeout=900))
            except subprocess.TimeoutExpired:
                rcs.append(124)
        if any(rcs):  # reap the sibling before retrying or reporting
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()
        if all(rc == 0 for rc in rcs):
            break
        if attempt == 2:
            return next((abs(rc) for rc in rcs if rc != 0), 1)
    from dlnetbench_tpu.metrics.merge import merge_files
    try:
        merge_files(records, parts)
    except ValueError as e:
        print(f"  merge failed: {e}", file=sys.stderr)
        return 1
    return 0


# ---------------------------------------------------------------------
# --serving mode: the latency-vs-offered-load study (ISSUE 8,
# docs/SERVING.md).  Offered load is swept as a FRACTION of this
# machine's measured capacity (a saturating calibration run first), so
# the knee lands inside the sweep on any box; each load point runs
# SERVING_SEEDS arrival-plan seeds and the report bands p99/goodput
# over them.  One extra point injects a straggler delay into the decode
# loop at mid load — the fault-composition proof: the same fault-plan
# JSON that drives the training tier measurably inflates serving p99.

SERVING_FRACTIONS = (0.25, 0.5, 1.0, 1.5, 2.0)
# 5 seeds per load point: each point's p99 is the MEDIAN over seeds
# with the full band shown — on a small shared box a single co-tenant
# stall lands squarely in one run's nearest-rank p99, and 3 seeds give
# that outlier veto power over the knee shape
SERVING_SEEDS = (0, 1, 2, 3, 4)
# long enough that sustained overload accumulates a real backlog: at
# 2x capacity the LAST arrival waits ~half the arrival span, so the
# span must dwarf a single request's clean service time or the queue
# never shows in p99
SERVING_REQUESTS = 120
SERVING_FLAGS = [
    "--slots", "4", "--page_size", "8", "--num_pages", "64",
    "--max_seq_len", "64", "--embed", "64", "--heads", "4",
    "--kv_heads", "2", "--ff", "128", "--layers", "2", "--vocab", "256",
    "--slo_ttft_ms", "100", "--slo_tpot_ms", "30",
]
SERVING_FAULT_DELAY_US = 20000  # straggler sleep per engine step


def serving_arrival(rate: float, seed: int,
                    n: int = SERVING_REQUESTS) -> str:
    return json.dumps({"kind": "poisson", "rate_rps": round(rate, 3),
                       "num_requests": n, "seed": seed,
                       "prompt_len": [8, 16], "output_len": [4, 8]})


# --disagg (ISSUE 16): the same sweep over the disaggregated engine —
# the prefill mesh and decode mesh split the two capacity ranks, KV
# pages migrate in the stored dtype, and the report's serving_summary
# carries the migration_* columns next to the latency bands
DISAGG_FLAGS = [
    "--disaggregate", "--world", "2", "--prefill_ranks", "1",
    "--decode_ranks", "1", "--multi_step_n", "4",
]

# --fleet (ISSUE 18): the same sweep over a two-replica FLEET — the
# seeded router places every arrival (p2c on the live load score), each
# replica keeps its own page pool, and the report's serving_summary
# carries the fleet_routing/fleet_replicas/fleet_goodput_per_chip_s
# columns next to the latency bands.  Capacity doubles (2 engines), so
# the same calibrate-then-sweep protocol finds this arm's own knee.
FLEET_FLAGS = [
    "--replicas", "2", "--routing", "p2c",
]


def _serve_argv(records: Path, arrival: str, tags: list[str],
                extra: list[str] | None = None) -> list:
    argv = [sys.executable, "-m", "dlnetbench_tpu.cli", "serve",
            "--arrival", arrival, "--platform", "cpu",
            "--out", str(records)] + SERVING_FLAGS + (extra or [])
    for t in tags:
        argv += ["--tag", t]
    return argv


def run_serving_plan(args, records: Path) -> int:
    from dlnetbench_tpu.metrics.parser import load_records

    repo = str(Path(__file__).resolve().parent.parent)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo, env.get("PYTHONPATH")) if p)
    # the disagg/fleet arms need a multi-device mesh; honor a caller's
    # own XLA_FLAGS (same discipline as run_plan)
    if not env.get("XLA_FLAGS"):
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    failed = 0
    disagg = bool(getattr(args, "disagg", False))
    fleet = bool(getattr(args, "fleet", False))
    if disagg:
        extra, eng = DISAGG_FLAGS, "disagg"
    elif fleet:
        extra, eng = FLEET_FLAGS, "fleet"
    else:
        extra, eng = None, "mono"
    eng_tag = f"engine={eng}"

    # 1. capacity calibration: a saturating rate (every request queued
    # at t~0) — measured_rps IS the engine's drain capacity here
    calib = records.parent / ".serving_calib.jsonl"
    calib.unlink(missing_ok=True)
    print("[serving 0] capacity calibration (saturating arrivals)",
          flush=True)
    rc = subprocess.run(
        _serve_argv(calib, serving_arrival(10000.0, 0),
                    ["load_frac=calib", eng_tag], extra),
        env=env, stdout=subprocess.DEVNULL).returncode
    if rc != 0 or not calib.exists():
        raise SystemExit(f"serving calibration failed rc={rc}")
    capacity = load_records(calib)[0]["global"]["serving"]["measured_rps"]
    calib.unlink(missing_ok=True)
    print(f"  capacity ~{capacity:.1f} req/s on this box", flush=True)

    # 2. the load sweep: fractions of capacity x arrival seeds
    n_pts = len(SERVING_FRACTIONS) * len(SERVING_SEEDS)
    for i, frac in enumerate(SERVING_FRACTIONS):
        for seed in SERVING_SEEDS:
            print(f"[serving {i + 1}/{len(SERVING_FRACTIONS)}] "
                  f"load {frac:.2f}x capacity, seed {seed} "
                  f"({n_pts} runs total)", flush=True)
            rc = subprocess.run(
                _serve_argv(records,
                            serving_arrival(capacity * frac, seed),
                            [f"load_frac={frac}",
                             f"serving_seed={seed}", eng_tag], extra),
                env=env, stdout=subprocess.DEVNULL).returncode
            if rc != 0:
                print(f"  FAILED frac={frac} seed={seed} rc={rc}",
                      file=sys.stderr)
                failed += 1

    # 3. the faulted point: a straggler delay on every decode-loop step
    # at mid load — same FaultPlan JSON as the training tier
    fault = json.dumps({"events": [{
        "kind": "delay", "iteration": 0,
        "magnitude_us": SERVING_FAULT_DELAY_US}]})
    print(f"[serving fault] 0.50x capacity + "
          f"{SERVING_FAULT_DELAY_US / 1000:.0f} ms straggler per "
          f"decode step", flush=True)
    rc = subprocess.run(
        _serve_argv(records, serving_arrival(capacity * 0.5, 0),
                    ["load_frac=0.5", "serving_fault=straggler",
                     eng_tag], extra)
        + ["--fault", fault],
        env=env, stdout=subprocess.DEVNULL).returncode
    if rc != 0:
        print("  FAILED", file=sys.stderr)
        failed += 1
    return failed


def serving_report(args, records: Path) -> int:
    """The latency-vs-load table with stat bands over seeds, the knee
    verdict, and the straggler-composition verdict — enforced at
    generation time like the goodput study's Daly check."""
    from dlnetbench_tpu.analysis.bandwidth import serving_summary
    from dlnetbench_tpu.metrics.parser import load_records
    from dlnetbench_tpu.metrics.stats import summarize

    recs = load_records(records)
    rows = []
    for rec in recs:
        g = rec.get("global", {})
        srv = g.get("serving")
        if not srv:
            continue
        v = g.get("variables", {})
        rows.append({
            "frac": v.get("load_frac", "?"),
            "fault": v.get("serving_fault", "-"),
            "offered_rps": srv["offered_rps"],
            "p99_ms": srv["e2e_ms"]["p99"],
            "ttft_p99_ms": srv["ttft_ms"]["p99"],
            "goodput_frac": srv["goodput_frac"],
            "goodput_rps": srv["goodput_rps"],
        })
    clean = {}
    for r in rows:
        if r["fault"] == "-":
            clean.setdefault(r["frac"], []).append(r)
    print("\n=== serving: latency vs offered load (bands over "
          f"{len(SERVING_SEEDS)} arrival seeds) ===")
    print(f"{'load':>6} {'offered_rps':>12} {'p99_ms':>24} "
          f"{'ttft_p99_ms':>24} {'goodput@SLO':>22}")
    by_frac = {}
    for frac in sorted(clean, key=lambda f: float(f)):
        pts = clean[frac]
        p99 = summarize([p["p99_ms"] for p in pts], ndigits=3)
        ttft = summarize([p["ttft_p99_ms"] for p in pts], ndigits=3)
        good = summarize([p["goodput_frac"] for p in pts], ndigits=4)
        offered = sum(p["offered_rps"] for p in pts) / len(pts)
        by_frac[float(frac)] = (p99, good)
        print(f"{frac:>6} {offered:>12.1f} "
              f"{p99['value']:>10.1f} {str(p99['band']):>13} "
              f"{ttft['value']:>10.1f} {str(ttft['band']):>13} "
              f"{good['value']:>8.2f} {str(good['band']):>13}")
    rc = 0
    if by_frac:
        lo, hi = min(by_frac), max(by_frac)
        knee = by_frac[hi][0]["value"] / max(by_frac[lo][0]["value"],
                                             1e-9)
        print(f"\nknee: p99({hi}x) / p99({lo}x) = {knee:.1f}x, "
              f"goodput@SLO {by_frac[lo][1]['value']:.2f} -> "
              f"{by_frac[hi][1]['value']:.2f}")
        if knee < 2.0:
            print("VERDICT: no visible saturation knee (p99 inflation "
                  "< 2x across the sweep) — the study failed its "
                  "acceptance bar", file=sys.stderr)
            rc = 1
    faulted = [r for r in rows if r["fault"] != "-"]
    if faulted:
        base = clean.get(faulted[0]["frac"], [])
        base_p99 = (summarize([p["p99_ms"] for p in base])["value"]
                    if base else float("nan"))
        f_p99 = faulted[0]["p99_ms"]
        print(f"straggler composition: clean p99 {base_p99:.1f} ms -> "
              f"faulted p99 {f_p99:.1f} ms at load "
              f"{faulted[0]['frac']}x "
              f"(+{SERVING_FAULT_DELAY_US / 1000:.0f} ms/step delay)")
        if not f_p99 > base_p99:
            print("VERDICT: injected straggler did NOT inflate p99 — "
                  "fault composition broke", file=sys.stderr)
            rc = 1
    ss = serving_summary(recs)
    if not ss.empty:
        ss.to_csv(args.out_dir / "serving_summary.csv", index=False)
        print(f"\nwrote {records} and "
              f"{args.out_dir}/serving_summary.csv")
    return rc


# ---------------------------------------------------------------------
# --kv_density mode: the serving-density study (ISSUE 12,
# docs/SERVING.md "Cache density").  Two halves into one artifact dir:
#
#   1. capacity A/B — bench.py's kv_density_ab line: dense vs int8 vs
#      fp8 paged-KV engines at the SAME pool bytes (scale arrays priced
#      in), one seeded saturating plan, interleaved rounds.  Acceptance
#      (enforced HERE, at generation): both quant recipes inside their
#      stated decode-parity bars, admitted concurrency >= 1.8x dense,
#      and the goodput-at-SLO win band-DISJOINT.
#   2. prefix-sharing A/B — one prefix-heavy arrival plan (seeded
#      shared system prompts, serving/arrivals.py shared_prefix_len/
#      prefix_pool) run through the SAME engine with sharing off/on:
#      token-identical streams (lossless), prefix_hit_rate > 0 and
#      bytes_saved > 0 stamped on the sharing record, TTFT deltas
#      reported.

KV_DENSITY_MIN_CAPACITY_X = 1.8


def run_kv_density_study(out_dir: Path) -> int:
    """Generate docs/studies/kv_density_r15's evidence into
    ``out_dir``; returns non-zero unless the acceptance bars hold."""
    import dataclasses

    import jax

    repo = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(repo))
    import bench

    from dlnetbench_tpu.metrics.emit import emit_result
    from dlnetbench_tpu.metrics.stats import bands_overlap
    from dlnetbench_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
    from dlnetbench_tpu.serving.arrivals import ArrivalPlan
    from dlnetbench_tpu.serving.scheduler import (Engine,
                                                  ServingConfig,
                                                  run_serving)

    rc = 0
    # ---- half 1: the equal-pool-bytes capacity A/B ------------------
    print("[kv_density 1/2] capacity A/B (dense vs int8 vs fp8 at "
          "equal pool bytes)", flush=True)
    line = bench._bench_kv_density()
    if line is None:
        print("kv_density_ab produced no line", file=sys.stderr)
        return 1
    (out_dir / "kv_density_ab.json").write_text(
        json.dumps(line, indent=1) + "\n")
    base = line["variants"]["bf16"]
    disjoint_wins = []
    for cd in ("int8", "fp8"):
        v = line["variants"][cd]
        cap = v["capacity_x"]["value"]
        disjoint = (bands_overlap(base["goodput_rps"]["band"],
                                  v["goodput_rps"]["band"]) is False
                    and v["goodput_rps"]["value"]
                    > base["goodput_rps"]["value"])
        disjoint_wins.append((cd, disjoint))
        print(f"  {cd}: parity {v['parity_max_err']['value']:.4f} "
              f"(tol {v['parity_tol']}, ok={v['parity_ok']}), "
              f"capacity {cap:.2f}x, goodput@SLO "
              f"{base['goodput_rps']['value']:.1f} -> "
              f"{v['goodput_rps']['value']:.1f} rps "
              f"(band-disjoint={disjoint})")
        # parity + the >= 1.8x capacity bar gate BOTH recipes
        if not v["parity_ok"]:
            print(f"VERDICT: {cd} decode parity exceeded its stated "
                  f"bar", file=sys.stderr)
            rc = 1
        if cap < KV_DENSITY_MIN_CAPACITY_X:
            print(f"VERDICT: {cd} admitted concurrency {cap:.2f}x < "
                  f"{KV_DENSITY_MIN_CAPACITY_X}x at equal pool bytes",
                  file=sys.stderr)
            rc = 1
    # the band-disjoint goodput-at-SLO win gates the recipe a
    # deployment would actually pick (int8 on the CPU mesh, where XLA
    # dequantizes fp8 in slow emulation); the other recipe's number is
    # still committed honestly above
    if not any(d for _, d in disjoint_wins):
        print("VERDICT: no quant recipe shows a band-disjoint "
              "goodput-at-SLO win vs dense at equal pool bytes",
              file=sys.stderr)
        rc = 1

    # ---- half 2: the prefix-heavy sharing A/B -----------------------
    print("[kv_density 2/2] prefix sharing A/B (shared system "
          "prompt, sharing off vs on)", flush=True)
    mc = TransformerConfig(
        vocab_size=256, embed_dim=64, num_heads=4, num_kv_heads=2,
        ff_dim=128, num_layers=2, seq_len=96, gated=True,
        max_positions=0, dtype="float32")
    # page-aligned 32-token system prompt over a 2-prompt pool; the
    # prefill chunk divides the prefix so shared/unshared runs chunk
    # the unshared tail identically (the bit-exactness precondition
    # docs/SERVING.md states)
    plan = ArrivalPlan(kind="poisson", rate_rps=400.0,
                       num_requests=40, seed=0,
                       prompt_len=[40, 56], output_len=[8, 16],
                       shared_prefix_len=32, prefix_pool=2)
    base_cfg = ServingConfig(slots=6, page_size=8, num_pages=96,
                             max_seq_len=96, prefill_chunk=8,
                             slo_ttft_ms=250.0, slo_tpot_ms=100.0,
                             attn_impl="gather")
    params = init_params(jax.random.key(0), mc)
    records = out_dir / "records.jsonl"
    records.unlink(missing_ok=True)
    results = {}
    for tag, cfg in (("off", base_cfg),
                     ("on", dataclasses.replace(base_cfg,
                                                prefix_sharing=True))):
        res = run_serving(mc, cfg, plan, params=params)
        res.global_meta.setdefault("variables", {})["prefix_sharing"] \
            = tag
        rec = emit_result(res, path=records)
        results[tag] = rec["global"]
    # losslessness: re-run both engines capturing token streams
    streams = {}
    for tag, cfg in (("off", base_cfg),
                     ("on", dataclasses.replace(base_cfg,
                                                prefix_sharing=True))):
        eng = Engine(mc, cfg, params=params)
        eng.run(plan.sample())
        streams[tag] = dict(eng.token_streams)
    lossless = streams["on"] == streams["off"]
    srv_off = results["off"]["serving"]
    srv_on = results["on"]["serving"]
    hit_rate = results["on"].get("prefix_hit_rate", 0.0)
    bytes_saved = results["on"].get("prefix_bytes_saved", 0)
    summary = {
        "lossless": lossless,
        "prefix_hit_rate": hit_rate,
        "prefix_bytes_saved": bytes_saved,
        "ttft_p50_ms": {"off": srv_off["ttft_ms"]["p50"],
                        "on": srv_on["ttft_ms"]["p50"]},
        "ttft_p99_ms": {"off": srv_off["ttft_ms"]["p99"],
                        "on": srv_on["ttft_ms"]["p99"]},
        "e2e_p99_ms": {"off": srv_off["e2e_ms"]["p99"],
                       "on": srv_on["e2e_ms"]["p99"]},
        "plan": plan.to_dict(),
    }
    (out_dir / "prefix_sharing_ab.json").write_text(
        json.dumps(summary, indent=1) + "\n")
    print(f"  lossless={lossless} hit_rate={hit_rate} "
          f"bytes_saved={bytes_saved} ttft_p50 "
          f"{srv_off['ttft_ms']['p50']:.1f} -> "
          f"{srv_on['ttft_ms']['p50']:.1f} ms")
    if not lossless:
        print("VERDICT: prefix sharing changed the token streams — "
              "sharing must be lossless", file=sys.stderr)
        rc = 1
    if not (hit_rate > 0 and bytes_saved > 0):
        print("VERDICT: prefix-heavy plan produced no measured "
              "sharing (hit_rate/bytes_saved)", file=sys.stderr)
        rc = 1
    return rc


# ---------------------------------------------------------------------
# --fault mode: the fault-injection & elastic-degradation study
# (docs/RESILIENCE.md).  Five points into ONE records.jsonl — three
# native (straggler / crash+shrink / drop+retry, the r8 set), one
# native preempt->rejoin (the grow half), and a python-tier seeded
# goodput-vs-interval sweep the Daly model is validated against:
#   1. straggler  — fsdp/shm, a 30 ms delay on rank 2 from step 4 on:
#                   the clean window is the in-record baseline, the
#                   summary reports straggler_amp and refuses busbw on
#                   the faulted runs;
#   2. crash      — dp over 3 TCP processes, rank 1 dies at step 4
#                   under policy `shrink`: the victim exits nonzero and
#                   emits nothing (dead is dead), survivors finish on
#                   the pre-split survivor group and their records
#                   merge through the degraded pathway with
#                   detection_ms/recovery_ms/degraded_world;
#   3. drop       — dp over 2 TCP processes at 20 % injected frame
#                   loss under policy `retry`: the run completes,
#                   backoff counts ride the record.

FAULT_MODEL = "gpt2_l_16_bfloat16"

# the seeded goodput sweep (point 5): checkpoint intervals x seeds; each
# seed draws its own preempt trigger, so the triggers are the "failure
# arrivals" the exponential-MTBF fit treats as draws (analysis/goodput)
ELASTIC_INTERVALS = (1, 2, 4, 8)
ELASTIC_SEEDS = (0, 1, 2)
ELASTIC_RUNS = 16  # measured steps per sweep run (+1 warmup)


def elastic_plan(seed: int, *, warmup: int = 1) -> dict:
    """The seeded preempt -> rejoin plan of one sweep run: rank 2 is
    evicted at a seed-drawn step (grace 20 ms) and returns 4 steps
    later.  Deterministic given the seed — the sweep is replayable."""
    import random
    rng = random.Random(seed)
    pre = warmup + 4 + rng.randrange(5)  # plan steps 5..9
    return {"policy": "shrink", "events": [
        {"kind": "preempt", "ranks": [2], "iteration": pre,
         "magnitude_us": 20000, "seed": seed},
        {"kind": "rejoin", "ranks": [2], "iteration": pre + 4}]}


def _fault_base(repo: str, runs: int = 6) -> list[str]:
    return ["--model", FAULT_MODEL, "--time_scale", "0.001",
            "--size_scale", "0.0001", "--runs", str(runs),
            "--warmup", "1", "--no_topology", "--base_path", repo]


def run_fault_plan(args, records: Path) -> int:
    from dlnetbench_tpu.metrics.merge import merge_files
    from dlnetbench_tpu.utils.native_build import native_bin as _locate

    repo = str(Path(__file__).resolve().parent.parent)
    try:
        native = _locate(repo)
    except Exception as e:
        raise SystemExit(f"--fault needs the native tier: {e}")
    failed = 0

    # 1. straggler (shm; fsdp declares a comm_model, so the faulted
    # busbw refusal + straggler_amp surface in the bandwidth table)
    plan = json.dumps({"events": [{"kind": "delay", "ranks": [2],
                                   "iteration": 4,
                                   "magnitude_us": 30000}]})
    print("[fault 1/5] straggler: fsdp/shm world 4, 30 ms delay on "
          "rank 2 from step 4", flush=True)
    rc = subprocess.run(
        [str(native / "fsdp"), "--world", "4", "--num_units", "4",
         "--sharding_factor", "2", "--fault", plan,
         "--out", str(records)] + _fault_base(repo),
        stdout=subprocess.DEVNULL).returncode
    if rc != 0:
        print("  FAILED", file=sys.stderr)
        failed += 1

    # 2. rank crash + shrink (tcp, 3 processes; rank 1 is the victim)
    plan = json.dumps({"events": [{"kind": "crash", "ranks": [1],
                                   "iteration": 4}]})
    print("[fault 2/5] crash+shrink: dp/tcp world 3, rank 1 dies at "
          "step 4, survivors regroup", flush=True)
    port = free_port()
    parts = [records.parent / f".fault_p{r}.jsonl" for r in range(3)]
    for p in parts:
        p.unlink(missing_ok=True)
    procs = [subprocess.Popen(
        [str(native / "dp"), "--world", "3", "--backend", "tcp",
         "--rank", str(r), "--coordinator", f"127.0.0.1:{port}",
         "--num_buckets", "2", "--fault", plan,
         "--fault_policy", "shrink", "--out", str(parts[r])]
        + _fault_base(repo),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        for r in range(3)]
    rcs = [p.wait(timeout=300) for p in procs]
    # the victim MUST die (nonzero, record-less); the survivors finish
    if rcs[1] == 0 or rcs[0] != 0 or rcs[2] != 0:
        print(f"  FAILED rcs={rcs}", file=sys.stderr)
        failed += 1
    else:
        try:
            merge_files(records, [parts[0], parts[2]])
        except ValueError as e:
            print(f"  merge failed: {e}", file=sys.stderr)
            failed += 1
    for p in parts:
        p.unlink(missing_ok=True)

    # 3. drop + retry (tcp, 2 processes, 20 % loss with backoff)
    plan = json.dumps({"events": [{"kind": "drop", "ranks": [0],
                                   "iteration": 0, "rate": 0.2,
                                   "magnitude_us": 200, "seed": 42}]})
    print("[fault 3/5] drop+retry: dp/tcp world 2, 20 % injected frame "
          "loss, exponential backoff", flush=True)
    port = free_port()
    parts = [records.parent / f".fault_d{r}.jsonl" for r in range(2)]
    for p in parts:
        p.unlink(missing_ok=True)
    procs = [subprocess.Popen(
        [str(native / "dp"), "--world", "2", "--backend", "tcp",
         "--rank", str(r), "--coordinator", f"127.0.0.1:{port}",
         "--num_buckets", "2", "--fault", plan,
         "--fault_policy", "retry", "--out", str(parts[r])]
        + _fault_base(repo),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        for r in range(2)]
    rcs = [p.wait(timeout=300) for p in procs]
    if any(rcs):
        print(f"  FAILED rcs={rcs}", file=sys.stderr)
        failed += 1
    else:
        try:
            merge_files(records, parts)
        except ValueError as e:
            print(f"  merge failed: {e}", file=sys.stderr)
            failed += 1
    for p in parts:
        p.unlink(missing_ok=True)

    # 4. preempt + rejoin (tcp, 3 processes): rank 1 is gracefully
    # evicted at step 4 (20 ms drain), survivors run degraded, everyone
    # re-splits onto the pre-built full-world comm at step 8 — ALL
    # THREE ranks emit records, degraded_world is cleared, rejoin_ms
    # measures the grow rendezvous (fault_session.hpp's grow half)
    plan = json.dumps(elastic_plan(0, warmup=1))
    print("[fault 4/5] preempt+rejoin: dp/tcp world 3, rank 1 evicted "
          "(20 ms grace), rejoins 4 steps later — full world restored",
          flush=True)
    port = free_port()
    parts = [records.parent / f".fault_e{r}.jsonl" for r in range(3)]
    for p in parts:
        p.unlink(missing_ok=True)
    procs = [subprocess.Popen(
        [str(native / "dp"), "--world", "3", "--backend", "tcp",
         "--rank", str(r), "--coordinator", f"127.0.0.1:{port}",
         "--num_buckets", "2", "--fault", plan,
         "--fault_policy", "shrink", "--out", str(parts[r])]
        + _fault_base(repo, runs=ELASTIC_RUNS),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        for r in range(3)]
    rcs = [p.wait(timeout=300) for p in procs]
    if any(rcs):
        print(f"  FAILED rcs={rcs}", file=sys.stderr)
        failed += 1
    else:
        try:
            merge_files(records, parts)
        except ValueError as e:
            print(f"  merge failed: {e}", file=sys.stderr)
            failed += 1
    for p in parts:
        p.unlink(missing_ok=True)

    # 5. the seeded goodput-vs-interval sweep (python tier: it owns the
    # checkpoint subsystem): the full preempt -> drain-save -> restore
    # -> shrink -> rejoin arc at every checkpoint interval x seed, each
    # a fresh cli subprocess on the virtual mesh, stall-mode npz saves
    # (the whole durable write on the timed path — the Daly model's d).
    # fault_report fits the model and verdicts measured-vs-predicted.
    n_pts = len(ELASTIC_INTERVALS) * len(ELASTIC_SEEDS)
    print(f"[fault 5/5] goodput sweep: dp x {args.devices} virtual "
          f"devices, intervals {ELASTIC_INTERVALS} x seeds "
          f"{ELASTIC_SEEDS} ({n_pts} runs)", flush=True)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices}")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo, env.get("PYTHONPATH")) if p)
    for every in ELASTIC_INTERVALS:
        for seed in ELASTIC_SEEDS:
            ckpt_dir = records.parent / f".ckpt_e{every}_s{seed}"
            rc = subprocess.run(
                [sys.executable, "-m", "dlnetbench_tpu.cli", "dp",
                 "--model", FAULT_MODEL, "--platform", "cpu",
                 "--num_buckets", "2", "-r", str(ELASTIC_RUNS),
                 "-w", "1", "--size_scale", "0.0001",
                 "--time_scale", "0.001", "--no_topology",
                 "--fault", json.dumps(elastic_plan(seed, warmup=1)),
                 "--checkpoint_dir", str(ckpt_dir),
                 "--checkpoint_every", str(every),
                 "--checkpoint_mode", "stall",
                 "--checkpoint_backend", "npz",
                 "--tag", f"elastic_seed={seed}",
                 "--out", str(records)],
                env=env, stdout=subprocess.DEVNULL).returncode
            import shutil
            shutil.rmtree(ckpt_dir, ignore_errors=True)
            if rc != 0:
                print(f"  FAILED every={every} seed={seed} rc={rc}",
                      file=sys.stderr)
                failed += 1
    return failed


def fault_report(args, records: Path) -> int:
    from dlnetbench_tpu.analysis.bandwidth import bandwidth_summary, \
        straggler_amplification
    from dlnetbench_tpu.metrics.parser import load_records

    recs = load_records(records)
    print("\n=== fault study: one row per record "
          "(docs/RESILIENCE.md columns) ===")
    header = (f"{'section':<8} {'fault':<18} {'policy':<10} "
              f"{'straggler_amp':>13} {'detection_ms':>12} "
              f"{'recovery_ms':>11} {'rejoin_ms':>10} {'ckpt_ms':>8} "
              f"{'lost':>5} {'goodput':>8} {'drops':>6} {'retries':>8} "
              f"degraded_world")
    print(header)

    def _f(v, width, prec=3):
        return (f"{v:>{width}.{prec}f}" if isinstance(v, (int, float))
                else f"{'-':>{width}}")

    for rec in recs:
        g = rec.get("global", {})
        plan = g.get("fault_plan") or {}
        kinds = "+".join(sorted({e.get("kind", "?")
                                 for e in plan.get("events", [])})) or "-"
        amp = straggler_amplification(rec)
        print(f"{rec.get('section', '?'):<8} {kinds:<18} "
              f"{g.get('fault_policy', '-'):<10} "
              f"{amp if amp == amp else float('nan'):>13.3f} "
              f"{_f(g.get('detection_ms'), 12)} "
              f"{_f(g.get('recovery_ms'), 11)} "
              f"{_f(g.get('rejoin_ms'), 10)} "
              f"{_f(g.get('checkpoint_ms'), 8)} "
              f"{_f(g.get('lost_steps'), 5, 0)} "
              f"{_f(g.get('goodput'), 8, 2)} "
              f"{g.get('fault_drops', 0):>6} "
              f"{g.get('fault_retries', 0):>8} "
              f"{g.get('degraded_world', '-')}")

    # the Daly-interval validation over the goodput sweep records
    # (analysis/goodput.py): nonzero when the measured optimum falls
    # OUTSIDE the model's prediction band — the study's acceptance
    # criterion, enforced at generation time, not just documented
    rc = 0
    from dlnetbench_tpu.analysis import goodput as goodput_mod
    try:
        verdict = goodput_mod.validate_sweep(recs)
    except ValueError:
        verdict = None  # no sweep records in this artifact
    if verdict is not None:
        print("\n=== checkpoint-interval planning: measured goodput vs "
              "the Daly model (analysis/goodput.py) ===")
        rc = 0 if verdict["in_band"] else 1
        goodput_mod.report(records, verdict=verdict)
        with open(args.out_dir / "goodput_verdict.json", "w") as f:
            json.dump(verdict, f, indent=1)

    bw = bandwidth_summary(recs)
    if not bw.empty:
        print("\n=== bandwidth under fault: faulted runs busbw-refused, "
              "clean runs keep their figures ===")
        cols = ["section", "collective", "bound", "time_us",
                "algbw_GBps", "busbw_GBps", "straggler_amp"]
        print(bw[cols].to_string(
            index=False, float_format=lambda v: f"{v:10.3f}"))
        bw.to_csv(args.out_dir / "fault_bandwidth_summary.csv",
                  index=False)
    print(f"\nwrote {records} and "
          f"{args.out_dir}/fault_bandwidth_summary.csv")
    return rc


def report(args, records: Path) -> None:
    import pandas as pd

    from dlnetbench_tpu.analysis import plots
    from dlnetbench_tpu.analysis.bandwidth import bandwidth_summary
    from dlnetbench_tpu.metrics.parser import load_records, \
        records_to_dataframe

    recs = load_records(records)
    df = records_to_dataframe(recs)

    # honesty note (VERDICT r3 #8): hier points fall back to the HOST
    # executor when no usable TPU plugin is present — those numbers
    # describe a virtual mesh on this machine's CPU, not TPU devices
    hier_hosted = sum(1 for r in recs
                      if r.get("global", {}).get("pjrt_executor") == "host")
    if hier_hosted:
        print(f"note: {hier_hosted}/{len(recs)} study points ran the "
              f"device path on the HOST executor (virtual mesh, no TPU "
              f"plugin) — fabric numbers are loopback, not ICI/DCN")

    # --- north-star table: iter time + effective bus GB/s per collective
    per_point = []
    for rec in recs:
        s = bandwidth_summary([rec])
        if s.empty:
            continue
        g = rec.get("global", {})
        # bandwidth_summary already carries model; add proxy + world size
        s.insert(0, "proxy", g.get("variables", {}).get("proxy",
                                                        rec.get("section")))
        s.insert(1, "world", len(rec.get("ranks", [])))
        s.insert(2, "sched", g.get("schedule", ""))
        per_point.append(s)
    if per_point:
        bw = pd.concat(per_point, ignore_index=True)
        # one line per (proxy, model, world, collective): the per-iteration
        # exposed time and the standard busbw figure
        # 'bound' rides along: "lower" rows (e.g. the native engine's
        # middle-stage pp_comm) must stay labeled in the table and CSV
        cols = ["proxy", "model", "world", "sched", "collective",
                "group_size", "bound", "time_us", "algbw_GBps",
                "busbw_GBps"]
        bw = (bw.groupby(cols[:7], as_index=False)[cols[7:]].mean()
              .sort_values(["proxy", "model", "world", "sched"]))[cols]
        print("\n=== effective bandwidth per collective "
              "(mean over ranks/runs) ===")
        print(bw.to_string(index=False,
                           float_format=lambda v: f"{v:10.2f}"))
        bw.to_csv(args.out_dir / "bandwidth_summary.csv", index=False)

    # --- runtime summary per study point (schedule column distinguishes
    # the hybrid_2d gpipe/1f1b/zb comparison points)
    group_cols = ["proxy", "model", "world_size"]
    if "schedule" in df:
        group_cols.append("schedule")
    summary = (df.groupby(group_cols, dropna=False)["runtime"]
               .mean().rename("runtime_us").reset_index())
    print("\n=== mean iteration runtime (us) ===")
    print(summary.to_string(index=False,
                            float_format=lambda v: f"{v:12.1f}"))

    # --- plots
    import matplotlib
    matplotlib.use("Agg")

    dp = df[df["proxy"] == "dp"]
    scaling = dp[dp["num_buckets"] == 4]
    if not scaling.empty:
        ax = plots.plot_runtime_scaling(scaling, group_by="model")
        ax.figure.savefig(args.out_dir / "dp_runtime_scaling.png", dpi=120)
    full = dp[dp["world_size"] == dp["world_size"].max()]
    if not full.empty:
        ax = plots.plot_barrier_scatter_by_bucket(full)
        ax.figure.savefig(args.out_dir / "dp_barrier_by_bucket.png", dpi=120)
    # cross-proxy exposure Pareto: mean runtime vs mean exposed comm.
    # Exposed-comm column differs per proxy; take the max-information one
    # present per proxy row (barrier_time for dp/fsdp, dp_comm_time for
    # the hybrids, ring/a2a wait for the sequence proxies).
    exposed_cols = [c for c in ("barrier_time", "dp_comm_time",
                                "ring_wait_time", "a2a_time") if c in df]
    if exposed_cols:
        exp = df.assign(exposed=df[exposed_cols].bfill(axis=1)
                        .iloc[:, 0]).dropna(subset=["exposed"])
        if not exp.empty:
            ax = plots.plot_pareto(exp, x="runtime", y="exposed",
                                   group_by="proxy")
            ax.figure.savefig(args.out_dir / "pareto_proxies.png", dpi=120)
    print(f"\nwrote {args.out_dir}/{{bandwidth_summary.csv,"
          f"dp_runtime_scaling,dp_barrier_by_bucket,pareto_proxies}}.png")


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--out_dir", type=Path, default=Path("/tmp/pod_study"))
    ap.add_argument("--devices", type=int, default=8,
                    help="world size (CPU: virtual device count)")
    ap.add_argument("--platform", default="cpu", choices=("cpu", "tpu"),
                    help="cpu = virtual mesh dev box; tpu = real slice")
    ap.add_argument("--tier", default="jax", choices=("jax", "native"),
                    help="jax = python CLI over the device mesh; native = "
                         "the C++17 binaries (threaded shm fabric)")
    ap.add_argument("--backend", default="shm",
                    choices=("shm", "pjrt-hier"),
                    help="native tier fabric: shm (threaded, one process) "
                         "or pjrt-hier (--procs OS processes, per-process "
                         "executor + TCP DCN combine — the multi-host "
                         "device path; records merged per point)")
    ap.add_argument("--procs", type=int, default=2,
                    help="pjrt-hier: number of OS processes composing the "
                         "DCN mesh; worlds that do not divide evenly get "
                         "the balanced uneven layout (first world%%procs "
                         "processes host one extra rank)")
    ap.add_argument("--fault", action="store_true",
                    help="run the fault-injection study instead of the "
                         "proxy grid: a straggler point (fsdp/shm, "
                         "measured amplification), a rank-crash point "
                         "(dp/tcp, shrink policy, detection/recovery + "
                         "degraded merge), a drop point (dp/tcp, retry "
                         "policy with backoff counts), a preempt+rejoin "
                         "point (dp/tcp, graceful eviction, full world "
                         "restored, rejoin_ms), and the seeded "
                         "goodput-vs-checkpoint-interval sweep the Daly "
                         "model is validated against (python tier, "
                         "analysis/goodput.py) — one records.jsonl "
                         "artifact; docs/RESILIENCE.md")
    ap.add_argument("--serving", action="store_true",
                    help="run the serving latency-vs-load study instead "
                         "of the proxy grid: capacity calibration, an "
                         "offered-load sweep (fractions of capacity x "
                         "arrival seeds, p99/goodput-at-SLO bands, "
                         "saturation-knee verdict) and a straggler-"
                         "composed point proving fault plans inflate "
                         "serving p99 — one records.jsonl artifact "
                         "(docs/SERVING.md)")
    ap.add_argument("--disagg", action="store_true",
                    help="with --serving: run the sweep over the "
                         "DISAGGREGATED prefill/decode engine "
                         "(ISSUE 16; 2 capacity ranks split 1 prefill "
                         "+ 1 decode, KV pages migrating in the "
                         "stored dtype) — the serving_summary carries "
                         "the migration_* columns; run once without "
                         "and once with into different --out_dir for "
                         "the Pareto comparison (docs/studies/"
                         "disagg_r17 automates exactly that)")
    ap.add_argument("--fleet", action="store_true",
                    help="with --serving: run the sweep over a "
                         "two-replica FLEET (ISSUE 18; seeded p2c "
                         "router over independent engines, each with "
                         "its own page pool) — the serving_summary "
                         "carries the fleet_* columns; compare "
                         "against a plain --serving run into a "
                         "different --out_dir for the equal-chips "
                         "question (docs/studies/fleet_r18 holds the "
                         "committed routing/autoscale/crash bars)")
    ap.add_argument("--kv_density", action="store_true",
                    help="run the serving-density study instead of the "
                         "proxy grid (ISSUE 12): dense vs int8 vs fp8 "
                         "paged-KV at equal pool bytes (admitted "
                         "concurrency + goodput-at-SLO + decode-parity "
                         "bars) and a prefix-heavy shared-system-"
                         "prompt plan with sharing off/on (lossless, "
                         "hit-rate/bytes-saved, TTFT deltas) — "
                         "generation FAILS unless the acceptance bars "
                         "hold (docs/SERVING.md 'Cache density')")
    ap.add_argument("--congest", action="store_true",
                    help="run a dp_loop congestor pair (native TCP fabric) "
                         "for the duration of the sweep — sustained "
                         "background frames sharing the DCN transport "
                         "path, the reference's _loop interference shape "
                         "(Makefile.common:96-109) composed with the "
                         "hier study; the study README/json records it")
    ap.add_argument("--congest_model", default="gpt2_l_16_bfloat16")
    ap.add_argument("--models", default=f"{DENSE},{MOE}",
                    help="comma-separated stats-file names")
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--size_scale", type=float, default=1e-4,
                    help="buffer shrink factor (CPU default)")
    ap.add_argument("--time_scale", type=float, default=1e-4,
                    help="burn-time shrink factor (CPU default)")
    ap.add_argument("--full_scale", action="store_true",
                    help="real buffer sizes and burn times (pod runs)")
    ap.add_argument("--report_only", action="store_true",
                    help="skip the sweep; re-analyze an existing "
                         "records.jsonl in --out_dir")
    args = ap.parse_args()
    if args.disagg and args.fleet:
        ap.error("--disagg and --fleet are different serving arms — "
                 "run them into separate --out_dir (the engine refuses "
                 "the composition too)")
    if args.backend == "pjrt-hier" and args.tier != "native":
        ap.error("--backend pjrt-hier applies to --tier native (the jax "
                 "tier composes ICI x DCN through jax.distributed instead)")
    if args.tier == "native" and args.platform != "cpu":
        ap.error("--tier native runs the C++ binaries on the threaded shm "
                 "fabric (host CPU); --platform tpu applies only to the "
                 "jax tier. For TPU runs on the native tier use the "
                 "binaries' --backend pjrt directly.")

    args.out_dir.mkdir(parents=True, exist_ok=True)
    records = args.out_dir / "records.jsonl"
    failed = 0
    if args.kv_density:
        failed = run_kv_density_study(args.out_dir)
        if failed:
            print("\nkv-density study failed its acceptance bars",
                  file=sys.stderr)
        return 1 if failed else 0
    if args.serving:
        if not args.report_only:
            records.unlink(missing_ok=True)
            failed = run_serving_plan(args, records)
        failed += serving_report(args, records)
        if failed:
            print(f"\n{failed} serving study point(s) failed",
                  file=sys.stderr)
        return 1 if failed else 0
    if args.fault:
        if not args.report_only:
            records.unlink(missing_ok=True)
            failed = run_fault_plan(args, records)
        failed += fault_report(args, records)
        if failed:
            print(f"\n{failed} fault study point(s) failed",
                  file=sys.stderr)
        return 1 if failed else 0
    if not args.report_only:
        records.unlink(missing_ok=True)
        # a stale marker from an earlier --congest sweep into the same
        # dir would mislabel THIS solo run's tables
        (args.out_dir / "CONGESTED").unlink(missing_ok=True)
        plan = build_plan([m for m in args.models.split(",") if m],
                          args.devices)
        congestors = _start_congestors(args) if args.congest else []
        try:
            failed = run_plan(plan, args, records)
        finally:
            from dlnetbench_tpu.utils.congest import kill_group
            kill_group(congestors)
    report(args, records)
    if failed:
        print(f"\n{failed} study point(s) failed", file=sys.stderr)
    return 1 if failed else 0


def _start_congestors(args) -> list:
    """A dp_loop pair over the native TCP fabric, running for the whole
    sweep: its frames share the DCN transport path (loopback here, real
    links on a cluster) with every hier point's combine legs — the
    reference's `_loop` interference composition.  Study output marks
    the run so congested tables are never mistaken for solo ones."""
    from dlnetbench_tpu.utils import congest
    from dlnetbench_tpu.utils.native_build import native_bin as _locate

    repo = Path(__file__).resolve().parent.parent
    procs = congest.launch_pair_retry(
        _locate(str(repo)), "dp_loop", args.congest_model, repo,
        args.time_scale, max(args.size_scale * 10, 1e-3),
        extra=["--num_buckets", "4"])
    (args.out_dir / "CONGESTED").write_text(
        f"sweep ran with a dp_loop x2 congestor pair "
        f"(model {args.congest_model}) sharing the DCN transport\n")
    print("congestor pair running (dp_loop x2 over tcp)", flush=True)
    return procs


if __name__ == "__main__":
    raise SystemExit(main())
