"""Proxy runner CLI — the counterpart of the reference's per-proxy binaries.

The reference builds one binary per proxy with an easyargs CLI: positional
``model`` (stats-file name), grid dims, plus ``-w`` warmups, ``-r`` runs,
``-d`` device list, ``-m`` min-exectime (reference
cpp/data_parallel/dp.cpp:108-124).  Here one entry point hosts all proxies:

    python -m dlnetbench_tpu.cli dp --model gpt2_l_16_bfloat16 --num_buckets 8
    python -m dlnetbench_tpu.cli fsdp --model llama3_8b_16_bfloat16 \
        --num_units 8 --sharding_factor 4
    python -m dlnetbench_tpu.cli hybrid_3d --model llama3_70b_16_bfloat16 \
        --num_stages 4 --num_microbatches 8 --tp 2

Rebuild extras: ``--size_scale`` / ``--time_scale`` shrink buffers and burn
times so any schedule runs on a dev box; ``--loop`` is the PROXY_LOOP
congestor mode; ``--out`` appends the JSON record to a file instead of
stdout.
"""
from __future__ import annotations

import argparse
import sys

from dlnetbench_tpu.core.model_card import arch_name_from_stats_name, load_model_card
from dlnetbench_tpu.core.model_stats import load_model_stats
from dlnetbench_tpu.metrics import spans
from dlnetbench_tpu.metrics.emit import emit_result
from dlnetbench_tpu.proxies.base import ProxyConfig, run_proxy


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--model", required=True,
                   help="stats-file name, e.g. gpt2_l_16_bfloat16")
    p.add_argument("-w", "--warmup", type=int, default=3)
    p.add_argument("-r", "--runs", type=int, default=5)
    p.add_argument("-m", "--min_exectime", type=float, default=0.0,
                   help="seconds; when set, runs are estimated from warmup")
    p.add_argument("-k", "--reps_per_fence", type=int, default=1,
                   help="K-chained fencing: K step dispatches per host "
                        "fence, so dispatch + fence latency amortize over K "
                        "iterations instead of biasing every sample "
                        "(utils/timing.py time_chain); 1 = fence per rep "
                        "(reference parity)")
    p.add_argument("--loop", action="store_true",
                   help="run the schedule forever (congestor mode)")
    p.add_argument("-d", "--devices", default="0",
                   help="device selection: a count N (first N devices, "
                        "0 = all) or an explicit index list like 0,2,3 "
                        "(the reference -d flag, utils.hpp:62-71)")
    p.add_argument("--platform", default=None,
                   help="force a jax platform (e.g. 'cpu'); combine with "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                        "for a virtual N-device mesh on a dev box")
    p.add_argument("--buffer_dtype", default="float32",
                   choices=["float32", "bfloat16", "float8", "int8",
                            "stats"],
                   help="device-buffer element type; 'stats' follows the "
                        "stat file's Dtype field (the reference's "
                        "compile-time PROXY_FLOAT8 / bf16 selection, "
                        "data_types.hpp:36-79, made a runtime switch). "
                        "float32 default keeps CPU-mesh runs universal")
    p.add_argument("--size_scale", type=float, default=1.0)
    p.add_argument("--time_scale", type=float, default=1.0)
    p.add_argument("--stats_dir", default=None)
    p.add_argument("--out", default=None, help="append JSON record to file")
    p.add_argument("--no_topology", action="store_true",
                   help="skip the startup fabric-topology graph")
    p.add_argument("--profile", action="store_true",
                   help="after the timed runs, trace one schedule iteration "
                        "with the JAX profiler and attach per-collective "
                        "device-op durations to the record (the cross-check "
                        "for the decomposition timers, SURVEY.md 7.3)")
    p.add_argument("--trace-out", "--trace_out", dest="trace_out",
                   default=None, metavar="PATH",
                   help="write ONE merged Chrome/Perfetto trace: host "
                        "harness spans (build/compile/warmup/timed/fence) "
                        "on top, the device-op timeline of one profiled "
                        "schedule iteration below, collectives colored by "
                        "kind (metrics/spans.py; docs/OBSERVABILITY.md)")
    p.add_argument("--tag", action="append", default=[], metavar="KEY=VALUE",
                   help="attach a variable to the emitted record (the "
                        "analysis layer hoists it to a DataFrame column; "
                        "the sweep driver tags each grid point this way — "
                        "the role of sbatchman job.variables in the "
                        "reference, plots/parser.py:238)")
    p.add_argument("--fault", default=None, metavar="PLAN",
                   help="JSON fault plan (inline or @path; "
                        "dlnetbench_tpu/faults/plan.py schema, shared "
                        "with the native binaries): delay/jitter/crash/"
                        "preempt/rejoin events injected at step "
                        "boundaries with deterministic triggers; the "
                        "record stamps the plan + recovery columns "
                        "(docs/RESILIENCE.md)")
    p.add_argument("--fault_policy", default=None,
                   choices=["fail_fast", "retry", "shrink"],
                   help="degradation policy on a scripted failure: "
                        "fail_fast (crash propagates), retry (bounded "
                        "backoff, same world), shrink (rebuild on the "
                        "survivor devices and finish degraded); "
                        "default: the plan's own policy")
    p.add_argument("--checkpoint_dir", default=None, metavar="DIR",
                   help="enable periodic snapshot checkpointing of the "
                        "proxy's state during a --fault run "
                        "(utils/checkpoint.py SnapshotCheckpointer): "
                        "saves every --checkpoint_every steps, restore-"
                        "from-latest priced into recovery on a crash/"
                        "preempt, lost work and goodput stamped into "
                        "the record (docs/RESILIENCE.md)")
    p.add_argument("--checkpoint_every", type=int, default=4,
                   help="harness steps between saves (plan step units, "
                        "warmup included; default 4)")
    p.add_argument("--checkpoint_mode", default="async",
                   choices=["stall", "async"],
                   help="stall: the whole durable write rides the timed "
                        "critical path; async: only the device sync + "
                        "host snapshot stays in-window (default)")
    p.add_argument("--checkpoint_backend", default="auto",
                   choices=["auto", "orbax", "npz"],
                   help="auto prefers orbax, falls back to the pure-"
                        "numpy npz backend")
    p.add_argument("--telemetry", action="store_true",
                   help="continuous telemetry (metrics/telemetry.py): "
                        "record a fixed-capacity flight ring of "
                        "per-step samples and run the anomaly engine "
                        "(watchdog stall / fault / SLO breach / "
                        "band-aware step-time change); the record "
                        "stamps telemetry + anomalies blocks and "
                        "anomaly dumps land in --flight-dir.  Also "
                        "enabled by DLNB_TELEMETRY=1 "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--flight-dir", "--flight_dir", dest="flight_dir",
                   default=None, metavar="DIR",
                   help="where anomaly-triggered flight_<trigger>.json "
                        "ring dumps land (default: DLNB_FLIGHT_DIR; "
                        "no dir = anomalies recorded without dumps)")


def _telemetry_enable(args) -> bool:
    """Install the flight recorder for this run (ISSUE 14): the
    ``--telemetry``/``--flight-dir`` flags or the ``DLNB_TELEMETRY``
    env channel.  Returns True when THIS call enabled it (the caller
    then owns the disable — an already-active recorder, e.g. a test
    harness's, is never torn down here)."""
    from dlnetbench_tpu.metrics import telemetry
    if telemetry.is_enabled():
        return False
    if getattr(args, "telemetry", False) \
            or getattr(args, "flight_dir", None):
        telemetry.enable(dump_dir=getattr(args, "flight_dir", None))
        return True
    return telemetry.enable_from_env() is not None


def _configure_jax(args) -> None:
    """``--platform`` selects the jax platform before any backend use
    (JAX_PLATFORMS is jax's own variable and needs no help) and the
    compile cache is placed before the first compile."""
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)
    from dlnetbench_tpu.core.executor import enable_persistent_cache
    enable_persistent_cache()


def _cfg(args) -> ProxyConfig:
    if args.reps_per_fence < 1:
        raise SystemExit("--reps_per_fence must be >= 1")
    return ProxyConfig(warmup=args.warmup, runs=args.runs,
                       min_exectime_s=args.min_exectime, loop=args.loop,
                       size_scale=args.size_scale, time_scale=args.time_scale,
                       reps_per_fence=args.reps_per_fence)


def _add_pipeline(p: argparse.ArgumentParser) -> None:
    """Flags shared by the three pipeline (hybrid) proxies."""
    _add_common(p)
    p.add_argument("--num_stages", type=int, required=True)
    p.add_argument("--num_microbatches", type=int, required=True)
    p.add_argument("--schedule", choices=["gpipe", "1f1b", "zb"],
                   default="gpipe",
                   help="pipeline schedule (gpipe = reference parity; "
                        "1f1b = interleaved fwd/bwd and zb = ZB-H1 "
                        "zero-bubble, rebuild extras)")


def _devices(args, parser):
    import jax
    devs = jax.devices()
    spec = str(args.devices).strip()
    if "," in spec:  # explicit index list: arbitrary subset, in order
        try:
            indices = [int(tok) for tok in spec.split(",") if tok.strip()]
        except ValueError:
            parser.error(f"--devices wants N or a list like 0,2,3, "
                         f"got {spec!r}")
        bad = [i for i in indices if not 0 <= i < len(devs)]
        if bad:
            parser.error(f"--devices indices {bad} out of range "
                         f"(have {len(devs)} devices)")
        if len(set(indices)) != len(indices):
            parser.error(f"--devices has duplicate indices: {spec}")
        return [devs[i] for i in indices]
    try:
        count = int(spec)
    except ValueError:
        parser.error(f"--devices wants N or a list like 0,2,3, got {spec!r}")
    if count < 0 or count > len(devs):
        parser.error(f"--devices {count} out of range "
                     f"(have {len(devs)} devices)")
    return devs[:count] if count else devs


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="dlnetbench_tpu", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = parser.add_subparsers(dest="proxy", required=True)

    p_dp = sub.add_parser("dp", help="bucketed data-parallel allreduce")
    _add_common(p_dp)
    p_dp.add_argument("--num_buckets", type=int, required=True)

    p_fsdp = sub.add_parser("fsdp", help="ZeRO-3 allgather/reduce-scatter")
    _add_common(p_fsdp)
    p_fsdp.add_argument("--num_units", type=int, required=True)
    p_fsdp.add_argument("--sharding_factor", type=int, default=0,
                        help="0 = whole world (no replicas)")

    p_2d = sub.add_parser("hybrid_2d", help="DP + GPipe pipeline")
    _add_pipeline(p_2d)
    p_2d.add_argument("--dp", type=int, default=0, help="0 = infer from devices")

    p_3d = sub.add_parser("hybrid_3d", help="DP + PP + tensor parallel")
    _add_pipeline(p_3d)
    p_3d.add_argument("--tp", type=int, required=True)
    p_3d.add_argument("--dp", type=int, default=0)

    p_moe = sub.add_parser("hybrid_3d_moe", help="DP + PP + expert parallel")
    _add_pipeline(p_moe)
    p_moe.add_argument("--num_expert_shards", type=int, required=True)
    p_moe.add_argument("--dp", type=int, default=0)

    p_ring = sub.add_parser("ring_attention",
                            help="ring (context-parallel) attention proxy")
    _add_common(p_ring)
    p_ring.add_argument("--sp", type=int, required=True)
    p_ring.add_argument("--dp", type=int, default=0)
    p_ring.add_argument("--max_layers", type=int, default=0,
                        help="cap replayed layers (0 = the model's full "
                             "depth); shortens dev-box runs")

    p_uly = sub.add_parser("ulysses", help="Ulysses sequence-parallel proxy")
    _add_common(p_uly)
    p_uly.add_argument("--sp", type=int, required=True)
    p_uly.add_argument("--dp", type=int, default=0)
    p_uly.add_argument("--max_layers", type=int, default=0,
                       help="cap replayed layers (0 = full depth)")

    _add_serve(sub.add_parser(
        "serve", help="serving tier: paged-KV decode under continuous "
                      "batching + an open-loop arrival plan "
                      "(docs/SERVING.md)"))

    args = parser.parse_args(argv)
    if args.proxy == "serve":
        tele_on = _telemetry_enable(args)
        try:
            return _run_serve(args, parser)
        finally:
            if tele_on:
                from dlnetbench_tpu.metrics import telemetry
                telemetry.disable()
    cfg = _cfg(args)

    if getattr(args, "max_layers", 0) < 0:
        parser.error("--max_layers must be >= 0")

    # validate tags before any expensive backend/bundle work; scheduler
    # identity (SLURM/JobSet/multislice env, DLNB_TAG_*) is collected
    # automatically and explicit --tag flags override it
    from dlnetbench_tpu.metrics.emit import scheduler_variables
    variables = scheduler_variables()
    for tag in args.tag:
        key, sep, value = tag.partition("=")
        if not sep or not key:
            parser.error(f"--tag wants KEY=VALUE, got {tag!r}")
        variables[key] = value

    _configure_jax(args)

    try:
        stats = load_model_stats(args.model, args.stats_dir)
    except FileNotFoundError as e:
        parser.error(str(e))
    devices = _devices(args, parser)

    # startup fabric graph (reference print_topology_graph at every proxy's
    # startup, cpp/netcommunicators.hpp:142); stderr keeps stdout pure JSON
    if not args.no_topology:
        from dlnetbench_tpu.utils.topology import print_topology
        print_topology(devices, stream=sys.stderr)

    import jax.numpy as jnp
    dtype_name = stats.dtype if args.buffer_dtype == "stats" \
        else args.buffer_dtype
    jnp_dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                  "float8": jnp.float8_e4m3fn, "int8": jnp.int8}
    if dtype_name not in jnp_dtypes:
        parser.error(f"stat file dtype {dtype_name!r} has no device buffer "
                     f"mapping; supported: {sorted(jnp_dtypes)}")
    dtype = jnp_dtypes[dtype_name]

    # span tracing covers the WHOLE config — build (with its compile
    # spans), warmup, timed runs, the profiled iteration — so the merged
    # timeline answers "where did this run's wall-clock go"
    tracer = spans.enable() if args.trace_out else None
    tele_on = _telemetry_enable(args)
    try:
        return _run_measured(args, parser, stats, cfg, devices, dtype,
                             dtype_name, variables, tracer)
    finally:
        # a failure anywhere in the run (backend error, parser.error's
        # SystemExit) must not leak the process-global tracer into later
        # runs in this process (sweep's in-process mode, test harnesses)
        if spans.is_enabled():
            spans.disable()
        if tele_on:
            from dlnetbench_tpu.metrics import telemetry
            telemetry.disable()


def _run_measured(args, parser, stats, cfg, devices, dtype, dtype_name,
                  variables, tracer) -> int:
    if args.checkpoint_dir and not args.fault:
        # knowable from the args alone: refuse BEFORE the mesh build +
        # AOT compile, not minutes into it
        parser.error("--checkpoint_dir prices checkpointing inside a "
                     "faulted run (faults/policy.py run_faulted) — it "
                     "needs --fault; a clean run has no recovery to "
                     "measure")
    if args.checkpoint_dir and args.checkpoint_every < 1:
        parser.error("--checkpoint_every must be >= 1 step")
    try:
        with spans.span("build", proxy=args.proxy, model=args.model):
            bundle = _build_bundle(args, parser, stats, cfg, devices, dtype)
    except ImportError as e:
        parser.error(f"proxy {args.proxy!r} is not implemented yet ({e})")
    except ValueError as e:
        parser.error(str(e))  # configuration-invariant violations
    bundle.global_meta["buffer_dtype"] = dtype_name
    if variables:
        bundle.global_meta["variables"] = variables
    if args.fault:
        from dlnetbench_tpu.faults.plan import FaultPlan
        from dlnetbench_tpu.faults.policy import CheckpointPolicy, \
            run_faulted
        # usage errors (malformed/invalid plan, unreadable @file,
        # plan/config conflicts) report as CLI errors; failures INSIDE
        # the measured run must keep their tracebacks — masking a JAX
        # error as 'bad --fault flag' would bury the real cause
        try:
            plan = FaultPlan.loads(args.fault)
            if args.fault_policy:
                plan.policy = args.fault_policy
            plan.validate()
        except (ValueError, OSError, KeyError) as e:
            parser.error(f"--fault: {e}")
        try:
            plan.check_config(cfg)
        except ValueError as e:
            parser.error(str(e))

        def rebuild(survivors):
            # shrink: the proxy rebuilds over the survivor devices
            # (recompile cost lands in recovery_ms, where it belongs);
            # rank ids keep their original numbering via the record's
            # degraded_world
            devs = _devices(args, parser)
            return _build_bundle(args, parser, stats, cfg,
                                 [devs[i] for i in survivors], dtype)

        ckpt = None
        if args.checkpoint_dir:
            ckpt = CheckpointPolicy(dir=args.checkpoint_dir,
                                    every=args.checkpoint_every,
                                    mode=args.checkpoint_mode,
                                    backend=args.checkpoint_backend)
        with spans.span("faulted_run", proxy=args.proxy,
                        policy=plan.policy):
            result = run_faulted(args.proxy, bundle, cfg, plan,
                                 rebuild=rebuild, world=len(devices),
                                 checkpoint=ckpt)
    else:
        result = run_proxy(args.proxy, bundle, cfg)

    # the profile/trace channels are AUXILIARY to the record: the timed
    # runs above are already measured, and no trace failure may cost
    # them — every step below degrades to a stderr note, never an abort
    device_events = None
    if args.profile or args.trace_out:
        # one schedule iteration under the JAX profiler serves BOTH
        # channels: per-collective stats for the record (--profile) and
        # raw device-op events for the merged timeline (--trace-out)
        try:
            import tempfile
            import jax
            from dlnetbench_tpu.metrics import profiling
            from dlnetbench_tpu.utils.timing import time_callable
            trace_dir = tempfile.mkdtemp(prefix="dlnb_prof_")
            with spans.span("profile", proxy=args.proxy):
                with jax.profiler.trace(trace_dir):
                    # fenced inside the trace window: the profiler
                    # context must not close before the device work
                    # finishes
                    time_callable(bundle.full, reps=1)
            device_events = profiling.load_trace_events(trace_dir)
            if args.profile:
                result.global_meta["profile"] = \
                    profiling.collective_stats(device_events)
                # per-op channel: the attribution block's top_ops
                # prefers this over the kind-level profile summary
                result.global_meta["device_top_ops"] = \
                    profiling.top_device_ops(device_events)
        except Exception as e:
            print(f"profile/trace capture failed "
                  f"({type(e).__name__}: {e}); record unaffected",
                  file=sys.stderr)
    if tracer is not None:
        spans.disable()
        try:
            # flight-recorder counter tracks ride the same timeline
            # (ISSUE 14): the full resident ring + anomaly instants
            from dlnetbench_tpu.metrics import telemetry
            rec_now = telemetry.current()
            extra = None
            if rec_now is not None:
                extra = spans.telemetry_counter_events(
                    rec_now.telemetry_block(last=rec_now.capacity),
                    rec_now.anomalies_block())
            spans.write_chrome_trace(args.trace_out, tracer,
                                     device_events, extra_events=extra)
            print(f"merged host+device trace -> {args.trace_out}",
                  file=sys.stderr)
        except OSError as e:
            print(f"trace-out write failed ({e}); record unaffected",
                  file=sys.stderr)
    record = emit_result(result, path=args.out)
    # one-line bottleneck verdict on stderr (stdout stays pure JSON):
    # the record's attribution block (metrics/emit.py joins cost
    # analysis + roofline + decomposition timers + transport peak —
    # analysis/attribution.py), rendered so a terminal run answers
    # "what bound this?" without an analysis pass
    attr = record.get("global", {}).get("attribution")
    if attr:
        fr = attr.get("fractions", {})
        print("bottleneck: " + attr.get("bound", "?")
              + " (" + " ".join(f"{k}={fr.get(k, 0.0):.2f}"
                                for k in ("compute", "hbm",
                                          "comm_exposed", "host"))
              + ")", file=sys.stderr)
    return 0


def _add_serve(p: argparse.ArgumentParser) -> None:
    """The serving tier's own flag set (no stats file, no proxy grid —
    the workload is an arrival plan over a decode-shaped model)."""
    p.add_argument("--arrival", required=True, metavar="PLAN",
                   help="JSON arrival plan (inline or @path; "
                        "serving/arrivals.py schema): poisson/bursty/"
                        "replay traffic with seeded splitmix64 draws — "
                        "a committable artifact like a fault plan")
    p.add_argument("--slots", type=int, default=4,
                   help="decode slots = max continuous batch")
    p.add_argument("--page_size", type=int, default=8,
                   help="tokens per KV page")
    p.add_argument("--num_pages", type=int, default=128,
                   help="physical KV pages shared by all slots")
    p.add_argument("--max_seq_len", type=int, default=128,
                   help="per-request cap (prompt + output); must be a "
                        "multiple of --page_size")
    p.add_argument("--prefill", default="separate",
                   choices=["separate", "inline"],
                   help="separate: drain the whole prompt at admit "
                        "time; inline: one chunk per engine step, "
                        "interleaved with decode")
    p.add_argument("--prefill_chunk", type=int, default=16)
    p.add_argument("--slo_ttft_ms", type=float, default=500.0)
    p.add_argument("--slo_tpot_ms", type=float, default=200.0)
    p.add_argument("--world", type=int, default=1,
                   help="capacity ranks (the fault-shrink unit: a "
                        "crashed rank takes slots/world decode slots "
                        "down with it)")
    p.add_argument("--kv_shard", type=int, default=1,
                   help=">1: shard paged attention along GQA KV heads "
                        "over this many devices via shard_map "
                        "(SNIPPETS [3] recipe)")
    p.add_argument("--attn_impl", default="auto",
                   choices=["auto", "pallas", "gather"],
                   help="decode attention path: Pallas paged_attention "
                        "kernel (TPU) vs dense gather fallback; auto "
                        "picks by backend")
    p.add_argument("--cache_dtype", default="bf16",
                   choices=["bf16", "int8", "fp8"],
                   help="paged-KV pool storage (ISSUE 12): bf16 = "
                        "unquantized (pools in the model dtype, the "
                        "quant path not even built); int8/fp8 store "
                        "quantized pages with per-page-per-head f32 "
                        "scales — ~2x the pages per pool byte of a "
                        "bf16 cache (~4x of the f32 CPU-mesh pools) "
                        "at a stated decode-parity tolerance "
                        "(docs/SERVING.md 'Cache density')")
    p.add_argument("--disaggregate", action="store_true",
                   help="disaggregated prefill/decode (ISSUE 16): "
                        "split --world into a prefill mesh and a "
                        "decode mesh on disjoint devices; finished "
                        "prompts' KV pages migrate decode-ward in "
                        "their stored dtype (migration bytes/ms/"
                        "overlap stamped in the record; docs/"
                        "SERVING.md 'Disaggregated prefill/decode')")
    p.add_argument("--prefill_ranks", type=int, default=1,
                   help="prefill-mesh ranks (with --disaggregate; "
                        "prefill_ranks + decode_ranks = --world)")
    p.add_argument("--decode_ranks", type=int, default=1,
                   help="decode-mesh ranks (with --disaggregate)")
    p.add_argument("--migration_chunk_pages", type=int, default=8,
                   help="KV pages per migration chunk transfer "
                        "(the PR-4 chunk-loop knob on the page wire)")
    p.add_argument("--prefix_sharing", action="store_true",
                   help="cross-request prefix sharing: requests whose "
                        "prompts share a prefix with a resident "
                        "sequence map their block tables onto the "
                        "same physical pages (refcounts + copy-on-"
                        "write; admission charges only unshared "
                        "pages, the shared prefix skips prefill); "
                        "lossless — record stamps prefix_hit_rate/"
                        "prefix_bytes_saved")
    p.add_argument("--multi_step_n", type=int, default=1,
                   help="decode steps fused per host dispatch "
                        "(ISSUE 11): >1 runs a device-resident "
                        "lax.while_loop with slot state on device, "
                        "host sync at admission boundaries only; 1 = "
                        "the classic per-token engine (docs/SERVING.md "
                        "'The multi-step loop')")
    p.add_argument("--no_adaptive_n", action="store_true",
                   help="disable the adaptive trip-count cap "
                        "(shortest-remaining-output + queue pressure "
                        "— the TTFT guard); the fused loop then "
                        "always runs the full N")
    p.add_argument("--speculative", action="store_true",
                   help="self-drafting speculative decode inside the "
                        "fused loop: draft k, verify in one batched "
                        "target pass, accept on device — lossless "
                        "under greedy; acceptance rate rides the "
                        "record")
    p.add_argument("--spec_k", type=int, default=4,
                   help="draft tokens per verify round")
    p.add_argument("--drafter", default="ngram",
                   choices=["ngram", "truncated"],
                   help="ngram: per-slot bigram table on device; "
                        "truncated: first --drafter_layers layers of "
                        "the target + shared head")
    p.add_argument("--drafter_layers", type=int, default=1,
                   help="truncated drafter depth (< --layers)")
    # seeded sampling + constrained decode (ISSUE 19).  Draws are
    # keyed by (sample_seed, request uid, stream position) — stateless,
    # so N-step fusing, adaptive N, and crash-shrink re-queue all
    # replay bit-identical tokens (docs/SERVING.md 'Sampling,
    # speculation & constrained decode')
    p.add_argument("--temperature", type=float, default=0.0,
                   help="sampling temperature: 0 = greedy (the "
                        "default, byte-identical to pre-sampling "
                        "engines); >0 turns on on-device seeded "
                        "sampling")
    p.add_argument("--sample_top_k", type=int, default=0,
                   help="keep only the k highest-probability tokens "
                        "before drawing (0 = off; needs "
                        "--temperature > 0; --top_k is the MoE "
                        "experts-per-token knob)")
    p.add_argument("--top_p", type=float, default=1.0,
                   help="nucleus sampling mass in (0, 1]: keep the "
                        "smallest prefix of the sorted distribution "
                        "whose mass reaches p (1.0 = off; needs "
                        "--temperature > 0)")
    p.add_argument("--sample_seed", type=int, default=0,
                   help="the draw-key seed (replay identity: records "
                        "under different seeds refuse to merge)")
    p.add_argument("--grammar", default="", choices=["", "json"],
                   help="constrained decode: mask generated tokens to "
                        "a grammar automaton (json = depth-3 bracket "
                        "grammar over token classes); composes with "
                        "--speculative (out-of-grammar drafts "
                        "auto-reject) and --prefix_sharing")
    p.add_argument("--num_experts", type=int, default=1,
                   help=">1 turns every layer's MLP into a MoE "
                        "(ISSUE 15): decode batches tokens per expert "
                        "into capacity buffers and pays overflow "
                        "ROUNDS when routing skews — imbalance "
                        "becomes a measurable p99 story "
                        "(docs/SERVING.md 'MoE decode')")
    p.add_argument("--top_k", type=int, default=1,
                   help="experts per token (MoE models)")
    p.add_argument("--moe_capacity_factor", type=float, default=1.0,
                   help="per-round expert capacity factor of the "
                        "serving MoE MLP")
    p.add_argument("--moe_skew", type=float, default=0.0,
                   help="seeded expert-skew injection: bias added to "
                        "the router logits (serving/moe_decode."
                        "skew_bias) — the imbalance-shaped sibling of "
                        "a fault plan's seeded delays; 0 = off")
    p.add_argument("--moe_skew_seed", type=int, default=0)
    # decode-model shape (tiny CPU-feasible defaults; a real study on
    # chip raises these)
    p.add_argument("--embed", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--kv_heads", type=int, default=2)
    p.add_argument("--ff", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--seed", type=int, default=0,
                   help="weight-init seed")
    p.add_argument("--fault", default=None, metavar="PLAN",
                   help="JSON fault plan (faults/plan.py schema) on the "
                        "decode loop: delay/jitter sleep at engine-step "
                        "boundaries inside the measured window; crash "
                        "under policy shrink costs capacity and prices "
                        "recovery (docs/SERVING.md, docs/RESILIENCE.md)")
    p.add_argument("--fault_policy", default=None,
                   choices=["fail_fast", "retry", "shrink"])
    p.add_argument("--out", default=None,
                   help="append the JSON record to a file")
    p.add_argument("--tag", action="append", default=[],
                   metavar="KEY=VALUE")
    p.add_argument("--platform", default=None)
    p.add_argument("--telemetry", action="store_true",
                   help="continuous telemetry (ISSUE 14): per-engine-"
                        "step flight ring (queue depth, occupancy, "
                        "sync costs) + the anomaly engine (SLO breach, "
                        "fault, step-time change); the record stamps "
                        "telemetry/anomalies blocks")
    p.add_argument("--flight-dir", "--flight_dir", dest="flight_dir",
                   default=None, metavar="DIR",
                   help="where anomaly flight_<trigger>.json ring "
                        "dumps land (default: DLNB_FLIGHT_DIR)")
    p.add_argument("--live-metrics", "--live_metrics",
                   dest="live_metrics", default=None, metavar="PATH",
                   help="stream one windowed snapshot JSONL line per "
                        "0.5 s of engine time (rolling TTFT/TPOT "
                        "percentiles, queue depth, occupancy) — the "
                        "live dashboard channel "
                        "(serving/metrics.LiveMetricsWriter)")
    p.add_argument("--replicas", type=int, default=1,
                   help=">1: fleet serving (ISSUE 18) — this many "
                        "independent engine replicas (each over its "
                        "own --world-device subset with its own page "
                        "pool) behind a seeded front-end router; the "
                        "record stamps the fleet block + "
                        "fleet_routing/fleet_replicas comparables "
                        "(docs/SERVING.md 'Fleet serving')")
    p.add_argument("--routing", default="round_robin",
                   choices=["round_robin", "p2c", "prefix_affinity"],
                   help="fleet routing policy (with --replicas > 1): "
                        "round_robin baseline; p2c = seeded power-of-"
                        "two-choices on live load; prefix_affinity = "
                        "route to the replica whose radix trie holds "
                        "the longest shared prefix (needs "
                        "--prefix_sharing), p2c fallback on ties and "
                        "full replicas")
    p.add_argument("--route_seed", type=int, default=0,
                   help="the router's splitmix64 stream seed "
                        "(assignment replay)")
    p.add_argument("--autoscale", action="store_true",
                   help="elastic fleet capacity (with --replicas > 1): "
                        "scale up on rolling SLO breach / queue "
                        "pressure (recompile priced into the scale "
                        "event), scale down idle replicas through the "
                        "drain arc (chip-seconds saved accounted)")


def _run_serve(args, parser) -> int:
    from dlnetbench_tpu.metrics.emit import scheduler_variables
    variables = scheduler_variables()
    for tag in args.tag:
        key, sep, value = tag.partition("=")
        if not sep or not key:
            parser.error(f"--tag wants KEY=VALUE, got {tag!r}")
        variables[key] = value

    _configure_jax(args)

    from dlnetbench_tpu.serving.arrivals import ArrivalPlan
    from dlnetbench_tpu.serving.scheduler import (ServingConfig,
                                                  run_serving)
    try:
        plan = ArrivalPlan.loads(args.arrival)
    except (ValueError, OSError, KeyError) as e:
        parser.error(f"--arrival: {e}")
    fault_plan = None
    if args.fault:
        from dlnetbench_tpu.faults.plan import FaultPlan
        try:
            fault_plan = FaultPlan.loads(args.fault)
            if args.fault_policy:
                fault_plan.policy = args.fault_policy
            fault_plan.validate()
        except (ValueError, OSError, KeyError) as e:
            parser.error(f"--fault: {e}")

    from dlnetbench_tpu.models.transformer import TransformerConfig
    model_cfg = TransformerConfig(
        vocab_size=args.vocab, embed_dim=args.embed,
        num_heads=args.heads, num_kv_heads=args.kv_heads,
        ff_dim=args.ff, num_layers=args.layers,
        seq_len=args.max_seq_len, gated=True, max_positions=0,
        dtype=args.dtype, num_experts=args.num_experts,
        top_k=args.top_k,
        moe_capacity_factor=args.moe_capacity_factor)
    srv_cfg = ServingConfig(
        slots=args.slots, page_size=args.page_size,
        num_pages=args.num_pages, max_seq_len=args.max_seq_len,
        prefill=args.prefill, prefill_chunk=args.prefill_chunk,
        slo_ttft_ms=args.slo_ttft_ms, slo_tpot_ms=args.slo_tpot_ms,
        world=args.world, kv_shard=args.kv_shard,
        attn_impl=args.attn_impl, multi_step_n=args.multi_step_n,
        adaptive_n=not args.no_adaptive_n,
        speculative=args.speculative, spec_k=args.spec_k,
        drafter=args.drafter, drafter_layers=args.drafter_layers,
        cache_dtype=args.cache_dtype,
        prefix_sharing=args.prefix_sharing,
        moe_skew=args.moe_skew, moe_skew_seed=args.moe_skew_seed,
        disaggregate=args.disaggregate,
        prefill_ranks=args.prefill_ranks,
        decode_ranks=args.decode_ranks,
        migration_chunk_pages=args.migration_chunk_pages,
        temperature=args.temperature, top_k=args.sample_top_k,
        top_p=args.top_p, sample_seed=args.sample_seed,
        grammar=args.grammar)
    try:
        srv_cfg.validate()
        if srv_cfg.speculative:
            # the model-shape half of the speculative guard (a
            # full-depth truncated drafter) fails HERE as a tidy usage
            # error, not as a traceback from the engine build
            from dlnetbench_tpu.serving.speculative import \
                check_spec_config
            check_spec_config(model_cfg, spec_k=srv_cfg.spec_k,
                              drafter=srv_cfg.drafter,
                              drafter_layers=srv_cfg.drafter_layers)
    except ValueError as e:
        parser.error(str(e))

    import jax
    from dlnetbench_tpu.models.transformer import init_params
    params = init_params(jax.random.key(args.seed), model_cfg)
    if args.replicas > 1:
        from dlnetbench_tpu.serving.fleet import FleetConfig, run_fleet
        try:
            fleet_cfg = FleetConfig(replicas=args.replicas,
                                    routing=args.routing,
                                    route_seed=args.route_seed,
                                    autoscale=args.autoscale).validate()
        except ValueError as e:
            parser.error(str(e))
        result = run_fleet(model_cfg, srv_cfg, plan, fleet_cfg,
                           fault_plan=fault_plan, params=params,
                           live_metrics=args.live_metrics)
    else:
        if srv_cfg.disaggregate:
            from dlnetbench_tpu.serving.disagg import run_disagg
            runner = run_disagg
        else:
            runner = run_serving
        result = runner(model_cfg, srv_cfg, plan,
                        fault_plan=fault_plan, params=params,
                        live_metrics=args.live_metrics)
    if variables:
        result.global_meta["variables"] = variables
    record = emit_result(result, path=args.out)
    srv = record.get("global", {}).get("serving", {})
    print(f"serving: {srv.get('completed')} requests at offered "
          f"{srv.get('offered_rps')} rps — ttft p99 "
          f"{(srv.get('ttft_ms') or {}).get('p99')} ms, goodput "
          f"{srv.get('goodput_frac')}", file=sys.stderr)
    return 0


def _build_bundle(args, parser, stats, cfg, devices, dtype):
    kw = {"dtype": dtype}
    if args.proxy == "dp":
        from dlnetbench_tpu.parallel.mesh import make_flat_mesh
        from dlnetbench_tpu.proxies import dp as proxy_mod
        mesh = make_flat_mesh(devices=devices)
        return proxy_mod.build(stats, args.num_buckets, cfg, mesh=mesh, **kw)
    else:
        card = load_model_card(arch_name_from_stats_name(args.model))
        if args.proxy == "fsdp":
            from dlnetbench_tpu.proxies import fsdp as proxy_mod
            bundle = proxy_mod.build(stats, args.num_units, cfg,
                                     devices=devices,
                                     sharding_factor=args.sharding_factor or None,
                                     **kw)
        elif args.proxy == "hybrid_2d":
            from dlnetbench_tpu.proxies import hybrid_2d as proxy_mod
            bundle = proxy_mod.build(stats, card, cfg,
                                     num_stages=args.num_stages,
                                     num_microbatches=args.num_microbatches,
                                     schedule=args.schedule,
                                     dp=args.dp, devices=devices, **kw)
        elif args.proxy == "hybrid_3d":
            from dlnetbench_tpu.proxies import hybrid_3d as proxy_mod
            bundle = proxy_mod.build(stats, card, cfg,
                                     num_stages=args.num_stages,
                                     num_microbatches=args.num_microbatches,
                                     schedule=args.schedule,
                                     tp=args.tp, dp=args.dp, devices=devices,
                                     **kw)
        elif args.proxy == "hybrid_3d_moe":
            from dlnetbench_tpu.proxies import hybrid_3d_moe as proxy_mod
            bundle = proxy_mod.build(stats, card, cfg,
                                     num_stages=args.num_stages,
                                     num_microbatches=args.num_microbatches,
                                     schedule=args.schedule,
                                     num_expert_shards=args.num_expert_shards,
                                     dp=args.dp, devices=devices, **kw)
        elif args.proxy == "ring_attention":
            from dlnetbench_tpu.proxies import ring_attention as proxy_mod
            bundle = proxy_mod.build(stats, card, cfg, sp=args.sp,
                                     dp=args.dp, devices=devices,
                                     max_layers=args.max_layers or None,
                                     **kw)
        elif args.proxy == "ulysses":
            from dlnetbench_tpu.proxies import ulysses as proxy_mod
            bundle = proxy_mod.build(stats, card, cfg, sp=args.sp,
                                     dp=args.dp, devices=devices,
                                     max_layers=args.max_layers or None,
                                     **kw)
        else:  # pragma: no cover
            parser.error(f"unknown proxy {args.proxy}")
        return bundle


if __name__ == "__main__":
    raise SystemExit(main())
