"""Headline benchmark — runs on the real TPU chip under the driver.

Measures the real-compute tier doing what the reference can only simulate:
a full training step (forward + backward + SGD) of a llama3_8b-shaped
block stack, and reports achieved FLOP/s as a fraction of this chip's
roofline — the same ``min(peak, AI*BW)`` model the stat-file generator
uses (reference python/model_stats.py:47-50, re-derived for TPU in
core/roofline.py).

Prints the auxiliary low-precision JSON lines first — fp8 MLP matmul,
fp8 swiglu stage-chain, int8 matmul, the paired fused-vs-composed
quantized-matmul A/B lines (r6, ops/quantized_matmul.py), the
end-to-end int8-MLP train step, the paired SPMD overlap A/B line (r7,
ops/collective_matmul.py — multi-chip sessions only), and the
``recommended_step`` line (fastest measured recipe passing the stated
numerics bar) — and LAST the headline train-step line (tail parsers
read the final line; the auxiliary results also ride inside it as
"fp8_mlp" / "fp8_swiglu" / "int8_matmul" / "int8_fused_ab" /
"fp8_fused_ab" / "spmd_overlap_ab" / "int8_step" /
"recommended_step", and the tuned-vs-frozen "tuned_ab" line — the
seeded block-shape search committed to the tuning DB and the paired
A/B it buys, ISSUE 9):
  {"metric": ..., "value": <step ms>, "unit": "ms",
   "best": <fastest round ms>, "band": [lo, hi], "n": <rounds>,
   "vs_baseline": <achieved/roofline, 1.0 = roofline-perfect>, ...}

Every line carries its band (metrics/stats.py): ``value`` is the round
median, ``best``/``band`` show what the rounds actually did, and a
bimodal sample set (a host or chip that moved between throughput states)
is flagged with a ``note`` instead of shipping one unannotated draw.

``--trace-out t.json`` additionally records host harness spans
(compile/warmup/timed/aux phases) and one profiled headline iteration,
merged into a single Chrome/Perfetto timeline (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from dlnetbench_tpu.metrics import spans
from dlnetbench_tpu.metrics import stats as stats_mod
from dlnetbench_tpu.models.bench_step import BATCH, SEQ, LAYERS, VOCAB


def _compile_chain(fn, arg):
    """AOT compile one chained microbench (core/executor.py: compile
    time can't leak into the first timed round; the persistent compile
    cache, placed by executor.enable_persistent_cache at the top of the
    run, makes the known ~300 s multi-large-matmul compile pathology a
    once-per-cache cost instead of once per run) + warm run + fence.
    The carry is donated; the executor rebinds it from the chain
    output."""
    from dlnetbench_tpu.core import executor
    prog = executor.CompiledProgram(executor.Program(
        fn=fn, args=(arg,), donate_argnums=(0,)))
    jax.block_until_ready(prog())  # warm run (already compiled)
    return prog


def _measure_chain(fn, arg, k: int, cost_out: dict | None = None) -> dict:
    """Compile+warm via ``_compile_chain``, then the band summary of 3
    K-chained rounds in per-iteration SECONDS ({"value": median,
    "best", "band", "n"} — metrics/stats.py).  Shared by every
    auxiliary bench line so fence/timing fixes happen once.
    ``cost_out`` (if a dict) receives the compiled program's own
    per-ITERATION cost analysis — the XLA-counted flops/bytes the
    attribution block records as provenance next to the analytic
    model."""
    from dlnetbench_tpu.utils.timing import time_callable
    prog = _compile_chain(fn, arg)
    if cost_out is not None and prog.cost_analysis:
        cost_out.update({name: v / k
                         for name, v in prog.cost_analysis.items()})
    return stats_mod.summarize([t / k for t in time_callable(prog, reps=3)])


def _measure_paired(progs: dict, k: int, rounds: int = 3):
    """The r4-MLP-study pairing protocol (docs/PERF.md r4): within each
    round every variant is timed back-to-back (adjacent in time), so
    per-round RATIOS between variants cancel slow drift of the host and
    the chip — the only microbench comparison that carries signal
    through ±10-30 % run-to-run noise.  Returns per-variant band summaries (s
    per iteration) and the raw per-round sample lists for ratio
    bands."""
    from dlnetbench_tpu.utils.timing import time_callable
    times: dict[str, list[float]] = {name: [] for name in progs}
    for _ in range(rounds):
        for name, prog in progs.items():
            times[name].append(time_callable(prog, reps=1)[0] / k)
    return {n: stats_mod.summarize(ts) for n, ts in times.items()}, times


def _band_ms(summary_s: dict) -> dict:
    """The artifact-grade stat keys of a JSON line, in ms, from a
    seconds-summary: best/band/n ride next to the median "value"."""
    return {
        "best": round(summary_s["best"] * 1e3, 3),
        "band": [round(v * 1e3, 3) for v in summary_s["band"]],
        "n": summary_s["n"],
    }


def _combine_linear(terms: list[tuple[float, dict]]) -> dict:
    """Band summary of a weighted sum of independently-measured stages
    (the swiglu chain sums 2x up + 1x down): medians/bests/bounds add
    linearly; n is the weakest stage's sample count."""
    return {
        "value": sum(w * s["value"] for w, s in terms),
        "best": sum(w * s["best"] for w, s in terms),
        "band": [sum(w * s["band"][0] for w, s in terms),
                 sum(w * s["band"][1] for w, s in terms)],
        "n": min(s["n"] for _, s in terms),
    }


def _roofline_s(flops: int, nbytes: int, hw, dtype_key: str) -> float:
    """min(peak, AI*BW) time for a measured kernel — one definition for
    every auxiliary line."""
    ai = flops / max(nbytes, 1)
    achievable = min(hw.peak(dtype_key), ai * hw.hbm_bandwidth)
    return flops / achievable


def _flag_above_peak(line: dict) -> dict:
    """A reading ABOVE the physical peak means the timed window did not
    cover the credited work (a fence that did not wait, or a FLOP model
    that over-credits).  Physically impossible readings must not ship
    unannotated: flag them as upper bounds."""
    if line.get("vs_baseline", 0) > 1.0:
        line["note"] = ("above-peak reading: the timed window did not "
                        "cover the credited work — treat the time as a "
                        "lower bound and the rate as an upper bound")
    return line


def _skipped(metric: str, why: str) -> None:
    print(json.dumps({"metric": metric, "skipped": why}))


def _stamp_attr(line: dict, *, time_s: float, flops: float, nbytes: float,
                hw, dtype_key: str, peak_flops: float | None = None,
                xla_cost: dict | None = None) -> dict:
    """Stamp the attribution block onto a bench line (every ms line
    carries one — the joined {fractions, bound} verdict next to its
    bands; analysis/attribution.py)."""
    from dlnetbench_tpu.analysis import attribution
    block = attribution.attribute_kernel(
        time_s, flops, nbytes, hw, dtype_key, peak_flops=peak_flops,
        source="model",
        extra_inputs=({"xla_cost_per_iter": xla_cost} if xla_cost
                      else None))
    if block is not None:
        line["attribution"] = block
    return line


from dlnetbench_tpu.utils.env import env_float, env_int  # noqa: E402

_AUX_DEADLINE_S = env_float("DLNB_BENCH_AUX_DEADLINE_S", 900.0)


class _Aux:
    """Runs the auxiliary bench lines of one run.  A line that raises
    fails the run: the exception propagates and the exit status is
    non-zero.  A wall-clock deadline bounds the auxiliary section as a
    whole (counted from the start of the run, headline compile
    included): lines that would start past it are skipped with a marker
    and named in ``incomplete``, which the headline carries."""

    def __init__(self, deadline_s: float = _AUX_DEADLINE_S):
        self.deadline_s = deadline_s
        self.t0 = time.monotonic()
        self.incomplete: list[str] = []

    def __call__(self, name: str, fn, *args):
        elapsed = time.monotonic() - self.t0
        if elapsed > self.deadline_s:
            _skipped(name, f"aux deadline ({self.deadline_s:.0f}s) "
                           f"exceeded at +{elapsed:.0f}s — headline "
                           f"takes precedence")
            self.incomplete.append(name)
            return None
        with spans.span("aux", line=name):
            return fn(*args)


def _headline_metric_name() -> str:
    return (f"llama3_8b-shaped {LAYERS}L train step, "
            f"B={BATCH} S={SEQ}")


def _bench_device():
    """``(device, HARDWARE key)`` of the chip this run measures.  No
    TPU is an error unless the CPU was asked for by name
    (``JAX_PLATFORMS=cpu``: the sentinel lane's tiny model, whose key
    is None and whose lines carry no roofline ratio), and a TPU kind
    ``HARDWARE`` does not list is an error too — nothing is priced
    against another chip's peaks."""
    from dlnetbench_tpu.core.hardware import hw_key_for_device_kind
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        hw_key = hw_key_for_device_kind(dev.device_kind)
        if hw_key is None:
            raise SystemExit(
                f"bench.py: core/hardware.py has no entry for device "
                f"kind {dev.device_kind!r}")
        return dev, hw_key
    if jax.config.jax_platforms != "cpu":
        raise SystemExit(
            f"bench.py: jax found no TPU (platform {dev.platform!r}) "
            f"and the CPU was not asked for (JAX_PLATFORMS=cpu)")
    return dev, None


def _parse_args(argv=None):
    p = argparse.ArgumentParser(prog="bench.py", description=__doc__)
    p.add_argument("--trace-out", "--trace_out", dest="trace_out",
                   default=None, metavar="PATH",
                   help="write a merged host+device Chrome/Perfetto "
                        "trace of this bench run (host harness spans + "
                        "one profiled headline iteration)")
    p.add_argument("--check", default=None, metavar="BASELINE",
                   help="regression sentinel (dlnetbench_tpu/sentinel.py):"
                        " compare this run's headline + aux lines against "
                        "a baseline bench artifact (BENCH_r*.json driver "
                        "capture or bench stdout JSONL), write a "
                        "'sentinel' section into the headline line, and "
                        "exit non-zero on a regression (median worse by "
                        "> --check-threshold %% AND stat bands disjoint)")
    p.add_argument("--check-threshold", "--check_threshold",
                   dest="check_threshold", type=float, default=5.0,
                   help="percent slowdown that (with disjoint bands) "
                        "counts as a regression (default 5)")
    p.add_argument("--fault", default=None, metavar="PLAN",
                   help="JSON fault plan (faults/plan.py schema) injected "
                        "at headline step boundaries INSIDE the timed "
                        "window — the deterministic-slowdown channel the "
                        "sentinel lane uses to prove --check trips; the "
                        "headline is stamped with the plan and its "
                        "attribution verdict becomes 'faulted'")
    p.add_argument("--skip-aux", "--skip_aux", dest="skip_aux",
                   action="store_true",
                   help="measure only the headline train step (the "
                        "sentinel lane's tiny-CPU mode; aux lines emit "
                        "nothing, not even skip markers)")
    p.add_argument("--live-metrics", "--live_metrics",
                   dest="live_metrics", default=None, metavar="PATH",
                   help="serving lines stream one windowed snapshot "
                        "JSONL line per 0.5 s of engine time to PATH "
                        "(rolling TTFT/TPOT percentiles, queue depth, "
                        "KV occupancy — serving/metrics."
                        "LiveMetricsWriter; ISSUE 14)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    # programmatic callers (tests) pass no argv and get defaults; only
    # the __main__ path below hands over sys.argv
    args = _parse_args(argv if argv is not None else [])
    tracer = spans.enable() if args.trace_out else None
    from dlnetbench_tpu.metrics import telemetry
    tele_on = (not telemetry.is_enabled()
               and telemetry.enable_from_env() is not None)
    try:
        return _run_bench(args, tracer)
    finally:
        # never leak the process-global tracer past this run — an
        # exception mid-bench must not leave later programmatic main()
        # calls (tests) recording into a dead tracer
        if spans.is_enabled():
            spans.disable()
        if tele_on:
            telemetry.disable()


def _run_bench(args, tracer) -> int:
    aux = _Aux()   # the aux deadline counts from here
    dev, hw_key = _bench_device()
    if hw_key is None and not args.skip_aux:
        raise SystemExit("bench.py: the auxiliary lines price against a "
                         "chip's roofline; on the CPU pass --skip-aux")

    from dlnetbench_tpu.core.hardware import HARDWARE
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.core import roofline
    from dlnetbench_tpu.models import bench_step
    from dlnetbench_tpu.utils.timing import time_callable

    # place the persistent compile cache BEFORE the first compile of the
    # run: the multi-large-matmul chains below are the known ~300 s
    # compile pathology on this toolchain (PERF.md r4) — with the cache,
    # that cost is paid once per cache, not per bench run; the directory
    # is stamped into the headline so the artifact records warm-vs-cold
    # provenance
    cache_dir = executor.enable_persistent_cache()
    print(f"persistent compile cache: {cache_dir}", file=sys.stderr)

    # tuning DB (ISSUE 9): like the compile cache, an opt-in warm-state
    # directory (DLNB_TUNING_DB_DIR) stamped into the headline so every
    # artifact is attributable to a tuning state — a DB-miss run and a
    # DB-hit run must be distinguishable in the record
    from dlnetbench_tpu import tuning
    tuning_db_dir = tuning.db_dir()
    if tuning_db_dir:
        print(f"tuning db: {tuning_db_dir}", file=sys.stderr)

    # --fault: parse and validate the plan BEFORE any compile spend.
    # The bench is a single-process measurement with no degradation
    # policy: only slowdown kinds make sense here.  A crash/partition
    # trigger would raise mid-timed-window after minutes of
    # compile+warmup — refuse up front instead (the same
    # refuse-what-you-can't-honor convention the unwired native proxies
    # follow).  The injector itself wraps the timed step further down.
    fault_plan = None
    if args.fault:
        from dlnetbench_tpu.faults.plan import FaultPlan
        fault_plan = FaultPlan.loads(args.fault).validate()
        bad = sorted({e.kind for e in fault_plan.events
                      if e.kind not in ("delay", "jitter")})
        if bad:
            print(f"--fault: bench.py only honors delay/jitter events "
                  f"(got {', '.join(bad)}) — crash/drop/partition need "
                  f"a multi-rank harness with a degradation policy "
                  f"(cli.py --fault)", file=sys.stderr)
            return 2

    # r3 accounting fixes: (1) vs_baseline_causal divides the credited
    # S^2 score FLOPs by 2 (the flash kernel executes only the causal
    # half); (2) the LM-head logits matmul is credited (see below) —
    # r1/r2 spent its time but not its FLOPs.  Both r3 ratio keys
    # include the LM head; only vs_baseline_decoder_only reproduces the
    # r1/r2 formula.  r3 perf attempts, measured paired A/B on-chip:
    # fwd flash block-shape sweep at S=6144 ((1024,2048), (3072,3072),
    # (2048,1024), (1024,1024), (2048,3072)) — NOT kept, all within the
    # +-8% run-to-run noise of (2048,2048) on 5-round medians; base-2
    # online softmax (exp2 with log2e folded into the q scale) — KEPT
    # in flash_attention.py on principle (one fewer VPU multiply per
    # score element, numerics identical) though it measured neutral
    # (0.998 median paired ratio).
    # Recipe (measured on v5e, r2): no remat (activations fit at this
    # shape; ~12% over full remat), unrolled layer loop (~5% over scan:
    # no dynamic-slice save/restore of stacked activations), flash
    # attention with direction-split blocks (fwd 2048 / bwd 1024, plus
    # parallel Mosaic dimension_semantics — fwd kernel 86 -> 120 TF/s,
    # bwd kernel at 183 TF/s), custom-VJP rmsnorm (the autodiff
    # norm-backward fusion alone cost ~15% of the step), bf16 logits
    # (~0.5%: halves the [B,S,V] logits traffic; CE still reduces in
    # f32 — surfaced in the output as logits_dtype), B=2 x S=6144 (at
    # fixed token count — 12288, the most that fits no-remat — longer
    # sequences win: flash computes only the causal half of the S^2
    # attention matmuls while the roofline, like standard MFU accounting,
    # budgets them in full; B=3 S=4096: 0.70, B=2 S=6144: 0.72), and a
    # 32 MiB XLA scoped-VMEM limit via per-compile compiler_options
    # (+3.5%: the 16 MiB default cramps tiling of the big backward
    # fusions; 24 MiB +3%, 40-64 MiB +3.2%, 32 MiB best at 0.75).
    # Measured dead ends, for the record: fused-QKV via concat (-2%:
    # concat HBM traffic), param donation (0%: XLA already aliases the
    # scan carry), barriered rmsnorm input or output (-0.5 to -1.5%:
    # splits fusions XLA had right), B=2 S=2048 (0.66), B=1 S=8192
    # (0.68, half the tokens), B=1 S=12288 / B=2 S=8192 / B=4 S=4096 /
    # B=2 S=7168 with the VMEM option (OOM).
    # r4 perf attempts on the dominant backward bucket, all paired A/B
    # on-chip (docs/PERF.md r4): split-dot custom VJP 0.9975 (neutral),
    # fused Pallas dg/du + dWd kernels 1.012 (slower), bare same-shape
    # dots 0.992 of peak in isolation — XLA's backward schedule is at
    # the wall; the SwiGLU backward is plain autodiff.
    # The step itself is built by models/bench_step.py, SHARED with
    # examples/xla_knob_study.py so compiler-knob sweeps tune exactly
    # this program.
    # train steps chained inside ONE program.  Env-overridable with the
    # same import-frozen discipline as the DLNB_BENCH_* shape knobs: the
    # sentinel lane raises K on its tiny CPU config so fence/dispatch
    # jitter amortizes and the 3-round band is tight enough for a 10%
    # injected slowdown to land outside it (tests/test_sentinel.py).
    K = env_int("DLNB_BENCH_K", 10)
    with spans.span("build", what="headline train_k"):
        train_k_fn, params, tokens, card, cfg = bench_step.build(K)

    # per-compile compiler option: it belongs to this program, not to
    # every compile of the process as XLA_FLAGS would make it; TPU-only
    # flag, so gate on the backend for CPU-mesh runs
    opts = ({"xla_tpu_scoped_vmem_limit_kib": "32768"}
            if jax.default_backend() == "tpu" else None)
    # AOT through the execution engine: compile happens HERE (recorded as
    # compile_ms, never inside a timed round), params are donated so the
    # optimizer update reuses their buffers in place (aliasing recorded
    # in memory_analysis), and each call rebinds the donated carry
    train_k = executor.CompiledProgram(executor.Program(
        fn=train_k_fn, args=(params, tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS,
        compiler_options=opts))
    aot_stats = train_k.stats
    del params  # the executor owns a private donated copy

    with spans.span("warmup", what="headline"):
        params2, losses = train_k()  # warm run (already compiled)
        jax.block_until_ready(losses)   # so rep 1 starts clean

    # --fault: scripted step-boundary injection INSIDE the timed window
    # (faults/inject.py — the same injector the proxies use), so a
    # deterministic slowdown inflates the measured headline exactly like
    # a real straggler would.  The warm run stays clean; the plan rides
    # the headline line so a faulted artifact can never pass as a clean
    # measurement.  (Plan already parsed+validated up top, before the
    # compile spend.)
    timed_step = train_k
    if fault_plan is not None:
        from dlnetbench_tpu.faults.inject import FaultInjector
        injector = FaultInjector(fault_plan)

        def timed_step():
            injector.before_chain(K)  # K in-program steps per dispatch
            return train_k()

    # three rounds of K in-program steps (each fences once); median guards
    # against a slow round from host jitter — and the band of
    # the three rounds ships on the line (metrics/stats.py)
    with spans.span("timed", what="headline", reps=3, k=K):
        step_summary = stats_mod.summarize(
            [t / K for t in time_callable(timed_step, reps=3)])
    step_s = step_summary["value"]
    # materialize EVERY device value the headline will print BEFORE any
    # auxiliary line runs: an aux failure that poisons the backend (the
    # r5 int8-step OOM did) must not take the headline down with it at
    # json-serialization time
    loss = float(losses[-1])

    # Analytic FLOPs: fwd + ~2x bwd = 3x forward (reference bwd/fwd=2
    # model).  The forward is the decoder stack (attention + MLP, the
    # reference's model_flops convention) PLUS the LM-head logits matmul
    # the step executes (2*B*S*D*V): standard MFU accounting — e.g. the
    # PaLM appendix-B formula — includes the unembedding projection, and
    # model_bytes already streams the vocab weights, so crediting the
    # time but not the FLOPs (as r1/r2 did) understated utilization by
    # the head's share (~23% at V=32768, S=6144).  The baseline divisor
    # gets the same flops through the same min(peak, AI*BW) model, so
    # 1.0 still means "running at this chip's roofline for the work the
    # step performs".
    lm_head_flops = 2 * BATCH * SEQ * card.embed_dim * VOCAB
    fwd_flops = roofline.model_flops(card, BATCH) + lm_head_flops
    total_flops = 3 * fwd_flops
    achieved = total_flops / step_s

    # Causal-honest accounting (VERDICT r2): the roofline — like standard
    # MFU convention (and the reference, python/model_stats.py:128) —
    # credits the S^2 score/AV matmuls in FULL, but the causal flash
    # kernel executes only the lower-triangular half.  vs_baseline_causal
    # divides those credited score FLOPs by 2, so it is the utilization
    # of FLOPs the chip actually ran.
    # NOTE: from r3 on, vs_baseline_causal also credits the LM head (it
    # is vs_baseline x executed_ratio on the SAME flop base); r1/r2's
    # causal figure had no LM-head term, so compare across rounds via
    # vs_baseline_decoder_only, not this key.
    causal_elided = card.num_layers * 2 * BATCH * SEQ * SEQ * card.embed_dim
    executed_ratio = (fwd_flops - causal_elided) / fwd_flops

    # Backward-aware baseline (VERDICT r3 #4): same credited FLOPs, but
    # the divisor prices the step's explicit traffic — weights x3,
    # working set x3, PLUS the saved-residual round trip (the [B,S,ff]
    # g/u pre-activations autodiff stores) — instead of scaling the
    # forward's AI by 3 (roofline.train_step_bytes).  At this shape the
    # step is deeply compute-bound either way (AI thousands vs a ~240
    # FLOP/B ridge), so if this key matches vs_baseline, none of the
    # residual gap was byte-model flattery.
    step_bytes_bwd = roofline.train_step_bytes(card, BATCH, "bfloat16")
    # the roofline ratios exist only against a chip HARDWARE lists: a
    # CPU run (hw_key None) reports its time and rate and no ratio
    ratios = {}
    if hw_key is not None:
        roofline_s = 3 * roofline.roofline_time_s(
            fwd_flops, roofline.model_bytes(card, BATCH, "bfloat16"),
            HARDWARE[hw_key], "bfloat16")
        # old (decoder-only) convention, for cross-round comparability
        roofline_dec_s = 3 * roofline.forward_time_s(
            card, BATCH, "bfloat16", hw_key)
        roofline_bwd_s = roofline.roofline_time_s(
            total_flops, step_bytes_bwd, HARDWARE[hw_key], "bfloat16")
        vs_baseline = roofline_s / step_s  # 1.0 = running at the roofline
        ratios = {
            "vs_baseline": round(vs_baseline, 4),
            "vs_baseline_causal": round(vs_baseline * executed_ratio, 4),
            "vs_baseline_bwd_aware": round(roofline_bwd_s / step_s, 4),
            # r1/r2's decoder-only accounting (LM-head time spent but
            # its flops uncredited) — kept so rounds stay comparable
            "vs_baseline_decoder_only": round(roofline_dec_s / step_s, 4),
        }

    # --trace-out: one profiled headline iteration for the device half
    # of the merged timeline — captured while the compiled program and
    # its buffers are still alive, BEFORE the residency cleanup below
    device_events = None
    if args.trace_out:
        try:
            import tempfile
            from dlnetbench_tpu.metrics import profiling
            trace_dir = tempfile.mkdtemp(prefix="dlnb_bench_prof_")
            with spans.span("profile", what="headline iteration"):
                with jax.profiler.trace(trace_dir):
                    # fenced inside the trace window: the profiler
                    # must not close mid-execution
                    time_callable(train_k, reps=1)
            device_events = profiling.load_trace_events(trace_dir)
        except Exception as e:  # the trace is auxiliary to the artifact
            print(f"trace-out device profile failed: {e}", file=sys.stderr)

    # free the headline's device buffers before any auxiliary line: the
    # params pytrees (executor-owned donated carry + the last outputs) +
    # the token batch are ~7 GB of HBM this chip no longer needs, and
    # the r5 capture showed the int8-step pair OOMing against exactly
    # that residency (then poisoning the rest of the aux section)
    del params2, losses, tokens, train_k

    # auxiliary lines FIRST so the headline train-step line stays LAST
    # on stdout (tail parsers take the final JSON line); results also
    # ride inside the headline object for first-line parsers; a line
    # that raises fails the run (_Aux)
    if args.skip_aux:
        fp8 = fp8_chain = int8 = int8_ab = fp8_ab = None
        straggler = ckpt_ab = int8_step = int8_sb = overlap_ab = None
        serving = tuned_ab = longcontext = kv_density = moe_ab = None
        disagg_ab = fleet_ab = sampling_ab = None
    else:
        fp8 = aux("fp8 mlp matmul", _bench_fp8_mlp, card, hw_key, dev)
        fp8_chain = aux("fp8 swiglu chain", _bench_fp8_swiglu_chain,
                         card, hw_key, dev)
        int8 = aux("int8 matmul", _bench_int8_matmul, card, hw_key, dev)
        int8_ab = aux("int8 fused-quant A/B", _bench_quant_fused_ab,
                       card, hw_key, dev, "int8")
        fp8_ab = aux("fp8 fused-quant A/B", _bench_quant_fused_ab,
                      card, hw_key, dev, "float8")
        # tuned-vs-frozen A/B (ISSUE 9): seeded block-shape search for
        # the fp8 fused-swiglu projections (committed to the tuning DB
        # — the env dir if set, an ephemeral one otherwise) followed by
        # the paired frozen-default vs DB-tuned chain under the r4
        # pairing protocol; the tuned chain's of-peak number lands in
        # the artifact with stat bands (the VERDICT r5 driver evidence)
        tuned_ab = aux("tuned A/B", _bench_tuned_ab, card, hw_key, dev)
        # cheap (tiny dp step, 3 interleaved rounds): the
        # faulted-vs-clean straggler pairing — measured amplification
        # of an injected delay
        straggler = aux("straggler A/B", _bench_straggler_ab)
        # cheap (tiny dp step again): stall-vs-async checkpoint save
        # cost — the measured input to the Daly interval model
        ckpt_ab = aux("checkpoint A/B", _bench_checkpoint_ab)
        # cheap (tiny decode engine, one compile, 3 replayed rounds):
        # the serving tier's latency line — TTFT/TPOT/e2e-p99 bands
        serving = aux("serving decode", _bench_serving_decode,
                       args.live_metrics)
        # the ISSUE-12 density evidence: dense vs int8 vs fp8 paged-KV
        # engines at EQUAL pool bytes — admitted concurrency, tokens/s
        # and the per-recipe decode-parity bars
        kv_density = aux("kv density A/B", _bench_kv_density)
        # the ISSUE-19 sampling evidence: seeded sampling with vs
        # without lossless speculative sampling at T=0.8, plus the
        # classic-vs-fused bit-identity witness — tiny engines, three
        # compiles (the bench HEADLINE stays greedy)
        sampling_ab = aux("sampling A/B", _bench_sampling_ab)
        # the ISSUE-16 disaggregation evidence: monolithic vs split
        # prefill/decode meshes at equal chips on one seeded plan —
        # two tiny engines + the migration channel, one compile each
        disagg_ab = aux("disagg A/B", _bench_disagg_ab)
        # the ISSUE-18 fleet evidence: three 2-replica fleets at equal
        # chips on one seeded prefix-heavy plan, differing only in
        # routing policy — tiny engines, three compiles, r4 pairing
        fleet_ab = aux("fleet A/B", _bench_fleet_ab)
        # the ISSUE-10 long-context evidence: dense-vs-splash paired
        # rounds at S=64k under causal/window/segment masks — four
        # attention-only compiles, bounded by the shared aux deadline
        longcontext = aux("longcontext A/B", _bench_longcontext_ab,
                           card, hw_key, dev)
        # the ISSUE-15 MoE evidence: dense FFN vs sparse-dispatch MoE
        # vs grouped-kernel MoE at matched active params — three
        # reduced-depth train-step compiles under the aux deadline
        moe_ab = aux("moe A/B", _bench_moe_ab, card, hw_key, dev)
        # LAST among the aux lines: they are the most expensive (a full
        # train-step compile+measure each) and the only ones with a
        # known backend-poisoning failure mode (the r5 composed-VJP
        # OOM) — running them after the cheap lines means a blowup
        # costs only itself; switchback last (it is the opt-in recipe,
        # int8_step the default one)
        int8_step = aux("int8 train step", _bench_int8_step, card,
                         hw_key, dev, step_s, opts)
        int8_sb = aux("int8 switchback train step", _bench_int8_step,
                       card, hw_key, dev, step_s, opts, "switchback")
        # LAST of all: six train-step compiles of its own (2 configs x
        # 3 A/B variants) — it must not spend the shared aux deadline
        # before the int8 step lines the recommended_step comparison
        # depends on; single-chip sessions skip it outright
        overlap_ab = aux("spmd overlap A/B", _bench_overlap_ab)

    # the driver-captured recommendation (VERDICT r5 item #1): the
    # fastest recipe among the A/B variants this run actually measured
    # that passes the stated numerics bar, as its own parseable line
    recommended = _recommended_step(
        step_summary, loss,
        {"int8_master": int8_step, "int8_switchback": int8_sb})
    print(json.dumps(recommended))

    headline = stats_mod.flag_low_mode({
        "metric": (f"{_headline_metric_name()}, {dev.device_kind} "
                   f"({hw_key or dev.platform})"),
        "value": round(step_s * 1e3, 3),
        "unit": "ms",
        **_band_ms(step_summary),
        **ratios,
        "tflops_achieved": round(achieved / 1e12, 2),
        "tflops_executed": round(achieved * executed_ratio / 1e12, 2),
        "loss": round(loss, 4),
        "logits_dtype": "float32" if cfg.logits_f32 else "bfloat16",
        # AOT engine bookkeeping: compile wall time (never inside a
        # timed round) and XLA's memory analysis — alias bytes > 0 is
        # the donation proof (params aliased argument->output)
        "compile_ms": aot_stats.get("compile_ms"),
        **({"memory_analysis": aot_stats["memory_analysis"]}
           if "memory_analysis" in aot_stats else {}),
        "compile_cache_dir": cache_dir,
        **({"incomplete": aux.incomplete} if aux.incomplete else {}),
        **({"tuning_db_dir": tuning_db_dir} if tuning_db_dir else {}),
        **({"fp8_mlp": fp8} if fp8 else {}),
        **({"fp8_swiglu": fp8_chain} if fp8_chain else {}),
        **({"int8_matmul": int8} if int8 else {}),
        **({"int8_fused_ab": int8_ab} if int8_ab else {}),
        **({"fp8_fused_ab": fp8_ab} if fp8_ab else {}),
        **({"tuned_ab": tuned_ab} if tuned_ab else {}),
        **({"straggler_ab": straggler} if straggler else {}),
        **({"checkpoint_ab": ckpt_ab} if ckpt_ab else {}),
        **({"serving_decode": serving} if serving else {}),
        **({"sampling_ab": sampling_ab} if sampling_ab else {}),
        **({"kv_density_ab": kv_density} if kv_density else {}),
        **({"disagg_ab": disagg_ab} if disagg_ab else {}),
        **({"fleet_ab": fleet_ab} if fleet_ab else {}),
        **({"longcontext_ab": longcontext} if longcontext else {}),
        **({"moe_ab": moe_ab} if moe_ab else {}),
        **({"spmd_overlap_ab": overlap_ab} if overlap_ab else {}),
        **({"int8_step": int8_step} if int8_step else {}),
        **({"int8_switchback_step": int8_sb} if int8_sb else {}),
        "recommended_step": recommended,
        **({"fault_plan": fault_plan.to_dict()} if fault_plan else {}),
    })
    # bottleneck attribution (analysis/attribution.py): the headline's
    # measured time against its own credited FLOPs and backward-aware
    # step traffic — {fractions, bound} rides the line like the bands do
    headline_attr = None
    if hw_key is not None:
        from dlnetbench_tpu.analysis import attribution
        headline_attr = attribution.attribute_kernel(
            step_s, total_flops, step_bytes_bwd, HARDWARE[hw_key],
            "bfloat16", faulted=fault_plan is not None, source="model",
            extra_inputs=({"xla_cost_per_step": {
                k: v / K for k, v in aot_stats["cost_analysis"].items()}}
                if "cost_analysis" in aot_stats else None))
    if headline_attr is not None:
        headline["attribution"] = headline_attr

    # regression sentinel (--check): stat-band-aware comparison against
    # a committed baseline artifact; the verdict ships INSIDE the
    # headline (the artifact records its own check) and the exit code
    # carries it to CI
    sentinel_section = None
    check_rc = 0
    if args.check:
        from dlnetbench_tpu import sentinel as sentinel_mod
        try:
            base_lines = sentinel_mod.bench_lines(args.check)
        except (OSError, ValueError) as e:
            # ValueError covers UnicodeDecodeError on a binary/mangled
            # baseline — the measurement above must survive either way
            print(f"--check: cannot read baseline ({e})", file=sys.stderr)
            base_lines = {}
        if not base_lines.get("headline"):
            # a tripwire that silently disarms is worse than no tripwire:
            # an unreadable/headline-less baseline is a misconfiguration
            # and must FAIL the run, not let every future regression ship
            # green.  The measurement above still prints in full.
            print(f"--check: baseline {args.check} has no comparable "
                  f"headline — sentinel cannot arm", file=sys.stderr)
            check_rc = 2
        cur_lines = {"headline": headline,
                     **{k: v for k, v in headline.items()
                        if sentinel_mod.is_ms_line(v)}}
        sentinel_section = sentinel_mod.check(
            base_lines, cur_lines, args.check_threshold,
            baseline_label=str(args.check))
        headline["sentinel"] = sentinel_section

    print(json.dumps(headline))
    if tracer is not None:
        spans.disable()
        try:
            extra = spans.attribution_counter_events(
                headline_attr or {}, dur_us=step_s * 1e6)
            from dlnetbench_tpu.metrics import telemetry
            rec_now = telemetry.current()
            if rec_now is not None:
                # the flight ring as counter tracks beside the spans
                extra = extra + spans.telemetry_counter_events(
                    rec_now.telemetry_block(last=rec_now.capacity),
                    rec_now.anomalies_block())
            spans.write_chrome_trace(
                args.trace_out, tracer, device_events,
                extra_events=extra)
            print(f"merged host+device trace -> {args.trace_out}",
                  file=sys.stderr)
        except OSError as e:  # the headline already printed — keep rc 0
            print(f"trace-out write failed ({e}); headline unaffected",
                  file=sys.stderr)
    if sentinel_section and sentinel_section.get("verdict") == "regression":
        from dlnetbench_tpu.sentinel import RC_REGRESSION
        print(f"sentinel: REGRESSION vs {args.check}: "
              f"{', '.join(sentinel_section['regressions'])}",
              file=sys.stderr)
        return RC_REGRESSION
    return check_rc


# numerics bar for the recommended-step recipe: single-step loss within
# this relative band of the bf16 headline's.  The convergence evidence
# justifying the bar is the r5 study (docs/studies/int8_step_r5):
# >= 500-step curves showed the int8 recipes tracking bf16.
REC_NUMERICS_BAR_REL = 0.02


def _recommended_step(bf16_summary_s: dict, bf16_loss: float,
                      candidates: dict) -> dict:
    """The driver-captured half of VERDICT r5 item #1 (pure —
    tests/test_bench_aux.py locks this schema): among the step recipes
    this run measured (bf16 headline + the int8 A/B variants), pick the
    FASTEST whose single-step loss passes the stated numerics bar, and
    say so in a machine-readable line with the winner's stat band.
    Candidates that were skipped (None) or lack value/loss keys simply
    don't compete — the bf16 headline always does, so the line always
    names a recipe."""
    entries = {"bf16": {"value": round(bf16_summary_s["value"] * 1e3, 3),
                        **_band_ms(bf16_summary_s),
                        "loss": round(bf16_loss, 4), "passes": True}}
    for name, ln in candidates.items():
        if not ln or "value" not in ln or "loss" not in ln:
            continue
        passes = (abs(ln["loss"] - bf16_loss)
                  <= REC_NUMERICS_BAR_REL * abs(bf16_loss))
        entries[name] = {"value": ln["value"], "best": ln.get("best"),
                         "band": ln.get("band"), "n": ln.get("n"),
                         "loss": ln["loss"], "passes": passes}
    winner = min((nm for nm, e in entries.items() if e["passes"]),
                 key=lambda nm: entries[nm]["value"])
    e = entries[winner]
    return {
        "metric": "recommended_step",
        "recipe": winner,
        "value": e["value"],
        "unit": "ms",
        "best": e["best"],
        "band": e["band"],
        "n": e["n"],
        "numerics_bar": (f"single-step loss within "
                         f"{REC_NUMERICS_BAR_REL:.0%} of the bf16 "
                         f"headline's (convergence evidence: "
                         f"docs/studies/int8_step_r5)"),
        "candidates": entries,
    }


def _serving_variant_block(base_rounds: list[dict],
                           rounds: list[dict]) -> dict:
    """Per-variant A/B sub-object for the serving_decode line: the
    serving figures with bands, the dispatch decomposition, and the
    paired per-round speedup over the 1-step baseline (r4 pairing —
    adjacent measurement cancels drift)."""
    dl = [r.get("decode_loop") or {} for r in rounds]
    block = {
        "tokens_per_s": stats_mod.summarize(
            [r["tokens_per_s"] for r in rounds], ndigits=2),
        "tpot_p50_ms": stats_mod.summarize(
            [r["tpot_ms"]["p50"] for r in rounds], ndigits=3),
        "e2e_p99_ms": stats_mod.summarize(
            [r["e2e_ms"]["p99"] for r in rounds], ndigits=3),
        "speedup_tokens_per_s": stats_mod.summarize(
            [r["tokens_per_s"] / b["tokens_per_s"]
             for b, r in zip(base_rounds, rounds)
             if b["tokens_per_s"] > 0], ndigits=3),
        "steps_per_dispatch": stats_mod.summarize(
            [d.get("steps_per_dispatch", 0.0) for d in dl], ndigits=3),
        "tokens_per_sync": stats_mod.summarize(
            [d.get("tokens_per_sync", 0.0) for d in dl], ndigits=3),
        "multi_step_n": (dl[0] or {}).get("multi_step_n"),
    }
    spec = (dl[0] or {}).get("spec")
    if isinstance(spec, dict):
        block["spec"] = {
            "k": spec.get("k"), "drafter": spec.get("drafter"),
            "acceptance_rate": stats_mod.summarize(
                [(d.get("spec") or {}).get("acceptance_rate", 0.0)
                 for d in dl], ndigits=4),
        }
    return block


def _serving_host_frac_ab(base_rounds: list[dict],
                          multi_rounds: list[dict],
                          spec_rounds: list[dict] | None
                          ) -> dict | None:
    """The attribution-flip evidence (ISSUE 11 acceptance): per-round
    host fractions with the measured per-dispatch floor folded in
    (analysis/attribution.dispatch_decomposition — the paired 1-step
    vs N-step rounds ARE the two-point measurement of dispatch cost),
    banded per variant, plus the band-disjoint verdict for the
    1-step -> N-step drop.  On a TPU platform the serving record's own
    attribution block flips the BOUND off host; on the CPU mesh (where
    a measured-compute verdict can never read mxu) THIS drop is the
    committed evidence."""
    from dlnetbench_tpu.analysis import attribution as A
    floors: list[float] = []
    fracs: dict[str, list[float]] = {}
    variants = {"one_step": base_rounds, "multi_step": multi_rounds}
    if spec_rounds:
        variants["speculative"] = spec_rounds
    for i, (b, m) in enumerate(zip(base_rounds, multi_rounds)):
        dec = A.dispatch_decomposition(b.get("decode_loop") or {},
                                       m.get("decode_loop") or {})
        if dec is None:
            return None
        floors.append(dec["dispatch_us"])
        for name, rnds in variants.items():
            r = rnds[i]
            host = A.serving_host_us(r.get("decode_loop") or {},
                                     dec["dispatch_us"])
            fracs.setdefault(name, []).append(
                host / (r["wall_s"] * 1e6))
    one = stats_mod.summarize(fracs["one_step"], ndigits=4)
    multi = stats_mod.summarize(fracs["multi_step"], ndigits=4)
    disjoint = (stats_mod.bands_overlap(one["band"], multi["band"])
                is False and multi["value"] < one["value"])
    out = {
        "dispatch_us": stats_mod.summarize(floors, ndigits=1),
        "one_step_host_frac": one,
        "multi_step_host_frac": multi,
        "band_disjoint_drop": disjoint,
        "verdict": ("host fraction dropped, bands disjoint — the "
                    "fused loop amortizes the measured per-dispatch "
                    "floor" if disjoint else
                    "host-fraction bands overlap — no flip at this "
                    "scale/noise"),
    }
    if spec_rounds:
        out["speculative_host_frac"] = stats_mod.summarize(
            fracs["speculative"], ndigits=4)
    return out


def _serving_decode_line(rounds: list[dict], suffix: str = "", *,
                         multi_rounds: list[dict] | None = None,
                         spec_rounds: list[dict] | None = None,
                         token_parity: bool | None = None) -> dict:
    """Assemble the serving_decode aux line from per-round ``serving``
    blocks (pure — tests/test_bench_aux.py locks this schema).  The
    headline ``value`` is the 1-step engine's round-median e2e p99 in
    ms (lower is better, so the sentinel compares it like every
    latency line), and TTFT/TPOT/p99 each ship their own
    artifact-grade ``{value, best, band, n}`` over the rounds.  With
    ``multi_rounds``/``spec_rounds`` (ISSUE 11) the line grows the
    paired A/B: per-variant tokens/s + TPOT bands with speedups, the
    dispatch decomposition, the host-fraction drop with its
    band-disjoint verdict, and the token-parity lock."""
    p99 = [r["e2e_ms"]["p99"] for r in rounds]
    summary = stats_mod.summarize(p99, ndigits=3)
    line = {
        "metric": f"serving_decode: paged-KV continuous-batching "
                  f"decode, e2e p99 under a seeded open-loop poisson "
                  f"plan (serving/){suffix}",
        "value": summary["value"],
        "unit": "ms",
        "best": summary["best"],
        "band": summary["band"],
        "n": summary["n"],
        "ttft_p50_ms": stats_mod.summarize(
            [r["ttft_ms"]["p50"] for r in rounds], ndigits=3),
        "tpot_p50_ms": stats_mod.summarize(
            [r["tpot_ms"]["p50"] for r in rounds], ndigits=3),
        "p99_ms": summary,
        "tokens_per_s": stats_mod.summarize(
            [r["tokens_per_s"] for r in rounds], ndigits=2),
        "goodput_frac": stats_mod.summarize(
            [r["goodput_frac"] for r in rounds], ndigits=4),
        "requests": rounds[0]["completed"],
        "offered_rps": rounds[0]["offered_rps"],
    }
    if multi_rounds:
        line["multi_step"] = _serving_variant_block(rounds,
                                                    multi_rounds)
        if spec_rounds:
            line["speculative"] = _serving_variant_block(rounds,
                                                         spec_rounds)
        flip = _serving_host_frac_ab(rounds, multi_rounds, spec_rounds)
        if flip is not None:
            line["attribution_flip"] = flip
        if token_parity is not None:
            line["token_parity"] = bool(token_parity)
    return stats_mod.flag_low_mode(line)


def _bench_serving_decode(live_path: str | None = None) -> dict | None:
    """The serving-tier A/B line (ISSUE 8 base + ISSUE 11 tentpole):
    THREE engines over the same weights — the classic 1-step engine,
    the device-resident N-step fused loop, and the fused loop with
    self-drafting speculative decode — replay the SAME seeded
    saturating poisson plan, interleaved per round (the r4 pairing
    protocol: adjacent measurement cancels drift).  Each engine is
    compiled once (AOT via core/executor.CompiledStep/CompiledLoop),
    warm round discarded.  The line keeps the ISSUE 8 schema (value =
    1-step e2e p99, sentinel-comparable) and adds the paired
    tokens/s + TPOT A/B, the measured dispatch decomposition, the
    host-fraction drop verdict, and the token-parity lock (the N-step
    and speculative greedy streams must EQUAL the 1-step stream)."""
    import dataclasses

    from dlnetbench_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
    from dlnetbench_tpu.serving import metrics as smetrics
    from dlnetbench_tpu.serving.arrivals import ArrivalPlan
    from dlnetbench_tpu.serving.scheduler import Engine, ServingConfig

    mc = TransformerConfig(
        vocab_size=256, embed_dim=64, num_heads=4, num_kv_heads=2,
        ff_dim=128, num_layers=2, seq_len=64, gated=True,
        max_positions=0, dtype="float32")
    # attn_impl pinned to the gather math on EVERY backend: the A/B
    # measures dispatch structure (steps per host round-trip), and the
    # token-parity lock demands all three engines share one attention
    # basis — the speculative verify pass runs dense-gather (the
    # Pallas decode kernel is single-query), so an auto-Pallas 1-step
    # engine on chip would only agree to kernel tolerance, flaking the
    # exact-equality lock on precisely the platform that matters
    base = ServingConfig(slots=4, page_size=8, num_pages=48,
                         max_seq_len=40, slo_ttft_ms=250.0,
                         slo_tpot_ms=100.0, attn_impl="gather")
    n_fused = 16
    variants = {
        "one_step": base,
        "multi_step": dataclasses.replace(base, multi_step_n=n_fused),
        "speculative": dataclasses.replace(
            base, multi_step_n=n_fused, speculative=True, spec_k=4,
            drafter="ngram"),
    }
    # saturating plan (arrivals land ~immediately): the wall is busy
    # time, so host fractions measure dispatch, not queue idle; long
    # outputs give the fused loop room to amortize
    plan = ArrivalPlan(kind="poisson", rate_rps=5000.0,
                       num_requests=8, seed=0, prompt_len=[8, 16],
                       output_len=[16, 24])
    params = init_params(jax.random.key(0), mc)
    requests = plan.sample()
    engines = {name: Engine(mc, cfg, params=params)
               for name, cfg in variants.items()}
    if live_path:
        # the --live-metrics stream (ISSUE 14 satellite): one windowed
        # snapshot line per 0.5 s of engine time from the 1-step
        # baseline engine (the sentinel-comparable line's engine —
        # mixing three engines into one stream would interleave
        # incomparable snapshots)
        from dlnetbench_tpu.serving.metrics import LiveMetricsWriter
        engines["one_step"].live = LiveMetricsWriter(live_path)
    streams: dict[str, dict] = {}
    for name, eng in engines.items():
        eng.run(requests)   # warm round (first-dispatch), discarded
    rounds: dict[str, list] = {name: [] for name in engines}
    for _ in range(3):
        for name, eng in engines.items():
            completed, wall = eng.run(requests)
            streams[name] = dict(eng.token_streams)
            rounds[name].append(smetrics.serving_block(
                completed, plan, slo_ttft_ms=base.slo_ttft_ms,
                slo_tpot_ms=base.slo_tpot_ms, wall_s=wall,
                engine_steps=eng.engine_steps,
                cache_stats=eng.cache.stats(),
                queue_depth_max=eng.queue_depth_max,
                batch_occupancy_mean=eng.batch_occupancy_mean(),
                decode_loop=eng.decode_loop_block()))
    parity = all(streams[name] == streams["one_step"]
                 for name in engines)
    dev = jax.devices()[0]
    line = _serving_decode_line(
        rounds["one_step"],
        suffix=f", {len(requests)} req slots={base.slots} "
               f"page={base.page_size} vs fused N={n_fused} vs "
               f"N={n_fused}+spec, {dev.device_kind}",
        multi_rounds=rounds["multi_step"],
        spec_rounds=rounds["speculative"], token_parity=parity)
    print(json.dumps(line))
    return line


def _disagg_line(mono_rounds: list[dict], dis_rounds: list[dict],
                 suffix: str = "", *,
                 token_parity: bool | None = None) -> dict:
    """Assemble the disagg_ab aux line from paired per-round
    ``serving`` blocks (pure — tests/test_bench_aux.py locks this
    schema).  The headline ``value`` is the DISAGGREGATED engine's
    round-median e2e p99 in ms (lower is better, sentinel-comparable
    like the serving_decode line); both arms ship artifact-grade
    ``{value, best, band, n}`` bands for TTFT p50/p99 and TPOT p50,
    the migration wire cost rides as bytes + per-send p50 ms bands,
    and the verdict is the interference question: did splitting the
    meshes pull decode TPOT below the monolithic band, bands
    disjoint?"""
    def _bands(rounds: list[dict]) -> dict:
        return {
            "ttft_p50_ms": stats_mod.summarize(
                [r["ttft_ms"]["p50"] for r in rounds], ndigits=3),
            "ttft_p99_ms": stats_mod.summarize(
                [r["ttft_ms"]["p99"] for r in rounds], ndigits=3),
            "tpot_p50_ms": stats_mod.summarize(
                [r["tpot_ms"]["p50"] for r in rounds], ndigits=3),
            "tokens_per_s": stats_mod.summarize(
                [r["tokens_per_s"] for r in rounds], ndigits=2),
        }
    mono, dis = _bands(mono_rounds), _bands(dis_rounds)
    migs = [r.get("migration") or {} for r in dis_rounds]
    dis["migration_bytes"] = stats_mod.summarize(
        [float(m.get("bytes", 0)) for m in migs], ndigits=1)
    dis["migration_ms_p50"] = stats_mod.summarize(
        [float((m.get("ms") or {}).get("p50", float("nan")))
         for m in migs], ndigits=3)
    dis["migration_bytes_ratio"] = migs[0].get("bytes_ratio_vs_bf16")
    p99 = stats_mod.summarize(
        [r["e2e_ms"]["p99"] for r in dis_rounds], ndigits=3)
    disjoint = (stats_mod.bands_overlap(
        mono["tpot_p50_ms"]["band"], dis["tpot_p50_ms"]["band"])
        is False
        and dis["tpot_p50_ms"]["value"] < mono["tpot_p50_ms"]["value"])
    line = {
        "metric": f"disagg_ab: monolithic vs disaggregated "
                  f"prefill/decode at equal chips, same seeded "
                  f"saturating plan (serving/disagg){suffix}",
        "value": p99["value"],
        "unit": "ms",
        "best": p99["best"],
        "band": p99["band"],
        "n": p99["n"],
        "monolithic": mono,
        "disaggregated": dis,
        "tpot_band_disjoint_drop": disjoint,
        "verdict": ("decode TPOT dropped, bands disjoint — the "
                    "prefill mesh's interference left the decode "
                    "replica" if disjoint else
                    "TPOT bands overlap — no interference flip at "
                    "this scale/noise"),
    }
    if token_parity is not None:
        line["token_parity"] = bool(token_parity)
    return stats_mod.flag_low_mode(line)


def _bench_disagg_ab() -> dict | None:
    """The ISSUE-16 A/B: a monolithic engine and a disaggregated
    prefill+decode pair — SAME weights, SAME chip count (world=2),
    SAME seeded saturating poisson plan — interleaved per round (r4
    pairing).  int8 KV on both arms so the migration channel carries
    the quantized wire the tentpole prices; the token-parity lock
    compares the full greedy streams."""
    import dataclasses

    from dlnetbench_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
    from dlnetbench_tpu.serving import metrics as smetrics
    from dlnetbench_tpu.serving.arrivals import ArrivalPlan
    from dlnetbench_tpu.serving.disagg import DisaggServer
    from dlnetbench_tpu.serving.scheduler import Engine, ServingConfig

    if len(jax.devices()) < 2:
        return None  # the split needs two devices to mean anything
    mc = TransformerConfig(
        vocab_size=256, embed_dim=64, num_heads=4, num_kv_heads=2,
        ff_dim=128, num_layers=2, seq_len=64, gated=True,
        max_positions=0, dtype="float32")
    # attn_impl pinned to gather for the same reason as serving_decode:
    # the parity lock needs one attention basis on every backend
    mono_cfg = ServingConfig(
        slots=4, page_size=8, num_pages=48, max_seq_len=40,
        slo_ttft_ms=250.0, slo_tpot_ms=100.0, attn_impl="gather",
        cache_dtype="int8", multi_step_n=8, adaptive_n=True, world=2)
    dis_cfg = dataclasses.replace(
        mono_cfg, disaggregate=True, prefill_ranks=1, decode_ranks=1)
    plan = ArrivalPlan(kind="poisson", rate_rps=5000.0,
                       num_requests=8, seed=0, prompt_len=[8, 16],
                       output_len=[16, 24])
    params = init_params(jax.random.key(0), mc)
    requests = plan.sample()
    mono = Engine(mc, mono_cfg, params=params)
    dis = DisaggServer(mc, dis_cfg, params=params)
    mono.run(requests)  # warm round (first-dispatch), discarded
    dis.run(requests)
    mono_rounds, dis_rounds = [], []
    streams = {}
    for _ in range(3):
        completed, wall = mono.run(requests)
        streams["mono"] = dict(mono.token_streams)
        mono_rounds.append(smetrics.serving_block(
            completed, plan, slo_ttft_ms=mono_cfg.slo_ttft_ms,
            slo_tpot_ms=mono_cfg.slo_tpot_ms, wall_s=wall,
            engine_steps=mono.engine_steps,
            cache_stats=mono.cache.stats(),
            queue_depth_max=mono.queue_depth_max,
            batch_occupancy_mean=mono.batch_occupancy_mean(),
            decode_loop=mono.decode_loop_block()))
        completed, wall = dis.run(requests)
        streams["dis"] = dis.token_streams
        dis_rounds.append(smetrics.serving_block(
            completed, plan, slo_ttft_ms=mono_cfg.slo_ttft_ms,
            slo_tpot_ms=mono_cfg.slo_tpot_ms, wall_s=wall,
            engine_steps=dis.engine_steps(),
            cache_stats=dis.decode.cache.stats(),
            queue_depth_max=dis.prefill.queue_depth_max,
            batch_occupancy_mean=dis.decode.batch_occupancy_mean(),
            decode_loop=dis.decode.decode_loop_block(),
            migration=dis.channel.stats_block()))
    parity = streams["dis"] == streams["mono"]
    dev = jax.devices()[0]
    line = _disagg_line(
        mono_rounds, dis_rounds,
        suffix=f", {len(requests)} req slots={mono_cfg.slots} "
               f"int8 KV, world=2 (1p+1d), {dev.device_kind}",
        token_parity=parity)
    print(json.dumps(line))
    return line


def _fleet_line(arm_rounds: dict, suffix: str = "", *,
                token_parity: bool | None = None) -> dict:
    """Assemble the fleet_ab aux line from per-policy per-round
    ``{"serving": ..., "fleet": ...}`` dicts (pure —
    tests/test_bench_aux.py locks this schema).  ``arm_rounds`` maps
    each routing policy (round_robin / p2c / prefix_affinity) to its
    measured rounds at EQUAL chips on one seeded prefix-heavy plan.
    The headline ``value`` is the prefix_affinity arm's round-median
    TTFT p50 in ms (lower is better, sentinel-comparable like the
    serving_decode line); every arm ships artifact-grade
    ``{value, best, band, n}`` bands, the affinity arm adds its hit
    rate and migration-free prefix-token reuse, and the verdict is the
    routing question: did prefix-aware placement pull TTFT p50 below
    the round_robin band, bands disjoint?"""
    def _bands(rounds: list[dict]) -> dict:
        srv = [r["serving"] for r in rounds]
        return {
            "ttft_p50_ms": stats_mod.summarize(
                [r["ttft_ms"]["p50"] for r in srv], ndigits=3),
            "ttft_p99_ms": stats_mod.summarize(
                [r["ttft_ms"]["p99"] for r in srv], ndigits=3),
            "tokens_per_s": stats_mod.summarize(
                [r["tokens_per_s"] for r in srv], ndigits=2),
        }
    arms = {pol: _bands(rounds) for pol, rounds in arm_rounds.items()}
    pa_rounds = arm_rounds["prefix_affinity"]
    arms["prefix_affinity"]["affinity_hit_rate"] = stats_mod.summarize(
        [r["fleet"]["affinity_hit_rate"] for r in pa_rounds], ndigits=4)
    arms["prefix_affinity"]["prefix_reuse_tokens"] = stats_mod.summarize(
        [float(r["fleet"]["prefix_reuse_tokens"]) for r in pa_rounds],
        ndigits=1)
    p50 = arms["prefix_affinity"]["ttft_p50_ms"]
    rr = arms["round_robin"]["ttft_p50_ms"]
    disjoint = (stats_mod.bands_overlap(rr["band"], p50["band"])
                is False and p50["value"] < rr["value"])
    replicas = pa_rounds[0]["fleet"]["replicas"]
    line = {
        "metric": f"fleet_ab: round_robin vs p2c vs prefix_affinity "
                  f"routing at equal chips ({replicas} replicas), same "
                  f"seeded prefix-heavy plan (serving/fleet){suffix}",
        "value": p50["value"],
        "unit": "ms",
        "best": p50["best"],
        "band": p50["band"],
        "n": p50["n"],
        "round_robin": arms["round_robin"],
        "p2c": arms["p2c"],
        "prefix_affinity": arms["prefix_affinity"],
        "ttft_band_disjoint_drop": disjoint,
        "verdict": ("prefix-affinity TTFT p50 dropped below "
                    "round_robin, bands disjoint — routing to the "
                    "pages beat routing blind" if disjoint else
                    "TTFT bands overlap — no routing flip at this "
                    "scale/noise"),
    }
    if token_parity is not None:
        line["token_parity"] = bool(token_parity)
    return stats_mod.flag_low_mode(line)


def _bench_fleet_ab() -> dict | None:
    """The ISSUE-18 A/B: three two-replica fleets — SAME weights, SAME
    chip count, SAME seeded prefix-heavy plan, prefix_sharing on every
    arm — differing ONLY in routing policy, interleaved per round (r4
    pairing).  The token-parity lock compares the full greedy streams
    across all three arms (routing must be lossless placement)."""
    from dlnetbench_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
    from dlnetbench_tpu.serving import metrics as smetrics
    from dlnetbench_tpu.serving.arrivals import ArrivalPlan
    from dlnetbench_tpu.serving.fleet import FleetConfig, FleetServer
    from dlnetbench_tpu.serving.scheduler import ServingConfig

    if len(jax.devices()) < 2:
        return None  # a fleet of one replica routes nothing
    mc = TransformerConfig(
        vocab_size=256, embed_dim=64, num_heads=4, num_kv_heads=2,
        ff_dim=128, num_layers=2, seq_len=64, gated=True,
        max_positions=0, dtype="float32")
    # attn_impl pinned to gather for the same reason as serving_decode:
    # the parity lock needs one attention basis on every backend
    cfg = ServingConfig(
        slots=2, page_size=8, num_pages=64, max_seq_len=64,
        slo_ttft_ms=250.0, slo_tpot_ms=100.0, attn_impl="gather",
        prefix_sharing=True, warmup_requests=0)
    # arrivals SPACED (not a t=0 burst): affinity only has pages to
    # route to once earlier prompts have prefilled and published — a
    # burst plan would route the whole batch against empty tries and
    # measure nothing but p2c fallback
    plan = ArrivalPlan(kind="poisson", rate_rps=120.0,
                       num_requests=12, seed=2, prompt_len=[36, 44],
                       output_len=[4, 8], shared_prefix_len=32,
                       prefix_pool=2)
    params = init_params(jax.random.key(0), mc)
    requests = plan.sample()
    devs = jax.devices()[:2]
    servers = {
        pol: FleetServer(mc, cfg, FleetConfig(replicas=2, routing=pol),
                         params=params, devices=devs)
        for pol in ("round_robin", "p2c", "prefix_affinity")}
    for srv in servers.values():
        srv.run(requests)  # warm round (first-dispatch), discarded
    rounds: dict = {pol: [] for pol in servers}
    streams: dict = {}
    for _ in range(3):
        for pol, srv in servers.items():   # interleaved (r4 pairing)
            completed, wall = srv.run(requests)
            streams[pol] = srv.token_streams
            rounds[pol].append({
                "serving": smetrics.serving_block(
                    completed, plan, slo_ttft_ms=cfg.slo_ttft_ms,
                    slo_tpot_ms=cfg.slo_tpot_ms, wall_s=wall,
                    engine_steps=srv.engine_steps(),
                    queue_depth_max=srv.queue_depth_max,
                    batch_occupancy_mean=srv.batch_occupancy_mean(),
                    admitted_peak=srv.concurrent_peak),
                "fleet": srv.fleet_block(completed)})
    parity = (streams["round_robin"] == streams["p2c"]
              == streams["prefix_affinity"])
    dev = jax.devices()[0]
    line = _fleet_line(
        rounds,
        suffix=f", {len(requests)} req slots={cfg.slots}/replica, "
               f"shared_prefix={plan.shared_prefix_len} "
               f"pool={plan.prefix_pool}, {dev.device_kind}",
        token_parity=parity)
    print(json.dumps(line))
    return line


def _sampling_ab_line(sampled_rounds: list[dict],
                      spec_rounds: list[dict], suffix: str = "", *,
                      token_identity: bool | None = None) -> dict:
    """Assemble the sampling_ab aux line from paired per-round
    ``serving`` blocks (pure — tests/test_bench_aux.py locks this
    schema).  The two arms run SEEDED SAMPLING at T=0.8: the fused
    N-step engine without speculation vs the same engine with
    lossless speculative sampling (truncated drafter).  The headline
    ``value`` is the SPECULATIVE arm's round-median e2e p99 in ms
    (lower is better, sentinel-comparable like serving_decode; the
    bench HEADLINE stays greedy — this line is the sampled tier's own
    evidence).  Both arms ship artifact-grade ``{value, best, band,
    n}`` bands, the spec arm adds its measured acceptance-rate band,
    the verdict is the ISSUE-19 question — did rejection-sampling
    speculation push sampled tokens/s band-disjointly ABOVE the
    non-spec sampled arm? — and ``token_identity`` locks the other
    half of the tentpole: the classic 1-step sampled stream equals
    the fused N-step sampled stream bit for bit."""
    def _bands(rounds: list[dict]) -> dict:
        return {
            "e2e_p99_ms": stats_mod.summarize(
                [r["e2e_ms"]["p99"] for r in rounds], ndigits=3),
            "tpot_p50_ms": stats_mod.summarize(
                [r["tpot_ms"]["p50"] for r in rounds], ndigits=3),
            "tokens_per_s": stats_mod.summarize(
                [r["tokens_per_s"] for r in rounds], ndigits=2),
        }
    sampled, spec = _bands(sampled_rounds), _bands(spec_rounds)
    spec["acceptance_rate"] = stats_mod.summarize(
        [((r.get("decode_loop") or {}).get("spec") or {})
         .get("acceptance_rate", 0.0) for r in spec_rounds],
        ndigits=4)
    tps_s, tps_p = sampled["tokens_per_s"], spec["tokens_per_s"]
    disjoint = (stats_mod.bands_overlap(tps_s["band"], tps_p["band"])
                is False and tps_p["value"] > tps_s["value"])
    p99 = spec["e2e_p99_ms"]
    line = {
        "metric": f"sampling_ab: seeded sampling T=0.8 — fused decode "
                  f"vs lossless speculative sampling (rejection "
                  f"verify, truncated drafter), same seeded plan "
                  f"(serving/sampling){suffix}",
        "value": p99["value"],
        "unit": "ms",
        "best": p99["best"],
        "band": p99["band"],
        "n": p99["n"],
        "sampled": sampled,
        "spec_sampled": spec,
        "tokens_per_s_band_disjoint_gain": disjoint,
        "verdict": ("speculative sampling pushed sampled tokens/s "
                    "above the non-spec arm, bands disjoint — the "
                    "rejection verify kept the speedup sampling used "
                    "to forfeit" if disjoint else
                    "tokens/s bands overlap — no speculation gain "
                    "under sampling at this scale/noise"),
    }
    if token_identity is not None:
        line["token_identity"] = bool(token_identity)
    return stats_mod.flag_low_mode(line)


def _bench_sampling_ab() -> dict | None:
    """The ISSUE-19 A/B: two sampled engines — SAME weights, SAME
    seeded saturating plan, SAME draw keys (seed/uid/position) —
    fused N-step seeded sampling vs fused N-step + lossless
    speculative sampling, interleaved per round (r4 pairing).  A
    classic 1-step sampled engine runs once alongside as the
    bit-identity witness (the tentpole's replay lock: the fused
    stream must EQUAL the 1-step stream token for token — sampling
    keyed by (seed, uid, position) makes N a pure perf knob)."""
    import dataclasses

    from dlnetbench_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
    from dlnetbench_tpu.serving import metrics as smetrics
    from dlnetbench_tpu.serving.arrivals import ArrivalPlan
    from dlnetbench_tpu.serving.scheduler import Engine, ServingConfig

    mc = TransformerConfig(
        vocab_size=256, embed_dim=64, num_heads=4, num_kv_heads=2,
        ff_dim=128, num_layers=2, seq_len=64, gated=True,
        max_positions=0, dtype="float32")
    # gather attention on every backend: the bit-identity lock needs
    # one attention basis (same reasoning as serving_decode's parity)
    base = ServingConfig(slots=4, page_size=8, num_pages=48,
                         max_seq_len=40, slo_ttft_ms=250.0,
                         slo_tpot_ms=100.0, attn_impl="gather",
                         temperature=0.8, top_p=0.95, sample_seed=7)
    n_fused = 16
    variants = {
        "sampled": dataclasses.replace(base, multi_step_n=n_fused),
        "spec_sampled": dataclasses.replace(
            base, multi_step_n=n_fused, speculative=True, spec_k=4,
            drafter="truncated", drafter_layers=1),
    }
    plan = ArrivalPlan(kind="poisson", rate_rps=5000.0,
                       num_requests=8, seed=0, prompt_len=[8, 16],
                       output_len=[16, 24])
    params = init_params(jax.random.key(0), mc)
    requests = plan.sample()
    engines = {name: Engine(mc, cfg, params=params)
               for name, cfg in variants.items()}
    one_step = Engine(mc, base, params=params)
    one_step.run(requests)          # the witness: one replay suffices
    one_step.run(requests)
    witness = dict(one_step.token_streams)
    for eng in engines.values():
        eng.run(requests)   # warm round (first-dispatch), discarded
    rounds: dict[str, list] = {name: [] for name in engines}
    identity = True
    for _ in range(3):
        for name, eng in engines.items():
            completed, wall = eng.run(requests)
            if name == "sampled":
                identity = identity and (dict(eng.token_streams)
                                         == witness)
            rounds[name].append(smetrics.serving_block(
                completed, plan, slo_ttft_ms=base.slo_ttft_ms,
                slo_tpot_ms=base.slo_tpot_ms, wall_s=wall,
                engine_steps=eng.engine_steps,
                cache_stats=eng.cache.stats(),
                queue_depth_max=eng.queue_depth_max,
                batch_occupancy_mean=eng.batch_occupancy_mean(),
                decode_loop=eng.decode_loop_block()))
    dev = jax.devices()[0]
    line = _sampling_ab_line(
        rounds["sampled"], rounds["spec_sampled"],
        suffix=f", {len(requests)} req slots={base.slots} "
               f"N={n_fused} spec_k=4 T={base.temperature} "
               f"top_p={base.top_p}, {dev.device_kind}",
        token_identity=identity)
    print(json.dumps(line))
    return line


def _kv_parity_err(cache_dtype: str, seed: int) -> float:
    """One seeded decode-parity probe (ISSUE 12): write the same
    token stream into a dense and a quantized page pool (the engine's
    own write path, ``kv_cache.quant_write_span``) and return the max
    absolute error of the paged-attention output vs the bf16 cache —
    the number the ``QUANT_DECODE_TOL`` bars judge."""
    import numpy as np

    from dlnetbench_tpu.serving import kv_cache as KV

    base = dict(num_layers=1, num_kv_heads=2, head_dim=16, num_pages=8,
                page_size=4, max_seqs=2, max_pages_per_seq=4)
    cc_d = KV.CacheConfig(**base)
    cc_q = KV.CacheConfig(**base, cache_dtype=cache_dtype)
    kd, vd = KV.device_buffers(cc_d)
    kq, vq, ks, vs = KV.device_buffers(cc_q)
    bt = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4))
    rng = np.random.RandomState(seed)
    fmt = cc_q.quant_fmt
    for t in range(10):
        knew = jnp.asarray(rng.randn(2, 1, 2, 16).astype(np.float32))
        vnew = jnp.asarray(rng.randn(2, 1, 2, 16).astype(np.float32))
        pos = jnp.full((2,), t, jnp.int32)
        ok = jnp.ones((2, 1), bool)
        pid = jnp.take_along_axis(bt, (pos // 4)[:, None], 1)[:, 0]
        kd = kd.at[0, :, pid, pos % 4, :].set(knew[:, 0], mode="drop")
        vd = vd.at[0, :, pid, pos % 4, :].set(vnew[:, 0], mode="drop")
        kq, ks = KV.quant_write_span(kq, ks, 0, knew, pos, ok, bt,
                                     fmt=fmt, page_size=4, num_pages=8)
        vq, vs = KV.quant_write_span(vq, vs, 0, vnew, pos, ok, bt,
                                     fmt=fmt, page_size=4, num_pages=8)
    q = jnp.asarray(rng.randn(2, 4, 16).astype(np.float32)) * 16**-0.5
    lengths = jnp.asarray([10, 9], jnp.int32)
    ref = KV.paged_attention_decode(q, kd[0], vd[0], lengths, bt,
                                    impl="gather")
    got = KV.paged_attention_decode(q, kq[0], vq[0], lengths, bt,
                                    k_scale=ks[0], v_scale=vs[0],
                                    fmt=fmt, impl="gather")
    return float(jnp.max(jnp.abs(got - ref)))


def _kv_density_line(rounds: dict, parity: dict, pool_bytes: int,
                     suffix: str = "") -> dict:
    """Assemble the kv_density_ab aux line (pure —
    tests/test_bench_aux.py locks this schema).  ``rounds`` maps cache
    dtype -> per-round ``serving`` blocks from engines sized to the
    SAME pool-byte budget (scale arrays priced in); ``parity`` maps
    quant dtype -> per-round decode-parity max errors.  The headline
    ``value`` is the DENSE engine's round-median e2e p99 ms (lower is
    better — sentinel-comparable like every latency line); each
    variant ships ``{value, best, band, n}`` for admitted slots,
    tokens/s and parity max-error, plus the capacity ratio vs dense
    with its band."""
    from dlnetbench_tpu.serving.kv_cache import QUANT_DECODE_TOL

    base = rounds["bf16"]
    summary = stats_mod.summarize([r["e2e_ms"]["p99"] for r in base],
                                  ndigits=3)
    base_adm = [r["admitted_concurrency_peak"] for r in base]
    variants = {}
    for name, rnds in rounds.items():
        v = {
            "num_pages": rnds[0]["kv_cache"]["num_pages"],
            "pool_bytes": rnds[0]["kv_cache"]["pool_bytes"],
            "admitted_slots": stats_mod.summarize(
                [r["admitted_concurrency_peak"] for r in rnds],
                ndigits=2),
            "tokens_per_s": stats_mod.summarize(
                [r["tokens_per_s"] for r in rnds], ndigits=2),
            "e2e_p99_ms": stats_mod.summarize(
                [r["e2e_ms"]["p99"] for r in rnds], ndigits=3),
            "goodput_frac": stats_mod.summarize(
                [r["goodput_frac"] for r in rnds], ndigits=4),
            # goodput-at-SLO in requests/s — the axis the capacity win
            # must be band-disjoint on (a denser cache drains the same
            # saturating plan faster at the same SLO)
            "goodput_rps": stats_mod.summarize(
                [r["goodput_rps"] for r in rnds], ndigits=3),
        }
        if name != "bf16":
            v["capacity_x"] = stats_mod.summarize(
                [r["admitted_concurrency_peak"] / b
                 for r, b in zip(rnds, base_adm) if b > 0], ndigits=3)
            errs = parity[name]
            tol = QUANT_DECODE_TOL[name]
            v["parity_max_err"] = stats_mod.summarize(errs, ndigits=6)
            v["parity_tol"] = tol
            v["parity_ok"] = bool(max(errs) <= tol)
        variants[name] = v
    return stats_mod.flag_low_mode({
        "metric": f"kv_density_ab: dense vs int8 vs fp8 paged-KV "
                  f"decode at equal pool bytes, admitted concurrency "
                  f"+ parity bars (serving/){suffix}",
        "value": summary["value"],
        "unit": "ms",
        "best": summary["best"],
        "band": summary["band"],
        "n": summary["n"],
        "pool_bytes_budget": pool_bytes,
        "variants": variants,
    })


def _bench_kv_density() -> dict | None:
    """The ISSUE 12 density A/B: three engines — dense, int8, fp8
    paged KV — each sized to the SAME pool-byte budget (the quantized
    pools buy ~4x the pages once their scale arrays are priced in),
    replay one seeded saturating plan interleaved per round (r4
    pairing).  The pool, not the slot count, is the binding resource
    (slots > pages/request), so admitted concurrency measures cache
    density; the decode-parity probes bound the numeric cost against
    the stated per-recipe tolerance bars."""
    import dataclasses

    from dlnetbench_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
    from dlnetbench_tpu.serving import kv_cache as KV
    from dlnetbench_tpu.serving import metrics as smetrics
    from dlnetbench_tpu.serving.arrivals import ArrivalPlan
    from dlnetbench_tpu.serving.scheduler import Engine, ServingConfig

    mc = TransformerConfig(
        vocab_size=256, embed_dim=64, num_heads=4, num_kv_heads=2,
        ff_dim=128, num_layers=2, seq_len=64, gated=True,
        max_positions=0, dtype="float32")
    # slots deliberately EXCEED what any variant's pool can hold, so
    # the page pool (the resource being densified), never the slot
    # count, caps admitted concurrency
    dense = ServingConfig(slots=24, page_size=8, num_pages=25,
                          max_seq_len=40, slo_ttft_ms=400.0,
                          slo_tpot_ms=150.0, attn_impl="gather")
    cc_args = dict(num_layers=mc.num_layers,
                   num_kv_heads=mc.num_kv_heads, head_dim=mc.head_dim,
                   page_size=dense.page_size, max_seqs=dense.slots,
                   max_pages_per_seq=dense.max_seq_len
                   // dense.page_size, dtype=mc.dtype)
    budget = KV.CacheConfig(**cc_args, num_pages=dense.num_pages,
                            cache_dtype="bf16").pool_bytes
    variants = {"bf16": dense}
    for cd in ("int8", "fp8"):
        pages = KV.pages_for_pool_bytes(
            budget, KV.CacheConfig(**cc_args, num_pages=1,
                                   cache_dtype=cd))
        variants[cd] = dataclasses.replace(dense, cache_dtype=cd,
                                           num_pages=pages)
    plan = ArrivalPlan(kind="poisson", rate_rps=5000.0,
                       num_requests=20, seed=0, prompt_len=[8, 16],
                       output_len=[12, 20])
    params = init_params(jax.random.key(0), mc)
    requests = plan.sample()
    engines = {name: Engine(mc, cfg, params=params)
               for name, cfg in variants.items()}
    for eng in engines.values():
        eng.run(requests)   # warm round (first-dispatch), discarded
    rounds: dict[str, list] = {name: [] for name in engines}
    parity: dict[str, list] = {"int8": [], "fp8": []}
    for rnd in range(3):
        for name, eng in engines.items():
            completed, wall = eng.run(requests)
            rounds[name].append(smetrics.serving_block(
                completed, plan, slo_ttft_ms=dense.slo_ttft_ms,
                slo_tpot_ms=dense.slo_tpot_ms, wall_s=wall,
                engine_steps=eng.engine_steps,
                cache_stats=eng.cache.stats(),
                queue_depth_max=eng.queue_depth_max,
                batch_occupancy_mean=eng.batch_occupancy_mean(),
                decode_loop=eng.decode_loop_block(),
                admitted_peak=eng.concurrent_peak))
        for cd in parity:
            parity[cd].append(_kv_parity_err(cd, seed=rnd))
    dev = jax.devices()[0]
    line = _kv_density_line(
        rounds, parity, budget,
        suffix=f", {len(requests)} req slots={dense.slots} "
               f"page={dense.page_size}, {dev.device_kind}")
    print(json.dumps(line))
    return line


def _bench_straggler_ab() -> dict | None:
    """Paired faulted-vs-clean straggler A/B (ISSUE 5 satellite): the
    dp proxy's bucketed-allreduce step at tiny scale, timed clean and
    with a scripted per-step delay (faults/inject.py) injected INSIDE
    the timed window, interleaved per round (the r4 pairing protocol —
    adjacent measurement cancels drift).  The line reports both bands,
    the injected delay, and the measured amplification
    (inflation / injected delay): ~1.0 on a single-controller mesh
    (the delay gates dispatch directly); on a multi-host mesh the same
    A/B prices collective gating by a straggler host.  Needs >= 2
    devices — one device has no collective to gate."""
    from dlnetbench_tpu.core.model_stats import load_model_stats
    from dlnetbench_tpu.faults.inject import FaultInjector
    from dlnetbench_tpu.faults.plan import FaultEvent, FaultPlan
    from dlnetbench_tpu.parallel.mesh import make_flat_mesh
    from dlnetbench_tpu.proxies import dp as dp_proxy
    from dlnetbench_tpu.proxies.base import ProxyConfig
    from dlnetbench_tpu.utils.timing import time_chain

    n = len(jax.devices())
    if n < 2:
        _skipped("straggler A/B",
                 f"needs >= 2 devices, have {n} — no collective for a "
                 f"straggler to gate")
        return None
    cfg = ProxyConfig(size_scale=1e-3, time_scale=1e-3)
    bundle = dp_proxy.build(load_model_stats("gpt2_l_16_bfloat16"), 2, cfg,
                            mesh=make_flat_mesh(devices=jax.devices()),
                            dtype=jnp.float32)
    k, rounds = 4, 3
    # calibrate the injected delay against the clean step so the signal
    # clears the run-to-run noise: ~3x a clean step, floored at 2 ms
    warm_s = time_chain(bundle.full, k=k)
    delay_us = max(3 * warm_s * 1e6, 2000.0)
    plan = FaultPlan(events=[FaultEvent(kind="delay", ranks=[1],
                                        magnitude_us=delay_us)]).validate()
    injector = FaultInjector(plan)

    def faulted_step():
        injector.before_step()
        return bundle.full()

    clean_s, faulted_s = [], []
    for _ in range(rounds):  # interleaved: adjacent in time per round
        clean_s.append(time_chain(bundle.full, k=k))
        faulted_s.append(time_chain(faulted_step, k=k))
    clean = stats_mod.summarize(clean_s)
    faulted = stats_mod.summarize(faulted_s)
    amp = (faulted["value"] - clean["value"]) / (delay_us / 1e6)
    line = {
        "metric": "straggler A/B (dp step, faulted vs clean)",
        "value": round(amp, 3),
        "unit": "x (step inflation / injected delay)",
        "injected_ms": round(delay_us / 1e3, 3),
        "clean_ms": {"value": round(clean["value"] * 1e3, 3),
                     **_band_ms(clean)},
        "faulted_ms": {"value": round(faulted["value"] * 1e3, 3),
                       **_band_ms(faulted)},
        "n": rounds,
        "world": n,
    }
    from dlnetbench_tpu.analysis.attribution import straggler_block
    attr = straggler_block(clean["value"] * 1e3, faulted["value"] * 1e3,
                           delay_us / 1e3)
    if attr is not None:
        line["attribution"] = attr
    print(json.dumps(line))
    return line


def _bench_checkpoint_ab() -> dict | None:
    """Paired stall-vs-async checkpoint A/B (ISSUE 7 tentpole): the dp
    proxy's step at tiny scale with a per-step snapshot save
    (utils/checkpoint.py SnapshotCheckpointer) in both modes, against
    the save-free baseline, interleaved per round (the r4 pairing
    protocol).  ``stall`` puts the whole durable write ON the timed
    critical path; ``async`` keeps only the device sync + host snapshot
    in-window and drains the writer thread OFF it (between chains).
    The line's headline value is the fraction of the measured save cost
    the async mode moved off the critical path — the number that says
    whether async checkpointing is worth its writer thread at this
    state size — next to all three step bands and the measured
    per-save cost.  This is the measured half of the Daly-interval
    story: analysis/goodput.py prices intervals from exactly this
    in-window cost."""
    import itertools
    import shutil
    import tempfile
    from pathlib import Path

    from dlnetbench_tpu.core.model_stats import load_model_stats
    from dlnetbench_tpu.parallel.mesh import make_flat_mesh
    from dlnetbench_tpu.proxies import dp as dp_proxy
    from dlnetbench_tpu.proxies.base import ProxyConfig
    from dlnetbench_tpu.utils.checkpoint import SnapshotCheckpointer
    from dlnetbench_tpu.utils.timing import time_chain

    cfg = ProxyConfig(size_scale=1e-3, time_scale=1e-3)
    bundle = dp_proxy.build(load_model_stats("gpt2_l_16_bfloat16"), 2, cfg,
                            mesh=make_flat_mesh(devices=jax.devices()),
                            dtype=jnp.float32)
    k, rounds = 4, 3
    root = tempfile.mkdtemp(prefix="dlnb_ckpt_ab_")
    try:
        ckpts = {mode: SnapshotCheckpointer(
            Path(root) / mode, bundle.state, every=1, mode=mode, keep=2)
            for mode in ("stall", "async")}
        counters = {mode: itertools.count() for mode in ckpts}

        def step_with(mode):
            bundle.full()
            ckpts[mode].on_step(next(counters[mode]))

        base_s, stall_s, async_s = [], [], []
        for _ in range(rounds):  # interleaved: adjacent in time per round
            base_s.append(time_chain(bundle.full, k=k))
            stall_s.append(time_chain(lambda: step_with("stall"), k=k))
            async_s.append(time_chain(lambda: step_with("async"), k=k))
            ckpts["async"].wait()  # drain the writer OFF the timed window
        base = stats_mod.summarize(base_s)
        stall = stats_mod.summarize(stall_s)
        asyn = stats_mod.summarize(async_s)
        save_cost = stall["value"] - base["value"]
        hidden = ((stall["value"] - asyn["value"]) / save_cost
                  if save_cost > 0 else 0.0)
        line = {
            "metric": "checkpoint A/B (dp step, stall vs async save)",
            "value": round(hidden, 3),
            "unit": "fraction of save cost off the critical path "
                    "(async vs stall)",
            "baseline_ms": {"value": round(base["value"] * 1e3, 3),
                            **_band_ms(base)},
            "stall_ms": {"value": round(stall["value"] * 1e3, 3),
                         **_band_ms(stall)},
            "async_ms": {"value": round(asyn["value"] * 1e3, 3),
                         **_band_ms(asyn)},
            # the measured durable-save cost (stall mode: the whole
            # write; the Daly model's d under mode="stall")
            "save_ms": stats_mod.summarize(ckpts["stall"].checkpoint_ms,
                                           ndigits=3),
            "state_bytes": ckpts["stall"].state_bytes,
            "backend": ckpts["stall"].backend,
            "n": rounds,
        }
        print(json.dumps(line))
        return line
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _bench_overlap_ab() -> dict | None:
    """Paired overlap-vs-baseline SPMD A/B (ISSUE 4 tentpole): the real
    dp x pp x tp train step with tp_overlap=decomposed +
    grad_sync=bucketed against the blocking baseline, interleaved
    rounds, plus the measured overlap fraction from the full/compute/
    comm decomposition (models/overlap_bench.py).  Needs >= 2 devices —
    a single-chip session has no communication to overlap and degrades
    to a skipped marker."""
    from dlnetbench_tpu.models import overlap_bench

    n = len(jax.devices())
    if n < 2:
        _skipped("spmd overlap A/B",
                 f"needs >= 2 devices, have {n} — single-chip session "
                 f"has no communication to overlap")
        return None
    # a REAL model shape (unlike the dryrun's toy defaults): per-block
    # matmuls must be MXU-bound on a chip or the walls, ratio, and
    # overlap fraction would measure dispatch/fence overhead instead of
    # comm-compute overlap.  Sized well under the bench headline shape
    # so the six-program compile fits the aux deadline.
    line = overlap_bench.measure(n_devices=n, cfg_kwargs=dict(
        embed_dim=2048, num_heads=16, num_kv_heads=16, ff_dim=8192,
        num_layers=4, seq_len=2048, vocab_size=32768, num_experts=4,
        dtype="bfloat16"))
    print(json.dumps(line))
    return line


def _bench_int8_step(card, hw_key: str, dev, bf16_step_s: float,
                     opts, int8_backward: str = "master") -> dict | None:
    """END-TO-END int8 train step (VERDICT r4 #2): the same headline
    program with ``mlp_dtype="int8"`` — forward MLP dots quantized
    per-tensor to int8 and accumulated in int32 on the MXU
    (ops/int8.py), backward straight-through in bf16.  The isolated
    int8 matmul runs at 0.99 of the chip's 2x-bf16 int8 peak (r4), so
    this line answers whether that silicon headroom survives inside the
    full step, where quantization costs extra HBM passes (amax
    reduction + rescale per operand).

    Runs at the headline's EXACT config (no remat) — ``mlp_dtype`` is
    the only difference — so ``speedup_vs_bf16`` divides the headline
    measurement of this same session by this line.  That needed the r5
    fused whole-SwiGLU VJP (ops/int8.py swiglu_int8): the composed
    int8_dot form saved the [B, S, ff] down-projection input ``h`` as
    a residual the bf16 path never materializes and OOM'd no-remat
    (first r5 capture, docs/studies/int8_step_r5); recomputing ``h``
    elementwise from g/u brings the residual footprint back to the
    bf16 path's, and the step fits — measured 494.3 ms vs 537.5
    (0.92).  With ``int8_backward="switchback"`` (a second, opt-in
    JSON line) the dx-side backward matmuls are quantized too —
    454.9 ms = 0.85 of the headline; numerics measured in
    docs/studies/int8_step_r5.  ``vs_baseline`` divides by an
    int8-AWARE split-peak roofline: the int8-executed dots (forward
    MLP always; plus the dx-side backward dots under switchback) are
    priced at the int8 peak, the rest of the step at the bf16 peak —
    the step's AI is thousands of FLOP/B vs a ~240 ridge, so the
    compute-bound form of min(peak, AI*BW) is exact here.

    Reference frame: the reference's low-precision support stops at
    comm-buffer dtype selection (data_types.hpp:36-79); an int8
    *compute* step is beyond it, as SURVEY §2.1 demands."""
    from dlnetbench_tpu.core.hardware import HARDWARE
    from dlnetbench_tpu.core import roofline
    from dlnetbench_tpu.models import bench_step
    from dlnetbench_tpu.utils.timing import time_callable

    hw = HARDWARE[hw_key]
    label = ("int8 switchback train step"
             if int8_backward == "switchback" else "int8 train step")
    try:
        int8_peak = hw.peak("int8")
    except ValueError:
        _skipped(f"{label} ({hw_key})", f"{hw_key} has no int8 peak")
        return None

    K = 10
    train_k_fn, params, tokens, _, _ = bench_step.build(
        K, mlp_dtype="int8", int8_backward=int8_backward)
    from dlnetbench_tpu.core import executor
    train_k = executor.CompiledProgram(executor.Program(
        fn=train_k_fn, args=(params, tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS,
        compiler_options=opts))
    del params                    # executor owns a private donated copy
    _, losses = train_k()         # warm run (already compiled)
    jax.block_until_ready(losses)
    summary = stats_mod.summarize(
        [t / K for t in time_callable(train_k, reps=3)])
    step_s, loss = summary["value"], float(losses[-1])

    lm_head_flops = 2 * BATCH * SEQ * card.embed_dim * VOCAB
    fwd_flops = roofline.model_flops(card, BATCH) + lm_head_flops
    total_flops = 3 * fwd_flops
    # int8-executed dots: fwd MLP always; switchback also quantizes the
    # backward's dx-side matmuls (dh + dx = same FLOPs as one fwd MLP
    # pass of the three dots' dx legs — 3 of the 6 bwd MLP dots)
    int8_flops = roofline.mlp_flops(card, BATCH)  # fwd MLP dots
    if int8_backward == "switchback":
        int8_flops *= 2  # + the dx-side backward dots
    roofline_split_s = (int8_flops / int8_peak
                        + (total_flops - int8_flops) / hw.peak("bfloat16"))
    if int8_backward == "switchback":
        bwd_desc = "dx-side bwd dots int8 too (SwitchBack recipe), dW " \
                   "master bf16"
        delta_desc = "mlp_dtype + int8_backward the only deltas"
    else:
        bwd_desc = "bwd straight-through bf16"
        delta_desc = "mlp_dtype the only delta"
    line = {
        "metric": f"int8-MLP train step (fwd MLP dots int8 via fused "
                  f"swiglu VJP, {bwd_desc}; headline "
                  f"config, {delta_desc}), "
                  f"{dev.device_kind} ({hw_key})",
        "value": round(step_s * 1e3, 3),
        "unit": "ms",
        **_band_ms(summary),
        "speedup_vs_bf16": round(bf16_step_s / step_s, 4),
        "headline_bf16_ms": round(bf16_step_s * 1e3, 3),
        "vs_baseline": round(roofline_split_s / step_s, 4),
        "tflops_achieved": round(total_flops / step_s / 1e12, 2),
        "loss": round(loss, 4),
    }
    # attribution against the same split-peak roofline the line's
    # vs_baseline prices (int8 dots at the int8 peak, rest at bf16):
    # the effective peak is total_flops / roofline_split_s
    line = _stamp_attr(
        stats_mod.flag_low_mode(line), time_s=step_s, flops=total_flops,
        nbytes=roofline.train_step_bytes(card, BATCH, "bfloat16"), hw=hw,
        dtype_key="bfloat16", peak_flops=total_flops / roofline_split_s)
    print(json.dumps(line))
    return line


def _bench_fp8_mlp(card, hw_key: str, dev) -> dict | None:
    """Second bench line: the fp8 (e4m3, per-tensor-scaled) MLP matmul
    path against the chip's OWN fp8 roofline (v5e 394 TF/s = 2x bf16) —
    the compute path the stat files' float8 dtype models.  Reported
    separately from the bf16 train step: its denominator is the fp8
    peak, so the two ratios are never mixed.

    Shape note (measured r3): MULTI-matmul fp8 bodies hit an XLA compile
    pathology on this toolchain — the full bench-shape swiglu_fp8 chain
    took >9 min to compile (gate+up+silu alone 296 s) while single-dot
    programs compile in seconds, so this line chains ONE square
    MLP-projection matmul per scan step (84 s compile at K=20, cut to
    K=10 here).  History: r3/r4 measured ~149 TF/s and concluded
    "bf16-class, upcast on the MXU" — REVISED in r5: with the
    headline's ~7 GB of device buffers freed before this line runs
    (main() del), the same code measures 274 TF/s = 0.70 of the fp8
    peak, above the bf16 peak — native e4m3 execution, previously
    throttled by the harness's own HBM residency (docs/PERF.md r5)."""
    import jax.numpy as jnp

    from dlnetbench_tpu.core.hardware import BYTES_PER_ELEMENT, HARDWARE
    from dlnetbench_tpu.ops.fp8 import fp8_dot

    hw = HARDWARE[hw_key]
    try:
        fp8_peak = hw.peak("float8")
    except ValueError:
        _skipped(f"fp8 mlp matmul ({hw_key})",
                 f"{hw_key} has no float8 peak")
        return None

    tokens, d = BATCH * SEQ, card.embed_dim
    x = jax.random.normal(jax.random.key(2), (tokens, d), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(3), (d, d), jnp.bfloat16) * 0.02

    K = 10  # chained in one program (dispatch amortization)

    def chain(x0):
        def body(xc, _):
            return fp8_dot(xc, w).astype(xc.dtype), ()
        return jax.lax.scan(body, x0, None, length=K)[0]

    xla_cost: dict = {}
    summary = _measure_chain(chain, x, K, cost_out=xla_cost)
    t_s = summary["value"]

    flops = 2 * tokens * d * d
    # bytes per matmul: e4m3 operand reads + bf16 output write
    nbytes = int(BYTES_PER_ELEMENT["float8"] * (tokens * d + d * d)
                 + BYTES_PER_ELEMENT["bfloat16"] * tokens * d)
    roofline_s = _roofline_s(flops, nbytes, hw, "float8")
    line = {
        "metric": f"fp8(e4m3) mlp-projection matmul, {tokens} tok D={d}, "
                  f"{dev.device_kind} ({hw_key}, fp8 peak "
                  f"{fp8_peak/1e12:.0f} TF/s)",
        "value": round(t_s * 1e3, 3),
        "unit": "ms",
        **_band_ms(summary),
        "vs_baseline": round(roofline_s / t_s, 4),
        "tflops_achieved": round(flops / t_s / 1e12, 2),
    }
    line = _stamp_attr(stats_mod.flag_low_mode(_flag_above_peak(line)),
                       time_s=t_s, flops=flops, nbytes=nbytes, hw=hw,
                       dtype_key="float8", xla_cost=xla_cost)
    print(json.dumps(line))
    return line


def _bench_fp8_swiglu_chain(card, hw_key: str, dev) -> dict | None:
    """The REAL ``swiglu_fp8`` path, stage by stage (VERDICT r3 #7a).

    Multi-matmul fp8 jit bodies hit the toolchain's compile pathology
    (>9 min for the full chain; r4 showed the same for bf16 pairs), so
    each of the three projections is measured as its OWN chained
    program — the same fp8_dot the model executes, exact bench shapes,
    quantization included — and the stage medians are summed.  The
    elementwise silu*u between stages is covered by the headline step's
    profile (VPU work that overlaps) and is not separately billed; the
    metric text says exactly what is summed.
    """
    import jax.numpy as jnp

    from dlnetbench_tpu.core.hardware import BYTES_PER_ELEMENT, HARDWARE
    from dlnetbench_tpu.ops.fp8 import fp8_dot

    hw = HARDWARE[hw_key]
    try:
        fp8_peak = hw.peak("float8")
    except ValueError:
        _skipped(f"fp8 swiglu chain ({hw_key})",
                 f"{hw_key} has no float8 peak")
        return None

    tokens, d, f = BATCH * SEQ, card.embed_dim, card.ff_dim
    x = jax.random.normal(jax.random.key(5), (tokens, d), jnp.bfloat16)
    wg = jax.random.normal(jax.random.key(6), (d, f), jnp.bfloat16) * 0.02
    wd = jax.random.normal(jax.random.key(7), (f, d), jnp.bfloat16) * 0.02
    h0 = jax.random.normal(jax.random.key(8), (tokens, f), jnp.bfloat16)

    K = 8

    def up_chain(x0):   # gate and up are the same (T,D)@(D,F) stage
        def body(xc, _):
            y = fp8_dot(xc, wg)
            # feed a slice back so the dot cannot be loop-hoisted
            return (xc + y[:, :d] * 1e-6).astype(xc.dtype), ()
        return jax.lax.scan(body, x0, None, length=K)[0]

    def down_chain(h):  # (T,F)@(F,D)
        def body(hc, _):
            y = fp8_dot(hc, wd)
            # the full (T,D) result feeds the carry — a scalar-only
            # dependency could legally let XLA shrink the dot to a
            # slice and void the measurement
            return hc.at[:, :d].add(y.astype(hc.dtype) * 1e-6), ()
        return jax.lax.scan(body, h, None, length=K)[0]

    # chain total: gate + up (two identical stages) + down — each stage
    # measured independently, bands added linearly
    up_cost: dict = {}
    down_cost: dict = {}
    summary = _combine_linear(
        [(2, _measure_chain(up_chain, x, K, cost_out=up_cost)),
         (1, _measure_chain(down_chain, h0, K, cost_out=down_cost))])
    t_s = summary["value"]
    xla_cost = ({k: 2 * up_cost.get(k, 0) + down_cost.get(k, 0)
                 for k in set(up_cost) | set(down_cost)}
                if up_cost or down_cost else {})

    flops = 6 * tokens * d * f  # three T*D*F matmuls
    nbytes = int(BYTES_PER_ELEMENT["float8"]
                 * (2 * tokens * d + 2 * d * f + 2 * tokens * f + f * d)
                 + BYTES_PER_ELEMENT["bfloat16"] * (2 * tokens * f
                                                    + tokens * d))
    line = {
        "metric": f"fp8(e4m3) swiglu chain (gate+up+down as separate "
                  f"chained stages; multi-matmul fp8 bodies hit the XLA "
                  f"compile pathology), {tokens} tok D={d} F={f}, "
                  f"{dev.device_kind} ({hw_key}, fp8 peak "
                  f"{fp8_peak/1e12:.0f} TF/s)",
        "value": round(t_s * 1e3, 3),
        "unit": "ms",
        **_band_ms(summary),
        "vs_baseline": round(_roofline_s(flops, nbytes, hw, "float8")
                             / t_s, 4),
        "tflops_achieved": round(flops / t_s / 1e12, 2),
    }
    line = _stamp_attr(stats_mod.flag_low_mode(_flag_above_peak(line)),
                       time_s=t_s, flops=flops, nbytes=nbytes, hw=hw,
                       dtype_key="float8", xla_cost=xla_cost)
    print(json.dumps(line))
    return line


def _bench_int8_matmul(card, hw_key: str, dev) -> dict | None:
    """int8 matmul line (VERDICT r3 #7b): the v5e's natively-accelerated
    low precision (394 TOPS = 2x bf16).  Square D x D chain of
    lax.dot_general(int8, int8) -> int32, rescaled to int8 between
    steps — measures whether this stack reaches the int8 rate the
    hardware table claims, or records the wall like the fp8 line."""
    import jax.numpy as jnp

    from dlnetbench_tpu.core.hardware import BYTES_PER_ELEMENT, HARDWARE

    hw = HARDWARE[hw_key]
    try:
        int8_peak = hw.peak("int8")
    except ValueError:
        _skipped(f"int8 matmul ({hw_key})", f"{hw_key} has no int8 peak")
        return None

    tokens, d = BATCH * SEQ, card.embed_dim
    x = jax.random.randint(jax.random.key(9), (tokens, d), -127, 128,
                           jnp.int8)
    w = jax.random.randint(jax.random.key(10), (d, d), -127, 128, jnp.int8)

    # K=40 so chain compute (~42 ms at peak) dominates the one
    # dispatch plus fence each round pays: a short chain's reading
    # swings with host latency.  Compile is O(1) in K (lax.scan).  The
    # fp8 lines keep K small deliberately — their compile pathology is
    # K-sensitive on this toolchain.
    K = 40

    def chain(x0):
        def body(xc, _):
            y = jax.lax.dot_general(xc, w, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.int32)
            return (y >> 8).astype(jnp.int8), ()
        return jax.lax.scan(body, x0, None, length=K)[0]

    xla_cost: dict = {}
    summary = _measure_chain(chain, x, K, cost_out=xla_cost)
    t_s = summary["value"]

    ops = 2 * tokens * d * d
    nbytes = int(BYTES_PER_ELEMENT["int8"] * (2 * tokens * d + d * d))
    line = {
        "metric": f"int8 matmul, {tokens} tok D={d}, {dev.device_kind} "
                  f"({hw_key}, int8 peak {int8_peak/1e12:.0f} TOP/s)",
        "value": round(t_s * 1e3, 3),
        "unit": "ms",
        **_band_ms(summary),
        "vs_baseline": round(_roofline_s(ops, nbytes, hw, "int8") / t_s,
                             4),
        "tops_achieved": round(ops / t_s / 1e12, 2),
    }
    line = _stamp_attr(stats_mod.flag_low_mode(_flag_above_peak(line)),
                       time_s=t_s, flops=ops, nbytes=nbytes, hw=hw,
                       dtype_key="int8", xla_cost=xla_cost)
    print(json.dumps(line))
    return line


def _ab_line(metric: str, summaries_s: dict, round_times_s: dict,
             flops_per_iter: int, roofline_s: float) -> dict:
    """Assemble one paired fused-vs-composed A/B JSON line (pure —
    tests/test_bench_aux.py locks this schema).  The line's headline
    ``value`` is the FUSED median (the path under test); every variant
    ships its own artifact-grade ``{value, best, band, n}`` sub-object
    in ms, and each non-composed variant a paired per-round ratio band
    vs composed (ratio < 1.0 = fused faster)."""
    fused = summaries_s["fused"]
    line = {
        "metric": metric,
        "value": round(fused["value"] * 1e3, 3),
        "unit": "ms",
        **_band_ms(fused),
        "vs_baseline": round(roofline_s / fused["value"], 4),
        "tflops_fused": round(flops_per_iter / fused["value"] / 1e12, 2),
        "tflops_composed": round(
            flops_per_iter / summaries_s["composed"]["value"] / 1e12, 2),
    }
    for name, s in summaries_s.items():
        line[name] = {"value": round(s["value"] * 1e3, 3), **_band_ms(s)}
    comp_rounds = round_times_s["composed"]
    for name in summaries_s:
        if name == "composed":
            continue
        ratios = [t / c for t, c in zip(round_times_s[name], comp_rounds)]
        line[f"ratio_{name}_vs_composed"] = stats_mod.summarize(
            ratios, ndigits=4)
    return stats_mod.flag_low_mode(_flag_above_peak(line))


def _bench_quant_fused_ab(card, hw_key: str, dev, fmt: str) -> dict | None:
    """Paired fused-vs-composed quantized-matmul A/B at the bench shape
    (ISSUE 3 tentpole; protocol = the r4 MLP study's interleaved
    rounds).  Two variants of the (T,D)@(D,F) up-projection chained
    K deep:

    * ``composed`` — the shipped XLA recipe (ops/int8.py int8_dot /
      ops/fp8.py fp8_dot): per-step amax reduction, rescale/cast to a
      materialized quantized copy, post-matmul sa*sb — each stage its
      own HBM pass.
    * ``fused`` — the Pallas kernel (ops/quantized_matmul.py): fresh
      amax still reduced by XLA (one read of x), but quantization
      happens in the kernel prologue in VMEM and sa*sb in the
      epilogue — the quantized activation never exists in HBM.

    The weight-quantization pass is loop-invariant and hoisted by XLA
    in BOTH variants (weights pre-quantized once per chain), so the A/B
    isolates exactly the per-step activation-quantization overhead."""
    import jax.numpy as jnp

    from dlnetbench_tpu.core.hardware import BYTES_PER_ELEMENT, HARDWARE
    from dlnetbench_tpu.ops import quantized_matmul as qmm

    hw = HARDWARE[hw_key]
    peak_key = "int8" if fmt == "int8" else "float8"
    label = f"{'int8' if fmt == 'int8' else 'fp8'} fused-quant A/B"
    try:
        peak = hw.peak(peak_key)
    except ValueError:
        _skipped(f"{label} ({hw_key})", f"{hw_key} has no {peak_key} peak")
        return None

    if fmt == "int8":
        from dlnetbench_tpu.ops.int8 import int8_dot as composed_dot
        fused_dot_op = qmm.int8_dot_fused
    else:
        from dlnetbench_tpu.ops.fp8 import fp8_dot as composed_dot
        fused_dot_op = qmm.fp8_dot_fused

    tokens, d, f = BATCH * SEQ, card.embed_dim, card.ff_dim
    x = jax.random.normal(jax.random.key(11), (tokens, d), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(12), (d, f), jnp.bfloat16) * 0.02
    # K=8 like the fp8 swiglu stages: these are single-matmul scan
    # bodies, but the fused variants add a Pallas call per step and the
    # composed fp8 body is the known compile-pathology shape — keep the
    # per-variant compile bounded (the persistent cache, enabled in
    # _compile_chain, amortizes re-runs)
    K = 8

    def chain_of(dot):
        def chain(x0):
            def body(xc, _):
                y = dot(xc, w)
                # feed a slice back so the dot cannot be loop-hoisted
                return (xc + y[:, :d] * 1e-6).astype(xc.dtype), ()
            return jax.lax.scan(body, x0, None, length=K)[0]
        return chain

    progs = {
        "composed": _compile_chain(chain_of(composed_dot), x),
        "fused": _compile_chain(chain_of(fused_dot_op), x),
    }
    summaries, round_times = _measure_paired(progs, K)

    flops = 2 * tokens * d * f
    # fused-path traffic model: x read once in bf16 (no quantized copy
    # materialized), pre-quantized weights read, bf16 output written
    nbytes = int(BYTES_PER_ELEMENT["bfloat16"] * (tokens * d + tokens * f)
                 + BYTES_PER_ELEMENT[peak_key] * d * f)
    line = _ab_line(
        f"{label}: fused-quantization Pallas matmul (VMEM prologue "
        f"quantize + in-register sa*sb epilogue) vs composed XLA "
        f"recipe, paired "
        f"interleaved rounds, {tokens} tok D={d} F={f}, "
        f"{dev.device_kind} ({hw_key}, {peak_key} peak "
        f"{peak/1e12:.0f} T/s)",
        summaries, round_times, flops,
        _roofline_s(flops, nbytes, hw, peak_key))
    # attribution of the FUSED path (the line's headline value)
    line = _stamp_attr(line, time_s=summaries["fused"]["value"],
                       flops=flops, nbytes=nbytes, hw=hw,
                       dtype_key=peak_key)
    print(json.dumps(line))
    return line


def _longcontext_line(summaries_s: dict, round_times_s: dict, *,
                      metric: str, mask_info: dict) -> dict:
    """Assemble the dense-vs-splash long-context A/B JSON line (pure —
    tests/test_bench_aux.py locks this schema).  The headline ``value``
    is the WINDOW-masked splash median ms (the production long-context
    recipe; lower-is-better, so the sentinel compares it like every ms
    line); every variant ships its artifact-grade ``{value, best,
    band, n}`` sub-object, masked variants a paired per-round ratio
    band vs dense, and ``speedup_vs_sparsity`` states measured speedup
    over the mask's block-accounting expectation (1.0 = the win is
    exactly the skipped work; ``mask_info`` carries each mask's spec
    label, sparsity_fraction and block skip stats as comparable
    globals)."""
    win = summaries_s["splash_window"]
    dense_rounds = round_times_s["dense"]
    line = {
        "metric": metric,
        "value": round(win["value"] * 1e3, 3),
        "unit": "ms",
        **_band_ms(win),
    }
    for name, s in summaries_s.items():
        line[name] = {"value": round(s["value"] * 1e3, 3), **_band_ms(s)}
    speedup_vs_sparsity = {}
    for name, s in summaries_s.items():
        if name == "dense":
            continue
        ratios = [t / d for t, d in zip(round_times_s[name],
                                        dense_rounds) if d > 0]
        ratio_band = stats_mod.summarize(ratios, ndigits=4)
        line[f"ratio_{name}_vs_dense"] = ratio_band
        info = mask_info.get(name)
        if info and info.get("expected_speedup") and ratio_band["value"]:
            # measured speedup from the PAIRED per-round ratio median
            # (the r4 protocol: only adjacent-in-time comparisons
            # cancel slow drift — unpaired medians don't)
            measured = 1.0 / ratio_band["value"]
            speedup_vs_sparsity[name] = round(
                measured / info["expected_speedup"], 4)
    line["speedup_vs_sparsity"] = speedup_vs_sparsity
    line["masks"] = mask_info
    # band-disjoint win of the headline (window) variant vs dense: the
    # acceptance bar (stats.bands_overlap), stated by the artifact
    line["band_disjoint_win"] = bool(
        win["value"] < summaries_s["dense"]["value"]
        and stats_mod.bands_overlap(win["band"],
                                    summaries_s["dense"]["band"])
        is False)
    return stats_mod.flag_low_mode(line)


def _bench_longcontext_ab(card, hw_key: str, dev) -> dict | None:
    """Dense-vs-splash long-context A/B (ISSUE 10 tentpole evidence):
    B=1 attention at S=64k (env-overridable) under causal / sliding-
    window / document-segment masks, r4 pairing protocol — per round
    every variant runs back-to-back, so the per-round ratios cancel
    slow drift.  The dense leg is the existing causal flash
    kernel; the splash legs consume the BlockMask (skipped blocks
    issue no DMA/MXU work), so the measured speedup should track each
    mask's block-level skip fraction — the line reports the ratio."""
    import jax.numpy as jnp

    import importlib

    from dlnetbench_tpu.core.hardware import HARDWARE
    from dlnetbench_tpu.ops import attention_mask as amask

    # the ops package re-exports the flash_attention FUNCTION under
    # the module's name; import the module itself for its internals
    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")

    hw = HARDWARE[hw_key]
    s = env_int("DLNB_BENCH_LC_SEQ", 64 * 1024)
    hq = env_int("DLNB_BENCH_LC_HEADS", 8)
    hkv = env_int("DLNB_BENCH_LC_KV_HEADS", max(1, hq // 4))
    dh = 128
    window = env_int("DLNB_BENCH_LC_WINDOW", max(1, s // 16))
    seg_avg = env_int("DLNB_BENCH_LC_SEG", max(1, s // 8))
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32

    q = jax.random.normal(jax.random.key(20), (1, s, hq, dh), dt)
    k = jax.random.normal(jax.random.key(21), (1, s, hkv, dh), dt)
    v = jax.random.normal(jax.random.key(22), (1, s, hkv, dh), dt)

    specs = {
        "splash_causal": amask.MaskSpec(causal=True),
        "splash_window": amask.MaskSpec(causal=True, window=window),
        "splash_segment": amask.MaskSpec(causal=True, seg_avg=seg_avg,
                                         seg_seed=0),
    }
    bq = fa._pick_block(s, fa._BLOCK_CANDIDATES_FWD)
    bk = bq
    if bq is None:
        _skipped(f"longcontext A/B ({hw_key})",
                 f"seq {s} has no flash block candidate")
        return None

    K = env_int("DLNB_BENCH_LC_K", 4)

    def chain_of(attn):
        def chain(q0):
            def body(qc, _):
                out = attn(qc)
                # feed the output back so the attention cannot be
                # loop-hoisted (the fp8-chain feedback convention)
                return (qc + out * 1e-6).astype(qc.dtype), ()
            return jax.lax.scan(body, q0, None, length=K)[0]
        return chain

    progs = {"dense": _compile_chain(
        chain_of(lambda qc: fa.flash_attention(qc, k, v, True, bq, bk)),
        q)}
    for name, spec in specs.items():
        progs[name] = _compile_chain(
            chain_of(lambda qc, _sp=spec: fa.splash_attention(
                qc, k, v, _sp, bq, bk)), q)
    summaries, round_times = _measure_paired(progs, K)

    # block-accounting expectations: visited blocks under each mask vs
    # the dense-causal baseline at the SAME block sizes
    dense_stats = amask.block_mask(specs["splash_causal"], s, bq,
                                   bk).stats()
    dense_visited = (dense_stats["blocks_total"]
                     - dense_stats["blocks_skipped"])
    mask_info = {}
    for name, spec in specs.items():
        st = amask.block_mask(spec, s, bq, bk).stats()
        visited = st["blocks_total"] - st["blocks_skipped"]
        mask_info[name] = {
            **amask.record_globals(spec, s),
            "block_skip_fraction": st["block_skip_fraction"],
            "expected_speedup": round(dense_visited / max(visited, 1),
                                      4),
        }

    # dense-causal forward flops (both matmuls, triangular half)
    flops = 2 * s * s * hq * dh
    line = _longcontext_line(
        summaries, round_times,
        metric=f"longcontext A/B: dense causal flash vs block-sparse "
               f"splash (causal / window({window}) / segment(avg="
               f"{seg_avg}) masks; skipped blocks issue no DMA/MXU "
               f"work; paired interleaved rounds, fwd attention only), "
               f"B=1 S={s} Hq={hq} Hkv={hkv} Dh={dh} blocks=({bq},"
               f"{bk}), {dev.device_kind} ({hw_key})",
        mask_info=mask_info)
    win_visited_frac = 1.0 - mask_info["splash_window"][
        "block_skip_fraction"]
    line["tflops_dense"] = round(
        flops / summaries["dense"]["value"] / 1e12, 2)
    line = _stamp_attr(
        line, time_s=summaries["splash_window"]["value"],
        flops=flops * win_visited_frac / max(
            1.0 - dense_stats["block_skip_fraction"], 1e-9),
        nbytes=int(jnp.dtype(dt).itemsize * s * (2 * hq + 2 * hkv)
                   * dh), hw=hw, dtype_key="bfloat16")
    print(json.dumps(line))
    return line


def _moe_ab_line(summaries_s: dict, round_times_s: dict, *,
                 metric: str, moe_info: dict,
                 active_params: dict) -> dict:
    """Assemble the dense-FFN-vs-MoE A/B line (ISSUE 15; pure —
    tests/test_bench_aux.py locks this schema).  The headline ``value``
    is the sparse-MoE train-step median ms (the production MoE recipe;
    lower-is-better so the sentinel compares it like every ms line);
    every variant ships its {value, best, band, n} sub-object, the MoE
    variants a paired per-round ratio band vs dense (the r4 protocol —
    at MATCHED ACTIVE PARAMS the ratio IS the routing+dispatch premium
    of sparse execution), and ``moe_info`` carries the routing knobs +
    measured layer-0 router stats as record globals."""
    mo = summaries_s["moe"]
    dense_rounds = round_times_s["dense"]
    line = {
        "metric": metric,
        "value": round(mo["value"] * 1e3, 3),
        "unit": "ms",
        **_band_ms(mo),
    }
    for name, s in summaries_s.items():
        line[f"{name}_ms"] = {"value": round(s["value"] * 1e3, 3),
                              **_band_ms(s)}
    for name in summaries_s:
        if name == "dense":
            continue
        ratios = [t / d for t, d in zip(round_times_s[name],
                                        dense_rounds) if d > 0]
        line[f"ratio_{name}_vs_dense"] = stats_mod.summarize(
            ratios, ndigits=4)
    line["band_disjoint"] = (
        stats_mod.bands_overlap(mo["band"],
                                summaries_s["dense"]["band"]) is False)
    line["active_params"] = active_params
    line.update(moe_info)
    return stats_mod.flag_low_mode(line)


def _bench_moe_ab(card, hw_key: str, dev) -> dict | None:
    """Dense FFN vs MoE at MATCHED ACTIVE PARAMS (ISSUE 15 satellite):
    three train-step chains under the r4 pairing protocol — a dense
    model with ``ff = top_k * f_e``, the sparse-dispatch MoE with E
    experts of width ``f_e`` (identical per-token FFN params, so the
    paired ratio prices routing/dispatch/combine, not model size), and
    the same MoE through the grouped Pallas expert-FFN kernels
    (ops/grouped_matmul.py).  Shapes ride the bench card's dims with
    DLNB_BENCH_MOE_* env overrides so the sentinel lane can run the
    exact pipeline on a tiny CPU model."""
    import dataclasses as _dc

    from dlnetbench_tpu.models import bench_step
    from dlnetbench_tpu.models import moe as moe_mod
    from dlnetbench_tpu.models import transformer as tfm

    e = env_int("DLNB_BENCH_MOE_EXPERTS", 8)
    top_k = env_int("DLNB_BENCH_MOE_TOPK", 2)
    f_e = env_int("DLNB_BENCH_MOE_FF", 0) or max(
        128, card.ff_dim // top_k)
    layers = env_int("DLNB_BENCH_MOE_LAYERS", 2)
    seq = env_int("DLNB_BENCH_MOE_SEQ", min(SEQ, 2048))
    cf = 1.25
    K = env_int("DLNB_BENCH_MOE_K", 4)

    base = dict(vocab_size=VOCAB, embed_dim=card.embed_dim,
                num_heads=card.num_heads,
                num_kv_heads=card.num_kv_heads, num_layers=layers,
                seq_len=seq, gated=True, max_positions=0,
                scan_layers=False, logits_f32=False)
    cfgs = {
        "dense": tfm.TransformerConfig(ff_dim=top_k * f_e, **base),
        "moe": tfm.TransformerConfig(
            ff_dim=f_e, num_experts=e, top_k=top_k, moe_impl="sparse",
            moe_capacity_factor=cf, **base),
        "moe_grouped": tfm.TransformerConfig(
            ff_dim=f_e, num_experts=e, top_k=top_k,
            moe_impl="grouped", moe_capacity_factor=cf, **base),
    }
    tokens = jax.random.randint(jax.random.key(1), (BATCH, seq + 1), 0,
                                VOCAB)
    progs = {}
    for name, cfg in cfgs.items():
        params = tfm.init_params(jax.random.key(0), cfg)
        train_k = bench_step.make_train_k(cfg, K)
        progs[name] = _compile_chain(
            lambda p, f=train_k: f(p, tokens), params)
    summaries, round_times = _measure_paired(progs, K)

    # measured router stats: the layer-0 routing of the benched model
    # over the benched tokens' embeddings (the honest cheap probe —
    # full per-layer load telemetry lives in the serving tier and the
    # SPMD stats step)
    mcfg = cfgs["moe"]
    mparams = tfm.init_params(jax.random.key(0), mcfg)

    def probe(params, toks):
        from dlnetbench_tpu.models import layers as L
        x = params["embed"][toks.reshape(-1)]
        y = L.rmsnorm(x, params["layers"]["norm2"][0])
        return moe_mod.dispatch(y, params["layers"]["w_router"][0], e,
                                top_k, cf, with_stats=True)[3]

    stats = jax.jit(probe)(mparams, tokens[:, :-1])
    moe_info = moe_mod.stats_globals(
        jax.device_get(stats), num_experts=e, top_k=top_k,
        capacity_factor=cf, drop_seed=None, group_tokens=0)

    d = card.embed_dim
    active = {"dense_ffn_params": 3 * d * top_k * f_e,
              "moe_active_ffn_params": 3 * d * top_k * f_e,
              "moe_total_ffn_params": 3 * d * e * f_e,
              "router_params": d * e}
    line = _moe_ab_line(
        summaries, round_times,
        metric=f"moe A/B: dense FFN (ff={top_k * f_e}) vs "
               f"{e}-expert top-{top_k} MoE (f_e={f_e}, cf={cf}; "
               f"matched active params; sparse dispatch vs grouped "
               f"Pallas expert FFN), {layers}L B={BATCH} S={seq}, "
               f"{dev.device_kind} ({hw_key})",
        moe_info=moe_info, active_params=active)
    print(json.dumps(line))
    return line


def _tuned_ab_line(summaries_s: dict, round_times_s: dict,
                   flops_per_iter: int, roofline_s: float, *,
                   metric: str, db_path: str, configs: dict,
                   db_prior_hit: dict, search_meta: dict) -> dict:
    """Assemble the tuned-vs-frozen A/B JSON line (pure —
    tests/test_bench_aux.py locks this schema).  The headline ``value``
    is the TUNED chain's median ms (lower-is-better, so the sentinel
    compares it like every ms line); both variants ship their
    artifact-grade ``{value, best, band, n}`` sub-objects and of-peak
    ratios, the paired per-round ratio band says what tuning bought,
    and ``band_disjoint_win`` states whether the win cleared the noise
    bands (the acceptance bar, stats.bands_overlap)."""
    tuned, frozen = summaries_s["tuned"], summaries_s["frozen"]
    ratios = [t / f for t, f in zip(round_times_s["tuned"],
                                    round_times_s["frozen"]) if f > 0]
    line = {
        "metric": metric,
        "value": round(tuned["value"] * 1e3, 3),
        "unit": "ms",
        **_band_ms(tuned),
        "vs_baseline": round(roofline_s / tuned["value"], 4),
        "vs_baseline_frozen": round(roofline_s / frozen["value"], 4),
        "tflops_tuned": round(flops_per_iter / tuned["value"] / 1e12, 2),
        "tflops_frozen": round(flops_per_iter / frozen["value"] / 1e12,
                               2),
        "tuned_ms": {"value": round(tuned["value"] * 1e3, 3),
                     **_band_ms(tuned)},
        "frozen_ms": {"value": round(frozen["value"] * 1e3, 3),
                      **_band_ms(frozen)},
        "ratio_tuned_vs_frozen": stats_mod.summarize(ratios, ndigits=4),
        "band_disjoint_win": bool(
            tuned["value"] < frozen["value"]
            and stats_mod.bands_overlap(tuned["band"],
                                        frozen["band"]) is False),
        "db_path": db_path,
        "db_prior_hit": db_prior_hit,
        "configs": configs,
        "search": search_meta,
    }
    return stats_mod.flag_low_mode(_flag_above_peak(line))


def _bench_tuned_ab(card, hw_key: str, dev) -> dict | None:
    """Tuned-vs-frozen fp8 fused-swiglu A/B (ISSUE 9 tentpole — the
    driver evidence).  Runs the seeded block-shape search
    (dlnetbench_tpu/tuning: splitmix64 candidate order, K-chained fence
    timing, band-aware pruning) over the two fused-swiglu projection
    shapes, COMMITS the winners to the tuning DB (``DLNB_TUNING_DB_DIR``
    if set, else an ephemeral dir — the line stamps which, plus whether
    the DB already held each key), then measures the full fused-swiglu
    chain frozen-default vs tuned under the r4 pairing protocol.  The
    tuned chain's of-peak number ships with {value, best, band, n}
    stat bands — the fp8 evidence the VERDICT r5 soft spot asked the
    driver artifact (not the docs) to carry."""
    import tempfile

    import jax.numpy as jnp

    from dlnetbench_tpu import tuning
    from dlnetbench_tpu.core.hardware import BYTES_PER_ELEMENT, HARDWARE
    from dlnetbench_tpu.ops import quantized_matmul as qmm
    from dlnetbench_tpu.utils.timing import time_callable

    hw = HARDWARE[hw_key]
    fmt = "float8"
    try:
        fp8_peak = hw.peak(fmt)
    except ValueError:
        _skipped(f"tuned A/B ({hw_key})", f"{hw_key} has no float8 peak")
        return None

    tokens, d, f = BATCH * SEQ, card.embed_dim, card.ff_dim
    x = jax.random.normal(jax.random.key(13), (tokens, d), jnp.bfloat16)
    wg = jax.random.normal(jax.random.key(14), (d, f), jnp.bfloat16) * .02
    wu = jax.random.normal(jax.random.key(15), (d, f), jnp.bfloat16) * .02
    wd = jax.random.normal(jax.random.key(16), (f, d), jnp.bfloat16) * .02
    wgq, swg = qmm.quantize_tensor(wg, fmt)
    wuq, swu = qmm.quantize_tensor(wu, fmt)
    wdq, swd = qmm.quantize_tensor(wd, fmt)
    K = 4  # three Pallas calls per step: keep per-candidate compiles
    #        bounded (the persistent cache amortizes re-runs)

    db_root = tuning.db_dir()
    ephemeral = db_root is None
    if ephemeral:
        db_root = tempfile.mkdtemp(prefix="dlnb_tuning_ephemeral_")
    db = tuning.TuningDB(db_root)
    hwk = tuning.hw_key()

    def dot_with(blocks, wq_, sw_):
        def dot(xc):
            sx = qmm.scale_from_amax(
                jnp.max(jnp.abs(xc.astype(jnp.float32))), fmt)
            return qmm.fused_matmul(xc, wq_, sw_, sx, fmt=fmt, **blocks)
        return dot

    def stage_chain(blocks, wq_, sw_, feed_dim):
        dot = dot_with(blocks, wq_, sw_)

        def chain(x0):
            def body(xc, _):
                y = dot(xc)
                # feed (a slice of) the result back into the carry so
                # the dot cannot be loop-hoisted; slice-add because the
                # carry's width and the output's width differ per stage
                # (the fp8-swiglu-chain feedback convention)
                return xc.at[:, :feed_dim].add(
                    y[:, :feed_dim].astype(xc.dtype) * 1e-6), ()
            return jax.lax.scan(body, x0, None, length=K)[0]
        return chain

    # candidate grid: the frozen default FIRST-CLASS among them (the
    # search can therefore never elect a config it measured slower
    # than the default) plus the two nearest block_m halvings/doublings
    defaults = dict(qmm.DEFAULT_BLOCKS)
    candidates = [defaults,
                  {**defaults, "block_m": defaults["block_m"] // 2},
                  {**defaults, "block_m": defaults["block_m"] * 2}]
    shapes = {
        "up": (tokens, d, f, wgq, swg, x, d),
        "down": (tokens, f, d, wdq, swd,
                 jax.random.normal(jax.random.key(17), (tokens, f),
                                   jnp.bfloat16), d),
    }
    configs: dict = {}
    db_prior_hit: dict = {}
    search_meta: dict = {}
    for name, (t_, k_, n_, wq_, sw_, arg, feed) in shapes.items():
        key = tuning.params.quantized_matmul_key(t_, k_, n_, fmt,
                                                 x.dtype)
        prior = db.get("quantized_matmul", key, hwk)
        db_prior_hit[name] = prior is not None
        if prior is not None:
            # the DB already holds a tuned record for this key (a CLI
            # tune, possibly over a richer grid): the A/B's job is to
            # measure what THAT record buys, never to overwrite the
            # operator's tuning with this line's quick 3-candidate
            # search
            configs[name] = {**defaults, **prior.get("config", {})}
            search_meta[name] = {"reused_db_record": True,
                                 "tuned_band": prior.get("band")}
            continue
        progs: dict = {}

        def measure(cfg, _arg=arg, _wq=wq_, _sw=sw_, _feed=feed,
                    _progs=progs):
            ck = json.dumps(cfg, sort_keys=True)
            if ck not in _progs:
                _progs[ck] = _compile_chain(
                    stage_chain(cfg, _wq, _sw, _feed), _arg)
            return time_callable(_progs[ck], reps=1)[0] / K

        res = tuning.tune_and_commit(
            db, "quantized_matmul", key, hwk, candidates, measure,
            seed=0, rounds=3, k=K)
        configs[name] = res["config"]
        search_meta[name] = {"candidates": len(candidates),
                             "pruned": res["pruned"],
                             "seed": res["seed"],
                             "tuned_band_ms": {
                                 kk: ([round(v * 1e3, 3) for v in vv]
                                      if kk == "band" else
                                      round(vv * 1e3, 3) if kk in
                                      ("value", "best") else vv)
                                 for kk, vv in res["band"].items()}}

    def swiglu_chain(blocks_up, blocks_down):
        dg = dot_with(blocks_up, wgq, swg)
        du = dot_with(blocks_up, wuq, swu)
        dd = dot_with(blocks_down, wdq, swd)

        def chain(x0):
            def body(xc, _):
                g = dg(xc)
                u = du(xc)
                h = (jax.nn.silu(g.astype(jnp.float32))
                     * u.astype(jnp.float32)).astype(xc.dtype)
                y = dd(h)
                return (xc + y * 1e-6).astype(xc.dtype), ()
            return jax.lax.scan(body, x0, None, length=K)[0]
        return chain

    progs = {
        "frozen": _compile_chain(swiglu_chain(defaults, defaults), x),
        "tuned": _compile_chain(swiglu_chain(configs["up"],
                                             configs["down"]), x),
    }
    summaries, round_times = _measure_paired(progs, K)

    flops = 6 * tokens * d * f  # three T*D*F matmuls per iteration
    # fused-path traffic: x/h read once in bf16 (no quantized copy in
    # HBM), pre-quantized weights read, bf16 outputs written
    nbytes = int(BYTES_PER_ELEMENT["bfloat16"]
                 * (tokens * d + 2 * tokens * f + tokens * f + tokens * d)
                 + BYTES_PER_ELEMENT[fmt] * (2 * d * f + f * d))
    line = _tuned_ab_line(
        summaries, round_times, flops,
        _roofline_s(flops, nbytes, hw, fmt),
        metric=f"tuned A/B: fp8(e4m3) fused swiglu chain, DB-tuned vs "
               f"frozen-default grid blocks (seeded search committed to "
               f"the tuning DB{' [ephemeral]' if ephemeral else ''}; "
               f"paired interleaved rounds), {tokens} tok D={d} F={f}, "
               f"{dev.device_kind} ({hw_key}, fp8 peak "
               f"{fp8_peak/1e12:.0f} TF/s)",
        db_path=str(db.path), configs=configs,
        db_prior_hit=db_prior_hit, search_meta=search_meta)
    line = _stamp_attr(line, time_s=summaries["tuned"]["value"],
                       flops=flops, nbytes=nbytes, hw=hw, dtype_key=fmt)
    print(json.dumps(line))
    return line


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
