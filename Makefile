# Convenience lanes. The python package needs no build step — these are
# the test/guard entry points CI and humans share.

PYTHON ?= python

.PHONY: test check-bench check-resilience check-serving check-tuning \
	check-longcontext check-decode check-density check-telemetry \
	check-moe check-disagg check-fleet check-sampling sentinel-scan

# tier-1: the full default test lane (see ROADMAP.md for the canonical
# driver invocation with its timeout/log plumbing)
test:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m 'not slow'

# the bench regression sentinel, end to end on a tiny CPU config
# (tests/test_sentinel.py::test_bench_check_lane): baseline capture, a
# clean re-run of bench.py --check that must stay quiet, and a
# deterministically injected +10% slowdown (faults delay injector) that
# must exit non-zero.  ~30s wall.
check-bench:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_sentinel.py -q -m sentinel

# the resilience lane (docs/RESILIENCE.md): fault plans + policies,
# the preempt->restore->rejoin arc on both tiers (native cases skip
# without cmake/ninja), checkpoint backends + the in-loop snapshot
# checkpointer, watchdog integration, the degraded/rejoin merge
# pathways with their committed fixtures, the Daly-interval validation
# against the committed elastic study, and the sentinel tiny baseline.
# ~2 min wall on a dev box.
check-resilience:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q -m 'not slow' \
	    tests/test_faults.py tests/test_native_faults.py \
	    tests/test_checkpoint.py tests/test_watchdog.py \
	    tests/test_goodput.py tests/test_merge.py
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_sentinel.py -q \
	    -m sentinel

# the serving lane (docs/SERVING.md): arrival-plan schema + fixtures,
# the paged KV cache, decode-vs-forward parity, the continuous-batching
# engine, fault composition (straggler p99 inflation, crash+shrink SLO
# dip/recovery), the committed record fixture round-trip, and the
# serving_decode bench-line schema + sentinel comparability.  The
# heavyweight load sweeps stay in the slow lane.  ~1 min wall.
check-serving:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q -m 'serving and not slow' \
	    tests/test_serving.py
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q \
	    tests/test_bench_aux.py::test_serving_decode_line_schema_locked \
	    tests/test_sentinel.py::test_serving_latency_line_is_comparable

# the autotuner lane (docs/PERF.md "Autotuning"): TuningDB durability
# (torn writes, schema refusal, the writer claim/retry race), the
# seeded band-aware search, every consult site's empty-DB bit-identity,
# the committed fixture round-trip, and the tune CLI proving
# search -> commit -> consult -> hit end to end with a tiny-CPU
# 2-candidate search.  Seconds of search inside ~1 min of lane wall.
check-tuning:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_tuning.py -q \
	    -m tuning
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q \
	    tests/test_bench_aux.py::test_tuned_ab_line_schema_locked \
	    tests/test_sentinel.py::test_tuned_ab_line_is_comparable

# the long-context lane (docs/PERF.md r13 "Block-sparse attention"):
# mask-builder verdict tables vs brute force, splash-vs-dense kernel
# parity (causal bit-identity + masked specs), sparse ring hop gating
# vs the gathered reference, the windowed serving prefill parity, and
# the longcontext_ab bench-line schema + sentinel comparability.  The
# S=64k cases live in the slow lane (pytest -m 'longcontext and slow').
# ~1 min wall.
check-longcontext:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q -m 'longcontext and not slow' \
	    tests/
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q \
	    tests/test_bench_aux.py::test_longcontext_line_schema_locked \
	    tests/test_sentinel.py::test_longcontext_line_is_comparable

# the decode-loop lane (docs/SERVING.md "The multi-step loop"): fused
# N-step-vs-1-step token parity, speculative greedy parity (both
# drafters), the verify pass, the host/device state split's sync
# contract + round-trip property, adaptive-N policy + TTFT guard,
# config guards, CompiledLoop, the record/attribution pathway, and the
# serving A/B line schema + sentinel comparability.  The full
# 3-engine bench e2e rides the slow lane (pytest -m 'decode and
# slow').  ~1 min wall.
check-decode:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q -m 'decode and not slow' \
	    tests/
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q \
	    tests/test_bench_aux.py::test_serving_decode_line_schema_locked \
	    tests/test_bench_aux.py::test_serving_decode_ab_schema_locked \
	    tests/test_sentinel.py::test_decode_ab_line_is_comparable

# the serving-density lane (docs/SERVING.md "Cache density"):
# quantized paged-KV config validation + pool-bytes accounting, the
# int8/fp8 decode-parity bars on the CPU mesh, the dequantizing Pallas
# kernel (interpret mode; chip_smoke.py runs it on the chip), the
# refcount/COW allocator property test, prefix-sharing
# losslessness + record globals, the arrival-plan prefix knobs, and
# the kv_density_ab bench-line schema + sentinel comparability.
# ~1 min wall.
check-density:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q -m 'density and not slow' \
	    tests/test_kv_density.py
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q \
	    tests/test_bench_aux.py::test_kv_density_line_schema_locked \
	    tests/test_sentinel.py::test_kv_density_line_is_comparable

# the continuous-telemetry lane (docs/OBSERVABILITY.md "Continuous
# telemetry & the flight recorder"): the flight-recorder ring + anomaly
# engine contracts (disabled-path zero overhead, byte-identical
# records, step-time band detection, dump cooldowns), the serving
# SLO-breach e2e (flight_slo.json + anomalies through parser -> merge),
# the committed record_telemetry.jsonl round trip into the bandwidth
# blame columns, the critical-path blame validation (straggler ->
# injected rank, clean -> no suspect), the watchdog ring-trend
# breadcrumb, and the live-metrics line schema.  ~1 min wall.
check-telemetry:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q -m 'telemetry and not slow' \
	    tests/test_telemetry.py tests/test_critical_path.py \
	    tests/test_watchdog.py
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q \
	    tests/test_bench_aux.py::test_live_metrics_line_schema_locked

# the expert-parallel MoE lane (ISSUE 15, docs/PERF.md "Expert-parallel
# MoE" / docs/SERVING.md "MoE decode"): seeded grouped routing
# (determinism, shard invariance, the capacity-factor drop closed
# form), the grouped Pallas expert-FFN kernels (count-aware skipping,
# int8 exactness, tuning-DB site), the decomposed-a2a dispatch/combine
# loop vs the monolithic pair, SPMD step parity across the knob matrix,
# the native-vs-SPMD a2a schedule-parity formula, MoE decode in the
# serving tier (per-expert batching, overflow rounds, seeded skew ->
# p99, imbalance telemetry + record/parser round trip), and the moe_ab
# bench-line schema + sentinel comparability.  ~2 min wall.
check-moe:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q -m 'moe and not slow' \
	    tests/test_moe.py tests/test_moe_serving.py
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q \
	    tests/test_bench_aux.py::test_moe_ab_line_schema_locked \
	    tests/test_sentinel.py::test_moe_ab_line_is_comparable

# the disaggregated-serving lane (ISSUE 16, docs/SERVING.md
# "Disaggregated prefill/decode"): the page-migration channel's
# bit-exact quantized wire + closed-form byte accounting + overlap-leg
# discipline, the replica config guards, the adaptive-N migration-ETA
# cap, int8 token parity vs the monolithic engine, the committed
# two-replica record fixture round trip, and the disagg_ab bench-line
# schema.  The bf16 parity and prefill-crash e2e cases ride the slow
# lane (pytest -m 'disagg and slow').  ~30s wall.
check-disagg:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q -m 'disagg and not slow' \
	    tests/test_disagg.py
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q \
	    tests/test_bench_aux.py::test_disagg_line_schema_locked

# the fleet-serving lane (ISSUE 18, docs/SERVING.md "Fleet serving"):
# the seeded router's policy semantics (round_robin cycling, p2c
# tie/draw rules, prefix-affinity's read-only trie probe), the diurnal
# arrival shape + committed fixture, the shared re-queue arc,
# fleet-vs-single-engine token parity + assignment replay determinism,
# the committed record_fleet.jsonl parser -> merge round trip, and the
# fleet_ab bench-line schema + sentinel comparability.  The autoscale
# and replica-crash e2e cases ride the slow lane (pytest -m 'fleet and
# slow').  ~40s wall.
check-fleet:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q -m 'fleet and not slow' \
	    tests/test_fleet.py
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q \
	    tests/test_bench_aux.py::test_fleet_line_schema_locked \
	    tests/test_sentinel.py::test_fleet_ab_line_is_comparable

# the sampling lane (ISSUE 19, docs/SERVING.md "Sampling, speculation
# & constrained decode"): the fmix32 key-derivation golden values, the
# filter pipeline + inverse-CDF math, the JSON grammar automaton, the
# N-step==1-step bit-identity lock, the crash-shrink replay property,
# the chi-square distribution-equality locks (plain draws AND the
# rejection-sampling verify rule), composition with speculative decode
# and prefix sharing, the committed record_sampling.jsonl parser ->
# merge round trip (comparable identity vs volatile acceptance curve),
# the CLI flag surface, and the sampling_ab bench-line schema +
# sentinel comparability.  ~90s wall.
check-sampling:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q -m 'sampling and not slow' \
	    tests/test_sampling.py
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q \
	    tests/test_bench_aux.py::test_sampling_ab_line_schema_locked \
	    tests/test_sentinel.py::test_sampling_ab_line_is_comparable

# stat-band-aware walk over the committed driver artifacts: fails when
# the LATEST BENCH_r*.json regressed against its predecessor
sentinel-scan:
	JAX_PLATFORMS=cpu $(PYTHON) -m dlnetbench_tpu.sentinel .
