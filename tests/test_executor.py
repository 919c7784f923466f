"""AOT execution engine (core/executor.py): compile-time bookkeeping,
donation + rebinding safety, chained-fence timing, and the no-compile-in-
warmup property that keeps estimate_runs honest."""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from dlnetbench_tpu.core import executor
from dlnetbench_tpu.parallel.buffers import sharded_zeros
from dlnetbench_tpu.parallel.mesh import make_flat_mesh
from dlnetbench_tpu.proxies.base import ProxyConfig, StepBundle, run_proxy
from dlnetbench_tpu.utils.jax_compat import shard_map


def _mesh4(eight_devices):
    return make_flat_mesh(4, devices=eight_devices[:4])


def _carry_program(mesh, trace_counter=None):
    """A tiny shard_map step with a donated carry: state <- tanh(s@s),
    plus a psum output per buffer (the dp-proxy shape)."""
    state = sharded_zeros(mesh, P(), (16, 16), jnp.float32) + 0.1
    bufs = tuple(sharded_zeros(mesh, P(), (32,), jnp.float32)
                 for _ in range(2))

    def step(s, gs):
        if trace_counter is not None:
            trace_counter.append(1)
        s = jnp.tanh(s @ s)
        outs = [jax.lax.psum(g, "x") for g in gs]
        return (s, *outs)

    fn = shard_map(step, mesh=mesh, in_specs=(P(), (P(), P())),
                   out_specs=P(), check_vma=False)
    return executor.Program(fn=fn, args=(state, bufs),
                            donate_argnums=(0, 1)), state, bufs


def test_compile_stats_recorded(eight_devices):
    mesh = _mesh4(eight_devices)
    prog, _, _ = _carry_program(mesh)
    meta: dict = {}
    compiled = executor.compile_programs({"full": prog}, meta)
    assert meta["compile_ms"]["full"] > 0
    # compile time ships OUTSIDE the timer arrays: it lives in the
    # global_meta channel the emitter serializes under "global"
    stats = compiled["full"].stats
    assert stats["donated_argnums"] == [0, 1]
    # XLA's cost model on CPU reports flops for the matmul
    assert meta["aot"]["full"]["cost_analysis"]["flops"] > 0
    # memory_analysis proves the donation: alias bytes cover the carry
    ma = meta["aot"]["full"]["memory_analysis"]
    assert ma["alias"] > 0


def test_donation_rebinds_and_siblings_survive(eight_devices):
    """Repeated calls must work (the donated buffer is rebound from the
    output), and the ORIGINAL buffers must stay alive for sibling
    programs — the executor clones donated args."""
    mesh = _mesh4(eight_devices)
    prog, state, bufs = _carry_program(mesh)
    compiled = executor.CompiledProgram(prog)
    for _ in range(3):  # would raise "buffer deleted" without rebinding
        outs = compiled()
    assert jnp.all(jnp.isfinite(outs[0]))
    # originals untouched (not donated — their clones were)
    assert float(jnp.max(jnp.abs(bufs[0]))) == 0.0
    assert state.shape == (16, 16) and bool(jnp.isfinite(state).all())


def test_unmatched_donation_dropped_not_fatal(eight_devices):
    """A requested donation whose leaves have no shape-matched output is
    dropped (recorded as ``undonated``), never handed to XLA to warn
    about or die on."""
    mesh = _mesh4(eight_devices)
    x = sharded_zeros(mesh, P(), (8,), jnp.float32)

    def f(v):
        return jnp.sum(v)  # scalar out: no (8,) output to rebind from

    fn = shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False)
    compiled = executor.CompiledProgram(
        executor.Program(fn=fn, args=(x,), donate_argnums=(0,)))
    assert compiled.stats["donated_argnums"] == []
    assert compiled.stats["undonated"] == [0]
    compiled()
    compiled()  # x was never donated, so the second call is fine


def test_no_donation_kill_switch(eight_devices, monkeypatch):
    """DLNB_NO_DONATION=1 disables donation (and therefore cloning) for
    memory-constrained full-scale runs, without touching call sites."""
    monkeypatch.setenv(executor.ENV_NO_DONATION, "1")
    mesh = _mesh4(eight_devices)
    prog, state, bufs = _carry_program(mesh)
    compiled = executor.CompiledProgram(prog)
    assert compiled.stats["donated_argnums"] == []
    compiled()
    compiled()  # nothing donated: same buffers reusable every call
    assert compiled.example_args[0] is state  # no clone was made


def test_run_proxy_never_retraces(eight_devices):
    """The no-compile-in-warmup property behind clean estimate_runs:
    bundles are AOT-compiled at build, so run_proxy's warmup+timed loop
    must never trace (= compile) again.  The trace counter ticks once,
    at Program compile time."""
    mesh = _mesh4(eight_devices)
    traces: list = []
    prog, _, _ = _carry_program(mesh, trace_counter=traces)
    compiled = executor.compile_programs({"full": prog}, {})
    # AOT lowering traces the function (eval_shape + lower each tick it)
    n_build = len(traces)
    assert n_build >= 1
    bundle = StepBundle(full=compiled["full"], compute=None, comm=None,
                        global_meta={"proxy": "t", "world_size": 4})
    cfg = ProxyConfig(warmup=3, runs=4, measure_energy=False)
    result = run_proxy("t", bundle, cfg)
    assert len(traces) == n_build, "run_proxy re-traced an AOT program"
    assert len(result.warmup_times_us) == 3
    assert len(result.timers_us["runtimes"]) == 4


def test_chained_fence_matches_per_rep_mean(eight_devices):
    """K-chained timing must agree with per-rep timing on a steady
    kernel — the chain amortizes dispatch+fence overhead, so its mean
    may sit BELOW the per-rep mean, but the two must be the same
    magnitude (a chain that mistimed k iterations as one would be ~k
    off)."""
    from dlnetbench_tpu.proxies import burn as burnlib
    from dlnetbench_tpu.utils.timing import time_callable, time_chain

    state = burnlib.make_state()
    cal = burnlib.calibrate()
    iters = cal.iters_for_us(3000)  # ~3 ms per rep: stable on CPU

    import functools
    import statistics
    j = jax.jit(functools.partial(burnlib.burn, iters=iters))
    j(state).block_until_ready()  # compile
    # warm the FENCE path too: the first transfer fence lazily compiles
    # the one-element slice for this state shape (~40 ms on CPU — a
    # 13x outlier against a 3 ms kernel), which used to land in the
    # first measured sample and flake this test on loaded hosts
    time_callable(j, state, reps=1)

    # medians: this test pins the chain bookkeeping (a chain that
    # mistimed k iterations as one would be ~k off), not the tail of
    # the host's scheduling-noise distribution
    per_rep = statistics.median(time_callable(j, state, reps=6))
    chained = statistics.median(time_chain(j, state, k=3)
                                for _ in range(3))
    assert chained > 0
    ratio = chained / per_rep
    assert 0.2 < ratio < 2.5, (
        f"chained per-iteration median {chained*1e3:.2f} ms vs per-rep "
        f"{per_rep*1e3:.2f} ms (ratio {ratio:.2f})")


def test_run_proxy_chain_partitioning(eight_devices):
    """reps_per_fence=K: runs partition into ceil(runs/K) fence chains,
    each contributing one per-iteration sample; the A/B barrier pairing
    stays chain-matched; the K lands in the record's global meta."""
    calls = {"full": 0, "comp": 0}

    def full():
        calls["full"] += 1

    def compute():
        calls["comp"] += 1

    bundle = StepBundle(full=full, compute=compute, comm=None,
                        global_meta={"proxy": "t", "world_size": 1})
    cfg = ProxyConfig(warmup=1, runs=5, reps_per_fence=2,
                      measure_energy=False)
    res = run_proxy("t", bundle, cfg)
    assert res.global_meta["reps_per_fence"] == 2
    # 5 runs -> chains of 2+2+1 -> 3 samples per timer
    assert len(res.timers_us["runtimes"]) == 3
    assert len(res.timers_us["barrier_time"]) == 3
    assert res.num_runs == 5
    # every configured iteration really dispatched: 1 warmup + 5 runs
    assert calls["full"] == 6
    # compute: 1 warm + 5 chained A/B iterations
    assert calls["comp"] == 6


def test_persistent_cache_placement(tmp_path, monkeypatch, eight_devices):
    """The compile cache is placed from outside: where
    JAX_COMPILATION_CACHE_DIR is set that directory is used (and
    compiling through the executor populates it); where it is not, the
    one fixed path inside the checkout — never a temporary name."""
    knobs = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in knobs}
    try:
        # conftest points the session's cache at a temporary directory
        # through the variable; without it the fixed path is the place
        jax.config.update("jax_enable_compilation_cache", True)
        monkeypatch.delenv(executor.ENV_CACHE_DIR, raising=False)
        repo = Path(__file__).resolve().parent.parent
        assert executor.enable_persistent_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            repo / ".jax_cache")

        monkeypatch.setenv(executor.ENV_CACHE_DIR, str(tmp_path))
        assert executor.enable_persistent_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        mesh = _mesh4(eight_devices)
        prog, _, _ = _carry_program(mesh)
        meta: dict = {}
        executor.compile_programs({"full": prog}, meta)
        assert meta["compile_cache_dir"] == str(tmp_path)
        assert any(f.name.endswith("-cache") or "cache" in f.name
                   for f in tmp_path.iterdir()), \
            "compile cache dir stayed empty"
    finally:  # do not leave the global cache pointed at a dead tmpdir
        for k, v in before.items():
            jax.config.update(k, v)
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()


def test_estimate_runs_sees_execution_only(eight_devices):
    """End-to-end guard on the estimate_runs channel: with an AOT bundle
    whose program costs ~c per call, the warmup mean feeding
    estimate_runs must be ~c — not c + compile.  Compile for this
    program costs >> one execution on CPU, so warmup[0] sitting within
    a small factor of warmup[-1] proves compilation never leaked in."""
    mesh = _mesh4(eight_devices)
    prog, _, _ = _carry_program(mesh)
    meta: dict = {}
    compiled = executor.compile_programs({"full": prog}, meta)
    bundle = StepBundle(full=compiled["full"], compute=None, comm=None,
                        global_meta=meta)
    cfg = ProxyConfig(warmup=4, runs=1, measure_energy=False)
    result = run_proxy("t", bundle, cfg)
    warm = result.warmup_times_us
    compile_us = meta["compile_ms"]["full"] * 1e3
    steady = min(warm)
    # the first warmup sample must not carry the compile (it is 100s of
    # ms on CPU for this program; execution is ~100 us)
    assert warm[0] < steady + 0.5 * compile_us, (
        f"warmup[0]={warm[0]:.0f}us vs steady {steady:.0f}us and "
        f"compile {compile_us:.0f}us — compilation leaked into warmup")


# ---------------------------------------------------------------------
# The build record (``stats["build"]``, ``executor.builds()``) and the
# same phases as attrs of the ``compile`` span.

RECORD_KEYS = {"fn", "module", "began_at_s", "trace_s", "lower_s",
               "executable_s", "cache", "cache_retrieval_s",
               "backend_compile_s", "code_bytes", "op_scopes_s",
               "analyses_s"}
PHASES = ("trace", "lower", "executable", "op_scopes")
KINDS = ("program", "step", "loop")


def _carried():
    """A fresh function object a call: jax keeps what it traced and
    compiled for a function it has seen."""
    def carried(s, g):
        s = jnp.tanh(s @ s)
        return s, jnp.sum(g) + jnp.sum(s)
    return carried


def _build(kind, eight_devices):
    if kind == "program":
        prog, _, _ = _carry_program(_mesh4(eight_devices))
        return executor.CompiledProgram(prog)
    args = (jnp.full((16, 16), 0.1), jnp.ones((32,)))
    if kind == "step":
        return executor.CompiledStep(_carried(), args, donate_argnums=(0,))
    return executor.CompiledLoop(_carried(), args, carry_argnums=(0,))


def _close(a_s, b_s):
    return abs(a_s - b_s) <= max(0.05 * b_s, 0.020)


@pytest.fixture
def cache_off():
    """No persistent cache while the case runs.  conftest's thresholds
    keep a program this small out of the session's cache, but a case of
    another file that ran in this process may have zeroed them
    (``cli.main`` calls ``enable_persistent_cache``), so the premise of
    ``cache == "off"`` is set here and not left to the order of files."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kind", KINDS)
def test_build_record_with_no_tracer(kind, eight_devices, cache_off):
    from dlnetbench_tpu.metrics import spans
    from dlnetbench_tpu.utils.timing import process_age_s
    assert not spans.is_enabled()
    before = executor.builds()
    built = _build(kind, eight_devices)
    rec = built.stats["build"]
    assert executor.builds() == (*before, rec)      # the log, in order
    assert set(rec) == RECORD_KEYS
    assert rec["module"] == executor.hlo_module_name(built.as_text())
    assert rec["module"] == "jit_" + rec["fn"]
    assert 0 < rec["began_at_s"] <= process_age_s()
    assert min(rec["trace_s"], rec["lower_s"], rec["executable_s"]) > 0
    assert rec["op_scopes_s"] == 0.0                # no tracer, no table
    assert rec["cache"] == "off"                    # ``cache_off``
    assert rec["cache_retrieval_s"] == 0.0
    assert 0 < rec["backend_compile_s"] <= rec["executable_s"]
    assert rec["code_bytes"] == \
        built.memory_analysis["generated_code"] >= 0
    phases = sum(rec[f"{p}_s"] for p in PHASES)
    assert _close(phases, built.stats["compile_ms"] * 1e-3)
    import json
    json.dumps(rec)                                 # the emitter's channel


@pytest.mark.parametrize("kind", KINDS)
def test_compile_span_wears_the_build_record(kind, eight_devices):
    """With a tracer on the build is still one span, ``compile`` (a
    train run's spans are its builds and nothing else: the benchmark's
    tests hold that), and its attrs carry the record's phases, the
    cache's verdict and the code's size; the table's cost is counted."""
    from dlnetbench_tpu.metrics import spans
    tracer = spans.enable()
    try:
        built = _build(kind, eight_devices)
    finally:
        spans.disable()
    rec = built.stats["build"]
    (whole,) = [s for s in tracer.spans if s["name"] == "compile"]
    assert {s["name"] for s in tracer.spans} <= {"compile", "donate-clone"}
    assert whole["attrs"] == {
        "fn": rec["fn"], **{k: rec[k] for k in (
            "cache", "code_bytes", "trace_s", "lower_s", "executable_s",
            "op_scopes_s")}}
    assert rec["op_scopes_s"] > 0
    assert "jit_" + rec["fn"] in tracer.op_scopes
    phases = sum(rec[f"{p}_s"] for p in PHASES)
    assert _close(phases, whole["dur_us"] * 1e-6)
    assert _close(phases, built.stats["compile_ms"] * 1e-3)


def test_a_second_lowering_adds_into_the_same_fields(monkeypatch):
    """``CompiledProgram`` lowers again when a donation is dropped:
    both tracings and both lowerings are in the one record."""
    import types
    clock = iter(range(100))
    monkeypatch.setattr(executor, "time", types.SimpleNamespace(
        perf_counter=lambda: float(next(clock))))
    x = jnp.ones((8,))
    built = executor.CompiledProgram(executor.Program(
        fn=lambda v: jnp.sum(v), args=(x,), donate_argnums=(0,)))
    assert built.stats["undonated"] == [0]
    rec = built.stats["build"]
    # a tick a clock read: began, (t0, t1, end) a lowering, twice, then
    # the executable's pair
    assert (rec["trace_s"], rec["lower_s"], rec["executable_s"]) == \
        (2.0, 2.0, 1.0)
    assert built.stats["compile_ms"] == 9000.0


def test_build_record_says_what_the_cache_did(tmp_path, monkeypatch):
    """miss, then hit (no backend compile), then off: the verdict is
    this build's own, from jax's events while its executable is made."""
    from jax.experimental.compilation_cache import compilation_cache
    knobs = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in knobs}
    args = (jnp.full((16, 16), 0.1), jnp.ones((32,)))
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        monkeypatch.setenv(executor.ENV_CACHE_DIR, str(tmp_path))
        executor.enable_persistent_cache()
        first = executor.CompiledStep(_carried(), args).stats["build"]
        second = executor.CompiledStep(_carried(), args).stats["build"]
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        third = executor.CompiledStep(_carried(), args).stats["build"]
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert (first["cache"], second["cache"], third["cache"]) == \
        ("miss", "hit", "off")
    assert first["backend_compile_s"] > 0 == first["cache_retrieval_s"]
    assert second["backend_compile_s"] == 0.0
    assert 0 < second["cache_retrieval_s"] <= second["executable_s"]
    assert third["backend_compile_s"] > 0 == third["cache_retrieval_s"]


def test_compile_programs_ships_the_build_record(eight_devices):
    prog, _, _ = _carry_program(_mesh4(eight_devices))
    meta: dict = {}
    compiled = executor.compile_programs({"full": prog}, meta)
    assert meta["aot"]["full"]["build"] is compiled["full"].stats["build"]
    assert meta["aot"]["full"]["build"] in executor.builds()
    assert "compile_ms" not in meta["aot"]["full"]


def test_process_age_is_the_kernels_and_grows():
    import time

    from dlnetbench_tpu.utils.timing import process_age_s
    a = process_age_s()
    time.sleep(0.03)
    b = process_age_s()
    # older than this module's import of jax, younger than the machine
    assert 0 < a < b
    with open("/proc/uptime") as f:
        assert b <= float(f.read().split()[0])
