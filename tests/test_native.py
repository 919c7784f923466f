"""Integration tests for the native (C++) tier.

Builds ``native/`` with CMake+Ninja once per session, runs its ctest unit
suites and every proxy binary on the in-process threaded fabric, and
verifies:
  * the emitted JSON record parses through the SAME analysis pipeline as
    the Python tier (``metrics.parser``) with full rank coverage,
  * the native schedule algebra agrees with the Python tier's
    (cross-implementation check — the Python module is the executable
    spec for ``native/include/dlnb/schedule.hpp``),
  * congestor (`_loop`) binaries exist for every proxy (reference
    PROXY_LOOP builds, Makefile.common:96-109).
"""
from __future__ import annotations

import json
import shutil
import subprocess
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
NATIVE = REPO / "native"

pytestmark = pytest.mark.skipif(
    shutil.which("cmake") is None or shutil.which("ninja") is None,
    reason="cmake/ninja not available")

# The session-scoped shared build-tree fixture `native_bin` lives in
# conftest.py, so the default lane and the opt-in heavy lane
# (-m native_slow; see pyproject [tool.pytest.ini_options]) share one
# incremental CMake/Ninja tree.  Heavy tests — wide multi-process
# configs, mid-run kill tests built on multi-second sleeps, sleep-driven
# schedule-wall proofs — carry @pytest.mark.native_slow; at least one
# representative of each family (shm, pjrt-host, tcp, hier, merge,
# energy, death-detection) stays in the default lane.


def run_proxy(native_bin, name, *extra, model="gpt2_l_16_bfloat16", world=4,
              env=None):
    cmd = [str(native_bin / name), "--model", model, "--world", str(world),
           "--time_scale", "0.0001", "--size_scale", "0.00001",
           "--runs", "2", "--warmup", "1", "--no_topology",
           "--base_path", str(REPO), *map(str, extra)]
    full_env = None
    if env:
        import os
        full_env = {**os.environ, **env}
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=180,
                         env=full_env)
    assert out.returncode == 0, f"{name} failed: {out.stderr}"
    return json.loads(out.stdout)


def test_native_unit_suites(native_bin):
    for t in ("test_core", "test_comm", "test_pjrt"):
        out = subprocess.run([str(native_bin.parent / t)],
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, f"{t} failures:\n{out.stdout}"


@pytest.mark.parametrize("name,extra,model,world", [
    ("dp", ("--num_buckets", 4), "gpt2_l_16_bfloat16", 4),
    ("fsdp", ("--num_units", 4, "--sharding_factor", 4),
     "llama3_8b_16_bfloat16", 8),
    ("hybrid_2d", ("--num_stages", 4, "--num_microbatches", 4),
     "llama3_8b_16_bfloat16", 8),
    ("hybrid_3d", ("--num_stages", 2, "--num_microbatches", 4, "--tp", 2),
     "llama3_8b_16_bfloat16", 8),
    ("hybrid_3d_moe",
     ("--num_stages", 4, "--num_microbatches", 4, "--num_expert_shards", 2),
     "mixtral_8x7b_16_bfloat16", 8),
    ("ring_attention", ("--sp", 4, "--max_layers", 2),
     "llama3_8b_16_bfloat16", 4),
    ("ulysses", ("--sp", 4, "--max_layers", 2), "llama3_8b_16_bfloat16", 4),
])
def test_native_proxy_record(native_bin, name, extra, model, world):
    from dlnetbench_tpu.metrics.parser import records_to_dataframe, \
        validate_record

    rec = run_proxy(native_bin, name, *extra, model=model, world=world)
    assert rec["section"] == name
    assert rec["global"]["world_size"] == world
    assert rec["global"]["backend"] == "shm"
    # transport provenance: in-process thread bytes, stamped so the
    # bandwidth table can never read these rows as fabric physics
    assert rec["global"]["transport"] == "shm"
    # schema v2 parity with the Python tier: band summaries ride the
    # record (validate_record cross-checks each n against its samples)
    assert rec["version"] == 2
    s = rec["ranks"][0]["summary"]["runtimes"]
    assert s["n"] == rec["num_runs"]
    assert s["band"][0] <= s["value"] <= s["band"][1]
    assert s["best"] == s["band"][0] > 0
    validate_record(rec)  # full rank set, per-run timer lengths
    df = records_to_dataframe([rec])
    assert len(df) == world * rec["num_runs"]
    assert (df["runtime"] > 0).all()


def test_native_timers_expected(native_bin):
    rec = run_proxy(native_bin, "fsdp", "--num_units", 4,
                    "--sharding_factor", 2, model="llama3_8b_16_bfloat16",
                    world=4)
    row = rec["ranks"][0]
    for timer in ("runtimes", "allgather", "allgather_wait_fwd",
                  "allgather_wait_bwd", "reduce_scatter", "barrier_time"):
        assert timer in row, f"missing fsdp timer {timer}"
        assert len(row[timer]) == rec["num_runs"]
    # replica grid recorded per rank
    assert {r["replica_id"] for r in rec["ranks"]} == {0, 1}


def test_native_schedule_matches_python(native_bin):
    """The dp bucket split and message sizes must agree across tiers."""
    from dlnetbench_tpu.core.model_stats import load_model_stats
    from dlnetbench_tpu.core.schedule import dp_schedule

    rec = run_proxy(native_bin, "dp", "--num_buckets", 7,
                    model="llama3_70b_16_bfloat16", world=2)
    stats = load_model_stats("llama3_70b_16_bfloat16")
    sched = dp_schedule(stats, 7)
    assert rec["global"]["schedule_bucket_bytes"] == sched.bucket_bytes


@pytest.mark.moe
def test_native_moe_a2a_matches_jax_twin(native_bin):
    """Native-vs-SPMD MoE schedule parity (ISSUE 15 satellite): the
    a2a bytes/step the native hybrid_3d_moe RECORD declares equal the
    JAX twin's arithmetic (models/moe.a2a_elems_per_rank — the same
    formula the twin's actual [E, C, d] dispatch buffer realizes at
    cf=1, pinned buffer-vs-formula by tests/test_moe.py)."""
    from dlnetbench_tpu.core.model_card import load_model_card
    from dlnetbench_tpu.core.model_stats import load_model_stats
    from dlnetbench_tpu.models import moe as moe_mod

    ep, mbs = 2, 4
    rec = run_proxy(native_bin, "hybrid_3d_moe", "--num_stages", 4,
                    "--num_microbatches", mbs, "--num_expert_shards",
                    ep, model="mixtral_8x7b_16_bfloat16", world=8)
    stats = load_model_stats("mixtral_8x7b_16_bfloat16")
    card = load_model_card("mixtral_8x7b")
    tokens_per_mb = (stats.batch_size // mbs) * stats.seq_len
    twin = moe_mod.a2a_elems_per_rank(tokens_per_mb, card.top_k,
                                      stats.embed_dim, ep)
    # the native record scales sizes (harness.hpp scale_count: floor,
    # min 1) — undo the dev-box scaling to compare the declared
    # full-size message against the twin formula
    scale = rec["global"]["size_scale"]
    elems = rec["global"]["a2a_bytes"] // 2  # bf16 itemsize
    assert elems == max(1, int(twin * scale))


def test_native_reads_reference_stats_files(native_bin, tmp_path):
    """Keyed parsing survives the reference's drifted committed files
    (lowercase ``non_expert_size``, SURVEY.md §7.4) — point the binary at a
    base-path layout holding the REFERENCE's file, not our clean copy."""
    ref = Path("/root/reference/model_stats/llama3_70b_16_bfloat16.txt")
    if not ref.exists():
        pytest.skip("reference tree not mounted")
    assert "non_expert_size" in ref.read_text(), \
        "expected the reference file to carry the lowercase-key drift"
    stats_dir = tmp_path / "dlnetbench_tpu" / "data" / "model_stats"
    stats_dir.mkdir(parents=True)
    shutil.copy(ref, stats_dir / ref.name)
    models_dir = tmp_path / "dlnetbench_tpu" / "data" / "models"
    models_dir.mkdir(parents=True)
    out = subprocess.run(
        [str(native_bin / "dp"), "--model", "llama3_70b_16_bfloat16",
         "--world", "2", "--num_buckets", "2", "--runs", "1", "--warmup", "1",
         "--time_scale", "0.00001", "--size_scale", "0.00001",
         "--no_topology", "--base_path", str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rec = json.loads(out.stdout)
    # drifted lowercase key parsed correctly (llama3_70b non_expert_size
    # equals model_size in the reference's committed data)
    total = sum(rec["global"]["schedule_bucket_bytes"])
    assert total > 0


# ---------------------------------------------------------------------
# --backend pjrt: the PJRT fabric (VERDICT r1 #1).  The host executor
# stands in for the plugin in CI — identical CollectiveProgram semantics,
# same rendezvous/slot/cache machinery (pjrt_fabric.hpp); the plugin
# path itself is exercised by test_native_pjrt_real_plugin when a TPU
# is reachable.

PJRT_HOST = {"DLNB_PJRT_EXECUTOR": "host"}


@pytest.mark.parametrize("name,extra,model,world", [
    ("dp", ("--num_buckets", 4), "gpt2_l_16_bfloat16", 4),
    ("fsdp", ("--num_units", 3, "--sharding_factor", 2),
     "gpt2_l_16_bfloat16", 4),
    ("hybrid_2d", ("--num_stages", 2, "--num_microbatches", 4),
     "gpt2_l_16_bfloat16", 4),
    ("hybrid_3d", ("--num_stages", 2, "--num_microbatches", 2, "--tp", 2),
     "gpt2_l_16_bfloat16", 8),
    ("hybrid_3d_moe",
     ("--num_stages", 2, "--num_microbatches", 2, "--num_expert_shards", 2),
     "mixtral_8x7b_16_bfloat16", 8),
    ("ring_attention", ("--sp", 4, "--max_layers", 2),
     "llama3_8b_16_bfloat16", 4),
    ("ulysses", ("--sp", 2, "--max_layers", 2), "llama3_8b_16_bfloat16", 4),
])
def test_native_pjrt_backend_record(native_bin, name, extra, model, world):
    from dlnetbench_tpu.metrics.parser import records_to_dataframe, \
        validate_record

    rec = run_proxy(native_bin, name, "--backend", "pjrt", *extra,
                    model=model, world=world, env=PJRT_HOST)
    g = rec["global"]
    assert g["backend"] == "pjrt"
    assert g["pjrt_executor"] == "host"
    assert g["p2p_transport"] == "host"
    # executor/transport provenance: the CI stand-in is host memory
    # traffic and must say so (analysis/bandwidth.py transport column)
    assert g["executor"] == "HostExecutor"
    assert g["transport"] == "host"
    # the executable cache was exercised: at least one compile, and reuse
    # across warmup+measured iterations produces hits
    assert g["cache_misses"] >= 1
    assert g["cache_hits"] > g["cache_misses"]
    validate_record(rec)
    df = records_to_dataframe([rec])
    assert len(df) == world * rec["num_runs"]
    assert (df["runtime"] > 0).all()


def test_native_pjrt_executor_forced_plugin_fails_cleanly(native_bin):
    """--backend pjrt with DLNB_PJRT_EXECUTOR=plugin and a bogus plugin
    path must error out, not silently fall back."""
    import os
    cmd = [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
           "--world", "2", "--num_buckets", "2", "--backend", "pjrt",
           "--pjrt_plugin", "/nonexistent/libtpu.so",
           "--runs", "1", "--warmup", "1", "--time_scale", "0.0001",
           "--size_scale", "0.00001", "--no_topology",
           "--base_path", str(REPO)]
    out = subprocess.run(
        cmd, capture_output=True, text=True, timeout=60,
        env={**os.environ, "DLNB_PJRT_EXECUTOR": "plugin"})
    assert out.returncode != 0
    assert "plugin" in out.stderr


def test_native_pjrt_devices_validation(native_bin):
    """--devices shorter than world is a startup error (reference -d
    semantics, utils.hpp:62-71)."""
    cmd = [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
           "--world", "4", "--num_buckets", "2", "--backend", "pjrt",
           "--devices", "0,1", "--no_topology", "--base_path", str(REPO)]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert "devices" in out.stderr


def test_native_pjrt_real_plugin(native_bin):
    """End-to-end on the real PJRT plugin (libtpu) when a device is
    reachable: world=1 degenerate collectives still compile, cache, and
    execute on the TPU runtime (VERDICT r1 #1 done-criterion)."""
    import os
    probe = subprocess.run([str(native_bin / "pjrt_probe")],
                           capture_output=True, text=True, timeout=120)
    report = json.loads(probe.stdout)
    if not report.get("available"):
        pytest.skip(f"no usable PJRT plugin: {report.get('reason', '?')}")
    rec = run_proxy(native_bin, "dp", "--backend", "pjrt",
                    "--num_buckets", "2", world=1,
                    env={"DLNB_PJRT_EXECUTOR": "plugin"})
    g = rec["global"]
    assert g["backend"] == "pjrt"
    assert g["pjrt_executor"] != "host"
    assert g["executor"] == "PluginExecutor"
    assert g["transport"] == "ici"
    assert g["cache_misses"] >= 1


def test_loop_binaries_exist(native_bin):
    for name in ("dp", "fsdp", "hybrid_2d", "hybrid_3d", "hybrid_3d_moe",
                 "ring_attention", "ulysses"):
        assert (native_bin / f"{name}_loop").exists(), f"{name}_loop missing"


def test_loop_mode_runs_forever(native_bin):
    """The _loop congestor must not terminate on its own (reference
    PROXY_LOOP infinite run loop, dp.cpp:251-256)."""
    cmd = [str(native_bin / "dp_loop"), "--model", "gpt2_l_16_bfloat16",
           "--world", "2", "--num_buckets", "2", "--time_scale", "0.0001",
           "--size_scale", "0.00001", "--no_topology",
           "--base_path", str(REPO)]
    with pytest.raises(subprocess.TimeoutExpired):
        subprocess.run(cmd, capture_output=True, timeout=3)


_BUBBLE_GRIDS = ((2, 8), (4, 4))     # (S, M) at fixed S * M


def _bubble_records(native_bin, schedule, *extra) -> dict:
    """{(S, M): the record of hybrid_2d at that grid}."""
    return {(S, M): run_proxy(native_bin, "hybrid_2d", "--num_stages", S,
                              "--num_microbatches", M, "--dp", 1,
                              "--schedule", schedule, *extra, world=S)
            for S, M in _BUBBLE_GRIDS}


@pytest.mark.parametrize("schedule", [
    "gpipe",  # the default-lane bubble representative
    pytest.param("1f1b", marks=[pytest.mark.slow, pytest.mark.native_slow]),
])
def test_native_pipeline_bubble(native_bin, schedule):
    """The native engine's pipeline clock counts the GPipe fill/drain
    bubble of its blocking rendezvous send/recv chain (reference
    hybrid_2d.cpp:106-133): at fixed S*M an iteration spans (M+S-1)
    slots a direction, not M.  S=2,M=8 -> 9/16 of the S*M model-time
    units; S=4,M=4 -> 7/16; ratio 7/9, vs 1/2 if stages never waited for
    upstream compute.  On the record's own slot counts: a ratio of
    measured runtimes is the ``slow`` lane's
    (``test_native_pipeline_bubble_by_the_wall_clock``)."""
    share = {}
    for (S, M), rec in _bubble_records(native_bin, schedule).items():
        g = rec["global"]
        assert (g["num_stages"], g["num_microbatches"]) == (S, M)
        assert g["ticks_per_direction"] == M + S - 1
        # one unit a forward slot, two a backward (the stat model)
        assert g["ticks_total"] == pytest.approx(3 * (M + S - 1))
        assert len(rec["ranks"]) == S
        share[S] = g["ticks_total"] / (3 * S * M)
    assert share == pytest.approx({2: 9 / 16, 4: 7 / 16})
    assert share[4] / share[2] == pytest.approx(7 / 9)


@pytest.mark.slow
@pytest.mark.native_slow
@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_native_pipeline_bubble_by_the_wall_clock(native_bin, schedule):
    """The engine REALIZES that clock: measured runtime scales with
    (M+S-1)/(S*M); expected ratio ~7/9 = 0.78, vs ~0.5 if stages never
    waited for upstream compute."""
    recs = _bubble_records(native_bin, schedule, "--time_scale", "0.05",
                           "--runs", 3)
    times = {S: min(rec["ranks"][0]["runtimes"])
             for (S, _), rec in recs.items()}
    ratio = times[4] / times[2]
    assert 0.62 < ratio < 0.95, (
        f"{schedule}: t(S=4)/t(S=2) = {ratio:.3f}; expected ~0.78 "
        f"(bubble present) — ~0.5 means the fill serialization regressed")


def test_native_1f1b_schedule(native_bin):
    """1F1B (slot-indexed Isend, per-stage warmup) emits a valid record
    with the schedule tagged and the same pp entry totals as GPipe."""
    from dlnetbench_tpu.metrics.parser import validate_record

    recs = {}
    for sch in ("gpipe", "1f1b", "zb"):
        rec = run_proxy(native_bin, "hybrid_2d", "--num_stages", 4,
                        "--num_microbatches", 8, "--schedule", sch,
                        model="llama3_8b_16_bfloat16", world=8)
        validate_record(rec)
        assert rec["global"]["schedule"] == sch
        recs[sch] = rec
    for other in ("1f1b", "zb"):
        for a, b in zip(recs["gpipe"]["ranks"], recs[other]["ranks"]):
            assert len(a["pp_comm"]) == len(b["pp_comm"])  # same hop totals


@pytest.mark.slow
@pytest.mark.native_slow
def test_native_zb_beats_two_phase_wall(native_bin):
    """ZB-H1's weight-grad ticks fill the drain bubble: with burns
    dominating (time_scale high enough that sleeps dwarf comm), the zb
    iteration must run measurably under the 1f1b/gpipe wall.  S=4, M=4:
    zb clock = 3M + S - 1 = 15 units vs 3(M + S - 1) = 21 — ratio 0.71."""
    times = {}
    for sch in ("1f1b", "zb"):
        rec = run_proxy(native_bin, "hybrid_2d", "--num_stages", 4,
                        "--num_microbatches", 4, "--dp", 1,
                        "--schedule", sch, "--time_scale", "0.05",
                        "--runs", 5, world=4)
        # min over ALL ranks x runs: the best observation is the one
        # closest to the schedule's clock; per-run jitter on a loaded CI
        # host only ever inflates sleep-driven runtimes
        times[sch] = min(t for row in rec["ranks"]
                         for t in row["runtimes"])
    ratio = times["zb"] / times["1f1b"]
    assert ratio < 0.9, (
        f"zb/1f1b runtime ratio {ratio:.3f}; expected ~0.71 — the "
        f"weight-grad ticks are not filling the bubble")


# ---------------------------------------------------------------------
# --backend tcp: the cross-process fabric (VERDICT r1 #7) — two real OS
# processes bootstrap over a loopback coordinator (the ncclUniqueId
# role, reference dp.cpp:166-189), run the proxy jointly, and their
# per-process records merge into one via dlnetbench_tpu.metrics.merge.

def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_ranks_with_port_retry(make_cmd, n, *, timeout=90):
    """Launch one process per rank on a freshly-probed port; the port
    can be stolen before rank 0 binds it (TOCTOU), so retry on a new
    port ONLY for that distinguishable signature — rank 0's bind
    failure, or a hang (the thief may itself be listening, wedging a
    rank against a foreign coordinator).  Any other non-zero exit is a
    real fabric regression and is returned for the caller to assert on,
    never retried into an occasional flake.  ``make_cmd(rank, port)``
    returns (argv, env-or-None); every process of an attempt is reaped
    before the next attempt or return.  Returns (procs, outs)."""
    for attempt in range(3):
        port = _free_port()
        procs = []
        for r in range(n):
            argv, env = make_cmd(r, port)
            procs.append(subprocess.Popen(
                argv, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env))
        outs, timed_out = [], False
        for p in procs:
            try:
                outs.append(p.communicate(timeout=timeout)[0])
            except subprocess.TimeoutExpired:
                timed_out = True
                p.kill()
                outs.append(p.communicate()[0])
        if all(p.returncode == 0 for p in procs):
            break
        port_stolen = (timed_out
                       or any("tcp: bind failed (port" in o for o in outs))
        if not port_stolen or attempt == 2:
            break
    return procs, outs


def test_native_tcp_selftest(native_bin):
    """Every collective + p2p + split verified across 2 OS processes
    ('correct sums' done-criterion)."""
    procs, outs = _spawn_ranks_with_port_retry(
        lambda r, port: ([str(native_bin / "tcp_selftest"), "--world", "2",
                          "--rank", str(r),
                          "--coordinator", f"127.0.0.1:{port}"], None),
        2)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"rank {r} OK" in out


@pytest.mark.slow
@pytest.mark.native_slow
def test_native_tcp_ring_zero_tail_blocks(native_bin):
    """DLNB_TCP_RING_THRESHOLD=1 forces every allreduce through the ring
    at world 5, where the selftest's small counts (2, 8 elements) leave
    ceil-partitioned blocks of length ZERO — the configuration whose
    tail-block pointer arithmetic was UB before the r4 fix (ADVICE r3).
    Sums must still come out exact."""
    import os
    procs, outs = _spawn_ranks_with_port_retry(
        lambda r, port: ([str(native_bin / "tcp_selftest"), "--world", "5",
                          "--rank", str(r),
                          "--coordinator", f"127.0.0.1:{port}"],
                         {**os.environ, "DLNB_TCP_RING_THRESHOLD": "1"}),
        5, timeout=120)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"rank {r} OK" in out


@pytest.mark.slow
@pytest.mark.native_slow
def test_native_tcp_ring_survives_clean_early_exit(native_bin):
    """Clean EARLY EXIT is not death (r4 fix): --final_ring makes fast
    ranks leave the fabric the instant their ring completes, while rank
    0's final receive is test-delayed 1 s.  Pre-fix, the departed peers'
    EOFs tripped the ring's transitive-death check (false positive) and
    the concurrent error paths double-joined shared slot workers (a
    deadlock seen ~40% of runs at procs 3).  Post-fix, the Bye frame
    marks the departure clean, rank 0's delayed take matches the
    already-queued frames, and every rank exits 0."""
    import os

    def make_cmd(r, port):
        env = {**os.environ}
        if r == 0:
            env["DLNB_TEST_RING_FINAL_RECV_DELAY_MS"] = "1000"
        return ([str(native_bin / "tcp_selftest"), "--world", "3",
                 "--rank", str(r), "--coordinator", f"127.0.0.1:{port}",
                 "--final_ring"], env)

    procs, outs = _spawn_ranks_with_port_retry(make_cmd, 3, timeout=60)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"rank {r} OK" in out


def test_native_tcp_peer_death_detected(native_bin, tmp_path):
    """Failure detection (SURVEY.md §5.3: the reference has none — a dead
    rank hangs the job at the vendor's mercy): when a TCP-fabric peer
    dies mid-run, the survivor must FAIL with a diagnostic, not hang."""
    import time

    port = _free_port()

    def spawn(r):
        return subprocess.Popen(
            [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
             "--world", "2", "--backend", "tcp", "--rank", str(r),
             "--coordinator", f"127.0.0.1:{port}", "--num_buckets", "2",
             "--time_scale", "0.2", "--size_scale", "0.00001",
             "--runs", "500", "--warmup", "1", "--no_topology",
             "--base_path", str(REPO)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

    # ~38 ms/iteration x 500 runs ≈ 19 s of measured runs: the kill at
    # t=2 s lands deep inside them, far from startup and teardown
    survivor, victim = spawn(0), spawn(1)
    try:
        time.sleep(2.0)
        victim.kill()
        victim.communicate()
        out = survivor.communicate(timeout=60)[0]
    finally:
        survivor.kill()
    assert survivor.returncode != 0, \
        f"survivor exited 0 after peer death:\n{out}"
    # either detection path is fine: the reader thread failing blocked
    # collectives ("disconnected mid-run") or a send hitting the dead
    # peer's closed socket first ("peer gone")
    assert "disconnected mid-run" in out or "peer gone" in out, out


@pytest.mark.slow
def test_congestion_study_end_to_end(native_bin, tmp_path):
    """examples/congestion_study.py (the `_loop` congestors' purpose,
    SURVEY.md §5.3) must run the solo + under-load measurement pair and
    write a finite report.  No inflation threshold is asserted — the
    contention magnitude is host-dependent; the study's job is to
    measure it, the test's job is that the machinery works."""
    import sys
    proc = subprocess.run(
        [sys.executable, "examples/congestion_study.py",
         "--out_dir", str(tmp_path), "--runs", "3"],
        capture_output=True, text=True, timeout=300, cwd=str(REPO))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads((tmp_path / "report.json").read_text())
    for key in ("solo", "congested"):
        assert report[key]["runtime_us"] > 0
        assert report[key]["barrier_us"] > 0
    assert report["runtime_inflation"] > 0
    assert "inflation" in proc.stdout


def test_native_dp_over_tcp_and_merge(native_bin, tmp_path):
    """dp across 2 processes: each emits its own record (own timers,
    process identity), metrics.merge reassembles the full rank set."""
    from dlnetbench_tpu.metrics.merge import merge_files
    from dlnetbench_tpu.metrics.parser import records_to_dataframe, \
        validate_record

    port = _free_port()
    outs = [tmp_path / f"p{r}.jsonl" for r in range(2)]
    procs = [subprocess.Popen(
        [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
         "--world", "2", "--backend", "tcp", "--rank", str(r),
         "--coordinator", f"127.0.0.1:{port}", "--num_buckets", "2",
         "--time_scale", "0.0001", "--size_scale", "0.00001",
         "--runs", "2", "--warmup", "1", "--no_topology",
         "--base_path", str(REPO), "--out", str(outs[r])],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in range(2)]
    texts = [p.communicate(timeout=120)[0] for p in procs]
    for r, (p, txt) in enumerate(zip(procs, texts)):
        assert p.returncode == 0, f"rank {r} failed:\n{txt}"

    for r, path in enumerate(outs):
        rec = json.loads(path.read_text().strip())
        assert rec["process"] == r
        assert rec["global"]["backend"] == "tcp"
        assert rec["global"]["num_processes"] == 2
        # 127.0.0.1 coordinator: the record says its sockets are
        # loopback, so the bandwidth table labels these rows' transport
        assert rec["global"]["transport"] == "tcp:loopback"
        assert [row["rank"] for row in rec["ranks"]] == [r]

    merged = merge_files(tmp_path / "merged.jsonl", outs)
    validate_record(merged)
    assert [row["rank"] for row in merged["ranks"]] == [0, 1]
    df = records_to_dataframe([merged])
    assert len(df) == 2 * merged["num_runs"]
    assert (df["runtime"] > 0).all()


# ---------------------------------------------------------------------
# --backend pjrt --procs N: the hierarchical ICI×DCN fabric (VERDICT r2
# #1) — each OS process drives its own CollectiveExecutor over its local
# "devices" (HostExecutor in CI, libtpu on a TPU host), the processes
# compose over the TCP mesh, and the per-process records merge into one
# run.  The reference's multi-node NCCL operating mode (dp.cpp:166-189).

_HOST_EXEC = {"DLNB_PJRT_EXECUTOR": "host"}


def _spawn_hier(native_bin, name, port, rank, *extra, world=4, procs=2,
                out=None, model="gpt2_l_16_bfloat16", env=None):
    import os
    cmd = [str(native_bin / name), "--model", model,
           "--world", str(world), "--backend", "pjrt",
           "--procs", str(procs), "--rank", str(rank),
           "--coordinator", f"127.0.0.1:{port}",
           "--time_scale", "0.0001", "--size_scale", "0.00001",
           "--runs", "2", "--warmup", "1", "--no_topology",
           "--base_path", str(REPO), *map(str, extra)]
    if out is not None:
        cmd += ["--out", str(out)]
    return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            env={**os.environ, **_HOST_EXEC,
                                 **(env or {})})


@pytest.mark.parametrize("world,nprocs", [
    (4, 2),   # default-lane representative; wider configs are opt-in
    # 3 processes, world 12: the uneven split in hier_selftest spans
    # strict subsets of the processes ({0,1}, the NON-adjacent {0,2})
    # with uneven per-process membership — this repo's own bug history
    # says fabric bugs hide just past the smallest config (VERDICT r3
    # weak #3)
    pytest.param(12, 3, marks=[pytest.mark.slow, pytest.mark.native_slow]),
    # UNEVEN LOCALS (VERDICT r4 #5): world does not divide procs — the
    # balanced layout gives locals 3,2 and 3,3,3,3,2,2 — so spanning
    # splits by local index produce groups missing members on the
    # smaller processes, and every collective's DCN routing must handle
    # the ragged layout.  The 6-process case is also the deepest DCN
    # mesh the suite runs.
    pytest.param(5, 2, marks=[pytest.mark.slow, pytest.mark.native_slow]),
    pytest.param(16, 6, marks=[pytest.mark.slow, pytest.mark.native_slow]),
    # VERDICT r5 item #5: 8 processes / world >= 24 with a RAGGED layout
    # (26 = 8*3+2 -> balanced locals 4,4,3,3,3,3,3,3) — the widest DCN
    # mesh the suite runs, with uneven per-process membership on every
    # subset-spanning split
    pytest.param(26, 8, marks=[pytest.mark.slow, pytest.mark.native_slow]),
])
def test_native_hier_selftest(native_bin, world, nprocs):
    """Every collective, all split orientations (groups inside one
    process, spanning all processes, and uneven groups spanning process
    subsets), and cross-process p2p verified by all global ranks
    ('correct sums' done-criterion for the multi-host device path)."""
    import os
    procs, outs = _spawn_ranks_with_port_retry(
        lambda r, port: ([str(native_bin / "hier_selftest"),
                          "--world", str(world), "--procs", str(nprocs),
                          "--rank", str(r),
                          "--coordinator", f"127.0.0.1:{port}"],
                         {**os.environ, **_HOST_EXEC}),
        nprocs)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {r} failed:\n{out}"
        assert f"hier_selftest process {r} OK" in out


def test_native_hier_dcn_wire_bytes(native_bin):
    """Bandwidth-trueness of every block-routed DCN algorithm, pinned to
    the EXACT byte count (no timing): hier_wire_probe runs a known
    collective sequence at world 8 over 4 processes and reports the
    socket bytes TcpFabric counted.  The expectation is the canonical
    direct algorithm's wire cost (hier_fabric.hpp header); the legacy
    gather-based alltoall leg alone would have moved 4x more
    ((P-1)*m*G*C vs m*(G-m)*C).  This is what makes busbw over hier
    records admissible (VERDICT r3 #2)."""
    import os
    world, nprocs, count, iters = 8, 4, 1024, 3
    m, esz, hdr = world // nprocs, 4, 40  # f32; sizeof(FrameHeader)
    G, P = world, nprocs
    per_iter = (
        # alltoall: blocks destined to each peer's members only
        (P - 1) * hdr + m * (G - m) * count * esz
        # reduce-scatter: each peer gets its members' partial blocks
        + (P - 1) * hdr + (G - m) * count * esz
        # allgather: packed local blocks to every peer, no padding
        + (P - 1) * hdr + (P - 1) * m * count * esz
        # ring shift: ONE boundary block crosses per process
        + (P - 1) * hdr + 1 * count * esz
        # allreduce DCN leg: count elems over the P-process TCP mesh
        # (below the ring threshold -> pairwise full mesh of P)
        + (P - 1) * (hdr + count * esz))
    expected = 2 * (P - 1) * hdr + iters * per_iter  # + 2 barriers

    procs, outs = _spawn_ranks_with_port_retry(
        lambda r, port: ([str(native_bin / "hier_wire_probe"),
                          "--world", str(world), "--procs", str(nprocs),
                          "--rank", str(r),
                          "--coordinator", f"127.0.0.1:{port}",
                          "--count", str(count), "--iters", str(iters)],
                         {**os.environ, **_HOST_EXEC}),
        nprocs)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {r} failed:\n{out}"
        rec = json.loads(out.strip().splitlines()[-1])
        assert rec["dcn_algo"] == "blocked"
        assert rec["tcp_bytes_sent"] == expected, \
            (r, rec["tcp_bytes_sent"], expected)


@pytest.mark.parametrize("name,extra,world,model,nprocs", [
    # default-lane representative: dp over the smallest hier config
    # (cross-process DCN combine + merge); the rest of the matrix —
    # wider meshes, pipelines, MoE ZB — is the opt-in heavy lane
    ("dp", ("--num_buckets", 2), 4, "gpt2_l_16_bfloat16", 2),
    # 4 OS processes x 2 local ranks: the DCN mesh at its widest test
    # configuration.  The test env forces the ring threshold to 1 byte
    # (scaled test buckets are ~4 KB, far under the 64 KiB default), so
    # the DCN allreduce leg genuinely rides ring_allreduce at P=4
    pytest.param("dp", ("--num_buckets", 4), 8, "gpt2_l_16_bfloat16", 4,
                 marks=[pytest.mark.slow, pytest.mark.native_slow]),
    pytest.param("fsdp", ("--num_units", 3, "--sharding_factor", 2), 4,
                 "gpt2_l_16_bfloat16", 2,
                 marks=[pytest.mark.slow, pytest.mark.native_slow]),
    # pipeline: the stage-1 -> stage-2 hop crosses the process boundary,
    # exercising Hier's cross-process p2p (TCP frames with encoded
    # endpoint tags)
    pytest.param("hybrid_2d", ("--num_stages", 4, "--num_microbatches", 4),
                 4, "gpt2_l_16_bfloat16", 2,
                 marks=[pytest.mark.slow, pytest.mark.native_slow]),
    # MoE ZB: spanning splits + Alltoall's block-routed DCN leg + the
    # zero-bubble schedule's p2p pattern, 2 procs x 4 local ranks
    pytest.param("hybrid_3d_moe",
                 ("--num_stages", 2, "--num_microbatches", 2,
                  "--num_expert_shards", 2, "--schedule", "zb"), 8,
                 "mixtral_8x7b_16_bfloat16", 2,
                 marks=[pytest.mark.slow, pytest.mark.native_slow]),
    # ring attention: RingShift's KV rotation crosses the process
    # boundary via the boundary-block-routed DCN leg
    pytest.param("ring_attention", ("--sp", 4, "--max_layers", 2), 4,
                 "llama3_8b_16_bfloat16", 2,
                 marks=[pytest.mark.slow, pytest.mark.native_slow]),
])
def test_native_proxy_over_hier_and_merge(native_bin, tmp_path, name, extra,
                                          world, model, nprocs):
    """Proxies across OS processes on the hier fabric: local
    collectives on each process's executor, DCN combine over TCP,
    records merged by metrics.merge with the hierarchy described.
    fsdp's allreduce_comm groups stride the process boundary, so the
    spanning-split slotted path is exercised too."""
    from dlnetbench_tpu.metrics.merge import merge_files
    from dlnetbench_tpu.metrics.parser import records_to_dataframe, \
        validate_record

    port = _free_port()
    local = world // nprocs
    outs = [tmp_path / f"p{r}.jsonl" for r in range(nprocs)]
    # the threshold must be IDENTICAL on every process (it is part of
    # the collective's wire protocol); 1 byte forces the ring at the
    # suite's tiny scaled buckets for the wide-mesh case
    env = ({"DLNB_TCP_RING_THRESHOLD": "1"} if nprocs > 2 else None)
    procs = [_spawn_hier(native_bin, name, port, r, *extra, world=world,
                         procs=nprocs, out=outs[r], model=model, env=env)
             for r in range(nprocs)]
    texts = [p.communicate(timeout=180)[0] for p in procs]
    for r, (p, txt) in enumerate(zip(procs, texts)):
        assert p.returncode == 0, f"process {r} failed:\n{txt}"

    for r, path in enumerate(outs):
        rec = json.loads(path.read_text().strip())
        assert rec["process"] == r
        g = rec["global"]
        assert g["backend"] == "pjrt"
        assert g["num_processes"] == nprocs
        assert g["local_world"] == local
        assert g["dcn_transport"] == "tcp"
        assert g["p2p_transport"] == "host+tcp"
        assert g["pjrt_executor"] == "host"
        # composed provenance: host-executor local leg + loopback DCN
        assert g["transport"] == "host+tcp:loopback"
        assert g["executor"] == "HostExecutor"
        # each process emits only its own local ranks
        assert [row["rank"] for row in rec["ranks"]] == \
            list(range(r * local, (r + 1) * local))
        # allreduce components carry their split's real spanning
        # process count (advisor r4): moe at world 8 / 2 procs has a
        # dp split {r, r+4} crossing the process boundary (span 2)
        # while the contiguous ep pairs stay inside one process
        # (span 1) — bandwidth.py keys the full-mesh refusal on these
        if name == "hybrid_3d_moe":
            cm = g["comm_model"]
            assert cm["dp_comm"][0]["span"] == 2
            assert cm["dp_ep_comm"][0]["span"] == 1

    merged = merge_files(tmp_path / "merged.jsonl", outs)
    validate_record(merged)
    assert [row["rank"] for row in merged["ranks"]] == list(range(world))
    assert [row["process_index"] for row in merged["ranks"]] == \
        [r // local for r in range(world)]
    df = records_to_dataframe([merged])
    assert len(df) == world * merged["num_runs"]
    assert (df["runtime"] > 0).all()


# ---------------------------------------------------------------------
# Native energy channel (VERDICT r2 #2): the C++ RAPL/hwmon chain
# (energy.hpp, the reference's -lpower_profiler role,
# Makefile.flags.mk:119-124) brackets each measured run and emits
# per-run energy_consumed on the process's first rank.  Tested against a
# fake sysfs tree (DLNB_RAPL_ROOT/DLNB_HWMON_ROOT), like the Python
# tier's tests — this rig has no real counters.

def test_native_energy_channel_and_pareto(native_bin, tmp_path):
    import os
    hw = tmp_path / "hwmon" / "hwmon0"
    hw.mkdir(parents=True)
    (hw / "power1_input").write_text("10000000\n")   # 10 W in uW
    (hw / "name").write_text("cpu_fake\n")
    cmd = [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
           "--world", "2", "--num_buckets", "2",
           "--time_scale", "0.1", "--size_scale", "0.00001",
           "--runs", "3", "--warmup", "1", "--no_topology",
           "--base_path", str(REPO)]
    env = {**os.environ, "DLNB_RAPL_ROOT": str(tmp_path / "absent"),
           "DLNB_HWMON_ROOT": str(tmp_path / "hwmon")}
    # an ambient device selector would disable the fake sensor
    env.pop("DLNB_HWMON_DEVICE", None)
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=180,
                         env=env)
    assert out.returncode == 0, out.stderr
    rec = json.loads(out.stdout)

    assert rec["global"]["energy_source"] == "hwmon:cpu_fake"
    assert rec["global"]["energy_scope"] == "process"
    rows = {row["rank"]: row for row in rec["ranks"]}
    # host counter: exactly the process's first rank carries the channel
    ej = rows[0]["energy_consumed"]
    assert len(ej) == rec["num_runs"]
    assert all(j >= 0 for j in ej)
    # 10 W for ~3 x tens-of-ms runs must integrate to something positive
    assert sum(ej) > 0, ej
    assert "energy_consumed" not in rows[1]

    # the Pareto analysis must accept native records and auto-pick the
    # energy axis (reference plots_pareto_energy role)
    import matplotlib
    matplotlib.use("Agg")
    from dlnetbench_tpu.metrics.parser import records_to_dataframe
    from dlnetbench_tpu.analysis.plots import plot_pareto
    df = records_to_dataframe([rec])
    assert "energy_consumed" in df.columns
    ax = plot_pareto(df.dropna(subset=["energy_consumed"]))
    assert ax.get_ylabel().startswith("energy_consumed")


def test_native_energy_absent_without_counters(native_bin, tmp_path):
    """No counter -> no channel, like the reference built without the
    profiler: records stay clean of zero-filled energy arrays."""
    import os
    rec_env = {**os.environ, "DLNB_RAPL_ROOT": str(tmp_path / "no_rapl"),
               "DLNB_HWMON_ROOT": str(tmp_path / "no_hwmon")}
    out = subprocess.run(
        [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
         "--world", "2", "--num_buckets", "2", "--time_scale", "0.0001",
         "--size_scale", "0.00001", "--runs", "2", "--warmup", "1",
         "--no_topology", "--base_path", str(REPO)],
        capture_output=True, text=True, timeout=180, env=rec_env)
    assert out.returncode == 0, out.stderr
    rec = json.loads(out.stdout)
    assert "energy_source" not in rec["global"]
    assert all("energy_consumed" not in row for row in rec["ranks"])


# ---------------------------------------------------------------------
# TCP ring allreduce (VERDICT r2 #6): large allreduces ride a
# bandwidth-optimal ring instead of the O(n^2) contribution mesh.

def test_native_tcp_ring_correct_sums(native_bin):
    """tcp_selftest at world=4 crosses the 64 KiB ring threshold with an
    odd count (tail block shorter), so the rotation math is verified by
    every rank across 4 real OS processes."""
    port = _free_port()
    procs = [subprocess.Popen(
        [str(native_bin / "tcp_selftest"), "--world", "4",
         "--rank", str(r), "--coordinator", f"127.0.0.1:{port}"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in range(4)]
    outs = [p.communicate(timeout=120)[0] for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"rank {r} OK" in out


def test_native_tcp_ring_wire_bytes_scale(native_bin, tmp_path):
    """The deterministic busbw-flatness proof: each record reports the
    process's actual socket bytes (tcp_bytes_sent).  With ring engaged,
    an allreduce moves ~2(n-1)/n x count per rank — far under the full
    mesh's (n-1) x count — so the world-4 dp run must sit near the ring
    estimate and well under the mesh estimate (no timing involved)."""
    port = _free_port()
    world, runs, warmup = 4, 2, 1
    outs = [tmp_path / f"p{r}.jsonl" for r in range(world)]
    procs = [subprocess.Popen(
        [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
         "--world", str(world), "--backend", "tcp", "--rank", str(r),
         "--coordinator", f"127.0.0.1:{port}", "--num_buckets", "2",
         "--time_scale", "0.0001", "--size_scale", "0.0002",
         "--runs", str(runs), "--warmup", str(warmup), "--no_topology",
         "--base_path", str(REPO), "--out", str(outs[r])],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in range(world)]
    texts = [p.communicate(timeout=180)[0] for p in procs]
    for r, (p, txt) in enumerate(zip(procs, texts)):
        assert p.returncode == 0, f"rank {r} failed:\n{txt}"

    rec = json.loads(outs[0].read_text().strip())
    g = rec["global"]
    bucket_bytes = g["bucket_bytes"]
    assert all(b >= g["tcp_ring_threshold_bytes"] for b in bucket_bytes), \
        "test premise broken: buckets must engage the ring"
    iters = runs + warmup
    ring_est = iters * sum(2 * (world - 1) / world * b
                           for b in bucket_bytes)
    mesh_est = iters * sum((world - 1) * b for b in bucket_bytes)
    sent = g["tcp_bytes_sent"]
    # ring plus bootstrap/barrier/estimate overhead, but nowhere near
    # the full mesh (at world=4 the mesh moves 2x the ring's bytes)
    assert sent < 0.75 * mesh_est, (sent, ring_est, mesh_est)
    assert sent > 0.9 * ring_est, (sent, ring_est, mesh_est)


@pytest.mark.slow
@pytest.mark.native_slow
def test_native_tcp_ring_peer_death_detected(native_bin, tmp_path):
    """A mid-ring death must fail ALL survivors promptly — including
    non-neighbors, whose next awaited block transitively depends on the
    dead rank — not just the dead rank's successor."""
    import time

    port = _free_port()
    world = 3

    def spawn(r):
        return subprocess.Popen(
            [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
             "--world", str(world), "--backend", "tcp", "--rank", str(r),
             "--coordinator", f"127.0.0.1:{port}", "--num_buckets", "2",
             "--time_scale", "0.2", "--size_scale", "0.0002",
             "--runs", "500", "--warmup", "1", "--no_topology",
             "--base_path", str(REPO)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

    procs = [spawn(r) for r in range(world)]
    try:
        time.sleep(2.0)
        procs[1].kill()
        procs[1].communicate()
        outs = []
        for r in (0, 2):
            outs.append(procs[r].communicate(timeout=60)[0])
    finally:
        for p in procs:
            p.kill()
    for r, out in zip((0, 2), outs):
        assert procs[r].returncode != 0, \
            f"rank {r} exited 0 after mid-ring peer death:\n{out}"
        assert "disconnected mid-run" in out or "peer gone" in out, out


def test_native_scheduler_variables_in_record(native_bin):
    """The native tier stamps the same launcher variables as the Python
    tier (metrics.emit.scheduler_variables parity)."""
    rec = run_proxy(native_bin, "dp", "--num_buckets", 2, world=2,
                    env={"DLNB_TAG_protocol": "ring",
                         "SLURM_JOB_ID": "1234"})
    v = rec["global"]["variables"]
    assert v["protocol"] == "ring"
    assert v["slurm_job_id"] == "1234"
    # parser hoists them to DataFrame columns
    from dlnetbench_tpu.metrics.parser import records_to_dataframe
    df = records_to_dataframe([rec])
    assert (df["protocol"] == "ring").all()


@pytest.mark.slow
@pytest.mark.native_slow
def test_native_hier_peer_death_detected(native_bin):
    """Failure detection on the hierarchical fabric: when one OS process
    of a --procs run dies mid-run, the survivor must fail fast with a
    diagnostic (the TCP layer's per-peer death tracking propagating
    through the DCN combine), not hang."""
    import os
    import time

    port = _free_port()

    def spawn(r):
        return subprocess.Popen(
            [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
             "--world", "4", "--backend", "pjrt", "--procs", "2",
             "--rank", str(r), "--coordinator", f"127.0.0.1:{port}",
             "--num_buckets", "2", "--time_scale", "0.2",
             "--size_scale", "0.0001", "--runs", "500", "--warmup", "1",
             "--no_topology", "--base_path", str(REPO)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env={**os.environ, **_HOST_EXEC})

    survivor, victim = spawn(0), spawn(1)
    try:
        time.sleep(3.0)
        victim.kill()
        victim.communicate()
        out = survivor.communicate(timeout=60)[0]
    finally:
        survivor.kill()
    assert survivor.returncode != 0, \
        f"survivor exited 0 after peer death:\n{out}"
    assert "disconnected mid-run" in out or "peer gone" in out, out


@pytest.mark.slow
@pytest.mark.native_slow
def test_native_hier_noncoordinator_death_at_three_procs(native_bin):
    """At procs=3, killing a NON-coordinator process (rank 1) mid-run
    must fail BOTH survivors fast — including rank 2, whose death signal
    arrives only via the TCP mesh, not the bootstrap socket (VERDICT r3
    weak #3: mid-run death beyond the 2-process config)."""
    import os
    import time

    port = _free_port()

    def spawn(r):
        return subprocess.Popen(
            [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
             "--world", "6", "--backend", "pjrt", "--procs", "3",
             "--rank", str(r), "--coordinator", f"127.0.0.1:{port}",
             "--num_buckets", "2", "--time_scale", "0.2",
             "--size_scale", "0.0001", "--runs", "500", "--warmup", "1",
             "--no_topology", "--base_path", str(REPO)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env={**os.environ, **_HOST_EXEC})

    procs = [spawn(r) for r in range(3)]
    victim = procs[1]
    survivors = [procs[0], procs[2]]
    outs = []
    try:
        time.sleep(3.0)
        victim.kill()
        victim.communicate()
        for s in survivors:
            outs.append(s.communicate(timeout=60)[0])
    finally:
        for s in survivors:
            s.kill()
    for i, (s, out) in enumerate(zip(survivors, outs)):
        assert s.returncode != 0, \
            f"survivor {i} exited 0 after peer death:\n{out}"
        assert "disconnected mid-run" in out or "peer gone" in out, out


# ---------------------------------------------------------------------
# Race detection (SURVEY.md §5.2: the reference ships no sanitizer
# configs at all).  The rank fabrics are thread-heavy — slot workers,
# reader threads, rendezvous — so the repo carries a dedicated TSan
# preset alongside the ASan/UBSan debug preset, and this (slow) test
# builds it and runs the unit suites plus the cross-process selftest
# under it.

def test_build_dir_claim_permission_discipline(tmp_path):
    """_claim (advisor r4): a pre-existing same-uid build dir with
    group/world WRITE bits may already contain planted build.ninja —
    must be wiped, not merely chmodded; read-only-permissive dirs are
    tightened in place; a foreign-uid dir is rejected (not testable
    unprivileged)."""
    from dlnetbench_tpu.utils.native_build import _claim

    d = tmp_path / "bld"
    d.mkdir(mode=0o755)  # world-readable, NOT writable
    (d / "build.ninja").write_text("ok")
    _claim(d)
    assert (d.stat().st_mode & 0o777) == 0o700
    assert (d / "build.ninja").exists()  # tightened in place, kept

    d.chmod(0o775)  # group-WRITABLE: contents are untrusted
    (d / "build.ninja").write_text("planted")
    _claim(d)
    assert (d.stat().st_mode & 0o777) == 0o700
    assert not (d / "build.ninja").exists()  # wiped and recreated


@pytest.mark.slow
@pytest.mark.native_slow
def test_native_tsan_fabrics(tmp_path):
    from dlnetbench_tpu.utils.native_build import build_root
    build = build_root(REPO, "tsan")
    # --preset keeps the committed TSan flags authoritative; -B only
    # relocates the tree out of the repo (CMake: CLI overrides preset).
    subprocess.run(["cmake", "--preset", "tsan", "-S", str(NATIVE),
                    "-B", str(build)],
                   check=True, capture_output=True)
    subprocess.run(["ninja", "-C", str(build), "test_comm", "test_pjrt",
                    "tcp_selftest", "hier_selftest", "fault_selftest"],
                   check=True, capture_output=True)
    for t in ("test_comm", "test_pjrt"):
        out = subprocess.run([str(build / t)], capture_output=True,
                             text=True, timeout=600)
        assert out.returncode == 0, f"{t} under tsan:\n{out.stdout[-2000:]}"
        assert "ThreadSanitizer" not in out.stdout + out.stderr
    procs, outs = _spawn_ranks_with_port_retry(
        lambda r, port: ([str(build / "bin" / "tcp_selftest"),
                          "--world", "4", "--rank", str(r),
                          "--coordinator", f"127.0.0.1:{port}"], None),
        4, timeout=300)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} under tsan:\n{out}"
        assert "ThreadSanitizer" not in out, out

    # the r4 hier additions are the thread-heaviest new code (per-slot
    # DCN exchanges from concurrent rendezvous execs, Bye-frame
    # teardown, concurrent quiesce): run the full hier selftest —
    # including the uneven subset-spanning splits — under TSan at
    # procs 3 x 4 local ranks
    import os
    # (12, 3): the r4 subset-spanning config; (16, 6): the r5
    # uneven-locals config (balanced layout 3,3,3,3,2,2); (26, 8): the
    # r7 scale-up (VERDICT r5 item #5) — 8 processes, world 26, ragged
    # locals 4,4,3,3,3,3,3,3, the widest DCN mesh in the suite — the
    # spanning-split rendezvous and block routing must stay race-free
    # on every ragged layout
    for world, nprocs in ((12, 3), (16, 6), (26, 8)):
        procs, outs = _spawn_ranks_with_port_retry(
            lambda r, port: ([str(build / "bin" / "hier_selftest"),
                              "--world", str(world),
                              "--procs", str(nprocs),
                              "--rank", str(r),
                              "--coordinator", f"127.0.0.1:{port}"],
                             {**os.environ, **_HOST_EXEC}),
            nprocs, timeout=300)
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, \
                f"hier proc {r}/{nprocs} w={world} under tsan:\n{out}"
            assert "ThreadSanitizer" not in out, out

    # fault-injection crash paths (ISSUE 5 satellite: injected delays
    # and scripted deaths are exactly where data races hide).  shm
    # crash + shrink: the group-abort poisoning races rank threads
    # blocked in rendezvous/mailboxes against the dying thread's
    # mark_rank_dead; the survivor regroup then reuses slot workers.
    crash = '{"events":[{"kind":"crash","ranks":[2],"iteration":3}]}'
    out = subprocess.run(
        [str(build / "bin" / "fault_selftest"), "--world", "4",
         "--iters", "6", "--fault", crash, "--fault_policy", "shrink"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, f"shm shrink under tsan:\n{out.stdout}"
    assert "ThreadSanitizer" not in out.stdout + out.stderr, out.stdout
    # shm crash fail-fast: every survivor thread must abort cleanly
    # (nonzero exit, no race) while the dying thread unwinds
    out = subprocess.run(
        [str(build / "bin" / "fault_selftest"), "--world", "4",
         "--iters", "6", "--fault", crash],
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "ThreadSanitizer" not in out.stdout + out.stderr, out.stdout
    # tcp crash + shrink: reader threads observe the victim's EOF while
    # rank threads are mid-collective; survivors switch comms live
    tcp_crash = '{"events":[{"kind":"crash","ranks":[1],"iteration":3}]}'
    procs, outs = _spawn_ranks_with_port_retry(
        lambda r, port: ([str(build / "bin" / "fault_selftest"),
                          "--backend", "tcp", "--world", "3",
                          "--rank", str(r),
                          "--coordinator", f"127.0.0.1:{port}",
                          "--iters", "6", "--fault", tcp_crash,
                          "--fault_policy", "shrink"], None),
        3, timeout=300)
    assert procs[1].returncode != 0  # the scripted victim
    for r in (0, 2):
        assert procs[r].returncode == 0, \
            f"tcp shrink survivor {r} under tsan:\n{outs[r]}"
    for out_text in outs:
        assert "ThreadSanitizer" not in out_text, out_text

    # preempt + rejoin (ISSUE 7 grow path): the evictee's drained
    # singleton replay runs CONCURRENTLY with the survivors' degraded
    # window, then everyone live-switches onto the pre-built full-world
    # comm at the rejoin trigger — the thread-heaviest elastic
    # transition (three communicators active across one run).  shm
    # races rank threads in one process; tcp adds reader threads and
    # the returning rank's cross-process rendezvous.
    rejoin = ('{"policy":"shrink","events":['
              '{"kind":"preempt","ranks":[1],"iteration":3,'
              '"magnitude_us":5000},'
              '{"kind":"rejoin","ranks":[1],"iteration":7}]}')
    out = subprocess.run(
        [str(build / "bin" / "fault_selftest"), "--world", "4",
         "--iters", "10", "--fault", rejoin, "--fault_policy", "shrink"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, f"shm rejoin under tsan:\n{out.stdout}"
    assert "ThreadSanitizer" not in out.stdout + out.stderr, out.stdout
    procs, outs = _spawn_ranks_with_port_retry(
        lambda r, port: ([str(build / "bin" / "fault_selftest"),
                          "--backend", "tcp", "--world", "3",
                          "--rank", str(r),
                          "--coordinator", f"127.0.0.1:{port}",
                          "--iters", "10", "--fault", rejoin,
                          "--fault_policy", "shrink"], None),
        3, timeout=300)
    for r in range(3):  # nobody dies on the elastic arc
        assert procs[r].returncode == 0, \
            f"tcp rejoin rank {r} under tsan:\n{outs[r]}"
    for out_text in outs:
        assert "ThreadSanitizer" not in out_text, out_text
