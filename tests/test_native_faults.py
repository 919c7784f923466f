"""Native fault-injection subsystem (fault_plan.hpp / fault_session.hpp):
the fault_selftest binary's policy matrix on the shm and tcp fabrics,
plus the dp proxy's faulted records through the analysis pipeline.

Default lane keeps one representative per family (shm shrink, tcp crash
fail-fast — the FIRST controlled end-to-end test of the PR-2 ``dying_``
flag + transitive fail-fast path — and the shm straggler record);
the wider matrix (tcp shrink + merge, drop policies, hier delay) is the
opt-in ``-m native_slow`` lane, and the crash paths also run under TSan
(test_native.py::test_native_tsan_fabrics)."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.skipif(
    not os.environ.get("DLNB_NATIVE_BIN")
    and (shutil.which("cmake") is None or shutil.which("ninja") is None),
    reason="cmake/ninja not available (set DLNB_NATIVE_BIN to a "
           "prebuilt bin dir to run anyway)")

# every survivor must RAISE within this budget, never hang — the
# watchdog-style bound satellite 1 asserts on the provoked death path
WATCHDOG_BUDGET_S = 30

CRASH_PLAN = '{"events":[{"kind":"crash","ranks":[1],"iteration":3}]}'
DELAY_PLAN = ('{"events":[{"kind":"delay","ranks":[2],"iteration":3,'
              '"magnitude_us":30000}]}')
DROP_PLAN = ('{"events":[{"kind":"drop","ranks":[0],"iteration":0,'
             '"rate":0.2,"magnitude_us":200,"seed":42}]}')
REJOIN_PLAN = ('{"policy":"shrink","events":['
               '{"kind":"preempt","ranks":[1],"iteration":3,'
               '"magnitude_us":5000},'
               '{"kind":"rejoin","ranks":[1],"iteration":7}]}')
PREEMPT_ONLY_PLAN = ('{"policy":"shrink","events":['
                     '{"kind":"preempt","ranks":[1],"iteration":3,'
                     '"magnitude_us":5000}]}')


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_tcp(native_bin, binary, world, rank, port, *extra, env=None):
    import os
    return subprocess.Popen(
        [str(native_bin / binary), "--backend", "tcp",
         "--world", str(world), "--rank", str(rank),
         "--coordinator", f"127.0.0.1:{port}", *map(str, extra)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env={**os.environ, **(env or {})})


def _communicate_all(procs, timeout=WATCHDOG_BUDGET_S):
    outs = []
    for p in procs:
        try:
            outs.append(p.communicate(timeout=timeout)[0])
        except subprocess.TimeoutExpired:
            p.kill()
            outs.append(p.communicate()[0] + "\n<TIMEOUT: survivor hung "
                        "past the watchdog budget>")
    return outs


# ------------------------------------------------------------- shm lane
def test_shm_crash_shrink_survivors_finish(native_bin):
    """Elastic degradation on the threaded fabric: the scripted victim
    dies, survivors regroup on the pre-split survivor comm, finish all
    iterations with exact survivor-group sums, and report measured
    detection/recovery."""
    out = subprocess.run(
        [str(native_bin / "fault_selftest"), "--world", "4", "--iters",
         "6", "--fault", '{"events":[{"kind":"crash","ranks":[2],'
         '"iteration":3}]}', "--fault_policy", "shrink"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    rows = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    assert [r["rank"] for r in rows] == [0, 1, 3]  # victim emits nothing
    for r in rows:
        assert r["checks"] == "OK" and r["iters_done"] == 6
        assert r["shrunk"] is True
        assert r["degraded_world"] == [0, 1, 3]
        assert r["detection_us"] > 0 and r["recovery_us"] > 0


def test_shm_preempt_rejoin_restores_full_world(native_bin):
    """The grow half (ISSUE 7 tentpole) on the threaded fabric: the
    evictee drains its grace window and replays locally, survivors run
    the degraded window on the pre-split comm, and at the rejoin
    trigger EVERY rank re-splits onto the pre-built full-world comm —
    exact full-world sums again, rejoin cost measured, nobody dies."""
    out = subprocess.run(
        [str(native_bin / "fault_selftest"), "--world", "4", "--iters",
         "10", "--fault", REJOIN_PLAN, "--fault_policy", "shrink"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    rows = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    # ALL ranks emit (the evictee never died) and all rejoined
    assert [r["rank"] for r in rows] == [0, 1, 2, 3]
    for r in rows:
        assert r["checks"] == "OK" and r["iters_done"] == 10
        assert r["rejoined"] is True
        assert r["rejoin_us"] > 0
        assert r["shrunk"] is False  # grow, not shrink: nobody crashed
        assert r["degraded_world"] == [0, 1, 2, 3]  # full again
    by_rank = {r["rank"]: r for r in rows}
    # the evictee slept its grace window; the others did not
    assert by_rank[1]["injected_delay_us"] >= 5000
    assert by_rank[0]["injected_delay_us"] == 0.0


def test_shm_crash_fail_fast_aborts_not_hangs(native_bin):
    """A dead in-process rank must ABORT the run promptly (the new
    group-poisoning path): before this subsystem, survivors blocked in
    a rendezvous waited forever for the dead rank."""
    out = subprocess.run(
        [str(native_bin / "fault_selftest"), "--world", "4", "--iters",
         "6", "--fault", '{"events":[{"kind":"crash","ranks":[2],'
         '"iteration":3}]}'],
        capture_output=True, text=True, timeout=WATCHDOG_BUDGET_S)
    assert out.returncode != 0
    blob = out.stdout + out.stderr
    assert "crashed by fault plan" in blob or "died during a collective" \
        in blob, blob


def test_shm_delay_and_retry_policies(native_bin):
    """Delay: injected straggler latency is accounted per rank; drop +
    retry on the shm fabric resolves locally (no frame layer) and the
    run completes exact."""
    out = subprocess.run(
        [str(native_bin / "fault_selftest"), "--world", "4", "--iters",
         "4", "--fault", DELAY_PLAN],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    rows = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    by_rank = {r["rank"]: r for r in rows}
    assert by_rank[2]["injected_delay_us"] >= 30000  # step 3 in-window
    assert by_rank[0]["injected_delay_us"] == 0.0


def _fsdp_straggler_record(native_bin) -> dict:
    """An fsdp run under a straggler plan: rank 2 sleeps 30 ms before
    steps 4, 5, 6 (runs 3.. after the one warmup)."""
    from dlnetbench_tpu.metrics.parser import validate_record
    out = subprocess.run(
        [str(native_bin / "fsdp"), "--model", "gpt2_l_16_bfloat16",
         "--world", "4", "--num_units", "4", "--sharding_factor", "2",
         "--time_scale", "0.001", "--size_scale", "0.0001",
         "--runs", "6", "--warmup", "1",
         "--no_topology", "--base_path", str(REPO),
         "--fault", '{"events":[{"kind":"delay","ranks":[2],'
         '"iteration":4,"magnitude_us":30000}]}'],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rec = json.loads(out.stdout)
    validate_record(rec)
    return rec


def test_fsdp_shm_straggler_record_through_analysis(native_bin):
    """An fsdp run with a straggler plan (fsdp declares a comm_model,
    so it feeds the bandwidth table) emits a v2 record whose faulted
    runs are busbw-refused (bound 'faulted') while the clean runs keep
    their figures, and the summary reports the straggler-amplification
    (the study's core readout) of the record's own plan and window.
    The native tier's clock cannot be handed in, so the amplification
    is read here over runtimes the test writes into the record (a clean
    step 1 ms, a faulted one the plan's 30 ms more: 1.0); over the
    measured ones it is the ``slow`` lane's."""
    from dlnetbench_tpu.analysis.bandwidth import bandwidth_summary, \
        straggler_amplification

    rec = _fsdp_straggler_record(native_bin)
    g = rec["global"]
    assert g["fault_policy"] == "fail_fast"
    assert g["fault_injected_delay_us"] >= 3 * 30000  # steps 4,5,6
    rows = {r["rank"]: r for r in rec["ranks"]}
    assert rows[2]["fault_injected_delay_us"] >= 3 * 30000
    assert rows[0]["fault_injected_delay_us"] == 0.0
    assert all(len(r["runtimes"]) == 6 for r in rec["ranks"])
    for r in rec["ranks"]:
        r["runtimes"] = [1000.0] * 3 + [31000.0] * 3
    assert straggler_amplification(rec) == pytest.approx(1.0)
    s = bandwidth_summary([rec])
    assert set(s["bound"]) == {"exact", "faulted"}
    faulted = s[s["bound"] == "faulted"]
    assert faulted["busbw_GBps"].isna().all()
    assert faulted["straggler_amp"].tolist() == pytest.approx(
        [1.0] * len(faulted))
    clean = s[s["bound"] == "exact"]
    assert clean["busbw_GBps"].notna().all()


@pytest.mark.slow
def test_fsdp_shm_straggler_gates_every_rank_by_the_wall_clock(native_bin):
    """The sleep gates every rank's measured step: the faulted runs
    (3.., steps 4..) cost about the injected delay more."""
    from dlnetbench_tpu.analysis.bandwidth import bandwidth_summary, \
        straggler_amplification
    rec = _fsdp_straggler_record(native_bin)
    amp = straggler_amplification(rec)
    assert 0.5 < amp < 3.0, amp
    s = bandwidth_summary([rec])
    assert (s[s["bound"] == "faulted"]["straggler_amp"] > 0.5).all()


def test_unwired_proxy_refuses_step_scoped_plan(native_bin):
    """Proxies without a step-boundary fault driver must refuse plans
    whose events could only fire at step boundaries — otherwise the
    record would stamp fault provenance onto an actually-clean run —
    while collective-scoped plans still apply through the fabric
    hooks."""
    base = [str(native_bin / "hybrid_2d"), "--model",
            "gpt2_l_16_bfloat16", "--world", "4", "--num_stages", "4",
            "--num_microbatches", "4", "--runs", "1", "--warmup", "1",
            "--time_scale", "0.0001", "--size_scale", "0.00001",
            "--no_topology", "--base_path", str(REPO)]
    out = subprocess.run(base + ["--fault", DELAY_PLAN],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "no step-boundary fault driver" in out.stderr
    coll = ('{"events":[{"kind":"delay","ranks":[1],"iteration":0,'
            '"magnitude_us":100,"where":"collective"}]}')
    out = subprocess.run(base + ["--fault", coll],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rec = json.loads(out.stdout)
    assert rec["global"]["fault_injected_delay_us"] > 0


def test_fsdp_refuses_crash_shrink_plan(native_bin):
    """The ZeRO grid cannot regroup around a dead rank: a crash+shrink
    plan must be refused loudly, never half-applied."""
    out = subprocess.run(
        [str(native_bin / "fsdp"), "--model", "gpt2_l_16_bfloat16",
         "--world", "4", "--num_units", "2", "--sharding_factor", "2",
         "--runs", "1", "--warmup", "1", "--no_topology",
         "--base_path", str(REPO), "--fault", CRASH_PLAN,
         "--fault_policy", "shrink"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert "shrink" in out.stderr


# ------------------------------------------------------------- tcp lane
def test_tcp_crash_fail_fast_every_survivor_raises(native_bin):
    """SATELLITE 1 — the first CONTROLLED end-to-end exercise of the
    PR-2 ``dying_`` flag + transitive fail-fast: a crash-plan victim
    dies mid-run WITHOUT a Bye (mark_rank_dead -> mark_dying), and
    every survivor must raise (not hang) within the watchdog budget,
    with a death diagnostic."""
    port = _free_port()
    procs = [_spawn_tcp(native_bin, "fault_selftest", 3, r, port,
                        "--iters", 6, "--fault", CRASH_PLAN)
             for r in range(3)]
    outs = _communicate_all(procs)
    assert procs[1].returncode != 0  # the victim
    assert "crashed by fault plan" in outs[1]
    for r in (0, 2):
        assert procs[r].returncode != 0, \
            f"survivor {r} exited 0 after scripted peer death:\n{outs[r]}"
        assert "TIMEOUT" not in outs[r], outs[r]
        assert ("disconnected mid-run" in outs[r]
                or "peer gone" in outs[r]), outs[r]


@pytest.mark.slow
@pytest.mark.native_slow
def test_tcp_crash_fail_fast_wide_world(native_bin):
    """The native_slow half of satellite 1: the same provoked-death
    fail-fast at world 5 — non-neighbor survivors whose signal arrives
    only transitively must also raise within the budget."""
    port = _free_port()
    plan = '{"events":[{"kind":"crash","ranks":[2],"iteration":3}]}'
    procs = [_spawn_tcp(native_bin, "fault_selftest", 5, r, port,
                        "--iters", 8, "--fault", plan)
             for r in range(5)]
    outs = _communicate_all(procs, timeout=60)
    assert procs[2].returncode != 0
    for r in (0, 1, 3, 4):
        assert procs[r].returncode != 0, \
            f"survivor {r} exited 0 after scripted peer death:\n{outs[r]}"
        assert "TIMEOUT" not in outs[r], outs[r]


@pytest.mark.slow
@pytest.mark.native_slow
def test_tcp_crash_shrink_survivors_finish(native_bin):
    port = _free_port()
    procs = [_spawn_tcp(native_bin, "fault_selftest", 3, r, port,
                        "--iters", 6, "--fault", CRASH_PLAN,
                        "--fault_policy", "shrink")
             for r in range(3)]
    outs = _communicate_all(procs, timeout=60)
    assert procs[1].returncode != 0  # dead is dead
    for r in (0, 2):
        assert procs[r].returncode == 0, f"survivor {r}:\n{outs[r]}"
        row = json.loads([ln for ln in outs[r].splitlines()
                          if ln.startswith("{")][0])
        assert row["shrunk"] is True
        assert row["degraded_world"] == [0, 2]
        assert row["iters_done"] == 6 and row["checks"] == "OK"
        assert row["detection_us"] > 0 and row["recovery_us"] > 0


@pytest.mark.slow
@pytest.mark.native_slow
def test_tcp_drop_retry_and_fail_fast(native_bin):
    """Drop + retry: every frame eventually delivered with backoff
    counted; drop + fail_fast: the first loss aborts."""
    port = _free_port()
    procs = [_spawn_tcp(native_bin, "fault_selftest", 2, r, port,
                        "--iters", 5, "--fault", DROP_PLAN,
                        "--fault_policy", "retry")
             for r in range(2)]
    outs = _communicate_all(procs, timeout=60)
    for r in range(2):
        assert procs[r].returncode == 0, f"rank {r}:\n{outs[r]}"
    row0 = json.loads([ln for ln in outs[0].splitlines()
                       if ln.startswith("{")][0])
    assert row0["drops"] >= 1 and row0["retries"] == row0["drops"]
    assert row0["injected_delay_us"] > 0

    port = _free_port()
    procs = [_spawn_tcp(native_bin, "fault_selftest", 2, r, port,
                        "--iters", 5, "--fault", DROP_PLAN)
             for r in range(2)]
    outs = _communicate_all(procs, timeout=60)
    assert any(p.returncode != 0 for p in procs)
    assert any("injected frame drop" in o for o in outs), outs


@pytest.mark.slow
@pytest.mark.native_slow
def test_dp_tcp_crash_shrink_merge_degraded(native_bin, tmp_path):
    """The acceptance chain on the cross-process fabric: dp under a
    crash plan with shrink — the victim process dies record-less, the
    survivors emit degraded records (detection/recovery/degraded_world)
    that metrics.merge reassembles through the degraded pathway."""
    from dlnetbench_tpu.metrics.merge import merge_files
    from dlnetbench_tpu.metrics.parser import records_to_dataframe, \
        validate_record

    port = _free_port()
    world = 3
    outs_p = [tmp_path / f"p{r}.jsonl" for r in range(world)]
    procs = [subprocess.Popen(
        [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
         "--world", str(world), "--backend", "tcp", "--rank", str(r),
         "--coordinator", f"127.0.0.1:{port}", "--num_buckets", "2",
         "--time_scale", "0.001", "--size_scale", "0.0001",
         "--runs", "5", "--warmup", "1", "--no_topology",
         "--base_path", str(REPO), "--fault", CRASH_PLAN,
         "--fault_policy", "shrink", "--out", str(outs_p[r])],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in range(world)]
    texts = _communicate_all(procs, timeout=120)
    assert procs[1].returncode != 0, texts[1]   # the victim
    assert not outs_p[1].exists()               # and it emits NO record
    for r in (0, 2):
        assert procs[r].returncode == 0, f"survivor {r}:\n{texts[r]}"

    merged = merge_files(tmp_path / "merged.jsonl",
                         [outs_p[0], outs_p[2]])
    validate_record(merged)
    assert [row["rank"] for row in merged["ranks"]] == [0, 2]
    g = merged["global"]
    assert g["degraded_world"] == [0, 2]
    assert g["detection_ms"] > 0 and g["recovery_ms"] > 0
    df = records_to_dataframe([merged])
    assert len(df) == 2 * merged["num_runs"]
    assert (df["runtime"] > 0).all()


@pytest.mark.slow
@pytest.mark.native_slow
def test_tcp_preempt_rejoin_all_ranks_finish(native_bin):
    """The grow half across OS processes: the returning rank is
    accepted deterministically on the plan-known fresh comm — all
    three processes finish with exact sums and measured rejoin cost."""
    port = _free_port()
    procs = [_spawn_tcp(native_bin, "fault_selftest", 3, r, port,
                        "--iters", 10, "--fault", REJOIN_PLAN,
                        "--fault_policy", "shrink")
             for r in range(3)]
    outs = _communicate_all(procs, timeout=60)
    for r in range(3):
        assert procs[r].returncode == 0, f"rank {r}:\n{outs[r]}"
        row = json.loads([ln for ln in outs[r].splitlines()
                          if ln.startswith("{")][0])
        assert row["rejoined"] is True and row["rejoin_us"] > 0
        assert row["iters_done"] == 10 and row["checks"] == "OK"


@pytest.mark.slow
@pytest.mark.native_slow
def test_dp_tcp_preempt_rejoin_record_full_world(native_bin, tmp_path):
    """The native-tier end-to-end rejoin acceptance: dp under a
    preempt->rejoin plan — ALL processes emit records (the evictee
    drained, nobody died), the merged record CLEARS degraded_world,
    stamps fault_rejoin_step + rejoin_ms, and parses with full rank
    coverage."""
    from dlnetbench_tpu.metrics.merge import merge_files
    from dlnetbench_tpu.metrics.parser import records_to_dataframe, \
        validate_record

    port = _free_port()
    world = 3
    outs_p = [tmp_path / f"p{r}.jsonl" for r in range(world)]
    procs = [subprocess.Popen(
        [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
         "--world", str(world), "--backend", "tcp", "--rank", str(r),
         "--coordinator", f"127.0.0.1:{port}", "--num_buckets", "2",
         "--time_scale", "0.001", "--size_scale", "0.0001",
         "--runs", "10", "--warmup", "1", "--no_topology",
         "--base_path", str(REPO), "--fault", REJOIN_PLAN,
         "--fault_policy", "shrink", "--out", str(outs_p[r])],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in range(world)]
    texts = _communicate_all(procs, timeout=120)
    for r in range(world):
        assert procs[r].returncode == 0, f"process {r}:\n{texts[r]}"
        assert outs_p[r].exists()  # the evictee emits too

    merged = merge_files(tmp_path / "merged.jsonl", outs_p)
    validate_record(merged)
    assert [row["rank"] for row in merged["ranks"]] == [0, 1, 2]
    g = merged["global"]
    assert "degraded_world" not in g          # the world grew back
    assert g["fault_rejoin_step"] == 7
    assert g["rejoin_ms"] > 0
    df = records_to_dataframe([merged])
    assert len(df) == world * merged["num_runs"]
    assert (df["runtime"] > 0).all()


@pytest.mark.slow
@pytest.mark.native_slow
def test_dp_tcp_preempt_without_rejoin_record_degraded(native_bin,
                                                      tmp_path):
    """An eviction that never grows back mirrors the python tier's
    record: the evictee drains out alive (exit 0) but emits NOTHING —
    its post-eviction rows are local replay, not fabric work — and the
    survivors declare degraded_world, so the merged record rides the
    degraded pathway exactly like a shrink."""
    from dlnetbench_tpu.metrics.merge import merge_files
    from dlnetbench_tpu.metrics.parser import validate_record

    port = _free_port()
    world = 3
    outs_p = [tmp_path / f"p{r}.jsonl" for r in range(world)]
    procs = [subprocess.Popen(
        [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
         "--world", str(world), "--backend", "tcp", "--rank", str(r),
         "--coordinator", f"127.0.0.1:{port}", "--num_buckets", "2",
         "--time_scale", "0.001", "--size_scale", "0.0001",
         "--runs", "8", "--warmup", "1", "--no_topology",
         "--base_path", str(REPO), "--fault", PREEMPT_ONLY_PLAN,
         "--fault_policy", "shrink", "--out", str(outs_p[r])],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in range(world)]
    texts = _communicate_all(procs, timeout=120)
    for r in range(world):   # the evictee drained — nobody dies
        assert procs[r].returncode == 0, f"process {r}:\n{texts[r]}"
    assert not outs_p[1].exists()            # ...but it emits no record

    merged = merge_files(tmp_path / "merged.jsonl",
                         [outs_p[0], outs_p[2]])
    validate_record(merged)
    assert merged["global"]["degraded_world"] == [0, 2]
    assert [row["rank"] for row in merged["ranks"]] == [0, 2]


# ------------------------------------------------------------ hier lane
@pytest.mark.slow
@pytest.mark.native_slow
def test_hier_collective_delay_injected(native_bin, tmp_path):
    """The per-collective delay hook threads through the hierarchical
    fabric: a collective-scoped straggler on one global rank inflates
    the run and is accounted on that rank."""
    import os
    port = _free_port()
    plan = ('{"events":[{"kind":"delay","ranks":[1],"iteration":0,'
            '"magnitude_us":5000,"where":"collective"}]}')
    outs_p = [tmp_path / f"h{r}.jsonl" for r in range(2)]
    procs = [subprocess.Popen(
        [str(native_bin / "dp"), "--model", "gpt2_l_16_bfloat16",
         "--world", "4", "--backend", "pjrt", "--procs", "2",
         "--rank", str(r), "--coordinator", f"127.0.0.1:{port}",
         "--num_buckets", "2", "--time_scale", "0.0001",
         "--size_scale", "0.00001", "--runs", "2", "--warmup", "1",
         "--no_topology", "--base_path", str(REPO),
         "--fault", plan, "--out", str(outs_p[r])],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env={**os.environ, "DLNB_PJRT_EXECUTOR": "host"})
        for r in range(2)]
    texts = _communicate_all(procs, timeout=120)
    for r in range(2):
        assert procs[r].returncode == 0, f"process {r}:\n{texts[r]}"
    rec0 = json.loads(outs_p[0].read_text().strip())
    rows = {row["rank"]: row for row in rec0["ranks"]}
    # rank 1 lives on process 0 (locals 2+2); its per-collective delays
    # are accounted there, rank 0's are zero
    assert rows[1]["fault_injected_delay_us"] > 0
    assert rows[0]["fault_injected_delay_us"] == 0.0
