"""The trace reduction against the hand-built event list kept beside it
(overlapping, nested and window-straddling intervals)."""
import json
from pathlib import Path

import pytest

from benchmarks import trace_reduce as tr

FIX = json.loads((Path(tr.__file__).parent / "trace_fixture.json")
                 .read_text())
OPS = [tuple(e) for e in FIX["ops"]]
HOST = [tuple(e) for e in FIX["host"]]
T0, T1 = FIX["window"]
WANT = FIX["expect"]


def test_union_merges_overlapping_and_nested():
    assert tr.union([(1, 3), (0, 2), (5, 6), (5.2, 5.5), (6, 6)]) == \
        [(0, 3), (5, 6)]


def test_busy_union_counts_overlap_once():
    assert tr.busy_seconds(tr.clip(OPS, T0, T1)) == \
        pytest.approx(WANT["busy_s"])


def test_idle_share():
    assert tr.idle_share(OPS, T0, T1) == pytest.approx(WANT["idle_share"])


@pytest.mark.parametrize("name", sorted(WANT["time_by_name"]))
def test_time_by_name(name):
    got = tr.time_by_name(tr.clip(OPS, T0, T1))
    assert got[name] == pytest.approx(WANT["time_by_name"][name])


def test_clip_drops_events_outside_the_window():
    assert "before.0" not in tr.time_by_name(tr.clip(OPS, T0, T1))


def test_top_ops_orders_by_device_time():
    got = tr.top_ops(tr.clip(OPS, T0, T1), 2)
    assert [g[0] for g in got] == [w[0] for w in WANT["top_ops"]]
    assert [g[1] for g in got] == pytest.approx(
        [w[1] for w in WANT["top_ops"]])


def test_gaps():
    got = tr.gaps(OPS, T0, T1)
    assert [list(g) for g in got] == [pytest.approx(w)
                                      for w in WANT["gaps"]]


def test_idle_gaps_named_by_host_span():
    got = tr.idle_gaps(OPS, HOST, T0, T1)
    assert [g[0] for g in got] == [w[0] for w in WANT["idle_gaps"]]
    assert [g[1] for g in got] == pytest.approx(
        [w[1] for w in WANT["idle_gaps"]])


def test_gap_without_host_span_takes_the_default():
    assert tr.idle_gaps(OPS, [], T0, T1, default="engine_loop")[0][0] \
        == "engine_loop"


def test_matching_by_pattern():
    assert len(tr.matching(OPS, r"^flash")) == 2


@pytest.mark.parametrize("name,want", [
    ("%fusion.54 = bf16[4096,51200]{1,0:T(8,128)(2,1)} fusion(bf16[4096]"
     " %p)", "fusion.54 bf16[4096,51200]"),
    ("%transpose_jvp___.18 = (bf16[2,6144,4096]{2,1,0}, bf16[2,6144,4096])"
     " custom-call(bf16[2] %c), custom_call_target=\"tpu_custom_call\"",
     "transpose_jvp___.18 bf16[2,6144,4096] pallas"),
    ("bench_step", "bench_step"),
])
def test_short_name(name, want):
    assert tr.short_name(name) == want


def reader_ctx():
    """Two executions of a step program inside the window, each holding
    one kernel of 0.25 s; a third straddles its end, and one that was
    running when the trace began is recorded from the trace's first
    operation on, cut short."""
    modules = [("jit_train_k(1)", 0.25, 0.5),
               ("jit_train_k(1)", 1.0, 2.0), ("jit_train_k(1)", 4.0, 2.0),
               ("jit_train_k(1)", 9.0, 2.0), ("jit_other(2)", 7.0, 0.5)]
    kernel = 'custom-call(bf16[2,4,8] %q), custom_call_target="tpu_custom_call"'
    ops = [("%fusion.2 = bf16[8] fusion()", 0.25, 0.5),
           (f"%k.1 = bf16[2,4,8] {kernel}", 1.5, 0.25),
           ("%fusion.2 = bf16[8] fusion()", 2.0, 0.75),
           (f"%k.1 = bf16[2,4,8] {kernel}", 4.5, 0.25),
           ("%fusion.2 = bf16[8] fusion()", 5.0, 0.5),
           (f"%k.1 = bf16[2,4,8] {kernel}", 9.5, 0.25)]
    return {"window": (0.0, 10.0),
            "devices": [{"ops": ops, "modules": modules}],
            "record": {"arch": {"num_layers": 1, "num_heads": 2,
                                "num_kv_heads": 1, "head_dim": 8},
                       "batch": 1, "seq": 4},
            "peaks": {"flops_per_s": {"bfloat16": 1792.0 * 8},
                      "hbm_bytes_per_s": 1e12},
            "cache": {"hits": 3, "misses": 2}}


def test_module_ms_is_the_median_busy_time_of_whole_executions():
    from benchmarks.readers import module_ms
    # executions at 1-3 and 4-6 lie inside; busy 1.0 s and 0.75 s
    assert module_ms.read(reader_ctx(), {"pattern": "train_k"}) == \
        pytest.approx(875.0)
    assert module_ms.read(reader_ctx(), {"pattern": "absent"}) is None


def test_executions_cut_short_by_the_traces_ends_are_left_out():
    from benchmarks.readers import module_ms
    ctx = reader_ctx()
    assert module_ms.executions(ctx, "train_k") == [(1.0, 3.0), (4.0, 6.0)]
    # one running when the trace stopped: recorded up to the last
    # operation (9.5 to 9.75), a few nanoseconds either way
    ctx["devices"][0]["modules"][3:4] = [("jit_train_k(1)", 9.5, 0.25 + 5e-9)]
    assert module_ms.executions(ctx, "train_k") == [(1.0, 3.0), (4.0, 6.0)]


def test_kernel_roofline_share():
    from benchmarks.readers import kernel_roofline
    params = {"module": "train_k", "pattern": "tpu_custom_call",
              "cost": "flash_attention", "dtype": "bfloat16"}
    # the hand-counted flash cost is 1792 operations (test_bench_stats):
    # least time 1/8 s a call, two whole executions, 0.5 s of kernels
    assert kernel_roofline.read(reader_ctx(), params) == pytest.approx(50.0)
    assert kernel_roofline.read(
        reader_ctx(), {**params, "pattern": "absent"}) is None


def test_device_idle_and_counters():
    from benchmarks.readers import cache_misses, device_idle
    ctx = reader_ctx()
    assert device_idle.read(ctx, {}) == pytest.approx(75.0)
    assert cache_misses.read(ctx, {}) == 2.0
