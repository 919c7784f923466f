"""Span tracer + merged Chrome-trace exporter (metrics/spans.py).

Locks the tentpole properties: spans nest and close correctly (including
across threads and through exceptions), the disabled path hands out one
shared no-op object (nothing allocated or recorded per span), and the
merged host+device trace.json round-trips through the SAME loader the
device-trace channel uses (``profiling.load_trace_events``), with
collective device ops colored/kind-tagged via ``classify_op``.
"""
from __future__ import annotations

import json
import threading

import pytest

from dlnetbench_tpu.metrics import spans
from dlnetbench_tpu.metrics.profiling import collective_stats, load_trace_events


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Never leak an enabled tracer into (or out of) a test."""
    spans.disable()
    yield
    spans.disable()


def test_disabled_span_is_shared_noop():
    assert not spans.is_enabled()
    a = spans.span("anything", key="value")
    b = spans.span("else")
    # ONE module-level singleton: the disabled path allocates no span
    # object, and entering it records nothing anywhere
    assert a is b is spans.NULL_SPAN
    with a:
        pass
    assert spans.current() is None


def test_enable_disable_lifecycle():
    tr = spans.enable()
    assert spans.is_enabled() and spans.current() is tr
    with spans.span("x"):
        pass
    got = spans.disable()
    assert got is tr and not spans.is_enabled()
    assert [s["name"] for s in tr.spans] == ["x"]
    # disabled again: back to the singleton
    assert spans.span("y") is spans.NULL_SPAN


def test_spans_nest_and_close_correctly():
    tr = spans.enable()
    with spans.span("outer", phase="run"):
        with spans.span("inner"):
            pass
        with spans.span("inner2"):
            pass
    spans.disable()
    by_name = {s["name"]: s for s in tr.spans}
    assert set(by_name) == {"outer", "inner", "inner2"}
    outer, inner, inner2 = (by_name[n] for n in ("outer", "inner", "inner2"))
    # children close before the parent (append order) and nest inside it
    assert [s["name"] for s in tr.spans] == ["inner", "inner2", "outer"]
    assert outer["depth"] == 0 and inner["depth"] == inner2["depth"] == 1
    for child in (inner, inner2):
        assert child["ts_us"] >= outer["ts_us"]
        assert (child["ts_us"] + child["dur_us"]
                <= outer["ts_us"] + outer["dur_us"] + 1e-6)
    assert outer["attrs"] == {"phase": "run"}


def test_span_survives_exception_and_marks_it():
    tr = spans.enable()
    with pytest.raises(RuntimeError):
        with spans.span("doomed", what="x"):
            raise RuntimeError("boom")
    # the failed phase stays on the timeline, marked — and the depth
    # stack unwound, so the next span is top-level again
    with spans.span("after"):
        pass
    spans.disable()
    doomed, after = tr.spans
    assert doomed["name"] == "doomed"
    assert doomed["attrs"]["error"] == "RuntimeError"
    assert after["depth"] == 0


def test_threads_keep_independent_depth():
    tr = spans.enable()
    seen = {}

    def worker():
        with spans.span("in-thread"):
            pass

    with spans.span("main-outer"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    spans.disable()
    for s in tr.spans:
        seen[s["name"]] = s
    # the worker's span is NOT nested under the main thread's open span
    assert seen["in-thread"]["depth"] == 0
    assert seen["in-thread"]["tid"] != seen["main-outer"]["tid"]


def _synthetic_device_events():
    """What load_trace_events returns from a jax profiler dir: complete
    events on the profiler's own epoch (big ts), some collectives."""
    return [
        {"ph": "X", "pid": 7, "tid": 1, "name": "fusion.3",
         "ts": 5_000_000.0, "dur": 40.0},
        {"ph": "X", "pid": 7, "tid": 1, "name": "all-reduce.1",
         "ts": 5_000_050.0, "dur": 25.0},
        {"ph": "X", "pid": 8, "tid": 1, "name": "collective-permute.2",
         "ts": 5_000_060.0, "dur": 10.0},
    ]


def test_merged_trace_roundtrips_through_load_trace_events(tmp_path):
    tr = spans.enable()
    with spans.span("build"):
        pass
    with spans.span("profile"):
        pass
    spans.disable()

    out = tmp_path / "trace.json"
    trace = spans.write_chrome_trace(out, tr, _synthetic_device_events())

    # one artifact, loadable by the same loader as the raw device traces
    events = load_trace_events(out)
    names = [e["name"] for e in events]
    assert "build" in names and "profile" in names
    assert "all-reduce.1" in names and "fusion.3" in names
    # the device half still feeds the per-collective stats channel
    stats = collective_stats(events)
    assert stats["allreduce"]["count"] == 1
    assert stats["permute"]["count"] == 1

    by_name = {e["name"]: e for e in trace["traceEvents"]
               if e.get("ph") == "X"}
    # host track on pid 0; device pids shifted past it
    assert by_name["build"]["pid"] == spans.HOST_PID
    assert by_name["all-reduce.1"]["pid"] > spans.HOST_PID
    # collectives colored + kind-tagged via classify_op; compute ops not
    assert by_name["all-reduce.1"]["cname"]
    assert by_name["all-reduce.1"]["args"]["kind"] == "allreduce"
    assert by_name["collective-permute.2"]["args"]["kind"] == "permute"
    assert "cname" not in by_name["fusion.3"]
    # device timeline aligned: earliest device event starts where the
    # host "profile" span (the profiled iteration) starts
    profile_ts = next(s["ts_us"] for s in tr.spans if s["name"] == "profile")
    assert by_name["fusion.3"]["ts"] == pytest.approx(profile_ts)


def test_host_only_trace_and_file_loader(tmp_path):
    tr = spans.enable()
    with spans.span("only-host"):
        pass
    spans.disable()
    out = tmp_path / "host.json"
    spans.write_chrome_trace(out, tr, None)
    events = load_trace_events(out)
    assert [e["name"] for e in events] == ["only-host"]
    # a directory without profiler output still raises (old contract)
    with pytest.raises(FileNotFoundError):
        load_trace_events(tmp_path / "empty_dir_nope")


@pytest.mark.slow
def test_cli_trace_out_end_to_end(eight_devices, tmp_path):
    """Acceptance lock: ONE cli command produces a merged host+device
    trace with build/compile/warmup/timed phases AND device collective
    ops visible, loadable through load_trace_events."""
    from dlnetbench_tpu.cli import main

    out = tmp_path / "rec.jsonl"
    trace = tmp_path / "t.json"
    rc = main(["dp", "--model", "gpt2_l_16_bfloat16", "--num_buckets", "2",
               "--platform", "cpu", "-r", "2", "-w", "1",
               "--size_scale", "1e-5", "--time_scale", "1e-4",
               "--no_topology", "--trace-out", str(trace),
               "--out", str(out)])
    assert rc == 0
    events = load_trace_events(trace)
    host_names = {e["name"] for e in events
                  if e.get("pid") == spans.HOST_PID}
    # the harness phases the tentpole demands, all on one timeline
    for phase in ("build", "compile", "warmup", "timed", "fence",
                  "profile"):
        assert phase in host_names, f"missing host span {phase!r}"
    # device collectives present and kind-tagged
    stats = collective_stats(events)
    assert stats.get("allreduce", {}).get("count", 0) >= 1
    assert any(e.get("args", {}).get("kind") == "allreduce"
               for e in events)


# ---------------------------------------------------------------------
# record-derived tracks: native-tier --trace-out (satellite)


def _attrib_record():
    from pathlib import Path
    return json.loads((Path(__file__).parent / "data"
                       / "record_attrib.jsonl").read_text())


def test_active_stacks_snapshot():
    spans.enable()
    try:
        assert spans.active_stacks() == {}
        with spans.span("outer"):
            with spans.span("inner"):
                stacks = spans.active_stacks()
                assert list(stacks.values()) == [["outer", "inner"]]
            assert list(spans.active_stacks().values()) == [["outer"]]
        assert spans.active_stacks() == {}
    finally:
        spans.disable()
    assert spans.active_stacks() == {}  # tracing off -> {}


def test_attribution_counter_events():
    attr = {"fractions": {"compute": 0.6, "hbm": 0.1,
                          "comm_exposed": 0.2, "host": 0.1},
            "bound": "mxu"}
    events = spans.attribution_counter_events(attr, dur_us=500.0)
    names = [e["name"] for e in events]
    assert "process_name" in names
    counters = [e for e in events if e["ph"] == "C"]
    # one sample at each end of the run window, all four series in args
    assert [e["ts"] for e in counters] == [0.0, 500.0]
    assert counters[0]["args"]["compute"] == 0.6
    meta = [e for e in events if e["name"] == "process_name"][0]
    assert "mxu" in meta["args"]["name"]
    assert spans.attribution_counter_events({}) == []
    assert spans.attribution_counter_events({"bound": "mxu"}) == []


def test_record_track_events_lay_out_runs():
    """A run record (either tier) becomes per-rank Perfetto tracks:
    runtimes as end-to-end duration events, sibling timers as counter
    series, band summaries as annotations, the attribution block as a
    counter track over the laid-out window."""
    rec = _attrib_record()
    events = spans.record_track_events(rec)
    by_ph = {}
    for e in events:
        by_ph.setdefault(e["ph"], []).append(e)
    runs = [e for e in by_ph["X"] if e["name"].startswith("run ")]
    n_ranks = len(rec["ranks"])
    n_runs = len(rec["ranks"][0]["runtimes"])
    assert len(runs) == n_ranks * n_runs
    # rank 0's runs are wall-adjacent: run j starts where j-1 ended
    r0 = [e for e in runs if e["pid"] == spans._RECORD_PID_BASE]
    assert r0[1]["ts"] == pytest.approx(r0[0]["ts"] + r0[0]["dur"])
    # band summaries annotate the track
    bands = [e for e in events if e["ph"] == "i"]
    assert any(e["name"] == "runtimes band" for e in bands)
    assert bands[0]["args"]["n"] == n_runs
    # the record's attribution block rides as a counter track
    counters = [e for e in by_ph["C"]
                if e["pid"] == spans.ATTRIBUTION_PID]
    assert counters and "compute" in counters[0]["args"]


def test_merge_trace_out_writes_native_style_trace(tmp_path):
    """metrics.merge --trace-out: a record-only trace (no in-process
    tracer, the native tier's situation) that round-trips through the
    shared loader."""
    from dlnetbench_tpu.metrics import merge as merge_mod

    rec = _attrib_record()
    src = tmp_path / "in.jsonl"
    src.write_text(json.dumps(rec) + "\n")
    out = tmp_path / "merged.jsonl"
    trace = tmp_path / "trace.json"
    rc = merge_mod.main(["--trace-out", str(trace), str(out), str(src)])
    assert rc == 0
    written = json.loads(trace.read_text())
    phs = {e["ph"] for e in written["traceEvents"]}
    assert {"X", "C", "M"} <= phs
    # the shared loader reads the complete events back (device-timeline
    # consumers only ever see X events)
    loaded = load_trace_events(trace)
    assert loaded and all(e["ph"] == "X" for e in loaded)


# ---------------------------------------------------------------------
# Spans on the profiler's clock (ISSUE 24).

def test_enabled_span_lies_on_the_profilers_clock(tmp_path):
    """While a profiler trace runs, an enabled span is an event on the
    host line of the same ``.xplane.pb`` as the executed operations,
    its numeric and string attrs the event's stats — and on the same
    clock: the operations of the call it wraps start and end inside
    it, with no shifting."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    tracer = spans.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("fenced", k=3, what="wait", share=0.5,
                        argnums=[0]):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    spans.disable()
    data = ProfileData.from_file(glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0])
    events = [ev for plane in data.planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    mine = [ev for ev in events if ev.name == "fenced"]
    assert len(mine) == 1
    assert dict(mine[0].stats) == {"k": 3, "what": "wait", "share": 0.5}
    begin, end = mine[0].start_ns, mine[0].start_ns + mine[0].duration_ns
    ran = [ev for ev in events if "hlo_op" in dict(ev.stats)]
    assert ran
    assert all(begin <= ev.start_ns
               and ev.start_ns + ev.duration_ns <= end for ev in ran)
    # the tracer kept the region too, with every attr: it is timed
    # inside the annotation
    (rec,) = tracer.spans
    assert rec["attrs"]["argnums"] == [0]
    assert 0 < rec["dur_us"] * 1e3 <= mine[0].duration_ns + 1e3


def test_span_without_a_profiler_trace_costs_no_event():
    """No trace running: the annotation is a flag test and the span is
    kept by the tracer as before."""
    tracer = spans.enable()
    with spans.span("alone", n=1):
        pass
    spans.disable()
    assert [(s["name"], s["attrs"]) for s in tracer.spans] == \
        [("alone", {"n": 1})]


def test_disabled_path_reads_no_clock_and_makes_no_annotation(monkeypatch):
    import time
    monkeypatch.setattr(spans, "_Span", None)        # would raise if made
    monkeypatch.setattr(time, "perf_counter",
                        lambda: pytest.fail("clock read"))
    with spans.span("off", k=1) as s:
        assert s is spans.NULL_SPAN
    assert spans.active_stacks() == {}


def test_export_is_plain_data_and_a_copy():
    tracer = spans.enable()
    with spans.span("a", k=1):
        pass
    tracer.register_op_scopes("jit_f", {"fusion.1": "attn"})
    spans.disable()
    got = tracer.export()
    assert json.loads(json.dumps(got)) == got
    assert got["op_scopes"] == {"jit_f": {"fusion.1": "attn"}}
    got["op_scopes"]["jit_f"]["fusion.1"] = "mlp"
    got["spans"][0]["name"] = "b"
    assert tracer.op_scopes["jit_f"]["fusion.1"] == "attn"
    assert tracer.spans[0]["name"] == "a"
