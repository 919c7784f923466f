"""bench.py auxiliary-line guard: a failing auxiliary line fails the
run; only the wall-clock deadline skips lines, and the headline says
which (``incomplete``).  The driver's tail parser reads the LAST stdout
line."""
from __future__ import annotations

import json

import pytest


def test_aux_failure_fails_the_run(capsys):
    """A line that raises is not turned into a skip marker: the
    exception reaches the caller (and the exit status)."""
    import bench

    def boom(*a):
        raise RuntimeError("synthetic compile pathology")

    aux = bench._Aux()
    with pytest.raises(RuntimeError, match="synthetic compile pathology"):
        aux("fp8 swiglu chain", boom, "card", "hw", "dev")
    assert capsys.readouterr().out == ""
    assert aux.incomplete == []


def test_aux_success_passes_through(capsys):
    import bench

    aux = bench._Aux()
    got = aux("x", lambda a: {"metric": a}, "ok")
    assert got == {"metric": "ok"}
    assert capsys.readouterr().out == ""
    assert aux.incomplete == []


def test_bench_device_refuses_a_silent_cpu(monkeypatch):
    """No TPU is an error unless the CPU was asked for by name; the
    named CPU has no HARDWARE key, so nothing is priced against a TPU
    peak; an unlisted TPU kind is an error too."""
    import jax

    import bench

    dev, hw_key = bench._bench_device()      # conftest names the CPU
    assert dev.platform == "cpu" and hw_key is None
    jax.config.update("jax_platforms", None)  # the backend is up: no effect
    try:
        with pytest.raises(SystemExit, match="found no TPU"):
            bench._bench_device()
    finally:
        jax.config.update("jax_platforms", "cpu")

    class Unlisted:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr(jax, "devices", lambda *a: [Unlisted()])
    with pytest.raises(SystemExit, match="no entry for device kind"):
        bench._bench_device()

    class V5e:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [V5e()])
    assert bench._bench_device()[1] == "tpu_v5e"


def test_above_peak_readings_are_flagged():
    """A line whose ratio exceeds 1.0 (physically impossible: the timed
    window did not cover the credited work) must carry the upper-bound
    note; in-range lines must not."""
    import bench

    hot = bench._flag_above_peak({"metric": "x", "vs_baseline": 1.05})
    assert "note" in hot and "above-peak" in hot["note"]
    ok = bench._flag_above_peak({"metric": "x", "vs_baseline": 0.98})
    assert "note" not in ok


def test_ab_line_schema_locked():
    """The fused-vs-composed A/B lines are BENCH artifacts (VERDICT r5
    top_next: aux results must appear in BENCH, not just session logs)
    — lock the artifact-grade stat-band schema: headline
    {value, unit, best, band, n}, one {value, best, band, n} sub-object
    per variant, and a paired per-round ratio band per non-composed
    variant."""
    import bench

    summaries = {
        "composed": {"value": 2.0, "best": 1.9, "band": [1.9, 2.2], "n": 3},
        "fused": {"value": 1.0, "best": 0.9, "band": [0.9, 1.2], "n": 3},
    }
    rounds = {"composed": [2.0, 1.9, 2.2], "fused": [1.0, 0.9, 1.2]}
    line = bench._ab_line("int8 fused-quant A/B (test)", summaries,
                          rounds, flops_per_iter=10 ** 12,
                          roofline_s=0.5)
    # headline band schema in ms
    assert line["unit"] == "ms"
    for key in ("value", "best", "band", "n"):
        assert key in line, key
    assert line["value"] == 1000.0 and line["n"] == 3
    assert line["band"] == [900.0, 1200.0]
    # per-variant sub-objects carry the same band schema
    for name in summaries:
        sub = line[name]
        for key in ("value", "best", "band", "n"):
            assert key in sub, (name, key)
        assert len(sub["band"]) == 2
    # paired ratio bands, fused vs composed pairing per round
    r = line["ratio_fused_vs_composed"]
    for key in ("value", "best", "band", "n"):
        assert key in r, key
    assert r["value"] == 0.5 and r["n"] == 3
    assert "ratio_composed_vs_composed" not in line
    # roofline ratio rides along (and the above-peak guard applies)
    assert line["vs_baseline"] == 0.5


def test_band_ms_schema():
    """Every aux line builds its band keys through _band_ms — lock the
    seconds->ms conversion and key set."""
    import bench

    got = bench._band_ms({"value": 0.0021, "best": 0.002,
                          "band": [0.002, 0.0025], "n": 3})
    assert got == {"best": 2.0, "band": [2.0, 2.5], "n": 3}


def test_overlap_ab_line_schema_locked():
    """The paired overlap-vs-baseline aux line (ISSUE 4: bench.py +
    multichip driver, models/overlap_bench.assemble_line) is a BENCH
    artifact — lock its schema: headline {value, unit, best, band, n}
    from the OVERLAPPED config, per-config band sub-objects, a paired
    per-round ratio band, and the measured overlap-fraction band per
    config."""
    from dlnetbench_tpu.models.overlap_bench import assemble_line

    walls = {"baseline": [0.2, 0.21, 0.19],
             "overlapped": [0.1, 0.12, 0.11]}
    overlaps = {"baseline": [0.05, 0.0, 0.1],
                "overlapped": [0.8, 0.7, 0.9]}
    line = assemble_line("spmd overlap A/B (test)", walls, overlaps)
    assert line["unit"] == "ms"
    for key in ("value", "best", "band", "n"):
        assert key in line, key
    assert line["value"] == 110.0 and line["n"] == 3
    for name in ("baseline", "overlapped"):
        sub = line[name]
        for key in ("value", "best", "band", "n"):
            assert key in sub, (name, key)
        assert len(sub["band"]) == 2
    r = line["ratio_overlapped_vs_baseline"]
    for key in ("value", "best", "band", "n"):
        assert key in r, key
    # per-round pairing: 0.1/0.2, 0.12/0.21, 0.11/0.19 -> median 0.5714
    assert r["value"] == 0.5714 and r["n"] == 3
    ov = line["overlap_fraction"]
    for name in ("baseline", "overlapped"):
        for key in ("value", "best", "band", "n"):
            assert key in ov[name], (name, key)
    assert ov["overlapped"]["value"] == 0.8


def test_recommended_step_line_schema_locked():
    """VERDICT r5 item #1's driver-captured half: the recommended_step
    line names the fastest recipe passing the stated numerics bar, with
    the winner's stat band and every candidate's loss + verdict."""
    import bench

    bf16 = {"value": 0.5375, "best": 0.53, "band": [0.53, 0.55], "n": 3}
    int8 = {"value": 494.3, "best": 490.0, "band": [490.0, 500.0],
            "n": 3, "loss": 10.41}
    sb = {"value": 454.9, "best": 450.0, "band": [450.0, 460.0],
          "n": 3, "loss": 10.45}
    line = bench._recommended_step(bf16, 10.42,
                                   {"int8_master": int8,
                                    "int8_switchback": sb})
    assert line["metric"] == "recommended_step"
    assert line["recipe"] == "int8_switchback"   # fastest, passes 2% bar
    assert line["unit"] == "ms"
    for key in ("value", "best", "band", "n", "numerics_bar"):
        assert key in line, key
    assert line["value"] == 454.9
    cands = line["candidates"]
    assert set(cands) == {"bf16", "int8_master", "int8_switchback"}
    assert all("loss" in c and "passes" in c for c in cands.values())
    # a candidate failing the bar cannot win, however fast
    sb_bad = dict(sb, loss=99.0)
    line2 = bench._recommended_step(bf16, 10.42,
                                    {"int8_master": int8,
                                     "int8_switchback": sb_bad})
    assert line2["recipe"] == "int8_master"
    assert line2["candidates"]["int8_switchback"]["passes"] is False
    # skipped candidates (None) don't compete; bf16 always does
    line3 = bench._recommended_step(bf16, 10.42, {"int8_master": None})
    assert line3["recipe"] == "bf16"
    assert line3["value"] == 537.5


def test_overlap_field_record_roundtrip_with_fixture():
    """Lock the ``overlap_fraction`` field of the record schema against
    the committed fixture: parser validation accepts it (per-rank timer
    array + band summary), the DataFrame carries it, metrics.merge
    round-trips it, and the bandwidth summary surfaces the ``overlap``
    column."""
    from pathlib import Path

    from dlnetbench_tpu.analysis.bandwidth import (bandwidth_summary,
                                                   effective_bandwidth)
    from dlnetbench_tpu.metrics.merge import merge_records
    from dlnetbench_tpu.metrics.parser import (load_records,
                                               records_to_dataframe,
                                               validate_record)

    path = Path(__file__).parent / "data" / "record_overlap.jsonl"
    records = load_records(path)
    assert len(records) == 1
    rec = records[0]
    validate_record(rec)
    # the fixture's overlap values are the formula applied to its timers
    from dlnetbench_tpu.metrics.stats import overlap_fraction
    row = rec["ranks"][0]
    expect = overlap_fraction(row["runtimes"], row["compute_time"],
                              row["comm_time"])
    assert row["overlap_fraction"] == [round(v, 4) for v in expect]

    df = records_to_dataframe(records)
    assert "overlap_fraction" in df.columns
    assert df["overlap_fraction"].tolist() == [0.5, 0.4318, 0.5, 0.4318]

    merged = merge_records(records)     # single-process merge: identity
    validate_record(merged)
    assert merged["ranks"][0]["overlap_fraction"] == [0.5, 0.4318]

    bw = effective_bandwidth([merged])
    assert "overlap" in bw.columns
    assert sorted(bw["overlap"].unique().tolist()) == [0.4318, 0.5]
    summary = bandwidth_summary([merged])
    assert "overlap" in summary.columns
    assert summary["overlap"].iloc[0] == (0.5 + 0.4318) / 2


def test_bandwidth_overlap_nan_without_decomposition():
    """Records that never measured the A/B decomposition get NaN in the
    overlap column — never a fabricated 0."""
    import math

    from dlnetbench_tpu.analysis.bandwidth import effective_bandwidth

    rec = {"section": "dp", "version": 2,
           "global": {"comm_model": {"comm_time": [
               {"kind": "allreduce", "group": 2, "bytes": 1000}]}},
           "mesh": {"platform": "cpu"},
           "ranks": [{"rank": 0, "comm_time": [10.0]}]}
    bw = effective_bandwidth([rec])
    assert math.isnan(bw["overlap"].iloc[0])


def test_serving_decode_line_schema_locked():
    """bench.py's serving_decode aux line (ISSUE 8) is a BENCH
    artifact: lock the stat-band schema — ms headline from the
    round-median e2e p99 (lower-is-better, so the sentinel compares it
    like every latency line), and {value, best, band, n} sub-objects
    for TTFT/TPOT/p99/tokens-per-s/goodput."""
    import bench
    rounds = [
        {"e2e_ms": {"p99": 10.0}, "ttft_ms": {"p50": 2.0},
         "tpot_ms": {"p50": 1.0}, "tokens_per_s": 100.0,
         "goodput_frac": 1.0, "completed": 16, "offered_rps": 80.0},
        {"e2e_ms": {"p99": 12.0}, "ttft_ms": {"p50": 2.2},
         "tpot_ms": {"p50": 1.1}, "tokens_per_s": 90.0,
         "goodput_frac": 0.9, "completed": 16, "offered_rps": 80.0},
        {"e2e_ms": {"p99": 11.0}, "ttft_ms": {"p50": 2.1},
         "tpot_ms": {"p50": 1.05}, "tokens_per_s": 95.0,
         "goodput_frac": 1.0, "completed": 16, "offered_rps": 80.0},
    ]
    line = bench._serving_decode_line(rounds, suffix=", test")
    assert line["unit"] == "ms"
    assert line["value"] == 11.0 and line["n"] == 3
    assert line["band"] == [10.0, 12.0] and line["best"] == 10.0
    for key in ("ttft_p50_ms", "tpot_p50_ms", "p99_ms",
                "tokens_per_s", "goodput_frac"):
        sub = line[key]
        for k in ("value", "best", "band", "n"):
            assert k in sub, (key, k)
    assert line["ttft_p50_ms"]["value"] == 2.1
    assert line["requests"] == 16 and line["offered_rps"] == 80.0
    # sentinel comparability: the line is an ms line, so bench.py
    # --check picks it up as "serving_decode" automatically
    from dlnetbench_tpu.sentinel import is_ms_line
    assert is_ms_line(line)


def test_disagg_line_schema_locked():
    """bench.py's disagg_ab aux line (ISSUE 16) is a BENCH artifact:
    lock the paired-arm stat-band schema — ms headline from the
    DISAGGREGATED arm's round-median e2e p99 (sentinel-comparable),
    {value, best, band, n} sub-objects for TTFT p50/p99 + TPOT p50 +
    tokens/s on BOTH arms, the migration wire cost on the disagg arm,
    and the band-disjoint interference verdict."""
    import bench

    def _round(p99, ttft50, ttft99, tpot, tps, mig=None):
        r = {"e2e_ms": {"p99": p99},
             "ttft_ms": {"p50": ttft50, "p99": ttft99},
             "tpot_ms": {"p50": tpot}, "tokens_per_s": tps}
        if mig is not None:
            r["migration"] = mig
        return r

    mono = [_round(10.0, 2.0, 5.0, 1.00, 100.0),
            _round(12.0, 2.2, 5.5, 1.10, 90.0),
            _round(11.0, 2.1, 5.2, 1.05, 95.0)]
    mig = {"bytes": 16896, "ms": {"p50": 0.4},
           "bytes_ratio_vs_bf16": 0.5156}
    dis = [_round(8.0, 1.8, 4.0, 0.50, 140.0, mig),
           _round(9.0, 1.9, 4.4, 0.55, 130.0, mig),
           _round(8.5, 1.85, 4.2, 0.52, 135.0, mig)]
    line = bench._disagg_line(mono, dis, suffix=", test",
                              token_parity=True)
    assert line["unit"] == "ms"
    assert line["value"] == 8.5 and line["n"] == 3
    assert line["band"] == [8.0, 9.0] and line["best"] == 8.0
    for arm in ("monolithic", "disaggregated"):
        for key in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                    "tokens_per_s"):
            sub = line[arm][key]
            for k in ("value", "best", "band", "n"):
                assert k in sub, (arm, key, k)
    d = line["disaggregated"]
    for key in ("migration_bytes", "migration_ms_p50"):
        for k in ("value", "best", "band", "n"):
            assert k in d[key], (key, k)
    assert d["migration_bytes"]["value"] == 16896.0
    assert d["migration_bytes_ratio"] == 0.5156
    # TPOT bands [1.0, 1.1] vs [0.5, 0.55]: disjoint AND lower — the
    # interference verdict the disagg study prices
    assert line["tpot_band_disjoint_drop"] is True
    assert line["token_parity"] is True
    # overlapping bands must NOT claim the win
    flat = bench._disagg_line(mono, mono)
    assert flat["tpot_band_disjoint_drop"] is False
    assert "token_parity" not in flat
    # sentinel comparability: an ms line, auto-compared by --check
    from dlnetbench_tpu.sentinel import is_ms_line
    assert is_ms_line(line)


def test_live_metrics_line_schema_locked(tmp_path):
    """ISSUE 14 satellite: the --live-metrics JSONL stream's snapshot
    line — one per window, rolling TTFT/TPOT percentiles over the
    WINDOW's completions, queue depth, admitted slots, KV occupancy —
    is a machine-read dashboard feed; lock its schema."""
    import json

    from dlnetbench_tpu.serving.metrics import (Completed,
                                                LiveMetricsWriter)

    done = [Completed(rid=i, arrival_s=0.1 * i, admitted_s=0.1 * i,
                      first_token_s=0.1 * i + 0.02,
                      finish_s=0.1 * i + 0.08, prompt_len=8,
                      output_len=4) for i in range(5)]
    line = LiveMetricsWriter.snapshot_line(
        t_s=0.5, window_s=0.5, window_completed=done, queue_depth=3,
        active_slots=2, kv_occupancy=0.625, engine_steps=40, run=1)
    assert set(line) == {"run", "t_s", "window_s", "completed",
                         "ttft_ms", "tpot_ms", "queue_depth",
                         "active_slots", "kv_occupancy",
                         "engine_steps"}
    # single-engine: unattributed — the key is absent so pre-fleet
    # consumers keep parsing byte-identical lines; a fleet replica's
    # stream carries it (ISSUE 18)
    fleet_line = LiveMetricsWriter.snapshot_line(
        t_s=0.5, window_s=0.5, window_completed=done, queue_depth=3,
        active_slots=2, kv_occupancy=0.625, engine_steps=40, run=1,
        replica_id=2)
    assert fleet_line["replica_id"] == 2
    assert set(fleet_line) - set(line) == {"replica_id"}
    assert line["run"] == 1  # (run, t_s) orders the feed — t_s is
    #                          run-relative and restarts per engine run
    assert line["completed"] == 5 and line["queue_depth"] == 3
    assert line["kv_occupancy"] == 0.625
    for base in ("ttft_ms", "tpot_ms"):
        for k in ("p50", "p95", "p99", "mean", "n"):
            assert k in line[base], (base, k)
    assert line["ttft_ms"]["p50"] == 20.0  # 0.02 s to first token
    # the writer emits at window boundaries, JSONL-append, and the
    # bench flag reaches the serving aux line
    path = tmp_path / "live.jsonl"
    w = LiveMetricsWriter(path, window_s=0.5)

    class _Eng:
        completed = done
        pending = [1, 2, 3]
        slots = [object(), object(), None]
        engine_steps = 40

        class cache:
            @staticmethod
            def stats():
                return {"occupancy": 0.625}

    assert w.maybe_emit(_Eng(), 0.5) is not None
    assert w.maybe_emit(_Eng(), 0.6) is None   # inside the window
    assert w.maybe_emit(_Eng(), 1.1) is not None
    got = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(got) == 2 and got[0]["active_slots"] == 2
    import bench
    args = bench._parse_args(["--live-metrics", str(path)])
    assert args.live_metrics == str(path)


def test_fleet_line_schema_locked():
    """bench.py's fleet_ab aux line (ISSUE 18) is a BENCH artifact:
    lock the three-arm routing A/B schema — ms headline from the
    PREFIX_AFFINITY arm's round-median TTFT p50 (sentinel-comparable),
    {value, best, band, n} sub-objects for TTFT p50/p99 + tokens/s on
    ALL THREE arms, the affinity arm's hit-rate and prefix-reuse
    bands, and the band-disjoint routing verdict vs round_robin."""
    import bench

    def _round(ttft50, ttft99, tps, *, hit=None, reuse=None):
        r = {"serving": {"ttft_ms": {"p50": ttft50, "p99": ttft99},
                         "tokens_per_s": tps}}
        if hit is not None:
            r["fleet"] = {"replicas": 2, "affinity_hit_rate": hit,
                          "prefix_reuse_tokens": reuse}
        else:
            r["fleet"] = {"replicas": 2}
        return r

    rr = [_round(10.0, 22.0, 100.0), _round(11.0, 24.0, 95.0),
          _round(10.5, 23.0, 98.0)]
    p2 = [_round(9.0, 20.0, 105.0), _round(9.5, 21.0, 102.0),
          _round(9.2, 20.5, 104.0)]
    pa = [_round(4.0, 12.0, 130.0, hit=0.8, reuse=256.0),
          _round(4.5, 13.0, 125.0, hit=0.75, reuse=224.0),
          _round(4.2, 12.5, 128.0, hit=0.8, reuse=256.0)]
    line = bench._fleet_line(
        {"round_robin": rr, "p2c": p2, "prefix_affinity": pa},
        suffix=", test", token_parity=True)
    assert line["unit"] == "ms"
    assert line["value"] == 4.2 and line["n"] == 3
    assert line["band"] == [4.0, 4.5] and line["best"] == 4.0
    for arm in ("round_robin", "p2c", "prefix_affinity"):
        for key in ("ttft_p50_ms", "ttft_p99_ms", "tokens_per_s"):
            sub = line[arm][key]
            for k in ("value", "best", "band", "n"):
                assert k in sub, (arm, key, k)
    for key in ("affinity_hit_rate", "prefix_reuse_tokens"):
        for k in ("value", "best", "band", "n"):
            assert k in line["prefix_affinity"][key], (key, k)
    assert line["prefix_affinity"]["affinity_hit_rate"]["value"] == 0.8
    # TTFT bands [10.0, 11.0] vs [4.0, 4.5]: disjoint AND lower — the
    # routing verdict the fleet study prices
    assert line["ttft_band_disjoint_drop"] is True
    assert line["token_parity"] is True
    # overlapping bands must NOT claim the win
    flat = bench._fleet_line(
        {"round_robin": rr, "p2c": rr,
         "prefix_affinity": [dict(r, fleet={"replicas": 2,
                                            "affinity_hit_rate": 0.0,
                                            "prefix_reuse_tokens": 0.0})
                             for r in rr]})
    assert flat["ttft_band_disjoint_drop"] is False
    assert "token_parity" not in flat
    # sentinel comparability: an ms line, auto-compared by --check
    from dlnetbench_tpu.sentinel import is_ms_line
    assert is_ms_line(line)


def _ab_round(e2e_p99, tokens_per_s, *, n=1, spd=1.0, dev_us=50000.0,
              steps=50, disp=50, host_us=500.0, spec=None):
    """A synthetic per-round serving block with a decode_loop section
    (the ISSUE 11 A/B inputs)."""
    dl = {"multi_step_n": n, "steps_per_dispatch": spd,
          "tokens_per_sync": spd * 4, "dispatches": disp,
          "device_steps": steps, "device_us": {"total": dev_us},
          "decode_device_us": {"total": dev_us},
          "host_dispatch_us": {"total": host_us, "p50": host_us / disp,
                               "mean": host_us / disp, "n": disp},
          "sync_h2d_us": {"total": 100.0, "n": 2},
          "sync_d2h_us": {"total": 100.0, "n": 2}}
    if spec:
        dl["spec"] = spec
    return {"e2e_ms": {"p99": e2e_p99}, "ttft_ms": {"p50": 2.0},
            "tpot_ms": {"p50": 1.0}, "tokens_per_s": tokens_per_s,
            "goodput_frac": 1.0, "completed": 8, "offered_rps": 80.0,
            "wall_s": 0.1, "decode_loop": dl}


def test_serving_decode_ab_schema_locked():
    """The ISSUE 11 A/B extensions of the serving_decode line: paired
    variant sub-blocks (tokens/s + TPOT bands, speedup, dispatch
    decomposition), the host-fraction drop with its band-disjoint
    verdict, speculative acceptance, and the token-parity lock — all
    while the ISSUE 8 base schema (sentinel-comparable ms line) stays
    intact."""
    import bench

    # one-step: 500us/dispatch floor hidden in dev (50 steps x 1000us);
    # multi: 8 steps/dispatch amortize it (48*500 + 6*500 = 27000us)
    one = [_ab_round(30.0, 4000.0, dev_us=50 * 1000.0)
           for _ in range(3)]
    multi = [_ab_round(15.0, 8000.0, n=8, spd=8.0,
                       dev_us=48 * 500.0 + 6 * 500.0, steps=48, disp=6,
                       host_us=120.0) for _ in range(3)]
    spec = [_ab_round(14.0, 9000.0, n=8, spd=9.0, dev_us=30000.0,
                      steps=45, disp=5, host_us=110.0,
                      spec={"k": 4, "drafter": "ngram",
                            "acceptance_rate": 0.4, "drafted": 100,
                            "accepted": 40}) for _ in range(3)]
    line = bench._serving_decode_line(one, suffix=", test",
                                      multi_rounds=multi,
                                      spec_rounds=spec,
                                      token_parity=True)
    # ISSUE 8 base schema intact
    assert line["unit"] == "ms" and line["value"] == 30.0
    from dlnetbench_tpu.sentinel import is_ms_line
    assert is_ms_line(line)
    # the A/B blocks
    for key in ("multi_step", "speculative"):
        blk = line[key]
        for sub in ("tokens_per_s", "tpot_p50_ms", "e2e_p99_ms",
                    "speedup_tokens_per_s", "steps_per_dispatch",
                    "tokens_per_sync"):
            for k in ("value", "best", "band", "n"):
                assert k in blk[sub], (key, sub, k)
        assert blk["multi_step_n"] == 8
    assert line["multi_step"]["speedup_tokens_per_s"]["value"] == 2.0
    assert line["speculative"]["spec"]["acceptance_rate"]["value"] \
        == 0.4
    # the attribution flip: per-dispatch floor solved from the pair
    # (d1=1000, dn=562.5, spd=8 -> floor=500us), host fractions banded,
    # drop verdict band-disjoint
    flip = line["attribution_flip"]
    assert flip["dispatch_us"]["value"] == pytest.approx(500.0, abs=1)
    assert flip["one_step_host_frac"]["value"] > \
        flip["multi_step_host_frac"]["value"]
    assert flip["band_disjoint_drop"] is True
    assert "speculative_host_frac" in flip
    assert line["token_parity"] is True
    # without the A/B inputs the line stays the ISSUE 8 shape (no
    # accidental keys) — the schema the committed BENCH_r05
    # artifact's sentinel walk expects
    base_line = bench._serving_decode_line(one, suffix=", test")
    for key in ("multi_step", "speculative", "attribution_flip",
                "token_parity"):
        assert key not in base_line


def test_aux_deadline_skips_instead_of_running(capsys):
    """Past the wall-clock deadline the aux fn must not even start —
    the headline line takes precedence over auxiliary coverage — and
    the skip is recorded, so the headline can carry ``incomplete``."""
    import bench

    aux = bench._Aux(deadline_s=-1.0)
    ran = []
    got = aux("int8 matmul", lambda: ran.append(1))
    assert got is None and not ran
    line = json.loads(capsys.readouterr().out.strip())
    assert line["metric"] == "int8 matmul"
    assert "deadline" in line["skipped"]
    assert aux.incomplete == ["int8 matmul"]


def test_checkpoint_ab_line_schema_locked(monkeypatch, tmp_path):
    """The stall-vs-async checkpoint A/B is a BENCH artifact: lock the
    schema — headline {value, unit, n}, the three step bands, the
    measured save-cost band, state size and backend — without paying
    for a real dp build (the proxy step is a stub; the checkpointer
    runs for real over a tiny state, so save costs are measured)."""
    import jax.numpy as jnp

    import bench

    class FakeBundle:
        full = staticmethod(lambda: None)
        state = {"w": jnp.ones((64,), jnp.float32)}

    monkeypatch.setattr(
        "dlnetbench_tpu.proxies.dp.build", lambda *a, **k: FakeBundle())
    monkeypatch.setenv("TMPDIR", str(tmp_path))

    def fake_time_chain(fn, k):
        import time as _t
        t0 = _t.monotonic()
        for _ in range(k):
            fn()
        return 0.001 + (_t.monotonic() - t0) / k

    monkeypatch.setattr("dlnetbench_tpu.utils.timing.time_chain",
                        fake_time_chain)
    line = bench._bench_checkpoint_ab()
    assert line is not None
    assert line["metric"].startswith("checkpoint A/B")
    assert line["unit"].startswith("fraction of save cost")
    for key in ("baseline_ms", "stall_ms", "async_ms", "save_ms"):
        sub = line[key]
        assert set(sub) == {"value", "best", "band", "n"}
        assert sub["band"][0] <= sub["value"] <= sub["band"][1]
    # a stall-mode save rides the step; the async step must sit closer
    # to the baseline than the stall step does
    assert line["stall_ms"]["value"] >= line["async_ms"]["value"]
    assert line["save_ms"]["n"] == 12  # 3 rounds x k=4, every=1
    assert line["state_bytes"] == 64 * 4
    assert line["backend"] in ("npz", "orbax")
    assert line["n"] == 3
    # nothing left behind: the A/B cleans up its checkpoint tree
    assert not list(tmp_path.glob("dlnb_ckpt_ab_*"))


def test_straggler_ab_line_schema_locked(monkeypatch):
    """The faulted-vs-clean straggler A/B is a BENCH artifact: lock the
    schema — amplification headline {value, unit, n}, both step bands
    ({value, best, band, n} in ms), and the injected delay — without
    paying for a real dp build (timing is monkeypatched)."""
    import itertools

    import bench

    class FakeBundle:
        full = staticmethod(lambda: None)

    monkeypatch.setattr(
        "dlnetbench_tpu.proxies.dp.build", lambda *a, **k: FakeBundle())
    # clean chains 1 ms/step; faulted chains ride the injector's sleep
    seq = itertools.cycle([0.001])

    def fake_time_chain(fn, k):
        base = next(seq)
        import time as _t
        t0 = _t.monotonic()
        for _ in range(k):
            fn()
        return base + (_t.monotonic() - t0) / k

    monkeypatch.setattr("dlnetbench_tpu.utils.timing.time_chain",
                        fake_time_chain)
    line = bench._bench_straggler_ab()
    assert line is not None
    assert line["metric"].startswith("straggler A/B")
    assert line["unit"].startswith("x (")
    assert line["injected_ms"] >= 2.0
    for key in ("clean_ms", "faulted_ms"):
        sub = line[key]
        assert set(sub) == {"value", "best", "band", "n"}
        assert sub["band"][0] <= sub["value"] <= sub["band"][1]
    # the faulted band must sit above the clean band by ~the injection
    assert line["faulted_ms"]["value"] > line["clean_ms"]["value"]
    assert 0.5 < line["value"] < 2.0  # measured amplification ~1 here
    assert line["n"] == 3


def test_tuned_ab_line_schema_locked():
    """bench.py's tuned-vs-frozen A/B line (ISSUE 9): the headline
    ``value`` is the TUNED chain's median ms with {value, best, band,
    n} bands, both variants ship sub-objects + of-peak ratios, the
    paired per-round ratio band pairs them, band_disjoint_win states
    the acceptance verdict, and the DB provenance (path, prior
    hit/miss, committed configs, search meta) rides the line."""
    import bench

    summaries = {
        "tuned": {"value": 0.010, "best": 0.009,
                  "band": [0.009, 0.011], "n": 3},
        "frozen": {"value": 0.020, "best": 0.019,
                   "band": [0.019, 0.021], "n": 3},
    }
    rounds = {"tuned": [0.009, 0.010, 0.011],
              "frozen": [0.019, 0.020, 0.021]}
    line = bench._tuned_ab_line(
        summaries, rounds, flops_per_iter=10 ** 12, roofline_s=0.008,
        metric="tuned A/B: test", db_path="/tmp/tdb/tuning_db.jsonl",
        configs={"up": {"block_m": 512}}, db_prior_hit={"up": False},
        search_meta={"up": {"candidates": 3, "pruned": 1, "seed": 0}})
    assert line["unit"] == "ms" and line["value"] == 10.0
    assert line["band"] == [9.0, 11.0] and line["n"] == 3
    assert line["vs_baseline"] == 0.8          # roofline / tuned
    assert line["vs_baseline_frozen"] == 0.4   # roofline / frozen
    for sub in ("tuned_ms", "frozen_ms"):
        for k in ("value", "best", "band", "n"):
            assert k in line[sub], (sub, k)
    r = line["ratio_tuned_vs_frozen"]
    assert r["n"] == 3 and r["value"] == 0.5
    assert line["band_disjoint_win"] is True   # disjoint AND faster
    assert line["db_path"].endswith("tuning_db.jsonl")
    assert line["db_prior_hit"] == {"up": False}
    assert line["configs"]["up"]["block_m"] == 512
    assert line["search"]["up"]["candidates"] == 3
    # an overlapping-band win is NOT band-disjoint
    summaries2 = dict(summaries)
    summaries2["frozen"] = {"value": 0.0105, "best": 0.010,
                            "band": [0.010, 0.011], "n": 3}
    line2 = bench._tuned_ab_line(
        summaries2, rounds, flops_per_iter=10 ** 12, roofline_s=0.008,
        metric="m", db_path="p", configs={}, db_prior_hit={},
        search_meta={})
    assert line2["band_disjoint_win"] is False
    # sentinel comparability: bench.py --check picks it up as
    # "tuned_ab" automatically
    from dlnetbench_tpu.sentinel import is_ms_line
    assert is_ms_line(line)


def test_longcontext_line_schema_locked():
    """bench.py's dense-vs-splash long-context A/B line (ISSUE 10):
    headline value = the WINDOW-masked splash median ms with {value,
    best, band, n}, every variant a sub-object, masked variants a
    paired per-round ratio band vs dense, speedup_vs_sparsity the
    measured-over-expected consistency ratio, and the mask specs +
    sparsity riding as comparable globals."""
    import bench

    summaries = {
        "dense": {"value": 0.020, "best": 0.019,
                  "band": [0.019, 0.021], "n": 3},
        "splash_causal": {"value": 0.019, "best": 0.018,
                          "band": [0.018, 0.020], "n": 3},
        "splash_window": {"value": 0.005, "best": 0.0045,
                          "band": [0.0045, 0.0055], "n": 3},
        "splash_segment": {"value": 0.010, "best": 0.009,
                           "band": [0.009, 0.011], "n": 3},
    }
    rounds = {
        "dense": [0.019, 0.020, 0.021],
        "splash_causal": [0.018, 0.019, 0.020],
        "splash_window": [0.0045, 0.005, 0.0055],
        "splash_segment": [0.009, 0.010, 0.011],
    }
    mask_info = {
        "splash_causal": {"attention_mask": "causal",
                          "mask_sparsity": 0.499,
                          "block_skip_fraction": 0.48,
                          "expected_speedup": 1.0},
        "splash_window": {"attention_mask": "causal&window(4096)",
                          "mask_sparsity": 0.94,
                          "block_skip_fraction": 0.87,
                          "expected_speedup": 4.0},
        "splash_segment": {"attention_mask": "causal&seg(avg=8192,seed=0)",
                           "mask_sparsity": 0.9,
                           "block_skip_fraction": 0.8,
                           "expected_speedup": 2.0},
    }
    line = bench._longcontext_line(summaries, rounds,
                                   metric="longcontext A/B: test",
                                   mask_info=mask_info)
    assert line["unit"] == "ms" and line["value"] == 5.0
    assert line["band"] == [4.5, 5.5] and line["n"] == 3
    for sub in ("dense", "splash_causal", "splash_window",
                "splash_segment"):
        for k in ("value", "best", "band", "n"):
            assert k in line[sub], (sub, k)
    r = line["ratio_splash_window_vs_dense"]
    assert r["n"] == 3 and r["value"] == 0.25
    # measured speedup 4.0 vs expected 4.0 -> consistency ratio 1.0
    assert line["speedup_vs_sparsity"]["splash_window"] == 1.0
    assert line["masks"]["splash_window"]["attention_mask"] \
        == "causal&window(4096)"
    assert line["band_disjoint_win"] is True
    from dlnetbench_tpu.sentinel import is_ms_line
    assert is_ms_line(line)
    # an overlapping-band "win" is not band-disjoint
    summaries2 = dict(summaries)
    summaries2["splash_window"] = {"value": 0.0195, "best": 0.019,
                                   "band": [0.019, 0.020], "n": 3}
    line2 = bench._longcontext_line(summaries2, rounds, metric="m",
                                    mask_info=mask_info)
    assert line2["band_disjoint_win"] is False


def test_kv_density_line_schema_locked():
    """bench.py's kv_density_ab aux line (ISSUE 12) is a pure
    assembler: lock the stat-band schema — ms headline from the DENSE
    engine's round-median e2e p99 (lower-is-better, sentinel-
    comparable), per-variant {value, best, band, n} sub-objects for
    admitted slots / tokens-per-s / goodput-at-SLO, capacity ratios
    and the per-recipe parity bars."""
    import bench

    def srv(p99, adm, tps, grps):
        return {"e2e_ms": {"p99": p99}, "tokens_per_s": tps,
                "goodput_frac": 1.0, "goodput_rps": grps,
                "admitted_concurrency_peak": adm,
                "kv_cache": {"num_pages": 25 if adm < 10 else 96,
                             "pool_bytes": 102400}}
    rounds = {
        "bf16": [srv(90.0, 7, 3000.0, 200.0), srv(95.0, 7, 2900.0,
                                                  195.0),
                 srv(92.0, 7, 3100.0, 205.0)],
        "int8": [srv(55.0, 20, 5000.0, 350.0), srv(58.0, 20, 5200.0,
                                                   360.0),
                 srv(56.0, 20, 5100.0, 355.0)],
        "fp8": [srv(100.0, 20, 2900.0, 190.0), srv(105.0, 20, 2800.0,
                                                   185.0),
                srv(102.0, 20, 2850.0, 188.0)],
    }
    parity = {"int8": [0.01, 0.012, 0.011], "fp8": [0.07, 0.08, 0.075]}
    line = bench._kv_density_line(rounds, parity, 102400, suffix=", t")
    assert line["unit"] == "ms" and line["n"] == 3
    assert line["value"] == 92.0 and line["band"] == [90.0, 95.0]
    assert line["pool_bytes_budget"] == 102400
    for name in ("bf16", "int8", "fp8"):
        v = line["variants"][name]
        for key in ("admitted_slots", "tokens_per_s", "e2e_p99_ms",
                    "goodput_frac", "goodput_rps"):
            for k in ("value", "best", "band", "n"):
                assert k in v[key], (name, key, k)
        assert v["num_pages"] in (25, 96) and v["pool_bytes"] == 102400
    i8 = line["variants"]["int8"]
    assert i8["capacity_x"]["value"] == pytest.approx(20 / 7, rel=1e-3)
    assert i8["parity_tol"] == 0.05 and i8["parity_ok"] is True
    assert i8["parity_max_err"]["value"] == 0.011
    # dense carries NO parity keys (it IS the reference)
    assert "parity_ok" not in line["variants"]["bf16"]
    # a parity excursion past the stated bar flips the verdict
    bad = bench._kv_density_line(
        rounds, {"int8": [0.2, 0.2, 0.2], "fp8": parity["fp8"]},
        102400)
    assert bad["variants"]["int8"]["parity_ok"] is False
    from dlnetbench_tpu.sentinel import is_ms_line
    assert is_ms_line(line)


def test_moe_ab_line_schema_locked():
    """bench.py's dense-FFN-vs-MoE A/B line (ISSUE 15): the headline
    ``value`` is the sparse-MoE median ms with {value, best, band, n},
    every variant a sub-object, the MoE variants a paired per-round
    ratio band vs dense (at matched active params the ratio IS the
    routing/dispatch premium), band_disjoint the separation verdict,
    and the routing knobs + measured router stats riding as record
    globals."""
    import bench

    summaries = {
        "dense": {"value": 0.010, "best": 0.009,
                  "band": [0.009, 0.011], "n": 3},
        "moe": {"value": 0.015, "best": 0.014,
                "band": [0.014, 0.016], "n": 3},
        "moe_grouped": {"value": 0.013, "best": 0.012,
                        "band": [0.012, 0.014], "n": 3},
    }
    rounds = {"dense": [0.009, 0.010, 0.011],
              "moe": [0.0135, 0.015, 0.0165],
              "moe_grouped": [0.0117, 0.013, 0.0143]}
    moe_info = {"moe_experts": 8, "moe_top_k": 2,
                "moe_capacity_factor": 1.25, "moe_drop_seed": None,
                "moe_group_tokens": 0,
                "moe": {"expert_load": [0.125] * 8,
                        "load_imbalance": 1.0, "drop_rate": 0.0,
                        "router_entropy": 1.0}}
    active = {"dense_ffn_params": 100, "moe_active_ffn_params": 100,
              "moe_total_ffn_params": 400, "router_params": 8}
    line = bench._moe_ab_line(summaries, rounds, metric="moe A/B: t",
                              moe_info=moe_info, active_params=active)
    assert line["unit"] == "ms" and line["value"] == 15.0
    assert line["band"] == [14.0, 16.0] and line["n"] == 3
    for sub in ("dense_ms", "moe_ms", "moe_grouped_ms"):
        for k in ("value", "best", "band", "n"):
            assert k in line[sub], (sub, k)
    r = line["ratio_moe_vs_dense"]
    assert r["n"] == 3 and r["value"] == 1.5
    assert line["ratio_moe_grouped_vs_dense"]["value"] == 1.3
    assert line["band_disjoint"] is True
    # matched active params stated, knobs + measured stats ride along
    assert (line["active_params"]["dense_ffn_params"]
            == line["active_params"]["moe_active_ffn_params"])
    assert line["moe_experts"] == 8
    assert line["moe"]["load_imbalance"] == 1.0
    # overlapping bands flip the verdict
    s2 = dict(summaries)
    s2["dense"] = {"value": 0.0145, "best": 0.014,
                   "band": [0.014, 0.015], "n": 3}
    line2 = bench._moe_ab_line(s2, rounds, metric="m",
                               moe_info=moe_info, active_params=active)
    assert line2["band_disjoint"] is False
    # sentinel comparability: --check picks it up as "moe_ab"
    from dlnetbench_tpu.sentinel import is_ms_line
    assert is_ms_line(line)


def test_sampling_ab_line_schema_locked():
    """bench.py's sampling_ab aux line (ISSUE 19): the headline
    ``value`` is the SPECULATIVE-sampled arm's round-median e2e p99 in
    ms (sentinel-comparable; the bench headline stays greedy), both
    arms ship {value, best, band, n} bands for e2e p99 / TPOT p50 /
    tokens/s, the spec arm adds its measured acceptance-rate band, the
    verdict is the band-disjoint tokens/s gain, and token_identity
    locks the classic-vs-fused sampled bit-identity."""
    import bench

    def _round(p99, tps, *, acc=None):
        r = {"e2e_ms": {"p99": p99}, "tpot_ms": {"p50": 1.0},
             "tokens_per_s": tps}
        if acc is not None:
            r["decode_loop"] = {"spec": {"acceptance_rate": acc}}
        return r

    sampled = [_round(50.0, 100.0), _round(52.0, 95.0),
               _round(51.0, 98.0)]
    spec = [_round(30.0, 150.0, acc=0.5), _round(32.0, 145.0, acc=0.55),
            _round(31.0, 148.0, acc=0.5)]
    line = bench._sampling_ab_line(sampled, spec, suffix=", test",
                                   token_identity=True)
    assert line["unit"] == "ms"
    assert line["value"] == 31.0 and line["n"] == 3
    assert line["band"] == [30.0, 32.0] and line["best"] == 30.0
    for arm in ("sampled", "spec_sampled"):
        for key in ("e2e_p99_ms", "tpot_p50_ms", "tokens_per_s"):
            sub = line[arm][key]
            for k in ("value", "best", "band", "n"):
                assert k in sub, (arm, key, k)
    acc = line["spec_sampled"]["acceptance_rate"]
    assert acc["value"] == 0.5 and acc["n"] == 3
    # tokens/s bands [95, 100] vs [145, 150]: disjoint AND higher —
    # the ISSUE-19 speculation-under-sampling verdict
    assert line["tokens_per_s_band_disjoint_gain"] is True
    assert line["token_identity"] is True
    # overlapping bands must NOT claim the win
    flat = bench._sampling_ab_line(sampled, [
        _round(50.0, 99.0, acc=0.2), _round(51.0, 101.0, acc=0.2),
        _round(50.5, 100.0, acc=0.2)])
    assert flat["tokens_per_s_band_disjoint_gain"] is False
    assert "token_identity" not in flat
    # sentinel comparability: an ms line, auto-compared by --check
    from dlnetbench_tpu.sentinel import is_ms_line
    assert is_ms_line(line)
