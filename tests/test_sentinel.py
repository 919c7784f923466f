"""Bench regression sentinel (dlnetbench_tpu/sentinel.py + bench.py
--check): stat-band-aware artifact comparison — a regression needs BOTH
a median shift past the threshold AND disjoint bands, the attribution
delta names the resource that moved, and the exit code carries the
verdict to CI.

The integration lane (``-m sentinel``, mirrored by ``make check-bench``)
runs the REAL bench.py pipeline on a tiny CPU config: baseline capture,
a clean re-run that must stay quiet, and a deterministically injected
+10% slowdown (the faults delay injector) that must trip.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from dlnetbench_tpu import sentinel

REPO = Path(__file__).parent.parent


def _line(value, band=None, **extra):
    d = {"metric": "m", "unit": "ms", "value": value}
    if band is not None:
        d["band"] = band
    d.update(extra)
    return d


# ---------------------------------------------------------------------
# bench_lines: headline + aux extraction from every artifact shape


def test_bench_lines_driver_artifact(tmp_path):
    aux = _line(2.0, [1.9, 2.1])
    head = _line(10.0, [9.8, 10.2], fp8_mlp=aux, other="not a line")
    p = tmp_path / "BENCH_r01.json"
    p.write_text(json.dumps({"parsed": head, "tail": ""}))
    lines = sentinel.bench_lines(p)
    assert lines["headline"]["value"] == 10.0
    assert lines["fp8_mlp"]["value"] == 2.0
    assert set(lines) == {"headline", "fp8_mlp"}


def test_bench_lines_tail_fallback_and_jsonl(tmp_path):
    # driver artifact whose parsed is null (failed parse): last ms line
    # of the tail wins
    p = tmp_path / "a.json"
    tail = "\n".join(["noise", json.dumps(_line(1.0)),
                      json.dumps(_line(5.0))])
    p.write_text(json.dumps({"parsed": None, "tail": tail}))
    assert sentinel.bench_lines(p)["headline"]["value"] == 5.0
    # bench stdout JSONL: last ms line is the headline
    q = tmp_path / "b.jsonl"
    q.write_text("warmup noise\n" + json.dumps(_line(3.0)) + "\n"
                 + json.dumps(_line(7.0)) + "\n")
    assert sentinel.bench_lines(q)["headline"]["value"] == 7.0


def test_bench_lines_empty_artifact(tmp_path):
    p = tmp_path / "dead.json"
    p.write_text(json.dumps({"parsed": None, "tail": "rc=1 boom"}))
    assert sentinel.bench_lines(p) == {}


# ---------------------------------------------------------------------
# compare_line: the two-signal regression definition


def test_regression_needs_shift_and_disjoint_bands():
    base = _line(10.0, [9.9, 10.1])
    # +20% with disjoint bands: regression
    r = sentinel.compare_line("headline", base, _line(12.0, [11.9, 12.1]))
    assert r["regression"] and not r["improvement"]
    assert r["bands_overlap"] is False
    # +20% but bands OVERLAP: run-to-run noise, not a regression
    r = sentinel.compare_line("headline", base, _line(12.0, [10.0, 12.5]))
    assert not r["regression"]
    assert r["bands_overlap"] is True
    # disjoint bands but under the threshold: too small to fail a build
    r = sentinel.compare_line("headline", base, _line(10.3, [10.25, 10.35]))
    assert not r["regression"]
    # -20% disjoint: improvement, never a failure
    r = sentinel.compare_line("headline", base, _line(8.0, [7.9, 8.1]))
    assert r["improvement"] and not r["regression"]


def test_bandless_lines_fall_back_to_threshold():
    r = sentinel.compare_line("headline", _line(10.0), _line(12.0))
    assert r["bands_overlap"] is None
    assert r["regression"]
    assert not sentinel.compare_line("headline", _line(10.0),
                                     _line(10.2))["regression"]


def test_compare_line_threshold_configurable():
    base = _line(10.0, [9.9, 10.1])
    cur = _line(10.8, [10.7, 10.9])   # +8%, disjoint
    assert sentinel.compare_line("h", base, cur, 5.0)["regression"]
    assert not sentinel.compare_line("h", base, cur, 10.0)["regression"]


def test_resource_moved_names_the_mover():
    """The attribution delta: per-resource wall-clock differenced, the
    largest increase named — 'comm grew 3 ms', not just 'slower'."""
    def attributed(value, fractions):
        return _line(value, [value - 0.1, value + 0.1],
                     attribution={"fractions": fractions, "bound": "mxu"})
    base = attributed(10.0, {"compute": 0.8, "hbm": 0.0,
                             "comm_exposed": 0.1, "host": 0.1})
    cur = attributed(13.0, {"compute": 0.62, "hbm": 0.0,
                            "comm_exposed": 0.3, "host": 0.08})
    r = sentinel.compare_line("headline", base, cur)
    assert r["regression"]
    assert r["resource_moved"] == "comm_exposed"
    # 0.3*13 - 0.1*10 = 2.9 ms of new exposed comm
    assert r["resource_delta_ms"] == pytest.approx(2.9, abs=0.01)


# ---------------------------------------------------------------------
# check / scan_dir


def test_check_verdicts():
    base = {"headline": _line(10.0, [9.9, 10.1]),
            "fp8": _line(2.0, [1.9, 2.1])}
    clean = sentinel.check(base, {"headline": _line(10.05, [9.95, 10.15]),
                                  "fp8": _line(2.0, [1.9, 2.1])})
    assert clean["verdict"] == "clean" and clean["regressions"] == []
    bad = sentinel.check(base, {"headline": _line(10.0, [9.9, 10.1]),
                                "fp8": _line(3.0, [2.9, 3.1])})
    assert bad["verdict"] == "regression"
    assert bad["regressions"] == ["fp8"]
    # baseline without a headline: nothing to regress against
    none = sentinel.check({}, {"headline": _line(1.0)})
    assert none["verdict"] == "no-baseline"


def test_check_surfaces_vanished_baseline_lines():
    # a baseline aux line absent from the current run is reported in
    # `missing` (not silently dropped), but does not fail the check —
    # --skip-aux / off-TPU runs legitimately drop aux lines
    base = {"headline": _line(10.0, [9.9, 10.1]),
            "fp8": _line(2.0, [1.9, 2.1])}
    sent = sentinel.check(base, {"headline": _line(10.0, [9.9, 10.1])})
    assert sent["missing"] == ["fp8"]
    assert sent["verdict"] == "clean"
    full = sentinel.check(base, {"headline": _line(10.0, [9.9, 10.1]),
                                 "fp8": _line(2.0, [1.9, 2.1])})
    assert full["missing"] == []


@pytest.mark.sentinel
def test_serving_latency_line_is_comparable():
    """The serving_decode aux line (ISSUE 8) rides the headline like
    every ms line, and the sentinel judges it with the same
    lower-is-better, band-aware semantics: a p99 median that worsens
    past threshold with disjoint bands is a regression; a
    band-overlapping shift is noise."""
    def serving_line(value, band):
        return {"metric": "serving_decode: paged-KV decode e2e p99",
                "value": value, "unit": "ms", "best": band[0],
                "band": band, "n": 3,
                "ttft_p50_ms": {"value": 2.0, "best": 1.9,
                                "band": [1.9, 2.1], "n": 3}}

    base = {"headline": _line(10.0, [9.9, 10.1]),
            "serving_decode": serving_line(20.0, [19.5, 20.5])}
    # engine p99 doubles with disjoint bands while the headline holds:
    # the serving line alone must trip the verdict
    cur = {"headline": _line(10.0, [9.9, 10.1]),
           "serving_decode": serving_line(40.0, [39.0, 41.0])}
    sent = sentinel.check(base, cur)
    assert sent["verdict"] == "regression"
    assert sent["regressions"] == ["serving_decode"]
    # band-overlapping latency wobble is noise, not a regression
    ok = sentinel.check(base, {
        "headline": _line(10.0, [9.9, 10.1]),
        "serving_decode": serving_line(22.0, [19.0, 24.0])})
    assert ok["verdict"] == "clean"
    # faster p99 with disjoint bands reads as an improvement
    fast = sentinel.check(base, {
        "headline": _line(10.0, [9.9, 10.1]),
        "serving_decode": serving_line(12.0, [11.5, 12.5])})
    assert fast["improvements"] == ["serving_decode"]


@pytest.mark.sentinel
def test_decode_ab_line_is_comparable():
    """The ISSUE 11 A/B extensions (multi_step/speculative sub-blocks,
    attribution_flip, token_parity) ride INSIDE the serving_decode ms
    line: the sentinel still compares it by its headline e2e p99 with
    the same lower-is-better band-aware semantics, and the nested A/B
    blocks never confuse the comparison."""
    def ab_line(value, band):
        return {"metric": "serving_decode: ... vs fused N=16 vs "
                          "N=16+spec, cpu",
                "value": value, "unit": "ms", "best": band[0],
                "band": band, "n": 3,
                "multi_step": {"tokens_per_s": {"value": 8000.0},
                               "multi_step_n": 16},
                "speculative": {"tokens_per_s": {"value": 9000.0}},
                "attribution_flip": {"band_disjoint_drop": True},
                "token_parity": True}

    base = {"headline": _line(10.0, [9.9, 10.1]),
            "serving_decode": ab_line(20.0, [19.5, 20.5])}
    worse = sentinel.check(base, {
        "headline": _line(10.0, [9.9, 10.1]),
        "serving_decode": ab_line(40.0, [39.0, 41.0])})
    assert worse["verdict"] == "regression"
    assert worse["regressions"] == ["serving_decode"]
    noise = sentinel.check(base, {
        "headline": _line(10.0, [9.9, 10.1]),
        "serving_decode": ab_line(22.0, [19.0, 24.0])})
    assert noise["verdict"] == "clean"
    # an OLD baseline without the A/B blocks still compares: the
    # extensions are additive, the ms-line contract is the interface
    old = {"headline": _line(10.0, [9.9, 10.1]),
           "serving_decode": {"metric": "serving_decode: paged-KV",
                              "value": 20.0, "unit": "ms",
                              "best": 19.5, "band": [19.5, 20.5],
                              "n": 3}}
    sent = sentinel.check(old, {
        "headline": _line(10.0, [9.9, 10.1]),
        "serving_decode": ab_line(41.0, [40.0, 42.0])})
    assert sent["verdict"] == "regression"


def _artifact(path, value, band):
    head = _line(value, band)
    path.write_text(json.dumps({"parsed": head, "tail": ""}))


def test_scan_dir_skips_dead_artifacts_and_flags_latest(tmp_path, capsys):
    _artifact(tmp_path / "BENCH_r01.json", 10.0, [9.9, 10.1])
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps({"parsed": None, "tail": "rc=1"}))  # failed capture
    _artifact(tmp_path / "BENCH_r03.json", 12.0, [11.9, 12.1])
    rc = sentinel.scan_dir(tmp_path)
    out = capsys.readouterr().out
    # r02 skipped with a note; r03 compared against r01, not blinded
    assert "BENCH_r02.json — no comparable headline" in out
    assert "baseline " + str(tmp_path / "BENCH_r01.json") in out
    assert rc == sentinel.RC_REGRESSION


def test_scan_dir_dead_latest_artifact_disarms_loudly(tmp_path, capsys):
    """A dead LATEST capture must not ride an older clean verdict to
    rc 0: the newest round is the one CI asked about, and a tripwire
    that silently disarms is worse than no tripwire."""
    _artifact(tmp_path / "BENCH_r01.json", 10.0, [9.9, 10.1])
    _artifact(tmp_path / "BENCH_r02.json", 10.05, [9.95, 10.15])
    (tmp_path / "BENCH_r03.json").write_text(
        json.dumps({"parsed": None, "tail": "rc=1"}))  # bench.py died
    rc = sentinel.scan_dir(tmp_path)
    out = capsys.readouterr().out
    assert rc == 2
    assert "LATEST artifact has no comparable headline" in out


def test_scan_dir_clean_and_underpopulated(tmp_path, capsys):
    assert sentinel.scan_dir(tmp_path) == 2    # nothing to compare
    _artifact(tmp_path / "BENCH_r01.json", 10.0, [9.9, 10.1])
    _artifact(tmp_path / "BENCH_r02.json", 10.1, [9.95, 10.2])
    assert sentinel.scan_dir(tmp_path) == 0
    capsys.readouterr()


def test_main_baseline_pair(tmp_path, capsys):
    _artifact(tmp_path / "a.json", 10.0, [9.9, 10.1])
    _artifact(tmp_path / "b.json", 14.0, [13.9, 14.1])
    rc = sentinel.main([str(tmp_path / "b.json"),
                        "--baseline", str(tmp_path / "a.json")])
    assert rc == sentinel.RC_REGRESSION
    out = capsys.readouterr().out
    assert "REGRESSION" in out
    # the machine-readable sentinel section rides stdout too
    sent = json.loads(out.strip().splitlines()[-1])["sentinel"]
    assert sent["verdict"] == "regression"
    assert sentinel.main([str(tmp_path / "a.json"),
                          "--baseline", str(tmp_path / "a.json")]) == 0
    capsys.readouterr()


# ---------------------------------------------------------------------
# the integration lane: REAL bench.py runs on a tiny CPU config
# (mirrored by `make check-bench`)

TINY_ENV = {
    "JAX_PLATFORMS": "cpu",
    "DLNB_BENCH_BATCH": "2", "DLNB_BENCH_SEQ": "256",
    "DLNB_BENCH_LAYERS": "1", "DLNB_BENCH_VOCAB": "512",
    "DLNB_BENCH_EMBED": "256", "DLNB_BENCH_FF": "1024",
    "DLNB_BENCH_HEADS": "4",
    # K=8 chained steps per fence: amortizes dispatch jitter so the
    # 3-round band is tight enough for a 10% shift to land outside it
    "DLNB_BENCH_K": "8",
}


def _run_bench(tmp_path, out_name, *extra, cache_dir=None):
    env = {**os.environ, **TINY_ENV}
    if cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    out = tmp_path / out_name
    with open(out, "w") as f:
        proc = subprocess.run(
            [sys.executable, str(REPO / "bench.py"), "--skip-aux", *extra],
            stdout=f, stderr=subprocess.PIPE, env=env, cwd=REPO,
            timeout=600, text=True)
    return proc, out


def _headline(path):
    lines = [json.loads(ln) for ln in path.read_text().splitlines()
             if ln.strip().startswith("{")]
    return lines[-1]


@pytest.mark.slow
@pytest.mark.sentinel
def test_bench_check_lane(tmp_path):
    """The CI tripwire, end to end: a clean re-run stays quiet (exit 0,
    verdict in the artifact), an injected +10% slowdown exits non-zero
    and names the regression."""
    cache = tmp_path / "cache"

    # 1. baseline capture
    proc, base = _run_bench(tmp_path, "baseline.jsonl", cache_dir=cache)
    assert proc.returncode == 0, proc.stderr
    base_head = _headline(base)
    # the CPU was asked for by name: the line says so, and carries no
    # roofline ratio and no attribution against a TPU's peaks
    assert base_head["metric"].endswith("(cpu)"), base_head["metric"]
    assert not any(k.startswith("vs_baseline") for k in base_head)
    assert "attribution" not in base_head

    # 2. clean re-run under --check: must stay quiet.  CPU wall-clock
    # on a shared box can genuinely drift between invocations — that is
    # exactly the shift the bands exist to absorb, but a scheduler
    # outlier round can defeat them; one bounded retry with a fresh
    # baseline keeps the lane honest without making it flaky.
    for attempt in range(2):
        proc, clean = _run_bench(tmp_path, "clean.jsonl",
                                 "--check", str(base), cache_dir=cache)
        if proc.returncode == 0 or attempt == 1:
            break
        proc2, base = _run_bench(tmp_path, "baseline.jsonl",
                                 cache_dir=cache)
        assert proc2.returncode == 0, proc2.stderr
    assert proc.returncode == 0, (proc.stderr, _headline(clean))
    sent = _headline(clean)["sentinel"]
    assert sent["verdict"] in ("clean", "no-baseline")
    assert sent["verdict"] == "clean", sent   # headline was comparable
    assert sent["baseline"] == str(base)

    # 3. deterministically injected headline slowdown: the faults delay
    # injector sleeps inside the timed window, once per chained step.
    # The injection floor is +10% of the baseline median (the acceptance
    # contract); on a noisy box the baseline's own band width is added
    # so the faulted band lands OUTSIDE it — the band veto exists to
    # absorb exactly that noise, and an injection the bands could
    # swallow would be testing the scheduler, not the sentinel.
    for attempt in range(2):
        bh = _headline(base)
        base_ms = float(bh["value"])
        band = bh.get("band") or [base_ms, base_ms]
        width_ms = float(band[1]) - float(band[0])
        delay_ms = (0.10 * base_ms + width_ms if attempt == 0
                    else 0.25 * base_ms + 2 * width_ms)
        plan = json.dumps({"policy": "fail_fast", "events": [
            {"kind": "delay", "iteration": 0,
             "magnitude_us": round(delay_ms * 1e3)}]})
        proc, faulted = _run_bench(tmp_path, "faulted.jsonl",
                                   "--check", str(base), "--fault", plan,
                                   cache_dir=cache)
        if proc.returncode == sentinel.RC_REGRESSION:
            break
        if attempt == 0:
            # a baseline captured on a transiently loaded box can sit
            # so far ABOVE the settled step time that even the bigger
            # injection can't reach it — refresh the baseline (and the
            # delay derived from it) before the second attempt
            proc2, base = _run_bench(tmp_path, "baseline.jsonl",
                                     cache_dir=cache)
            assert proc2.returncode == 0, proc2.stderr
    assert proc.returncode == sentinel.RC_REGRESSION, (
        proc.returncode, proc.stderr, _headline(faulted))
    head = _headline(faulted)
    assert head["sentinel"]["verdict"] == "regression"
    assert "headline" in head["sentinel"]["regressions"]
    # the faulted artifact can never pass as a clean measurement
    assert head["fault_plan"]["events"][0]["kind"] == "delay"
    assert float(head["value"]) > base_ms


@pytest.mark.sentinel
def test_tuned_ab_line_is_comparable():
    """The tuned_ab aux line (ISSUE 9) rides the headline like every ms
    line and the sentinel judges it band-aware lower-is-better: a tuned
    chain that got slower past threshold with disjoint bands is a
    regression; band-overlapping wobble is noise."""
    def tuned_line(value, band):
        return {"metric": "tuned A/B: fp8 fused swiglu, DB-tuned vs "
                          "frozen", "value": value, "unit": "ms",
                "best": band[0], "band": band, "n": 3,
                "frozen_ms": {"value": 2 * value, "best": 2 * band[0],
                              "band": [2 * b for b in band], "n": 3}}

    assert sentinel.is_ms_line(tuned_line(10.0, [9.5, 10.5]))
    base = {"headline": _line(10.0, [9.9, 10.1]),
            "tuned_ab": tuned_line(10.0, [9.5, 10.5])}
    cur = {"headline": _line(10.0, [9.9, 10.1]),
           "tuned_ab": tuned_line(20.0, [19.5, 20.5])}
    sent = sentinel.check(base, cur)
    assert sent["verdict"] == "regression"
    assert sent["regressions"] == ["tuned_ab"]
    ok = sentinel.check(base, {
        "headline": _line(10.0, [9.9, 10.1]),
        "tuned_ab": tuned_line(10.3, [9.8, 10.8])})
    assert ok["verdict"] == "clean"


def test_longcontext_line_is_comparable():
    """The longcontext_ab aux line (ISSUE 10) rides the headline like
    every ms line and the sentinel judges it band-aware
    lower-is-better: a splash chain that got slower past threshold
    with disjoint bands is a regression; band-overlapping wobble is
    noise."""
    def lc_line(value, band):
        return {"metric": "longcontext A/B: dense vs splash",
                "value": value, "unit": "ms",
                "best": band[0], "band": band, "n": 3,
                "dense": {"value": 4 * value, "best": 4 * band[0],
                          "band": [4 * b for b in band], "n": 3},
                "masks": {"splash_window": {
                    "attention_mask": "causal&window(4096)",
                    "mask_sparsity": 0.94}}}

    assert sentinel.is_ms_line(lc_line(10.0, [9.5, 10.5]))
    base = {"headline": _line(10.0, [9.9, 10.1]),
            "longcontext_ab": lc_line(10.0, [9.5, 10.5])}
    cur = {"headline": _line(10.0, [9.9, 10.1]),
           "longcontext_ab": lc_line(20.0, [19.5, 20.5])}
    sent = sentinel.check(base, cur)
    assert sent["verdict"] == "regression"
    assert sent["regressions"] == ["longcontext_ab"]
    ok = sentinel.check(base, {
        "headline": _line(10.0, [9.9, 10.1]),
        "longcontext_ab": lc_line(10.3, [9.8, 10.8])})
    assert ok["verdict"] == "clean"


@pytest.mark.sentinel
def test_kv_density_line_is_comparable():
    """The kv_density_ab aux line (ISSUE 12) rides the headline like
    every ms line: the sentinel compares it by the dense engine's e2e
    p99, band-aware lower-is-better, and the nested per-variant
    capacity/parity blocks never confuse the comparison."""
    def density_line(value, band):
        return {"metric": "kv_density_ab: dense vs int8 vs fp8",
                "value": value, "unit": "ms", "best": band[0],
                "band": band, "n": 3,
                "variants": {"int8": {
                    "capacity_x": {"value": 2.9, "band": [2.8, 3.0]},
                    "parity_ok": True}}}

    base = {"headline": _line(10.0, [9.9, 10.1]),
            "kv_density_ab": density_line(90.0, [88.0, 92.0])}
    cur = {"headline": _line(10.0, [9.9, 10.1]),
           "kv_density_ab": density_line(180.0, [176.0, 184.0])}
    sent = sentinel.check(base, cur)
    assert sent["verdict"] == "regression"
    assert sent["regressions"] == ["kv_density_ab"]
    ok = sentinel.check(base, {
        "headline": _line(10.0, [9.9, 10.1]),
        "kv_density_ab": density_line(93.0, [87.0, 96.0])})
    assert ok["verdict"] == "clean"


def test_moe_ab_line_is_comparable():
    """The moe_ab aux line (ISSUE 15) rides the headline like every ms
    line and the sentinel judges it band-aware lower-is-better: a MoE
    step that got slower past threshold with disjoint bands is a
    regression; band-overlapping wobble is noise; old baselines
    without the line still compare clean."""
    def moe_line(value, band):
        return {"metric": "moe A/B: dense FFN vs 8-expert MoE",
                "value": value, "unit": "ms", "best": band[0],
                "band": band, "n": 3,
                "dense_ms": {"value": value / 1.5,
                             "best": band[0] / 1.5,
                             "band": [b / 1.5 for b in band], "n": 3}}

    assert sentinel.is_ms_line(moe_line(15.0, [14.0, 16.0]))
    base = {"headline": _line(10.0, [9.9, 10.1]),
            "moe_ab": moe_line(15.0, [14.0, 16.0])}
    cur = {"headline": _line(10.0, [9.9, 10.1]),
           "moe_ab": moe_line(30.0, [29.0, 31.0])}
    sent = sentinel.check(base, cur)
    assert sent["verdict"] == "regression"
    assert sent["regressions"] == ["moe_ab"]
    ok = sentinel.check(base, {
        "headline": _line(10.0, [9.9, 10.1]),
        "moe_ab": moe_line(15.5, [14.4, 16.5])})
    assert ok["verdict"] == "clean"
    # a baseline predating the line compares clean (new line ignored)
    old = sentinel.check({"headline": _line(10.0, [9.9, 10.1])},
                         cur)
    assert old["verdict"] == "clean"


@pytest.mark.sentinel
def test_fleet_ab_line_is_comparable():
    """The fleet_ab aux line (ISSUE 18) rides the headline like every
    ms line: the sentinel compares it by the prefix_affinity arm's
    TTFT p50, band-aware lower-is-better, and the nested per-policy
    bands never confuse the comparison."""
    def fleet_line(value, band):
        return {"metric": "fleet_ab: round_robin vs p2c vs "
                          "prefix_affinity routing at equal chips",
                "value": value, "unit": "ms", "best": band[0],
                "band": band, "n": 3,
                "round_robin": {"ttft_p50_ms": {
                    "value": value * 1.5, "best": band[0] * 1.5,
                    "band": [b * 1.5 for b in band], "n": 3}},
                "ttft_band_disjoint_drop": True}

    assert sentinel.is_ms_line(fleet_line(5.0, [4.5, 5.5]))
    base = {"headline": _line(10.0, [9.9, 10.1]),
            "fleet_ab": fleet_line(5.0, [4.5, 5.5])}
    cur = {"headline": _line(10.0, [9.9, 10.1]),
           "fleet_ab": fleet_line(10.0, [9.5, 10.5])}
    sent = sentinel.check(base, cur)
    assert sent["verdict"] == "regression"
    assert sent["regressions"] == ["fleet_ab"]
    ok = sentinel.check(base, {
        "headline": _line(10.0, [9.9, 10.1]),
        "fleet_ab": fleet_line(5.2, [4.6, 5.6])})
    assert ok["verdict"] == "clean"
    # a baseline predating the line compares clean (new line ignored)
    old = sentinel.check({"headline": _line(10.0, [9.9, 10.1])}, cur)
    assert old["verdict"] == "clean"


def test_sampling_ab_line_is_comparable():
    """The sampling_ab aux line (ISSUE 19) rides the headline like
    every ms line: the sentinel compares it by the speculative-sampled
    arm's e2e p99, band-aware lower-is-better, and the nested per-arm
    bands never confuse the comparison."""
    def sampling_line(value, band):
        return {"metric": "sampling_ab: seeded sampling T=0.8 — fused "
                          "decode vs lossless speculative sampling",
                "value": value, "unit": "ms", "best": band[0],
                "band": band, "n": 3,
                "sampled": {"tokens_per_s": {
                    "value": value * 2.0, "best": band[0] * 2.0,
                    "band": [b * 2.0 for b in band], "n": 3}},
                "tokens_per_s_band_disjoint_gain": True}

    assert sentinel.is_ms_line(sampling_line(5.0, [4.5, 5.5]))
    base = {"headline": _line(10.0, [9.9, 10.1]),
            "sampling_ab": sampling_line(5.0, [4.5, 5.5])}
    cur = {"headline": _line(10.0, [9.9, 10.1]),
           "sampling_ab": sampling_line(10.0, [9.5, 10.5])}
    sent = sentinel.check(base, cur)
    assert sent["verdict"] == "regression"
    assert sent["regressions"] == ["sampling_ab"]
    ok = sentinel.check(base, {
        "headline": _line(10.0, [9.9, 10.1]),
        "sampling_ab": sampling_line(5.2, [4.6, 5.6])})
    assert ok["verdict"] == "clean"
    # a baseline predating the line compares clean (new line ignored)
    old = sentinel.check({"headline": _line(10.0, [9.9, 10.1])}, cur)
    assert old["verdict"] == "clean"
