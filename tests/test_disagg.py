"""Disaggregated prefill/decode serving (ISSUE 16): the migration
channel's bit-exact quantized wire, the closed-form byte accounting,
the overlap-leg discipline, the config guards, the adaptive-N ETA cap,
token parity against the monolithic engine per cache dtype, fault
composition (a prefill-replica crash under shrink), and the committed
two-replica record fixture's round trip."""
from __future__ import annotations

import copy
import dataclasses
import json
import math
import time
from collections import deque
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlnetbench_tpu.metrics import telemetry
from dlnetbench_tpu.models import transformer as tfm
from dlnetbench_tpu.serving.arrivals import ArrivalPlan, Request
from dlnetbench_tpu.serving.kv_cache import CacheConfig, device_buffers
from dlnetbench_tpu.serving.page_migration import (MigrationChannel,
                                                   bf16_equiv_page_bytes)
from dlnetbench_tpu.serving.scheduler import (Engine, ServingConfig,
                                              _SlotState)

DATA = Path(__file__).parent / "data"

pytestmark = [pytest.mark.serving, pytest.mark.disagg]


@pytest.fixture(autouse=True)
def _clean_recorder():
    """Never leak an enabled recorder into (or out of) a test."""
    telemetry.disable()
    yield
    telemetry.disable()


def tiny_model(**over) -> tfm.TransformerConfig:
    kw = dict(vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2,
              ff_dim=64, num_layers=2, seq_len=32, gated=True,
              max_positions=0, dtype="float32")
    kw.update(over)
    return tfm.TransformerConfig(**kw)


def disagg_serving(**over) -> ServingConfig:
    # page_size=8 so the int8 wire's scale overhead amortizes below the
    # 0.55x bar: bytes ratio = (S*Dh + 4) / (2*S*Dh) per page
    kw = dict(slots=4, page_size=8, num_pages=16, max_seq_len=32,
              slo_ttft_ms=200.0, slo_tpot_ms=100.0, world=2,
              disaggregate=True, prefill_ranks=1, decode_ranks=1,
              multi_step_n=4, adaptive_n=True, warmup_requests=0)
    kw.update(over)
    return ServingConfig(**kw)


def chan_cache(**over) -> CacheConfig:
    kw = dict(num_layers=2, num_kv_heads=2, head_dim=16, num_pages=16,
              page_size=8, max_seqs=2, max_pages_per_seq=4,
              cache_dtype="int8")
    kw.update(over)
    return CacheConfig(**kw).validate()


def _fill(pool, rng):
    """Random content in the pool's STORED dtype (int8 pools get the
    full signed range; float pools get gaussian values cast down)."""
    if pool.dtype == jnp.int8:
        return jnp.asarray(
            rng.randint(-127, 128, pool.shape).astype(np.int8))
    return jnp.asarray(rng.randn(*pool.shape).astype(np.float32),
                       pool.dtype)


# ---------------------------------------------------------------------
# the migration channel: bit-exact payload, closed-form bytes, overlap


@pytest.mark.parametrize("cache_dtype", ["bf16", "int8", "fp8"])
def test_migration_payload_bit_exact(cache_dtype):
    """send -> scatter moves pages (+ scales) in the STORED dtype and
    lands them bit-identical at the destination page ids — the
    token-parity bar's transport half, per cache dtype."""
    cfg = chan_cache(cache_dtype=cache_dtype)
    rng = np.random.RandomState(0)
    src = tuple(_fill(p, rng) for p in device_buffers(cfg))
    dst = device_buffers(cfg)
    ch = MigrationChannel(cfg, jax.devices()[1], chunk_pages=3)
    src_ids, dst_ids = [5, 1, 7, 2], [0, 3, 9, 11]
    pending = ch.send(src, src_ids, fence=True)
    out = ch.scatter(dst, pending, dst_ids)
    assert len(out) == len(src)
    for got, want in zip(out, src):
        assert got.dtype == want.dtype  # never widened to bf16
        g, w = np.asarray(got), np.asarray(want)
        for s, d in zip(src_ids, dst_ids):
            assert np.array_equal(g[:, :, d], w[:, :, s]), \
                (cache_dtype, s, d)
    # 4 pages through chunk_pages=3 is exactly two chunk transfers
    rec = ch._sends[0]
    assert rec.pages == 4 and rec.chunks == 2 and not rec.overlapped
    assert rec.bytes == 4 * cfg.page_bytes


def test_migration_bytes_closed_form():
    """migration_bytes is the pool algebra, not a transport guess:
    n * page_bytes with the per-page-per-head f32 scales INCLUDED, and
    the quantized wire prices under 0.55x of the bf16 equivalent at
    page_size=8 (the ISSUE 16 acceptance bar)."""
    cfg = chan_cache(cache_dtype="int8")
    ch = MigrationChannel(cfg, jax.devices()[1])
    payload = (2 * cfg.num_layers * cfg.num_kv_heads * cfg.page_size
               * cfg.head_dim)                      # int8: 1 B/elem
    scales = 2 * cfg.num_layers * cfg.num_kv_heads * 4
    assert cfg.page_bytes == payload + scales
    assert ch.bytes_for_pages(3) == 3 * cfg.page_bytes
    assert ch.bf16_equiv_bytes(3) == 3 * bf16_equiv_page_bytes(cfg) \
        == 3 * 2 * payload
    ratio = ch.bytes_for_pages(3) / ch.bf16_equiv_bytes(3)
    s_dh = cfg.page_size * cfg.head_dim
    assert ratio == pytest.approx((s_dh + 4) / (2 * s_dh))
    assert ratio <= 0.55


def test_migration_channel_refusals():
    cfg = chan_cache()
    with pytest.raises(ValueError, match="chunk_pages"):
        MigrationChannel(cfg, jax.devices()[1], chunk_pages=0)
    ch = MigrationChannel(cfg, jax.devices()[1])
    src = device_buffers(cfg)
    with pytest.raises(ValueError, match="empty page list"):
        ch.send(src, [])
    pending = ch.send(src, [0, 1], fence=True)
    with pytest.raises(ValueError, match="destination pages"):
        ch.scatter(device_buffers(cfg), pending, [4])


def test_migration_overlap_nan_unless_all_legs():
    """The overlap fraction exists only when comm-solo, compute-solo
    AND together legs were all measured — anything less emits NaN, and
    a channel that never carried a sequence has no stats block."""
    cfg = chan_cache()
    ch = MigrationChannel(cfg, jax.devices()[1])
    assert ch.stats_block() is None
    src = device_buffers(cfg)
    # an OVERLAPPED send alone is not a comm-solo leg
    p = ch.send(src, [0], fence=False, overlapped=True)
    assert p._record is None      # unfenced: not recorded yet
    r1 = p.wait()
    assert p.wait() is r1         # idempotent
    assert r1.overlapped
    ch.note_compute_solo(0.010)
    ch.note_both(0.012)
    assert math.isnan(ch.overlap())     # no fenced (solo) send yet
    ch.send(src, [1], fence=True)       # the comm-solo leg
    assert not math.isnan(ch.overlap())
    blk = ch.stats_block()
    assert blk["sends"] == 2 and blk["overlapped_sends"] == 1
    assert blk["pages"] == 2 and blk["bytes"] == 2 * cfg.page_bytes
    # missing legs -> NaN, not a fabricated number
    ch2 = MigrationChannel(cfg, jax.devices()[1])
    ch2.send(device_buffers(cfg), [0], fence=True)
    assert math.isnan(ch2.overlap())
    assert math.isnan(ch2.stats_block()["overlap"])


# ---------------------------------------------------------------------
# config guards


def test_disagg_config_refusals():
    with pytest.raises(ValueError, match="each phase is a replica"):
        disagg_serving(prefill_ranks=0, world=1).validate()
    with pytest.raises(ValueError, match="disjoint"):
        disagg_serving(world=4).validate()
    with pytest.raises(ValueError, match="divisible"):
        disagg_serving(slots=3, world=3, prefill_ranks=2).validate()
    with pytest.raises(ValueError, match="speculative"):
        disagg_serving(speculative=True).validate()
    with pytest.raises(ValueError, match="prefix_sharing"):
        disagg_serving(prefix_sharing=True).validate()
    with pytest.raises(ValueError, match="kv_shard"):
        disagg_serving(kv_shard=2).validate()
    with pytest.raises(ValueError, match="inline"):
        disagg_serving(prefill="inline").validate()
    with pytest.raises(ValueError, match="migration_chunk_pages"):
        disagg_serving(migration_chunk_pages=0).validate()
    # a disaggregated config drives TWO engines, never one
    with pytest.raises(ValueError, match="run_disagg"):
        Engine(tiny_model(), disagg_serving())
    # and the server refuses a monolithic config right back
    from dlnetbench_tpu.serving.disagg import DisaggServer
    with pytest.raises(ValueError, match="disaggregate=True"):
        DisaggServer(tiny_model(),
                     disagg_serving(disaggregate=False, world=1))


# ---------------------------------------------------------------------
# the adaptive-N migration-ETA cap (unit: no engine build needed)


def _bare_engine(cfg: ServingConfig) -> Engine:
    """_pick_n_steps touches only host-side scheduler state — build
    that state without compiling any programs."""
    eng = object.__new__(Engine)
    eng.cfg = cfg
    eng.pending = deque()
    eng.queue = deque()
    eng._t0 = time.monotonic()
    eng._step_ewma_s = 0.010
    eng._migration_eta_s = None
    st = _SlotState(Request(rid=0, arrival_s=0.0, prompt_len=8,
                            output_len=100), admitted_s=0.0)
    st.prefill_done = 8
    eng.slots = [st, None, None, None]
    return eng


def test_pick_n_steps_migration_eta_cap():
    cfg = ServingConfig(slots=4, page_size=8, num_pages=16,
                        max_seq_len=32, multi_step_n=8, adaptive_n=True)
    eng = _bare_engine(cfg)
    # None (every monolithic engine, always): bit-identical full N
    assert eng._pick_n_steps([0]) == 8
    # a handoff expected NOW caps the trip count to one device step
    eng._migration_eta_s = eng._now()
    assert eng._pick_n_steps([0]) == 1
    # an ETA a few step-EWMAs out caps to roughly that many trips
    eng._migration_eta_s = eng._now() + 2.5 * eng._step_ewma_s
    assert eng._pick_n_steps([0]) == 3
    # a far-future ETA leaves the full fused loop alone
    eng._migration_eta_s = eng._now() + 10.0
    assert eng._pick_n_steps([0]) == 8
    # non-adaptive engines ignore the ETA entirely
    eng2 = _bare_engine(dataclasses.replace(cfg, adaptive_n=False))
    eng2._migration_eta_s = eng2._now()
    assert eng2._pick_n_steps([0]) == 8


# ---------------------------------------------------------------------
# token parity vs the monolithic engine (the tentpole bar)


def _parity_streams(cache_dtype: str):
    mc = tiny_model()
    plan = ArrivalPlan(kind="poisson", rate_rps=200.0, num_requests=8,
                       seed=7, prompt_len=[4, 9], output_len=5)
    params = tfm.init_params(jax.random.PRNGKey(0), mc)
    mono_cfg = disagg_serving(disaggregate=False, world=2,
                              cache_dtype=cache_dtype)
    eng = Engine(mc, mono_cfg, params=params)
    eng.run(plan.sample())
    mono = {rid: list(t) for rid, t in eng.token_streams.items()}

    from dlnetbench_tpu.serving.disagg import DisaggServer
    srv = DisaggServer(mc, disagg_serving(cache_dtype=cache_dtype),
                       params=params)
    completed, _wall = srv.run(plan.sample())
    return mono, srv, completed


def test_token_parity_int8_and_wire_stays_quantized():
    """The quantized representative: disaggregated greedy output is
    token-identical to monolithic int8, TTFT is stamped for every
    completion (prefill-side), and the wire carried the stored-int8
    pages at <= 0.55x the bf16-equivalent bytes."""
    mono, srv, completed = _parity_streams("int8")
    assert srv.token_streams == mono
    assert len(completed) == 8
    assert all(c.first_token_s is not None
               and c.first_token_s <= c.finish_s for c in completed)
    blk = srv.channel.stats_block()
    assert blk["sends"] == 8      # every request crossed the wire
    assert blk["bytes_ratio_vs_bf16"] <= 0.55
    assert blk["bytes"] == blk["pages"] * srv.decode.cache_cfg.page_bytes


@pytest.mark.slow
def test_token_parity_bf16():
    mono, srv, completed = _parity_streams("bf16")
    assert srv.token_streams == mono
    assert len(completed) == 8
    assert srv.channel.stats_block()["sends"] == 8


# ---------------------------------------------------------------------
# fault composition: a prefill-replica crash under shrink


@pytest.mark.slow
def test_prefill_crash_blows_ttft_keeps_tpot(tmp_path):
    """Crash ONE prefill rank mid-plan under shrink: decode survivors
    keep TPOT at the decode SLO while TTFT p99 blows up (re-queued
    requests keep their ORIGINAL arrival stamps, so the rebuild is on
    the record), the degraded/detection/recovery fields stamp, the
    anomaly engine fires the ``slo`` trigger, and the flight dump
    carries the migration provenance next to the stall."""
    from dlnetbench_tpu.faults.plan import FaultEvent, FaultPlan
    from dlnetbench_tpu.metrics.emit import result_to_record
    from dlnetbench_tpu.metrics.parser import validate_record
    from dlnetbench_tpu.serving.disagg import run_disagg

    mc = tiny_model()
    cfg = disagg_serving(world=3, prefill_ranks=2, decode_ranks=1,
                         cache_dtype="int8")
    trace = [{"t": 0.01 * i, "prompt_len": 6, "output_len": 4}
             for i in range(10)]
    trace += [{"t": 4.0 + 0.05 * i, "prompt_len": 6, "output_len": 4}
              for i in range(6)]
    plan = ArrivalPlan(kind="replay", trace=trace)
    params = tfm.init_params(jax.random.PRNGKey(0), mc)

    clean = run_disagg(mc, cfg, plan, params=params) \
        .global_meta["serving"]

    rec = telemetry.enable(capacity=256, dump_dir=tmp_path)
    fp = FaultPlan(events=[FaultEvent(kind="crash", ranks=[0],
                                      iteration=4)], policy="shrink")
    res = run_disagg(mc, cfg, plan, fault_plan=fp, params=params)
    g = res.global_meta
    assert g["degraded_world"] == [1, 2]   # prefill rank 0 is gone
    assert g["degraded_slots"] == 4        # decode share untouched
    assert g["detection_ms"] >= 0 and g["recovery_ms"] > 0
    assert res.num_runs == len(trace)      # every request completes
    srv = g["serving"]
    # the asymmetry the monolithic engine cannot express: admission
    # (TTFT) eats the rebuild while decode survivors hold their SLO
    assert srv["ttft_ms"]["p99"] > clean["ttft_ms"]["p99"]
    # > 10x the TTFT SLO is only reachable if re-queued requests kept
    # their ORIGINAL arrival stamps — a re-stamped arrival would reset
    # TTFT to the clean sub-SLO regime
    assert srv["ttft_ms"]["p99"] > 10 * cfg.slo_ttft_ms
    assert srv["tpot_ms"]["p50"] <= cfg.slo_tpot_ms
    assert srv["completed"] == len(trace)
    # both segments' migrations folded into ONE wire block
    assert srv["migration"]["sends"] >= len(trace)
    # the fault trigger names the replica; the SLO breach fired and
    # dumped a window whose ring holds the migration records
    kinds = {a["trigger"]: a for a in rec.anomalies}
    assert kinds["fault"]["detail"]["replica"] == "prefill"
    assert "slo" in kinds
    dump = json.loads((tmp_path / "flight_slo.json").read_text())
    assert dump["trigger"] == "slo"
    assert any(s["source"] == "migration" for s in dump["samples"])
    mig = [s for s in rec.samples() if s["source"] == "migration"]
    assert mig and all("queue_depth" in s and "bytes" in s
                       for s in mig)
    record = result_to_record(res)  # recorder still live: anomalies stamp
    validate_record(record)
    assert record["global"]["disaggregated"] is True
    assert record["global"]["anomalies"]["triggers"].get("slo", 0) >= 1


# ---------------------------------------------------------------------
# the record pathway: committed two-replica fixture round trip


def test_disagg_record_fixture_roundtrip():
    """The committed disaggregated record (a REAL two-replica int8
    run of serving/disagg.run_disagg) flows parser -> merge -> summary
    with the migration and replica columns populated."""
    from dlnetbench_tpu.analysis.bandwidth import serving_summary
    from dlnetbench_tpu.metrics.merge import merge_records
    from dlnetbench_tpu.metrics.parser import (load_records,
                                               records_to_dataframe,
                                               validate_record)
    records = load_records(DATA / "record_disagg.jsonl")
    assert len(records) == 1
    rec = records[0]
    validate_record(rec)
    g = rec["global"]
    assert g["disaggregated"] is True
    sc = g["serving_config"]
    assert sc["prefill_ranks"] == 1 and sc["decode_ranks"] == 1
    mig = g["serving"]["migration"]
    assert mig["sends"] > 0 and mig["bytes"] > 0
    assert mig["bytes_ratio_vs_bf16"] <= 0.55    # int8 wire, page_size=8
    assert mig["bytes"] == pytest.approx(
        mig["bytes_ratio_vs_bf16"] * mig["bf16_equiv_bytes"], rel=1e-3)

    df = records_to_dataframe(records)
    for col in ("serving_migration_bytes", "serving_migration_bytes_ratio",
                "serving_migration_ms_p50", "serving_migration_overlap",
                "disaggregated"):
        assert col in df.columns, col
    assert df["serving_migration_bytes"].iloc[0] == mig["bytes"]

    merged = merge_records(records)   # single-process identity
    validate_record(merged)
    ss = serving_summary([merged])
    row = ss.iloc[0]
    assert bool(row["disaggregated"]) is True
    assert row["prefill_ranks"] == 1 and row["decode_ranks"] == 1
    assert row["migration_bytes"] == mig["bytes"]
    assert row["migration_bytes_ratio"] == mig["bytes_ratio_vs_bf16"]
    assert not math.isnan(row["migration_ms_p50"])


def test_pre_disagg_records_still_parse_and_merge_refuses_mix():
    """Monolithic v2 and v1 records keep parsing (migration columns
    absent/NaN — records are byte-identical to pre-disagg), and a
    disaggregated record never merges with a monolithic one: the
    ``disaggregated`` global is run IDENTITY, not volatile."""
    from dlnetbench_tpu.analysis.bandwidth import serving_summary
    from dlnetbench_tpu.metrics.merge import merge_records
    from dlnetbench_tpu.metrics.parser import (load_records,
                                               records_to_dataframe)
    mono = load_records(DATA / "record_serving.jsonl")
    df = records_to_dataframe(mono)
    assert "serving_migration_bytes" not in df.columns
    row = serving_summary(mono).iloc[0]
    assert bool(row["disaggregated"]) is False
    assert math.isnan(row["migration_bytes"])
    v1 = load_records(DATA / "record_v1.jsonl")
    assert "disaggregated" not in records_to_dataframe(v1).columns

    dis = load_records(DATA / "record_disagg.jsonl")[0]
    a = copy.deepcopy(dis)
    b = copy.deepcopy(dis)
    a["global"]["num_processes"] = b["global"]["num_processes"] = 2
    b["process"] = 1
    del b["global"]["disaggregated"]    # "the other arm was monolithic"
    with pytest.raises(ValueError, match="disaggregated"):
        merge_records([a, b])


def test_prefill_stall_blame_from_fixture():
    """analysis.critical_path.prefill_stall_blame prices the exposed
    (non-overlapped) migration time against the decode device wall from
    the committed fixture; a monolithic record yields None."""
    from dlnetbench_tpu.analysis.critical_path import prefill_stall_blame
    from dlnetbench_tpu.metrics.parser import load_records
    rec = load_records(DATA / "record_disagg.jsonl")[0]
    blame = prefill_stall_blame(rec)
    assert blame is not None
    mig = rec["global"]["serving"]["migration"]
    assert blame["migration_ms_total"] == mig["ms"]["total"]
    if math.isnan(mig.get("overlap", float("nan"))):
        assert math.isnan(blame["exposed_ms"])
    else:
        assert 0.0 <= blame["exposed_ms"] <= mig["ms"]["total"]
    mono = load_records(DATA / "record_serving.jsonl")[0]
    assert prefill_stall_blame(mono) is None
