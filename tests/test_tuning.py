"""The persistent seeded autotuner (ISSUE 9, dlnetbench_tpu/tuning/).

Covers, per the issue's satellite checklist:

* TuningDB durability — torn/partial-write recovery (truncate
  mid-record, reopen), newer-schema refusal, the concurrent writer
  claim/retry race (the ``test_native_build.py`` wipe-race pattern);
* the seeded search — deterministic candidate order, band-aware
  pruning, winner committed with its measured band;
* the consult layer — disabled-by-default bit-identity (every tunable
  site reproduces today's frozen defaults on an empty/absent DB),
  freeze-after-first-consult, explicit values winning, loud rejection
  of inapplicable DB configs;
* the committed fixture ``tests/data/tuning_db.jsonl`` round-tripped
  consult -> emit -> parser -> merge -> bandwidth;
* the ``python -m dlnetbench_tpu.tuning tune`` CLI end to end on a
  tiny CPU shape (2 candidates, seconds — the ``make check-tuning``
  lane);
* the flash backward's block resolution: the tuner's
  ``override_blocks`` held to the sequence, the DB consulted for an
  all-default call.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from dlnetbench_tpu import tuning
from dlnetbench_tpu.tuning.db import TuningDB

pytestmark = pytest.mark.tuning

FIXTURE = Path(__file__).parent / "data" / "tuning_db.jsonl"


@pytest.fixture(autouse=True)
def _clean_tuning_state(monkeypatch):
    """Every test starts disabled with an empty consult cache, and
    leaves no process-global consult log behind for unrelated tests."""
    monkeypatch.delenv(tuning.ENV_DB_DIR, raising=False)
    tuning.reset()
    yield
    tuning.reset()


def _enable(monkeypatch, tmp_path, with_fixture: bool = False) -> Path:
    root = tmp_path / "tdb"
    root.mkdir(exist_ok=True)
    if with_fixture:
        shutil.copy(FIXTURE, root / tuning.DB_FILENAME)
    monkeypatch.setenv(tuning.ENV_DB_DIR, str(root))
    tuning.reset()
    return root


# ------------------------------------------------------------------ DB

def test_db_put_get_roundtrip(tmp_path):
    db = TuningDB(tmp_path)
    rec = db.put("op", "k=1", "cpu", {"block": 64},
                 band={"value": 1.0, "best": 0.9, "band": [0.9, 1.1],
                       "n": 3},
                 meta={"seed": 7})
    assert rec["schema"] == tuning.SCHEMA_VERSION
    got = db.get("op", "k=1", "cpu")
    assert got["config"] == {"block": 64}
    assert got["band"]["n"] == 3 and got["meta"]["seed"] == 7
    # replace-in-place: same key overwrites, no duplicate lines
    db.put("op", "k=1", "cpu", {"block": 32})
    assert db.get("op", "k=1", "cpu")["config"] == {"block": 32}
    assert len(db.load()) == 1


def test_db_torn_write_recovery(tmp_path):
    """Truncate mid-record and reopen: the damaged line is skipped, the
    intact records stay readable, and a later put() heals the file."""
    db = TuningDB(tmp_path)
    db.put("op", "k=1", "cpu", {"block": 64})
    db.put("op", "k=2", "cpu", {"block": 128})
    raw = db.path.read_bytes()
    db.path.write_bytes(raw[:-20])  # tear the LAST record mid-json
    recs = db.load()
    assert len(recs) == 1
    assert ("op", "k=1", "cpu") in recs
    # write path still works on the torn file, and re-persists clean
    db.put("op", "k=3", "cpu", {"block": 256})
    assert len(db.load()) == 2
    for line in db.path.read_text().splitlines():
        json.loads(line)  # every surviving line is whole again


def test_db_newer_schema_refused(tmp_path):
    db = TuningDB(tmp_path)
    db.path.parent.mkdir(parents=True, exist_ok=True)
    db.path.write_text(json.dumps(
        {"schema": tuning.SCHEMA_VERSION + 1, "op": "op", "key": "k",
         "hw": "cpu", "config": {}}) + "\n")
    with pytest.raises(ValueError, match="newer than this build"):
        db.load()


class _FlakyLock:
    """Lock-dir stand-in emulating a concurrent writer that holds the
    lock for the first ``held`` rounds (the test_native_build.py
    wipe-race pattern): mkdir sees it exist, stat sees it already
    released.  After that the real lock claims cleanly."""

    def __init__(self, real: Path, held: int):
        self.real = real
        self.held = held
        self.attempt = 0

    def mkdir(self):
        self.attempt += 1
        if self.attempt <= self.held:
            raise FileExistsError(self)   # the racer holds it...
        self.real.mkdir()

    def stat(self):
        if self.attempt <= self.held:
            raise FileNotFoundError(self)  # ...and released under us
        return self.real.stat()

    def rmdir(self):
        self.real.rmdir()


def test_db_claim_retries_after_concurrent_release(tmp_path):
    target = tmp_path / "lock"
    TuningDB._claim(_FlakyLock(target, held=2))
    assert target.is_dir()


def test_db_claim_gives_up_after_bounded_attempts(tmp_path):
    flaky = _FlakyLock(tmp_path / "never", held=10**9)
    with pytest.raises(RuntimeError, match="could not claim"):
        TuningDB._claim(flaky, attempts=3, wait_s=0.0)
    assert flaky.attempt == 3  # bounded, not an infinite spin


def test_db_claim_steals_stale_lock(tmp_path):
    lock = tmp_path / "lock"
    lock.mkdir()
    TuningDB._claim(lock, attempts=3, wait_s=0.0, stale_s=0.0)
    assert lock.is_dir()  # stolen from the 'crashed' writer, re-held


# -------------------------------------------------------------- search

def test_seeded_order_deterministic_and_seed_sensitive():
    a = tuning.seeded_order(8, seed=3)
    assert a == tuning.seeded_order(8, seed=3)
    assert sorted(a) == list(range(8))
    assert a != tuning.seeded_order(8, seed=4)


def test_search_elects_min_median_and_commits_band(tmp_path):
    times = {"a": [3.0, 3.1, 3.2], "b": [1.0, 1.1, 1.2],
             "c": [2.0, 2.1, 2.2]}
    calls = {k: 0 for k in times}

    def measure(cfg):
        name = cfg["name"]
        t = times[name][calls[name] % 3]
        calls[name] += 1
        return t

    db = TuningDB(tmp_path)
    res = tuning.tune_and_commit(
        db, "op", "k", "cpu",
        [{"name": "a"}, {"name": "b"}, {"name": "c"}], measure,
        seed=0, rounds=3, k=4)
    assert res["config"] == {"name": "b"}
    assert res["band"]["value"] == 1.1 and res["band"]["n"] == 3
    rec = db.get("op", "k", "cpu")
    assert rec["config"] == {"name": "b"}
    assert rec["band"]["band"] == [1.0, 1.2]
    assert rec["meta"]["reps_per_fence"] == 4


def test_search_prunes_band_disjoint_losers():
    """A candidate whose best-of-two samples lands strictly above the
    incumbent's whole band is cut after two rounds (never one — a
    single draw can hit a slow mode); a band-ambiguous one
    gets its full rounds."""
    seen = []
    # fast's samples SPREAD (band [1.0, 1.2]); slow's best-of-two is
    # strictly above that whole band (pruned); close lands inside it
    # (band-ambiguous -> full rounds)
    seqs = {"fast": [1.0, 1.2, 1.1], "slow": [9.0, 9.0, 9.0],
            "close": [1.15, 1.15, 1.15]}

    def measure(cfg):
        name = cfg["name"]
        seen.append(name)
        return seqs[name][seen.count(name) - 1]

    # seeded_order(3, seed=0) fixes visit order; find a seed where
    # 'fast' is visited first so the pruning logic is actually hit
    import itertools
    for seed in itertools.count():
        order = tuning.seeded_order(3, seed)
        if order[0] == 0:
            break
    res = tuning.run_search(
        [{"name": "fast"}, {"name": "slow"}, {"name": "close"}],
        measure, seed=seed, rounds=3)
    assert res["config"] == {"name": "fast"}
    assert res["pruned"] == 1
    assert seen.count("slow") == 2      # cut after two samples, not 1
    assert seen.count("close") == 3     # band-ambiguous: full rounds
    pruned = [t for t in res["trials"] if t["pruned"]]
    assert len(pruned) == 1 and pruned[0]["config"]["name"] == "slow"
    assert pruned[0]["summary"]["n"] == 2


def test_search_single_slow_draw_does_not_prune():
    """The exact hazard stats.py documents: the true winner's FIRST
    draw hits the slow mode.  Two-sample pruning lets its later rounds
    elect it anyway."""
    seen = []
    seqs = {"incumbent": [1.0, 1.1, 1.2],
            "winner": [1.5, 0.9, 0.9]}   # slow-mode first draw

    def measure(cfg):
        name = cfg["name"]
        seen.append(name)
        return seqs[name][seen.count(name) - 1]

    import itertools
    for seed in itertools.count():
        if tuning.seeded_order(2, seed) == [0, 1]:
            break
    res = tuning.run_search(
        [{"name": "incumbent"}, {"name": "winner"}], measure,
        seed=seed, rounds=3)
    assert res["config"] == {"name": "winner"}
    assert res["pruned"] == 0
    assert res["band"]["value"] == 0.9


def test_search_refuses_empty_candidates():
    with pytest.raises(ValueError, match="no candidates"):
        tuning.run_search([], lambda cfg: 1.0)


# ---------------------------------------- consult layer: defaults & DB

def test_disabled_consult_returns_default_and_logs_nothing():
    out = tuning.consult("op", "k", {"block": 64})
    assert out == {"block": 64}
    assert tuning.provenance() is None
    assert not tuning.enabled()


def test_consult_hit_miss_and_freeze(monkeypatch, tmp_path):
    root = _enable(monkeypatch, tmp_path)
    TuningDB(root).put("op", "k", tuning.hw_key(), {"block": 32},
                       band={"value": 1.0, "best": 1.0,
                             "band": [1.0, 1.0], "n": 3})
    assert tuning.consult("op", "k", {"block": 64}) == {"block": 32}
    miss = tuning.consult("op", "other", {"block": 64})
    assert miss == {"block": 64}
    prov = tuning.provenance()
    assert prov["hits"] == 1 and prov["misses"] == 1
    assert prov["sites"]["op|k"]["hit"] is True
    assert prov["sites"]["op|k"]["tuned_band"]["n"] == 3
    assert prov["sites"]["op|other"]["hit"] is False
    # freeze-after-first-consult: a DB edit after the first consult is
    # invisible for the process lifetime (the jit-cache hazard)
    TuningDB(root).put("op", "k", tuning.hw_key(), {"block": 8})
    assert tuning.consult("op", "k", {"block": 64}) == {"block": 32}


def test_consult_rejects_inapplicable_db_config(monkeypatch, tmp_path):
    root = _enable(monkeypatch, tmp_path)
    TuningDB(root).put("op", "k", tuning.hw_key(), {"block": -5})

    def check(cfg):
        if cfg["block"] < 1:
            raise ValueError(f"block={cfg['block']} is not positive")

    with pytest.raises(ValueError, match="inapplicable"):
        tuning.consult("op", "k", {"block": 64}, validate=check)


# ------------------------------- tunable sites: empty-DB bit-identity

def test_fused_matmul_empty_db_bit_identical(monkeypatch, tmp_path):
    """With an EMPTY DB enabled, fused_matmul runs the frozen default
    blocks and produces bit-identical int8 results to the explicit-
    default call; the consult is logged as a miss."""
    from dlnetbench_tpu.ops import quantized_matmul as qmm

    x = jax.random.normal(jax.random.key(0), (64, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (64, 64), jnp.bfloat16)
    wq, sw = qmm.quantize_tensor(w, "int8")
    sx = qmm.scale_from_amax(jnp.max(jnp.abs(x.astype(jnp.float32))),
                             "int8")
    baseline = qmm.fused_matmul(x, wq, sw, sx, fmt="int8",
                                **qmm.DEFAULT_BLOCKS)
    _enable(monkeypatch, tmp_path)   # empty DB
    got = qmm.fused_matmul(x, wq, sw, sx, fmt="int8")
    assert jnp.array_equal(baseline, got)
    prov = tuning.provenance()
    assert prov["hits"] == 0 and prov["misses"] == 1


def test_fused_matmul_db_hit_changes_blocks_not_math(monkeypatch,
                                                     tmp_path):
    """A DB hit reroutes the grid blocks (provenance says so) and the
    int8 result stays EXACTLY equal — tiled int32 accumulation is
    associative, so tuning can never change quantized numerics."""
    from dlnetbench_tpu.ops import quantized_matmul as qmm

    x = jax.random.normal(jax.random.key(0), (64, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (64, 64), jnp.bfloat16)
    wq, sw = qmm.quantize_tensor(w, "int8")
    sx = qmm.scale_from_amax(jnp.max(jnp.abs(x.astype(jnp.float32))),
                             "int8")
    baseline = qmm.fused_matmul(x, wq, sw, sx, fmt="int8",
                                **qmm.DEFAULT_BLOCKS)
    root = _enable(monkeypatch, tmp_path)
    key = tuning.params.quantized_matmul_key(64, 64, 64, "int8", x.dtype)
    TuningDB(root).put("quantized_matmul", key, tuning.hw_key(),
                       {"block_m": 32, "block_n": 64, "block_k": 32})
    got = qmm.fused_matmul(x, wq, sw, sx, fmt="int8")
    assert jnp.array_equal(baseline, got)
    assert tuning.provenance()["hits"] == 1


def test_spmd_config_resolution(monkeypatch, tmp_path):
    """None knobs resolve to the frozen defaults on an empty DB, to the
    DB's answer on a hit (only when the knob's mode is LIVE), and
    explicit values always win."""
    from dlnetbench_tpu.models.spmd import SpmdConfig

    cfg = SpmdConfig(tp_overlap="decomposed", grad_sync="bucketed")
    r = cfg.resolve_tuned(2, 1, 2)
    assert r.tp_overlap_chunks == 2 and r.grad_bucket_layers == 1
    root = _enable(monkeypatch, tmp_path)
    TuningDB(root).put(
        "tp_overlap_chunks",
        tuning.params.tp_overlap_chunks_key(cfg.embed_dim, cfg.ff_dim,
                                            cfg.seq_len, 2, cfg.dtype),
        tuning.hw_key(), {"chunks": 4})
    r = cfg.resolve_tuned(2, 1, 2)
    assert r.tp_overlap_chunks == 4     # DB answered
    assert r.grad_bucket_layers == 1    # miss -> frozen default
    explicit = SpmdConfig(tp_overlap="decomposed", grad_sync="bucketed",
                          tp_overlap_chunks=8, grad_bucket_layers=2)
    r = explicit.resolve_tuned(2, 1, 2)
    assert r.tp_overlap_chunks == 8 and r.grad_bucket_layers == 2
    # INERT knobs never consult: tp_overlap='none'/grad_sync=
    # 'monolithic' resolve to the defaults with no provenance logged,
    # even with the same DB entry present — a 'hit' on a knob the
    # compiled program ignores would stamp tuned provenance onto a
    # bit-identical-to-untuned run
    tuning.reset()
    import os
    assert os.environ.get(tuning.ENV_DB_DIR)  # still enabled
    r = SpmdConfig().resolve_tuned(2, 1, 2)
    assert r.tp_overlap_chunks == 2 and r.grad_bucket_layers == 1
    assert tuning.provenance() is None


def test_flash_blocks_empty_db_bit_identical(monkeypatch, tmp_path):
    """Flash attention fwd+grad on an empty enabled DB is bit-identical
    to the disabled path (same _pick_block defaults)."""
    import importlib
    flash_attention = importlib.import_module(
        "dlnetbench_tpu.ops.flash_attention").flash_attention

    q = jax.random.normal(jax.random.key(0), (1, 256, 2, 128),
                          jnp.float32)
    k = jax.random.normal(jax.random.key(1), (1, 256, 2, 128),
                          jnp.float32)
    v = jax.random.normal(jax.random.key(2), (1, 256, 2, 128),
                          jnp.float32)

    def loss(q_, k_, v_):
        return flash_attention(q_, k_, v_).astype(jnp.float32).sum()

    base, base_grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    _enable(monkeypatch, tmp_path)
    got, got_grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert jnp.array_equal(base, got)
    for b, g in zip(base_grads, got_grads):
        assert jnp.array_equal(b, g)
    prov = tuning.provenance()
    assert prov and prov["hits"] == 0
    assert any(s.startswith("flash_fwd|") for s in prov["sites"])
    assert any(s.startswith("flash_bwd|") for s in prov["sites"])


def test_flash_tuned_blocks_must_divide_seq(monkeypatch, tmp_path):
    """An inapplicable DB block config fails LOUD at the flash site
    (a truncated grid would silently drop contributions)."""
    import importlib
    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")

    q = jax.random.normal(jax.random.key(0), (1, 256, 2, 128),
                          jnp.float32)
    root = _enable(monkeypatch, tmp_path)
    key = tuning.params.flash_fwd_key(1, 256, 2, 2, 128, True, q.dtype)
    TuningDB(root).put("flash_fwd", key, tuning.hw_key(),
                       {"block_q": 96, "block_k": 128})
    with pytest.raises(ValueError, match="does not divide"):
        fa.flash_attention(q, q, q)


def test_paged_attention_default_and_validation(monkeypatch, tmp_path):
    """Empty-DB consult reproduces the historical min(pages, 8) block
    pick; explicit non-divisors are refused on every impl."""
    from dlnetbench_tpu.serving.kv_cache import (
        paged_attention_decode, resolve_pages_per_compute_block)

    q = jax.random.normal(jax.random.key(0), (2, 4, 8), jnp.float32)
    kp = jax.random.normal(jax.random.key(1), (2, 8, 4, 8), jnp.float32)
    pidx = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    assert resolve_pages_per_compute_block(q, kp, pidx, None) == 4
    _enable(monkeypatch, tmp_path)
    assert resolve_pages_per_compute_block(q, kp, pidx, None) == 4
    assert tuning.provenance()["misses"] == 1
    with pytest.raises(ValueError, match="does not divide"):
        resolve_pages_per_compute_block(q, kp, pidx, 3)
    with pytest.raises(ValueError, match="does not divide"):
        paged_attention_decode(q, kp, kp,
                               jnp.full((2,), 16, jnp.int32), pidx,
                               impl="gather", pages_per_compute_block=3)


# ------------------------- fixture round-trip: consult -> emit -> ...

def test_fixture_roundtrip_consult_emit_parser_merge(monkeypatch,
                                                     tmp_path):
    """The committed tests/data/tuning_db.jsonl drives a real consult
    hit; the provenance block rides emit -> validate -> dataframe
    (tuned column) -> merge (volatile global) -> bandwidth (tuned
    column), and v1/no-tuning records still parse beside it."""
    from dlnetbench_tpu.analysis.bandwidth import (bandwidth_summary,
                                                   effective_bandwidth)
    from dlnetbench_tpu.metrics.emit import result_to_record
    from dlnetbench_tpu.metrics.merge import merge_records
    from dlnetbench_tpu.metrics.parser import (records_to_dataframe,
                                               validate_record)
    from dlnetbench_tpu.ops import quantized_matmul as qmm
    from dlnetbench_tpu.proxies.base import ProxyResult

    _enable(monkeypatch, tmp_path, with_fixture=True)
    # the fixture's quantized_matmul entry: consult must HIT, and the
    # tuned blocks (32, 64, 64) must leave int8 math exactly alone
    x = jax.random.normal(jax.random.key(0), (64, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (64, 64), jnp.bfloat16)
    wq, sw = qmm.quantize_tensor(w, "int8")
    sx = qmm.scale_from_amax(jnp.max(jnp.abs(x.astype(jnp.float32))),
                             "int8")
    baseline = qmm.fused_matmul(x, wq, sw, sx, fmt="int8",
                                **qmm.DEFAULT_BLOCKS)
    got = qmm.fused_matmul(x, wq, sw, sx, fmt="int8")
    assert jnp.array_equal(baseline, got)
    prov = tuning.provenance()
    assert prov["hits"] == 1 and prov["misses"] == 0
    site = prov["sites"]["quantized_matmul|"
                         "fmt=int8,k=64,n=64,t=64,xdtype=bfloat16"]
    assert site["config"]["block_m"] == 32
    assert site["tuned_band"]["n"] == 3

    # emit: the record carries the tuning block
    result = ProxyResult(
        name="dp",
        global_meta={
            "proxy": "dp", "model": "m", "world_size": 2,
            "comm_model": {"runtimes": [
                {"kind": "allreduce", "bytes": 1024, "group": 2}]},
            "mesh": {"platform": "cpu", "device_kind": "host",
                     "num_hosts": 1,
                     "devices": [{"id": 0, "process": 0},
                                 {"id": 1, "process": 0}]}},
        timers_us={"runtimes": [100.0, 110.0, 105.0]},
        warmup_times_us=[500.0], num_runs=3)
    rec = result_to_record(result)
    assert rec["global"]["tuning"]["hits"] == 1
    validate_record(rec)
    json.dumps(rec)  # emitted shape is serializable

    # parser: the tuned column
    df = records_to_dataframe([rec])
    assert set(df["tuned"]) == {"1/1"}

    # merge: tuning is per-process warm state (volatile), so a merged
    # single-process record keeps it and the merge never aborts on it
    merged = merge_records([json.loads(json.dumps(rec))])
    assert merged["global"]["tuning"]["hits"] == 1

    # bandwidth: every row carries the tuned provenance column
    bw = effective_bandwidth([merged])
    assert set(bw["tuned"]) == {"1/1"}
    summary = bandwidth_summary([merged])
    assert "tuned" in summary.columns

    # a v1/no-tuning record parses beside it, tuned column absent/NaN
    old = json.loads(json.dumps(rec))
    old["global"].pop("tuning")
    df2 = records_to_dataframe([old])
    assert "tuned" not in df2.columns
    bw2 = effective_bandwidth([old])
    assert set(bw2["tuned"]) == {"-"}


def test_merge_tolerates_mixed_tuning_globals(monkeypatch, tmp_path):
    """One process tuned, one not (a host without the env set): the
    merge must not read that as 'different runs'."""
    from dlnetbench_tpu.metrics.merge import merge_records

    def rec_for(proc: int, with_tuning: bool):
        r = {"section": "dp", "version": 2, "process": proc,
             "global": {"model": "m", "world_size": 2,
                        "num_processes": 2},
             "mesh": {"platform": "cpu"},
             "num_runs": 2, "warmup_times": [1.0],
             "ranks": [{"rank": proc, "device_id": proc,
                        "process_index": proc,
                        "hostname": f"h{proc}",
                        "runtimes": [1.0, 2.0],
                        "summary": {"runtimes": {
                            "value": 1.5, "best": 1.0,
                            "band": [1.0, 2.0], "n": 2}}}]}
        if with_tuning:
            r["global"]["tuning"] = {"db_dir": "/x", "hits": 1,
                                     "misses": 0, "sites": {}}
        return r

    merged = merge_records([rec_for(0, True), rec_for(1, False)])
    assert merged["global"]["tuning"]["hits"] == 1


# ----------------------------------------------- the tune CLI, end2end

def test_tune_cli_search_commit_consult_hit(monkeypatch, tmp_path,
                                            capsys):
    """The check-tuning lane's proof: a 2-candidate CPU search over a
    tiny int8 fused matmul commits a winner; a consult through the
    REAL site then hits it.  Seconds on CPU."""
    from dlnetbench_tpu.ops import quantized_matmul as qmm
    from dlnetbench_tpu.tuning.__main__ import main as tuning_main

    root = tmp_path / "tdb"
    rc = tuning_main([
        "tune", "--op", "quantized_matmul", "--db", str(root),
        "--fmt", "int8", "--tokens", "64", "--d", "64", "--n", "64",
        "--candidates", "64,64,64;32,64,64", "--rounds", "2", "-k", "2",
    ])
    assert rc == 0
    committed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert committed["op"] == "quantized_matmul"
    assert committed["band"]["n"] == 2
    assert committed["config"]["block_m"] in (64, 32)
    # the committed record is consultable through the real site
    monkeypatch.setenv(tuning.ENV_DB_DIR, str(root))
    tuning.reset()
    x = jax.random.normal(jax.random.key(0), (64, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (64, 64), jnp.bfloat16)
    wq, sw = qmm.quantize_tensor(w, "int8")
    sx = qmm.scale_from_amax(jnp.max(jnp.abs(x.astype(jnp.float32))),
                             "int8")
    qmm.fused_matmul(x, wq, sw, sx, fmt="int8")
    prov = tuning.provenance()
    assert prov["hits"] == 1 and prov["misses"] == 0
    # show lists it
    rc = tuning_main(["show", "--db", str(root)])
    assert rc == 0
    shown = [json.loads(ln)
             for ln in capsys.readouterr().out.strip().splitlines()]
    assert any(r["op"] == "quantized_matmul" for r in shown)


def test_flash_explicit_blocks_bypass_db_in_backward(monkeypatch,
                                                     tmp_path):
    """Explicit flash blocks bind the BACKWARD too: with a flash_bwd
    DB record present, a call with explicit block_q/block_k must never
    consult it (a DB hit silently overriding explicit blocks would
    re-create the 'measured 4 configs while timing one' sweep
    hazard)."""
    import importlib

    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")
    root = _enable(monkeypatch, tmp_path)
    q = jax.random.normal(jax.random.key(0), (1, 256, 2, 128),
                          jnp.float32)
    key = tuning.params.flash_bwd_key(1, 256, 2, 2, 128, True, q.dtype)
    TuningDB(root).put("flash_bwd", key, tuning.hw_key(),
                       {"bq_dq": 64, "bk_dq": 64,
                        "bq_dkv": 64, "bk_dkv": 64})

    def loss(q_):
        return fa.flash_attention(q_, q_, q_, True, 128,
                                  128).astype(jnp.float32).sum()

    jax.grad(loss)(q)
    assert tuning.provenance() is None   # the DB was never asked


def test_bench_tuned_ab_reuses_existing_db_record(monkeypatch,
                                                  tmp_path):
    """A pre-existing DB record (e.g. a richer CLI tune) is MEASURED,
    never overwritten, by the bench tuned A/B."""
    import types

    import bench

    monkeypatch.setattr(bench, "BATCH", 2)
    monkeypatch.setattr(bench, "SEQ", 32)     # 64 tokens
    root = _enable(monkeypatch, tmp_path)
    up_key = tuning.params.quantized_matmul_key(
        64, 64, 128, "float8", jnp.zeros((), jnp.bfloat16).dtype)
    operator_cfg = {"block_m": 256, "block_n": 64, "block_k": 32}
    TuningDB(root).put("quantized_matmul", up_key, tuning.hw_key(),
                       operator_cfg)
    card = types.SimpleNamespace(embed_dim=64, ff_dim=128)
    line = bench._bench_tuned_ab(card, "tpu_v5e", jax.devices()[0])
    assert line is not None
    assert line["db_prior_hit"]["up"] is True
    assert line["search"]["up"] == {"reused_db_record": True,
                                    "tuned_band": None}
    assert line["configs"]["up"] == operator_cfg
    # the operator's record survived untouched
    assert TuningDB(root).get("quantized_matmul", up_key,
                              tuning.hw_key())["config"] == operator_cfg
    # the down shape had no record: searched and committed as before
    assert line["db_prior_hit"]["down"] is False
    assert line["search"]["down"]["candidates"] == 3


def test_tune_cli_flash_key_agrees_with_consult_site(monkeypatch,
                                                     tmp_path, capsys):
    """The CLI's committed flash key must be CONSULTABLE by the real
    flash_attention site (the key-spelling agreement the shared
    params builders exist for)."""
    import importlib

    from dlnetbench_tpu.tuning.__main__ import main as tuning_main

    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")
    root = tmp_path / "tdb"
    rc = tuning_main([
        "tune", "--op", "flash_fwd", "--db", str(root), "--batch", "1",
        "--seq", "256", "--heads", "2", "--kv_heads", "2",
        "--head_dim", "128", "--candidates", "256,256", "--rounds", "1",
        "-k", "1",
    ])
    assert rc == 0
    capsys.readouterr()
    monkeypatch.setenv(tuning.ENV_DB_DIR, str(root))
    tuning.reset()
    q = jax.random.normal(jax.random.key(0), (1, 256, 2, 128),
                          jnp.float32)
    fa.flash_attention(q, q, q)
    prov = tuning.provenance()
    flash_sites = {k: v for k, v in prov["sites"].items()
                   if k.startswith("flash_fwd|")}
    assert flash_sites and all(v["hit"] for v in flash_sites.values())


def test_bench_tuned_ab_end_to_end_tiny(monkeypatch):
    """bench.py's tuned A/B aux line at tiny CPU shapes: the seeded
    search runs, commits to an EPHEMERAL DB (env unset), and the line
    reports both variants' bands + the committed configs + prior
    hit/miss — the CPU half of the acceptance bar (search mechanism +
    provenance proven; the TPU number comes from the driver)."""
    import types

    import bench

    monkeypatch.setattr(bench, "BATCH", 2)
    monkeypatch.setattr(bench, "SEQ", 32)     # 64 tokens
    card = types.SimpleNamespace(embed_dim=64, ff_dim=128)
    line = bench._bench_tuned_ab(card, "tpu_v5e", jax.devices()[0])
    assert line is not None and line["unit"] == "ms"
    json.dumps(line)
    for sub in ("tuned_ms", "frozen_ms", "ratio_tuned_vs_frozen"):
        assert line[sub]["n"] == 3
    assert line["db_prior_hit"] == {"up": False, "down": False}
    assert "[ephemeral]" in line["metric"]
    for stage in ("up", "down"):
        assert set(line["configs"][stage]) == {"block_m", "block_n",
                                               "block_k"}
        assert line["search"][stage]["candidates"] == 3
    from dlnetbench_tpu.sentinel import is_ms_line
    assert is_ms_line(line)


# ------------------------------- the flash backward's block resolution

@pytest.mark.parametrize("kernel", ["flash", "splash"])
def test_flash_bwd_override_blocks_must_divide_the_sequence(kernel):
    """The tuner's ``override_blocks`` fail LOUD on a block that does
    not divide the sequence: a truncated grid would leave dq rows
    unwritten and drop query contributions from dk/dv while the sweep
    records a plausible-looking time.  Blocks that divide it run."""
    import importlib
    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")
    from dlnetbench_tpu.ops.attention_mask import MaskSpec

    q = jax.random.normal(jax.random.key(0), (1, 256, 2, 128),
                          jnp.float32)
    if kernel == "flash":
        out, lse = fa._fwd(q, q, q, causal=True, block_q=128, block_k=128)

        def bwd(blocks):
            return fa._bwd_impl(q, q, q, out, lse, q, causal=True,
                                block_q=128, block_k=128,
                                override_blocks=blocks)
    else:
        spec = MaskSpec(causal=True, window=64)
        out, lse = fa._splash_fwd(q, q, q, spec, block_q=128, block_k=128)

        def bwd(blocks):
            return fa._splash_bwd_impl(q, q, q, out, lse, q, spec,
                                       block_q=128, block_k=128,
                                       override_blocks=blocks)
    for bad in (((96, 128), (128, 128)), ((128, 128), (128, 0))):
        with pytest.raises(ValueError, match="does not divide"):
            bwd(bad)
    want = bwd(((128, 128), (128, 128)))
    got = bwd(((64, 128), (128, 64)))
    for a, b in zip(got, want):
        assert a.shape == q.shape
        assert jnp.allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("kernel", ["flash", "splash"])
def test_flash_bwd_dq_pair_is_unused_where_dq_is_resident(kernel,
                                                          monkeypatch):
    """``override_blocks`` and the records keep their two pairs.  Where
    a head's dq is resident in the dk/dv kernel the dq pair changes
    nothing, not a bit and not an instruction; past the rule (its share
    set to nothing here) the dq kernel runs at that pair."""
    import functools
    import importlib
    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")
    from dlnetbench_tpu.ops.attention_mask import MaskSpec

    q = jax.random.normal(jax.random.key(0), (1, 256, 2, 128),
                          jnp.float32)
    if kernel == "flash":
        out, lse = fa._fwd(q, q, q, causal=True, block_q=128, block_k=128)
        bwd = functools.partial(fa._bwd_impl, q, q, q, out, lse, q,
                                causal=True, block_q=128, block_k=128)
    else:
        spec = MaskSpec(causal=True, window=64)
        out, lse = fa._splash_fwd(q, q, q, spec, block_q=128, block_k=128)
        bwd = functools.partial(fa._splash_bwd_impl, q, q, q, out, lse, q,
                                spec, block_q=128, block_k=128)

    def program(dq_pair):
        return str(jax.make_jaxpr(lambda: bwd(
            override_blocks=(dq_pair, (128, 64))))())
    want = bwd(override_blocks=((128, 64), (128, 64)))
    got = bwd(override_blocks=((64, 128), (128, 64)))
    assert all(jnp.array_equal(a, b) for a, b in zip(got, want))
    assert program((64, 128)) == program((128, 64))
    assert "flash_bwd_dq" not in program((64, 128))
    monkeypatch.setattr(fa, "_DQ_RESIDENT_SHARE", 0.0)
    assert "flash_bwd_dq" in program((64, 128))
    assert program((64, 128)) != program((128, 64))
    two = bwd(override_blocks=((64, 128), (128, 64)))
    assert all(jnp.allclose(a, b, atol=1e-4, rtol=1e-4)
               for a, b in zip(two, want))


@pytest.mark.parametrize("op", ["flash_bwd", "splash_bwd"])
def test_tune_cli_backward_records_keep_their_four_keys(op, monkeypatch,
                                                        tmp_path, capsys):
    """The tuner's backward search still measures and commits
    ``bq_dq``, ``bk_dq``, ``bq_dkv``, ``bk_dkv``, and the real site
    reads the record back as its two pairs."""
    import importlib

    from dlnetbench_tpu.ops.attention_mask import MaskSpec
    from dlnetbench_tpu.tuning.__main__ import main as tuning_main

    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")
    root = tmp_path / "tdb"
    rc = tuning_main([
        "tune", "--op", op, "--db", str(root), "--batch", "1",
        "--seq", "256", "--heads", "2", "--kv_heads", "2",
        "--head_dim", "128", "--dtype", "float32", "--window", "64",
        "--candidates", "64,128,128,64;128,128,128,128",
        "--rounds", "1", "-k", "1",
    ])
    assert rc == 0
    committed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert committed["op"] == op
    assert set(committed["config"]) == {"bq_dq", "bk_dq", "bq_dkv",
                                        "bk_dkv"}
    monkeypatch.setenv(tuning.ENV_DB_DIR, str(root))
    tuning.reset()
    q = jax.random.normal(jax.random.key(0), (1, 256, 2, 128),
                          jnp.float32)
    cfg = committed["config"]
    pairs = ((cfg["bq_dq"], cfg["bk_dq"]), (cfg["bq_dkv"], cfg["bk_dkv"]))
    if op == "flash_bwd":
        assert fa._resolve_bwd_blocks(q, q, True, 256, 256) == pairs
    else:
        assert fa._resolve_splash_bwd_blocks(
            q, q, MaskSpec(causal=True, window=64), 256, 256) == pairs
    assert tuning.provenance()["hits"] == 1


def test_flash_bwd_db_consulted_for_default_call(monkeypatch, tmp_path):
    import importlib
    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")

    root = _enable(monkeypatch, tmp_path)
    q = jax.random.normal(jax.random.key(0), (1, 256, 2, 128),
                          jnp.float32)
    key = tuning.params.flash_bwd_key(1, 256, 2, 2, 128, True, q.dtype)
    TuningDB(root).put("flash_bwd", key, tuning.hw_key(),
                       {"bq_dq": 64, "bk_dq": 128,
                        "bq_dkv": 128, "bk_dkv": 64})
    blocks = fa._resolve_bwd_blocks(q, q, True, 256, 256)
    assert blocks == ((64, 128), (128, 64))
    assert tuning.provenance()["hits"] == 1


@pytest.mark.longcontext
def test_splash_blocks_empty_db_bit_identical(monkeypatch, tmp_path):
    """Splash attention fwd+grad on an empty enabled DB is bit-identical
    to the disabled path, and the consult logs under the mask-labeled
    splash keys (ISSUE 10: splash blocks are their own tuning site —
    dense flash records must never answer)."""
    import importlib
    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")
    from dlnetbench_tpu.ops.attention_mask import MaskSpec

    spec = MaskSpec(causal=True, window=64)
    q = jax.random.normal(jax.random.key(0), (1, 256, 2, 128),
                          jnp.float32)
    k = jax.random.normal(jax.random.key(1), (1, 256, 2, 128),
                          jnp.float32)
    v = jax.random.normal(jax.random.key(2), (1, 256, 2, 128),
                          jnp.float32)

    def loss(q_, k_, v_):
        return fa.splash_attention(q_, k_, v_,
                                   spec).astype(jnp.float32).sum()

    base, base_grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    _enable(monkeypatch, tmp_path)
    got, got_grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert jnp.array_equal(base, got)
    for b, g in zip(base_grads, got_grads):
        assert jnp.array_equal(b, g)
    prov = tuning.provenance()
    assert prov and prov["hits"] == 0
    splash_sites = [s for s in prov["sites"]
                    if s.startswith(("splash_fwd|", "splash_bwd|"))]
    assert len(splash_sites) == 2
    assert all("mask=causal&window(64)" in s for s in splash_sites)


@pytest.mark.longcontext
def test_splash_tuned_blocks_hit_and_divide_validation(monkeypatch,
                                                       tmp_path):
    """A committed splash record is consulted (numerics unchanged —
    block sizes never change the math) and an inapplicable one fails
    loud at the site."""
    import importlib
    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")
    from dlnetbench_tpu.ops.attention_mask import MaskSpec

    spec = MaskSpec(causal=True, window=64)
    q = jax.random.normal(jax.random.key(0), (1, 256, 2, 128),
                          jnp.float32)
    want = fa.splash_attention(q, q, q, spec, 128, 128)
    root = _enable(monkeypatch, tmp_path)
    key = tuning.params.splash_key(1, 256, 2, 2, 128, spec.label(),
                                   q.dtype)
    TuningDB(root).put("splash_fwd", key, tuning.hw_key(),
                       {"block_q": 128, "block_k": 128})
    got = fa.splash_attention(q, q, q, spec)
    assert jnp.array_equal(want, got)
    assert tuning.provenance()["hits"] == 1
    tuning.reset()
    TuningDB(root).put("splash_fwd", key, tuning.hw_key(),
                       {"block_q": 96, "block_k": 128})
    with pytest.raises(ValueError, match="does not divide"):
        fa.splash_attention(q, q, q, spec)
