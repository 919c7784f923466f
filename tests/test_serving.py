"""Serving tier (ISSUE 8): arrival plans, the paged KV cache, the
decode/prefill split, the continuous-batching engine, fault
composition, and the record round-trip against committed fixtures."""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlnetbench_tpu.models import transformer as tfm
from dlnetbench_tpu.serving.arrivals import ArrivalPlan
from dlnetbench_tpu.serving.kv_cache import (CacheConfig, CacheOOM,
                                             PagedKVCache,
                                             device_buffers,
                                             paged_attention_decode,
                                             sharded_paged_attention)
from dlnetbench_tpu.utils.seeded import Rng, splitmix64

DATA = Path(__file__).parent / "data"

pytestmark = pytest.mark.serving


def tiny_model(**over) -> tfm.TransformerConfig:
    kw = dict(vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2,
              ff_dim=64, num_layers=2, seq_len=32, gated=True,
              max_positions=0, dtype="float32")
    kw.update(over)
    return tfm.TransformerConfig(**kw)


def tiny_serving(**over):
    from dlnetbench_tpu.serving.scheduler import ServingConfig
    kw = dict(slots=4, page_size=4, num_pages=32, max_seq_len=32,
              slo_ttft_ms=200.0, slo_tpot_ms=100.0)
    kw.update(over)
    return ServingConfig(**kw)


# ---------------------------------------------------------------------
# arrival plans


def test_arrival_plan_validation_errors():
    with pytest.raises(ValueError, match="unknown kind"):
        ArrivalPlan(kind="lunar").validate()
    with pytest.raises(ValueError, match="phases"):
        ArrivalPlan(kind="diurnal", rate_rps=10.0,
                    num_requests=5, phases=[]).validate()
    with pytest.raises(ValueError, match="phases"):
        ArrivalPlan(kind="diurnal", rate_rps=10.0, num_requests=5,
                    phases=[[0.5, 2.0], [0.2, 1.0]]).validate()
    with pytest.raises(ValueError, match="multiplier"):
        ArrivalPlan(kind="diurnal", rate_rps=10.0, num_requests=5,
                    phases=[[0.0, -1.0]]).validate()
    with pytest.raises(ValueError, match="rate_rps > 0"):
        ArrivalPlan(kind="poisson", rate_rps=-3.0,
                    num_requests=5).validate()
    with pytest.raises(ValueError, match="rate_rps > 0"):
        ArrivalPlan(kind="poisson", rate_rps=0.0,
                    num_requests=5).validate()
    with pytest.raises(ValueError, match="num_requests"):
        ArrivalPlan(kind="poisson", rate_rps=10.0,
                    num_requests=0).validate()
    with pytest.raises(ValueError, match="non-empty 'trace'"):
        ArrivalPlan(kind="replay", trace=[]).validate()
    with pytest.raises(ValueError, match="non-decreasing"):
        ArrivalPlan(kind="replay",
                    trace=[{"t": 1.0}, {"t": 0.5}]).validate()
    with pytest.raises(ValueError, match="prompt_len"):
        ArrivalPlan(kind="poisson", rate_rps=1.0, num_requests=1,
                    prompt_len=0).validate()
    with pytest.raises(ValueError, match="duty"):
        ArrivalPlan(kind="bursty", rate_rps=1.0, num_requests=1,
                    duty=1.5).validate()


def test_arrival_plan_roundtrip_and_determinism():
    plan = ArrivalPlan(kind="bursty", rate_rps=20.0, num_requests=30,
                       seed=5, prompt_len=[4, 9], output_len=3,
                       period_s=0.5, duty=0.25, factor=3.0)
    again = ArrivalPlan.from_dict(json.loads(plan.dumps()))
    assert again.to_dict() == plan.to_dict()
    a, b = plan.sample(), again.sample()
    assert [(r.arrival_s, r.prompt_len, r.output_len) for r in a] \
        == [(r.arrival_s, r.prompt_len, r.output_len) for r in b]
    assert all(r.output_len == 3 for r in a)
    assert all(4 <= r.prompt_len <= 9 for r in a)
    assert all(a[i].arrival_s <= a[i + 1].arrival_s
               for i in range(len(a) - 1))


def test_arrival_plan_fixture_loads():
    """The committed plan fixture parses via the @path convention and
    round-trips through its own wire format."""
    plan = ArrivalPlan.loads(f"@{DATA / 'arrival_poisson.json'}")
    assert plan.kind == "poisson" and plan.num_requests == 24
    assert plan.to_dict() == json.loads(
        (DATA / "arrival_poisson.json").read_text())
    assert len(plan.sample()) == 24


def test_splitmix64_matches_native_constants():
    """First draws of the shared splitmix64 (fault_plan.hpp:147) —
    golden values computed from the reference constants so a silent
    constant drift breaks loudly."""
    v1, s = splitmix64(0)
    v2, _ = splitmix64(s)
    assert v1 == 0xE220A8397B1DCDAF
    assert v2 == 0x6E789E6AA1B965F4


def test_seeded_rng_stream_is_pinned():
    """The draws every plan, prompt, router and tuner order is made of
    (``utils/seeded.Rng``): golden values from the generator as it was
    in ``serving/arrivals.py``, so moving or editing it cannot shift a
    committed plan's stream."""
    rng = Rng(7)
    assert rng.u01() == 0.3898297483912715
    assert rng.uniform_int(3, 11) == 9
    assert rng.expovariate(2.5) == 0.9240883913492564
    assert rng.uniform_int(5, 5) == 5          # no draw spent
    assert rng.state == 0xDAA66D2C7DDF7446
    assert Rng(-1).state == (1 << 64) - 1      # seeds wrap to 64 bits


def test_replay_plan_samples_trace_verbatim():
    plan = ArrivalPlan(kind="replay", trace=[
        {"t": 0.0, "prompt_len": 5, "output_len": 2},
        {"t": 0.25, "prompt_len": 7, "output_len": 3}])
    reqs = plan.sample()
    assert [(r.arrival_s, r.prompt_len, r.output_len) for r in reqs] \
        == [(0.0, 5, 2), (0.25, 7, 3)]


# ---------------------------------------------------------------------
# paged KV cache


def test_cache_allocate_append_free_and_stats():
    cc = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=8,
                     num_pages=8, page_size=4, max_seqs=2,
                     max_pages_per_seq=4)
    cache = PagedKVCache(cc)
    cache.allocate(0, 6)           # 2 pages
    assert cache.pages_in_use == 2
    pages0 = list(cache.block_tables[0, :2])
    assert len(set(pages0)) == 2
    cache.append(0, 5)
    st = cache.stats()
    assert st["pages_in_use"] == 2 and st["peak_pages_in_use"] == 2
    # 5 tokens in 8 allocated slots: 3 wasted
    assert st["fragmentation"] == round(3 / 8, 4)
    cache.allocate(1, 16)          # 4 pages
    assert cache.pages_in_use == 6
    with pytest.raises(ValueError, match="max_pages_per_seq"):
        PagedKVCache(cc).allocate(0, 100)
    tight = PagedKVCache(CacheConfig(
        num_layers=1, num_kv_heads=2, head_dim=8, num_pages=6,
        page_size=4, max_seqs=2, max_pages_per_seq=4))
    tight.allocate(0, 16)          # 4 of 6 pages
    with pytest.raises(CacheOOM, match="free"):
        tight.allocate(1, 16)      # needs 4, only 2 free
    cache.free(0)
    assert cache.pages_in_use == 4 and cache.lengths[0] == 0
    # freed pages are reusable
    cache.allocate(0, 16)
    assert cache.pages_in_use == 8
    assert not cache.can_fit(1)


def test_cache_append_past_reservation_refused():
    cc = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=8,
                     num_pages=8, page_size=4, max_seqs=1,
                     max_pages_per_seq=4)
    cache = PagedKVCache(cc)
    cache.allocate(0, 4)
    cache.append(0, 4)
    with pytest.raises(CacheOOM, match="reservation"):
        cache.append(0)


def test_gather_attention_matches_dense_reference():
    """The fallback path against plain masked attention on a
    contiguous copy of the same cache."""
    key = jax.random.key(0)
    b, hq, hkv, dh, pages, psize, pmax = 3, 4, 2, 8, 16, 4, 6
    q = jax.random.normal(key, (b, hq, dh))
    kp = jax.random.normal(jax.random.key(1), (hkv, pages, psize, dh))
    vp = jax.random.normal(jax.random.key(2), (hkv, pages, psize, dh))
    lengths = jnp.asarray([5, 9, 1], jnp.int32)
    pidx = jnp.asarray(
        np.arange(b * pmax).reshape(b, pmax) % pages, jnp.int32)
    got = paged_attention_decode(q, kp, vp, lengths, pidx,
                                 impl="gather")
    # dense reference per batch element
    for i in range(b):
        k = kp[:, pidx[i]].reshape(hkv, pmax * psize, dh)
        v = vp[:, pidx[i]].reshape(hkv, pmax * psize, dh)
        t = int(lengths[i])
        g = hq // hkv
        qi = q[i].reshape(hkv, g, dh)
        scores = jnp.einsum("hgd,htd->hgt", qi, k[:, :t])
        p = jax.nn.softmax(scores, axis=-1)
        ref = jnp.einsum("hgt,htd->hgd", p, v[:, :t]).reshape(hq, dh)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_sharded_paged_attention_matches_unsharded(eight_devices):
    """The shard_map KV-head sharding (SNIPPETS [3] recipe) on the CPU
    mesh: numerics identical to the unsharded fallback."""
    from dlnetbench_tpu.parallel.mesh import make_flat_mesh
    mesh = make_flat_mesh(devices=eight_devices[:2], axis="kv")
    q = jax.random.normal(jax.random.key(7), (3, 4, 8))
    kp = jax.random.normal(jax.random.key(8), (2, 16, 4, 8))
    vp = jax.random.normal(jax.random.key(9), (2, 16, 4, 8))
    lengths = jnp.asarray([5, 9, 2], jnp.int32)
    pidx = jnp.asarray(np.arange(18).reshape(3, 6) % 16, jnp.int32)
    ref = paged_attention_decode(q, kp, vp, lengths, pidx,
                                 impl="gather")
    got = sharded_paged_attention(mesh, impl="gather")(
        q, kp, vp, lengths, pidx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_paged_attention_block_parity_and_validation(eight_devices):
    """``pages_per_compute_block`` is a real parameter now (ISSUE 9
    satellite — the old inline ``min(pages, 8)`` hard-code): results
    are identical across 3 explicit block values (the knob sizes the
    kernel grid, never the math — the gather impl computes full
    attention regardless; ``chip_smoke.py``'s kernels phase holds the
    Pallas kernel to the same contract on the chip), it flows through
    ``sharded_paged_attention``, and a non-divisor is refused loudly on
    every impl."""
    from dlnetbench_tpu.parallel.mesh import make_flat_mesh

    q = jax.random.normal(jax.random.key(7), (3, 4, 8))
    kp = jax.random.normal(jax.random.key(8), (2, 16, 4, 8))
    vp = jax.random.normal(jax.random.key(9), (2, 16, 4, 8))
    lengths = jnp.asarray([5, 9, 2], jnp.int32)
    pidx = jnp.asarray(np.arange(18).reshape(3, 6) % 16, jnp.int32)
    ref = paged_attention_decode(q, kp, vp, lengths, pidx,
                                 impl="gather")
    for blk in (1, 2, 6):          # 3 divisors of pages_per_seq=6
        got = paged_attention_decode(q, kp, vp, lengths, pidx,
                                     impl="gather",
                                     pages_per_compute_block=blk)
        assert jnp.array_equal(got, ref), blk
    # flows through the sharded wrapper unchanged
    mesh = make_flat_mesh(devices=eight_devices[:2], axis="kv")
    got = sharded_paged_attention(mesh, impl="gather",
                                  pages_per_compute_block=2)(
        q, kp, vp, lengths, pidx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    # a non-divisor fails LOUD (experiment-knob convention), every impl
    with pytest.raises(ValueError, match="does not divide"):
        paged_attention_decode(q, kp, vp, lengths, pidx, impl="gather",
                               pages_per_compute_block=4)


# ---------------------------------------------------------------------
# decode path parity


def test_decode_path_matches_full_forward():
    """Prefill (uneven chunks) + single-token decode over the paged
    cache must greedy-decode the SAME tokens as iterated full forwards
    — the whole serving tier's correctness anchor."""
    from dlnetbench_tpu.serving import decode as D
    cfg = tiny_model()
    params = tfm.init_params(jax.random.key(0), cfg)
    cc = CacheConfig(num_layers=2, num_kv_heads=2, head_dim=8,
                     num_pages=16, page_size=4, max_seqs=2,
                     max_pages_per_seq=6)
    cache = PagedKVCache(cc)
    k, v = device_buffers(cc)
    prompt = np.array([5, 9, 3, 11, 7], np.int32)
    out_len = 6
    cache.allocate(0, len(prompt) + out_len)

    toks = list(prompt)
    for _ in range(out_len):
        logits = tfm.forward(params, jnp.asarray([toks]), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    ref = toks[len(prompt):]

    prefill = D.make_prefill_chunk(cfg, cc, chunk=3)
    decode = D.make_decode_step(cfg, cc)
    row = jnp.asarray(cache.block_tables[0])
    pos = 0
    nxt = None
    while pos < len(prompt):
        n = min(3, len(prompt) - pos)
        ch = np.zeros(3, np.int32)
        ch[:n] = prompt[pos:pos + n]
        k, v, nxt = prefill(params, k, v, jnp.asarray(ch),
                            jnp.int32(pos), jnp.int32(n), row)
        pos += n
        cache.append(0, n)
    got = [int(nxt)]
    last = int(nxt)
    bt = jnp.asarray(cache.block_tables)
    for _ in range(out_len - 1):
        k, v, nxt = decode(
            params, k, v,
            jnp.asarray(np.array([last, 0], np.int32)),
            jnp.asarray(np.array([int(cache.lengths[0]), 0], np.int32)),
            bt, jnp.asarray(np.array([True, False])))
        cache.append(0)
        last = int(np.asarray(nxt)[0])
        got.append(last)
    assert got == ref


def test_decode_rejects_unsupported_configs():
    from dlnetbench_tpu.serving.decode import check_config
    with pytest.raises(ValueError, match="gated"):
        check_config(tiny_model(gated=False, max_positions=32))
    # MoE models are SUPPORTED since ISSUE 15 (the per-expert batched
    # decode path); only non-gated MoE keeps refusing
    check_config(tiny_model(num_experts=4, top_k=2))


# ---------------------------------------------------------------------
# the continuous-batching engine


@pytest.fixture(scope="module")
def tiny_engine():
    """One compiled engine shared by the engine tests (compile is the
    expensive part; ``run`` resets all run state)."""
    from dlnetbench_tpu.serving.scheduler import Engine
    return Engine(tiny_model(), tiny_serving())


def test_engine_completes_all_requests(tiny_engine):
    plan = ArrivalPlan(kind="poisson", rate_rps=80.0, num_requests=12,
                       seed=3, prompt_len=[4, 8], output_len=[2, 5])
    reqs = plan.sample()
    completed, wall = tiny_engine.run(reqs)
    assert len(completed) == 12
    assert {c.rid for c in completed} == {r.rid for r in reqs}
    for c in completed:
        assert c.first_token_s >= c.arrival_s
        assert c.finish_s >= c.first_token_s
        assert c.ttft_ms >= 0 and c.e2e_ms >= c.ttft_ms
    assert wall > 0
    # every page returned to the free list
    assert tiny_engine.cache.pages_in_use == 0


def test_engine_inline_prefill_generates_same_tokens():
    """Inline-chunked prefill and separate-phase prefill are
    scheduling policies over the SAME math — the generated token
    streams must match request for request."""
    from dlnetbench_tpu.serving.scheduler import Engine
    plan = ArrivalPlan(kind="poisson", rate_rps=100.0, num_requests=6,
                       seed=11, prompt_len=[4, 9], output_len=3)
    cfg = tiny_model()
    params = tfm.init_params(jax.random.key(0), cfg)
    outs = {}
    for mode in ("separate", "inline"):
        eng = Engine(cfg, tiny_serving(prefill=mode, prefill_chunk=4),
                     params=params)
        tokens = {}
        orig = eng._maybe_finish

        def spy(slot, st, _tokens=tokens, _orig=orig):
            if st.generated >= st.req.output_len:
                _tokens.setdefault(st.req.rid, st.last_token)
            _orig(slot, st)

        eng._maybe_finish = spy
        completed, _ = eng.run(plan.sample())
        assert len(completed) == 6
        outs[mode] = tokens
    assert outs["separate"] == outs["inline"]


def test_engine_kv_sharded_matches_unsharded(eight_devices):
    """A kv_shard=2 ENGINE (not just the attention op): the AOT decode
    step is lowered against NamedSharding page pools and its outputs
    keep that sharding call after call — the op-level parity test
    missed exactly this (an AOT program never auto-reshards), so the
    engine-level run is the regression guard.  Token streams must match
    the unsharded engine's."""
    from dlnetbench_tpu.serving.scheduler import Engine
    plan = ArrivalPlan(kind="poisson", rate_rps=100.0, num_requests=5,
                       seed=4, prompt_len=[4, 8], output_len=3)
    cfg = tiny_model()
    params = tfm.init_params(jax.random.key(0), cfg)
    outs = {}
    for shard in (1, 2):
        eng = Engine(cfg, tiny_serving(kv_shard=shard), params=params)
        tokens = {}
        orig = eng._maybe_finish

        def spy(slot, st, _tokens=tokens, _orig=orig):
            if st.generated >= st.req.output_len:
                _tokens.setdefault(st.req.rid, st.last_token)
            _orig(slot, st)

        eng._maybe_finish = spy
        completed, _ = eng.run(plan.sample())
        assert len(completed) == 5
        # a second run through the same compiled engine exercises the
        # post-output sharding round trip
        completed2, _ = eng.run(plan.sample())
        assert len(completed2) == 5
        outs[shard] = tokens
    assert outs[1] == outs[2]


def test_engine_rejects_oversized_request(tiny_engine):
    plan = ArrivalPlan(kind="replay", trace=[
        {"t": 0.0, "prompt_len": 30, "output_len": 10}])
    with pytest.raises(ValueError, match="max_seq_len"):
        tiny_engine.run(plan.sample())


def test_serving_config_validation():
    with pytest.raises(ValueError, match="prefill"):
        tiny_serving(prefill="speculative").validate()
    with pytest.raises(ValueError, match="multiple"):
        tiny_serving(max_seq_len=30).validate()
    with pytest.raises(ValueError, match="divide"):
        tiny_serving(slots=3, world=2).validate()
    # a pool too small for even ONE max-length request would starve the
    # queue head forever (the admission gate can never pass) — refused
    # at config time, not discovered as a busy-spin
    with pytest.raises(ValueError, match="cannot hold"):
        tiny_serving(num_pages=4, max_seq_len=32,
                     page_size=4).validate()


# ---------------------------------------------------------------------
# fault composition (the satellite the record schema pays for)


def _fault_plan(events, policy="fail_fast"):
    from dlnetbench_tpu.faults.plan import FaultEvent, FaultPlan
    return FaultPlan(events=[FaultEvent(**e) for e in events],
                     policy=policy)


def test_delay_fault_inflates_p99_over_clean_baseline():
    """A straggler delay plan on the decode loop must show up as a
    measured p99/p50 amplification over the clean baseline — the same
    plan JSON that drives the training tier."""
    from dlnetbench_tpu.serving.scheduler import run_serving
    cfg = tiny_model()
    sc = tiny_serving(slo_ttft_ms=100.0, slo_tpot_ms=50.0)
    plan = ArrivalPlan(kind="poisson", rate_rps=100.0, num_requests=12,
                       seed=3, prompt_len=[4, 8], output_len=[3, 5])
    params = tfm.init_params(jax.random.key(0), cfg)
    clean = run_serving(cfg, sc, plan,
                        params=params).global_meta["serving"]
    fp = _fault_plan([{"kind": "delay", "magnitude_us": 20000,
                       "iteration": 0}])
    faulted_res = run_serving(cfg, sc, plan, fault_plan=fp,
                              params=params)
    faulted = faulted_res.global_meta["serving"]
    assert faulted["e2e_ms"]["p99"] > clean["e2e_ms"]["p99"]
    assert faulted["e2e_ms"]["p50"] > clean["e2e_ms"]["p50"]
    # amplification, not noise: the delay rides every engine step
    assert faulted["e2e_ms"]["p99"] > clean["e2e_ms"]["p99"] + 15.0
    g = faulted_res.global_meta
    assert g["fault_plan"]["events"][0]["kind"] == "delay"
    assert g["fault_injected_delay_us"] > 0


def test_crash_shrink_dips_and_recovers_goodput():
    """crash+shrink: capacity halves, in-flight work is redone on the
    rebuilt engine (recompile priced into recovery_ms), and the
    record's SLO-goodput timeline shows the dip AND the recovery —
    post-disruption arrivals meet the SLO again."""
    from dlnetbench_tpu.serving.scheduler import run_serving
    cfg = tiny_model()
    sc = tiny_serving(world=2, slots=4, slo_ttft_ms=300.0,
                      slo_tpot_ms=100.0)
    # two waves: the first saturates into the crash, the second lands
    # AFTER recovery so its requests meet the SLO again
    trace = [{"t": 0.01 * i, "prompt_len": 6, "output_len": 4}
             for i in range(10)]
    trace += [{"t": 4.0 + 0.05 * i, "prompt_len": 6, "output_len": 4}
              for i in range(6)]
    plan = ArrivalPlan(kind="replay", trace=trace)
    fp = _fault_plan([{"kind": "crash", "ranks": [1], "iteration": 4}],
                     policy="shrink")
    res = run_serving(cfg, sc, plan, fault_plan=fp)
    g = res.global_meta
    assert g["degraded_world"] == [0]
    assert g["degraded_slots"] == 2
    assert g["detection_ms"] >= 0
    assert g["recovery_ms"] > 0        # the rebuild+recompile is priced
    assert res.num_runs == len(trace)  # every request still completes
    tl = g["serving"]["goodput_timeline"]
    fracs = [w["goodput_frac"] for w in tl if w["completed"]]
    assert min(fracs) < 1.0            # the dip (SLO missed mid-crash)
    assert fracs[-1] == 1.0            # the recovery arc closes
    # the record flows through emit/parser like any training record
    from dlnetbench_tpu.metrics.emit import result_to_record
    from dlnetbench_tpu.metrics.parser import validate_record
    rec = result_to_record(res)
    validate_record(rec)
    assert rec["global"]["degraded_world"] == [0]
    assert len(rec["ranks"]) == 1      # survivor mesh rows only


def test_fail_fast_crash_propagates():
    from dlnetbench_tpu.faults.inject import RankFailure
    from dlnetbench_tpu.serving.scheduler import run_serving
    plan = ArrivalPlan(kind="poisson", rate_rps=200.0, num_requests=8,
                       seed=0, prompt_len=4, output_len=4)
    fp = _fault_plan([{"kind": "crash", "ranks": [0], "iteration": 2}])
    with pytest.raises(RankFailure):
        run_serving(tiny_model(), tiny_serving(), plan, fault_plan=fp)


# ---------------------------------------------------------------------
# the record pathway (fixtures committed; schema v2 unchanged)


def test_serving_record_fixture_roundtrip():
    """The committed serving record flows through parser -> merge ->
    serving_summary without special-casing, and its arrival plan
    re-validates through the plan schema."""
    from dlnetbench_tpu.analysis.bandwidth import serving_summary
    from dlnetbench_tpu.metrics.merge import merge_records
    from dlnetbench_tpu.metrics.parser import (load_records,
                                               records_to_dataframe,
                                               validate_record)
    records = load_records(DATA / "record_serving.jsonl")
    assert len(records) == 1
    rec = records[0]
    assert rec["version"] == 2
    validate_record(rec)
    srv = rec["global"]["serving"]
    ArrivalPlan.from_dict(rec["global"]["arrival_plan"])  # re-validates
    # per-request timers ride like any timer: num_runs long, with v2
    # band summaries that describe them
    row = rec["ranks"][0]
    assert len(row["ttft"]) == rec["num_runs"] == srv["completed"]
    assert row["summary"]["ttft"]["n"] == rec["num_runs"]

    df = records_to_dataframe(records)
    for col in ("serving_offered_rps", "serving_ttft_p99_ms",
                "serving_goodput_frac", "ttft", "tpot", "e2e"):
        assert col in df.columns, col
    assert len(df) == rec["num_runs"]

    merged = merge_records(records)   # single-process merge: identity
    validate_record(merged)
    ss = serving_summary([merged])
    assert len(ss) == 1
    got = ss.iloc[0]
    assert got["offered_rps"] == srv["offered_rps"]
    assert got["ttft_p99_ms"] == srv["ttft_ms"]["p99"]
    assert got["goodput_frac"] == srv["goodput_frac"]
    assert got["fault"] == "-" and math.isnan(got["detection_ms"])


def test_v1_and_no_serving_records_still_parse():
    """Pre-serving records keep parsing and contribute nothing to the
    serving summary; a mixed-version merge is still refused."""
    from dlnetbench_tpu.analysis.bandwidth import serving_summary
    from dlnetbench_tpu.metrics.merge import merge_records
    from dlnetbench_tpu.metrics.parser import (load_records,
                                               records_to_dataframe,
                                               validate_record)
    v1 = load_records(DATA / "record_v1.jsonl")
    for rec in v1:
        validate_record(rec)
    df = records_to_dataframe(v1)
    assert "serving_offered_rps" not in df.columns
    assert serving_summary(v1).empty
    serving = load_records(DATA / "record_serving.jsonl")
    with pytest.raises(ValueError):
        merge_records([serving[0], dict(v1[0], process=1)])


def test_mixed_plan_merge_refused():
    """Two serving records with DIFFERENT arrival plans are different
    runs — the merge must refuse them like mismatched fault plans."""
    import copy

    from dlnetbench_tpu.metrics.merge import merge_records
    from dlnetbench_tpu.metrics.parser import load_records
    rec = load_records(DATA / "record_serving.jsonl")[0]
    other = copy.deepcopy(rec)
    other["process"] = 1
    other["global"]["num_processes"] = 2
    rec = copy.deepcopy(rec)
    rec["global"]["num_processes"] = 2
    other["global"]["arrival_plan"]["rate_rps"] = 999.0
    with pytest.raises(ValueError, match="arrival_plan"):
        merge_records([rec, other])


@pytest.mark.slow
@pytest.mark.decode
def test_bench_serving_decode_runs_end_to_end():
    """The real aux line: three compiled engines (1-step, fused
    N-step, N-step + speculative), 3 interleaved rounds — heavier than
    a schema lock, so it rides the slow lane.  The ISSUE 11 acceptance
    pieces must be present and true: exact token parity across
    variants, and the dispatch decomposition in the A/B blocks."""
    import bench
    line = bench._bench_serving_decode()
    assert line is not None and line["unit"] == "ms"
    assert line["n"] == 3 and line["value"] > 0
    assert line["p99_ms"]["band"][0] <= line["value"] \
        <= line["p99_ms"]["band"][1]
    assert line["token_parity"] is True
    assert line["multi_step"]["steps_per_dispatch"]["value"] > 1.0
    assert line["speculative"]["spec"]["acceptance_rate"]["n"] == 3
    flip = line["attribution_flip"]
    assert flip["one_step_host_frac"]["n"] == 3
    assert flip["multi_step_host_frac"]["value"] \
        < flip["one_step_host_frac"]["value"]


# ---------------------------------------------------------------------
# serving metrics units


def test_percentiles_and_slo_goodput():
    from dlnetbench_tpu.serving import metrics as M
    vals = [float(v) for v in range(1, 101)]
    assert M.percentile(vals, 50) == 50.0
    assert M.percentile(vals, 99) == 99.0
    assert math.isnan(M.percentile([], 50))
    c_ok = M.Completed(rid=0, arrival_s=0.0, admitted_s=0.0,
                       first_token_s=0.05, finish_s=0.2,
                       prompt_len=4, output_len=4)
    c_late = M.Completed(rid=1, arrival_s=0.0, admitted_s=0.0,
                         first_token_s=0.5, finish_s=0.9,
                         prompt_len=4, output_len=4)
    assert M.meets_slo(c_ok, slo_ttft_ms=100, slo_tpot_ms=100)
    assert not M.meets_slo(c_late, slo_ttft_ms=100, slo_tpot_ms=100)
    # single-token outputs are judged on TTFT alone (no TPOT sample)
    c_one = M.Completed(rid=2, arrival_s=0.0, admitted_s=0.0,
                        first_token_s=0.05, finish_s=0.05,
                        prompt_len=4, output_len=1)
    assert math.isnan(c_one.tpot_ms)
    assert M.meets_slo(c_one, slo_ttft_ms=100, slo_tpot_ms=0.001)
    # an outage window with zero completions reports null, never a
    # fabricated 1.0 (the crash-dip channel must show the outage)
    tl = M.goodput_timeline([c_ok, M.Completed(
        rid=3, arrival_s=0.0, admitted_s=0.0, first_token_s=1.6,
        finish_s=1.7, prompt_len=4, output_len=4)],
        slo_ttft_ms=100, slo_tpot_ms=100, window_s=0.5)
    assert tl[0]["completed"] == 1 and tl[0]["goodput_frac"] == 1.0
    assert tl[1]["completed"] == 0 and tl[1]["goodput_frac"] is None
    assert tl[-1]["completed"] == 1 and tl[-1]["goodput_frac"] == 0.0


# ------------------------------- windowed sparse prefill (ISSUE 10)

longcontext = pytest.mark.longcontext


def _run_prefill(cfg, cc, prompt, chunk=3):
    from dlnetbench_tpu.serving import decode as D
    params = tfm.init_params(jax.random.key(0), cfg)
    cache = PagedKVCache(cc)
    k, v = device_buffers(cc)
    cache.allocate(0, len(prompt) + 1)
    prefill = jax.jit(D.make_prefill_chunk(cfg, cc, chunk))
    row = jnp.asarray(cache.block_tables[0])
    pos, nxt = 0, None
    while pos < len(prompt):
        n = min(chunk, len(prompt) - pos)
        ch = np.zeros(chunk, np.int32)
        ch[:n] = prompt[pos:pos + n]
        k, v, nxt = prefill(params, k, v, jnp.asarray(ch),
                            jnp.int32(pos), jnp.int32(n), row)
        pos += n
    return int(nxt)


@longcontext
def test_windowed_prefill_token_parity_with_dense():
    """ISSUE 10 satellite: the sliding-window prefill gathers only the
    window's pages, yet (a) with a window covering the whole prompt it
    reproduces the dense path's token exactly, and (b) with a NARROW
    window it reproduces the windowed full forward (the dense-masked
    reference) — same mask builders, same semantics."""
    import dataclasses
    cc = CacheConfig(num_layers=2, num_kv_heads=2, head_dim=8,
                     num_pages=16, page_size=4, max_seqs=2,
                     max_pages_per_seq=6)
    prompt = np.asarray([5, 9, 3, 11, 7, 2, 13, 1, 8, 4, 10, 6,
                         12, 14], np.int32)
    cfg = tiny_model()
    dense_tok = _run_prefill(cfg, cc, prompt)
    big = dataclasses.replace(cfg, attention_window=cc.max_seq_len)
    assert _run_prefill(big, cc, prompt) == dense_tok

    win = dataclasses.replace(cfg, attention_window=6)
    got = _run_prefill(win, cc, prompt)
    params = tfm.init_params(jax.random.key(0), cfg)
    ref_cfg = dataclasses.replace(win, seq_len=len(prompt),
                                  attention_impl="xla")
    logits = tfm.forward(params, jnp.asarray(prompt)[None], ref_cfg)
    assert got == int(jnp.argmax(logits[0, -1]))


@longcontext
def test_windowed_prefill_single_chunk_and_page_aligned_window():
    """Window edge shapes: a window equal to one page and a chunk
    larger than the remaining prompt (padding tail) still match the
    dense-masked reference."""
    import dataclasses
    cc = CacheConfig(num_layers=2, num_kv_heads=2, head_dim=8,
                     num_pages=16, page_size=4, max_seqs=2,
                     max_pages_per_seq=6)
    prompt = np.asarray([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], np.int32)
    win = tiny_model(attention_window=4)
    got = _run_prefill(win, cc, prompt, chunk=8)
    params = tfm.init_params(jax.random.key(0), win)
    ref_cfg = dataclasses.replace(win, seq_len=len(prompt),
                                  attention_impl="xla")
    logits = tfm.forward(params, jnp.asarray(prompt)[None], ref_cfg)
    assert got == int(jnp.argmax(logits[0, -1]))


@longcontext
def test_serving_rejects_segment_masks():
    from dlnetbench_tpu.serving.decode import check_config
    with pytest.raises(ValueError, match="segment"):
        check_config(tiny_model(attention_seg_avg=16))


@longcontext
def test_decode_step_refuses_window_configs():
    """The decode step attends the FULL cache (the paged kernel has no
    lower-bound mask): a window config must fail loud at construction
    instead of silently generating under different attention semantics
    than the windowed prefill/training path."""
    from dlnetbench_tpu.serving import decode as D
    cc = CacheConfig(num_layers=2, num_kv_heads=2, head_dim=8,
                     num_pages=16, page_size=4, max_seqs=2,
                     max_pages_per_seq=6)
    cfg = tiny_model(attention_window=6)
    with pytest.raises(ValueError, match="window"):
        D.make_decode_step(cfg, cc)
    # the prefill side stays windowed (the ISSUE 10 satellite)
    D.make_prefill_chunk(cfg, cc, chunk=4)
