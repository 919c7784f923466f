"""Serving density (ISSUE 12): quantized paged KV (int8/fp8 pools +
per-page scales, write/dequant parity vs the bf16 cache under the
stated tolerance bars, the dequantizing Pallas kernel) and
cross-request prefix sharing (refcounted allocator, radix trie,
copy-on-write, lossless engine runs, record globals)."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlnetbench_tpu.models import transformer as tfm
from dlnetbench_tpu.serving import kv_cache as KV
from dlnetbench_tpu.serving.arrivals import ArrivalPlan, Request
from dlnetbench_tpu.serving.kv_cache import (CacheConfig, CacheOOM,
                                             PagedKVCache,
                                             QUANT_DECODE_TOL,
                                             device_buffers,
                                             paged_attention_decode,
                                             pages_for_pool_bytes,
                                             quant_write_span)

DATA = Path(__file__).parent / "data"

pytestmark = [pytest.mark.density, pytest.mark.serving]


def tiny_model(**over) -> tfm.TransformerConfig:
    kw = dict(vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2,
              ff_dim=64, num_layers=2, seq_len=64, gated=True,
              max_positions=0, dtype="float32")
    kw.update(over)
    return tfm.TransformerConfig(**kw)


def tiny_serving(**over):
    from dlnetbench_tpu.serving.scheduler import ServingConfig
    kw = dict(slots=3, page_size=4, num_pages=40, max_seq_len=32,
              prefill_chunk=4, slo_ttft_ms=200.0, slo_tpot_ms=100.0,
              warmup_requests=0)
    kw.update(over)
    return ServingConfig(**kw)


def _cache_cfg(**over) -> CacheConfig:
    kw = dict(num_layers=1, num_kv_heads=2, head_dim=8, num_pages=8,
              page_size=4, max_seqs=2, max_pages_per_seq=4)
    kw.update(over)
    return CacheConfig(**kw)


# ---------------------------------------------------------------------
# config validation + pool-bytes accounting (satellite 1)


def test_cache_config_cache_dtype_validation():
    with pytest.raises(ValueError, match="unknown cache_dtype"):
        _cache_cfg(cache_dtype="int4").validate()
    for cd in KV.CACHE_DTYPES:
        assert _cache_cfg(cache_dtype=cd).validate().cache_dtype == cd
    assert not _cache_cfg().quantized
    assert _cache_cfg(cache_dtype="int8").quantized
    assert _cache_cfg(cache_dtype="fp8").quant_fmt == "float8"


def test_pool_bytes_counts_scale_arrays():
    """The "same pool bytes" axis is honest only if the quantized
    config's scale arrays are priced in: page_bytes = k+v payload at
    the storage dtype PLUS 2 * L * Hkv f32 scales per page."""
    dense = _cache_cfg()                       # f32 payload
    i8 = _cache_cfg(cache_dtype="int8")
    payload_f32 = 2 * 1 * 2 * 4 * 8 * 4
    payload_i8 = 2 * 1 * 2 * 4 * 8 * 1
    scales = 2 * 1 * 2 * 4
    assert dense.page_bytes == payload_f32
    assert i8.page_bytes == payload_i8 + scales
    assert i8.pool_bytes == 8 * i8.page_bytes
    # a byte budget converts to MORE pages for the quantized config
    pages = pages_for_pool_bytes(dense.pool_bytes, i8)
    assert pages > dense.num_pages
    assert pages * i8.page_bytes <= dense.pool_bytes


def test_one_request_guard_covers_quantized_configs():
    """The loud-refusal guard (pool must hold one max-seq request)
    fires on a quantized config exactly like a dense one — the
    byte-budget path can produce too few pages and must fail loud,
    not starve the admission gate."""
    with pytest.raises(ValueError, match="cannot hold even"):
        _cache_cfg(num_pages=3, cache_dtype="int8").validate()
    with pytest.raises(ValueError, match="cannot hold even"):
        tiny_serving(num_pages=3, cache_dtype="int8").validate()


def test_serving_config_cache_knobs():
    from dlnetbench_tpu.serving.scheduler import ServingConfig
    with pytest.raises(ValueError, match="unknown cache_dtype"):
        tiny_serving(cache_dtype="nf4").validate()
    with pytest.raises(ValueError, match="bf16 cache only"):
        tiny_serving(cache_dtype="int8", speculative=True,
                     multi_step_n=2).validate()
    cfg = tiny_serving(cache_dtype="fp8", prefix_sharing=True)
    assert cfg.validate() is cfg


def test_cli_serve_cache_dtype_knob():
    """cli serve grew --cache_dtype/--prefix_sharing; a bad dtype is
    an argparse usage error, never an engine traceback."""
    from dlnetbench_tpu.cli import main
    with pytest.raises(SystemExit) as e:
        main(["serve", "--arrival", '{"kind": "poisson"}',
              "--cache_dtype", "int4"])
    assert e.value.code == 2


# ---------------------------------------------------------------------
# quantized write + dequant read parity (the tolerance bars)


def _write_streams(cache_dtype: str, steps: int = 10, seed: int = 0):
    """Write one seeded decode-style token stream into a dense AND a
    quantized pool (the engine's own write paths); returns both pool
    sets + lengths/block tables."""
    cc_d = _cache_cfg(head_dim=16)
    cc_q = _cache_cfg(head_dim=16, cache_dtype=cache_dtype)
    kd, vd = device_buffers(cc_d)
    kq, vq, ks, vs = device_buffers(cc_q)
    fmt = cc_q.quant_fmt
    bt = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4))
    rng = np.random.RandomState(seed)
    for t in range(steps):
        knew = jnp.asarray(rng.randn(2, 1, 2, 16).astype(np.float32))
        vnew = jnp.asarray(rng.randn(2, 1, 2, 16).astype(np.float32))
        pos = jnp.full((2,), t, jnp.int32)
        ok = jnp.ones((2, 1), bool)
        pid = jnp.take_along_axis(bt, (pos // 4)[:, None], 1)[:, 0]
        kd = kd.at[0, :, pid, pos % 4, :].set(knew[:, 0], mode="drop")
        vd = vd.at[0, :, pid, pos % 4, :].set(vnew[:, 0], mode="drop")
        kq, ks = quant_write_span(kq, ks, 0, knew, pos, ok, bt,
                                  fmt=fmt, page_size=4, num_pages=8)
        vq, vs = quant_write_span(vq, vs, 0, vnew, pos, ok, bt,
                                  fmt=fmt, page_size=4, num_pages=8)
    q = jnp.asarray(rng.randn(2, 4, 16).astype(np.float32)) * 16**-0.5
    lengths = jnp.asarray([steps, steps - 1], jnp.int32)
    return (kd, vd), (kq, vq, ks, vs), q, lengths, bt, fmt


@pytest.mark.parametrize("cache_dtype", ["int8", "fp8"])
def test_quant_decode_parity_within_stated_bar(cache_dtype):
    """Greedy-decode parity vs the bf16 cache, per recipe: the
    dequantizing gather attention over a quantized pool written by the
    engine's own write path stays inside the STATED tolerance bar
    (kv_cache.QUANT_DECODE_TOL) — the bar the bench line and the
    committed study enforce too."""
    (kd, vd), (kq, vq, ks, vs), q, lengths, bt, fmt = _write_streams(
        cache_dtype)
    ref = paged_attention_decode(q, kd[0], vd[0], lengths, bt,
                                 impl="gather")
    got = paged_attention_decode(q, kq[0], vq[0], lengths, bt,
                                 k_scale=ks[0], v_scale=vs[0], fmt=fmt,
                                 impl="gather")
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err <= QUANT_DECODE_TOL[cache_dtype], (cache_dtype, err)
    # and the error is genuinely nonzero — the quant path really ran
    assert err > 0.0


def test_quant_write_masks_stale_page_content():
    """Page reuse: the fresh-amax requant masks rows beyond the
    sequence's own content, so a huge stale value in a reused page can
    never inflate the scale (silent precision loss for the real
    rows)."""
    cc = _cache_cfg(cache_dtype="int8", max_seqs=1, num_pages=4)
    kq, vq, ks, vs = device_buffers(cc)
    bt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    # poison page 0 with a huge stale row + a huge stale scale
    kq = kq.at[0, :, 0, 3, :].set(127)
    ks = ks.at[0, :, 0].set(1e6)
    new = jnp.ones((1, 1, 2, 8), jnp.float32)
    kq, ks = quant_write_span(kq, ks, 0, new, jnp.zeros((1,), jnp.int32),
                              jnp.ones((1, 1), bool), bt, fmt="int8",
                              page_size=4, num_pages=4)
    # the fresh scale reflects ONLY the new row (amax 1.0), and the
    # stale row was zeroed by the rewrite
    assert float(ks[0, 0, 0]) == pytest.approx(1.0 / 127.0, rel=1e-5)
    deq = np.asarray(kq[0, :, 0], np.float32) * float(ks[0, 0, 0])
    np.testing.assert_allclose(deq[:, 0, :], 1.0, rtol=2e-2)
    assert np.all(deq[:, 3, :] == 0.0)


def test_quant_kernel_matches_dequant_gather():
    """The Pallas quantized paged-attention kernel (interpret mode on
    the CPU mesh — the pallas_common backend split) against the
    dequantizing gather fallback: same masked softmax to f32 rounding,
    block-size invariant, non-divisor refused loudly."""
    from dlnetbench_tpu.ops.paged_attention_quant import \
        quant_paged_attention
    (_, _), (kq, vq, ks, vs), q, lengths, bt, fmt = _write_streams(
        "int8")
    ref = paged_attention_decode(q, kq[0], vq[0], lengths, bt,
                                 k_scale=ks[0], v_scale=vs[0], fmt=fmt,
                                 impl="gather")
    for ppcb in (1, 2, 4):
        got = quant_paged_attention(q, kq[0], vq[0], ks[0], vs[0],
                                    lengths, bt, fmt=fmt,
                                    pages_per_compute_block=ppcb)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="does not divide"):
        quant_paged_attention(q, kq[0], vq[0], ks[0], vs[0], lengths,
                              bt, fmt=fmt, pages_per_compute_block=3)
    with pytest.raises(ValueError, match="unknown fmt"):
        quant_paged_attention(q, kq[0], vq[0], ks[0], vs[0], lengths,
                              bt, fmt="int4", pages_per_compute_block=1)


def test_quant_tuning_site_is_its_own_key():
    """pages_per_compute_block consults op "paged_attention_quant"
    with the format in the key (ISSUE 12: a dense optimum must never
    answer a quantized consult) — and an explicit non-divisor fails
    loud on the gather path too."""
    from dlnetbench_tpu.tuning.params import (paged_attention_key,
                                              paged_attention_quant_key)
    kd = paged_attention_key(4, 4, 2, 4, 2, 16)
    kq8 = paged_attention_quant_key(4, 4, 2, 4, 2, 16, "int8")
    kf8 = paged_attention_quant_key(4, 4, 2, 4, 2, 16, "float8")
    assert kd != kq8 and kq8 != kf8
    (_, _), (kq, vq, ks, vs), q, lengths, bt, fmt = _write_streams(
        "int8", steps=4)
    with pytest.raises(ValueError, match="does not divide"):
        paged_attention_decode(q, kq[0], vq[0], lengths, bt,
                               k_scale=ks[0], v_scale=vs[0], fmt=fmt,
                               impl="gather", pages_per_compute_block=3)


# ---------------------------------------------------------------------
# engine end-to-end per cache dtype


def _run_engine(cfg, mc, params, reqs):
    from dlnetbench_tpu.serving.scheduler import Engine
    eng = Engine(mc, cfg, params=params)
    done, wall = eng.run(reqs)
    return eng, done


def test_engine_bf16_is_the_default_and_multi_step_quant_parity():
    """cache_dtype="bf16" IS the pre-ISSUE-12 engine (same program
    signature, no scale buffers), and on a quantized cache the fused
    N-step loop emits exactly the 1-step quantized engine's stream
    (same write sequence, so parity holds per cache dtype)."""
    mc = tiny_model()
    params = tfm.init_params(jax.random.key(0), mc)
    plan = ArrivalPlan(kind="poisson", rate_rps=500.0, num_requests=5,
                       seed=2, prompt_len=[5, 9], output_len=[3, 6])
    reqs = plan.sample()
    eng_d, done_d = _run_engine(tiny_serving(), mc, params, reqs)
    assert eng_d.k_scale is None and len(eng_d._pool_argnums) == 2
    eng_b, _ = _run_engine(tiny_serving(cache_dtype="bf16"), mc,
                           params, reqs)
    assert eng_b.token_streams == eng_d.token_streams
    for cd in ("int8", "fp8"):
        eng_1, done_1 = _run_engine(tiny_serving(cache_dtype=cd), mc,
                                    params, reqs)
        assert len(done_1) == len(reqs)
        assert eng_1.k_scale is not None
        eng_n, _ = _run_engine(tiny_serving(cache_dtype=cd,
                                            multi_step_n=4), mc,
                               params, reqs)
        assert eng_n.token_streams == eng_1.token_streams, cd


def test_quant_record_stamps_cache_dtype():
    from dlnetbench_tpu.serving.scheduler import run_serving
    mc = tiny_model()
    plan = ArrivalPlan(kind="poisson", rate_rps=400.0, num_requests=3,
                       seed=0, prompt_len=6, output_len=3)
    res = run_serving(mc, tiny_serving(cache_dtype="int8",
                                       warmup_requests=1), plan)
    g = res.global_meta
    assert g["kv_cache_dtype"] == "int8"
    assert g["serving_config"]["cache_dtype"] == "int8"
    assert g["serving"]["kv_cache"]["cache_dtype"] == "int8"
    assert g["serving"]["kv_cache"]["pool_bytes"] > 0
    assert g["serving"]["admitted_concurrency_peak"] >= 1


def test_merge_refuses_mismatched_cache_dtype():
    """kv_cache_dtype is a COMPARABLE global: records from
    differently-quantized caches are different runs and must refuse to
    merge, exactly like mismatched fault plans."""
    from dlnetbench_tpu.metrics.emit import emit_result
    from dlnetbench_tpu.metrics.merge import merge_records
    from dlnetbench_tpu.serving.scheduler import run_serving
    mc = tiny_model()
    plan = ArrivalPlan(kind="poisson", rate_rps=400.0, num_requests=2,
                       seed=0, prompt_len=6, output_len=2)
    recs = []
    for cd in ("bf16", "int8"):
        res = run_serving(mc, tiny_serving(cache_dtype=cd,
                                           warmup_requests=0), plan)
        recs.append(emit_result(res))
    recs[1]["process"] = 1
    recs[1]["global"]["num_processes"] = 2
    recs[0]["global"]["num_processes"] = 2
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        merge_records(recs)


# ---------------------------------------------------------------------
# refcounted allocator + trie + COW (satellite 2: the property test)


def test_admission_plan_charges_only_unshared_pages():
    cc = _cache_cfg(num_pages=16, max_seqs=4, max_pages_per_seq=4)
    cache = PagedKVCache(cc)
    prompt_a = np.arange(10, dtype=np.int32)       # 10 tokens
    # owner admits cold: full charge
    plan_a = cache.plan_admission(12, prompt_a)
    assert plan_a.need_pages == 3 and plan_a.shared_tokens == 0
    cache.admit(0, plan_a)
    cache.append(0, 10)      # prompt prefilled
    cache.publish(0, prompt_a)
    # same 8-token (2-page) prefix, different tail: 2 pages shared by
    # reference, partial boundary page COW-charged
    prompt_b = np.concatenate([prompt_a[:9], [99, 98, 97]]).astype(
        np.int32)
    plan_b = cache.plan_admission(12, prompt_b)
    # match capped at prompt_len-1 = 11 -> 9 matched tokens (8 full +
    # 1 partial row of A's page 2)
    assert plan_b.shared_tokens == 9
    assert len(plan_b.shared_pages) == 2
    assert plan_b.cow_src is not None and plan_b.cow_rows == 1
    assert plan_b.need_pages == 3 - 2  # only the unshared page count
    cow_dst = cache.admit(1, plan_b)
    assert cow_dst is not None and cow_dst != plan_b.cow_src
    # shared pages now have refcount 2; block tables alias them
    for p in plan_b.shared_pages:
        assert cache.refcount(p) == 2
    assert list(cache.block_tables[1, :2]) == plan_b.shared_pages
    # B's boundary page is PRIVATE — never the shared physical page
    assert cache.block_tables[1, 2] == cow_dst
    # lengths start at the shared token count (content already cached)
    assert cache.lengths[1] == 9
    # growing into the COW'd page is fine...
    cache.append(1, 3)
    # ...but a write into a page with refcount > 1 is refused loudly
    cache.lengths[1] = 7     # force the next append into shared page 1
    with pytest.raises(RuntimeError, match="shared page"):
        cache.append(1, 2)


def test_refcount_frees_on_last_reader_and_trie_drops():
    cc = _cache_cfg(num_pages=8, max_seqs=3, max_pages_per_seq=4)
    cache = PagedKVCache(cc)
    prompt = np.arange(9, dtype=np.int32)
    cache.admit(0, cache.plan_admission(9, prompt))
    cache.append(0, 9)
    cache.publish(0, prompt)
    plan = cache.plan_admission(9, prompt)
    assert plan.shared_tokens == 8 and len(plan.shared_pages) == 2
    cache.admit(1, plan)
    shared = plan.shared_pages
    used_before = cache.pages_in_use
    # owner evicts: shared pages stay (B still reads them)
    cache.free(0)
    for p in shared:
        assert cache.refcount(p) == 1
    assert cache.pages_in_use < used_before
    # B evicts: refcount hits zero, pages return to the free list and
    # leave the trie — a third request can no longer share them
    cache.free(1)
    for p in shared:
        assert cache.refcount(p) == 0
    plan2 = cache.plan_admission(9, prompt)
    assert plan2.shared_tokens == 0 and plan2.need_pages == 3
    assert cache.pages_in_use == 0


def test_allocator_refcount_cow_property():
    """Seeded property test (ISSUE 12 satellite, mirroring the
    device_state round-trip property): arbitrary interleavings of
    admit (with and without shared prefixes), prefill+publish,
    append-past-divergence, and evict — asserting no page leaks, no
    double frees, refcounts hitting zero exactly when the last reader
    evicts, and block tables never aliasing a written page."""
    rng = np.random.RandomState(7)
    cc = _cache_cfg(num_pages=24, max_seqs=4, max_pages_per_seq=4,
                    page_size=4)
    cache = PagedKVCache(cc)
    prompts = {}      # slot -> prompt tokens
    shared_full = {}  # slot -> full pages shared at admit
    # a small pool of system prompts drives real prefix collisions
    pool = [rng.randint(0, 50, size=8).astype(np.int32)
            for _ in range(2)]
    for step in range(300):
        op = rng.randint(0, 3)
        free_slots = [i for i in range(cc.max_seqs)
                      if not cache._pages_of[i]]
        busy = [i for i in range(cc.max_seqs) if cache._pages_of[i]]
        if op == 0 and free_slots:
            slot = free_slots[0]
            pre = pool[rng.randint(0, len(pool))]
            tail = rng.randint(50, 64, size=rng.randint(2, 7)).astype(
                np.int32)
            prompt = (np.concatenate([pre, tail])
                      if rng.rand() < 0.7 else tail)
            n_out = rng.randint(1, 5)
            total = len(prompt) + n_out
            if total > cc.max_seq_len:
                continue
            plan = cache.plan_admission(
                total, prompt if rng.rand() < 0.8 else None)
            if plan.need_pages > cache.free_pages:
                continue
            cache.admit(slot, plan)
            prompts[slot] = prompt
            shared_full[slot] = len(plan.shared_pages)
            # prefill the rest of the prompt, then publish
            cache.append(slot, len(prompt)
                         - int(plan.shared_tokens))
            cache.publish(slot, prompt)
        elif op == 1 and busy:
            slot = busy[rng.randint(0, len(busy))]
            # append past divergence (a decode token) while room holds
            room = (len(cache._pages_of[slot]) * cc.page_size
                    - int(cache.lengths[slot]))
            if room > 0:
                cache.append(slot)
        elif op == 2 and busy:
            slot = busy[rng.randint(0, len(busy))]
            cache.free(slot)
            prompts.pop(slot, None)
            shared_full.pop(slot, None)
        # ---- invariants, every step --------------------------------
        refs = np.zeros(cc.num_pages, np.int64)
        for i in range(cc.max_seqs):
            for p in cache._pages_of[i]:
                refs[p] += 1
        # refcounts == live block-table references, never negative
        assert np.array_equal(refs, np.asarray(cache._ref)), step
        # no leaks / double frees: the free list and the held pages
        # partition the physical pool exactly
        free_set = set(cache._free)
        assert len(free_set) == len(cache._free), "double free"
        held = {p for i in range(cc.max_seqs)
                for p in cache._pages_of[i]}
        assert free_set.isdisjoint(held), "freed page still held"
        assert free_set == set(range(cc.num_pages)) - held, step
        # block tables never alias a WRITTEN page: a page with
        # refcount > 1 can only be a FULL prompt page of each holder
        # (only prompt pages enter the trie; the partial boundary page
        # and every decode page are private — COW replaced the shared
        # one at admission, so writes land on refcount-1 pages only)
        for i in range(cc.max_seqs):
            if i not in prompts:
                continue
            full_prompt_pages = len(prompts[i]) // cc.page_size
            for col, p in enumerate(cache._pages_of[i]):
                if refs[p] > 1:
                    assert col < full_prompt_pages, (step, i, col)
    # drain everything: the pool must come back whole
    for i in range(cc.max_seqs):
        if cache._pages_of[i]:
            cache.free(i)
    assert cache.free_pages == cc.num_pages
    assert not cache.trie._node_of_page
    assert all(r == 0 for r in cache._ref)


# ---------------------------------------------------------------------
# prefix sharing: lossless engine runs + stats


def _prefix_plan(**over):
    kw = dict(kind="poisson", rate_rps=500.0, num_requests=8, seed=3,
              prompt_len=[10, 14], output_len=[3, 5],
              shared_prefix_len=8, prefix_pool=2)
    kw.update(over)
    return ArrivalPlan(**kw)


def test_prefix_sharing_engine_lossless_with_hits():
    """The acceptance lock: a prefix-sharing engine run produces
    TOKEN-IDENTICAL outputs to a non-sharing run on the same plan,
    with measured hits and bytes saved (page-aligned prefix + chunk
    dividing it — the stated exactness conditions)."""
    mc = tiny_model()
    params = tfm.init_params(jax.random.key(0), mc)
    plan = _prefix_plan()
    reqs = plan.sample()
    eng_off, done_off = _run_engine(tiny_serving(), mc, params, reqs)
    eng_on, done_on = _run_engine(tiny_serving(prefix_sharing=True),
                                  mc, params, reqs)
    assert len(done_on) == len(done_off) == len(reqs)
    assert eng_on.token_streams == eng_off.token_streams
    st = eng_on.cache.stats()["prefix"]
    assert st["hits"] > 0 and st["bytes_saved"] > 0
    assert 0 < st["hit_rate"] <= 1


def test_prefix_sharing_lossless_with_cow():
    """Unaligned prefix (9 tokens over 4-token pages): the divergence
    page is shared copy-on-write — still token-identical, with COW
    copies counted."""
    mc = tiny_model()
    params = tfm.init_params(jax.random.key(0), mc)
    plan = _prefix_plan(shared_prefix_len=9, prefix_pool=1)
    reqs = plan.sample()
    eng_off, _ = _run_engine(tiny_serving(), mc, params, reqs)
    eng_on, _ = _run_engine(tiny_serving(prefix_sharing=True), mc,
                            params, reqs)
    assert eng_on.token_streams == eng_off.token_streams
    st = eng_on.cache.stats()["prefix"]
    assert st["cow_copies"] > 0 and st["bytes_saved"] > 0


def test_prefix_sharing_composes_with_int8_cache():
    """Sharing + quantized cache: shared pages hold exactly the bytes
    the sharer's own prefill would have written (same chunking, same
    write sequence), so the combination stays token-identical to the
    non-sharing quantized engine."""
    mc = tiny_model()
    params = tfm.init_params(jax.random.key(0), mc)
    plan = _prefix_plan()
    reqs = plan.sample()
    eng_off, _ = _run_engine(tiny_serving(cache_dtype="int8"), mc,
                             params, reqs)
    eng_on, _ = _run_engine(tiny_serving(cache_dtype="int8",
                                         prefix_sharing=True), mc,
                            params, reqs)
    assert eng_on.token_streams == eng_off.token_streams
    assert eng_on.cache.stats()["prefix"]["hits"] > 0


def test_prefix_sharing_record_globals():
    from dlnetbench_tpu.serving.scheduler import run_serving
    mc = tiny_model()
    res = run_serving(mc, tiny_serving(prefix_sharing=True,
                                       warmup_requests=0),
                      _prefix_plan())
    g = res.global_meta
    assert g["prefix_hit_rate"] > 0
    assert g["prefix_bytes_saved"] > 0
    assert g["serving_config"]["prefix_sharing"] is True


# ---------------------------------------------------------------------
# arrival-plan prefix knobs (satellite 3)


def test_arrival_plan_prefix_knobs_roundtrip_and_validation():
    plan = _prefix_plan()
    d = plan.to_dict()
    assert d["shared_prefix_len"] == 8 and d["prefix_pool"] == 2
    back = ArrivalPlan.from_dict(d)
    assert back.shared_prefix_len == 8 and back.prefix_pool == 2
    assert [dataclasses.astuple(r) for r in back.sample()] \
        == [dataclasses.astuple(r) for r in plan.sample()]
    # no-prefix plans serialize WITHOUT the keys (committed fixtures
    # round-trip byte-identically)
    assert "shared_prefix_len" not in ArrivalPlan(
        kind="poisson", rate_rps=1.0, num_requests=1).to_dict()
    with pytest.raises(ValueError, match="shared_prefix_len"):
        ArrivalPlan(kind="poisson", rate_rps=1.0, num_requests=1,
                    shared_prefix_len=-1).validate()
    with pytest.raises(ValueError, match="prefix_pool"):
        _prefix_plan(prefix_pool=0).validate()
    with pytest.raises(ValueError, match="must be < the minimum"):
        _prefix_plan(shared_prefix_len=10).validate()
    # replay traces with explicit SHORTER prompts cannot sneak past
    # the plan-level range check
    with pytest.raises(ValueError, match="must be < the minimum"):
        ArrivalPlan(kind="replay", prompt_len=[8, 16],
                    shared_prefix_len=4,
                    trace=[{"t": 0.0, "prompt_len": 2,
                            "output_len": 4}]).validate()


def test_arrival_plan_prefix_fixture_roundtrip():
    """Committed prefix-heavy plan fixture beside the existing arrival
    fixtures: loads, validates, and samples deterministically with
    prefix ids drawn from the pool."""
    plan = ArrivalPlan.loads(f"@{DATA / 'arrival_prefix.json'}")
    assert plan.shared_prefix_len == 8 and plan.prefix_pool == 2
    reqs = plan.sample()
    assert all(0 <= r.prefix_id < 2 and r.prefix_len == 8
               for r in reqs)
    assert len({r.prefix_id for r in reqs}) == 2  # both prompts drawn
    # same plan json -> same stream, machine-independent
    again = ArrivalPlan.loads(f"@{DATA / 'arrival_prefix.json'}")
    assert [dataclasses.astuple(r) for r in again.sample()] \
        == [dataclasses.astuple(r) for r in reqs]


def test_prompt_tokens_for_prefix_requests():
    """Requests drawing the same prefix id share their first
    prefix_len tokens exactly; the tails stay rid-specific; prefix-less
    requests reproduce the legacy prompt_tokens stream."""
    from dlnetbench_tpu.serving import decode as D
    a = Request(rid=1, arrival_s=0.0, prompt_len=12, output_len=2,
                prefix_id=0, prefix_len=8)
    b = Request(rid=2, arrival_s=0.0, prompt_len=12, output_len=2,
                prefix_id=0, prefix_len=8)
    c = Request(rid=3, arrival_s=0.0, prompt_len=12, output_len=2,
                prefix_id=1, prefix_len=8)
    ta, tb, tc = (D.prompt_tokens_for(r, 64) for r in (a, b, c))
    assert np.array_equal(ta[:8], tb[:8])
    assert not np.array_equal(ta[:8], tc[:8])
    assert not np.array_equal(ta[8:], tb[8:])
    plain = Request(rid=1, arrival_s=0.0, prompt_len=12, output_len=2)
    assert np.array_equal(D.prompt_tokens_for(plain, 64),
                          D.prompt_tokens(1, 12, 64))
