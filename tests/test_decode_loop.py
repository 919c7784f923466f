"""Device-resident multi-step decode + speculative decode (ISSUE 11):
the fused loop's token parity with the classic engine, the verify
pass, the host/device state split's sync contract, adaptive N, config
guards, the record/attribution pathway, and the CompiledLoop executor
shape."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlnetbench_tpu.models import transformer as tfm
from dlnetbench_tpu.serving import decode as D
from dlnetbench_tpu.serving.arrivals import ArrivalPlan
from dlnetbench_tpu.serving.device_state import (DeviceDecodeState,
                                                 SyncContractError)
from dlnetbench_tpu.serving.kv_cache import (CacheConfig, PagedKVCache,
                                             device_buffers)
from dlnetbench_tpu.serving.scheduler import Engine, ServingConfig

pytestmark = [pytest.mark.decode, pytest.mark.serving]


def tiny_model(**over) -> tfm.TransformerConfig:
    kw = dict(vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2,
              ff_dim=64, num_layers=2, seq_len=32, gated=True,
              max_positions=0, dtype="float32")
    kw.update(over)
    return tfm.TransformerConfig(**kw)


def tiny_serving(**over) -> ServingConfig:
    kw = dict(slots=4, page_size=4, num_pages=32, max_seq_len=32,
              slo_ttft_ms=200.0, slo_tpot_ms=100.0)
    kw.update(over)
    return ServingConfig(**kw)


PLAN = ArrivalPlan(kind="poisson", rate_rps=200.0, num_requests=10,
                   seed=7, prompt_len=[4, 9], output_len=[1, 7])


def _run_streams(cfg, sc, params, plan=PLAN):
    eng = Engine(cfg, sc, params=params)
    completed, _ = eng.run(plan.sample())
    assert len(completed) == plan.num_requests
    assert eng.cache.pages_in_use == 0
    return dict(eng.token_streams), eng


# ---------------------------------------------------------------------
# token parity: the acceptance anchor


@pytest.fixture(scope="module")
def shared():
    cfg = tiny_model()
    params = tfm.init_params(jax.random.key(0), cfg)
    base, _ = _run_streams(cfg, tiny_serving(), params)
    return cfg, params, base


def test_multi_step_token_parity(shared):
    """N-step fused greedy == 1-step greedy, exactly, across N values
    and both prefill policies."""
    cfg, params, base = shared
    for n in (2, 8):
        got, eng = _run_streams(cfg, tiny_serving(multi_step_n=n),
                                params)
        assert got == base, f"N={n}"
        blk = eng.decode_loop_block()
        assert blk["multi_step_n"] == n
        assert blk["steps_per_dispatch"] > 1.0
    got, _ = _run_streams(
        cfg, tiny_serving(multi_step_n=4, prefill="inline",
                          prefill_chunk=4), params)
    assert got == base


def test_speculative_token_parity_both_drafters(shared):
    """Speculative decode is LOSSLESS under greedy acceptance: the
    emitted stream equals the 1-step stream whatever the drafter
    proposes — for the ngram table AND the truncated-layer drafter."""
    cfg, params, base = shared
    for drafter, extra in (("ngram", {}),
                           ("truncated", {"drafter_layers": 1})):
        sc = tiny_serving(multi_step_n=4, speculative=True, spec_k=3,
                          drafter=drafter, **extra)
        got, eng = _run_streams(cfg, sc, params)
        assert got == base, drafter
        spec = eng.decode_loop_block()["spec"]
        assert spec["drafter"] == drafter
        assert spec["drafted"] > 0
        assert 0.0 <= spec["acceptance_rate"] <= 1.0


def test_multi_step_n1_is_classic_engine(shared):
    """multi_step_n=1 reproduces today's engine bit-identically — the
    loop program is not even BUILT (the tuning-layer convention: the
    default path is the untouched path), and the classic single-step
    program drives the run."""
    cfg, params, base = shared
    eng = Engine(cfg, tiny_serving(multi_step_n=1), params=params)
    assert eng._loop is None and eng._decode is not None
    assert eng.dstate is None
    assert "decode_step" in eng.meta["compile_ms"]
    completed, _ = eng.run(PLAN.sample())
    assert len(completed) == PLAN.num_requests
    assert dict(eng.token_streams) == base
    blk = eng.decode_loop_block()
    assert blk["steps_per_dispatch"] == 1.0
    assert blk["host_dispatch_us"]["n"] > 0   # the measured before-
    #                                           number (ISSUE 11 sat.)


def test_multi_step_loop_matches_iterated_single_steps():
    """Op-level: the fused program's token block over N steps equals N
    iterated single-step calls on the same starting state (same math,
    same cache writes — the shared ``_step_tokens`` body)."""
    cfg = tiny_model()
    params = tfm.init_params(jax.random.key(0), cfg)
    cc = CacheConfig(num_layers=2, num_kv_heads=2, head_dim=8,
                     num_pages=16, page_size=4, max_seqs=2,
                     max_pages_per_seq=6)
    cache = PagedKVCache(cc)
    k, v = device_buffers(cc)
    prompt = np.array([5, 9, 3, 11, 7], np.int32)
    cache.allocate(0, len(prompt) + 8)
    prefill = D.make_prefill_chunk(cfg, cc, chunk=5)
    row = jnp.asarray(cache.block_tables[0])
    k, v, nxt = prefill(params, k, v, jnp.asarray(prompt),
                        jnp.int32(0), jnp.int32(5), row)
    cache.append(0, 5)
    first = int(nxt)
    bt = jnp.asarray(cache.block_tables)

    # (a) four iterated single steps
    step = D.make_decode_step(cfg, cc)
    k1, v1 = k, v
    last, pos, ref = first, 5, []
    for _ in range(4):
        k1, v1, nx = step(
            params, k1, v1,
            jnp.asarray(np.array([last, 0], np.int32)),
            jnp.asarray(np.array([pos, 0], np.int32)), bt,
            jnp.asarray(np.array([True, False])))
        last = int(np.asarray(nx)[0])
        pos += 1
        ref.append(last)

    # (b) one fused call on the SAME starting state
    loop = D.make_multi_step_decode(cfg, cc, n_max=8)
    state = np.zeros((D.STATE_ROWS, 2), np.int32)
    state[D.STATE_LAST, 0] = first
    state[D.STATE_POS, 0] = 5
    state[D.STATE_REM, 0] = 4
    state[D.STATE_LIMIT, 0] = 13
    k2, v2, st, out, cnt, steps = loop(params, k, v,
                                       jnp.asarray(state), bt,
                                       jnp.int32(4))
    assert int(steps) == 4
    assert int(np.asarray(cnt)[0]) == 4
    assert np.asarray(out)[0, :4].tolist() == ref
    st = np.asarray(st)
    assert st[D.STATE_POS, 0] == 9 and st[D.STATE_REM, 0] == 0
    # the loop exits EARLY once every slot is done
    _, _, _, _, cnt2, steps2 = loop(params, k, v, jnp.asarray(state),
                                    bt, jnp.int32(8))
    assert int(steps2) == 4 and int(np.asarray(cnt2)[0]) == 4


def test_verify_pass_matches_iterated_decode():
    """The speculative verify pass computes, at every fed position,
    exactly the single-step program's greedy continuation — the
    property that makes greedy acceptance lossless."""
    from dlnetbench_tpu.serving.speculative import _verify_tokens
    cfg = tiny_model()
    params = tfm.init_params(jax.random.key(1), cfg)
    cc = CacheConfig(num_layers=2, num_kv_heads=2, head_dim=8,
                     num_pages=16, page_size=4, max_seqs=2,
                     max_pages_per_seq=6)
    cache = PagedKVCache(cc)
    k, v = device_buffers(cc)
    prompt = np.array([1, 8, 2, 60], np.int32)
    cache.allocate(0, 20)
    prefill = D.make_prefill_chunk(cfg, cc, chunk=4)
    row = jnp.asarray(cache.block_tables[0])
    k, v, nxt = prefill(params, k, v, jnp.asarray(prompt),
                        jnp.int32(0), jnp.int32(4), row)
    cache.append(0, 4)
    bt = jnp.asarray(cache.block_tables)
    fed = [int(nxt), 17, 42, 3]         # last token + 3 arbitrary drafts

    # reference: feed them one at a time through the single-step program
    step = D.make_decode_step(cfg, cc)
    k1, v1, ref = k, v, []
    for j, tok in enumerate(fed):
        k1, v1, nx = step(
            params, k1, v1,
            jnp.asarray(np.array([tok, 0], np.int32)),
            jnp.asarray(np.array([4 + j, 0], np.int32)), bt,
            jnp.asarray(np.array([True, False])))
        ref.append(int(np.asarray(nx)[0]))

    # one batched verify pass over the same fed tokens
    tokens = jnp.asarray(np.array([fed, [0] * 4], np.int32))
    write_ok = jnp.asarray(np.array([[True] * 4, [False] * 4]))
    _, _, out = _verify_tokens(cfg, cc, params, k, v, tokens,
                               jnp.asarray(np.array([4, 0], np.int32)),
                               write_ok, bt)
    assert np.asarray(out)[0].tolist() == ref


# ---------------------------------------------------------------------
# the host/device state split (satellite: property + sync contract)


def test_device_state_roundtrip_property():
    """Any interleaving of admit / evict / device-advance / flush /
    pull round-trips device_state <-> host view losslessly: the host
    mirrors after a final pull equal a pure-host reference model that
    applied the same operations."""
    from dlnetbench_tpu.utils.seeded import Rng
    slots, pmax, vocab = 4, 6, 32
    ds = DeviceDecodeState(slots, pmax, vocab=vocab)
    ref = {"state": np.zeros((D.STATE_ROWS, slots), np.int32),
           "bt": np.zeros((slots, pmax), np.int32),
           "tab": np.zeros((slots, vocab), np.int32)}

    # a tiny jitted "device advance" mirroring the loop's state update:
    # active slots feed their last token and move forward one step
    @jax.jit
    def advance(state, table):
        last, pos, rem = (state[D.STATE_LAST], state[D.STATE_POS],
                          state[D.STATE_REM])
        act = rem > 0
        nxt = (last * 7 + pos) % vocab
        rows = jnp.arange(state.shape[1])
        table = table.at[rows, jnp.where(act, last, vocab)].set(
            nxt, mode="drop")
        state = state.at[D.STATE_LAST].set(jnp.where(act, nxt, last))
        state = state.at[D.STATE_POS].set(pos + act.astype(jnp.int32))
        state = state.at[D.STATE_REM].set(rem - act.astype(jnp.int32))
        return state, table

    def ref_advance():
        st, tab = ref["state"], ref["tab"]
        for s in range(slots):
            if st[D.STATE_REM, s] > 0:
                last, pos = st[D.STATE_LAST, s], st[D.STATE_POS, s]
                nxt = (last * 7 + pos) % vocab
                tab[s, last] = nxt
                st[D.STATE_LAST, s] = nxt
                st[D.STATE_POS, s] += 1
                st[D.STATE_REM, s] -= 1

    rng = Rng(123)
    for _ in range(120):
        op = rng.uniform_int(0, 3)
        if op == 0:                       # admit a slot
            ds.pull()
            s = rng.uniform_int(0, slots - 1)
            row = np.asarray([rng.uniform_int(0, 15)
                              for _ in range(pmax)], np.int32)
            tab_row = np.asarray([rng.uniform_int(0, vocab - 1)
                                  for _ in range(vocab)], np.int32)
            kw = dict(last_token=rng.uniform_int(0, vocab - 1),
                      position=rng.uniform_int(0, 10),
                      remaining=rng.uniform_int(1, 6),
                      seq_limit=16,
                      # ISSUE 19 rows (negative uid = a warm rid)
                      uid=rng.uniform_int(0, 20) - 5,
                      grammar_state=rng.uniform_int(0, 9))
            ds.admit(s, block_row=row, ngram_row=tab_row, **kw)
            ref["state"][:, s] = [kw["last_token"], kw["position"],
                                  kw["remaining"], kw["seq_limit"],
                                  kw["uid"], kw["grammar_state"]]
            ref["bt"][s] = row
            ref["tab"][s] = tab_row
        elif op == 1:                     # evict a slot
            ds.pull()
            s = rng.uniform_int(0, slots - 1)
            ds.evict(s)
            ref["state"][D.STATE_REM, s] = 0
        elif op == 2:                     # device advance
            carries = ds.carries()
            st, tab = advance(*carries)
            ds.rebind((st, tab))
            ref_advance()
        else:                             # explicit sync
            ds.pull()
    ds.pull()
    view = ds.host_view()
    np.testing.assert_array_equal(view["last_tokens"],
                                  ref["state"][D.STATE_LAST])
    np.testing.assert_array_equal(view["positions"],
                                  ref["state"][D.STATE_POS])
    np.testing.assert_array_equal(view["remaining"],
                                  ref["state"][D.STATE_REM])
    np.testing.assert_array_equal(view["uids"],
                                  ref["state"][D.STATE_UID])
    np.testing.assert_array_equal(view["grammar_states"],
                                  ref["state"][D.STATE_GRAMMAR])
    np.testing.assert_array_equal(view["block_tables"], ref["bt"])
    np.testing.assert_array_equal(view["ngram_table"], ref["tab"])
    # every crossing was priced
    assert ds.sync_h2d_us and ds.sync_d2h_us


def test_device_state_stale_mutation_refused():
    """The sync contract fails LOUD: mutating a stale mirror (the
    device advanced since the last pull) raises instead of silently
    clobbering device state at the next flush."""
    ds = DeviceDecodeState(2, 4)
    ds.admit(0, last_token=3, position=2, remaining=4, seq_limit=8,
             block_row=np.zeros(4, np.int32))
    carries = ds.carries()
    ds.rebind(carries)                    # device "advanced"
    with pytest.raises(SyncContractError, match="STALE"):
        ds.admit(1, last_token=1, position=0, remaining=2, seq_limit=8,
                 block_row=np.zeros(4, np.int32))
    with pytest.raises(SyncContractError, match="STALE"):
        ds.evict(0)
    ds.pull()
    ds.evict(0)                           # fresh again after the sync
    assert ds.host_view()["remaining"][0] == 0


# ---------------------------------------------------------------------
# adaptive N (satellite: the fused loop must not starve admissions)


def test_pick_n_steps_policy():
    """The deterministic half of the TTFT guard: pending work caps N
    at the shortest remaining output; an imminent arrival caps by the
    measured step rate; an idle queue runs the full N; a prefilling
    slot (inline mode) forces 1."""
    from dlnetbench_tpu.serving.arrivals import Request
    from dlnetbench_tpu.serving.scheduler import _SlotState
    import time
    cfg = tiny_model()
    eng = Engine(cfg, tiny_serving(multi_step_n=8),
                 params=tfm.init_params(jax.random.key(0), cfg))
    eng._reset_state()
    eng._t0 = time.monotonic()    # "now" ~= 0 on the engine clock

    def slot(prompt, out, generated):
        st = _SlotState(Request(rid=0, arrival_s=0.0, prompt_len=prompt,
                                output_len=out), admitted_s=0.0)
        st.prefill_done = prompt
        st.generated = generated
        return st

    eng.slots[0] = slot(4, 6, 1)          # 5 remaining
    eng.slots[1] = slot(4, 4, 1)          # 3 remaining
    assert eng._pick_n_steps([0, 1]) == 8        # nothing waiting
    eng.pending.append(Request(rid=9, arrival_s=0.0, prompt_len=4,
                               output_len=2))
    assert eng._pick_n_steps([0, 1]) == 3        # min remaining caps
    eng.pending.clear()
    # queue head arrives in ~2 measured steps: cap there
    eng._step_ewma_s = 1.0
    eng.queue.append(Request(rid=10, arrival_s=1.5, prompt_len=4,
                             output_len=2))
    assert eng._pick_n_steps([0, 1]) == 2
    eng.queue.clear()
    # a prefilling slot (inline) pins the engine at one step
    eng.slots[2] = slot(4, 4, 0)
    eng.slots[2].prefill_done = 2
    assert eng._pick_n_steps([0, 1]) == 1
    # adaptive off: always the configured N
    eng.cfg = dataclasses.replace(eng.cfg, adaptive_n=False)
    assert eng._pick_n_steps([0, 1]) == 8


def test_adaptive_n_ttft_holds_under_poisson():
    """TTFT p99 under Poisson arrivals with the adaptive fused loop
    must not regress past the 1-step engine's beyond the stat band
    (the satellite's acceptance): same seeds, interleaved rounds, and
    a generous noise margin since this is wall-clock."""
    from dlnetbench_tpu.serving import metrics as M
    cfg = tiny_model()
    params = tfm.init_params(jax.random.key(0), cfg)
    plan = ArrivalPlan(kind="poisson", rate_rps=120.0,
                       num_requests=12, seed=5, prompt_len=[4, 8],
                       output_len=[4, 8])
    reqs = plan.sample()
    engines = {1: Engine(cfg, tiny_serving(multi_step_n=1),
                         params=params),
               8: Engine(cfg, tiny_serving(multi_step_n=8),
                         params=params)}
    for eng in engines.values():
        eng.run(reqs)                     # warm
    p99 = {1: [], 8: []}
    for _ in range(3):
        for n, eng in engines.items():
            completed, _ = eng.run(reqs)
            p99[n].append(M.percentile(
                [c.ttft_ms for c in completed], 99))
    med1 = sorted(p99[1])[1]
    med8 = sorted(p99[8])[1]
    # regression = worse beyond band overlap AND a 2x margin (the
    # starvation failure this guards against is ~N x, not 2x)
    from dlnetbench_tpu.metrics import stats
    band1 = [min(p99[1]), max(p99[1])]
    band8 = [min(p99[8]), max(p99[8])]
    assert stats.bands_overlap(band1, band8) or med8 <= 2.0 * med1, \
        (p99[1], p99[8])


# ---------------------------------------------------------------------
# config guards (satellite)


def test_spec_config_validation():
    with pytest.raises(ValueError, match="multi_step_n"):
        tiny_serving(multi_step_n=0).validate()
    with pytest.raises(ValueError, match="spec_k"):
        tiny_serving(speculative=True, spec_k=0).validate()
    with pytest.raises(ValueError, match="drafter"):
        tiny_serving(speculative=True, drafter="oracle").validate()
    # sampling knobs validate through check_sampling_config (ISSUE 19)
    with pytest.raises(ValueError, match="temperature"):
        tiny_serving(top_p=0.9).validate()
    with pytest.raises(ValueError, match="top_p"):
        tiny_serving(temperature=0.8, top_p=1.5).validate()
    # speculative sampling needs a drafter DISTRIBUTION: the ngram
    # drafter emits argmax tokens only, so rejection sampling has no
    # q(t) to accept against — the old "spec requires greedy" refusal
    # is gone, replaced by this per-drafter guard
    with pytest.raises(ValueError, match="drafter probs"):
        tiny_serving(speculative=True, temperature=0.8,
                     drafter="ngram").validate()
    # a full-depth truncated drafter is refused at build (it IS the
    # target: no draft speedup, double cost)
    cfg = tiny_model()
    with pytest.raises(ValueError, match="drafter_layers"):
        Engine(cfg, tiny_serving(speculative=True, drafter="truncated",
                                 drafter_layers=cfg.num_layers),
               params=tfm.init_params(jax.random.key(0), cfg))


def test_compiled_loop_validates_carry_contract():
    """The fourth executor shape: a loop program that does NOT return
    a donated carry as a leading output fails loud at build instead of
    handing back a dead buffer at the second sync."""
    from dlnetbench_tpu.core.executor import CompiledLoop
    x = jnp.zeros((4,), jnp.float32)
    y = jnp.zeros((4,), jnp.float32)

    def good(a, b):
        return a + 1.0, b * 2.0, jnp.sum(a)

    loop = CompiledLoop(good, (x, y), carry_argnums=(0, 1))
    assert loop.num_carry_outputs == 2
    outs = loop(x, y)
    carries, extras = loop.split(outs)
    assert len(carries) == 2 and len(extras) == 1

    def bad(a, b):
        return jnp.sum(a), b * 2.0       # carry 0 has no matching out

    with pytest.raises(ValueError, match="carry argnum"):
        CompiledLoop(bad, (x, y), carry_argnums=(0, 1))


# ---------------------------------------------------------------------
# fault composition + record pathway


def test_crash_shrink_requeues_with_original_stamps_multi_step():
    """The crash-fault composition survives the engine split: a shrink
    on a MULTI-STEP engine re-queues in-flight requests with their
    ORIGINAL arrival stamps on the rebuilt engine (satellite's
    crash-fault composition case)."""
    from dlnetbench_tpu.faults.plan import FaultEvent, FaultPlan
    from dlnetbench_tpu.serving.scheduler import run_serving
    cfg = tiny_model()
    sc = tiny_serving(world=2, slots=4, multi_step_n=4,
                      slo_ttft_ms=300.0, slo_tpot_ms=100.0)
    trace = [{"t": 0.01 * i, "prompt_len": 6, "output_len": 4}
             for i in range(10)]
    plan = ArrivalPlan(kind="replay", trace=trace)
    fp = FaultPlan(events=[FaultEvent(kind="crash", ranks=[1],
                                     iteration=3)], policy="shrink")
    res = run_serving(cfg, sc, plan, fault_plan=fp)
    g = res.global_meta
    assert g["degraded_world"] == [0] and g["degraded_slots"] == 2
    assert res.num_runs == len(trace)     # every request completed
    # original arrival stamps survived the re-queue: TTFT of the
    # disrupted requests includes the pre-crash wait
    arrivals = sorted(t["t"] for t in trace)
    srv = g["serving"]
    assert srv["completed"] == len(arrivals)
    assert g["serving"]["decode_loop"]["multi_step_n"] == 4


def test_serving_record_carries_decode_loop_and_attribution():
    """run_serving -> emit: the record's serving block carries the
    dispatch decomposition, attribution stamps the serving_dispatch
    block (the ISSUE 11 fold), and the parser hoists the new
    columns."""
    from dlnetbench_tpu.metrics.emit import result_to_record
    from dlnetbench_tpu.metrics.parser import (records_to_dataframe,
                                               validate_record)
    from dlnetbench_tpu.serving.scheduler import run_serving
    cfg = tiny_model()
    sc = tiny_serving(multi_step_n=4, speculative=True, spec_k=2,
                      warmup_requests=0)
    plan = ArrivalPlan(kind="poisson", rate_rps=200.0, num_requests=6,
                       seed=1, prompt_len=[4, 8], output_len=[2, 5])
    res = run_serving(cfg, sc, plan)
    rec = result_to_record(res)
    validate_record(rec)
    dl = rec["global"]["serving"]["decode_loop"]
    assert dl["multi_step_n"] == 4 and dl["speculative"]
    assert dl["dispatches"] >= 1
    assert dl["host_dispatch_us"]["n"] >= 1
    assert dl["sync_h2d_us"]["n"] >= 1
    assert dl["spec"]["k"] == 2
    assert rec["global"]["serving_config"]["multi_step_n"] == 4
    attr = rec["global"]["attribution"]
    assert attr["inputs"]["source"] == "serving_dispatch"
    assert attr["inputs"]["steps_per_dispatch"] == \
        dl["steps_per_dispatch"]
    assert attr["bound"] in ("host", "hbm")   # CPU mesh: never mxu
    assert abs(sum(attr["fractions"].values()) - 1.0) < 1e-6
    df = records_to_dataframe([rec])
    for col in ("serving_steps_per_dispatch", "serving_tokens_per_sync",
                "serving_host_dispatch_us_p50",
                "serving_spec_acceptance"):
        assert col in df.columns, col


def test_dispatch_decomposition_two_point_solve():
    """The paired-round solver recovers the per-dispatch floor from a
    synthetic 1-step vs N-step pair exactly."""
    from dlnetbench_tpu.analysis.attribution import (
        dispatch_decomposition, serving_host_us)
    # silicon 100us/step, floor 400us/dispatch; device_us additionally
    # carries prefill time the solve must NOT divide into decode steps
    # (the decode_device_us split)
    one = {"device_us": {"total": 50 * (100.0 + 400.0) + 9999.0},
           "decode_device_us": {"total": 50 * (100.0 + 400.0)},
           "device_steps": 50, "steps_per_dispatch": 1.0,
           "dispatches": 50}
    multi = {"device_us": {"total": 48 * 100.0 + 6 * 400.0 + 9999.0},
             "decode_device_us": {"total": 48 * 100.0 + 6 * 400.0},
             "device_steps": 48, "steps_per_dispatch": 8.0,
             "dispatches": 6}
    dec = dispatch_decomposition(one, multi)
    assert dec is not None
    assert abs(dec["dispatch_us"] - 400.0) < 1.0
    assert abs(dec["silicon_us_per_step"] - 100.0) < 1.0
    # degenerate pair (no fused amortization) refuses
    assert dispatch_decomposition(one, one) is None
    # the fold: N fused steps pay ONE floor
    h1 = serving_host_us({"host_dispatch_us": {"total": 0.0},
                          "dispatches": 50}, dec["dispatch_us"])
    hn = serving_host_us({"host_dispatch_us": {"total": 0.0},
                          "dispatches": 6}, dec["dispatch_us"])
    assert h1 / hn == pytest.approx(50 / 6)
