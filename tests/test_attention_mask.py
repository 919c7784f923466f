"""Mask-builder unit tests (ops/attention_mask.py, ISSUE 10): verdict
tables vs brute force, sparsity goldens, seeded segment-plan
determinism, ring-hop verdicts, and the record-globals round trip
(parser hoist + merge mismatch-refusal)."""
from __future__ import annotations

import numpy as np
import pytest

from dlnetbench_tpu.ops import attention_mask as am

pytestmark = pytest.mark.longcontext

SPECS = [
    am.MaskSpec(causal=True),
    am.MaskSpec(causal=True, window=24),
    am.MaskSpec(causal=True, seg_avg=20, seg_seed=3),
    am.MaskSpec(causal=False, seg_avg=16, seg_seed=1),
    am.MaskSpec(causal=True, window=16, seg_avg=24, seg_seed=7),
]


def _brute_verdicts(spec, s, bq, bk):
    d = am.dense_mask(spec, s)
    out = np.zeros((s // bq, s // bk), np.uint8)
    for i in range(s // bq):
        for j in range(s // bk):
            blk = d[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            out[i, j] = (am.FULL if blk.all()
                         else am.PARTIAL if blk.any() else am.SKIP)
    return out


@pytest.mark.parametrize("spec", SPECS)
@pytest.mark.parametrize("s,bq,bk", [(128, 16, 16), (128, 32, 16),
                                     (96, 16, 32)])
def test_verdicts_match_brute_force(spec, s, bq, bk):
    """The interval math (never an S x S materialization) must agree
    with the O(S^2) dense mask block by block — verdicts AND both
    visit-range tables (fwd/dq per-q-block, dkv per-kv-block)."""
    bm = am.block_mask(spec, s, bq, bk)
    want = _brute_verdicts(spec, s, bq, bk)
    assert (bm.verdicts() == want).all()
    for i in range(bm.nq):
        nz = np.nonzero(want[i] != am.SKIP)[0]
        assert bm.q_first_k[i] == nz.min() and bm.q_last_k[i] == nz.max()
    for j in range(bm.nk):
        nz = np.nonzero(want[:, j] != am.SKIP)[0]
        assert (bm.kv_first_q[j] == nz.min()
                and bm.kv_last_q[j] == nz.max())


@pytest.mark.parametrize("spec", SPECS)
@pytest.mark.parametrize("s,bq,bk", [(128, 16, 16), (128, 32, 16),
                                     (96, 16, 32)])
def test_widest_visit_ranges_match_verdicts(spec, s, bq, bk):
    """The two integers the kernels' minor grid axes are as long as:
    the row and the column maxima of ``verdicts()``'s non-SKIP counts
    (held to the brute force above), and their sum, which is the same
    set counted either way."""
    bm = am.block_mask(spec, s, bq, bk)
    live = bm.verdicts() != am.SKIP
    assert bm.q_visits == live.sum(axis=1).max()
    assert bm.kv_visits == live.sum(axis=0).max()
    assert bm.visited == live.sum() \
        == (bm.kv_last_q - bm.kv_first_q + 1).sum()
    assert type(bm.q_visits) is type(bm.kv_visits) is int
    if spec.is_plain_causal:    # the widest row visits every block
        assert (bm.q_visits, bm.kv_visits) == (bm.nk, bm.nq)
    elif spec.window:           # a band: its width, whatever S is
        assert bm.q_visits < bm.nk and bm.kv_visits < bm.nq
        wide = am.block_mask(spec, 4 * s, bq, bk)
        assert (wide.q_visits, wide.kv_visits) == (bm.q_visits,
                                                   bm.kv_visits)


@pytest.mark.parametrize("spec", SPECS)
def test_allowed_predicate_matches_dense(spec):
    """The traceable predicate (ring hops, serving prefill) is the same
    semantics as the dense builder."""
    import jax.numpy as jnp
    s = 96
    seg = (am.segment_ids(spec.seg_seed, spec.seg_avg, s)
           if spec.seg_avg else None)
    q = jnp.arange(s)
    got = np.asarray(am.allowed(spec, q[:, None], q[None, :],
                                seg_ids=seg))
    assert (got == am.dense_mask(spec, s)).all()


def test_sparsity_fraction_goldens():
    # causal S=64: 1 - (64*65/2) / 64^2
    assert am.sparsity_fraction(am.MaskSpec(causal=True), 64) \
        == pytest.approx(1 - (64 * 65 / 2) / 64 ** 2)
    # causal & window W=4, S=8: allowed per row = min(q+1, 4)
    allowed = sum(min(q + 1, 4) for q in range(8))
    assert am.sparsity_fraction(
        am.MaskSpec(causal=True, window=4), 8) \
        == pytest.approx(1 - allowed / 64)
    # windows tighter than causal are strictly sparser
    assert (am.sparsity_fraction(am.MaskSpec(causal=True, window=8), 64)
            > am.sparsity_fraction(am.MaskSpec(causal=True), 64))


def test_segment_plan_seeded_determinism():
    a = am.segment_ids(5, 16, 256)
    b = am.segment_ids(5, 16, 256)
    c = am.segment_ids(6, 16, 256)
    assert (a == b).all()
    assert not (a == c).all()
    # ids are monotone from 0, lengths within the drawn range
    assert a[0] == 0 and (np.diff(a) >= 0).all() and (np.diff(a) <= 1).all()
    lengths = np.diff(np.flatnonzero(np.diff(a)))  # interior doc lengths
    if lengths.size:
        assert lengths.min() >= max(1, 16 // 2)
        assert lengths.max() <= 16 + 16 // 2


def test_spec_validation_and_round_trip():
    with pytest.raises(ValueError):
        am.MaskSpec(causal=False, window=8)       # non-causal window
    with pytest.raises(ValueError):
        am.MaskSpec(causal=False)                 # trivial all-allowed
    with pytest.raises(ValueError):
        am.MaskSpec(causal=True, window=-1)
    spec = am.MaskSpec(causal=True, window=128, seg_avg=64, seg_seed=9)
    assert am.MaskSpec.from_dict(spec.to_dict()) == spec
    assert spec.label() == "causal&window(128)&seg(avg=64,seed=9)"
    assert am.MaskSpec(causal=True).is_plain_causal
    assert not spec.is_plain_causal
    with pytest.raises(ValueError):
        am.block_mask(spec, 100, 16, 16)          # blocks don't divide


def test_block_stats_account_for_all_blocks():
    bm = am.block_mask(am.MaskSpec(causal=True, window=16), 128, 16, 16)
    st = bm.stats()
    assert (st["blocks_skipped"] + st["blocks_full"]
            + st["blocks_partial"] == st["blocks_total"] == 64)
    assert 0 < st["block_skip_fraction"] < 1
    assert st["sparsity_fraction"] == pytest.approx(
        am.sparsity_fraction(bm.spec, 128), abs=1e-6)


@pytest.mark.parametrize("spec", [None] + SPECS)
def test_ring_hop_work_matches_dense_tiles(spec):
    s, n = 128, 8
    work = am.ring_hop_work(spec, s, n)
    dspec = spec if spec is not None else am.MaskSpec(causal=True)
    d = am.dense_mask(dspec, s)
    sl = s // n
    for me in range(n):
        for src in range(n):
            assert work[me, src] == d[me * sl:(me + 1) * sl,
                                      src * sl:(src + 1) * sl].any()
    frac = am.ring_skipped_hop_fraction(spec, s, n)
    assert frac == pytest.approx(1 - work.mean())
    if spec is None:
        # plain causal: the strictly-future half of the hop grid
        assert frac == pytest.approx((n * (n - 1) / 2) / n ** 2)


def test_long_context_block_coverage_64k_128k():
    """The mask layer itself is O(S + blocks) host work — the 64k/128k
    plans the bench shapes use must build instantly and account for
    every block (ISSUE 10 satellite's coverage check at scale)."""
    for s in (64 * 1024, 128 * 1024):
        spec = am.MaskSpec(causal=True, window=s // 16)
        bm = am.block_mask(spec, s, 2048, 2048)
        st = bm.stats()
        assert st["blocks_total"] == (s // 2048) ** 2
        assert st["block_skip_fraction"] > 0.8   # the window is narrow
        bm_c = am.block_mask(am.MaskSpec(causal=True), s, 2048, 2048)
        assert bm_c.stats()["block_skip_fraction"] == pytest.approx(
            (s // 2048 - 1) / (2 * (s // 2048)), abs=1e-6)


def test_record_globals_round_trip_and_merge_refusal():
    """Mask spec + sparsity are COMPARABLE globals: the parser hoists
    them to columns, and records measured under different masks refuse
    to merge — a different mask IS a different run, exactly like
    mismatched fault or arrival plans."""
    import copy

    from dlnetbench_tpu.metrics.merge import merge_records
    from dlnetbench_tpu.metrics.parser import records_to_dataframe

    spec = am.MaskSpec(causal=True, window=32)
    g = am.record_globals(spec, 128, n_shards=4)
    assert g["attention_mask"] == "causal&window(32)"
    assert 0 < g["mask_sparsity"] < 1
    assert g["ring_skipped_hop_fraction"] > 0

    def rec(proc, globals_extra):
        return {"section": "spmd", "version": 2, "process": proc,
                "global": {"world_size": 2, "num_processes": 2,
                           **globals_extra},
                "mesh": {"platform": "cpu"}, "num_runs": 1,
                "warmup_times": [],
                "ranks": [{"rank": proc, "device_id": proc,
                           "process_index": proc, "hostname": f"h{proc}",
                           "runtimes": [1.0],
                           "summary": {"runtimes": {
                               "value": 1.0, "best": 1.0,
                               "band": [1.0, 1.0], "n": 1}}}]}

    r0, r1 = rec(0, g), rec(1, g)
    merged = merge_records([copy.deepcopy(r0), copy.deepcopy(r1)])
    assert merged["global"]["attention_mask"] == g["attention_mask"]
    df = records_to_dataframe([merged], validate=False)
    assert set(df["attention_mask"]) == {g["attention_mask"]}
    assert set(df["mask_sparsity"]) == {g["mask_sparsity"]}
    assert set(df["ring_skipped_hop_fraction"]) \
        == {g["ring_skipped_hop_fraction"]}

    # a different mask must refuse the merge, naming the key
    g2 = am.record_globals(am.MaskSpec(causal=True, window=64), 128,
                           n_shards=4)
    with pytest.raises(ValueError, match="attention_mask"):
        merge_records([rec(0, g), rec(1, g2)])
