"""``ops/sparse_attention.py``: the selection against the benchmark's
float32 reference and by hand, its counted ranking against ``lax.top_k``,
the kernels (interpret mode) against dense masked softmax on the same
lists."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_sparse_linear as ref
from dlnetbench_tpu.ops import sparse_attention as sa

F32 = jnp.float32
SIZES = sa.SparseSizes(8, 4, 16, 4, 32, 1, 64)
B, S, HQ, HKV, DH = 2, 256, 8, 2, 16


def draws(dtype=F32, s=S, seed=1):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, s, HQ, DH), F32).astype(dtype)
    k, v = (jax.random.normal(kk, (B, s, HKV, DH), F32).astype(dtype)
            for kk in ks[1:3])
    return q, k, v, jax.random.normal(ks[3], (B, s, HQ, DH), F32)


def attention(q, k, v, blocks, block_size):
    return sa.block_sparse_attention(
        q, k, v, sa.plan_visits(blocks, block_size, q.dtype))


@contextlib.contextmanager
def sized(**sizes):
    """While inside, the module's own sizes (a tile's candidates, the
    selection's row block) are these: a test's, where the shapes would
    choose the cell's."""
    with pytest.MonkeyPatch.context() as patch:
        for name, value in sizes.items():
            patch.setattr(sa, name, value)
        yield


@pytest.fixture(scope="module")
def chosen():
    q, k, v, w = draws()
    with sized(_SELECT_ROWS=64):
        return q, k, v, w, sa.select_blocks(q, k, SIZES)


def reference_lists(q, k, sizes):
    """The benchmark's reference, a (batch row, group) at a time."""
    g = HQ // HKV
    with jax.default_matmul_precision("highest"):
        return np.stack([np.stack([
            np.asarray(ref.select(
                q[b, :, h * g:(h + 1) * g], k[b, :, h], tuple(sizes)))
            for h in range(HKV)], 1) for b in range(B)])


def test_selection_is_the_references_in_float32(chosen):
    q, k, _, _, blocks = chosen
    assert blocks.dtype == jnp.int32
    assert blocks.shape == (B, S, HKV, SIZES.topk)
    assert np.array_equal(np.asarray(blocks), reference_lists(q, k, SIZES))


def test_forced_blocks_and_short_lists(chosen):
    lists = np.asarray(chosen[4])
    own = np.arange(S) // SIZES.block_size
    # block 0 and the window's two blocks ending at the token's own come
    # first (they score +inf, ties to the lower index)
    assert (lists[:, :, :, 0] == 0).all()
    far = lists[:, own >= 2]
    at = own[own >= 2][None, :, None]
    assert (far[..., 1] == at - 1).all() and (far[..., 2] == at).all()
    # no more visible blocks than topk: all of them (the forced ones
    # first), then NONE
    for t in (0, 15, 16, 40, 63):
        n = t // SIZES.block_size + 1
        assert (np.sort(lists[:, t, :, :n], -1) == np.arange(n)).all()
        assert (lists[:, t, :, n:] == sa.NONE).all()
    # no block after the token, none twice
    assert (lists <= own[None, :, None, None]).all()
    for row in lists.reshape(-1, SIZES.topk)[::97]:
        kept = row[row >= 0]
        assert len(set(kept)) == len(kept)


def test_ties_go_to_the_lower_index():
    """Keys that are all alike score every compressed key alike: the one
    free place goes to the lowest unforced block."""
    q, k, _, _ = draws()
    blocks = np.asarray(sa.select_blocks(q, jnp.ones_like(k), SIZES))
    own = np.arange(S) // SIZES.block_size
    assert (blocks[:, own >= 4][..., 3] == 1).all()


def drawn_scores(case: str):
    """float32 [2, 64, 2, 64] block scores as ``_block_scores`` can make
    them (sums of shares: no -0.0), row ``t`` a token of block ``t``."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, 64, 2, 64)).astype(np.float32)
    own = np.arange(64)[None, :, None, None]
    blk = np.arange(64)[None, None, None, :]
    if case == "ties":          # eight values or so a row of 64
        x = np.round(x, 0) + 0.0
    if case == "equal":
        x = np.full_like(x, 0.25)
    if case == "forced":        # step (e): two first blocks, a window of 4
        x = np.where((blk < 2) | (blk > own - 4), np.inf, x)
    if case in ("short", "forced"):     # the causal cut: t + 1 visible
        x = np.where(blk <= own, x, -np.inf)
    return jnp.asarray(x, F32)


@pytest.mark.parametrize("take", [16, 64])
@pytest.mark.parametrize("case", ["random", "ties", "equal", "short",
                                  "forced"])
def test_the_counted_ranking_gives_top_ks_lists(case, take):
    """Best first, ties to the lower index, the +inf blocks first in
    index order, ``NONE`` where fewer than ``take`` are visible: to the
    integer what ``lax.top_k`` answers."""
    score = drawn_scores(case)
    top, idx = jax.lax.top_k(score, take)
    want = np.asarray(jnp.where(top > -jnp.inf, idx, sa.NONE))
    got = jax.jit(sa._best_blocks, static_argnums=1)(score, take)
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), want)
    if case in ("short", "forced"):
        assert (want[:, :take - 1, :, -1] == sa.NONE).all()
    if case == "ties":
        assert (np.diff(np.sort(np.asarray(score), -1), axis=-1)
                == 0).mean() > 0.8


def primitives(jaxpr):
    """The names of a jaxpr's equations, those of its sub-jaxprs (a
    ``scan``'s body, a ``pjit``'s) included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from primitives(sub)


def test_the_selection_sorts_nothing():
    """A ``top_k`` of 256 is a full sort of every row on the chip: the
    selection's program holds neither (``plan_visits``'s ``argsort`` of
    a row of tiles is another matter, and is there)."""
    q, k, _, _ = draws()
    with sized(_SELECT_ROWS=64):
        names = set(primitives(jax.make_jaxpr(
            lambda q, k: sa.select_blocks(q, k, SIZES))(q, k).jaxpr))
    assert "scan" in names and "reduce_max" in names    # the walk went in
    assert not names & {"sort", "top_k", "approx_top_k"}
    visits = set(primitives(jax.make_jaxpr(
        lambda b: sa.plan_visits(b, SIZES.block_size, F32))(
            jnp.zeros((B, S, HKV, SIZES.topk), jnp.int32)).jaxpr))
    assert "sort" in visits


def test_the_selection_takes_no_gradient_and_is_made_in_row_blocks(chosen):
    q, k, _, _, blocks = chosen
    again = sa.select_blocks(q, k, SIZES)       # one block of 256 rows
    assert np.array_equal(np.asarray(blocks), np.asarray(again))
    # integers out: attention over lists made inside the differentiated
    # function has the gradients it has over the same lists held fixed
    v, w = chosen[2], chosen[3]

    def loss(q, k, lists=None):
        picked = sa.select_blocks(q, k, SIZES) if lists is None else lists
        return jnp.sum(attention(q, k, v, picked, SIZES.block_size) * w)
    inside = jax.grad(loss, (0, 1))(q, k)
    fixed = jax.grad(loss, (0, 1))(q, k, blocks)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(inside, fixed))
    with pytest.raises(ValueError, match="needs the stride"):
        sa.select_blocks(q[:, :200], k[:, :200], SIZES)
    # a window under a block would leave a token's own block unforced,
    # and a tile that no list names is never written
    with pytest.raises(ValueError, match="one block at least"):
        sa.select_blocks(q, k, SIZES._replace(window_size=0))


@pytest.mark.parametrize("dtype,tiles,tol", [
    ("float32", {}, 1e-5),
    ("float32", {"_BLOCK_Q": (32,), "_BLOCK_K": (64,)}, 1e-5),
    ("bfloat16", {"_BLOCK_Q": (64,), "_BLOCK_K": (32,)}, 2e-2)])
def test_kernels_equal_dense_masked_softmax_on_the_same_lists(
        chosen, dtype, tiles, tol):
    q, k, v, w = (x.astype(dtype) for x in chosen[:4])
    blocks = chosen[4]

    def loss(fn):
        def f(q, k, v):
            o = fn(q, k, v, blocks, SIZES.block_size)
            return jnp.sum(o.astype(F32) * w.astype(F32)), o
        return jax.value_and_grad(f, (0, 1, 2), has_aux=True)
    with sized(**tiles):
        assert sa.tile_plan(S, SIZES.block_size) == (
            tiles.get("_BLOCK_Q", (256,))[0], tiles.get("_BLOCK_K", (256,))[0])
        (_, o), grads = loss(attention)(q, k, v)
    (_, o_r), grads_r = loss(sa.reference_attention)(
        *(x.astype(F32) for x in (q, k, v)))
    assert o.dtype == jnp.dtype(dtype)
    for name, a, b in zip(("o", "dq", "dk", "dv"), (o, *grads),
                          (o_r, *grads_r)):
        err = float(jnp.linalg.norm(a.astype(F32) - b)
                    / jnp.linalg.norm(b))
        assert err <= tol, (name, err)


def test_a_key_outside_a_tokens_selection_adds_nothing(chosen):
    """Move the values and keys of one block: only the tokens that chose
    it (and can see it) change, in output and in dq."""
    q, k, v, w, blocks = chosen
    blk = 5
    lo, hi = blk * SIZES.block_size, (blk + 1) * SIZES.block_size
    k2 = k.at[:, lo:hi].add(0.5)
    v2 = v.at[:, lo:hi].add(1.0)

    def both(k, v):
        f = lambda q: jnp.sum(attention(q, k, v, blocks,
                                        SIZES.block_size) * w)
        return attention(q, k, v, blocks, SIZES.block_size), jax.grad(f)(q)
    (o1, dq1), (o2, dq2) = both(k, v), both(k2, v2)
    chose = (np.asarray(blocks) == blk).any(-1)         # [B, S, Hkv]
    chose = np.repeat(chose, HQ // HKV, axis=-1)        # [B, S, Hq]
    moved = np.abs(np.asarray(o1 - o2)).max(-1) > 0
    assert not (moved & ~chose).any() and (moved & chose).any()
    moved = np.abs(np.asarray(dq1 - dq2)).max(-1) > 0
    assert not (moved & ~chose).any()


def test_counters_and_tile_visits(chosen):
    blocks = chosen[4]
    with sized(_BLOCK_Q=(32,), _BLOCK_K=(64,)):
        visits = sa.plan_visits(blocks, SIZES.block_size, F32)
    assert visits.tiles == (32, 64, SIZES.block_size)
    c = sa.counters(blocks, visits)
    assert int(c["selected"]) == int((np.asarray(blocks) >= 0).sum())
    member = sa.membership(blocks, S // SIZES.block_size, F32)
    assert np.array_equal(np.asarray(visits.member), np.asarray(member))
    visit = np.asarray(sa.tile_visits(member, 32, 4))
    assert int(c["visited"]) == visit.sum() * 32 * 4
    # a row tile's visited key tiles first, ascending, and their count;
    # the same of a key tile's row tiles
    for lists, counts, seen in (
            (visits.rows, visits.row_counts, visit),
            (visits.cols, visits.col_counts, visit.swapaxes(-1, -2))):
        lists, counts = np.asarray(lists), np.asarray(counts)
        assert np.array_equal(counts, seen.sum(-1))
        at = (0, 1, lists.shape[2] - 1)
        n = counts[at]
        assert np.array_equal(lists[at][:n], np.flatnonzero(seen[at]))
    # a visited tile is one some token of the row tile chose a block of;
    # nothing above the diagonal is visited
    nq, nk = visit.shape[-2:]
    for i in range(nq):
        for j in range(nk):
            if j * 64 > i * 32 + 31:
                assert not visit[..., i, j].any()
    assert visit[..., np.arange(nq), np.arange(nq) // 2].all()
    assert int(c["selected"]) <= int(c["visited"])
    with sized(_BLOCK_Q=(48,)), pytest.raises(ValueError,
                                              match="do not tile"):
        sa.tile_plan(S, 16)
