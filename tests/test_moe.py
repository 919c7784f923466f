"""Expert-parallel MoE subsystem tests (ISSUE 15): seeded grouped
routing (determinism, shard invariance, the capacity-factor drop
closed form), the grouped Pallas expert-FFN kernels (einsum parity,
count skipping, int8 exactness, empty-DB bit-identity), the decomposed
a2a dispatch/combine loop (monolithic parity forward and backward, the
A/B fake legs), the SPMD training-step wiring, and the
native-vs-SPMD a2a schedule parity."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlnetbench_tpu.models import layers as L
from dlnetbench_tpu.models import moe
from dlnetbench_tpu.ops import grouped_matmul as gm

pytestmark = pytest.mark.moe

_F32 = jnp.float32


def _routing_case(t=64, d=16, e=4, seed=0):
    x = jax.random.normal(jax.random.key(seed), (t, d), _F32)
    wr = jax.random.normal(jax.random.key(seed + 100), (d, e),
                           _F32) * 0.3
    return x, wr


# ------------------------------------------------------------ routing
def _onehot_dispatch(x, wr, e, k, cf, *, drop_seed=None, group_tokens=0):
    """The dense [T, E, C] one-hot dispatch the program ran before the
    routing became a plan of indices, kept here as the reference the
    gathers are compared with: ``(xe, disp, gate)``."""
    t = x.shape[0]
    g = group_tokens or t
    n_groups, cap = t // g, moe.group_capacity(g, k, e, cf)
    weights, idx = L.moe_router(x, wr, k)
    onehot = jax.nn.one_hot(idx, e, dtype=_F32)
    gate = jnp.sum(onehot * weights[..., None], axis=1)
    maskg = jnp.sum(onehot, axis=1).reshape(n_groups, g, e)
    if drop_seed is None:
        pos = jnp.cumsum(maskg, axis=1) - 1.0
    else:
        prio = moe.token_priority(drop_seed, jnp.arange(t))
        order = jnp.argsort(prio.reshape(n_groups, g), axis=1)
        ms = jnp.take_along_axis(maskg, order[..., None], axis=1)
        pos = jnp.take_along_axis(jnp.cumsum(ms, axis=1) - 1.0,
                                  jnp.argsort(order, axis=1)[..., None],
                                  axis=1)
    keep = maskg * (pos < cap)
    slot = pos + (jnp.arange(n_groups, dtype=_F32) * cap)[:, None, None]
    disp = jax.nn.one_hot(slot.astype(jnp.int32).reshape(t, e),
                          n_groups * cap, dtype=_F32) \
        * keep.reshape(t, e)[..., None]
    return jnp.einsum("tec,td->ecd", disp, x), disp, gate


def _onehot_combine(out, disp, gate):
    return jnp.einsum("ecd,tec->td", out, disp * gate[..., None])


PATHS = {"legacy": {}, "seeded_grouped": {"drop_seed": 7,
                                          "group_tokens": 16}}


def _plan_as_onehot(plan, c_total):
    """[T, E, C] 0/1 from a plan's ``slot`` (-1 matches no slot)."""
    return (plan.slot[..., None] == jnp.arange(c_total)).astype(_F32)


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("cf", [0.5, 1.25, 2.0], ids=["cf0.5", "cf1.25",
                                                      "nodrop"])
def test_plan_dispatch_and_combine_match_onehot(cf, path):
    """Values and gradients (x, the router weight, the expert output)
    of the gathers against the one-hot matmuls: ``xe`` exact, the rest
    to 1e-6 of the largest reference entry."""
    x, wr = _routing_case()
    kw = PATHS[path]
    xe, plan, gate = moe.dispatch(x, wr, 4, 2, cf, **kw)
    xe0, disp, gate0 = _onehot_dispatch(x, wr, 4, 2, cf, **kw)
    assert xe.dtype == x.dtype and jnp.all(xe == xe0)
    assert jnp.all(gate == gate0)
    assert jnp.all(_plan_as_onehot(plan, xe.shape[1]) == disp)
    eo = jax.random.normal(jax.random.key(5), xe.shape, _F32)

    def new(x, wr, eo):
        xe, plan, gate = moe.dispatch(x, wr, 4, 2, cf, **kw)
        return jnp.sum(jnp.sin(L.moe_combine(jnp.tanh(xe) * eo, plan,
                                             gate)))

    def old(x, wr, eo):
        xe, disp, gate = _onehot_dispatch(x, wr, 4, 2, cf, **kw)
        return jnp.sum(jnp.sin(_onehot_combine(jnp.tanh(xe) * eo, disp,
                                               gate)))
    got = jax.jit(jax.value_and_grad(new, argnums=(0, 1, 2)))(x, wr, eo)
    want = jax.jit(jax.value_and_grad(old, argnums=(0, 1, 2)))(x, wr, eo)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = max(1.0, float(jnp.max(jnp.abs(b))))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-6 * scale


@pytest.mark.parametrize("path", sorted(PATHS))
def test_empty_slots_are_zero_and_dropped_tokens_get_nothing(path):
    """At capacity factor 0.5 slots stay empty in one expert while
    another drops: an empty slot of ``xe`` and of the combine's
    transpose is zero; a token dropped at both its experts gets zero
    output and zero gradient, a dropped choice zero gate gradient."""
    x, wr = _routing_case()
    x = x + 1.0                              # no zero row by accident
    xe, plan, gate = moe.dispatch(x, wr, 4, 2, 0.5, **PATHS[path])
    empty = plan.src == x.shape[0]
    lost = jnp.all(jnp.take_along_axis(plan.slot, plan.idx, 1) < 0, 1)
    assert bool(empty.any()) and bool(lost.any())
    assert jnp.all(jnp.where(empty[..., None], xe, 0.0) == 0.0)
    assert jnp.all(jnp.any(xe != 0.0, axis=-1) == ~empty)
    out = jax.random.normal(jax.random.key(6), xe.shape, _F32)
    y = L.moe_combine(out, plan, gate)
    assert jnp.all(y[lost] == 0.0) and jnp.all(jnp.any(y[~lost] != 0, 1))

    def loss(x, out):
        xe, plan, gate = moe.dispatch(x, wr, 4, 2, 0.5, **PATHS[path])
        return jnp.sum(L.moe_combine(out + xe, plan, gate) ** 2)
    dx, dout = jax.grad(loss, argnums=(0, 1))(x, out)
    assert jnp.all(dx[lost] == 0.0) and jnp.all(jnp.any(dx[~lost] != 0, 1))
    assert jnp.all(jnp.where(empty[..., None], dout, 0.0) == 0.0)
    dgate = jax.grad(lambda g: jnp.sum(L.moe_combine(out, plan, g) ** 2)
                     )(gate)
    assert jnp.all(jnp.where(plan.slot < 0, dgate, 0.0) == 0.0)
    assert jnp.all((dgate != 0.0) == (plan.slot >= 0))


def test_legacy_dispatch_bit_identical():
    """drop_seed=None + one group delegates to layers.moe_dispatch —
    the pre-ISSUE-15 harness bit for bit."""
    x, wr = _routing_case()
    xe0, p0, g0 = L.moe_dispatch(x, wr, 4, 2, 1.25)
    xe1, p1, g1 = moe.dispatch(x, wr, 4, 2, 1.25)
    assert jnp.all(xe0 == xe1) and jnp.all(g0 == g1)
    assert all(jnp.all(a == b) for a, b in zip(p0, p1))


def test_seeded_routing_deterministic_and_seed_sensitive():
    x, wr = _routing_case()
    a = moe.dispatch(x, wr, 4, 2, 0.5, drop_seed=7, group_tokens=16)
    b = moe.dispatch(x, wr, 4, 2, 0.5, drop_seed=7, group_tokens=16)
    c = moe.dispatch(x, wr, 4, 2, 0.5, drop_seed=8, group_tokens=16)
    # same seed: identical; the seed is load-bearing
    assert all(jnp.all(p == q) for p, q in zip(a[1], b[1]))
    assert not jnp.all(a[1].slot == c[1].slot)
    assert not jnp.all(a[1].src == c[1].src)


@pytest.mark.parametrize("shards", [2, 4])
def test_seeded_routing_shard_invariant(shards):
    """The acceptance bar: the kept/dropped set computed per shard is
    IDENTICAL to the single-device computation over the same global
    tokens (exact equality of the plan, a shard's tokens and slots
    counted from its own start — groups nest inside shards and the
    priority is a pure function of (seed, global token id))."""
    t, g = 64, 16
    x, wr = _routing_case(t=t)
    full = moe.dispatch(x, wr, 4, 2, 1.0, drop_seed=11, group_tokens=g,
                        gids=jnp.arange(t))
    h = t // shards
    ch = full[1].src.shape[1] // shards
    for s in range(shards):
        part = moe.dispatch(x[s * h:(s + 1) * h], wr, 4, 2, 1.0,
                            drop_seed=11, group_tokens=g,
                            gids=jnp.arange(s * h, (s + 1) * h))
        slot = full[1].slot[s * h:(s + 1) * h]
        src = full[1].src[:, s * ch:(s + 1) * ch]
        assert jnp.all(jnp.where(slot >= 0, slot - s * ch, -1)
                       == part[1].slot), f"shard {s} routing differs"
        assert jnp.all(jnp.where(src < t, src - s * h, h)
                       == part[1].src), f"shard {s} routing differs"
        assert jnp.all(full[1].idx[s * h:(s + 1) * h] == part[1].idx)
        assert jnp.all(full[2][s * h:(s + 1) * h] == part[2])
        assert jnp.all(full[0][:, s * ch:(s + 1) * ch] == part[0])


@pytest.mark.parametrize("cf", [0.25, 0.5, 1.0, 4.0])
@pytest.mark.parametrize("seed", [0, 3])
def test_drop_counts_match_capacity_closed_form(cf, seed):
    """Measured drops == sum_{g,e} max(0, n_ge - cap_g) — the
    capacity-factor closed form, at every capacity and seed."""
    x, wr = _routing_case(seed=seed)
    out = moe.dispatch(x, wr, 4, 2, cf, drop_seed=seed,
                       group_tokens=16, with_stats=True)
    stats = out[3]
    assert float(stats["dropped"]) == float(stats["expected_dropped"])
    # and the closed form recomputed independently agrees
    _, idx = L.moe_router(x, wr, 2)
    counts = np.zeros((4, 4))
    for tok in range(64):
        for kk in range(2):
            counts[tok // 16, int(idx[tok, kk])] += 1
    cap = moe.group_capacity(16, 2, 4, cf)
    assert float(stats["dropped"]) == np.maximum(
        counts - cap, 0).sum()


def test_dispatch_group_divisibility_refused():
    x, wr = _routing_case(t=60)
    with pytest.raises(ValueError, match="group_tokens"):
        moe.dispatch(x, wr, 4, 2, 1.0, group_tokens=16)


def test_stats_globals_shape():
    x, wr = _routing_case()
    stats = moe.dispatch(x, wr, 4, 2, 1.0, drop_seed=1,
                         group_tokens=16, with_stats=True)[3]
    g = moe.stats_globals(jax.device_get(stats), num_experts=4,
                          top_k=2, capacity_factor=1.0, drop_seed=1,
                          group_tokens=16)
    assert g["moe_experts"] == 4 and g["moe_drop_seed"] == 1
    blk = g["moe"]
    assert len(blk["expert_load"]) == 4
    assert abs(sum(blk["expert_load"]) - 1.0) < 1e-3
    assert 0.0 <= blk["drop_rate"] <= 1.0
    assert 0.0 <= blk["router_entropy"] <= 1.0 + 1e-6
    assert blk["load_imbalance"] >= 1.0


# ----------------------------------------------------- grouped kernel
def _gm_case(e=4, c=16, d=32, h=48, dtype=_F32):
    x = jax.random.normal(jax.random.key(0), (e, c, d), dtype)
    wg = jax.random.normal(jax.random.key(1), (e, d, h), dtype) * 0.05
    wu = jax.random.normal(jax.random.key(2), (e, d, h), dtype) * 0.05
    wd = jax.random.normal(jax.random.key(3), (e, h, d), dtype) * 0.05
    return x, wg, wu, wd


# widths that are an odd multiple of the lane tile, scaled down for the
# interpreter: 88 = 11 x 8 stands for 1408 = 11 x 128.  Each case is
# (c, d, h, explicit blocks): a block left out is the plan's, which at
# these sizes is the whole dimension.
_TILINGS = {
    "pow2": (16, 32, 48, dict(block_c=8, block_n=16, block_k=16)),
    "n88_whole_k": (16, 32, 88, dict(block_c=8, block_n=8)),
    "n88_rows_inside": (16, 32, 88, dict(block_c=4, block_n=8)),
    "n88_nk4": (16, 32, 88, dict(block_c=4, block_n=8, block_k=8)),
    "k88_nk11": (16, 88, 32, dict(block_c=4, block_n=16, block_k=8)),
    "k88_whole": (16, 88, 32, dict(block_c=4)),
    "plan": (16, 88, 32, {}),
}


def _grid_of(c, d, h, blocks):
    """(bc, bn, bk, rows inside columns) the kernel runs a case on."""
    plan = gm.tile_plan(c, d, h, 4)
    bc, bn, bk = (blocks.get(k, plan[k])
                  for k in ("block_c", "block_n", "block_k"))
    return bc, bn, bk, gm.n_outer(c, d, h, bc, bn, bk)


@pytest.mark.parametrize("tiling", _TILINGS)
def test_grouped_matmul_matches_einsum(tiling):
    c, d, h, blocks = _TILINGS[tiling]
    x, wg, _, _ = _gm_case(c=c, d=d, h=h)
    ref = jnp.einsum("ecd,edh->ech", x, wg)
    out = gm.grouped_matmul(x, wg, **blocks)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
    # the cases cover both grid orders, nk == 1 and nk > 1
    _, _, bk, inside = _grid_of(c, d, h, blocks)
    assert inside == (tiling == "n88_rows_inside")
    assert (bk == d) == (tiling in ("n88_whole_k", "n88_rows_inside",
                                    "k88_whole", "plan"))


# counts that end inside a block, at a block's edge and at 0, the empty
# experts first, between and last
_COUNTS = {"mixed": (16, 5, 0, 9), "edges": (8, 4, 16, 12),
           "empty_first_last": (0, 7, 0, 0), "all_empty": (0, 0, 0, 0)}


@pytest.mark.parametrize("counts", _COUNTS)
@pytest.mark.parametrize("tiling", [t for t in _TILINGS if t != "plan"])
def test_grouped_matmul_counts_skip(tiling, counts):
    """Blocks past an expert's count emit exact zeros; live rows match
    the dense reference."""
    c, d, h, blocks = _TILINGS[tiling]
    x, wg, _, _ = _gm_case(c=c, d=d, h=h)
    ref = jnp.einsum("ecd,edh->ech", x, wg)
    cnt = jnp.array(_COUNTS[counts], jnp.int32)
    out = gm.grouped_matmul(x, wg, counts=cnt, **blocks)
    bc = blocks["block_c"]
    for e in range(4):
        n = int(cnt[e])
        nb = -(-n // bc) * bc
        if n:
            assert float(jnp.max(jnp.abs(out[e, :n] - ref[e, :n]))) \
                < 1e-5
        assert not jnp.any(out[e, nb:])


@pytest.mark.parametrize("tiling", ["n88_whole_k", "n88_nk4"])
def test_grouped_matmul_bf16_as_stored_equals_float32_copies(tiling):
    """bf16 operands go to the dot as they are stored; the kernel used
    to widen both to float32 first.  A float32 copy of a bf16 number is
    that number, so the products are the same and only the order of
    the float32 sums can differ."""
    c, d, h, blocks = _TILINGS[tiling]
    x, wg, _, _ = _gm_case(c=c, d=d, h=h, dtype=jnp.bfloat16)
    cnt = jnp.array(_COUNTS["mixed"], jnp.int32)
    out = gm.grouped_matmul(x, wg, counts=cnt, out_dtype=_F32, **blocks)
    live = (jnp.arange(c)[None, :] // blocks["block_c"]
            * blocks["block_c"] < cnt[:, None])[..., None]
    was = jnp.einsum("ecd,edh->ech", x.astype(_F32), wg.astype(_F32),
                     precision="highest") * live
    assert out.dtype == _F32
    assert float(jnp.max(jnp.abs(out - was))) < 1e-5
    # and in the stored dtype: the old result to bf16's rounding
    out16 = gm.grouped_matmul(x, wg, counts=cnt, **blocks)
    assert out16.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(out16.astype(_F32)
                                 - was.astype(jnp.bfloat16).astype(_F32)))) \
        <= 2 ** -8 * float(jnp.max(jnp.abs(was)))


def test_grouped_matmul_int8_exact_vs_composed():
    """Same scales + associative int32 accumulation: the fused grouped
    int8 matmul EQUALS the composed XLA reference exactly (the PR-3
    exactness discipline on the expert axis)."""
    from dlnetbench_tpu.ops.quantized_matmul import (_cast_q,
                                                     scale_from_amax)
    x, wg, _, _ = _gm_case(dtype=jnp.bfloat16)
    wq, sw = gm.quantize_experts(wg, "int8")
    sx = scale_from_amax(gm.expert_amax(x), "int8")
    out = gm.grouped_matmul(x, wq, sx=sx, sw=sw, fmt="int8",
                            block_c=8, block_n=16, block_k=16)
    xq = _cast_q(x.astype(_F32) / sx[:, None, None], "int8")
    comp = (jnp.einsum("ecd,edh->ech", xq.astype(jnp.int32),
                       wq.astype(jnp.int32)).astype(_F32)
            * (sx * sw)[:, None, None]).astype(jnp.bfloat16)
    assert jnp.all(out == comp)


def _ffn_ref(x_, a, b, c, live=None):
    """The einsum SwiGLU the grouped FFN is held to; ``live`` [E, C]
    marks the rows the kernels compute (the rest emit zeros)."""
    h = (jax.nn.silu(jnp.einsum("ecd,edh->ech", x_, a))
         * jnp.einsum("ecd,edh->ech", x_, b))
    y = jnp.einsum("ech,ehd->ecd", h, c)
    return y if live is None else y * live[..., None]


_GM_BLOCKS = dict(block_c=4, block_n=16, block_k=16)


@pytest.mark.parametrize("blocks", [
    _GM_BLOCKS, dict(block_c=4, block_n=8, block_k=8), dict(block_c=4)],
    ids=["pow2", "nk_gt_1", "whole_k"])
@pytest.mark.parametrize("h", [48, 88])
@pytest.mark.parametrize("counts", [None, (16, 5, 0, 9), (8, 0, 0, 4)],
                         ids=["all_slots", "blocks_skipped", "at_edges"])
def test_grouped_ffn_grads_match_reference(counts, h, blocks):
    """All four gradients against the einsum reference, at a width
    that is a power of two times 16 and at 88 = 11 x 8 (so gate/up
    have the odd multiple in N and down has it in K).  With counts,
    x is nonzero everywhere and the cotangent is nonzero everywhere, so
    only the forward's own g, u (zeros in a skipped block) keep rows
    past the count at zero dx: a recomputed g, u would not."""
    x, wg, wu, wd = _gm_case(h=h)
    r = jax.random.normal(jax.random.key(7), x.shape, _F32)
    cnt = live = None
    if counts is not None:
        cnt = jnp.array(counts, jnp.int32)
        # a block of 4 rows is live when its first row is under the count
        live = ((jnp.arange(16)[None, :] // 4 * 4)
                < cnt[:, None]).astype(_F32)

    def loss(x_, a, b, c):
        return jnp.sum(gm.grouped_ffn(x_, a, b, c, counts=cnt,
                                      **blocks) * r)

    def ref(x_, a, b, c):
        return jnp.sum(_ffn_ref(x_, a, b, c, live) * r)

    g1 = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(x, wg, wu, wd)
    g2 = jax.jit(jax.grad(ref, argnums=(0, 1, 2, 3)))(x, wg, wu, wd)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5
    if live is not None:
        assert float(jnp.max(jnp.abs(g1[0] * (1.0 - live)[..., None]))) \
            == 0.0
        # ... and the mask is not why: a live block's rows get theirs
        assert float(jnp.max(jnp.abs(g1[0][0, :4]))) > 0.0


def _eqns_outside_kernels(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry
    (einsum's jit, custom-derivative bodies), the bodies of
    ``pallas_call`` left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_outside_kernels(sub)


def test_grouped_ffn_backward_reads_forward_g_u():
    """The backward holds six matmuls, not eight: the forward's three
    kernels wrote g and u, and nothing multiplies x by w_gate or w_up
    again (the only [E, C, h] product left is dh = dy @ w_down^T)."""
    x, wg, wu, wd = _gm_case()
    e, c, _ = x.shape
    h = wg.shape[2]

    def loss(x_, a, b, c_):
        return jnp.sum(gm.grouped_ffn(x_, a, b, c_, **_GM_BLOCKS) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
        x, wg, wu, wd).jaxpr
    eqns = list(_eqns_outside_kernels(jaxpr))
    assert sum(q.primitive.name == "pallas_call" for q in eqns) == 3
    dots = [q for q in eqns if q.primitive.name == "dot_general"]
    assert len(dots) == 6
    assert sum(q.outvars[0].aval.shape == (e, c, h) for q in dots) == 1


# ---- the counted backward (ISSUE 41) -----------------------------------
# an expert with no row first, between and last; one row; a count inside
# a block; at a block's edge; a full buffer; every expert empty
_BWD_COUNTS = {"blocks_skipped": (16, 5, 0, 9), "at_edges": (8, 0, 0, 4),
               "one_row": (0, 1, 13, 0), "full_buffer": (16, 16, 16, 16),
               "all_empty": (0, 0, 0, 0)}
_BWD_KERNELS = ["grouped_mm_bwd_dh", "grouped_mm_bwd_dx",
                "grouped_mm_bwd_dw", "grouped_mm_bwd_dw"]


@pytest.fixture(scope="module")
def counted_backward_grads():
    """``grads(h, blocks)(x, wg, wu, wd, cnt, live, r)``: the four
    gradients under ``backward="counted"``, under the einsum backward
    and of the einsum reference, jitted once a width and tiling.  The
    counts are an argument, so the grid's count cases share the build
    (interpret-mode kernels traced and compiled once, not a case)."""
    built = {}

    def grads(h, blocks):
        key = (h, tuple(sorted(blocks.items())))
        if key not in built:
            def loss(backward):
                return lambda x_, a, b, c, cnt, r: jnp.sum(gm.grouped_ffn(
                    x_, a, b, c, counts=cnt, backward=backward, **blocks)
                    * r)

            def ref(x_, a, b, c, live, r):
                return jnp.sum(_ffn_ref(x_, a, b, c, live) * r)

            def all_three(x, wg, wu, wd, cnt, live, r):
                w = (x, wg, wu, wd)
                return (jax.grad(loss("counted"), (0, 1, 2, 3))(*w, cnt, r),
                        jax.grad(loss("einsum"), (0, 1, 2, 3))(*w, cnt, r),
                        jax.grad(ref, (0, 1, 2, 3))(*w, live, r))
            built[key] = jax.jit(all_three)
        return built[key]
    return grads


@pytest.mark.parametrize("blocks", [
    _GM_BLOCKS, dict(block_c=4, block_n=8, block_k=8), dict(block_c=4)],
    ids=["pow2", "nk_gt_1", "whole_k"])
@pytest.mark.parametrize("h", [48, 88])
@pytest.mark.parametrize("counts", _BWD_COUNTS)
def test_counted_backward_matches_reference_and_einsums(
        counts, h, blocks, counted_backward_grads):
    """``backward="counted"``: all four gradients against the einsum
    reference and against the einsum backward, with x and the
    cotangent nonzero past every count.  A block is live in the
    backward if and only if it was live in the forward: rows of a live
    block past the count get their gradient, rows of a skipped block
    exact zeros, and the weight gradient of an expert with no row is
    exactly zero."""
    x, wg, wu, wd = _gm_case(h=h)
    r = jax.random.normal(jax.random.key(7), x.shape, _F32)
    cnt = jnp.array(_BWD_COUNTS[counts], jnp.int32)
    live = ((jnp.arange(16)[None, :] // 4 * 4) < cnt[:, None]).astype(_F32)
    got, was, want = counted_backward_grads(h, blocks)(
        x, wg, wu, wd, cnt, live, r)
    for a, b, c in zip(got, was, want):
        assert float(jnp.max(jnp.abs(a - c))) < 1e-5
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5
    assert not jnp.any(got[0] * (1.0 - live)[..., None])
    for e in range(4):
        if int(cnt[e]) == 0:
            assert not any(jnp.any(dw[e]) for dw in got[1:])
        elif int(cnt[e]) % 4:
            # rows past the count in a live block are multiplied
            assert jnp.any(got[0][e, int(cnt[e])])


def test_counted_backward_rounds_the_sum_of_the_dx_products_once():
    """bf16 operands holding small whole numbers, so that every float32
    sum is exact whatever its order: the row-side kernel over both
    products equals the float32 sum of the two einsums rounded once,
    to the last bit, and not the sum of two rounded products."""
    e, c, d, h = 4, 16, 32, 88
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.key(11), 4)
    dg, du = (jax.random.randint(k, (e, c, h), -9, 10).astype(bf)
              for k in ks[:2])
    wg, wu = (jax.random.randint(k, (e, d, h), -9, 10).astype(bf)
              for k in ks[2:])
    cnt = jnp.array((16, 5, 0, 9), jnp.int32)
    live = ((jnp.arange(c)[None, :] // 4 * 4) < cnt[:, None])[..., None]
    dx = gm._bwd_rows((dg, du), (wg, wu), cnt, (4, 8, 8),
                      name="grouped_mm_bwd_dx")
    parts = [jnp.einsum("ech,edh->ecd", t.astype(_F32), w.astype(_F32),
                        precision="highest") * live
             for t, w in ((dg, wg), (du, wu))]
    once = (parts[0] + parts[1]).astype(bf)
    twice = parts[0].astype(bf) + parts[1].astype(bf)
    assert dx.dtype == bf and jnp.all(dx == once)
    assert jnp.any(once != twice)


def test_counted_backward_in_bf16_is_the_einsum_backward_to_rounding():
    """bf16 as the cells run it: ``h``, ``dg``, ``du`` are rounded to
    bf16 on their way to a kernel, which is what the MXU's default
    precision does to the einsums' float32 operands on the chip (there
    the two paths read 2e-5 apart, PERF.md section 6, PR 41); the CPU
    multiplies the einsums' float32 operands as they are, so here the
    gap is that one rounding."""
    x, wg, wu, wd = _gm_case(dtype=jnp.bfloat16)
    cnt = jnp.array((16, 5, 0, 9), jnp.int32)

    def loss(backward):
        return lambda x_, a, b, c: jnp.sum(gm.grouped_ffn(
            x_, a, b, c, counts=cnt, backward=backward,
            **_GM_BLOCKS).astype(_F32) ** 2)

    got = jax.grad(loss("counted"), argnums=(0, 1, 2, 3))(x, wg, wu, wd)
    was = jax.grad(loss("einsum"), argnums=(0, 1, 2, 3))(x, wg, wu, wd)
    for a, b in zip(got, was):
        assert a.dtype == b.dtype == jnp.bfloat16
        a, b = a.astype(_F32), b.astype(_F32)
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 2 ** -7


def test_counted_backward_refuses_what_it_cannot_count():
    x, wg, wu, wd = _gm_case()
    with pytest.raises(ValueError, match="counts"):
        gm.grouped_ffn(x, wg, wu, wd, backward="counted")
    with pytest.raises(ValueError, match="quantized"):
        gm.grouped_ffn(x, wg, wu, wd, counts=jnp.full((4,), 16), fmt="int8",
                       backward="counted")
    with pytest.raises(ValueError, match="backward"):
        gm.grouped_ffn(x, wg, wu, wd, backward="ragged")


def _held_case(t=32, d=16, e=4, f=24):
    ks = jax.random.split(jax.random.key(5), 5)
    return (jax.random.normal(ks[0], (t, d), _F32),
            jax.random.normal(ks[1], (d, e), _F32),
            jax.random.normal(ks[2], (e, d, f), _F32) * 0.1,
            jax.random.normal(ks[3], (e, d, f), _F32) * 0.1,
            jax.random.normal(ks[4], (e, f, d), _F32) * 0.1)


_EXPERT_LAYERS = {
    "moe_held": (lambda *a: moe.moe_held(*a, 2, held=(0, 4), slots=32)[0],
                 "counted", 4 * 32),
    "moe_grouped": (lambda *a: moe.moe_grouped(*a, 2, 1.25), "einsum",
                    4 * moe.group_capacity(32, 2, 4, 1.25)),
}


def _eqns_under(jaxpr, scope: str, inside: bool = False):
    """The equations outside kernels whose name stack holds ``scope``,
    or that lie in a jaxpr carried by one that does (a ``jit`` of its
    own starts a name stack of its own)."""
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        if here:
            yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_under(sub, scope, here)


def _expert_eqns(layer):
    """(kernel names, dot_generals outside kernels) under the scope
    ``moe.experts`` in the gradient's jaxpr of an expert layer."""
    args = _held_case()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(layer(*a) ** 2), argnums=(0, 1, 2, 3, 4)))(
            *args).jaxpr
    eqns = list(_eqns_under(jaxpr, "moe.experts"))
    return ([q.params["name"] for q in eqns
             if q.primitive.name == "pallas_call"],
            [q for q in eqns if q.primitive.name == "dot_general"])


def test_moe_held_backward_is_kernels_by_name():
    """The layer whose buffer is a bound takes the counted backward:
    its gradient holds the forward's three kernels and the backward's
    four by their names, and nothing multiplies an ``[E, C, .]`` array
    outside a kernel under ``moe.experts``."""
    kernels, dots = _expert_eqns(_EXPERT_LAYERS["moe_held"][0])
    assert kernels == ["grouped_mm"] * 3 + _BWD_KERNELS
    assert dots == []


@pytest.mark.parametrize("path", ["moe_grouped", "int8"])
def test_capacity_and_quantized_paths_keep_the_einsum_backward(path):
    """``moe_grouped`` (a capacity: rows are dropped to fit) and the
    quantized recipes keep ``_grouped_ffn_bwd``: three kernels, all the
    forward's, and six einsums."""
    if path == "int8":
        x, wg, wu, wd = _gm_case(dtype=jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(gm.grouped_ffn(
            *a, fmt="int8", **_GM_BLOCKS).astype(_F32) ** 2),
            argnums=(0, 1, 2, 3)))(x, wg, wu, wd).jaxpr
        eqns = list(_eqns_outside_kernels(jaxpr))
        kernels = [q.params["name"] for q in eqns
                   if q.primitive.name == "pallas_call"]
        dots = [q for q in eqns if q.primitive.name == "dot_general"]
    else:
        kernels, dots = _expert_eqns(_EXPERT_LAYERS["moe_grouped"][0])
    assert kernels == ["grouped_mm"] * 3
    assert len(dots) == 6


@pytest.mark.parametrize("name", sorted(_EXPERT_LAYERS))
def test_a_traced_expert_layer_marks_its_backward(name):
    """One ``moe.experts_bwd`` mark a traced site, on the open span
    (a build's ``compile``), and none without a tracer."""
    from dlnetbench_tpu.metrics import spans
    layer, path, slots = _EXPERT_LAYERS[name]
    args = _held_case()

    def trace():
        jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(layer(*a) ** 2)))(*args)
    trace()
    assert spans.current() is None
    tracer = spans.enable()
    try:
        with spans.span("compile", fn="layer"):
            trace()
    finally:
        spans.disable()
    build, = tracer.export()["spans"]
    assert build["attrs"]["moe.experts_bwd"] == [
        {"path": path, "slots": slots,
         "row_block": gm.tile_plan(slots // 4, 16, 24, 4)["block_c"]}]


@pytest.mark.parametrize("fmt", ["int8", "float8"])
def test_grouped_ffn_quantized_grads_straight_through(fmt):
    """A quantized format's backward is the master-dtype gradient at
    the forward's own (quantized-kernel) g, u: close to the unquantized
    reference's, to the format's rounding."""
    x, wg, wu, wd = _gm_case(dtype=jnp.bfloat16)

    def loss(x_, a, b, c):
        y = gm.grouped_ffn(x_, a, b, c, fmt=fmt, **_GM_BLOCKS)
        return jnp.sum(y.astype(_F32) ** 2)

    def ref(x_, a, b, c):
        return jnp.sum(_ffn_ref(x_, a, b, c) ** 2)

    g1 = jax.grad(loss, argnums=(0, 1, 2, 3))(x, wg, wu, wd)
    g2 = jax.grad(ref, argnums=(0, 1, 2, 3))(
        *(t.astype(_F32) for t in (x, wg, wu, wd)))
    for a, b in zip(g1, g2):
        assert a.dtype == jnp.bfloat16
        a = a.astype(_F32)
        assert jnp.all(jnp.isfinite(a))
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 0.15


def test_grouped_ffn_fp8_runs_finite():
    x, wg, wu, wd = _gm_case(dtype=jnp.bfloat16)
    y = gm.grouped_ffn(x, wg, wu, wd, fmt="float8", block_c=8,
                       block_n=16, block_k=16)
    assert jnp.all(jnp.isfinite(y.astype(_F32)))


def test_grouped_blocks_validated():
    x, wg, _, _ = _gm_case()
    with pytest.raises(ValueError, match="block_c"):
        gm.grouped_matmul(x, wg, block_c=-4, block_n=16, block_k=16)
    with pytest.raises(ValueError, match="fmt"):
        gm.grouped_matmul(x, wg, fmt="int4")
    with pytest.raises(ValueError, match="sx/sw"):
        gm.grouped_matmul(x, wg, fmt="int8")


@pytest.mark.tuning
def test_grouped_ffn_empty_db_bit_identity(tmp_path, monkeypatch):
    """The ISSUE-9 consult contract on this site: with no DB the
    consult path is BIT-identical to the explicit blocks that each
    matmul's own ``tile_plan`` returns, and a committed record is
    consulted (frozen after first consult)."""
    from dlnetbench_tpu import tuning
    x, wg, wu, wd = _gm_case(e=2, c=8, d=16, h=16)
    tuning.reset(clear_env=True)
    try:
        y_off = gm.grouped_ffn(x, wg, wu, wd)
        # d == h: gate, up and down are one shape and share one plan
        y_exp = gm.grouped_ffn(x, wg, wu, wd, **gm.tile_plan(8, 16, 16, 4))
        assert jnp.all(y_off == y_exp)
        assert tuning.provenance() is None  # disabled: logs nothing
        # now a DB with a record for THIS key must hit
        from dlnetbench_tpu.tuning.db import TuningDB
        monkeypatch.setenv(tuning.params.ENV_DB_DIR, str(tmp_path))
        db = TuningDB(str(tmp_path))
        key = tuning.params.grouped_ffn_key(2, 8, 16, 16, "none",
                                            x.dtype)
        db.put("grouped_ffn", key, tuning.params.hw_key(),
               {"block_c": 4, "block_n": 8, "block_k": 8})
        tuning.reset()
        y_tuned = gm.grouped_ffn(x, wg, wu, wd)
        prov = tuning.provenance()
        hit = [v for k, v in prov["sites"].items()
               if k == f"grouped_ffn|{key}"]
        assert hit and hit[0]["hit"]
        assert hit[0]["config"]["block_c"] == 4
        # tuned divisor blocks produce the same values (pure tiling)
        assert float(jnp.max(jnp.abs(y_tuned - y_off))) < 1e-5
    finally:
        tuning.reset(clear_env=True)


# ------------------------------------------------- the tile plan alone
# the four matmuls the benchmark's cells run, bf16: (E, C, K, N), counts
# as a window of the cell reads them (one expert emptied, to walk the
# steps that look past it), and the most bytes a call may move (inputs
# fetched plus output written).  The frozen triple (512, 1024, 1024)
# halved to divisors walked 3.7 GB of inputs for the first: 2816 steps
# of a 1 MiB activation tile and a 256 KiB weight block each.
_KIMI_ROWS = (1536, 1210, 1922, 1405, 2344, 0, 1660, 1490, 1380, 1777,
              1033, 1605, 1850, 1290, 1512, 1462)
_MIXTRAL_ROWS = (2560, 2560, 1800, 1700, 2204, 2560, 1500, 1500)
_CELL_MATMULS = {
    "kimi_gate_up": (16, 4096, 2048, 1408, _KIMI_ROWS, 0.5e9),
    "kimi_down": (16, 4096, 1408, 2048, _KIMI_ROWS, 0.5e9),
    "mixtral_gate_up": (8, 2560, 4096, 14336, _MIXTRAL_ROWS, 2.8e9),
    "mixtral_down": (8, 2560, 14336, 4096, _MIXTRAL_ROWS, 3.5e9),
}


def _walk(e, c, kdim, n, counts):
    """The grid of the planned kernel, step by step, with its index
    maps as plain functions: ``[(live, x block, w block)]`` and the
    blocks ``(bc, bn, bk)``."""
    plan = gm.tile_plan(c, kdim, n, 2)
    bc, bn, bk = plan["block_c"], plan["block_n"], plan["block_k"]
    nc, nn, nk = c // bc, n // bn, kdim // bk
    inside = gm.n_outer(c, kdim, n, bc, bn, bk)
    cnt = np.asarray(counts, np.int32)
    te, tc = (np.asarray(t) for t in gm.hold_table(jnp.asarray(cnt), bc))
    x_index, w_index, _ = gm.index_maps(bc, nn, nk, inside)
    steps = []
    for idx in np.ndindex(*((e, nn, nc, nk) if inside else (e, nc, nn, nk))):
        ci = idx[2] if inside else idx[1]
        steps.append((bool(ci * bc < cnt[idx[0]]),
                      tuple(int(v) for v in x_index(*idx, cnt, te, tc)),
                      tuple(int(v) for v in w_index(*idx, cnt, te, tc)),
                      idx))
    return steps, (bc, bn, bk), inside


@pytest.mark.parametrize("name", _CELL_MATMULS)
def test_tile_plan_of_the_cells_matmuls(name):
    """From shapes alone: the blocks divide, a step's tiles fit the
    budget (and so the Mosaic limit), the contraction is whole and no
    output tile is narrower than its dimension allows; walking the
    grid, a live step names its own blocks, a step past the count
    names the next live step's (so consecutive ones name the same and
    the pipeline fetches nothing of their own), and the bytes a call
    moves stay under the case's figure."""
    from dlnetbench_tpu.ops import pallas_common
    e, c, kdim, n, counts, most = _CELL_MATMULS[name]
    steps, (bc, bn, bk), inside = _walk(e, c, kdim, n, counts)
    assert c % bc == 0 and n % bn == 0 and kdim % bk == 0
    assert gm.tile_bytes(bc, bn, bk, 2) <= gm.VMEM_BUDGET \
        < pallas_common.DEFAULT_VMEM_LIMIT_MB * 2 ** 20
    assert bc == gm.ROW_BLOCK and bk == kdim
    if name.startswith("kimi"):
        # 1408 = 11 x 128 whole, never 128: an expert's weight resident
        assert bn == n and not inside
    else:
        # a weight block stays while the expert's rows pass
        assert bn % 128 == 0 and bn >= 1024 and inside
    needed_x = {x for live, x, _, _ in steps if live}
    needed_w = {w for live, _, w, _ in steps if live}
    fetched, held = 0, (None, None)
    for i, (live, x, w, idx) in enumerate(steps):
        if live:
            ci, ni = (idx[2], idx[1]) if inside else (idx[1], idx[2])
            assert x == (idx[0], ci, idx[3]) and w == (idx[0], idx[3], ni)
        else:
            # whatever it names, a live step needs
            assert x in needed_x and w in needed_w
            if i and not steps[i - 1][0]:
                assert (x, w) == steps[i - 1][1:3]
            nxt = next((s for s in steps[i + 1:] if s[0]), None)
            if nxt is not None:
                assert (x, w) == nxt[1:3]
        fetched += (x != held[0]) * bc * bk * 2 + (w != held[1]) * bk * bn * 2
        held = (x, w)
    assert fetched + e * c * n * 2 < most
    # each input block comes once for every run of steps that use it
    rows = sum(-(-r // bc) * bc for r in counts)
    if name.startswith("kimi"):
        assert fetched == rows * kdim * 2 + sum(
            r > 0 for r in counts) * kdim * n * 2


def test_tile_plan_takes_a_dimension_whole_or_by_lane_multiples():
    """Never by halving: 1408 is 1408 when it fits and 11 x 128-lane
    blocks' divisor when it does not; a budget nothing fits gives the
    smallest tiles there are, not an error."""
    assert gm.blocks_of(1408, 128) == [1408, 128]
    assert gm.blocks_of(14336, 128)[:4] == [14336, 7168, 3584, 2048]
    assert gm.blocks_of(88, 128) == [88]
    whole = gm.tile_plan(4096, 2048, 1408, 2)
    assert (whole["block_n"], whole["block_k"]) == (1408, 2048)
    tight = gm.tile_plan(4096, 2048, 1408, 2, budget=8 * 2 ** 20)
    assert tight["block_n"] == 1408 and tight["block_k"] < 2048
    assert 2048 % tight["block_k"] == 0 and tight["block_k"] % 128 == 0
    least = gm.tile_plan(4096, 2048, 1408, 2, budget=1)
    assert (least["block_n"], least["block_k"]) == (128, 128)
    # rows: a divisor that is a multiple of the dtype's sublane tile
    assert gm.tile_plan(2560, 4096, 14336, 2)["block_c"] == 256
    assert gm.tile_plan(24, 32, 48, 4)["block_c"] == 24
    # the quantizing prologue's float32 copy of the rows counts too
    assert gm.tile_bytes(512, 512, 14336, 2, quantized=True) \
        == gm.tile_bytes(512, 512, 14336, 2) + 512 * 14336 * 4


# the contraction side of the three held cells' expert layers, bf16:
# (E, C, K, N, outputs a call, rows an expert as a window reads them)
_CELL_DW = {
    "lfm2_down": (32, 2048, 1792, 2048, 1, (1024,) * 30 + (0, 2048)),
    "lfm2_gate_up": (32, 2048, 2048, 1792, 2, (1024,) * 30 + (0, 2048)),
    "kimi_gate_up": (16, 4096, 2048, 1408, 2, _KIMI_ROWS),
    "qwen_gate_up": (32, 1536, 2048, 512, 2, (0, 0) + (322,) * 29 + (0,)),
}


@pytest.mark.parametrize("name", _CELL_DW)
def test_dw_tile_plan_of_the_cells_keeps_a_whole_gradient(name):
    """An expert's whole ``[K, N]`` gradient (both of gate and up) fits
    a step, so every live row block is fetched once and a step past
    the count names the blocks the step before it named: it fetches
    nothing, and an expert with no row nothing either."""
    e, c, kdim, n, outs, counts = _CELL_DW[name]
    bc = gm.tile_plan(c, kdim, n, 2)["block_c"]
    plan = gm.dw_tile_plan(bc, kdim, n, 2, outs=outs)
    assert plan == {"block_k": kdim, "block_n": n}
    cnt = np.asarray(counts, np.int32)
    be, bl = (np.asarray(t) for t in gm.last_live(jnp.asarray(cnt), bc))
    fetched, before = 0, None
    for ei in range(e):
        for ci in range(c // bc):
            live = ci * bc < cnt[ei]
            block = (ei, ci) if live else (int(be[ei]), int(bl[ei]))
            fetched += block != before
            assert live or block == before or before is None
            before = block
    assert fetched == int((-(-cnt // bc)).sum()) + (cnt[0] == 0)


def test_dw_tile_plan_splits_by_lane_multiples_under_a_tight_budget():
    whole = gm.dw_tile_plan(256, 2048, 1792, 2, outs=2)
    assert whole == {"block_k": 2048, "block_n": 1792}
    tight = gm.dw_tile_plan(256, 2048, 1792, 2, outs=2, budget=24 * 2 ** 20)
    bk, bn = tight["block_k"], tight["block_n"]
    assert (bk, bn) != (2048, 1792)
    assert 2048 % bk == 0 and 1792 % bn == 0 and bk % 128 == bn % 128 == 0
    least = gm.dw_tile_plan(256, 2048, 1792, 2, budget=1)
    assert least == {"block_k": 128, "block_n": 128}


def test_moe_grouped_matches_sparse_lossless():
    x, wr = _routing_case(t=32, d=16)
    _, wg, wu, wd = _gm_case(e=4, c=32, d=16, h=24)
    ys = L.moe_sparse(x, wr, wg, wu, wd, 2, capacity_factor=2.0)
    yg = moe.moe_grouped(x, wr, wg, wu, wd, 2, capacity_factor=2.0)
    assert float(jnp.max(jnp.abs(ys - yg))) < 1e-5


def test_transformer_moe_grouped_impl():
    """moe_impl='grouped' runs the transformer forward/loss and stays
    near the sparse impl (same routing, grouped kernels)."""
    from dlnetbench_tpu.models import transformer as tfm
    kw = dict(vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2,
              ff_dim=32, num_layers=2, seq_len=16, gated=True,
              max_positions=0, dtype="float32", num_experts=4,
              top_k=2, moe_capacity_factor=2.0)
    cfg_s = tfm.TransformerConfig(moe_impl="sparse", **kw)
    cfg_g = tfm.TransformerConfig(moe_impl="grouped", **kw)
    params = tfm.init_params(jax.random.key(0), cfg_s)
    toks = jax.random.randint(jax.random.key(1), (2, 17), 0, 64)
    l_s = float(tfm.loss_fn(params, toks, cfg_s))
    l_g = float(tfm.loss_fn(params, toks, cfg_g))
    assert abs(l_s - l_g) < 1e-4 * max(1.0, abs(l_s))


# --------------------------------------------------- decomposed a2a
def _shardmap_ffn(fn, mesh):
    from jax.sharding import PartitionSpec as P

    from dlnetbench_tpu.utils.jax_compat import shard_map
    specs = (P("tp"), P("tp"), P("tp"), P("tp"))
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=specs,
                             out_specs=P("tp"), check_vma=False))


def _a2a_case(n=4, e=8, c=6, d=16, h=24):
    """Per-rank [E, C, d] dispatch buffers stacked on the shard axis
    (shard_map P("tp") hands each rank its own buffer) + GLOBAL expert
    weights sharded to [E/n, ...] per rank."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:n]), ("tp",))
    ein = jax.random.normal(jax.random.key(0), (n * e, c, d), _F32)
    wg = jax.random.normal(jax.random.key(1), (e, d, h), _F32) * 0.1
    wu = jax.random.normal(jax.random.key(2), (e, d, h), _F32) * 0.1
    wd = jax.random.normal(jax.random.key(3), (e, h, d), _F32) * 0.1
    return mesh, ein, wg, wu, wd


def test_a2a_expert_ffn_matches_monolithic(eight_devices):
    from jax import lax

    from dlnetbench_tpu.ops.moe_dispatch import a2a_expert_ffn
    mesh, ein, wg, wu, wd = _a2a_case()

    def mono(e_, a, b, c):
        x = lax.all_to_all(e_, "tp", split_axis=0, concat_axis=1,
                           tiled=True)
        y = moe.expert_ffn(x, a, b, c)
        return lax.all_to_all(y.astype(e_.dtype), "tp", split_axis=1,
                              concat_axis=0, tiled=True)

    def deco(e_, a, b, c):
        return a2a_expert_ffn(e_, a, b, c, "tp", moe.expert_ffn,
                              chunks=2).astype(e_.dtype)

    out_m = np.asarray(_shardmap_ffn(mono, mesh)(ein, wg, wu, wd))
    out_d = np.asarray(_shardmap_ffn(deco, mesh)(ein, wg, wu, wd))
    assert np.abs(out_m - out_d).max() < 1e-6


def test_a2a_expert_ffn_backward_matches(eight_devices):
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from dlnetbench_tpu.ops.moe_dispatch import a2a_expert_ffn
    from dlnetbench_tpu.utils.jax_compat import shard_map
    mesh, ein, wg, wu, wd = _a2a_case()

    def grads_of(fn):
        def local(e_, a, b, c):
            def l(e2, a2, b2, c2):
                return jnp.sum(fn(e2, a2, b2, c2) ** 2)
            return jax.grad(l, argnums=(0, 1, 2, 3))(e_, a, b, c)
        specs = (P("tp"),) * 4
        f = jax.jit(shard_map(local, mesh=mesh, in_specs=specs,
                              out_specs=(P("tp"),) * 4,
                              check_vma=False))
        return [np.asarray(g) for g in f(ein, wg, wu, wd)]

    def mono(e_, a, b, c):
        x = lax.all_to_all(e_, "tp", split_axis=0, concat_axis=1,
                           tiled=True)
        y = moe.expert_ffn(x, a, b, c)
        return lax.all_to_all(y.astype(e_.dtype), "tp", split_axis=1,
                              concat_axis=0, tiled=True)

    def deco(e_, a, b, c):
        return a2a_expert_ffn(e_, a, b, c, "tp",
                              moe.expert_ffn).astype(e_.dtype)

    for a, b in zip(grads_of(mono), grads_of(deco)):
        assert np.abs(a - b).max() < 1e-5


def test_a2a_expert_ffn_fake_legs(eight_devices):
    """The A/B decomposition legs keep shapes (comm leg) / values that
    differ from the full program (both legs are stubs, not the real
    math) while executing — the overlap metric's Tc/Tm inputs."""
    from dlnetbench_tpu.ops.moe_dispatch import a2a_expert_ffn
    mesh, ein, wg, wu, wd = _a2a_case()
    full = _shardmap_ffn(
        lambda e_, a, b, c: a2a_expert_ffn(e_, a, b, c, "tp",
                                           moe.expert_ffn)
        .astype(e_.dtype), mesh)(ein, wg, wu, wd)
    for kw in ({"fake_compute": True}, {"fake_comm": True}):
        out = _shardmap_ffn(
            lambda e_, a, b, c, _kw=kw: a2a_expert_ffn(
                e_, a, b, c, "tp", moe.expert_ffn, **_kw).astype(e_.dtype),
            mesh)(ein, wg, wu, wd)
        assert out.shape == full.shape
        assert np.all(np.isfinite(np.asarray(out)))


def test_a2a_expert_ffn_rejects_flat_weights():
    from dlnetbench_tpu.ops.moe_dispatch import a2a_expert_ffn
    with pytest.raises(ValueError, match="E_local"):
        a2a_expert_ffn(jnp.zeros((4, 2, 8)), jnp.zeros((8, 16)),
                       jnp.zeros((8, 16)), jnp.zeros((16, 8)), "tp",
                       moe.expert_ffn)


# --------------------------------------------------------- SPMD step
def test_spmd_moe_knob_validation():
    from dlnetbench_tpu.models import spmd
    with pytest.raises(ValueError, match="moe_a2a"):
        spmd.SpmdConfig(moe_a2a="ring").validate(1, 1, 2)
    with pytest.raises(ValueError, match="group_tokens"):
        spmd.SpmdConfig(moe_group_tokens=12).validate(1, 1, 2)
    with pytest.raises(ValueError, match="grouped"):
        spmd.SpmdConfig(moe_ffn_quant="int8").validate(1, 1, 2)
    with pytest.raises(ValueError, match="quant"):
        spmd.SpmdConfig(mlp_int8=True,
                        moe_ffn_impl="grouped").validate(1, 1, 2)


def test_spmd_moe_decomposed_parity(eight_devices):
    """The dryrun bar as a test: decomposed a2a (and the grouped FFN)
    produce the SAME training step as the monolithic einsum baseline
    under seeded grouped routing at finite capacity."""
    import dataclasses

    from dlnetbench_tpu.models import spmd
    cfg0 = spmd.SpmdConfig(batch=8, num_microbatches=2,
                           capacity_factor=1.0, moe_drop_seed=11,
                           moe_group_tokens=8)
    mesh, cfg0, step0, params, tokens = spmd.build(8, cfg0)
    p0, l0 = step0(params, tokens)
    for kw in (dict(moe_a2a="decomposed", moe_chunks=2),
               dict(moe_ffn_impl="grouped")):
        cfg_x = dataclasses.replace(cfg0, **kw)
        step_x = spmd.make_train_step(mesh, cfg_x)
        px, lx = step_x(params, tokens)
        assert abs(float(lx) - float(l0)) <= 1e-4 * max(
            1.0, abs(float(l0))), kw
        dmax = max(float(jnp.max(jnp.abs(
            a.astype(_F32) - b.astype(_F32))))
            for a, b in zip(jax.tree.leaves(px), jax.tree.leaves(p0)))
        assert dmax <= 1e-4, (kw, dmax)


def test_spmd_moe_decomposed_variants_run(eight_devices):
    """The A/B decomposition legs of the decomposed-MoE step compile
    and execute (the overlap-fraction metric's inputs)."""
    from dlnetbench_tpu.models import spmd
    cfg = spmd.SpmdConfig(batch=8, num_microbatches=2,
                          moe_a2a="decomposed")
    mesh, cfg, _, params, tokens = spmd.build(8, cfg)
    for variant in ("compute", "comm"):
        step = spmd.make_train_step(mesh, cfg, variant=variant)
        out = step(params, tokens)
        jax.block_until_ready(out)


# ------------------------------------------------- schedule parity
def test_a2a_elems_matches_native_schedule():
    """Native-vs-SPMD MoE schedule parity (the satellite): the twin
    helper restates core/schedule.moe_schedule's a2a arithmetic — the
    formula the native hybrid_3d_moe proxy declares and moves — and
    the JAX tier's ACTUAL dispatch buffer equals it at dp=1, cf=1."""
    from dlnetbench_tpu.core.model_card import load_model_card
    from dlnetbench_tpu.core.model_stats import load_model_stats
    from dlnetbench_tpu.core.schedule import moe_schedule
    stats = load_model_stats("mixtral_8x7b_16_bfloat16")
    card = load_model_card("mixtral_8x7b")
    for ep in (2, 4):
        sched = moe_schedule(stats, card, num_stages=4,
                             num_microbatches=2, num_expert_shards=ep)
        tokens_per_mb = (stats.batch_size // 2) * stats.seq_len
        assert sched.a2a_elems == moe.a2a_elems_per_rank(
            tokens_per_mb, card.top_k, stats.embed_dim, ep)
        # 2 a2as (dispatch+combine) per MoE layer per direction
        assert sched.a2a_per_direction == 2 * (card.num_layers // 4)


def test_spmd_dispatch_buffer_matches_twin():
    """The twin arithmetic against the REAL dispatch buffer: at dp=1
    and capacity_factor=1 the [E, C, d] buffer _moe_block hands the EP
    all-to-all holds exactly the native message's elements."""
    from dlnetbench_tpu.models import spmd
    cfg = spmd.SpmdConfig(batch=4, num_microbatches=2, seq_len=32,
                          num_experts=4, top_k=2, capacity_factor=1.0,
                          embed_dim=64)
    tp = 2
    t_loc = (cfg.batch // (1 * cfg.num_microbatches)) * \
        (cfg.seq_len // tp)
    x, wr = _routing_case(t=t_loc, d=cfg.embed_dim)
    xe, _, _ = moe.dispatch(x, wr, cfg.num_experts, cfg.top_k,
                            cfg.capacity_factor)
    assert xe.size == moe.spmd_a2a_elems(cfg, dp=1, tp=tp)
    # the native formula over this rank's token share (ep == tp, the
    # per-rank tokens are the global microbatch over dp*tp)
    assert xe.size == moe.a2a_elems_per_rank(
        t_loc * tp, cfg.top_k, cfg.embed_dim, tp)


def test_bandwidth_moe_columns():
    """A record carrying the moe global surfaces expert_imbalance /
    moe_drop_rate on its bandwidth rows; dense records get NaN."""
    pd = pytest.importorskip("pandas")  # noqa: F841
    from dlnetbench_tpu.analysis.bandwidth import (bandwidth_summary,
                                                   effective_bandwidth)
    rec = {
        "section": "t", "num_runs": 1,
        "global": {"model": "m", "comm_model": {
            "ep_comm_time": [{"kind": "alltoall", "group": 2,
                              "bytes": 1024}]},
            "moe": {"load_imbalance": 2.5, "drop_rate": 0.1}},
        "mesh": {"platform": "cpu"},
        "ranks": [{"rank": 0, "ep_comm_time": [100.0]}],
    }
    bw = effective_bandwidth([rec])
    assert float(bw["expert_imbalance"].iloc[0]) == 2.5
    assert float(bw["moe_drop_rate"].iloc[0]) == 0.1
    summ = bandwidth_summary([rec])
    assert "expert_imbalance" in summ.columns
    clean = dict(rec, **{"global": {"model": "m",
                                    "comm_model": rec["global"]
                                    ["comm_model"]}})
    bw2 = effective_bandwidth([clean])
    assert np.isnan(float(bw2["expert_imbalance"].iloc[0]))


def test_merge_moe_volatile():
    """The measured moe block is per-process state, never run
    identity: _comparable_global drops it, so differently-imbalanced
    hosts merge."""
    from dlnetbench_tpu.metrics.merge import _comparable_global
    g = {"model": "m", "moe": {"load_imbalance": 2.0},
         "moe_experts": 8}
    out = _comparable_global(g)
    assert "moe" not in out
    assert out["moe_experts"] == 8   # the KNOB stays comparable


@pytest.mark.tuning
def test_tune_cli_grouped_ffn_e2e(tmp_path, monkeypatch):
    """search -> commit -> consult -> hit on a tiny CPU shape, keys
    built by the same builders the site consults."""
    from dlnetbench_tpu import tuning
    from dlnetbench_tpu.tuning.__main__ import main
    tuning.reset(clear_env=True)
    try:
        rc = main(["tune", "--op", "grouped_ffn", "--db",
                   str(tmp_path), "--experts", "2", "--capacity", "8",
                   "--d", "16", "--n", "16", "--fmt", "none",
                   "--candidates", "4,8,8;8,16,16", "--k", "2",
                   "--rounds", "2"])
        assert rc == 0
        monkeypatch.setenv(tuning.params.ENV_DB_DIR, str(tmp_path))
        tuning.reset()
        x, wg, wu, wd = _gm_case(e=2, c=8, d=16, h=16)
        gm.grouped_ffn(x, wg, wu, wd)
        prov = tuning.provenance()
        key = tuning.params.grouped_ffn_key(2, 8, 16, 16, "none",
                                            x.dtype)
        assert prov["sites"][f"grouped_ffn|{key}"]["hit"]
    finally:
        tuning.reset(clear_env=True)
