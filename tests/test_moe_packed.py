"""The packed layout of a held expert layer's rows (ISSUE 45): where the
bound reserves more slots than all the (token, choice) pairs can fill,
``moe_held`` keeps one buffer ``[1, R, d]`` in which each expert's rows
start at a row-block boundary, and dispatch, the grouped kernels, the
counted backward and combine go through it.  The same rows are
multiplied in the same blocks and summed in the same order as in the
padded ``[E, C, d]`` layout, so the two are equal to the bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlnetbench_tpu.metrics import spans
from dlnetbench_tpu.models import layers as L
from dlnetbench_tpu.models import moe
from dlnetbench_tpu.ops import grouped_matmul as gm

_F32, _BF16 = jnp.float32, jnp.bfloat16
BC = 16                 # the kernels' row block, forced small
T, K, D, F = 64, 2, 32, 48
N_ALL, HELD = 12, (3, 6)


@pytest.fixture(autouse=True)
def small_row_block(monkeypatch):
    monkeypatch.setattr(gm, "ROW_BLOCK", BC)


def forced_padded(monkeypatch):
    monkeypatch.setattr(L, "packed_room", lambda *a: None)


# ---- the layer --------------------------------------------------------

def logits_of(load: str, seed: int = 0):
    """Router logits [T, N_ALL] over all the router's experts, of which
    ``HELD`` live here: ``even`` a seeded draw; ``skewed`` every token's
    first choice the second held expert (T rows, the bound where it is
    T), three held experts chosen by nobody; ``past`` the same under a
    bound below T."""
    rng = np.random.default_rng(seed)
    z = rng.standard_normal((T, N_ALL)).astype(np.float32)
    if load != "even":
        first, n = HELD
        z[:, first + 1] = 40.0
        z[:, first + 2:first + 5] = -40.0
    return z


SLOTS = {"even": 64, "skewed": 64, "past": 48}


def layer_case(load, dtype, seed=0):
    """``moe_held``'s arguments: the router reads ``router_x`` through
    a ``w_router`` that hands its first N_ALL lanes on, so the logits
    are ``logits_of(load)`` (rounded to ``dtype``) and still a function
    of both."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    e = HELD[1]
    router_x = jnp.zeros((T, D), _F32).at[:, :N_ALL].set(
        logits_of(load, seed)).astype(dtype)
    w_router = jnp.eye(D, N_ALL, dtype=_F32).astype(dtype)
    x = jax.random.normal(ks[0], (T, D), _F32).astype(dtype)
    w = [(jax.random.normal(k, shape, _F32) * 0.2).astype(dtype)
         for k, shape in zip(ks[1:4], ((e, D, F), (e, D, F), (e, F, D)))]
    return x, router_x, w_router, *w


def held(load, activation):
    def layer(x, router_x, w_router, w_gate, w_up, w_down):
        return moe.moe_held(x, w_router, w_gate, w_up, w_down, K, held=HELD,
                            slots=SLOTS[load], router_x=router_x,
                            activation=activation)

    def loss(*args):
        y, routing = layer(*args)
        return jnp.sum(jnp.sin(y.astype(_F32))), (y, routing)
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)),
                                      has_aux=True))


@pytest.mark.parametrize("load,dtype,activation", [
    ("even", _F32, "silu"), ("even", _BF16, "relu"),
    ("skewed", _F32, "relu"), ("skewed", _BF16, "silu"),
    ("past", _F32, "silu"), ("past", _BF16, "relu")],
    ids=lambda v: v if isinstance(v, str) else jnp.dtype(v).name)
def test_packed_layer_equals_padded_to_the_bit(load, dtype, activation,
                                               monkeypatch):
    """Forward, the routing's counters and every gradient; the same
    rows are left out past the bound.  Each load in both dtypes, each
    under both gates."""
    args = layer_case(load, dtype)
    packs = []
    pack = L._pack
    monkeypatch.setattr(L, "_pack", lambda *a: packs.append(a[2]) or pack(*a))
    (_, (y, routing)), grads = held(load, activation)(*args)
    e, c = HELD[1], SLOTS[load]
    room = gm.packed_rows(T * K, e, BC)
    assert packs == [room] and room < e * c     # it did pack
    forced_padded(monkeypatch)
    (_, (y0, routing0)), grads0 = held(load, activation)(*args)
    assert packs == [room]                      # and this one did not
    assert y.dtype == dtype and bool(jnp.all(y == y0))
    assert bool(jnp.any(y != 0))
    for key in ("routed", "max_load", "past_bound", "choices"):
        assert bool(jnp.all(routing[key] == routing0[key])), key
    assert int(routing["past_bound"]) == {"past": T - SLOTS["past"]}.get(
        load, 0)
    if load != "even":
        assert int(routing["max_load"]) == T
    for name, a, b in zip(("x2d", "router_x", "w_router", "w_gate", "w_up",
                           "w_down"), grads, grads0):
        assert a.dtype == dtype and bool(jnp.all(a == b)), name
        assert bool(jnp.any(a != 0)), name


def test_the_mark_names_the_layout_and_its_room():
    """``moe.plan_side`` at a packed site: the pair side as before
    (packed means E * C exceeds the pairs), ``layout`` and ``room``
    beside it; ``moe.experts_bwd`` counts the buffer's rows."""
    args = layer_case("even", _F32)
    e, c = HELD[1], SLOTS["even"]
    room = gm.packed_rows(T * K, e, BC)
    tracer = spans.enable()
    try:
        with spans.span("compile", fn="layer"):
            jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(moe.moe_held(
                a[0], *a[2:], K, held=HELD, slots=c,
                router_x=a[1])[0])))(*args)
    finally:
        spans.disable()
    build, = tracer.export()["spans"]
    assert build["attrs"]["moe.plan_side"] == [
        {"site": site, "side": "pairs", "rows": T * K, "pairs": T * K,
         "layout": "packed", "room": room}
        for site in ("combine", "combine.bwd", "dispatch.bwd")]
    assert build["attrs"]["moe.experts_bwd"] == [
        {"path": "counted", "slots": room, "row_block": BC}]


# ---- the layout -------------------------------------------------------

def check_layout(plan, kept, slots):
    """``start`` on row-block boundaries, the live prefix inside the
    buffer, and in it exactly the rows and the order of ``src``."""
    src, start = np.asarray(plan.packed.src[0]), np.asarray(plan.packed.start)
    padded, kept = np.asarray(plan.src), np.asarray(kept)
    t, e = plan.slot.shape
    room, = src.shape
    assert plan.packed.src.shape == (1, room)
    assert room == gm.packed_rows(plan.idx.size, e, BC)
    assert start[0] == 0 and not (start % BC).any()
    blocks = -(-kept // BC) * BC
    assert (np.diff(start) == blocks[:-1]).all()
    assert start[-1] + blocks[-1] <= room
    want = np.full(room, t, np.int32)
    for i in range(e):
        want[start[i]:start[i] + kept[i]] = padded[i, :kept[i]]
    assert (src == want).all()
    assert (kept == np.minimum(kept, slots)).all()


@pytest.mark.parametrize("seed", range(6))
def test_random_routings_lie_inside_the_buffer(seed):
    rng = np.random.default_rng(seed)
    top, idx = jax.lax.top_k(jnp.asarray(
        rng.standard_normal((T, N_ALL)) * rng.choice([0.3, 3.0]), _F32), K)
    x = jnp.asarray(rng.standard_normal((T, D)), _F32)
    slots = 64
    xe, plan, _, load = L.moe_dispatch_held(x, jax.nn.softmax(top, -1), idx,
                                            HELD, slots, row_block=BC)
    kept = jnp.minimum(load, slots)
    check_layout(plan, kept, slots)
    assert xe.shape == (1, plan.packed.src.shape[1], D)
    xe, = xe
    padded, plan0, _, _ = L.moe_dispatch_held(x, jax.nn.softmax(top, -1), idx,
                                              HELD, slots)
    assert plan0.packed is None and padded.shape == (HELD[1], slots, D)
    for i, (s, n) in enumerate(zip(plan.packed.start, kept)):
        assert bool(jnp.all(xe[s:s + n] == padded[i, :n]))
    assert bool(jnp.all(jnp.where((plan.packed.src[0] == T)[:, None], xe, 0)
                        == 0))


@pytest.mark.parametrize("case", ["all_pairs_held", "one_past_a_block",
                                  "one_expert_takes_all", "nothing_held"])
def test_adversarial_routings_lie_inside_the_buffer(case):
    """The loads that fill the most room: every pair held; every expert
    one row past a block boundary (the live prefix then ends at R
    exactly); every row on one expert; no row at all."""
    e, slots, k = 6, 48, 1
    if case == "all_pairs_held":
        t, k, slots = 48, 2, 64
        idx = np.stack([np.arange(t) % e, (np.arange(t) + 1) % e], axis=1)
    elif case == "one_past_a_block":
        t = e * (BC + 1)
        idx = (np.arange(t) % e)[:, None]
    elif case == "one_expert_takes_all":
        t = 40
        idx = np.full((t, 1), 4)
    else:
        t = 40
        idx = np.full((t, 1), e + 3)            # held by another chip
    x = jnp.ones((t, 8), _F32)
    w = jnp.ones((t, k), _F32) / k
    xe, plan, _, load = L.moe_dispatch_held(x, w, jnp.asarray(idx, jnp.int32),
                                            (0, e), slots, row_block=BC)
    assert plan.packed is not None
    check_layout(plan, jnp.minimum(load, slots), slots)
    if case == "one_past_a_block":
        assert int(plan.packed.start[-1]) + 2 * BC == xe.shape[1]
    # through the layer's own passes: every kept pair comes back
    y = L.moe_combine(xe, plan, jnp.ones((t, e), _F32))
    held_pairs = (jnp.asarray(idx) < e).sum(axis=1)
    assert bool(jnp.all(y[:, 0] == held_pairs))


@pytest.mark.parametrize("cell,shape,room", [
    ("smallthinker_21b_a3b_train_s16k", (16384, 6, 16, 12032, 2560, 768),
     102400),
    ("lfm2_8b_a1b_train_s8k", (8192, 4, 32, 2048, 2048, 1792), 40960),
    ("kimivl_a3b_train_s8k", (16384, 6, 16, 4096, 2048, 1408), None),
    ("qwen3next_a3b_train_s16k", (16384, 10, 32, 1536, 2048, 512), None),
])
def test_the_rule_at_the_cells_shapes(cell, shape, room, monkeypatch):
    """(T, k, E held, the bound, d, f) of the four ``moe_held`` cells,
    under the kernels' own row block."""
    monkeypatch.undo()                          # the real ROW_BLOCK
    t, k, e, c, d, f = shape
    bc = gm.row_block(e, c, d, f, _BF16)
    assert bc == 256 == gm.ROW_BLOCK
    assert L.packed_room(k * t, e, c, bc) == room
    assert (room is not None) == (gm.packed_rows(k * t, e, bc) < e * c)
    # a caller that names no row block never packs
    assert L.packed_room(k * t, e, c, None) is None


def test_moe_grouped_never_packs(monkeypatch):
    """A capacity, whatever its shapes: here 8 x 64 slots for 128
    pairs, which a bound would pack into 256 rows."""
    monkeypatch.setattr(L, "_pack", lambda *a: pytest.fail("packed"))
    e, cf = 8, 4.0
    assert L.packed_room(T * K, e, moe.group_capacity(T, K, e, cf), BC) == 256
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    args = (jax.random.normal(ks[0], (T, D)), jax.random.normal(ks[1], (D, e)),
            jax.random.normal(ks[2], (e, D, F)) * 0.2,
            jax.random.normal(ks[3], (e, D, F)) * 0.2,
            jax.random.normal(ks[4], (e, F, D)) * 0.2)
    y, = jax.make_jaxpr(lambda *a: moe.moe_grouped(*a, K, cf))(
        *args).out_avals
    assert y.shape == (T, D)


# ---- the kernels ------------------------------------------------------

E, C, KD, N = 5, 48, 32, 128
COUNTS = {"mixed": (17, 0, 48, 1, 16), "none": (0, 0, 0, 0, 0),
          "full": (48, 48, 48, 48, 48), "first_empty": (0, 0, 33, 0, 5)}


def packed_case(counts, seed=0, dtype=_F32):
    """Rows ``[1, R, KD]`` laid out by ``packed_first`` (zeros where no
    row lies, as dispatch leaves them), the same rows padded
    ``[E, C, KD]``, the counts, and the row each expert starts at."""
    rng = np.random.default_rng(seed)
    counts = np.asarray(counts, np.int32)
    start = np.asarray(gm.packed_first(jnp.asarray(counts), BC)) * BC
    room = max(gm.packed_rows(2 * C, E, BC), int(start[-1]))
    packed = np.zeros((room, KD), np.float32)
    padded = np.zeros((E, C, KD), np.float32)
    for i, n in enumerate(counts):
        rows = rng.standard_normal((n, KD)).astype(np.float32)
        packed[start[i]:start[i] + n] = rows
        padded[i, :n] = rows
    return (jnp.asarray(packed, dtype)[None], jnp.asarray(padded, dtype),
            jnp.asarray(counts), start)


def live(a_packed, a_padded, counts, start):
    """[(expert, its live rows packed, the same rows padded)]."""
    return [(i, a_packed[0, start[i]:start[i] + n], a_padded[i, :n])
            for i, n in enumerate(np.asarray(counts))]


def weights(shape, seed, dtype=_F32):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape, _F32)
            * 0.2).astype(dtype)


# the kernels under test, each jitted once a module: the counts are an
# argument, so the count cases of a test share one build of its
# interpret-mode kernel (``ROW_BLOCK`` is ``BC`` in every case here)
grouped_mm = jax.jit(gm.grouped_matmul, static_argnames=(
    "bound", "block_n", "block_k"))
bwd_rows = jax.jit(gm._bwd_rows, static_argnums=3, static_argnames=(
    "name", "act", "bound"))
bwd_dw = jax.jit(gm._bwd_dw, static_argnums=(3, 4), static_argnames=(
    "name", "bound"))


@pytest.mark.parametrize("blocks", [{}, {"block_n": 128, "block_k": 16}],
                         ids=["whole_k", "k_in_blocks"])
@pytest.mark.parametrize("name", sorted(COUNTS))
def test_packed_grouped_mm_against_an_einsum_over_the_live_rows(name, blocks):
    x, xp, counts, start = packed_case(COUNTS[name])
    w = weights((E, KD, N), 1)
    out = grouped_mm(x, w, counts=counts, bound=C, **blocks)
    pad = grouped_mm(xp, w, counts=counts, **blocks)
    assert out.shape == (1, x.shape[1], N)
    for i, got, same in live(out, pad, counts, start):
        n = got.shape[0]
        want = jnp.einsum("ck,kn->cn", xp[i, :n], w[i],
                          precision="highest")
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert bool(jnp.all(got == same))       # the padded form's bits
    dead = np.ones(x.shape[1], bool)
    for i, n in enumerate(np.asarray(counts)):
        dead[start[i]:start[i] + n] = False
    assert bool(jnp.all(out[0, dead] == 0))


@pytest.mark.parametrize("name,act", [
    *((name, "silu") for name in sorted(COUNTS)), ("mixed", "relu")])
def test_packed_bwd_dh_against_an_einsum_over_the_live_rows(name, act):
    """``dh = dy @ w_down^T`` and the gate's backward in its epilogue:
    ``(h, dg, du)``."""
    dy, dyp, counts, start = packed_case(COUNTS[name], 2)
    g, gp, _, _ = packed_case(COUNTS[name], 3)
    u, up, _, _ = packed_case(COUNTS[name], 4)
    w_down = weights((E, KD, KD), 5)            # [E, f, d], f = d = KD
    got = bwd_rows((dy,), (w_down,), counts, (BC, None, None),
                   swiglu=(g, u), name="grouped_mm_bwd_dh", act=act,
                   bound=C)
    pad = bwd_rows((dyp,), (w_down,), counts, (BC, None, None),
                   swiglu=(gp, up), name="grouped_mm_bwd_dh", act=act)
    for i in range(E):
        n = int(counts[i])
        dh = jnp.einsum("cd,fd->cf", dyp[i, :n], w_down[i],
                        precision="highest")
        a, slope = gm.gate_act(gp[i, :n], act)
        want = (a * up[i, :n], dh * up[i, :n] * slope, dh * a)
        for tile, ref, same in zip(got, want, pad):
            rows = tile[0, start[i]:start[i] + n]
            np.testing.assert_allclose(rows, ref, rtol=1e-5, atol=1e-5)
            assert bool(jnp.all(rows == same[i, :n]))


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_packed_bwd_dx_against_an_einsum_over_the_live_rows(name):
    """Both dx products into one sum."""
    dg, dgp, counts, start = packed_case(COUNTS[name], 6)
    du, dup, _, _ = packed_case(COUNTS[name], 7)
    w_gate, w_up = weights((E, N, KD), 8), weights((E, N, KD), 9)
    got = bwd_rows((dg, du), (w_gate, w_up), counts, (BC, None, None),
                   name="grouped_mm_bwd_dx", bound=C)
    pad = bwd_rows((dgp, dup), (w_gate, w_up), counts, (BC, None, None),
                   name="grouped_mm_bwd_dx")
    assert got.shape == (1, dg.shape[1], N)
    for i, rows, same in live(got, pad, counts, start):
        n = rows.shape[0]
        want = (jnp.einsum("ch,dh->cd", dgp[i, :n], w_gate[i],
                           precision="highest")
                + jnp.einsum("ch,dh->cd", dup[i, :n], w_up[i],
                             precision="highest"))
        np.testing.assert_allclose(rows, want, rtol=1e-5, atol=1e-5)
        assert bool(jnp.all(rows == same))


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_packed_bwd_dw_against_an_einsum_over_the_live_rows(name):
    """Two weight gradients from one walk of each expert's row blocks,
    an expert with no row writing zeros."""
    x, xp, counts, start = packed_case(COUNTS[name], 10)
    b = [packed_case(COUNTS[name], 11 + j) for j in range(2)]
    got = bwd_dw(x, tuple(p[0] for p in b), counts, (BC, None, None),
                 (_F32, _F32), name="grouped_mm_bwd_dw", bound=C)
    pad = bwd_dw(xp, tuple(p[1] for p in b), counts, (BC, None, None),
                 (_F32, _F32), name="grouped_mm_bwd_dw")
    for dw, same, (_, bp, _, _) in zip(got, pad, b):
        assert dw.shape == (E, KD, KD)
        want = jnp.einsum("eck,ecn->ekn", xp, bp, precision="highest")
        np.testing.assert_allclose(dw, want, rtol=1e-5, atol=1e-5)
        assert bool(jnp.all(dw == same))
        for i, n in enumerate(np.asarray(counts)):
            assert n or bool(jnp.all(dw[i] == 0))


def test_the_packed_forms_say_what_they_cannot_take():
    x, _, counts, _ = packed_case(COUNTS["mixed"])
    w = weights((E, KD, N), 1)
    with pytest.raises(ValueError, match="needs counts"):
        gm.grouped_matmul(x, w, bound=C)
    with pytest.raises(ValueError, match="whole number"):
        gm.grouped_matmul(x[:, :-1], w, counts=counts, bound=C)
    with pytest.raises(ValueError, match="counted backward only"):
        gm.grouped_ffn(x, w, w, jnp.swapaxes(w, 1, 2), counts=counts,
                       bound=C)
