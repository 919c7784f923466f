"""The two sides of a routing plan (ISSUE 39): where the experts'
buffers hold fewer rows than the routing has (token, choice) pairs, the
token-side passes (combine, the dispatch's transpose, the gate's
gradient) go through the slots; elsewhere they are the parent's
program, operation for operation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlnetbench_tpu.metrics import spans
from dlnetbench_tpu.models import layers as L

_F32 = jnp.float32

# (T, k, the router's experts, held = (first, count), slots an expert)
HELD = {
    # a sixteenth of the pairs held, as qwen3next_a3b_train_s16k
    "qwen_like": (64, 10, 96, (8, 12), 40),
    # a quarter held, as kimivl_a3b_train_s8k
    "kimi_like": (64, 6, 32, (4, 8), 44),
    # every expert here at capacity factor 1.25, as mixtral8x7b_train
    "mixtral_like": (64, 2, 8, (0, 8), 20),
}
SIDE = {"qwen_like": "slots", "kimi_like": "slots", "mixtral_like": "pairs"}


def routing_case(name, d=16):
    """A seeded routing with the corners in it: token 0 chooses no held
    expert, token 1 only held ones, the last held expert is chosen by
    nobody and the second by every other token (rows past the bound)."""
    t, k, n_all, (first, count), slots = HELD[name]
    rng = np.random.default_rng(sum(map(ord, name)))
    logits = rng.standard_normal((t, n_all)).astype(np.float32)
    hot, empty = first + 1, first + count - 1
    logits[1:, hot] = 50.0
    logits[:, empty] = -50.0
    if n_all - count >= k:                   # somewhere else to go
        logits[0, first:first + count] = -40.0
    logits[1, first:empty] += 30.0
    top, idx = jax.lax.top_k(jnp.asarray(logits), k)
    x = jnp.asarray(rng.standard_normal((t, d)), _F32)
    eo = jnp.asarray(rng.standard_normal((count, slots, d)), _F32)
    return x, jax.nn.softmax(top, axis=-1), idx, eo


def layer_loss(name):
    _, _, _, held, slots = HELD[name]

    def loss(x, weights, eo, idx):
        xe, plan, gate, _ = L.moe_dispatch_held(x, weights, idx, held, slots)
        return jnp.sum(jnp.sin(L.moe_combine(jnp.tanh(xe) * eo, plan, gate)))
    return loss


def forced(monkeypatch, side):
    monkeypatch.setattr(L, "_plan_side", lambda plan, site: side)


@pytest.mark.parametrize("name", sorted(HELD))
def test_the_rule_reads_the_side_off_the_shapes(name):
    t, k, _, (_, count), slots = HELD[name]
    x, weights, idx, _ = routing_case(name)
    _, plan, _, _ = L.moe_dispatch_held(x, weights, idx, HELD[name][3], slots)
    assert L._plan_side(plan, "combine") == SIDE[name]
    assert (count * slots < k * t) == (SIDE[name] == "slots")


@pytest.mark.parametrize("name", sorted(HELD))
def test_the_corners_are_in_the_case(name):
    t, k, n_all, (first, count), slots = HELD[name]
    x, weights, idx, _ = routing_case(name)
    xe, plan, gate, load = L.moe_dispatch_held(x, weights, idx,
                                               (first, count), slots)
    here = (idx >= first) & (idx < first + count)
    if n_all - count >= k:
        assert not bool(here[0].any())       # no held choice
    assert bool(here[1].all())               # all k held
    assert int(load[count - 1]) == 0         # an empty expert
    assert int(load[1]) > slots              # rows past the bound:
    kept = jnp.sum(plan.slot >= 0, axis=0)   # left out, and counted
    assert int(kept[1]) == slots and int(load[1] - kept[1]) > 0
    assert bool(jnp.all(jnp.where((plan.src == t)[..., None], xe, 0) == 0))


@pytest.mark.parametrize("name", sorted(HELD))
def test_slot_side_equals_pair_side_in_value_and_every_gradient(
        name, monkeypatch):
    x, weights, idx, eo = routing_case(name)
    f = jax.value_and_grad(layer_loss(name), argnums=(0, 1, 2))
    forced(monkeypatch, "pairs")
    want = f(x, weights, eo, idx)
    forced(monkeypatch, "slots")
    got = f(x, weights, eo, idx)
    again = f(x, weights, eo, idx)
    for a, b, c in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(again)):
        scale = max(1.0, float(jnp.max(jnp.abs(b))))
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-6 * scale
        assert bool(jnp.all(a == c))         # the same bits twice
    dx, dw, deo = got[1]
    assert bool(jnp.any(dx != 0)) and bool(jnp.any(dw != 0))
    assert bool(jnp.any(deo != 0))


@pytest.mark.parametrize("name", ["qwen_like", "kimi_like"])
def test_slot_side_in_bf16_sums_in_float32(name, monkeypatch):
    """bf16 rows, float32 products and sums, one cast: against the
    pair side within a bf16 rounding of the result."""
    x, weights, idx, eo = routing_case(name)
    _, _, _, held, slots = HELD[name]
    xe, plan, gate, _ = L.moe_dispatch_held(x.astype(jnp.bfloat16), weights,
                                            idx, held, slots)
    out = (jnp.tanh(xe.astype(_F32)) * eo).astype(jnp.bfloat16)
    forced(monkeypatch, "pairs")
    want = L.moe_combine(out, plan, gate)
    forced(monkeypatch, "slots")
    got = L.moe_combine(out, plan, gate)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(_F32) - want.astype(_F32))
    assert float(jnp.max(err / (jnp.abs(want.astype(_F32)) + 1e-3))) <= 2 ** -7


@pytest.mark.parametrize("held", [0, 1, 119, 120, 121, 240, 241, 480])
def test_the_sum_takes_the_least_share_that_holds_the_held_slots(held):
    """480 slots, of which the sum visits 120, 240 or all: none held,
    one, a share's last slot, the next share's first, all of them;
    tokens repeat across experts and the held slots lie anywhere in
    the buffers."""
    t, e, c, d = 64, 8, 60, 8
    rng = np.random.default_rng(held)
    src = np.full(e * c, t, np.int32)
    src[rng.permutation(e * c)[:held]] = rng.integers(0, t, held)
    rows = rng.standard_normal((e, c, d)).astype(np.float32)
    w = rng.standard_normal((t, e)).astype(np.float32)
    plan = L.MoePlan(jnp.zeros((t, e), jnp.int32),
                     jnp.asarray(src.reshape(e, c)), jnp.zeros((t, 2), jnp.int32))
    for weights in (None, w):
        want = np.zeros((t, d), np.float64)
        for i in np.flatnonzero(src < t):
            scale = 1.0 if weights is None else weights[src[i], i // c]
            want[src[i]] += rows.reshape(-1, d)[i] * scale
        got = L._sum_by_token(jnp.asarray(rows), plan,
                              None if weights is None else jnp.asarray(w))
        assert got.dtype == _F32
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---- the pair side is the parent's program ---------------------------

def parent():
    """``layers.py`` at commit 1368ad1: the three passes as they stood,
    under the names they have (a jaxpr prints them)."""
    def _from_slots(out, plan):
        e, c, d = out.shape
        slot = L._of_choice(plan.slot, plan)
        row = jnp.where(slot >= 0, plan.idx * c + slot, e * c)
        return jnp.take(out.reshape(e * c, d), row.T, axis=0, mode="fill",
                        fill_value=0)

    def _to_slots(x, plan, w=None):
        xe = jnp.take(x, plan.src, axis=0, mode="fill", fill_value=0)
        if w is None:
            return xe
        ws = jnp.take_along_axis(w.T, plan.src, axis=1, mode="fill",
                                 fill_value=0)
        return xe.astype(ws.dtype) * ws[..., None]

    @jax.custom_vjp
    def dispatch_rows(x, plan):
        return _to_slots(x, plan)

    def _dispatch_rows_fwd(x, plan):
        return dispatch_rows(x, plan), plan

    def _dispatch_rows_bwd(plan, dxe):
        dx = jnp.sum(_from_slots(dxe, plan), axis=0, dtype=_F32)
        return dx.astype(dxe.dtype), None

    dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)

    @jax.custom_vjp
    def moe_combine(out, plan, gate):
        w = L._of_choice(gate.astype(_F32), plan).T
        rows = _from_slots(out, plan).astype(_F32)
        return jnp.sum(rows * w[..., None], axis=0).astype(out.dtype)

    def _moe_combine_fwd(out, plan, gate):
        return moe_combine(out, plan, gate), (out, plan, gate)

    def _moe_combine_bwd(res, dy):
        out, plan, gate = res
        dout = _to_slots(dy, plan, gate.astype(_F32))
        dw = jnp.sum(_from_slots(out, plan).astype(_F32)
                     * dy.astype(_F32), axis=-1).T
        dgate = jnp.sum(jnp.where(L._chosen(plan, gate.shape[1]),
                                  dw[..., None], 0), axis=1)
        return dout.astype(out.dtype), None, dgate.astype(gate.dtype)

    moe_combine.defvjp(_moe_combine_fwd, _moe_combine_bwd)
    return dispatch_rows, moe_combine


def _plan_of(t, k, e, c, seed=0):
    """A plan and gate of the given shapes, every expert here, the
    capacity rule's drops included."""
    rng = np.random.default_rng(seed)
    top, idx = jax.lax.top_k(jnp.asarray(rng.standard_normal((t, e)),
                                         _F32), k)
    x = jnp.zeros((t, 8), _F32)
    _, plan, gate, _ = L._dispatch_choices(x, jax.nn.softmax(top, -1), idx,
                                           e, c)
    return plan, gate


# (T, k, E, C): mixtral8x7b_train's layer and the SPMD step's local
# share at capacity factors 1.0 and 1.25, shrunk by a common factor
PAIR_SHAPES = {"mixtral": (512, 2, 8, 160), "spmd_cf1": (256, 2, 8, 64),
               "spmd_cf1.25": (256, 2, 4, 160)}


@pytest.mark.parametrize("name", sorted(PAIR_SHAPES))
def test_pair_side_traces_to_the_parents_program(name):
    t, k, e, c = PAIR_SHAPES[name]
    plan, gate = _plan_of(t, k, e, c)
    assert e * c >= k * t and L._plan_side(plan, "combine") == "pairs"
    x = jnp.ones((t, 8), jnp.bfloat16)
    eo = jnp.ones((e, c, 8), jnp.bfloat16)

    def through(dispatch_rows, combine):
        def loss(x, eo, gate):
            y = combine(dispatch_rows(x, plan) * eo, plan, gate)
            return jnp.sum(y.astype(_F32))
        return str(jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2)))(
            x, eo, gate))
    now = through(L.dispatch_rows, L.moe_combine)
    assert now == through(*parent())
    assert " gather[" in now
    assert not {"scatter-add[", "scatter[", "while[", "sort["} & set(
        now.replace("=", " ").split())


@pytest.mark.parametrize("name,shape", [
    ("qwen3next_a3b_train_s16k", (16384, 10, 32, 1536)),
    ("kimivl_a3b_train_s8k", (16384, 6, 16, 4096)),
    ("mixtral8x7b_train", (8192, 2, 8, 2560)),
    # a whole expert layer: every pair's expert is here, at any bound at
    # or over the even router's 1024 rows
    ("lfm2_8b_a1b_train_s8k", (8192, 4, 32, 1024)),
    ("lfm2_8b_a1b_train_s8k", (8192, 4, 32, 3072)),
])
def test_the_rule_at_the_cells_shapes(name, shape):
    t, k, e, c = shape
    plan = L.MoePlan(jax.ShapeDtypeStruct((t, e), jnp.int32),
                     jax.ShapeDtypeStruct((e, c), jnp.int32),
                     jax.ShapeDtypeStruct((t, k), jnp.int32))
    want = "pairs" if name.startswith(("mixtral", "lfm2")) else "slots"
    assert L._plan_side(plan, "combine") == want


# ---- the mark ---------------------------------------------------------

def _trace_a_layer(name):
    x, weights, idx, eo = routing_case(name)
    jax.make_jaxpr(jax.grad(layer_loss(name), argnums=(0, 1, 2)))(
        x, weights, eo, idx)


@pytest.mark.parametrize("name", sorted(HELD))
def test_a_traced_build_marks_each_sites_side(name):
    """These layers call ``moe_dispatch_held`` and ``moe_combine``
    themselves and name no row block of any kernels, so every one keeps
    the padded layout, whatever its shapes: ``room`` is its E * C."""
    t, k, _, (_, count), slots = HELD[name]
    side = SIDE[name]
    rows = count * slots if side == "slots" else k * t
    tracer = spans.enable()
    try:
        with spans.span("compile", fn="layer"):
            _trace_a_layer(name)
        _trace_a_layer(name)                 # and with no span open
    finally:
        spans.disable()
    build, *alone = tracer.export()["spans"]
    assert build["name"] == "compile" and build["attrs"]["fn"] == "layer"
    want = [{"site": site, "side": side, "rows": rows, "pairs": k * t,
             "layout": "padded", "room": count * slots}
            for site in ("combine", "combine.bwd", "dispatch.bwd")]
    assert build["attrs"]["moe.plan_side"] == want
    assert [s["name"] for s in alone] == ["moe.plan_side"] * 3
    assert [s["attrs"] for s in alone] == want
    assert all(s["dur_us"] == 0 and s["depth"] == 0 for s in alone)


@pytest.mark.parametrize("row_block,layout,room", [
    (None, "padded", 8 * 20), (4, "packed", 152), (20, "padded", 8 * 20)])
def test_the_mark_says_which_layout_and_how_much_room(row_block, layout,
                                                      room):
    """The whole layer of ``mixtral_like`` (8 x 20 slots for 128 pairs)
    as a bound: with the kernels' row block named, 4 rows, the pairs
    and 8 x 3 rows of slack fit 152 rows and the site is packed; at a
    row block of 20 they would need 280, more than the slots, and with
    none named nothing packs.  ``side`` and ``rows`` are the pairs'
    in all three."""
    t, k, _, held, slots = HELD["mixtral_like"]
    x, weights, idx, _ = routing_case("mixtral_like")

    def loss(x, weights):
        xe, plan, gate, _ = L.moe_dispatch_held(x, weights, idx, held, slots,
                                                row_block=row_block)
        assert xe.shape == ((1, room, 16) if layout == "packed"
                            else (held[1], slots, 16))
        return jnp.sum(jnp.sin(L.moe_combine(jnp.tanh(xe), plan, gate)))
    tracer = spans.enable()
    try:
        with spans.span("compile", fn="layer"):
            jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, weights)
    finally:
        spans.disable()
    build, = tracer.export()["spans"]
    assert build["attrs"]["moe.plan_side"] == [
        {"site": site, "side": "pairs", "rows": k * t, "pairs": k * t,
         "layout": layout, "room": room}
        for site in ("combine", "combine.bwd", "dispatch.bwd")]


def test_no_tracer_no_mark():
    assert not spans.is_enabled()
    _trace_a_layer("qwen_like")              # nothing to record into
    assert spans.current() is None
    tracer = spans.Tracer()                  # and one not installed
    _trace_a_layer("qwen_like")
    assert tracer.export()["spans"] == []


def test_the_executors_build_carries_the_marks_beside_its_record():
    """``CompiledStep`` traces the step inside its ``compile`` span:
    the marks lie in that span's attrs with the build's record, and
    the span's name stays the only one a train run exports."""
    from dlnetbench_tpu.core import executor
    x, weights, idx, eo = routing_case("qwen_like")
    tracer = spans.enable()
    try:
        executor.CompiledStep(
            jax.grad(layer_loss("qwen_like"), argnums=(0, 1, 2)),
            (x, weights, eo, idx))
    finally:
        spans.disable()
    build, = tracer.export()["spans"]
    assert build["name"] == "compile" and "trace_s" in build["attrs"]
    marks = build["attrs"]["moe.plan_side"]
    assert [m["site"] for m in marks] == ["combine", "combine.bwd",
                                          "dispatch.bwd"]
    assert {m["side"] for m in marks} == {"slots"}
