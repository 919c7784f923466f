"""The window-and-full attention expert decoder (``models/hybrid.py``
with ``swa`` and ``nope`` layers, a router that reads the layer's input
and ReLU-gated experts, the head untied) against the benchmark's plain
reference (``benchmarks/reference_swa_moe.py``) at a small size in
float32 on the CPU: the one attention mixer at a group of seven under
each kind's mask and positions and what it must not be, the expert
layer's router input and activation, the ReLU tiles of the grouped
kernels, the whole model at the cell's pattern and at the published one,
and the shares of the expert layer adding up to the uncut layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_swa_moe as ref
from benchmarks import weights_swa_moe as weights
from benchmarks.runners import train_swa_moe
from dlnetbench_tpu.models import hybrid, moe
from dlnetbench_tpu.ops import grouped_matmul as gm

EXPERTS, TOP_K, SEQ, D, WINDOW = 64, 6, 48, 64, 16
CUT = [0, 1, 1, 1, 0, 1, 1, 1]
PUBLISHED = CUT * 6 + CUT[:4]
CONFIG = {
    "hidden_size": D, "num_attention_heads": 14, "num_key_value_heads": 2,
    "head_dim": 16, "moe_ffn_hidden_size": 32,
    "moe_num_active_primary_experts": TOP_K,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1500000, "rope_scaling": None,
    "sliding_window_size": WINDOW, "tie_word_embeddings": False,
    "vocab_size": 256, "num_hidden_layers": 8, "rope_layout": CUT,
    "sliding_window_layout": CUT, "torch_dtype": "float32"}
F32 = ref.MATMULS["float32"]


def arch_of(first: int = 0, held: int = EXPERTS, layout=CUT, **over):
    return weights.arch_of({
        **CONFIG, "moe_num_primary_experts": held,
        "num_hidden_layers": len(layout), "rope_layout": layout,
        "sliding_window_layout": layout,
        "published": {"moe_num_primary_experts": EXPERTS},
        "assumed": {"first_held_expert": first}, **over})


def config(arch: dict, slots: int = SEQ, seq: int = SEQ, **over):
    return train_swa_moe.config_of(arch, seq, slots, **over)


def with_(cfg, **over):
    return hybrid.HybridConfig(**{**cfg.__dict__, **over})


def tokens(seq: int = SEQ):
    return jax.random.randint(jax.random.key(1), (1, seq + 1), 0, 256)


def moved(params, seed=9):
    """The seeded weights with every norm's weight drawn away from
    one."""
    out = jax.tree.map(lambda a: a, params)
    keys = iter(jax.random.split(jax.random.key(seed), 3))
    for g, k in ((None, "final_norm"), ("block", "norm1"),
                 ("block", "norm2")):
        tree = out if g is None else out[g]
        tree[k] = 1.0 + 0.3 * jax.random.normal(next(keys), tree[k].shape)
    return out


def leaves(tree):
    """{name: leaf of one layer}."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(k.key) for k in path)
        if len(path) > 1:
            out.update({f"{name}/{i}": a[i] for i in range(a.shape[0])})
        else:
            out[name] = a
    return out


def close(got, want, rtol=1e-4, atol=1e-5, **kw):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol, **kw)


def gap(a, b) -> float:
    return float(jnp.abs(a - b).max())


# ----------------------------------------------------------- the mixer
def attention_layer(seed=3):
    arch = arch_of()
    p = jax.tree.map(lambda a: a[0],
                     weights.make_params(arch, seed)["gated"])
    y = jax.random.normal(jax.random.key(seed), (2, SEQ, D))
    return arch, config(arch), p, y


def want(kind, arch, p, y):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, y: ref.attention(y, p, arch, F32, kind))(
            p, y)


def got(kind, cfg, p, y):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, y: hybrid.gated_mixer(cfg, y, p, kind))(
            p, y)


@pytest.mark.parametrize("kind", ["swa", "nope"])
def test_mixer_forward_and_every_gradient_against_the_reference(kind):
    """14 query heads over 2 key/value heads (groups of seven), a window
    of 16 keys in a sequence of 48: the output and the gradients of the
    four projections and the input."""
    arch, cfg, p, y = attention_layer()
    assert cfg.num_heads // cfg.num_kv_heads == 7 and WINDOW < SEQ
    ct = jax.random.normal(jax.random.key(8), y.shape)

    def both(fn):
        def run(p, y):
            out, vjp = jax.vjp(fn, p, y)
            return out, vjp(ct)
        return jax.jit(run)
    with jax.default_matmul_precision("highest"):
        out, (g_p, g_y) = both(
            lambda p, y: hybrid.gated_mixer(cfg, y, p, kind))(p, y)
        w_out, (w_p, w_y) = both(
            lambda p, y: ref.attention(y, p, arch, F32, kind))(p, y)
    close(out, w_out)
    close(g_y, w_y)
    assert set(g_p) == {"wq", "wk", "wv", "wo"}
    for k in g_p:
        close(g_p[k], w_p[k], err_msg=k)
        assert float(jnp.abs(w_p[k]).max()) > 1e-2, k


def test_a_window_layer_sees_the_last_window_keys_and_no_more():
    """Key ``t - window`` moved: no query at or after ``t`` in a window
    layer notices; key ``t - window + 1`` moved: query ``t`` does.  The
    full layer notices both."""
    arch, cfg, p, y = attention_layer()
    t = SEQ - 4
    for back, seen in ((WINDOW, False), (WINDOW - 1, True)):
        y2 = y.at[:, t - back].add(1.0)
        assert (gap(got("swa", cfg, p, y2)[:, t],
                    got("swa", cfg, p, y)[:, t]) > 1e-3) == seen
        assert gap(got("nope", cfg, p, y2)[:, t],
                   got("nope", cfg, p, y)[:, t]) > 1e-3
    # one key more or fewer in the program's window is not the model's
    for off in (-1, 1):
        other = with_(cfg, attention_window=WINDOW + off)
        assert gap(got("swa", other, p, y), want("swa", arch, p, y)) > 1e-3


def test_a_full_layer_is_not_windowed_nor_turned_and_a_window_layer_is_both():
    arch, cfg, p, y = attention_layer()
    close(got("swa", cfg, p, y), want("swa", arch, p, y))
    close(got("nope", cfg, p, y), want("nope", arch, p, y))
    # the other layer's mask or positions on this layer's weights
    assert gap(got("swa", cfg, p, y), want("nope", arch, p, y)) > 1e-2
    wide = with_(cfg, attention_window=SEQ)       # RoPE alone differs
    assert gap(got("swa", wide, p, y), want("nope", arch, p, y)) > 1e-2
    assert gap(got("nope", cfg, p, y),
               want("swa", {**arch, "window": SEQ}, p, y)) > 1e-2
    # "gated", the kind the other cells run, turns and never masks
    close(got("gated", cfg, p, y), want("swa", {**arch, "window": SEQ},
                                        p, y))


def test_no_norm_a_head_is_applied():
    """The card states no norm a head: the group has no ``q_norm`` nor
    ``k_norm`` and a configuration that norms takes other weights and
    gives another output."""
    arch, cfg, p, y = attention_layer()
    assert not cfg.head_norm and not cfg.attn_gate
    names = set(hybrid.param_shapes(cfg))
    assert not names & {"gated/q_norm", "gated/k_norm"}
    normed = with_(cfg, head_norm=True)
    assert {"gated/q_norm", "gated/k_norm"} <= set(
        hybrid.param_shapes(normed))
    ones = {**p, "q_norm": jnp.ones(16), "k_norm": jnp.ones(16)}
    assert gap(got("swa", normed, ones, y), want("swa", arch, p, y)) > 1e-2


# ---------------------------------------------------- the expert layer
def one_layer(kind="swa", seed=7, **over):
    arch = arch_of(layout=[int(kind == "swa")])
    cfg = config(arch, slots=2 * SEQ, **over)
    p = moved(weights.make_params(arch, seed))
    layer = {g: jax.tree.map(lambda a: a[0], p[g])
             for g in ("block", "gated", "moe")}
    x = jax.random.normal(jax.random.key(seed), (2, SEQ, D))
    return arch, cfg, layer, x


def run_layer(cfg, layer, x):
    """(the layer's output, its routing), traceable."""
    out, _, routing = hybrid._layer(cfg, 0, x, layer["block"],
                                    layer["gated"], layer["moe"], None,
                                    None)
    return out, routing


def ref_layer(arch, layer, x, kind="swa"):
    return ref.layer(x, {"block": layer["block"], "mixer": layer["gated"],
                         "ffn": layer["moe"]}, kind=kind, arch=arch, mm=F32)


def jitted(fn, *static):
    """``fn(*static, layer, x)`` jitted over the layer's weights and its
    input, at the reference's matmul precision."""
    def run(layer, x):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda layer, x: fn(*static, layer, x))(layer, x)
    return run


def test_layer_against_the_reference_and_the_router_reads_norm1():
    """The whole layer and its selection; a router fed ``norm2`` of the
    stream after attention selects other experts and gives another
    layer."""
    arch, cfg, layer, x = one_layer()
    assert cfg.early_router and cfg.expert_activation == "relu"
    out, routing = jitted(run_layer, cfg)(layer, x)
    w_out, w_idx = jitted(ref_layer, arch)(layer, x)
    close(out, w_out)
    assert (np.sort(routing["choices"]) == np.sort(w_idx)).all()
    assert int(routing["past_bound"]) == 0
    late = with_(cfg, early_router=False)
    l_out, l_routing = jitted(run_layer, late)(layer, x)
    differ = (np.sort(l_routing["choices"]) != np.sort(w_idx)).any(-1)
    assert differ.mean() > 0.5 and gap(l_out, w_out) > 1e-2
    # what the late router read: norm2 of the stream after attention
    def late_choice(layer, x):
        x1 = x + hybrid.gated_mixer(
            cfg, hybrid._norm(cfg, x, layer["block"], "norm1"),
            layer["gated"], "swa")
        y2 = hybrid._norm(cfg, x1, layer["block"], "norm2")
        return jax.lax.top_k(
            y2.reshape(-1, D) @ layer["moe"]["w_router"], TOP_K)[1]
    assert (np.sort(l_routing["choices"])
            == np.sort(jitted(late_choice)(layer, x))).all()


def test_silu_for_relu_is_another_layer():
    arch, cfg, layer, x = one_layer()
    silu = with_(cfg, expert_activation="silu")
    s_out, s_routing = jitted(run_layer, silu)(layer, x)
    out, routing = jitted(run_layer, cfg)(layer, x)
    assert (s_routing["choices"] == routing["choices"]).all()
    assert gap(s_out, out) > 1e-2
    with pytest.raises(ValueError, match="activation"):
        jitted(run_layer, with_(cfg, expert_activation="gelu"))(layer, x)


def test_the_routings_gradient_reaches_norm1_and_the_stream_before_attention():
    """With the attention's output projection at zero the mixer adds
    nothing, so ``norm1``'s weight is reached through the router alone:
    its gradient is the reference's and is not zero; under a late router
    it is zero there."""
    arch, cfg, layer, x = one_layer()
    layer["gated"]["wo"] = jnp.zeros_like(layer["gated"]["wo"])
    ct = jax.random.normal(jax.random.key(5), x.shape)

    def grads(run, *static):
        def loss(n1, x):
            return jnp.sum(ct * run(*static, {**layer, "block": {
                **layer["block"], "norm1": n1}}, x)[0])
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(loss, (0, 1)))(
                layer["block"]["norm1"], x)
    g_n1, g_x = grads(run_layer, cfg)
    w_n1, w_x = grads(ref_layer, arch)
    assert float(jnp.abs(w_n1).max()) > 1e-3
    close(g_n1, w_n1, rtol=1e-3)
    close(g_x, w_x, rtol=1e-3)
    l_n1, _ = grads(run_layer, with_(cfg, early_router=False))
    assert float(jnp.abs(l_n1).max()) == 0.0


def test_an_early_router_needs_a_layer_that_hands_it_its_input():
    arch = arch_of()
    with pytest.raises(ValueError, match="early_router"):
        config(arch, layer_kinds=("mla",) * 8, kv_lora_rank=8,
               qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8)


# -------------------------------------------- the grouped kernels' tiles
def ffn_inputs(e=2, c=32, d=128, f=768, seed=4):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (e, c, d))
    wg, wu = (jax.random.normal(k, (e, d, f)) / np.sqrt(d) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (e, f, d)) / np.sqrt(f)
    counts = jnp.array([c, c // 2 + 3], jnp.int32)
    live = jnp.arange(c)[None, :, None] < counts[:, None, None]
    return jnp.where(live, x, 0.0), wg, wu, wd, counts, \
        jax.random.normal(ks[4], (e, c, d)) * live


def einsum_ffn(x, wg, wu, wd, act):
    g = jnp.einsum("ecd,edh->ech", x, wg, precision="highest")
    u = jnp.einsum("ecd,edh->ech", x, wu, precision="highest")
    a = jnp.maximum(g, 0.0) if act == "relu" else jax.nn.silu(g)
    return jnp.einsum("ech,ehd->ecd", a * u, wd, precision="highest")


@pytest.mark.parametrize("backward", ["counted", "einsum"])
@pytest.mark.parametrize("act", ["relu", "silu"])
def test_grouped_ffn_tiles_against_the_einsum_at_six_lane_tiles(act,
                                                                backward):
    """Width 768 = 6 x 128: the forward and both backwards (the counted
    one's fused tiles in interpret mode) give what the plain einsums and
    their autodiff give, for either gate."""
    x, wg, wu, wd, counts, ct = ffn_inputs()
    assert gm.tile_plan(32, 128, 768, 4)["block_n"] == 768

    def ours(x, wg, wu, wd):
        return gm.grouped_ffn(x, wg, wu, wd, counts=counts,
                              backward=backward, activation=act)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(ours, x, wg, wu, wd)
        w_out, w_vjp = jax.vjp(
            lambda *a: einsum_ffn(*a, act), x, wg, wu, wd)
        grads, w_grads = vjp(ct), w_vjp(ct)
    close(out, w_out)
    for name, g, w in zip(("dx", "dwg", "dwu", "dwd"), grads, w_grads):
        close(g, w, rtol=2e-4, atol=2e-5, err_msg=name)
        assert float(jnp.abs(w).max()) > 1e-2


def test_relu_is_not_silu_and_the_default_is_the_swiglu():
    x, wg, wu, wd, counts, _ = ffn_inputs(c=8, f=128)
    kw = dict(counts=counts, backward="counted")
    silu = gm.grouped_ffn(x, wg, wu, wd, **kw)
    assert (silu == gm.grouped_ffn(x, wg, wu, wd, activation="silu",
                                   **kw)).all()
    assert gap(silu, gm.grouped_ffn(x, wg, wu, wd, activation="relu",
                                    **kw)) > 1e-2
    with pytest.raises(ValueError, match="activation"):
        gm.grouped_ffn(x, wg, wu, wd, activation="gelu", **kw)


def test_swiglu_tiles_are_bit_equal_to_the_parents():
    """The activation and its slope now come from one function; for the
    SwiGLU it computes what the forward, the einsum backward and the
    fused tiles each wrote out before, to the last bit."""
    g, u, dh = (jax.random.normal(jax.random.key(i), (64, 256)) * 3
                for i in range(3))

    @jax.jit
    def parents(dh, g, u):
        sig = jax.nn.sigmoid(g)
        silu = g * sig
        return (jax.nn.silu(g) * u, silu * u,
                dh * u * (sig + silu * (1.0 - sig)), dh * silu)

    @jax.jit
    def ours(dh, g, u):
        a, _ = gm.gate_act(g, "silu")
        return (a * u, *gm._swiglu_bwd_tiles(dh, g, u, jnp.float32))
    fwd, h, dg, du = parents(dh, g, u)
    o_fwd, o_h, o_dg, o_du = ours(dh, g, u)
    for a, b in ((fwd, o_fwd), (h, o_h), (dg, o_dg), (du, o_du)):
        assert (a == b).all()
    a, slope = gm.gate_act(g, "relu")
    assert (a == jnp.maximum(g, 0)).all()
    assert (slope == (g > 0)).all()


# ----------------------------------------------------------- the model
def test_benchmark_weights_follow_the_programs_layout():
    arch = arch_of(16, 16)
    assert {k: shape for k, (shape, _) in weights.shapes(arch).items()} \
        == {k: shape for k, (shape, _)
            in hybrid.param_shapes(config(arch)).items()}
    made = weights.make_params(arch, 3)
    own = jax.eval_shape(
        lambda: hybrid.init_params(jax.random.key(3), config(arch)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), made) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), own)
    assert made["head"].shape == made["embed"].shape == (256, D)
    assert made["moe"]["w_gate"].shape[:2] == (8, 16)
    assert made["moe"]["w_router"].shape == (8, D, EXPERTS)
    assert "router_bias" not in made["moe"]


@pytest.mark.parametrize("layout,held,remat,rows,leaves_too", [
    (CUT, (0, 16), True, 16, True),
    (PUBLISHED, (16, 16), False, 0, False)],
    ids=["the_cut", "published_52_layers"])
def test_loss_and_every_gradient_leaf_against_the_reference(
        layout, held, remat, rows, leaves_too, monkeypatch):
    """The whole model at the cell's pattern (two periods ``[full,
    window, window, window]``, 16 of 64 experts held, each layer
    recomputed, head and loss in row blocks): the loss and every leaf's
    gradient.  At the published 52 layers (full at 0, 4, ..., 48),
    another share held, nothing recomputed and the loss over whole
    logits: the pattern and the kinds' counts on the configuration, the
    routing's and the weights' shapes from ``jax.eval_shape`` of the 52
    layers, and the loss against the reference's through the first
    three periods.  (The pattern repeats every four layers and adds no
    kind of leaf to the cut's; compiling 52 unrolled layers for both
    sides was 20 s here for the same comparison 13 times over.)"""
    arch = arch_of(*held, layout=layout)
    assert [i for i, k in enumerate(arch["layer_kinds"]) if k == "nope"] \
        == list(range(0, len(layout), 4))
    toks = tokens()
    cfg = config(arch, remat=remat, loss_row_block=rows)
    assert cfg.ffn_kinds == ("moe",) * len(layout) and not cfg.tied_head
    if not leaves_too:
        # the expert layers are traced once, as one jitted function
        # that the step calls (156 kernels in interpret mode otherwise)
        monkeypatch.setattr(hybrid, "moe_held", jax.jit(
            moe.moe_held, static_argnums=(5,), static_argnames=(
                "held", "slots", "scoring", "scale", "activation")))
        assert len(layout) == 52 and cfg.layer_kinds.count("nope") == 13
        shapes = jax.eval_shape(lambda: weights.make_params(arch, 0))
        assert shapes["gated"]["wq"].shape[0] == shapes["moe"][
            "w_gate"].shape[0] == 52
        loss, routing = jax.eval_shape(
            lambda p: hybrid.loss_and_routing(p, toks, cfg), shapes)
        assert loss.shape == () and routing["choices"].shape \
            == (52, SEQ, TOP_K)
        layout = layout[:12]
        arch = arch_of(*held, layout=layout)
        cfg = config(arch, remat=remat, loss_row_block=rows)
    params = moved(weights.make_params(arch, 2**31 + 5))
    both = jax.value_and_grad if leaves_too else (lambda f, **kw: f)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(both(lambda p: ref.loss_fn(p, toks, arch)))(params)
        got = jax.jit(both(
            lambda p: hybrid.loss_and_routing(p, toks, cfg),
            has_aux=True))(params)
    (loss, routing), want_loss = (got[0], want[0]) if leaves_too \
        else (got, want)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert int(routing["past_bound"]) == 0
    assert routing["choices"].shape == (len(layout), SEQ, TOP_K)
    if not leaves_too:
        return
    got, want = leaves(got[1]), leaves(want[1])
    assert set(got) == set(want)
    scale = np.median([float(jnp.abs(w).max()) for w in want.values()])
    for name, w in want.items():
        close(got[name], w, rtol=5e-4,
              atol=5e-5 * max(scale, float(jnp.abs(w).max())),
              err_msg=name)
        assert np.asarray(w).any(), name


def expert_layer_inputs(seed=7):
    arch = arch_of()
    fp = jax.tree.map(lambda a: a[0],
                      weights.make_params(arch, seed)["moe"])
    y1, y2 = (jax.random.normal(jax.random.key(seed + i), (2 * SEQ, D))
              for i in range(2))
    return arch, fp, y1, y2


def held_part(fp, y1, y2, first, n, slots):
    return moe.moe_held(
        y2, fp["w_router"], *(fp[k][first:first + n] for k in
                              ("w_gate", "w_up", "w_down")),
        TOP_K, held=(first, n), slots=slots, scoring="softmax",
        router_x=y1, activation="relu")


@pytest.mark.parametrize("shares", [1, 4, 8])
def test_the_shares_routed_parts_add_up_to_the_uncut_layer(shares):
    """What each of the chips that share a layer computes of the routed
    experts, summed, is the reference's layer with every expert held:
    four shares of 16 (the configuration's own cut), eight of 8, and the
    whole layer.  No expert is shared, so nothing is counted twice."""
    arch, fp, y1, y2 = expert_layer_inputs()
    n = EXPERTS // shares

    @jax.jit
    def parts(fp):
        outs, routed, past = [], 0, 0
        for first in range(0, EXPERTS, n):
            out, routing = held_part(fp, y1, y2, first, n, 2 * SEQ)
            outs.append(out)
            routed += routing["routed"]
            past += routing["past_bound"]
        return outs, routed, past

    with jax.default_matmul_precision("highest"):
        combine, _ = ref.route(y1, fp["w_router"], arch)
        whole = jax.jit(lambda fp: ref.expert_layer(
            y2, combine, fp, arch, F32))(fp)
        outs, routed, past = parts(fp)
    assert len(outs) == shares and int(past) == 0
    assert int(routed) == 2 * SEQ * TOP_K
    close(sum(outs), whole)
    if shares > 1:
        assert gap(outs[0], whole) > 1e-2


def test_a_load_past_the_bound_is_counted():
    _, fp, y1, y2 = expert_layer_inputs()
    slots = 4

    def held(slots):
        return held_part(fp, y1, y2, 0, 16, slots)[1]
    tight, loose = (jax.jit(held, static_argnums=0)(n)
                    for n in (slots, 2 * SEQ))
    assert int(loose["past_bound"]) == 0 < int(tight["past_bound"])
    assert int(tight["max_load"]) == int(loose["max_load"]) > slots
    assert int(tight["routed"]) == int(loose["routed"])


def test_card_states_the_layers_and_the_config_follows_it():
    from dlnetbench_tpu.core.model_card import load_model_card
    card = load_model_card("smallthinker_21b_a3b")
    assert card.num_params() == pytest.approx(21.507e9, rel=1e-4)
    # active a token in the layers: attention, router, six experts
    layer = (card.mixer_params("swa") + 2560 * 64 + 2 * 2560
             + 6 * card.routed_expert_params())
    assert 52 * layer == pytest.approx(2.94e9, rel=1e-3)
    cfg = hybrid.HybridConfig.from_card(card, seq_len=128, moe_slots=64)
    assert cfg.layer_kinds == tuple(weights.KIND_OF[w] for w in PUBLISHED)
    assert cfg.layer_kinds.count("nope") == 13
    assert cfg.ffn_kinds == ("moe",) * 52
    assert (cfg.router_scoring, cfg.top_k, cfg.num_experts,
            cfg.expert_ff_dim, cfg.shared_ff_dim, cfg.early_router,
            cfg.expert_activation, cfg.held_experts) == (
        "softmax", 6, 64, 768, 0, True, "relu", (0, 64))
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.rope_dim,
            cfg.attn_gate, cfg.head_norm, cfg.attention_window) == (
        28, 4, 128, 0, False, False, 4096)
    assert cfg.rms_norm and not cfg.norm_plus_one and not cfg.tied_head
    assert cfg.norm_eps == 1e-6 and cfg.rope_theta == 1.5e6
    assert cfg.vocab_size == 151936 and cfg.seq_len == 128
    assert hybrid._splash_block(cfg, 16384) == 2048
