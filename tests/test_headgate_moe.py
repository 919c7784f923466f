"""The head-gated window-and-full attention expert decoder
(``models/hybrid.py`` with ``swa`` and ``gated`` layers at head counts
and RoPE of their own, one sigmoid gate a head, a leading dense layer,
softmax-routed experts times a scale beside a plain shared one) against
the benchmark's plain reference (``benchmarks/reference_headgate_moe.py``)
at a small size in float32 on the CPU: YaRN's frequencies against hand
values, each kind's mixer at its own group (nine and six) with the
gate's gradient and what it must not be, the expert layer's scale and
shared expert, the whole model at the cell's pattern, and the shares of
the expert layer adding up to the uncut layer."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_headgate_moe as ref
from benchmarks import weights_headgate_moe as weights
from benchmarks.runners import train_headgate_moe
from dlnetbench_tpu.models import hybrid, layers, moe

EXPERTS, TOP_K, SEQ, D, WINDOW = 32, 4, 48, 64, 16
KINDS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
CONFIG = {
    "hidden_size": D, "intermediate_size": 128, "num_attention_heads": 12,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "attention_bias": False, "rms_norm_eps": 1e-6,
    "num_experts_per_tok": TOP_K, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": WINDOW,
    "rope_parameters": {
        # the original positions inside the sequence, so that the ramp
        # and the factor both show at 48 tokens and 8 turned lanes
        "full_attention": {**YARN, "original_max_position_embeddings": 32,
                           "factor": 8},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "torch_dtype": "float32"}
F32 = ref.MATMULS["float32"]


def arch_of(first: int = 0, held: int = EXPERTS, kinds=KINDS, **over):
    n = len(kinds)
    return weights.arch_of({
        **CONFIG, "num_experts": held, "num_hidden_layers": n,
        "layer_types": kinds,
        "mlp_layer_types": ["dense"] + ["sparse"] * (n - 1),
        "gating_types": ["per_head"] * n,
        "num_attention_heads_per_layer": [
            12 if k == "full_attention" else 18 for k in kinds],
        "published": {"num_experts": EXPERTS},
        "assumed": {"first_held_expert": first}, **over})


def config(arch: dict, slots: int = 2 * SEQ, seq: int = SEQ, **over):
    return train_headgate_moe.config_of(arch, seq, slots, **over)


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


# ------------------------------------------------------------- YaRN
PUBLISHED_YARN = (128.0, 8192.0, 32.0, 1.0, 1.4852030263919618)


def _c(turns, d=64, b=5e5, l0=8192):
    return d * math.log(l0 / (2 * math.pi * turns)) / (2 * math.log(b))


@pytest.mark.parametrize("side", ["program", "reference"])
def test_yarn_frequencies_at_the_published_numbers_against_hand_values(
        side):
    """d = 64 turned lanes, base 5e5, factor 128 over 8192 original
    positions, 32 and 1 turns: c(32) = 9.04 and c(1) = 17.5, so the ramp
    rises from pair 9 to pair 18; pairs 0-9 turn at the plain
    frequencies, pairs 18-31 at a 128th of them, pair 13 at
    ``b^(-26/64) (5/9 + 4/9/128)``; cos and sin times 1.4852."""
    assert _c(32) == pytest.approx(9.04, abs=0.01)
    assert _c(1) == pytest.approx(17.5, abs=0.05)
    assert ref.yarn_range(64, 5e5, PUBLISHED_YARN) == (9, 18)
    if side == "program":
        inv, factor = layers.rope_freqs(5e5, 64, PUBLISHED_YARN)
    else:
        inv, factor = ref.inv_freqs(5e5, 64, PUBLISHED_YARN)
    inv = np.asarray(inv, np.float64)
    assert inv.shape == (32,) and factor == 1.4852030263919618
    plain = 5e5 ** (-2.0 * np.arange(32) / 64)
    close(inv[:10], plain[:10], rtol=2e-6, atol=0)
    close(inv[18:], plain[18:] / 128, rtol=2e-6, atol=0)
    close(inv[13], 5e5 ** (-26 / 64) * (5 / 9 + 4 / 9 / 128), rtol=2e-6,
          atol=0)
    hand = {0: 1.0, 9: 0.024955, 10: 0.014735, 17: 1.1079e-4,
            18: 4.8654e-6, 31: 2.3546e-8}
    for i, v in hand.items():
        assert inv[i] == pytest.approx(v, rel=2e-4), i
    assert (np.diff(inv) < 0).all()


def test_plain_rope_is_what_it_was_and_yarn_scales_cos_and_sin():
    q = jax.random.normal(jax.random.key(0), (1, SEQ, 3, 8))
    k = jax.random.normal(jax.random.key(1), (1, SEQ, 1, 8))
    pos = jnp.arange(SEQ)
    plain = layers.rope(q, k, pos, 5e5)
    assert all((a == b).all() for a, b in zip(
        plain, layers.rope(q, k, pos, 5e5, None)))
    # no ramp inside these lanes and a factor of 1: YaRN's path gives
    # plain RoPE; the factor alone scales both
    flat = (1.0, 1e9, 32.0, 1.0, 1.0)
    for a, b in zip(plain, layers.rope(q, k, pos, 5e5, flat)):
        close(a, b, rtol=1e-5)
    for a, b in zip(plain, layers.rope(q, k, pos, 5e5, (*flat[:4], 1.5))):
        close(1.5 * a, b, rtol=1e-5)
    yarn = arch_of()["rope_full"][2]
    assert gap(plain[0], layers.rope(q, k, pos, 5e5, yarn)[0]) > 0.1


# ----------------------------------------------------------- the mixer
@pytest.fixture(scope="module")
def attention_layer():
    arch = arch_of()
    made = weights.make_params(arch, 3)
    p = {g: jax.tree.map(lambda a: a[0], made[g]) for g in ref.GROUPS}
    y = jax.random.normal(jax.random.key(3), (2, SEQ, D))
    return arch, config(arch), p, y


def want(kind, arch, p, y):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, y: ref.attention(y, p, arch, F32, kind))(
            p, y)


def got(kind, cfg, p, y):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, y: hybrid.gated_mixer(cfg, y, p, kind))(
            p, y)


@pytest.mark.parametrize("kind,heads", [("swa", 18), ("gated", 12)])
def test_mixer_forward_and_every_gradient_against_the_reference(
        attention_layer, kind, heads):
    """18 query heads over 2 in a window layer (groups of nine), 12 over
    2 in a full layer (groups of six): the output and the gradients of
    the four projections, the gate's projection and the input."""
    arch, cfg, p, y = attention_layer
    p = p[kind]
    assert cfg.heads_of(kind) == heads == p["wg"].shape[-1]
    assert p["wq"].shape == (D, heads * 16) and WINDOW < SEQ
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
    assert set(g_p) == {"wq", "wk", "wv", "wo", "wg"}
    for k in g_p:
        close(g_p[k], w_p[k], err_msg=k)
        assert float(jnp.abs(w_p[k]).max()) > 1e-2, k


def test_the_gate_is_a_heads_sigmoid_of_the_layers_input(attention_layer):
    """A gate's projection at zero halves every head; one head's column
    far below zero closes that head and no other; without the gate the
    layer is another layer."""
    arch, cfg, p, y = attention_layer
    for kind in ("swa", "gated"):
        pk = p[kind]
        open_ = got(kind, with_(cfg, attn_gate=False), pk, y)
        half = got(kind, cfg, {**pk, "wg": jnp.zeros_like(pk["wg"])}, y)
        close(half, 0.5 * open_)
        assert gap(got(kind, cfg, pk, y), open_) > 1e-2
        assert gap(got(kind, cfg, pk, y), want(kind, arch, pk, y)) < 1e-4
    # head 5 of the window layer shut in the rows where its gate's
    # projection is far below zero: there the layer is the layer with
    # that head's rows of W_o at zero
    pk = p["swa"]
    col = -1e3 * jnp.sign(jnp.sum(y, (0, 1)))
    shut = {**pk, "wg": pk["wg"].at[:, 5].set(col)}
    live = jnp.einsum("bsd,d->bs", y, col) < -20
    without = {**pk, "wo": pk["wo"].at[5 * 16:6 * 16].set(0.0)}
    a, b = got("swa", cfg, shut, y), got("swa", cfg, without, y)
    assert bool(live.any())
    close(a[live], b[live], atol=1e-4)
    wide = with_(cfg, attn_gate=True)
    assert hybrid.param_shapes(wide)["swa/wq"][0][-1] == 2 * 18 * 16
    assert "swa/wg" not in hybrid.param_shapes(wide)
    assert hybrid.param_shapes(cfg)["gated/wg"][0] == (2, D, 12)


def test_a_window_layer_sees_the_last_window_keys_and_no_more(
        attention_layer):
    arch, cfg, p, y = attention_layer
    t = SEQ - 4
    for back, seen in ((WINDOW, False), (WINDOW - 1, True)):
        y2 = y.at[:, t - back].add(1.0)
        # the gate reads the query's own row, which is not moved
        assert (gap(got("swa", cfg, p["swa"], y2)[:, t],
                    got("swa", cfg, p["swa"], y)[:, t]) > 1e-3) == seen
        assert gap(got("gated", cfg, p["gated"], y2)[:, t],
                   got("gated", cfg, p["gated"], y)[:, t]) > 1e-3


def test_each_kinds_positions_are_its_own(attention_layer):
    """A full layer turned as a window layer is (every lane, theta 1e4,
    no YaRN), a window layer turned as a full one, YaRN without its
    factor or without its ramp: each another layer than the
    reference's."""
    arch, cfg, p, y = attention_layer
    assert cfg.rope_of("swa") == (10000.0, 16, None)
    theta, lanes, yarn = cfg.rope_of("gated")
    assert (theta, lanes) == (5e5, 8) and yarn == arch["rope_full"][2]
    w_full, w_win = (want(k, arch, p[k], y) for k in ("gated", "swa"))
    close(got("gated", cfg, p["gated"], y), w_full)
    close(got("swa", cfg, p["swa"], y), w_win)
    plain = with_(cfg, **train_headgate_moe.FAULTS["plain_rope"])
    assert plain.rope_of("gated") == (10000.0, 16, None)
    assert plain.rope_of("swa") == cfg.rope_of("swa")
    assert gap(got("gated", plain, p["gated"], y), w_full) > 1e-2
    close(got("swa", plain, p["swa"], y), w_win)
    for other in (with_(cfg, rope_yarn=()),
                  with_(cfg, rope_yarn=(*yarn[:4], 1.0)),
                  with_(cfg, rope_yarn=(1.0, *yarn[1:])),
                  with_(cfg, rope_dim=16)):
        assert gap(got("gated", other, p["gated"], y), w_full) > 1e-3
    for other in (with_(cfg, window_rope_theta=5e5),
                  with_(cfg, window_rope_dim=8)):
        assert gap(got("swa", other, p["swa"], y), w_win) > 1e-3
    # and the reference with twice the lanes turned is another layer
    assert gap(want("gated", {**arch, "rope_full": (theta, 16, yarn)},
                    p["gated"], y), w_full) > 1e-3


def test_a_configuration_refuses_what_no_layer_computes():
    arch = arch_of()
    for over, match in (({"window_heads": 17}, "window_heads"),
                        ({"attn_gate": "lane"}, "attn_gate"),
                        ({"rope_yarn": (1.0, 2.0)}, "rope_yarn"),
                        ({"window_rope_dim": 18}, "window_rope_dim")):
        with pytest.raises(ValueError, match=match):
            config(arch, **over)
    one = config(arch, window_heads=12)     # one head count: one stack
    assert one.group_of("swa") == "gated" == one.group_of("gated")
    assert "swa/wq" not in hybrid.param_shapes(one)
    assert hybrid.param_shapes(one)["gated/wq"][0] == (5, D, 12 * 16)
    two = config(arch)
    assert (two.group_of("swa"), two.group_of("gated")) == ("swa", "gated")
    assert two.group_sizes()["swa"] == 3 and two.group_sizes()["gated"] == 2
    assert [two.index_in_group(i) for i in range(5)] == [0, 0, 1, 2, 1]
    assert (two.attn_scope("swa"), two.attn_scope("gated")) \
        == ("attn.window", "attn.full")
    alone = with_(two, layer_kinds=("gated",) * 5)
    assert alone.attn_scope("gated") is None
    # what only a window layer reads is checked only where there is one
    assert with_(alone, window_heads=17).group_of("gated") == "gated"
    assert all(two.group_of(k) for k in hybrid.KINDS)


# ---------------------------------------------------- the expert layer
@pytest.fixture(scope="module")
def one_layer():
    """A window layer with an expert FFN: layer 1 of a stack of two."""
    arch = arch_of(kinds=KINDS[:2])
    cfg = config(arch)
    p = moved(weights.make_params(arch, 7))
    layer = {"block": {k: a[1] for k, a in p["block"].items()
                       if k not in ref.MLP},
             "swa": jax.tree.map(lambda a: a[0], p["swa"]),
             "moe": jax.tree.map(lambda a: a[0], p["moe"])}
    x = jax.random.normal(jax.random.key(7), (2, SEQ, D))
    return arch, cfg, layer, x


def run_layer(cfg, layer, x):
    out, _, routing = hybrid._layer(cfg, 1, x, layer["block"], layer["swa"],
                                    layer["moe"], None, None)
    return out, routing


def ref_layer(arch, layer, x):
    return ref.layer(x, {"block": layer["block"], "mixer": layer["swa"],
                         "ffn": layer["moe"]}, kind="swa", dense=False,
                     arch=arch, mm=F32)


def jitted(fn, *static):
    def run(layer, x):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda layer, x: fn(*static, layer, x))(layer, x)
    return run


def test_layer_against_the_reference_with_its_scale_and_shared_expert(
        one_layer):
    arch, cfg, layer, x = one_layer
    assert (cfg.routed_scale, cfg.shared_ff_dim, cfg.shared_gate,
            cfg.router_scoring, cfg.early_router) == (
        2.5, 32, False, "softmax", False)
    out, routing = jitted(run_layer, cfg)(layer, x)
    w_out, w_idx = jitted(ref_layer, arch)(layer, x)
    close(out, w_out)
    assert (np.sort(routing["choices"]) == np.sort(w_idx)).all()
    assert int(routing["past_bound"]) == 0
    for fault in ("unit_routed_scale", "no_head_gate"):
        other = with_(cfg, **train_headgate_moe.FAULTS[fault])
        f_out, f_routing = jitted(run_layer, other)(layer, x)
        assert gap(f_out, w_out) > 1e-2, fault
        if fault == "unit_routed_scale":
            assert (f_routing["choices"] == routing["choices"]).all()
    assert gap(jitted(run_layer, with_(cfg, shared_ff_dim=0))(layer, x)[0],
               w_out) > 1e-2


def test_the_softmax_routers_weights_sum_to_the_scale():
    x = jax.random.normal(jax.random.key(2), (SEQ, D))
    w_r = jax.random.normal(jax.random.key(3), (D, EXPERTS)) / 8
    plain, idx = layers.moe_router(x, w_r, TOP_K)
    scaled, idx2 = layers.moe_router(x, w_r, TOP_K, scale=2.5)
    assert (idx == idx2).all()
    close(jnp.sum(plain, -1), jnp.ones(SEQ))
    close(scaled, 2.5 * plain, rtol=1e-6)
    combine, r_idx = ref.route(x, w_r, {"top_k": TOP_K, "routed_scale": 2.5,
                                        "num_experts": EXPERTS})
    assert (np.sort(idx) == np.sort(r_idx)).all()
    close(jnp.take_along_axis(combine, idx, -1), scaled)


# ----------------------------------------------------------- the model
def test_benchmark_weights_follow_the_programs_layout():
    arch = arch_of(4, 2)
    assert {k: shape for k, (shape, _) in weights.shapes(arch).items()} \
        == {k: shape for k, (shape, _)
            in hybrid.param_shapes(config(arch)).items()}
    made = weights.make_params(arch, 3)
    own = jax.eval_shape(
        lambda: hybrid.init_params(jax.random.key(3), config(arch)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), made) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), own)
    assert made["head"].shape == made["embed"].shape == (256, D)
    assert made["moe"]["w_gate"].shape[:2] == (4, 2)
    assert made["moe"]["w_router"].shape == (4, D, EXPERTS)
    assert made["block"]["w_gate"].shape == (1, D, 128)
    assert made["swa"]["wq"].shape == (3, D, 18 * 16)
    assert made["gated"]["wq"].shape == (2, D, 12 * 16)
    assert "router_bias" not in made["moe"] and "ws_sig" not in made["moe"]


def test_loss_selection_and_every_gradient_leaf_against_the_reference():
    """The whole model at the cell's pattern: a full layer with a dense
    MLP, three window layers at nine a group and a full layer at six in
    one stack, each with experts (2 of 32 held from expert 4) beside a
    shared one, each layer recomputed, head and loss in row blocks: the
    loss, the selection of every expert layer and every leaf's
    gradient."""
    arch = arch_of(4, 2)
    toks = tokens()
    cfg = config(arch, remat=True, loss_row_block=16)
    assert cfg.layer_kinds == ("gated", "swa", "swa", "swa", "gated")
    assert cfg.ffn_kinds == ("dense",) + ("moe",) * 4 and not cfg.tied_head
    params = moved(weights.make_params(arch, 2**31 + 5))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_fn(p, toks, arch)))(params)
        got = jax.jit(jax.value_and_grad(
            lambda p: hybrid.loss_and_routing(p, toks, cfg),
            has_aux=True))(params)
        _, _, chosen = ref.LayerwiseGrad(arch)(ref.unstack(params, arch),
                                               toks)
    (loss, routing), want_loss = got[0], want[0]
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert int(routing["past_bound"]) == 0
    assert routing["choices"].shape == (4, SEQ, TOP_K)
    assert (np.sort(routing["choices"]) == np.sort(jnp.stack(chosen))).all()
    got, want = leaves(got[1]), leaves(want[1])
    assert set(got) == set(want)
    assert {"swa/wg/2", "gated/wg/1", "moe/ws_up/3", "block/w_up/0"} \
        <= set(got)
    scale = np.median([float(jnp.abs(w).max()) for w in want.values()])
    for name, w in want.items():
        close(got[name], w, rtol=5e-4,
              atol=5e-5 * max(scale, float(jnp.abs(w).max())),
              err_msg=name)
        assert np.asarray(w).any(), name


@pytest.fixture(scope="module")
def expert_layer_inputs():
    arch = arch_of()
    fp = jax.tree.map(lambda a: a[0], weights.make_params(arch, 7)["moe"])
    y = jax.random.normal(jax.random.key(7), (2 * SEQ, D))
    return arch, fp, y


def held_part(fp, y, first, n, slots):
    return moe.moe_held(
        y, fp["w_router"], *(fp[k][first:first + n] for k in
                             ("w_gate", "w_up", "w_down")),
        TOP_K, held=(first, n), slots=slots, scoring="softmax", scale=2.5)


@pytest.mark.parametrize("shares", [1, 4, 16])
def test_the_shares_routed_parts_add_up_to_the_uncut_layer(
        expert_layer_inputs, shares):
    """What each of the chips that share a layer computes of the routed
    experts, summed, with the shared expert (which every chip computes
    alike) counted once, is the reference's layer with every expert
    held: sixteen shares of 2 (the configuration's own cut of a
    sixteenth), four of 8, and the whole layer."""
    arch, fp, y = expert_layer_inputs
    n = EXPERTS // shares

    @jax.jit
    def parts(fp):
        outs, routed, past = [], 0, 0
        for first in range(0, EXPERTS, n):
            out, routing = held_part(fp, y, first, n, 2 * SEQ)
            outs.append(out)
            routed += routing["routed"]
            past += routing["past_bound"]
        shared = layers.swiglu(y, fp["ws_gate"], fp["ws_up"], fp["ws_down"])
        return outs, shared, routed, past

    with jax.default_matmul_precision("highest"):
        whole, _ = jax.jit(lambda fp: ref.expert_layer(y, fp, arch, F32))(fp)
        outs, shared, routed, past = parts(fp)
    assert len(outs) == shares and int(past) == 0
    assert int(routed) == 2 * SEQ * TOP_K
    close(sum(outs) + shared, whole)
    assert gap(sum(outs) + shares * shared, whole) > 1e-2 or shares == 1
    if shares > 1:
        assert gap(outs[0] + shared, whole) > 1e-2


def test_a_load_past_the_bound_is_counted(expert_layer_inputs):
    _, fp, y = expert_layer_inputs
    slots = 2

    def held(slots):
        return held_part(fp, y, 0, 8, slots)[1]
    tight, loose = (jax.jit(held, static_argnums=0)(n)
                    for n in (slots, 2 * SEQ))
    assert int(loose["past_bound"]) == 0 < int(tight["past_bound"])
    assert int(tight["max_load"]) == int(loose["max_load"]) > slots
    assert int(tight["routed"]) == int(loose["routed"])


def test_card_states_the_layers_and_the_config_follows_it():
    from dlnetbench_tpu.core.model_card import load_model_card
    card = load_model_card("laguna_s_2_1")
    assert card.num_params() == pytest.approx(117.56e9, rel=1e-4)
    assert card.mixer_params("swa") == 63_135_744      # 63.13 M
    assert card.mixer_params("gated") == 44_187_648    # 44.19 M
    # read a token: attention, router, ten experts and the shared one,
    # the dense layer, the head
    read = (12 * card.mixer_params("gated") + 36 * card.mixer_params("swa")
            + card.mlp_params_per_expert()
            + 47 * (11 * card.routed_expert_params() + 3072 * 256)
            + card.vocab_size * 3072)
    assert read == pytest.approx(8.14e9, rel=2e-3)
    cfg = hybrid.HybridConfig.from_card(card, seq_len=64, moe_slots=64,
                                        held_experts=(0, 16))
    assert cfg.layer_kinds[:5] == ("gated", "swa", "swa", "swa", "gated")
    assert cfg.layer_kinds.count("gated") == 12
    assert (cfg.num_heads, cfg.window_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.attention_window, cfg.attn_gate, cfg.head_norm) == (
        48, 72, 8, 128, 512, "head", False)
    assert cfg.rope_of("swa") == (10000.0, 128, None)
    assert cfg.rope_of("gated") == (500000.0, 64, PUBLISHED_YARN)
    assert (cfg.num_experts, cfg.top_k, cfg.expert_ff_dim, cfg.shared_ff_dim,
            cfg.routed_scale, cfg.ffn_kinds[:2], cfg.ff_dim) == (
        256, 10, 1024, 1024, 2.5, ("dense", "moe"), 12288)
    shapes = hybrid.param_shapes(cfg)
    assert shapes["swa/wq"][0] == (36, 3072, 72 * 128)
    assert shapes["gated/wg"][0] == (12, 3072, 48)
    assert shapes["moe/w_gate"][0] == (47, 16, 3072, 1024)
