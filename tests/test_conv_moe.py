"""The short-convolution expert decoder (``models/hybrid.py`` with
``conv`` layers, ``gated`` layers without a gate, leading dense FFNs and
then expert ones, the head tied) against the benchmark's plain reference
(``benchmarks/reference_conv_moe.py``) at a small size in float32 on the
CPU: the conv mixer and what it must not be (taps reversed, an
activation, another stream order), the attention layer (its norms a
head, before RoPE, no gate's lanes), the whole model at the cell's
pattern and at the published one, the tie, and the shares of the expert
layer adding up to the uncut layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_conv_moe as ref
from benchmarks import weights_conv_moe as weights
from benchmarks.runners import train_conv_moe
from dlnetbench_tpu.models import hybrid, moe

EXPERTS, TOP_K, SEQ, D = 32, 4, 48, 64
CUT = ["conv", "full_attention", "conv", "conv", "conv"]
PUBLISHED = [
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
CONFIG = {
    "hidden_size": D, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts_per_tok": TOP_K, "conv_L_cache": 3, "conv_bias": False,
    "norm_eps": 1e-5, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "rope_theta": 1000000, "vocab_size": 256,
    "num_hidden_layers": 5, "num_dense_layers": 1, "layer_types": CUT,
    "torch_dtype": "float32"}
F32 = ref.MATMULS["float32"]


def arch_of(first: int = 0, held: int = EXPERTS, **over) -> dict:
    return weights.arch_of({
        **CONFIG, "num_experts": held,
        "published": {"num_experts": EXPERTS},
        "assumed": {"first_held_expert": first, "router_bias_scale": 0.01},
        **over})


def config(arch: dict, slots: int = SEQ, seq: int = SEQ, **over):
    return train_conv_moe.config_of(arch, seq, slots, **over)


def tokens(seq: int = SEQ):
    return jax.random.randint(jax.random.key(1), (1, seq + 1), 0, 256)


def moved(params, seed=9):
    """The seeded weights with every norm's weight drawn away from one
    (a norm a head with weight one commutes with RoPE) and the selection
    bias ten times as wide, so that it moves the selection at this
    size."""
    out = jax.tree.map(lambda a: a, params)
    keys = iter(jax.random.split(jax.random.key(seed), 8))
    for g, k in ((None, "final_norm"), ("block", "norm1"),
                 ("block", "norm2"), ("gated", "q_norm"),
                 ("gated", "k_norm")):
        tree = out if g is None else out[g]
        tree[k] = 1.0 + 0.3 * jax.random.normal(next(keys), tree[k].shape)
    out["moe"]["router_bias"] = 10.0 * params["moe"]["router_bias"]
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


# ------------------------------------------------------ the conv mixer
def conv_layer(seed=3):
    p = jax.tree.map(lambda a: a[0],
                     weights.make_params(arch_of(), seed)["conv"])
    y = jax.random.normal(jax.random.key(seed), (2, SEQ, D))
    return p, y


def conv_want(p, y):
    with jax.default_matmul_precision("highest"):
        return ref.short_conv(y, p, F32)


def test_conv_mixer_forward_and_every_gradient_against_the_reference():
    """``(c * conv(b * u)) W_out`` through the hand-written backward:
    the output and the gradients of ``W_in``, the taps, ``W_out`` and
    the input."""
    p, y = conv_layer()
    ct = jax.random.normal(jax.random.key(8), y.shape)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda p, y: hybrid.conv_mixer(y, p), p, y)
        want, want_vjp = jax.vjp(lambda p, y: ref.short_conv(y, p, F32),
                                 p, y)
        (g_p, g_y), (w_p, w_y) = vjp(ct), want_vjp(ct)
    close(got, want)
    close(g_y, w_y)
    for k in ("w_in", "conv_w", "w_out"):
        close(g_p[k], w_p[k], err_msg=k)
        assert float(jnp.abs(w_p[k]).max()) > 1e-2, k


def test_conv_mixer_in_bf16_keeps_the_projection_alone_for_the_backward():
    """The residuals of the gated convolution are the three streams as
    the projection wrote them and the taps: no float32 [B, S, D]."""
    p, y = conv_layer()
    p, y = jax.tree.map(lambda a: a.astype(jnp.bfloat16), (p, y))
    bcu = jnp.dot(y, p["w_in"])
    out, res = hybrid._gated_conv_fwd(bcu, p["conv_w"])
    assert out.dtype == jnp.bfloat16 and out.shape == y.shape
    assert [(r.shape, r.dtype) for r in res] == [
        (bcu.shape, jnp.bfloat16), (p["conv_w"].shape, jnp.bfloat16)]
    dbcu, dw = hybrid._gated_conv_bwd(res, out)
    assert (dbcu.dtype, dbcu.shape, dw.shape) \
        == (jnp.bfloat16, bcu.shape, p["conv_w"].shape)


def _reversed_taps(p, y):
    return conv_want({**p, "conv_w": p["conv_w"][::-1]}, y)


def _with_activation(p, y):
    """``silu`` over the convolution's output, as the linear-attention
    family's conv has it."""
    b, s, d = y.shape
    bcu = y @ p["w_in"]
    z = jnp.pad(bcu[..., :d] * bcu[..., 2 * d:], ((0, 0), (2, 0), (0, 0)))
    h = sum(z[:, j:j + s] * p["conv_w"][j] for j in range(3))
    return (bcu[..., d:2 * d] * ref.silu(h)) @ p["w_out"]


def _streams_as_b_u_c(p, y):
    w = p["w_in"].reshape(D, 3, D)
    return conv_want({**p, "w_in": w[:, [0, 2, 1]].reshape(D, 3 * D)}, y)


@pytest.mark.parametrize("other", [_reversed_taps, _with_activation,
                                   _streams_as_b_u_c])
def test_conv_mixer_is_none_of_its_neighbours(other):
    """A conv that sees the future (tap 0 the current token), an
    activation on the convolution, the streams taken as ``[b | u | c]``:
    each is another function of the same weights, and the program is
    the reference's."""
    p, y = conv_layer()
    with jax.default_matmul_precision("highest"):
        got = hybrid.conv_mixer(y, p)
        assert gap(got, conv_want(p, y)) < 1e-5
        assert gap(got, other(p, y)) > 1e-2


def test_conv_mixer_is_causal_and_starts_from_a_zero_history():
    """A token's output moves with no later token, and the first two
    tokens see zeros before the sequence's start: token 0 its own
    product under the last tap alone."""
    p, y = conv_layer()
    with jax.default_matmul_precision("highest"):
        got = hybrid.conv_mixer(y, p)
        later = hybrid.conv_mixer(y.at[:, 20:].add(1.0), p)
        bcu = y @ p["w_in"]
        b, c, u = jnp.split(bcu, 3, axis=-1)
        z, w = b * u, p["conv_w"]
        first = (c[:, 0] * (w[2] * z[:, 0])) @ p["w_out"]
        second = (c[:, 1] * (w[1] * z[:, 0] + w[2] * z[:, 1])) @ p["w_out"]
    assert gap(got[:, :20], later[:, :20]) == 0.0
    assert gap(got[:, 20:], later[:, 20:]) > 1e-2
    close(got[:, 0], first)
    close(got[:, 1], second)


# ------------------------------------------------- the attention layer
def attention_layer(seed=6):
    arch = arch_of()
    p = jax.tree.map(lambda a: a[0],
                     moved(weights.make_params(arch, seed))["gated"])
    y = jax.random.normal(jax.random.key(2), (1, SEQ, D))
    cfg = config(arch, attention_impl="xla")

    def want(p):
        with jax.default_matmul_precision("highest"):
            return ref.attention(y, p, arch, F32)
    return arch, cfg, p, y, jax.jit(hybrid.gated_mixer,
                                    static_argnums=0), want


def test_attention_norms_each_head_before_rope_and_has_no_gate():
    """Grouped softmax attention with a norm a head: the program is the
    reference's; without the norms, or with RoPE before them, it is
    another layer (the norms' weights are away from one: a weight of one
    commutes with the rotation)."""
    arch, cfg, p, y, mixer, want = attention_layer()
    assert (cfg.attn_gate, cfg.rope_dim, cfg.head_dim) == (False, 0, 16)
    close(mixer(cfg, y, p), want(p))
    plain = jax.jit(train_conv_moe._no_qk_norm(hybrid.gated_mixer),
                    static_argnums=0)
    assert gap(plain(cfg, y, p), want(p)) > 1e-2

    def rope_first(p):
        """The reference with the rotation before the norm."""
        b, s, _ = y.shape
        turn = jax.vmap(lambda t: ref.rope(t, arch["rope_theta"]))
        q = ref.rmsnorm(turn((y @ p["wq"]).reshape(b, s, 4, 16)),
                        p["q_norm"], 1e-5)
        k = ref.rmsnorm(turn((y @ p["wk"]).reshape(b, s, 2, 16)),
                        p["k_norm"], 1e-5)
        v = (y @ p["wv"]).reshape(b, s, 2, 16)
        k, v = (jnp.repeat(t, 2, axis=2) for t in (k, v))
        o = jnp.stack([ref.attention_head(q[0, :, h], k[0, :, h],
                                          v[0, :, h]) for h in range(4)], 1)
        return o.reshape(1, s, 64) @ p["wo"]
    with jax.default_matmul_precision("highest"):
        assert gap(rope_first(p), want(p)) > 1e-2


def test_query_projection_carries_no_gate_lanes():
    """The card states no output gate: ``W_q`` is the queries alone, and
    a configuration that expects a gate's lanes beside them does not
    take these weights."""
    arch, cfg, p, y, mixer, _ = attention_layer()
    assert hybrid.param_shapes(cfg)["gated/wq"][0] == (1, D, D)
    gated = hybrid.HybridConfig(**{**cfg.__dict__, "attn_gate": True})
    assert hybrid.param_shapes(gated)["gated/wq"][0] == (1, D, 2 * D)
    with pytest.raises(TypeError, match="reshape"):
        mixer(gated, y, p)


# ----------------------------------------------------------- the model
def test_benchmark_weights_follow_the_programs_layout():
    arch = arch_of(8, 8)
    assert {k: shape for k, (shape, _) in weights.shapes(arch).items()} \
        == {k: shape for k, (shape, _)
            in hybrid.param_shapes(config(arch)).items()}
    made = weights.make_params(arch, 3)
    own = jax.eval_shape(
        lambda: hybrid.init_params(jax.random.key(3), config(arch)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), made) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), own)
    assert "head" not in made and made["moe"]["w_gate"].shape[:2] == (4, 8)
    bias = np.asarray(made["moe"]["router_bias"])
    assert bias.dtype == np.float32 and 0.002 < bias.std() < 0.02
    # a model of conv layers alone has no attention group, on both sides
    only = arch_of(layer_types=["conv"] * 5)
    assert not [k for k in weights.shapes(only) if k.startswith("gated/")]
    assert set(weights.shapes(only)) \
        == set(hybrid.param_shapes(config(only)))


@pytest.mark.parametrize("types,dense,held,remat,rows,leaves_too", [
    (CUT, 1, (0, EXPERTS), True, 16, True),
    (PUBLISHED, 2, (8, 8), False, 0, False)],
    ids=["the_cut", "published_24_layers_a_share_held"])
def test_loss_and_every_gradient_leaf_against_the_reference(
        types, dense, held, remat, rows, leaves_too):
    """The whole model at the cell's pattern with every expert held and
    each layer recomputed: the loss and every leaf's gradient.  At the
    published 24 layers (attention at 2, 6, 10, 14, 18, 21; two leading
    dense layers) with a share held (the reference is given the same
    share): the pattern and the two kinds of FFN on the configuration,
    the routing's and the weights' shapes from ``jax.eval_shape`` of
    the 24 layers, and the loss against the reference's through the
    first eight (both dense layers, attention at 2 and 6, six expert
    layers): the pattern adds no kind of leaf to the cut's, and 24
    unrolled layers compiled twice was most of this file's time.
    Every norm's weight away from one."""
    arch = arch_of(*held, layer_types=types, num_hidden_layers=len(types),
                   num_dense_layers=dense)
    assert [i for i, k in enumerate(arch["layer_kinds"]) if k == "gated"] \
        == ([1] if types is CUT else [2, 6, 10, 14, 18, 21])
    toks = tokens()
    cfg = config(arch, remat=remat, loss_row_block=rows)
    assert cfg.ffn_kinds == ("dense",) * dense + ("moe",) * (len(types)
                                                             - dense)
    if not leaves_too:
        shapes = jax.eval_shape(lambda: weights.make_params(arch, 0))
        assert (shapes["gated"]["wq"].shape[0],
                shapes["moe"]["w_gate"].shape[:2]) == (6, (22, 8))
        loss, routing = jax.eval_shape(
            lambda p: hybrid.loss_and_routing(p, toks, cfg), shapes)
        assert loss.shape == () and routing["choices"].shape \
            == (22, SEQ, TOP_K)
        types = types[:8]
        arch = arch_of(*held, layer_types=types, num_hidden_layers=8,
                       num_dense_layers=dense)
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
    assert routing["choices"].shape == (len(types) - dense, SEQ, TOP_K)
    if not leaves_too:
        return
    got, want = leaves(got[1]), leaves(want[1])
    assert set(got) == set(want)
    scale = np.median([float(jnp.abs(w).max()) for w in want.values()])
    for name, w in want.items():
        close(got[name], w, rtol=5e-4,
              atol=5e-5 * max(scale, float(jnp.abs(w).max())),
              err_msg=name)
        if "router_bias" not in name:       # the bias has no gradient
            assert np.asarray(w).any(), name


def test_tied_heads_gradient_reaches_the_table_from_both_ends():
    """The table's gradient is the sum of what reaches it as the head
    and as the embedding: the same model with an untied copy of the
    table as its head gives the two parts."""
    arch = arch_of(layer_types=CUT[:2], num_hidden_layers=2)
    params, toks = weights.make_params(arch, 4), tokens()
    tied = config(arch)
    untied = hybrid.HybridConfig(**{**tied.__dict__, "tied_head": False})
    with jax.default_matmul_precision("highest"):
        g = jax.jit(jax.grad(
            lambda p: hybrid.loss_fn(p, toks, tied)))(params)
        two = jax.jit(jax.grad(lambda p: hybrid.loss_fn(
            p, toks, untied)))({**params, "head": params["embed"]})
    assert float(jnp.abs(two["embed"]).max()) > 1e-3 \
        and float(jnp.abs(two["head"]).max()) > 1e-3
    close(g["embed"], two["embed"] + two["head"])


def expert_layer_inputs(seed=7):
    arch = arch_of()
    p = moved(weights.make_params(arch, seed))
    fp = jax.tree.map(lambda a: a[0], p["moe"])
    y = jax.random.normal(jax.random.key(seed), (2 * SEQ, D))
    return arch, fp, y


@pytest.mark.parametrize("shares", [1, 2, 4])
def test_the_shares_routed_parts_add_up_to_the_uncut_layer(shares):
    """What each of the chips that share a layer computes of the routed
    experts, summed, is the reference's layer with every expert held:
    two shares of 16, four of 8, and the whole layer on one chip, the
    configuration's own cut.  No expert is shared, so nothing is counted
    twice."""
    arch, fp, y = expert_layer_inputs()
    n = EXPERTS // shares

    @jax.jit
    def parts(fp):
        outs, routed, past = [], 0, 0
        for first in range(0, EXPERTS, n):
            out, routing = moe.moe_held(
                y, fp["w_router"], *(fp[k][first:first + n] for k in
                                     ("w_gate", "w_up", "w_down")),
                TOP_K, held=(first, n), slots=2 * SEQ, scoring="sigmoid",
                bias=fp["router_bias"])
            outs.append(out)
            routed += routing["routed"]
            past += routing["past_bound"]
        return outs, routed, past

    with jax.default_matmul_precision("highest"):
        whole, idx = jax.jit(lambda fp: ref.expert_layer(
            y, fp, arch, F32))(fp)
        outs, routed, past = parts(fp)
    assert len(outs) == shares and int(past) == 0
    assert int(routed) == 2 * SEQ * TOP_K
    close(sum(outs), whole)
    if shares > 1:
        assert gap(outs[0], whole) > 1e-2
    # the selection bias moves the selection and is no part of a weight
    _, plain = jax.lax.top_k(jax.nn.sigmoid(y @ fp["w_router"]), TOP_K)
    assert (np.sort(np.asarray(idx)) != np.sort(np.asarray(plain))).any()


def test_a_load_past_the_bound_is_counted():
    _, fp, y = expert_layer_inputs()
    slots = 8

    def held(slots):
        return moe.moe_held(
            y, fp["w_router"], fp["w_gate"], fp["w_up"], fp["w_down"],
            TOP_K, held=(0, EXPERTS), slots=slots, scoring="sigmoid",
            bias=fp["router_bias"])[1]
    tight, loose = jax.jit(held, static_argnums=0)(slots), held(2 * SEQ)
    assert int(loose["past_bound"]) == 0 < int(tight["past_bound"])
    assert int(tight["max_load"]) == int(loose["max_load"]) > slots
    assert int(tight["routed"]) == 2 * SEQ * TOP_K


def test_card_states_the_layers_and_the_config_follows_it():
    from dlnetbench_tpu.core.model_card import load_model_card
    card = load_model_card("lfm2_8b_a1b")
    assert card.num_params() == pytest.approx(8.34e9, rel=1e-3)
    cfg = hybrid.HybridConfig.from_card(card, seq_len=128, moe_slots=64)
    kinds = tuple(weights.KIND_OF[t] for t in PUBLISHED)
    assert cfg.layer_kinds == kinds and kinds.count("gated") == 6
    assert cfg.ffn_kinds == ("dense",) * 2 + ("moe",) * 22
    assert (cfg.router_scoring, cfg.top_k, cfg.num_experts,
            cfg.expert_ff_dim, cfg.shared_ff_dim, cfg.routed_scale,
            cfg.held_experts) == ("sigmoid", 4, 32, 1792, 0, 1.0, (0, 32))
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.rope_dim,
            cfg.attn_gate, cfg.short_conv) == (32, 8, 64, 0, False, 3)
    assert cfg.rms_norm and not cfg.norm_plus_one and cfg.tied_head
    assert cfg.norm_eps == 1e-5 and cfg.rope_theta == 1e6
    assert cfg.ff_dim == 7168 and cfg.vocab_size == 65536
