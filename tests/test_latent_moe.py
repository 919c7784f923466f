"""The latent-attention expert decoder (``models/hybrid.py`` with ``mla``
layers and expert FFNs, ``layers.moe_router`` / ``moe_dispatch_held``,
``moe.moe_held``) against the benchmark's plain reference
(``benchmarks/reference_latent_moe.py``) at a small size in float32 on
the CPU: the whole model with every expert held and with a share held,
the shares adding up to the uncut layer, the gate, the bound."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_latent_moe as ref
from benchmarks import weights_latent_moe as weights
from benchmarks.runners import train_latent_moe
from dlnetbench_tpu.models import bench_step, hybrid, moe
from dlnetbench_tpu.models import layers as L

EXPERTS, TOP_K, SEQ = 8, 3, 64
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_shared_experts": 2,
    "num_experts_per_tok": TOP_K, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "vocab_size": 256, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
    "rope_theta": 800000, "q_lora_rank": None, "n_group": 1,
    "topk_group": 1, "rope_scaling": None, "moe_layer_freq": 1,
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "tie_word_embeddings": False, "attention_bias": False,
    "torch_dtype": "float32"}


def arch_of(first: int, held: int, **over) -> dict:
    return weights.arch_of({
        **CONFIG, "n_routed_experts": held,
        "published": {"n_routed_experts": EXPERTS},
        "assumed": {"first_held_expert": first,
                    "router_bias_scale": 0.1}, **over})


def config(arch: dict, slots: int = 2 * SEQ, **over):
    return train_latent_moe.config_of(arch, SEQ, slots, **over)


def tokens():
    return jax.random.randint(jax.random.key(1), (2, SEQ + 1), 0, 256)


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


def test_benchmark_weights_follow_the_programs_layout():
    arch = arch_of(2, 3)
    assert {k: shape for k, (shape, _) in weights.shapes(arch).items()} \
        == {k: shape for k, (shape, _)
            in hybrid.param_shapes(config(arch)).items()}
    made = weights.make_params(arch, 3)
    own = jax.eval_shape(
        lambda: hybrid.init_params(jax.random.key(3), config(arch)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), made) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), own)
    assert float(jnp.abs(made["moe"]["router_bias"]).max()) > 0


@pytest.mark.parametrize("first,held,remat,rows", [
    (0, EXPERTS, False, 0), (2, 3, True, 32), (6, 2, False, 32)])
def test_loss_and_every_gradient_leaf_against_the_reference(
        first, held, remat, rows):
    """The whole model with every expert held, and with a share held:
    the reference is given the same share."""
    arch = arch_of(first, held)
    params, toks = weights.make_params(arch, 2**31 + 5), tokens()
    cfg = config(arch, remat=remat, loss_row_block=rows)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_fn(p, toks, arch)))(params)
        (loss, routing), grad = jax.jit(jax.value_and_grad(
            lambda p: hybrid.loss_and_routing(p, toks, cfg),
            has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    got, want = leaves(grad), leaves(want)
    assert set(got) == set(want)
    scale = np.median([float(jnp.abs(w).max()) for w in want.values()])
    for name, w in want.items():
        np.testing.assert_allclose(
            np.asarray(got[name]), np.asarray(w), rtol=2e-4,
            atol=2e-5 * max(scale, float(jnp.abs(w).max())),
            err_msg=name)
    # the selection bias steers and is not trained
    assert not np.asarray(got["moe/router_bias/0"]).any()
    assert int(routing["past_bound"]) == 0
    assert routing["choices"].shape == (2, 2 * SEQ, TOP_K)


def test_train_step_returns_the_routing_beside_the_loss():
    arch = arch_of(0, 4)
    params = weights.make_params(arch, 5)
    step = jax.jit(bench_step.make_train_k(config(arch), 2, 0.05))
    new, (losses, routing) = step(params, tokens())
    assert losses.shape == (2,) and losses[1] < losses[0]
    assert set(routing) == set(hybrid.ROUTING)
    for k in hybrid.COUNTERS:
        assert routing[k].shape == (2,)
    # two expert layers; about half of T * k choices land on 4 of 8
    assert 0.3 < int(routing["routed"][0]) / (2 * 2 * SEQ * TOP_K) < 0.7
    assert jax.tree.structure(new) == jax.tree.structure(params)


def expert_layer_inputs(seed=7):
    arch = arch_of(0, EXPERTS)
    p = weights.make_params(arch, seed)
    fp = jax.tree.map(lambda a: a[0], p["moe"])
    y = jax.random.normal(jax.random.key(seed), (2 * SEQ, 64))
    return arch, fp, y


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """What the chips that share a layer each compute of the routed
    experts, summed, plus the shared expert counted once, is the
    reference's layer with every expert held."""
    arch, fp, y = expert_layer_inputs()
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.expert_layer(y, fp, arch, ref.MATMULS["float32"])
        shared = L.swiglu(y, fp["ws_gate"], fp["ws_up"], fp["ws_down"])
        parts, routed = [], 0
        for first in range(0, EXPERTS, 2):
            held = slice(first, first + 2)
            out, routing = moe.moe_held(
                y, fp["w_router"], fp["w_gate"][held], fp["w_up"][held],
                fp["w_down"][held], TOP_K, held=(first, 2), slots=2 * SEQ,
                scoring="sigmoid", bias=fp["router_bias"], scale=2.446)
            parts.append(out)
            routed += int(routing["routed"])
            assert int(routing["past_bound"]) == 0
    assert routed == 2 * SEQ * TOP_K           # every choice, once
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    # a share alone is not the layer
    assert float(jnp.abs(parts[0] + shared - whole).max()) > 1e-2


def test_gate_weights_leave_the_bias_out_and_the_bias_moves_the_choice():
    _, fp, y = expert_layer_inputs()
    w, idx = L.moe_router(y, fp["w_router"], TOP_K, scoring="sigmoid",
                          bias=fp["router_bias"], scale=2.446)
    s = jax.nn.sigmoid(y @ fp["w_router"])
    at = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(2.446 * at / at.sum(-1, keepdims=True)),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.446, rtol=1e-6)
    # selection is the top-k of s + b ...
    want = jax.lax.top_k(s + fp["router_bias"], TOP_K)[1]
    assert (np.sort(idx, -1) == np.sort(want, -1)).all()
    # ... and with this seeded b it is another one than without b for
    # a share of the tokens that a zero b would leave at nothing
    _, plain = L.moe_router(y, fp["w_router"], TOP_K, scoring="sigmoid",
                            scale=2.446)
    moved = (np.sort(idx, -1) != np.sort(plain, -1)).any(-1).mean()
    assert 0.2 < moved < 0.9
    # the bias has no gradient, the router's weights have
    g_b, g_w = jax.grad(
        lambda b, wr: jnp.sum(L.moe_router(
            y, wr, TOP_K, scoring="sigmoid", bias=b, scale=2.446)[0]
            * jnp.arange(TOP_K)), argnums=(0, 1))(
        fp["router_bias"], fp["w_router"])
    assert not np.asarray(g_b).any() and np.asarray(g_w).any()


def test_softmax_gate_through_the_shared_router_is_the_plan_it_gave():
    """Mixtral's gate: the shared router's default is the old spelling
    bit for bit, and the capacity dispatch built on it gives the plan
    that the dispatch over held experts gives when every expert is held
    and the bound is the capacity."""
    _, fp, y = expert_layer_inputs()
    w, idx = L.moe_router(y, fp["w_router"], 2)
    logits = y.astype(jnp.float32) @ fp["w_router"].astype(jnp.float32)
    top, want_idx = jax.lax.top_k(logits, 2)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    assert (np.asarray(w) == np.asarray(jax.nn.softmax(top, -1))).all()
    xe, plan, gate = L.moe_dispatch(y, fp["w_router"], EXPERTS, 2, 1.25)
    cap = xe.shape[1]
    xh, plan_h, gate_h, load = L.moe_dispatch_held(y, w, idx,
                                                   (0, EXPERTS), cap)
    for a, b in zip(plan, plan_h):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert (np.asarray(xe) == np.asarray(xh)).all()
    assert (np.asarray(gate) == np.asarray(gate_h)).all()
    assert int(load.sum()) == 2 * SEQ * 2


def test_rows_past_the_bound_are_counted_never_silently_dropped():
    _, fp, y = expert_layer_inputs()
    held = slice(0, 4)
    kw = dict(held=(0, 4), scoring="sigmoid", bias=fp["router_bias"],
              scale=2.446)
    args = (y, fp["w_router"], fp["w_gate"][held], fp["w_up"][held],
            fp["w_down"][held], TOP_K)
    roomy, r0 = moe.moe_held(*args, slots=2 * SEQ, **kw)
    load = int(r0["max_load"])
    exact, r1 = moe.moe_held(*args, slots=load, **kw)
    tight, r2 = moe.moe_held(*args, slots=load - 5, **kw)
    assert int(r0["past_bound"]) == int(r1["past_bound"]) == 0
    assert int(r2["past_bound"]) >= 5
    assert int(r2["routed"]) == int(r0["routed"])   # counted all the same
    np.testing.assert_allclose(np.asarray(exact), np.asarray(roomy),
                               rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(tight - roomy).max()) > 1e-3


def test_card_states_the_gate_and_the_config_follows_it():
    from dlnetbench_tpu.core.model_card import load_model_card
    card = load_model_card("kimi_vl_a3b")
    assert card.num_params() == pytest.approx(15.96e9, rel=1e-3)
    cfg = hybrid.HybridConfig.from_card(card, seq_len=128, moe_slots=64)
    assert cfg.layer_kinds == ("mla",) * 27
    assert cfg.ffn_kinds == ("dense",) + ("moe",) * 26
    assert (cfg.router_scoring, cfg.routed_scale, cfg.top_k,
            cfg.num_experts) == ("sigmoid", 2.446, 6, 64)
    assert (cfg.expert_ff_dim, cfg.shared_ff_dim, cfg.ff_dim) \
        == (1408, 2816, 11264)
    assert cfg.held_experts == (0, 64) and cfg.rms_norm
    assert not cfg.tied_head and cfg.norm_eps == 1e-5
    assert cfg.rope_theta == 800000.0
    with pytest.raises(ValueError, match="held_experts"):
        hybrid.HybridConfig.from_card(card, moe_slots=64,
                                      held_experts=(60, 8))
