"""The gated delta rule (``ops/gated_delta_rule.py``) against its token
recurrence, and the linear-attention expert decoder (``models/hybrid.py``
with ``gdn`` and ``gated`` layers and an expert FFN behind a gated shared
expert) against the benchmark's plain reference
(``benchmarks/reference_linear_moe.py``) at a small size in float32 on
the CPU: the whole model with every expert held and with a share held,
the eight shares adding up to the uncut layer, the norm, the rotary
lanes, the gate lanes, the bound."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_linear_moe as ref
from benchmarks import weights_linear_moe as weights
from benchmarks.runners import train_linear_moe
from dlnetbench_tpu.models import hybrid, moe
from dlnetbench_tpu.models import layers as L
from dlnetbench_tpu.ops import gated_delta_rule as gdr

EXPERTS, TOP_K, SEQ = 16, 3, 48
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts_per_tok": TOP_K,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "full_attention_interval": 2,
    "vocab_size": 256, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
    "mlp_only_layers": [], "decoder_sparse_step": 1,
    "norm_topk_prob": True, "rope_scaling": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "hidden_act": "silu", "torch_dtype": "float32"}


def arch_of(first: int, held: int, **over) -> dict:
    return weights.arch_of({
        **CONFIG, "num_experts": held,
        "published": {"num_experts": EXPERTS},
        "assumed": {"first_held_expert": first, "decay_max": 16.0},
        **over})


def config(arch: dict, slots: int = SEQ, seq: int = SEQ, **over):
    return train_linear_moe.config_of(arch, seq, slots, **over)


def tokens(seq: int = SEQ):
    return jax.random.randint(jax.random.key(1), (1, seq + 1), 0, 256)


def moved(params, seed=9):
    """The seeded weights with every zero-centred norm's ``w`` drawn
    away from 0, so that ``1 + w`` is not ``w``'s own function."""
    keys = iter(jax.random.split(jax.random.key(seed), 8))

    def draw(a):
        return 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
    out = jax.tree.map(lambda a: a, params)
    out["final_norm"] = draw(params["final_norm"])
    for g, k in (("block", "norm1"), ("block", "norm2"),
                 ("gated", "q_norm"), ("gated", "k_norm")):
        out[g][k] = draw(params[g][k])
    out["gdn"]["o_norm"] = 1.0 + draw(params["gdn"]["o_norm"])
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


# ------------------------------------------------------------ the rule
def rule_inputs(seed=0, b=1, t=100, h=3, dk=16, dv=16):
    """A head that decays by exp(-20) to exp(-60) a token (its gates sum
    to -2500 over a chunk: ``exp(-c)`` overflows float32) beside one
    that decays by exp(-0.01), at a length that is no multiple of the
    chunk."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q, k = (jax.random.normal(kk, (b, t, h, dk)) for kk in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    rate = jnp.resize(jnp.array([40.0, 0.01, 1.0, 0.3]), h)
    g = -rate * (0.5 + jax.random.uniform(ks[3], (b, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (b, t, h, dv))


@pytest.fixture(scope="module")
def recurrence():
    """The token recurrence's output and five gradients, once."""
    args, ct = rule_inputs()
    out, vjp = jax.vjp(gdr.reference_rule, *args)
    return args, ct, out, vjp(ct)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_rule_forward_and_all_five_gradients_against_the_recurrence(
        recurrence, impl):
    """Both forms of the sweep, fast- and slow-decaying heads in one
    call; only differences of gates that are <= 0 are exponentiated, so
    everything is finite where ``exp(-c)`` is not."""
    args, ct, want, g_want = recurrence
    g = args[3]
    assert float(g[..., 0].max()) <= -20 and float(g[..., 1].min()) > -0.02
    assert float(g[0, :64, 0].sum()) < -1280      # exp(1280) overflows
    got, vjp = jax.vjp(lambda *a: gdr.gated_delta_rule(*a, impl, 64),
                       *args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for name, a, w in zip("q k v g beta".split(), vjp(ct), g_want):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(w), rtol=1e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)
    # a head that decays by exp(-20) a token holds its own token only
    q, k, v, _, beta = args
    own = beta[..., 0, None] * v[..., 0, :] * jnp.sum(
        k[..., 0, :] * q[..., 0, :], -1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got[..., 0, :]),
                               np.asarray(own), atol=1e-6)


def test_rule_keeps_its_given_dtype_and_refuses_an_unknown_impl():
    (q, k, v, g, beta), _ = rule_inputs(1, t=64, h=2)
    half = tuple(a.astype(jnp.bfloat16) for a in (q, k, v))
    out = gdr.gated_delta_rule(*half, g, beta)
    assert out.dtype == jnp.bfloat16 and out.shape == v.shape
    want = gdr.reference_rule(*half, g, beta).astype(jnp.float32)
    assert float(jnp.abs(out.astype(jnp.float32) - want).max()) < 0.05
    with pytest.raises(ValueError, match="unknown gated_delta_rule impl"):
        gdr.gated_delta_rule(q, k, v, g, beta, "triton")


@pytest.mark.parametrize("c,atol", [
    (8, 1e-6), (16, 2e-6), (32, 1e-5), (64, 2e-5), (128, 2e-4)])
def test_inverse_of_a_whole_tile_is_the_inverse(c, atol):
    """``_inv_unit_lower`` on whole tiles (diagonal blocks of 16 as a
    product of powers, then the blocks' own product), every product in
    float32: times ``I + a`` it is ``I``, and it is what a triangular
    solve gives."""
    a = jnp.tril(jax.random.normal(jax.random.key(0), (c, c)), -1) * 0.2
    x = gdr._inv_unit_lower(a)
    np.testing.assert_allclose(np.asarray(x @ (jnp.eye(c) + a)),
                               np.eye(c), atol=atol)
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(jax.scipy.linalg.solve_triangular(
            jnp.eye(c) + a, jnp.eye(c), lower=True, unit_diagonal=True)),
        atol=atol)


def test_float32_products_of_a_chunk_are_float32_whatever_the_inputs():
    """The inverse's products and ``dA = -X^T dX X^T`` run at
    ``highest`` for every dtype of the inputs (bfloat16 inputs round
    ``T`` and the operands, not ``X``): no ``dot_general`` between
    float32 operands in either function of a chunk runs at the
    default precision, which on the MXU is one bfloat16 pass."""
    (q, k, v, g, beta), ct = rule_inputs(2, t=64, h=1)
    half = [a[0, :, 0].astype(jnp.bfloat16) for a in (q, k, v)]
    cum, bt = jnp.cumsum(g[0, :, 0])[:, None], beta[0, :, 0][:, None]
    small = (cum, cum.T, bt, bt.T)
    s = jnp.zeros((16, 16))
    fwd = jax.make_jaxpr(gdr._chunk_fwd)(*half, *small, s)
    x = jnp.eye(64)
    bwd = jax.make_jaxpr(gdr._chunk_bwd)(
        *half, *small, x, s.astype(jnp.bfloat16),
        ct[0, :, 0].astype(jnp.bfloat16), s)
    for jaxpr, products in ((fwd, 10), (bwd, 2)):
        dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
        exact = [e for e in dots
                 if all(v.aval.dtype == jnp.float32 for v in e.invars)]
        assert len(exact) == products < len(dots)
        assert all(e.params["precision"] is not None and "HIGHEST" in str(
            e.params["precision"]) for e in exact)


def rule_grads(args, ct, *rest):
    got, vjp = jax.vjp(lambda *a: gdr.gated_delta_rule(*a, *rest), *args)
    return (got, *vjp(ct))


@pytest.fixture(scope="module")
def recurrence_8():
    """Eight heads of 64 lanes (two of them fast, two slow), 150
    tokens: the token recurrence's output and gradients."""
    args, ct = rule_inputs(4, t=150, h=8, dk=64, dv=64)
    out, vjp = jax.vjp(gdr.reference_rule, *args)
    return args, ct, (out, *vjp(ct))


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_head_groups_of_a_grid_step_do_not_meet(recurrence_8, groups,
                                                 monkeypatch):
    """Eight heads as one group, as two and as four grid rows of the
    same kernels (the budget a step may fill is the test's to set; a
    group fills whole 128-lane tiles, so two heads of 64 lanes are the
    fewest): the recurrence's output and gradients each time."""
    args, ct, want = recurrence_8
    t, h, dk = args[0].shape[1:]
    for budget in range(1 << 16, 1 << 25, 1 << 16):
        monkeypatch.setattr(gdr, "_VMEM_BUDGET", budget)
        if gdr.tile_plan(t, h, dk, dk, 4)[1] == h // groups:
            break
    assert gdr.tile_plan(t, h, dk, dk, 4) == (128, h // groups)
    for name, a, w in zip("o q k v g beta".split(),
                          rule_grads(args, ct, "pallas"), want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(w), rtol=1e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


@pytest.fixture(scope="module")
def recurrence_4():
    """Four heads (the fast one, the slow one, two between), 150
    tokens: the token recurrence's output and gradients."""
    args, ct = rule_inputs(3, t=150, h=4)
    out, vjp = jax.vjp(gdr.reference_rule, *args)
    return args, ct, (out, *vjp(ct))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunks_of_64_and_of_128_give_the_same_gradients(recurrence_4,
                                                          impl):
    """The chunk is where the state is kept and how large ``I + A`` is,
    not what is computed: 150 tokens as three chunks of 64 and as two of
    128, and as the shapes' own choice, which is what a call that names
    none gets."""
    args, ct, want = recurrence_4
    by_64, by_128, own = (rule_grads(args, ct, impl, c)
                          for c in (64, 128, None))
    assert gdr.tile_plan(*args[0].shape[1:], 16, 4)[0] == 128
    for name, a, b, c, w in zip("o q k v g beta".split(), by_64, by_128,
                                own, want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=name)
        np.testing.assert_allclose(np.asarray(b), np.asarray(w), rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=name)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(b), name)


@pytest.mark.parametrize("shape,itemsize,want", [
    ((16384, 32, 128, 128), 2, (128, 8)),   # the linear-attention cell
    ((16384, 32, 128, 128), 4, (128, 4)),   # the same in float32
    ((80, 2, 16, 16), 4, (64, 2)),          # the model test's
    ((48, 4, 16, 16), 4, (64, 4)),          # short of one chunk
    ((4096, 6, 128, 128), 2, (128, 6)),     # heads no power of two
    ((4096, 14, 128, 128), 2, (128, 7)),
    ((16384, 32, 64, 64), 2, (128, 8)),     # heads of half a lane tile
    ((16384, 32, 64, 128), 4, (128, 8)),
    ((16384, 24, 16, 16), 4, (128, 8)),     # eight heads fill a tile
    ((16384, 6, 64, 64), 4, (128, 6)),
    ((16384, 7, 16, 16), 4, (128, 7)),      # no divisor does: all heads
])
def test_tiles_are_a_function_of_the_shapes(shape, itemsize, want):
    """``(C, hb)``: the largest chunk the sequence fills, the most
    heads whose step fits the budget, always a divisor of the heads
    and, short of all of them, whole 128-lane tiles of a token block
    (the TPU lowering takes no other last dimension, and interpret
    mode would not say); nothing but the shapes is read."""
    assert gdr.tile_plan(*shape, itemsize) == want
    t, h, dk, dv = shape
    assert h % want[1] == 0
    assert want[1] == h or want[1] * dk % 128 == 0 == want[1] * dv % 128


# ----------------------------------------------------------- the model
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
    rate = np.exp(np.asarray(made["gdn"]["a_log"]))
    assert 0 < rate.min() and rate.max() < 16 and rate.std() > 1
    assert not np.asarray(made["block"]["norm1"]).any()
    assert weights.layer_kinds({**CONFIG, "num_hidden_layers": 8,
                                "full_attention_interval": 4}) \
        == ("gdn", "gdn", "gdn", "gated") * 2


@pytest.mark.parametrize("first,held,remat,rows,impl,seq", [
    (0, EXPERTS, False, 0, "xla", SEQ), (2, 2, True, 16, "pallas", 80)])
def test_loss_and_every_gradient_leaf_against_the_reference(
        first, held, remat, rows, impl, seq):
    """The whole model with every expert held, and with a share held
    (the reference is given the same share), with every norm's ``w``
    away from zero, at a sequence short of one chunk of the rule and
    at one of a chunk and a quarter."""
    arch = arch_of(first, held)
    params, toks = moved(weights.make_params(arch, 2**31 + 5)), tokens(seq)
    cfg = config(arch, slots=seq, seq=seq, remat=remat,
                 loss_row_block=rows, rule_impl=impl)
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
            np.asarray(got[name]), np.asarray(w), rtol=5e-4,
            atol=5e-5 * max(scale, float(jnp.abs(w).max())),
            err_msg=name)
        assert np.asarray(w).any(), name        # no leaf is left out
    assert int(routing["past_bound"]) == 0
    assert routing["choices"].shape == (2, seq, TOP_K)


def test_norm_scales_by_one_plus_w():
    cfg = config(arch_of(0, EXPERTS))
    x = jax.random.normal(jax.random.key(0), (2, 5, 64))
    w = {"n": 0.3 * jax.random.normal(jax.random.key(1), (64,))}
    np.testing.assert_allclose(
        np.asarray(hybrid._norm(cfg, x, w, "n")),
        np.asarray(ref.norm0(x, w["n"], 1e-6)), rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(hybrid._norm(cfg, x, w, "n")
                         - ref.rmsnorm(x, w["n"], 1e-6)).max()) > 0.5


def gated_layer(seed=6):
    arch = arch_of(0, EXPERTS)
    p = jax.tree.map(lambda a: a[0],
                     moved(weights.make_params(arch, seed))["gated"])
    y = jax.random.normal(jax.random.key(2), (1, SEQ, 64))
    cfg = config(arch, attention_impl="xla")
    mixer = jax.jit(hybrid.gated_mixer, static_argnums=0)

    def want(p):
        with jax.default_matmul_precision("highest"):
            return ref.gated_attention(y, p, arch, ref.MATMULS["float32"])
    return cfg, p, y, mixer, jax.jit(want)


def test_only_the_first_quarter_of_a_heads_lanes_is_rotated():
    """With all 32 lanes rotated the layer is another model: this fails
    if ``rope_dim`` is ignored."""
    cfg, p, y, mixer, want = gated_layer()
    assert cfg.rope_dim == 8 and cfg.head_dim == 32
    np.testing.assert_allclose(np.asarray(mixer(cfg, y, p)),
                               np.asarray(want(p)), rtol=1e-4, atol=1e-5)
    every = hybrid.HybridConfig(**{**cfg.__dict__, "rope_dim": 32})
    assert float(jnp.abs(mixer(every, y, p) - want(p)).max()) > 1e-3


def test_gate_lanes_of_the_query_projection_gate_the_output():
    """Each head's second 32 lanes of ``W_q`` are its gate: with them
    zeroed every head passes at ``sigmoid(0)``, another output, and the
    reference agrees on both."""
    cfg, p, y, mixer, want = gated_layer()
    wq = p["wq"].reshape(64, 4, 2, 32)
    level = {**p, "wq": wq.at[:, :, 1].set(0.0).reshape(64, 256)}
    np.testing.assert_allclose(np.asarray(mixer(cfg, y, level)),
                               np.asarray(want(level)), rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.abs(mixer(cfg, y, level)
                         - mixer(cfg, y, p)).max()) > 1e-3
    # the query lanes zeroed instead: attention is uniform over the
    # past, the gate still acts
    flat = {**p, "wq": wq.at[:, :, 0].set(0.0).reshape(64, 256)}
    np.testing.assert_allclose(np.asarray(mixer(cfg, y, flat)),
                               np.asarray(want(flat)), rtol=1e-4,
                               atol=1e-5)


def expert_layer_inputs(seed=7):
    arch = arch_of(0, EXPERTS)
    p = weights.make_params(arch, seed)
    fp = jax.tree.map(lambda a: a[0], p["moe"])
    y = jax.random.normal(jax.random.key(seed), (2 * SEQ, 64))
    return arch, fp, y


def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """What the eight chips that share a layer each compute of the
    routed experts, summed, plus the gated shared expert counted once,
    is the reference's layer with every expert held."""
    arch, fp, y = expert_layer_inputs()
    cfg = config(arch_of(0, 2), slots=2 * SEQ)
    norm = {"norm2": jnp.zeros((64,))}

    @jax.jit
    def shares(fp):
        """Each share's routed part of the layer, the rows routed in
        all and the rows past the bound."""
        outs, routed, past = [], 0, 0
        for first in range(0, EXPERTS, 2):
            out, routing = moe.moe_held(
                y, fp["w_router"], *(fp[k][first:first + 2] for k in
                                     ("w_gate", "w_up", "w_down")),
                TOP_K, held=(first, 2), slots=2 * SEQ, scoring="softmax")
            outs.append(out)
            routed += routing["routed"]
            past += routing["past_bound"]
        return outs, routed, past

    with jax.default_matmul_precision("highest"):
        whole, _ = jax.jit(lambda fp: ref.expert_layer(
            y, fp, arch, ref.MATMULS["float32"]))(fp)
        parts, routed, past = shares(fp)
        plain = L.swiglu(y, fp["ws_gate"], fp["ws_up"], fp["ws_down"])
        shared = plain * jax.nn.sigmoid(y @ fp["ws_sig"])[:, None]
        # the program's own layer on the first chip, x = 0 + y unnormed
        unit = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6)
        first = {k: (a[:2] if k in ("w_gate", "w_up", "w_down") else a)
                 for k, a in fp.items()}
        x = y[None]
        layer, _ = jax.jit(lambda x, fp: hybrid.expert_ffn(
            cfg, x, norm, fp))(x, first)
        w_unit, _ = jax.jit(lambda fp: ref.expert_layer(
            unit, fp, {**arch, "held": (0, 2)},
            ref.MATMULS["float32"]))(first)
    assert len(parts) == 8 and int(routed) == 2 * SEQ * TOP_K
    assert int(past) == 0
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(parts[0] + shared - whole).max()) > 1e-2
    # ungated, the shared expert is another layer
    assert float(jnp.abs(sum(parts) + plain - whole).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(layer[0] - y),
                               np.asarray(w_unit), rtol=1e-4, atol=1e-5)


def test_top10_of_512_renormalised_is_the_softmax_over_the_selected():
    """``norm_topk_prob``: softmax over all the router's logits, the
    top-k of it divided by their sum, is the softmax over the k selected
    logits, which the shared router computes (Mixtral's gate)."""
    _, fp, y = expert_layer_inputs()
    w, idx = L.moe_router(y, fp["w_router"], TOP_K)
    full = jax.nn.softmax(y @ fp["w_router"], -1)
    top, want_idx = jax.lax.top_k(full, TOP_K)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(top / top.sum(-1, keepdims=True)),
        rtol=1e-5)


@pytest.mark.parametrize("scoring,k", [("softmax", 2), ("sigmoid", 3)])
def test_routing_through_the_shared_functions_gives_the_plan_it_gave(
        scoring, k):
    """Mixtral's and Kimi's gates through the functions this model
    shares with them: with every expert held and the bound at the
    capacity the plan is the capacity dispatch's, and a load past the
    bound is counted."""
    _, fp, y = expert_layer_inputs()
    kw = {} if scoring == "softmax" else {
        "bias": jnp.linspace(-0.1, 0.1, EXPERTS), "scale": 2.446}
    w, idx = L.moe_router(y, fp["w_router"], k, scoring=scoring, **kw)
    if scoring == "softmax":
        xe, plan, gate = jax.jit(lambda y: L.moe_dispatch(
            y, fp["w_router"], EXPERTS, k, 1.25))(y)
        xh, plan_h, gate_h, _ = jax.jit(lambda y: L.moe_dispatch_held(
            y, w, idx, (0, EXPERTS), xe.shape[1]))(y)
        for a, b in zip((xe, gate, *plan), (xh, gate_h, *plan_h)):
            assert (np.asarray(a) == np.asarray(b)).all()
    load = jax.jit(lambda y: L.moe_dispatch_held(
        y, w, idx, (0, EXPERTS), 2 * SEQ)[3])(y)
    assert int(load.sum()) == 2 * SEQ * k
    tight = int(load.max()) - 3
    _, routing = jax.jit(lambda y: moe.moe_held(
        y, fp["w_router"], fp["w_gate"], fp["w_up"], fp["w_down"], k,
        held=(0, EXPERTS), slots=tight, scoring=scoring, **kw))(y)
    assert int(routing["past_bound"]) >= 3
    assert int(routing["max_load"]) == int(load.max())


def test_card_states_the_layers_and_the_config_follows_it():
    from dlnetbench_tpu.core.model_card import load_model_card
    card = load_model_card("qwen3_next_80b_a3b")
    assert card.num_params() == pytest.approx(79.7e9, rel=2e-3)
    cfg = hybrid.HybridConfig.from_card(card, seq_len=128, moe_slots=64,
                                        held_experts=(0, 64))
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "gated") * 12
    assert cfg.ffn_kinds == ("moe",) * 48
    assert (cfg.router_scoring, cfg.top_k, cfg.num_experts,
            cfg.expert_ff_dim, cfg.shared_ff_dim, cfg.shared_gate) \
        == ("softmax", 10, 512, 512, 512, True)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.rope_dim) \
        == (16, 2, 256, 64)
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.gdn_conv) == (16, 32, 128, 128, 4)
    assert cfg.rms_norm and cfg.norm_plus_one and not cfg.tied_head
    assert cfg.norm_eps == 1e-6 and cfg.rope_theta == 1e7
    shapes = hybrid.param_shapes(cfg)
    assert shapes["gdn/w_qkvz"][0] == (36, 2048, 12288)
    assert shapes["gated/wq"][0] == (12, 2048, 8192)
    with pytest.raises(ValueError, match="gdn layers need"):
        hybrid.HybridConfig(**{**cfg.__dict__, "gdn_key_heads": 5})
