"""What a checkpointed layer of ``models/hybrid.py`` keeps: its attention
kernel's output and lse (``ops.flash_attention.KEPT_NAMES``), the gated
delta rule's output, chunk states and chunk matrices
(``ops.gated_delta_rule.KEPT_NAMES``) and nothing else, so its
recomputation runs no forward kernel.  Small shapes in float32 on the
CPU, the kernels forced (interpret mode)."""
import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from dlnetbench_tpu.core import executor
from dlnetbench_tpu.metrics import spans
from dlnetbench_tpu.models import bench_step, hybrid
from dlnetbench_tpu.ops import gated_delta_rule as gdr

# the module: the package's ``flash_attention`` is its function
fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")

S = 128
SMALL = dict(vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2,
             ff_dim=64, seq_len=S, ssm_inner=64, ssm_state=8,
             ssm_dt_rank=4, attention_window=64, dtype="float32",
             attention_impl="flash")
LATENT = dict(kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16)
LINEAR = dict(gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
              gdn_value_dim=16, rule_impl="pallas")
# name: (layer kinds, what the configuration states beside SMALL, the
# forward kernel calls of each layer: the rule's sweep in a ``gdn``
# layer, attention's anywhere else)
KERNEL_CASES = {
    "window": (("window",), {}, (2,)),
    "full": (("full",), {}, (2,)),
    "cross": (("full", "cross"), {}, (2, 2)),
    "mla": (("mla",), LATENT, (1,)),
    # Kimi's widths: the scores' 192 lanes pad to two lane tiles, the
    # values, and so the kept output, are one
    "mla_wide_scores": (("mla",), {**LATENT, "qk_nope_head_dim": 128,
                                   "qk_rope_head_dim": 64,
                                   "v_head_dim": 128}, (1,)),
    "gated": (("gated",), {}, (1,)),
    "swa": (("swa",), {}, (1,)),
    "nope": (("nope",), {}, (1,)),
    # Laguna's: a gate a head, the window layers' heads their own
    "headgate": (("gated", "swa"),
                 {"attn_gate": "head", "window_heads": 8, "rope_dim": 4,
                  "rms_norm": True}, (1, 1)),
    # SmallThinker's: neither gate nor norm a head, no positions in full
    "swa_nope": (("swa", "nope"),
                 {"attn_gate": False, "head_norm": False,
                  "rms_norm": True}, (1, 1)),
    # beside layers that call no kernel
    "mixed": (("conv", "gated"), {}, (0, 1)),
    # the rule's two sweeps: the kernel pair, and a scan of chunks
    "gdn": (("gdn",), LINEAR, (1,)),
    "gdn_xla": (("gdn",), {**LINEAR, "rule_impl": "xla"}, (1,)),
    "gdn_mixed": (("conv", "gdn"), LINEAR, (0, 1)),
    # Qwen3-Next's: a rule's three beside an attention kernel's pair
    "gdn_gated": (("gdn", "gdn", "gated"), LINEAR, (1, 1, 1)),
}
NO_KERNEL_CASES = {
    "mamba": (("mamba",), {}),
    "gmu": (("mamba", "gmu"), {}),
    "conv": (("conv",), {}),
}
KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq", "gdr_fwd",
           "gdr_bwd")


def config(kinds, stated, **over):
    return hybrid.HybridConfig(**{**SMALL, **stated, "layer_kinds": kinds,
                                  **over})


def inputs(cfg):
    params = hybrid.init_params(jax.random.key(7), cfg)
    tokens = jax.random.randint(jax.random.key(8), (1, S + 1), 0,
                                cfg.vocab_size)
    return params, tokens


def loss_and_grads(cfg, tokens):
    return jax.value_and_grad(lambda p: hybrid.loss_fn(p, tokens, cfg))


def kernels(cfg, params, tokens) -> dict:
    """{kernel name: its calls, "scan": the scans} in the differentiated
    step's jaxpr."""
    text = str(jax.make_jaxpr(loss_and_grads(cfg, tokens))(params))
    return {**{name: len(re.findall(rf"name={name}\b", text))
               for name in KERNELS},
            "scan": len(re.findall(r"\bscan\[", text))}


def calls_of(name, forwards: int) -> dict:
    """What ``kernels`` gives for a case whose every kernel layer runs
    its forward ``forwards`` times and its backward once (the rule under
    ``"xla"`` is a scan either way, and no kernel)."""
    kinds, stated, calls = KERNEL_CASES[name]
    rule = sum(n for kind, n in zip(kinds, calls) if kind == "gdn")
    flash = sum(calls) - rule
    scans = stated.get("rule_impl") == "xla"
    return {"flash_fwd": forwards * flash, "flash_bwd_dkv": flash,
            "flash_bwd_dq": 0,
            "gdr_fwd": 0 if scans else forwards * rule,
            "gdr_bwd": 0 if scans else rule,
            "scan": (forwards + 1) * rule if scans else 0}


def residuals(cfg, params, tokens, capsys) -> list:
    """The lines of ``jax.ad_checkpoint.print_saved_residuals``: what the
    forward's backward keeps, each with the reason."""
    jax.ad_checkpoint.print_saved_residuals(
        lambda p: hybrid.forward(p, tokens[:, :-1], cfg).sum(), params)
    return capsys.readouterr().out.strip().splitlines()


def kept_marks(cfg, params, tokens, build=None) -> list:
    """The ``remat.kept`` marks of the train step, from the ``compile``
    span around its trace: ``build`` makes the span (the executor's
    whole build), else one is opened here around the lowering alone."""
    step = bench_step.make_train_k(cfg, 1, 0.05)
    tracer = spans.enable()
    try:
        if build is not None:
            build(step, (params, tokens))
        else:
            with spans.span("compile", fn="train_k"):
                jax.jit(step).lower(params, tokens)
    finally:
        spans.disable()
    made, = (s for s in tracer.export()["spans"] if s["name"] == "compile")
    return made["attrs"].get("remat.kept", [])


def compiled_text(fn, *args) -> str:
    """``fn``'s compiled text without its instructions' metadata (a
    name is an ``op_name`` and nothing else outside a checkpoint)."""
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return re.sub(r", metadata=\{[^}]*\}", "", hlo)


def keep_nothing(monkeypatch):
    """The checkpoint of before the names: a policy that saves none, so
    every layer is recomputed whole."""
    monkeypatch.setattr(hybrid, "_KEPT", ())


def same(a, b):
    eq = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), a, b)
    assert jax.tree.all(eq), eq


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_a_kept_layer_gives_the_floats_of_a_layer_recomputed_whole(
        name, monkeypatch):
    """Loss and every gradient leaf, bit for bit: the kept ``out`` and
    ``lse`` are the bytes the second forward call would have written.
    Without ``remat`` the program is another one (XLA fuses the layers'
    other passes differently, on the parent too), so that comparison is
    to a float32's rounding.

    A ``gdn`` layer is held to the same bits with XLA:CPU's fusion pass
    off, and to 2e-5 of a leaf's largest (7e-6 read, the same under
    either sweep) as the CPU compiles it by default.  That pass copies
    the mixer's cheap input passes (the short convolution, its SiLU, the
    unit length of ``q`` and ``k``) into the loop in front of each dot
    that reads them, and a copy's floats are a last bit from those of
    the chain read from a stored convolution (3e-8 of 0.21, one loop
    against two).  So each program makes ``q`` and ``k`` more than once:
    the kept ``X`` and ``o`` come from the first pass's copies, a whole
    recomputation's from its own, and the rule's inverse carries the bit
    to the gates' gradients.  The rule alone, its inputs stored once, is
    held to the bits as compiled by default
    (``test_under_a_checkpoint_the_kept_three_are_the_first_calls``)."""
    kinds, stated, _ = KERNEL_CASES[name]
    params, tokens = inputs(config(kinds, stated))

    def run(options=None, **over):
        cfg = config(kinds, stated, **over)
        step = jax.jit(loss_and_grads(cfg, tokens)).lower(params)
        return step.compile(compiler_options=options)(params)

    def close(a, b):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert float(jnp.max(jnp.abs(x - y))) \
                <= 2e-5 * float(jnp.max(jnp.abs(y))) + 1e-7
    unfused = {"xla_disable_hlo_passes": "fusion"} if "gdn" in kinds \
        else None
    kept, plain = run(remat=True), run(remat=False)
    kept_unfused = unfused and run(unfused, remat=True)
    keep_nothing(monkeypatch)
    whole = run(remat=True)
    assert float(kept[0]) > 0
    if unfused:
        same(kept_unfused, run(unfused, remat=True))
        for other in (whole, kept_unfused, plain):
            close(kept, other)
        return
    same(kept, whole)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(plain)):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_one_forward_kernel_a_call_where_the_recomputation_ran_a_second(
        name, monkeypatch):
    kinds, stated, _ = KERNEL_CASES[name]
    params, tokens = inputs(config(kinds, stated))
    cfg = config(kinds, stated, remat=True)
    assert kernels(cfg, params, tokens) == calls_of(name, 1)
    assert kernels(config(kinds, stated), params, tokens) \
        == calls_of(name, 1)
    keep_nothing(monkeypatch)
    assert kernels(cfg, params, tokens) == calls_of(name, 2)


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_the_compile_span_says_what_each_layer_kept(name):
    """``remat.kept`` once a saved value: the layer, its kind, the name
    and the bytes; a call's pair is its ``out`` in the model's dtype and
    the kernel's sublane-replicated float32 ``lse``, a rule's three its
    ``o`` and the state entering each chunk (one of ``S`` tokens here) in
    the model's dtype and each chunk's float32 ``X``."""
    kinds, stated, calls = KERNEL_CASES[name]
    cfg = config(kinds, stated, remat=True)
    marks = kept_marks(cfg, *inputs(cfg))
    want = []
    for li, (kind, n) in enumerate(zip(kinds, calls)):
        if kind == "gdn":
            hv, dk, dv = (cfg.gdn_value_heads, cfg.gdn_key_dim,
                          cfg.gdn_value_dim)
            want += [{"layer": li, "kind": kind, "value": v, "bytes": b}
                     for v, b in (("rule_out", S * hv * dv * 4),
                                  ("rule_states", hv * dk * dv * 4),
                                  ("rule_chunks", hv * S * S * 4))]
            continue
        # differential attention: a pair of heads over values twice as wide
        paired = kind in ("window", "full", "cross")
        heads = cfg.heads_of(kind) // (2 if paired else 1)
        width = cfg.v_head_dim if kind == "mla" \
            else cfg.head_dim * (2 if paired else 1)
        pair = [("attn_out", S * heads * width * 4),
                ("attn_lse", heads * 8 * S * 4)]
        want += [{"layer": li, "kind": kind, "value": v, "bytes": b}
                 for v, b in pair * n]
    assert marks == want


@pytest.mark.parametrize("name", sorted(NO_KERNEL_CASES))
def test_a_layer_without_a_kept_kernel_keeps_nothing(name, capsys):
    """No named value in the layer, so no mark, and the backward's
    residuals are the checkpoint's arguments alone."""
    kinds, stated = NO_KERNEL_CASES[name]
    cfg = config(kinds, stated, remat=True)
    params, tokens = inputs(cfg)
    assert kept_marks(cfg, params, tokens) == []
    saved = residuals(cfg, params, tokens, capsys)
    assert saved and not [line for line in saved if "named" in line]


def test_a_head_wider_than_a_lane_tile_is_not_kept_yet():
    """256 lanes a head (``_kept``): no name, no mark, and the layer's
    recomputation runs the forward kernel as before."""
    cfg = config(("gated",), {"attn_head_dim": 256}, remat=True)
    params, tokens = inputs(cfg)
    calls = kernels(cfg, params, tokens)
    assert {k: calls[k] for k in ("flash_fwd", "flash_bwd_dkv",
                                  "flash_bwd_dq")} == {
        "flash_fwd": 2, "flash_bwd_dkv": 1, "flash_bwd_dq": 0}
    assert kept_marks(cfg, params, tokens) == []


def test_a_kept_layers_residuals_hold_the_pair(capsys):
    """``lse`` under its name and ``out`` ([B, S, H, dh]; the checkpoint
    rounds a residual that the forward goes on from to its own dtype,
    ``reduce_precision``, which names it anew) beside the arguments."""
    cfg = config(("gated",), {}, remat=True)
    saved = residuals(cfg, *inputs(cfg), capsys)
    assert [line.split(" from ")[0] for line in saved
            if "(attention)" in line] == [
        "f32[1,128,4,8] output of reduce_precision",
        "f32[1,4,8,128] named 'attn_lse'"]


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_a_kept_rule_layers_residuals_hold_the_three(impl, capsys):
    """The states and ``X`` under their names and ``o`` rounded to its
    own dtype (as attention's ``out``) beside the arguments, whichever
    way the chunks are swept."""
    cfg = config(("gdn",), {**LINEAR, "rule_impl": impl}, remat=True)
    saved = residuals(cfg, *inputs(cfg), capsys)
    assert [line.split(" from ")[0] for line in saved
            if "(gdn_mixer)" in line] == [
        "f32[1,128,4,16] output of reduce_precision",
        "f32[1,4,1,16,16] named 'rule_states'",
        "f32[1,4,1,128,128] named 'rule_chunks'"]


def test_the_executors_build_carries_the_marks_and_no_tracer_none():
    """``CompiledStep`` traces the step inside its ``compile`` span;
    without ``remat`` or without a tracer nothing is marked."""
    cfg = config(("gated",), {}, remat=True)
    params, tokens = inputs(cfg)
    marks = kept_marks(cfg, params, tokens, build=executor.CompiledStep)
    assert [(m["layer"], m["value"]) for m in marks] \
        == [(0, "attn_out"), (0, "attn_lse")]
    assert kept_marks(config(("gated",), {}), params, tokens) == []
    assert not spans.is_enabled()
    jax.make_jaxpr(loss_and_grads(cfg, tokens))(params)
    assert spans.current() is None


@pytest.mark.parametrize("masked", [False, True], ids=["dense", "splash"])
def test_outside_a_checkpoint_the_names_change_no_instruction(masked,
                                                              monkeypatch):
    """The differentiated kernel call compiles to the same text with the
    names and with ``_kept`` the identity: a model that checkpoints
    nothing (``models/transformer.py`` without ``remat``) is unmoved."""
    from dlnetbench_tpu.ops.attention_mask import MaskSpec
    x = jnp.ones((1, S, 2, 128), jnp.float32)

    def text(kept):
        monkeypatch.setattr(fa, "_kept", kept)
        if masked:
            spec = MaskSpec(causal=True, window=64)
            fn = lambda q, k, v: fa.splash_attention(q, k, v, spec)  # noqa: E731
        else:
            fn = lambda q, k, v: fa.flash_attention(q, k, v, True)  # noqa: E731
        grad = jax.grad(lambda *a: fn(*a).sum(), argnums=(0, 1, 2))
        return compiled_text(grad, x, x, x)
    named, plain = (text(kept) for kept in (
        fa._kept, lambda out, lse: (out, lse)))
    assert named == plain


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_outside_a_checkpoint_the_rules_names_change_no_instruction(
        impl, monkeypatch):
    """As attention's: the differentiated rule compiles to the same text
    with its three names and without them (the op's own tests,
    ``chip_smoke.py``'s phase of kernels)."""
    q = jnp.ones((1, S, 2, 16), jnp.float32)
    g = -jnp.ones((1, S, 2), jnp.float32)

    def text(name):
        monkeypatch.setattr(gdr, "checkpoint_name", name)
        grad = jax.grad(
            lambda *a: gdr.gated_delta_rule(*a, impl).sum(),
            argnums=(0, 1, 2, 3, 4))
        return compiled_text(grad, q, q, q, g, -g)
    named, plain = (text(name) for name in (
        gdr.checkpoint_name, lambda x, name: x))
    assert named == plain


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_under_a_checkpoint_the_kept_three_are_the_first_calls(impl):
    """The rule alone under a checkpoint that saves its names, compiled
    as the CPU compiles by default: what the backward holds beside the
    five inputs is the states and ``X`` of a second forward call, bit
    for bit, the value returned is that call's ``o``, and the five
    gradients are those of a rule recomputed whole."""
    keys = jax.random.split(jax.random.key(1), 6)
    q, k, v, do = (0.3 * jax.random.normal(key, (1, S, 4, 16), jnp.float32)
                   for key in keys[:4])
    g, beta = (jax.random.normal(key, (1, S, 4), jnp.float32)
               for key in keys[4:])
    args = (q, k, v, -jax.nn.softplus(g), jax.nn.sigmoid(beta))

    def pulled(*names):
        rule = jax.checkpoint(
            lambda *a: gdr.gated_delta_rule(*a, impl),
            policy=jax.checkpoint_policies.save_only_these_names(*names))
        out, pull = jax.jit(lambda *a: jax.vjp(rule, *a))(*args)
        return out, jax.tree.leaves(pull), jax.jit(pull)(do)
    out, held, grads = pulled(*gdr.KEPT_NAMES)
    o, (*_, s0, x) = jax.jit(
        lambda *a: gdr._vjp_fwd(*a, impl, None))(*args)
    same((out, held), (o, [*args, s0, x]))
    _, held_whole, grads_whole = pulled()
    same(held_whole, list(args))
    same(grads, grads_whole)
