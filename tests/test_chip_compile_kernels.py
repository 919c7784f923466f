"""The other kernels compile for the chip: the fused quantized matmuls,
paged attention (the repo's and jax's), the gated delta rule, the
selective scan, the blocked vocabulary head, and the names and scopes a
kernel's instruction carries (see ``chip_compile_support``: a described
``v5e:2x2``).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from chip_compile_support import (
    BF16, D, DH, F, F32, HKV, HQ, I32, QDTYPE, QKV, TOKENS,
    chunk_arrays, grad_of, hlo_computations, kernel_instructions,
    kernels_in, ops_module, re_sub_number)


@pytest.mark.parametrize("fmt", ["int8", "float8"])
def test_fused_matmul(for_chip, fmt):
    qm = ops_module("quantized_matmul")
    text = for_chip(
        lambda x, w, sw, sx: qm.fused_matmul(x, w, sw, sx, fmt=fmt),
        ((TOKENS, D), BF16), ((D, F), QDTYPE[fmt]), ((), F32), ((), F32))
    assert kernels_in(text) == 1


@pytest.mark.parametrize("fmt", ["int8", "float8"])
def test_fused_swiglu_forward_backward(for_chip, fmt):
    """The fused-quantization SwiGLU as the train step runs it
    (``quant_fusion="fused"``): gate, up and down through the kernel in
    the forward, the backward in the master dtype with no kernel."""
    from dlnetbench_tpu.models import layers

    def loss(x, wg, wu, wd):
        y = layers.quantized_swiglu(x, wg, wu, wd, mlp_dtype=fmt,
                                    quant_fusion="fused")
        return jnp.sum(jnp.square(y.astype(F32)))   # dy reads y
    text = for_chip(jax.grad(loss, argnums=(0, 1, 2, 3)),
                    ((TOKENS, D), BF16), ((D, F), BF16), ((D, F), BF16),
                    ((F, D), BF16))
    assert kernels_in(text) == 3


# the serving page layout: 32 slots, 2048 pages of 16 tokens, 128 pages
# (2048 tokens) a sequence
SLOTS, PAGES, PAGE, PAGES_PER_SEQ = 32, 2048, 16, 128


@pytest.mark.parametrize("fmt", ["int8", "float8"])
def test_quant_paged_attention(for_chip, fmt):
    """The per-page scale operand was a (1, 1, 8) VMEM block the
    lowering refused."""
    pq = ops_module("paged_attention_quant")
    pool = ((HKV, PAGES, PAGE, DH), QDTYPE[fmt])
    text = for_chip(
        lambda q, k, v, ks, vs, n, idx: pq.quant_paged_attention(
            q, k, v, ks, vs, n, idx, fmt=fmt, pages_per_compute_block=8),
        ((SLOTS, HQ, DH), BF16), pool, pool, ((HKV, PAGES), F32),
        ((HKV, PAGES), F32), ((SLOTS,), I32),
        ((SLOTS, PAGES_PER_SEQ), I32))
    assert kernels_in(text) == 1


@pytest.mark.parametrize("page", [8, 16])
def test_jax_paged_attention_at_repo_layout(for_chip, page):
    """jax's own kernel, called as ``serving/kv_cache.py`` calls it."""
    from dlnetbench_tpu.serving.kv_cache import paged_attention_decode
    pool = ((HKV, PAGES, page, DH), BF16)
    text = for_chip(
        lambda q, k, v, n, idx: paged_attention_decode(
            q, k, v, n, idx, impl="pallas"),
        ((SLOTS, HQ, DH), BF16), pool, pool, ((SLOTS,), I32),
        ((SLOTS, PAGES_PER_SEQ), I32))
    assert kernels_in(text) == 1


def test_gated_delta_rule_sweeps_at_the_cell_shapes(for_chip):
    """The rule at the linear-attention cell's shapes (T=16384, 32
    heads of 128 x 128), Pallas: the two kernels under their names and
    no loop around them; of a chunk's matrices only what the kernels
    themselves write crosses HBM (the kept states in the inputs' dtype
    and ``X`` in float32: no ``w``, ``u``, ``qg``, ``kr``, ``kg`` of
    XLA's making), and no state a token."""
    from dlnetbench_tpu.metrics import spans
    gdr = ops_module("gated_delta_rule")
    t, h, d = 16384, 32, 128

    def rule(*x):
        with spans.scope("linattn.rule"):     # as hybrid.gdn_mixer does
            return jnp.sum(gdr.gated_delta_rule(*x, "pallas").astype(F32))

    def grads(q, k, v, g, beta):
        return jax.grad(rule, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    qkv = ((1, t, h, d), BF16)
    text = for_chip(grads, qkv, qkv, qkv, ((1, t, h), F32),
                    ((1, t, h), F32))
    names = sorted(re.sub(r"\.\d+$", "", n)
                   for n in kernel_instructions(text))
    assert names == ["gdr_bwd", "gdr_fwd"]
    assert " while(" not in text
    c, hb = gdr.tile_plan(t, h, d, d, 2)
    assert (c, hb) == (128, 8)
    made = chunk_arrays(text, h, t // c)
    assert set(made) == {f"bf16[1,{h},{t // c},{d},{d}]",
                         f"f32[1,{h},{t // c},{c},{c}]"}
    assert set().union(*made.values()) <= {"get-tuple-element", "bitcast"}
    assert f"[1,{t},{h},{d},{d}]" not in text


@pytest.mark.parametrize("d,h,budget,hb", [
    (64, 8, 6 << 20, 2), (64, 8, 20 << 20, 8), (16, 8, 20 << 20, 8),
    (256, 4, 1 << 20, 1)])
def test_gated_delta_rule_head_groups_fill_lane_tiles(for_chip, monkeypatch,
                                                      d, h, budget, hb):
    """Heads narrower and wider than the 128-lane tile: the groups
    ``tile_plan`` allows (whole tiles of a token block, or every head)
    are blocks the TPU lowering takes, forward and backward; interpret
    mode takes any."""
    gdr = ops_module("gated_delta_rule")
    monkeypatch.setattr(gdr, "_VMEM_BUDGET", budget)
    t = 1024
    assert gdr.tile_plan(t, h, d, d, 2) == (128, hb)
    qkv = ((1, t, h, d), BF16)
    text = for_chip(
        jax.grad(lambda *x: jnp.sum(gdr.gated_delta_rule(
            *x, "pallas").astype(F32)), argnums=(0, 1, 2, 3, 4)),
        qkv, qkv, qkv, ((1, t, h), F32), ((1, t, h), F32))
    assert kernels_in(text) == 2


def test_blocked_head_and_loss_at_the_cell_shapes(one_chip,
                                                  no_persistent_cache):
    """``phi4miniflash_train_s8k``'s head and loss alone (8192 rows of
    2560 in blocks of 2048 against the tied 200064-row table, bf16),
    forward and backward: one loop whose body holds three matmuls
    against the table (logits, dx, the table's gradient) and none
    outside it, where the checkpointed form it replaced, written out
    here, ran four a block (the logits twice); no more memory than that
    form; every operation under ``head_loss``."""
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import layers
    rows, d, v, block = 8192, 2560, 200064, 2048

    def fused(x, table, targets):
        return layers.blocked_head_cross_entropy(x, table, targets, block)

    def checkpointed(x, table, targets):
        part = jax.checkpoint(lambda xt: layers.cross_entropy(
            jnp.dot(xt[0], table.T), xt[1]))
        return jnp.mean(jax.lax.map(part, (
            x.reshape(-1, block, d), targets.reshape(-1, block))))

    def compiled(fn):
        args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in
                (((rows, d), BF16), ((v, d), BF16), ((rows,), I32))]
        exe = jax.jit(jax.value_and_grad(fn, argnums=(0, 1))) \
            .lower(*args).compile()
        mem = exe.memory_analysis()
        return (exe.as_text(),
                mem.argument_size_in_bytes + mem.temp_size_in_bytes)

    def matmuls(comps, name):
        """Matmuls of a computation and of the fusions it calls (every
        matmul of this program has the table's 200064 on one side)."""
        return sum(
            bool(re.search(r" (dot|convolution)\(", line))
            + sum(matmuls(comps, c)
                  for c in re.findall(r"calls=%([\w.\-]+)", line))
            for line in comps[name])

    def loops(text):
        """The matmuls of each loop body, those of the entry outside
        the loops, and the lines of entry and bodies."""
        comps = hlo_computations(text)
        bodies = re.findall(r" while\(.*body=%([\w.\-]+)", text)
        entry = re.search(r"^ENTRY %([\w.\-]+)", text, re.M).group(1)
        return (sorted(matmuls(comps, b) for b in bodies),
                matmuls(comps, entry),
                [line for c in (entry, *bodies) for line in comps[c]])

    text, size = compiled(fused)
    was_text, was_size = compiled(checkpointed)
    assert loops(was_text)[:2] == ([1, 3], 0)
    per_loop, outside, lines = loops(text)
    assert (per_loop, outside) == ([3], 0)
    assert size < was_size + 0.4e9
    table = executor.hlo_op_scopes(text)
    fusions = [m.group(1) for line in lines if " fusion(" in line
               and (m := executor._HLO_INSTRUCTION.match(line))]
    assert len(fusions) >= 3
    assert {table[f] for f in fusions} == {"head_loss"}


def test_selective_scan_forward_backward_at_the_cell_shapes(for_chip):
    """The two scan kernels at the hybrid cell's shapes (T=8192,
    E=5120, N=16): one forward, one backward, and no ``[T, E, N]``
    array beside them."""
    ss = ops_module("selective_scan")
    t, e, n = 8192, 5120, 16

    def grads(u, delta, a, b, c, d):
        return jax.grad(lambda *x: jnp.sum(ss.selective_scan(
            *x, "pallas").astype(F32)), argnums=(0, 1, 2, 3, 4, 5))(
                u, delta, a, b, c, d)
    text = for_chip(grads, ((1, t, e), BF16), ((1, t, e), F32),
                    ((e, n), F32), ((1, t, n), BF16), ((1, t, n), BF16),
                    ((e,), F32))
    assert kernels_in(text) == 2
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
    assert f"[1,{t},{e},{n}]" not in text and f"[1,{t},{n},{e}]" not in text


def test_kernels_carry_their_given_names_on_the_chip(for_chip):
    """Under one of the step's scopes, as the models call them, the
    chip's compiler names a kernel's instruction by the ``name=`` of its
    ``pallas_call``: no ``pallas_call.N``, ``jvp__.N`` or
    ``transpose_jvp___.N``, which said nothing of which kernel ran.
    (With no scope around it the transform wraps the name itself:
    ``jvp_flash_fwd_``.)"""
    from dlnetbench_tpu.metrics import spans
    fa, gm = ops_module("flash_attention"), ops_module("grouped_matmul")

    def scoped(q, k, v):
        with spans.scope("attn"):
            return fa.flash_attention(q, k, v)
    flash = kernel_instructions(for_chip(grad_of(scoped), *QKV))
    assert sorted(re.sub(r"\.\d+$", "", n) for n in flash) == \
        ["flash_bwd_dkv", "flash_fwd"]
    grouped = kernel_instructions(for_chip(
        lambda x, w, n: gm.grouped_matmul(x, w, counts=n),
        ((8, 2048, D), BF16), ((8, D, F), BF16), ((8,), I32)))
    assert [re.sub(r"\.\d+$", "", n) for n in grouped] == ["grouped_mm"]


def test_op_scopes_of_a_program_compiled_for_the_chip(for_chip):
    """The table made from the chip compiler's text: the two flash
    kernels (a step holds no ``flash_bwd_dq`` where a head's dq is
    resident in the dk/dv kernel) and the projection's fusion, forward
    and backward, under the scope the function wore; nothing of it
    under another."""
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.metrics import spans
    fa = ops_module("flash_attention")

    def attn(q, k, v, w):
        with spans.scope("attn"):
            out = fa.flash_attention(q, k, v)
            return jnp.sum(jnp.dot(out.reshape(2, 6144, HQ * DH), w)
                           .astype(F32))
    text = for_chip(jax.grad(attn, argnums=(0, 1, 2, 3)), *QKV,
                    ((HQ * DH, D), BF16))
    table = executor.hlo_op_scopes(text)
    kernels = kernel_instructions(text)
    assert sorted(re_sub_number(k) for k in kernels) == \
        ["flash_bwd_dkv", "flash_fwd"]
    assert {table[k] for k in kernels} == {"attn"}
    entry = text[text.index("ENTRY"):]
    fusions = [m.group(1) for line in entry.splitlines()
               if " fusion(" in line
               and (m := executor._HLO_INSTRUCTION.match(line))]
    assert fusions and {table[f] for f in fusions} <= {"attn", "other"}
    assert sum(table[f] == "attn" for f in fusions) >= 2
