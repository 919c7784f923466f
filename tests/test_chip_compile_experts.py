"""The expert layers' kernels and routing compile for the chip: the
grouped matmul at the cells' shapes, the counted backward and its packed
form, dispatch and combine by row index, the held layers' routing (see
``chip_compile_support``: a described ``v5e:2x2``).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from chip_compile_support import (
    BF16, D, EXPERTS_BWD, F, F32, I32, QDTYPE, kernel_instructions,
    kernels_in, ops_module, re_sub_number, scopes_by_opcode)


@pytest.mark.parametrize("fmt", [None, "int8"], ids=["bf16", "int8"])
def test_grouped_matmul(for_chip, fmt):
    """mixtral_8x7b's expert FFN (8 experts, same D and F).  The
    per-expert scale operand was a (1, 1) SMEM block the lowering
    refused — on the bf16 path too, which carries it unused."""
    gm = ops_module("grouped_matmul")
    e, c = 8, 2048
    x, counts = ((e, c, D), BF16), ((e,), I32)
    if fmt is None:
        text = for_chip(
            lambda x, w, n: gm.grouped_matmul(x, w, counts=n),
            x, ((e, D, F), BF16), counts)
    else:
        text = for_chip(
            lambda x, w, n, sx, sw: gm.grouped_matmul(
                x, w, counts=n, sx=sx, sw=sw, fmt=fmt),
            x, ((e, D, F), QDTYPE[fmt]), counts, ((e,), F32), ((e,), F32))
    assert kernels_in(text) == 1


# the four grouped matmuls the benchmark's cells run (E, C, K, N), bf16
CELL_GROUPED = {
    "kimi_gate_up": (16, 4096, 2048, 1408),
    "kimi_down": (16, 4096, 1408, 2048),
    "mixtral_gate_up": (8, 2560, 4096, 14336),
    "mixtral_down": (8, 2560, 14336, 4096),
}


@pytest.mark.parametrize("name", CELL_GROUPED)
def test_grouped_matmul_at_the_cells_shapes(for_chip, name):
    """Each under the tiles its own shape plans (1408 = 11 x 128 whole,
    a 14336-deep contraction in one block): the chip's compiler takes
    them inside the kernel family's VMEM limit, and the instruction is
    still ``grouped_mm.N`` with the ``s32[E]`` counts first, which is
    how the two roofline readers find it."""
    gm = ops_module("grouped_matmul")
    e, c, k, n = CELL_GROUPED[name]
    text = for_chip(lambda x, w, cnt: gm.grouped_matmul(x, w, counts=cnt),
                    ((e, c, k), BF16), ((e, k, n), BF16), ((e,), I32))
    assert kernels_in(text) == 1
    call, = [line for line in text.splitlines()
             if "tpu_custom_call" in line]
    first = re.search(rf"%grouped_mm\.\d+ = bf16\[{e},{c},{n}\]\S* "
                      rf"custom-call\((%[\w.\-]+)", call).group(1)
    # the text names operands without their types (a trace prints them)
    assert re.search(rf"{re.escape(first)} = s32\[{e}\]", text)


CELL_HELD_FFN = {          # (E, C, d, F), bf16
    "lfm2": (32, 2048, 2048, 1792),
    "kimi": (16, 4096, 2048, 1408),
    "qwen": (32, 1536, 2048, 512),
    "smallthinker": (16, 12032, 2560, 768),
}


@pytest.mark.parametrize("cell", CELL_HELD_FFN)
def test_counted_expert_backward_at_the_cells_shapes(for_chip, cell):
    """The four kernels of ``grouped_ffn(backward="counted")`` under
    the tiles their own shapes plan (an expert's whole weight a step on
    the row side, its whole gradient in VMEM on the contraction side,
    both gate and up at once): the chip's compiler takes them inside
    the family's VMEM limit beside the forward's two, and nothing of
    ``[E, C, .]`` is multiplied outside a kernel."""
    from dlnetbench_tpu.metrics import spans
    gm = ops_module("grouped_matmul")
    e, c, d, f = CELL_HELD_FFN[cell]

    def loss(x, wg, wu, wd, cnt):
        with spans.scope("moe.experts"):
            y = gm.grouped_ffn(x, wg, wu, wd, counts=cnt,
                               backward="counted")
        return jnp.sum(y.astype(F32))
    text = for_chip(jax.grad(loss, argnums=(0, 1, 2, 3)),
                    ((e, c, d), BF16), ((e, d, f), BF16), ((e, d, f), BF16),
                    ((e, f, d), BF16), ((e,), I32))
    names = [re.sub(r"\.\d+$", "", k) for k in kernel_instructions(text)]
    assert sorted(names) == sorted(
        ["grouped_mm"] * 2 + [*EXPERTS_BWD, "grouped_mm_bwd_dw"])
    assert not re.search(rf" (dot|convolution)\(", text)


# the two cells whose bound reserves more slots than their pairs can
# fill, so that ``moe_held`` packs the rows: (R, E, C, d, F), bf16
CELL_PACKED_FFN = {
    "smallthinker": (102400, 16, 12032, 2560, 768),
    "lfm2": (40960, 32, 2048, 2048, 1792),
}


@pytest.mark.parametrize("cell", CELL_PACKED_FFN)
def test_packed_expert_ffn_at_the_cells_shapes(for_chip, cell):
    """The packed forms of the forward kernel and of the counted
    backward's three (``grouped_ffn(bound=C)``: one buffer ``[1, R, d]``,
    the row axis of the grid over its R / 256 blocks, each block's
    weight found through a prefetched table of owners): the chip's
    compiler takes them under the names the padded forms carry, and
    nothing as large as ``[E, C, .]`` is left."""
    from dlnetbench_tpu.metrics import spans
    from dlnetbench_tpu.models import layers
    gm = ops_module("grouped_matmul")
    r, e, c, d, f = CELL_PACKED_FFN[cell]
    pairs = {"smallthinker": 6 * 16384, "lfm2": 4 * 8192}[cell]
    assert layers.packed_room(pairs, e, c,
                              gm.row_block(e, c, d, f, BF16)) == r

    def loss(x, wg, wu, wd, cnt):
        with spans.scope("moe.experts"):
            y = gm.grouped_ffn(x, wg, wu, wd, counts=cnt,
                               backward="counted", bound=c)
        return jnp.sum(y.astype(F32))
    text = for_chip(jax.grad(loss, argnums=(0, 1, 2, 3)),
                    ((1, r, d), BF16), ((e, d, f), BF16), ((e, d, f), BF16),
                    ((e, f, d), BF16), ((e,), I32))
    names = [re_sub_number(k) for k in kernel_instructions(text)]
    assert sorted(names) == sorted(
        ["grouped_mm"] * 2 + [*EXPERTS_BWD, "grouped_mm_bwd_dw"])
    assert not re.search(r" (dot|convolution)\(", text)
    assert f"[1,{r},{f}]" in text and f"[{e},{c},{d}]" not in text


def test_moe_dispatch_and_combine_at_the_cell_shapes(for_chip):
    """The row-gather dispatch and combine with their hand-written
    backward, at ``mixtral8x7b_train``'s shapes (T = 8192 tokens, 8
    experts top-2, C = 2560 slots, D = 4096, bf16): the chip's compiler
    takes the gathers and the sort; nothing of shape [T, E, C], no
    matmul, scatter or kernel under the two scopes."""
    from dlnetbench_tpu.models import layers, moe
    t, e, k, c = 8192, 8, 2, 2560

    def loss(x, w_router, scale):
        xe, plan, gate = moe.dispatch(x, w_router, e, k, 1.25)
        assert xe.shape == (e, c, D) and xe.dtype == BF16
        y = layers.moe_combine(xe * scale, plan, gate)
        return jnp.sum(y.astype(F32))
    text = for_chip(jax.grad(loss, argnums=(0, 1, 2)), ((t, D), BF16),
                    ((D, e), BF16), ((e, c, D), BF16))
    assert kernels_in(text) == 0
    assert f"[{t},{e},{c}]" not in text
    route = {"moe.dispatch", "moe.combine"}
    found = scopes_by_opcode(text, "gather|sort|dot|convolution|scatter",
                             lambda line, scope: scope in route)
    assert found["gather"] == route and found["sort"] == {"moe.dispatch"}
    assert not {"dot", "convolution", "scatter"} & set(found)


# the held layers of the two cells whose chip holds a share of the
# experts: (T, k, E held, C, D)
CELL_HELD = {"qwen3next_a3b_train_s16k": (16384, 10, 32, 1536, 2048),
             "kimivl_a3b_train_s8k": (16384, 6, 16, 4096, 2048)}


@pytest.mark.parametrize("cell", CELL_HELD)
def test_held_routing_at_the_cell_shapes_moves_no_pair_rows(for_chip, cell):
    """Dispatch and combine of a layer whose chip holds a share of the
    router's experts, with their hand-written backward, bf16: the
    plan's slot side (E * C < k * T).  The chip's compiler takes the
    sort, the steps' gathers and scatter-adds; nothing is as long as
    the k * T pairs, every gather and scatter lies under the two
    scopes, no matmul or kernel does."""
    from dlnetbench_tpu.models import layers
    t, k, e, c, d = CELL_HELD[cell]

    def loss(x, weights, scale, idx):
        xe, plan, gate, _ = layers.moe_dispatch_held(x, weights, idx,
                                                     (2 * e, e), c)
        assert layers._plan_side(plan, "combine") == "slots"
        y = layers.moe_combine(xe * scale, plan, gate)
        return jnp.sum(jnp.sin(y.astype(F32)))     # y and dy both live
    text = for_chip(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    ((t, d), BF16), ((t, k), F32), ((e, c, d), BF16),
                    ((t, k), I32))
    assert kernels_in(text) == 0
    assert not re.search(rf"\[{k},{t},{d}\]|\[{k * t},{d}\]|"
                         rf"\[{t},{k},{d}\]", text)
    found = scopes_by_opcode(
        text, "gather|scatter|dot|convolution",
        lambda line, scope: re.search(rf"\[\d+,{d}\]", line))
    assert found == {"gather": {"moe.dispatch", "moe.combine"},
                     "scatter": {"moe.dispatch", "moe.combine"}}
