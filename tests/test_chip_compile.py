"""The main path's Pallas kernels compile for the chip: each is lowered
at llama3_8b / mixtral_8x7b widths for a DESCRIBED ``v5e:2x2`` (the TPU
compiler is installed where no TPU is attached) and must come out as a
``tpu_custom_call``.  Interpret mode — what every other kernel test runs
— accepts programs the TPU lowering refuses (a block that is not a
multiple of the (8, 128) tile, too much VMEM), so these compiles are what
guards a kernel between chip runs.  A compile that passes is not a chip
run: ``chip_smoke.py`` executes the same kernels against their
references.

This is the only file that describes a topology, and it does so inside
a fixture: only one process at a time may load the TPU's library, so the
call must not run while any module is imported (every xdist worker
imports every test file), and the compiles run in this process, not in a
child.
"""
from __future__ import annotations

import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# llama3_8b widths (dlnetbench_tpu/data/models/llama3_8b.json)
D, F, HQ, HKV, DH = 4096, 14336, 32, 8, 128
TOKENS = 12288          # B=2 x S=6144, the bench step's token count
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
QDTYPE = {"int8": jnp.int8, "float8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def for_chip(one_chip, no_persistent_cache, monkeypatch):
    """``compile_for_chip(fn, *shapes)``: the compiled text of ``fn`` at
    ``(shape, dtype)`` arguments on one described chip, with the kernels
    in Mosaic mode (``pallas_common.interpret_mode`` would say "CPU"
    here, and every kernel file reads it through the module)."""
    from dlnetbench_tpu.ops import pallas_common
    monkeypatch.setattr(pallas_common, "interpret_mode", lambda: False)

    def compile_for_chip(fn, *shapes) -> str:
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return compile_for_chip


def ops_module(name: str):
    """``dlnetbench_tpu.ops.<name>`` the module: the package re-exports
    a function ``flash_attention`` that shadows its submodule."""
    return importlib.import_module(f"dlnetbench_tpu.ops.{name}")


def kernels_in(text: str) -> int:
    return text.count("tpu_custom_call")


QKV = [((2, 6144, HQ, DH), BF16), ((2, 6144, HKV, DH), BF16),
       ((2, 6144, HKV, DH), BF16)]
QKV_LONG = [((1, 16384, HQ, DH), BF16), ((1, 16384, HKV, DH), BF16),
            ((1, 16384, HKV, DH), BF16)]


def grad_of(attn):
    return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v).astype(F32)),
                    argnums=(0, 1, 2))


def test_flash_forward(for_chip):
    fa = ops_module("flash_attention")
    assert kernels_in(for_chip(fa.flash_attention, *QKV)) == 1


def test_flash_forward_backward(for_chip):
    fa = ops_module("flash_attention")
    # forward, and the dk/dv kernel with a head's dq resident
    assert kernels_in(for_chip(grad_of(fa.flash_attention), *QKV)) == 2


def test_flash_at_latent_attention_widths(for_chip):
    """``kimivl_a3b_train_s8k``'s attention (B=2, S=8192, 16 heads,
    scores over 128 + 64 lanes, values of 128): the two kernels at the
    default blocks; the 192 lie padded to 256 (a block's last dimension
    is a multiple of the 128-lane tile), the values stay 128 wide; dq
    leaves the dk/dv kernel at the scores' width."""
    import re

    from dlnetbench_tpu.metrics import spans
    fa = ops_module("flash_attention")

    def scoped(q, k, v):
        with spans.scope("attn"):
            return fa.flash_attention(q, k, v)
    text = for_chip(grad_of(scoped),
                    ((2, 8192, 16, 192), BF16), ((2, 8192, 16, 192), BF16),
                    ((2, 8192, 16, 128), BF16))
    calls = {re.sub(r"\.\d+$", "", m.group(1)): line
             for line in text.splitlines() if "tpu_custom_call" in line
             and (m := re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line))}
    assert sorted(calls) == ["flash_bwd_dkv", "flash_fwd"]
    scores, values = "bf16[2,8192,4096]", "bf16[2,8192,2048]"

    def outputs(name):
        return calls[name].partition(" custom-call(")[0]

    def operands(name):
        return calls[name].partition("operand_layout_constraints={")[2] \
            .partition("}}")[0]
    assert values in outputs("flash_fwd")
    assert scores not in outputs("flash_fwd")
    # q and k at the scores' width, v at its own
    assert (operands("flash_fwd").count(scores),
            operands("flash_fwd").count(values)) == (2, 1)
    # dk and dq wide, dv narrow
    assert (outputs("flash_bwd_dkv").count(scores),
            outputs("flash_bwd_dkv").count(values)) == (2, 1)
    # q, k | v, dO
    assert (operands("flash_bwd_dkv").count(scores),
            operands("flash_bwd_dkv").count(values)) == (2, 2)


@pytest.mark.parametrize("mask", ["window", "segments"])
def test_splash_forward_backward(for_chip, mask):
    from dlnetbench_tpu.ops.attention_mask import MaskSpec
    fa = ops_module("flash_attention")
    spec = (MaskSpec(window=4096) if mask == "window"
            else MaskSpec(seg_avg=2048, seg_seed=0))
    text = for_chip(grad_of(lambda q, k, v: fa.splash_attention(
        q, k, v, spec)), *QKV_LONG)
    assert kernels_in(text) == 2


# the seven cells' attention, as their models call ``ops.attention``:
# q's shape, key/value heads, value lanes, window, explicit blocks
CELL_ATTENTION = {
    "minerva7b_train": ((2, 6144, 32, 128), 8, 128, None, None),
    "mixtral8x7b_train": ((2, 4096, 32, 128), 8, 128, None, None),
    "phi4miniflash_train_s8k.window": ((1, 8192, 20, 128), 10, 128, 512,
                                       512),
    "phi4miniflash_train_s8k.full": ((1, 8192, 20, 128), 10, 128, None,
                                     None),
    "kimivl_a3b_train_s8k": ((2, 8192, 16, 192), 16, 128, None, None),
    "qwen3next_a3b_train_s16k": ((1, 16384, 16, 256), 2, 256, None, None),
    "lfm2_8b_a1b_train_s8k": ((1, 8192, 32, 64), 8, 64, None, None),
    "smallthinker_21b_a3b_train_s16k.window": ((1, 16384, 28, 128), 4, 128,
                                               4096, 2048),
    "smallthinker_21b_a3b_train_s16k.full": ((1, 16384, 28, 128), 4, 128,
                                             None, None),
}


@pytest.mark.parametrize("cell", sorted(CELL_ATTENTION))
def test_dkv_with_a_resident_dq_at_the_cells_shapes(for_chip, cell):
    """The dk/dv kernel with one query head's dq in VMEM (a float32
    accumulator ``[S, dh_p]`` and the output block twice: 16 + 16 MiB
    at Qwen's 16384 x 256, beside the score tiles under the 64 MiB
    limit) compiles for the chip at every cell's shape, dense and
    block-sparse: no dq kernel is left, and dq is the dk/dv kernel's
    third output at q's padded width."""
    from dlnetbench_tpu import ops
    from dlnetbench_tpu.metrics import spans
    from dlnetbench_tpu.ops.attention_mask import MaskSpec
    (b, s, hq, dh), hkv, dv, window, block = CELL_ATTENTION[cell]
    mask = MaskSpec(causal=True, window=window) if window else None

    def scoped(q, k, v):        # as the models call it: the kernels'
        with spans.scope("attn"):       # instructions keep their names
            return ops.attention(q, k, v, causal=True, impl="flash",
                                 mask=mask, block_q=block, block_k=block)
    text = for_chip(grad_of(scoped), ((b, s, hq, dh), BF16),
                    ((b, s, hkv, dh), BF16), ((b, s, hkv, dv), BF16))
    calls = {re_sub_number(m.group(1)): line
             for line in text.splitlines() if "tpu_custom_call" in line
             and (m := re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line))}
    assert sorted(calls) == ["flash_bwd_dkv", "flash_fwd"]
    dh_p, dv_p = -(-dh // 128) * 128, -(-dv // 128) * 128
    outputs = calls["flash_bwd_dkv"].partition(" custom-call(")[0]
    wide, narrow = f"bf16[{b},{s},{hq * dh_p}]", f"bf16[{b},{s},{hq * dv_p}]"
    # dk and dq at the scores' width, dv at the values'
    assert outputs.count("bf16[") == 3
    assert (outputs.count(wide), outputs.count(narrow)) == (
        (3, 3) if dh_p == dv_p else (2, 1))


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
    import re
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


# the kernels of ``moe_held``'s counted backward, by name: one dh (with
# the SwiGLU epilogue) and one dx a layer, and two contraction-side
# calls (dW_down; dW_gate with dW_up)
EXPERTS_BWD = ("grouped_mm_bwd_dh", "grouped_mm_bwd_dx", "grouped_mm_bwd_dw")
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
    import re

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


def scopes_by_opcode(text: str, opcodes: str, keep) -> dict:
    """{opcode: the scopes its instructions lie under}, over the
    instructions of ``text`` whose opcode is one of ``opcodes`` (a
    regex alternation) and that ``keep(line, scope)`` takes."""
    import re

    from dlnetbench_tpu.core import executor
    table = executor.hlo_op_scopes(text)
    opcode = re.compile(rf"\s({opcodes})\(")
    found = {}
    for line in text.splitlines():
        m = executor._HLO_INSTRUCTION.match(line)
        op = opcode.search(line.partition(", metadata=")[0])
        if m and op and keep(line, table[m.group(1)]):
            found.setdefault(op.group(1), set()).add(table[m.group(1)])
    return found


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
    import re

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


def test_moe_train_step_at_the_cell_shapes_fits_the_chip(for_chip, one_chip):
    """``mixtral8x7b_train``'s whole step (one layer, 8 experts, B=2 x
    S=4096, the cell's own files and compiler options), as the runner
    builds it: arguments and temporaries fit the chip's 15.75 GiB, and
    of the expert backward's ``[E, C, F]`` float32 arrays only ``dh``
    is left: ``g`` and ``u`` are read as the forward's kernels wrote
    them, in bf16.  ``auto`` attention asks the backend, the CPU here,
    so the test names the kernels the chip would pick."""
    import re

    from benchmarks import harness, weights
    from benchmarks.runners import train
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step, moe
    cell = harness.load_cell("mixtral8x7b_train")
    wl, tr = cell.workload, cell.traffic
    arch = weights.arch_of(cell.config,
                           capacity_factor=wl["capacity_factor"])
    cfg = train.program_config(cell, arch, {"attention_impl": "flash"})

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(
        on_chip, jax.eval_shape(lambda: weights.make_params(arch, 0)))
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"] + 1), I32,
                                  sharding=one_chip)
    step = executor.CompiledStep(
        bench_step.make_train_k(cfg, 1, wl["lr"]), (params, tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS,
        compiler_options=wl["compiler_options"])
    mem = step.memory_analysis
    assert mem["argument"] + mem["temp"] < 15.75 * 2 ** 30
    text = step.as_text()
    assert kernels_in(text) == 5     # two flash, three grouped_mm
    assert sum(k.startswith("grouped_mm.")
               for k in kernel_instructions(text)) == 3
    e, f = arch["num_experts"], arch["ff_dim"]
    c = moe.group_capacity(tr["batch"] * tr["seq_len"], arch["top_k"], e,
                           arch["capacity_factor"])
    wide = re.compile(rf"^\s*(?:ROOT )?\S+ = f32\[{e},{c},{f}\]", re.M)
    assert len(wide.findall(text[text.index("ENTRY"):])) == 1


def test_latent_moe_train_step_at_the_cell_shapes_fits_the_chip(one_chip,
                                                                no_persistent_cache,
                                                                monkeypatch):
    """``kimivl_a3b_train_s8k``'s whole step (the cell's own files and
    compiler options, as the runner builds it): the depth rule of the
    configuration file, twice the arguments plus the temporaries at or
    under 13.0 GB by the chip compiler's count; three attention kernels
    a layer (the forward twice: each layer is recomputed; dq from the
    dk/dv kernel), six
    grouped matmuls an expert layer and the four kernels of its
    counted backward; the step's outputs carry the routing."""
    from benchmarks import harness, weights_latent_moe as weights
    from benchmarks.runners import train_latent_moe
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step
    from dlnetbench_tpu.ops import pallas_common
    monkeypatch.setattr(pallas_common, "interpret_mode", lambda: False)
    cell = harness.load_cell("kimivl_a3b_train_s8k")
    wl, tr = cell.workload, cell.traffic
    arch = weights.arch_of(cell.config)
    cfg = train_latent_moe.program_config(cell, arch,
                                          {"attention_impl": "flash"})

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(
        on_chip, jax.eval_shape(lambda: weights.make_params(arch, 0)))
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"] + 1), I32,
                                  sharding=one_chip)
    step = executor.CompiledStep(
        bench_step.make_train_k(cfg, 1, wl["lr"]), (params, tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS,
        compiler_options=wl["compiler_options"])
    mem = step.memory_analysis
    assert 2 * mem["argument"] + mem["temp"] <= 13.0e9
    assert mem["alias"] > 0.99 * mem["argument"]   # the state is donated
    layers = arch["num_layers"]
    experts = weights.expert_layers(arch)
    text = step.as_text()
    assert kernels_in(text) == 3 * layers + 10 * experts
    names = [re_sub_number(k) for k in kernel_instructions(text)]
    assert names.count("grouped_mm") == 6 * experts == 30
    assert [names.count(k) for k in EXPERTS_BWD] == [experts] * 2 \
        + [2 * experts]


def test_linear_moe_train_step_at_the_cell_shapes_compiles_for_the_chip(
        one_chip, no_persistent_cache, monkeypatch):
    """``qwen3next_a3b_train_s16k``'s step as the runner builds it (the
    cell's own files, widths, sequence, bound and compiler options, the
    sweeps that "auto" takes on the chip), cut to one layer of each kind
    so that it compiles in a minute: the kernels by name (the rule's
    forward kernel twice a linear layer and its backward once, four
    attention kernels a full layer, six grouped matmuls a layer and the
    four kernels of its counted backward), no
    loop but the head's (none around the rule: all heads go through one
    call), no chunk matrix but what the rule's kernels write, the state
    donated, and the temporaries under what let 32 held experts keep
    the 13.0 GB rule (the whole step's count is in the configuration
    file)."""
    import dataclasses
    import re

    from benchmarks import harness, weights_linear_moe as weights
    from benchmarks.runners import train_linear_moe
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step
    from dlnetbench_tpu.ops import pallas_common
    monkeypatch.setattr(pallas_common, "interpret_mode", lambda: False)
    cell = harness.load_cell("qwen3next_a3b_train_s16k")
    cell = dataclasses.replace(cell, config={
        **cell.config, "num_hidden_layers": 2,
        "full_attention_interval": 2})
    wl, tr = cell.workload, cell.traffic
    arch = weights.arch_of(cell.config)
    assert arch["layer_kinds"] == ("gdn", "gated")
    cfg = train_linear_moe.program_config(
        cell, arch, {"attention_impl": "flash", "rule_impl": "pallas"})

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(
        on_chip, jax.eval_shape(lambda: weights.make_params(arch, 0)))
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"] + 1), I32,
                                  sharding=one_chip)
    step = executor.CompiledStep(
        bench_step.make_train_k(cfg, 1, wl["lr"]), (params, tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS,
        compiler_options=wl["compiler_options"])
    mem = step.memory_analysis
    assert mem["alias"] > 0.99 * mem["argument"]   # the state is donated
    assert mem["temp"] <= 5.5e9        # 5.39 GB read, PR 33 (5.52, PR 32)
    text = step.as_text()
    names = kernel_instructions(text)
    assert sorted(re.sub(r"\.\d+$", "", k) for k in names) == sorted(
        ["gdr_fwd"] * 2 + ["gdr_bwd"] + ["flash_fwd"] * 2
        + ["flash_bwd_dkv"] + ["grouped_mm"] * 12
        + [*EXPERTS_BWD, "grouped_mm_bwd_dw"] * 2)
    table = executor.hlo_op_scopes(text)
    loops = [m.group(1) for line in text.splitlines() if " while(" in line
             and (m := executor._HLO_INSTRUCTION.match(line))]
    assert [table[w] for w in loops] == ["head_loss"]
    made = chunk_arrays(text, cfg.gdn_value_heads, tr["seq_len"] // 128)
    assert set().union(*made.values()) <= {"get-tuple-element", "bitcast"}


def test_conv_moe_train_step_at_the_cell_shapes_fits_the_chip(
        one_chip, no_persistent_cache, monkeypatch):
    """``lfm2_8b_a1b_train_s8k``'s whole step (the cell's own files and
    compiler options, as the runner builds it): the rule of the
    configuration file, twice the arguments plus the temporaries at or
    under 14.0 GB by the chip compiler's count with all 32 experts of
    every layer held; four attention kernels for the one attention
    layer at 64 lanes (the forward twice: each layer is recomputed),
    six grouped matmuls an expert layer and the four kernels of its
    counted backward (no ``[E, C, F]`` float32 array is left in the
    step: ``dh`` stays inside its kernel); the gated convolution under
    its own scope, forward and backward; the state donated."""
    from benchmarks import harness, weights_conv_moe as weights
    from benchmarks.runners import train_conv_moe
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step
    from dlnetbench_tpu.ops import pallas_common
    monkeypatch.setattr(pallas_common, "interpret_mode", lambda: False)
    cell = harness.load_cell("lfm2_8b_a1b_train_s8k")
    wl, tr = cell.workload, cell.traffic
    arch = weights.arch_of(cell.config)
    assert arch["held"] == (0, 32) and arch["head_dim"] == 64
    cfg = train_conv_moe.program_config(cell, arch,
                                        {"attention_impl": "flash"})

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(
        on_chip, jax.eval_shape(lambda: weights.make_params(arch, 0)))
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"] + 1), I32,
                                  sharding=one_chip)
    step = executor.CompiledStep(
        bench_step.make_train_k(cfg, 1, wl["lr"]), (params, tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS,
        compiler_options=wl["compiler_options"])
    mem = step.memory_analysis
    assert 2 * mem["argument"] + mem["temp"] <= 14.0e9
    assert mem["alias"] > 0.99 * mem["argument"]   # the state is donated
    text = step.as_text()
    names = [re_sub_number(k) for k in kernel_instructions(text)]
    experts = weights.expert_layers(arch)
    assert sorted(names) == sorted(
        ["flash_fwd"] * 2 + ["flash_bwd_dkv"]
        + ["grouped_mm"] * 6 * experts
        + [*EXPERTS_BWD, "grouped_mm_bwd_dw"] * experts) and experts == 4
    import re
    assert not re.findall(r"^\s*(?:ROOT )?\S+ = f32\[(?:1,)?32,2048,1792\]",
                          text[text.index("ENTRY"):], re.M)
    scopes = set(executor.hlo_op_scopes(text).values())
    assert {"conv", "conv.gate", "attn", "mlp", "moe.experts",
            "head_loss"} <= scopes


def test_swa_moe_train_step_at_the_cell_shapes_fits_the_chip(
        one_chip, no_persistent_cache, monkeypatch):
    """``smallthinker_21b_a3b_train_s16k``'s whole step (the cell's own
    files and compiler options, as the runner builds it): the rule of
    the configuration file, twice the arguments plus the temporaries at
    or under 14.0 GB by the chip compiler's count with 16 of 64 experts
    held in eight layers; four attention kernels a layer at 28 query
    heads over 4 (the forward twice: each layer is recomputed), the
    window layers' block-sparse at blocks of 2048 and the full layers'
    dense under one set of names and two scopes; six grouped matmuls a
    layer at width 768 and the four kernels of its counted backward; the
    router's logits read the layer's input; the state donated."""
    from benchmarks import harness, weights_swa_moe as weights
    from benchmarks.runners import train_swa_moe
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step, hybrid
    from dlnetbench_tpu.ops import pallas_common
    monkeypatch.setattr(pallas_common, "interpret_mode", lambda: False)
    cell = harness.load_cell("smallthinker_21b_a3b_train_s16k")
    wl, tr = cell.workload, cell.traffic
    arch = weights.arch_of(cell.config)
    assert arch["held"] == (0, 16) and arch["head_dim"] == 128
    cfg = train_swa_moe.program_config(cell, arch,
                                       {"attention_impl": "flash"})
    assert hybrid._splash_block(cfg, tr["seq_len"]) == 2048
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.attention_window) \
        == (28, 4, 4096)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(
        on_chip, jax.eval_shape(lambda: weights.make_params(arch, 0)))
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"] + 1), I32,
                                  sharding=one_chip)
    step = executor.CompiledStep(
        bench_step.make_train_k(cfg, 1, wl["lr"]), (params, tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS,
        compiler_options=wl["compiler_options"])
    mem = step.memory_analysis
    assert 2 * mem["argument"] + mem["temp"] <= 14.0e9
    assert mem["alias"] > 0.99 * mem["argument"]   # the state is donated
    text = step.as_text()
    names = [re_sub_number(k) for k in kernel_instructions(text)]
    layers = arch["num_layers"]
    assert sorted(names) == sorted(
        (["flash_fwd"] * 2 + ["flash_bwd_dkv"]
         + ["grouped_mm"] * 6 + [*EXPERTS_BWD, "grouped_mm_bwd_dw"])
        * layers) and layers == 8
    table = executor.hlo_op_scopes(text)
    by_scope = {}
    for inst, scope in table.items():
        if re_sub_number(inst) in ("flash_fwd", "flash_bwd_dq",
                                   "flash_bwd_dkv"):
            by_scope.setdefault(scope, []).append(re_sub_number(inst))
    assert {k: len(v) for k, v in by_scope.items()} \
        == {"attn.window": 6 * 3, "attn.full": 2 * 3}
    assert {"attn", "moe.router", "moe.dispatch", "moe.experts",
            "moe.combine", "head_loss"} <= set(table.values())


def re_sub_number(name: str) -> str:
    import re
    return re.sub(r"\.\d+$", "", name)


def chunk_arrays(text: str, h: int, nc: int) -> dict:
    """{shape: opcodes of the instructions that make it} for every
    array ``[1, h, nc, ...]`` of five dimensions: a matrix a head and
    chunk."""
    import re
    made = {}
    shape = re.compile(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+\[1," + f"{h},{nc}"
                       + r",\d+,\d+\])\S* ([\w\-]+)\(")
    for line in text.splitlines():
        if m := shape.match(line):
            made.setdefault(m.group(1), set()).add(m.group(2))
    return made


def test_gated_delta_rule_sweeps_at_the_cell_shapes(for_chip):
    """The rule at the linear-attention cell's shapes (T=16384, 32
    heads of 128 x 128), Pallas: the two kernels under their names and
    no loop around them; of a chunk's matrices only what the kernels
    themselves write crosses HBM (the kept states in the inputs' dtype
    and ``X`` in float32: no ``w``, ``u``, ``qg``, ``kr``, ``kg`` of
    XLA's making), and no state a token."""
    import re

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


def test_flash_at_gated_attention_widths(for_chip):
    """16 query heads over 2 key/value heads of 256 lanes at S=16384,
    twice the longest sequence another cell runs: forward, and dkv with
    a head's dq resident, 16 + 16 MiB of it."""
    from dlnetbench_tpu import ops
    text = for_chip(grad_of(lambda q, k, v: ops.attention(
        q, k, v, causal=True, impl="flash")), ((1, 16384, 16, 256), BF16),
        ((1, 16384, 2, 256), BF16), ((1, 16384, 2, 256), BF16))
    assert kernels_in(text) == 2


def hlo_computations(text: str) -> dict:
    """{computation name: its lines} of a compiled module's text."""
    import re
    comps, lines = {}, None
    for line in text.splitlines():
        if m := re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line):
            lines = comps.setdefault(m.group(1), [])
        elif lines is not None:
            lines.append(line.partition(", metadata=")[0])
    return comps


def test_blocked_head_and_loss_at_the_cell_shapes(one_chip,
                                                  no_persistent_cache):
    """``phi4miniflash_train_s8k``'s head and loss alone (8192 rows of
    2560 in blocks of 2048 against the tied 200064-row table, bf16),
    forward and backward: one loop whose body holds three matmuls
    against the table (logits, dx, the table's gradient) and none
    outside it, where the checkpointed form it replaced, written out
    here, ran four a block (the logits twice); no more memory than that
    form; every operation under ``head_loss``."""
    import re

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


def kernel_instructions(text: str) -> list:
    """The names of the Pallas custom calls, as a device trace prints
    them first in each event's name."""
    from dlnetbench_tpu.core import executor
    return [m.group(1) for line in text.splitlines()
            if "tpu_custom_call" in line
            and (m := executor._HLO_INSTRUCTION.match(line))]


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


def test_differential_window_attention_at_the_cell_shapes(for_chip):
    """Window-512 attention over pairs of 64-wide heads padded to the
    value's 128, in blocks of 512: forward, and dkv with dq."""
    from dlnetbench_tpu import ops
    from dlnetbench_tpu.ops.attention_mask import MaskSpec
    spec = MaskSpec(window=512)
    text = for_chip(grad_of(lambda q, k, v: ops.attention(
        q, k, v, causal=True, impl="flash", mask=spec, block_q=512,
        block_k=512)), ((1, 8192, 20, 128), BF16),
        ((1, 8192, 10, 128), BF16), ((1, 8192, 10, 128), BF16))
    assert kernels_in(text) == 2


def test_kernels_carry_their_given_names_on_the_chip(for_chip):
    """Under one of the step's scopes, as the models call them, the
    chip's compiler names a kernel's instruction by the ``name=`` of its
    ``pallas_call``: no ``pallas_call.N``, ``jvp__.N`` or
    ``transpose_jvp___.N``, which said nothing of which kernel ran.
    (With no scope around it the transform wraps the name itself:
    ``jvp_flash_fwd_``.)"""
    import re

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
