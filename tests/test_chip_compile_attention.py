"""The attention kernels compile for the chip: flash and splash,
the dk/dv kernel with a head's dq resident at every cell's shape, the
latent-attention and gated-attention widths, the differential window
(see ``chip_compile_support``: a described ``v5e:2x2``, each kernel a
``tpu_custom_call``).
"""
from __future__ import annotations

import re

import pytest
from chip_compile_support import (
    BF16, QKV, QKV_LONG, grad_of, kernels_in, ops_module, re_sub_number)


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


# the eight cells' attention, as their models call ``ops.attention``:
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
    # groups of nine through the block-sparse kernels in blocks of 512,
    # groups of six through the dense ones
    "laguna_s21_train_s16k.window": ((1, 16384, 72, 128), 8, 128, 512, 512),
    "laguna_s21_train_s16k.full": ((1, 16384, 48, 128), 8, 128, None, None),
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


def test_flash_at_gated_attention_widths(for_chip):
    """16 query heads over 2 key/value heads of 256 lanes at S=16384,
    twice the longest sequence another cell runs: forward, and dkv with
    a head's dq resident, 16 + 16 MiB of it."""
    from dlnetbench_tpu import ops
    text = for_chip(grad_of(lambda q, k, v: ops.attention(
        q, k, v, causal=True, impl="flash")), ((1, 16384, 16, 256), BF16),
        ((1, 16384, 2, 256), BF16), ((1, 16384, 2, 256), BF16))
    assert kernels_in(text) == 2


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
