"""``lfm2_8b_a1b_train_s8k``'s whole train step compiles for the chip
and fits it (see ``chip_compile_support.cell_step``)."""
from __future__ import annotations

import re

from chip_compile_support import (
    EXPERTS_BWD, cell_step, kernel_instructions, re_sub_number)


def test_conv_moe_train_step_at_the_cell_shapes_fits_the_chip(
        one_chip, no_persistent_cache):
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
    from benchmarks import weights_conv_moe as weights
    from dlnetbench_tpu.core import executor
    step, cell, arch = cell_step("lfm2_8b_a1b_train_s8k", one_chip)
    assert arch["held"] == (0, 32) and arch["head_dim"] == 64
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
    assert not re.findall(r"^\s*(?:ROOT )?\S+ = f32\[(?:1,)?32,2048,1792\]",
                          text[text.index("ENTRY"):], re.M)
    scopes = set(executor.hlo_op_scopes(text).values())
    assert {"conv", "conv.gate", "attn", "mlp", "moe.experts",
            "head_loss"} <= scopes
