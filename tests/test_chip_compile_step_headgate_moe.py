"""``laguna_s21_train_s16k``'s whole train step compiles for the chip
and fits it (see ``chip_compile_support.cell_step``)."""
from __future__ import annotations

import pytest
from chip_compile_support import (
    EXPERTS_BWD, cell_program, cell_step, kernel_instructions,
    re_sub_number)


def test_headgate_moe_train_step_at_the_cell_shapes_fits_the_chip(
        one_chip, no_persistent_cache):
    """``laguna_s21_train_s16k``'s whole step (the cell's own files and
    compiler options, as the runner builds it): the rule of the
    configuration file, twice the arguments plus the temporaries at or
    under 14.0 GB by the chip compiler's count with 16 of 256 experts
    held in four of five layers; three attention kernels a layer (the
    forward twice: each layer is recomputed), the window layers'
    block-sparse at blocks of 512 on 72 query heads over 8 and the full
    layers' dense on 48 over 8 under one set of names and two scopes,
    the gate a head under a third; six grouped matmuls an expert layer
    at width 1024 and the four kernels of its counted backward; the
    state donated."""
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import hybrid
    cell, arch, cfg, _ = cell_program("laguna_s21_train_s16k")
    assert arch["held"] == (0, 16) and arch["head_dim"] == 128
    assert hybrid._splash_block(cfg, cell.traffic["seq_len"]) == 512
    assert (cfg.num_heads, cfg.window_heads, cfg.num_kv_heads,
            cfg.attention_window, cfg.attn_gate) == (48, 72, 8, 512, "head")
    step, cell, arch = cell_step("laguna_s21_train_s16k", one_chip)
    mem = step.memory_analysis
    assert 2 * mem["argument"] + mem["temp"] <= 14.0e9
    # the weights by count (small leaves are padded to their tiles)
    assert mem["argument"] == pytest.approx(cell_arguments(arch), rel=1e-4)
    assert mem["alias"] > 0.99 * mem["argument"]   # the state is donated
    text = step.as_text()
    names = [re_sub_number(k) for k in kernel_instructions(text)]
    assert sorted(names) == sorted(
        (["flash_fwd"] * 2 + ["flash_bwd_dkv"]) * 5
        + (["grouped_mm"] * 6 + [*EXPERTS_BWD, "grouped_mm_bwd_dw"]) * 4)
    table = executor.hlo_op_scopes(text)
    by_scope = {}
    for inst, scope in table.items():
        if re_sub_number(inst) in ("flash_fwd", "flash_bwd_dq",
                                   "flash_bwd_dkv"):
            by_scope.setdefault(scope, []).append(re_sub_number(inst))
    assert {k: len(v) for k, v in by_scope.items()} \
        == {"attn.window": 3 * 3, "attn.full": 2 * 3}
    assert {"attn", "attn.gate", "mlp", "moe.router", "moe.dispatch",
            "moe.experts", "moe.combine", "moe.shared", "head_loss"} \
        <= set(table.values())


def cell_arguments(arch) -> int:
    """The step's arguments by count: every weight in bf16 but the norms
    in float32, and one row of S + 1 tokens."""
    import math

    from benchmarks import weights_headgate_moe as weights
    total = 0
    for name, (shape, _) in weights.shapes(arch).items():
        f32 = name.rsplit("/", 1)[-1] in weights.F32_LEAVES
        total += math.prod(shape) * (4 if f32 else 2)
    return total + 4 * 16385
