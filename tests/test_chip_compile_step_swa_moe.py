"""``smallthinker_21b_a3b_train_s16k``'s whole train step compiles for
the chip and fits it (see ``chip_compile_support.cell_step``)."""
from __future__ import annotations

from chip_compile_support import (
    EXPERTS_BWD, cell_program, cell_step, kernel_instructions,
    re_sub_number)


def test_swa_moe_train_step_at_the_cell_shapes_fits_the_chip(
        one_chip, no_persistent_cache):
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
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import hybrid
    cell, arch, cfg, _ = cell_program("smallthinker_21b_a3b_train_s16k")
    assert arch["held"] == (0, 16) and arch["head_dim"] == 128
    assert hybrid._splash_block(cfg, cell.traffic["seq_len"]) == 2048
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.attention_window) \
        == (28, 4, 4096)
    step, cell, arch = cell_step("smallthinker_21b_a3b_train_s16k", one_chip)
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
