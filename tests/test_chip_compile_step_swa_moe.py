"""``smallthinker_21b_a3b_train_s16k``'s whole train step compiles for
the chip and fits it (see ``chip_compile_support.cell_step``)."""
from __future__ import annotations

import pytest
from chip_compile_support import (
    EXPERTS_BWD, cell_program, cell_step, kernel_instructions,
    phases_of_kernels, re_sub_number)


@pytest.fixture(scope="module")
def built(one_chip, no_persistent_cache):
    """One compile for the file's cases."""
    return cell_step("smallthinker_21b_a3b_train_s16k", one_chip)


def test_swa_moe_train_step_at_the_cell_shapes_fits_the_chip(built):
    """``smallthinker_21b_a3b_train_s16k``'s whole step (the cell's own
    files and compiler options, as the runner builds it): the rule of
    the configuration file, twice the arguments plus the temporaries at
    or under 14.0 GB by the chip compiler's count with 16 of 64 experts
    held in eight layers; two attention kernels a layer at 28 query
    heads over 4 (the forward once: each layer is recomputed, but its
    checkpoint keeps the kernel's output and lse), the
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
    step, cell, arch = built
    mem = step.memory_analysis
    assert 2 * mem["argument"] + mem["temp"] <= 14.0e9
    assert mem["alias"] > 0.99 * mem["argument"]   # the state is donated
    text = step.as_text()
    names = [re_sub_number(k) for k in kernel_instructions(text)]
    layers = arch["num_layers"]
    assert sorted(names) == sorted(
        (["flash_fwd", "flash_bwd_dkv"]
         + ["grouped_mm"] * 6 + [*EXPERTS_BWD, "grouped_mm_bwd_dw"])
        * layers) and layers == 8
    table = executor.hlo_op_scopes(text)
    by_scope = {}
    for inst, scope in table.items():
        if re_sub_number(inst) in ("flash_fwd", "flash_bwd_dq",
                                   "flash_bwd_dkv"):
            by_scope.setdefault(scope, []).append(re_sub_number(inst))
    assert {k: len(v) for k, v in by_scope.items()} \
        == {"attn.window": 6 * 2, "attn.full": 2 * 2}
    assert {"attn", "moe.router", "moe.dispatch", "moe.experts",
            "moe.combine", "head_loss"} <= set(table.values())


def test_kept_attention_runs_forward_once_and_the_experts_twice(built):
    """The compiled step's table of phases: every layer's checkpoint
    keeps its attention kernel's output and lse, so all eight
    ``flash_fwd`` are ``forward`` and none is ``recompute``; every
    ``flash_bwd_dkv`` is ``backward``; each layer's three expert
    matmuls run forward and again under ``recompute`` (ROADMAP S7)."""
    step, _, arch = built
    layers = arch["num_layers"]
    assert phases_of_kernels(step) == {
        "flash_fwd": {"forward": layers},
        "flash_bwd_dkv": {"backward": layers},
        "grouped_mm": {"forward": 3 * layers, "recompute": 3 * layers},
        "grouped_mm_bwd_dh": {"backward": layers},
        "grouped_mm_bwd_dx": {"backward": layers},
        "grouped_mm_bwd_dw": {"backward": 2 * layers}}


def test_the_steps_scopes_by_phase(built):
    from dlnetbench_tpu.metrics import spans
    step, _, _ = built
    scopes, phases = step.op_scopes(), step.op_phases()
    assert set(scopes) == set(phases)
    by_scope = {}
    for inst, scope in scopes.items():
        by_scope.setdefault(scope, set()).add(phases[inst])
    # a window layer's RoPE stands between its projections and the
    # kernel and is run again with them; the kernel is not (above)
    for scope in ("attn", "attn.window", "moe.router", "moe.experts"):
        assert by_scope[scope] == set(spans.PHASES), scope
    assert {"forward", "backward"} <= by_scope["attn.full"]
    assert by_scope["head_loss"] == {"forward", "backward"}
    assert spans.NO_PHASE in by_scope["optimizer"]
