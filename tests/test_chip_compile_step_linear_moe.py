"""``qwen3next_a3b_train_s16k``'s train step, one layer of each kind,
compiles for the chip (see ``chip_compile_support.cell_step``)."""
from __future__ import annotations

import pytest
from chip_compile_support import (
    EXPERTS_BWD, cell_program, cell_step, chunk_arrays,
    kernel_instructions, phases_of_kernels, re_sub_number)


@pytest.fixture(scope="module")
def built(one_chip, no_persistent_cache):
    """One compile for the file's cases."""
    return cell_step("qwen3next_a3b_train_s16k", one_chip)


def test_linear_moe_train_step_at_the_cell_shapes_compiles_for_the_chip(
        built):
    """``qwen3next_a3b_train_s16k``'s step as the runner builds it (the
    cell's own files, widths, sequence, bound and compiler options, the
    sweeps that "auto" takes on the chip), cut to one layer of each kind
    so that it compiles in a minute: the kernels by name (the rule's
    forward kernel once a linear layer and its backward once: the
    checkpoint keeps the forward's three arrays,
    ``ops/gated_delta_rule.KEPT_NAMES``; three
    attention kernels a full layer (the forward twice: at 256 lanes a
    head the checkpoint does not keep the kernel's output yet,
    ``ops/flash_attention._kept``), six grouped matmuls a layer and the
    four kernels of its counted backward), no
    loop but the head's (none around the rule: all heads go through one
    call), no chunk matrix but what the rule's kernels write, the state
    donated, and the temporaries under what let 32 held experts keep
    the 13.0 GB rule.  The whole step's count in the configuration file
    is PR 32's (2 x 2.341 + 5.912 = 10.59 GB); ISSUE 51 reckoned the
    three kept layers at 537 MB each on top of it, 2 x 2.341 + 5.912 +
    1.61 = 12.20 GB; the compiler, asked here for the whole cell (PR 51,
    a count and no chip run), reads temporaries of 5.312 GB with the
    rule kept and 5.475 with it recomputed, 2 x 2.341 + 5.312 = 9.99 GB
    against 10.16: the recomputation's forward held more beside the
    backward than the three arrays a layer that now live from the
    forward on (cut to one layer of each kind: 3.38 against 4.71)."""
    from dlnetbench_tpu.core import executor
    step, cell, arch = built
    assert arch["layer_kinds"] == ("gdn", "gated")
    cfg = cell_program("qwen3next_a3b_train_s16k")[2]
    mem = step.memory_analysis
    assert mem["alias"] > 0.99 * mem["argument"]   # the state is donated
    # 3.38 GB read, PR 51 (4.71 with the rule recomputed; 5.39, PR 33)
    assert mem["temp"] <= 3.6e9
    text = step.as_text()
    names = kernel_instructions(text)
    assert sorted(re_sub_number(k) for k in names) == sorted(
        ["gdr_fwd", "gdr_bwd"] + ["flash_fwd"] * 2
        + ["flash_bwd_dkv"] + ["grouped_mm"] * 12
        + [*EXPERTS_BWD, "grouped_mm_bwd_dw"] * 2)
    table = executor.hlo_op_scopes(text)
    loops = [m.group(1) for line in text.splitlines() if " while(" in line
             and (m := executor._HLO_INSTRUCTION.match(line))]
    assert [table[w] for w in loops] == ["head_loss"]
    made = chunk_arrays(text, cfg.gdn_value_heads,
                        cell.traffic["seq_len"] // 128)
    assert set().union(*made.values()) <= {"get-tuple-element", "bitcast"}


def test_the_kept_rule_runs_forward_once_and_the_unkept_attention_twice(
        built):
    """The compiled step's table of phases (``executor.hlo_op_phases``,
    on the text the chip's compiler wrote): the ``gdn`` layer's
    checkpoint keeps the rule's three arrays, so its ``gdr_fwd`` is
    ``forward`` and there is none under ``recompute``; the ``gated``
    layer's does not keep the attention kernel's output at 256 lanes
    (ROADMAP S7), so ``flash_fwd`` stands once in each; every backward
    kernel is ``backward``, and each layer's three expert matmuls run
    forward and again."""
    step, _, _ = built
    assert phases_of_kernels(step) == {
        "gdr_fwd": {"forward": 1},
        "gdr_bwd": {"backward": 1},
        "flash_fwd": {"forward": 1, "recompute": 1},
        "flash_bwd_dkv": {"backward": 1},
        "grouped_mm": {"forward": 6, "recompute": 6},
        "grouped_mm_bwd_dh": {"backward": 2},
        "grouped_mm_bwd_dx": {"backward": 2},
        "grouped_mm_bwd_dw": {"backward": 4}}


def test_every_phase_and_scope_of_the_step_is_in_its_tables(built):
    """Both tables from one reading of the text hold the same
    instructions; the head's loop and what the compiler put inside it
    are ``forward`` (the fused head makes its gradients in its forward
    rule), the update has no phase."""
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.metrics import spans
    step, _, _ = built
    scopes, phases = step.op_scopes(), step.op_phases()
    assert set(scopes) == set(phases)
    assert set(phases.values()) == {*spans.PHASES, spans.NO_PHASE}
    loops = [m.group(1) for line in step.as_text().splitlines()
             if " while(" in line
             and (m := executor._HLO_INSTRUCTION.match(line))]
    assert [(scopes[w], phases[w]) for w in loops] \
        == [("head_loss", "forward")]
    assert {phases[i] for i in scopes if scopes[i] == "head_loss"} \
        == {"forward", "backward"}
    by_scope = {}
    for inst, scope in scopes.items():
        by_scope.setdefault(scope, set()).add(phases[inst])
    for scope in ("linattn", "attn", "moe.router", "moe.experts",
                  "moe.shared"):
        assert by_scope[scope] == set(spans.PHASES), scope
    assert by_scope["linattn.rule"] == {"forward", "backward"}
    assert spans.NO_PHASE in by_scope["optimizer"]
    assert "recompute" not in by_scope["optimizer"] | by_scope["embed"]
