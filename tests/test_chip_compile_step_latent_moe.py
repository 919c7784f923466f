"""``kimivl_a3b_train_s8k``'s whole train step compiles for the chip and
fits it (see ``chip_compile_support.cell_step``)."""
from __future__ import annotations

from chip_compile_support import (
    EXPERTS_BWD, cell_step, kernel_instructions, kernels_in, re_sub_number)


def test_latent_moe_train_step_at_the_cell_shapes_fits_the_chip(
        one_chip, no_persistent_cache):
    """``kimivl_a3b_train_s8k``'s whole step (the cell's own files and
    compiler options, as the runner builds it): the depth rule of the
    configuration file, twice the arguments plus the temporaries at or
    under 13.0 GB by the chip compiler's count; three attention kernels
    a layer (the forward twice: each layer is recomputed; dq from the
    dk/dv kernel), six
    grouped matmuls an expert layer and the four kernels of its
    counted backward; the step's outputs carry the routing."""
    from benchmarks import weights_latent_moe as weights
    step, cell, arch = cell_step("kimivl_a3b_train_s8k", one_chip)
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
