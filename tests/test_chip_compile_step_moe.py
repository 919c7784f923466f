"""``mixtral8x7b_train``'s whole train step compiles for the chip and
fits it (see ``chip_compile_support.cell_step``)."""
from __future__ import annotations

import re

from chip_compile_support import cell_step, kernel_instructions, kernels_in


def test_moe_train_step_at_the_cell_shapes_fits_the_chip(
        one_chip, no_persistent_cache):
    """``mixtral8x7b_train``'s whole step (one layer, 8 experts, B=2 x
    S=4096, the cell's own files and compiler options), as the runner
    builds it: arguments and temporaries fit the chip's 15.75 GiB, and
    of the expert backward's ``[E, C, F]`` float32 arrays only ``dh``
    is left: ``g`` and ``u`` are read as the forward's kernels wrote
    them, in bf16.  ``auto`` attention asks the backend, the CPU here,
    so the test names the kernels the chip would pick."""
    from dlnetbench_tpu.models import moe
    step, cell, arch = cell_step("mixtral8x7b_train", one_chip)
    tr = cell.traffic
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
