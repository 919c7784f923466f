"""``minicpm_sala_train_s16k``'s whole train step compiles for the chip
and fits it (see ``chip_compile_support.cell_step``)."""
from __future__ import annotations

import math

import pytest
from chip_compile_support import (
    cell_program, cell_step, kernel_instructions, re_sub_number)


def test_sparse_linear_train_step_at_the_cell_shapes_fits_the_chip(
        one_chip, no_persistent_cache):
    """``minicpm_sala_train_s16k``'s whole step (the cell's own files
    and compiler options, as the runner builds it) at the rung the
    configuration file takes: twice the arguments plus the temporaries
    by the chip compiler's count are what that file says of its rung
    (the whole vocabulary: 15.08 GB, over the older cells' 14.0 GB rule
    and inside the chip's 16.9; the file says why it is taken all the
    same); ONE sparse forward kernel and the two backward ones for the
    one sparse layer (the layer is recomputed, but its checkpoint keeps
    the kernel's output, its lse and the lists, so neither the forward
    kernel nor the selection runs again, and the selection ranks its
    scores by counting: no sort under its scope); the lightning
    rule's forward twice and its backward once a lightning layer; each
    under its scope; the state donated."""
    from dlnetbench_tpu.core import executor
    cell, arch, cfg, weights = cell_program("minicpm_sala_train_s16k")
    rungs = cell.config["rungs"]
    taken = next(r for r in rungs.values() if r.get("taken"))
    assert arch["vocab_size"] == taken["vocab_size"]
    assert weights.num_params(arch) == taken["parameters"]
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.ff_dim,
            cfg.embed_dim, cfg.gdn_key_heads, cfg.gdn_key_dim) \
        == (32, 2, 128, 16384, 4096, 32, 128)
    assert cfg.layer_kinds == ("sparse",) + ("lightning",) * 3
    assert cfg.has_selection and cfg.remat and cfg.seq_len == 16384
    step, cell, arch = cell_step("minicpm_sala_train_s16k", one_chip)
    mem = step.memory_analysis
    assert 2 * mem["argument"] + mem["temp"] == pytest.approx(
        taken["rule_bytes"], rel=2e-2) and taken["rule_bytes"] < 15.2e9
    assert (mem["argument"], mem["temp"]) == pytest.approx(
        (taken["argument_bytes"], taken["temp_bytes"]), rel=2e-2)
    # the weights by count (small leaves are padded to their tiles)
    total = sum(math.prod(shape) * (
        4 if name.rsplit("/", 1)[-1] in weights.F32_LEAVES else 2)
        for name, (shape, _) in weights.shapes(arch).items())
    assert mem["argument"] == pytest.approx(total + 4 * 16385, rel=1e-4)
    assert mem["alias"] > 0.99 * mem["argument"]   # the state is donated
    text = step.as_text()
    names = [re_sub_number(k) for k in kernel_instructions(text)]
    assert sorted(names) == sorted(
        ["sparse_fwd", "sparse_bwd_dq", "sparse_bwd_dkv"]
        + ["lightning_fwd", "lightning_fwd", "lightning_bwd"] * 3)
    table = executor.hlo_op_scopes(text)
    by_kernel = {re_sub_number(i): s for i, s in table.items()
                 if re_sub_number(i) in set(names)}
    assert by_kernel == {"sparse_fwd": "attn.sparse",
                         "sparse_bwd_dq": "attn.sparse",
                         "sparse_bwd_dkv": "attn.sparse",
                         "lightning_fwd": "linattn.rule",
                         "lightning_bwd": "linattn.rule"}
    # the sparse layer's sorts are the visit lists' (``plan_visits``)
    sorts = {s for i, s in table.items() if re_sub_number(i) == "sort"}
    assert "attn.sparse" in sorts and "attn.select" not in sorts
    assert {"attn", "attn.select", "attn.sparse", "linattn", "linattn.rule",
            "mlp", "head_loss", "embed", "optimizer"} <= set(table.values())
