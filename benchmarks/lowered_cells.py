"""The lowered text of each accepted cell's train step, for the check
that a PR which adds a configuration leaves the other cells' programs as
they were.  Not a test (nothing here knows the parent commit): a script
for both sides of the comparison, run without a chip.

    python3 benchmarks/lowered_cells.py write <out dir>     # from a checkout
    python3 benchmarks/lowered_cells.py diff <dir a> <dir b>

``write`` lowers (``jax.jit(make_train_k(...)).lower``, no compile) the
step of every cell of ``CELLS`` at its timed sizes for one chip of a
described ``v5e:2x2``, the kernels in Mosaic mode, from the checkout at
the current directory, one ``<cell>.txt`` each.  A kernel's serialized
body carries the file's path and line numbers, so unpack both sides at
ONE path in turn (``git archive <commit> | tar -x -C <path>``) and run
from there.  ``diff`` counts, a cell, the lines that differ outside a
kernel call (``tpu_custom_call``: its body is on the call's own line)
and inside one; a PR that moves no accepted cell reads 0 outside.
"""
from __future__ import annotations

import importlib
import os
import sys
from pathlib import Path
from unittest import mock

# (the weights' module, the runner's, what "auto" takes on the chip)
CELLS = {
    "minerva7b_train": ("weights", "train", {"attention_impl": "flash"}),
    "mixtral8x7b_train": ("weights", "train", {"attention_impl": "flash"}),
    "phi4miniflash_train_s8k": (
        "weights_hybrid", "train_hybrid",
        {"attention_impl": "flash", "scan_impl": "pallas"}),
    "kimivl_a3b_train_s8k": ("weights_latent_moe", "train_latent_moe",
                             {"attention_impl": "flash"}),
    "qwen3next_a3b_train_s16k": (
        "weights_linear_moe", "train_linear_moe",
        {"attention_impl": "flash", "rule_impl": "pallas"}),
    "lfm2_8b_a1b_train_s8k": ("weights_conv_moe", "train_conv_moe",
                              {"attention_impl": "flash"}),
    "smallthinker_21b_a3b_train_s16k": ("weights_swa_moe", "train_swa_moe",
                                        {"attention_impl": "flash"}),
    "laguna_s21_train_s16k": ("weights_headgate_moe", "train_headgate_moe",
                              {"attention_impl": "flash"}),
}
KERNEL = "tpu_custom_call"


def write(out: Path) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
    sys.path.insert(0, os.getcwd())
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import harness
    from dlnetbench_tpu.models import bench_step
    from dlnetbench_tpu.ops import pallas_common
    jax.config.update("jax_enable_compilation_cache", False)
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    out.mkdir(parents=True, exist_ok=True)
    for name, (weights_name, runner_name, over) in CELLS.items():
        weights = importlib.import_module(f"benchmarks.{weights_name}")
        runner = importlib.import_module(f"benchmarks.runners.{runner_name}")
        cell = harness.load_cell(name)
        wl, tr = cell.workload, cell.traffic
        arch = weights.arch_of(cell.config, **{
            k: wl[k] for k in ("capacity_factor",) if k in wl})
        cfg = runner.program_config(cell, arch, over)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            jax.eval_shape(lambda: weights.make_params(arch, 0)))
        tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"] + 1),
                                      jnp.int32, sharding=one)
        with mock.patch.object(pallas_common, "interpret_mode",
                               lambda: False):
            text = jax.jit(
                bench_step.make_train_k(cfg, 1, wl["lr"]),
                donate_argnums=bench_step.DONATE_ARGNUMS
            ).lower(params, tokens).as_text()
        (out / f"{name}.txt").write_text(text)
        print(name, len(text), flush=True)


def diff(a: Path, b: Path) -> int:
    """Prints a line a cell; returns the lines that differ outside the
    kernels over all cells (a cell one side lacks counts as one)."""
    outside_all = 0
    for name in CELLS:
        try:
            ta, tb = ((d / f"{name}.txt").read_text().splitlines()
                      for d in (a, b))
        except FileNotFoundError as e:
            print(f"{name}: {e}")
            outside_all += 1
            continue
        pairs = [(x, y) for x, y in zip(ta, tb) if x != y]
        inside = sum(1 for x, y in pairs if KERNEL in x and KERNEL in y)
        outside = len(pairs) - inside + abs(len(ta) - len(tb))
        outside_all += outside
        print(f"{name}: {len(ta)} lines, {outside} differ outside the "
              f"kernels' bodies, {inside} inside")
    return outside_all


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "write":
        write(Path(sys.argv[2]))
    elif len(sys.argv) == 4 and sys.argv[1] == "diff":
        raise SystemExit(1 if diff(Path(sys.argv[2]), Path(sys.argv[3]))
                         else 0)
    else:
        raise SystemExit(__doc__)
