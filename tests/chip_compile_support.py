"""What the ``tests/test_chip_compile_*.py`` files share: the fixtures
that describe a ``v5e:2x2`` and compile for one of its chips (the TPU
compiler is installed where no TPU is attached), the readers of the
compiled text, and ``cell_step``, the one build of a benchmark cell's
whole train step for that chip.

Interpret mode (what every other kernel test runs) accepts programs
the TPU lowering refuses (a block that is not a multiple of the (8, 128)
tile, too much VMEM), so these compiles are what guards a kernel between
chip runs.  A compile that passes is not a chip run: ``chip_smoke.py``
executes the same kernels against their references.

The files are split by what they compile (attention, experts, the other
kernels, one whole step a file) so that ``--dist loadfile`` can spread
them over workers.  Several processes may then describe the topology at
once, which the TPU's library allows only under
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``: ``tests/conftest.py`` sets it, and a
lockfile error in ``topo`` fails the case instead of skipping it.  The
topology is described here and nowhere else, inside a fixture, so that
importing a test file (every xdist worker imports every one) never loads
that library (``tests/test_guard_imports.py`` holds this), and the
compiles run in the test's own process, not in a child.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# llama3_8b widths (dlnetbench_tpu/data/models/llama3_8b.json)
D, F, HQ, HKV, DH = 4096, 14336, 32, 8, 128
TOKENS = 12288          # B=2 x S=6144, the bench step's token count
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
QDTYPE = {"int8": jnp.int8, "float8": jnp.float8_e4m3fn}

QKV = [((2, 6144, HQ, DH), BF16), ((2, 6144, HKV, DH), BF16),
       ((2, 6144, HKV, DH), BF16)]
QKV_LONG = [((1, 16384, HQ, DH), BF16), ((1, 16384, HKV, DH), BF16),
            ((1, 16384, HKV, DH), BF16)]

# the kernels of ``moe_held``'s counted backward, by name: one dh (with
# the SwiGLU epilogue) and one dx a layer, and two contraction-side
# calls (dW_down; dW_gate with dW_up)
EXPERTS_BWD = ("grouped_mm_bwd_dh", "grouped_mm_bwd_dx", "grouped_mm_bwd_dw")


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler is installed here: nothing to ask")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def mosaic_mode():
    """The kernels in Mosaic mode while the block runs
    (``pallas_common.interpret_mode`` would say "CPU" here, and every
    kernel file reads it through the module)."""
    from dlnetbench_tpu.ops import pallas_common
    return mock.patch.object(pallas_common, "interpret_mode", lambda: False)


@pytest.fixture
def for_chip(one_chip, no_persistent_cache):
    """``compile_for_chip(fn, *shapes)``: the compiled text of ``fn`` at
    ``(shape, dtype)`` arguments on one described chip, with the kernels
    in Mosaic mode."""
    def compile_for_chip(fn, *shapes) -> str:
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    with mosaic_mode():
        yield compile_for_chip


# a cell's step as its runner builds it: the weights' module and the
# runner's (of ``benchmarks`` and ``benchmarks.runners``), the overrides
# that name what "auto" takes on the chip (it asks the backend, the CPU
# here), and a cut of the configuration file where the whole is too long
CELL_BUILD = {
    "mixtral8x7b_train": ("weights", "train", {"attention_impl": "flash"},
                          {}),
    "kimivl_a3b_train_s8k": ("weights_latent_moe", "train_latent_moe",
                             {"attention_impl": "flash"}, {}),
    # one layer of each kind, so that it compiles in a minute
    "qwen3next_a3b_train_s16k": (
        "weights_linear_moe", "train_linear_moe",
        {"attention_impl": "flash", "rule_impl": "pallas"},
        {"num_hidden_layers": 2, "full_attention_interval": 2}),
    "lfm2_8b_a1b_train_s8k": ("weights_conv_moe", "train_conv_moe",
                              {"attention_impl": "flash"}, {}),
    "smallthinker_21b_a3b_train_s16k": ("weights_swa_moe", "train_swa_moe",
                                        {"attention_impl": "flash"}, {}),
    "laguna_s21_train_s16k": ("weights_headgate_moe", "train_headgate_moe",
                              {"attention_impl": "flash"}, {}),
    "minicpm_sala_train_s16k": (
        "weights_sparse_linear", "train_sparse_linear",
        {"attention_impl": "flash", "rule_impl": "pallas"}, {}),
}


def cell_program(cell_name: str):
    """``(cell, arch, cfg, weights)`` of a cell of ``CELL_BUILD``: the
    cell's own files (cut as the table says), the sizes its weights'
    module reads from them, the program's configuration as its runner
    makes it, and that weights' module."""
    from benchmarks import harness
    weights_name, runner_name, overrides, cut = CELL_BUILD[cell_name]
    weights = importlib.import_module(f"benchmarks.{weights_name}")
    runner = importlib.import_module(f"benchmarks.runners.{runner_name}")
    cell = harness.load_cell(cell_name)
    if cut:
        cell = dataclasses.replace(cell, config={**cell.config, **cut})
    wl = cell.workload
    arch = weights.arch_of(cell.config, **{
        k: wl[k] for k in ("capacity_factor",) if k in wl})
    return cell, arch, runner.program_config(cell, arch, overrides), weights


def cell_step(cell_name: str, one_chip):
    """``(step, cell, arch)``: the whole train step of a benchmark cell
    compiled for one described chip as the cell's runner builds it (the
    cell's own files and compiler options, one step a call, the state
    donated), from the weights' shapes alone."""
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step
    cell, arch, cfg, weights = cell_program(cell_name)
    wl, tr = cell.workload, cell.traffic

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(
        on_chip, jax.eval_shape(lambda: weights.make_params(arch, 0)))
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"] + 1), I32,
                                  sharding=one_chip)
    with mosaic_mode():
        step = executor.CompiledStep(
            bench_step.make_train_k(cfg, 1, wl["lr"]), (params, tokens),
            donate_argnums=bench_step.DONATE_ARGNUMS,
            compiler_options=wl["compiler_options"])
    return step, cell, arch


def ops_module(name: str):
    """``dlnetbench_tpu.ops.<name>`` the module: the package re-exports
    a function ``flash_attention`` that shadows its submodule."""
    return importlib.import_module(f"dlnetbench_tpu.ops.{name}")


def kernels_in(text: str) -> int:
    return text.count("tpu_custom_call")


def grad_of(attn):
    return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v).astype(F32)),
                    argnums=(0, 1, 2))


def re_sub_number(name: str) -> str:
    return re.sub(r"\.\d+$", "", name)


def kernel_instructions(text: str) -> list:
    """The names of the Pallas custom calls, as a device trace prints
    them first in each event's name."""
    from dlnetbench_tpu.core import executor
    return [m.group(1) for line in text.splitlines()
            if "tpu_custom_call" in line
            and (m := executor._HLO_INSTRUCTION.match(line))]


def phases_of_kernels(step) -> dict:
    """{Pallas kernel's name without its number: {phase: how many of
    its calls}} by the compiled step's own table of phases."""
    phases = step.op_phases()
    found: dict = {}
    for inst in kernel_instructions(step.as_text()):
        row = found.setdefault(re_sub_number(inst), {})
        row[phases[inst]] = row.get(phases[inst], 0) + 1
    return found


def scopes_by_opcode(text: str, opcodes: str, keep) -> dict:
    """{opcode: the scopes its instructions lie under}, over the
    instructions of ``text`` whose opcode is one of ``opcodes`` (a
    regex alternation) and that ``keep(line, scope)`` takes."""
    from dlnetbench_tpu.core import executor
    table = executor.hlo_op_scopes(text)
    opcode = re.compile(rf"\s({opcodes})\(")
    found = {}
    for line in text.splitlines():
        m = executor._HLO_INSTRUCTION.match(line)
        op = opcode.search(line.partition(", metadata=")[0])
        if m and op and keep(line, table[m.group(1)]):
            found.setdefault(op.group(1), set()).add(table[m.group(1)])
    return found


def chunk_arrays(text: str, h: int, nc: int) -> dict:
    """{shape: opcodes of the instructions that make it} for every
    array ``[1, h, nc, ...]`` of five dimensions: a matrix a head and
    chunk."""
    made = {}
    shape = re.compile(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+\[1," + f"{h},{nc}"
                       + r",\d+,\d+\])\S* ([\w\-]+)\(")
    for line in text.splitlines():
        if m := shape.match(line):
            made.setdefault(m.group(1), set()).add(m.group(2))
    return made


def hlo_computations(text: str) -> dict:
    """{computation name: its lines} of a compiled module's text."""
    comps, lines = {}, None
    for line in text.splitlines():
        if m := re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line):
            lines = comps.setdefault(m.group(1), [])
        elif lines is not None:
            lines.append(line.partition(", metadata=")[0])
    return comps
