"""The op->scope table (core/executor.py ``hlo_op_scopes``) and the
scopes the model step wears (``spans.SCOPES``): every matmul of a tiny
dense and a tiny grouped-MoE train step lands in a vocabulary scope,
forward and backward of a layer together; the rule for fusions; the
table is made when asked or when a tracer is on, never otherwise."""
from __future__ import annotations

import importlib
import json
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness, scope_dump
from benchmarks.readers import scope_ms
from dlnetbench_tpu.core import executor
from dlnetbench_tpu.core.model_card import ModelCard, MoEParams
from dlnetbench_tpu.metrics import spans
from dlnetbench_tpu.models import bench_step, transformer as tfm


@pytest.fixture(autouse=True)
def _clean_tracer():
    spans.disable()
    yield
    spans.disable()


def tiny_step(moe: bool, **over):
    card = ModelCard(
        name="tiny", embed_dim=32, num_heads=4, num_kv_heads=2, ff_dim=64,
        seq_len=16, num_decoder_blocks=2, vocab_size=64, gated_mlp=True,
        moe_params=MoEParams(4, 2) if moe else None)
    if moe:
        over.setdefault("moe_impl", "grouped")
    cfg = bench_step.bench_cfg(card, dtype="float32", **over)
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 17), 0, 64)
    return executor.CompiledStep(
        bench_step.make_train_k(cfg, 1, 0.1), (params, tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS)


@pytest.fixture(scope="module")
def steps():
    return {"dense": tiny_step(False), "moe": tiny_step(True)}


WORK = re.compile(r"\s(dot|convolution|custom-call)\(")


def instructions(text, pattern=WORK):
    """[(name, op_name)] of the instructions whose opcode matches."""
    out = []
    for line in text.splitlines():
        m = executor._HLO_INSTRUCTION.match(line)
        head, _, meta = line.partition(", metadata={")
        if m and pattern.search(head):
            name = executor._HLO_OP_NAME.search(meta)
            out.append((m.group(1), name.group(1) if name else ""))
    return out


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_every_matmul_and_custom_call_has_a_vocabulary_scope(steps, kind):
    step = steps[kind]
    table = step.op_scopes()
    work = instructions(step.as_text())
    assert len(work) >= 10
    assert {table[n] for n, _ in work} <= set(spans.SCOPES)
    want = {"attn", "head_loss"} | (
        {"mlp"} if kind == "dense" else {"moe.router", "moe.experts"})
    assert want <= {table[n] for n, _ in work}


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_table_names_the_scopes_of_the_step(steps, kind):
    got = set(steps[kind].op_scopes().values())
    block = ({"mlp"} if kind == "dense" else
             {"moe.router", "moe.dispatch", "moe.experts", "moe.combine"})
    assert {"embed", "attn", "head_loss", "optimizer"} | block <= got
    assert got <= set(spans.SCOPES) | {spans.OTHER_SCOPE}
    assert ("mlp" in got) == (kind == "dense")


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_forward_and_backward_of_a_layer_share_a_scope(steps, kind):
    step = steps[kind]
    table = step.op_scopes()
    layer = "mlp" if kind == "dense" else "moe.experts"
    for scope in ("attn", layer, "head_loss"):
        mine = [op for n, op in instructions(step.as_text())
                if table[n] == scope]
        assert any(f"/jvp({scope})/" in op for op in mine), scope
        assert any(f"transpose(jvp({scope}))" in op for op in mine), scope


def test_moe_dispatch_and_combine_gather_rows_and_multiply_nothing(steps):
    """The routing is a plan of indices: under ``moe.dispatch`` and
    ``moe.combine``, forward and backward, no instruction is a matmul
    (and so no fusion calls one: a fusion takes its dot's scope) or a
    scatter, and each gathers; the step's scatters are those of the
    embedding, the loss's target pick and the router's top-k, as
    before; all four MoE scopes are still in the table."""
    step = steps["moe"]
    text, table = step.as_text(), step.op_scopes()
    route = {"moe.dispatch", "moe.combine"}

    def scopes_of(opcodes):
        found = instructions(text, re.compile(rf"\s({opcodes})\("))
        return [table[n] for n, _ in found]
    assert route & set(scopes_of("dot|convolution|custom-call")) == set()
    assert set(scopes_of("dot|convolution")) >= {"moe.router",
                                                 "moe.experts"}
    assert set(scopes_of("scatter")) <= {"embed", "head_loss",
                                         "moe.router"}
    assert route <= set(scopes_of("gather"))
    assert route | {"moe.router", "moe.experts"} <= set(table.values())
    for scope in route:     # the hand-written backward wears it too
        ops = [op for n, op in instructions(text, re.compile(
            r"\sgather\(")) if table[n] == scope]
        assert any(f"transpose(jvp({scope}))" in op for op in ops), scope


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_k)/while/body/closed_call/jvp(attn)/dot_general", "attn"),
    ("jit(train_k)/transpose(jvp(attn))/flash_bwd_dkv/pallas_call",
     "attn"),
    ("jit(f)/jvp(moe.router)/inner/tanh", "moe.router"),
    ("jit(f)/jvp(moe.combine)/moe.experts/dot_general", "moe.experts"),
    ("jit(f)/transpose(jvp(mlp))/jvp(mlp)/checkpoint/dot_general", "mlp"),
    ("jit(f)/optimizer/sub", "optimizer"),
    # the gated convolution's backward, which wears the inner scope once
    # more, and its layer's recomputed projections
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/conv/conv.gate/conv.gate/mul",
     "conv.gate"),
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/conv/"
     "dot_general", "conv"),
    # a window or a full layer's kernel call inside its attention layer
    ("jit(f)/jvp(attn)/attn.window/pallas_call", "attn.window"),
    ("jit(f)/transpose(jvp(attn))/attn.full/reduce_sum", "attn.full"),
    # the gate a head, beside the kernel call inside the same layer
    ("jit(f)/jvp(attn)/attn.gate/logistic", "attn.gate"),
    ("jit(f)/transpose(jvp(attn))/checkpoint/attn/attn.gate/dot_general",
     "attn.gate"),
    ("jit(attn)/add", None),    # a function's name is not a scope
    ("jit(f)/attention/add", None),
    ("", None)])
def test_scope_of_op_name(op_name, want):
    assert executor.scope_of_op_name(op_name) == want


SNIPPET = '''HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %convolution.3 = f32[8,8]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp(moe.experts))/dot_general" stack_frame_id=1}
  ROOT %subtract.4 = f32[8,8]{1,0} subtract(%p1, %convolution.3), metadata={op_name="jit(step)/optimizer/sub" stack_frame_id=2}
}

%fused_computation.2 (p0.1: f32[8,8]) -> (f32[8,8], f32[8]) {
  %p0.1 = f32[8,8]{1,0} parameter(0)
  %add.5 = f32[8,8]{1,0} add(%p0.1, %p0.1), metadata={op_name="jit(step)/jvp(attn)/add"}
  %multiply.6 = f32[8,8]{1,0} multiply(%add.5, %add.5), metadata={op_name="jit(step)/jvp(mlp)/mul"}
  %reduce.7 = f32[8]{0} reduce(%multiply.6, %p0.1), dimensions={1}, metadata={op_name="jit(step)/jvp(mlp)/reduce_sum"}
  ROOT %tuple.8 = (f32[8,8]{1,0}, f32[8]{0}) tuple(%multiply.6, %reduce.7)
}

%fused_computation.3 (p0.2: f32[8,8]) -> f32[8,8] {
  %p0.2 = f32[8,8]{1,0} parameter(0)
  ROOT %copy.9 = f32[8,8]{0,1} copy(%p0.2)
}

ENTRY %main.10 (a: f32[8,8], b: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0), metadata={op_name="a"}
  %b = f32[8,8]{1,0} parameter(1), metadata={op_name="b"}
  %fusion.1 = f32[8,8]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/optimizer/sub"}
  %fusion.2 = (f32[8,8]{1,0}, f32[8]{0}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8,8]{0,1} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/embed/transpose"}
  %fusion.4 = f32[8,8]{0,1} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.3
  %flash_fwd.11 = f32[8,8]{1,0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn)/flash_fwd/pallas_call"}
  %custom-call.12 = f32[8,8]{1,0} custom-call(%fusion.3), custom_call_target="ConcatBitcast"
  ROOT %copy.13 = f32[8,8]{1,0} copy(%flash_fwd.11)
}
'''


@pytest.mark.parametrize("instruction,want,why", [
    ("fusion.1", "moe.experts", "the dot inside, not the root or itself"),
    ("fusion.2", "mlp", "no dot, the root a bare tuple: the last before"),
    ("fusion.3", "embed", "nothing inside says: its own"),
    ("fusion.4", "other", "nothing anywhere"),
    ("flash_fwd.11", "attn", "a custom call keeps its own"),
    ("custom-call.12", "other", "the compiler's, no metadata"),
    ("convolution.3", "moe.experts", "inside a fusion: its own"),
    ("subtract.4", "optimizer", "inside a fusion: its own"),
    ("a", "other", "a parameter")])
def test_fusion_rule_on_a_written_module(instruction, want, why):
    assert executor.hlo_op_scopes(SNIPPET)[instruction] == want, why


def test_module_name_of_a_text():
    assert executor.hlo_module_name(SNIPPET) == "jit_step"
    assert executor.hlo_module_name("nothing") == ""


def count_as_text(monkeypatch):
    calls = []
    real = executor._Compiled.as_text

    def as_text(self):
        calls.append(self)
        return real(self)
    monkeypatch.setattr(executor._Compiled, "as_text", as_text)
    return calls


def small():
    return executor.CompiledStep(lambda x: x * 2.0, (jnp.ones((4,)),))


def test_table_is_not_made_at_build_with_tracing_off(monkeypatch):
    calls = count_as_text(monkeypatch)
    step = small()
    assert not calls and step._op_scopes is None
    step(jnp.ones((4,)))
    assert not calls
    table = step.op_scopes()
    assert table and len(calls) == 1
    assert step.op_scopes() is table and len(calls) == 1    # kept


def test_tracer_is_handed_the_table_inside_the_compile_span():
    tracer = spans.enable()

    def doubled(x):
        with spans.scope("mlp"):
            return x * 2.0
    step = executor.CompiledStep(doubled, (jnp.ones((4,)),))
    prog = executor.CompiledProgram(
        executor.Program(doubled, (jnp.ones((4,)),)))
    spans.disable()
    assert tracer.op_scopes["jit_doubled"] == step.op_scopes() \
        == prog.op_scopes()
    assert "mlp" in step.op_scopes().values()
    assert [s["attrs"]["fn"] for s in tracer.spans
            if s["name"] == "compile"] == ["doubled", "doubled"]
    got = tracer.export()
    assert got["op_scopes"] == tracer.op_scopes
    assert got["spans"] == tracer.spans and got["spans"] is not tracer.spans


def test_a_call_opens_no_span_of_its_own():
    """Tracing on or off, a call is the executable's and nothing else:
    a dispatch gets its name from the caller that has a reader for it
    (the serving engine calls a step for every decode and prefill)."""
    step = small()
    tracer = spans.enable()
    out = step(jnp.ones((4,)))
    spans.disable()
    assert float(out[0]) == 2.0
    assert tracer.spans == [] and tracer.op_scopes == {}


def test_an_unscoped_instruction_inside_a_loop_takes_the_loops_scope():
    """The compiler's own prefetch copies inside a loop's body carry no
    ``op_name``: they take the scope of the innermost loop around them
    that has one, so that a loop's time lies under one scope; outside
    a loop they stay ``other``."""
    text = """HloModule jit_f

%body.1 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %copy-start.3 = (f32[4], f32[4], u32[]) copy-start(f32[4] %x)
  %copy-done.3 = f32[4] copy-done(%copy-start.3)
  %mul.1 = f32[4] multiply(%copy-done.3, %copy-done.3), metadata={op_name="jit(f)/linattn/linattn.rule/mul"}
  %inner.2 = (s32[], f32[4]) while(%p), condition=%cond.2, body=%body.2
}

%body.2 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %slice-done.5 = f32[4] slice-done(%s)
  %add.7 = f32[4] add(%a, %b), metadata={op_name="jit(f)/attn/add"}
}

%cond.1 (p: (s32[], f32[4])) -> pred[] {
  %compare.9 = pred[] compare(%a, %b), direction=LT
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %copy.1 = f32[4] copy(%x)
  %while.8 = (s32[], f32[4]) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(f)/linattn/linattn.rule/while"}
}
"""
    table = executor.hlo_op_scopes(text)
    assert table["while.8"] == table["mul.1"] == "linattn.rule"
    assert table["copy-start.3"] == table["copy-done.3"] == "linattn.rule"
    assert table["compare.9"] == "linattn.rule"        # the condition's
    # a loop with no scope of its own, inside one that has: the outer's
    assert table["inner.2"] == table["slice-done.5"] == "linattn.rule"
    assert table["add.7"] == "attn"                    # its own stays
    assert table["copy.1"] == spans.OTHER_SCOPE        # outside any loop


def test_a_conditional_is_read_as_a_loop_and_takes_its_branches_scope():
    """A conditional that the compiler rebuilt (an operation moved into
    or out of every branch) carries no ``op_name``: it takes its
    branches' scope, and what has none inside a branch takes the
    conditional's; one with a scope of its own keeps it."""
    text = """HloModule jit_f

%region_1.1 (p: (f32[4])) -> (f32[4]) {
  %copy.2 = f32[4] copy(%x)
  %fusion.5 = f32[4] fusion(%copy.2), kind=kCustom, calls=%scatter_comp, metadata={op_name="jit(f)/transpose(jvp(moe.dispatch))/scatter-add"}
  ROOT %tuple.1 = (f32[4]) tuple(%fusion.5)
}

%region_2.2 (p: (f32[4])) -> (f32[4]) {
  %copy.3 = f32[4] copy(%x)
  %fusion.6 = f32[4] fusion(%copy.3), kind=kCustom, calls=%scatter_comp, metadata={op_name="jit(f)/transpose(jvp(moe.dispatch))/scatter-add"}
  ROOT %tuple.2 = (f32[4]) tuple(%fusion.6)
}

%then.3 (p: (f32[4])) -> (f32[4]) {
  %copy.4 = f32[4] copy(%x)
  ROOT %add.9 = f32[4] add(%copy.4, %copy.4), metadata={op_name="jit(f)/attn/add"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %conditional.4 = (f32[4]) conditional(%i, %t.1, %t.2), branch_computations={%region_1.1, %region_2.2}, backend_config={"flag_configs":[]}
  %cond.7 = (f32[4]) conditional(%i, %t.1, %t.2), branch_computations={%region_1.1, %region_2.2}, metadata={op_name="jit(f)/jvp(moe.combine)/cond"}
  %conditional.8 = f32[4] conditional(%b, %x, %x), true_computation=%then.3, false_computation=%then.3
  %copy.1 = f32[4] copy(%x)
}
"""
    table = executor.hlo_op_scopes(text)
    assert table["conditional.4"] == "moe.dispatch"    # its branches'
    assert table["cond.7"] == "moe.combine"            # its own stays
    assert table["conditional.8"] == table["copy.4"] == "attn"
    assert table["fusion.5"] == "moe.dispatch"
    # a branch belongs to the conditional named last, as a body would
    assert table["copy.2"] == table["copy.3"] == "moe.combine"
    assert table["copy.1"] == spans.OTHER_SCOPE


_LOOP_ATTRS = re.compile(r", condition=%?[\w.\-]+, body=%?[\w.\-]+")


@pytest.mark.parametrize("runner,cls,name", [
    ("train_hybrid", "HybridCell", "phi4miniflash_train_s8k"),
    ("train_latent_moe", "LatentMoeCell", "kimivl_a3b_train_s8k"),
    ("train_linear_moe", "LinearMoeCell", "qwen3next_a3b_train_s16k"),
    ("train_conv_moe", "ConvMoeCell", "lfm2_8b_a1b_train_s8k"),
    ("train_swa_moe", "SwaMoeCell", "smallthinker_21b_a3b_train_s16k"),
    ("train_headgate_moe", "HeadgateMoeCell", "laguna_s21_train_s16k")])
def test_the_loop_rule_moves_only_what_had_no_scope(runner, cls, name):
    """The six ``models/hybrid.py`` cells' steps at their rehearsal
    sizes, the table with the loop rule against the table without it
    (the same text with the loops' ``condition=`` and ``body=`` taken
    off, which is what ``hlo_op_scopes`` read before it knew loops):
    an instruction that had a scope keeps it; one that moves had none,
    lies inside a loop, and takes the scope the loop (or a loop around
    it) had already, so the union of a scope's device time, which holds
    the loop's own event, holds what it held."""
    import importlib

    from benchmarks import harness
    module = importlib.import_module(f"benchmarks.runners.{runner}")
    cell = harness.rehearsal(harness.load_cell(name))
    text = getattr(module, cls)(cell, 5, lambda o: None).step.as_text()
    new = executor.hlo_op_scopes(text)
    old = executor.hlo_op_scopes(_LOOP_ATTRS.sub("", text))
    assert set(old) == set(new)
    moved = {k for k in new if new[k] != old[k]}
    assert moved and {old[k] for k in moved} == {spans.OTHER_SCOPE}
    # every loop of the step, by the computations it runs
    loops, where, comp = {}, {}, None
    for line in text.splitlines():
        m = executor._HLO_INSTRUCTION.match(line)
        if not m:
            c = executor._HLO_COMPUTATION.match(line)
            comp = c.group(1) if c else comp
            continue
        where[m.group(1)] = comp
        loop = executor._HLO_LOOP.search(line)
        if loop:
            loops.update(dict.fromkeys(loop.groups(), m.group(1)))
    assert len(set(loops.values())) >= 2
    for k in moved:
        around, scopes = loops.get(where[k]), []
        while around is not None:
            scopes.append(old[around])
            around = loops.get(where[around])
        scoped = [s for s in scopes if s != spans.OTHER_SCOPE]
        assert scoped and new[k] == scoped[0], (k, scopes)
    # and the loops themselves stay where they were
    assert all(new[w] == old[w] or old[w] == spans.OTHER_SCOPE
               for w in loops.values())


def test_scope_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="spans.SCOPES"):
        spans.scope("attention")
    assert len(set(spans.SCOPES)) == len(spans.SCOPES) == 22
    assert spans.OTHER_SCOPE not in spans.SCOPES


# ---------------------------------------- a step with no dq kernel
# Where a head's dq is resident in the dk/dv kernel (PR 43) a step holds
# no ``flash_bwd_dq`` instruction.  The benchmark's readers and fixtures
# (``benchmarks/scope_fixture_*.json``) stay as they are; the same
# step without that kernel is derived here.

def read(metric, c):
    s = harness.load_json(harness.HERE / "layer_metrics" / f"{metric}.json")
    return importlib.import_module(
        f"benchmarks.readers.{s['reader']}").read(c, s["params"])


ATTENTION_FIXTURES = {
    # fixture: (its attention scopes, its roofline metrics)
    "scope_fixture_latent_moe.json": (("attn",), ("mla_flash_roofline",)),
    "scope_fixture_linear_moe.json": (("attn",), ("gated_flash_roofline",)),
    "scope_fixture_conv_moe.json": (("attn",), ("gqa64_flash_roofline",)),
    "scope_fixture_swa_moe.json": (("attn.window", "attn.full"),
                                   ("swa_window_roofline",
                                    "swa_full_roofline")),
}


def fixture_ctx(fix, keep=lambda inst: True):
    """A reader's ``ctx`` on a cell's fixture, of the instructions that
    ``keep`` takes, in the trace and in the program's table alike."""
    program_trace = json.loads(json.dumps(fix["program_trace"]))
    for name, table in program_trace["op_scopes"].items():
        program_trace["op_scopes"][name] = {
            inst: scope for inst, scope in table.items() if keep(inst)}
    ops = [tuple(e) for e in fix["ops"]
           if keep(scope_ms.instruction(e[0]) or "")]
    return {"record": {**json.loads(json.dumps(fix["record"])),
                       "program_trace": program_trace},
            "devices": [{"ops": ops,
                         "modules": [tuple(e) for e in fix["modules"]]}],
            "window": tuple(fix["window"]), "peaks": fix["peaks"]}


@pytest.mark.parametrize("name", sorted(ATTENTION_FIXTURES))
def test_a_step_with_no_dq_kernel_keeps_its_attention_time_under_attn(name):
    fix = harness.load_json(harness.HERE / name)
    scopes, rooflines = ATTENTION_FIXTURES[name]
    whole = fixture_ctx(fix)
    fused = fixture_ctx(fix, lambda inst: not inst.startswith("flash_bwd_dq"))
    assert len(fused["devices"][0]["ops"]) < len(whole["devices"][0]["ops"])
    before, after = scope_dump.by_scope(whole), scope_dump.by_scope(fused)
    assert "unknown" not in after and set(after) == set(before)
    dq = {e[0] for e in whole["devices"][0]["ops"]} \
        - {e[0] for e in fused["devices"][0]["ops"]}
    dq_ms = scope_dump.by_op(whole, lambda n: n in dq)[True]
    # the attention scopes lose the dq kernel's time, the others nothing
    assert sum(before[s] - after[s] for s in scopes) == pytest.approx(dq_ms)
    assert all(before[s] - after[s] > 0 for s in scopes)
    assert {s: after[s] for s in after if s not in scopes} == \
        {s: pytest.approx(before[s]) for s in before if s not in scopes}
    # the same work over less time: every share rises, none past 100 %
    for metric in rooflines:
        assert read(metric, whole) < read(metric, fused) < 100.0
