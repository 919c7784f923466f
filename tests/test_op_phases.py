"""The op->phase table (core/executor.py ``hlo_op_phases``): which pass
of a train step runs an instruction, read from its ``op_name`` as the
scope is (``tests/test_op_scopes.py``).  A path's phase; a checkpointed
step on the CPU whose kept value is not made twice; the fusion, loop and
conditional rules on written text; the table is made and exported with
the table of scopes and never without a tracer."""
from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import pytest
from jax.ad_checkpoint import checkpoint_name

from dlnetbench_tpu.core import executor
from dlnetbench_tpu.metrics import spans


@pytest.fixture(autouse=True)
def _clean_tracer():
    spans.disable()
    yield
    spans.disable()


REMAT = "jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation"


@pytest.mark.parametrize("op_name,want", [
    # plain: traced under no transform (the optimizer's update)
    ("jit(train_k)/while/body/closed_call/optimizer/sub", None),
    ("jit(f)/attn/dot_general", None),
    # forward: differentiated, not transposed
    ("jit(train_k)/while/body/closed_call/jvp(attn)/dot_general",
     "forward"),
    ("jit(f)/jvp()/add", "forward"),
    ("jvp(moe.dispatch)/reshape", "forward"),       # no jit(..) before it
    # the fused head makes its gradients in its forward rule: forward
    ("jit(f)/jvp(head_loss)/head_loss/while/body/closed_call/dot_general",
     "forward"),
    # backward
    ("jit(f)/transpose(jvp(attn))/flash_bwd_dkv/pallas_call", "backward"),
    ("jit(f)/transpose(jvp())/add_any", "backward"),
    ("jit(f)/transpose(jvp(mlp))/jvp(mlp)/checkpoint/dot_general",
     "backward"),
    # a checkpointed layer's backward ...
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/attn/flash_bwd_dkv/"
     "pallas_call", "backward"),
    # ... and its forward run again inside it
    (f"{REMAT}/attn/flash_fwd/pallas_call", "recompute"),
    (f"{REMAT}/moe.experts/jit(floor_divide)/select_n", "recompute"),
    ("transpose(jvp(jvp()))/checkpoint/rematted_computation/mlp/mul",
     "recompute"),
    # jit(..) is a function's name, whatever it is called
    ("jit(transpose)/add", None),
    ("jit(jvp)/jit(rematted_computation)/add", None),
    ("jit(f)/jvp(attn)/jit(transpose)/mul", "forward"),
    # two paths joined: split at "/" alone, as the scope's reading is
    ("jit(f)/jvp(moe.dispatch)/reshape;jvp(moe.dispatch)/transpose",
     "forward"),
    (f"{REMAT}/moe.dispatch/reshape;transpose(jvp(jvp()))/checkpoint/"
     "rematted_computation/moe.dispatch/transpose", "recompute"),
    ("", None)])
def test_phase_of_op_name(op_name, want):
    assert executor.phase_of_op_name(op_name) == want
    assert want is None or want in spans.PHASES


@pytest.mark.parametrize("op_name,scope,phase", [
    (f"{REMAT}/conv/dot_general", "conv", "recompute"),
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/conv/conv.gate/conv.gate/mul",
     "conv.gate", "backward"),
    ("jit(f)/jvp(moe.combine)/moe.experts/dot_general", "moe.experts",
     "forward"),
    ("jit(f)/optimizer/sub", "optimizer", None)])
def test_scope_and_phase_come_from_one_reading_of_the_path(op_name, scope,
                                                           phase):
    assert executor.scope_of_op_name(op_name) == scope
    assert executor.phase_of_op_name(op_name) == phase


def test_phases_are_three_names_and_none_is_not_one():
    assert spans.PHASES == ("forward", "recompute", "backward")
    assert spans.NO_PHASE == "none" not in spans.PHASES


# ------------------------------------------- a checkpointed step, CPU
@jax.custom_vjp
def kernel(x, w):
    return jnp.tanh(x @ w)


def _kernel_fwd(x, w):
    out = checkpoint_name(jnp.tanh(x @ w), "kept_out")
    return out, (x, w, out)


def _kernel_bwd(res, g):
    x, w, out = res
    d = g * (1 - out * out)
    return d @ w.T, x.T @ d


kernel.defvjp(_kernel_fwd, _kernel_bwd)


def layer(p, x):
    with spans.scope("attn"):
        h = x @ p["proj"]
        with spans.scope("attn.full"):
            h = kernel(h, p["w"])
    with spans.scope("mlp"):
        return x + jnp.sin(h @ p["up"]) @ p["down"]


def checkpointed_step(keep: bool):
    """Two layers, each under ``jax.checkpoint``, around a
    ``custom_vjp`` whose forward rule names its output; ``keep``: the
    checkpoint's policy keeps that name."""
    policy = jax.checkpoint_policies.save_only_these_names(
        *(("kept_out",) if keep else ()))

    def step(params, x):
        def loss(params):
            h = x
            for p in params:
                h = jax.checkpoint(layer, policy=policy)(p, h)
            with spans.scope("head_loss"):
                return jnp.mean(h * h)
        grads = jax.grad(loss)(params)
        with spans.scope("optimizer"):
            return jax.tree.map(lambda a, g: a - 0.1 * g, params, grads)
    key = jax.random.key(0)
    params = [{n: jax.random.normal(jax.random.fold_in(key, 4 * i + j),
                                    (16, 16))
               for j, n in enumerate(("proj", "w", "up", "down"))}
              for i in range(2)]
    return executor.CompiledStep(step, (params, jnp.ones((8, 16))))


@pytest.fixture(scope="module")
def steps():
    return {keep: checkpointed_step(keep) for keep in (True, False)}


def dots(step) -> list:
    """[(scope, phase)] of the step's matmuls."""
    scopes, phases = step.op_scopes(), step.op_phases()
    found = []
    for line in step.as_text().splitlines():
        m = executor._HLO_INSTRUCTION.match(line)
        if m and re.search(r"\sdot\(", line.partition(", metadata={")[0]):
            found.append((scopes[m.group(1)], phases[m.group(1)]))
    return found


@pytest.mark.parametrize("keep", [True, False])
def test_a_checkpointed_step_holds_all_three_phases(steps, keep):
    step = steps[keep]
    phases = step.op_phases()
    assert set(phases) == set(step.op_scopes())
    assert set(phases.values()) == {*spans.PHASES, spans.NO_PHASE}
    got = dots(step)
    for phase in spans.PHASES:
        assert ("mlp", phase) in got
    # the update is no pass of the differentiation
    scopes = step.op_scopes()
    assert {phases[i] for i in phases if scopes[i] == "optimizer"} \
        == {spans.NO_PHASE}


def test_what_a_checkpoint_keeps_is_not_made_again(steps):
    """The kernel's matmul makes the value the policy keeps: it runs
    forward and not again, where the same step without the name in its
    policy runs it a second time."""
    kept, unkept = dots(steps[True]), dots(steps[False])
    assert ("attn.full", "forward") in kept
    assert ("attn.full", "backward") in kept
    assert ("attn.full", "recompute") not in kept
    assert ("attn.full", "recompute") in unkept
    assert ("attn.full", "forward") in unkept


# ------------------------------------------------ the rules, on text
SNIPPET = '''HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %multiply.2 = f32[8,8]{1,0} multiply(%p0, %p0), metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/mlp/mul"}
  %convolution.3 = f32[8,8]{1,0} convolution(%multiply.2, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/mlp/dot_general" stack_frame_id=1}
  ROOT %subtract.4 = f32[8,8]{1,0} subtract(%p1, %convolution.3), metadata={op_name="jit(step)/optimizer/sub" stack_frame_id=2}
}

%fused_computation.2 (p0.1: f32[8,8]) -> (f32[8,8], f32[8]) {
  %p0.1 = f32[8,8]{1,0} parameter(0)
  %add.5 = f32[8,8]{1,0} add(%p0.1, %p0.1), metadata={op_name="jit(step)/jvp(attn)/add"}
  %multiply.6 = f32[8,8]{1,0} multiply(%add.5, %add.5), metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/mlp/mul"}
  %reduce.7 = f32[8]{0} reduce(%multiply.6, %p0.1), dimensions={1}, metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/mlp/reduce_sum"}
  ROOT %tuple.8 = (f32[8,8]{1,0}, f32[8]{0}) tuple(%multiply.6, %reduce.7)
}

%fused_computation.3 (p0.2: f32[8,8]) -> f32[8,8] {
  %p0.2 = f32[8,8]{1,0} parameter(0)
  ROOT %copy.9 = f32[8,8]{0,1} copy(%p0.2)
}

%fused_computation.4 (p0.3: f32[8,8]) -> f32[8,8] {
  %p0.3 = f32[8,8]{1,0} parameter(0)
  ROOT %subtract.14 = f32[8,8]{1,0} subtract(%p0.3, %p0.3), metadata={op_name="jit(step)/optimizer/sub"}
}

ENTRY %main.10 (a: f32[8,8], b: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0), metadata={op_name="a"}
  %b = f32[8,8]{1,0} parameter(1), metadata={op_name="b"}
  %fusion.1 = f32[8,8]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/optimizer/sub"}
  %fusion.2 = (f32[8,8]{1,0}, f32[8]{0}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8,8]{0,1} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/jvp(embed)/transpose"}
  %fusion.4 = f32[8,8]{0,1} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.3
  %fusion.5 = f32[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(step)/optimizer/sub"}
  %flash_fwd.11 = f32[8,8]{1,0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/attn/flash_fwd/pallas_call"}
  %custom-call.12 = f32[8,8]{1,0} custom-call(%fusion.3), custom_call_target="ConcatBitcast"
  ROOT %copy.13 = f32[8,8]{1,0} copy(%flash_fwd.11)
}
'''


@pytest.mark.parametrize("instruction,phase,scope,why", [
    ("fusion.1", "backward", "mlp",
     "the dot inside: a recomputed product fused into a backward matmul "
     "is backward, the update at the root is not asked"),
    ("fusion.2", "recompute", "mlp",
     "no dot, the root a bare tuple: the last before it"),
    ("fusion.3", "forward", "embed", "nothing inside says: its own"),
    ("fusion.4", "none", "other", "nothing anywhere"),
    ("fusion.5", "none", "optimizer", "a scope and no phase: the update"),
    ("flash_fwd.11", "recompute", "attn", "a custom call keeps its own"),
    ("custom-call.12", "none", "other", "the compiler's, no metadata"),
    ("multiply.2", "recompute", "mlp", "inside a fusion: its own"),
    ("subtract.4", "none", "optimizer", "inside a fusion: its own"),
    ("a", "none", "other", "a parameter")])
def test_fusion_rule_on_a_written_module(instruction, phase, scope, why):
    assert executor.hlo_op_phases(SNIPPET)[instruction] == phase, why
    scopes, phases = executor.hlo_op_tables(SNIPPET)
    assert (scopes[instruction], phases[instruction]) == (scope, phase)
    assert scopes == executor.hlo_op_scopes(SNIPPET)


def test_an_unphased_instruction_inside_a_loop_takes_the_loops_phase():
    """The head's loop over row blocks is ``forward`` by its own
    ``op_name`` and so are the compiler's prefetch copies inside it; a
    body's instruction that says otherwise keeps its own; a loop that
    names no phase (the step's own ``while``) gives none."""
    text = """HloModule jit_f

%body.1 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %copy-start.3 = (f32[4], f32[4], u32[]) copy-start(f32[4] %x)
  %copy-done.3 = f32[4] copy-done(%copy-start.3)
  %mul.1 = f32[4] multiply(%copy-done.3, %copy-done.3), metadata={op_name="jit(f)/jvp(head_loss)/head_loss/while/body/mul"}
  %add.2 = f32[4] add(%mul.1, %mul.1), metadata={op_name="jit(f)/transpose(jvp(head_loss))/add_any"}
  %inner.2 = (s32[], f32[4]) while(%p), condition=%cond.2, body=%body.2
}

%body.2 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %slice-done.5 = f32[4] slice-done(%s)
}

%cond.1 (p: (s32[], f32[4])) -> pred[] {
  %compare.9 = pred[] compare(%a, %b), direction=LT
}

%body.3 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %copy.7 = f32[4] copy(%x)
  %sub.8 = f32[4] subtract(%a, %b), metadata={op_name="jit(f)/while/body/closed_call/optimizer/sub"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %copy.1 = f32[4] copy(%x)
  %while.8 = (s32[], f32[4]) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(f)/jvp(head_loss)/head_loss/while"}
  %while.9 = (s32[], f32[4]) while(%t), condition=%cond.3, body=%body.3, metadata={op_name="jit(f)/while"}
}
"""
    scopes, phases = executor.hlo_op_tables(text)
    assert phases["while.8"] == phases["mul.1"] == "forward"
    assert phases["copy-start.3"] == phases["copy-done.3"] == "forward"
    assert phases["compare.9"] == "forward"            # the condition's
    assert phases["inner.2"] == phases["slice-done.5"] == "forward"
    assert phases["add.2"] == "backward"               # its own stays
    assert scopes["add.2"] == scopes["copy-done.3"] == "head_loss"
    assert phases["copy.1"] == spans.NO_PHASE          # outside any loop
    assert phases["while.9"] == phases["copy.7"] == phases["sub.8"] \
        == spans.NO_PHASE
    assert (scopes["copy.7"], scopes["sub.8"]) == ("other", "optimizer")


def test_a_conditional_takes_its_branches_phase_as_it_does_their_scope():
    text = """HloModule jit_f

%region_1.1 (p: (f32[4])) -> (f32[4]) {
  %copy.2 = f32[4] copy(%x)
  %fusion.5 = f32[4] fusion(%copy.2), kind=kCustom, calls=%scatter_comp, metadata={op_name="jit(f)/transpose(jvp(jvp()))/checkpoint/moe.dispatch/moe.dispatch/cond/branch_0_fun/scatter-add"}
  ROOT %tuple.1 = (f32[4]) tuple(%fusion.5)
}

%region_2.2 (p: (f32[4])) -> (f32[4]) {
  %copy.3 = f32[4] copy(%x)
  ROOT %tuple.2 = (f32[4]) tuple(%copy.3)
}

%then.3 (p: (f32[4])) -> (f32[4]) {
  %copy.4 = f32[4] copy(%x)
  ROOT %add.9 = f32[4] add(%copy.4, %copy.4), metadata={op_name="jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/moe.combine/cond/branch_1_fun/add"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %conditional.4 = (f32[4]) conditional(%i, %t.1, %t.2), branch_computations={%region_1.1, %region_2.2}, backend_config={"flag_configs":[]}
  %cond.7 = (f32[4]) conditional(%b, %x, %x), true_computation=%then.3, false_computation=%then.3, metadata={op_name="jit(f)/jvp(moe.combine)/moe.combine/cond"}
  %copy.1 = f32[4] copy(%x)
}
"""
    scopes, phases = executor.hlo_op_tables(text)
    # rebuilt by the compiler, no op_name: its branches'
    assert (scopes["conditional.4"], phases["conditional.4"]) \
        == ("moe.dispatch", "backward")
    assert phases["copy.2"] == phases["copy.3"] == "backward"
    # its own stays, and what has none inside a branch takes it
    assert (scopes["cond.7"], phases["cond.7"]) == ("moe.combine", "forward")
    assert phases["copy.4"] == "forward" and phases["add.9"] == "recompute"
    assert phases["copy.1"] == spans.NO_PHASE


# --------------------------------------------- made when, handed where
def small():
    def doubled(x):
        with spans.scope("mlp"):
            return jax.grad(lambda y: jnp.sum(y * y))(x)
    return executor.CompiledStep(doubled, (jnp.ones((4,)),))


def test_table_is_not_made_at_build_with_tracing_off(monkeypatch):
    calls = []
    real = executor._Compiled.as_text
    monkeypatch.setattr(executor._Compiled, "as_text",
                        lambda self: calls.append(self) or real(self))
    step = small()
    assert not calls and step._op_phases is None and step._op_scopes is None
    assert step.stats["build"]["op_scopes_s"] == 0.0
    phases = step.op_phases()
    assert phases and len(calls) == 1
    # one reading of the text made both, and both are kept
    assert step.op_scopes() and len(calls) == 1
    assert step.op_phases() is phases and set(phases) == set(step.op_scopes())
    assert set(phases.values()) <= {*spans.PHASES, spans.NO_PHASE}


def test_tracer_is_handed_both_tables_and_exports_them():
    untraced = small()
    tracer = spans.enable()
    step = small()
    prog = executor.CompiledProgram(
        executor.Program(lambda x: x * 2.0, (jnp.ones((4,)),)))
    spans.disable()
    assert set(tracer.op_phases) == set(tracer.op_scopes) \
        >= {"jit_doubled"} and len(tracer.op_phases) == 2
    assert tracer.op_phases["jit_doubled"] == step.op_phases()
    assert tracer.op_scopes["jit_doubled"] == step.op_scopes()
    assert step.stats["build"]["op_scopes_s"] > 0
    got = tracer.export()
    assert set(got) == {"spans", "op_scopes", "op_phases"}
    assert json.loads(json.dumps(got)) == got
    assert got["op_phases"] == tracer.op_phases
    got["op_phases"]["jit_doubled"].clear()             # a copy
    assert tracer.op_phases["jit_doubled"]
    # the table of scopes is what it is without the table of phases
    assert got["op_scopes"]["jit_doubled"] == untraced.op_scopes() \
        == executor.hlo_op_scopes(step.as_text())
    assert prog.op_phases() == tracer.op_phases[
        executor.hlo_module_name(prog.as_text())]
    # a build's span is still the only one
    assert {s["name"] for s in got["spans"]} <= {"compile", "donate-clone"}


def test_scopes_registered_alone_export_no_phases():
    tracer = spans.enable()
    tracer.register_op_scopes("jit_f", {"fusion.1": "attn"})
    tracer.register_op_scopes("jit_g", {"fusion.1": "mlp"},
                              {"fusion.1": "backward"})
    spans.disable()
    got = tracer.export()
    assert got["op_scopes"] == {"jit_f": {"fusion.1": "attn"},
                                "jit_g": {"fusion.1": "mlp"}}
    assert got["op_phases"] == {"jit_g": {"fusion.1": "backward"}}
