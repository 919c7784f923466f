"""AOT execution engine: compile once, donate everything, measure clean.

Every step-building path (the L4 proxies, ``models/bench_step.py`` via
``bench.py``, the sweep driver) routes its jitted programs through this
module instead of calling ``jax.jit`` and letting the first timed call
pay for tracing + compilation.  Three properties fall out:

1. **Compilation can never leak into measurement.**  Each program is
   lowered and compiled ahead of time (``jit(fn).lower(...).compile()``)
   at *build* time, with the wall cost recorded as ``compile_ms`` in the
   bundle's ``global_meta`` (and split by phase, with the cache's
   verdict and the code's size, in the build's record: ``builds()``) —
   so ``warmup_times_us`` (and therefore
   ``estimate_runs``, the reference's ``-m`` min-exectime logic) see
   only execution.  The compiled executable also yields XLA's
   ``cost_analysis`` (FLOPs / bytes accessed — cross-checkable against
   the schedule algebra's ``comm_model`` byte declarations) and
   ``memory_analysis`` (argument/output/temp/alias bytes), both stamped
   into the metadata channel the emitter already carries.

2. **Donation without footguns.**  Proxy steps carry a burn state and
   gradient/shard buffers through every iteration; donating them
   (``donate_argnums``) lets XLA update in place instead of emitting a
   fresh output allocation + copy per step.  A donated jax buffer is
   *deleted* after the call, so the engine rebinds each donated
   argument to the structurally-matching output before the next call —
   callers keep the zero-arg ``bundle.full()`` interface and never see
   a dead buffer.  The output<->argument pairing is computed from
   ``jax.eval_shape`` *before* compilation; a requested donation whose
   leaves have no shape/dtype-matching output is dropped (and recorded
   in the meta as ``undonated``) rather than left to XLA to warn about.

3. **Warm-start re-runs.**  The entry points (``cli.py``, ``bench.py``,
   ``sweep.py``, ``chip_smoke.py``) call ``enable_persistent_cache``
   before their first compile: jax's persistent compilation cache at
   ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at one fixed
   path inside the checkout (size/compile-time thresholds zeroed so
   every program is eligible), so a re-run of a sweep — each grid point
   a fresh process — deserializes executables instead of recompiling.
   The directory is part of the cache key, which is why it is never a
   temporary name; importing the package sets nothing.
"""
from __future__ import annotations

import dataclasses
import os
import re
import threading
import time
from collections.abc import Callable
from pathlib import Path

import jax

from dlnetbench_tpu.metrics import spans
from dlnetbench_tpu.utils.timing import process_age_s

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# where the cache lives when the environment does not place it: one
# fixed path inside the checkout (listed in .gitignore)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# Donation kill-switch.  Each donated program owns a PRIVATE clone of
# its donated buffers (sibling programs must survive the donation), so
# a bundle with full/compute/comm step programs holds up to 3 carry
# sets where the pre-AOT path shared 1.  At dev scales that is noise;
# at --size_scale 1 on a real chip it can be the OOM margin (bench.py's
# r5 history) — DLNB_NO_DONATION=1 restores the shared-buffer,
# copy-per-step behavior without touching any call site.
ENV_NO_DONATION = "DLNB_NO_DONATION"


def enable_persistent_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` where the environment
    sets it (jax reads that variable itself, so no directory is set in
    code), else ``DEFAULT_CACHE_DIR``.  Idempotent.

    Thresholds are zeroed so even fast-compiling CPU-mesh programs are
    cached — the sweep acceptance case is a 3-config CPU sweep whose
    per-point compiles are hundreds of ms, under jax's 1 s default
    minimum."""
    from jax.experimental.compilation_cache import compilation_cache

    changed = False
    cache_dir = os.environ.get(ENV_CACHE_DIR) or str(DEFAULT_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != cache_dir:
        # never true where the variable was set before jax was imported
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        changed = True
    if jax.config.jax_persistent_cache_min_compile_time_secs != 0:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        changed = True
    if changed:
        # jax latches its cache-enabled decision at the FIRST compile of
        # the process; buffer allocation usually compiles before an
        # entry point gets here, so force a re-evaluation under the new
        # config or the whole run silently skips the cache
        compilation_cache.reset_cache()
    return cache_dir


# --------------------------------------------------------------------
# From compiled HLO text to {instruction name: scope} and {instruction
# name: phase}: the join between what a device trace prints first in
# each event's name (``%fusion.54 = ...``) and the ``jax.named_scope``
# the model wore where that work was written (``spans.SCOPES``), and the
# pass of the step that runs it (``spans.PHASES``).

_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_HLO_OPCODE = re.compile(r"\s(fusion|dot|convolution)\(")
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_LOOP = re.compile(r"\swhile\(.*\bcondition=%?([\w.\-]+), "
                       r"body=%?([\w.\-]+)")
_HLO_BRANCHES = re.compile(
    r"\sconditional\(.*\b(?:branch_computations=\{([^}]*)\}"
    r"|true_computation=(\S+), false_computation=(\S+))")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_TRANSFORM = re.compile(r"(\w+)\((.*)\)")
_REMATTED = "rematted_computation"


def _path_parts(op_name: str):
    """The parts of an ``op_name`` path from the innermost out, each as
    (the transforms around it, what they wrap): ``transpose(jvp(attn))``
    is ``(["transpose", "jvp"], "attn")``, the scope transformed;
    ``jit(attn)`` is a function that happens to be called so and wraps
    nothing.  The path is split at ``/`` alone: where the compiler
    joined two paths with ``;``, the part that holds the ``;`` is
    neither's."""
    for part in reversed(op_name.split("/")):
        transforms = []
        while (m := _HLO_TRANSFORM.fullmatch(part)) and m.group(1) != "jit":
            transforms.append(m.group(1))
            part = m.group(2)
        yield transforms, part


def scope_of_op_name(op_name: str, vocabulary=spans.SCOPES) -> str | None:
    """The innermost vocabulary scope of an ``op_name`` path, with the
    transforms peeled off (``jit(f)/transpose(jvp(attn))/dot_general``
    -> ``attn``), so that forward and backward of a layer land together
    (``phase_of_op_name`` tells them apart); None where the path holds
    none."""
    for _, part in _path_parts(op_name):
        if part in vocabulary:
            return part
    return None


def phase_of_op_name(op_name: str) -> str | None:
    """The pass of a train step an ``op_name`` path was traced in, one
    of ``spans.PHASES``: ``recompute`` where the path holds a
    ``rematted_computation`` part (``jax.checkpoint``'s forward run
    again inside the backward pass); else ``backward`` where a part is
    a ``transpose(...)``; else ``forward`` where a part is a
    ``jvp(...)``; else None (the optimizer's updates, whatever was not
    differentiated).  It is what the path says: a ``custom_vjp`` whose
    forward rule also makes gradients (the fused head) is ``forward``
    whole."""
    found = None
    for transforms, part in _path_parts(op_name):
        if part == _REMATTED:
            return "recompute"
        if "transpose" in transforms:
            found = "backward"
        elif "jvp" in transforms and found is None:
            found = "forward"
    return found


def hlo_module_name(hlo_text: str) -> str:
    m = re.match(r"HloModule\s+([\w.\-]+)", hlo_text)
    return m.group(1) if m else ""


@dataclasses.dataclass
class _HloText:
    """What a table needs of a compiled module's text, from one pass
    over it."""
    op_name: dict[str, str | None]  # instruction -> its op_name, in
    #                                 the text's order
    where: dict[str, str | None]    # instruction -> its computation
    matmuls: set[str]               # the dots and convolutions
    fusions: dict[str, str]         # fusion instruction -> callee
    loop_of: dict[str, str]         # body or condition -> its while,
    #                                 a branch -> its conditional
    branches: dict[str, list]       # conditional -> its branches


def _read_hlo(hlo_text: str) -> _HloText:
    text = _HloText({}, {}, set(), {}, {}, {})
    comp = None
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            c = _HLO_COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        inst = m.group(1)
        head, _, meta = line.partition(", metadata={")
        name = _HLO_OP_NAME.search(meta)
        text.op_name[inst] = name.group(1) if name else None
        text.where[inst] = comp
        loop = _HLO_LOOP.search(head)
        if loop:
            text.loop_of.update(dict.fromkeys(loop.groups(), inst))
        cond = _HLO_BRANCHES.search(head)
        if cond:
            names = re.findall(r"[\w.\-]+", " ".join(filter(None,
                                                            cond.groups())))
            text.branches[inst] = names
            text.loop_of.update(dict.fromkeys(names, inst))
        op = _HLO_OPCODE.search(head)
        kind = op.group(1) if op else None
        if kind == "fusion":
            callee = _HLO_CALLS.search(head)
            if callee:
                text.fusions[inst] = callee.group(1)
        elif kind is not None:
            text.matmuls.add(inst)
    return text


def _labelled(text: _HloText, label, default: str) -> dict[str, str]:
    """{instruction name: ``label(its op_name)``} under the rules
    ``hlo_op_scopes`` states; ``default`` where they find none."""
    read: dict[str, str | None] = {}    # op_name -> its label
    own: dict[str, str | None] = {}     # instruction -> label or None
    matmul: dict[str, str] = {}         # computation -> its dot's label
    root: dict[str, str] = {}           # computation -> its root's label
    for inst, name in text.op_name.items():
        if name is not None and name not in read:
            read[name] = label(name)
        found = own[inst] = read.get(name)
        comp = text.where[inst]
        if found and comp is not None:
            if inst in text.matmuls:
                matmul.setdefault(comp, found)
            # text order is a topological order and the root comes last
            root[comp] = found
    for inst, callee in text.fusions.items():
        own[inst] = matmul.get(callee) or root.get(callee) or own[inst]
    for inst, names in text.branches.items():
        own[inst] = own[inst] or next(
            (root[b] for b in names if b in root), None)

    def resolved(inst):
        """``inst``'s label, or that of the innermost loop around it
        that has one."""
        while inst is not None and own[inst] is None:
            inst = text.loop_of.get(text.where[inst])
        return own[inst] if inst is not None else None
    return {k: resolved(k) or default for k in own}


def _scopes(text: _HloText, vocabulary) -> dict[str, str]:
    return _labelled(text, lambda name: scope_of_op_name(name, vocabulary),
                     spans.OTHER_SCOPE)


def _phases(text: _HloText) -> dict[str, str]:
    return _labelled(text, phase_of_op_name, spans.NO_PHASE)


def hlo_op_scopes(hlo_text: str, vocabulary=spans.SCOPES) -> dict[str, str]:
    """{instruction name: scope} of every instruction of every
    computation of a compiled module's text.

    An instruction's scope is that of its own ``op_name``.  A fusion
    mixes instructions of several scopes (a weight gradient with the
    SGD update fused in, a residual add riding with the next norm), so
    its rule is fixed here: the scope of the ``dot``/``convolution``
    inside the computation it calls where that has one, else of that
    computation's root (where the root carries none, being a tuple of
    outputs or a bitcast, of the last instruction before it that does),
    else its own.  A custom call (a Pallas kernel) keeps its own.  What
    still has none inside a loop's body or condition (the copies and
    slices the compiler adds to prefetch a loop's operands carry no
    ``op_name``) takes the loop's: the trace holds the loop's event
    and its body's, and time inside a loop belongs to one scope.  A
    conditional is read as a loop is, its branches as the body; one
    that the compiler rebuilt (an operation moved into or out of every
    branch) has lost its ``op_name`` and takes its branches' scope.
    ``spans.OTHER_SCOPE`` where there is none."""
    return _scopes(_read_hlo(hlo_text), vocabulary)


def hlo_op_phases(hlo_text: str) -> dict[str, str]:
    """{instruction name: phase} of every instruction of every
    computation of a compiled module's text: ``phase_of_op_name`` under
    the rules ``hlo_op_scopes`` fixes for scopes.  A fusion takes the
    phase of the ``dot``/``convolution`` it calls, else of its callee's
    root, else its own (a recomputed product fused into a backward
    matmul is ``backward``); a custom call keeps its own; what has none
    inside a loop's body or condition or a conditional's branches takes
    the loop's.  ``spans.NO_PHASE`` where there is none."""
    return _phases(_read_hlo(hlo_text))


def hlo_op_tables(hlo_text: str) -> tuple[dict[str, str], dict[str, str]]:
    """``(hlo_op_scopes, hlo_op_phases)`` of a text from one pass over
    it."""
    text = _read_hlo(hlo_text)
    return _scopes(text, spans.SCOPES), _phases(text)


# --------------------------------------------------------------------
# The build record: what one build cost and where, kept whether or not a
# tracer is on (a build happens once; the step's path pays nothing).

_BUILDS: list[dict] = []        # every build of this process, in order
_BUILDING = threading.local()   # .record: the build whose executable
#                                 this thread is making right now
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_DURATION_EVENTS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s"}
_listening = False


def builds() -> tuple[dict, ...]:
    """The record of every program this process has built through the
    executor, in build order (``_Compiled._build`` says what one
    holds); each is also its program's ``stats["build"]``."""
    return tuple(_BUILDS)


def _on_event(event: str, **_) -> None:
    record = getattr(_BUILDING, "record", None)
    if record is not None and event in _CACHE_EVENTS:
        record["cache"] = _CACHE_EVENTS[event]


def _on_duration(event: str, duration: float, **_) -> None:
    record = getattr(_BUILDING, "record", None)
    if record is not None and event in _DURATION_EVENTS:
        record[_DURATION_EVENTS[event]] += duration


def _listen() -> None:
    """jax's monitoring events reach the build in progress through one
    pair of listeners, installed at the process's first build."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


class _Compiled:
    """What ``CompiledProgram`` and ``CompiledStep`` share: the build
    and its record, XLA's analyses of the executable, its text, and the
    op->scope and op->phase tables made from that text — computed when
    asked and kept, never at build when tracing is off."""

    _compiled = None
    _op_scopes = None
    _op_phases = None
    stats: dict

    def _build(self, fn: Callable, args, donate: tuple,
               compiler_options: dict | None, plan=None):
        """Trace, lower and compile ``fn`` ahead of time; returns the
        lowering and the argnums donated.  ``plan(lowered)`` may drop
        donations, and the function is then lowered again without them.

        Fills ``stats`` and, in it and in ``builds()``, the build's
        record: ``fn``, ``module`` (the name a device trace prints),
        ``began_at_s`` (the process's age when the build began),
        ``trace_s`` (Python to jaxpr), ``lower_s`` (jaxpr to StableHLO,
        the Pallas kernels' Mosaic lowering included; a second lowering
        adds into both), ``executable_s`` (``lowered.compile`` whole:
        the key, then the persistent cache's retrieval or the backend's
        compile and the cache's write), ``cache`` (``"hit"``,
        ``"miss"`` or ``"off"`` for this build) with
        ``cache_retrieval_s`` and ``backend_compile_s`` as jax's own
        events give them, ``code_bytes`` (the executable's generated
        code, None where the backend does not say), ``op_scopes_s``
        (the executable's text and the op->scope and op->phase tables
        from it: what tracing itself costs a build, 0.0 with no tracer)
        and
        ``analyses_s`` (XLA's cost analysis, after the build).  With a
        tracer on, the ``compile`` span wears the phases, the cache's
        verdict and the code's size as attrs: a train run's spans are
        its builds and nothing else, so a build's inside is no span."""
        clock = time.perf_counter
        name = getattr(fn, "__name__", type(fn).__name__)
        record = {"fn": name, "module": "", "began_at_s": process_age_s(),
                  "trace_s": 0.0, "lower_s": 0.0, "executable_s": 0.0,
                  "cache": "off", "cache_retrieval_s": 0.0,
                  "backend_compile_s": 0.0, "code_bytes": None,
                  "op_scopes_s": 0.0, "analyses_s": 0.0}
        _listen()

        def lower(donate):
            jitted = jax.jit(fn, donate_argnums=donate)
            t0 = clock()
            traced = jitted.trace(*args)
            t1 = clock()
            lowered = traced.lower()
            record["trace_s"] += t1 - t0
            record["lower_s"] += clock() - t1
            return lowered

        began = clock()
        with spans.span("compile", fn=name) as whole:
            # one trace covers both lowering and donation planning: the
            # plan needs only output shapes/dtypes, which
            # ``lowered.out_info`` already carries — a separate
            # eval_shape pass would re-trace every program (tracing
            # these unrolled pipeline bodies costs as much as compiling
            # them warm)
            lowered = lower(donate)
            if plan is not None and (kept := plan(lowered)) != donate:
                # some requested donations have no output to rebind from
                # (mode/schedule-dependent dummies): re-lower with only
                # the kept set — the dropped buffers must NOT be
                # invalidated
                donate = kept
                lowered = lower(donate)
            record["module"] = lowered.compiler_ir().operation.attributes[
                "sym_name"].value
            t0 = clock()
            _BUILDING.record = record
            try:
                self._compiled = lowered.compile(compiler_options)
            finally:
                _BUILDING.record = None
            record["executable_s"] = clock() - t0
            if record["cache"] == "hit":
                # jax times the retrieval under its compile event too
                record["backend_compile_s"] = 0.0
            memory = _memory_analysis(self._compiled)
            record["code_bytes"] = memory.get("generated_code")
            if spans.is_enabled():
                t0 = clock()
                self._register_op_scopes()
                record["op_scopes_s"] = clock() - t0
                whole.attrs.update({k: record[k] for k in (
                    "cache", "code_bytes", "trace_s", "lower_s",
                    "executable_s", "op_scopes_s")})
        t0 = clock()
        self.stats = {"compile_ms": round((t0 - began) * 1e3, 3),
                      "donated_argnums": list(donate), "build": record}
        if cost := _cost_analysis(self._compiled):
            self.stats["cost_analysis"] = cost
        if memory:
            self.stats["memory_analysis"] = memory
        record["analyses_s"] = clock() - t0
        _BUILDS.append(record)
        return lowered, donate

    # per-program cost stats as first-class attributes (not just the
    # global_meta channel compile_programs writes): the attribution
    # engine joins a program's OWN flops/bytes with its OWN timers —
    # e.g. bench.py's chained microbenches, which never go through
    # compile_programs
    @property
    def cost_analysis(self) -> dict | None:
        """XLA's {flops, bytes_accessed} for THIS executable, or None
        when the backend implements no cost analysis."""
        return self.stats.get("cost_analysis")

    @property
    def memory_analysis(self) -> dict | None:
        return self.stats.get("memory_analysis")

    def as_text(self) -> str:
        """The compiled HLO: what will run, Pallas kernels included
        (each appears as a ``tpu_custom_call``)."""
        return self._compiled.as_text()

    def op_scopes(self) -> dict[str, str]:
        """{instruction name: ``spans.SCOPES`` name or "other"} of the
        compiled program (``hlo_op_scopes``).

        Read from the executable's own text, so an executable loaded
        from the persistent cache gives the scopes it was compiled
        with: jax leaves ``op_name`` metadata out of the cache key, and
        an edit that only moves a ``spans.scope`` finds the old
        executable and the old table, with no warning.  Clear the cache
        directory (``enable_persistent_cache``) after such an edit."""
        if self._op_scopes is None:
            self._read_tables(self.as_text())
        return self._op_scopes

    def op_phases(self) -> dict[str, str]:
        """{instruction name: ``spans.PHASES`` name or "none"} of the
        compiled program (``hlo_op_phases``), made with ``op_scopes``
        from one reading of the text.

        Read from the executable's own text, as ``op_scopes`` is: an
        executable loaded from the persistent cache gives the phases it
        was compiled with, and an edit that only moves a
        ``jax.checkpoint`` or what its policy keeps may find the old
        executable and the old table, with no warning."""
        if self._op_phases is None:
            self._read_tables(self.as_text())
        return self._op_phases

    def _read_tables(self, text: str) -> None:
        self._op_scopes, self._op_phases = hlo_op_tables(text)

    def _register_op_scopes(self) -> None:
        """Hand both tables to the current tracer, keyed by the module's
        name as a device trace prints it."""
        text = self.as_text()
        self._read_tables(text)
        spans.current().register_op_scopes(
            hlo_module_name(text), self._op_scopes, self._op_phases)


@dataclasses.dataclass
class Program:
    """One jittable callable plus the concrete buffers it runs on.

    ``donate_argnums`` names top-level positional args whose buffers the
    engine may donate; the engine only donates an argnum when every one
    of its leaves has a shape/dtype-matching output leaf to rebind from
    (otherwise the donation is dropped and listed in the compile record
    as ``undonated``).
    """
    fn: Callable
    args: tuple
    donate_argnums: tuple = ()
    compiler_options: dict | None = None


class CompiledProgram(_Compiled):
    """A zero-arg callable around an AOT-compiled executable.

    Owns the argument buffers: after each call, donated arguments are
    rebound to their paired outputs so the next call never touches a
    deleted buffer.  ``stats`` carries compile_ms / cost_analysis /
    memory_analysis / donation bookkeeping for the metadata channel.
    """

    def __init__(self, program: Program):
        # the traceable python callable, kept for structural analyses
        # (metrics/profiling.py re-traces it to a jaxpr — the compiled
        # executable is opaque to make_jaxpr)
        self.traceable = program.fn
        args = list(program.args)
        requested = (() if os.environ.get(ENV_NO_DONATION)
                     else tuple(program.donate_argnums))

        undonated: list = []

        def plan(lowered):
            donate, self._rebind, undonated[:] = _plan_donation(
                jax.tree.leaves(lowered.out_info), args, requested)
            return donate

        _, donate = self._build(program.fn, args, requested,
                                program.compiler_options, plan)

        # donation consumes the buffer, and sibling programs (full /
        # compute / comm share the proxy's buffers) must stay callable:
        # every donated argument gets a private device-side copy
        # (structurally identical to the original, so the executable
        # lowered above accepts it)
        with spans.span("donate-clone", argnums=list(donate)):
            for argnum in donate:
                args[argnum] = _clone(args[argnum])
        self._args = args
        self._treedef = jax.tree.structure(tuple(args))

        if undonated:
            self.stats["undonated"] = undonated

    @property
    def example_args(self) -> tuple:
        """The program's current argument buffers (for re-tracing)."""
        return tuple(self._args)

    def __call__(self):
        outs = self._compiled(*self._args)
        if self._rebind:
            # the rebind is host-side pytree bookkeeping inside the hot
            # loop — span-tagged so a traced run shows its cost on the
            # timeline, gated on is_enabled so an untraced timed rep
            # pays nothing here (same discipline as timing._fence)
            if spans.is_enabled():
                with spans.span("rebind", pairs=len(self._rebind)):
                    self._do_rebind(outs)
            else:
                self._do_rebind(outs)
        return outs

    def _do_rebind(self, outs) -> None:
        flat_out = jax.tree.leaves(outs)
        flat_args = jax.tree.leaves(tuple(self._args))
        for arg_i, out_i in self._rebind:
            flat_args[arg_i] = flat_out[out_i]
        self._args = list(jax.tree.unflatten(self._treedef, flat_args))


class CompiledStep(_Compiled):
    """An AOT-compiled callable that still takes per-call arguments.

    ``CompiledProgram`` owns fixed buffers and exposes a zero-arg
    callable — right for the proxy schedules, whose every iteration is
    identical.  A serving decode step is not: tokens, positions and
    block tables change every engine step while the weights and KV page
    pools persist.  ``CompiledStep`` keeps the engine's AOT contract —
    compile at build time (``compile_ms``/``cost_analysis``/
    ``memory_analysis`` recorded, persistent cache honored), never
    inside a measured window — but leaves argument passing to the
    caller.

    ``donate_argnums`` are honored WITHOUT the private-clone rebinding
    machinery: the caller owns the donated buffers and must rebind them
    from the outputs itself (the serving engine threads its page pools
    functionally, so that is its natural shape anyway).  Arguments must
    match the example args' shapes/dtypes exactly — AOT executables
    don't re-trace.
    """

    def __init__(self, fn: Callable, example_args: tuple,
                 donate_argnums: tuple = (),
                 compiler_options: dict | None = None):
        self.traceable = fn
        donate = (() if os.environ.get(ENV_NO_DONATION)
                  else tuple(donate_argnums))
        lowered, _ = self._build(fn, example_args, donate,
                                 compiler_options)
        # abstract output leaves (shape/dtype), kept so subclasses can
        # validate structural contracts (CompiledLoop's carry check)
        # without re-tracing
        self.out_info = lowered.out_info

    def __call__(self, *args):
        return self._compiled(*args)


class CompiledLoop(CompiledStep):
    """The FOURTH executor shape (ISSUE 11): a device-resident
    multi-step program whose donated arguments are LOOP CARRIES.

    A fused N-step decode program carries slot state (last tokens,
    positions, active flags, remaining budgets) and the KV page pools
    through every in-loop step and hands them back to the caller only
    at sync boundaries.  Those buffers are donated (``carry_argnums``)
    so XLA updates them in place across the N steps, and the caller
    rebinds each carry from the program's outputs before the next
    call — which only works if the program actually RETURNS its
    carries as the LEADING outputs, in argument order, shape/dtype
    matched.  ``CompiledStep`` leaves a donation without a matching
    output to an XLA warning; for a loop program that mistake hands
    the caller a dead buffer at the second sync, so construction here
    validates the carry contract and fails loud.

    ``num_carry_outputs`` is the split point: ``outs[:n]`` are the
    updated carries (rebind them), ``outs[n:]`` the per-sync results
    (token blocks, counts, loop-trip stats)."""

    def __init__(self, fn: Callable, example_args: tuple,
                 carry_argnums: tuple,
                 compiler_options: dict | None = None):
        carry_argnums = tuple(carry_argnums)
        if len(set(carry_argnums)) != len(carry_argnums) or any(
                b <= a for a, b in zip(carry_argnums,
                                       carry_argnums[1:])):
            # the rebind walk below pairs carries with leading outputs
            # IN ARGNUM ORDER — an out-of-order or repeated argnum
            # would silently pair the wrong buffers (shape-compatible
            # carries, e.g. two [6, slots] int32 blocks, would pass
            # the structural check and corrupt state at the rebind)
            raise ValueError(
                f"CompiledLoop: carry_argnums must be strictly "
                f"increasing and unique, got {carry_argnums}")
        super().__init__(fn, example_args,
                         donate_argnums=carry_argnums,
                         compiler_options=compiler_options)
        self.carry_argnums = carry_argnums
        out_leaves = jax.tree.leaves(self.out_info)
        pos = 0
        for argnum in self.carry_argnums:
            for leaf in jax.tree.leaves(example_args[argnum]):
                if pos >= len(out_leaves):
                    raise ValueError(
                        f"CompiledLoop: carry argnum {argnum} has no "
                        f"output to rebind from — the loop program "
                        f"must return its carries first, in argument "
                        f"order ({len(out_leaves)} outputs total)")
                o = out_leaves[pos]
                if o.shape != leaf.shape or o.dtype != leaf.dtype:
                    raise ValueError(
                        f"CompiledLoop: carry argnum {argnum} "
                        f"(leaf {leaf.shape}/{leaf.dtype}) does not "
                        f"match leading output {pos} "
                        f"({o.shape}/{o.dtype}) — a donated carry "
                        f"without a structurally matching output "
                        f"would be a dead buffer at the next sync")
                pos += 1
        self.num_carry_outputs = pos

    def split(self, outs: tuple) -> tuple[tuple, tuple]:
        """(updated carries, per-sync results) from one call's
        outputs."""
        return (tuple(outs[:self.num_carry_outputs]),
                tuple(outs[self.num_carry_outputs:]))


def _clone(tree):
    """Device-side copy of a pytree of jax.Arrays, shardings preserved.
    ``device_put`` with the same sharding short-circuits to the original
    buffer, so the copy goes through a compiled identity-with-copy."""
    shardings = jax.tree.map(lambda a: a.sharding, tree)
    copy = jax.jit(lambda t: jax.tree.map(jax.numpy.copy, t),
                   out_shardings=shardings)
    return copy(tree)


def _plan_donation(out_leaves, args, donate_argnums):
    """(kept argnums, flat arg-index -> flat out-index rebind pairs,
    dropped argnums) — computed from the lowering's abstract output
    leaves (anything with ``.shape``/``.dtype``), before compile."""
    if not donate_argnums:
        return (), [], []
    out_taken = [False] * len(out_leaves)

    # flat index range of each top-level argument
    arg_leaf_ranges = []
    pos = 0
    for a in args:
        n = len(jax.tree.leaves(a))
        arg_leaf_ranges.append((pos, pos + n))
        pos += n
    flat_args = jax.tree.leaves(tuple(args))

    keep, rebind, dropped = [], [], []
    for argnum in donate_argnums:
        lo, hi = arg_leaf_ranges[argnum]
        pairs = []
        taken_here: set[int] = set()

        def free(j):
            return not out_taken[j] and j not in taken_here

        for i in range(lo, hi):
            a = flat_args[i]
            # positional preference first: when the step returns its
            # carries in argument order (every proxy step and the bench
            # scan do), flat position i pairs with output i — this keeps
            # equal-shaped sibling leaves (param tensors, double-buffered
            # activations) wired to THEIR updated value instead of a
            # same-shaped neighbor's
            if (i < len(out_leaves) and free(i)
                    and out_leaves[i].shape == a.shape
                    and out_leaves[i].dtype == a.dtype):
                match = i
            else:
                match = next(
                    (j for j, o in enumerate(out_leaves)
                     if free(j) and o.shape == a.shape
                     and o.dtype == a.dtype), None)
            if match is None:
                break
            pairs.append((i, match))
            taken_here.add(match)
        # all-or-nothing per argnum: donate_argnums is top-level, so a
        # partially-rebindable argument cannot be donated at all
        if len(pairs) == hi - lo:
            for _, j in pairs:
                out_taken[j] = True
            rebind.extend(pairs)
            keep.append(argnum)
        else:
            dropped.append(argnum)
    return tuple(keep), rebind, dropped


def _cost_analysis(compiled) -> dict:
    """XLA's {flops, bytes_accessed} of an executable, JSON-ready; an
    analysis a backend doesn't implement is simply absent, never fatal."""
    cost = {}
    try:
        ca = compiled.cost_analysis()
        props = ca[0] if isinstance(ca, (list, tuple)) and ca else ca
        if isinstance(props, dict):
            if "flops" in props:
                cost["flops"] = float(props["flops"])
            ba = [float(v) for k, v in props.items()
                  if k.startswith("bytes accessed")]
            if ba:
                cost["bytes_accessed"] = max(ba)
    except Exception:
        pass
    return cost


def _memory_analysis(compiled) -> dict:
    """XLA's byte counts of an executable (arguments, outputs,
    temporaries, aliased, generated code), absent as above."""
    try:
        ma = compiled.memory_analysis()
        return {k: int(getattr(ma, f"{k}_size_in_bytes"))
                for k in ("argument", "output", "temp", "alias",
                          "generated_code")
                if hasattr(ma, f"{k}_size_in_bytes")}
    except Exception:
        return {}


def compile_programs(programs: dict[str, Program],
                     global_meta: dict | None = None
                     ) -> dict[str, CompiledProgram]:
    """AOT-compile a named set of programs, recording per-program
    ``compile_ms`` (plus analyses under ``aot``) into ``global_meta`` —
    the record every proxy's emitter already serializes, which is how
    compile time ships *separate from* ``runtimes``."""
    compiled = {name: CompiledProgram(prog)
                for name, prog in programs.items()}
    if global_meta is not None:
        global_meta["compile_ms"] = {
            name: c.stats["compile_ms"] for name, c in compiled.items()}
        global_meta["aot"] = {
            name: {k: v for k, v in c.stats.items() if k != "compile_ms"}
            for name, c in compiled.items()}
        cache_dir = jax.config.jax_compilation_cache_dir
        if cache_dir and jax.config.jax_enable_compilation_cache:
            global_meta["compile_cache_dir"] = cache_dir
    return compiled
