"""Runner ``train``: one optimizer step a call, through the program's
own step builder (``models/bench_step.make_train_k``) compiled ahead of
time by ``core/executor.CompiledStep`` with the state donated; the
window keeps the workload's ``steps_in_flight`` of them queued.

Set-up builds ONE object (the compiled step and its state), drives it
from the seed through its first steps on the window's own feed, and
hands the same object to the window.  Those first steps are what
``correct`` compares with the plain reference, after the window has
closed and the program's state is freed.
"""
from __future__ import annotations

import collections
import gc
import math
import time

from benchmarks import harness, reference, stats, weights


def program_config(cell, arch, extra: dict | None = None):
    """The program's configuration of this cell: a card from the
    configuration file's sizes, the bench recipe, the workload's
    overrides (and ``extra``: a control's switch)."""
    from dlnetbench_tpu.core.model_card import ModelCard, MoEParams
    from dlnetbench_tpu.models import bench_step
    moe = (MoEParams(arch["num_experts"], arch["top_k"])
           if arch["num_experts"] > 1 else None)
    card = ModelCard(
        name=cell.config_name, embed_dim=arch["embed_dim"],
        num_heads=arch["num_heads"], num_kv_heads=arch["num_kv_heads"],
        ff_dim=arch["ff_dim"], seq_len=cell.traffic["seq_len"],
        num_decoder_blocks=arch["num_layers"],
        vocab_size=arch["vocab_size"], gated_mlp=True, moe_params=moe)
    over = {**cell.workload.get("program", {}), **(extra or {})}
    if moe is not None:
        over.setdefault("moe_capacity_factor", arch["capacity_factor"])
    return bench_step.bench_cfg(card, dtype=arch["dtype"], **over)


class TrainCell:
    # the weights a fixed horizon starts over from, and the program that
    # puts them back (``horizon``); a subclass's ``__init__`` sets neither
    kept = restore = None

    def __init__(self, cell: harness.Cell, seed: int, log,
                 program_over: dict | None = None):
        import jax
        from dlnetbench_tpu.core import executor
        from dlnetbench_tpu.models import bench_step
        self.cell, self.seed, self.log = cell, seed, log
        wl, tr = cell.workload, cell.traffic
        self.arch = weights.arch_of(
            cell.config, capacity_factor=wl.get("capacity_factor", 1.25))
        self.cfg = program_config(cell, self.arch, program_over)
        self.lr = float(wl["lr"])
        self.check_steps = int(wl["check_steps"])
        self.in_flight = int(wl.get("steps_in_flight", 1))
        self.batch, self.seq = tr["batch"], tr["seq_len"]
        self.tokens_per_step = self.batch * self.seq
        self.pool = weights.make_token_pool(
            seed, tr["pool_batches"], self.batch, self.seq + 1,
            self.arch["vocab_size"])
        self.params = weights.make_params(self.arch, seed)
        opts = wl.get("compiler_options") \
            if jax.devices()[0].platform == "tpu" else None
        t0 = time.perf_counter()
        self.step = executor.CompiledStep(
            bench_step.make_train_k(self.cfg, 1, self.lr),
            (self.params, self.pool[0]),
            donate_argnums=bench_step.DONATE_ARGNUMS,
            compiler_options=opts)
        self.compile_s = time.perf_counter() - t0
        self.kernels = self.step.as_text().count("tpu_custom_call")
        self.steps_done = 0

    def feed(self):
        return self.pool[self.steps_done % len(self.pool)]

    def call(self):
        """The window's own call: one step on the next batch of the
        feed; returns the step's loss (a device scalar)."""
        self.params, losses = self.step(self.params, self.feed())
        self.steps_done += 1
        return losses

    def first_steps(self) -> dict:
        """The first ``check_steps`` steps, with the per-leaf norms the
        reference is compared on."""
        import jax
        p0 = weights.make_params(self.arch, self.seed)
        first, delta = reference.norm_readers(self.lr)
        losses, grad_norms = [], None
        for i in range(self.check_steps):
            losses.append(float(self.call()[0]))
            if i == 0:
                grad_norms = jax.device_get(first(p0, self.params))
        delta_norms = jax.device_get(delta(p0, self.params))
        del p0
        return {"losses": losses,
                "grad_norms": {k: float(v) for k, v in grad_norms.items()},
                "delta_norms": {k: float(v)
                                for k, v in delta_norms.items()}}

    def horizon(self) -> int:
        """The workload's ``cycle_steps`` (its ``cycle_why`` says why a
        cell fixes its training horizon), 0 without the key.  With it,
        on the first call, which set-up makes: a device copy of the
        weights as they stand, after the checked steps, and the compiled
        program that writes a fresh copy of them over the trained ones.
        Those are donated and the kept ones pass a barrier, so XLA's one
        copy a leaf lands in the trained weights' buffers: the peak
        rises by the kept copy and the program has no temporaries
        (``jnp.copy`` there compiles to two copies of each large leaf
        through 1.16 GB of them; a ``trained`` that jit prunes as unused
        donates nothing and the output is a third copy)."""
        cycle = int(self.cell.workload.get("cycle_steps", 0))
        if cycle and self.restore is None:
            import jax
            import jax.numpy as jnp
            self.kept = jax.block_until_ready(jax.jit(
                lambda p: jax.tree.map(jnp.copy, p))(self.params))
            self.restore = jax.jit(
                lambda trained, kept: jax.lax.optimization_barrier(kept),
                donate_argnums=0, keep_unused=True,
            ).lower(self.params, self.kept).compile()
        return cycle

    def window(self, seconds: float, tracer: harness.TraceWindow) -> dict:
        """Steps for ``seconds``, ``steps_in_flight`` of them queued on
        the device at a time, as a training loop runs: the host waits
        for the oldest step while the younger ones keep the chip busy,
        so a notice of completion that comes late (PERF.md, stalls)
        idles nothing.  With 1 in flight every step is fenced before
        the next.  New steps are offered until those queued are due to
        end at ``seconds``; the window closes when the last has ended.
        Under a fixed horizon every ``cycle_steps``-th step is followed
        by the restore, queued behind it like a step: the window trains
        the same steps from the checked weights over and over, however
        many of them fit."""
        import jax
        cycle = self.horizon()
        clock, t0 = time.perf_counter, time.perf_counter()
        pending, ends, losses, dispatch = collections.deque(), [], [], []
        trace_after = self.cell.workload.get("trace_after_s", 2.0)
        step_s = 0.0        # the lowest mean so far: a stall cannot raise it

        def retire():
            nonlocal step_s
            ends.append(clock() - t0)
            losses.append(pending.popleft())
            mean = ends[-1] / len(ends)
            step_s = min(step_s, mean) if step_s else mean

        while True:
            # steps that ended while the host was held up are known at
            # once, so that the queue is filled again behind them
            while pending and pending[0].is_ready():
                retire()
            now = clock() - t0
            if now + len(pending) * step_s >= seconds:
                break
            tracer.maybe_start(now, min(trace_after, seconds / 4))
            with tracer.annotate("bench_dispatch"):
                t_call = clock()
                pending.append(self.call())
                dispatch.append(clock() - t_call)
            if cycle and len(dispatch) % cycle == 0:
                self.params = self.restore(self.params, self.kept)
            if len(pending) >= self.in_flight:
                with tracer.annotate("bench_wait"):
                    jax.block_until_ready(pending[0])
                retire()
            tracer.maybe_stop()
        while pending:
            jax.block_until_ready(pending[0])
            retire()
            tracer.maybe_stop()
        tracer.maybe_stop(force=True)
        return {"step_ends_s": ends, "dispatch_s": dispatch,
                "losses": [float(v[0]) for v in losses],
                "restores": len(dispatch) // cycle if cycle else 0}

    def free(self):
        self.params = self.step = self.kept = self.restore = None
        gc.collect()

    def reference_steps(self, precision: str = "float32") -> dict:
        p0 = weights.make_params(self.arch, self.seed)
        try:
            return reference.sgd_steps(
                p0, self.pool[:self.check_steps], self.arch, self.lr,
                precision)
        finally:
            del p0
            gc.collect()


def compare(got: dict, want: dict, limits: dict) -> list:
    """[(name, value, limit, where)]: each number compared beside its
    limit."""
    loss_gap = max(abs(g - w) / abs(w)
                   for g, w in zip(got["losses"], want["losses"]))
    if not all(math.isfinite(v) for v in got["losses"]):
        loss_gap = float("inf")
    g_gap, g_at = reference.worst_leaf_gap(got["grad_norms"],
                                           want["grad_norms"])
    d_gap, d_at = reference.worst_leaf_gap(got["delta_norms"],
                                           want["delta_norms"])
    return [("loss_gap", loss_gap, limits["loss_gap"], "steps"),
            ("grad_norm_gap", g_gap, limits["grad_norm_gap"], g_at),
            ("delta_norm_gap", d_gap, limits["delta_norm_gap"], d_at)]


def run(ctx) -> dict:
    cell = ctx["cell"]
    tc = TrainCell(cell, ctx["seed"], ctx["log"])
    ctx["log"]({"line": "compiled", "compile_s": tc.compile_s,
                "tpu_custom_calls": tc.kernels,
                "memory_analysis": tc.step.memory_analysis})
    got = tc.first_steps()
    tc.horizon()        # what a fixed horizon keeps and compiles is set-up
    ctx["log"]({"line": "set-up", "first_losses": got["losses"]})
    setup_s = harness.process_age_s()
    before = harness.host_pressure()
    win = tc.window(ctx["seconds"], ctx["tracer"])
    ctx["log"]({"line": "host", **{k: v - before[k] for k, v in
                                   harness.host_pressure().items()}})
    memory_peak = harness.memory_peak_bytes(cell.chips)
    ctx["log"]({"line": "memory", **harness.memory_stats(cell.chips)})
    tc.free()
    t0 = time.perf_counter()
    want = tc.reference_steps()
    ctx["log"]({"line": "reference", "seconds": time.perf_counter() - t0,
                "losses": want["losses"]})
    checks = compare(got, want, cell.workload["limits"])
    ends = win["step_ends_s"]
    durs = [b - a for a, b in zip([0.0] + ends[:-1], ends)]
    rate = stats.train_tokens_per_s(tc.tokens_per_step, ends)
    from benchmarks.costs import decoder_train
    flops = decoder_train.flops_per_token(tc.arch, tc.seq)
    ctx["log"]({"line": "window", "steps": len(ends),
                "steps_in_flight": tc.in_flight,
                "step_ms_median": stats.percentile(durs, 50) * 1e3,
                # the longest wait between two notices of completion:
                # seconds here, with the rate unmoved, is a late notice
                # that the queued steps rode over
                "step_ms_max": max(durs) * 1e3,
                "step_max_index": max(range(len(durs)),
                                      key=durs.__getitem__),
                # a dispatch as long as a step means the runtime would
                # not queue that many
                "dispatch_ms_median":
                    stats.percentile(win["dispatch_s"], 50) * 1e3,
                "dispatch_ms_max": max(win["dispatch_s"]) * 1e3,
                "loss_first": win["losses"][0],
                "loss_last": win["losses"][-1],
                "model_flops_per_token": flops,
                "model_flops_per_s": rate * flops})
    return {
        "end_to_end": {"train_tokens_per_s": rate, "setup_s": setup_s},
        "attempted": len(ends),
        "failed": sum(1 for v in win["losses"] if not math.isfinite(v)),
        "checks": checks,
        "memory_peak_bytes": memory_peak,
        "record": {"step_durations_s": durs,
                   "tokens_per_step": tc.tokens_per_step,
                   "arch": tc.arch, "batch": tc.batch, "seq": tc.seq},
    }


def readings(cell, seed: int, log, control: str | None) -> list:
    """The numbers ``correct`` compares, with no measured window (a
    training cell's readings need none).  ``control`` None: the program
    against the reference.  ``"reference_int8"``: the reference computed
    in int8, put in the program's place.  ``"program"``: the program
    with the workload's ``control_program`` switches on."""
    if control == "reference_int8":
        tc = TrainCell(cell, seed, log)
        tc.free()
        got = tc.reference_steps("int8")
    else:
        tc = TrainCell(cell, seed, log,
                       cell.workload["control_program"]
                       if control == "program" else None)
        got = tc.first_steps()
        tc.free()
    want = tc.reference_steps()
    log({"line": "losses", "seed": seed, "got": got["losses"],
         "want": want["losses"]})
    return compare(got, want, cell.workload["limits"])
