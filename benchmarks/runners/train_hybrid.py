"""Runner ``train_hybrid``: ``runners/train.py``'s cell, window and
comparison for a configuration of the hybrid decoder
(``models/hybrid.py``), through the same step builder
(``models/bench_step.make_train_k``) and executor
(``core/executor.CompiledStep``).  What differs is what the gated
decoder's runner names by file: the seeded weights
(``weights_hybrid``), the plain reference (``reference_hybrid``), the
program's configuration, and the model FLOPs a token.

On a traced run the program's tracer is on while the step is built, so
that the executor registers the step's op->scope table, and its export
goes under ``record["program_trace"]``, where the scope readers look.
"""
from __future__ import annotations

import gc
import math
import time

from benchmarks import harness, stats
from benchmarks.runners import train

try:
    from benchmarks import reference_hybrid as reference
    from benchmarks import weights_hybrid as weights
    from dlnetbench_tpu.models import hybrid as _program  # noqa: F401
except ImportError as e:     # a program without the hybrid decoder
    raise harness.BenchError(
        f"runner train_hybrid: this program cannot run the hybrid "
        f"decoder ({e})") from e


def program_config(cell, arch, extra: dict | None = None):
    """The program's configuration of this cell: a card of the
    configuration file's sizes, the workload's overrides."""
    from dlnetbench_tpu.core.model_card import ModelCard
    from dlnetbench_tpu.models import hybrid
    card = ModelCard(
        name=cell.config_name, embed_dim=arch["embed_dim"],
        num_heads=arch["num_heads"], num_kv_heads=arch["num_kv_heads"],
        ff_dim=arch["ff_dim"], seq_len=cell.traffic["seq_len"],
        num_decoder_blocks=arch["num_layers"],
        vocab_size=arch["vocab_size"], gated_mlp=True,
        tied_embeddings=True, layer_kinds=arch["layer_kinds"],
        sliding_window=arch["window"], differential_attention=True,
        ssm_inner=arch["ssm_inner"], ssm_state=arch["ssm_state"],
        ssm_conv=arch["ssm_conv"], ssm_dt_rank=arch["ssm_dt_rank"])
    over = {**cell.workload.get("program", {}), **(extra or {})}
    return hybrid.HybridConfig.from_card(
        card, dtype=arch["dtype"], norm_eps=arch["eps"], **over)


class HybridCell(train.TrainCell):
    """``train.TrainCell`` with the hybrid decoder's weights, reference
    and configuration; ``feed``, ``call``, ``window`` and ``free`` are
    the base's."""

    def __init__(self, cell: harness.Cell, seed: int, log,
                 program_over: dict | None = None):
        import jax
        from dlnetbench_tpu.core import executor
        from dlnetbench_tpu.models import bench_step
        self.cell, self.seed, self.log = cell, seed, log
        wl, tr = cell.workload, cell.traffic
        self.arch = weights.arch_of(cell.config)
        self.cfg = program_config(cell, self.arch, program_over)
        self.lr = float(wl["lr"])
        self.check_steps = int(wl["check_steps"])
        self.in_flight = int(wl.get("steps_in_flight", 1))
        self.batch, self.seq = tr["batch"], tr["seq_len"]
        self.tokens_per_step = self.batch * self.seq
        t0 = time.perf_counter()
        self.pool = weights.make_token_pool(
            seed, tr["pool_batches"], self.batch, self.seq + 1,
            self.arch["vocab_size"])
        self.params = jax.block_until_ready(self.make_params())
        log({"line": "weights", "seconds": time.perf_counter() - t0})
        opts = wl.get("compiler_options") \
            if jax.devices()[0].platform == "tpu" else None
        t0 = time.perf_counter()
        self.step = executor.CompiledStep(
            bench_step.make_train_k(self.cfg, 1, self.lr),
            (self.params, self.pool[0]),
            donate_argnums=bench_step.DONATE_ARGNUMS,
            compiler_options=opts)
        self.compile_s = time.perf_counter() - t0
        # the text of an executable loaded from the cache takes half a
        # minute to make at this size: asked for only where it is made
        # anyway (a tracer on builds the op->scope table from it)
        from dlnetbench_tpu.metrics import spans
        self.kernels = (self.step.as_text().count("tpu_custom_call")
                        if spans.is_enabled() else None)
        self.steps_done = 0

    def make_params(self):
        return weights.make_params(self.arch, self.seed)

    def first_steps(self) -> dict:
        import jax
        first, delta = reference.norm_readers(self.lr,
                                              self.arch["layer_kinds"])
        losses = []
        for i in range(self.check_steps):
            losses.append(float(self.call()[0]))
            if i == 0:
                grad_norms = jax.device_get(
                    first(self.make_params(), self.params))
        delta_norms = jax.device_get(
            delta(self.make_params(), self.params))
        return {"losses": losses,
                "grad_norms": {k: float(v) for k, v in grad_norms.items()},
                "delta_norms": {k: float(v)
                                for k, v in delta_norms.items()}}

    def reference_steps(self, precision: str = "float32") -> dict:
        try:
            return reference.sgd_steps(
                self.make_params, self.pool[:self.check_steps], self.arch,
                self.lr, precision)
        finally:
            gc.collect()


def run(ctx) -> dict:
    cell = ctx["cell"]
    from dlnetbench_tpu.metrics import spans
    traced = ctx["tracer"].enabled
    # a tracer someone else turned on (scope_dump.py) is theirs to stop
    own_tracer = traced and not spans.is_enabled()
    if own_tracer:
        spans.enable()
    tc = HybridCell(cell, ctx["seed"], ctx["log"])
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
    record = {"tokens_per_step": tc.tokens_per_step,
              "arch": {**tc.arch, "layer_kinds":
                       list(tc.arch["layer_kinds"])},
              "batch": tc.batch, "seq": tc.seq}
    if traced:
        record["program_trace"] = (spans.disable() if own_tracer
                                   else spans.current()).export()
    tc.free()
    t0 = time.perf_counter()
    want = tc.reference_steps()
    ctx["log"]({"line": "reference", "seconds": time.perf_counter() - t0,
                "losses": want["losses"]})
    checks = train.compare(got, want, cell.workload["limits"])
    ends = win["step_ends_s"]
    durs = [b - a for a, b in zip([0.0] + ends[:-1], ends)]
    record["step_durations_s"] = durs
    rate = stats.train_tokens_per_s(tc.tokens_per_step, ends)
    from benchmarks.costs import hybrid_train
    flops = hybrid_train.flops_per_token(tc.arch, tc.seq)
    ctx["log"]({"line": "window", "steps": len(ends),
                "steps_in_flight": tc.in_flight,
                "step_ms_median": stats.percentile(durs, 50) * 1e3,
                "step_ms_max": max(durs) * 1e3,
                "step_max_index": max(range(len(durs)),
                                      key=durs.__getitem__),
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
        "record": record,
    }


def readings(cell, seed: int, log, control: str | None) -> list:
    """What ``train.readings`` gives: the numbers ``correct`` compares,
    with no measured window.  ``control`` None or "reference_int8"."""
    tc = HybridCell(cell, seed, log)
    if control == "reference_int8":
        tc.free()
        got = tc.reference_steps("int8")
    elif control is None:
        got = tc.first_steps()
        tc.free()
    else:
        raise harness.BenchError(f"train_hybrid has no control {control!r}")
    want = tc.reference_steps()
    log({"line": "losses", "seed": seed, "got": got["losses"],
         "want": want["losses"]})
    return train.compare(got, want, cell.workload["limits"])
