"""Runner ``train_headgate_moe``: ``runners/train_latent_moe.py``'s cell
(itself ``runners/train.py``'s) for a configuration of the head-gated
window-and-full attention expert decoder (``models/hybrid.py`` with
``swa`` and ``gated`` layers at head counts and RoPE of their own, one
sigmoid gate a head, a leading dense layer and then softmax-routed
experts times a scale beside a plain shared one), through the same step
builder (``models/bench_step.make_train_k``) and executor
(``core/executor.CompiledStep``).  What it names by file: the seeded
weights (``weights_headgate_moe``), the plain reference
(``reference_headgate_moe``), the program's configuration, the model
FLOPs a token (``costs/headgate_moe_train``).  ``feed``, ``window``,
``free``, ``call``, ``counted``, ``horizon``, ``selection_gap`` and
``compare`` are the runners' it builds on, by import; the methods below
are its own only because those files read their weights and reference
as module globals (PERF.md section 7 (d)).

``readings`` knows three planted faults beside the int8 reference, each
a program that computes another model than the configuration's:
``no_head_gate`` (the gate a head left off), ``plain_rope`` (a full
layer turned as a window layer is: every lane, theta 1e4, no YaRN, cos
and sin times 1) and ``unit_routed_scale`` (the routed experts' weights
times 1.0).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

from benchmarks import harness, stats
from benchmarks.runners import train_latent_moe
from benchmarks.runners.train_conv_moe import compare

try:
    from benchmarks import reference_headgate_moe as reference
    from benchmarks import weights_headgate_moe as weights
    from dlnetbench_tpu.core.model_card import ModelCard
    from dlnetbench_tpu.metrics.spans import SCOPES
    if not ({"window_heads", "rope_yarn"}
            <= {f.name for f in dataclasses.fields(ModelCard)}
            and "attn.gate" in SCOPES):
        raise ImportError("core/model_card.py has no head count or RoPE "
                          "by kind of layer, metrics/spans.py no attn.gate")
except ImportError as e:     # a program without the head-gated stack
    raise harness.BenchError(
        f"runner train_headgate_moe: this program cannot run the "
        f"head-gated window-and-full attention expert decoder ({e})") from e


def config_of(arch: dict, seq_len: int, moe_slots: int, **over):
    """The program's configuration of a model of ``arch``'s sizes: a
    card that states them, the share of the experts held here and the
    bound on an expert's rows."""
    from dlnetbench_tpu.core.model_card import MoEParams
    from dlnetbench_tpu.models import hybrid
    (theta, lanes, yarn), (w_theta, w_lanes, w_yarn) = (
        arch["rope_full"], arch["rope_window"])
    if w_yarn is not None:
        raise harness.BenchError("the program has no YaRN on a window layer")
    card = ModelCard(
        name="headgate_moe", embed_dim=arch["embed_dim"],
        num_heads=arch["num_heads"], num_kv_heads=arch["num_kv_heads"],
        ff_dim=arch["ff_dim"], seq_len=seq_len,
        num_decoder_blocks=arch["num_layers"],
        vocab_size=arch["vocab_size"], gated_mlp=True,
        layer_kinds=tuple(arch["layer_kinds"]),
        sliding_window=arch["window"], attn_head_dim=arch["head_dim"],
        attn_output_gate="head", attn_head_norm=False,
        rope_theta=theta, rope_dim=lanes, rope_yarn=yarn or (),
        window_heads=arch["window_heads"], window_rope_theta=w_theta,
        window_rope_dim=w_lanes, rms_norm=True, norm_eps=arch["eps"],
        moe_params=MoEParams(
            arch["num_experts"], arch["top_k"], scoring="softmax",
            routed_scale=arch["routed_scale"],
            shared_experts=arch["shared_ff_dim"] // arch["expert_ff_dim"],
            expert_ff_dim=arch["expert_ff_dim"],
            first_dense_layers=arch["first_dense"]))
    return hybrid.HybridConfig.from_card(
        card, dtype=arch["dtype"], held_experts=arch["held"],
        moe_slots=moe_slots, **over)


def program_config(cell, arch, extra: dict | None = None):
    """The program's configuration of this cell: the configuration
    file's sizes, the traffic's length, the workload's bound and its
    overrides (and ``extra``: a planted fault's switch)."""
    return config_of(arch, cell.traffic["seq_len"],
                     cell.workload["moe_slots"],
                     **{**cell.workload.get("program", {}),
                        **(extra or {})})


class HeadgateMoeCell(train_latent_moe.LatentMoeCell):
    """``LatentMoeCell`` with this model's weights, reference and
    configuration; ``feed``, ``window``, ``free``, ``call``,
    ``counted`` and ``horizon`` are the bases'."""

    def __init__(self, cell: harness.Cell, seed: int, log,
                 program_over: dict | None = None):
        import jax
        from dlnetbench_tpu.core import executor
        from dlnetbench_tpu.metrics import spans
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
        self.kernels = (self.step.as_text().count("tpu_custom_call")
                        if spans.is_enabled() else None)
        self.steps_done = 0
        self.counters = []      # each step's, device scalars
        self.chosen = None      # the first step's selections

    def make_params(self):
        return weights.make_params(self.arch, self.seed)

    def first_steps(self) -> dict:
        import jax
        first, delta = reference.norm_readers(self.lr, self.arch)
        losses = []
        for i in range(self.check_steps):
            losses.append(float(self.call()[0]))
            if i == 0:
                grad_norms = jax.device_get(
                    first(self.make_params(), self.params))
        delta_norms = jax.device_get(
            delta(self.make_params(), self.params))
        return {"losses": losses, "chosen": jax.device_get(self.chosen),
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
    from benchmarks.costs import headgate_moe_train
    from dlnetbench_tpu.metrics import spans
    traced = ctx["tracer"].enabled
    # a tracer someone else turned on (scope_dump.py) is theirs to stop
    own_tracer = traced and not spans.is_enabled()
    if own_tracer:
        spans.enable()
    tc = HeadgateMoeCell(cell, ctx["seed"], ctx["log"])
    ctx["log"]({"line": "compiled", "compile_s": tc.compile_s,
                "tpu_custom_calls": tc.kernels,
                "memory_analysis": tc.step.memory_analysis})
    got = tc.first_steps()
    cycle = tc.horizon()    # what a fixed horizon keeps and compiles is set-up
    ctx["log"]({"line": "set-up", "first_losses": got["losses"]})
    setup_s = harness.process_age_s()
    before = harness.host_pressure()
    win = tc.window(ctx["seconds"], ctx["tracer"])
    ctx["log"]({"line": "host", **{k: v - before[k] for k, v in
                                   harness.host_pressure().items()}})
    memory_peak = harness.memory_peak_bytes(cell.chips)
    ctx["log"]({"line": "memory", **harness.memory_stats(cell.chips)})
    counted = {k: v[tc.check_steps:] for k, v in tc.counted().items()}
    held = tc.arch["held"][1]
    record = {"tokens_per_step": tc.tokens_per_step,
              "arch": {k: list(v) if isinstance(v, tuple) else v
                       for k, v in tc.arch.items()},
              "batch": tc.batch, "seq": tc.seq,
              "moe": {"routed": counted["routed"],
                      "max_load": counted["max_load"],
                      "slots": (weights.expert_layers(tc.arch) * held
                                * tc.cfg.moe_slots)}}
    if traced:
        record["program_trace"] = (spans.disable() if own_tracer
                                   else spans.current()).export()
    tc.free()
    t0 = time.perf_counter()
    want = tc.reference_steps()
    ctx["log"]({"line": "reference", "seconds": time.perf_counter() - t0,
                "losses": want["losses"]})
    checks = compare(got, want, cell.workload["limits"])
    ends = win["step_ends_s"]
    durs = [b - a for a, b in zip([0.0] + ends[:-1], ends)]
    record["step_durations_s"] = durs
    rate = stats.train_tokens_per_s(tc.tokens_per_step, ends)
    flops = headgate_moe_train.flops_per_token(tc.arch, tc.seq)
    ctx["log"]({"line": "window", "steps": len(ends),
                "steps_in_flight": tc.in_flight,
                "step_ms_median": stats.percentile(durs, 50) * 1e3,
                "step_ms_max": max(durs) * 1e3,
                "dispatch_ms_median":
                    stats.percentile(win["dispatch_s"], 50) * 1e3,
                "dispatch_ms_max": max(win["dispatch_s"]) * 1e3,
                "loss_first": win["losses"][0],
                "loss_last": win["losses"][-1],
                # the experts' bound and what the window's steps read
                # against it
                "moe_slots": tc.cfg.moe_slots,
                "moe_max_load": max(counted["max_load"]),
                "moe_max_load_first_step": counted["max_load"][0],
                "moe_max_load_last_step": counted["max_load"][-1],
                # under a fixed horizon every later cycle repeats the
                # first: every loss and counter is that of the step a
                # cycle earlier
                "cycle_steps": cycle,
                "restores": win["restores"],
                "cycles_repeat": bool(cycle) and all(
                    v[cycle:] == v[:-cycle]
                    for v in (win["losses"], *counted.values())),
                "moe_routed_rows_median":
                    stats.percentile(counted["routed"], 50),
                "moe_rows_past_bound": sum(counted["past_bound"]),
                "model_flops_per_token": flops,
                "model_flops_per_s": rate * flops})
    return {
        "end_to_end": {"train_tokens_per_s": rate, "setup_s": setup_s},
        "attempted": len(ends),
        "failed": sum(1 for v, past in zip(win["losses"],
                                           counted["past_bound"])
                      if past or not math.isfinite(v)),
        "checks": checks,
        "memory_peak_bytes": memory_peak,
        "record": record,
    }


# a planted fault is a switch of the program's configuration: the
# weights stay the configuration's (an unused gate's projection gets no
# gradient, which the norms read)
FAULTS = {"no_head_gate": {"attn_gate": False},
          "plain_rope": {"rope_yarn": (), "rope_theta": 10000.0,
                         "rope_dim": 0},
          "unit_routed_scale": {"routed_scale": 1.0}}


def readings(cell, seed: int, log, control: str | None) -> list:
    """What ``train.readings`` gives: the numbers ``correct`` compares,
    with no measured window.  ``control`` None, "reference_int8" or one
    of ``FAULTS``."""
    if control == "reference_int8":
        tc = HeadgateMoeCell(cell, seed, log)
        tc.free()
        got = tc.reference_steps("int8")
    elif control is None or control in FAULTS:
        tc = HeadgateMoeCell(cell, seed, log, FAULTS.get(control))
        got = tc.first_steps()
        log({"line": "counted", **tc.counted()})
        tc.free()
    else:
        raise harness.BenchError(
            f"train_headgate_moe has no control {control!r}")
    want = tc.reference_steps()
    log({"line": "losses", "seed": seed, "got": got["losses"],
         "want": want["losses"]})
    return compare(got, want, cell.workload["limits"])
