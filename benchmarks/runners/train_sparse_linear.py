"""Runner ``train_sparse_linear``: ``runners/train.py``'s cell and
window for a configuration of the sparse-and-linear hybrid decoder
(``models/hybrid.py`` with ``sparse`` and ``lightning`` layers, dense
SwiGLUs and MiniCPM's three scalars), through the same step builder
(``models/bench_step.make_train_k``) and executor
(``core/executor.CompiledStep``).  What it names by file: the seeded
weights (``weights_sparse_linear``), the plain reference
(``reference_sparse_linear``), the program's configuration, the model
FLOPs a token (``costs/sparse_linear_train``).  What it adds: the step
returns its sparse layers' selections and two counters beside its loss
(``hybrid.SELECTION``); the counters go under ``record["sparse"]``, and
the first step's lists are compared with the reference's, which selects
for itself in float32 (``block_selection_gap``).  No horizon: nothing
here drifts toward a bound.

``readings`` knows five planted faults beside the int8 reference, each
a program that computes another model than the configuration's:
``dense_for_sparse`` (the sparse layer selects every visible block: it
attends every earlier key), ``lists_unread`` (the selection is the
configuration's and the sparse kernels' mask of a tile leaves its
membership test out: forward and the three gradients see every earlier
key of every tile they visit; the lists compared are sound, so only the
norms can tell), ``no_decay`` (lambda = 1 in every head),
``no_lightning_rope`` (the lightning layers' queries and keys not
turned) and ``unit_residual_scale`` (c = 1).
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import math
import time

from benchmarks import harness, stats
from benchmarks.runners import train

try:
    from benchmarks import reference_sparse_linear as reference
    from benchmarks import weights_sparse_linear as weights
    from dlnetbench_tpu.models.hybrid import SELECTION
except ImportError as e:     # a program without the two kinds of layer
    raise harness.BenchError(
        f"runner train_sparse_linear: this program cannot run the "
        f"sparse-and-linear hybrid decoder ({e})") from e

COUNTED = tuple(k for k in SELECTION if k != "blocks")


def config_of(arch: dict, seq_len: int, **over):
    """The program's configuration of a model of ``arch``'s sizes."""
    from dlnetbench_tpu.core.model_card import ModelCard
    from dlnetbench_tpu.models import hybrid
    lh, ld = arch["lightning_heads"], arch["lightning_dim"]
    card = ModelCard(
        name="sparse_linear", embed_dim=arch["embed_dim"],
        num_heads=arch["num_heads"], num_kv_heads=arch["num_kv_heads"],
        ff_dim=arch["ff_dim"], seq_len=seq_len,
        num_decoder_blocks=arch["num_layers"],
        vocab_size=arch["vocab_size"], gated_mlp=True,
        layer_kinds=tuple(arch["layer_kinds"]),
        attn_head_dim=arch["head_dim"], attn_output_gate=True,
        attn_head_norm=True, rope_theta=arch["rope_theta"], rms_norm=True,
        norm_eps=arch["eps"], linear_key_heads=lh, linear_value_heads=lh,
        linear_key_dim=ld, linear_value_dim=ld,
        embed_scale=arch["embed_scale"],
        residual_scale=arch["residual_scale"],
        logit_scale=arch["logit_scale"],
        sparse_attention=tuple(arch["sparse_sizes"]),
        published_layers=arch["published_layers"])
    return hybrid.HybridConfig.from_card(card, dtype=arch["dtype"], **over)


def program_config(cell, arch, extra: dict | None = None):
    """The program's configuration of this cell: the configuration
    file's sizes, the traffic's length and the workload's overrides (and
    ``extra``: a planted fault's switch)."""
    return config_of(arch, cell.traffic["seq_len"],
                     **{**cell.workload.get("program", {}),
                        **(extra or {})})


class SparseLinearCell(train.TrainCell):
    """``train.TrainCell`` with this model's weights, reference and
    configuration, and a step that returns its selections; ``feed``,
    ``window``, ``horizon`` and ``free`` are the base's."""

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
        # as in train_hybrid: the text only where a tracer makes it anyway
        self.kernels = (self.step.as_text().count("tpu_custom_call")
                        if spans.is_enabled() else None)
        self.steps_done = 0
        self.counters = []      # each step's, device scalars
        self.chosen = None      # the first step's lists

    def make_params(self):
        return weights.make_params(self.arch, self.seed)

    def call(self):
        self.params, (losses, picked) = self.step(self.params, self.feed())
        if self.chosen is None:
            self.chosen = picked["blocks"][0]
        self.counters.append({k: picked[k] for k in COUNTED})
        self.steps_done += 1
        return losses

    def counted(self) -> dict:
        """{counter: [its reading of every step so far]} on the host."""
        import jax
        rows = jax.device_get(self.counters)
        return {k: [int(r[k][0]) for r in rows] for k in COUNTED}

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
        return {"losses": losses, "blocks": jax.device_get(self.chosen),
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


def block_selection_gap(got, want, sizes) -> float:
    """Of ``got``'s unforced selections [sparse layers, B, S, Hkv, n]
    (a block that is neither one of the first ``init_blocks`` nor one of
    the ``window / block`` ending at the token's own), the share that
    ``want`` does not hold for that (layer, row, token, group); 0.0
    where ``got`` has none, 1.0 where ``want`` selected nothing."""
    import numpy as np
    if want is None:
        return 1.0
    _, _, block, _, window, init, _ = sizes
    own = (np.arange(got.shape[2]) // block)[None, None, :, None, None]
    free = (got >= init) & (got <= own - window // block)
    if not free.any():
        return 0.0
    held = (got[..., :, None] == want[..., None, :]).any(-1)
    return float(1.0 - held[free].mean())


def compare(got: dict, want: dict, limits: dict, sizes) -> list:
    """``train.compare``'s three rows and the selections' gap."""
    return train.compare(got, want, limits) + [
        ("block_selection_gap",
         block_selection_gap(got["blocks"], want["blocks"], sizes),
         limits["block_selection_gap"],
         "first step, unforced (token, group, block) selections")]


def run(ctx) -> dict:
    cell = ctx["cell"]
    from benchmarks.costs import sparse_linear_train
    from dlnetbench_tpu.metrics import spans
    traced = ctx["tracer"].enabled
    # a tracer someone else turned on (scope_dump.py) is theirs to stop
    own_tracer = traced and not spans.is_enabled()
    if own_tracer:
        spans.enable()
    tc = SparseLinearCell(cell, ctx["seed"], ctx["log"])
    ctx["log"]({"line": "compiled", "compile_s": tc.compile_s,
                "tpu_custom_calls": tc.kernels,
                "memory_analysis": tc.step.memory_analysis})
    got = tc.first_steps()
    tc.horizon()        # none in this cell's workload: nothing is kept
    ctx["log"]({"line": "set-up", "first_losses": got["losses"]})
    setup_s = harness.process_age_s()
    before = harness.host_pressure()
    win = tc.window(ctx["seconds"], ctx["tracer"])
    ctx["log"]({"line": "host", **{k: v - before[k] for k, v in
                                   harness.host_pressure().items()}})
    memory_peak = harness.memory_peak_bytes(cell.chips)
    ctx["log"]({"line": "memory", **harness.memory_stats(cell.chips)})
    counted = {k: v[tc.check_steps:] for k, v in tc.counted().items()}
    record = {"tokens_per_step": tc.tokens_per_step,
              "arch": {k: list(v) if isinstance(v, tuple) else v
                       for k, v in tc.arch.items()},
              "batch": tc.batch, "seq": tc.seq,
              "sparse": {**counted, "visited_median":
                         stats.percentile(counted["visited"], 50)}}
    if traced:
        record["program_trace"] = (spans.disable() if own_tracer
                                   else spans.current()).export()
    tc.free()
    t0 = time.perf_counter()
    want = tc.reference_steps()
    ctx["log"]({"line": "reference", "seconds": time.perf_counter() - t0,
                "losses": want["losses"]})
    checks = compare(got, want, cell.workload["limits"],
                     tc.arch["sparse_sizes"])
    ends = win["step_ends_s"]
    durs = [b - a for a, b in zip([0.0] + ends[:-1], ends)]
    record["step_durations_s"] = durs
    rate = stats.train_tokens_per_s(tc.tokens_per_step, ends)
    flops = sparse_linear_train.flops_per_token(tc.arch, tc.seq)
    ctx["log"]({"line": "window", "steps": len(ends),
                "steps_in_flight": tc.in_flight,
                "step_ms_median": stats.percentile(durs, 50) * 1e3,
                "step_ms_max": max(durs) * 1e3,
                "dispatch_ms_median":
                    stats.percentile(win["dispatch_s"], 50) * 1e3,
                "dispatch_ms_max": max(win["dispatch_s"]) * 1e3,
                "loss_first": win["losses"][0],
                "loss_last": win["losses"][-1],
                # (token, group, block) pairs: what the lists hold and
                # what the kernels' tiles cover
                "sparse_selected_median":
                    stats.percentile(counted["selected"], 50),
                "sparse_visited_median":
                    stats.percentile(counted["visited"], 50),
                "sparse_visited_min": min(counted["visited"]),
                "sparse_visited_max": max(counted["visited"]),
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


def _no_decay(decay):
    """Every head's ``ln lambda`` at zero."""
    return lambda *a: 0.0 * decay(*a)


def _no_rope(mixer):
    """The lightning layer with queries and keys as they were normed."""
    def unturned(cfg, y, p, li):
        from dlnetbench_tpu.models import layers
        real = layers.rope
        layers.rope = lambda q, k, *a, **kw: (q, k)
        try:
            return mixer(cfg, y, p, li)
        finally:
            layers.rope = real
    return unturned


def _causal_only(tile_mask):
    """The sparse kernels' mask of a tile as if every token had chosen
    every block: what is left is the causal test."""
    def mask(mem, *at):
        import jax.numpy as jnp
        return tile_mask(jnp.ones_like(mem), *at)
    return mask


@contextlib.contextmanager
def _planted(module: str, name: str, fault):
    """While inside, ``dlnetbench_tpu.<module>.<name>`` is ``fault(the
    real one)`` (``train_conv_moe._planted`` is this for
    ``models/hybrid.py`` alone; one of these faults lives in a kernel
    file)."""
    import jax
    home = importlib.import_module(f"dlnetbench_tpu.{module}")
    real = getattr(home, name)
    setattr(home, name, fault(real))
    jax.clear_caches()      # jax.checkpoint keeps the layer it traced
    try:
        yield
    finally:
        setattr(home, name, real)
        jax.clear_caches()


# a planted fault is a switch of the program's configuration (a function
# of the cell's own) or a function of the program wrapped; the weights
# stay the configuration's
SWITCHES = {
    "dense_for_sparse": lambda tc: {"sparse_sizes": tuple(
        tc["seq"] // tc["sizes"][2] if i == 3 else v
        for i, v in enumerate(tc["sizes"]))},
    "unit_residual_scale": lambda tc: {"residual_scale": 1.0}}
WRAPPED = {
    "lists_unread": ("ops.sparse_attention", "_tile_mask", _causal_only),
    "no_decay": ("models.hybrid", "head_log_decay", _no_decay),
    "no_lightning_rope": ("models.hybrid", "lightning_mixer", _no_rope)}
FAULTS = (*SWITCHES, *WRAPPED)


def readings(cell, seed: int, log, control: str | None) -> list:
    """What ``train.readings`` gives: the numbers ``correct`` compares,
    with no measured window.  ``control`` None, "reference_int8" or one
    of ``FAULTS``."""
    sizes = weights.arch_of(cell.config)["sparse_sizes"]
    if control == "reference_int8":
        tc = SparseLinearCell(cell, seed, log)
        tc.free()
        got = tc.reference_steps("int8")
    elif control is None or control in FAULTS:
        over = SWITCHES[control]({"seq": cell.traffic["seq_len"],
                                  "sizes": sizes}) \
            if control in SWITCHES else None
        planted = (_planted(*WRAPPED[control]) if control in WRAPPED
                   else contextlib.nullcontext())
        with planted:
            tc = SparseLinearCell(cell, seed, log, over)
            got = tc.first_steps()
        log({"line": "counted", **tc.counted()})
        tc.free()
    else:
        raise harness.BenchError(
            f"train_sparse_linear has no control {control!r}")
    want = tc.reference_steps()
    log({"line": "losses", "seed": seed, "got": got["losses"],
         "want": want["losses"]})
    return compare(got, want, cell.workload["limits"], sizes)
