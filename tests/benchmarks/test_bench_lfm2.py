"""The short-convolution cell's own pieces: the control and the two
planted faults that have to come out as not correct at the rehearsal
size, a row past the experts' bound failing the run, the configuration
file against the catalog's row, the new costs and readers on a fixture
of their own (``scope_fixture_conv_moe.json``), each reader giving
nothing from a program without what it reads, and the three cases that
``test_bench_scopes.py`` keys by its table of the gated-decoder cells
(``tests/conftest.py`` skips them for this cell)."""
import dataclasses
import functools
import importlib
import json

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness, run, scope_dump
from benchmarks import reference_conv_moe as ref
from benchmarks import weights_conv_moe as weights
from benchmarks.costs import (conv_moe_train, gated_flash_attention,
                              held_grouped_matmul, short_conv)
from benchmarks.runners import train_conv_moe, train_latent_moe

CELL = "lfm2_8b_a1b_train_s8k"
FIX = harness.load_json(harness.HERE / "scope_fixture_conv_moe.json")
MANIFEST = harness.load_json(harness.ROOT / "BENCHMARK.json")
SCOPE_MS = ["conv_mixer_ms", "conv_gate_ms", "gqa64_attn_ms",
            "moe_whole_route_ms", "moe_whole_experts_ms"]
ROOFLINES = ["conv_gate_roofline", "gqa64_flash_roofline",
             "moe_whole_mm_roofline"]
NEW = SCOPE_MS + ROOFLINES
# the whole step and the device's idle share: copies, for this cell, of
# two entries the benchmark has and this PR may not join; they read the
# trace alone, so a program without this PR's scopes gives them too
WHOLE = ["step_device_ms.conv_moe", "device_idle_pct.conv_moe"]
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention",
        "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv",
        "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


def rehearsal_cell():
    return harness.rehearsal(harness.load_cell(CELL))


def spec(metric):
    return harness.load_json(harness.HERE / "layer_metrics"
                             / f"{metric}.json")


def trace():
    return {"devices": {0: {"ops": [tuple(e) for e in FIX["ops"]],
                            "modules": [tuple(e)
                                        for e in FIX["modules"]]}},
            "host": [("bench_window", 0.0, 10.0)]}


def record():
    return {**json.loads(json.dumps(FIX["record"])),
            "program_trace": json.loads(json.dumps(FIX["program_trace"]))}


def ctx():
    return {"record": record(), "devices": [trace()["devices"][0]],
            "window": tuple(FIX["window"]), "peaks": FIX["peaks"]}


def read(metric, c):
    s = spec(metric)
    return importlib.import_module(
        f"benchmarks.readers.{s['reader']}").read(c, s["params"])


def rehearse(capsys, seed=5):
    run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
              "--trace", "0", "--rehearse-cpu", "1"])
    got = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("{")]
    return got, next(g for g in got if g["line"].startswith("rehearsal"))


def bad(rows):
    return {name for name, value, limit, _ in rows if not value <= limit}


# ----------------------------------------------------------- correct
@pytest.fixture(scope="module")
def one_reference():
    """The float32 reference's three steps of a seed computed once for
    the controls that share it."""
    real = train_conv_moe.ConvMoeCell.reference_steps

    @functools.lru_cache(maxsize=None)
    def steps(seed, precision):
        return real(cells[seed], precision)
    cells = {}

    def cached(self, precision="float32"):
        cells[self.seed] = self
        return steps(self.seed, precision)
    train_conv_moe.ConvMoeCell.reference_steps = cached
    yield
    train_conv_moe.ConvMoeCell.reference_steps = real


@pytest.mark.parametrize("control", ["reference_int8", "no_history",
                                     "no_qk_norm"])
def test_controls_and_planted_faults_at_the_rehearsal_size(one_reference,
                                                           control):
    """The int8 reference, a conv mixer that sees no other token and an
    attention layer without its norms a head are each not correct
    (``test_bench_rehearsal.py`` runs the sound program: correct)."""
    rows = train_conv_moe.readings(rehearsal_cell(), 2**31 + 11,
                                   lambda _: None, control)
    # the loss's gap has no limit in this cell (workload file,
    # limits_from) and so is no row
    assert [name for name, *_ in rows] == [
        "grad_norm_gap", "delta_norm_gap", "selection_gap"]
    assert "grad_norm_gap" in bad(rows)


def test_a_planted_fault_leaves_the_program_as_it_found_it():
    from dlnetbench_tpu.models import hybrid, layers
    before = (hybrid._causal_conv, hybrid.gated_mixer, layers.rmsnorm)
    for name in train_conv_moe.FAULTS:
        with train_conv_moe._planted(*train_conv_moe.FAULTS[name]):
            assert (hybrid._causal_conv, hybrid.gated_mixer) != before[:2]
    assert (hybrid._causal_conv, hybrid.gated_mixer,
            layers.rmsnorm) == before


def test_a_number_without_a_limit_is_read_and_not_compared():
    got = {"losses": [2.0, float("nan")], "chosen": jnp.zeros((1, 4, 2)),
           "grad_norms": {"w": 1.0}, "delta_norms": {"w": 1.0}}
    want = {**got, "losses": [2.0, 2.0]}
    limits = {"grad_norm_gap": 0.1, "delta_norm_gap": 0.1,
              "selection_gap": 0.1}
    assert [r[0] for r in train_conv_moe.compare(got, want, limits)] \
        == list(limits)
    rows = train_conv_moe.compare(got, want, {**limits, "loss_gap": 1e-3})
    assert rows[0][:3] == ("loss_gap", float("inf"), 1e-3)


def test_unknown_control_is_refused():
    with pytest.raises(harness.BenchError, match="no control"):
        train_conv_moe.readings(rehearsal_cell(), 7, lambda _: None,
                                "program")


def test_unchanged_state_and_a_row_past_the_bound_are_not_correct(
        capsys, monkeypatch):
    """A step that returns its state unchanged fails the parameters'
    change; with a bound under the load rows are left out, the step
    counts them and the run counts the step as failed."""
    load = harness.load_cell

    def tight(name, *a, **kw):
        cell = load(name, *a, **kw)
        cell.workload["rehearsal"]["moe_slots"] = 24
        return cell

    def call(self):
        _, (losses, routing) = self.step(
            jax.tree.map(jnp.copy, self.params), self.feed())
        if self.chosen is None:
            self.chosen = routing["choices"][0]
        self.counters.append({k: routing[k]
                              for k in train_latent_moe.COUNTERS})
        self.steps_done += 1
        return losses
    monkeypatch.setattr(harness, "load_cell", tight)
    monkeypatch.setattr(train_conv_moe.ConvMoeCell, "call", call)
    got, result = rehearse(capsys)
    assert result["correct"] is False
    assert "delta_norm_gap" in {g["name"] for g in got
                                if g["line"] == "compared" and not g["ok"]}
    window = next(g for g in got if g["line"] == "window")
    assert window["moe_slots"] == 24 < window["moe_max_load"]
    assert window["moe_rows_past_bound"] > 0
    assert 0 < result["failed"] <= result["attempted"]


def test_runner_refuses_a_program_without_the_conv_mixer(monkeypatch):
    """On the parent's program the runner's import raises the harness's
    own error: the run exits non-zero at once, with no result."""
    import sys

    from dlnetbench_tpu.models import hybrid
    monkeypatch.delattr(hybrid, "conv_mixer")
    monkeypatch.delitem(sys.modules, "benchmarks.runners.train_conv_moe")
    with pytest.raises(harness.BenchError, match="cannot run the "
                                                 "short-convolution"):
        importlib.import_module("benchmarks.runners.train_conv_moe")


# ----------------------------------------------------- configuration
def test_configuration_keeps_every_published_width():
    """Every key of the catalog row's ``config`` under its name, but the
    three reduced ones, each beside its published value; the whole
    vocabulary and all 32 experts."""
    body = harness.load_cell(CELL).config
    reduced = {"num_hidden_layers", "num_dense_layers", "layer_types"}
    assert set(body["reduced"]) == reduced
    assert {k: body[k] for k in CATALOG if k not in reduced} \
        == {k: v for k, v in CATALOG.items() if k not in reduced}
    assert body["published"] == {k: CATALOG[k] for k in reduced}
    # the cut: published layers 1-5, the second dense conv layer and the
    # first period of expert layers
    assert body["layer_types"] == CATALOG["layer_types"][1:6] \
        == ["conv", "full_attention", "conv", "conv", "conv"]
    assert (body["num_hidden_layers"], body["num_dense_layers"]) == (5, 1)
    assert "all 32 routed experts" in body["deployment"]
    assert set(body["assumed"]) >= {"first_held_expert",
                                    "router_bias_scale", "why"}
    assert body["assumed"]["why"].startswith("The head is the embedding")
    assert "14.0 GB" in body["cut_by_the_rule"] \
        and "16 held" in body["cut_by_the_rule"]
    arch = weights.arch_of(body)
    assert arch["layer_kinds"] == ("conv", "gated", "conv", "conv", "conv")
    assert (arch["num_experts"], arch["held"], arch["top_k"],
            arch["head_dim"], arch["first_dense"]) == (32, (0, 32), 4, 64, 1)
    params = {k: math_prod(shape)
              for k, (shape, _) in weights.shapes(arch).items()}

    def group(g):
        return sum(v for k, v in params.items() if k.startswith(g + "/"))
    # the issue's table: a conv mixer 16.78 M, the attention mixer
    # 10.49 M, the dense FFN 44.04 M, a layer's router, bias and 32
    # experts 352.39 M, the tied table 134.2 M: 1665 M in all
    assert group("conv") == pytest.approx(4 * 16.78e6, rel=1e-3)
    assert group("gated") == pytest.approx(10.49e6, rel=1e-3)
    assert group("moe") == pytest.approx(4 * 352.39e6, rel=1e-4)
    assert params["embed"] == 65536 * 2048 and "head" not in params
    assert sum(params.values()) == pytest.approx(1665e6, rel=1e-3)


def math_prod(shape):
    out = 1
    for s in shape:
        out *= s
    return out


# --------------------------------------------------------- reference
def test_reference_imports_nothing_of_the_program():
    import ast
    for module in (ref, weights):
        tree = ast.parse(open(module.__file__).read())
        names = [n.module or "" for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)]
        names += [a.name for n in ast.walk(tree)
                  if isinstance(n, ast.Import) for a in n.names]
        assert not [n for n in names if n.startswith("dlnetbench_tpu")]
    # the published constant in the gate's normaliser, not the program's
    assert "+ 1e-6)" in open(ref.__file__).read()


def test_layer_at_a_time_backward_equals_autodiff_of_the_whole_loss():
    """Every kind of layer at the rehearsal's three (conv and dense,
    attention and experts, conv and experts), the tied table's two
    parts summed."""
    cell = rehearsal_cell()
    arch = weights.arch_of(cell.config)
    p = ref.unstack(weights.make_params(arch, 3), arch)
    tokens = weights.make_token_pool(3, 1, 1, 81, arch["vocab_size"])[0]
    with jax.default_matmul_precision("highest"):
        loss, grads, chosen = ref.LayerwiseGrad(arch)(p, tokens)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda q: ref.loss_fn(q, tokens, arch)))(p)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    gaps = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-30)), grads, want)
    assert max(jax.tree.leaves(gaps)) < 1e-4
    assert [c.shape for c in chosen] == [(80, arch["top_k"])] * 2


# ------------------------------------------------- costs and readers
def test_short_conv_cost_from_shapes():
    got = short_conv.cost(batch=1, seq=8, embed_dim=5, short_conv=3,
                          layer_kinds=["conv", "gated", "conv"])
    lanes = 8 * 5
    assert got["flops"] == 2 * (2 * 7 * lanes + 21 * lanes)
    assert got["bytes"] == 2 * (2 * (4 * lanes * 2 + 30)
                                + 7 * lanes * 2 + 60)
    # at the cell's shapes the bytes bind by two orders: 2.46 ms
    cell = short_conv.cost(**weights.arch_of(
        harness.load_cell(CELL).config), batch=1, seq=8192)
    assert cell["bytes"] / 819e9 == pytest.approx(2.46e-3, rel=1e-2)
    assert cell["flops"] / 197e12 < 0.01 * cell["bytes"] / 819e9


def test_flash_cost_counts_the_true_64_lanes():
    """The count the 64-lane roofline is read against: 32 query heads of
    64 lanes, not the 128 the wrapper pads them to."""
    arch = weights.arch_of(harness.load_cell(CELL).config)
    cell = gated_flash_attention.cost(**arch, batch=1, seq=8192)
    assert cell["flops"] == 9 * 32 * 8192 * 8192 * 64      # 1.24 TFLOP
    padded = gated_flash_attention.cost(**{**arch, "head_dim": 128},
                                        batch=1, seq=8192)
    assert padded["flops"] == 2 * cell["flops"]


def test_model_flops_count_the_whole_expert_layer():
    arch = weights.arch_of(harness.load_cell(CELL).config)
    per_token = conv_moe_train.matmul_params_per_token(arch)
    # the issue's forward FLOP a token: the dense layer 122 M, a conv
    # mixer 33.6 M, the attention's projections 21 M, 4 experts of a
    # layer 88 M and the router 0.13 M, the tied head 268 M
    want = (121.6e6 + 3 * 33.55e6 + 20.97e6 + 4 * (88.08e6 + 0.131e6)
            + 268.4e6) / 2
    assert per_token == pytest.approx(want, rel=2e-3)
    flops = conv_moe_train.forward_flops_per_token(arch, 8192)
    assert flops == pytest.approx(
        2 * per_token + 32 * 2 * 4096.5 * 128 + 4 * 7 * 2048)
    # a share of the experts counts its share
    half = conv_moe_train.matmul_params_per_token(
        {**arch, "held": (0, 16)})
    assert per_token - half == pytest.approx(4 * 2 * 3 * 2048 * 1792)


@pytest.mark.parametrize("metric", SCOPE_MS)
def test_metrics_against_the_fixture(metric):
    assert read(metric, ctx()) == pytest.approx(FIX["expect"][metric])


@pytest.mark.parametrize("metric,want", [
    # the two whole executions are busy throughout, 3.6 and 3.96 s: the
    # step is their median and what every scope and the rest sum to
    ("step_device_ms.conv_moe", 3780.0),
    # busy 8.46 of the trace's 10 s, the cut executions included
    ("device_idle_pct.conv_moe", 15.4)])
def test_step_and_idle_share_against_the_fixture(metric, want):
    assert read(metric, ctx()) == pytest.approx(want)
    old = spec(metric.replace(".conv_moe",
                              "" if "step" in metric else ".train"))
    mine = spec(metric)
    assert (mine["reader"], mine["params"], mine["layer"], mine["unit"]) \
        == (old["reader"], old["params"], old["layer"], old["unit"])
    bare = ctx()        # the parent's program: no table
    bare["record"] = {k: v for k, v in FIX["record"].items()
                      if k != "program_trace"}
    assert read(metric, bare) == pytest.approx(want)


@pytest.mark.parametrize("metric,cost,seconds", [
    ("conv_gate_roofline", short_conv, "gate_seconds"),
    ("gqa64_flash_roofline", gated_flash_attention, "flash_seconds"),
    ("moe_whole_mm_roofline", held_grouped_matmul, "grouped_mm_seconds")])
def test_rooflines_against_the_fixture(metric, cost, seconds):
    c = cost.cost(**FIX["record"]["arch"], **FIX["record"])
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    want = 100.0 * least * 2 / sum(FIX["expect"][seconds])
    assert read(metric, ctx()) == pytest.approx(want)


def test_an_operation_of_the_gated_convolution_is_the_inner_scopes():
    from dlnetbench_tpu.core import executor
    for path in ("jit(train_k)/jit(main)/conv/conv.gate/mul",
                 "jit(train_k)/jvp(conv)/conv.gate/add",
                 "jit(train_k)/transpose(jvp(conv))/conv.gate/reduce_sum",
                 "jit(train_k)/conv.gate/mul"):     # the backward's own
        assert executor.scope_of_op_name(path) == "conv.gate"
    assert executor.scope_of_op_name(
        "jit(train_k)/transpose(jvp(conv))/dot_general") == "conv"


@pytest.mark.parametrize("metric", NEW)
def test_new_metrics_read_nothing_from_a_program_without_them(metric):
    """A program without these scopes or kernel names (the parent's)
    exports no table and names no such kernel: the reader gives None
    and does not raise."""
    bare = ctx()
    bare["record"] = {k: v for k, v in FIX["record"].items()
                      if k != "program_trace"}
    bare["devices"][0]["ops"] = [
        (name.replace("flash_", "custom-call.").replace(
            "grouped_mm", "custom-call"), s, d)
        for name, s, d in bare["devices"][0]["ops"]]
    assert read(metric, bare) is None
    if metric in SCOPE_MS + ["conv_gate_roofline"]:
        empty = ctx()
        empty["record"]["program_trace"] = {"op_scopes": {"jit_train_k": {
            k: "other" for k in FIX["program_trace"]["op_scopes"][
                "jit_train_k"]}}, "spans": []}
        assert read(metric, empty) is None
    if metric.endswith("_roofline"):
        assert read(metric, {**ctx(), "peaks": None}) is None


@pytest.mark.parametrize("metric", NEW + WHOLE)
def test_spec_file(metric):
    s = spec(metric)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert s["cells"] == entry["workloads"] == [CELL]
    assert (s["layer"], s["unit"], s["moves"]) == (
        entry["layer"], entry["unit"], "train_tokens_per_s")
    assert entry["source"] == "device_trace"
    assert entry["better"] == ("higher" if s["unit"] == "%"
                               and "idle" not in metric else "lower")
    if "scopes" in s["params"]:
        from dlnetbench_tpu.metrics import spans
        assert set(s["params"]["scopes"]) <= set(spans.SCOPES)
    if "kernels" in s["params"]:
        text = "".join(
            open(harness.ROOT / "dlnetbench_tpu" / "ops" / f).read()
            for f in ("flash_attention.py", "grouped_matmul.py"))
        for k in s["params"]["kernels"]:
            assert f'name="{k}"' in text


def test_manifest_has_the_cell_its_configuration_and_its_metrics():
    """By name, wherever later PRs' entries come to stand: nothing here
    asks the cell to be the manifest's last."""
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("lfm2_8b_a1b_1chip", "pretrain_b1_s8192_v65536", 1)
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "layer_types"]
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("conv_mixer_ms")
    assert names[at:at + 10] == [
        "conv_mixer_ms", "conv_gate_ms", "conv_gate_roofline",
        "gqa64_attn_ms", "gqa64_flash_roofline", "moe_whole_route_ms",
        "moe_whole_experts_ms", "moe_whole_mm_roofline", *WHOLE]
    rate = next(m for m in MANIFEST["end_to_end"]
                if m["name"] == "train_tokens_per_s")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    loaded = harness.load_cell(CELL)
    assert (loaded.traffic["batch"], loaded.traffic["seq_len"],
            loaded.traffic["pool_batches"]) == (1, 8192, 8)
    assert "65536" in loaded.traffic["what"]
    assert loaded.workload["cycle_steps"] == 48


def test_the_linear_attention_cells_entries_are_what_they_were():
    """What ``test_bench_qwen3next.py``'s
    ``test_manifest_gains_the_cell_and_changes_nothing_else`` holds,
    by name and not by the manifest's tail: that case wants its cell
    to be the last, the driver wants a new cell appended, and
    ``tests/conftest.py`` skips it for as long as both hold."""
    other = "qwen3next_a3b_train_s16k"
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == other)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("qwen3next_a3b_4l", "pretrain_b1_s16384", 1)
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("gdn_mixer_ms")
    assert names[at:at + 12] == [
        "gdn_mixer_ms", "gdn_rule_ms", "gdn_rule_roofline",
        "gated_attn_ms", "gated_flash_roofline", "moe_top10_route_ms",
        "moe_small_experts_ms", "moe_small_mm_roofline",
        "moe_gated_shared_ms", "moe_small_slot_fill_pct",
        "step_device_ms.linear_moe", "device_idle_pct.linear_moe"]
    assert all(m["workloads"] == [other]
               for m in MANIFEST["per_layer"][at:at + 12])
    assert names.index("conv_mixer_ms") == at + 12  # appended, not put in
    rate = next(m for m in MANIFEST["end_to_end"]
                if m["name"] == "train_tokens_per_s")
    cells = rate["workloads"]
    assert cells.index(CELL) == cells.index(other) + 1
    assert rate["bound"] == 0.01
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells.index(CELL) == cells.index(other) + 1
    traffic = harness.load_cell(other).traffic
    assert (traffic["batch"], traffic["seq_len"],
            traffic["pool_batches"]) == (1, 16384, 8)


# ----------- the three cases test_bench_scopes.py keys by its KIND
def traced(export):
    """``run.traced_metrics`` on the fixture's trace with the cell's
    listed metrics, the record as the runner leaves it."""
    outcome = {"record": {k: v for k, v in record().items()
                          if k != "program_trace"},
               "cache": {"hits": 0, "misses": 0},
               "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    cell = harness.load_cell(CELL)
    cell = dataclasses.replace(cell, per_layer=[
        m for m in cell.per_layer if m["name"] in NEW])
    with scope_dump.reading_unlisted(export) as seen:
        metrics = run.traced_metrics(cell, outcome, trace(), 1)[0]
    return metrics, seen


def test_scope_dump_reads_through_the_harness():
    assert scope_dump.unlisted(CELL) == []      # every spec is listed
    metrics, seen = traced(lambda: record()["program_trace"])
    assert set(metrics) == set(NEW) and metrics is seen["metrics"]
    for m in SCOPE_MS:
        assert metrics[m]["value"] == pytest.approx(FIX["expect"][m])
    got = scope_dump.report(CELL, seen, record()["program_trace"])
    assert got["scope_ms"]["conv.gate"] == pytest.approx(420.0)
    assert got["scope_ms"]["conv"] == pytest.approx(420.0)
    assert sum(got["scope_ms"].values()) == pytest.approx(3600 * 1.05)
    assert got["top_ops"][0][0] in ("conv", "head_loss", "attn")


def test_scope_dump_fails_the_run_on_a_program_without_scopes():
    with pytest.raises(harness.BenchError, match="found nothing to read"):
        traced(lambda: {"spans": [], "op_scopes": {}})


def test_run_with_the_programs_tracer_names_every_new_layer(capsys):
    """The whole runner at the rehearsal size with the program's tracer
    on: the step's own table holds every scope the new metrics read,
    the gated convolution's backward among them, the plan's pair side
    is marked at every site, and the tracer is off again afterwards."""
    from dlnetbench_tpu.metrics import spans
    rc, seen, got = scope_dump.run_with_program_tracer(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.5",
         "--trace", "0", "--rehearse-cpu", "1"])
    capsys.readouterr()
    assert rc != 0 and not spans.is_enabled() and seen == {}
    scopes = set(got["op_scopes"]["jit_train_k"].values())
    assert {"conv", "conv.gate", "attn", "mlp", "moe.router",
            "moe.dispatch", "moe.experts", "moe.combine", "head_loss",
            "optimizer", "embed"} <= scopes
    assert "moe.shared" not in scopes           # no shared expert
    assert {s["name"] for s in got["spans"]} == {"compile"}
    marks = [m for s in got["spans"]
             for m in s["attrs"].get("moe.plan_side", [])]
    assert marks and {m["side"] for m in marks} == {"pairs"}
