"""The linear-attention cell's own pieces: the controls and the two
planted faults that have to come out as not correct at the rehearsal
size, a row past the experts' bound failing the run, the configuration
file against the catalog's row, the new costs and readers on a fixture
of their own (``scope_fixture_linear_moe.json``), each reader giving
nothing from a program without what it reads, and the three cases that
``test_bench_scopes.py`` keys by its table of the gated-decoder cells
(``tests/conftest.py`` skips them for this cell)."""
import dataclasses
import importlib
import json

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness, run, scope_dump
from benchmarks import reference_linear_moe as ref
from benchmarks import weights_linear_moe as weights
from benchmarks.costs import (gated_delta_rule, gated_flash_attention,
                              held_grouped_matmul, linear_moe_train)
from benchmarks.runners import train_latent_moe, train_linear_moe

CELL = "qwen3next_a3b_train_s16k"
FIX = harness.load_json(harness.HERE / "scope_fixture_linear_moe.json")
MANIFEST = harness.load_json(harness.ROOT / "BENCHMARK.json")
SCOPE_MS = ["gdn_mixer_ms", "gdn_rule_ms", "gated_attn_ms",
            "moe_top10_route_ms", "moe_small_experts_ms",
            "moe_gated_shared_ms"]
ROOFLINES = ["gdn_rule_roofline", "gated_flash_roofline",
             "moe_small_mm_roofline"]
NEW = SCOPE_MS + ROOFLINES + ["moe_small_slot_fill_pct"]
# the whole step and the device's idle share: copies, for this cell, of
# two entries the benchmark has and this PR may not join; they read the
# trace alone, so a program without this PR's scopes gives them too
WHOLE = ["step_device_ms.linear_moe", "device_idle_pct.linear_moe"]


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
    import functools
    real = train_linear_moe.LinearMoeCell.reference_steps

    @functools.lru_cache(maxsize=None)
    def steps(seed, precision):
        return real(cells[seed], precision)
    cells = {}

    def cached(self, precision="float32"):
        cells[self.seed] = self
        return steps(self.seed, precision)
    train_linear_moe.LinearMoeCell.reference_steps = cached
    yield
    train_linear_moe.LinearMoeCell.reference_steps = real


@pytest.mark.parametrize("control", ["reference_int8", "no_decay",
                                     "no_shared_gate"])
def test_controls_and_planted_faults_at_the_rehearsal_size(one_reference,
                                                           control):
    """The int8 reference, a rule whose state never decays and a shared
    expert without its gate are each not correct
    (``test_bench_rehearsal.py`` runs the sound program: correct)."""
    rows = train_linear_moe.readings(rehearsal_cell(), 2**31 + 11,
                                     lambda _: None, control)
    assert [name for name, *_ in rows] == [
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "selection_gap"]
    assert "grad_norm_gap" in bad(rows)


def test_unknown_control_is_refused():
    with pytest.raises(harness.BenchError, match="no control"):
        train_linear_moe.readings(rehearsal_cell(), 7, lambda _: None,
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
    monkeypatch.setattr(train_linear_moe.LinearMoeCell, "call", call)
    got, result = rehearse(capsys)
    assert result["correct"] is False
    assert "delta_norm_gap" in {g["name"] for g in got
                                if g["line"] == "compared" and not g["ok"]}
    window = next(g for g in got if g["line"] == "window")
    assert window["moe_slots"] == 24 < window["moe_max_load"]
    assert window["moe_rows_past_bound"] > 0
    assert 0 < result["failed"] <= result["attempted"]


# ----------------------------------------------------- configuration
def test_configuration_keeps_every_published_width():
    """Every number of the catalog row's ``config`` under its key, but
    the two reduced ones, each beside its published count."""
    catalog = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    body = harness.load_cell(CELL).config
    reduced = {"num_hidden_layers", "num_experts"}
    assert set(body["reduced"]) == reduced
    assert {k: body[k] for k in catalog if k not in reduced} \
        == {k: v for k, v in catalog.items() if k not in reduced}
    assert body["published"] == {k: catalog[k] for k in reduced}
    assert body["num_experts"] in (64, 32) and body["num_hidden_layers"] == 4
    chips = {64: "eight", 32: "sixteen"}[body["num_experts"]]
    assert f"{chips} v5e chips" in body["deployment"] \
        and "not run" in body["deployment"]
    assert set(body["assumed"]) >= {"first_held_expert", "decay_max", "why"}
    assert "13.0 GB" in body["held_by_the_rule"]
    arch = weights.arch_of(body)
    assert arch["layer_kinds"] == ("gdn", "gdn", "gdn", "gated")
    assert (arch["num_experts"], arch["held"], arch["top_k"]) \
        == (512, (0, body["num_experts"]), 10)
    params = {k: math_prod(shape)
              for k, (shape, _) in weights.shapes(arch).items()}

    def group(g):
        return sum(v for k, v in params.items() if k.startswith(g + "/"))
    # the issue's table: a linear mixer 33.72 M, the gated attention
    # 27.26 M, router, shared expert and its gate 4.20 M a layer, a
    # routed expert 3.146 M, embedding and head 622.3 M
    assert group("gdn") == pytest.approx(3 * 33.72e6, rel=1e-3)
    assert group("gated") == pytest.approx(27.26e6, rel=1e-3)
    assert group("moe") == pytest.approx(
        4 * (4.20e6 + body["num_experts"] * 3.1457e6), rel=1e-3)
    assert params["embed"] + params["head"] == 2 * 151936 * 2048
    if body["num_experts"] == 64:
        assert sum(params.values()) == pytest.approx(1572.9e6, rel=1e-3)


def math_prod(shape):
    out = 1
    for s in shape:
        out *= s
    return out


# --------------------------------------------------------- reference
def test_reference_imports_nothing_of_the_program_and_no_chunked_rule():
    import ast
    for module in (ref, weights):
        text = open(module.__file__).read()
        tree = ast.parse(text)
        names = [n.module or "" for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)]
        names += [a.name for n in ast.walk(tree)
                  if isinstance(n, ast.Import) for a in n.names]
        assert not [n for n in names if n.startswith("dlnetbench_tpu")]
    # the rule is the token recurrence: a scan whose step is one token
    assert "jax.lax.scan(token" in open(ref.__file__).read()


def test_layer_at_a_time_backward_equals_autodiff_of_the_whole_loss():
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
    assert [c.shape for c in chosen] \
        == [(80, arch["top_k"])] * arch["num_layers"]


# ------------------------------------------------- costs and readers
def test_delta_rule_cost_from_shapes():
    got = gated_delta_rule.cost(
        batch=1, seq=8, layer_kinds=["gdn", "gated", "gdn"],
        linear_key_heads=2, linear_value_heads=4, linear_key_dim=3,
        linear_value_dim=5)
    forward = 8 * 4 * 7 * 3 * 5
    assert got["flops"] == 2 * 4 * forward    # fwd, again, twice that back
    inputs = 8 * ((2 * 2 * 3 + 4 * 5) * 2 + 2 * 4 * 4)
    out = 8 * 4 * 5 * 2
    assert got["bytes"] == 2 * (2 * (inputs + out) + 2 * inputs + out)
    # at the cell's shapes the bytes bind: about 5.5 ms against 3.7
    cell = gated_delta_rule.cost(**weights.arch_of(
        harness.load_cell(CELL).config), batch=1, seq=16384)
    assert cell["flops"] / 197e12 == pytest.approx(3.66e-3, rel=1e-2)
    assert cell["bytes"] / 819e9 == pytest.approx(5.47e-3, rel=1e-2)


def test_gated_flash_cost_from_shapes():
    got = gated_flash_attention.cost(
        batch=1, seq=4, num_heads=4, num_kv_heads=2, head_dim=3,
        layer_kinds=["gdn", "gated"])
    unit = 2 * 4 * 4 * 4 * 3 // 2
    assert got["flops"] == 2 * 2 * unit + 5 * unit
    q, kv = 4 * 4 * 3 * 2, 4 * 2 * 3 * 2
    assert got["bytes"] == 2 * (2 * q + 2 * kv) + 4 * q + 4 * kv
    cell = gated_flash_attention.cost(**weights.arch_of(
        harness.load_cell(CELL).config), batch=1, seq=16384)
    assert cell["flops"] == 9 * 16 * 16384 * 16384 * 256    # 9.9 TFLOP


def test_model_flops_count_the_held_share_and_the_recurrence():
    arch = weights.arch_of(harness.load_cell(CELL).config)
    per_token = linear_moe_train.matmul_params_per_token(arch)
    held = arch["held"][1]
    # the issue's forward FLOP a token: a linear layer's projections
    # 67.4 M, the full layer's 54.5 M, router 2.1 M, shared expert
    # 6.3 M, the held experts' 10 * held / 512 of 3.15 M x 2, head 622 M
    want = (3 * 67.4e6 + 54.5e6 + 4 * (2.1e6 + 6.3e6)
            + 4 * 10 * held / 512 * 6.29e6 + 622.3e6) / 2
    assert per_token == pytest.approx(want, rel=2e-3)
    flops = linear_moe_train.forward_flops_per_token(arch, 16384)
    rule = 3 * (7 * 32 * 128 * 128 + 2 * 4 * 8192)
    assert flops == pytest.approx(
        2 * per_token + 16 * 2 * 8192.5 * 512 + rule)


@pytest.mark.parametrize("metric",
                         SCOPE_MS + ["moe_small_slot_fill_pct"])
def test_metrics_against_the_fixture(metric):
    assert read(metric, ctx()) == pytest.approx(FIX["expect"][metric])


@pytest.mark.parametrize("metric,want", [
    # the two whole executions are busy throughout, 3.6 and 3.96 s: the
    # step is their median and what every scope and the rest sum to
    ("step_device_ms.linear_moe", 3780.0),
    # busy 8.46 of the trace's 10 s, the cut executions included
    ("device_idle_pct.linear_moe", 15.4)])
def test_step_and_idle_share_against_the_fixture(metric, want):
    assert read(metric, ctx()) == pytest.approx(want)
    old = spec(metric.replace(".linear_moe",
                              "" if "step" in metric else ".train"))
    mine = spec(metric)
    assert (mine["reader"], mine["params"], mine["layer"], mine["unit"]) \
        == (old["reader"], old["params"], old["layer"], old["unit"])
    bare = ctx()        # the parent's program: no table, no counters
    bare["record"] = {k: v for k, v in FIX["record"].items()
                      if k not in ("moe", "program_trace")}
    assert read(metric, bare) == pytest.approx(want)


@pytest.mark.parametrize("metric,cost,seconds", [
    ("gdn_rule_roofline", gated_delta_rule, "rule_seconds"),
    ("gated_flash_roofline", gated_flash_attention, "flash_seconds"),
    ("moe_small_mm_roofline", held_grouped_matmul, "grouped_mm_seconds")])
def test_rooflines_against_the_fixture(metric, cost, seconds):
    c = cost.cost(**FIX["record"]["arch"], **FIX["record"])
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    want = 100.0 * least * 2 / sum(FIX["expect"][seconds])
    assert read(metric, ctx()) == pytest.approx(want)


def test_an_operation_of_the_rule_is_the_inner_scopes():
    from dlnetbench_tpu.core import executor
    for path in ("jit(train_k)/jit(main)/linattn/linattn.rule/exp",
                 "jit(train_k)/jvp(linattn)/linattn.rule/while",
                 "jit(train_k)/transpose(jvp(linattn))/linattn.rule/dot"):
        assert executor.scope_of_op_name(path) == "linattn.rule"
    assert executor.scope_of_op_name(
        "jit(train_k)/transpose(jvp(linattn))/mul") == "linattn"


@pytest.mark.parametrize("metric", NEW)
def test_new_metrics_read_nothing_from_a_program_without_them(metric):
    """A program without these scopes, kernel names or counters (the
    parent's) exports no table, names no such kernel and returns no
    routing: the reader gives None and does not raise."""
    bare = ctx()
    bare["record"] = {k: v for k, v in FIX["record"].items()
                      if k not in ("moe", "program_trace")}
    bare["devices"][0]["ops"] = [
        (name.replace("flash_", "custom-call.").replace(
            "grouped_mm", "custom-call"), s, d)
        for name, s, d in bare["devices"][0]["ops"]]
    assert read(metric, bare) is None
    if metric in SCOPE_MS + ["gdn_rule_roofline"]:
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
    assert entry["source"] == ("program_counter"
                               if metric == "moe_small_slot_fill_pct"
                               else "device_trace")
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


def test_manifest_gains_the_cell_and_changes_nothing_else():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == MANIFEST["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("qwen3next_a3b_4l", "pretrain_b1_s16384", 1)
    assert [m["name"] for m in MANIFEST["per_layer"][-12:]] == [
        "gdn_mixer_ms", "gdn_rule_ms", "gdn_rule_roofline",
        "gated_attn_ms", "gated_flash_roofline", "moe_top10_route_ms",
        "moe_small_experts_ms", "moe_small_mm_roofline",
        "moe_gated_shared_ms", "moe_small_slot_fill_pct", *WHOLE]
    rate = next(m for m in MANIFEST["end_to_end"]
                if m["name"] == "train_tokens_per_s")
    assert rate["workloads"][-1] == CELL and rate["bound"] == 0.01
    traffic = harness.load_cell(CELL).traffic
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
    for m in SCOPE_MS + ["moe_small_slot_fill_pct"]:
        assert metrics[m]["value"] == pytest.approx(FIX["expect"][m])
    got = scope_dump.report(CELL, seen, record()["program_trace"])
    assert got["scope_ms"]["linattn.rule"] == pytest.approx(630.0)
    assert got["scope_ms"]["linattn"] == pytest.approx(420.0)
    assert sum(got["scope_ms"].values()) == pytest.approx(3600 * 1.05)
    assert got["top_ops"][0][0] in ("linattn", "head_loss", "attn")


def test_scope_dump_fails_the_run_on_a_program_without_scopes():
    with pytest.raises(harness.BenchError, match="found nothing to read"):
        traced(lambda: {"spans": [], "op_scopes": {}})


def test_run_with_the_programs_tracer_names_every_new_layer(capsys):
    """The whole runner at the rehearsal size with the program's tracer
    on: the step's own table holds every scope the new metrics read,
    the rule's backward among them, and the tracer is off again
    afterwards."""
    from dlnetbench_tpu.metrics import spans
    rc, seen, got = scope_dump.run_with_program_tracer(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.5",
         "--trace", "0", "--rehearse-cpu", "1"])
    capsys.readouterr()
    assert rc != 0 and not spans.is_enabled() and seen == {}
    scopes = set(got["op_scopes"]["jit_train_k"].values())
    assert {"linattn", "linattn.rule", "attn", "moe.router",
            "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
            "head_loss", "optimizer", "embed"} <= scopes
    assert {s["name"] for s in got["spans"]} == {"compile"}
