"""The head-gated window-and-full attention cell's own pieces: the
rehearsal run correct, the control and the three planted faults that
have to come out as not correct at the rehearsal size, an unchanged
state and a row past the experts' bound failing the run, the
configuration file against the catalog's row with the issue's parameter
counts, the new costs against hand counts and the readers on a fixture
of their own (``scope_fixture_headgate_moe.json``), each reader giving
nothing from a program without what it reads, and the three cases that
``test_bench_scopes.py`` keys by its table of the gated-decoder cells
(``tests/conftest.py`` skips them for this cell)."""
import dataclasses
import functools
import importlib
import json
import math

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness, run, scope_dump
from benchmarks import reference_headgate_moe as ref
from benchmarks import weights_headgate_moe as weights
from benchmarks.costs import (headgate_full_flash_attention,
                              headgate_moe_train,
                              headgate_window_flash_attention,
                              held_grouped_matmul, window_flash_attention)
from benchmarks.runners import train_headgate_moe, train_latent_moe

CELL = "laguna_s21_train_s16k"
FIX = harness.load_json(harness.HERE / "scope_fixture_headgate_moe.json")
MANIFEST = harness.load_json(harness.ROOT / "BENCHMARK.json")
SCOPE_MS = ["headgate_proj_ms", "headgate_gate_ms", "headgate_window_ms",
            "headgate_full_ms", "moe_top10of256_route_ms",
            "moe_scaled_shared_ms", "moe_scaled_experts_ms"]
ROOFLINES = ["headgate_window_roofline", "headgate_full_roofline",
             "moe_scaled_mm_roofline"]
NEW = SCOPE_MS + ROOFLINES + ["moe_scaled_slot_fill_pct"]
# the whole step and the device's idle share: copies, for this cell, of
# two entries the benchmark has; they read the trace alone, so a program
# without this PR's scopes gives them too
WHOLE = ["step_device_ms.headgate_moe", "device_idle_pct.headgate_moe"]
REDUCED = {"num_hidden_layers", "layer_types", "mlp_layer_types",
           "gating_types", "num_attention_heads_per_layer", "num_experts"}


def catalog_row() -> dict:
    """The catalog's row for Laguna-S-2.1, where the guide's catalog is
    installed; its keys as this file states them otherwise."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                if row["name"] == "Laguna-S-2.1":
                    return row["config"]
    except OSError:
        pass
    period = ["full_attention"] + ["sliding_attention"] * 3
    return {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 1048576,
        "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": period * 12,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "moe_router_logit_softcapping": 0}


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
    real = train_headgate_moe.HeadgateMoeCell.reference_steps

    @functools.lru_cache(maxsize=None)
    def steps(seed, precision):
        return real(cells[seed], precision)
    cells = {}

    def cached(self, precision="float32"):
        cells[self.seed] = self
        return steps(self.seed, precision)
    train_headgate_moe.HeadgateMoeCell.reference_steps = cached
    yield
    train_headgate_moe.HeadgateMoeCell.reference_steps = real


@pytest.mark.parametrize("control,fails", [
    (None, None), ("reference_int8", "grad_norm_gap"),
    ("no_head_gate", "delta_norm_gap"), ("plain_rope", "grad_norm_gap"),
    ("unit_routed_scale", "grad_norm_gap")])
def test_the_program_is_correct_and_each_control_and_fault_is_not(
        one_reference, control, fails):
    """The sound program at the rehearsal size is correct by all four
    numbers; the int8 reference, a program without the gate a head (its
    ``wg`` never moves: 1.0, what an unchanged state reads, or more), a
    full layer turned as a window layer is and routed weights times 1.0
    are each not."""
    rows = train_headgate_moe.readings(rehearsal_cell(), 2**31 + 11,
                                       lambda _: None, control)
    assert [name for name, *_ in rows] == [
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "selection_gap"]
    if control is None:
        assert not bad(rows)
        return
    assert fails in bad(rows)
    if control == "no_head_gate":
        # at least what a leaf that never moves reads
        assert {r[0]: r[1] for r in rows}["delta_norm_gap"] >= 0.999


def test_a_planted_fault_is_a_switch_of_the_configuration_alone():
    cell = rehearsal_cell()
    arch = weights.arch_of(cell.config)
    sound = train_headgate_moe.program_config(cell, arch)
    assert (sound.attn_gate, sound.routed_scale, sound.rope_theta,
            sound.rope_dim, bool(sound.rope_yarn)) == (
        "head", 2.5, 5e5, 8, True)
    assert set(train_headgate_moe.FAULTS) == {
        "no_head_gate", "plain_rope", "unit_routed_scale"}
    for name, over in train_headgate_moe.FAULTS.items():
        cfg = train_headgate_moe.program_config(cell, arch, over)
        assert cfg != sound, name
        assert dataclasses.replace(
            cfg, **{k: getattr(sound, k) for k in over}) == sound
        # the window layers keep their own positions under every fault
        assert cfg.rope_of("swa") == sound.rope_of("swa")
    plain = train_headgate_moe.program_config(
        cell, arch, train_headgate_moe.FAULTS["plain_rope"])
    assert plain.rope_of("gated") == (10000.0, 16, None)


def test_the_cell_compares_the_four_numbers_the_latent_cell_compares():
    limits = harness.load_cell(CELL).workload["limits"]
    assert set(limits) == {"loss_gap", "grad_norm_gap", "delta_norm_gap",
                           "selection_gap"}
    # the cell's own: three times its sound largest (limits_from), not
    # a limit borrowed from another cell
    assert 3 * 2.97e-5 <= limits["loss_gap"] <= 4 * 2.97e-5
    got = {"losses": [2.0, float("nan")], "chosen": jnp.zeros((1, 4, 2)),
           "grad_norms": {"w": 1.0}, "delta_norms": {"w": 1.0}}
    want = {**got, "losses": [2.0, 2.0]}
    rows = train_headgate_moe.compare(got, want, limits)
    assert [r[0] for r in rows] == ["loss_gap", "grad_norm_gap",
                                    "delta_norm_gap", "selection_gap"]
    assert rows[0][:3] == ("loss_gap", float("inf"), limits["loss_gap"])


def test_unknown_control_is_refused():
    with pytest.raises(harness.BenchError, match="no control"):
        train_headgate_moe.readings(rehearsal_cell(), 7, lambda _: None,
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
    monkeypatch.setattr(train_headgate_moe.HeadgateMoeCell, "call", call)
    got, result = rehearse(capsys)
    assert result["correct"] is False
    assert "delta_norm_gap" in {g["name"] for g in got
                                if g["line"] == "compared" and not g["ok"]}
    window = next(g for g in got if g["line"] == "window")
    assert window["moe_slots"] == 24 < window["moe_max_load"]
    assert window["moe_rows_past_bound"] > 0
    assert 0 < result["failed"] <= result["attempted"]
    assert window["cycle_steps"] == 8 and window["cycles_repeat"] is True


def test_runner_refuses_a_program_without_the_new_fields(monkeypatch):
    """On the parent's program the runner's import raises the harness's
    own error: the run exits non-zero at once, with no result."""
    import sys

    from dlnetbench_tpu.core import model_card
    from dlnetbench_tpu.metrics import spans

    @dataclasses.dataclass(frozen=True)
    class ParentCard:
        name: str
        rope_theta: float = 0.0
    for module, name, value in (
            (model_card, "ModelCard", ParentCard),
            (spans, "SCOPES", tuple(s for s in spans.SCOPES
                                    if s != "attn.gate"))):
        with monkeypatch.context() as m:
            m.setattr(module, name, value)
            m.delitem(sys.modules, "benchmarks.runners.train_headgate_moe")
            with pytest.raises(harness.BenchError,
                               match="cannot run the head-gated"):
                importlib.import_module(
                    "benchmarks.runners.train_headgate_moe")
    importlib.import_module("benchmarks.runners.train_headgate_moe")


# ----------------------------------------------------- configuration
def test_configuration_keeps_every_published_width():
    """Every key of the catalog row's ``config`` under its name, but the
    six reduced ones, each beside its published value; the whole
    vocabulary, the router's 256 outputs and top-10."""
    body = harness.load_cell(CELL).config
    row = catalog_row()
    assert set(body["reduced"]) == REDUCED
    assert {k: body[k] for k in row if k not in REDUCED} \
        == {k: v for k, v in row.items() if k not in REDUCED}
    assert body["published"] == {k: row[k] for k in REDUCED}
    # the cut: published layers 0-4, 16 of 256 experts held
    for key in REDUCED - {"num_hidden_layers", "num_experts"}:
        assert body[key] == row[key][:5], key
    assert body["layer_types"] == ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert body["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert body["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert (body["num_hidden_layers"], body["num_experts"]) == (5, 16)
    assert (body["hidden_size"], body["head_dim"],
            body["num_key_value_heads"], body["sliding_window"],
            body["moe_intermediate_size"], body["num_experts_per_tok"],
            body["moe_routed_scaling_factor"], body["intermediate_size"],
            body["vocab_size"]) == (3072, 128, 8, 512, 1024, 10, 2.5,
                                    12288, 100352)
    assert "sixteen chips" in body["deployment"]
    assert set(body["assumed"]) >= {"first_held_expert", "why"}
    assert "14.0 GB" in body["cut_by_the_rule"] \
        and "8 held" in body["cut_by_the_rule"]
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "laguna_s21_ep16")
    assert entry["source"] == body["source"] \
        == "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"


def test_the_cuts_parameter_counts_are_the_issues():
    arch = weights.arch_of(harness.load_cell(CELL).config)
    assert arch["layer_kinds"] == ("gated", "swa", "swa", "swa", "gated")
    assert (arch["num_experts"], arch["held"], arch["top_k"],
            arch["num_heads"], arch["window_heads"], arch["num_kv_heads"],
            arch["first_dense"], arch["routed_scale"]) \
        == (256, (0, 16), 10, 48, 72, 8, 1, 2.5)
    assert arch["rope_window"] == (10000.0, 128, None)
    assert arch["rope_full"] == (500000.0, 64, (
        128.0, 8192.0, 32.0, 1.0, 1.4852030263919618))
    params = {k: math.prod(shape)
              for k, (shape, _) in weights.shapes(arch).items()}

    def group(g):
        return sum(v for k, v in params.items() if k.startswith(g + "/"))
    # the issue's table: a full layer's attention 44.19 M, a window
    # layer's 63.13 M, the dense MLP 113.25 M, an expert layer's router
    # 0.79 M, shared expert 9.44 M and 16 experts of 9.437 M, each
    # table 308.3 M: 1652.6 M in all
    assert group("gated") == pytest.approx(2 * 44.19e6, rel=1e-3)
    assert group("swa") == pytest.approx(3 * 63.13e6, rel=1e-3)
    assert group("block") == pytest.approx(113.25e6, rel=1e-3)
    assert group("moe") == pytest.approx(
        4 * (0.786e6 + 9.437e6 + 16 * 9.437e6), rel=1e-3)
    assert params["embed"] == params["head"] == 100352 * 3072
    assert sum(params.values()) == pytest.approx(1652.6e6, rel=1e-3)
    # a kind of layer has one head count, dense layers lead
    body = harness.load_cell(CELL).config
    for over in ({"num_attention_heads_per_layer": [48, 72, 64, 72, 48]},
                 {"mlp_only_layers": [1]}, {"gating": "per-lane"},
                 {"layer_types": ["full_attention"] * 4}):
        with pytest.raises(ValueError, match="neither side computes"):
            weights.arch_of({**body, **over})


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


def test_layer_at_a_time_backward_equals_autodiff_of_the_whole_loss():
    """Every kind of layer of the rehearsal's five (a full layer with a
    dense MLP, window layers and a full layer with experts), the
    selections of the four expert layers, the untied head."""
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
    assert [c.shape for c in chosen] == [(80, arch["top_k"])] * 4


# ------------------------------------------------- costs and readers
def test_attention_costs_count_each_kinds_pairs_at_its_own_heads():
    arch = weights.arch_of(harness.load_cell(CELL).config)
    s, w = 16384, 512
    band = w * (w + 1) // 2 + (s - w) * w
    assert window_flash_attention.pairs("swa", s, w) == band \
        == sum(min(t + 1, w) for t in range(s))
    win = headgate_window_flash_attention.cost(**arch, batch=1, seq=s)
    full = headgate_full_flash_attention.cost(**arch, batch=1, seq=s)
    # nine products' worth (forward twice, the backward's five) of
    # 2 x heads x 128 lanes a pair: three window layers at 72 heads, two
    # full ones at 48
    assert win["flops"] == 3 * 9 * 2 * 72 * 128 * band
    assert full["flops"] == 2 * 9 * 2 * 48 * 128 * (s * (s + 1) // 2)
    # a window layer does 0.09 of a full layer's true work
    assert win["flops"] / 3 / (full["flops"] / 2) \
        == pytest.approx(0.0923, abs=1e-3)
    # a group's gradients count at the 8 heads the model has
    q, kv = s * 48 * 128 * 2, s * 8 * 128 * 2
    assert full["bytes"] == 2 * (2 * (2 * q + 2 * kv) + 4 * q + 4 * kv)
    q = s * 72 * 128 * 2
    assert win["bytes"] == 3 * (2 * (2 * q + 2 * kv) + 4 * q + 4 * kv)
    # both compute-bound at the peaks: 21 and 151 ms are the least times
    assert win["flops"] / 197e12 == pytest.approx(0.0209, rel=1e-2)
    assert full["flops"] / 197e12 == pytest.approx(0.1507, rel=1e-2)
    assert win["bytes"] / 819e9 < 0.5 * win["flops"] / 197e12


def test_model_flops_are_the_issues_count_of_the_forward():
    """ISSUE 47's MACs a token at S = 16384: attention 507 M
    (projections and gates 278 M, full scores and values 2 x 100.7 M,
    window 3 x 9.4 M), the head 308 M, the dense MLP 113 M, router,
    shared and held experts 64 M: 993 M, attention 51 %."""
    arch = weights.arch_of(harness.load_cell(CELL).config)
    per_token = headgate_moe_train.matmul_params_per_token(arch)
    proj = 2 * 44.19e6 + 3 * 63.13e6
    ffn = 4 * (0.786e6 + 9.437e6 + 10 * 16 / 256 * 9.437e6)
    assert proj == pytest.approx(278e6, rel=2e-3)
    assert ffn == pytest.approx(64e6, rel=1e-2)
    assert per_token == pytest.approx(
        proj + 113.25e6 + ffn + 100352 * 3072, rel=1e-3)
    scores = headgate_moe_train.forward_flops_per_token(arch, 16384) \
        - 2 * per_token
    full = 48 * 2 * 8192.5 * 128        # MACs a token a full layer
    band = window_flash_attention.pairs("swa", 16384, 512) / 16384
    assert full == pytest.approx(100.7e6, rel=1e-3)
    assert 72 * 2 * band * 128 == pytest.approx(9.3e6, rel=1e-2)
    assert scores / 2 == pytest.approx(2 * full + 3 * 72 * 2 * band * 128)
    forward = headgate_moe_train.forward_flops_per_token(arch, 16384) / 2
    assert forward == pytest.approx(993e6, rel=2e-3)
    assert (proj + scores / 2) / forward == pytest.approx(0.51, abs=0.005)
    assert 100352 * 3072 / forward == pytest.approx(0.31, abs=0.005)
    whole = headgate_moe_train.matmul_params_per_token(
        {**arch, "held": (0, 256)})
    assert whole - per_token == pytest.approx(
        4 * 10 * (240 / 256) * 3 * 3072 * 1024)
    # the grouped matmuls' count: 640 rows an expert under an even router
    c = held_grouped_matmul.cost(**arch, batch=1, seq=16384)
    assert c["flops"] == 2 * 4 * 3 * 2 * (16 * 640) * 3072 * 1024


@pytest.mark.parametrize("metric", SCOPE_MS + ["moe_scaled_slot_fill_pct"])
def test_metrics_against_the_fixture(metric):
    assert read(metric, ctx()) == pytest.approx(FIX["expect"][metric])


@pytest.mark.parametrize("metric,want", [
    # the two whole executions are busy throughout, 3.6 and 3.96 s: the
    # step is their median and what every scope and the rest sum to
    ("step_device_ms.headgate_moe", 3780.0),
    # busy 8.42 of the trace's 10 s, the cut executions included
    ("device_idle_pct.headgate_moe", 15.8)])
def test_step_and_idle_share_against_the_fixture(metric, want):
    assert read(metric, ctx()) == pytest.approx(want)
    old = spec(metric.replace(".headgate_moe",
                              "" if "step" in metric else ".train"))
    mine = spec(metric)
    assert (mine["reader"], mine["params"], mine["layer"], mine["unit"]) \
        == (old["reader"], old["params"], old["layer"], old["unit"])
    bare = ctx()        # the parent's program: no table
    bare["record"] = {k: v for k, v in FIX["record"].items()
                      if k != "program_trace"}
    assert read(metric, bare) == pytest.approx(want)
    # the scopes' own sum is the step
    assert sum(FIX["expect"][m] for m in SCOPE_MS) + 1050 * (
        0.2 + 0.4 + 0.2 + 0.2) == pytest.approx(3780.0)


@pytest.mark.parametrize("metric,cost,seconds", [
    ("headgate_window_roofline", headgate_window_flash_attention,
     "window_seconds"),
    ("headgate_full_roofline", headgate_full_flash_attention,
     "full_seconds"),
    ("moe_scaled_mm_roofline", held_grouped_matmul, "grouped_mm_seconds")])
def test_rooflines_against_the_fixture(metric, cost, seconds):
    c = cost.cost(**FIX["record"]["arch"], **FIX["record"])
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    want = 100.0 * least * 2 / sum(FIX["expect"][seconds])
    assert read(metric, ctx()) == pytest.approx(want)


def test_the_gate_and_both_kinds_of_kernel_are_parted_by_scope():
    table = FIX["program_trace"]["op_scopes"]["jit_train_k"]
    by_scope = {}
    for inst, scope in table.items():
        by_scope.setdefault(scope, set()).add(inst.rsplit(".", 1)[0])
    assert by_scope["attn.window"] >= {"flash_fwd", "flash_bwd_dkv",
                                       "fusion"}
    assert by_scope["attn.full"] >= {"flash_fwd", "flash_bwd_dkv", "fusion"}
    assert by_scope["attn.gate"] == {"fusion"}
    assert spec("headgate_window_roofline")["reader"] == "scope_roofline" \
        == spec("headgate_full_roofline")["reader"]
    from dlnetbench_tpu.core import executor
    for path, want in (
            ("jit(train_k)/jit(main)/attn/attn.gate/logistic", "attn.gate"),
            ("jit(train_k)/transpose(jvp(attn))/attn.gate/dot_general",
             "attn.gate"),
            ("jit(train_k)/checkpoint/attn/attn.full/pallas_call",
             "attn.full"),
            ("jit(train_k)/transpose(jvp(attn))/dot_general", "attn")):
        assert executor.scope_of_op_name(path) == want


@pytest.mark.parametrize("metric", NEW)
def test_new_metrics_read_nothing_from_a_program_without_them(metric):
    """A program without these scopes or kernel names (the parent's)
    exports no table, names no such kernel and returns no routing: the
    reader gives None and does not raise."""
    bare = ctx()
    bare["record"] = {k: v for k, v in FIX["record"].items()
                      if k not in ("program_trace", "moe")}
    bare["devices"][0]["ops"] = [
        (name.replace("flash_", "custom-call.").replace(
            "grouped_mm", "custom-call"), s, d)
        for name, s, d in bare["devices"][0]["ops"]]
    assert read(metric, bare) is None
    if metric in SCOPE_MS + ROOFLINES[:2]:
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
    assert entry["source"] == ("program_counter" if "fill" in metric
                               else "device_trace")
    assert entry["better"] == ("higher" if s["unit"] == "%"
                               and "idle" not in metric else "lower")
    if "scopes" in s["params"]:
        from dlnetbench_tpu.metrics import spans
        assert set(s["params"]["scopes"]) <= set(spans.SCOPES)
    if "kernels" in s["params"]:
        text = open(harness.ROOT / "dlnetbench_tpu" / "ops"
                    / "grouped_matmul.py").read()
        for k in s["params"]["kernels"]:
            assert f'name="{k}"' in text


def test_manifest_has_the_cell_its_configuration_and_its_metrics():
    """By name, wherever later PRs' entries come to stand: nothing here
    asks the cell to be the manifest's last."""
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("laguna_s21_ep16", "pretrain_b1_s16384_v100352", 1)
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == cell["config"])
    assert set(config["reduced"]) == REDUCED
    assert config["file"] == "benchmarks/configs/laguna_s21_ep16.json"
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("headgate_proj_ms")
    assert names[at:at + 13] == [
        "headgate_proj_ms", "headgate_gate_ms", "headgate_window_ms",
        "headgate_full_ms", "headgate_window_roofline",
        "headgate_full_roofline", "moe_top10of256_route_ms",
        "moe_scaled_shared_ms", "moe_scaled_experts_ms",
        "moe_scaled_mm_roofline", "moe_scaled_slot_fill_pct", *WHOLE]
    rate = next(m for m in MANIFEST["end_to_end"]
                if m["name"] == "train_tokens_per_s")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    loaded = harness.load_cell(CELL)
    assert (loaded.traffic["batch"], loaded.traffic["seq_len"],
            loaded.traffic["pool_batches"]) == (1, 16384, 8)
    assert "100352" in loaded.traffic["what"]
    assert loaded.workload["cycle_steps"] == 24
    assert loaded.workload["runner"] == "train_headgate_moe"
    assert {m["name"] for m in loaded.per_layer} == set(NEW + WHOLE) | {
        "compile_cache_misses"}
    for text in (cell["why"], config["why"]):
        assert len(text) <= 200


def test_the_older_cells_entries_are_what_they_were():
    """The entries this PR found, by name: every older configuration,
    cell and per-layer metric in the order it had, this cell's behind
    them, and no older metric lists this cell."""
    cells = [w["name"] for w in MANIFEST["workloads"]]
    older = ["minerva7b_train", "mixtral8x7b_train",
             "phi4miniflash_train_s8k", "kimivl_a3b_train_s8k",
             "qwen3next_a3b_train_s16k", "lfm2_8b_a1b_train_s8k",
             "smallthinker_21b_a3b_train_s16k"]
    assert cells[:7] == older and cells.index(CELL) >= 7
    rate = next(m for m in MANIFEST["end_to_end"]
                if m["name"] == "train_tokens_per_s")
    assert rate["workloads"][:7] == older
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("headgate_proj_ms")
    assert at >= 50 and names[49] == "device_idle_pct.swa_moe"
    assert not any(CELL in m.get("workloads", [])
                   for m in MANIFEST["per_layer"][:at])
    small = harness.load_cell("smallthinker_21b_a3b_train_s16k")
    assert small.traffic["what"] != harness.load_cell(CELL).traffic["what"]


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
    assert got["scope_ms"]["attn.window"] == pytest.approx(735.0)
    assert got["scope_ms"]["attn.full"] == pytest.approx(525.0)
    assert got["scope_ms"]["attn.gate"] == pytest.approx(105.0)
    assert got["scope_ms"]["attn"] == pytest.approx(315.0)
    assert sum(got["scope_ms"].values()) == pytest.approx(3600 * 1.05)


def test_scope_dump_fails_the_run_on_a_program_without_scopes():
    with pytest.raises(harness.BenchError, match="found nothing to read"):
        traced(lambda: {"spans": [], "op_scopes": {}})


def test_run_with_the_programs_tracer_names_every_new_layer(capsys):
    """The whole runner at the rehearsal size with the program's tracer
    on: the step's own table holds every scope the new metrics read, the
    gate's among them, and the tracer is off again afterwards."""
    from dlnetbench_tpu.metrics import spans
    # the marks are left while a layer is traced, and jax.checkpoint
    # keeps the layer that an earlier case of this file traced
    jax.clear_caches()
    rc, seen, got = scope_dump.run_with_program_tracer(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.5",
         "--trace", "0", "--rehearse-cpu", "1"])
    capsys.readouterr()
    assert rc != 0 and not spans.is_enabled() and seen == {}
    scopes = set(got["op_scopes"]["jit_train_k"].values())
    assert {"attn", "attn.window", "attn.full", "attn.gate", "mlp",
            "moe.router", "moe.dispatch", "moe.experts", "moe.combine",
            "moe.shared", "head_loss", "optimizer", "embed"} <= scopes
    assert {s["name"] for s in got["spans"]} == {"compile"}
    marks = [m for s in got["spans"]
             for m in s["attrs"].get("moe.experts_bwd", [])]
    assert marks and {m["path"] for m in marks} == {"counted"}
