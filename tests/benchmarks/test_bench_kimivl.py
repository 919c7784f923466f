"""The latent-attention cell's own pieces: the controls that have to
come out as not correct at the rehearsal size, a row past the experts'
bound failing the run, the configuration file against the catalog's row,
the reference's layer-at-a-time backward against autodiff of its whole
loss, the new costs and readers on a fixture of their own
(``scope_fixture_latent_moe.json``), each reader giving nothing from a
program without what it reads, and the three cases that
``test_bench_scopes.py`` keys by its table of the gated-decoder cells
(``tests/conftest.py`` skips them for this cell)."""
import dataclasses
import importlib
import json

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness, run, scope_dump
from benchmarks import reference_latent_moe as ref
from benchmarks import weights_latent_moe as weights
from benchmarks.costs import (held_grouped_matmul, latent_flash_attention,
                              latent_moe_train)
from benchmarks.readers import named_kernel_roofline
from benchmarks.runners import train, train_latent_moe

CELL = "kimivl_a3b_train_s8k"
FIX = harness.load_json(harness.HERE / "scope_fixture_latent_moe.json")
MANIFEST = harness.load_json(harness.ROOT / "BENCHMARK.json")
SCOPE_MS = ["mla_attn_ms", "moe_shared_ms", "moe_held_experts_ms",
            "moe_topk_route_ms"]
NEW = SCOPE_MS + ["mla_flash_roofline", "moe_held_mm_roofline",
                  "moe_slot_fill_pct"]


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
    run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
              "--trace", "0", "--rehearse-cpu", "1"])
    got = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("{")]
    return got, next(g for g in got if g["line"].startswith("rehearsal"))


def bad(got):
    return {g["name"] for g in got
            if g["line"] == "compared" and not g["ok"]}


# ----------------------------------------------------------- correct
def test_int8_reference_is_not_correct_at_the_rehearsal_size():
    rows = train_latent_moe.readings(rehearsal_cell(), 7, lambda _: None,
                                     "reference_int8")
    assert any(value > limit for _, value, limit, _ in rows)


def test_sound_program_is_correct_and_unknown_control_is_refused():
    rows = train_latent_moe.readings(rehearsal_cell(), 2**31 + 11,
                                     lambda _: None, None)
    assert [name for name, *_ in rows] == [
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "selection_gap"]
    assert all(value <= limit for _, value, limit, _ in rows)
    with pytest.raises(harness.BenchError, match="no control"):
        train_latent_moe.readings(rehearsal_cell(), 7, lambda _: None,
                                  "program")


def test_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    def call(self):
        _, (losses, _) = self.step(jax.tree.map(jnp.copy, self.params),
                                   self.feed())
        self.counters.append({k: jnp.zeros((1,), jnp.int32)
                              for k in train_latent_moe.COUNTERS})
        self.chosen = jnp.zeros((2, 128, 3), jnp.int32)
        self.steps_done += 1
        return losses
    monkeypatch.setattr(train_latent_moe.LatentMoeCell, "call", call)
    got, result = rehearse(capsys)
    assert result["correct"] is False
    assert "delta_norm_gap" in bad(got)


def test_part_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    feed = train.TrainCell.feed

    def half(self):
        tokens = feed(self)
        return tokens.at[1:].set(tokens[:1])    # row 0 in every row
    monkeypatch.setattr(train.TrainCell, "feed", half)
    _, result = rehearse(capsys)
    assert result["correct"] is False


def test_selection_that_ignores_the_bias_is_not_correct(capsys,
                                                        monkeypatch):
    """A program whose router leaves the selection bias out selects
    other experts than the model does: the selections' gap catches it."""
    from dlnetbench_tpu.models import layers
    real = layers.moe_router
    monkeypatch.setattr(
        layers, "moe_router",
        lambda x, w, k, **kw: real(x, w, k, **{**kw, "bias": None}))
    jax.clear_caches()      # jax.checkpoint keeps the layer it traced
    try:
        got, result = rehearse(capsys)
    finally:
        jax.clear_caches()
    assert result["correct"] is False
    assert "selection_gap" in bad(got)


def test_a_row_past_the_bound_fails_the_run(capsys, monkeypatch):
    """With a bound under the load, rows are left out: the step counts
    them, the run counts the step as failed and is not correct."""
    load = harness.load_cell

    def tight(name, *a, **kw):
        cell = load(name, *a, **kw)
        cell.workload["rehearsal"]["moe_slots"] = 40
        return cell
    monkeypatch.setattr(harness, "load_cell", tight)
    got, result = rehearse(capsys)
    window = next(g for g in got if g["line"] == "window")
    assert window["moe_slots"] == 40 < window["moe_max_load"]
    assert window["moe_rows_past_bound"] > 0
    assert 0 < result["failed"] <= result["attempted"]
    assert result["correct"] is False


def test_selection_gap_counts_pairs_the_reference_does_not_name():
    import numpy as np
    want = np.array([[[0, 1, 2], [3, 4, 5]]])
    assert train_latent_moe.selection_gap(want[..., ::-1], want) == 0.0
    got = np.array([[[0, 1, 7], [3, 4, 5]]])
    assert train_latent_moe.selection_gap(got, want) \
        == pytest.approx(1 / 6)


# ----------------------------------------------------- configuration
def test_configuration_keeps_every_published_width():
    """Every number of the catalog row's ``config`` under its key, but
    the two reduced ones, each beside its published count."""
    catalog = {
        "vocab_size": 163840, "max_position_embeddings": 131072,
        "hidden_size": 2048, "intermediate_size": 11264,
        "moe_intermediate_size": 1408, "num_hidden_layers": 27,
        "num_attention_heads": 16, "n_shared_experts": 2,
        "n_routed_experts": 64, "ep_size": 1,
        "routed_scaling_factor": 2.446, "kv_lora_rank": 512,
        "q_lora_rank": None, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "num_experts_per_tok": 6, "moe_layer_freq": 1,
        "first_k_dense_replace": 1, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "seq_aux": True,
        "num_key_value_heads": 16, "hidden_act": "silu",
        "rms_norm_eps": 1e-05, "rope_theta": 800000,
        "rope_scaling": None, "attention_bias": False,
        "tie_word_embeddings": False}
    body = harness.load_cell(CELL).config
    reduced = {"num_hidden_layers", "n_routed_experts"}
    assert set(body["reduced"]) == reduced
    assert {k: body[k] for k in catalog if k not in reduced} \
        == {k: v for k, v in catalog.items() if k not in reduced}
    assert body["published"] == {k: catalog[k] for k in reduced}
    assert body["n_routed_experts"] == 16
    assert 5 <= body["num_hidden_layers"] <= 9
    assert "four chips" in body["deployment"]
    assert set(body["assumed"]) >= {"first_held_expert",
                                    "router_bias_scale", "why"}
    arch = weights.arch_of(body)
    assert (arch["num_experts"], arch["held"], arch["top_k"]) \
        == (64, (0, 16), 6)
    params = {k: math_prod(shape)
              for k, (shape, _) in weights.shapes(arch).items()}
    layers = arch["num_layers"] - 1
    # the issue's table: attention 13.76 M a layer, the dense layer's
    # MLP 69.2 M, an expert layer as held 169.6 M less its attention,
    # embedding and head 671.09 M
    assert params["embed"] + params["head"] == 671088640
    assert sum(v for k, v in params.items() if k.startswith("mla/")) \
        == pytest.approx(13.76e6 * (layers + 1), rel=1e-3)
    assert sum(v for k, v in params.items() if k.startswith("moe/")) \
        == pytest.approx((169.61e6 - 13.76e6) * layers, rel=1e-3)


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


def test_layer_at_a_time_backward_equals_autodiff_of_the_whole_loss():
    cell = rehearsal_cell()
    arch = weights.arch_of(cell.config)
    p = ref.unstack(weights.make_params(arch, 3), arch)
    tokens = weights.make_token_pool(3, 1, 2, 65, arch["vocab_size"])[0]
    with jax.default_matmul_precision("highest"):
        loss, grads, chosen = ref.LayerwiseGrad(arch)(p, tokens)
        want_loss, want = jax.value_and_grad(
            lambda q: ref.loss_fn(q, tokens, arch))(p)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    gaps = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-30)), grads, want)
    assert max(jax.tree.leaves(gaps)) < 1e-4
    assert [c.shape for c in chosen] == [(128, arch["top_k"])] * 2


# ------------------------------------------------- costs and readers
def test_latent_flash_cost_from_shapes():
    got = latent_flash_attention.cost(
        batch=1, seq=4, num_heads=2, num_layers=3, qk_nope_head_dim=2,
        qk_rope_head_dim=1, v_head_dim=2)
    pairs = 1 * 2 * 4 * 4 // 2
    score, value = 2 * pairs * 3, 2 * pairs * 2
    # forward twice (the recomputation), backward once: three score
    # products and two value products
    assert got["flops"] == 3 * (2 * (score + value)
                                + 3 * score + 2 * value)
    rows = 1 * 4 * 2 * 2
    assert got["bytes"] == 3 * (2 * rows * (2 * 3 + 2 * 2)
                                + rows * (4 * 3 + 4 * 2))
    # at the cell's widths the values stay 128 wide: 5 x 192 + 4 x 128
    cell = latent_flash_attention.cost(
        batch=2, seq=8192, num_heads=16, num_layers=1,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    assert cell["flops"] == 2 * 16 * 8192 * 8192 * (5 * 192 + 4 * 128)


def test_held_grouped_matmul_cost_from_shapes():
    got = held_grouped_matmul.cost(
        batch=2, seq=64, embed_dim=8, expert_ff_dim=4, num_experts=8,
        held=(0, 4), top_k=3, num_layers=3, first_dense=1)
    rows = 2 * 64 * 3 * 4 / 8
    assert got["flops"] == 2 * 2 * 3 * 2 * rows * 8 * 4
    assert got["bytes"] == 2 * 2 * (3 * 4 * 8 * 4 * 2
                                    + rows * (3 * 8 + 3 * 4) * 2)


def test_model_flops_count_the_held_share():
    arch = weights.arch_of(harness.load_cell(CELL).config)
    per_token = latent_moe_train.matmul_params_per_token(arch)
    layers = arch["num_layers"] - 1
    # the issue's forward FLOP a token and expert layer: projections
    # 27.5 M, shared 34.6 M, routed 1.5 experts 26.0 M, router 0.3 M
    want = (layers + 1) * 13.76e6 + 69.2e6 + layers * (
        17.30e6 + 1.5 * 8.65e6 + 0.13e6) + 335.5e6
    assert per_token == pytest.approx(want, rel=2e-3)
    flops = latent_moe_train.flops_per_token(arch, 8192)
    assert flops == pytest.approx(
        3 * (2 * per_token + (layers + 1) * 16 * 2 * 4096.5 * 320))


@pytest.mark.parametrize("metric", SCOPE_MS + ["moe_slot_fill_pct"])
def test_metrics_against_the_fixture(metric):
    assert read(metric, ctx()) == pytest.approx(FIX["expect"][metric])


@pytest.mark.parametrize("metric,cost,seconds", [
    ("mla_flash_roofline", latent_flash_attention, "flash_seconds"),
    ("moe_held_mm_roofline", held_grouped_matmul, "grouped_mm_seconds")])
def test_named_kernel_rooflines_against_the_fixture(metric, cost, seconds):
    c = cost.cost(**FIX["record"]["arch"], **FIX["record"])
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    want = 100.0 * least * 2 / sum(FIX["expect"][seconds])
    assert read(metric, ctx()) == pytest.approx(want)


def test_kernels_are_found_by_name_not_by_operand_shape():
    assert named_kernel_roofline.kernel_of(
        '%flash_bwd_dkv.12 = (bf16[2,8]{1,0}) custom-call(bf16[2,8] %c)') \
        == "flash_bwd_dkv"
    assert named_kernel_roofline.kernel_of("%fusion.3 = bf16[2] fusion()") \
        == "fusion"
    assert named_kernel_roofline.kernel_of("bench_step") is None
    # the fixture's flash and grouped kernels share their operands'
    # shape and dtype: a pattern on the first operand would merge them
    flash, grouped = (read(m, ctx()) for m in ("mla_flash_roofline",
                                               "moe_held_mm_roofline"))
    assert flash != grouped


@pytest.mark.parametrize("metric", NEW)
def test_new_metrics_read_nothing_from_a_program_without_them(metric):
    """A program without these scopes, kernel names or counters (the
    parent's) exports no table, names no such kernel and returns no
    routing: the reader gives None and does not raise."""
    bare = ctx()
    bare["record"] = {k: v for k, v in FIX["record"].items()
                      if k != "moe"}
    bare["devices"][0]["ops"] = [
        (name.replace("flash_", "custom-call.").replace(
            "grouped_mm", "custom-call"), s, d)
        for name, s, d in bare["devices"][0]["ops"]]
    assert read(metric, bare) is None
    if metric in SCOPE_MS:
        empty = ctx()
        empty["record"]["program_trace"] = {"op_scopes": {"jit_train_k": {
            k: "other" for k in FIX["program_trace"]["op_scopes"][
                "jit_train_k"]}}, "spans": []}
        assert read(metric, empty) is None
    if metric.endswith("_roofline"):
        assert read(metric, {**ctx(), "peaks": None}) is None


@pytest.mark.parametrize("metric", NEW)
def test_spec_file(metric):
    s = spec(metric)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert s["cells"] == entry["workloads"] == [CELL]
    assert (s["layer"], s["unit"], s["moves"]) == (
        entry["layer"], entry["unit"], "train_tokens_per_s")
    assert entry["source"] == ("program_counter"
                               if metric == "moe_slot_fill_pct"
                               else "device_trace")
    if "scopes" in s["params"]:
        from dlnetbench_tpu.metrics import spans
        assert set(s["params"]["scopes"]) <= set(spans.SCOPES)
    if "kernels" in s["params"]:
        text = "".join(
            open(harness.ROOT / "dlnetbench_tpu" / "ops" / f).read()
            for f in ("flash_attention.py", "grouped_matmul.py"))
        for k in s["params"]["kernels"]:
            assert f'name="{k}"' in text


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
    for m in SCOPE_MS + ["moe_slot_fill_pct"]:
        assert metrics[m]["value"] == pytest.approx(FIX["expect"][m])
    got = scope_dump.report(CELL, seen, record()["program_trace"])
    assert got["scope_ms"]["attn"] == pytest.approx(1365.0)
    assert sum(got["scope_ms"].values()) == pytest.approx(3600 * 1.05)
    assert got["top_ops"][0][0] == "head_loss"


def test_scope_dump_fails_the_run_on_a_program_without_scopes():
    with pytest.raises(harness.BenchError, match="found nothing to read"):
        traced(lambda: {"spans": [], "op_scopes": {}})


def test_run_with_the_programs_tracer_names_every_new_layer(capsys):
    """The whole runner at the rehearsal size with the program's tracer
    on: the step's own table holds every scope the new metrics read,
    and the tracer is off again afterwards."""
    from dlnetbench_tpu.metrics import spans
    rc, seen, got = scope_dump.run_with_program_tracer(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.5",
         "--trace", "0", "--rehearse-cpu", "1"])
    capsys.readouterr()
    assert rc != 0 and not spans.is_enabled() and seen == {}
    scopes = set(got["op_scopes"]["jit_train_k"].values())
    assert {"attn", "mlp", "moe.router", "moe.dispatch", "moe.experts",
            "moe.combine", "moe.shared", "head_loss", "optimizer",
            "embed"} <= scopes
    assert {s["name"] for s in got["spans"]} == {"compile"}
