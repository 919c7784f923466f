"""The ``minicpm_sala_train_s16k`` cell: its files against the catalog's
row, its rehearsal on the CPU, each control and planted fault not
``correct``, the costs by hand at a small size, and the readers of its
per-layer metrics on a hand-built trace
(``benchmarks/scope_fixture_sparse_linear.json``).  Nothing here is a
device number."""
from __future__ import annotations

import dataclasses
import functools
import importlib
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, run, scope_dump
from benchmarks import reference_sparse_linear as ref
from benchmarks import weights_sparse_linear as weights
from benchmarks.costs import (lightning_attention, sparse_block_attention,
                              sparse_linear_train)
from benchmarks.runners import train_sparse_linear

CELL = "minicpm_sala_train_s16k"
CONFIG = "minicpm_sala_4l"
FIX = harness.load_json(harness.HERE / "scope_fixture_sparse_linear.json")
MANIFEST = harness.load_json(harness.ROOT / "BENCHMARK.json")
SCOPE_MS = ["sparse_select_ms", "sparse_attn_ms", "sparse_proj_ms",
            "lightning_mixer_ms", "lightning_rule_ms"]
ROOFLINES = ["sparse_attn_roofline", "lightning_rule_roofline"]
NEW = SCOPE_MS + ROOFLINES + ["sparse_visit_fill_pct"]
WHOLE = ["step_device_ms.sparse_linear", "device_idle_pct.sparse_linear"]
LISTED = ["sparse_select_ms", "sparse_attn_ms", "sparse_attn_roofline",
          "sparse_proj_ms", "lightning_mixer_ms", "lightning_rule_ms",
          "lightning_rule_roofline", "sparse_visit_fill_pct", *WHOLE]
REDUCED = {"num_hidden_layers", "mixer_types"}
FOUR = ["loss_gap", "grad_norm_gap", "delta_norm_gap",
        "block_selection_gap"]


def catalog_row() -> dict:
    """The catalog's row for MiniCPM-SALA (``config.json`` as
    published), by hand: the guide's file is not in the repository."""
    sparse_at = {0, 9, 16, 17, 22, 29, 30, 31}
    return {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "mixer_types": ["minicpm4" if li in sparse_at else "lightning-attn"
                        for li in range(32)],
        "num_attention_heads": 32, "num_hidden_layers": 32,
        "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
        "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
        "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
        "dim_model_base": 256, "tie_word_embeddings": False,
        "use_output_gate": True, "use_output_norm": True,
        "attn_use_output_gate": True}


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
    real = train_sparse_linear.SparseLinearCell.reference_steps

    @functools.lru_cache(maxsize=None)
    def steps(seed, precision):
        return real(cells[seed], precision)
    cells = {}

    def cached(self, precision="float32"):
        cells[self.seed] = self
        return steps(self.seed, precision)
    train_sparse_linear.SparseLinearCell.reference_steps = cached
    yield
    train_sparse_linear.SparseLinearCell.reference_steps = real


@pytest.mark.parametrize("control,fails", [
    (None, None), ("reference_int8", "grad_norm_gap"),
    ("dense_for_sparse", "block_selection_gap"),
    ("lists_unread", "grad_norm_gap"),
    ("no_decay", "grad_norm_gap"), ("no_lightning_rope", "grad_norm_gap"),
    ("unit_residual_scale", "grad_norm_gap")])
def test_the_program_is_correct_and_each_control_and_fault_is_not(
        one_reference, control, fails):
    """The sound program at the rehearsal size is correct by all four
    numbers; the int8 reference, a sparse layer that attends every
    earlier key (by its lists, or by kernels that do not read them),
    lightning layers without decay or without RoPE and a residual scale
    of 1 are each not."""
    rows = train_sparse_linear.readings(rehearsal_cell(), 2**31 + 11,
                                        lambda _: None, control)
    assert [name for name, *_ in rows] == FOUR
    if control is None:
        assert not bad(rows)
        return
    assert fails in bad(rows)
    if control == "dense_for_sparse":
        # every unforced block that is not one of the reference's four
        assert {r[0]: r[1] for r in rows}["block_selection_gap"] > 0.5
        assert "grad_norm_gap" in bad(rows)
    if control == "lists_unread":
        # the lists are the sound program's: the norms alone tell
        assert "block_selection_gap" not in bad(rows)


def test_the_faults_are_switches_and_wrapped_functions_of_the_program():
    from dlnetbench_tpu.models import hybrid
    from dlnetbench_tpu.ops import sparse_attention
    cell = rehearsal_cell()
    arch = weights.arch_of(cell.config)
    sound = train_sparse_linear.program_config(cell, arch)
    assert set(train_sparse_linear.FAULTS) == {
        "dense_for_sparse", "unit_residual_scale", "lists_unread",
        "no_decay", "no_lightning_rope"}
    at = {"seq": cell.traffic["seq_len"], "sizes": arch["sparse_sizes"]}
    dense = train_sparse_linear.SWITCHES["dense_for_sparse"](at)
    assert dense == {"sparse_sizes": (8, 4, 16, 128 // 16, 32, 1, 64)}
    for name, switch in train_sparse_linear.SWITCHES.items():
        over = switch(at)
        cfg = train_sparse_linear.program_config(cell, arch, over)
        assert cfg != sound and cfg.has_selection, name
        assert dataclasses.replace(
            cfg, **{k: getattr(sound, k) for k in over}) == sound
    homes = {"models.hybrid": hybrid, "ops.sparse_attention": sparse_attention}
    for name, (module, attr, _) in train_sparse_linear.WRAPPED.items():
        real = getattr(homes[module], attr)
        with train_sparse_linear._planted(
                *train_sparse_linear.WRAPPED[name]):
            assert getattr(homes[module], attr) is not real
        assert getattr(homes[module], attr) is real
    with train_sparse_linear._planted(
            *train_sparse_linear.WRAPPED["no_decay"]):
        assert not jnp.any(hybrid.head_log_decay(4, 1, 32))
    # the kernels' mask with the lists unread is the causal test alone
    mem = jnp.zeros((8, 2)).at[:, 0].set(1.0)    # every token chose block 0
    at = (1, 0, 8, 16, 8)       # tokens 8-15 against keys 0-15
    assert np.array_equal(
        np.asarray(sparse_attention._tile_mask(mem, *at)),
        np.arange(16)[None, :] < 8 + 0 * np.arange(8)[:, None])
    with train_sparse_linear._planted(
            *train_sparse_linear.WRAPPED["lists_unread"]):
        assert np.array_equal(
            np.asarray(sparse_attention._tile_mask(mem, *at)),
            np.arange(16)[None, :] <= 8 + np.arange(8)[:, None])


def test_the_selections_gap_counts_unforced_blocks_the_reference_lacks():
    sizes = (8, 4, 16, 4, 32, 1, 64)
    gap = train_sparse_linear.block_selection_gap
    # one layer, one row, 128 tokens, one group; token 127's own block
    # is 7: 0, 6, 7 are forced, places 1-5 free
    got = np.full((1, 1, 128, 1, 4), -1)
    got[..., 127, 0, :] = [0, 6, 7, 3]
    got[..., 100, 0, :] = [0, 5, 6, 2]
    want = got.copy()
    assert gap(got, want, sizes) == 0.0
    want[..., 127, 0, 3] = 4
    assert gap(got, want, sizes) == 0.5     # one of two free choices
    assert gap(got, None, sizes) == 1.0
    only_forced = np.full((1, 1, 128, 1, 4), -1)
    only_forced[..., 20, 0, :2] = [0, 1]
    assert gap(only_forced, only_forced * 0 - 1, sizes) == 0.0
    # a list of another length (the dense control's) is compared too
    wide = np.full((1, 1, 128, 1, 8), -1)
    wide[..., 127, 0, :] = range(8)
    assert gap(wide, want, sizes) == pytest.approx(1 - 1 / 5)


def test_the_cell_compares_the_four_numbers_and_refuses_other_controls():
    limits = harness.load_cell(CELL).workload["limits"]
    assert list(limits) == FOUR
    got = {"losses": [2.0, float("nan")],
           "blocks": np.zeros((1, 1, 128, 1, 4), int),
           "grad_norms": {"w": 1.0}, "delta_norms": {"w": 1.0}}
    want = {**got, "losses": [2.0, 2.0]}
    rows = train_sparse_linear.compare(got, want, limits,
                                       (8, 4, 16, 4, 32, 1, 64))
    assert [r[0] for r in rows] == FOUR
    assert rows[0][:3] == ("loss_gap", float("inf"), limits["loss_gap"])
    with pytest.raises(harness.BenchError, match="no control"):
        train_sparse_linear.readings(rehearsal_cell(), 7, lambda _: None,
                                     "program")


def test_rehearsal_of_the_whole_run_and_an_unchanged_state(
        capsys, monkeypatch):
    """The cell's rehearsal is correct, fails no step and records the
    step's counters; a step that returns its state unchanged fails the
    parameters' change."""
    got, result = rehearse(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["compared"]) == set(FOUR)
    window = next(g for g in got if g["line"] == "window")
    assert 0 < window["sparse_selected_median"] \
        <= window["sparse_visited_median"]
    assert window["model_flops_per_token"] == pytest.approx(
        sparse_linear_train.flops_per_token(
            weights.arch_of(rehearsal_cell().config), 128))

    def call(self):
        _, (losses, picked) = self.step(
            jax.tree.map(jnp.copy, self.params), self.feed())
        if self.chosen is None:
            self.chosen = picked["blocks"][0]
        self.counters.append({k: picked[k]
                              for k in train_sparse_linear.COUNTED})
        self.steps_done += 1
        return losses
    monkeypatch.setattr(train_sparse_linear.SparseLinearCell, "call", call)
    got, result = rehearse(capsys)
    assert result["correct"] is False
    assert "delta_norm_gap" in {g["name"] for g in got
                                if g["line"] == "compared" and not g["ok"]}


def test_runner_refuses_a_program_without_the_two_kinds(monkeypatch):
    """On the parent's program the runner's import raises the harness's
    own error: the run exits non-zero at once, with no result."""
    import sys

    from dlnetbench_tpu.models import hybrid
    monkeypatch.delattr(hybrid, "SELECTION")
    monkeypatch.delitem(sys.modules,
                        "benchmarks.runners.train_sparse_linear")
    with pytest.raises(harness.BenchError,
                       match="cannot run the sparse-and-linear"):
        importlib.import_module("benchmarks.runners.train_sparse_linear")
    monkeypatch.undo()
    sys.modules.pop("benchmarks.runners.train_sparse_linear", None)
    importlib.import_module("benchmarks.runners.train_sparse_linear")


# ----------------------------------------------------- configuration
def test_configuration_keeps_every_published_width():
    """Every key of the catalog row's ``config`` under its name, but the
    two reduced ones, each beside its published value; the whole
    vocabulary."""
    body = harness.load_cell(CELL).config
    row = catalog_row()
    assert set(body["reduced"]) == REDUCED
    assert {k: body[k] for k in row if k not in REDUCED} \
        == {k: v for k, v in row.items() if k not in REDUCED}
    assert body["published"] == {k: row[k] for k in REDUCED}
    assert body["mixer_types"] == row["mixer_types"][:4] \
        == ["minicpm4"] + ["lightning-attn"] * 3
    assert body["num_hidden_layers"] == 4
    assert (body["hidden_size"], body["head_dim"],
            body["num_attention_heads"], body["num_key_value_heads"],
            body["lightning_nh"], body["lightning_head_dim"],
            body["intermediate_size"], body["scale_emb"],
            body["scale_depth"], body["dim_model_base"],
            body["rms_norm_eps"]) == (4096, 128, 32, 2, 32, 128, 16384, 12,
                                      1.4, 256, 1e-6)
    # the rungs: whole, a half, a quarter, the floor of an eighth, with
    # the counts the sandbox's TPU compiler gave the first two.  The
    # first is taken though it does not keep the 14.0 GB rule: a sliced
    # vocabulary cannot stand in ``reduced`` (test_bench_manifest.py's
    # ``_size$``), and the file says so
    rungs = body["rungs"]
    assert [rungs[k]["vocab_size"] for k in "1234"] \
        == [73448, 36724, 18362, 9181]
    assert [rungs[k]["parameters"] for k in "1234"] == [
        1711117696, 1410274688, 1259853184, 1184642432]
    taken = [k for k in rungs if rungs[k].get("taken")]
    assert taken == ["1"] and body["vocab_size"] == 73448
    assert 14.0e9 < rungs["1"]["rule_bytes"] < 15.2e9 \
        and not rungs["1"]["keeps_the_rule"]
    assert rungs["2"]["rule_bytes"] <= 14.0e9 and rungs["2"]["keeps_the_rule"]
    assert "DOES NOT KEEP IT" in body["cut_by_the_rule"] \
        and "test_config_file" in body["cut_by_the_rule"]
    assert "eight pipeline stages" in body["deployment"]
    assert body["assumed"]["not_given"] == []
    assert set(body["assumed"]["why"]) >= {
        "sparse_config", "selection", "lightning_decay", "no_activation",
        "gates", "mup_denominator", "optimizer"}
    assert body["assumed"]["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "window_size": 2048, "init_blocks": 1,
        "dense_len": 8192}
    assert "14.0 GB" in body["cut_by_the_rule"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["source"] == body["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    assert set(entry["reduced"]) == REDUCED


def test_the_cuts_parameter_counts_are_the_issues():
    body = harness.load_cell(CELL).config
    arch = weights.arch_of(body)
    assert arch["layer_kinds"] == ("sparse",) + ("lightning",) * 3
    assert (arch["published_layers"], arch["embed_scale"],
            arch["logit_scale"]) == (32, 12.0, 1 / 16)
    assert arch["residual_scale"] == pytest.approx(1.4 / math.sqrt(32))
    assert arch["sparse_sizes"] == (32, 16, 64, 64, 2048, 1, 8192)
    params = {k: math.prod(shape)
              for k, (shape, _) in weights.shapes(arch).items()}

    def group(g):
        return sum(v for k, v in params.items() if k.startswith(g + "/"))
    # the issue's table: a sparse layer 253.8 M and a lightning layer
    # 285.2 M with their SwiGLUs of 201.3 M
    swiglu = 3 * 4096 * 16384
    assert group("gated") + swiglu == pytest.approx(253.8e6, rel=1e-3)
    assert group("lightning") / 3 + swiglu == pytest.approx(285.2e6,
                                                            rel=1e-3)
    whole = weights.arch_of({**body, "vocab_size": 73448})
    assert weights.num_params(whole) == pytest.approx(1711.1e6, rel=1e-4)
    assert weights.num_params(arch) == body["rungs"]["1"]["parameters"]
    assert weights.num_params(weights.arch_of(
        {**body, "vocab_size": 36724})) == body["rungs"]["2"]["parameters"]
    for over in ({"mixer_types": ["minicpm4"] * 3},
                 {"mixer_types": ["minicpm4", "mamba", "minicpm4",
                                  "minicpm4"]},
                 {"lightning_nkv": 8}, {"attn_use_rope": True},
                 {"lightning_scale": "1/d"}):
        with pytest.raises(ValueError, match="neither side computes"):
            weights.arch_of({**body, **over})


def test_the_two_kinds_of_leaf_that_are_not_one_plain_draw():
    """A sparse layer's norms a head start at ``SHARP`` (scores of
    deviation SHARP^4 over the keys: a token's mass on few keys, so the
    norms tell the selected keys from all of them); a lightning layer's
    keys are ``KEY_MIX`` of its queries' draw and the rest their own (a
    token's score with itself is far from zero, where the output norm's
    gradient is singular, and ``q`` is not ``k``).  Every other leaf is
    its own draw or all ones."""
    arch = weights.arch_of(rehearsal_cell().config)
    assert weights.KEY_MIX == {"lightning/wk": ("lightning/wq", 0.8)}
    p = weights.make_params(arch, 3)
    light, gated = p["lightning"], p["gated"]
    for leaf in ("q_norm", "k_norm"):
        assert bool(jnp.all(gated[leaf] == weights.SHARP))
        assert bool(jnp.all(light[leaf] == 1.0))
    assert weights.SHARP > 1.0 and bool(jnp.all(light["o_norm"] == 1.0))
    assert bool(jnp.all(p["final_norm"] == 1.0))
    wq, wk = (light[k].astype(jnp.float32).ravel() for k in ("wq", "wk"))
    cos = float(wq @ wk / jnp.linalg.norm(wq) / jnp.linalg.norm(wk))
    assert cos == pytest.approx(0.8, abs=0.02)
    assert float(jnp.std(wk) / jnp.std(wq)) == pytest.approx(1.0, abs=0.02)
    own = float(light["wv"].astype(jnp.float32).ravel() @ wq
                / jnp.linalg.norm(wq) ** 2)
    assert abs(own) < 0.02
    assert not bool(jnp.array_equal(light["wq"][0], light["wq"][1]))
    other = weights.make_params(arch, 4)["lightning"]
    assert not bool(jnp.array_equal(other["wq"], light["wq"]))
    # a token's score with itself in a lightning layer: about sqrt(d) *
    # 0.8 (at this size's 16 lanes and 64 columns with a wide spread)
    y = jax.random.normal(jax.random.key(0), (64, arch["embed_dim"]))
    ld = arch["lightning_dim"]
    q, k = ((y @ light[n][0].astype(jnp.float32)).reshape(64, -1, ld)
            for n in ("wq", "wk"))
    unit = lambda t: t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True))
    own = jnp.sum(unit(q) * unit(k), -1) / math.sqrt(ld)
    assert float(jnp.mean(own)) == pytest.approx(0.8 * math.sqrt(ld),
                                                 rel=0.1)
    assert float(jnp.min(own)) > 0.0


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


def test_the_reference_states_the_decay_and_the_pool_by_hand():
    lam = np.asarray(ref.decays(32, 3, 32))
    assert lam[0] == pytest.approx(
        math.exp(-2 ** -0.25 * (1 - 3 / 31 + 1e-5)))
    assert lam[31] == pytest.approx(
        math.exp(-2 ** -8.0 * (1 - 3 / 31 + 1e-5)))
    # two tokens of the recurrence by hand
    q = k = v = jnp.ones((2, 1, 2))
    o = ref.decayed_rule(q, k, v, jnp.asarray([0.5]))
    assert np.allclose(o[:, 0, 0], [2 / math.sqrt(2), 3 / math.sqrt(2)])


# ------------------------------------------------------------- costs
def test_costs_by_hand_at_a_small_size():
    arch = {**FIX["record"]["arch"]}
    rec = FIX["record"]
    # sparse: 832 (token, group, block) entries of 16 keys, less the
    # diagonal's 7.5 a (token, group); 4 heads a group, 16 lanes
    pairs = 832 * 16 - 1 * 1 * 2 * 128 * 7.5
    c = sparse_block_attention.cost(**arch, **rec)
    assert c["flops"] == pytest.approx(7 * 2 * 4 * pairs * 16)
    q, kv = 128 * 8 * 16 * 2, 128 * 2 * 16 * 2
    assert c["bytes"] == 6 * q + 6 * kv
    # forward once and backward once: no second forward is counted
    assert c["flops"] / (2 * 4 * pairs * 16) == pytest.approx(2 + 5)
    c = lightning_attention.cost(**arch, **rec)
    assert c["flops"] == 3 * 128 * (5 + 10) * 4 * 16 * 16
    assert c["bytes"] == 3 * 11 * 128 * 4 * 16 * 2
    parts = sparse_linear_train.macs(weights.arch_of(
        rehearsal_cell().config), 128)
    assert parts["mlp"] == 4 * 3 * 64 * 128
    assert parts["head"] == 64 * 256
    assert parts["lightning_proj"] == 3 * 5 * 64 * 64
    assert parts["lightning_rule"] == 3 * 2 * 4 * 16 * 16
    assert parts["sparse_proj"] == 64 * 256 + 2 * 64 * 32 + 128 * 64
    seen = sum(min(t + 1, 64 - (15 - t % 16)) for t in range(128)) / 128
    assert parts["sparse_pairs"] == pytest.approx(2 * 8 * 16 * seen)
    assert sparse_linear_train.attended_keys(64, (8, 4, 16, 4, 32, 1, 64)) \
        == 32.5
    # the cell's own: SwiGLUs most of a token's work, the head next
    full = sparse_linear_train.macs(
        weights.arch_of(harness.load_cell(CELL).config), 16384)
    assert full["mlp"] == 4 * 3 * 4096 * 16384
    assert full["head"] == 4096 * 73448
    assert full["mlp"] > full["head"] > full["lightning_proj"] \
        > full["sparse_proj"] > full["sparse_pairs"] > full["lightning_rule"]


# ----------------------------------------------------------- readers
@pytest.mark.parametrize("metric", SCOPE_MS + ["sparse_visit_fill_pct"])
def test_metrics_against_the_fixture(metric):
    assert read(metric, ctx()) == pytest.approx(FIX["expect"][metric])


@pytest.mark.parametrize("metric,want", [
    ("step_device_ms.sparse_linear", FIX["expect"]["step_ms"]),
    ("device_idle_pct.sparse_linear", FIX["expect"]["idle_pct"])])
def test_step_and_idle_share_against_the_fixture(metric, want):
    assert read(metric, ctx()) == pytest.approx(want)
    old = spec(metric.replace(".sparse_linear",
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
        0.5 + 0.2 + 0.1 + 0.1) == pytest.approx(3150.0)


@pytest.mark.parametrize("metric,cost,seconds", [
    ("sparse_attn_roofline", sparse_block_attention, "sparse_seconds"),
    ("lightning_rule_roofline", lightning_attention, "rule_seconds")])
def test_rooflines_against_the_fixture(metric, cost, seconds):
    c = cost.cost(**FIX["record"]["arch"], **FIX["record"])
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    want = 100.0 * least * 2 / sum(FIX["expect"][seconds])
    assert read(metric, ctx()) == pytest.approx(want)


def test_selection_kernels_and_rule_are_parted_by_scope():
    table = FIX["program_trace"]["op_scopes"]["jit_train_k"]
    by_scope = {}
    for inst, scope in table.items():
        by_scope.setdefault(scope, set()).add(inst.rsplit(".", 1)[0])
    assert by_scope["attn.select"] == {"fusion", "sort"}
    assert by_scope["attn.sparse"] == {
        "sparse_fwd", "sparse_bwd_dq", "sparse_bwd_dkv", "fusion"}
    assert by_scope["linattn.rule"] == {"lightning_fwd", "lightning_bwd"}
    from dlnetbench_tpu.core import executor
    for path, want in (
            ("jit(train_k)/jit(main)/attn/attn.select/while/body/dot_general",
             "attn.select"),
            ("jit(train_k)/checkpoint/attn/attn.sparse/pallas_call",
             "attn.sparse"),
            ("jit(train_k)/transpose(jvp(attn))/attn/attn.sparse/pallas_call",
             "attn.sparse"),
            ("jit(train_k)/transpose(jvp(linattn))/linattn.rule/pallas_call",
             "linattn.rule"),
            ("jit(train_k)/transpose(jvp(attn))/dot_general", "attn")):
        assert executor.scope_of_op_name(path) == want


@pytest.mark.parametrize("metric", NEW)
def test_new_metrics_read_nothing_from_a_program_without_them(metric):
    """A program without these scopes (the parent's) exports no table and
    returns no counters: the reader gives None and does not raise."""
    bare = ctx()
    bare["record"] = {k: v for k, v in FIX["record"].items()
                      if k not in ("program_trace", "sparse")}
    assert read(metric, bare) is None
    if metric != "sparse_visit_fill_pct":
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
    if "cost" in s["params"]:
        importlib.import_module(f"benchmarks.costs.{s['params']['cost']}")


def test_manifest_has_the_cell_its_configuration_and_its_metrics():
    """By name, wherever later PRs' entries come to stand."""
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    loaded = harness.load_cell(CELL)
    rows = loaded.config["vocab_size"]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, f"pretrain_b1_s16384_v{rows}", 1)
    config = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("sparse_select_ms")
    assert names[at:at + len(LISTED)] == LISTED
    rate = next(m for m in MANIFEST["end_to_end"]
                if m["name"] == "train_tokens_per_s")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    assert (loaded.traffic["batch"], loaded.traffic["seq_len"],
            loaded.traffic["pool_batches"]) == (1, 16384, 8)
    assert str(rows) in loaded.traffic["what"]
    assert loaded.traffic["seq_len"] \
        > loaded.config["assumed"]["sparse_config"]["dense_len"]
    assert "cycle_steps" not in loaded.workload
    assert loaded.workload["runner"] == "train_sparse_linear"
    assert loaded.workload["program"] == {"remat": True,
                                          "loss_row_block": 2048}
    assert {m["name"] for m in loaded.per_layer} == set(LISTED) | {
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
             "smallthinker_21b_a3b_train_s16k", "laguna_s21_train_s16k"]
    assert cells[:8] == older and cells.index(CELL) >= 8
    rate = next(m for m in MANIFEST["end_to_end"]
                if m["name"] == "train_tokens_per_s")
    assert rate["workloads"][:8] == older
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("sparse_select_ms")
    assert at >= 63 and names[62] == "device_idle_pct.headgate_moe"
    assert not any(CELL in m.get("workloads", [])
                   for m in MANIFEST["per_layer"][:at])


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
    assert got["scope_ms"]["attn.select"] == pytest.approx(210.0)
    assert got["scope_ms"]["attn.sparse"] == pytest.approx(945.0)
    assert got["scope_ms"]["linattn.rule"] == pytest.approx(525.0)
    assert sum(got["scope_ms"].values()) == pytest.approx(3000 * 1.05)


def test_scope_dump_fails_the_run_on_a_program_without_scopes():
    with pytest.raises(harness.BenchError, match="found nothing to read"):
        traced(lambda: {"spans": [], "op_scopes": {}})


def test_run_with_the_programs_tracer_names_every_new_layer(capsys):
    """The whole runner at the rehearsal size with the program's tracer
    on: the step's own table holds every scope the new metrics read, the
    marks of the kept values and of the sparse kernels' grids are on the
    build's span, and the tracer is off again afterwards."""
    from dlnetbench_tpu.metrics import spans
    jax.clear_caches()      # a cached trace leaves no marks
    rc, seen, got = scope_dump.run_with_program_tracer(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.5",
         "--trace", "0", "--rehearse-cpu", "1"])
    capsys.readouterr()
    assert rc != 0 and not spans.is_enabled() and seen == {}
    scopes = set(got["op_scopes"]["jit_train_k"].values())
    assert {"attn", "attn.select", "attn.sparse", "linattn",
            "linattn.rule", "mlp", "head_loss", "optimizer",
            "embed"} <= scopes
    assert {s["name"] for s in got["spans"]} == {"compile"}
    marks = [m for s in got["spans"]
             for m in s["attrs"].get("sparse.grid", [])]
    assert {m["kernel"] for m in marks} == {
        "sparse_fwd", "sparse_bwd_dq", "sparse_bwd_dkv"}
    kept = {m["value"] for s in got["spans"]
            for m in s["attrs"].get("remat.kept", [])}
    assert kept == {"attn_out", "attn_lse", "attn_blocks"}
