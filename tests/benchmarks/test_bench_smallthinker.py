"""The window-and-full attention cell's own pieces: the control and the
three planted faults that have to come out as not correct at the
rehearsal size, a row past the experts' bound failing the run, the
configuration file against the catalog's row, the new costs and readers
on a fixture of their own (``scope_fixture_swa_moe.json``), each reader
giving nothing from a program without what it reads, and the three cases
that ``test_bench_scopes.py`` keys by its table of the gated-decoder
cells (``tests/conftest.py`` skips them for this cell)."""
import dataclasses
import functools
import importlib
import json

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness, run, scope_dump
from benchmarks import reference_swa_moe as ref
from benchmarks import weights_swa_moe as weights
from benchmarks.costs import (full_flash_attention, held_grouped_matmul,
                              swa_moe_train, window_flash_attention)
from benchmarks.runners import train_latent_moe, train_swa_moe

CELL = "smallthinker_21b_a3b_train_s16k"
FIX = harness.load_json(harness.HERE / "scope_fixture_swa_moe.json")
MANIFEST = harness.load_json(harness.ROOT / "BENCHMARK.json")
SCOPE_MS = ["swa_proj_ms", "swa_window_ms", "swa_full_ms",
            "moe_early_route_ms", "moe_reglu_experts_ms"]
ROOFLINES = ["swa_window_roofline", "swa_full_roofline",
             "moe_reglu_mm_roofline"]
NEW = SCOPE_MS + ROOFLINES + ["moe_reglu_slot_fill_pct"]
# the whole step and the device's idle share: copies, for this cell, of
# two entries the benchmark has and this PR may not join; they read the
# trace alone, so a program without this PR's scopes gives them too
WHOLE = ["step_device_ms.swa_moe", "device_idle_pct.swa_moe"]
LAYOUT = [0, 1, 1, 1] * 13
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers", "rope_layout", "sliding_window_layout",
           "moe_num_primary_experts"}


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
    real = train_swa_moe.SwaMoeCell.reference_steps

    @functools.lru_cache(maxsize=None)
    def steps(seed, precision):
        return real(cells[seed], precision)
    cells = {}

    def cached(self, precision="float32"):
        cells[self.seed] = self
        return steps(self.seed, precision)
    train_swa_moe.SwaMoeCell.reference_steps = cached
    yield
    train_swa_moe.SwaMoeCell.reference_steps = real


@pytest.mark.parametrize("control,fails", [
    ("reference_int8", "grad_norm_gap"), ("no_window", "grad_norm_gap"),
    ("late_router", "selection_gap"), ("silu_experts", "grad_norm_gap")])
def test_controls_and_planted_faults_at_the_rehearsal_size(one_reference,
                                                           control, fails):
    """The int8 reference, a window layer that sees every earlier key, a
    router fed the stream after attention and SiLU in the experts' gate
    are each not correct (``test_bench_rehearsal.py`` runs the sound
    program: correct)."""
    rows = train_swa_moe.readings(rehearsal_cell(), 2**31 + 11,
                                  lambda _: None, control)
    assert [name for name, *_ in rows] == [
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "selection_gap"]
    assert fails in bad(rows)
    if control == "late_router":    # other experts for most tokens
        assert dict((r[0], r[1]) for r in rows)["selection_gap"] > 0.1


def test_a_planted_fault_leaves_the_program_as_it_found_it():
    from dlnetbench_tpu.models import hybrid
    before = hybrid.MaskSpec
    with train_swa_moe._no_window():
        assert hybrid.MaskSpec is not before
        assert hybrid.MaskSpec(causal=True, window=8).is_plain_causal
    assert hybrid.MaskSpec is before
    assert not before(causal=True, window=8).is_plain_causal
    cell = rehearsal_cell()
    arch = weights.arch_of(cell.config)
    sound = train_swa_moe.program_config(cell, arch)
    assert sound.early_router and sound.expert_activation == "relu"
    for name in ("late_router", "silu_experts"):
        planted, over = train_swa_moe.FAULTS[name]
        cfg = train_swa_moe.program_config(cell, arch, over)
        assert (cfg.early_router, cfg.expert_activation) != (True, "relu")
        assert dataclasses.replace(cfg, early_router=True,
                                   expert_activation="relu") == sound


def test_the_cell_compares_the_four_numbers_the_latent_cell_compares():
    """The limits are the Kimi cell's four (``runners/train_conv_moe.
    compare``, by import, gives a row for each limit the file has, so a
    cell whose loss the precision did not move could leave it out: this
    one keeps it)."""
    limits = harness.load_cell(CELL).workload["limits"]
    assert limits == harness.load_cell(
        "kimivl_a3b_train_s8k").workload["limits"]
    got = {"losses": [2.0, float("nan")], "chosen": jnp.zeros((1, 4, 2)),
           "grad_norms": {"w": 1.0}, "delta_norms": {"w": 1.0}}
    want = {**got, "losses": [2.0, 2.0]}
    rows = train_swa_moe.compare(got, want, limits)
    assert [r[0] for r in rows] == ["loss_gap", "grad_norm_gap",
                                    "delta_norm_gap", "selection_gap"]
    assert rows[0][:3] == ("loss_gap", float("inf"), 3.5e-4)


def test_unknown_control_is_refused():
    with pytest.raises(harness.BenchError, match="no control"):
        train_swa_moe.readings(rehearsal_cell(), 7, lambda _: None,
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
    monkeypatch.setattr(train_swa_moe.SwaMoeCell, "call", call)
    got, result = rehearse(capsys)
    assert result["correct"] is False
    assert "delta_norm_gap" in {g["name"] for g in got
                                if g["line"] == "compared" and not g["ok"]}
    window = next(g for g in got if g["line"] == "window")
    assert window["moe_slots"] == 24 < window["moe_max_load"]
    assert window["moe_rows_past_bound"] > 0
    assert 0 < result["failed"] <= result["attempted"]
    assert window["cycle_steps"] == 8 and window["cycles_repeat"] is True


def test_runner_refuses_a_program_without_the_new_layers(monkeypatch):
    """On the parent's program the runner's import raises the harness's
    own error: the run exits non-zero at once, with no result."""
    import sys

    from dlnetbench_tpu.models import hybrid
    from dlnetbench_tpu.ops import grouped_matmul
    for module, name, value in ((grouped_matmul, "ACTIVATIONS", None),
                                (hybrid, "KINDS", hybrid.KINDS[:-2])):
        with monkeypatch.context() as m:
            if value is None:
                m.delattr(module, name)
            else:
                m.setattr(module, name, value)
            m.delitem(sys.modules, "benchmarks.runners.train_swa_moe")
            with pytest.raises(harness.BenchError,
                               match="cannot run the window-and-full"):
                importlib.import_module("benchmarks.runners.train_swa_moe")
    importlib.import_module("benchmarks.runners.train_swa_moe")


# ----------------------------------------------------- configuration
def test_configuration_keeps_every_published_width():
    """Every key of the catalog row's ``config`` under its name, but the
    four reduced ones, each beside its published value; the whole
    vocabulary and the router's 64 outputs."""
    body = harness.load_cell(CELL).config
    assert set(body["reduced"]) == REDUCED
    assert {k: body[k] for k in CATALOG if k not in REDUCED} \
        == {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert body["published"] == {k: CATALOG[k] for k in REDUCED}
    # the cut: published layers 0-7, two periods, 16 of 64 experts held
    assert body["rope_layout"] == body["sliding_window_layout"] \
        == LAYOUT[:8] == [0, 1, 1, 1, 0, 1, 1, 1]
    assert (body["num_hidden_layers"], body["moe_num_primary_experts"]) \
        == (8, 16)
    assert "16 routed experts a chip" in body["deployment"]
    assert set(body["assumed"]) >= {"first_held_expert", "why"}
    assert "14.0 GB" in body["cut_by_the_rule"] \
        and "four layers" in body["cut_by_the_rule"]
    arch = weights.arch_of(body)
    assert arch["layer_kinds"] == ("nope", "swa", "swa", "swa") * 2
    assert (arch["num_experts"], arch["held"], arch["top_k"],
            arch["head_dim"], arch["window"], arch["rope_theta"]) \
        == (64, (0, 16), 6, 128, 4096, 1.5e6)
    assert arch["num_heads"] // arch["num_kv_heads"] == 7
    params = {k: math_prod(shape)
              for k, (shape, _) in weights.shapes(arch).items()}

    def group(g):
        return sum(v for k, v in params.items() if k.startswith(g + "/"))
    # the issue's table: attention 20.97 M a layer, the router 0.16 M,
    # 16 experts of 5.90 M, each table 389 M: 1702 M in all
    assert group("gated") == pytest.approx(8 * 20.97e6, rel=1e-3)
    assert group("moe") == pytest.approx(8 * (94.37e6 + 0.164e6), rel=1e-3)
    assert params["embed"] == params["head"] == 151936 * 2560
    assert sum(params.values()) == pytest.approx(1702e6, rel=1e-3)
    # a layer whose layouts differ is no layer of either side
    with pytest.raises(ValueError, match="a window and RoPE, or neither"):
        weights.arch_of({**body, "rope_layout": [1] + LAYOUT[1:8]})


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
    """Both kinds of layer at the rehearsal's four, the selections a
    layer, the untied head."""
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


def test_reference_attention_masks_by_the_window_in_blocks_of_rows():
    """The explicit mask a block of query rows at a time gives what the
    whole [S, S] mask gives, at a window that cuts through a block."""
    s, dh, window = 64, 8, 24
    q, k, v = (jax.random.normal(jax.random.key(i), (s, dh))
               for i in range(3))
    pos = jnp.arange(s)
    seen = (pos[:, None] >= pos[None, :]) \
        & (pos[:, None] - pos[None, :] < window)
    sc = jnp.where(seen, q @ k.T / dh ** 0.5, -jnp.inf)
    want = jax.nn.softmax(sc, -1) @ v
    for block in (16, 64):
        orig = ref.row_blocks
        ref.row_blocks = functools.partial(orig, block=block)
        try:
            got = ref.attention_head(q, k, v, window)
        finally:
            ref.row_blocks = orig
        assert float(jnp.abs(got - want).max()) < 1e-5
    assert int(seen.sum(1).max()) == window
    full = ref.attention_head(q, k, v, None)
    assert float(jnp.abs(full - want)[window:].max()) > 1e-3


# ------------------------------------------------- costs and readers
def test_window_cost_counts_the_band_and_the_full_cost_the_triangle():
    arch = weights.arch_of(harness.load_cell(CELL).config)
    s, w = 16384, 4096
    band = w * (w + 1) // 2 + (s - w) * w
    assert window_flash_attention.pairs("swa", s, w) == band
    assert window_flash_attention.pairs("nope", s, w) == s * (s + 1) // 2
    assert band == sum(min(t + 1, w) for t in range(s))
    win = window_flash_attention.cost(**arch, batch=1, seq=s)
    full = full_flash_attention.cost(**arch, batch=1, seq=s)
    # nine products' worth (forward twice, the backward's five) of
    # 2 x 28 heads x 128 lanes a pair: six window layers, two full ones
    assert win["flops"] == 6 * 9 * 2 * 28 * 128 * band
    assert full["flops"] == 2 * 9 * 2 * 28 * 128 * (s * (s + 1) // 2)
    # a window layer does 0.44 of a full layer's true work
    assert win["flops"] / 6 / (full["flops"] / 2) \
        == pytest.approx(0.4375, abs=2e-3)
    # a group's gradients count at the 4 heads the model has
    q, kv = s * 28 * 128 * 2, s * 4 * 128 * 2
    assert full["bytes"] == 2 * (2 * (2 * q + 2 * kv) + 4 * q + 4 * kv)
    # compute-bound at the peaks: 115 and 88 ms are the least times
    assert win["flops"] / 197e12 == pytest.approx(0.1154, rel=1e-2)
    assert full["flops"] / 197e12 == pytest.approx(0.0879, rel=1e-2)
    assert win["bytes"] / 819e9 < 0.1 * win["flops"] / 197e12
    # a window as long as the sequence is the full mask
    assert window_flash_attention.pairs("swa", 64, 64) == 64 * 65 // 2


def test_model_flops_count_the_band_and_the_held_share():
    arch = weights.arch_of(harness.load_cell(CELL).config)
    per_token = swa_moe_train.matmul_params_per_token(arch)
    # the issue's forward FLOP a token: a layer's projections 41.9 M,
    # the held share of six experts 17.7 M, the router 0.33 M; the head
    # 778 M
    want = (8 * (41.94e6 + 17.69e6 + 0.328e6) + 777.9e6) / 2
    assert per_token == pytest.approx(want, rel=2e-3)
    assert swa_moe_train.keys_seen("nope", 16384, 4096) == 8192.5
    assert swa_moe_train.keys_seen("swa", 16384, 4096) \
        == pytest.approx(3584.1, abs=0.1)
    flops = swa_moe_train.forward_flops_per_token(arch, 16384)
    assert flops == pytest.approx(
        2 * per_token + 28 * 4 * 128 * (2 * 8192.5 + 6 * 3584.125))
    whole = swa_moe_train.matmul_params_per_token(
        {**arch, "held": (0, 64)})
    assert whole - per_token == pytest.approx(8 * 6 * 0.75 * 3 * 2560 * 768)


@pytest.mark.parametrize("metric", SCOPE_MS + ["moe_reglu_slot_fill_pct"])
def test_metrics_against_the_fixture(metric):
    assert read(metric, ctx()) == pytest.approx(FIX["expect"][metric])


@pytest.mark.parametrize("metric,want", [
    # the two whole executions are busy throughout, 3.6 and 3.96 s: the
    # step is their median and what every scope and the rest sum to
    ("step_device_ms.swa_moe", 3780.0),
    # busy 8.42 of the trace's 10 s, the cut executions included
    ("device_idle_pct.swa_moe", 15.8)])
def test_step_and_idle_share_against_the_fixture(metric, want):
    assert read(metric, ctx()) == pytest.approx(want)
    old = spec(metric.replace(".swa_moe",
                              "" if "step" in metric else ".train"))
    mine = spec(metric)
    assert (mine["reader"], mine["params"], mine["layer"], mine["unit"]) \
        == (old["reader"], old["params"], old["layer"], old["unit"])
    bare = ctx()        # the parent's program: no table
    bare["record"] = {k: v for k, v in FIX["record"].items()
                      if k != "program_trace"}
    assert read(metric, bare) == pytest.approx(want)


@pytest.mark.parametrize("metric,cost,seconds", [
    ("swa_window_roofline", window_flash_attention, "window_seconds"),
    ("swa_full_roofline", full_flash_attention, "full_seconds"),
    ("moe_reglu_mm_roofline", held_grouped_matmul, "grouped_mm_seconds")])
def test_rooflines_against_the_fixture(metric, cost, seconds):
    c = cost.cost(**FIX["record"]["arch"], **FIX["record"])
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    want = 100.0 * least * 2 / sum(FIX["expect"][seconds])
    assert read(metric, ctx()) == pytest.approx(want)


def test_the_kernels_of_both_kinds_share_their_names_and_the_scopes_part_them():
    """``flash_fwd`` runs under ``attn.window`` and under ``attn.full``
    in one step: a reader by kernel name would add the two, so the two
    rooflines read by scope; RoPE and the group's gradient sum are in
    their layer's time."""
    table = FIX["program_trace"]["op_scopes"]["jit_train_k"]
    by_scope = {}
    for inst, scope in table.items():
        by_scope.setdefault(scope, set()).add(inst.rsplit(".", 1)[0])
    assert by_scope["attn.window"] >= {"flash_fwd", "flash_bwd_dq",
                                       "flash_bwd_dkv", "fusion"}
    assert by_scope["attn.full"] >= {"flash_fwd", "flash_bwd_dq", "fusion"}
    assert spec("swa_window_roofline")["reader"] == "scope_roofline" \
        == spec("swa_full_roofline")["reader"]
    from dlnetbench_tpu.core import executor
    for path, want in (
            ("jit(train_k)/jit(main)/attn/attn.window/mul", "attn.window"),
            ("jit(train_k)/transpose(jvp(attn))/attn.full/reduce_sum",
             "attn.full"),
            ("jit(train_k)/checkpoint/attn/attn.window/pallas_call",
             "attn.window"),
            ("jit(train_k)/transpose(jvp(attn))/dot_general", "attn"),
            ("jit(train_k)/jvp(moe.router)/dot_general", "moe.router")):
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
        == ("smallthinker_21b_a3b_ep4", "pretrain_b1_s16384", 1)
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == cell["config"])
    assert set(config["reduced"]) == REDUCED
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("swa_proj_ms")
    assert names[at:at + 11] == [
        "swa_proj_ms", "swa_window_ms", "swa_full_ms",
        "swa_window_roofline", "swa_full_roofline", "moe_early_route_ms",
        "moe_reglu_experts_ms", "moe_reglu_mm_roofline",
        "moe_reglu_slot_fill_pct", *WHOLE]
    rate = next(m for m in MANIFEST["end_to_end"]
                if m["name"] == "train_tokens_per_s")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    loaded = harness.load_cell(CELL)
    assert (loaded.traffic["batch"], loaded.traffic["seq_len"],
            loaded.traffic["pool_batches"]) == (1, 16384, 8)
    assert "151936" in loaded.traffic["what"]
    assert loaded.workload["cycle_steps"] == 24
    assert loaded.workload["moe_slots"] % 256 == 0


def test_the_older_cells_entries_are_what_they_were():
    """The entries this PR found, by name and not by their place in a
    list: every older configuration, cell and per-layer metric with the
    values it had, in the order it had, and this cell's appended behind
    them."""
    cells = [w["name"] for w in MANIFEST["workloads"]]
    older = ["minerva7b_train", "mixtral8x7b_train",
             "phi4miniflash_train_s8k", "kimivl_a3b_train_s8k",
             "qwen3next_a3b_train_s16k", "lfm2_8b_a1b_train_s8k"]
    assert cells[:6] == older and cells.index(CELL) == 6
    rate = next(m for m in MANIFEST["end_to_end"]
                if m["name"] == "train_tokens_per_s")
    assert rate["workloads"][:6] == older
    assert rate["workloads"].index(CELL) == 6
    assert [c["name"] for c in MANIFEST["configs"]][:6] == [
        "minerva7b_4l", "mixtral8x7b_1l", "phi4miniflash_10l",
        "kimivl_a3b_ep4", "qwen3next_a3b_4l", "lfm2_8b_a1b_1chip"]
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("swa_proj_ms")
    assert at == 39 and names[at - 10] == "conv_mixer_ms" \
        and names[at - 1] == "device_idle_pct.conv_moe"
    assert not any(CELL in m.get("workloads", [])
                   for m in MANIFEST["per_layer"][:at])
    qwen = harness.load_cell("qwen3next_a3b_train_s16k")
    assert (qwen.traffic, qwen.workload["cycle_steps"]) \
        == (harness.load_cell(CELL).traffic, 48)    # one traffic file


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
    assert got["scope_ms"]["attn.window"] == pytest.approx(1050.0)
    assert got["scope_ms"]["attn.full"] == pytest.approx(525.0)
    assert got["scope_ms"]["attn"] == pytest.approx(315.0)
    assert sum(got["scope_ms"].values()) == pytest.approx(3600 * 1.05)
    assert got["top_ops"][0][0] in ("attn.window", "head_loss")


def test_scope_dump_fails_the_run_on_a_program_without_scopes():
    with pytest.raises(harness.BenchError, match="found nothing to read"):
        traced(lambda: {"spans": [], "op_scopes": {}})


def test_run_with_the_programs_tracer_names_every_new_layer(capsys):
    """The whole runner at the rehearsal size with the program's tracer
    on: the step's own table holds every scope the new metrics read, the
    plan's slot side is marked at every site, the counted backward at
    every layer, and the tracer is off again afterwards."""
    from dlnetbench_tpu.metrics import spans
    rc, seen, got = scope_dump.run_with_program_tracer(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.5",
         "--trace", "0", "--rehearse-cpu", "1"])
    capsys.readouterr()
    assert rc != 0 and not spans.is_enabled() and seen == {}
    scopes = set(got["op_scopes"]["jit_train_k"].values())
    assert {"attn", "attn.window", "attn.full", "moe.router",
            "moe.dispatch", "moe.experts", "moe.combine", "head_loss",
            "optimizer", "embed"} <= scopes
    assert not scopes & {"moe.shared", "mlp"}   # every FFN routed experts
    assert {s["name"] for s in got["spans"]} == {"compile"}
    marks = [m for s in got["spans"]
             for m in s["attrs"].get("moe.experts_bwd", [])]
    assert marks and {m["path"] for m in marks} == {"counted"}
