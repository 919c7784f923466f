"""The hybrid cell's own pieces: the controls that have to come out as
not correct at the rehearsal size, the reference's layer-at-a-time
backward against autodiff of its whole loss, the scan's cost and the
``scope_roofline`` reader on a fixture of their own
(``scope_fixture_hybrid.json``), and the rule that puts an operation
under ``ssm`` and ``ssm.scan`` in the inner one, once."""
import importlib
import json

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness, reference_hybrid as ref, run
from benchmarks import weights_hybrid
from benchmarks.costs import hybrid_train, selective_scan
from benchmarks.readers import scope_ms, scope_roofline
from benchmarks.runners import train, train_hybrid

CELL = "phi4miniflash_train_s8k"
FIX = harness.load_json(harness.HERE / "scope_fixture_hybrid.json")
NEW = ["ssm_scan_ms", "ssm_scan_roofline", "ssm_ms", "gmu_ms",
       "diff_attn_ms"]


def rehearsal_cell():
    return harness.rehearsal(harness.load_cell(CELL))


def spec(metric):
    return harness.load_json(harness.HERE / "layer_metrics"
                             / f"{metric}.json")


def ctx():
    return {"record": {**FIX["record"],
                       "program_trace": FIX["program_trace"]},
            "devices": [{"ops": [tuple(e) for e in FIX["ops"]],
                         "modules": [tuple(e) for e in FIX["modules"]]}],
            "window": tuple(FIX["window"]), "peaks": FIX["peaks"]}


def read(metric, c):
    s = spec(metric)
    return importlib.import_module(
        f"benchmarks.readers.{s['reader']}").read(c, s["params"])


# ----------------------------------------------------------- correct
def test_int8_reference_is_not_correct_at_the_rehearsal_size():
    rows = train_hybrid.readings(rehearsal_cell(), 7, lambda _: None,
                                 "reference_int8")
    assert any(value > limit for _, value, limit, _ in rows)


def test_sound_program_is_correct_and_unknown_control_is_refused():
    rows = train_hybrid.readings(rehearsal_cell(), 2**31 + 11,
                                 lambda _: None, None)
    assert all(value <= limit for _, value, limit, _ in rows)
    with pytest.raises(harness.BenchError, match="no control"):
        train_hybrid.readings(rehearsal_cell(), 7, lambda _: None,
                              "program")


def test_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    def call(self):
        _, losses = self.step(jax.tree.map(jnp.copy, self.params),
                              self.feed())
        self.steps_done += 1
        return losses
    monkeypatch.setattr(train.TrainCell, "call", call)
    run.main(["--workload", CELL, "--seed", "5", "--seconds", "1",
              "--trace", "0", "--rehearse-cpu", "1"])
    got = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("{")]
    result = next(g for g in got if g["line"].startswith("rehearsal"))
    assert result["correct"] is False
    bad = {g["name"] for g in got
           if g["line"] == "compared" and not g["ok"]}
    assert "delta_norm_gap" in bad


def test_configuration_keeps_the_published_widths_and_vocabulary():
    body = harness.load_cell(CELL).config
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
               "intermediate_size": 10240, "layer_norm_eps": 1e-05,
               "max_position_embeddings": 262144, "mb_per_layer": 2,
               "model_type": "phi4flash", "num_attention_heads": 40,
               "num_key_value_heads": 20, "resid_pdrop": 0,
               "sliding_window": 512, "tie_word_embeddings": True,
               "mlp_bias": False, "lm_head_bias": False,
               "vocab_size": 200064}
    assert {k: body[k] for k in catalog} == catalog
    assert list(body["reduced"]) == ["num_hidden_layers"]
    kinds = body["layer_kinds"]
    assert len(kinds) == body["num_hidden_layers"] == 10
    assert set(kinds) == set(body["published"]["layer_kinds"])
    assert set(body["assumed"]) >= {"ssm_inner", "ssm_state", "ssm_conv",
                                    "ssm_dt_rank", "why"}
    arch = weights_hybrid.arch_of(body)
    params = sum(math_prod(shape) for shape, _ in
                 weights_hybrid.shapes(arch).values())
    assert abs(params - 1.56e9) < 0.01e9


def math_prod(shape):
    out = 1
    for s in shape:
        out *= s
    return out


# --------------------------------------------------------- reference
def test_layer_at_a_time_backward_equals_autodiff_of_the_whole_loss():
    cell = rehearsal_cell()
    arch = weights_hybrid.arch_of(cell.config)
    p = ref.unstack(weights_hybrid.make_params(arch, 3),
                    arch["layer_kinds"])
    tokens = weights_hybrid.make_token_pool(3, 1, 2, 65,
                                            arch["vocab_size"])[0]
    with jax.default_matmul_precision("highest"):
        loss, grads = ref.LayerwiseGrad(arch)(p, tokens)
        want_loss, want = jax.value_and_grad(
            lambda q: ref.loss_fn(q, tokens, arch))(p)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    gaps = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-30)), grads, want)
    assert max(jax.tree.leaves(gaps)) < 1e-4


def test_reference_recurrence_by_chunks_is_the_plain_scan(monkeypatch):
    ks = jax.random.split(jax.random.key(0), 5)
    t, e, n = 64, 8, 4
    u = jax.random.normal(ks[0], (t, e))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (t, e)))
    a = -jnp.exp(jax.random.normal(ks[2], (e, n)))
    bm, cm = (jax.random.normal(k, (t, n)) for k in ks[3:])
    d = jnp.ones(e)
    h, want = jnp.zeros((e, n)), []
    for i in range(t):          # the recurrence as the paper writes it
        h = jnp.exp(delta[i][:, None] * a) * h \
            + (delta[i] * u[i])[:, None] * bm[i][None, :]
        want.append(h @ cm[i] + d * u[i])
    whole = ref.recurrence(u, delta, a, bm, cm, d)
    monkeypatch.setattr(ref, "SCAN_CHUNK", 16)
    chunked = ref.recurrence(u, delta, a, bm, cm, d)
    assert jnp.allclose(whole, jnp.stack(want), rtol=1e-5, atol=1e-6)
    assert jnp.allclose(chunked, whole, rtol=1e-6, atol=1e-7)


# ------------------------------------------------- costs and readers
def test_selective_scan_cost_from_shapes():
    got = selective_scan.cost(batch=1, seq=2, ssm_inner=3, ssm_state=2,
                              layer_kinds=("mamba", "full"),
                              vocab_size=9)
    # a (step, channel): forward 7 * 2 + 3 = 17, backward 20 * 2 + 6 =
    # 46; six of them; forward counted twice (the recomputation)
    assert got["flops"] == 2 * 6 * 17 + 6 * 46
    # forward: u 2 + delta 4 + s 2 a (step, channel), B and C 2 each a
    # (step, state); backward: u, delta, ds in, du, ddelta out, B, C in,
    # dB, dC out, dA [3, 2] float32
    assert got["bytes"] == 2 * (6 * 8 + 4 * 4) + (6 * 10 + 16 + 6 * 6
                                                  + 16 + 24)
    two = selective_scan.cost(batch=1, seq=2, ssm_inner=3, ssm_state=2,
                              layer_kinds=("mamba", "mamba"))
    assert two == {k: 2 * v for k, v in got.items()}


def test_model_flops_count_every_kind_of_layer():
    arch = weights_hybrid.arch_of(harness.load_cell(CELL).config)
    per_token = hybrid_train.matmul_params_per_token(arch)
    # every weight but the float32 vectors and the conv is a matmul's
    assert abs(per_token - 1.56e9) < 0.01e9
    assert hybrid_train.keys_per_query("window", 8192, 512) \
        == pytest.approx(512 - 511 * 512 / 2 / 8192)
    assert hybrid_train.keys_per_query("full", 8192, 512) == 4096.5
    flops = hybrid_train.flops_per_token(arch, 8192)
    assert 6 * per_token < flops < 7 * per_token


@pytest.mark.parametrize("metric", ["ssm_scan_ms", "ssm_ms", "gmu_ms",
                                    "diff_attn_ms"])
def test_scope_metrics_against_the_fixture(metric):
    assert read(metric, ctx()) == pytest.approx(FIX["expect"][metric])


def test_scope_roofline_against_the_fixture():
    cost = selective_scan.cost(**FIX["record"]["arch"], **FIX["record"])
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    spent = sum(FIX["expect"]["scan_seconds"])
    want = 100.0 * least * 2 / spent
    assert read("ssm_scan_roofline", ctx()) == pytest.approx(want)
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12   # HBM-bound


@pytest.mark.parametrize("metric", NEW)
def test_new_metrics_read_nothing_from_a_program_without_them(metric):
    """The parent's program has no such scope and exports no table: the
    reader gives None and does not raise."""
    bare = ctx()
    bare["record"] = dict(FIX["record"])
    assert read(metric, bare) is None
    empty = ctx()
    empty["record"]["program_trace"] = {"op_scopes": {"jit_train_k": {
        k: "other" for k in FIX["program_trace"]["op_scopes"][
            "jit_train_k"]}}, "spans": []}
    assert read(metric, empty) is None
    no_peaks = {**ctx(), "peaks": None}
    assert scope_roofline.read(no_peaks,
                               spec("ssm_scan_roofline")["params"]) is None


def test_run_with_the_programs_tracer_names_every_new_layer(capsys):
    """The whole runner at the rehearsal size with the program's tracer
    on: the step's own table holds every scope the new metrics read,
    and the scan's operations lie under ``ssm.scan``, not ``ssm``."""
    from benchmarks import scope_dump
    from dlnetbench_tpu.metrics import spans
    rc, seen, got = scope_dump.run_with_program_tracer(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.5",
         "--trace", "0", "--rehearse-cpu", "1"])
    capsys.readouterr()
    assert rc != 0 and not spans.is_enabled() and seen == {}
    table = got["op_scopes"]["jit_train_k"]
    assert {"ssm", "ssm.scan", "gmu", "attn", "mlp", "head_loss",
            "optimizer", "embed"} <= set(table.values())
    assert {s["name"] for s in got["spans"]} == {"compile"}
    for metric in NEW:
        assert set(spec(metric)["params"]["scopes"]) <= set(spans.SCOPES)


def test_an_operation_under_ssm_and_ssm_scan_is_counted_once():
    from dlnetbench_tpu.core import executor
    for path in ("jit(train_k)/jit(main)/ssm/ssm.scan/exp",
                 "jit(train_k)/jvp(ssm)/ssm.scan/mul",
                 "jit(train_k)/transpose(jvp(ssm))/ssm.scan/while"):
        assert executor.scope_of_op_name(path) == "ssm.scan"
    assert executor.scope_of_op_name("jit(train_k)/jvp(ssm)/dot") == "ssm"
    # in the fixture the scan's time is in ssm_scan_ms and not in ssm_ms
    both = scope_ms.scope_seconds(ctx(), "train_k", {"ssm", "ssm.scan"})
    inner = scope_ms.scope_seconds(ctx(), "train_k", {"ssm.scan"})
    outer = scope_ms.scope_seconds(ctx(), "train_k", {"ssm"})
    for (b, _), (i, _), (o, _) in zip(both, inner, outer):
        assert b == pytest.approx(i + o)
