"""End-to-end tests for the FSDP and hybrid (2D/3D/3D-MoE) proxies on the
8-device virtual CPU mesh."""
import pytest

from dlnetbench_tpu.core.model_card import load_model_card
from dlnetbench_tpu.core.model_stats import load_model_stats
from dlnetbench_tpu.proxies import fsdp as fsdp_proxy
from dlnetbench_tpu.proxies import hybrid_2d, hybrid_3d, hybrid_3d_moe
from dlnetbench_tpu.proxies.base import ProxyConfig, run_proxy

TINY = dict(size_scale=1e-6, time_scale=5e-5)
CFG = ProxyConfig(warmup=1, runs=2, **TINY)


def _stats(name):
    return load_model_stats(name)


def test_fsdp_sharded_world(eight_devices):
    bundle = fsdp_proxy.build(_stats("llama3_8b_16_bfloat16"), 4, CFG,
                              devices=eight_devices)
    result = run_proxy("fsdp", bundle, CFG)
    g = result.global_meta
    assert g["sharding_factor"] == 8 and g["num_replicas"] == 1
    assert len(result.timers_us["runtimes"]) == 2
    assert "allgather_time" in result.timers_us
    assert "reduce_scatter_time" in result.timers_us
    assert all(t > 0 for t in result.timers_us["allgather_time"])


def test_fsdp_hybrid_replicas(eight_devices):
    bundle = fsdp_proxy.build(_stats("llama3_8b_16_bfloat16"), 3, CFG,
                              devices=eight_devices, sharding_factor=4)
    result = run_proxy("fsdp", bundle, CFG)
    g = result.global_meta
    assert g["sharding_factor"] == 4 and g["num_replicas"] == 2
    assert g["mesh"]["axes"] == {"dp": 2, "tp": 4}


def test_fsdp_bad_factor(eight_devices):
    with pytest.raises(ValueError, match="divisible"):
        fsdp_proxy.build(_stats("llama3_8b_16_bfloat16"), 4, CFG,
                         devices=eight_devices, sharding_factor=3)


def test_hybrid_2d(eight_devices):
    stats = _stats("llama3_8b_16_bfloat16")
    card = load_model_card("llama3_8b")
    bundle = hybrid_2d.build(stats, card, CFG, num_stages=4,
                             num_microbatches=4, devices=eight_devices)
    result = run_proxy("hybrid_2d", bundle, CFG)
    g = result.global_meta
    assert g["dp"] == 2 and g["num_stages"] == 4  # dp inferred: 8/(4*1)
    assert g["layers_per_stage"] == 8
    assert "pp_comm_time" in result.timers_us
    assert "dp_comm_time" in result.timers_us
    assert all(t > 0 for t in result.timers_us["runtimes"])


def test_hybrid_3d(eight_devices):
    stats = _stats("llama3_8b_16_bfloat16")
    card = load_model_card("llama3_8b")
    bundle = hybrid_3d.build(stats, card, CFG, num_stages=2,
                             num_microbatches=4, tp=2, devices=eight_devices)
    result = run_proxy("hybrid_3d", bundle, CFG)
    g = result.global_meta
    assert g["dp"] == 2 and g["tp"] == 2
    assert g["tp_msg_bytes"] > 0
    assert "tp_comm_time" in result.timers_us
    assert "pp_comm_time" in result.timers_us


def test_hybrid_3d_world_mismatch(eight_devices):
    stats = _stats("llama3_8b_16_bfloat16")
    card = load_model_card("llama3_8b")
    with pytest.raises(ValueError, match="not divisible"):
        hybrid_3d.build(stats, card, CFG, num_stages=2, num_microbatches=4,
                        tp=3, devices=eight_devices)


def test_hybrid_3d_moe(eight_devices):
    stats = _stats("mixtral_8x7b_16_bfloat16")
    card = load_model_card("mixtral_8x7b")
    bundle = hybrid_3d_moe.build(stats, card, CFG, num_stages=4,
                                 num_microbatches=2, num_expert_shards=2,
                                 devices=eight_devices)
    result = run_proxy("hybrid_3d_moe", bundle, CFG)
    g = result.global_meta
    assert g["dp"] == 1 and g["num_expert_shards"] == 2
    assert g["a2a_bytes"] > 0
    assert "ep_comm_time" in result.timers_us
    assert "dp_ep_comm_time" in result.timers_us


def test_moe_requires_moe_card(eight_devices):
    stats = _stats("llama3_8b_16_bfloat16")
    card = load_model_card("llama3_8b")
    with pytest.raises(ValueError, match="moe_params"):
        hybrid_3d_moe.build(stats, card, CFG, num_stages=4,
                            num_microbatches=2, num_expert_shards=2,
                            devices=eight_devices)


@pytest.mark.parametrize("schedule", ["1f1b", "zb"])
@pytest.mark.parametrize("mode_build,kw", [
    (hybrid_2d.build, {}),
    (hybrid_3d.build, {"tp": 2}),
    (hybrid_3d_moe.build, {"num_expert_shards": 2}),
])
def test_extra_schedules_run(eight_devices, mode_build, kw, schedule):
    """1F1B and ZB-H1 (rebuild extras — the reference only has GPipe)
    must run end to end with the same microbatch totals and tag the
    record."""
    model = ("mixtral_8x7b" if mode_build is hybrid_3d_moe.build
             else "llama3_8b")
    stats = _stats(f"{model}_16_bfloat16")
    card = load_model_card(model)
    bundle = mode_build(stats, card, CFG, num_stages=2, num_microbatches=4,
                        schedule=schedule, **kw)
    assert bundle.global_meta["schedule"] == schedule
    res = run_proxy(bundle.global_meta["proxy"], bundle, CFG)
    assert len(res.timers_us["runtimes"]) == CFG.runs
    assert all(t > 0 for t in res.timers_us["runtimes"])
    assert "pp_comm_time" in res.timers_us


def test_zb_tick_accounting(eight_devices):
    """The zb record advertises the zero-bubble clock: 3M + (S-1) unit
    ticks, vs the 2-phase schedules' 3(M+S-1) (their 2(M+S-1) ticks count
    a 2-unit backward tick double) — and the same edge-message invariant
    as every other schedule."""
    stats = _stats("llama3_8b_16_bfloat16")
    card = load_model_card("llama3_8b")
    bundle = hybrid_2d.build(stats, card, CFG, num_stages=4,
                             num_microbatches=8, dp=2, schedule="zb")
    g = bundle.global_meta
    assert g["ticks_total"] == 3 * 8 + 3
    assert g["pp_edge_messages"] == 2 * 8 * 3


def test_unknown_schedule_rejected(eight_devices):
    stats = _stats("llama3_8b_16_bfloat16")
    card = load_model_card("llama3_8b")
    with pytest.raises(ValueError, match="schedule"):
        hybrid_2d.build(stats, card, CFG, num_stages=2, num_microbatches=4,
                        schedule="interleaved")


def _count_primitive(jaxpr, name: str) -> int:
    """Equations named ``name`` in ``jaxpr`` and every jaxpr nested in
    its equations' params (shard_map bodies, cond branches, loops)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_primitive(sub, name)
    return n


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_bubble_modeled(eight_devices, schedule):
    """The fill/drain bubble (reference hybrid_2d.cpp:106-133: stage s's
    first compute serialized behind s upstream computes) must be in the
    program: at fixed S*M the critical path is (M + S - 1) ticks a
    direction, NOT M as a bubble-free steady-state schedule would have.

    S=2,M=8 -> 9 ticks of 1/16 model time; S=4,M=4 -> 7 ticks.  Bubble
    modeled: path(S=4)/path(S=2) = 7/9 = 0.78; bubble missing: 0.5.

    Decided from the traced program and the proxy's own burn times, not
    from wall clock: the virtual devices are threads on shared cores,
    and a ratio of their timings says how loaded the box was."""
    import jax
    from dlnetbench_tpu.core.model_card import load_model_card
    stats = _stats("gpt2_l_16_bfloat16")
    card = load_model_card("gpt2_l")
    cfg = ProxyConfig(warmup=1, runs=1, size_scale=1e-6, time_scale=0.5)

    path_us = {}
    for S, M in ((2, 8), (4, 4)):
        bundle = hybrid_2d.build(stats, card, cfg, num_stages=S,
                                 num_microbatches=M, dp=1,
                                 schedule=schedule,
                                 devices=eight_devices[:S])
        meta = bundle.global_meta
        assert meta["ticks_per_direction"] == M + S - 1
        # the masking invariant: every edge still carries exactly one
        # message per microbatch per direction despite the extra ticks
        assert meta["pp_edge_messages"] == 2 * M * (S - 1)
        # the program that runs holds one stage-gated burn (a cond
        # around the burn loop) per tick and direction, chained on the
        # burn state: the bubble ticks are executed, not just declared
        jaxpr = jax.make_jaxpr(bundle.full.traceable)(
            *bundle.full.example_args)
        assert _count_primitive(jaxpr.jaxpr, "cond") == 2 * (M + S - 1)
        path_us[S] = meta["ticks_per_direction"] * (
            meta["fwd_us_per_stage_mb"] + meta["bwd_us_per_stage_mb"])

    ratio = path_us[4] / path_us[2]
    assert ratio == pytest.approx(7 / 9), (
        f"{schedule}: path(S=4)/path(S=2) = {ratio:.3f}; expected 7/9 "
        f"(bubble modeled) — 0.5 means the fill/drain bubble is missing")


def test_1f1b_updown_hops_independent_gpipe_chained(eight_devices):
    """VERDICT r1 #5: the 1F1B overlap claim, verified against the program
    rather than asserted.  Whether the up and down pipe hops of a steady
    1F1B pair can ride the bidirectional links together is a dataflow
    property — XLA may only overlap ops with no dependency path between
    them.  This must hold in the traced program (and fail if the
    independent-carry structure regresses); GPipe's hops must instead form
    one serial chain, which is what makes its two phases serial."""
    from dlnetbench_tpu.core.model_card import load_model_card
    from dlnetbench_tpu.metrics.profiling import permute_dependencies

    stats = _stats("gpt2_l_16_bfloat16")
    card = load_model_card("gpt2_l")
    cfg = ProxyConfig(warmup=1, runs=1, size_scale=1e-5, time_scale=1e-5)
    S, M = 4, 8

    deps_of = {}
    for sch in ("gpipe", "1f1b"):
        bundle = hybrid_2d.build(stats, card, cfg, num_stages=S,
                                 num_microbatches=M, dp=1, schedule=sch,
                                 devices=eight_devices[:S])
        n, deps = permute_dependencies(bundle.variants["pp_comm"])
        deps_of[sch] = (n, deps)

    # gpipe: every later hop transitively depends on every earlier one
    n, deps = deps_of["gpipe"]
    assert n > 0
    assert all((i, i + 1) in deps for i in range(n - 1)), \
        "GPipe hops must form a serial chain"

    # 1f1b: the steady phase interleaves up/down on independent carries —
    # most adjacent pairs must be mutually schedulable (no dependency)
    n, deps = deps_of["1f1b"]
    indep = [i for i in range(n - 1) if (i, i + 1) not in deps]
    # S-1 fill hops chain; of the remaining adjacent pairs the steady
    # up/down interleave must be independent (allow edge effects)
    assert len(indep) >= M, \
        f"1F1B lost its up/down overlap structure: only {indep}"

    # the same property must survive in the full comm program (burns and
    # gradient sync included), not just the hop-only variant
    bundle = hybrid_2d.build(stats, card, cfg, num_stages=S,
                             num_microbatches=M, dp=1, schedule="1f1b",
                             devices=eight_devices[:S])
    n_full, deps_full = permute_dependencies(bundle.comm)
    indep_full = [i for i in range(n_full - 1)
                  if (i, i + 1) not in deps_full]
    assert len(indep_full) >= M // 2, \
        f"full 1F1B program serialized its hops: {indep_full}"
