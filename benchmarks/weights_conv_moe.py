"""Seeded weights of the short-convolution expert decoder (``lfm2_*``
configurations), made by the benchmark and handed to both sides
(``benchmarks/weights.py`` does the same for the gated decoder; the
token pool is that module's).

One jitted call makes the whole tree on the device in the layout the
program's ``models/hybrid.py`` reads: ``embed`` ([V, D], the head too:
tied), ``final_norm`` and four groups stacked on a leading axis:
``block`` (both norms of every layer, the SwiGLU of every dense one),
``conv`` (every conv layer's gated short convolution), ``gated`` (every
attention layer's projections and its two norms a head), ``moe`` (every
expert layer's router, selection bias and HELD routed experts).  A group
without a layer is left out, as the program leaves it out.  The layout
is the program's interface; the values are the benchmark's (the
configuration file's ``assumed``): normal draws scaled by
1/sqrt(fan-in), the conv's fan-in its taps, the tied table by
1/sqrt(hidden), norm weights 1, the selection bias normal x
``bias_scale``; norms and the bias are float32 whatever the
configuration's dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import make_token_pool, seed_key  # noqa: F401

F32_LEAVES = frozenset({"norm1", "norm2", "final_norm", "q_norm", "k_norm",
                        "router_bias"})
KIND_OF = {"conv": "conv", "full_attention": "gated"}


def arch_of(config: dict) -> dict:
    """The sizes both sides need, from a configuration file's keys (the
    published ``config.json`` names; what it lacks under ``assumed``).
    ``num_experts`` in the file is the experts HELD here; where that is
    a share, ``published`` has the router's width."""
    unsupported = {k: config.get(k) for k, ok in (
        ("conv_bias", (False,)), ("norm_topk_prob", (True,)),
        ("use_expert_bias", (True,))) if config.get(k) not in ok}
    if unsupported:
        raise ValueError(f"neither side computes {unsupported}")
    kinds = tuple(KIND_OF[t] for t in config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layer_types for "
                         f"{config['num_hidden_layers']} layers")
    held = config["num_experts"]
    experts = config.get("published", {}).get("num_experts", held)
    first = config["assumed"]["first_held_expert"]
    if not 0 <= first <= experts - held:
        raise ValueError(f"experts {first}..{first + held - 1} of {experts}")
    heads = config["num_attention_heads"]
    return {
        "vocab_size": config["vocab_size"],
        "embed_dim": config["hidden_size"],
        "num_heads": heads,
        "num_kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // heads,
        "rope_theta": float(config["rope_theta"]),
        "ff_dim": config["intermediate_size"],
        "num_layers": config["num_hidden_layers"],
        "layer_kinds": kinds,
        "first_dense": config["num_dense_layers"],
        "short_conv": config["conv_L_cache"],
        "num_experts": experts,
        "held": (first, held),
        "top_k": config["num_experts_per_tok"],
        "expert_ff_dim": config["moe_intermediate_size"],
        "routed_scale": float(config["routed_scaling_factor"]),
        "eps": config["norm_eps"],
        "bias_scale": config["assumed"]["router_bias_scale"],
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def expert_layers(arch: dict) -> int:
    return arch["num_layers"] - arch["first_dense"]


def shapes(arch: dict) -> dict:
    """{"group/leaf" or "leaf": (shape, init)}; ``init`` is the scale of
    normal draws, or "ones"."""
    d, f, v = arch["embed_dim"], arch["ff_dim"], arch["vocab_size"]
    nl, nd, m = arch["num_layers"], arch["first_dense"], expert_layers(arch)
    kinds = arch["layer_kinds"]
    mc, ma = kinds.count("conv"), kinds.count("gated")
    h, hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    x, held, fe = arch["num_experts"], arch["held"][1], arch["expert_ff_dim"]
    taps = arch["short_conv"]
    s_d = 1.0 / math.sqrt(d)
    groups = [
        (1, "", {"embed": ((v, d), s_d), "final_norm": ((d,), "ones")}),
        (nl, "block/", {"norm1": ((nl, d), "ones"),
                        "norm2": ((nl, d), "ones")}),
        (nd, "block/", {
            "w_gate": ((nd, d, f), s_d), "w_up": ((nd, d, f), s_d),
            "w_down": ((nd, f, d), 1.0 / math.sqrt(f))}),
        (mc, "conv/", {
            "w_in": ((mc, d, 3 * d), s_d),
            "conv_w": ((mc, taps, d), 1.0 / math.sqrt(taps)),
            "w_out": ((mc, d, d), s_d)}),
        (ma, "gated/", {
            "wq": ((ma, d, h * dh), s_d), "wk": ((ma, d, hkv * dh), s_d),
            "wv": ((ma, d, hkv * dh), s_d), "q_norm": ((ma, dh), "ones"),
            "k_norm": ((ma, dh), "ones"),
            "wo": ((ma, h * dh, d), 1.0 / math.sqrt(h * dh))}),
        (m, "moe/", {
            "w_router": ((m, d, x), s_d),
            "router_bias": ((m, x), arch["bias_scale"]),
            "w_gate": ((m, held, d, fe), s_d),
            "w_up": ((m, held, d, fe), s_d),
            "w_down": ((m, held, fe, d), 1.0 / math.sqrt(fe))}),
    ]
    # a group without a layer is left out, as the program leaves it out
    return {prefix + k: spec for count, prefix, leaves in groups if count
            for k, spec in leaves.items()}


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, arch_items):
    arch = dict(arch_items)
    dtype = jnp.dtype(arch["dtype"])
    spec = shapes(arch)
    tree: dict = {}
    for k, (name, (shape, init)) in zip(
            jax.random.split(key, len(spec)), sorted(spec.items())):
        group, _, leaf = name.rpartition("/")
        dt = jnp.float32 if leaf in F32_LEAVES else dtype
        value = (jnp.ones(shape, dt) if init == "ones" else
                 (jax.random.normal(k, shape, jnp.float32) * init)
                 .astype(dt))
        (tree.setdefault(group, {}) if group else tree)[leaf] = value
    return tree


def make_params(arch: dict, seed: int):
    return _make(seed_key(seed), tuple(sorted(arch.items())))
