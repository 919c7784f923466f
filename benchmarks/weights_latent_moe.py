"""Seeded weights of the latent-attention expert decoder
(``kimivl_*`` configurations), made by the benchmark and handed to both
sides (``benchmarks/weights.py`` does the same for the gated decoder;
the token pool is that module's).

One jitted call makes the whole tree on the device in the layout the
program's ``models/hybrid.py`` reads: ``embed``, ``head`` ([V, D],
untied), ``final_norm`` and three groups stacked on a leading axis:
``block`` (both norms of every layer, the SwiGLU of every dense one),
``mla`` (every layer's latent attention), ``moe`` (every expert layer's
router, selection bias, HELD routed experts and shared expert).  The
layout is the program's interface; the values are the benchmark's:
normal draws scaled by 1/sqrt(fan-in), the embedding by 1, norm weights
1, the selection bias normal x ``bias_scale``; norms and the bias are
float32 whatever the configuration's dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import make_token_pool, seed_key  # noqa: F401

F32_LEAVES = frozenset({"norm1", "norm2", "final_norm", "kv_norm",
                        "router_bias"})


def arch_of(config: dict) -> dict:
    """The sizes both sides need, from a configuration file's keys (the
    published ``config.json`` names; what it lacks under ``assumed``).
    ``n_routed_experts`` is the experts HELD here, ``published`` has the
    router's width."""
    unsupported = {k: config.get(k) for k, ok in (
        ("q_lora_rank", (None,)), ("n_group", (1,)), ("topk_group", (1,)),
        ("rope_scaling", (None,)), ("moe_layer_freq", (1,)),
        ("scoring_func", ("sigmoid",)), ("norm_topk_prob", (True,)),
        ("tie_word_embeddings", (False,)), ("attention_bias", (False,)))
        if config.get(k) not in ok}
    if unsupported:
        raise ValueError(f"neither side computes {unsupported}")
    held = config["n_routed_experts"]
    experts = config.get("published", {}).get("n_routed_experts", held)
    first = config["assumed"]["first_held_expert"]
    if not 0 <= first <= experts - held:
        raise ValueError(f"experts {first}..{first + held - 1} of {experts}")
    return {
        "vocab_size": config["vocab_size"],
        "embed_dim": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "ff_dim": config["intermediate_size"],
        "num_layers": config["num_hidden_layers"],
        "first_dense": config["first_k_dense_replace"],
        "num_experts": experts,
        "held": (first, held),
        "top_k": config["num_experts_per_tok"],
        "expert_ff_dim": config["moe_intermediate_size"],
        "shared_ff_dim": (config["n_shared_experts"]
                          * config["moe_intermediate_size"]),
        "routed_scale": config["routed_scaling_factor"],
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_head_dim": config["qk_nope_head_dim"],
        "qk_rope_head_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "rope_theta": float(config["rope_theta"]),
        "eps": config["rms_norm_eps"],
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
    h, r = arch["num_heads"], arch["kv_lora_rank"]
    dn, dr, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                  arch["v_head_dim"])
    x, held = arch["num_experts"], arch["held"][1]
    fe, fs = arch["expert_ff_dim"], arch["shared_ff_dim"]
    s_d = 1.0 / math.sqrt(d)
    return {
        "embed": ((v, d), 1.0),
        "head": ((v, d), s_d),
        "final_norm": ((d,), "ones"),
        "block/norm1": ((nl, d), "ones"),
        "block/norm2": ((nl, d), "ones"),
        "block/w_gate": ((nd, d, f), s_d),
        "block/w_up": ((nd, d, f), s_d),
        "block/w_down": ((nd, f, d), 1.0 / math.sqrt(f)),
        "mla/wq": ((nl, d, h * (dn + dr)), s_d),
        "mla/w_kva": ((nl, d, r + dr), s_d),
        "mla/kv_norm": ((nl, r), "ones"),
        "mla/w_kvb": ((nl, r, h * (dn + dv)), 1.0 / math.sqrt(r)),
        "mla/wo": ((nl, h * dv, d), 1.0 / math.sqrt(h * dv)),
        "moe/w_router": ((m, d, x), s_d),
        "moe/router_bias": ((m, x), arch["bias_scale"]),
        "moe/w_gate": ((m, held, d, fe), s_d),
        "moe/w_up": ((m, held, d, fe), s_d),
        "moe/w_down": ((m, held, fe, d), 1.0 / math.sqrt(fe)),
        "moe/ws_gate": ((m, d, fs), s_d),
        "moe/ws_up": ((m, d, fs), s_d),
        "moe/ws_down": ((m, fs, d), 1.0 / math.sqrt(fs)),
    }


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
