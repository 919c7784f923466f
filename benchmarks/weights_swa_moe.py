"""Seeded weights of the window-and-full attention expert decoder
(``smallthinker_*`` configurations), made by the benchmark and handed to
both sides (``benchmarks/weights.py`` does the same for the gated
decoder; the token pool is that module's).

One jitted call makes the whole tree on the device in the layout the
program's ``models/hybrid.py`` reads: ``embed``, ``head`` ([V, D],
untied), ``final_norm`` and three groups stacked on a leading axis:
``block`` (both norms of every layer), ``gated`` (every layer's
grouped-query attention, window or full: one stack, the kind is the
layer's) and ``moe`` (every layer's router over ALL the published
experts and the HELD experts' three matrices).  The layout is the
program's interface; the values are the benchmark's: normal draws scaled
by 1/sqrt(fan-in), the embedding by 1, norm weights 1; norms are float32
whatever the configuration's dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import make_token_pool, seed_key  # noqa: F401

F32_LEAVES = frozenset({"norm1", "norm2", "final_norm"})
# a layer's kind by its entry of the two published layouts
KIND_OF = {1: "swa", 0: "nope"}


def arch_of(config: dict) -> dict:
    """The sizes both sides need, from a configuration file's keys (the
    published ``config.json`` names; what it lacks under ``assumed``).
    ``moe_num_primary_experts`` in the file is the experts HELD here;
    where that is a share, ``published`` has the router's width."""
    unsupported = {k: config.get(k) for k, ok in (
        ("moe_primary_router_apply_softmax", (True,)),
        ("norm_topk_prob", (True,)), ("rope_scaling", (None,)),
        ("tie_word_embeddings", (False,))) if config.get(k) not in ok}
    if unsupported:
        raise ValueError(f"neither side computes {unsupported}")
    window, turned = config["sliding_window_layout"], config["rope_layout"]
    if not (list(window) == list(turned)
            and len(window) == config["num_hidden_layers"]):
        raise ValueError(
            f"sliding_window_layout {window} and rope_layout {turned} "
            f"have to be one list of {config['num_hidden_layers']}: a "
            f"layer has a window and RoPE, or neither")
    held = config["moe_num_primary_experts"]
    experts = config.get("published", {}).get("moe_num_primary_experts",
                                              held)
    first = config["assumed"]["first_held_expert"]
    if not 0 <= first <= experts - held:
        raise ValueError(f"experts {first}..{first + held - 1} of {experts}")
    return {
        "vocab_size": config["vocab_size"],
        "embed_dim": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window_size"],
        "rope_theta": float(config["rope_theta"]),
        "num_layers": config["num_hidden_layers"],
        "layer_kinds": tuple(KIND_OF[int(w)] for w in window),
        "num_experts": experts,
        "held": (first, held),
        "top_k": config["moe_num_active_primary_experts"],
        "expert_ff_dim": config["moe_ffn_hidden_size"],
        "eps": config["rms_norm_eps"],
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def shapes(arch: dict) -> dict:
    """{"group/leaf" or "leaf": (shape, init)}; ``init`` is the scale of
    normal draws, or "ones"."""
    d, v, nl = arch["embed_dim"], arch["vocab_size"], arch["num_layers"]
    h, hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    x, held, fe = arch["num_experts"], arch["held"][1], arch["expert_ff_dim"]
    s_d = 1.0 / math.sqrt(d)
    return {
        "embed": ((v, d), 1.0),
        "head": ((v, d), s_d),
        "final_norm": ((d,), "ones"),
        "block/norm1": ((nl, d), "ones"),
        "block/norm2": ((nl, d), "ones"),
        "gated/wq": ((nl, d, h * dh), s_d),
        "gated/wk": ((nl, d, hkv * dh), s_d),
        "gated/wv": ((nl, d, hkv * dh), s_d),
        "gated/wo": ((nl, h * dh, d), 1.0 / math.sqrt(h * dh)),
        "moe/w_router": ((nl, d, x), s_d),
        "moe/w_gate": ((nl, held, d, fe), s_d),
        "moe/w_up": ((nl, held, d, fe), s_d),
        "moe/w_down": ((nl, held, fe, d), 1.0 / math.sqrt(fe)),
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
