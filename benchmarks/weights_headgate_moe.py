"""Seeded weights of the head-gated window-and-full attention expert
decoder (``laguna_*`` configurations), made by the benchmark and handed
to both sides (``benchmarks/weights.py`` does the same for the gated
decoder; the token pool is that module's).

One jitted call makes the whole tree on the device in the layout the
program's ``models/hybrid.py`` reads: ``embed``, ``head`` ([V, D],
untied), ``final_norm`` and four groups stacked on a leading axis:
``block`` (both norms of every layer and the MLP of the leading dense
ones), ``gated`` (the full layers' attention at their head count),
``swa`` (the window layers' at theirs; each with the gate's projection
``wg`` [D, H]) and ``moe`` (the expert layers' router over ALL the
published experts, the HELD experts' three matrices and the shared
expert's).  The layout is the program's interface; the values are the
benchmark's: normal draws scaled by 1/sqrt(fan-in), the embedding by 1,
norm weights 1; norms are float32 whatever the configuration's dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import make_token_pool, seed_key  # noqa: F401

F32_LEAVES = frozenset({"norm1", "norm2", "final_norm"})
# a layer's kind, and its stack of weights, by its entry of layer_types
KIND_OF = {"full_attention": "gated", "sliding_attention": "swa"}


def _rope_of(rope: dict, head_dim: int) -> tuple:
    """(theta, turned lanes, YaRN's five numbers or None) of one entry
    of ``rope_parameters``."""
    kind = rope.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(f"neither side computes rope_type {kind!r}")
    yarn = None
    if kind == "yarn":
        yarn = (float(rope["factor"]),
                float(rope["original_max_position_embeddings"]),
                float(rope["beta_fast"]), float(rope["beta_slow"]),
                float(rope["attention_factor"]))
    return (float(rope["rope_theta"]),
            int(head_dim * rope.get("partial_rotary_factor", 1)), yarn)


def arch_of(config: dict) -> dict:
    """The sizes both sides need, from a configuration file's keys (the
    published ``config.json`` names; what it lacks under ``assumed``).
    ``num_experts`` in the file is the experts HELD here; where that is
    a share, ``published`` has the router's width."""
    unsupported = {k: config.get(k) for k, ok in (
        ("gating", ("per-head",)), ("norm_topk_prob", (True,)),
        ("decoder_sparse_step", (1,)),
        ("moe_router_logit_softcapping", (0,)),
        ("moe_apply_router_weight_on_input", (False,)),
        ("attention_bias", (False,)), ("tie_word_embeddings", (False,)))
        if config.get(k) not in ok}
    layers = config["num_hidden_layers"]
    kinds, ffns = config["layer_types"], config["mlp_layer_types"]
    heads = config["num_attention_heads_per_layer"]
    dense = list(config["mlp_only_layers"])
    per_kind = {k: {h for kk, h in zip(kinds, heads) if kk == k}
                for k in KIND_OF}
    if (unsupported or set(config["gating_types"]) != {"per_head"}
            or not len(kinds) == len(ffns) == len(heads) == layers
            == len(config["gating_types"])
            or set(kinds) - set(KIND_OF)
            or any(len(hs) > 1 for hs in per_kind.values())
            or per_kind["full_attention"]
            - {config["num_attention_heads"]}
            or dense != list(range(len(dense)))
            or [f == "dense" for f in ffns]
            != [li < len(dense) for li in range(layers)]):
        raise ValueError(
            f"neither side computes {unsupported or 'these per-layer lists'}"
            f": one head count a kind of layer (the full layers' "
            f"num_attention_heads), a gate a head, leading dense layers")
    held = config["num_experts"]
    experts = config.get("published", {}).get("num_experts", held)
    first = config["assumed"]["first_held_expert"]
    if not 0 <= first <= experts - held:
        raise ValueError(f"experts {first}..{first + held - 1} of {experts}")
    dh = config["head_dim"]
    rope = config["rope_parameters"]
    window = per_kind["sliding_attention"] or {config["num_attention_heads"]}
    return {
        "vocab_size": config["vocab_size"],
        "embed_dim": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "window_heads": next(iter(window)),
        "num_kv_heads": config["num_key_value_heads"],
        "head_dim": dh,
        "window": config["sliding_window"],
        "rope_full": _rope_of(rope["full_attention"], dh),
        "rope_window": _rope_of(rope["sliding_attention"], dh),
        "num_layers": layers,
        "layer_kinds": tuple(KIND_OF[k] for k in kinds),
        "first_dense": len(dense),
        "ff_dim": config["intermediate_size"],
        "num_experts": experts,
        "held": (first, held),
        "top_k": config["num_experts_per_tok"],
        "expert_ff_dim": config["moe_intermediate_size"],
        "shared_ff_dim": config["shared_expert_intermediate_size"],
        "routed_scale": float(config["moe_routed_scaling_factor"]),
        "eps": config["rms_norm_eps"],
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def expert_layers(arch: dict) -> int:
    return arch["num_layers"] - arch["first_dense"]


def heads_of(arch: dict, kind: str) -> int:
    return arch["window_heads" if kind == "swa" else "num_heads"]


def shapes(arch: dict) -> dict:
    """{"group/leaf" or "leaf": (shape, init)}; ``init`` is the scale of
    normal draws, or "ones"."""
    d, f, v = arch["embed_dim"], arch["ff_dim"], arch["vocab_size"]
    nl, nd, m = arch["num_layers"], arch["first_dense"], expert_layers(arch)
    hkv, dh = arch["num_kv_heads"], arch["head_dim"]
    x, held = arch["num_experts"], arch["held"][1]
    fe, fs = arch["expert_ff_dim"], arch["shared_ff_dim"]
    s_d = 1.0 / math.sqrt(d)
    out = {
        "embed": ((v, d), 1.0),
        "head": ((v, d), s_d),
        "final_norm": ((d,), "ones"),
        "block/norm1": ((nl, d), "ones"),
        "block/norm2": ((nl, d), "ones"),
        "block/w_gate": ((nd, d, f), s_d),
        "block/w_up": ((nd, d, f), s_d),
        "block/w_down": ((nd, f, d), 1.0 / math.sqrt(f)),
        "moe/w_router": ((m, d, x), s_d),
        "moe/w_gate": ((m, held, d, fe), s_d),
        "moe/w_up": ((m, held, d, fe), s_d),
        "moe/w_down": ((m, held, fe, d), 1.0 / math.sqrt(fe)),
        "moe/ws_gate": ((m, d, fs), s_d),
        "moe/ws_up": ((m, d, fs), s_d),
        "moe/ws_down": ((m, fs, d), 1.0 / math.sqrt(fs)),
    }
    for g in ("gated", "swa"):
        n, h = arch["layer_kinds"].count(g), heads_of(arch, g)
        out.update({
            f"{g}/wq": ((n, d, h * dh), s_d),
            f"{g}/wk": ((n, d, hkv * dh), s_d),
            f"{g}/wv": ((n, d, hkv * dh), s_d),
            f"{g}/wg": ((n, d, h), s_d),
            f"{g}/wo": ((n, h * dh, d), 1.0 / math.sqrt(h * dh))})
    return out


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
