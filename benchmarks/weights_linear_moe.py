"""Seeded weights of the linear-attention expert decoder
(``qwen3next_*`` configurations), made by the benchmark and handed to
both sides (``benchmarks/weights.py`` does the same for the gated
decoder; the token pool is that module's).

One jitted call makes the whole tree on the device in the layout the
program's ``models/hybrid.py`` reads: ``embed``, ``head`` ([V, D],
untied), ``final_norm`` and four groups stacked on a leading axis:
``block`` (both norms of every layer), ``gdn`` (every linear layer's
Gated DeltaNet), ``gated`` (every full layer's gated attention), ``moe``
(every layer's router, HELD routed experts, shared expert and its gate).
The layout is the program's interface; the values are the benchmark's
(the configuration file's ``assumed``): normal draws scaled by
1/sqrt(fan-in), the embedding by 1, the zero-centred norms' ``w`` 0, the
linear layers' output norm 1, ``A_log = log(u)`` with ``u`` uniform on
(0, 16), ``dt_bias`` 1; norms, ``A_log`` and ``dt_bias`` are float32
whatever the configuration's dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import make_token_pool, seed_key  # noqa: F401

F32_LEAVES = frozenset({"norm1", "norm2", "final_norm", "q_norm", "k_norm",
                        "o_norm", "a_log", "dt_bias"})
KIND_OF = {"linear_attention": "gdn", "full_attention": "gated"}


def layer_kinds(config: dict) -> tuple:
    """``layer_types`` if the file states them, else as the published
    configuration derives them from ``full_attention_interval``."""
    if config.get("layer_types"):
        return tuple(KIND_OF[t] for t in config["layer_types"])
    every = config["full_attention_interval"]
    return tuple("gated" if (i + 1) % every == 0 else "gdn"
                 for i in range(config["num_hidden_layers"]))


def arch_of(config: dict) -> dict:
    """The sizes both sides need, from a configuration file's keys (the
    published ``config.json`` names; what it lacks under ``assumed``).
    ``num_experts`` in the file is the experts HELD here, ``published``
    has the router's width."""
    unsupported = {k: config.get(k) for k, ok in (
        ("mlp_only_layers", ([],)), ("decoder_sparse_step", (1,)),
        ("norm_topk_prob", (True,)), ("rope_scaling", (None,)),
        ("use_sliding_window", (False,)),
        ("tie_word_embeddings", (False,)), ("hidden_act", ("silu",)))
        if config.get(k) not in ok}
    if unsupported:
        raise ValueError(f"neither side computes {unsupported}")
    held = config["num_experts"]
    experts = config.get("published", {}).get("num_experts", held)
    first = config["assumed"]["first_held_expert"]
    if not 0 <= first <= experts - held:
        raise ValueError(f"experts {first}..{first + held - 1} of {experts}")
    return {
        "vocab_size": config["vocab_size"],
        "embed_dim": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rope_dim": int(config["head_dim"]
                        * config["partial_rotary_factor"]),
        "rope_theta": float(config["rope_theta"]),
        "ff_dim": config["intermediate_size"],
        "num_layers": config["num_hidden_layers"],
        "layer_kinds": layer_kinds(config),
        "first_dense": 0,
        "linear_key_heads": config["linear_num_key_heads"],
        "linear_value_heads": config["linear_num_value_heads"],
        "linear_key_dim": config["linear_key_head_dim"],
        "linear_value_dim": config["linear_value_head_dim"],
        "linear_conv": config["linear_conv_kernel_dim"],
        "num_experts": experts,
        "held": (first, held),
        "top_k": config["num_experts_per_tok"],
        "expert_ff_dim": config["moe_intermediate_size"],
        "shared_ff_dim": config["shared_expert_intermediate_size"],
        "eps": config["rms_norm_eps"],
        "decay_max": config["assumed"]["decay_max"],
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def shapes(arch: dict) -> dict:
    """{"group/leaf" or "leaf": (shape, init)}; ``init`` is the scale of
    normal draws, "ones", "zeros" or "decay_log"."""
    d, v, nl = arch["embed_dim"], arch["vocab_size"], arch["num_layers"]
    kinds = arch["layer_kinds"]
    ml, mf = kinds.count("gdn"), kinds.count("gated")
    h, hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    hv, w = arch["linear_value_heads"], arch["linear_conv"]
    qk = arch["linear_key_heads"] * arch["linear_key_dim"]
    vz = hv * arch["linear_value_dim"]
    x, held = arch["num_experts"], arch["held"][1]
    fe, fs = arch["expert_ff_dim"], arch["shared_ff_dim"]
    s_d = 1.0 / math.sqrt(d)
    return {
        "embed": ((v, d), 1.0),
        "head": ((v, d), s_d),
        "final_norm": ((d,), "zeros"),
        "block/norm1": ((nl, d), "zeros"),
        "block/norm2": ((nl, d), "zeros"),
        "gdn/w_qkvz": ((ml, d, 2 * qk + 2 * vz), s_d),
        "gdn/w_ba": ((ml, d, 2 * hv), s_d),
        "gdn/conv_w": ((ml, w, 2 * qk + vz), 1.0 / math.sqrt(w)),
        "gdn/a_log": ((ml, hv), "decay_log"),
        "gdn/dt_bias": ((ml, hv), "ones"),
        "gdn/o_norm": ((ml, arch["linear_value_dim"]), "ones"),
        "gdn/w_out": ((ml, vz, d), 1.0 / math.sqrt(vz)),
        "gated/wq": ((mf, d, 2 * h * dh), s_d),
        "gated/wk": ((mf, d, hkv * dh), s_d),
        "gated/wv": ((mf, d, hkv * dh), s_d),
        "gated/q_norm": ((mf, dh), "zeros"),
        "gated/k_norm": ((mf, dh), "zeros"),
        "gated/wo": ((mf, h * dh, d), 1.0 / math.sqrt(h * dh)),
        "moe/w_router": ((nl, d, x), s_d),
        "moe/w_gate": ((nl, held, d, fe), s_d),
        "moe/w_up": ((nl, held, d, fe), s_d),
        "moe/w_down": ((nl, held, fe, d), 1.0 / math.sqrt(fe)),
        "moe/ws_gate": ((nl, d, fs), s_d),
        "moe/ws_up": ((nl, d, fs), s_d),
        "moe/ws_down": ((nl, fs, d), 1.0 / math.sqrt(fs)),
        "moe/ws_sig": ((nl, d), s_d),
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
        if init in ("ones", "zeros"):
            value = jnp.full(shape, float(init == "ones"), dt)
        elif init == "decay_log":
            # the decay's rate u = exp(A_log), uniform on (0, decay_max)
            value = jnp.log(arch["decay_max"] * jax.random.uniform(
                k, shape, jnp.float32, 2.0 ** -16, 1.0)).astype(dt)
        else:
            value = (jax.random.normal(k, shape, jnp.float32) * init
                     ).astype(dt)
        (tree.setdefault(group, {}) if group else tree)[leaf] = value
    return tree


def make_params(arch: dict, seed: int):
    return _make(seed_key(seed), tuple(sorted(arch.items())))
