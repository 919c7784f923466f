"""Seeded weights of the hybrid decoder, made by the benchmark and
handed to both sides (``benchmarks/weights.py`` does the same for the
gated decoder; the token pool is that module's).

One jitted call makes the whole tree on the device in the layout the
program's ``models/hybrid.py`` reads: ``embed``, ``final_norm``,
``final_norm_b`` and four groups stacked on a leading axis by kind of
layer (``block``: both norms and the MLP of every layer; ``mamba``;
``attn``: the window and full layers; ``cross``; ``gmu``).  The layout is
the program's interface; the values are the benchmark's: normal draws
scaled by 1/sqrt(fan-in) (the tied embedding as the head it also is:
1/sqrt(hidden); 0.1 for the lambda vectors), norm weights 1, biases 0, A_log = log(1..N) a channel, D = 1,
b_dt the inverse softplus of a step drawn log-uniform in [1e-3, 1e-1].
A_log, D, b_dt, the conv bias, lambdas and norms are float32 whatever
the configuration's dtype (the family's convention).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import make_token_pool, seed_key  # noqa: F401

GROUP_OF = {"mamba": "mamba", "window": "attn", "full": "attn",
            "gmu": "gmu", "cross": "cross"}
F32_LEAVES = frozenset({
    "norm1", "norm1_b", "norm2", "norm2_b", "final_norm", "final_norm_b",
    "a_log", "d_skip", "b_dt", "conv_b", "sub_norm",
    "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"})


def arch_of(config: dict) -> dict:
    """The sizes both sides need, from a configuration file's keys (the
    published ``config.json`` names; what it lacks under ``assumed``)."""
    heads = config["num_attention_heads"]
    assumed = config["assumed"]
    kinds = tuple(config["layer_kinds"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_kinds must name num_hidden_layers layers")
    return {
        "vocab_size": config["vocab_size"],
        "embed_dim": config["hidden_size"],
        "num_heads": heads,
        "num_kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // heads,
        "ff_dim": config["intermediate_size"],
        "num_layers": len(kinds),
        "layer_kinds": kinds,
        "window": config["sliding_window"],
        "eps": config["layer_norm_eps"],
        "ssm_inner": assumed["ssm_inner"],
        "ssm_state": assumed["ssm_state"],
        "ssm_conv": assumed["ssm_conv"],
        "ssm_dt_rank": assumed["ssm_dt_rank"],
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def shapes(arch: dict) -> dict:
    """{"group/leaf" or "leaf": (shape, init)}; ``init`` is the scale of
    normal draws or "ones", "zeros", "a_log", "b_dt"."""
    d, f, v = arch["embed_dim"], arch["ff_dim"], arch["vocab_size"]
    e, n = arch["ssm_inner"], arch["ssm_state"]
    r, w = arch["ssm_dt_rank"], arch["ssm_conv"]
    dh = arch["head_dim"]
    dq, dkv = arch["num_heads"] * dh, arch["num_kv_heads"] * dh
    nl = arch["num_layers"]
    m = {g: sum(1 for k in arch["layer_kinds"] if GROUP_OF[k] == g)
         for g in ("mamba", "attn", "cross", "gmu")}
    s_d, s_e = 1.0 / math.sqrt(d), 1.0 / math.sqrt(e)
    out = {
        "embed": ((v, d), s_d),      # tied: the table is the head too
        "final_norm": ((d,), "ones"),
        "final_norm_b": ((d,), "zeros"),
        "block/norm1": ((nl, d), "ones"),
        "block/norm1_b": ((nl, d), "zeros"),
        "block/norm2": ((nl, d), "ones"),
        "block/norm2_b": ((nl, d), "zeros"),
        "block/w_gate": ((nl, d, f), s_d),
        "block/w_up": ((nl, d, f), s_d),
        "block/w_down": ((nl, f, d), 1.0 / math.sqrt(f)),
    }
    if m["mamba"]:
        k = m["mamba"]
        out.update({
            "mamba/w_in": ((k, d, 2 * e), s_d),
            "mamba/conv_w": ((k, w, e), 1.0 / math.sqrt(w)),
            "mamba/conv_b": ((k, e), "zeros"),
            "mamba/w_x": ((k, e, r + 2 * n), s_e),
            "mamba/w_dt": ((k, r, e), 1.0 / math.sqrt(r)),
            "mamba/b_dt": ((k, e), "b_dt"),
            "mamba/a_log": ((k, e, n), "a_log"),
            "mamba/d_skip": ((k, e), "ones"),
            "mamba/w_out": ((k, e, d), s_e),
        })
    for g in ("attn", "cross"):
        if not m[g]:
            continue
        k = m[g]
        out.update({
            f"{g}/wq": ((k, d, dq), s_d),
            f"{g}/wo": ((k, dq, d), 1.0 / math.sqrt(dq)),
            f"{g}/sub_norm": ((k, 2 * dh), "ones"),
            **{f"{g}/lambda_{x}": ((k, dh), 0.1)
               for x in ("q1", "k1", "q2", "k2")}})
    if m["attn"]:
        out.update({"attn/wk": ((m["attn"], d, dkv), s_d),
                    "attn/wv": ((m["attn"], d, dkv), s_d)})
    if m["gmu"]:
        out.update({"gmu/w1": ((m["gmu"], d, e), s_d),
                    "gmu/w2": ((m["gmu"], e, d), s_e)})
    return out


def _leaf(key, name, shape, init, dtype):
    dt = jnp.float32 if name.rsplit("/", 1)[-1] in F32_LEAVES else dtype
    if init == "ones":
        return jnp.ones(shape, dt)
    if init == "zeros":
        return jnp.zeros(shape, dt)
    if init == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[-1] + 1, dtype=jnp.float32)), shape).astype(dt)
    if init == "b_dt":
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
    return (jax.random.normal(key, shape, jnp.float32) * init).astype(dt)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, arch_items):
    arch = dict(arch_items)
    dtype = jnp.dtype(arch["dtype"])
    spec = shapes(arch)
    tree: dict = {}
    for k, (name, (shape, init)) in zip(
            jax.random.split(key, len(spec)), sorted(spec.items())):
        group, _, leaf = name.rpartition("/")
        (tree.setdefault(group, {}) if group else tree)[leaf] = _leaf(
            k, name, shape, init, dtype)
    return tree


def make_params(arch: dict, seed: int):
    return _make(seed_key(seed), tuple(sorted(arch.items())))
