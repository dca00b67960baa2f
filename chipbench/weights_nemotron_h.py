"""Weights from a seed for the ``nemotron_h`` pattern (Mamba-2 ``M``,
attention ``*``, LatentMoE ``E`` layers), for the driver and the reference.

As ``chipbench.weights``: every leaf is a pure function of ``(seed, leaf
name, layer index)``, and an expert's two matrices of ``(..., expert
index)`` too, so the driver builds the held share on the device in one
jitted call, rounding leaf by leaf (the float32 tree never exists), and
the reference asks for one layer, or one block of experts, at a time and
gets the same numbers. Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.weights import seed_key  # noqa: F401  (the one seed rule)

_LEAF_IDS = {n: i for i, n in enumerate((
    "embed", "lm_head", "ln_f_scale", "ln1_scale", "wqkv", "wo", "in_proj",
    "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm_scale", "out_proj",
    "router", "router_bias", "w_down", "w_up", "w1", "w2", "ws1", "ws2"))}

#: leaves the program computes with in float32 whatever its compute dtype
FLOAT32_LEAVES = ("router", "router_bias", "A_log", "D", "dt_bias")


def model_dims(config: dict) -> dict:
    """The sizes from a configuration file's published keys. ``held``
    experts of ``E`` (the published count) live here, from ``held0`` on."""
    Hm, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == config["num_hidden_layers"]
    return {
        "D": config["hidden_size"], "H": config["num_attention_heads"],
        "Hkv": config["num_key_value_heads"], "Dh": config["head_dim"],
        "L": config["num_hidden_layers"], "V": config["vocab_size"],
        "pattern": pattern, "eps": float(config["layer_norm_epsilon"]),
        "Hm": Hm, "P": P, "G": G, "N": N, "K": config["conv_kernel"],
        "Q": config["chunk_size"], "d_inner": Hm * P,
        "conv_dim": Hm * P + 2 * G * N,
        "E": config["published"]["n_routed_experts"],
        "held": config["n_routed_experts"],
        "held0": config.get("experts_held_from", 0),
        "k": config["num_experts_per_tok"], "R": config["moe_latent_size"],
        "F": config["moe_intermediate_size"],
        "Fs": config["moe_shared_expert_intermediate_size"],
        "scale": float(config["routed_scaling_factor"]),
        "dt_min": float(config["time_step_min"]),
        "dt_max": float(config["time_step_max"]),
    }


def _normal(k, shape, scale):
    return jax.random.normal(k, shape, jnp.float32) * scale


def _near_one(k, shape):   # a norm scale: near one, not all alike
    return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)


def _layer_spec(m: dict, kind: str) -> dict:
    """leaf -> (shape, how): a float is a normal's scale, a string one of
    the special draws of :func:`_leaf`."""
    D, L = m["D"], m["L"]
    spec = {"ln1_scale": ((D,), "near_one")}
    if kind == "*":
        kvw = 2 * m["Hkv"] * m["Dh"]
        spec["wqkv"] = ((D, m["H"] * m["Dh"] + kvw), D ** -0.5)
        spec["wo"] = ((m["H"] * m["Dh"], D), (2 * D * L) ** -0.5)
    elif kind == "M":
        di, Hm = m["d_inner"], m["Hm"]
        spec["in_proj"] = ((D, 2 * di + 2 * m["G"] * m["N"] + Hm), D ** -0.5)
        spec["conv_w"] = ((m["K"], m["conv_dim"]), m["K"] ** -0.5)
        spec["conv_b"] = ((m["conv_dim"],), 0.02)
        spec["dt_bias"] = ((Hm,), "dt_bias")
        spec["A_log"] = ((Hm,), "A_log")
        spec["D"] = ((Hm,), "near_one")
        spec["norm_scale"] = ((di,), "near_one")
        spec["out_proj"] = ((di, D), (2 * di * L) ** -0.5)
    elif kind == "E":
        R, Fs = m["R"], m["Fs"]
        spec["router"] = ((D, m["E"]), D ** -0.5)
        spec["router_bias"] = ((m["E"],), 0.02)   # assumed: drawn small
        spec["w_down"] = ((D, R), D ** -0.5)
        spec["w_up"] = ((R, D), (2 * R * L) ** -0.5)
        spec["ws1"] = ((D, Fs), D ** -0.5)
        spec["ws2"] = ((Fs, D), (2 * Fs * L) ** -0.5)
    else:
        raise ValueError(f"layer kind {kind!r} not in 'M*E'")
    return spec


def _leaf(key, name: str, shape, how, m: dict):
    k = jax.random.fold_in(key, _LEAF_IDS[name])
    if how == "near_one":
        return _near_one(k, shape)
    if how == "dt_bias":   # steps log-uniform in the published range,
        step = jnp.exp(jax.random.uniform(   # through the softplus
            k, shape, jnp.float32, math.log(m["dt_min"]),
            math.log(m["dt_max"])))
        return step + jnp.log(-jnp.expm1(-step))
    if how == "A_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    return _normal(k, shape, how)


def _layer_key(key, index):
    return jax.random.fold_in(key, 1000 + index)


def layer(key, m: dict, index, kind: str | None = None) -> dict:
    """Layer ``index``'s leaves but an ``E`` layer's experts, float32.
    With its ``kind`` given, ``index`` may be traced."""
    kl = _layer_key(key, index)
    spec = _layer_spec(m, kind or m["pattern"][index])
    return {n: _leaf(kl, n, sh, how, m) for n, (sh, how) in spec.items()}


def expert(key, m: dict, index, e):
    """Expert ``e`` (its number among all ``E``) of layer ``index``: (w1
    (R, F), w2 (F, R)) float32. ``index`` and ``e`` may be traced."""
    kl = _layer_key(key, index)
    R, F = m["R"], m["F"]
    k1 = jax.random.fold_in(jax.random.fold_in(kl, _LEAF_IDS["w1"]), e)
    k2 = jax.random.fold_in(jax.random.fold_in(kl, _LEAF_IDS["w2"]), e)
    return (_normal(k1, (R, F), R ** -0.5),
            _normal(k2, (F, R), (2 * F) ** -0.5))


def top(key, m: dict, names=("embed", "ln_f_scale", "lm_head")) -> dict:
    D, V = m["D"], m["V"]
    spec = {"embed": ((V, D), 0.02), "ln_f_scale": ((D,), "near_one"),
            "lm_head": ((D, V), D ** -0.5)}
    return {n: _leaf(key, n, *spec[n], m) for n in names}


def build(key, m: dict, dtype):
    """The held share in the program's layout (``layers``: a tuple of
    per-layer dicts), every leaf rounded to ``dtype`` as it is made but
    ``FLOAT32_LEAVES``. Trace under one ``jax.jit``: the experts come one
    after another (``lax.map``), so the float32 scratch is one leaf's."""
    cast = lambda n, a: a if n in FLOAT32_LEAVES else a.astype(dtype)
    layers = []
    for i, kind in enumerate(m["pattern"]):
        lw = {n: cast(n, a) for n, a in layer(key, m, i).items()}
        if kind == "E":
            w1, w2 = lax.map(
                lambda e: jax.tree.map(lambda a: a.astype(dtype),
                                       expert(key, m, i, e)),
                m["held0"] + jnp.arange(m["held"]))
            lw["w1"], lw["w2"] = w1, w2
        layers.append(lw)
    return {**{n: cast(n, a) for n, a in top(key, m).items()},
            "layers": tuple(layers)}
