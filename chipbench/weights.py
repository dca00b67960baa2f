"""Weights from a seed, for the drivers and for the plain references.

The tree's one block (RMSNorm, packed qkv, rope, tanh-GELU MLP, untied
head) takes these leaves. Every leaf is a pure function of
``(seed, leaf name, layer index)``: the drivers build the whole stacked
tree on the device in ONE jitted call (``build``), and a reference asks
for one layer at a time (``layer``, ``top``) and gets the same numbers
without holding the model. Imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_LEAF_IDS = {"embed": 0, "lm_head": 1, "ln_f_scale": 2, "ln1_scale": 3,
             "ln2_scale": 4, "wqkv": 5, "wo": 6, "w1": 7, "w2": 8}


def model_dims(config: dict) -> dict:
    """The block's sizes from a configuration file's published keys."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {
        "D": d, "H": h, "Hkv": config["num_key_value_heads"],
        "Dh": d // h, "F": config["intermediate_size"],
        "L": config["num_hidden_layers"], "V": config["vocab_size"],
        "theta": float(config["rope_theta"]),
    }


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _layer_spec(m: dict) -> dict:
    D, F, L = m["D"], m["F"], m["L"]
    kvw = 2 * m["Hkv"] * m["Dh"]
    return {
        "ln1_scale": ((D,), None), "ln2_scale": ((D,), None),
        "wqkv": ((D, D + kvw), D ** -0.5),
        "wo": ((D, D), (2 * D * L) ** -0.5),
        "w1": ((D, F), D ** -0.5),
        "w2": ((F, D), (2 * F * L) ** -0.5),
    }


def _top_spec(m: dict) -> dict:
    D, V = m["D"], m["V"]
    return {"embed": ((V, D), 0.02), "ln_f_scale": ((D,), None),
            "lm_head": ((D, V), D ** -0.5)}


def _leaf(key, name: str, shape, scale):
    k = jax.random.fold_in(key, _LEAF_IDS[name])
    if scale is None:  # a norm scale: near one, not all alike
        return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
    return jax.random.normal(k, shape, jnp.float32) * scale


def layer(key, m: dict, index) -> dict:
    """Layer ``index``'s leaves, float32."""
    kl = jax.random.fold_in(key, 1000 + index)
    return {n: _leaf(kl, n, sh, sc) for n, (sh, sc) in _layer_spec(m).items()}


def top(key, m: dict, names=("embed", "ln_f_scale", "lm_head")) -> dict:
    spec = _top_spec(m)
    return {n: _leaf(key, n, *spec[n]) for n in names}


def build(key, m: dict):
    """The whole stacked tree in the program's layout (leaves under
    ``layers`` carry a leading layer axis). Trace under one ``jax.jit``;
    ``lax.map`` makes the layers one after another, so the generator's
    scratch is one layer's, not the stack's."""
    layers = lax.map(lambda i: layer(key, m, i), jnp.arange(m["L"]))
    return {**top(key, m), "layers": layers}
