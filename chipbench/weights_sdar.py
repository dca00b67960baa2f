"""Weights from a seed for the ``sdar_moe`` layer (GQA attention with
per-head q/k norms, then a softmax top-k of SiLU-gated experts), for the
driver and the reference.

As ``chipbench.weights``: every leaf is a pure function of ``(seed, leaf
name, layer index)``; an expert's three matrices of ``(..., expert index)``
and the embedding and the head of ``(..., block of the vocabulary)`` too,
so that the driver fills the stacks expert by expert and block by block in
bfloat16 and the reference never holds a layer's experts (2.4 GB) or either
table (1.2 GB) whole in float32. Imports nothing of the program.

Scales (the configuration's ``assumed`` lists them): matrices at the usual
fan-in scale, output projections over ``sqrt(2 x fan-in x layers)``, norm
scales near one, not all alike, and the embedding at 0.01. The last is what
makes the route of a block's masked positions a model's and not a constant:
every masked position embeds the same token, so under a large embedding the
hidden states of all masks of all rows are nearly one vector (at an
embedding of 1, 32 rows' masks pick 10 of 128 experts at layer 0 and a
forward on the chip touched 23-53 experts a layer, by the seed); at 0.01
what attention adds from a row's own context outweighs it and they pick 106
(layer 0, float32, on the CPU: PERF.md, PR 32). Sharper attention (q/k norm
scales of 2) spreads the picks further (124) and makes the bfloat16 program
wander a whole logit from the float32 reference; at unit scales it stays
within some tenths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.weights import seed_key  # noqa: F401  (the one seed rule)

_LEAF_IDS = {n: i for i, n in enumerate((
    "embed", "lm_head", "ln_f_scale", "ln1_scale", "ln2_scale", "wqkv",
    "wo", "q_norm", "k_norm", "router", "w_gate", "w_up", "w_down"))}

#: the leaves with a leading expert axis
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")

#: leaves the program computes with in float32 whatever its compute dtype
FLOAT32_LEAVES = ("router",)

#: blocks the embedding's rows (the head's columns) are made in, where
#: the vocabulary is large and divides by it
VOCAB_BLOCKS = 16


def model_dims(config: dict) -> dict:
    """The sizes from a configuration file's published keys, and how it
    generates from its ``generation`` group (hashable: the reference
    freezes it)."""
    V = config["vocab_size"]
    gen = config["generation"]
    assert config["decoder_sparse_step"] == 1 and not config["mlp_only_layers"]
    return {
        "D": config["hidden_size"], "H": config["num_attention_heads"],
        "Hkv": config["num_key_value_heads"], "Dh": config["head_dim"],
        "L": config["num_hidden_layers"], "V": V,
        "Vb": V // VOCAB_BLOCKS if V > 65536 and V % VOCAB_BLOCKS == 0
        else V,
        "E": config["num_experts"], "k": config["num_experts_per_tok"],
        "F": config["moe_intermediate_size"],
        "renorm": bool(config["norm_topk_prob"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "B": int(gen["block_length"]), "mask_id": int(gen["mask_token_id"]),
    }


def _normal(k, shape, scale):
    return jax.random.normal(k, shape, jnp.float32) * scale


def _layer_spec(m: dict) -> dict:
    """leaf -> (shape, scale); a negative scale: a norm scale, near its
    absolute value. An expert leaf's shape is ONE expert's."""
    D, L, F, Dh = m["D"], m["L"], m["F"], m["Dh"]
    W, kv = m["H"] * Dh, m["Hkv"] * Dh
    fan = D ** -0.5
    return {
        "ln1_scale": ((D,), -1.0), "ln2_scale": ((D,), -1.0),
        "wqkv": ((D, W + 2 * kv), fan),
        "wo": ((W, D), (2 * W * L) ** -0.5),
        "q_norm": ((Dh,), -1.0), "k_norm": ((Dh,), -1.0),
        "router": ((D, m["E"]), fan),
        "w_gate": ((D, F), fan), "w_up": ((D, F), fan),
        "w_down": ((F, D), (2 * F * L) ** -0.5),
    }


def _leaf(key, name: str, shape, scale):
    k = jax.random.fold_in(key, _LEAF_IDS[name])
    if scale < 0:
        return -scale * (1.0 + 0.1 * jax.random.normal(k, shape,
                                                       jnp.float32))
    return _normal(k, shape, scale)


def _layer_key(key, index):
    return jax.random.fold_in(key, 1000 + index)


def expert(key, m: dict, index, e) -> dict:
    """Expert ``e`` of layer ``index``: its three matrices, float32.
    Both indices may be traced."""
    spec = _layer_spec(m)
    kl = _layer_key(key, index)
    return {n: _normal(jax.random.fold_in(
        jax.random.fold_in(kl, _LEAF_IDS[n]), e), *spec[n])
        for n in EXPERT_LEAVES}


def layer(key, m: dict, index, names=None) -> dict:
    """Layer ``index``'s leaves WITHOUT its experts (or those of
    ``names``), float32. ``index`` may be traced."""
    kl = _layer_key(key, index)
    return {n: _leaf(kl, n, sh, sc) for n, (sh, sc) in _layer_spec(m).items()
            if n not in EXPERT_LEAVES and (names is None or n in names)}


def vocab_blocks(m: dict) -> int:
    return m["V"] // m["Vb"]


def embed_block(key, m: dict, b):
    """Rows ``b Vb .. (b + 1) Vb`` of the embedding, float32."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_IDS["embed"]), b)
    return _normal(k, (m["Vb"], m["D"]), 0.01)


def head_block(key, m: dict, b):
    """Columns ``b Vb .. (b + 1) Vb`` of the head, float32."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_IDS["lm_head"]), b)
    return _normal(k, (m["D"], m["Vb"]), m["D"] ** -0.5)


def final_norm(key, m: dict):
    return _leaf(key, "ln_f_scale", (m["D"],), -1.0)


def build(key, m: dict, dtype):
    """The tree in the program's layout (``layers``: a tuple of per-layer
    dicts, the expert leaves stacked on a leading axis), every leaf
    rounded to ``dtype`` as it is made but ``FLOAT32_LEAVES``. Trace under
    one ``jax.jit``: the float32 scratch is one expert's, one leaf's, or
    one block's of the vocabulary."""
    cast = lambda n, a: a if n in FLOAT32_LEAVES else a.astype(dtype)

    def one(i):
        lw = {n: cast(n, a) for n, a in layer(key, m, i).items()}
        lw.update(lax.map(
            lambda e: {n: a.astype(dtype)
                       for n, a in expert(key, m, i, e).items()},
            jnp.arange(m["E"])))
        return lw

    nb = vocab_blocks(m)
    embed = lax.map(lambda b: embed_block(key, m, b).astype(dtype),
                    jnp.arange(nb)).reshape(m["V"], m["D"])
    head = lax.fori_loop(   # each block written into its columns, in place
        0, nb, lambda b, buf: lax.dynamic_update_slice(
            buf, head_block(key, m, b).astype(dtype), (0, b * m["Vb"])),
        jnp.zeros((m["D"], m["V"]), dtype))
    return {"embed": embed, "ln_f_scale": final_norm(key, m).astype(dtype),
            "lm_head": head, "layers": tuple(one(i) for i in range(m["L"]))}
