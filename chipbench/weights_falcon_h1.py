"""Weights from a seed for the ``falcon_h1`` block (attention and Mamba-2
side by side off one norm, then a SiLU-gated MLP), for the driver and the
reference.

As ``chipbench.weights``: every leaf is a pure function of ``(seed, leaf
name, layer index)``; the embedding and the head, 1.3 G elements each at
the whole vocabulary, of ``(..., block of the vocabulary)`` too, so that
the driver fills them block by block in bfloat16 and the reference never
holds either whole in float32. Imports nothing of the program.

The published multipliers were tuned for trained weights. Drawn at unit
scale, ``key_multiplier`` 0.011 would flatten every attention row and the
comparison would see neither rope nor the keys. So a leaf that a
multiplier follows is drawn at the usual scale DIVIDED by that multiplier:
the stored tensors are then what the program multiplies back to order one
at use, as a trained model's are (``SCALES`` below; the configuration's
``assumed`` lists them).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.weights import seed_key  # noqa: F401  (the one seed rule)

_LEAF_IDS = {n: i for i, n in enumerate((
    "embed", "lm_head", "ln_f_scale", "ln1_scale", "ln2_scale", "wqkv",
    "wo", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
    "norm_scale", "out_proj", "w_gate", "w_up", "w_down"))}

#: leaves the program computes with in float32 whatever its compute dtype
FLOAT32_LEAVES = ("A_log", "D", "dt_bias")

#: rows of the vocabulary a block of the embedding (columns, of the head)
VOCAB_BLOCK = 15360


def model_dims(config: dict) -> dict:
    """The sizes and the multipliers from a configuration file's
    published keys (hashable: the reference freezes it)."""
    Hm, P = config["mamba_n_heads"], config["mamba_d_head"]
    G, N = config["mamba_n_groups"], config["mamba_d_state"]
    assert Hm * P == config["mamba_d_ssm"]
    V = config["vocab_size"]
    return {
        "D": config["hidden_size"], "H": config["num_attention_heads"],
        "Hkv": config["num_key_value_heads"], "Dh": config["head_dim"],
        "L": config["num_hidden_layers"], "V": V,
        "Vb": VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V,
        "F": config["intermediate_size"],
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "Hm": Hm, "P": P, "G": G, "N": N, "K": config["mamba_d_conv"],
        "Q": config["mamba_chunk_size"], "d_inner": Hm * P,
        "conv_dim": Hm * P + 2 * G * N,
        "dt_min": float(config["time_step_min"]),
        "dt_max": float(config["time_step_max"]),
        "m_embed": float(config["embedding_multiplier"]),
        "m_attn_in": float(config["attention_in_multiplier"]),
        "m_key": float(config["key_multiplier"]),
        "m_attn_out": float(config["attention_out_multiplier"]),
        "m_ssm_in": float(config["ssm_in_multiplier"]),
        "m_ssm": tuple(float(v) for v in config["ssm_multipliers"]),
        "m_ssm_out": float(config["ssm_out_multiplier"]),
        "m_mlp": tuple(float(v) for v in config["mlp_multipliers"]),
        "m_head": float(config["lm_head_multiplier"]),
    }


def _normal(k, shape, scale):
    return jax.random.normal(k, shape, jnp.float32) * scale


def _columns(widths, scales):
    """One scale a column: ``scales[i]`` over ``widths[i]`` columns."""
    return jnp.concatenate([jnp.full((w,), s, jnp.float32)
                            for w, s in zip(widths, scales)])


def _layer_spec(m: dict) -> dict:
    """leaf -> (shape, how): a float is a normal's scale, an array one
    scale a column, a string one of the special draws of :func:`_leaf`.
    ``SCALES``: the usual fan-in scale over the multiplier that follows
    the leaf's product."""
    D, L, F = m["D"], m["L"], m["F"]
    W, kv = m["H"] * m["Dh"], m["Hkv"] * m["Dh"]
    di, bc, Hm = m["d_inner"], m["G"] * m["N"], m["Hm"]
    fan = D ** -0.5
    return {
        "ln1_scale": ((D,), "near_one"), "ln2_scale": ((D,), "near_one"),
        # q, v at the fan-in scale over the input's multiplier; k over
        # the key's too
        "wqkv": ((D, W + 2 * kv), _columns(
            (W, kv, kv), (fan / m["m_attn_in"],
                          fan / (m["m_attn_in"] * m["m_key"]),
                          fan / m["m_attn_in"]))),
        "wo": ((W, D), (2 * W * L) ** -0.5 / m["m_attn_out"]),
        # [z | x | B | C | dt], each over the input's and its own
        "in_proj": ((D, 2 * di + 2 * bc + Hm), _columns(
            (di, di, bc, bc, Hm),
            [fan / (m["m_ssm_in"] * s) for s in m["m_ssm"]])),
        "conv_w": ((m["K"], m["conv_dim"]), m["K"] ** -0.5),
        "conv_b": ((m["conv_dim"],), 0.02),
        "dt_bias": ((Hm,), "dt_bias"), "A_log": ((Hm,), "A_log"),
        "D": ((Hm,), "near_one"), "norm_scale": ((di,), "near_one"),
        "out_proj": ((di, D), (2 * di * L) ** -0.5 / m["m_ssm_out"]),
        "w_gate": ((D, F), fan / m["m_mlp"][0]),
        "w_up": ((D, F), fan),
        "w_down": ((F, D), (2 * F * L) ** -0.5 / m["m_mlp"][1]),
    }


def _leaf(key, name: str, shape, how, m: dict):
    k = jax.random.fold_in(key, _LEAF_IDS[name])
    if isinstance(how, str):
        if how == "near_one":   # a norm scale: near one, not all alike
            return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        if how == "dt_bias":    # steps log-uniform in the assumed range,
            step = jnp.exp(jax.random.uniform(   # through the softplus
                k, shape, jnp.float32, math.log(m["dt_min"]),
                math.log(m["dt_max"])))
            return step + jnp.log(-jnp.expm1(-step))
        assert how == "A_log"
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    return _normal(k, shape, how)


def layer(key, m: dict, index, names=None) -> dict:
    """Layer ``index``'s leaves (or those of ``names``), float32.
    ``index`` may be traced."""
    kl = jax.random.fold_in(key, 1000 + index)
    return {n: _leaf(kl, n, sh, how, m)
            for n, (sh, how) in _layer_spec(m).items()
            if names is None or n in names}


def vocab_blocks(m: dict) -> int:
    return m["V"] // m["Vb"]


def embed_block(key, m: dict, b):
    """Rows ``b Vb .. (b + 1) Vb`` of the embedding, float32."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_IDS["embed"]), b)
    return _normal(k, (m["Vb"], m["D"]), 1.0 / m["m_embed"])


def head_block(key, m: dict, b):
    """Columns ``b Vb .. (b + 1) Vb`` of the head, float32."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_IDS["lm_head"]), b)
    return _normal(k, (m["D"], m["Vb"]), m["D"] ** -0.5 / m["m_head"])


def final_norm(key, m: dict):
    return _leaf(key, "ln_f_scale", (m["D"],), "near_one", m)


def build(key, m: dict, dtype):
    """The tree in the program's layout (``layers``: a tuple of
    per-layer dicts), every leaf rounded to ``dtype`` as it is made but
    ``FLOAT32_LEAVES``. Trace under one ``jax.jit``: the float32 scratch
    is one leaf's, or one block's of the vocabulary."""
    cast = lambda n, a: a if n in FLOAT32_LEAVES else a.astype(dtype)
    layers = tuple({n: cast(n, a) for n, a in layer(key, m, i).items()}
                   for i in range(m["L"]))
    nb = vocab_blocks(m)
    embed = lax.map(lambda b: embed_block(key, m, b).astype(dtype),
                    jnp.arange(nb)).reshape(m["V"], m["D"])
    head = lax.fori_loop(   # each block written into its columns, in place
        0, nb, lambda b, buf: lax.dynamic_update_slice(
            buf, head_block(key, m, b).astype(dtype), (0, b * m["Vb"])),
        jnp.zeros((m["D"], m["V"]), dtype))
    return {"embed": embed, "ln_f_scale": final_norm(key, m).astype(dtype),
            "lm_head": head, "layers": layers}
