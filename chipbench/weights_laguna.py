"""Weights from a seed for the ``laguna`` layers (global and window
attention with their own head counts and rotations and a per-head output
gate; the leading dense SiLU-gated MLP; a softmax top-10 of 256 SiLU-gated
experts with a shared expert), for the driver and the reference.

As ``chipbench.weights``: every leaf is a pure function of ``(seed, leaf
name, layer index)``; an expert's three matrices of ``(..., expert
index)``, the expert's index over ALL the layer's experts, so the other
chip's share is made alike; the embedding and the head of ``(..., block of
the vocabulary)``. The driver makes a layer at a time in bfloat16 and the
reference never holds a layer's experts (2.4 GB) whole in float32. Imports
nothing of the program.

Scales (the configuration's ``assumed`` lists them): the embedding at 1,
matrices that read the hidden state at fan-in^-0.5 (a head's scores, the
gates' and the router's logits of order one), output projections over
``sqrt(2 x fan-in x layers)``, norm scales near one, not all alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.weights import seed_key  # noqa: F401  (the one seed rule)

_LEAF_IDS = {n: i for i, n in enumerate((
    "embed", "lm_head", "ln_f_scale", "ln1_scale", "ln2_scale", "wqkv",
    "wo", "attn_gate", "w_gate", "w_up", "w_down", "router", "ws_gate",
    "ws_up", "ws_down"))}

#: the leaves with a leading expert axis in a routed layer
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")

#: leaves the program computes with in float32 whatever its compute dtype
FLOAT32_LEAVES = ("router",)

#: blocks the embedding's rows (the head's columns) are made in
VOCAB_BLOCKS = 8


def pattern(config: dict) -> str:
    """One character a layer of the first ``num_hidden_layers``: ``D``
    global attention and the dense MLP, ``W`` window attention and the
    routed experts, ``R`` global attention and the routed experts."""
    L = config["num_hidden_layers"]
    kinds = {("full_attention", "dense"): "D",
             ("sliding_attention", "sparse"): "W",
             ("full_attention", "sparse"): "R"}
    return "".join(kinds[(a, f)] for a, f in zip(
        config["layer_types"][:L], config["mlp_layer_types"][:L]))


def model_dims(config: dict) -> dict:
    """The sizes from a configuration file's published keys (hashable: the
    reference freezes it). ``held`` experts of ``E`` (the published count)
    live here, from ``held0`` on; ``V`` rows of the vocabulary."""
    pat = pattern(config)
    L = config["num_hidden_layers"]
    heads = config["num_attention_heads_per_layer"][:L]
    glob = config["rope_parameters"]["full_attention"]
    win = config["rope_parameters"]["sliding_attention"]
    Dh = config["head_dim"]
    Hw = {h for h, c in zip(heads, pat) if c == "W"}
    assert {h for h, c in zip(heads, pat) if c != "W"} == {
        config["num_attention_heads"]} and len(Hw) == 1
    assert win["partial_rotary_factor"] == 1 and win["rope_type"] == "default"
    assert glob["rope_type"] == "yarn" and config["gating"] == "per-head"
    assert config["mlp_only_layers"] == [0] and pat[0] == "D"
    V = config["vocab_size"]
    return {
        "D": config["hidden_size"], "H": config["num_attention_heads"],
        "Hw": Hw.pop(), "Hkv": config["num_key_value_heads"], "Dh": Dh,
        "L": L, "V": V, "pattern": pat,
        "Vb": V // VOCAB_BLOCKS if V % VOCAB_BLOCKS == 0 else V,
        "E": config["published"]["num_experts"],
        "held": config["num_experts"],
        "held0": config.get("experts_held_from", 0),
        "k": config["num_experts_per_tok"],
        "F": config["moe_intermediate_size"],
        "Fs": config["shared_expert_intermediate_size"],
        "Fd": config["intermediate_size"],
        "scale": float(config["moe_routed_scaling_factor"]),
        "renorm": bool(config["norm_topk_prob"]),
        "eps": float(config["rms_norm_eps"]),
        "window": int(config["sliding_window"]),
        # (theta, rotated dims, yarn (factor, original positions,
        # beta_fast, beta_slow, attention_factor))
        "rope_g": (float(glob["rope_theta"]),
                   int(Dh * glob["partial_rotary_factor"]),
                   (float(glob["factor"]),
                    int(glob["original_max_position_embeddings"]),
                    float(glob["beta_fast"]), float(glob["beta_slow"]),
                    float(glob["attention_factor"]))),
        "rope_w": (float(win["rope_theta"]), Dh, ()),
    }


def heads(m: dict, kind: str) -> int:
    return m["Hw"] if kind == "W" else m["H"]


def _normal(k, shape, scale):
    return jax.random.normal(k, shape, jnp.float32) * scale


def _layer_spec(m: dict, kind: str) -> dict:
    """leaf -> (shape, scale); a negative scale: a norm scale, near its
    absolute value. An expert leaf's shape is ONE expert's."""
    D, L, Dh = m["D"], m["L"], m["Dh"]
    W = heads(m, kind) * Dh
    fan = D ** -0.5
    spec = {
        "ln1_scale": ((D,), -1.0), "ln2_scale": ((D,), -1.0),
        "wqkv": ((D, W + 2 * m["Hkv"] * Dh), fan),
        "wo": ((W, D), (2 * W * L) ** -0.5),
        "attn_gate": ((D, heads(m, kind)), fan),
    }
    F = m["Fd"] if kind == "D" else m["F"]
    spec.update({"w_gate": ((D, F), fan), "w_up": ((D, F), fan),
                 "w_down": ((F, D), (2 * F * L) ** -0.5)})
    if kind != "D":
        Fs = m["Fs"]
        spec.update({"router": ((D, m["E"]), fan),
                     "ws_gate": ((D, Fs), fan), "ws_up": ((D, Fs), fan),
                     "ws_down": ((Fs, D), (2 * Fs * L) ** -0.5)})
    return spec


def _leaf(key, name: str, shape, scale):
    k = jax.random.fold_in(key, _LEAF_IDS[name])
    if scale < 0:
        return -scale * (1.0 + 0.1 * jax.random.normal(k, shape,
                                                       jnp.float32))
    return _normal(k, shape, scale)


def _layer_key(key, index):
    return jax.random.fold_in(key, 1000 + index)


def expert(key, m: dict, index, e) -> dict:
    """Expert ``e`` (of all ``E``) of routed layer ``index``: its three
    matrices, float32. Both indices may be traced."""
    spec = _layer_spec(m, "W")
    kl = _layer_key(key, index)
    return {n: _normal(jax.random.fold_in(
        jax.random.fold_in(kl, _LEAF_IDS[n]), e), *spec[n])
        for n in EXPERT_LEAVES}


def layer(key, m: dict, index, kind: str) -> dict:
    """Layer ``index``'s leaves WITHOUT its experts, float32 (a dense
    layer's MLP is no expert). ``index`` may be traced."""
    kl = _layer_key(key, index)
    return {n: _leaf(kl, n, sh, sc)
            for n, (sh, sc) in _layer_spec(m, kind).items()
            if kind == "D" or n not in EXPERT_LEAVES}


def vocab_blocks(m: dict) -> int:
    return m["V"] // m["Vb"]


def embed_block(key, m: dict, b):
    """Rows ``b Vb .. (b + 1) Vb`` of the embedding, float32."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_IDS["embed"]), b)
    return _normal(k, (m["Vb"], m["D"]), 1.0)


def head_block(key, m: dict, b):
    """Columns ``b Vb .. (b + 1) Vb`` of the head, float32."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_IDS["lm_head"]), b)
    return _normal(k, (m["D"], m["Vb"]), m["D"] ** -0.5)


def final_norm(key, m: dict):
    return _leaf(key, "ln_f_scale", (m["D"],), -1.0)


def build_layer(key, m: dict, index: int, dtype):
    """Layer ``index`` in the program's layout, its held experts stacked
    on a leading axis, every leaf rounded to ``dtype`` as it is made but
    ``FLOAT32_LEAVES``. Trace under ``jax.jit``: the float32 scratch is
    one expert's or one leaf's."""
    kind = m["pattern"][index]
    lw = {n: a if n in FLOAT32_LEAVES else a.astype(dtype)
          for n, a in layer(key, m, index, kind).items()}
    if kind != "D":
        lw.update(lax.map(
            lambda e: {n: a.astype(dtype)
                       for n, a in expert(key, m, index, e).items()},
            m["held0"] + jnp.arange(m["held"])))
    return lw


def build_top(key, m: dict, dtype):
    """The embedding, the final norm and the head, made a block of the
    vocabulary at a time."""
    nb = vocab_blocks(m)
    embed = lax.map(lambda b: embed_block(key, m, b).astype(dtype),
                    jnp.arange(nb)).reshape(m["V"], m["D"])
    head = lax.fori_loop(   # each block written into its columns, in place
        0, nb, lambda b, buf: lax.dynamic_update_slice(
            buf, head_block(key, m, b).astype(dtype), (0, b * m["Vb"])),
        jnp.zeros((m["D"], m["V"]), dtype))
    return {"embed": embed, "ln_f_scale": final_norm(key, m).astype(dtype),
            "lm_head": head}


def build(key, m: dict, dtype):
    """The whole tree (``layers``: a tuple of per-layer dicts)."""
    return {**build_top(key, m, dtype),
            "layers": tuple(build_layer(key, m, i, dtype)
                            for i in range(m["L"]))}
