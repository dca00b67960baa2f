"""The plain reference of the tree's one block, float32, no kernels.

RMSNorm -> packed qkv (no bias) -> rope (rotate-half) -> causal GQA
softmax attention -> output projection -> residual -> RMSNorm ->
tanh-GELU MLP (no bias) -> residual; final RMSNorm; untied head. Straight
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, weights
from ``chipbench.weights`` one layer at a time. Imports nothing of the
program. ``lowp`` names the control's precision: the same mathematics
with every matmul's two operands rounded to that type first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import weights

EPS = 1e-6


def _round_operand(a, lowp: str, axis: int):
    """Round to the control's precision: a symmetric scale per row
    (``axis`` is the contraction axis) and int8 / fp8 values. The
    gradient passes straight through the rounding."""
    if lowp == "bfloat16":
        q = a.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
        if lowp == "int8":
            s = jnp.maximum(amax, 1e-12) / 127.0
            q = jnp.clip(jnp.round(a / s), -127, 127) * s
        elif lowp == "fp8":
            s = jnp.maximum(amax, 1e-12) / 448.0
            q = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        else:
            raise ValueError(f"control precision {lowp!r}")
    return a + lax.stop_gradient(q - a)


def mm(a, w, lowp):
    """a (..., K) @ w (K, N) in float32 at ``highest``."""
    if lowp:
        a = _round_operand(a, lowp, -1)
        w = _round_operand(w, lowp, 0)
    return jnp.matmul(a, w, precision=lax.Precision.HIGHEST)


def rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + EPS) * scale


def rope(x, positions, theta):
    """x (T, heads, Dh): rotate the (first half, second half) pairs."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v):
    """Causal GQA: q (T, H, Dh), k/v (T, Hkv, Dh) -> (T, H*Dh). One head
    at a time, so one head's (T, T) scores are what is live; under a
    gradient each head is recomputed, not kept."""
    T, H, Dh = q.shape
    g = H // k.shape[1]
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def one(h):
        qh, kh, vh = q[:, h], k[:, h // g], v[:, h // g]
        s = jnp.matmul(qh, kh.T, precision=lax.Precision.HIGHEST) / (Dh ** 0.5)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, vh, precision=lax.Precision.HIGHEST)

    o = lax.map(one, jnp.arange(H))                      # (H, T, Dh)
    return o.transpose(1, 0, 2).reshape(T, H * Dh)


def block(x, lw, m, lowp=None):
    """One layer on one sequence: x (T, D) float32."""
    T = x.shape[0]
    D, H, Hkv, Dh = m["D"], m["H"], m["Hkv"], m["Dh"]
    h = rmsnorm(x, lw["ln1_scale"])
    qkv = mm(h, lw["wqkv"], lowp)
    q, k, v = jnp.split(qkv, [D, D + Hkv * Dh], axis=-1)
    pos = jnp.arange(T)
    q = rope(q.reshape(T, H, Dh), pos, m["theta"])
    k = rope(k.reshape(T, Hkv, Dh), pos, m["theta"])
    o = attention(q, k, v.reshape(T, Hkv, Dh))
    x = x + mm(o, lw["wo"], lowp)
    h = rmsnorm(x, lw["ln2_scale"])
    h = jax.nn.gelu(mm(h, lw["w1"], lowp), approximate=True)
    return x + mm(h, lw["w2"], lowp)


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _layer_step(key, index, x, *, m, lowp):
    return block(x, weights.layer(key, dict(m), index), dict(m), lowp)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, *, m):
    return weights.top(key, dict(m), ("embed",))["embed"][tokens]


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _head(key, x_rows, *, m, lowp):
    t = weights.top(key, dict(m), ("ln_f_scale", "lm_head"))
    return mm(rmsnorm(x_rows, t["ln_f_scale"]), t["lm_head"], lowp)


def _freeze(m: dict):
    return tuple(sorted(m.items()))


def logits_at(seed: int, m: dict, sequences, rows, *, lowp=None,
              pad_to=512):
    """Float32 logits of each sequence at its ``rows`` (positions).
    ``sequences``: 1-D int token arrays; each is right-padded to a
    multiple of ``pad_to`` (causal, so the pad changes no row asked for)
    to bound the shapes compiled. Layers outermost: each layer's weights
    are made once and serve every sequence."""
    key = weights.seed_key(seed)
    fm = _freeze(m)
    xs = []
    for s in sequences:
        n = -(-len(s) // pad_to) * pad_to
        tok = jnp.zeros((n,), jnp.int32).at[:len(s)].set(jnp.asarray(s))
        xs.append(_embed(key, tok, m=fm))
    for l in range(m["L"]):
        xs = [_layer_step(key, l, x, m=fm, lowp=lowp) for x in xs]
    return [_head(key, x[jnp.asarray(r)], m=fm, lowp=lowp)
            for x, r in zip(xs, rows)]
