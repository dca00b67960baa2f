"""The plain reference of the ``falcon_h1`` block, float32, no kernels, no
cache, no batching. Every layer runs attention AND a Mamba-2 mixer off one
norm and adds both to the residual, then a SiLU-gated MLP (``m_*``: the
configuration's multipliers, applied where the published forward applies
them, as recalled; the configuration file's ``assumed`` says so):

    x  = embed[token] m_embed
    h  = RMSNorm(x)
    q, k, v = (h m_attn_in) Wqkv;  k <- k m_key;  rope(q), rope(k)
    a  = causal GQA softmax(q k^T / sqrt(Dh)) v  Wo  m_attn_out
    [z | x' | B | C | dt] = ((h m_ssm_in) W_in) * [m_ssm by segment]
    [x' | B | C] <- silu(causal depthwise conv_K([x' | B | C]) + b)
    dt <- softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x'_t (x) B_t;  y_t = S_t C_t + D x'_t
    m  = GroupRMSNorm(y silu(z)) W_out m_ssm_out          (gate before norm)
    x  = x + a + m
    h2 = RMSNorm(x)
    x  = x + (silu((h2 W_gate) m_mlp[0]) (h2 W_up)) W_down m_mlp[1]
    logits = (RMSNorm(x) W_head) m_head

The recurrence is the SEQUENTIAL one (a ``lax.scan`` over positions; the
program's prefill uses the chunked form), a head uses its group's B and C.
Straight ``jax.numpy`` at ``Precision.HIGHEST``, weights from
``chipbench.weights_falcon_h1`` a layer (a block of the vocabulary) at a
time. Imports nothing of the program. ``lowp`` names the control's
precision: the same mathematics with every matmul's two operands rounded
to that type first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import weights_falcon_h1 as weights
from chipbench.reference.transformer import attention, mm, rope


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def attn(h, lw, m, lowp=None):
    """The attention half on one sequence: h (T, D) normed -> (T, D)."""
    T = h.shape[0]
    H, Hkv, Dh = m["H"], m["Hkv"], m["Dh"]
    qkv = mm(h * m["m_attn_in"], lw["wqkv"], lowp)
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    pos = jnp.arange(T)
    q = rope(q.reshape(T, H, Dh), pos, m["theta"])
    k = rope((k * m["m_key"]).reshape(T, Hkv, Dh), pos, m["theta"])
    o = attention(q, k, v.reshape(T, Hkv, Dh))
    return mm(o, lw["wo"], lowp) * m["m_attn_out"]


def mamba(h, lw, m, lowp=None):
    """The Mamba-2 half on one sequence: h (T, D) normed -> (T, D)."""
    T = h.shape[0]
    Hm, P, G, N, K = m["Hm"], m["P"], m["G"], m["N"], m["K"]
    di, bc = m["d_inner"], m["G"] * m["N"]
    zxbcdt = mm(h * m["m_ssm_in"], lw["in_proj"], lowp)
    z, x, B, C, dt = jnp.split(
        zxbcdt, np.cumsum([di, di, bc, bc]).tolist(), axis=-1)
    mz, mx, mB, mC, mdt = m["m_ssm"]
    z, dt = z * mz, dt * mdt
    xbc = jnp.concatenate([x * mx, B * mB, C * mC], axis=-1)
    xp = jnp.pad(xbc, [(K - 1, 0), (0, 0)])
    conv = lw["conv_b"] + sum(xp[k:k + T] * lw["conv_w"][k] for k in range(K))
    x, B, C = jnp.split(jax.nn.silu(conv), [di, di + bc], axis=-1)
    x = x.reshape(T, Hm, P)
    B = jnp.repeat(B.reshape(T, G, N), Hm // G, axis=1)      # (T, Hm, N)
    C = jnp.repeat(C.reshape(T, G, N), Hm // G, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                 # (T, Hm)
    A = -jnp.exp(lw["A_log"])

    def step(S, t):
        x_t, B_t, C_t, dt_t = t
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.sum(S * C_t[:, None, :], axis=-1)

    _, y = lax.scan(step, jnp.zeros((Hm, P, N), jnp.float32), (x, B, C, dt))
    y = (y + lw["D"][:, None] * x).reshape(T, di)
    g = (y * jax.nn.silu(z)).reshape(T, G, di // G)
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + m["eps"])
    return (mm(g.reshape(T, di) * lw["norm_scale"], lw["out_proj"], lowp)
            * m["m_ssm_out"])


def gated_mlp(h, lw, m, lowp=None):
    gate = mm(h, lw["w_gate"], lowp) * m["m_mlp"][0]
    return (mm(jax.nn.silu(gate) * mm(h, lw["w_up"], lowp), lw["w_down"],
               lowp) * m["m_mlp"][1])


def block(x, lw, m, lowp=None, halves=("attn", "mamba")):
    """One layer on one sequence: x (T, D) float32. ``halves``: a test
    leaves one mixer out of the sum."""
    h = rmsnorm(x, lw["ln1_scale"], m["eps"])
    if "attn" in halves:
        x = x + attn(h, lw, m, lowp)
    if "mamba" in halves:
        x = x + mamba(h, lw, m, lowp)
    return x + gated_mlp(rmsnorm(x, lw["ln2_scale"], m["eps"]), lw, m, lowp)


def _freeze(m: dict):
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _layer_step(key, index, x, *, m, lowp):
    """One layer; ``index`` is traced, so one program serves every layer
    at a length."""
    m = dict(m)
    return block(x, weights.layer(key, m, index), m, lowp)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, *, m):
    """The tokens' rows, a block of the vocabulary at a time."""
    m = dict(m)

    def add(x, b):
        at = tokens - b * m["Vb"]
        inside = (at >= 0) & (at < m["Vb"])
        rows = weights.embed_block(key, m, b)[jnp.clip(at, 0, m["Vb"] - 1)]
        return x + jnp.where(inside[:, None], rows, 0.0), None

    x, _ = lax.scan(add, jnp.zeros((tokens.shape[0], m["D"]), jnp.float32),
                    jnp.arange(weights.vocab_blocks(m)))
    return x * m["m_embed"]


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _head(key, x_rows, *, m, lowp):
    m = dict(m)
    xn = rmsnorm(x_rows, weights.final_norm(key, m), m["eps"])
    blocks = lax.map(lambda b: mm(xn, weights.head_block(key, m, b), lowp),
                     jnp.arange(weights.vocab_blocks(m)))    # (nb, R, Vb)
    return (jnp.moveaxis(blocks, 0, 1).reshape(x_rows.shape[0], m["V"])
            * m["m_head"])


def logits_at(seed: int, m: dict, sequences, rows, *, lowp=None,
              pad_to=512):
    """Float32 logits of each sequence at its ``rows`` (positions). Every
    sequence is right-padded to ONE length, the longest's next multiple
    of ``pad_to`` (both mixers are causal, so the pad changes no row asked
    for): one program a step, whatever the lengths sampled. Layers
    outermost."""
    key = weights.seed_key(seed)
    fm = _freeze(m)
    n = -(-max(len(s) for s in sequences) // pad_to) * pad_to
    xs = [_embed(key, jnp.zeros((n,), jnp.int32).at[:len(s)].set(
        jnp.asarray(s)), m=fm) for s in sequences]
    for l in range(m["L"]):
        xs = [_layer_step(key, jnp.int32(l), x, m=fm, lowp=lowp) for x in xs]
    # the head too: every sequence's rows padded to one count (its last
    # row again), the pad cut off the result
    k = -(-max(len(r) for r in rows) // 64) * 64
    return [_head(key, x[np.pad(np.asarray(r), (0, k - len(r)), "edge")],
                  m=fm, lowp=lowp)[:len(r)] for x, r in zip(xs, rows)]
