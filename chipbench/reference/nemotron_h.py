"""The plain reference of the ``nemotron_h`` pattern, float32, no kernels,
no cache, no batching. Each layer is ONE mixer under a pre-norm residual,
``x <- x + Mixer_t(RMSNorm(x))``, ``t`` read from the pattern:

- ``M``, Mamba-2, as the SEQUENTIAL recurrence (a ``lax.scan`` over
  positions; the program's prefill uses the chunked form):
  ``[z | xBC | dt] = h W_in``; ``xBC <- silu(causal depthwise
  conv1d_K(xBC) + b)`` = ``[x | B | C]``; ``dt <- softplus(dt +
  dt_bias)``; ``A = -exp(A_log)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  (x) B_t``; ``y_t = S_t C_t + D x_t`` (a head uses its group's B, C);
  ``y <- GroupRMSNorm(y silu(z))`` (gate before norm); ``out = y W_out``.
- ``*``, causal GQA attention, no bias, NO positional rotation.
- ``E``, LatentMoE on a share of the experts: ``s = sigmoid(h W_r)``; the
  k chosen are the top of ``s + b``; ``g = scale s[chosen] / sum
  s[chosen]``; ``u = h W_down``; ``r = sum_k g_k W2_k relu2(W1_k u)`` over
  the chosen experts HELD HERE only, as a loop over the held experts with
  a mask; ``out = r W_up + Ws2 relu2(Ws1 h)``.

Final RMSNorm, untied head over the vocabulary slice. Straight
``jax.numpy`` at ``Precision.HIGHEST``, weights from
``chipbench.weights_nemotron_h`` a layer (an expert) at a time. Imports
nothing of the program. ``lowp`` names the control's precision: the same
mathematics with every matmul's two operands rounded to that type first.

Departures from the published description: the multi-token-prediction
module is left out (the published greedy forward does not run it); what
the experts held on the deployment's other chips would add to ``r`` is
left out, in the program alike; the head is the vocabulary's slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import weights_nemotron_h as weights
from chipbench.reference.transformer import attention, mm

HI = lax.Precision.HIGHEST


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def mamba(h, lw, m, lowp=None):
    """The mixer on one sequence: h (T, D) normed -> (T, D)."""
    T = h.shape[0]
    Hm, P, G, N, K = m["Hm"], m["P"], m["G"], m["N"], m["K"]
    di = m["d_inner"]
    zxbcdt = mm(h, lw["in_proj"], lowp)
    z, xbc, dt = jnp.split(zxbcdt, [di, di + m["conv_dim"]], axis=-1)
    xp = jnp.pad(xbc, [(K - 1, 0), (0, 0)])
    conv = lw["conv_b"] + sum(xp[k:k + T] * lw["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(conv)
    x, B, C = jnp.split(xbc, [di, di + G * N], axis=-1)
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
    return mm(g.reshape(T, di) * lw["norm_scale"], lw["out_proj"], lowp)


def attn(h, lw, m, lowp=None):
    T = h.shape[0]
    H, Hkv, Dh = m["H"], m["Hkv"], m["Dh"]
    qkv = mm(h, lw["wqkv"], lowp)
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    o = attention(q.reshape(T, H, Dh), k.reshape(T, Hkv, Dh),
                  v.reshape(T, Hkv, Dh))
    return mm(o, lw["wo"], lowp)


def route(h, lw, m, lowp=None):
    """(idx (T, k) over ALL experts, gates (T, k))."""
    s = jax.nn.sigmoid(mm(h, lw["router"], lowp))
    _, idx = lax.top_k(s + lw["router_bias"], m["k"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    return idx, m["scale"] * g / jnp.sum(g, axis=-1, keepdims=True)


def routed(u, idx, gates, expert_fn, first: int, count: int, lowp=None):
    """What experts ``first .. first + count`` give: a loop over them,
    each on EVERY token, masked by its gate (0 where it was not chosen).
    ``expert_fn(e) -> (w1, w2)``."""
    def one(r, e):
        w1, w2 = expert_fn(e)
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        return r + gate[:, None] * mm(relu2(mm(u, w1, lowp)), w2, lowp), None

    r, _ = lax.scan(one, jnp.zeros_like(u), first + jnp.arange(count))
    return r


def moe(h, lw, expert_fn, m, lowp=None, first=None, count=None):
    """The layer on the share ``first .. first + count`` of the experts
    (default: the configuration's held share): h (T, D) normed -> (T, D)."""
    first = m["held0"] if first is None else first
    count = m["held"] if count is None else count
    idx, gates = route(h, lw, m, lowp)
    u = mm(h, lw["w_down"], lowp)
    r = routed(u, idx, gates, expert_fn, first, count, lowp)
    shared = mm(relu2(mm(h, lw["ws1"], lowp)), lw["ws2"], lowp)
    return mm(r, lw["w_up"], lowp) + shared


def mixer(kind, h, lw, expert_fn, m, lowp=None):
    if kind == "M":
        return mamba(h, lw, m, lowp)
    if kind == "*":
        return attn(h, lw, m, lowp)
    return moe(h, lw, expert_fn, m, lowp)


def _freeze(m: dict):
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnames=("kind", "m", "lowp"))
def _layer_step(key, index, x, *, kind, m, lowp):
    """One layer of ``kind``; ``index`` is traced, so one program serves
    every layer of a kind at a length."""
    m = dict(m)
    lw = weights.layer(key, m, index, kind)
    h = rmsnorm(x, lw["ln1_scale"], m["eps"])
    return x + mixer(kind, h, lw,
                     lambda e: weights.expert(key, m, index, e), m, lowp)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, *, m):
    return weights.top(key, dict(m), ("embed",))["embed"][tokens]


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _head(key, x_rows, *, m, lowp):
    m = dict(m)
    t = weights.top(key, m, ("ln_f_scale", "lm_head"))
    return mm(rmsnorm(x_rows, t["ln_f_scale"], m["eps"]), t["lm_head"], lowp)


def logits_at(seed: int, m: dict, sequences, rows, *, lowp=None,
              pad_to=512):
    """Float32 logits of each sequence at its ``rows`` (positions). Every
    sequence is right-padded to ONE length, the longest's next multiple
    of ``pad_to`` (every mixer is causal, so the pad changes no row asked
    for): a program a layer kind, whatever the lengths sampled (88 when
    each length and layer had its own, 14 minutes of compiling cold).
    Layers outermost."""
    key = weights.seed_key(seed)
    fm = _freeze(m)
    n = -(-max(len(s) for s in sequences) // pad_to) * pad_to
    xs = [_embed(key, jnp.zeros((n,), jnp.int32).at[:len(s)].set(
        jnp.asarray(s)), m=fm) for s in sequences]
    for l, kind in enumerate(m["pattern"]):
        xs = [_layer_step(key, jnp.int32(l), x, kind=kind, m=fm, lowp=lowp)
              for x in xs]
    # the head too: every sequence's rows padded to one count (its last
    # row again), the pad cut off the result
    k = -(-max(len(r) for r in rows) // 64) * 64
    return [_head(key, x[np.pad(np.asarray(r), (0, k - len(r)), "edge")],
                  m=fm, lowp=lowp)[:len(r)] for x, r in zip(xs, rows)]
