"""The plain reference of the ``laguna`` layers, float32, no kernels, no
cache, no batching. Each layer is attention under a pre-norm residual, then
an MLP under its own pre-norm residual, ``t`` (the layer's kind) read from
the pattern:

- attention: ``h = RMSNorm(x)``; ``[q | k | v] = h W_qkv`` with the kind's
  query heads (``D``, ``R`` global: 48; ``W`` window: 72) and 8 K/V heads
  of 128; the rotation (global: the first 64 dims of a head in
  (first half, second half) pairs at theta 500,000 under YaRN, cos and sin
  times the attention factor, the other 64 passed; window: all 128 at
  theta 10,000); softmax(q k^T / sqrt(128)) over the keys a position sees
  (global: j <= i; window: i - 512 < j <= i, an explicit mask); each
  head's output times ``sigmoid(h W_g)`` of that head; ``x <- x + o W_o``.
- MLP, ``h = RMSNorm(x)``: ``D`` the SiLU-gated MLP,
  ``(silu(h W_gate) * (h W_up)) W_down``; ``W`` and ``R``: ``p = softmax(h
  W_r)`` over all 256 experts, the 10 largest chosen, their gates
  renormalised to one and times 2.5; ``r = sum_k g_k Wd_k (silu(Wg_k h) *
  (Wu_k h))`` over the chosen experts HELD HERE only, as a loop over the
  held experts each on every token, masked by its gate (0 where it was not
  chosen); plus the shared expert's SiLU-gated MLP.

Final RMSNorm, untied head over the vocabulary's held rows. Straight
``jax.numpy`` at ``Precision.HIGHEST``, the weights from
``chipbench.weights_laguna`` rounded to bfloat16 as the driver rounds them
and taken up to float32 a layer (an expert, a block of the vocabulary) at
a time: the float32 tree (22 GB) never exists. Imports nothing of the
program. ``lowp`` names the control's precision: the same mathematics with
every matmul's two operands rounded to that type first.

Departures from the published description (the configuration lists them):
what the experts held on the deployment's other chip would add to ``r`` is
left out, in the program alike; the head is the vocabulary's held rows.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import weights_laguna as weights
from chipbench.reference.transformer import mm

HI = lax.Precision.HIGHEST


def as_served(a):
    """A weight as the driver holds it: rounded to bfloat16, read back in
    float32."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def frequencies(rope):
    """(inv_freq, factor of cos and sin) of ``(theta, dims, yarn)``, the
    equations of transformers' ``_compute_yarn_parameters``."""
    theta, dims, yarn = rope
    inv = 1.0 / theta ** (np.arange(0, dims, 2) / dims)
    if not yarn:
        return inv, 1.0
    factor, orig, beta_fast, beta_slow, attention_factor = yarn

    def corr(rot):
        return dims * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dims - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dims // 2) - low) / (high - low), 0, 1)
    extrapolation = 1 - ramp
    inv = (inv / factor) * (1 - extrapolation) + inv * extrapolation
    return inv, attention_factor


def rope(x, rot):
    """x (T, heads, Dh): the first ``dims`` rotated in (first half, second
    half) pairs, the rest passed."""
    inv, scale = frequencies(rot)
    dims = rot[1]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    half = dims // 2
    x1, x2 = x[..., :half], x[..., half:dims]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., dims:]], axis=-1)


def attention(q, k, v, window: int):
    """GQA over q (T, H, Dh), k/v (T, Hkv, Dh) -> (T, H, Dh): causal, and
    with ``window`` > 0 the keys of the last ``window`` positions alone.
    One head at a time."""
    T, H, Dh = q.shape
    g = H // k.shape[1]
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    mask = j <= i
    if window:
        mask = mask & (j > i - window)

    def one(h):
        qh, kh, vh = q[:, h], k[:, h // g], v[:, h // g]
        s = jnp.matmul(qh, kh.T, precision=HI) / (Dh ** 0.5)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, vh, precision=HI)

    return lax.map(one, jnp.arange(H)).transpose(1, 0, 2)


def attn(x, lw, m, kind, lowp=None):
    """The attention half of a layer on one sequence: x (T, D) -> what it
    adds to the residual."""
    T = x.shape[0]
    H, Hkv, Dh = weights.heads(m, kind), m["Hkv"], m["Dh"]
    h = rmsnorm(x, lw["ln1_scale"], m["eps"])
    qkv = mm(h, lw["wqkv"], lowp)
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    rot = m["rope_w"] if kind == "W" else m["rope_g"]
    q = rope(q.reshape(T, H, Dh), rot)
    k = rope(k.reshape(T, Hkv, Dh), rot)
    o = attention(q, k, v.reshape(T, Hkv, Dh),
                  m["window"] if kind == "W" else 0)
    o = o * jax.nn.sigmoid(mm(h, lw["attn_gate"], lowp))[..., None]
    return mm(o.reshape(T, H * Dh), lw["wo"], lowp)


def gated(h, w_gate, w_up, w_down, lowp=None):
    return mm(silu(mm(h, w_gate, lowp)) * mm(h, w_up, lowp), w_down, lowp)


def route(h, lw, m, lowp=None):
    """(idx (T, k) over ALL experts, gates (T, k))."""
    p = jax.nn.softmax(mm(h, lw["router"], lowp), axis=-1)
    g, idx = lax.top_k(p, m["k"])
    if m["renorm"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return idx, m["scale"] * g


def routed(h, idx, gates, expert_fn, first: int, count: int, lowp=None):
    """What experts ``first .. first + count`` give: a loop over them,
    each on EVERY token, masked by its gate (0 where it was not chosen).
    ``expert_fn(e) -> {w_gate, w_up, w_down}``."""
    def one(r, e):
        w = expert_fn(e)
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        return r + gate[:, None] * gated(h, w["w_gate"], w["w_up"],
                                         w["w_down"], lowp), None

    r, _ = lax.scan(one, jnp.zeros_like(h), first + jnp.arange(count))
    return r


def mlp(x, lw, expert_fn, m, kind, lowp=None, *, first=None, count=None,
        shared=True):
    """The MLP half on one sequence: x (T, D) -> what it adds. A routed
    layer on the share ``first .. first + count`` of the experts (default:
    the configuration's held share), with the shared expert where
    ``shared``."""
    h = rmsnorm(x, lw["ln2_scale"], m["eps"])
    if kind == "D":
        return gated(h, lw["w_gate"], lw["w_up"], lw["w_down"], lowp)
    first = m["held0"] if first is None else first
    count = m["held"] if count is None else count
    idx, gates = route(h, lw, m, lowp)
    out = routed(h, idx, gates, expert_fn, first, count, lowp)
    if shared:
        out = out + gated(h, lw["ws_gate"], lw["ws_up"], lw["ws_down"], lowp)
    return out


def _freeze(m: dict):
    return tuple(sorted(m.items()))


def served_layer(key, m, index, kind):
    return {n: a if n in weights.FLOAT32_LEAVES else as_served(a)
            for n, a in weights.layer(key, m, index, kind).items()}


def served_expert(key, m, index, e):
    return {n: as_served(a)
            for n, a in weights.expert(key, m, index, e).items()}


@functools.partial(jax.jit, static_argnames=("kind", "m", "lowp"))
def _layer_step(key, index, x, *, kind, m, lowp):
    """One layer of ``kind``; ``index`` is traced, so one program serves
    every layer of a kind at a length."""
    m = dict(m)
    lw = served_layer(key, m, index, kind)
    x = x + attn(x, lw, m, kind, lowp)
    return x + mlp(x, lw, lambda e: served_expert(key, m, index, e), m,
                   kind, lowp)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, *, m):
    m = dict(m)
    blocks = lax.map(lambda b: as_served(weights.embed_block(key, m, b)),
                     jnp.arange(weights.vocab_blocks(m)))
    return blocks.reshape(m["V"], m["D"])[tokens]


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _head(key, x_rows, *, m, lowp):
    """The logits over the held vocabulary, a block of its rows at a
    time."""
    m = dict(m)
    h = rmsnorm(x_rows, as_served(weights.final_norm(key, m)), m["eps"])
    z = lax.map(lambda b: mm(h, as_served(weights.head_block(key, m, b)),
                             lowp), jnp.arange(weights.vocab_blocks(m)))
    return z.transpose(1, 0, 2).reshape(x_rows.shape[0], m["V"])


def logits_at(seed: int, m: dict, sequences, rows, *, lowp=None,
              pad_to=512):
    """Float32 logits of each sequence at its ``rows`` (positions). Every
    sequence is right-padded to ONE length, the longest's next multiple of
    ``pad_to`` (every layer is causal, so the pad changes no row asked
    for): a program a layer kind. Layers outermost."""
    key = weights.seed_key(seed)
    fm = _freeze(m)
    n = -(-max(len(s) for s in sequences) // pad_to) * pad_to
    xs = [_embed(key, jnp.zeros((n,), jnp.int32).at[:len(s)].set(
        jnp.asarray(s)), m=fm) for s in sequences]
    for l, kind in enumerate(m["pattern"]):
        xs = [_layer_step(key, jnp.int32(l), x, kind=kind, m=fm, lowp=lowp)
              for x in xs]
    k = -(-max(len(r) for r in rows) // 64) * 64
    return [_head(key, x[np.pad(np.asarray(r), (0, k - len(r)), "edge")],
                  m=fm, lowp=lowp)[:len(r)] for x, r in zip(xs, rows)]
