"""The Mamba-2 mixer of a patterned model's ``M`` layers and of the
Mamba half of its ``H`` layers (which brings the config's multipliers:
``h`` times ``ssm_in_multiplier``, the in-projection's five segments
``[z | x | B | C | dt]`` times ``ssm_multipliers``, ``out`` times
``ssm_out_multiplier``).

    [z | xBC | dt] = h W_in
    xBC  <- silu(causal depthwise conv1d_K(xBC) + b_conv);  xBC = [x | B | C]
    dt   <- softplus(dt + dt_bias);   A = -exp(A_log)  (a head)
    S_t  =  exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t
    y    <- GroupRMSNorm(y * silu(z));   out = y W_out

A head uses its group's ``B`` and ``C``. Two forms of the one recurrence:

- :func:`mamba_prefill`: the chunked (SSD) form over a whole prompt in
  plain matmuls (``cfg.ssm_chunk`` positions a chunk: a masked
  ``(C B^T) . L`` product inside a chunk, one state a chunk carried by a
  short scan). Under bucket padding ``dt`` is zero past ``last_pos``, so
  the state it returns is the one at the prompt's true last position, and
  the convolution tail is gathered there;
- :func:`mamba_step`: one token against the carried state. The pass over
  ``S`` is the Pallas kernel ``ssm_step`` (ops/ssm_step.py): it visits the
  rows that are active, in place, and no others.

The state ``S`` is held in float32 and every decay is computed in
float32; the matmul operands are the compute dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

import numpy as np

from hpc_patterns_tpu.models.transformer import matmul_weight, scaled
from hpc_patterns_tpu.ops.ssm_step import ssm_step


def ssm_dims(cfg) -> dict:
    """The mixer's derived sizes."""
    d_inner = cfg.ssm_heads * cfg.ssm_head_dim
    bc = cfg.ssm_groups * cfg.ssm_state
    return {"d_inner": d_inner, "bc": bc, "conv_dim": d_inner + 2 * bc,
            "proj": 2 * d_inner + 2 * bc + cfg.ssm_heads}


def init_state(cfg, batch: int) -> tuple:
    """One ``M`` layer's state for ``batch`` rows: the convolution's tail
    (the last K-1 inputs, compute dtype: they are matmul outputs, held as
    computed) and ``S``."""
    d = ssm_dims(cfg)
    return (jnp.zeros((batch, cfg.ssm_conv - 1, d["conv_dim"]),
                      jnp.dtype(cfg.dtype)),
            jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state), jnp.float32))


def _project(h, lp, cfg):
    d = ssm_dims(cfg)
    h = scaled(h, cfg.ssm_in_multiplier)
    zxbcdt = jnp.dot(h, matmul_weight(lp, "in_proj", h.dtype))
    if set(cfg.ssm_multipliers) != {1.0}:   # one each of [z | x | B | C | dt]
        zxbcdt = zxbcdt * jnp.asarray(np.repeat(
            cfg.ssm_multipliers,
            [d["d_inner"], d["d_inner"], d["bc"], d["bc"], cfg.ssm_heads]),
            zxbcdt.dtype)
    z, xbc, dt = jnp.split(
        zxbcdt, [d["d_inner"], d["d_inner"] + d["conv_dim"]], axis=-1)
    return z, xbc, dt


def _split_xbc(xbc, cfg):
    """Activated ``xBC`` (..., conv_dim) -> x (..., H, P), B and C
    (..., G, N)."""
    d = ssm_dims(cfg)
    x, b, c = jnp.split(xbc, [d["d_inner"], d["d_inner"] + d["bc"]], axis=-1)
    lead = xbc.shape[:-1]
    return (x.reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim),
            b.reshape(*lead, cfg.ssm_groups, cfg.ssm_state),
            c.reshape(*lead, cfg.ssm_groups, cfg.ssm_state))


def _step_sizes(dt, lp):
    """softplus(dt + dt_bias) and A, float32."""
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + lp["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(lp["A_log"].astype(jnp.float32))


def _gated_norm(y, z, lp, cfg):
    """GroupRMSNorm(y * silu(z)): the gate before the norm, one variance
    a group of d_inner / G channels."""
    dt = z.dtype
    g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    lead = g.shape[:-1]
    gg = g.reshape(*lead, cfg.ssm_groups, -1)
    var = jnp.mean(jnp.square(gg), axis=-1, keepdims=True)
    gg = gg * lax.rsqrt(var + cfg.norm_eps)
    return (gg.reshape(*lead, -1)
            * lp["norm_scale"].astype(jnp.float32)).astype(dt)


def ssd_chunked(x, dt, A, B, C, chunk: int, S0=None):
    """The recurrence over a whole sequence, a chunk at a time.

    x (b, T, H, P) and B, C (b, T, G, N) in the matmul dtype, dt (b, T, H)
    and A (H,) float32. Returns y (b, T, H, P) float32 (without the
    ``D x`` skip) and the state after position T-1, float32 (b, H, P, N).
    Positions with dt == 0 leave the state as it is."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    hpg = H // G
    pad = -T % chunk
    if pad:   # dt = 0 there: the state does not move
        padT = lambda a: jnp.pad(a, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (a.ndim - 2))
        x, dt, B, C = padT(x), padT(dt), padT(B), padT(C)
    nc, Q = (T + pad) // chunk, chunk
    mdt = x.dtype
    f32 = jnp.float32
    cs = jnp.cumsum((dt * A).reshape(b, nc, Q, H), axis=2)   # inclusive
    xdt = (x.astype(f32) * dt[..., None]).reshape(b, nc, Q, G, hpg, P)
    Bc = B.reshape(b, nc, Q, G, N)
    Cc = C.reshape(b, nc, Q, G, N)
    # inside a chunk: y_i = sum_{j<=i} (C_i . B_j) exp(cs_i - cs_j) dt_j x_j
    i = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    j = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]        # (b,nc,i,j,H)
    decay = jnp.exp(jnp.where((i >= j)[None, None, :, :, None], seg,
                              -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                    preferred_element_type=f32)
    m = cb[:, :, :, None] * jnp.moveaxis(decay, -1, 2).reshape(
        b, nc, G, hpg, Q, Q)
    y = jnp.einsum("bcghij,bcjghp->bcighp", m.astype(mdt), xdt.astype(mdt),
                   preferred_element_type=f32)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cs[:, :, -1:, :] - cs).reshape(b, nc, Q, G, hpg)
    own = jnp.einsum("bcjgn,bcjghp->bcghpn", Bc,
                     (xdt * to_end[..., None]).astype(mdt),
                     preferred_element_type=f32)
    total = jnp.exp(cs[:, :, -1, :]).reshape(b, nc, G, hpg)  # chunk's decay

    def carry(S, c):
        own_c, total_c = c
        return total_c[..., None, None] * S + own_c, S      # S: entering

    if S0 is None:
        S0 = jnp.zeros((b, G, hpg, P, N), f32)
    else:
        S0 = S0.astype(f32).reshape(b, G, hpg, P, N)
    S_end, S_in = lax.scan(carry, S0, (jnp.moveaxis(own, 1, 0),
                                       jnp.moveaxis(total, 1, 0)))
    S_in = jnp.moveaxis(S_in, 0, 1)                          # (b,nc,G,hpg,P,N)
    # what the state entering the chunk gives at position i
    off = jnp.einsum("bcign,bcghpn->bcighp", Cc, S_in.astype(mdt),
                     preferred_element_type=f32)
    y = y + off * jnp.exp(cs).reshape(b, nc, Q, G, hpg)[..., None]
    y = y.reshape(b, nc * Q, H, P)[:, :T]
    return y, S_end.reshape(b, H, P, N)


def mamba_prefill(h, lp, cfg, last_pos=None):
    """The mixer over a prompt: h (b, T, D) normed input -> (out (b, T, D),
    (conv tail (b, K-1, conv_dim), S (b, H, P, N))). ``last_pos`` (b,)
    int32: the state returned is the one at that position (default: the
    last)."""
    b, T, _ = h.shape
    dt_c = h.dtype
    K = cfg.ssm_conv
    z, xbc, dt = _project(h, lp, cfg)
    with jax.named_scope("conv"):
        xp = jnp.pad(xbc, [(0, 0), (K - 1, 0), (0, 0)])
        w = lp["conv_w"].astype(jnp.float32)
        conv = lp["conv_b"].astype(jnp.float32) + sum(
            xp[:, k:k + T].astype(jnp.float32) * w[k] for k in range(K))
        act = jax.nn.silu(conv).astype(dt_c)
    with jax.named_scope("state_write"):
        if last_pos is None:
            tail = xp[:, T:]
        else:   # rows last_pos-K+2 .. last_pos of xbc
            tail = jax.vmap(lambda a, p: lax.dynamic_slice_in_dim(
                a, p + 1, K - 1, axis=0))(xp, last_pos)
    with jax.named_scope("scan"):
        x, B, C = _split_xbc(act, cfg)
        dt, A = _step_sizes(dt, lp)
        if last_pos is not None:
            live = jnp.arange(T)[None, :] <= last_pos[:, None]
            dt = jnp.where(live[..., None], dt, 0.0)
        y, S = ssd_chunked(x, dt, A, B, C, cfg.ssm_chunk)
        y = y + lp["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = _gated_norm(y.reshape(b, T, -1), z, lp, cfg)
    out = jnp.dot(y, matmul_weight(lp, "out_proj", dt_c))
    return scaled(out, cfg.ssm_out_multiplier), (tail.astype(dt_c), S)


def mamba_step(h, lp, cfg, state, active=None):
    """One token: h (b, D) normed input against ``state`` = (conv tail,
    S) -> (out (b, D), new state). Where ``active`` (b,) is false the
    row's state is handed back as it came (its ``S`` is not even read) and
    its ``out`` means nothing."""
    tail, S = state
    dt_c = h.dtype
    f32 = jnp.float32
    z, xbc, dt = _project(h, lp, cfg)
    with jax.named_scope("conv"):
        window = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], 1)
        conv = lp["conv_b"].astype(f32) + jnp.sum(
            window.astype(f32) * lp["conv_w"].astype(f32), axis=1)
        act = jax.nn.silu(conv)
        new_tail = window[:, 1:]
    with jax.named_scope("step"):
        x, B, C = _split_xbc(act, cfg)                       # float32
        dt, A = _step_sizes(dt, lp)
        y, S_new = ssm_step(S, x, dt, A, B, C, active)
        y = y + lp["D"].astype(f32)[:, None] * x
        if active is not None:
            with jax.named_scope("state_write"):
                new_tail = jnp.where(active[:, None, None], new_tail, tail)
    y = _gated_norm(y.reshape(y.shape[0], -1).astype(dt_c), z, lp, cfg)
    out = jnp.dot(y, matmul_weight(lp, "out_proj", dt_c))
    return scaled(out, cfg.ssm_out_multiplier), (new_tail, S_new)
