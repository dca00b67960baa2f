"""Fused transformer MLP (matmul → gelu → matmul) as Pallas TPU kernels.

A builder's step profile on an older toolchain put most of the
training step in matmul fusions running well under the rate the same
shapes reach in isolation (ROADMAP.md Speed 6, not re-measured) — the
MLP block is most of that time. This kernel applies the framework's own-the-hot-loop rule
(docs/ARCHITECTURE.md; reference analog concurency/sycl_con.cpp:26-33)
to the d_ff block:

- **forward**: grid (N/bt, F/bf), token-block outer. For one token
  block, the F axis streams through VMEM: a = x·W1[:, f] (f32),
  g = gelu(a), acc += g·W2[f, :] — the (N, F) activation NEVER exists
  in HBM (XLA materializes it between its two matmul fusions: a 128 MB
  write + read per layer at the headline shape). HBM traffic per token
  block is x once + both weight panels once.
- **backward**: one fused pass, grid (F/bf, N/bt), f outer. Per step
  (5 block matmuls): recompute a = x·W1f and g, dh = dy·W2fᵀ,
  da = dh ⊙ gelu'(a), dW2f += gᵀ·dy, dW1f += xᵀ·da, and the partial
  dx contribution da·W1fᵀ goes to an (F/bf, N, D) slab summed outside
  (the flash fused backward's partial-dQ pattern,
  ops/flash_attention.py). dW accumulators live in f32 VMEM scratch
  and write once per f panel.
- custom_vjp residuals: (x, w1, w2) only — the g recompute is 1 of the
  5 backward matmuls, the price of never storing (N, F).

gelu is the tanh approximation (jax.nn.gelu's default) with an
analytic derivative, so the kernel matches the einsum path's math.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hpc_patterns_tpu.ops.tiling import (
    fit_block_divisor as _fit_block,
    resolve_interpret,
)

_SQRT_2_OVER_PI = 0.7978845608028654
_GELU_C = 0.044715


def _gelu(a):
    """tanh-approx gelu in f32 (== jax.nn.gelu(approximate=True))."""
    u = _SQRT_2_OVER_PI * (a + _GELU_C * a * a * a)
    return 0.5 * a * (1.0 + jnp.tanh(u))


def _dgelu(a):
    """d/da of the tanh-approx gelu, analytic."""
    u = _SQRT_2_OVER_PI * (a + _GELU_C * a * a * a)
    t = jnp.tanh(u)
    du = _SQRT_2_OVER_PI * (1.0 + 3.0 * _GELU_C * a * a)
    return 0.5 * (1.0 + t) + 0.5 * a * (1.0 - t * t) * du


def _fwd_kernel(x_ref, w1_ref, w2_ref, o_ref, acc_ref, *, a_ref=None):
    # grid (n_t, n_f), f inner: x block constant across f (fetch
    # elided); acc carries the growing y in f32 scratch. ``a_ref``:
    # optionally also emit the pre-gelu activation (the residual the
    # save-a backward consumes — matmul-count parity with XLA's
    # dots-saved remat backward)
    fi = pl.program_id(1)
    n_f = pl.num_programs(1)
    a = jnp.dot(x_ref[...], w1_ref[...],
                preferred_element_type=jnp.float32)
    if a_ref is not None:
        a_ref[...] = a.astype(a_ref.dtype)
    g = _gelu(a).astype(x_ref.dtype)
    part = jnp.dot(g, w2_ref[...], preferred_element_type=jnp.float32)

    @pl.when(fi == 0)
    def _():
        acc_ref[...] = part

    @pl.when(fi > 0)
    def _():
        acc_ref[...] += part

    @pl.when(fi == n_f - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, w1_ref, w2_ref, dxs_ref, dw1_ref, dw2_ref,
                dw1_acc, dw2_acc):
    # grid (n_f, n_t), t inner: weight panels constant across t; dW
    # accumulates across the token stream in f32 scratch and writes
    # once per f panel
    ti = pl.program_id(1)
    n_t = pl.num_programs(1)
    x = x_ref[...]
    dy = dy_ref[...]
    w1 = w1_ref[...]
    a = jnp.dot(x, w1, preferred_element_type=jnp.float32)
    g = _gelu(a).astype(x.dtype)
    # dh = dy · W2ᵀ  (contract the model dim)
    dh = lax.dot_general(dy, w2_ref[...], (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    da = (dh * _dgelu(a)).astype(x.dtype)

    # dW2f += gᵀ · dy ; dW1f += xᵀ · da  (contract the token dim)
    dw2_part = lax.dot_general(g, dy, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dw1_part = lax.dot_general(x, da, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    @pl.when(ti == 0)
    def _():
        dw2_acc[...] = dw2_part
        dw1_acc[...] = dw1_part

    @pl.when(ti > 0)
    def _():
        dw2_acc[...] += dw2_part
        dw1_acc[...] += dw1_part

    # partial dx for this f panel: da · W1fᵀ (contract the d_ff dim)
    dxs_ref[...] = lax.dot_general(
        da, w1, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dxs_ref.dtype)

    @pl.when(ti == n_t - 1)
    def _():
        dw1_ref[...] = dw1_acc[...]
        dw2_ref[...] = dw2_acc[...]


def _resolve(N, D, F, block_t, block_f, interpret, kernel):
    # block fitting + interpret default live in ops.tiling, shared with
    # the flash and fused-collective kernels
    block_t = _fit_block(N, block_t)
    block_f = _fit_block(F, block_f)
    return block_t, block_f, resolve_interpret(interpret, kernel)


def _fwd_kernel_save_a(x_ref, w1_ref, w2_ref, o_ref, a_ref, acc_ref):
    _fwd_kernel(x_ref, w1_ref, w2_ref, o_ref, acc_ref, a_ref=a_ref)


def _forward(x2, w1, w2, block_t, block_f, interpret, save_a=False):
    N, D = x2.shape
    F = w1.shape[1]
    bt, bf, interpret = _resolve(N, D, F, block_t, block_f, interpret,
                                 "fused_mlp.fwd")
    out_specs = pl.BlockSpec((bt, D), lambda t, f: (t, 0),
                             memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((N, D), x2.dtype)
    if save_a:
        out_specs = [out_specs,
                     pl.BlockSpec((bt, bf), lambda t, f: (t, f),
                                  memory_space=pltpu.VMEM)]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((N, F), x2.dtype)]
    return pl.pallas_call(
        _fwd_kernel_save_a if save_a else _fwd_kernel,
        grid=(N // bt, F // bf),
        in_specs=[
            pl.BlockSpec((bt, D), lambda t, f: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((D, bf), lambda t, f: (0, f),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bf, D), lambda t, f: (f, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bt, D), jnp.float32)],
        # big token blocks (f32 acc + double-buffered panels) can pass
        # Mosaic's 16 MB default scoped limit; physical VMEM is larger
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        name="fused_mlp_fwd",
        interpret=interpret,
    )(x2, w1, w2)


def _backward(x2, w1, w2, dy2, block_t, block_f, interpret):
    N, D = x2.shape
    F = w1.shape[1]
    bt, bf, interpret = _resolve(N, D, F, block_t, block_f, interpret,
                                 "fused_mlp.bwd")
    n_f = F // bf
    dx_slab, dw1, dw2 = pl.pallas_call(
        _bwd_kernel,
        grid=(n_f, N // bt),
        in_specs=[
            pl.BlockSpec((bt, D), lambda f, t: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bt, D), lambda f, t: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((D, bf), lambda f, t: (0, f),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bf, D), lambda f, t: (f, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((None, bt, D), lambda f, t: (f, t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((D, bf), lambda f, t: (0, f),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bf, D), lambda f, t: (f, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_f, N, D), x2.dtype),
            jax.ShapeDtypeStruct((D, F), jnp.float32),
            jax.ShapeDtypeStruct((F, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((D, bf), jnp.float32),
            pltpu.VMEM((bf, D), jnp.float32),
        ],
        # block set + f32 dW accumulators legitimately need ~18-24 MB
        # of VMEM at the flagship shape — above Mosaic's 16 MB default
        # scoped limit, well under the physical budget
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        name="fused_mlp_bwd",
        interpret=interpret,
    )(x2, dy2, w1, w2)
    # the partial-dx slab sums outside the kernel (flash's dQ pattern);
    # f32 accumulation of the bf16 partials
    dx2 = jnp.sum(dx_slab.astype(jnp.float32), axis=0).astype(x2.dtype)
    return dx2, dw1.astype(w1.dtype), dw2.astype(w2.dtype)


def _backward_xla(x2, w1, w2, dy2):
    """Reference backward in plain XLA ops (recompute a and g, then the
    same 5 matmuls the kernel fuses). Diagnostic path — selected with
    HPCPAT_FUSED_MLP_BWD=xla — to separate the forward kernel's in-situ
    effect from the backward kernel's."""
    a = jnp.dot(x2, w1, preferred_element_type=jnp.float32)
    g = _gelu(a).astype(x2.dtype)
    dh = lax.dot_general(dy2, w2, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    da = (dh * _dgelu(a)).astype(x2.dtype)
    dw2 = lax.dot_general(g, dy2, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    dw1 = lax.dot_general(x2, da, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    dx2 = lax.dot_general(da, w1, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    return (dx2.astype(x2.dtype), dw1.astype(w1.dtype),
            dw2.astype(w2.dtype))


def _bwd_mode() -> str:
    """Backward strategy (env knob):

    - "kernel": the one-pass fused backward kernel (5 matmuls,
      partial-dx slab) — residuals (x, w1, w2) only, lowest memory;
    - "xla": XLA ops recomputing a from x — same residuals, and XLA
      fuses/schedules the 5 matmuls itself;
    - "xla_a": the forward kernel ALSO writes the pre-gelu activation
      and the backward starts from it (4 matmuls — parity with the
      dots-saved dense remat backward) at (N, F) extra residual memory.
    """
    return os.environ.get("HPCPAT_FUSED_MLP_BWD", "kernel")


def _backward_xla_from_a(x2, a, w1, w2, dy2):
    """Save-a backward: gelu recomputed elementwise from the saved
    pre-activation; 4 matmuls, no recompute matmul."""
    a = a.astype(jnp.float32)
    g = _gelu(a).astype(x2.dtype)
    dh = lax.dot_general(dy2, w2, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    da = (dh * _dgelu(a)).astype(x2.dtype)
    dw2 = lax.dot_general(g, dy2, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    dw1 = lax.dot_general(x2, da, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    dx2 = lax.dot_general(da, w1, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    return (dx2.astype(x2.dtype), dw1.astype(w1.dtype),
            dw2.astype(w2.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_mlp(x2, w1, w2, block_t, block_f, interpret):
    return _forward(x2, w1, w2, block_t, block_f, interpret)


def _fused_mlp_fwd(x2, w1, w2, block_t, block_f, interpret):
    if _bwd_mode() == "xla_a":
        y, a = _forward(x2, w1, w2, block_t, block_f, interpret,
                        save_a=True)
        return y, (x2, a, w1, w2)
    return (_forward(x2, w1, w2, block_t, block_f, interpret),
            (x2, None, w1, w2))


def _fused_mlp_bwd(block_t, block_f, interpret, res, dy2):
    x2, a, w1, w2 = res
    mode = _bwd_mode()
    if mode == "xla_a":
        return _backward_xla_from_a(x2, a, w1, w2, dy2.astype(x2.dtype))
    if mode == "xla":
        return _backward_xla(x2, w1, w2, dy2.astype(x2.dtype))
    return _backward(x2, w1, w2, dy2.astype(x2.dtype), block_t, block_f,
                     interpret)


_fused_mlp.defvjp(_fused_mlp_fwd, _fused_mlp_bwd)


def fused_mlp(x, w1, w2, *, block_t: int = 512, block_f: int = 512,
              interpret: bool | None = None):
    """gelu MLP ``x @ w1 -> gelu -> @ w2`` with the (tokens, d_ff)
    activation never materialized in HBM.

    ``x``: (..., D) in the compute dtype (leading dims flatten to the
    token axis); ``w1``: (D, F); ``w2``: (F, D), both already cast to
    the compute dtype. Block sizes auto-fit to the largest divisor of
    the token count / F at or below the request (off-size shapes run
    at a smaller tile, never error). Differentiable (one fused
    backward pass, see module docstring); numerically the einsum
    path's math with the gelu evaluated in f32.
    """
    lead = x.shape[:-1]
    D = x.shape[-1]
    if w1.shape[0] != D or w2.shape[1] != D or w1.shape[1] != w2.shape[0]:
        raise ValueError(
            f"shape mismatch: x (..., {D}), w1 {w1.shape}, w2 {w2.shape}"
        )
    x2 = x.reshape(-1, D)
    y2 = _fused_mlp(x2, w1, w2, block_t, block_f, interpret)
    return y2.reshape(*lead, D)
