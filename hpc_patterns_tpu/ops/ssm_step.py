"""One step of the Mamba-2 recurrence over the rows that are live, in place.

    S[b, h] <- exp(dt[b, h] A[h]) S[b, h] + (dt[b, h] x[b, h]) (x) B[b, g(h)]
    y[b, h]  = S[b, h] C[b, g(h)]                      g(h) = h // (H // G)

for the rows ``b`` where ``active`` holds, and nothing at all for the
others. A server's decode step has a fifth of its slots live and a state
of megabytes a row (64 slots x 4.19 MB a layer), so the kernel's job is to
move the live rows' bytes once each way and no others:

- grid = (live rows, head blocks). The list of live rows arrives by scalar
  prefetch and the grid's first extent IS its length (as the number of
  ops/grouped_matmul's visits is); the ``index_map``s read the row from
  the list: an idle row's state is never fetched.
- ``S`` is aliased input to output, so a row that is not visited is not
  read, not written and keeps its bits: ``where(active, S_new, S)``
  without the pass over S. ``y`` of a row not visited is NOT written by
  the kernel; :func:`ssm_step` zeroes it.
- a tile is (head block, P, N) of one row, megabytes (:func:`_head_block`):
  a head's (P, N) slab has P on the sublanes and N on the lanes, so ``B``
  and ``C`` meet it as a row broadcast down the sublanes, ``dt x`` as a
  column broadcast along the lanes (its tile arrives transposed, P by
  heads, so that a head is a lane of it), the decay as a scalar from SMEM,
  and ``y`` leaves as a column of a (P, heads) tile. The transposes of
  those small operands are XLA's, outside.

float32 throughout whatever ``S``'s dtype (it is rounded to it once, after
the read-out): the arithmetic of :func:`ssm_step_reference`, the order of
the sum over N apart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hpc_patterns_tpu.ops.tiling import (fit_block_divisor, live_rows,
                                          resolve_interpret)

#: bytes of one tile of S; two come in and two go out at a time. From
#: 1 MiB up the kernel runs at what its DMAs allow (a plain copy of the same
#: tiles is no faster); 0.5 MiB measured 11 % slower at 15 live rows of 64,
#: 2 MiB 1 % (PERF.md, PR 29), and a smaller tile unrolls fewer heads
_TILE_BYTES = 1 << 20


def ssm_step_reference(S, x, dt, A, B, C, active=None):
    """The plain formulation, one pass over all of ``S``: the tests'
    oracle. Same arguments and results as :func:`ssm_step`."""
    f32 = jnp.float32
    hpg = S.shape[1] // B.shape[1]
    Bh = jnp.repeat(B, hpg, axis=1)                      # (b, H, N)
    Ch = jnp.repeat(C, hpg, axis=1)
    S_new = (jnp.exp(dt * A)[..., None, None] * S.astype(f32)
             + (dt[..., None] * x)[..., None] * Bh[:, :, None, :])
    y = jnp.sum(S_new * Ch[:, :, None, :], axis=-1)
    S_new = S_new.astype(S.dtype)
    if active is not None:
        S_new = jnp.where(active[:, None, None, None], S_new, S)
        y = jnp.where(active[:, None, None], y, 0.0)
    return y, S_new


def _head_block(heads: int, slab_bytes: int) -> int:
    """Heads a tile: the most that divide ``heads`` and keep the tile
    under ``_TILE_BYTES``."""
    return fit_block_divisor(heads, max(1, _TILE_BYTES // slab_bytes))


def _kernel(rows_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref, y_ref,
            s_out_ref, *, hpg):
    f32 = jnp.float32
    hb = s_ref.shape[0]
    first = pl.program_id(1) * hb
    at = rows_ref[pl.program_id(0)] * (hb * pl.num_programs(1)) + first
    dtx = dtx_ref[...]                                   # (P, hb)
    lane = lax.broadcasted_iota(jnp.int32, dtx.shape, 1)
    ones = jnp.ones((s_ref.shape[2], hb), f32)
    y = jnp.zeros(dtx.shape, f32)
    for h in range(hb):
        g = (first + h) // hpg
        s = (decay_ref[at + h] * s_ref[h].astype(f32)
             + dtx[:, h:h + 1] * b_ref[pl.ds(g, 1), :])
        s_out_ref[h] = s.astype(s_out_ref.dtype)
        # the sum over N on the otherwise idle MXU, in every lane of a
        # (P, hb) tile: float32 products summed in float32 (HIGHEST splits
        # them into bfloat16 pieces that add up to them exactly). As a
        # lane reduction on the XLU it bound the kernel (PERF.md, PR 29)
        y = jnp.where(lane == h,
                      jnp.dot(s * c_ref[pl.ds(g, 1), :], ones,
                              preferred_element_type=f32,
                              precision=lax.Precision.HIGHEST), y)
    y_ref[...] = y


# jitted: the ``M`` layers of a program call it with one signature, and the
# outer trace then lowers the kernel once, not once a layer
@functools.partial(jax.jit, static_argnums=(7,))
def _call(S, x, dt, A, B, C, active, interpret):
    b, H, P, N = S.shape
    G = B.shape[1]
    hb = _head_block(H, P * N * S.dtype.itemsize)
    nb = H // hb
    rows, count = live_rows(active, b)
    decay = jnp.exp(dt * A).reshape(-1)                  # (b * H,) to SMEM
    dtx = jnp.swapaxes((dt[..., None] * x).reshape(b, nb, hb, P), 2, 3)
    block = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    heads_of_row = lambda r, j, rows, decay: (rows[r], j, 0, 0)
    tile = block((None, hb, P, N), heads_of_row)
    col = block((None, None, P, hb), heads_of_row)
    row = block((None, G, N), lambda r, j, rows, decay: (rows[r], 0, 0))
    y, S_new = pl.pallas_call(
        functools.partial(_kernel, hpg=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(count, nb),
            in_specs=[col, row, row, tile],
            out_specs=[col, tile],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, nb, P, hb), jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="ssm_step",
        interpret=interpret,
    )(rows, decay, dtx, B, C, S)
    y = jnp.swapaxes(y, 2, 3).reshape(b, H, P)
    if active is not None:   # the kernel left the idle rows' y unwritten
        y = jnp.where(active[:, None, None], y, 0.0)
    return y, S_new


def ssm_step(S, x, dt, A, B, C, active=None, *,
             interpret: bool | None = None):
    """``(y, S_new)``: one token a row against the carried state.

    ``S`` (b, H, P, N) in any float dtype, ``x`` (b, H, P), ``dt`` (b, H)
    (after its softplus), ``A`` (H,), ``B`` and ``C`` (b, G, N) with ``G``
    dividing ``H``, all used as float32; ``active`` (b,) bool or None for
    every row. ``S_new`` is ``S`` updated IN PLACE where ``active`` holds (donate
    ``S`` or the compiler copies it first) and the very bits of ``S``
    elsewhere; ``y`` (b, H, P) float32 is the read-out of the new state
    (without the ``D x`` skip), zero where ``active`` does not hold. Not
    differentiable: a decode step."""
    b, H, P, N = S.shape
    if (x.shape != (b, H, P) or dt.shape != (b, H) or A.shape != (H,)
            or B.shape != C.shape or B.ndim != 3
            or (B.shape[0], B.shape[2]) != (b, N) or H % B.shape[1]):
        raise ValueError(
            f"ssm_step: S {S.shape} against x {x.shape}, dt {dt.shape}, "
            f"A {A.shape}, B {B.shape}, C {C.shape}; want (b, H, P, N), "
            "(b, H, P), (b, H), (H,) and twice (b, G, N) with G dividing H")
    if active is not None and (active.shape != (b,)
                               or active.dtype != jnp.bool_):
        raise ValueError(
            f"ssm_step: active {active.shape} {active.dtype}; want "
            f"({b},) bool")
    f32 = jnp.float32
    return _call(S, x.astype(f32), dt.astype(f32), A.astype(f32),
                 B.astype(f32), C.astype(f32), active,
                 resolve_interpret(interpret, "ssm_step"))
