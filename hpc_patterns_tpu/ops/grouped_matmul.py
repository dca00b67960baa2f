"""Grouped matrix product: rows sorted by group, one weight matrix a group.

The two products of an expert layer that holds a share of its experts
(parallel/moe.held_experts): ``out[rows of g] = lhs[rows of g] @ rhs[g]``
with group ``g`` the rows ``offset[g] : offset[g] + sizes[g]``. Few rows
meet many megabytes of weights (a decode step: ~90 picks over ~64 of 128
experts of 11 MB), so the kernel's job is to read each TOUCHED group's
weights once, in few large DMAs, and nothing else (the layout follows
jax.experimental.pallas.ops.tpu.megablox.gmm):

- grid = (n tiles, visits): a visit is one (group, row tile) pair that
  holds rows of the group, listed group by group. The lists and the
  number of visits arrive by scalar prefetch; the grid's second extent
  IS that number, so an empty group is never visited and its weights are
  never fetched, and the row tiles behind every group cost nothing.
- a weight tile is the whole contraction by ``tn`` columns, megabytes
  each (:func:`_tiles`): one DMA a visit. Consecutive visits of one group
  (a group longer than a row tile) keep the block index, and Pallas
  elides the fetch: a group's weights are read once an n tile however
  many row tiles it spans. The row tile stays at the MXU's 128 rows.
- rows of a tile that belong to another group are masked at the store;
  the output tile is revisited (consecutively) until its groups are
  done. Rows behind every group are left UNWRITTEN: the caller masks
  them (held_experts does, under ``here``).

float32 accumulation, one rounding to the output dtype, then the optional
activation in that dtype: what ``activation(ragged_dot(...))`` gives.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hpc_patterns_tpu.ops.tiling import fit_block_divisor, resolve_interpret

#: rows a visit multiplies: the MXU's height. A taller tile only adds
#: masked rows to every visit (a group's weights are fetched once
#: whatever the tile, see the module note): 256 rows measured 8-11 %
#: slower at a decode step and at a 512-token prefill (PERF.md, PR 27)
_TM = 128
#: bytes of one weight tile; two are in flight. With the row and output
#: tiles beside them the call takes about half of the 16 MiB of scoped
#: VMEM (whole-width tiles of 5.25 MiB fit too and measured 0-3 % faster
#: where the cell's traffic is, 7-11 % at its two top rungs)
_WEIGHT_TILE_BYTES = 3 << 20


def grouped_matmul_reference(lhs, rhs, sizes, *, preferred_element_type=None,
                             activation=None):
    """The plain formulation: the tests' oracle and the backward's source.
    Rows behind every group come out zero."""
    out = lax.ragged_dot(lhs, rhs, sizes,
                         preferred_element_type=preferred_element_type)
    return out if activation is None else activation(out)


def _tiles(k: int, n: int, itemsize: int) -> int:
    """Columns of a weight tile, from the shapes alone: the widest
    128-multiple that divides ``n`` and keeps (k, tn) under
    ``_WEIGHT_TILE_BYTES`` (128 where none does); all of ``n`` where it
    is no 128-multiple (a block may always span a whole dimension)."""
    if n % 128:
        return n
    return 128 * fit_block_divisor(
        n // 128, max(1, _WEIGHT_TILE_BYTES // (128 * k * itemsize)))


def _visits(sizes, m: int, tm: int):
    """(offsets (groups + 1,), group_of, tile_of (max visits,), visits):
    the (group, row tile) pairs that hold rows, group by group. A group
    starts at most one tile it shares, so there are at most tiles +
    groups - 1 of them; the entries past ``visits`` are padding (a valid
    group and tile, never visited)."""
    groups, tiles = sizes.shape[0], pl.cdiv(m, tm)
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    most = tiles + groups - 1
    group_of = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), spans,
                          total_repeat_length=most)
    nth = jnp.arange(most, dtype=jnp.int32) - (jnp.cumsum(spans)
                                                - spans)[group_of]
    tile_of = jnp.minimum(first[group_of] + nth, tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group_of, tile_of, jnp.sum(spans)


def _kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref, *,
            activation):
    v = pl.program_id(1)
    g = group_ref[v]
    tm = out_ref.shape[0]
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32).astype(out_ref.dtype)
    if activation is not None:
        acc = activation(acc)
    row = tile_ref[v] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    out_ref[...] = jnp.where(mine, acc, out_ref[...])


# jitted: the layers of a program call it with one signature, and the
# outer trace then lowers the kernel once, not once a layer (a Mosaic
# lowering is ~0.1 s of every process's set-up, cached executable or not)
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _call(lhs, rhs, sizes, out_dtype, activation, interpret):
    m, k = lhs.shape
    n = rhs.shape[2]
    tm = min(_TM, m)
    tn = _tiles(k, n, rhs.dtype.itemsize)
    offsets, group_of, tile_of, visits = _visits(sizes, m, tm)
    block = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, visits),
            in_specs=[
                block((tm, k), lambda j, v, off, grp, til: (til[v], 0)),
                block((None, k, tn),
                      lambda j, v, off, grp, til: (grp[v], 0, j)),
            ],
            out_specs=block((tm, tn),
                            lambda j, v, off, grp, til: (til[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="grouped_matmul",
        interpret=interpret,
    )(offsets, group_of, tile_of, lhs, rhs)


_grouped_matmul = jax.custom_vjp(_call, nondiff_argnums=(3, 4, 5))


def _fwd(lhs, rhs, sizes, *static):
    return _call(lhs, rhs, sizes, *static), (lhs, rhs, sizes)


def _bwd(out_dtype, activation, interpret, saved, g):
    # the plain formulation's backward. The rows behind every group hold
    # whatever the forward left there (this kernel's own output, when the
    # products are chained): zeroed before anything multiplies them
    lhs, rhs, sizes = saved
    live = (jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None]
    _, vjp = jax.vjp(
        lambda a, b: grouped_matmul_reference(
            a, b, sizes, preferred_element_type=out_dtype,
            activation=activation),
        jnp.where(live, lhs, 0), rhs)
    d_lhs, d_rhs = vjp(jnp.where(live, g, 0))
    return d_lhs, d_rhs, None


_grouped_matmul.defvjp(_fwd, _bwd)


def grouped_matmul(lhs, rhs, sizes, *, preferred_element_type=None,
                   activation=None, interpret: bool | None = None):
    """``out[rows of g] = activation(lhs[rows of g] @ rhs[g])``.

    ``lhs`` (m, k) with its rows sorted by group, ``rhs`` (groups, k, n),
    ``sizes`` (groups,) int32 with ``sum(sizes) <= m``: group ``g`` is the
    rows ``offset[g] : offset[g] + sizes[g]``, ``offset`` the running sum.
    Products accumulate in float32 and round once to
    ``preferred_element_type`` (``lhs``'s dtype by default), where
    ``activation`` (elementwise, optional) then runs: the rounding of
    ``activation(jax.lax.ragged_dot(...))``. The rows behind every group
    (``sum(sizes):``) are NOT written: mask them. Differentiable in
    ``lhs`` and ``rhs``: the backward is :func:`grouped_matmul_reference`'s.
    """
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1]:
        raise ValueError(
            f"grouped_matmul: lhs {lhs.shape} against rhs {rhs.shape}; "
            "want (m, k) and (groups, k, n)")
    if sizes.shape != (rhs.shape[0],) or sizes.dtype != jnp.int32:
        raise ValueError(
            f"grouped_matmul: sizes {sizes.shape} {sizes.dtype}; want "
            f"({rhs.shape[0]},) int32")
    if lhs.dtype != rhs.dtype:
        raise ValueError(
            f"grouped_matmul: lhs {lhs.dtype} and rhs {rhs.dtype} differ")
    out_dtype = jnp.dtype(preferred_element_type or lhs.dtype)
    interpret = resolve_interpret(interpret, "grouped_matmul")
    return _grouped_matmul(lhs, rhs, sizes, out_dtype, activation, interpret)
