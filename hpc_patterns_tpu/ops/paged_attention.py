"""Exact-softmax paged-attention decode kernel (``decode_attn="paged_flash"``).

The third paged decode route, next to ``flash_decode_paged`` (online
softmax, streamed) and ``_paged_attend_gather`` (pure XLA): the kernel
GATHERS each sequence's live pages into VMEM through the page table —
per-page blocks whose index map CLAMPS past-the-fill steps to the last
live page, so unfilled pages are never fetched from HBM — and then
runs the attention in ONE pass whose math mirrors the gather route
term for term (same einsum spellings, same mask constant, same
``jax.nn.softmax``). Two properties fall out:

- **parity**: on compute-dtype (f32/bf16) pools the kernel is
  BITWISE-equal to ``cfg.decode_attn="gather"`` in interpret mode
  (tests/test_quantization.py pins it across page counts, partial
  pages, ladder rungs, and tp shards) — the serving routes can swap
  per backend without an oracle caveat. Quantized pools dequantize
  in-kernel with the same elementwise order the gather view uses, so
  they ride the same battery (tolerance-tier, see below);
- **no online-softmax rescale**: a decode step has ONE query group, so
  the (g, S) score row costs g·S·4 bytes of VMEM — cheap enough to
  hold, which removes the per-block rescale multiplies entirely
  (the FlashDecoding-- observation: online softmax exists for big
  query tiles, not single queries).

Quantized pools (``kv_cache_dtype`` "int8"/"fp8"): per-row scales ride
alongside the pool in kernel-lane layout ``(pool, Hkv, 1, P)``; the
kernel streams the one-byte pages — HALF the HBM bytes of bf16, a
QUARTER of f32, on a cache-read-bound path — and dequantizes in VMEM
before the score/value einsums exactly as the gather view does
(``kd = k.astype(f32) * scale_row``). The parity battery holds these
to tight tolerance rather than asserting bitwise (the dequant multiply
order is the one place backends may legally differ;
docs/quantization.md has the full precision matrix).

VMEM bound: the gather scratch holds the whole ALLOCATED span —
``pages·P·D`` elements of the pool dtype for K and V each, plus the
(g, pages·P) f32 score row. At chip serving shapes (S_alloc 16k,
D 128) that is ~4 MB for int8 pools and ~8 MB for bf16 — inside the
~16 MB budget quantized serving targets; f32 pools at long context
belong on the streaming (``flash``) route. HBM traffic stays
position-proportional either way: the clamped index map never fetches
a page past the fill, and Pallas elides the repeated clamped fetch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hpc_patterns_tpu.ops.tiling import resolve_interpret

# the mask constant the bitwise route-parity contract depends on; must
# equal parallel.ring_attention._NEG_INF (importing it here is circular
# via comm.ring -> ops; tests/test_quantization.py pins the equality)
_NEG_INF = -1e30


def _paged_attention_kernel(pos_ref, table_ref, q_ref, k_ref, v_ref,
                            *rest, scale: float, page_size: int,
                            quantized: bool, hkv_per_row: int):
    # grid (B·Hkv, pages): steps 0..pages-1 stage this row's (clamped)
    # page into the gather scratch; the LAST step runs the whole
    # attention — the gather route's einsum/mask/softmax sequence on
    # the staged span. The table ref is consumed by the index maps.
    del table_ref
    if quantized:
        (ks_ref, vs_ref, o_ref, k_sc, v_sc, ks_sc, vs_sc) = rest
    else:
        ks_ref = vs_ref = ks_sc = vs_sc = None
        (o_ref, k_sc, v_sc) = rest
    P = page_size
    si = pl.program_id(1)
    n_s = pl.num_programs(1)
    pos = (pos_ref[pl.program_id(0) // hkv_per_row] if hkv_per_row
           else pos_ref[0])

    # UNCONDITIONAL stage (clamped steps re-stage the last live page):
    # past-the-fill scratch slots must hold FINITE bytes — the mask
    # zeroes their probability, and 0 * garbage-NaN would poison the
    # value einsum exactly where uninitialized VMEM can surprise
    k_sc[pl.ds(si * P, P), :] = k_ref[...]
    v_sc[pl.ds(si * P, P), :] = v_ref[...]
    if quantized:
        ks_sc[:, pl.ds(si * P, P)] = ks_ref[...]
        vs_sc[:, pl.ds(si * P, P)] = vs_ref[...]

    @pl.when(si == n_s - 1)
    def _():
        # the gather route's math, term for term (_paged_attend_gather):
        # f32 dequant/upcast, HIGHEST-precision einsums, the same mask
        # constant, jax.nn.softmax — bitwise parity on compute dtypes
        q = q_ref[...].astype(jnp.float32)          # (g, D)
        kd = k_sc[...].astype(jnp.float32)          # (S_alloc, D)
        vd = v_sc[...].astype(jnp.float32)
        if quantized:
            kd = kd * ks_sc[...][0, :, None]
            vd = vd * vs_sc[...][0, :, None]
        s = jnp.einsum("gd,sd->gs", q, kd,
                       precision=lax.Precision.HIGHEST) * scale
        idx = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx <= pos, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_ref[...] = jnp.einsum("gs,sd->gd", p, vd,
                                precision=lax.Precision.HIGHEST)


def paged_attention_decode(
    q,
    k_pool,
    v_pool,
    table,
    pos,
    *,
    k_scale_pool=None,
    v_scale_pool=None,
    scale: float | None = None,
    interpret: bool | None = None,
):
    """Single-query attention against a paged KV pool, exact-softmax
    form (module docstring has the design).

    ``q``: (B, n_heads, head_dim); ``k_pool``/``v_pool``:
    (pool_pages, kv_heads, page_size, head_dim) in the pool dtype
    (compute dtype, int8, or float8_e4m3fn); ``table``:
    (B, pages_per_seq) int32 page ids; ``pos``: traced int32 scalar or
    (B,) per-sequence fill positions (ragged serving — each grid row
    clamps and masks by its own sequence's position).
    ``k_scale_pool``/``v_scale_pool``: (pool_pages, kv_heads, 1,
    page_size) f32 per-row dequant scales — REQUIRED for quantized
    pools, refused for compute-dtype ones. Returns (B, n_heads,
    head_dim) f32, the gather route's numbers.
    """
    B, H, D = q.shape
    n_pool, Hkv, P, Dp = k_pool.shape
    pages = table.shape[1]
    if H % Hkv or v_pool.shape != k_pool.shape or Dp != D:
        raise ValueError(
            f"shape mismatch: q {q.shape}, pools {k_pool.shape}/"
            f"{v_pool.shape}"
        )
    if table.shape[0] != B:
        raise ValueError(f"table rows {table.shape[0]} != batch {B}")
    quantized = k_scale_pool is not None
    if quantized != (v_scale_pool is not None):
        raise ValueError("k_scale_pool and v_scale_pool come together")
    storage_quantized = k_pool.dtype in (jnp.int8, jnp.float8_e4m3fn)
    if quantized != storage_quantized:
        raise ValueError(
            f"pool dtype {k_pool.dtype} "
            f"{'needs' if storage_quantized else 'refuses'} per-row "
            "scale pools (kv_cache_dtype and the scale operands must "
            "agree)")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    interpret = resolve_interpret(interpret, "paged_attention")
    g = H // Hkv

    qr = q.reshape(B * Hkv, g, D)
    ragged = jnp.ndim(pos) == 1
    if ragged and jnp.shape(pos)[0] != B:
        raise ValueError(
            f"ragged pos has {jnp.shape(pos)[0]} entries for batch {B}"
        )
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(B if ragged else 1)
    table_flat = table.reshape(-1).astype(jnp.int32)

    def page_idx(r, si, pos_ref, table_ref):
        # clamp past-the-fill steps to the last live page (the fetch
        # elision shared with flash_decode_paged), then indirect
        # through this sequence's page list
        b = r // Hkv
        live = jnp.minimum(si, pos_ref[b if ragged else 0] // P)
        return table_ref[b * pages + live], r % Hkv, 0, 0

    row = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    in_specs = [
        row((None, g, D), lambda r, si, pos, tab: (r, 0, 0)),
        row((None, None, P, D), page_idx),
        row((None, None, P, D), page_idx),
    ]
    operands = [pos_arr, table_flat, qr, k_pool, v_pool]
    scratch = [
        pltpu.VMEM((pages * P, D), k_pool.dtype),   # K gather span
        pltpu.VMEM((pages * P, D), v_pool.dtype),   # V gather span
    ]
    if quantized:
        in_specs += [row((None, None, 1, P), page_idx),
                     row((None, None, 1, P), page_idx)]
        operands += [k_scale_pool, v_scale_pool]
        scratch += [pltpu.VMEM((1, pages * P), jnp.float32),
                    pltpu.VMEM((1, pages * P), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_paged_attention_kernel, scale=float(scale),
                          page_size=P, quantized=quantized,
                          hkv_per_row=Hkv if ragged else 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * Hkv, pages),
            in_specs=in_specs,
            out_specs=row((None, g, D), lambda r, si, pos, tab: (r, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, g, D), jnp.float32),
        name="paged_attention_decode",
        interpret=interpret,
    )(*operands)
    return out.reshape(B, H, D)
