"""Ring attention: context parallelism on the ring engine.

The reference's ring allreduce dataflow — per step: neighbor ppermute,
local combine, buffer rotation (allreduce-mpi-sycl.cpp:173-182) — with
the combine generalized from ``VC += VA`` to blockwise online-softmax
attention. This is exactly the generalization SURVEY.md §5 calls for
("per-step neighbor ppermute + local compute + buffer rotation is exactly
the ring-attention/context-parallel dataflow").

Rank r holds the r-th sequence block of Q, K, V. K/V blocks travel the
ring; each step attends local Q against the visiting K/V block and folds
the result into a numerically-stable running (max, sum, output) — the
flash-attention accumulator — so no rank ever materializes the full
sequence. Causal masking uses global positions derived from the block's
source rank, so the sharded result is bit-for-bit the attention of the
gathered sequence (the analytic-oracle test style of SURVEY.md §4.2).

On TPU the K/V ppermute rides ICI neighbor links while the MXU computes
the current block — the same DMA/compute overlap story as the
concurrency suite, one level up.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from hpc_patterns_tpu.comm import ring

_NEG_INF = -1e30  # finite mask value: avoids inf-inf=nan in the rescale


def _check_gqa(q, k, v) -> int:
    """Validate head counts; return the GQA group factor H // Hkv (1 =
    MHA). q head h attends kv head h // group — the same map as the
    flash kernel's GQA row maps (ops/flash_attention.py)."""
    H, Hkv = q.shape[2], k.shape[2]
    if H % max(Hkv, 1) or v.shape[2] != Hkv:
        raise ValueError(
            f"kv heads {Hkv}/{v.shape[2]} must match and divide "
            f"n_heads {H} (GQA attends the narrow K/V)"
        )
    return H // Hkv


def _grouped_scores(q, k, scale):
    """(B, H, T, S) f32 scores against possibly-narrow K: q head h
    scores kv head h // group, with no expanded K copy."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, T, Hkv, H // Hkv, D)
    s = jnp.einsum(
        "btkgd,bskd->bkgts", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    return s.reshape(B, H, T, k.shape[1])


def _grouped_pv(p, v):
    """(B, H, T, D) f32 = P @ V with possibly-narrow V (no expansion)."""
    B, H, T, S = p.shape
    Hkv = v.shape[2]
    pg = p.reshape(B, Hkv, H // Hkv, T, S)
    out = jnp.einsum("bkgts,bskd->bkgtd", pg, v.astype(jnp.float32))
    return out.reshape(B, H, T, v.shape[3])


def _block_step(q, k, v, acc, m, l, *, scale, q_offset, k_offset, causal):
    """Fold one visiting K/V block into the running accumulator.

    q: (B, T, H, D); k/v: (B, S, Hkv, D) with Hkv | H (GQA — the narrow
    block is what travels the ring); acc: (B, H, T, D) f32;
    m, l: (B, H, T) f32 running max / normalizer.
    """
    s = _grouped_scores(q, k, scale)
    if causal:
        t_idx = q_offset + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s_idx = k_offset + lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(s_idx <= t_idx, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # exp(_NEG_INF - m_new) underflows to 0 — masked rows stay masked
    p = jnp.exp(s - m_new[..., None])
    rescale = jnp.exp(m - m_new)
    l_new = l * rescale + p.sum(axis=-1)
    acc_new = acc * rescale[..., None] + _grouped_pv(p, v)
    return acc_new, m_new, l_new


def _kv_rotate(axis: str, shift_impl: str):
    """The per-step K/V neighbor hop, selectable between the XLA
    collective permute (``"ppermute"``) and the device-initiated Pallas
    remote-DMA shift (``"fused"``, comm/fused.py) — the same
    algorithm-selection axis the Communicator exposes for allreduce,
    at the ring-attention step. Both produce identical bytes (a shift
    is a pure permutation); what changes is who issues the transfer."""
    if shift_impl == "ppermute":
        return lambda kv: jax.tree.map(
            lambda t: ring.ring_shift(t, axis, 1), kv)
    if shift_impl == "fused":
        from hpc_patterns_tpu.comm import fused
        from hpc_patterns_tpu.ops import tiling

        # K and V shift as two data-independent kernels the scheduler
        # may overlap on chip — distinct registered collective_ids keep
        # their barrier/DMA state apart (the registry in ops/tiling.py
        # owns the numbering; hand-picked integers are a pallaslint
        # finding)
        k_id = tiling.collective_id("parallel.ring_attention.kshift")
        v_id = tiling.collective_id("parallel.ring_attention.vshift")

        def rotate(kv):
            k_blk, v_blk = kv
            return (fused.fused_ring_shift(k_blk, axis, 1,
                                           collective_id=k_id),
                    fused.fused_ring_shift(v_blk, axis, 1,
                                           collective_id=v_id))

        return rotate
    raise ValueError(
        f"shift_impl {shift_impl!r} not in ('ppermute', 'fused')")


def ring_attention(
    q,
    k,
    v,
    axis: str,
    *,
    causal: bool = False,
    scale: float | None = None,
    impl: str = "dense",
    block_q: int | None = None,
    block_k: int | None = None,
    shift_impl: str = "ppermute",
):
    """Attention over a sequence sharded on mesh ``axis`` (rank-local; run
    inside ``shard_map``).

    ``q``, ``k``, ``v``: (batch, seq_local, heads, head_dim) — the local
    sequence block; global sequence = blocks in rank order. K/V may be
    GQA-narrow (kv_heads dividing q's heads): the narrow block is what
    circulates, cutting per-step ring traffic by the group factor.
    Returns the local block of the softmax attention output, same
    shape/dtype as ``q``, numerically equal to attending the gathered
    sequence.

    ``impl``: per-step local compute. ``"dense"`` materializes the
    (T_local, S) score block (any shape); ``"flash"`` runs the Pallas
    blockwise kernel per visiting block (ops.flash_attention_block) and
    merges partials by logsumexp — O(block) VMEM on-chip, MXU-shaped,
    and causally-skipped blocks cost no fetches or matmuls. Requires
    the local sequence to divide by the (clamped) block sizes.

    ``shift_impl``: who moves the K/V block each step — ``"ppermute"``
    (XLA collective permute, the default) or ``"fused"`` (the
    device-initiated Pallas remote-DMA shift; single-axis meshes).
    """
    if q.ndim != 4:
        raise ValueError(f"want (batch, seq, heads, head_dim), got {q.shape}")
    if impl not in ("dense", "flash"):
        raise ValueError(f"impl {impl!r} not in ('dense', 'flash')")
    rotate = _kv_rotate(axis, shift_impl)
    _check_gqa(q, k, v)
    size = ring.axis_size(axis)
    me = ring.axis_index(axis)
    B, T, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    q_offset = me * T

    if impl == "flash":
        return _ring_attention_flash(
            q, k, v, axis, size=size, me=me, q_offset=q_offset,
            causal=causal, scale=scale, block_q=block_q, block_k=block_k,
            rotate=rotate,
        )

    acc = jnp.zeros((B, H, T, D), jnp.float32)
    m = jnp.full((B, H, T), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, T), jnp.float32)

    kv = (k, v)
    for step in range(size):
        k_blk, v_blk = kv
        # block visiting at step s started at rank (me - s) % size
        src = (me - step) % size
        acc, m, l = _block_step(
            q, k_blk, v_blk, acc, m, l,
            scale=scale, q_offset=q_offset, k_offset=src * k_blk.shape[1],
            causal=causal,
        )
        if step + 1 < size:
            # rotate K/V one neighbor over (ICI hop), like the reference's
            # SendRecvRing + swap(VA, VB)
            kv = rotate(kv)

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhtd->bthd", out).astype(q.dtype)


def _ring_attention_flash(q, k, v, axis, *, size, me, q_offset, causal,
                          scale, block_q, block_k, rotate):
    """Flash per-step ring attention: each visiting K/V block is one
    Pallas partial attention (normalized within the block, with its
    logsumexp), merged into the running result by the standard
    logsumexp combine. Same ring dataflow, kernel-grade local compute."""
    from hpc_patterns_tpu.ops import flash_attention_block

    out = jnp.zeros(q.shape, jnp.float32)           # (B, T, H, D)
    lse = jnp.full(q.shape[:3], _NEG_INF, jnp.float32)  # (B, T, H)

    kv = (k, v)
    for step in range(size):
        k_blk, v_blk = kv
        src = (me - step) % size
        o_b, lse_b = flash_attention_block(
            q, k_blk, v_blk, q_offset, src * k_blk.shape[1],
            causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        )
        m = jnp.maximum(lse, lse_b)
        e_run = jnp.exp(lse - m)
        e_b = jnp.exp(lse_b - m)
        denom = e_run + e_b
        out = (out * e_run[..., None]
               + o_b.astype(jnp.float32) * e_b[..., None]) / denom[..., None]
        lse = m + jnp.log(denom)
        if step + 1 < size:
            kv = rotate(kv)

    return out.astype(q.dtype)


def full_attention(q, k, v, *, causal: bool = False, scale: float | None = None,
                   mask_block: int = 1):
    """Single-device oracle: plain softmax attention over the full
    sequence, used by tests to validate the ring result (§4.2 style).
    K/V may be GQA-narrow (kv_heads dividing q's heads) — grouped-query
    scores, never an expanded K/V copy. ``mask_block`` > 1 (with
    ``causal``): the BLOCK mask, position i sees j iff
    j // mask_block <= i // mask_block."""
    _check_gqa(q, k, v)
    B, T, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    s = _grouped_scores(q, k, scale)
    if causal:
        t_idx = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s_idx = lax.broadcasted_iota(jnp.int32, s.shape, 3)
        if mask_block > 1:   # the last position of the query's block
            t_idx = t_idx // mask_block * mask_block + (mask_block - 1)
        s = jnp.where(s_idx <= t_idx, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = _grouped_pv(p, v)
    return jnp.einsum("bhtd->bthd", out).astype(q.dtype)
