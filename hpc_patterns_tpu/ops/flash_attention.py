"""Blockwise (flash) causal attention as a Pallas TPU kernel.

Standard flash-attention dataflow, TPU-shaped:

- grid = (batch·heads, Tq/BLOCK_Q, Tk/BLOCK_K): K/V stream through VMEM
  one block per grid step while the online-softmax state (m, l, acc)
  carries across the kv axis in f32 scratch — sequence length is
  HBM-bounded, not VMEM-bounded (same accumulator as
  parallel/ring_attention, which runs this dataflow *across chips*).
  Pallas auto-pipelines each step's HBM→VMEM block loads against the
  previous step's compute (the same DMA/compute overlap the concurrency
  suite measures, here for free from the grid).
- big blocks by default (512×1024): grid-step overhead amortizes over
  the MXU-shaped block matmuls (``jnp.dot(...,
  preferred_element_type=f32)``; bf16 inputs stay bf16 into the MXU).
- causal masking is in GLOBAL positions: the kernels take (q_offset,
  k_offset) scalars via scalar prefetch, so the same kernel serves the
  single-device case (offsets 0) and one ring-attention step (q at
  rank·T, the visiting K/V block at src·S). Masked entries get a finite
  -1e30 (inf-free, like ring_attention); blocks outside the causal
  triangle skip their compute via ``pl.when`` AND their HBM fetch — the
  index map clamps to the last visible block, and Pallas elides the
  repeated fetch.
- backward (Dao 2023 §B): Δ = rowsum(dO ⊙ O), then two blockwise passes
  — dQ streaming K blocks, dK/dV streaming Q blocks — recomputing P
  from the forward's saved per-row logsumexp. O(block) VMEM in both
  directions.

Two public entry points:

- :func:`flash_attention` — full softmax attention, square (Tq == Tk),
  offsets 0. Drop-in equal to parallel.ring_attention.full_attention.
- :func:`flash_attention_block` — one *partial* attention over a K/V
  block at a global offset, returning (out, lse) so partial results
  merge by logsumexp (parallel/ring_attention's flash path does this
  per ring step). Differentiable in q, k, v AND through lse: the lse
  cotangent folds into Δ (d lse/d s = P, so ds = P∘(dP − Δ + ḡ_lse)).
"""

from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hpc_patterns_tpu.ops.tiling import fit_block_pow2, resolve_interpret

_NEG_INF = -1e30

# NOTE on dimension_semantics: marking grid axes 0/1 "parallel" measured
# ~10% SLOWER at T=8192 (fwd+bwd 3.13 ms vs 2.83 ms) — Mosaic's
# reordering breaks the causal index-map fetch-elision, which needs
# consecutive grid steps to revisit the same clamped K/V block. The
# default sequential walk is the fast path; do not "optimize" this.


def _causal_mask(s, q_start, k_start, mask_block: int = 1, window: int = 0):
    """Mask score block ``s`` so position (i, j) survives iff the global
    key index k_start+j is at or before the global query index q_start+i.
    Shared by the forward and both backward kernels — the mask must be
    identical or the recomputed P diverges from the forward's. Offsets
    may be traced (dynamic) values. ``mask_block`` > 1 (the forward
    alone): the BLOCK mask, a key survives up to the last position of the
    query's block of that many positions. It divides the tiles and the
    offsets, so a tile's last query is its block's last and the tiles
    that are skipped and elided stay the causal ones. ``window`` > 0
    (the forward alone): a key survives only inside the query's window,
    ``q - window < k <= q``."""
    q_pos = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if mask_block > 1:
        q_pos = q_pos // mask_block * mask_block + (mask_block - 1)
    if window:
        return jnp.where((k_pos <= q_pos) & (k_pos > q_pos - window), s,
                         _NEG_INF)
    return jnp.where(k_pos <= q_pos, s, _NEG_INF)


def _kv_row(H, Hkv):
    """bh (0..B·H) → row of the kv-heads-narrow (B·Hkv, T, D) array: the
    GQA group map, head h reads kv head h // (H/Hkv). Identity-shaped
    when Hkv == H (the div/mod folds away)."""
    if Hkv == H:
        return lambda bh: bh
    group = H // Hkv
    return lambda bh: (bh // H) * Hkv + (bh % H) // group


def _kv_index_map(block_q, block_k, causal, H, Hkv, window: int = 0):
    """kv-block index map for grid (bh, qi, ki): causal clamps ki to the
    last block visible from this query block, so every fully-future grid
    step revisits the previous block and Pallas skips its HBM fetch.
    The row map sends each q head to its (possibly shared) kv head — GQA
    streams the NARROW cache, no expanded copy in HBM. ``window`` > 0:
    ki is also clamped UP to the first block inside the first query's
    window, so the blocks wholly behind it are never fetched either."""
    row = _kv_row(H, Hkv)
    if not causal:
        return lambda bh, qi, ki, offs: (row(bh), ki, 0)
    if window:
        def idx_w(bh, qi, ki, offs):
            q_start_g = offs[0] + qi * block_q
            last = jnp.maximum(
                (q_start_g + block_q - 1 - offs[1]) // block_k, 0)
            first = jnp.clip((q_start_g - window + 1 - offs[1]) // block_k,
                             0, last)
            return row(bh), jnp.minimum(jnp.maximum(ki, first), last), 0

        return idx_w

    def idx(bh, qi, ki, offs):
        q_end_g = offs[0] + (qi + 1) * block_q - 1
        last = jnp.maximum((q_end_g - offs[1]) // block_k, 0)
        return row(bh), jnp.minimum(ki, last), 0

    return idx


def _q_index_map(block_q, block_k, causal, n_q, H, Hkv):
    """q-side index map for the dK/dV grid (bkv, ki, j) where
    j = g_idx·n_q + qi enumerates every (query head of the group, query
    block) pair: row = the g_idx-th q head served by kv row bkv; causal
    clamps qi UP to the first block that can see this K block (earlier
    steps revisit it, skipping the fetch)."""
    group = H // Hkv

    def row(bkv, j):
        if group == 1:
            return bkv
        return (bkv // Hkv) * H + (bkv % Hkv) * group + j // n_q

    if not causal:
        return lambda bkv, ki, j, offs: (row(bkv, j), j % n_q, 0)

    def idx(bkv, ki, j, offs):
        k_start_g = offs[1] + ki * block_k
        first = jnp.clip((k_start_g - offs[0]) // block_q, 0, n_q - 1)
        return row(bkv, j), jnp.maximum(j % n_q, first), 0

    return idx


def _kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, *rest, scale: float,
            causal: bool, with_lse: bool, mask_block: int = 1,
            window: int = 0):
    # grid (B·H, n_q, n_kv): K/V stream through VMEM one block per grid
    # step (no whole-sequence residency — T is bounded by HBM, not VMEM);
    # the online-softmax state (m, l, acc) carries across the kv axis in
    # scratch. offs_ref: (2,) int32 scalar-prefetch [q_offset, k_offset].
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)
    q_start_g = offs_ref[0] + pl.program_id(1) * block_q
    k_start_g = offs_ref[1] + ki * block_k

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros((block_q, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((block_q, d), jnp.float32)

    # causal: a K/V block fully in the future contributes nothing — its
    # compute is skipped here and its fetch was already elided by the
    # clamped index map (the streamed analog of the loop-bound skip)
    visible = (k_start_g <= q_start_g + block_q - 1) if causal else True
    if window:
        # a block wholly behind the first query's window: skipped too
        # (its fetch elided by the index map's clamp from below)
        visible = jnp.logical_and(
            visible, k_start_g + block_k - 1 > q_start_g - window)

    @pl.when(visible)
    def _():
        # matmuls in the inputs' native dtype (bf16 stays bf16 into the
        # MXU — f32xf32 runs at a fraction of MXU rate), f32 accumulate
        # via preferred_element_type; softmax state stays f32 throughout
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, q_start_g, k_start_g, mask_block, window)
        m = m_ref[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        rescale = jnp.exp(m - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * rescale + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * rescale + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    @pl.when(ki == n_kv - 1)
    def _():
        m = m_ref[:]
        l = jnp.maximum(l_ref[:], 1e-30)
        out = acc_ref[:] / l
        if causal:
            # rows with nothing visible (m never rose): out 0,
            # lse -> -1e30, matching _dense_forward
            out = jnp.where(m <= _NEG_INF * 0.5, 0.0, out)
        o_ref[:] = out.astype(o_ref.dtype)
        if with_lse:
            lse_ref[:] = m + jnp.log(l)


def _dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc_ref, *, scale: float, causal: bool):
    # grid (B·H, n_q, n_kv), dQ carried in scratch across the kv axis.
    # dS = P * (dO·Vᵀ − Δ); dQ = scale · dS·K, with P recomputed from the
    # saved per-row logsumexp (no (T,T) matrix ever materialized).
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)
    q_start_g = offs_ref[0] + pl.program_id(1) * block_q
    k_start_g = offs_ref[1] + ki * block_k

    @pl.when(ki == 0)
    def _():
        dq_acc_ref[:] = jnp.zeros((block_q, d), jnp.float32)

    visible = (k_start_g <= q_start_g + block_q - 1) if causal else True

    @pl.when(visible)
    def _():
        # native-dtype matmul operands (see _kernel); s must be computed
        # exactly as the forward computed it or P diverges from lse
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        do = do_ref[:]
        lse = lse_ref[:]      # (BLOCK_Q, 1)
        delta = delta_ref[:]  # (BLOCK_Q, 1)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, q_start_g, k_start_g)
        p = jnp.exp(s - lse)
        if causal:
            # dead rows have lse=-1e30, where exp(s - lse) = 1 on masked
            # entries; match _dense_backward's explicit zero
            p = jnp.where(s > _NEG_INF * 0.5, p, 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc_ref[:] = dq_acc_ref[:] + jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    @pl.when(ki == n_kv - 1)
    def _():
        dq_ref[:] = (dq_acc_ref[:] * scale).astype(dq_ref.dtype)


def _fused_bwd_kernel(offs_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref,
                      v_ref, dk_ref, dv_ref, dqp_ref, dk_acc_ref, dv_acc_ref,
                      *, scale: float, causal: bool, n_q: int):
    # Fused backward: the _dkv_kernel walk — grid (B·Hkv, n_kv,
    # group·n_q) — with ONE extra matmul per visible pair (dS·K), whose
    # result is this pair's dQ contribution, written to its own slot of
    # a (n_kv, B·H, Tq, D) partial slab and summed outside. This
    # replaces the whole separate dQ pass: the two-pass backward runs 7
    # block matmuls per visible pair (S and dP are recomputed in BOTH
    # passes), the fused one runs 5 — the theoretical-minimum FLOP count
    # (Dao 2023 §B) — at the cost of the slab's HBM round-trip (written
    # in the inputs' dtype to halve it). Causality: invisible (fully
    # future-q) steps skip compute AND the slab write; their slots are
    # never targeted (the clamped q index map points them at the first
    # visible block, whose own later step overwrites before flush), and
    # the outside sum masks never-written slots analytically.
    block_k, d = k_ref.shape
    block_q = q_ref.shape[0]
    j = pl.program_id(2)
    qi = lax.rem(j, n_q)
    q_start_g = offs_ref[0] + qi * block_q
    k_start_g = offs_ref[1] + pl.program_id(1) * block_k

    @pl.when(j == 0)
    def _():
        dk_acc_ref[:] = jnp.zeros((block_k, d), jnp.float32)
        dv_acc_ref[:] = jnp.zeros((block_k, d), jnp.float32)

    visible = (q_start_g + block_q - 1 >= k_start_g) if causal else True

    @pl.when(visible)
    def _():
        q = q_ref[:]
        do = do_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        lse = lse_ref[:]
        delta = delta_ref[:]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, q_start_g, k_start_g)
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(s > _NEG_INF * 0.5, p, 0.0)
        dv_acc_ref[:] = dv_acc_ref[:] + jnp.dot(
            p.astype(do.dtype).T, do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc_ref[:] = dk_acc_ref[:] + jnp.dot(
            ds.astype(q.dtype).T, q, preferred_element_type=jnp.float32
        )
        dqp_ref[:] = (jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        ) * scale).astype(dqp_ref.dtype)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dk_ref[:] = (dk_acc_ref[:] * scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_acc_ref[:].astype(dv_ref.dtype)


def _dkv_kernel(offs_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, scale: float,
                causal: bool, n_q: int):
    # grid (B·Hkv, n_kv, group·n_q): axis 2 walks every (q head of this
    # kv head's group, q block) pair — j = g_idx·n_q + qi — with dK/dV
    # carried in scratch across the WHOLE axis, so GQA's cross-head
    # gradient sum happens in the same accumulator as the q-block walk.
    # dV = Pᵀ·dO; dK = scale · dSᵀ·Q. Causal: query blocks strictly
    # before this K block see none of it — skipped via pl.when.
    block_k, d = k_ref.shape
    block_q = q_ref.shape[0]
    j = pl.program_id(2)
    qi = lax.rem(j, n_q)
    q_start_g = offs_ref[0] + qi * block_q
    k_start_g = offs_ref[1] + pl.program_id(1) * block_k

    @pl.when(j == 0)
    def _():
        dk_acc_ref[:] = jnp.zeros((block_k, d), jnp.float32)
        dv_acc_ref[:] = jnp.zeros((block_k, d), jnp.float32)

    visible = (q_start_g + block_q - 1 >= k_start_g) if causal else True

    @pl.when(visible)
    def _():
        # native-dtype matmul operands (see _kernel)
        q = q_ref[:]
        do = do_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        lse = lse_ref[:]
        delta = delta_ref[:]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, q_start_g, k_start_g)
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(s > _NEG_INF * 0.5, p, 0.0)
        dv_acc_ref[:] = dv_acc_ref[:] + jnp.dot(
            p.astype(do.dtype).T, do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc_ref[:] = dk_acc_ref[:] + jnp.dot(
            ds.astype(q.dtype).T, q, preferred_element_type=jnp.float32
        )

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dk_ref[:] = (dk_acc_ref[:] * scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_acc_ref[:].astype(dv_ref.dtype)


def _fit_block(block, t):
    """Pow2 block fitting, floored at the 128 lane width — shared rule
    in :mod:`hpc_patterns_tpu.ops.tiling` (streamed kernels want big
    blocks; lengths that no 128-multiple divides still fail validation
    — pad upstream)."""
    return fit_block_pow2(block, t)


def _resolve(Tq, Tk, D, scale, block_q, block_k, interpret, kernel, *,
             validate=True):
    """Resolve the shared per-call parameters (scale default, block
    fitting, interpret default). ``validate=False`` for the backward,
    whose shapes the forward already validated — the resolution logic
    must stay common so fwd and bwd never disagree on block sizes.

    ``block_q``/``block_k`` of None pick the defaults (512, 1024).
    Builder-measured on an older toolchain (ROADMAP.md Design 9): a
    standalone kernel microbench preferred (512, 512) at T=2048 and
    (512, 2048) at T=8192, and both LOST inside the full train step,
    where the kernel competes with the surrounding matmuls for VMEM
    and scheduling. Trust the end-to-end number, not the microbench.
    """
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if block_q is None:
        block_q = 512
    if block_k is None:
        block_k = 1024
    block_q = _fit_block(block_q, Tq)
    block_k = _fit_block(block_k, Tk)
    if validate and (Tq % block_q or Tk % block_k):
        raise ValueError(
            f"seq ({Tq}, {Tk}) must divide by blocks ({block_q}, {block_k})"
        )
    return (float(scale), block_q, block_k,
            resolve_interpret(interpret, kernel))


def _to_kernel_layout(x):
    B, T, H, D = x.shape
    return jnp.einsum("bthd->bhtd", x).reshape(B * H, T, D)


def _expand_rows(xr, B, Hkv, group):
    """Expand kernel-layout (B·Hkv, T, D) rows to (B·H, T, D) by group
    repetition — ONLY for the dense interpret-mode mirrors; the kernels
    themselves read the narrow array through their index maps."""
    if group == 1:
        return xr
    _, T, D = xr.shape
    return jnp.repeat(
        xr.reshape(B, Hkv, T, D), group, axis=1
    ).reshape(B * Hkv * group, T, D)


def _align_vma(*arrays):
    """Bring every array to the union of their varying-mesh-axes sets
    (``lax.pcast`` to varying), so the kernels work inside ``shard_map``
    (check_vma=True) even when some inputs — e.g. the constant zero
    offsets — are replicated. Returns (arrays, union_vma); outside
    ``shard_map`` the union is empty and nothing is cast."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in arrays))
    out = tuple(
        lax.pcast(x, tuple(vma - jax.typeof(x).vma), to="varying")
        if vma - jax.typeof(x).vma else x
        for x in arrays
    )
    return out, vma


def _sds(shape, dtype, vma):
    """``pallas_call`` out_shape entry declaring its varying mesh axes
    (``shard_map``'s vma check refuses an undeclared one)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _masked_scores(qr, kr, offs, scale, causal, mask_block: int = 1,
                   window: int = 0):
    """(N, Tq, Tk) scaled scores with the global causal mask — the dense
    mirror of the kernels' per-block ``_causal_mask`` walk."""
    s = jnp.einsum(
        "ntd,nsd->nts", qr.astype(jnp.float32), kr.astype(jnp.float32)
    ) * scale
    if causal:
        q_pos = offs[0] + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        k_pos = offs[1] + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if mask_block > 1:
            q_pos = q_pos // mask_block * mask_block + (mask_block - 1)
        keep = k_pos <= q_pos
        if window:
            keep = keep & (k_pos > q_pos - window)
        s = jnp.where(keep, s, _NEG_INF)
    return s


def _dense_forward(qr, kr, vr, offs, *, causal, scale, need_lse, out_dtype,
                   mask_block: int = 1, window: int = 0):
    """jnp mirror of ``_kernel`` (same clamps and dead-row semantics),
    used where Pallas interpret mode can't run — inside ``shard_map`` on
    CPU (its vma tracking rejects kernel-internal constants). Real-TPU
    execution always takes the kernel path. Numerics match the kernel
    exactly for f32 inputs; for bf16 inputs the kernel's native-dtype
    matmuls round p to bf16 where this mirror keeps f32 — equal only to
    bf16 precision."""
    s = _masked_scores(qr, kr, offs, scale, causal, mask_block, window)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m) * (s > _NEG_INF / 2)  # fully-masked rows stay 0
    l = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    outr = (
        jnp.einsum("nts,nsd->ntd", p, vr.astype(jnp.float32)) / l
    ).astype(out_dtype)
    lse = (m + jnp.log(l)) if need_lse else None
    return outr, lse


def _dense_backward(qr, kr, vr, dor, lse, delta, offs, *, causal, scale):
    """jnp mirror of ``_dq_kernel``/``_dkv_kernel`` (same P recompute from
    lse and the same Δ shift); see ``_dense_forward`` for when it runs
    and the bf16-input precision caveat."""
    s = _masked_scores(qr, kr, offs, scale, causal)
    p = jnp.exp(s - lse) * (s > _NEG_INF / 2)
    dp = jnp.einsum(
        "ntd,nsd->nts", dor.astype(jnp.float32), vr.astype(jnp.float32)
    )
    ds = p * (dp - delta)
    dq = jnp.einsum("nts,nsd->ntd", ds, kr.astype(jnp.float32)) * scale
    dk = jnp.einsum("nts,ntd->nsd", ds, qr.astype(jnp.float32)) * scale
    dv = jnp.einsum("nts,ntd->nsd", p, dor.astype(jnp.float32))
    return dq.astype(qr.dtype), dk.astype(kr.dtype), dv.astype(vr.dtype)


def _forward_impl(q, k, v, offs, *, causal, scale, block_q, block_k,
                  interpret, need_lse, mask_block: int = 1, window: int = 0):
    """Shared forward. ``offs``: (1, 2) int32 [q_offset, k_offset].
    Returns (out, residuals) — residuals in kernel layout (B·H, T, D),
    lse (B·H, Tq, 1) f32; both None-lse when ``need_lse`` is False (the
    inference path skips the lse work entirely). ``mask_block`` > 1: the
    block mask (:func:`_causal_mask`), offsets 0."""
    if q.ndim != 4:
        raise ValueError(f"want (batch, seq, heads, head_dim), got {q.shape}")
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    Hkv = k.shape[2]
    if H % max(Hkv, 1) or v.shape[2] != Hkv:
        raise ValueError(
            f"kv heads {Hkv}/{v.shape[2]} must match and divide "
            f"n_heads {H} (GQA streams the narrow K/V)"
        )
    group = H // Hkv
    scale, block_q, block_k, interpret = _resolve(
        Tq, Tk, D, scale, block_q, block_k, interpret, "flash_attention.fwd"
    )

    qr, kr, vr = map(_to_kernel_layout, (q, k, v))

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, with_lse=need_lse,
        **({"mask_block": mask_block} if mask_block > 1 else {}),
        **({"window": window} if window else {}),
    )
    # index maps see the prefetched offsets: for causal, clamp the kv
    # block index to the last visible block — consecutive clamped steps
    # revisit the same block, so Pallas elides the HBM fetch entirely
    kv_idx = _kv_index_map(block_q, block_k, causal, H, Hkv, window)
    blk_q = pl.BlockSpec((None, block_q, D),
                         lambda bh, qi, ki, offs: (bh, qi, 0),
                         memory_space=pltpu.VMEM)
    blk_k = pl.BlockSpec((None, block_k, D), kv_idx,
                         memory_space=pltpu.VMEM)
    (offs, qr, kr, vr), vma = _align_vma(offs, qr, kr, vr)
    if interpret and vma:
        kr_e = _expand_rows(kr, B, Hkv, group)
        vr_e = _expand_rows(vr, B, Hkv, group)
        outr, lse = _dense_forward(qr, kr_e, vr_e, offs, causal=causal,
                                   scale=scale, need_lse=need_lse,
                                   out_dtype=q.dtype, mask_block=mask_block,
                                   window=window)
        out = outr.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)
        return out, (qr, kr, vr, outr, lse)
    out_specs = [blk_q]
    out_shape = [_sds((B * H, Tq, D), q.dtype, vma)]
    if need_lse:
        out_specs.append(
            pl.BlockSpec((None, block_q, 1),
                         lambda bh, qi, ki, offs: (bh, qi, 0),
                         memory_space=pltpu.VMEM)
        )
        out_shape.append(
            _sds((B * H, Tq, 1), jnp.float32, vma)
        )

    results = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, Tq // block_q, Tk // block_k),
            in_specs=[blk_q, blk_k, blk_k],
            out_specs=tuple(out_specs),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
                pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
                pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
            ],
        ),
        out_shape=tuple(out_shape),
        name="flash_fwd",
        interpret=interpret,
    )(offs, qr, kr, vr)
    outr = results[0]
    out = outr.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)  # -> (B, Tq, H, D)
    lse = results[1] if need_lse else None
    return out, (qr, kr, vr, outr, lse)


# The fused backward materializes a (n_kv, B·H, Tq, D) partial-dQ slab;
# above this byte budget the two-pass backward (no slab, more FLOPs) is
# the memory-safe automatic choice. 1.5 GiB measured against a 16 GiB
# chip: the T=32k flagship step fits with the (512, 2048) ladder rung's
# 1.07 GiB slab but OOMs by ~270 MiB with the 2.15 GiB (1024, 1024)
# slab. Overridable per call via ``bwd``, or globally via
# HPCPAT_FLASH_BWD_SLAB_LIMIT (bytes; 0 forces two-pass).
_FUSED_SLAB_LIMIT = int(
    os.environ.get("HPCPAT_FLASH_BWD_SLAB_LIMIT", 3 << 29)
)


def _backward_impl(qr, kr, vr, outr, lse, offs, g, g_lse, *, causal, scale,
                   block_q, block_k, interpret, bwd=None,
                   block_q_bwd=None, block_k_bwd=None):
    """Shared backward. ``g``: (B, Tq, H, D) out-cotangent; ``g_lse``:
    (B, Tq, H) lse-cotangent or None. Returns (dq, dk, dv) user-layout
    (dk/dv with the narrow kv head count — the group sum happens in the
    dkv kernel's accumulator). ``bwd``: "fused" (single pass, 5 block
    matmuls + partial-dQ slab), "split" (dQ pass + dK/dV pass, 7 block
    matmuls, O(T·D) extra memory only), or None/"auto" (fused when the
    slab fits _FUSED_SLAB_LIMIT)."""
    B, Tq, H, D = g.shape
    Tk = kr.shape[1]
    Hkv = kr.shape[0] // B
    group = H // Hkv
    # the backward has its own block-size optimum: the fused kernel's
    # 5-matmul body amortizes best at (1024, 1024) (measured on chip at
    # T=8192: 135 TF/s vs 125 at the forward's (512, 1024)); callers may
    # still pin both passes via block_q_bwd/block_k_bwd. When the
    # partial-dQ slab at that shape would bust the memory budget, the
    # auto ladder steps to (512, 2048) — doubling block_k halves the
    # slab (fewer kv chunks), and block_q must drop to keep the kernel
    # inside VMEM — before giving up and going two-pass.
    # the heuristic only fires when the caller pinned NOTHING: an
    # explicit forward block_q or block_k carries into the backward
    # (the resolve below falls back to them), and block_q_bwd/block_k_bwd
    # pin the backward outright
    if block_q_bwd is None and block_q is None and block_k is None:
        block_q_bwd = 1024
        if bwd in (None, "auto", "fused") and Tk >= 4096:
            slab_at = lambda bk: (Tk // bk) * B * H * Tq * D *                 jnp.dtype(qr.dtype).itemsize
            if slab_at(1024) > _FUSED_SLAB_LIMIT:
                # take the rung whenever the (1024,1024) slab busts the
                # budget — halving the slab either fits directly or
                # halves the q-chunk count (each chunk re-streams K/V,
                # so fewer chunks beats smaller ones); (512,2048) also
                # measured FASTER standalone at long T (137 vs 133 TF/s
                # at T=16k)
                block_q_bwd, block_k_bwd = 512, 2048
    scale, block_q, block_k, interpret = _resolve(
        Tq, Tk, D, scale,
        block_q if block_q_bwd is None else block_q_bwd,
        block_k if block_k_bwd is None else block_k_bwd,
        interpret, "flash_attention.bwd", validate=False,
    )

    dor = _to_kernel_layout(g)
    delta = jnp.sum(
        dor.astype(jnp.float32) * outr.astype(jnp.float32),
        axis=-1, keepdims=True,
    )  # (B·H, Tq, 1) — trailing unit dim keeps TPU block shapes legal
    if g_lse is not None:
        # d lse/d s = P, so the lse cotangent enters ds = P∘(dP − Δ + ḡ)
        # — i.e. it just shifts Δ.
        delta = delta - jnp.einsum("bth->bht", g_lse).reshape(B * H, Tq, 1)

    (offs, qr, kr, vr, dor, lse, delta), vma = _align_vma(
        offs, qr, kr, vr, dor, lse, delta
    )
    if interpret and vma:
        kr_e = _expand_rows(kr, B, Hkv, group)
        vr_e = _expand_rows(vr, B, Hkv, group)
        dq, dk, dv = _dense_backward(qr, kr_e, vr_e, dor, lse, delta, offs,
                                     causal=causal, scale=scale)
        if group > 1:  # fold the per-q-head contributions into kv heads
            dk = dk.reshape(B, Hkv, group, Tk, D).sum(2).reshape(-1, Tk, D)
            dv = dv.reshape(B, Hkv, group, Tk, D).sum(2).reshape(-1, Tk, D)
        back = lambda x, h, t: x.reshape(B, h, t, D).transpose(0, 2, 1, 3)
        return back(dq, H, Tq), back(dk, Hkv, Tk), back(dv, Hkv, Tk)
    if bwd not in (None, "auto", "fused", "split"):
        raise ValueError(f"bwd {bwd!r} not in (None, 'auto', 'fused', 'split')")
    n_q = Tq // block_q
    n_kv = Tk // block_k
    slab_bytes = n_kv * B * H * Tq * D * jnp.dtype(qr.dtype).itemsize
    # q-chunking: when the whole-Tq slab busts the budget, run the fused
    # kernel over static query-range chunks — each call's slab is
    # slab/nc, dK/dV accumulate across calls, and causal fetch-elision
    # means early chunks never touch their future K/V blocks (the extra
    # cost is re-streaming K/V once per chunk). This keeps the 5-matmul
    # backward available at 65k+ context where one slab cannot fit.
    n_chunks = 1
    if bwd in (None, "auto", "fused") and slab_bytes > _FUSED_SLAB_LIMIT:
        while (slab_bytes // n_chunks > _FUSED_SLAB_LIMIT
               and n_chunks < 16
               and Tq % (2 * n_chunks) == 0
               and (Tq // (2 * n_chunks)) % block_q == 0):
            n_chunks *= 2
    use_fused = bwd == "fused" or (
        bwd in (None, "auto")
        and slab_bytes // n_chunks <= _FUSED_SLAB_LIMIT
    )
    row = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    kv_idx = _kv_index_map(block_q, block_k, causal, H, Hkv)
    q_idx = _q_index_map(block_q, block_k, causal, n_q, H, Hkv)
    # grid (B·H, n_q, n_kv): q-indexed blocks follow axis 1, kv axis 2
    q_on1 = row((None, block_q, D), lambda bh, qi, ki, offs: (bh, qi, 0))
    k_on2 = row((None, block_k, D), kv_idx)
    vec_on1 = row((None, block_q, 1), lambda bh, qi, ki, offs: (bh, qi, 0))
    # grid (B·Hkv, n_kv, group·n_q): kv-indexed blocks follow axis 1,
    # the (q head of the group, q block) walk axis 2
    k_on1 = row((None, block_k, D), lambda bkv, ki, j, offs: (bkv, ki, 0))
    q_on2 = row((None, block_q, D), q_idx)
    vec_on2 = row((None, block_q, 1),
                  lambda bkv, ki, j, offs: q_idx(bkv, ki, j, offs))

    if use_fused:
        Tq_c = Tq // n_chunks
        n_q_c = Tq_c // block_q
        q_idx_c = _q_index_map(block_q, block_k, causal, n_q_c, H, Hkv)
        q_on2c = row((None, block_q, D), q_idx_c)
        vec_on2c = row((None, block_q, 1),
                       lambda bkv, ki, j, offs: q_idx_c(bkv, ki, j, offs))

        def dqp_idx(bkv, ki, j, offs):
            r, qi, _ = q_idx_c(bkv, ki, j, offs)
            return ki, r, qi, 0

        fused_call = pl.pallas_call(
            functools.partial(_fused_bwd_kernel, scale=scale, causal=causal,
                              n_q=n_q_c),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B * Hkv, n_kv, group * n_q_c),
                in_specs=[q_on2c, q_on2c, vec_on2c, vec_on2c, k_on1, k_on1],
                out_specs=(k_on1, k_on1,
                           row((None, None, block_q, D), dqp_idx)),
                scratch_shapes=[
                    pltpu.VMEM((block_k, D), jnp.float32),
                    pltpu.VMEM((block_k, D), jnp.float32),
                ],
            ),
            out_shape=(
                _sds((B * Hkv, Tk, D), kr.dtype, vma),
                _sds((B * Hkv, Tk, D), vr.dtype, vma),
                _sds((n_kv, B * H, Tq_c, D), qr.dtype, vma),
            ),
            name="flash_bwd_fused",
            interpret=interpret,
        )

        dq_parts = []
        dk_acc = dv_acc = None
        for i in range(n_chunks):
            lo = i * Tq_c
            offs_i = offs + jnp.array([lo, 0], jnp.int32)
            dk_i, dv_i, dqp = fused_call(
                offs_i, qr[:, lo:lo + Tq_c], dor[:, lo:lo + Tq_c],
                lse[:, lo:lo + Tq_c], delta[:, lo:lo + Tq_c], kr, vr,
            )
            if causal:
                # a slab slot (ki, ·, t, ·) was written iff the q block
                # holding row t can see kv block ki; never-written slots
                # hold whatever HBM held (possibly NaN) — select, not
                # multiply
                q_end_g = offs_i[0] + (
                    lax.iota(jnp.int32, Tq_c) // block_q + 1
                ) * block_q - 1
                k_start_g = offs[1] + lax.iota(jnp.int32, n_kv) * block_k
                written = q_end_g[None, :] >= k_start_g[:, None]
                dqp = jnp.where(written[:, None, :, None], dqp, 0)
            dq_parts.append(dqp.astype(jnp.float32).sum(0).astype(qr.dtype))
            if dk_acc is None:
                dk_acc, dv_acc = (dk_i.astype(jnp.float32),
                                  dv_i.astype(jnp.float32))
            else:
                dk_acc = dk_acc + dk_i.astype(jnp.float32)
                dv_acc = dv_acc + dv_i.astype(jnp.float32)
        dq = (dq_parts[0] if n_chunks == 1
              else jnp.concatenate(dq_parts, axis=1))
        dk = dk_acc.astype(kr.dtype)
        dv = dv_acc.astype(vr.dtype)
        back = lambda x, h, t: x.reshape(B, h, t, D).transpose(0, 2, 1, 3)
        return back(dq, H, Tq), back(dk, Hkv, Tk), back(dv, Hkv, Tk)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, Tq // block_q, Tk // block_k),
            in_specs=[q_on1, k_on2, k_on2, q_on1, vec_on1, vec_on1],
            out_specs=q_on1,
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        ),
        out_shape=_sds((B * H, Tq, D), qr.dtype, vma),
        name="flash_bwd_dq",
        interpret=interpret,
    )(offs, qr, kr, vr, dor, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, n_q=n_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * Hkv, Tk // block_k, group * n_q),
            in_specs=[q_on2, q_on2, vec_on2, vec_on2, k_on1, k_on1],
            out_specs=(k_on1, k_on1),
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
        ),
        out_shape=(
            _sds((B * Hkv, Tk, D), kr.dtype, vma),
            _sds((B * Hkv, Tk, D), vr.dtype, vma),
        ),
        name="flash_bwd_dkv",
        interpret=interpret,
    )(offs, qr, dor, lse, delta, kr, vr)

    back = lambda x, h, t: x.reshape(B, h, t, D).transpose(0, 2, 1, 3)
    return back(dq, H, Tq), back(dk, Hkv, Tk), back(dv, Hkv, Tk)


def _zero_offs():
    return jnp.zeros((2,), jnp.int32)


# ---------------------------------------------------------------- square


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash_with_vjp(q, k, v, causal, scale, block_q, block_k, interpret, bwd,
                    block_q_bwd, block_k_bwd):
    out, _ = _forward_impl(q, k, v, _zero_offs(), causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret, need_lse=False)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret, bwd,
               block_q_bwd, block_k_bwd):
    out, residuals = _forward_impl(q, k, v, _zero_offs(), causal=causal,
                                   scale=scale, block_q=block_q,
                                   block_k=block_k, interpret=interpret,
                                   need_lse=True)
    return out, residuals


def _flash_bwd(causal, scale, block_q, block_k, interpret, bwd,
               block_q_bwd, block_k_bwd, residuals, g):
    qr, kr, vr, outr, lse = residuals
    return _backward_impl(qr, kr, vr, outr, lse, _zero_offs(), g, None,
                          causal=causal, scale=scale, block_q=block_q,
                          block_k=block_k, interpret=interpret, bwd=bwd,
                          block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd)


_flash_with_vjp.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    bwd: str | None = None,
    block_q_bwd: int | None = None,
    block_k_bwd: int | None = None,
    mask_block: int = 1,
    window: int = 0,
):
    """Softmax attention over (batch, seq, heads, head_dim) inputs.

    Numerically equal to parallel.ring_attention.full_attention (the
    oracle in tests); O(block) VMEM instead of the (T, T) score matrix.
    Sequence length must divide by the block sizes (pad upstream — the
    model keeps T a multiple of 128). Differentiable: custom VJP whose
    backward is two blockwise Pallas kernels (dQ pass, dK/dV pass)
    recomputing P from the forward's saved logsumexp — O(block) VMEM in
    both directions.

    ``mask_block`` > 1 (a power of two up to 128, so that it divides the
    tiles): the BLOCK mask in place of the causal one, position i sees j
    iff ``j // mask_block <= i // mask_block``: the whole blocks before
    it and all of its own. Forward only (no VJP is defined for it).

    ``window`` > 0 (causal): position i sees i - window < j <= i alone; a
    K/V tile wholly behind a query tile's window is neither fetched nor
    computed. Forward only, as ``mask_block``.
    """
    if window or mask_block > 1:   # the forward-only masks
        if window and (not causal or mask_block > 1 or window < 1):
            raise ValueError(
                f"window {window}: a positive width with causal=True and "
                "no block mask")
        if not causal or mask_block > 128 or mask_block & (mask_block - 1):
            raise ValueError(
                f"mask_block {mask_block}: a power of two up to 128, with "
                "causal=True (it widens the causal mask to whole blocks)")
        return _forward_impl(q, k, v, _zero_offs(), causal=True, scale=scale,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret, need_lse=False,
                             mask_block=mask_block, window=window)[0]
    return _flash_with_vjp(q, k, v, causal, scale, block_q, block_k,
                           interpret, bwd, block_q_bwd, block_k_bwd)


# ----------------------------------------------------------------- block


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11)
)
def _flash_block_with_vjp(q, k, v, offs_i, causal, scale, block_q, block_k,
                          interpret, bwd, block_q_bwd, block_k_bwd):
    offs = offs_i.reshape(2)
    out, (_, _, _, _, lse) = _forward_impl(
        q, k, v, offs, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, need_lse=True,
    )
    B, Tq, H, _ = q.shape
    lse_user = jnp.einsum("bht->bth", lse.reshape(B, H, Tq))
    return out, lse_user


def _flash_block_fwd(q, k, v, offs_i, causal, scale, block_q, block_k,
                     interpret, bwd, block_q_bwd, block_k_bwd):
    offs = offs_i.reshape(2)
    out, residuals = _forward_impl(
        q, k, v, offs, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, need_lse=True,
    )
    B, Tq, H, _ = q.shape
    lse = residuals[4]
    lse_user = jnp.einsum("bht->bth", lse.reshape(B, H, Tq))
    return (out, lse_user), (*residuals, offs)


def _flash_block_bwd(causal, scale, block_q, block_k, interpret, bwd,
                     block_q_bwd, block_k_bwd, residuals, g):
    qr, kr, vr, outr, lse, offs = residuals
    g_out, g_lse = g
    dq, dk, dv = _backward_impl(
        qr, kr, vr, outr, lse, offs, g_out, g_lse, causal=causal,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret,
        bwd=bwd, block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd,
    )
    # offsets are integer positions: their cotangent is the symbolic
    # float0 zero (also exempt from shard_map's varying-axes check)
    return dq, dk, dv, np.zeros((2,), jax.dtypes.float0)


_flash_block_with_vjp.defvjp(_flash_block_fwd, _flash_block_bwd)


def flash_attention_block(
    q,
    k,
    v,
    q_offset,
    k_offset,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    bwd: str | None = None,
    block_q_bwd: int | None = None,
    block_k_bwd: int | None = None,
):
    """One *partial* attention: local queries ``q`` (global position
    ``q_offset``) against one visiting K/V block (global position
    ``k_offset``); Tq and Tk may differ. Returns ``(out, lse)`` —
    the softmax attention restricted to this block, normalized within
    it, plus the per-row logsumexp (B, Tq, H) f32 — so partials over
    disjoint K/V blocks merge exactly:

        m = max(lse_a, lse_b); e_x = exp(lse_x - m)
        out = (e_a·out_a + e_b·out_b) / (e_a + e_b);  lse = m + log(e_a+e_b)

    This is the per-step compute of ring attention (the reference's
    ring exchange-accumulate, allreduce-mpi-sycl.cpp:173-182, with
    attention as the combine). Offsets may be traced (e.g. derived from
    ``axis_index`` inside shard_map). A fully-future block (causal,
    k_offset > all query positions) skips all fetches/matmuls and
    returns out=0, lse≈-1e30, which the merge weights to zero.
    Differentiable in q, k, v, including gradient flow through lse.
    (A fully-future block's fetches and matmuls are skipped, not just
    masked.)
    """
    offs_i = jnp.stack([
        jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)
    ])
    return _flash_block_with_vjp(q, k, v, offs_i, causal, scale, block_q,
                                 block_k, interpret, bwd,
                                 block_q_bwd, block_k_bwd)
