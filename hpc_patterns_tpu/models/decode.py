"""Autoregressive decoding with a KV cache for the flagship model.

Completes the model lifecycle (train → checkpoint → serve): a batched
``prefill`` over the prompt, then a jitted single-token ``decode_step``
against a static-shape KV cache, composed by ``greedy_generate`` into a
``lax.scan`` decode loop — no data-dependent Python control flow, one
compilation for the whole generation (the XLA ground rule).

TPU-shaped choices:

- the cache is per-layer (batch, kv_heads, max_len, head_dim) buffers
  in the compute dtype (or int8 + per-row scales, kv_cache_dtype) —
  KERNEL layout, sequence contiguous per (batch, kv
  head) row — written in place with ``dynamic_update_slice`` under a
  donated jit; steady-state HBM traffic is the cache read, not a
  re-materialization;
- grouped-query attention pays off here: the cache stores ``kv_heads``
  (not ``n_heads``) heads, and decode attends with GROUPED queries
  against the unexpanded cache — both the memory and the per-step
  bandwidth saving GQA exists for;
- decode attention is the flash-decode Pallas kernel by default
  (ops/flash_decode.py): one streamed pass over the cache whose HBM
  traffic is proportional to the fill POSITION (blocks past it are
  never fetched — clamped index map), vs the XLA gather path that
  reads all of max_len and masks. ``cfg.decode_attn = "gather"`` keeps
  the einsum path: position masking over the full cache, static
  shapes — the partitioning-friendly form sharded (tp) serving needs
  (GSPMD splits einsums; it cannot split a pallas_call);
- MoE decode routes drop-free (capacity = token count): training-time
  capacity drops are load-balance pressure over B·T competing tokens,
  which a decode step doesn't have — and serving must never drop a
  token.

Params are shared verbatim with transformer.forward; under a mesh with
Megatron-sharded params, GSPMD partitions these einsums the same way
(no decode-specific annotations needed for tp).
"""

from __future__ import annotations

import warnings
import weakref
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from hpc_patterns_tpu.harness import trace as tracelib
from hpc_patterns_tpu.models.sharding_util import mesh_axis_size, resolve_spec
from jax import shard_map
from hpc_patterns_tpu.models.transformer import (
    LAYER_KINDS,
    TransformerConfig,
    _rmsnorm,
    apply_rope,
    attn_proj,
    attn_scope,
    gated_mlp,
    head_logits,
    attn_norm,
    layer_params,
    matmul_weight,
    moe_mixer,
    project_qkv,
    routed_mlp,
    scaled,
    scoped,
    ssm_branch,
    ssm_branch_step,
    ssm_mixer,
    ssm_mixer_step,
)
from hpc_patterns_tpu.parallel.ring_attention import full_attention


def _tp_size(mesh, cfg: TransformerConfig) -> int:
    return mesh_axis_size(mesh, cfg.axis_tp) if mesh is not None else 1


def _flash_partition(mesh, cfg: TransformerConfig) -> bool:
    """Can the Pallas decode kernels run tp-sharded on this mesh?

    GSPMD partitions einsums but not a ``pallas_call`` — the round-3
    limitation that forced sharded serving onto the gather path. The
    kernels' head axes are embarrassingly parallel though, so a
    ``shard_map`` manual partition over ``axis_tp`` (contiguous head
    blocks: q head k·g+j stays with kv head k) recovers the flash
    kernels under tp whenever tp divides kv_heads. Returns False (with
    a warning) when it cannot, and the caller keeps the gather path.
    """
    tp = _tp_size(mesh, cfg)
    if tp <= 1:
        return False
    if cfg.kv_heads % tp:
        warnings.warn(
            f"decode: tp size {tp} does not divide kv_heads "
            f"{cfg.kv_heads}; decode_attn='flash' falls back to the "
            "gather path (shard_map needs whole kv-head blocks per "
            "rank) — use a tp that divides kv_heads to keep the kernel",
            stacklevel=3,
        )
        return False
    return True


def _tp_serving_specs(mesh, cfg: TransformerConfig):
    """The ONE definition of the tp manual-partition layout shared by
    the linear and paged kernel routes: ``(row3, block4)`` — 3-D leaves
    (q (B, H, Dh); linear scale rows (B, Hkv, len)) shard dim 1, 4-D
    leaves (linear cache, page pools, scale pools) shard dim 1. Head
    blocks are contiguous, so q head k·g+j stays with kv head k."""
    from jax.sharding import PartitionSpec as PS

    tp = cfg.axis_tp
    return (resolve_spec(PS(None, tp, None), mesh, cfg.mesh_axes),
            resolve_spec(PS(None, tp, None, None), mesh, cfg.mesh_axes))


def _tp_pin_cache(cache, mesh, cfg: TransformerConfig):
    """Constrain every cache/pool leaf kv-head-sharded over tp (dim 1;
    3-D or 4-D leaves — the layout both sharded decode routes consume
    in place). Non-array entries (the page table) pass through."""
    from jax.sharding import NamedSharding

    row3, block4 = _tp_serving_specs(mesh, cfg)
    sh = {3: NamedSharding(mesh, row3), 4: NamedSharding(mesh, block4)}

    def pin(a):
        return (lax.with_sharding_constraint(a, sh[a.ndim])
                if hasattr(a, "ndim") and a.ndim in sh else a)

    return jax.tree.map(pin, cache)


def _flash_route(mesh, cfg: TransformerConfig):
    """(use_flash, flash_sharded): the ONE flash/gather routing decision
    shared by prefill and decode_step — the prompt pass and the step
    pass must always take the same route under the same mesh."""
    flash_sharded = (cfg.decode_attn == "flash"
                     and _flash_partition(mesh, cfg))
    use_flash = cfg.decode_attn == "flash" and (
        _tp_size(mesh, cfg) <= 1 or flash_sharded
    )
    return use_flash, flash_sharded


#: KV storage dtypes carrying per-row dequant scales (the quantized
#: cache family; "compute" stores the model dtype scale-free)
KV_QUANTIZED = ("int8", "fp8")

#: float8_e4m3fn's largest finite value — the fp8 analog of int8's 127
FP8_MAX = 448.0


def _kv_quantized(cfg: TransformerConfig) -> bool:
    return cfg.kv_cache_dtype in KV_QUANTIZED


def _kv_storage_dtype(cfg: TransformerConfig):
    """The dtype KV bytes are STORED in: the compute dtype, int8, or
    float8_e4m3fn — one byte per element for both quantized forms, so
    the pool-byte win is identical; fp8 trades int8's uniform grid for
    a floating one (more headroom inside a row's dynamic range).
    Backends without fp8 support surface through
    :func:`hpc_patterns_tpu.dtypes.supports_fp8` — callers (the
    serving CLIs) degrade to int8 with a note instead of hitting a
    deep XLA lowering error."""
    if cfg.kv_cache_dtype == "int8":
        return jnp.int8
    if cfg.kv_cache_dtype == "fp8":
        return jnp.float8_e4m3fn
    return jnp.dtype(cfg.dtype)


def _quantize_rows(x, kv_dtype: str = "int8"):
    """Per-row symmetric quantization of (..., D) rows: returns
    (quantized values, f32 scales shaped (...,)) with x ~= q * scale.
    ``kv_dtype``: "int8" (round-to-nearest onto the +-127 integer
    grid) or "fp8" (scale the row's amax onto float8_e4m3fn's +-448
    range and let the float cast do the rounding)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    if kv_dtype == "fp8":
        scale = jnp.maximum(amax / FP8_MAX, 1e-8)
        q = (x.astype(jnp.float32)
             / scale[..., None]).astype(jnp.float8_e4m3fn)
    else:
        scale = jnp.maximum(amax / 127.0, 1e-8)
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(cache, scale):
    return cache.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def init_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """Zeroed KV cache: {"k","v"}: PER-LAYER tuples of (B, kv_heads,
    max_len, head_dim) in the compute dtype — or the one-byte storage
    dtype when cfg.kv_cache_dtype is quantized ("int8"/"fp8"), with
    per-row f32 dequant scales in extra "k_scale"/"v_scale" tuples
    (B, kv_heads, max_len), halving the cache bytes vs bf16 —
    (kernel layout: the
    sequence axis contiguous per (batch, kv head) row, what
    ops/flash_decode.py streams). Per-layer arrays — not one stacked
    (L, ...) block — so each decode step's dynamic_update_slice aliases
    its own buffer inside the generation scan's carry: the step's HBM
    traffic is the attention read plus one row write, NOT a rewrite of
    the whole cache (a stacked cache driven through a layer lax.scan
    re-materializes every byte every token — measured 25 ms/token at an
    8k cache where the read cost is ~3 ms). GQA stores kv_heads only —
    the cache is n_heads/kv_heads times smaller than MHA's."""
    dt = _kv_storage_dtype(cfg)
    shape = (batch, cfg.kv_heads, max_len, cfg.head_dim)
    # independent buffers per key AND per layer: sharing one zeros tuple
    # would alias k and v, and a donated jit would then double-donate
    # each buffer (silent copy fallback — exactly the in-place update
    # this layout exists for)
    fresh = lambda sh, d: tuple(jnp.zeros(sh, d)
                                for _ in range(cfg.n_attn_layers))
    cache = {"k": fresh(shape, dt), "v": fresh(shape, dt)}
    if _kv_quantized(cfg):
        # per-row dequant scales ride alongside (tiny: D times smaller)
        cache["k_scale"] = fresh(shape[:-1], jnp.float32)
        cache["v_scale"] = fresh(shape[:-1], jnp.float32)
    cache.update(init_layer_state(cfg, batch))
    return cache


#: cache entries that are per-ROW state of a patterned model's "M" and
#: "H" layers (one row a sequence, beside the K/V of its attention
#: layers, which for "H" are the same layers)
STATE_KEYS = ("conv", "ssm")


def init_layer_state(cfg: TransformerConfig, batch: int) -> dict:
    """What a patterned model's cache holds beside K/V: ``conv`` / ``ssm``,
    one (batch, ...) array a layer that holds state (the convolution's
    tail and the recurrent state S, models/ssm.py), and ``moe_stats``,
    the "E" and "R" layers' route's running sums
    (parallel/moe.ROUTE_STATS; row 0 prefills, row 1 decode steps). Empty
    for the default pattern."""
    out = {}
    if cfg.n_state_layers:
        from hpc_patterns_tpu.models import ssm

        out.update(_state_entries(
            [ssm.init_state(cfg, batch)
             for _ in range(cfg.n_state_layers)]))
    if cfg.n_routed_layers:
        from hpc_patterns_tpu.parallel.moe import ROUTE_STATS

        out["moe_stats"] = jnp.zeros((2, len(ROUTE_STATS)), jnp.int32)
    return out


def _state_entries(pairs) -> dict:
    """The state layers' (conv tail, S) pairs, in layer order, as the
    cache's ``STATE_KEYS`` entries; none gives none."""
    return dict(zip(STATE_KEYS, map(tuple, zip(*pairs)))) if pairs else {}


def _dense_only(cfg: TransformerConfig, what: str) -> None:
    if cfg.layer_pattern:
        raise ValueError(
            f"{what} covers the default layer pattern only (causal, "
            f"dense blocks): a patterned model ({cfg.layer_pattern!r}) "
            "may hold per-row recurrent state, which this route neither "
            "carries nor rewinds, and its layer kinds are not written "
            "here. The multi-position step of an all-'R' model under the "
            "block mask is paged_block_step")


@scoped("mlp")
def _mlp(x, lp, cfg: TransformerConfig):
    dt = x.dtype
    h = _rmsnorm(x, lp["ln2_scale"], cfg.norm_eps)
    if cfg.n_experts:
        from hpc_patterns_tpu.parallel import moe

        *lead, D = h.shape
        flat = h.reshape(-1, D)
        # capacity = token count: serving never drops a token. The
        # training forward's capacity_factor drops are a TRAINING
        # behavior (load-balance pressure over B*T competing tokens);
        # a decode step has no such competition, so drop-free routing is
        # both the correct serving semantic and what makes incremental
        # decode equal a drop-free full forward (test_decode's oracle).
        # capacity = token count stays drop-free for ANY k: a token's k
        # choices hit k DISTINCT experts, so no expert can be assigned
        # more than N tokens
        y, _ = moe.moe_dense(flat, lp["router"], lp["w1"], lp["w2"],
                             capacity=flat.shape[0],
                             top_k=cfg.n_experts_top_k)
        return x + y.reshape(*lead, D).astype(dt)
    h = jax.nn.gelu(jnp.dot(h, matmul_weight(lp, "w1", dt)))
    return x + jnp.dot(h, matmul_weight(lp, "w2", dt))


def prefill(params, prompt, cfg: TransformerConfig, max_len: int,
            mesh=None, last_pos=None):
    """Run the prompt in one batched pass (MXU-shaped, exactly
    transformer.forward's math) while capturing each layer's K/V into a
    fresh cache. Returns (last_logits (B, V) f32, cache); the logits are
    None for a model with ``cfg.block_len`` (generation by diffusion over
    blocks), whose prompt pass runs under the block mask over the prompt's
    WHOLE blocks (``last_pos``: the last position of the last whole block;
    what lies behind it is padding) and feeds no head.

    ``max_len`` sizes the static cache (prompt + planned new tokens,
    <= cfg.max_seq). ``mesh``: tp-sharded serving — the flash prefill
    kernel runs shard_mapped over ``cfg.axis_tp`` and the captured
    cache is constrained kv-head-sharded over tp (what the sharded
    decode steps consume in place).

    ``last_pos``: the BUCKETED-prompt route. A prompt right-padded to a
    bucket length compiles once per bucket instead of once per distinct
    length; causality makes positions < true length independent of the
    padding, so the K/V prefix is exact and only the returned logits
    need redirecting — ``last_pos`` (traced scalar or (B,) int32) picks
    which position's logits come back (default: the last). Padding K/V
    is garbage the caller's position cursor masks until generation
    overwrites it — the same stale-row invariant speculative decoding
    relies on."""
    B, T = prompt.shape
    use_flash, flash_sharded = _flash_route(mesh, cfg)
    if not 0 < T <= max_len <= cfg.max_seq:
        raise ValueError(
            f"need 0 < prompt len {T} <= max_len {max_len} <= "
            f"max_seq {cfg.max_seq}"
        )
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = scaled(params["embed"].astype(dt)[prompt],
                   cfg.embedding_multiplier)
        if cfg.pos_embed == "learned":
            x = x + params["pos_embed"].astype(dt)[:T]

    def attend(hn, lp, ak=None):
        """The normed input's attention before its output projection:
        (o (B, T, H, Dh), K, V). ``ak``: a patterned layer's
        :class:`~hpc_patterns_tpu.models.transformer.AttnKind`."""
        with jax.named_scope(attn_scope(ak)):
            q, k, v = project_qkv(hn, lp, cfg,
                                  None if ak is None else ak.heads)
            if cfg.pos_embed == "rope":
                # the cache stores POST-rope K: a key's rotation depends
                # only on its own (fixed) position, so decode steps never
                # re-rotate history
                pos = jnp.arange(T, dtype=jnp.int32)
                rope = None if ak is None else ak.rope
                q = apply_rope(q, pos, cfg, rope)
                k = apply_rope(k, pos, cfg, rope)
            if ak is not None and ak.window:
                # a window layer: unsharded (a patterned model), the kernel
                # skips the tiles wholly behind every query's window
                if use_flash and T % 128 == 0:
                    from hpc_patterns_tpu.ops import flash_attention

                    return flash_attention(q, k, v, window=ak.window), k, v
                return full_attention(q, k, v, causal=True,
                                      window=ak.window), k, v
            # long prompts go through the flash kernel (the dense oracle
            # materializes the (T, T) scores — an 8k-token prompt would be
            # a 17 GB allocation at B=8); short/ragged prompts and sharded
            # (gather-mode) serving keep the einsum path, which consumes
            # the narrow GQA K/V directly
            # a model that generates by diffusion over blocks prefills under
            # the block mask (unsharded: it is a patterned model)
            mb = {"mask_block": cfg.block_len} if cfg.block_len else {}
            if use_flash and T % 128 == 0:
                from hpc_patterns_tpu.ops import flash_attention

                if flash_sharded:
                    hspec = resolve_spec(P(None, None, cfg.axis_tp, None),
                                         mesh, cfg.mesh_axes)
                    o = shard_map(
                        partial(flash_attention, causal=True), mesh=mesh,
                        in_specs=(hspec, hspec, hspec), out_specs=hspec,
                        check_vma=False,  # pallas_call can't declare vma
                    )(q, k, v)
                else:
                    o = flash_attention(q, k, v, causal=True, **mb)
            else:
                o = full_attention(q, k, v, causal=True, **mb)
            return o, k, v

    @scoped("kv_write")
    def capture(k, v):
        # in kernel layout (B, Hkv, T, D), padded to the static cache
        # length — one transpose at prefill, zero per decode step
        kc = jnp.einsum("bthd->bhtd", k)
        vc = jnp.einsum("bthd->bhtd", v)
        pad = [(0, 0), (0, 0), (0, max_len - T), (0, 0)]
        return jnp.pad(kc, pad).astype(dt), jnp.pad(vc, pad).astype(dt)

    def body(h, lp):
        o, k, v = attend(attn_norm(h, lp, cfg), lp)
        h = h + attn_proj(o, lp, cfg, dt)
        return _mlp(h, lp, cfg), capture(k, v)

    rows_last = lambda: jnp.broadcast_to(
        jnp.asarray(last_pos, jnp.int32), (B,))
    extra = {}
    if cfg.layer_pattern:
        last = None if last_pos is None else rows_last()
        # by the layer's kind: "M" and "H" layers hand back the state at
        # the prompt's TRUE last position, "E" layers route the true
        # tokens alone (a bucket's padding picks no expert)
        valid = (None if last is None else
                 jnp.arange(T, dtype=jnp.int32)[None, :] <= last[:, None])
        ks, vs, states, stats = [], [], [], []
        for kind, lp in zip(cfg.layer_pattern, params["layers"]):
            if kind in "*RDW":   # attention, then the kind's MLP
                ak = cfg.attn_kind(kind)
                hn = attn_norm(x, lp, cfg, ak)
                o, k, v = attend(hn, lp, ak)
                x = x + attn_proj(o, lp, cfg, dt, ak, hn)
                kc, vc = capture(k, v)
                mlp = LAYER_KINDS[kind].mlp
                if mlp == "routed":
                    x, st = routed_mlp(x, lp, cfg, valid)
                    stats.append(st)
                elif mlp == "gated":
                    x = gated_mlp(x, lp, cfg)
            elif kind == "M":
                x, st = ssm_mixer(x, lp, cfg, last)
            elif kind == "E":
                x, st = moe_mixer(x, lp, cfg, valid)
                stats.append(st)
            else:   # "H": attention and the recurrence off one norm
                hn = attn_norm(x, lp, cfg)
                o, k, v = attend(hn, lp)
                m, st = ssm_branch(hn, lp, cfg, last)
                x = gated_mlp(x + attn_proj(o, lp, cfg, dt) + m, lp, cfg)
                kc, vc = capture(k, v)
            if LAYER_KINDS[kind].kv:
                ks.append(kc)
                vs.append(vc)
            if LAYER_KINDS[kind].state:
                states.append(st)
        extra = _state_entries(states)
        if stats:
            extra["moe_stats"] = jnp.stack(
                [sum(stats), jnp.zeros_like(stats[0])])
    else:
        x, (ks, vs) = lax.scan(body, x, params["layers"])
    if cfg.block_len:
        # the first block is denoised from the stored K/V: no position
        # of the prompt pass predicts a token that anyone reads
        logits = None
    else:
        with jax.named_scope("head"):
            x = _rmsnorm(x, params["ln_f_scale"], cfg.norm_eps)
            if last_pos is None:
                x_last = x[:, -1]
            else:
                x_last = jnp.take_along_axis(
                    x, rows_last()[:, None, None], axis=1)[:, 0]
            logits = head_logits(x_last, params, cfg)
    L = cfg.n_attn_layers
    if _kv_quantized(cfg):
        kvd = cfg.kv_cache_dtype
        kq, ksc = zip(*(_quantize_rows(ks[l], kvd) for l in range(L)))
        vq, vsc = zip(*(_quantize_rows(vs[l], kvd) for l in range(L)))
        cache = {
            "k": tuple(kq), "v": tuple(vq),
            "k_scale": tuple(ksc), "v_scale": tuple(vsc),
        }
    else:
        cache = {
            "k": tuple(ks[l] for l in range(L)),
            "v": tuple(vs[l] for l in range(L)),
        }
    if mesh is not None and _tp_size(mesh, cfg) > 1:
        # pin the cache kv-head-sharded over tp so the per-step
        # dynamic_update_slice and attention read stay rank-local (the
        # sharded decode step's shard_map consumes exactly this layout)
        cache = _tp_pin_cache(cache, mesh, cfg)
    cache.update(extra)
    return logits, cache


def _token_step(params, pos, tokens, cfg: TransformerConfig,
                layer_states, attend_update, row_states=None,
                active=None):
    """Shared single-token transformer skeleton: embed, the UNROLLED
    layer loop (static per-layer param slices fuse; a lax.scan would
    stack the updated caches into a fresh (L, ...) block — a full
    cache rewrite per token), final norm, lm_head. Per layer it runs
    norm → qkv → rope (the CURRENT global position; cached keys are
    already post-rope from prefill) and then delegates to
    ``attend_update(q, k_new, v_new, state) -> (o, new_state)`` — the
    cache write + attention, the ONLY part that differs between the
    linear cache (flash/gather/int8/tp routes, :func:`decode_step`)
    and the paged cache (:func:`paged_decode_step`). One skeleton, so
    the two cannot drift. The loop reads ``cfg.pattern``: a "B" layer is
    that block, "*" its attention half alone, "M" one step of the
    recurrence against ``row_states`` (the (conv tail, S) pairs, written
    back only where ``active``), "E" the expert layer (idle rows pick
    nothing), "H" the attention and one step of the recurrence off one
    norm, summed, then the gated MLP, "R" the attention and then the
    routed experts. Returns (logits, the K/V layers' new states, the
    other cache entries)."""
    dt = jnp.dtype(cfg.dtype)
    B = tokens.shape[0]
    with jax.named_scope("embed"):
        x = scaled(params["embed"].astype(dt)[tokens],   # (B, D)
                   cfg.embedding_multiplier)
        if cfg.pos_embed == "learned":
            pe = params["pos_embed"].astype(dt)
            # scalar pos: one shared row (DUS slice); ragged (B,) pos:
            # per-row gather. rope needs no branch — apply_rope
            # broadcasts either shape over the heads
            x = x + (pe[pos] if jnp.ndim(pos)
                     else lax.dynamic_slice_in_dim(pe, pos, 1, axis=0))
    new_states, new_rows, stats = [], [], []
    for l, kind in enumerate(cfg.pattern):
        lp = layer_params(params, l)
        if kind == "M":   # the recurrence; idle rows keep their state
            x, st = ssm_mixer_step(x, lp, cfg, row_states[len(new_rows)],
                                   active)
            new_rows.append(st)
            continue
        if kind == "E":   # idle rows pick no expert
            x, st = moe_mixer(x, lp, cfg, active)
            stats.append(st)
            continue
        ak = cfg.attn_kind(kind)
        hn = attn_norm(x, lp, cfg, ak)
        with jax.named_scope(attn_scope(ak)):
            # (B, H/Hkv, Dh)
            q, k_new, v_new = project_qkv(hn, lp, cfg, ak.heads)
            if cfg.pos_embed == "rope":
                q = apply_rope(q, pos, cfg, ak.rope)
                k_new = apply_rope(k_new, pos, cfg, ak.rope)
        # GQA grouped attention against the UNEXPANDED cache: q head
        # k*g+j (project_qkv's order) reads kv head k directly — no
        # materialized n_heads-wide repeat, so per-step HBM traffic is
        # the kv_heads-narrow cache read (the saving GQA exists for).
        # Outside the scope: the paged attend_update names its own
        # halves (the cache write is ``kv_write``, the attention
        # ``attn``), so the two stay disjoint. A window layer's goes
        # to its ring (``window``: the paged route alone takes it)
        state = layer_states[len(new_states)]
        if ak.window:
            o, st = attend_update(q, k_new, v_new, state, window=ak.window)
        else:
            o, st = attend_update(q, k_new, v_new, state)
        x = x + attn_proj(o.reshape(B, ak.heads, cfg.head_dim), lp,
                          cfg, dt, ak, hn)
        new_states.append(st)
        holds = LAYER_KINDS[kind]
        if holds.state:   # "H": the recurrence, off the same normed input
            m, rs = ssm_branch_step(hn, lp, cfg, row_states[len(new_rows)],
                                    active)
            new_rows.append(rs)
            x = x + m
        if holds.mlp == "routed":   # idle rows pick no expert
            x, st = routed_mlp(x, lp, cfg, active)
            stats.append(st)
        elif holds.mlp:
            x = (gated_mlp if holds.mlp == "gated" else _mlp)(x, lp, cfg)
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["ln_f_scale"], cfg.norm_eps)
        logits = head_logits(x, params, cfg)
    return logits, new_states, _step_extra(new_rows, stats)


def _row_states(cache):
    """The state layers' (conv tail, S) pairs of a cache, in layer order."""
    return list(zip(*(cache.get(k, ()) for k in STATE_KEYS)))


def _step_extra(new_rows, stats) -> dict:
    """What one token step adds to a patterned model's cache entries:
    the state layers' new rows and the route's sums of this step."""
    extra = _state_entries(new_rows)
    if stats:
        extra["moe_stats"] = sum(stats)
    return extra


def _apply_extra(cache, out, extra) -> None:
    """Fold :func:`_step_extra` into the step's new cache ``out``."""
    stats = extra.pop("moe_stats", None)
    out.update(extra)
    if stats is not None:   # row 1: decode steps
        out["moe_stats"] = cache["moe_stats"].at[1].add(stats)


def decode_step(params, cache, pos, tokens, cfg: TransformerConfig,
                mesh=None):
    """One token for every sequence in the batch: ``tokens`` (B,) int32
    at position ``pos`` (traced scalar — the true current length, so one
    compilation serves the whole generation). Returns
    (logits (B, V) f32, updated cache).

    ``mesh``: for tp-sharded serving with ``decode_attn="flash"`` — the
    single-query kernel runs under a ``shard_map`` manual partition
    over ``cfg.axis_tp`` (heads are embarrassingly parallel in its
    grid); all other einsums partition via GSPMD as before. Without a
    mesh, sharded params still work through pure GSPMD on the gather
    path."""
    if cfg.window:
        raise ValueError(
            "a model with window layers decodes through the paged cache "
            "(paged_decode_step), where a window layer's K/V is a ring of "
            "pages a row; the linear cache holds every position")
    dt = jnp.dtype(cfg.dtype)
    B = tokens.shape[0]
    scale = 1.0 / (cfg.head_dim ** 0.5)
    use_flash, flash_sharded = _flash_route(mesh, cfg)

    Hkv, g, Dh = cfg.kv_heads, cfg.n_heads // cfg.kv_heads, cfg.head_dim
    quant_cache = _kv_quantized(cfg)

    def attend_update(q, k_new, v_new, state):
        k_cache, v_cache, k_scale, v_scale = state
        if quant_cache:
            k_q, k_s = _quantize_rows(k_new, cfg.kv_cache_dtype)
            v_q, v_s = _quantize_rows(v_new, cfg.kv_cache_dtype)
            k_cache = lax.dynamic_update_slice(
                k_cache, k_q[:, :, None], (0, 0, pos, 0)
            )
            v_cache = lax.dynamic_update_slice(
                v_cache, v_q[:, :, None], (0, 0, pos, 0)
            )
            k_scale = lax.dynamic_update_slice(
                k_scale, k_s[:, :, None], (0, 0, pos)
            )
            v_scale = lax.dynamic_update_slice(
                v_scale, v_s[:, :, None], (0, 0, pos)
            )
        else:
            k_cache = lax.dynamic_update_slice(
                k_cache, k_new[:, :, None].astype(dt), (0, 0, pos, 0)
            )
            v_cache = lax.dynamic_update_slice(
                v_cache, v_new[:, :, None].astype(dt), (0, 0, pos, 0)
            )
        if use_flash:
            from hpc_patterns_tpu.ops.flash_decode import (
                flash_decode_attention,
            )

            if flash_sharded:
                # manual partition over tp: contiguous head blocks —
                # q heads [c·H/tp, ...) are exactly the g-groups of kv
                # heads [c·Hkv/tp, ...), so each rank runs the kernel
                # on its own whole (q-group, cache) rows
                spec_q, spec_c = _tp_serving_specs(mesh, cfg)
                args = [q, k_cache, v_cache,
                        jnp.asarray(pos, jnp.int32).reshape(1)]
                specs = [spec_q, spec_c, spec_c, P()]
                if quant_cache:
                    args += [k_scale, v_scale]
                    specs += [spec_q] * 2  # scale rows are 3-D too

                def local_attn(q, kc, vc, p, ks=None, vs=None):
                    return flash_decode_attention(
                        q, kc, vc, p[0], k_scale=ks, v_scale=vs,
                        scale=scale,
                    )

                o = shard_map(
                    local_attn, mesh=mesh,
                    in_specs=tuple(specs), out_specs=spec_q,
                    check_vma=False,  # pallas_call can't declare vma
                )(*args)
            else:
                o = flash_decode_attention(q, k_cache, v_cache, pos,
                                           k_scale=k_scale,
                                           v_scale=v_scale, scale=scale)
        else:
            # ONE gather attention block for both cache dtypes (an int8
            # cache dequantizes in the einsum stream — elementwise
            # producers fuse, the HBM reads stay int8).
            # precision=HIGHEST: a TPU f32 einsum at default precision
            # rounds its inputs to bf16 on the MXU; true f32 here both
            # matches the flash kernel's f32 math (greedy tokens agree
            # across impls) and is free — the step is cache-read-bound
            if quant_cache:
                kd = _dequant(k_cache, k_scale)
                vd = _dequant(v_cache, v_scale)
            else:
                kd = k_cache.astype(jnp.float32)
                vd = v_cache.astype(jnp.float32)
            qg = q.reshape(B, Hkv, g, Dh)
            s = jnp.einsum(
                "bkgd,bksd->bkgs", qg.astype(jnp.float32), kd,
                precision=lax.Precision.HIGHEST,
            ) * scale
            visible = lax.broadcasted_iota(jnp.int32, s.shape, 3) <= pos
            s = jnp.where(visible, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bkgs,bksd->bkgd", p, vd,
                           precision=lax.Precision.HIGHEST)
        return o, (k_cache, v_cache, k_scale, v_scale)

    states = [
        (cache["k"][l], cache["v"][l],
         cache["k_scale"][l] if quant_cache else None,
         cache["v_scale"][l] if quant_cache else None)
        for l in range(cfg.n_attn_layers)
    ]
    logits, new_states, extra = _token_step(
        params, pos, tokens, cfg, states, attend_update,
        _row_states(cache))
    new_cache = {"k": tuple(s[0] for s in new_states),
                 "v": tuple(s[1] for s in new_states)}
    if quant_cache:
        new_cache["k_scale"] = tuple(s[2] for s in new_states)
        new_cache["v_scale"] = tuple(s[3] for s in new_states)
    _apply_extra(cache, new_cache, extra)
    return logits, new_cache


def extend_step(params, cache, pos, tokens, cfg: TransformerConfig):
    """Multi-token cache extension: feed ``tokens`` (B, c) occupying
    positions ``pos .. pos+c-1`` through the model against the existing
    cache, writing their K/V and returning logits for EVERY chunk
    position — ``decode_step`` generalized from c=1. The verification
    primitive of speculative decoding (models/speculative.py): one
    batched pass scores a whole proposed chunk at large-matmul shapes
    instead of c sequential single-token steps. Causality within the
    chunk: query i attends cache rows <= pos+i. Compute-dtype caches
    only (the c=1 step covers int8 serving), and the attention is the
    GATHER form regardless of cfg.decode_attn — a c-row query block
    against the cache is partitioning-friendly XLA territory, and the
    flash-decode kernel is single-query by design; expect the usual
    f32-association differences vs sequential flash steps.

    Returns (logits (B, c, vocab) f32, updated cache).
    """
    if cfg.kv_cache_dtype != "compute":
        raise ValueError("extend_step supports compute-dtype caches only")
    _dense_only(cfg, "extend_step")
    dt = jnp.dtype(cfg.dtype)
    B, c = tokens.shape
    scale = 1.0 / (cfg.head_dim ** 0.5)
    x = params["embed"].astype(dt)[tokens]  # (B, c, D)
    positions = pos + jnp.arange(c, dtype=jnp.int32)
    if cfg.pos_embed == "learned":
        x = x + lax.dynamic_slice_in_dim(
            params["pos_embed"].astype(dt), pos, c, axis=0
        )

    Hkv, g, Dh = cfg.kv_heads, cfg.n_heads // cfg.kv_heads, cfg.head_dim

    def body(h, lp, k_cache, v_cache):
        hn = _rmsnorm(h, lp["ln1_scale"])
        q, k_new, v_new = project_qkv(hn, lp, cfg)  # (B, c, H/Hkv, Dh)
        if cfg.pos_embed == "rope":
            q = apply_rope(q, positions, cfg)
            k_new = apply_rope(k_new, positions, cfg)
        # chunk K/V into kernel layout rows at pos..pos+c-1
        k_cache = lax.dynamic_update_slice(
            k_cache, jnp.einsum("bchd->bhcd", k_new).astype(dt),
            (0, 0, pos, 0),
        )
        v_cache = lax.dynamic_update_slice(
            v_cache, jnp.einsum("bchd->bhcd", v_new).astype(dt),
            (0, 0, pos, 0),
        )
        qg = q.reshape(B, c, Hkv, g, Dh)
        s = jnp.einsum(
            "bckgd,bksd->bkgcs", qg.astype(jnp.float32),
            k_cache.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        ) * scale
        # query i sees cache rows <= pos+i (its own row included)
        row_pos = lax.broadcasted_iota(jnp.int32, s.shape, 4)
        q_pos = pos + lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(row_pos <= q_pos, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgcs,bksd->bckgd", p,
                       v_cache.astype(jnp.float32),
                       precision=lax.Precision.HIGHEST)
        o = jnp.dot(o.reshape(B, c, cfg.attn_width).astype(dt),
                    matmul_weight(lp, "wo", dt))
        h = _mlp(h + o, lp, cfg)
        return h, (k_cache, v_cache)

    ks, vs = [], []
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        x, (k_l, v_l) = body(x, lp, cache["k"][l], cache["v"][l])
        ks.append(k_l)
        vs.append(v_l)
    x = _rmsnorm(x, params["ln_f_scale"])
    logits = jnp.dot(x, matmul_weight(params, "lm_head", dt))
    return logits.astype(jnp.float32), {"k": tuple(ks), "v": tuple(vs)}


def _topk_mask(logits, top_k: int):
    """Top-k truncation (0 = off): everything below the kth-highest
    logit goes to -inf, ties at the kth value all survive. THE single
    definition of the sampling support — _pick samples from it and the
    speculative verifier's warped distributions are built from it
    (models/speculative.py), so the two can never drift apart."""
    if top_k:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    return logits


@scoped("sample")
def _pick(logits, key, temperature, greedy: bool, top_k: int):
    """Next-token choice. ``greedy`` (static) picks the branch; the
    temperature itself stays traced so every sampling temperature
    shares one compilation. ``top_k`` (static, 0 = off) truncates to
    the k highest logits via the TPU top-k kernel (no full-vocab
    sort)."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _topk_mask(logits, top_k)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(
        jnp.int32
    )


def _generation_scan(step_fn, logits, cache, start_pos, new_tokens, key,
                     temperature, greedy, top_k):
    """The shared generation loop: pick the first token from the
    prefill logits, then scan ``step_fn(cache, pos, tok) -> (logits,
    cache)`` for the rest — ONE copy of the pick/scan/emit machinery
    for the linear and paged caches (a sampling change lands in both
    or neither)."""
    key, sub = jax.random.split(key)
    first = _pick(logits, sub, temperature, greedy, top_k)
    if new_tokens == 1:
        return first[:, None]

    def step(carry, _):
        cache, pos, tok, key = carry
        logits, cache = step_fn(cache, pos, tok)
        key, sub = jax.random.split(key)
        nxt = _pick(logits, sub, temperature, greedy, top_k)
        return (cache, pos + 1, nxt, key), tok

    (_, _, last, _), toks = lax.scan(
        step, (cache, jnp.int32(start_pos), first, key), None,
        length=new_tokens - 1,
    )
    return jnp.concatenate([toks.T, last[:, None]], axis=1)


@partial(jax.jit, static_argnums=(2, 3, 6, 7, 8))
def _generate_jit(params, prompt, cfg, new_tokens, key, temperature,
                  greedy, top_k, mesh=None):
    B, T = prompt.shape
    max_len = T + new_tokens
    logits, cache = prefill(params, prompt, cfg, max_len, mesh=mesh)
    return _generation_scan(
        lambda c, p, t: decode_step(params, c, p, t, cfg, mesh=mesh),
        logits, cache, T, new_tokens, key, temperature, greedy, top_k,
    )


def generate(params, prompt, cfg: TransformerConfig, new_tokens: int, *,
             key=None, temperature: float = 0.0, top_k: int = 0,
             mesh=None):
    """Continuation tokens (B, new_tokens) int32: greedy by default,
    temperature/top-k sampling when ``temperature > 0`` (``key``
    required then). One jit for prefill + the whole scan'd decode
    loop. ``mesh``: tp-sharded serving with the flash kernels (see
    :func:`decode_step`); without it, sharded params serve via GSPMD
    on the gather path."""
    if new_tokens < 1:
        raise ValueError(f"new_tokens must be >= 1, got {new_tokens}")
    if prompt.shape[1] + new_tokens > cfg.max_seq:
        raise ValueError(
            f"prompt {prompt.shape[1]} + new {new_tokens} exceeds "
            f"max_seq {cfg.max_seq}"
        )
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if not 0 <= top_k <= cfg.vocab:
        raise ValueError(f"top_k {top_k} outside [0, vocab]")
    if key is None:
        key = jax.random.PRNGKey(0)  # unused in greedy mode
    with tracelib.compile_watch("decode.generate", _generate_jit,
                                batch=prompt.shape[0],
                                prompt_len=prompt.shape[1],
                                new_tokens=new_tokens):
        return _generate_jit(params, prompt, cfg, new_tokens, key,
                             jnp.float32(max(temperature, 1e-6)),
                             temperature <= 0.0, int(top_k), mesh)


def greedy_generate(params, prompt, cfg: TransformerConfig,
                    new_tokens: int, *, mesh=None):
    """Greedy continuation: (B, new_tokens) int32. The oracle
    equivalence (identical to re-running forward() on the growing
    sequence each step) is the decode test's invariant."""
    return generate(params, prompt, cfg, new_tokens, mesh=mesh)


# ---------------------------------------------------------------------------
# Paged KV cache (block-table serving)
# ---------------------------------------------------------------------------

def ring_table(rows, pages_per_seq: int, ring: int):
    """(len(rows), pages_per_seq) int32: logical page j of the row held by
    slot ``rows[b]`` lies at ring page rows[b]·ring + j mod ring of a
    window layer's pool. THE ring layout: a function of the slot and the
    page alone, computed inside each program that reads or writes a
    ring, never held as state."""
    rows = jnp.asarray(rows, jnp.int32)[:, None] * ring
    return rows + jnp.arange(pages_per_seq, dtype=jnp.int32)[None, :] % ring


def init_paged_cache(cfg: TransformerConfig, batch: int,
                     pages_per_seq: int, page_size: int,
                     pool_pages: int | None = None, table=None):
    """Paged KV cache: per-layer page POOLS plus one page table.

    The capacity lever the linear cache cannot offer: a linear cache
    allocates ``batch x max_len`` rows up front (the declared maximum),
    a paged cache allocates ``pool_pages x page_size`` rows — sized to
    the tokens that will actually exist. Layout per layer:
    (pool_pages, kv_heads, page_size, head_dim), the page-major form
    ops/flash_decode.flash_decode_paged streams; ``table``:
    (batch, pages_per_seq) int32 page ids (default: the identity
    layout; any permutation is equally valid — the kernel indirects
    through the table, which is what makes future dynamic allocation
    policies free). With a quantized ``cfg.kv_cache_dtype`` ("int8" or
    "fp8") the pools store one byte per element plus per-row f32 scale
    pools (kernel-lane layout (pool_pages, kv_heads, 1, page_size)) —
    the two CAPACITY levers stack: quantization halves page bytes vs
    bf16 (quarters vs f32), paging frees the allocate-for-longest
    waste (docs/quantization.md).

    A WINDOW layer (``cfg.window_layers``) sees its last ``cfg.window``
    positions alone, which span at most R = ``cfg.ring_pages(page_size)``
    pages, distinct mod R: its pool is a RING of R pages a row,
    (batch x R, kv_heads, page_size, head_dim), held by the row from the
    start and never taken from the arena: logical page j of row b lies
    at ring page b·R + j mod R (:func:`ring_table`). A page written
    over held positions the window has passed; whatever a ring page
    holds past the row's position is masked like any stale row of the
    arena."""
    if pool_pages is None:
        pool_pages = batch * pages_per_seq
    if table is None:
        if pool_pages < batch * pages_per_seq:
            # a default table over an undersized pool would silently
            # ALIAS pages across sequences (each clobbering the others'
            # K/V); page sharing is an eviction policy, not a default —
            # callers wanting it must pass an explicit table
            raise ValueError(
                f"pool_pages {pool_pages} < batch*pages_per_seq "
                f"{batch * pages_per_seq}: the default identity table "
                "needs a page per (sequence, slot); pass an explicit "
                "table to share pages deliberately"
            )
        table = jnp.arange(batch * pages_per_seq, dtype=jnp.int32)
        table = table.reshape(batch, pages_per_seq)
    quant = _kv_quantized(cfg)
    dt = _kv_storage_dtype(cfg)
    shape = (pool_pages, cfg.kv_heads, page_size, cfg.head_dim)
    fresh = lambda sh, d: tuple(jnp.zeros(sh, d)
                                for _ in range(cfg.n_attn_layers))
    if cfg.window:
        R = cfg.ring_pages(page_size)
        ring = (batch * R,) + shape[1:]
        fresh = lambda sh, d: tuple(jnp.zeros(ring if win else sh, d)
                                    for win in cfg.window_layers)
    cache = {"k": fresh(shape, dt), "v": fresh(shape, dt),
             "table": jnp.asarray(table, jnp.int32)}
    if quant:
        sshape = (pool_pages, cfg.kv_heads, 1, page_size)
        cache["k_scale"] = fresh(sshape, jnp.float32)
        cache["v_scale"] = fresh(sshape, jnp.float32)
    # a patterned model's per-row state lives beside the pools: one row
    # a sequence, no paging (its size does not grow with the context)
    cache.update(init_layer_state(cfg, batch))
    return cache


def paged_prefill(params, prompt, cfg: TransformerConfig, cache,
                  page_size: int, mesh=None, last_pos=None, ring_rows=None):
    """Prompt pass writing into the paged cache: the ordinary prefill
    captures K/V for the prompt (a transient sized to the PROMPT, not
    the serving maximum), then each layer's pages scatter into the pool
    through the table. Returns (last_logits, cache). ``mesh``:
    tp-sharded serving — the prefill kernel runs shard_mapped and the
    page POOLS are constrained kv-head-sharded over tp (the layout
    :func:`paged_decode_step`'s sharded route consumes in place).
    ``last_pos``: the bucketed-prompt route (see :func:`prefill`) —
    logits come from this position instead of the last, so a prompt
    right-padded to a bucket rung still answers for its true end.
    ``ring_rows``: (B,) int32, the slots whose window-layer rings the
    prompt rows fill (default: row b's own, b) — the engine's one-row
    admission names its slot."""
    B, T = prompt.shape
    P = page_size  # shadows the PartitionSpec alias in this scope
    t_pad = -(-T // P) * P
    n_used = t_pad // P
    table = cache["table"]
    if n_used > table.shape[1]:
        raise ValueError(
            f"prompt needs {n_used} pages; table has {table.shape[1]}"
        )
    # capture at the PROMPT length (always legal), pad to the page
    # boundary afterwards — asking prefill for t_pad would spuriously
    # trip its max_len <= cfg.max_seq guard for prompts within a page
    # of the model maximum
    logits, lin = prefill(params, prompt, cfg, T, mesh=mesh,
                          last_pos=last_pos)
    # a patterned model's rows of state pass through as the prompt pass
    # left them (B rows: the caller's own batch, or the engine's one
    # admission, which installs them at its slot); the route's sums add
    # to the cache's
    extra = {k: lin.pop(k) for k in STATE_KEYS if k in lin}
    if "moe_stats" in lin:
        stats = lin.pop("moe_stats")
        extra["moe_stats"] = (cache["moe_stats"] + stats
                              if "moe_stats" in cache else stats)
    # everything after the prompt pass: pad to the page boundary and
    # scatter each layer's pages into the pool through the table
    with jax.named_scope("kv_write"):
        if t_pad > T:
            # pad the sequence axis of every leaf (values are 4-D, int8
            # scales 3-D)
            lin = jax.tree.map(
                lambda a: jnp.pad(
                    a, [(0, 0)] * 2 + [(0, t_pad - T)]
                    + [(0, 0)] * (a.ndim - 3)
                ),
                lin,
            )
        idx = table[:, :n_used]  # (B, n_used)
        out = {"table": table, **extra}
        if cfg.window:
            # a window layer's ring takes the R pages that end at the
            # prompt's last true position: what the next step's window
            # reaches back to (pages before the first clamp to it and
            # write the same page again)
            last = jnp.broadcast_to(jnp.asarray(
                T - 1 if last_pos is None else last_pos, jnp.int32), (B,))
            R = cfg.ring_pages(P)
            src = jnp.clip(last[:, None] // P
                           - jnp.arange(R, dtype=jnp.int32)[None, :],
                           0, n_used - 1)                    # (B, R)
            rows = jnp.arange(B) if ring_rows is None else ring_rows
            ring_idx = jnp.take_along_axis(
                ring_table(rows, n_used, R), src, axis=1)
        for name in ("k", "v"):
            pool = list(cache[name])
            for l in range(cfg.n_attn_layers):
                # (B, Hkv, t_pad, D) -> (B, n_used, Hkv, P, D) page blocks
                pages = jnp.einsum(
                    "bhpsd->bphsd",
                    lin[name][l].reshape(B, cfg.kv_heads, n_used, P,
                                         cfg.head_dim),
                )
                if cfg.window and cfg.window_layers[l]:
                    pages = jnp.take_along_axis(
                        pages, src[:, :, None, None, None], axis=1)
                    pool[l] = pool[l].at[ring_idx].set(
                        pages.astype(pool[l].dtype))
                    continue
                pool[l] = pool[l].at[idx].set(pages.astype(pool[l].dtype))
            out[name] = tuple(pool)
        if _kv_quantized(cfg):
            for name in ("k_scale", "v_scale"):
                pool = list(cache[name])
                for l in range(cfg.n_attn_layers):
                    # (B, Hkv, t_pad) -> (B, n_used, Hkv, 1, P) lane-major
                    pages = jnp.einsum(
                        "bhps->bphs",
                        lin[name][l].reshape(B, cfg.kv_heads, n_used, P),
                    )[:, :, :, None, :]
                    pool[l] = pool[l].at[idx].set(pages)
                out[name] = tuple(pool)
        if mesh is not None and _tp_size(mesh, cfg) > 1:
            # pin every pool kv-head-sharded over tp (all pool leaves are
            # 4-D with kv_heads on dim 1, scale pools included) so the
            # per-step writes and the sharded kernel stay rank-local
            out = {k: (_tp_pin_cache(v, mesh, cfg)
                       if k in ("k", "v", "k_scale", "v_scale") else v)
                   for k, v in out.items()}
    return logits, out


#: SIMD row-alignment quantum for bitwise prefill parity (see
#: :func:`paged_tail_prefill`): XLA:CPU GEMMs reproduce a row's dot
#: products bitwise across DIFFERENT total row counts only when both
#: counts are multiples of this (measured: 3-row and 1-row tails
#: diverged in ULPs from the monolithic prefill's remainder-loop rows;
#: every multiple-of-8 pairing tested matched exactly). The sharing
#: engine enforces rung/page alignment to it at construction.
PREFIX_ALIGN = 8


def paged_tail_prefill(params, tail, cfg: TransformerConfig, cache,
                       page_size: int, n_prefix_pages: int, mesh=None,
                       last_pos=None):
    """Prefill ONLY the tail of a prompt whose first ``n_prefix_pages``
    pages of K/V already sit in the pool (the prefix-sharing arena's
    admission path, models/serving.py): positions ``[M, M + c)`` with
    ``M = n_prefix_pages * page_size`` are computed and scattered into
    the pages ``table[:, n_prefix:]``; the prefix pages are GATHERED as
    attention context and never written. Returns ``(logits, cache)``
    like :func:`paged_prefill`, with ``last_pos`` TAIL-RELATIVE (the
    true last token's offset into ``tail``).

    BITWISE PARITY CONTRACT (the prefix-cache oracle rides on it): the
    written tail pages and the returned logits are bit-identical to a
    monolithic :func:`paged_prefill` of the full ``M + c`` prompt,
    provided (a) the prefix pages hold bytes a SAME-LENGTH monolithic
    prefill wrote (rung-keyed sharing — prefix K/V is bitwise
    suffix-independent under causal masking, but NOT length-independent:
    prefill(32) and prefill(40) disagree in ULPs on shared rows), (b)
    ``M`` and ``c`` are multiples of :data:`PREFIX_ALIGN` (SIMD-stable
    GEMM row counts), and (c) the monolithic side took the einsum
    attention route (``full_attention``), which this function mirrors
    term for term — same grouped-score/grouped-pv einsums, same mask
    constant, same softmax axis length ``M + c``.

    Quantized KV pools (``kv_cache_dtype`` "int8"/"fp8") are refused:
    the monolithic prefill attends to the EXACT K/V and quantizes only
    for storage, so a tail computed from dequantized prefix pages
    could not be bit-equal."""
    _dense_only(cfg, "paged_tail_prefill")
    if _kv_quantized(cfg):
        raise ValueError(
            f"paged_tail_prefill: kv_cache_dtype="
            f"{cfg.kv_cache_dtype!r} pools cannot share prefixes "
            "bitwise — the monolithic prefill attends to exact K/V "
            "and quantizes only for storage, so a tail computed from "
            "dequantized shared pages would diverge in ULPs and break "
            "the parity contract; serve quantized KV with "
            "prefix_cache=False (or keep sharing on a compute-dtype "
            "pool) — docs/quantization.md")
    from hpc_patterns_tpu.parallel.ring_attention import (
        _NEG_INF,
        _grouped_pv,
        _grouped_scores,
    )

    B, c = tail.shape
    if B != 1:
        raise ValueError(
            f"paged_tail_prefill is single-row (got B={B}): the "
            "prefix context gathers through table[0], so rows with "
            "different chains would all attend row 0's pages — batch "
            "callers must map per row")
    P = page_size  # shadows the PartitionSpec alias in this scope
    M = n_prefix_pages * P
    if M % PREFIX_ALIGN or c % PREFIX_ALIGN:
        raise ValueError(
            f"paged_tail_prefill needs prefix length {M} and tail "
            f"length {c} aligned to {PREFIX_ALIGN} rows (bitwise GEMM "
            "row stability); pad the rung/page geometry")
    table = cache["table"]
    n_tail = -(-c // P)
    if n_prefix_pages + n_tail > table.shape[1]:
        raise ValueError(
            f"tail needs pages {n_prefix_pages}..{n_prefix_pages + n_tail}"
            f"; table has {table.shape[1]}")
    dt = jnp.dtype(cfg.dtype)
    pidx = table[0, :n_prefix_pages]

    def gather_ctx(pool):
        # prefix pages -> (1, M, Hkv, D), the s-major "bskd" layout the
        # grouped-score einsum consumes (pure layout moves, bit-neutral)
        return jnp.einsum("phsd->pshd", pool[pidx]).reshape(
            1, M, cfg.kv_heads, cfg.head_dim)

    pk = jnp.stack([gather_ctx(cache["k"][l])
                    for l in range(cfg.n_layers)])
    pv = jnp.stack([gather_ctx(cache["v"][l])
                    for l in range(cfg.n_layers)])

    x = params["embed"].astype(dt)[tail]
    pos = M + jnp.arange(c, dtype=jnp.int32)
    if cfg.pos_embed == "learned":
        x = x + params["pos_embed"].astype(dt)[pos]
    scale = 1.0 / (cfg.head_dim ** 0.5)

    def body(h, layer):
        lp, pkl, pvl = layer
        hn = _rmsnorm(h, lp["ln1_scale"])
        q, k, v = project_qkv(hn, lp, cfg)
        if cfg.pos_embed == "rope":
            q = apply_rope(q, pos, cfg)
            k = apply_rope(k, pos, cfg)
        # context axis = M + c, the SAME softmax reduction length the
        # monolithic prefill used — the mask's exact zeros are the only
        # difference, and only at positions both sides zero out
        k_ctx = jnp.concatenate([pkl.astype(dt), k], axis=1)
        v_ctx = jnp.concatenate([pvl.astype(dt), v], axis=1)
        s = _grouped_scores(q, k_ctx, scale)
        t_idx = lax.broadcasted_iota(jnp.int32, s.shape, 2) + M
        s_idx = lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(s_idx <= t_idx, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhtd->bthd", _grouped_pv(p, v_ctx)).astype(
            q.dtype)
        o = jnp.dot(o.reshape(B, c, cfg.attn_width),
                    matmul_weight(lp, "wo", dt))
        h = _mlp(h + o.astype(dt), lp, cfg)
        kc = jnp.einsum("bthd->bhtd", k)
        vc = jnp.einsum("bthd->bhtd", v)
        return h, (kc.astype(dt), vc.astype(dt))

    x, (ks, vs) = lax.scan(body, x, (params["layers"], pk, pv))
    x = _rmsnorm(x, params["ln_f_scale"])
    if last_pos is None:
        x_last = x[:, -1]
    else:
        lp_ = jnp.broadcast_to(jnp.asarray(last_pos, jnp.int32), (B,))
        x_last = jnp.take_along_axis(x, lp_[:, None, None], axis=1)[:, 0]
    logits = jnp.dot(x_last, matmul_weight(params, "lm_head",
                                           dt)).astype(jnp.float32)

    # scatter the tail pages exactly as paged_prefill does: pad the
    # tail K/V to the page boundary with zeros (the monolithic path's
    # jnp.pad bytes), page-blocked through the table
    t_pad = n_tail * P
    idx = table[:, n_prefix_pages:n_prefix_pages + n_tail]
    out = {"table": table}
    for name, lin in (("k", ks), ("v", vs)):
        pool = list(cache[name])
        for l in range(cfg.n_layers):
            linl = lin[l]
            if t_pad > c:
                linl = jnp.pad(
                    linl, [(0, 0), (0, 0), (0, t_pad - c), (0, 0)])
            pages = jnp.einsum(
                "bhpsd->bphsd",
                linl.reshape(B, cfg.kv_heads, n_tail, P, cfg.head_dim))
            pool[l] = pool[l].at[idx].set(pages.astype(pool[l].dtype))
        out[name] = tuple(pool)
    if mesh is not None and _tp_size(mesh, cfg) > 1:
        out = {k_: (v_ if k_ == "table"
                    else _tp_pin_cache(v_, mesh, cfg))
               for k_, v_ in out.items()}
    return logits, out


# tables already verified as identity layout, keyed by id() (jax arrays
# compare elementwise, so set membership is unusable); WeakValue so a
# collected table's id can never alias a new object
_identity_verified: "weakref.WeakValueDictionary[int, object]" = (
    weakref.WeakValueDictionary()
)


def _pool_write(pool, page_ids, page, offset, rows, pages: int,
                identity: bool, tp: int = 1):
    """Write K/V rows ``rows`` (n, Hkv, D) into their page slots: row
    ``i`` lands in page ``page_ids[i]`` at position ``offset[i]`` (n is
    the batch for a decode step, batch x chunk for an extension).

    The general form is a scatter (pages anywhere in the pool), correct
    for ANY table. It indexes the K/V-head axis too — ``page_ids``,
    ``arange(Hkv)`` and ``offset`` broadcast to (n, Hkv), the window is
    one row of ``head_dim`` — because XLA then scatters in place in the
    layout ``flash_decode_paged`` reads (``{3,2,1,0:T(8,128)}``). With
    the head axis a window of the scatter (``pool.at[ids, :, offset,
    :]``) the TPU compiler gave the scatter a layout of its own (a
    position's heads one tile) and copied the whole pool back to the
    kernel's layout after every write: 0.41 ms a write of a 134 MB pool
    against 0.012 ms (PERF.md, PR 31); the values are the same.

    Under ``tp`` > 1 the pools are sharded on the K/V-head axis; an
    index into a sharded axis makes GSPMD gather the pool, so there the
    head axis stays a window (no collective, as before). That form is
    NOT measured on a chip: a rank's local head count is another layout
    question and no benchmark cell shards a pool.

    With the default identity layout (page j of sequence b at pool row
    b·pages + j, ``pages`` = the TABLE's pages_per_seq) AND an
    exact-size pool, the write is a pure ``dynamic_update_slice`` on a
    (B, pages, ...) view — aliased in place through the generation
    scan, the same no-rematerialization property the linear cache's DUS
    has. An OVERSIZED pool makes the view layout disagree with the
    table's row numbering, so it falls through to the scatter."""
    B = rows.shape[0]
    if identity and pool.shape[0] == B * pages:
        n_pool, Hkv, P, D = pool.shape
        v = pool.reshape(B, pages, Hkv, P, D)
        v = lax.dynamic_update_slice(
            v, rows[:, None, :, None, :].astype(pool.dtype),
            (0, page, 0, offset, 0),
        )
        return v.reshape(pool.shape)
    rows = rows.astype(pool.dtype)
    if tp > 1:
        return pool.at[page_ids, :, offset, :].set(rows)
    heads = jnp.arange(pool.shape[1], dtype=jnp.int32)
    # offset: (n,) ragged, or the scalar cursor all rows share
    return pool.at[page_ids[:, None], heads[None, :],
                   jnp.reshape(offset, (-1, 1)), :].set(rows)


def _scale_write(pool, page_ids, page, offset, rows, pages: int,
                 identity: bool):
    """int8 companion of :func:`_pool_write` for the (pool_pages,
    kv_heads, 1, page_size) lane-major scale pools: one (n, kv_heads)
    scale row lands at lane ``offset`` of its page. Its scatter keeps
    the head axis as a window: indexed like :func:`_pool_write`'s it
    still compiles with copies of the (2 MB) scale pools around it
    (described v5e, PR 31), so there was nothing to gain, and no
    benchmark cell runs quantized pools."""
    B = rows.shape[0]
    if identity and pool.shape[0] == B * pages:
        v = pool.reshape(B, pages, *pool.shape[1:])
        v = lax.dynamic_update_slice(
            v, rows[:, None, :, None, None].astype(pool.dtype),
            (0, page, 0, 0, offset),
        )
        return v.reshape(pool.shape)
    return pool.at[page_ids, :, 0, offset].set(rows.astype(pool.dtype))


def _paged_attend_gather(q, k_pool, v_pool, ks_pool, vs_pool, table,
                         pos, cfg: TransformerConfig, scale, window=0):
    """The pure-XLA paged attention step (``cfg.decode_attn ==
    "gather"``): each row's pages gather through the table into a
    contiguous (B, Hkv, pages·P, D) view and the step is
    :func:`decode_step`'s gather block — one fused mask+softmax pass,
    past-the-fill positions (pad pages, trash entries) masked by the
    position cursor. This is the serving route off-TPU: a pallas_call
    runs in INTERPRET mode there, paying per-grid-point host cost that
    scales with batch × kv_heads (measured ~10x a decode step on the
    8-device CPU mesh at serving widths); it also partitions via plain
    GSPMD under tp, where the kernel needs a shard_map. On TPU the
    kernel remains the default — its clamped index map reads
    position-proportional bytes; this view reads the full allocation.
    ``pos``: scalar or ragged (B,); int8 pools dequantize in the einsum
    stream like the linear gather. ``window`` > 0: the keys
    ``pos - window < j <= pos`` alone (a window layer's ring: the view
    holds each logical page's ring page, which past the window may hold
    another page's rows, masked)."""
    B, pages = table.shape
    # g from q: a block step folds its positions into the group
    Hkv, Dh = cfg.kv_heads, cfg.head_dim
    g = q.shape[1] // Hkv
    P = k_pool.shape[2]
    quant = ks_pool is not None

    def view(pool):  # (pool, Hkv, P, D) -> (B, Hkv, pages*P, D)
        gat = pool[table]  # (B, pages, Hkv, P, D)
        return jnp.einsum("bphsd->bhpsd", gat).reshape(
            B, Hkv, pages * P, Dh).astype(jnp.float32)

    def scale_view(pool):  # (pool, Hkv, 1, P) -> (B, Hkv, pages*P)
        gat = pool[table][:, :, :, 0, :]  # (B, pages, Hkv, P)
        return jnp.einsum("bphs->bhps", gat).reshape(B, Hkv, pages * P)

    kd, vd = view(k_pool), view(v_pool)
    if quant:
        kd = kd * scale_view(ks_pool)[..., None]
        vd = vd * scale_view(vs_pool)[..., None]
    qg = q.reshape(B, Hkv, g, Dh)
    s = jnp.einsum("bkgd,bksd->bkgs", qg.astype(jnp.float32), kd,
                   precision=lax.Precision.HIGHEST) * scale
    idx = lax.broadcasted_iota(jnp.int32, s.shape, 3)
    at = pos[:, None, None, None] if jnp.ndim(pos) else pos
    visible = idx <= at
    if window:
        visible = visible & (idx > at - window)
    s = jnp.where(visible, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p, vd,
                   precision=lax.Precision.HIGHEST)
    return o.reshape(B, Hkv * g, Dh)


def paged_decode_step(params, cache, pos, tokens, cfg: TransformerConfig,
                      identity_layout: bool = False, mesh=None,
                      pages_per_step: int | None = None, active=None):
    """One token per sequence against the paged cache: the new K/V row
    scatters into page ``table[:, pos // P]`` at offset ``pos % P``,
    and attention streams the live pages through
    ops/flash_decode.flash_decode_paged. ``pos``: a shared scalar
    cursor (like decode_step) OR a (B,) vector of per-sequence
    positions — RAGGED serving, every sequence at its own length (the
    kernel masks and clamps per row; rope/learned embeddings gather
    per row; the cache write scatters per-row offsets).
    ``cfg.decode_attn`` routes the attention like the linear step:
    "flash" (default) streams live pages through the pallas kernel;
    "paged_flash" gathers the live pages into VMEM through the table
    and runs the exact-softmax paged kernel
    (:func:`~hpc_patterns_tpu.ops.paged_attention.
    paged_attention_decode` — bitwise the gather route's math on
    compute-dtype pools, in-kernel dequant of int8/fp8);
    "gather" takes :func:`_paged_attend_gather` — the pure-XLA view
    that serving uses off-TPU (a pallas_call interprets per grid point
    there) and that partitions via GSPMD under any tp. ``mesh``:
    tp-sharded paged serving — the paged kernel runs under a shard_map
    manual partition over ``cfg.axis_tp`` (whole kv-head blocks per
    rank, like the linear route; tp must divide kv_heads), pools enter
    kv-head-sharded (``paged_prefill(..., mesh=...)``'s layout) and
    the pool writes partition via GSPMD. ``identity_layout`` (static):
    promise that the table is the default identity layout, enabling
    the in-place DUS write for the scalar-cursor case (ragged writes
    always scatter; see :func:`_pool_write`).

    CONTRACT: every position < pages_per_seq * page_size — the caller
    owns the capacity check (:func:`paged_generate` guards it). A
    CONCRETE ``pos`` (eager call) is checked here and raises past
    capacity; a traced ``pos`` (inside jit) cannot be — past-capacity
    steps then clamp to the LAST page (``jnp.take``'s mode) and
    silently corrupt its history."""
    P = cache["k"][0].shape[2]
    table = cache["table"]
    scale = 1.0 / (cfg.head_dim ** 0.5)
    ragged = jnp.ndim(pos) == 1

    # identity_layout is a static promise the tracer cannot check — but
    # when the caller hands a CONCRETE table (direct API use outside
    # jit), verify it eagerly before trusting the DUS fast path: a
    # permuted table plus an exact-size pool would write to the wrong
    # pool rows and silently corrupt other sequences' K/V. (The internal
    # _paged_generate_jit caller builds the identity table itself.)
    # Ragged steps always scatter (ident below), so the promise is
    # inert there; the check memoizes per table OBJECT so an eager
    # serving loop reusing one table pays the host compare once, not
    # per token.
    if (identity_layout and not ragged
            and not isinstance(table, jax.core.Tracer)
            and cache["k"][0].shape[0] == table.shape[0] * table.shape[1]
            and _identity_verified.get(id(table)) is not table):
        expect = np.arange(table.size, dtype=np.int32).reshape(table.shape)
        if not np.array_equal(np.asarray(table), expect):
            raise ValueError(
                "identity_layout=True but cache['table'] is not the "
                "identity layout over an exact-size pool — the in-place "
                "DUS write would corrupt other sequences' K/V; drop the "
                "flag (scatter path) or use the default table"
            )
        _identity_verified[id(table)] = table
    # pos is usually traced (the caller owns the capacity check, see
    # the contract below) — but an eager/concrete pos CAN be checked,
    # and ragged direct callers are exactly who hits this
    if not isinstance(pos, jax.core.Tracer):
        if np.any(np.asarray(pos) >= table.shape[1] * P):
            raise ValueError(
                f"position(s) {np.asarray(pos).max()} past cache "
                f"capacity {table.shape[1] * P} tokens: past-capacity "
                "writes clamp to the last page and corrupt its history"
            )

    from hpc_patterns_tpu.ops.flash_decode import flash_decode_paged

    page = pos // P  # scalar, or (B,) per-sequence page index
    if ragged:
        page_ids = jnp.take_along_axis(
            table, page[:, None], axis=1
        )[:, 0]  # (B,) — each row its own column
    else:
        page_ids = jnp.take(table, page, axis=1)  # (B,)
    offset = pos % P
    if cfg.window:
        # a window layer's row lands in its ring: logical page ``page``
        # through the ring layout, the same offset
        win_table = ring_table(jnp.arange(table.shape[0]), table.shape[1],
                               cfg.ring_pages(P))
        win_ids = (jnp.take_along_axis(win_table, page[:, None], axis=1)
                   [:, 0] if ragged else jnp.take(win_table, page, axis=1))
        if cfg.decode_attn == "paged_flash" or mesh is not None:
            raise ValueError(
                "window layers decode through decode_attn 'flash' or "
                "'gather', unsharded")

    quant = _kv_quantized(cfg)
    ident = identity_layout and not ragged
    pages = table.shape[1]
    tp = _tp_size(mesh, cfg)
    # THE paged routing decision (one place, like _flash_route on the
    # linear path): "flash" streams pages through flash_decode_paged,
    # "paged_flash" gathers them into VMEM through the table and runs
    # the exact-softmax kernel (ops/paged_attention.py — bitwise the
    # gather route's math on compute-dtype pools, in-kernel dequant on
    # quantized ones), "gather" is the pure-XLA view. Both kernels
    # shard_map over tp with whole kv-head blocks per rank.
    kernel_route = cfg.decode_attn if cfg.decode_attn in (
        "flash", "paged_flash") else None
    if kernel_route and tp > 1 and cfg.kv_heads % tp:
        raise ValueError(
            f"paged tp serving needs tp {tp} to divide kv_heads "
            f"{cfg.kv_heads} (whole kv-head blocks per rank) — or "
            "decode_attn='gather', which partitions via GSPMD"
        )
    paged_sharded = kernel_route is not None and tp > 1
    if kernel_route == "paged_flash":
        from hpc_patterns_tpu.ops.paged_attention import (
            paged_attention_decode,
        )

        def kernel_fn(q, kp, vp, tbl, p, act, ksp, vsp):
            return paged_attention_decode(
                q, kp, vp, tbl, p, k_scale_pool=ksp, v_scale_pool=vsp,
                scale=scale)
    else:
        def kernel_fn(q, kp, vp, tbl, p, act, ksp, vsp, **win):
            # the rows that do not count are not visited: zeros
            return flash_decode_paged(
                q, kp, vp, tbl, p, active=act, k_scale_pool=ksp,
                v_scale_pool=vsp, scale=scale,
                pages_per_step=pages_per_step, **win)

    @scoped("kv_write")
    def write(k_new, v_new, state, window=0):
        k_pool, v_pool, ks_pool, vs_pool = state
        if window:   # the ring: never the identity layout's view
            return (_pool_write(k_pool, win_ids, page, offset, k_new,
                                pages, False),
                    _pool_write(v_pool, win_ids, page, offset, v_new,
                                pages, False), ks_pool, vs_pool)
        if quant:
            k_new, k_s = _quantize_rows(k_new, cfg.kv_cache_dtype)
            v_new, v_s = _quantize_rows(v_new, cfg.kv_cache_dtype)
            ks_pool = _scale_write(ks_pool, page_ids, page, offset, k_s,
                                   pages, ident)
            vs_pool = _scale_write(vs_pool, page_ids, page, offset, v_s,
                                   pages, ident)
        k_pool = _pool_write(k_pool, page_ids, page, offset, k_new,
                             pages, ident, tp)
        v_pool = _pool_write(v_pool, page_ids, page, offset, v_new,
                             pages, ident, tp)
        return k_pool, v_pool, ks_pool, vs_pool

    def attend_update(q, k_new, v_new, state, window=0):
        state = write(k_new, v_new, state, window)
        if window:
            with jax.named_scope("attn_win"):
                return attend_window(q, state, window), state
        with jax.named_scope("attn"):
            return attend(q, state), state

    def attend_window(q, state, window):
        """A window layer's step: the keys ``pos - window < j <= pos`` of
        the row's ring (the kernel fetches the pages they lie on)."""
        k_pool, v_pool = state[:2]
        if kernel_route is None:
            return _paged_attend_gather(q, k_pool, v_pool, None, None,
                                        win_table, pos, cfg, scale,
                                        window=window)
        return kernel_fn(q, k_pool, v_pool, win_table, pos, active, None,
                         None, window=window)

    def attend(q, state):
        k_pool, v_pool, ks_pool, vs_pool = state
        if kernel_route is None:
            o = _paged_attend_gather(q, k_pool, v_pool, ks_pool,
                                     vs_pool, table, pos, cfg, scale)
        elif paged_sharded:
            # manual partition over tp, mirroring decode_step's linear
            # route: q heads block-shard with their kv heads, pools
            # shard on the kv_heads dim, table/pos/active ride
            # replicated. (PS, not the module alias P — the page size
            # shadows it in this scope.)
            from jax.sharding import PartitionSpec as PS

            spec_q, spec_pool = _tp_serving_specs(mesh, cfg)
            pos_arr = (pos if ragged
                       else jnp.asarray(pos, jnp.int32).reshape(1))
            args = [q, k_pool, v_pool, table, pos_arr]
            specs = [spec_q, spec_pool, spec_pool, PS(), PS()]
            if quant:
                args += [ks_pool, vs_pool]
                specs += [spec_pool, spec_pool]
            if active is not None:
                args.append(active)
                specs.append(PS())

            def local_attn(q, kp, vp, tbl, p, *rest):
                ksp, vsp = rest[:2] if quant else (None, None)
                act = None if active is None else rest[-1]
                return kernel_fn(q, kp, vp, tbl, p if ragged else p[0],
                                 act, ksp, vsp)

            o = shard_map(
                local_attn, mesh=mesh, in_specs=tuple(specs),
                out_specs=spec_q,
                check_vma=False,  # pallas_call can't declare vma
            )(*args)
        else:
            o = kernel_fn(q, k_pool, v_pool, table, pos, active,
                          ks_pool, vs_pool)
        return o

    states = [
        (cache["k"][l], cache["v"][l],
         cache["k_scale"][l] if quant else None,
         cache["v_scale"][l] if quant else None)
        for l in range(cfg.n_attn_layers)
    ]
    logits, new_states, extra = _token_step(
        params, pos, tokens, cfg, states, attend_update,
        _row_states(cache), active)
    out = {
        "k": tuple(s[0] for s in new_states),
        "v": tuple(s[1] for s in new_states),
        "table": table,
    }
    if quant:
        out["k_scale"] = tuple(s[2] for s in new_states)
        out["v_scale"] = tuple(s[3] for s in new_states)
    _apply_extra(cache, out, extra)
    return logits, out


def paged_extend_step(params, cache, pos, tokens, cfg: TransformerConfig,
                      mesh=None):
    """RAGGED multi-token cache extension against the paged cache: row
    ``b``'s chunk ``tokens[b]`` occupies positions ``pos[b] ..
    pos[b]+c-1`` — every row at its own length, the verification
    primitive per-row-progress batched speculative decoding needs
    (:mod:`~hpc_patterns_tpu.models.speculative`). ``pos``: (B,) int32.

    The chunk K/V scatter into the pool at per-row page/offset pairs
    (the ragged write generalized from one row to ``c``); attention is
    the gather form over the table-linearized pools — a c-row query
    block against the live prefix is MXU territory, exactly
    :func:`extend_step`'s reasoning, with per-row causal masks
    ``row <= pos[b]+i``. int8 pools compose: chunk rows quantize
    per-row like :func:`paged_decode_step`'s writes, and the gather
    dequantizes the linearized view (unlike linear
    :func:`extend_step`, which stays compute-only).
    Returns (logits (B, c, vocab) f32, updated cache).

    ``mesh``: only tells :func:`_pool_write` whether the pools are
    tp-sharded (its scatter then keeps the head axis whole); the math
    partitions via GSPMD from the sharded params/pools alone.

    CONTRACT (same as :func:`paged_decode_step`): every touched
    position < pages_per_seq * page_size; concrete ``pos`` is checked,
    traced ``pos`` clamps silently past capacity.
    """
    quant = _kv_quantized(cfg)
    dt = jnp.dtype(cfg.dtype)
    _dense_only(cfg, "paged_extend_step")
    B, c = tokens.shape
    if jnp.ndim(pos) != 1 or jnp.shape(pos)[0] != B:
        raise ValueError(
            f"pos must be (batch,)={B} per-row positions, got "
            f"{jnp.shape(pos)}")
    table = cache["table"]
    Pg = cache["k"][0].shape[2]
    pages = table.shape[1]
    if not isinstance(pos, jax.core.Tracer):
        if np.any(np.asarray(pos) + c > pages * Pg):
            raise ValueError(
                f"chunk end {int(np.asarray(pos).max()) + c} past cache "
                f"capacity {pages * Pg} tokens")
    scale = 1.0 / (cfg.head_dim ** 0.5)
    Hkv, g, Dh = cfg.kv_heads, cfg.n_heads // cfg.kv_heads, cfg.head_dim
    tp = _tp_size(mesh, cfg)

    positions = pos[:, None] + jnp.arange(c, dtype=jnp.int32)  # (B, c)
    x = params["embed"].astype(dt)[tokens]
    if cfg.pos_embed == "learned":
        x = x + params["pos_embed"].astype(dt)[positions]

    page = positions // Pg
    off = (positions % Pg).reshape(-1)  # (B*c,)
    pids = jnp.take_along_axis(table, page, axis=1).reshape(-1)

    def lin_view(pool):
        # table-linearized view: (B, Hkv, pages*Pg, D) — the extend
        # reads the whole live prefix once, gather-form
        return jnp.einsum("bphsd->bhpsd", pool[table]).reshape(
            B, Hkv, pages * Pg, Dh)

    def lin_scales(spool):
        # (pool, Hkv, 1, Pg) lane-major -> (B, Hkv, pages*Pg)
        return jnp.einsum("bphls->bhpls", spool[table]).reshape(
            B, Hkv, pages * Pg)

    def body(h, lp, state):
        k_pool, v_pool, ks_pool, vs_pool = state
        hn = _rmsnorm(h, lp["ln1_scale"])
        q, k_new, v_new = project_qkv(hn, lp, cfg)  # (B, c, H/Hkv, Dh)
        if cfg.pos_embed == "rope":
            q = apply_rope(q, positions, cfg)
            k_new = apply_rope(k_new, positions, cfg)
        rows_k = k_new.reshape(B * c, Hkv, Dh)
        rows_v = v_new.reshape(B * c, Hkv, Dh)
        if quant:
            rows_k, k_s = _quantize_rows(rows_k, cfg.kv_cache_dtype)
            rows_v, v_s = _quantize_rows(rows_v, cfg.kv_cache_dtype)
            ks_pool = _scale_write(ks_pool, pids, None, off, k_s, pages,
                                   False)
            vs_pool = _scale_write(vs_pool, pids, None, off, v_s, pages,
                                   False)
        k_pool = _pool_write(k_pool, pids, None, off, rows_k, pages, False,
                             tp)
        v_pool = _pool_write(v_pool, pids, None, off, rows_v, pages, False,
                             tp)
        if quant:
            kd = (lin_view(k_pool).astype(jnp.float32)
                  * lin_scales(ks_pool)[..., None])
            vd = (lin_view(v_pool).astype(jnp.float32)
                  * lin_scales(vs_pool)[..., None])
        else:
            kd = lin_view(k_pool).astype(jnp.float32)
            vd = lin_view(v_pool).astype(jnp.float32)
        qg = q.reshape(B, c, Hkv, g, Dh)
        s = jnp.einsum(
            "bckgd,bksd->bkgcs", qg.astype(jnp.float32), kd,
            precision=lax.Precision.HIGHEST,
        ) * scale
        row_pos = lax.broadcasted_iota(jnp.int32, s.shape, 4)
        q_pos = positions[:, None, None, :, None]  # (B,1,1,c,1)
        s = jnp.where(row_pos <= q_pos, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgcs,bksd->bckgd", p, vd,
                       precision=lax.Precision.HIGHEST)
        o = jnp.dot(o.reshape(B, c, cfg.attn_width).astype(dt),
                    matmul_weight(lp, "wo", dt))
        h = _mlp(h + o, lp, cfg)
        return h, (k_pool, v_pool, ks_pool, vs_pool)

    states = []
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        x, st = body(x, lp, (
            cache["k"][l], cache["v"][l],
            cache["k_scale"][l] if quant else None,
            cache["v_scale"][l] if quant else None,
        ))
        states.append(st)
    x = _rmsnorm(x, params["ln_f_scale"])
    logits = jnp.dot(x, matmul_weight(params, "lm_head", dt))
    out = {
        "k": tuple(s[0] for s in states),
        "v": tuple(s[1] for s in states),
        "table": table,
    }
    if quant:
        out["k_scale"] = tuple(s[2] for s in states)
        out["v_scale"] = tuple(s[3] for s in states)
    return logits.astype(jnp.float32), out


def paged_block_step(params, cache, pos, tokens, cfg: TransformerConfig,
                     active=None):
    """One forward over a BLOCK a row against the paged cache, for a model
    that generates by diffusion over blocks (``cfg.block_len``, all "R"
    layers): row ``b``'s ``tokens[b]`` (B, c = block_len) occupy positions
    ``pos[b] .. pos[b] + c - 1`` (``pos`` (B,) int32, a multiple of c:
    rows are ragged). Each layer writes the block's K/V rows into the
    pool at those positions, over whatever an earlier forward of the same
    block left there, and every position of the block attends over keys
    ``0 .. pos[b] + c - 1``: the stored blocks before it and ALL of its
    own, through one ``flash_decode_paged`` call with the block folded
    into the group (``cfg.decode_attn == "gather"``: the pure-XLA view).
    The write is PROVISIONAL while the block still holds masks (the next
    forward of the block overwrites it) and stands once the caller moves
    the row's cursor past the block. Returns (logits (B, c, vocab)
    float32, the logits AT a position predict that position, and the
    updated cache).

    ``active`` (B,) bool: rows that count. The others run at position 0,
    write nothing (their page ids point past the pool: the scatter drops
    them) and pick no expert.

    CONTRACT (as :func:`paged_decode_step`): ``pos[b] + c`` within the
    row's pages; a concrete ``pos`` is checked."""
    c = cfg.block_len
    if not c or tokens.ndim != 2 or tokens.shape[1] != c:
        raise ValueError(
            f"paged_block_step takes (batch, block_len = {c}) tokens of a "
            f"model with block_len > 0, got {tokens.shape}")
    if cfg.decode_attn not in ("flash", "gather") or _kv_quantized(cfg):
        raise ValueError(
            "paged_block_step is written for decode_attn 'flash' or "
            "'gather' over compute-dtype pools, not "
            f"{cfg.decode_attn!r} / kv_cache_dtype {cfg.kv_cache_dtype!r}")
    B = tokens.shape[0]
    table = cache["table"]
    n_pool, _, Pg, _ = cache["k"][0].shape
    pages = table.shape[1]
    if jnp.ndim(pos) != 1 or jnp.shape(pos)[0] != B:
        raise ValueError(
            f"pos must be (batch,)={B} per-row positions, got "
            f"{jnp.shape(pos)}")
    if not isinstance(pos, jax.core.Tracer):
        if np.any(np.asarray(pos) % c) or np.any(
                np.asarray(pos) + c > pages * Pg):
            raise ValueError(
                f"block starts {np.asarray(pos)} must be multiples of {c} "
                f"and end within the cache's {pages * Pg} tokens")
    dt = jnp.dtype(cfg.dtype)
    scale = 1.0 / (cfg.head_dim ** 0.5)
    if active is not None:
        pos = jnp.where(active, pos, 0)
    positions = pos[:, None] + jnp.arange(c, dtype=jnp.int32)   # (B, c)
    off = (positions % Pg).reshape(-1)
    pids = jnp.take_along_axis(table, positions // Pg, axis=1).reshape(-1)
    valid = None
    if active is not None:
        valid = jnp.broadcast_to(active[:, None], (B, c))
        pids = jnp.where(valid.reshape(-1), pids, n_pool)   # dropped
    with jax.named_scope("embed"):
        x = scaled(params["embed"].astype(dt)[tokens],
                   cfg.embedding_multiplier)
        if cfg.pos_embed == "learned":
            x = x + params["pos_embed"].astype(dt)[positions]

    @scoped("kv_write")
    def write(pool, rows):
        return _pool_write(pool, pids, None, off,
                           rows.reshape(B * c, cfg.kv_heads, cfg.head_dim),
                           pages, False)

    @scoped("attn")
    def attend(q, k_pool, v_pool):
        from hpc_patterns_tpu.ops import flash_decode

        if cfg.decode_attn == "gather":   # the same fold, the XLA view
            o = _paged_attend_gather(
                flash_decode.fold_block(q, cfg.kv_heads), k_pool, v_pool,
                None, None, table, pos + (c - 1), cfg, scale)
            return flash_decode.unfold_block(o, c, cfg.kv_heads)
        return flash_decode.flash_decode_paged_block(
            q, k_pool, v_pool, table, pos, active=active, scale=scale)

    ks, vs, stats = [], [], []
    for l, lp in enumerate(params["layers"]):
        hn = attn_norm(x, lp, cfg)
        with jax.named_scope("attn"):
            q, k_new, v_new = project_qkv(hn, lp, cfg)   # (B, c, H/Hkv, Dh)
            if cfg.pos_embed == "rope":
                q = apply_rope(q, positions, cfg)
                k_new = apply_rope(k_new, positions, cfg)
        k_pool = write(cache["k"][l], k_new)
        v_pool = write(cache["v"][l], v_new)
        x = x + attn_proj(attend(q, k_pool, v_pool), lp, cfg, dt)
        x, st = routed_mlp(x, lp, cfg, valid)
        ks.append(k_pool)
        vs.append(v_pool)
        stats.append(st)
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["ln_f_scale"], cfg.norm_eps)
        logits = head_logits(x, params, cfg)
    out = {"k": tuple(ks), "v": tuple(vs), "table": table}
    _apply_extra(cache, out, {"moe_stats": sum(stats)})
    return logits, out


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 8, 9, 10))
def _paged_generate_jit(params, prompt, cfg, new_tokens, page_size,
                        pages_per_seq, key, temperature, greedy, top_k,
                        mesh=None):
    B, T = prompt.shape
    cache = init_paged_cache(cfg, B, pages_per_seq, page_size)
    logits, cache = paged_prefill(params, prompt, cfg, cache, page_size,
                                  mesh=mesh)
    # the jit built its own default (identity) table above, so the
    # in-place DUS write path is sound
    return _generation_scan(
        lambda c, p, t: paged_decode_step(params, c, p, t, cfg,
                                          identity_layout=True,
                                          mesh=mesh),
        logits, cache, T, new_tokens, key, temperature, greedy, top_k,
    )


def paged_generate(params, prompt, cfg: TransformerConfig,
                   new_tokens: int, *, page_size: int = 512,
                   pages_per_seq: int | None = None, key=None,
                   temperature: float = 0.0, top_k: int = 0, mesh=None):
    """Continuation (B, new_tokens) int32 served from the paged cache —
    token-identical to :func:`generate` (the paged kernel reproduces
    the linear kernel's f32 math exactly; oracle-tested). The cache
    footprint is ``pages_per_seq * page_size`` tokens per sequence
    (default: just enough pages for prompt + new_tokens) instead of the
    linear cache's ``max_len`` — THE serving-capacity lever when the
    declared maximum is far above typical generation length. ``mesh``:
    tp-sharded paged serving (the two serving levers compose — see
    :func:`paged_decode_step`)."""
    if new_tokens < 1:
        raise ValueError(f"new_tokens must be >= 1, got {new_tokens}")
    B, T = prompt.shape
    need = T + new_tokens
    if need > cfg.max_seq:
        raise ValueError(
            f"prompt {T} + new {new_tokens} exceeds max_seq {cfg.max_seq}"
        )
    if pages_per_seq is None:
        pages_per_seq = -(-need // page_size)
    if pages_per_seq * page_size < need:
        raise ValueError(
            f"{pages_per_seq} pages of {page_size} < {need} tokens"
        )
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if key is None:
        key = jax.random.PRNGKey(0)
    with tracelib.compile_watch("decode.paged_generate",
                                _paged_generate_jit,
                                batch=B, prompt_len=T,
                                new_tokens=new_tokens,
                                page_size=page_size):
        return _paged_generate_jit(
            params, prompt, cfg, new_tokens, page_size, pages_per_seq,
            key, jnp.float32(max(temperature, 1e-6)),
            temperature <= 0.0, int(top_k), mesh,
        )
