"""Pipeline-parallel training for the flagship transformer.

The missing member of the parallelism matrix (dp/sp/tp/ep live in
models/transformer.py + models/sharding.py): layers split into P
contiguous stages over the ``pp`` mesh axis, driven by the 1F1B schedule
(parallel/pipeline.py — itself built on the reference's pt2pt ring,
SURVEY.md §2.2 "pairwise pt2pt: the core of PP").

Decomposition:

- **embedding** (embed + pos_embed): computed outside the pipeline on
  every rank (replicated math); its gradient comes back through the
  pipeline's input cotangents (``return_input_grads``).
- **stages**: the stacked layer params' leading ``n_layers`` axis is
  sharded over ``pp`` — each rank scans its ``L/P`` layers as one
  shape-preserving ``stage_fn``.
- **head** (ln_f_scale + lm_head): the last stage's loss head,
  differentiated via the pipeline's ``loss_params`` hook.

Gradients for the replicated pieces are psum'd over ``pp`` (only one
rank produces nonzero values — rank 0 for the embedding, rank P-1 for
the head — so the psum is a broadcast), exactly the §2.3 backend
property: collectives on device-resident shards, no host staging.

Composes with data parallelism: on a ("dp", "pp") mesh the batch is
dp-sharded outside, the pipeline runs per dp-slice, and gradients are
pmean'd over dp. The dp axis may cross slices (a DCN axis from
topology.make_hybrid_mesh): the once-per-step gradient pmean is the
latency-tolerant collective, while the per-tick stage ppermutes stay
slice-internal.

Composes with FSDP (ZeRO-3) over an ``fsdp`` mesh axis: stage params
are stored sharded on a feature dim (the same per-weight dims as
models/sharding.param_specs), all-gathered JUST BEFORE the stage scan
inside the pipeline shard_map, and their gradients leave as a
reduce-scatter (psum_scatter) back to the shard — params, grads, AND
optimizer state hold 1/fsdp of each stage weight per rank. The batch
shards over (dp, fsdp) together, like the non-pp fsdp path. The
embedding/head stay replicated (they are not stage params; shard them
over fsdp via the vocab dim if they ever dominate).

Composes with Megatron tensor parallelism over an ``tp`` mesh axis
INSIDE each stage (the canonical large-model layout: tp innermost over
ICI neighbors, pp across): stage weights column/row-split per
models/sharding.py's rule table, rank-local attention on local
q/kv-head shards, the f/g conjugate pair at region boundaries (explicit
custom_vjps — see the Megatron block below), two psums per layer.
Dense MLP stages only (MoE + tp rejected); the packed qkv weight is
column-permuted on the way in so contiguous tp splits align with the
q/k/v sections (public layout unchanged).

Composes with MoE: stages return their load-balance aux loss alongside
the activation and the 1F1B schedule threads it through
(``stage_aux_weight``) — the aux gradient rides the normal backward,
and the reported loss adds the psum'd aux term. Experts are
stage-local (dense routing per pp rank, no ep axis inside the
pipeline).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import optax

from jax import shard_map

from hpc_patterns_tpu.models.transformer import (
    TransformerConfig,
    _attention,
    _layer,
    _rmsnorm,
    apply_rope,
    chunked_masked_causal_nll,
    init_params,
    masked_causal_nll,
)
from hpc_patterns_tpu.models.train import make_optimizer
from hpc_patterns_tpu.parallel.pipeline import pipeline_train_1f1b


def _embed(outer, tokens, cfg):
    dt = jnp.dtype(cfg.dtype)
    T = tokens.shape[-1]
    x = outer["embed"].astype(dt)[tokens]
    if cfg.pos_embed == "learned":
        x = x + outer["pos_embed"].astype(dt)[:T]
    return x


def _stage_fn(layers_shard, h, cfg):
    """One pipeline stage: scan this rank's L/P layers (shape-preserving,
    single-device math — mesh=None inside the pp rank). MoE configs
    return ``(h, aux)`` — the stage-local load-balance loss sum, which
    the 1F1B schedule threads through via ``stage_aux_weight`` (experts
    are stage-local here: dense routing per rank, no ep axis inside the
    pipeline)."""
    def body(carry, lp):
        x, aux = carry
        x, a = _layer(x, lp, cfg, mesh=None, act_spec=None)
        return (x, aux + a), None

    (h, aux), _ = lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                           layers_shard)
    if cfg.n_experts:
        return h, aux
    return h


# ---------------------------------------------------------------------------
# Megatron TP inside pipeline stages
# ---------------------------------------------------------------------------
#
# Stage math runs rank-local inside the pipeline shard_map, so tensor
# parallelism here is the MANUAL Megatron form: column-parallel
# qkv/up-projections, row-parallel out/down-projections, and the f/g
# conjugate operators at the region boundaries. f and g are explicit
# custom_vjps (identity-fwd/psum-bwd and psum-fwd/identity-bwd) rather
# than relying on lax.psum's transpose under check_vma=False — psum
# transposing to psum would double-count the replicated residual
# cotangent by a factor of tp (the documented shard_map AD footgun).
# This is the building-block composition SURVEY.md §2.2 calls for: the
# row-parallel reduction IS the reference's allreduce
# (allreduce-mpi-sycl.cpp:61-67) riding inside a pipeline stage.


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_f(x, axis):
    """Megatron's f: identity forward; backward psums the cotangent
    over ``axis`` (the input is replicated over tp, and each rank only
    computes its own column-shard's contribution)."""
    return x


def _tp_f_fwd(x, axis):
    return x, None


def _tp_f_bwd(axis, _, ct):
    return (lax.psum(ct, axis),)


_tp_f.defvjp(_tp_f_fwd, _tp_f_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_g(x, axis):
    """Megatron's g: psum forward (the row-parallel reduction);
    backward passes the replicated cotangent straight through to every
    rank's partial sum."""
    return lax.psum(x, axis)


def _tp_g_fwd(x, axis):
    return lax.psum(x, axis), None


def _tp_g_bwd(axis, _, ct):
    return (ct,)


_tp_g.defvjp(_tp_g_fwd, _tp_g_bwd)


def tp_permute_wqkv(wqkv, cfg: TransformerConfig, tp: int):
    """Reorder the packed-qkv columns ``[q | k | v]`` into per-rank
    blocks ``[q_0|k_0|v_0 | q_1|k_1|v_1 | ...]`` so a contiguous
    last-dim split over tp hands each rank its own q/k/v sections (a
    naive contiguous split of the packed layout would cut across the
    sections). Pure column gather — applied once per step on the way
    into the pipeline shard_map; the public param layout stays
    standard."""
    D = cfg.attn_width
    S = cfg.kv_heads * cfg.head_dim
    q, k, v = jnp.split(wqkv, [D, D + S], axis=-1)
    qs = jnp.split(q, tp, axis=-1)
    ks = jnp.split(k, tp, axis=-1)
    vs = jnp.split(v, tp, axis=-1)
    return jnp.concatenate(
        [jnp.concatenate([qs[r], ks[r], vs[r]], axis=-1)
         for r in range(tp)],
        axis=-1,
    )


def tp_unpermute_wqkv(wqkv_p, cfg: TransformerConfig, tp: int):
    """Inverse of :func:`tp_permute_wqkv` (applied to the wqkv gradient
    on the way out, so optimizer/checkpoint/oracle all see the standard
    packed layout)."""
    Dl = cfg.attn_width // tp
    Sl = cfg.kv_heads * cfg.head_dim // tp
    qs, ks, vs = [], [], []
    for blk in jnp.split(wqkv_p, tp, axis=-1):
        qb, kb, vb = jnp.split(blk, [Dl, Dl + Sl], axis=-1)
        qs.append(qb)
        ks.append(kb)
        vs.append(vb)
    return jnp.concatenate(qs + ks + vs, axis=-1)


def _tp_layer(x, lp, cfg: TransformerConfig, axis_tp: str, tp: int):
    """One pre-norm block with Megatron TP over ``axis_tp``: local
    q/kv heads (column split), rank-local attention (heads are
    embarrassingly parallel; GQA stays narrow — tp must divide
    kv_heads), row-parallel wo and w2 closed by g. Activations x are
    replicated over tp; exactly two psums per layer."""
    B, T, D = x.shape
    dt = x.dtype
    Hl, Hkvl, Dh = cfg.n_heads // tp, cfg.kv_heads // tp, cfg.head_dim
    Dl = cfg.attn_width // tp

    a = _tp_f(x, axis_tp)
    h = _rmsnorm(a, lp["ln1_scale"])
    qkv = jnp.dot(h, lp["wqkv"].astype(dt))  # local [q_r|k_r|v_r]
    q, k, v = jnp.split(qkv, [Dl, Dl + Hkvl * Dh], axis=-1)
    q = q.reshape(B, T, Hl, Dh)
    k = k.reshape(B, T, Hkvl, Dh)
    v = v.reshape(B, T, Hkvl, Dh)
    if cfg.pos_embed == "rope":
        pos = lax.broadcasted_iota(jnp.int32, (T,), 0)
        q = apply_rope(q, pos, cfg)
        k = apply_rope(k, pos, cfg)
    o = _attention(q, k, v, cfg, None).reshape(B, T, Dl)
    x = x + _tp_g(jnp.dot(o, lp["wo"].astype(dt)), axis_tp)

    b = _tp_f(x, axis_tp)
    h2 = _rmsnorm(b, lp["ln2_scale"])
    if cfg.mlp_impl == "fused":
        from hpc_patterns_tpu.ops.fused_mlp import fused_mlp

        y = fused_mlp(h2, lp["w1"].astype(dt), lp["w2"].astype(dt))
    else:
        y = jnp.dot(jax.nn.gelu(jnp.dot(h2, lp["w1"].astype(dt))),
                    lp["w2"].astype(dt))
    return x + _tp_g(y, axis_tp)


def _tp_stage_fn(layers_shard, h, cfg, axis_tp, tp):
    """TP counterpart of :func:`_stage_fn` (dense MLP only — pp x tp
    with MoE stages is rejected upstream)."""
    def body(x, lp):
        return _tp_layer(x, lp, cfg, axis_tp, tp), None

    h, _ = lax.scan(body, h, layers_shard)
    return h


def check_tp(cfg: TransformerConfig, tp: int):
    if cfg.n_experts:
        raise ValueError(
            "pp x tp with MoE stages is unsupported: experts route "
            "densely per stage (use ep outside pp, or tp without "
            "experts)"
        )
    for name, val in (("d_model", cfg.d_model), ("n_heads", cfg.n_heads),
                      ("kv_heads", cfg.kv_heads), ("d_ff", cfg.d_ff)):
        if val % tp:
            raise ValueError(
                f"{name} {val} must divide by tp={tp} for Megatron "
                "stage sharding"
            )


def _loss_head(lp, y, target_tokens, *, loss_chunk: int = 0):
    """Final-norm + LM head + the shared masked causal NLL
    (transformer.masked_causal_nll — identical loss semantics to
    transformer.loss_fn by construction). With ``loss_chunk`` the NLL is
    the online-logsumexp chunked form: the per-microbatch (b, T, vocab)
    logits never materialize, which is where the long-context memory
    wall bites hardest inside a pipeline stage (the 1F1B tick holds the
    stage's activations AND the loss head's intermediates live)."""
    x = _rmsnorm(y, lp["ln_f_scale"])
    if loss_chunk:
        return chunked_masked_causal_nll(
            x, lp["lm_head"].astype(y.dtype), target_tokens,
            chunk=loss_chunk,
        )
    logits = jnp.dot(x, lp["lm_head"].astype(y.dtype)).astype(jnp.float32)
    return masked_causal_nll(logits, target_tokens)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_pmax_sg(x, axis):
    """stop-gradient pmax over ``axis``: lax.pmax has no
    differentiation rule at all (even a downstream stop_gradient
    doesn't save the trace), and a logsumexp stability shift's
    cotangent is identically zero anyway — so the backward is an
    explicit zero."""
    return lax.pmax(x, axis)


def _tp_pmax_sg_fwd(x, axis):
    return lax.pmax(x, axis), None


def _tp_pmax_sg_bwd(axis, _, ct):
    return (jnp.zeros_like(ct),)


_tp_pmax_sg.defvjp(_tp_pmax_sg_fwd, _tp_pmax_sg_bwd)


def _loss_head_tp(lp, y, target_tokens, *, axis_tp: str):
    """Vocab-sharded pipeline loss head: the last stage's lm_head is
    column-split over tp (each rank holds V/tp vocab columns — the
    Megatron head), so per-rank logits are (b, T, V/tp) instead of the
    full vocabulary replicated per tp rank, and the masked causal NLL
    comes out of sharded-softmax reductions. The tp sums ride the g
    operator (psum-fwd/identity-bwd — lax.psum's transpose under
    check_vma=False would be wrong, same as the layer math) and the
    stability max-shift is stop_gradient'd (exact: a logsumexp shift's
    cotangent is identically zero). ``y`` enters through f so the
    stage backward receives a REPLICATED cotangent (each rank only
    computes the contribution through its own vocab columns).
    Numerically masked_causal_nll at f32, oracle-tested."""
    y = _tp_f(y, axis_tp)
    x = _rmsnorm(y, lp["ln_f_scale"])
    logits = jnp.dot(x, lp["lm_head"].astype(y.dtype)).astype(
        jnp.float32)  # (b, T, V/tp)
    B, T = target_tokens.shape
    targets = jnp.roll(target_tokens, -1, axis=1)
    v_loc = logits.shape[-1]
    lo = lax.axis_index(axis_tp) * v_loc
    m = _tp_pmax_sg(jnp.max(logits, axis=-1), axis_tp)
    se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
    t_loc = targets - lo
    in_shard = (t_loc >= 0) & (t_loc < v_loc)
    gold_local = jnp.take_along_axis(
        logits, jnp.clip(t_loc, 0, v_loc - 1)[..., None], axis=-1
    )[..., 0]
    # one stacked psum for both reductions (se and the masked gold
    # logit share the (B, T) shape; the pmax above must stay separate
    # — se depends on m)
    se, gold = _tp_g(
        jnp.stack([se, jnp.where(in_shard, gold_local, 0.0)]), axis_tp)
    logz = m + jnp.log(se)
    nll = logz - gold
    mask = (lax.broadcasted_iota(jnp.int32, (B, T), 1)
            < T - 1).astype(nll.dtype)
    return jnp.sum(nll * mask) / jnp.sum(mask)


def _pp_layer_specs(cfg: TransformerConfig, axis_pp: str,
                    axis_fsdp: str | None, axis_tp: str | None = None):
    """Per-leaf PartitionSpecs for the stacked layer params inside the
    pipeline: leading ``n_layers`` axis over pp, and (with
    ``axis_fsdp``/``axis_tp``) the same per-weight feature dims models/
    sharding.param_specs shards under fsdp and Megatron tp — one rule
    table, three parallelism schemes. ep axes are dropped (no expert
    axis inside pipeline stages); tp is dropped unless requested."""
    import dataclasses

    from hpc_patterns_tpu.models import sharding as shardlib

    base = shardlib.param_specs(
        dataclasses.replace(cfg, fsdp=bool(axis_fsdp),
                            axis_fsdp=axis_fsdp or "fsdp",
                            axis_tp=axis_tp or "tp")
    )["layers"]
    keep = {ax for ax in (axis_fsdp, axis_tp) if ax}

    def fix(spec):
        rest = [ax if ax in keep else None for ax in spec[1:]]
        return P(axis_pp, *rest)

    return jax.tree.map(fix, base, is_leaf=lambda x: isinstance(x, P))


def _fsdp_dim(spec, axis_fsdp):
    """Index of the fsdp-sharded dim in a layer-leaf spec (None when
    the leaf is replicated over fsdp — norm scales, router)."""
    for i, ax in enumerate(spec):
        if ax == axis_fsdp:
            return i
    return None


def pp_loss_and_grads(params, tokens, cfg: TransformerConfig, mesh,
                      *, microbatches: int, axis_pp: str = "pp",
                      axis_dp: str | None = None,
                      axis_fsdp: str | None = None,
                      axis_tp: str | None = None):
    """Mean causal-LM loss and full-parameter gradients via a 1F1B
    pipeline over ``axis_pp`` (optionally data-parallel over ``axis_dp``,
    ZeRO-3-sharded over ``axis_fsdp``, and/or Megatron tensor-parallel
    INSIDE each stage over ``axis_tp`` — see module docstring).

    ``params``: the standard init_params pytree (layers stacked on
    n_layers, which must divide by the pp axis size); with
    ``axis_fsdp``, layer leaves sharded per
    :func:`init_pp_train_state`'s placement. ``tokens``: (batch, seq)
    int32, batch divisible by microbatches (× dp × fsdp size).
    Loss, embedding, and head gradients are replicated on return;
    layer gradients return fsdp-sharded when ``axis_fsdp`` is set
    (matching the param storage, what the optimizer update consumes).

    ``axis_tp``: the canonical large-model layout — tp innermost (ICI
    neighbors), stage weights column/row-split per models/sharding.py's
    rule table, activations replicated over tp, two psums per layer
    (see the Megatron block above). The loss head is vocab-sharded too
    (lm_head column-split over tp, V/tp logits per rank, sharded-
    softmax NLL — :func:`_loss_head_tp`) whenever vocab divides by tp
    and ``loss_chunk`` is off; otherwise it falls back to the
    replicated head (chunked when ``loss_chunk`` is set). Tokens are
    shared across tp. MoE stages reject tp.
    """
    M = microbatches
    from hpc_patterns_tpu.models.transformer import QUANT_SCALE_SUFFIX

    if any(k.endswith(QUANT_SCALE_SUFFIX)
           for k in (*params, *params["layers"])):
        raise ValueError(
            "pp_loss_and_grads refuses an int8-quantized params tree "
            "(quantize_weights_int8): the pipeline's stage math spells "
            "its own matmuls and would apply raw int8 magnitudes — "
            "quantized weights are a decode-serving artifact "
            "(transformer.matmul_weight; docs/quantization.md)")
    pp = mesh.shape[axis_pp]
    L = cfg.n_layers
    if L % pp:
        raise ValueError(f"n_layers {L} must divide by pp={pp}")
    B = tokens.shape[0]
    dp = mesh.shape[axis_dp] if axis_dp else 1
    fs = mesh.shape[axis_fsdp] if axis_fsdp else 1
    tp = mesh.shape[axis_tp] if axis_tp else 1
    if tp == 1:
        axis_tp = None  # size-1 tp axis: plain stage math
    else:
        check_tp(cfg, tp)
    # Megatron (vocab-sharded) loss head whenever it can serve;
    # otherwise the replicated head stays available as the fallback
    # (loss_chunk keeps its chunked form, and a vocab tp doesn't
    # divide keeps full-vocab logits per rank)
    shard_head = bool(axis_tp) and cfg.vocab % tp == 0 and not cfg.loss_chunk
    if B % (M * dp * fs):
        raise ValueError(
            f"batch {B} must divide by microbatches*dp*fsdp={M * dp * fs}"
        )
    layer_specs = _pp_layer_specs(cfg, axis_pp, axis_fsdp, axis_tp)
    if axis_fsdp:
        for name, spec in layer_specs.items():
            d = _fsdp_dim(spec, axis_fsdp)
            if d is None:
                continue
            size = params["layers"][name].shape[d]
            if size % fs:
                raise ValueError(
                    f"layers[{name}] dim {d} ({size}) must divide by "
                    f"fsdp={fs}"
                )

    outer = {"embed": params["embed"]}
    if cfg.pos_embed == "learned":
        outer["pos_embed"] = params["pos_embed"]
    head = {"ln_f_scale": params["ln_f_scale"], "lm_head": params["lm_head"]}

    def local(outer, layers_shard, head, tokens_local):
        toks = tokens_local.reshape(M, -1, tokens_local.shape[-1])
        x_mb = _embed(outer, toks, cfg)

        if axis_fsdp:
            # ZeRO-3 gather: materialize this stage's full weights just
            # before use (the stored shard is 1/fs of each feature dim)
            layers_full = {
                k: (v if _fsdp_dim(layer_specs[k], axis_fsdp) is None
                    else lax.all_gather(
                        v, axis_fsdp,
                        axis=_fsdp_dim(layer_specs[k], axis_fsdp),
                        tiled=True,
                    ))
                for k, v in layers_shard.items()
            }
        else:
            layers_full = layers_shard

        stage = (partial(_tp_stage_fn, cfg=cfg, axis_tp=axis_tp, tp=tp)
                 if axis_tp else partial(_stage_fn, cfg=cfg))
        loss, layer_grads, extras = pipeline_train_1f1b(
            stage,
            layers_full,
            x_mb,
            toks,
            (partial(_loss_head_tp, axis_tp=axis_tp) if shard_head
             else partial(_loss_head, loss_chunk=cfg.loss_chunk)),
            axis_pp,
            loss_params=head,
            return_input_grads=True,
            stage_aux_weight=cfg.moe_aux_weight if cfg.n_experts else None,
        )

        # embedding backward: cotangents of the pipeline inputs (nonzero
        # on pp rank 0) pulled through the replicated embedding math
        _, embed_vjp = jax.vjp(lambda o: _embed(o, toks, cfg), outer)
        (outer_grads,) = embed_vjp(extras["input_grads"].astype(x_mb.dtype))

        # replicate the rank-local pieces: loss and head grads live on
        # the last pp rank, embedding grads on rank 0, so psum = broadcast
        loss = lax.psum(loss, axis_pp)
        if cfg.n_experts:
            # total load-balance loss: stage-local sums live per rank;
            # psum over pp = the sum over all layers, / M for the
            # per-microbatch mean (matching transformer.loss_fn, whose
            # aux is summed over layers on the whole batch)
            aux_mean = lax.psum(extras["aux_sum"], axis_pp) / M
            loss = loss + cfg.moe_aux_weight * aux_mean
        head_grads = jax.tree.map(lambda g: lax.psum(g, axis_pp),
                                  extras["loss_grads"])
        if shard_head:
            # sharded-head grads: lm_head's shard is per-rank unique,
            # but ln_f_scale is replicated over tp and each rank only
            # computed the contribution through its own vocab columns.
            # (The replicated-head fallback needs neither: its grads
            # are identical across tp ranks.)
            head_grads = dict(head_grads)
            head_grads["ln_f_scale"] = lax.psum(
                head_grads["ln_f_scale"], axis_tp)
        outer_grads = jax.tree.map(
            lambda g: lax.psum(
                jnp.where(lax.axis_index(axis_pp) == 0, g.astype(jnp.float32),
                          jnp.zeros_like(g, jnp.float32)),
                axis_pp,
            ),
            outer_grads,
        )
        if axis_tp:
            # tp-replicated stage leaves (the norm scales): each rank
            # only computed its own column-shard's contribution through
            # the f region, so the true grad is the sum over tp
            layer_grads = {
                k: (lax.psum(g, axis_tp)
                    if axis_tp not in layer_specs[k] else g)
                for k, g in layer_grads.items()
            }
        if axis_fsdp:
            # ZeRO-3 reduce-scatter: each rank keeps the grad tile of
            # the shard it stores; /fs makes it the MEAN over the fsdp
            # batch shards (the dp convention)
            layer_grads = {
                k: (lax.pmean(g, axis_fsdp)
                    if _fsdp_dim(layer_specs[k], axis_fsdp) is None
                    else lax.psum_scatter(
                        g, axis_fsdp,
                        scatter_dimension=_fsdp_dim(layer_specs[k],
                                                    axis_fsdp),
                        tiled=True,
                    ) / fs)
                for k, g in layer_grads.items()
            }
        small = (outer_grads, head_grads)
        for ax in (axis_dp, axis_fsdp):
            if ax:
                loss = lax.pmean(loss, ax)
                small = jax.tree.map(lambda g: lax.pmean(g, ax), small)
        if axis_dp:
            layer_grads = jax.tree.map(
                lambda g: lax.pmean(g, axis_dp), layer_grads
            )
        outer_grads, head_grads = small
        grads_all = (outer_grads, layer_grads, head_grads)
        # grads are summed over microbatches; the loss head is per-
        # microbatch mean, so divide by M for the mean-loss gradient
        return loss[None], *jax.tree.map(lambda g: g / M, grads_all)

    layers_in = params["layers"]
    if axis_tp:
        # per-rank packed-qkv blocks so the contiguous tp split lands
        # each rank its own q/k/v sections; grads unpermute below
        layers_in = dict(layers_in)
        layers_in["wqkv"] = tp_permute_wqkv(layers_in["wqkv"], cfg, tp)

    batch_axes = tuple(a for a in (axis_dp, axis_fsdp) if a)
    tok_spec = P(batch_axes) if batch_axes else P()
    # with the Megatron head, lm_head enters column-split over tp and
    # the final norm replicated
    head_specs = ({"ln_f_scale": P(), "lm_head": P(None, axis_tp)}
                  if shard_head else P())
    loss_spec = (P((*batch_axes, axis_pp)) if batch_axes else P(axis_pp))
    loss_r, outer_g, layer_g, head_g = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), layer_specs, head_specs, tok_spec),
        out_specs=(loss_spec, P(), layer_specs, head_specs),
        check_vma=False,  # validity masks + psum-broadcasts aren't VMA-provable
    )(outer, layers_in, head, tokens)
    if axis_tp:
        layer_g = dict(layer_g)
        layer_g["wqkv"] = tp_unpermute_wqkv(layer_g["wqkv"], cfg, tp)

    # pin the scalar replicated: XLA may otherwise leave it sharded
    # along an axis that spans OS processes (observed with pp x tp in
    # a 2-process launch), making float(loss) fail on non-addressable
    # ranks
    from jax.sharding import NamedSharding

    loss = lax.with_sharding_constraint(
        loss_r[0], NamedSharding(mesh, P()))
    grads = {
        "embed": outer_g["embed"],
        "layers": layer_g,
        "ln_f_scale": head_g["ln_f_scale"],
        "lm_head": head_g["lm_head"],
    }
    if "pos_embed" in outer_g:
        grads["pos_embed"] = outer_g["pos_embed"]
    return loss, grads


def make_pp_train_step(cfg: TransformerConfig, mesh, *, microbatches: int,
                       axis_pp: str = "pp", axis_dp: str | None = None,
                       axis_fsdp: str | None = None,
                       axis_tp: str | None = None, optimizer=None,
                       offload_opt_example=None):
    """Jitted ``step(params, opt_state, tokens) -> (loss, params,
    opt_state)`` training the full model through the 1F1B pipeline.

    ``axis_fsdp``: ZeRO-3 stage params (see :func:`pp_loss_and_grads`);
    the layer gradients arrive sharded like the params, so the
    optimizer update runs shard-local. ``offload_opt_example``: a
    host-resident optimizer state (models/train.offload_opt_state) —
    the update pulls it to HBM, applies, pushes back, all inside the
    one jit, exactly the sharded-train path's offload contract (the
    pipeline state lives inside the shard_map, but the OPTIMIZER state
    never does — it updates outside, where memory-kind streaming
    composes unchanged)."""
    optimizer = optimizer or make_optimizer()
    if offload_opt_example is not None:
        # tolerant of offload_opt_state's probe-gated identity
        # fallback (no usable pinned_host -> the example was left in
        # place and the tiers collapse), same as make_train_step
        from hpc_patterns_tpu.models.train import (
            offload_example_shardings,
        )

        host_sh, hbm_sh = offload_example_shardings(offload_opt_example)
    else:
        host_sh = hbm_sh = None

    def step(params, opt_state, tokens):
        if hbm_sh is not None:
            opt_state = jax.device_put(opt_state, hbm_sh)
        loss, grads = pp_loss_and_grads(
            params, tokens, cfg, mesh, microbatches=microbatches,
            axis_pp=axis_pp, axis_dp=axis_dp, axis_fsdp=axis_fsdp,
            axis_tp=axis_tp,
        )
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if host_sh is not None:
            opt_state = jax.device_put(opt_state, host_sh)
        return loss, params, opt_state

    # the loss OUTPUT is pinned replicated at the jit boundary: the
    # internal with_sharding_constraint alone can be overridden by the
    # partitioner's output placement, and a loss left sharded along a
    # process-spanning axis (seen with pp x tp under a 2-process
    # launch) breaks float(loss) on non-addressable ranks
    from jax.sharding import NamedSharding

    rep = NamedSharding(mesh, P())
    if host_sh is not None:
        return jax.jit(
            step, donate_argnums=(0, 1),
            in_shardings=(None, host_sh, None),
            out_shardings=(rep, None, host_sh),
        )
    return jax.jit(step, donate_argnums=(0, 1),
                   out_shardings=(rep, None, None))


def init_pp_train_state(key, cfg: TransformerConfig, optimizer=None,
                        mesh=None, *, axis_pp: str = "pp",
                        axis_fsdp: str | None = None):
    """f32 params + opt state. Replicated by default (the layer stack's
    leading axis is what the pp shard_map slices); with ``mesh`` and
    ``axis_fsdp``, layer leaves are PLACED sharded over (pp, fsdp) —
    each rank materializes only its own stage-weight shard, and the
    optax state inherits the placement (zeros_like preserves
    sharding)."""
    optimizer = optimizer or make_optimizer()
    if mesh is not None and axis_fsdp:
        from jax.sharding import NamedSharding

        specs = _pp_layer_specs(cfg, axis_pp, axis_fsdp)
        shardings = {
            "layers": jax.tree.map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P),
            )
        }
        replicated = NamedSharding(mesh, P())
        full = jax.tree.map(
            lambda _: replicated,
            jax.eval_shape(lambda k: init_params(k, cfg), key),
        )
        full["layers"] = shardings["layers"]
        # jaxlint: disable=recompile-hazard — init-time one-shot (once
        # per pp train state); out_shardings close over the runtime mesh
        params = jax.jit(
            lambda k: init_params(k, cfg), out_shardings=full
        )(key)
    else:
        params = init_params(key, cfg)
    return params, optimizer.init(params)
