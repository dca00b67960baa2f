"""Decoder-only transformer, TPU-first.

Architecture choices driven by the hardware (SURVEY.md preamble +
/opt/skills/guides/pallas_guide.md):

- all matmuls shaped for the MXU: bf16 compute dtype, model dims kept in
  multiples of 128, no per-layer Python loop — layers are stacked on a
  leading axis and driven by ``lax.scan`` (one traced layer body);
- attention is pluggable: ``"full"`` (single-device oracle),
  ``"flash"`` (the Pallas blockwise kernel, ops/flash_attention.py —
  single device, or any mesh that leaves the sequence unsharded),
  ``"ring"`` (context parallelism over the ``sp`` mesh axis — the
  reference's ring dataflow, parallel/ring_attention.py),
  ``"ring_flash"`` (the same ring with the Pallas kernel as each
  step's local compute), ``"ulysses"`` (all-to-all SP), or
  ``"ulysses_flash"`` (Ulysses with the Pallas kernel as the
  rank-local full-sequence attention);
- activation sharding is annotated with ``with_sharding_constraint``;
  parameter shardings live in models/sharding.py (Megatron column/row
  rules, ≙ parallel/tensor.py helpers);
- optional remat trades FLOPs for HBM (the bandwidth-vs-memory lever),
  with a policy axis (``remat_policy``): the default "split" leaves the
  attention kernel outside any remat region so its custom_vjp
  residuals persist and the flash forward runs exactly once per step
  (builder-measured on an older toolchain — ROADMAP.md Design 9).

Params are a plain pytree of f32 arrays (master weights). Training
keeps them and ``forward`` casts to ``cfg.dtype`` (bf16 by default) at
use; a server, which never updates them, holds
:func:`serving_weights` of the tree: the same cast made once
(models/serving.EngineCore), after which the casts at use emit nothing.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial, wraps
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from hpc_patterns_tpu.models.sharding_util import mesh_axis_size, resolve_spec
from jax import shard_map
from hpc_patterns_tpu.parallel.ring_attention import full_attention, ring_attention
from hpc_patterns_tpu.parallel.ulysses import ulysses_attention

ATTENTION_IMPLS = ("full", "flash", "ring", "ring_flash", "ulysses",
                   "ulysses_flash")


class LayerKind(NamedTuple):
    """What a layer of one kind holds: K/V (a pool of pages in the
    cache), a row of recurrent state (convolution tail, S), and which
    MLP closes it ("" none, "gelu" the default block's, "gated" the
    SiLU-gated one, "routed" a softmax top-k of SiLU-gated experts)."""
    kv: bool
    state: bool
    mlp: str


#: THE table of layer kinds, by the pattern's character: "B" spells the
#: default block (no pattern names it). Read by the config's counts and
#: by every loop over a pattern (forward_hidden, decode.prefill,
#: decode._token_step, decode.init_layer_state)
LAYER_KINDS = {
    "B": LayerKind(kv=True, state=False, mlp="gelu"),
    "*": LayerKind(kv=True, state=False, mlp=""),
    "M": LayerKind(kv=False, state=True, mlp=""),
    "E": LayerKind(kv=False, state=False, mlp=""),
    "H": LayerKind(kv=True, state=True, mlp="gated"),
    "R": LayerKind(kv=True, state=False, mlp="routed"),
}


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32768
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_seq: int = 2048
    dtype: str = "bfloat16"  # compute dtype (MXU-native)
    attention: str = "full"  # full | flash | ring[_flash] | ulysses[_flash]
    # grouped-query attention: 0 = MHA (kv heads == n_heads); smaller
    # values share each KV head across n_heads/n_kv_heads query heads,
    # shrinking the qkv projection (weights + FLOPs), the KV cache, AND
    # attention-side K/V activations — every impl consumes the narrow
    # K/V (grouped-query scores, no expansion; the ring circulates
    # group-factor less K/V). n_heads must divide by n_kv_heads
    n_kv_heads: int = 0
    # remat=True recomputes layer activations in backward; remat_policy
    # picks what is SAVED anyway (the FLOPs/HBM trade):
    #   "nothing" — recompute everything (max memory saving);
    #   "attn"    — save each attention output (the flash kernel's
    #               backward only needs its out/lse residuals, so
    #               re-running the kernel forward in the backward pass
    #               is pure waste — this skips exactly that);
    #   "dots"    — save all matmul outputs with no batch dims
    #               (jax.checkpoint_policies.dots_with_no_batch_dims)
    #   "dots_attn" — both of the above (note: a remat policy CANNOT
    #               stop the flash forward kernel re-running in the
    #               backward — custom_vjp residuals (out, lse) are
    #               internal to the kernel call, and saving the named
    #               attention output doesn't save them)
    #   "split"   — checkpoint the qkv-projection block and the
    #               mlp/residual block SEPARATELY and leave attention
    #               outside any remat region, so the flash kernel's own
    #               vjp residuals persist and its forward runs exactly
    #               once (the kernel was profiled at ~25% of step time;
    #               the replay is the removable quarter of it). Costs
    #               q/k/v/out (+lse) per layer in HBM; the big per-layer
    #               interiors (d_ff gelu, qkv matmul) still recompute.
    remat_policy: str = "split"
    # scan_layers=True drives the stacked layer weights with one traced
    # lax.scan body (fast compiles, the long-model default);
    # False unrolls the layer loop — each layer's weight slice becomes
    # static, XLA drops the per-iteration dynamic-slice copies of the
    # weight stack (builder-measured flat on an older toolchain —
    # ROADMAP.md Design 9)
    scan_layers: bool = True
    # positional scheme: "learned" absolute table, or "rope" rotary
    # embeddings (relative; the long-context default — composes with
    # ring/ulysses sequence sharding because rotation angles are a
    # function of GLOBAL position only, applied before the shard_map)
    pos_embed: str = "learned"
    rope_theta: float = 10000.0
    remat: bool = False
    # mixture-of-experts: 0 = dense MLP; >0 = Switch-style top-1 MoE
    # with experts sharded over the ep axis (parallel/moe.py)
    n_experts: int = 0
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # experts consulted per token: 1 = Switch top-1; k >= 2 routes each
    # token to its k highest-gate experts with the k gates renormalized
    # (GShard style, first choices claim capacity slots before any
    # second choice). Drop telemetry for either: moe_drop_rates
    n_experts_top_k: int = 1
    # routing dispatch: "einsum" (one-hot (N, E, C) tensors — oracle
    # form, O(N²·cf/E) memory), "scatter" (stable-sort, O(N + E·C) —
    # identical assignments, the at-scale form), or "auto" (scatter
    # once the one-hot tensors would exceed ~16 MB)
    moe_dispatch: str = "auto"
    # fully-sharded data parallelism (ZeRO-3 style): params, grads, and
    # optimizer state shard over axis_fsdp; XLA inserts the per-layer
    # all-gather (fwd/bwd) and gradient reduce-scatter from the
    # annotations alone — GSPMD is the FSDP engine, no wrapper class.
    # The batch shards over (dp, fsdp) together. Set axis_fsdp = "dp"
    # to fully shard over the data ranks with a single axis.
    fsdp: bool = False
    axis_fsdp: str = "fsdp"
    # chunked cross-entropy: 0 = dense (materialize (B, T, V) f32
    # logits); > 0 = online-logsumexp over vocab chunks of this size —
    # the logits never exist, removing the long-context memory wall
    # (see chunked_masked_causal_nll). Must divide vocab. Training-loss
    # path only (eval/decode read real logits).
    loss_chunk: int = 0
    # training MLP implementation: "dense" = two XLA einsums (gelu
    # fused by XLA; the (N, d_ff) activation materializes in HBM
    # between them), "fused" = the Pallas fused kernel
    # (ops/fused_mlp.py — matmul→gelu→matmul streamed through VMEM,
    # d_ff activation never in HBM; one-pass fused backward). Dense
    # MLP layers only (MoE routes through parallel/moe.py)
    mlp_impl: str = "dense"
    # decode-step attention against the KV cache (models/decode.py):
    # "flash" = the single-query Pallas kernel streaming the live cache
    # prefix (ops/flash_decode.py); "gather" = the XLA einsum+mask path
    # over the full static cache — required for GSPMD-sharded (tp)
    # serving, where einsums partition but a pallas_call does not;
    # "paged_flash" = the paged-pool Pallas kernel
    # (ops/paged_attention.py): pages gather through the table into
    # VMEM with a clamped index map (unfilled pages are never fetched)
    # and the attention mirrors the gather math term for term —
    # bitwise-equal to "gather" on compute-dtype pools, in-kernel
    # dequant on int8/fp8 pools. Paged routes only; the linear-cache
    # paths (prefill, decode_step) treat it as "gather", so prefill
    # bytes stay identical between the two routes.
    decode_attn: str = "flash"
    # KV-cache storage dtype for decode: "compute" (the model dtype),
    # "int8" (per-row symmetric quantization — HALF the cache bytes and
    # per-step read traffic on the cache-read-bound decode path;
    # dequantized in the kernel/einsum stream), or "fp8"
    # (float8_e4m3fn storage with the same per-row scale layout — the
    # same byte win with ~2 more bits of mantissa headroom; probe
    # backend support with dtypes.supports_fp8, docs/quantization.md)
    kv_cache_dtype: str = "compute"
    # the layer pattern: "" = ``n_layers`` of the one block (attention +
    # MLP/MoE, the default and everything above). Otherwise one character
    # a layer (LAYER_KINDS). Three are ONE mixer under a pre-norm
    # residual: "*" attention alone (GQA, no MLP), "M" a Mamba-2 mixer
    # (models/ssm.py), "E" a LatentMoE layer on this chip's share of the
    # experts (parallel/moe.latent_moe). "H" is the parallel hybrid
    # block: attention AND the Mamba-2 mixer off one norm, summed into
    # the residual, then a second norm and a SiLU-gated MLP of width
    # ``d_ff``. "R" is attention, then a second norm and ROUTED experts: a
    # softmax over ``moe_experts``, the ``moe_top_k`` largest (their gates
    # renormalised to one where ``moe_renorm``), each expert a SiLU-gated
    # MLP of width ``moe_d_ff`` (parallel/moe.gated_moe), on this chip's
    # share ``moe_held_start .. + moe_held`` of them. A patterned model's
    # ``params["layers"]`` is a tuple of per-layer dicts, its layer loop is
    # unrolled, and it runs unsharded (mesh=None)
    layer_pattern: str = ""
    norm_eps: float = 1e-6
    # the attention head size where heads x size is not d_model (0 =
    # d_model // n_heads): wqkv is (d_model, (n_heads + 2 kv_heads) x
    # size), wo (n_heads x size, d_model)
    attn_head_dim: int = 0
    # RMSNorm over each head's ``head_dim`` of q and of k, with a learned
    # scale (``q_norm`` / ``k_norm``), before the rotation
    qk_norm: bool = False
    # generation by diffusion over blocks (0 = one token a row a step):
    # position i sees j iff j // block_len <= i // block_len (the whole
    # blocks before it and ALL of its own), the logits at a position
    # predict that position, and a block starts as ``mask_id`` wherever no
    # token is given (decode.paged_block_step, serving._block_chunk). An
    # all-"R" pattern; ``block_len`` a power of two up to 128, so that it
    # divides the attention kernels' tiles
    block_len: int = 0
    mask_id: int = -1
    # an all-"H" model's scalar multipliers, under their published names,
    # applied at use on activations (1 = no operation is emitted): on the
    # embedding; on the attention's input, its keys, its output; on the
    # Mamba-2 mixer's input, on the five segments [z | x | B | C | dt] of
    # its in-projection, on its output; on the MLP's gate and its output;
    # on the logits
    embedding_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: tuple = (1.0, 1.0)
    lm_head_multiplier: float = 1.0
    # "M": heads x head_dim = d_inner; B and C are shared by the heads of
    # a group; the convolution's width; positions a chunk of the prefill's
    # chunked form (the recurrent state is held in float32)
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # "E": the router scores all ``moe_experts`` and picks ``moe_top_k``;
    # this chip holds experts [moe_held_start, moe_held_start + moe_held)
    # (0 = all) and computes their picks alone; the routed experts are
    # latent -> moe_d_ff -> latent, the shared one d_model ->
    # moe_shared_d_ff -> d_model; gates sum to ``moe_scale``
    moe_experts: int = 0
    moe_held: int = 0
    moe_held_start: int = 0
    moe_top_k: int = 1
    moe_latent: int = 0
    moe_d_ff: int = 0
    moe_shared_d_ff: int = 0
    moe_scale: float = 1.0
    # "R": the chosen gates sum to one (True) or stay the softmax's
    moe_renorm: bool = True
    # mesh axis names (data / sequence(context) / tensor / expert)
    axis_dp: str = "dp"
    axis_sp: str = "sp"
    axis_tp: str = "tp"
    axis_ep: str = "ep"

    @property
    def mesh_axes(self) -> frozenset:
        """Declared axis names — the set resolve_spec may prune."""
        return frozenset((self.axis_dp, self.axis_sp, self.axis_tp,
                          self.axis_ep, self.axis_fsdp))

    @property
    def batch_axes(self) -> tuple:
        """Mesh axes the batch dimension shards over: (dp, fsdp) under
        FSDP (the fsdp ranks are data ranks too), else (dp,). Always a
        tuple — PartitionSpec treats a singleton tuple as the axis."""
        if self.fsdp and self.axis_fsdp != self.axis_dp:
            return (self.axis_dp, self.axis_fsdp)
        return (self.axis_dp,)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def pattern(self) -> str:
        """One character a layer; "B" spells the default's block, which
        no explicit pattern names."""
        return self.layer_pattern or "B" * self.n_layers

    @property
    def n_attn_layers(self) -> int:
        """Layers that hold K/V: the cache has one pool for each."""
        return sum(LAYER_KINDS[c].kv for c in self.pattern)

    @property
    def n_state_layers(self) -> int:
        """Layers that hold a row of recurrent state a sequence."""
        return sum(LAYER_KINDS[c].state for c in self.pattern)

    @property
    def n_routed_layers(self) -> int:
        """Layers with a route over experts: the cache carries their sums."""
        return sum(c in "ER" for c in self.pattern)

    @property
    def experts_held(self) -> int:
        return self.moe_held or self.moe_experts

    @property
    def head_dim(self) -> int:
        if self.attn_head_dim:
            return self.attn_head_dim
        if self.d_model % self.n_heads:
            raise ValueError(f"d_model {self.d_model} % n_heads {self.n_heads} != 0")
        return self.d_model // self.n_heads

    @property
    def attn_width(self) -> int:
        """Width of the attention's output before ``wo``: d_model unless
        the head size is explicit."""
        return self.n_heads * self.head_dim

    @property
    def multipliers(self) -> tuple:
        """The fourteen multiplier values, flat."""
        return (self.embedding_multiplier, self.attention_in_multiplier,
                self.key_multiplier, self.attention_out_multiplier,
                self.ssm_in_multiplier, *self.ssm_multipliers,
                self.ssm_out_multiplier, *self.mlp_multipliers,
                self.lm_head_multiplier)

    def __post_init__(self):
        if self.pos_embed not in ("learned", "rope", "none"):
            raise ValueError(
                f"pos_embed {self.pos_embed!r} not in "
                "('learned', 'rope', 'none')"
            )
        pat = self.layer_pattern
        if pat:
            if len(pat) != self.n_layers or set(pat) - set("*MEHR"):
                raise ValueError(
                    f"layer_pattern {pat!r}: one of '*MEHR' for each of "
                    f"the {self.n_layers} layers")
            if self.n_state_layers and not (
                    self.ssm_heads > 0
                    and self.ssm_heads % self.ssm_groups == 0):
                raise ValueError(
                    "an 'M' or 'H' layer needs ssm_heads > 0, a multiple "
                    f"of ssm_groups (got {self.ssm_heads}, "
                    f"{self.ssm_groups})")
            if "E" in pat and not (
                    0 < self.moe_top_k <= self.moe_experts
                    and self.moe_held_start + self.experts_held
                    <= self.moe_experts
                    and min(self.moe_latent, self.moe_d_ff,
                            self.moe_shared_d_ff) > 0):
                raise ValueError(
                    "an 'E' layer needs moe_experts >= moe_top_k > 0, a "
                    "held range inside the experts, and moe_latent, "
                    "moe_d_ff, moe_shared_d_ff > 0")
            if "R" in pat and not (
                    0 < self.moe_top_k <= self.moe_experts
                    and self.moe_held_start + self.experts_held
                    <= self.moe_experts and self.moe_d_ff > 0):
                raise ValueError(
                    "an 'R' layer needs moe_experts >= moe_top_k > 0, a "
                    "held range inside the experts and moe_d_ff > 0")
        if self.block_len:
            B = self.block_len
            if B < 2 or B > 128 or B & (B - 1):
                raise ValueError(
                    f"block_len {B}: a power of two from 2 to 128 (it has "
                    "to divide the attention kernels' tiles, every rung "
                    "and the page size)")
            if set(pat) != {"R"}:
                raise ValueError(
                    "block_len > 0 (generation by diffusion over blocks) "
                    f"needs a layer_pattern of 'R' alone, not {pat!r}: "
                    "the other kinds have no multi-position step, and a "
                    "recurrence cannot see the rest of its own block")
            if not 0 <= self.mask_id < self.vocab:
                raise ValueError(
                    f"mask_id {self.mask_id} outside the vocabulary "
                    f"[0, {self.vocab})")
            if self.attention not in ("full", "flash"):
                raise ValueError(
                    "the block mask is written for attention 'full' and "
                    f"'flash', not {self.attention!r}")
        elif self.mask_id != -1:
            raise ValueError("mask_id is read with block_len > 0 only")
        if (len(self.ssm_multipliers), len(self.mlp_multipliers)) != (5, 2):
            raise ValueError(
                "ssm_multipliers has five values ([z | x | B | C | dt]) "
                "and mlp_multipliers two (gate, output)")
        if set(self.multipliers) != {1.0} and set(pat) != {"H"}:
            # the other kinds' and the default block's paths (the MLPs,
            # extend/tail prefill, the pipeline stages) apply none
            raise ValueError(
                "multipliers other than 1 are read by 'H' layers: they "
                f"need a layer_pattern of 'H' alone, not {pat!r}")
        if self.pos_embed == "rope" and self.head_dim % 2:
            raise ValueError("rope needs an even head_dim")
        if self.attention not in ATTENTION_IMPLS:
            raise ValueError(
                f"attention {self.attention!r} not in {ATTENTION_IMPLS}"
            )
        if self.loss_chunk < 0 or (self.loss_chunk and
                                   self.vocab % self.loss_chunk):
            raise ValueError(
                f"loss_chunk {self.loss_chunk} must be 0 or divide "
                f"vocab {self.vocab}"
            )
        if self.moe_dispatch not in ("auto", "einsum", "scatter"):
            raise ValueError(
                f"moe_dispatch {self.moe_dispatch!r} not in "
                "('auto', 'einsum', 'scatter')"
            )
        if self.n_experts and not (
            1 <= self.n_experts_top_k <= max(self.n_experts, 1)
        ):
            raise ValueError(
                f"n_experts_top_k {self.n_experts_top_k} outside "
                f"[1, n_experts={self.n_experts}]"
            )
        if self.kv_cache_dtype not in ("compute", "int8", "fp8"):
            raise ValueError(
                f"kv_cache_dtype {self.kv_cache_dtype!r} not in "
                "('compute', 'int8', 'fp8')"
            )
        if self.decode_attn not in ("flash", "gather", "paged_flash"):
            raise ValueError(
                f"decode_attn {self.decode_attn!r} not in "
                "('flash', 'gather', 'paged_flash')"
            )
        if self.mlp_impl not in ("dense", "fused"):
            raise ValueError(
                f"mlp_impl {self.mlp_impl!r} not in ('dense', 'fused')"
            )
        if self.remat_policy not in ("nothing", "attn", "dots", "dots_attn",
                                     "split"):
            raise ValueError(
                f"remat_policy {self.remat_policy!r} not in "
                "('nothing', 'attn', 'dots', 'dots_attn', 'split')"
            )
        if self.n_kv_heads < 0 or self.n_kv_heads > self.n_heads or (
            self.n_kv_heads and self.n_heads % self.n_kv_heads
        ):
            raise ValueError(
                f"n_kv_heads {self.n_kv_heads} must be in [1, n_heads] and "
                f"divide n_heads {self.n_heads} (0 = MHA)"
            )


def init_params(key, cfg: TransformerConfig):
    """f32 master params; layer weights stacked on a leading n_layers
    axis for ``lax.scan``."""
    D, F, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab
    if cfg.layer_pattern:
        return _init_patterned(key, cfg)
    k = iter(jax.random.split(key, 8))

    def initn(shape, scale):
        return jax.random.normal(next(k), shape, jnp.float32) * scale

    layers = {
        "ln1_scale": jnp.ones((L, D), jnp.float32),
        "ln2_scale": jnp.ones((L, D), jnp.float32),
        # fused q + k + v projection; with GQA the kv widths shrink to
        # kv_heads * head_dim
        "wqkv": initn((L, D, cfg.attn_width
                       + 2 * cfg.kv_heads * cfg.head_dim), D ** -0.5),
        "wo": initn((L, cfg.attn_width, D), (2 * D * L) ** -0.5),
    }
    pos = (
        {"pos_embed": initn((cfg.max_seq, D), 0.02)}
        if cfg.pos_embed == "learned" else {}
    )
    if cfg.n_experts:
        E = cfg.n_experts
        layers["router"] = initn((L, D, E), D ** -0.5)
        layers["w1"] = initn((L, E, D, F), D ** -0.5)
        layers["w2"] = initn((L, E, F, D), (2 * F * L) ** -0.5)
    else:
        layers["w1"] = initn((L, D, F), D ** -0.5)
        layers["w2"] = initn((L, F, D), (2 * F * L) ** -0.5)
    return {
        "embed": initn((V, D), 0.02),
        **pos,
        "layers": layers,
        "ln_f_scale": jnp.ones((D,), jnp.float32),
        "lm_head": initn((D, V), D ** -0.5),
    }


def _init_patterned(key, cfg: TransformerConfig):
    """f32 master params of a patterned model: ``layers`` is a tuple of
    per-layer dicts, each with the leaves of its own mixer."""
    from hpc_patterns_tpu.models.ssm import ssm_dims

    D, L, V = cfg.d_model, cfg.n_layers, cfg.vocab
    top_key, *layer_keys = jax.random.split(key, L + 1)

    def one(kind, lkey):
        k = iter(jax.random.split(lkey, 12))
        n = lambda shape, scale: jax.random.normal(
            next(k), shape, jnp.float32) * scale
        lp = {"ln1_scale": jnp.ones((D,), jnp.float32)}
        holds = LAYER_KINDS[kind]
        if holds.kv:
            lp["wqkv"] = n((D, cfg.attn_width
                            + 2 * cfg.kv_heads * cfg.head_dim), D ** -0.5)
            lp["wo"] = n((cfg.attn_width, D), (2 * D * L) ** -0.5)
            if cfg.qk_norm:
                lp["q_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
                lp["k_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
        if holds.state:
            d, H = ssm_dims(cfg), cfg.ssm_heads
            lp["in_proj"] = n((D, d["proj"]), D ** -0.5)
            lp["conv_w"] = n((cfg.ssm_conv, d["conv_dim"]),
                             cfg.ssm_conv ** -0.5)
            lp["conv_b"] = jnp.zeros((d["conv_dim"],), jnp.float32)
            # steps log-uniform in [1e-3, 1e-1] through the softplus
            step = jnp.exp(jax.random.uniform(
                next(k), (H,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            lp["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
            lp["A_log"] = jnp.log(jax.random.uniform(
                next(k), (H,), jnp.float32, 1.0, 16.0))
            lp["D"] = jnp.ones((H,), jnp.float32)
            lp["norm_scale"] = jnp.ones((d["d_inner"],), jnp.float32)
            lp["out_proj"] = n((d["d_inner"], D),
                               (2 * d["d_inner"] * L) ** -0.5)
        if holds.mlp == "gated":
            F = cfg.d_ff
            lp["ln2_scale"] = jnp.ones((D,), jnp.float32)
            lp["w_gate"] = n((D, F), D ** -0.5)
            lp["w_up"] = n((D, F), D ** -0.5)
            lp["w_down"] = n((F, D), (2 * F * L) ** -0.5)
        if holds.mlp == "routed":
            E, held, F = cfg.moe_experts, cfg.experts_held, cfg.moe_d_ff
            lp["ln2_scale"] = jnp.ones((D,), jnp.float32)
            lp["router"] = n((D, E), D ** -0.5)
            lp["w_gate"] = n((held, D, F), D ** -0.5)
            lp["w_up"] = n((held, D, F), D ** -0.5)
            lp["w_down"] = n((held, F, D), (2 * F * L) ** -0.5)
        if kind == "E":
            E, held = cfg.moe_experts, cfg.experts_held
            R, F, Fs = cfg.moe_latent, cfg.moe_d_ff, cfg.moe_shared_d_ff
            lp["router"] = n((D, E), D ** -0.5)
            lp["router_bias"] = jnp.zeros((E,), jnp.float32)
            lp["w_down"] = n((D, R), D ** -0.5)
            lp["w_up"] = n((R, D), (2 * R * L) ** -0.5)
            lp["w1"] = n((held, R, F), R ** -0.5)
            lp["w2"] = n((held, F, R), (2 * F) ** -0.5)
            lp["ws1"] = n((D, Fs), D ** -0.5)
            lp["ws2"] = n((Fs, D), (2 * Fs * L) ** -0.5)
        return lp

    kt = iter(jax.random.split(top_key, 3))
    top = {"embed": jax.random.normal(next(kt), (V, D), jnp.float32) * 0.02,
           "ln_f_scale": jnp.ones((D,), jnp.float32),
           "lm_head": jax.random.normal(next(kt), (D, V), jnp.float32)
           * D ** -0.5}
    if cfg.pos_embed == "learned":
        top["pos_embed"] = jax.random.normal(
            next(kt), (cfg.max_seq, D), jnp.float32) * 0.02
    return {**top, "layers": tuple(one(c, lk) for c, lk
                                   in zip(cfg.layer_pattern, layer_keys))}


def layer_params(params, l: int):
    """Layer ``l``'s leaves: a static slice of the stacked tree, or the
    patterned tree's own dict."""
    layers = params["layers"]
    if isinstance(layers, (tuple, list)):
        return layers[l]
    return jax.tree.map(lambda a: a[l], layers)


#: sibling-key suffix carrying a quantized weight's per-output-channel
#: dequant scales (see :func:`quantize_weights_int8`). Riding INSIDE
#: the params tree (not a parallel tree) keeps every existing
#: per-layer slice (``jax.tree.map(lambda a: a[l], ...)``, the prefill
#: ``lax.scan``) working unchanged — the scales slice with their
#: weights.
QUANT_SCALE_SUFFIX = "_qscale"


def _quantize_channels(w):
    """Per-output-channel symmetric int8 quantization of a matmul
    weight ``(..., d_in, d_out)``: returns (int8 values, f32 scales
    shaped ``(..., d_out)``) with ``w ~= q * scale``. Output-channel
    granularity because the matmul contracts over ``d_in``: every
    element of an output column shares one scale, so dequant folds
    into the column (lane) axis of the product stream."""
    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale[..., None, :]),
                 -127, 127).astype(jnp.int8)
    return q, scale


#: the decode-matmul weights :func:`quantize_weights_int8` covers —
#: every per-layer GEMM of the decode step (qkv projection, attention
#: output, MLP up/down) plus the lm_head below
QUANTIZED_LAYER_WEIGHTS = ("wqkv", "wo", "w1", "w2")


def quantize_weights_int8(params):
    """Opt-in int8 weight quantization for the DECODE matmuls: every
    2-D GEMM weight of the step (``wqkv``/``wo``/``w1``/``w2`` per
    layer, plus ``lm_head``) is replaced by int8 values with
    per-output-channel f32 scales under ``<name>_qscale`` sibling keys
    — 4x (vs f32 masters) fewer weight bytes per decode step, the
    second lever next to the quantized KV pools on the
    data-movement-bound decode path. Norm scales and the embedding
    table stay full precision (they are gathers/elementwise, not
    GEMMs). Dequant happens AT USE (:func:`matmul_weight`): the HBM
    read is int8, the f32 product of the dequant fuses into the matmul
    stream.

    Token identity CANNOT hold across precision — the law is pinned
    TV-distance-style by the sampling oracles instead (greedy top-1
    agreement rate + total-variation bounds, tests/test_quantization.py;
    docs/quantization.md)."""
    if "router" in params["layers"]:
        raise ValueError(
            "quantize_weights_int8 covers dense decode layers "
            f"({QUANTIZED_LAYER_WEIGHTS}); MoE expert weights would "
            "need per-expert channel scales (and paged serving is "
            "dense-only anyway)")
    layers = dict(params["layers"])
    for name in QUANTIZED_LAYER_WEIGHTS:
        q, s = _quantize_channels(layers[name])
        layers[name] = q
        layers[name + QUANT_SCALE_SUFFIX] = s
    out = dict(params)
    out["layers"] = layers
    q, s = _quantize_channels(params["lm_head"])
    out["lm_head"] = q
    out["lm_head" + QUANT_SCALE_SUFFIX] = s
    return out


def matmul_weight(tree, name, dt):
    """THE dequant-at-use accessor for a (possibly int8-quantized)
    matmul weight: plain weights cast to the compute dtype exactly as
    before; quantized weights (a ``<name>_qscale`` sibling present)
    dequantize per output channel in the einsum stream — the HBM
    traffic stays int8, the f32 multiply fuses. Shared by the training
    layer (qkv/wo/mlp/lm_head/loss-head sites) and every decode path so
    a quantized params tree serves through all of them or none; the
    pipeline-parallel stage math spells its own matmuls and REFUSES
    quantized trees instead (pp_loss_and_grads)."""
    w = tree[name]
    qs = tree.get(name + QUANT_SCALE_SUFFIX)
    if qs is None:
        return w.astype(dt)
    # scales are per OUTPUT channel (the last weight axis); the
    # explicit lane broadcast also covers a still-stacked (L, ...) tree
    return (w.astype(jnp.float32)
            * qs.astype(jnp.float32)[..., None, :]).astype(dt)


#: leaves whose use sites compute in float32 whatever ``cfg.dtype`` is:
#: the MoE router (parallel/moe._route, sigmoid_route, softmax_route) and
#: its selection bias, the Mamba-2 mixer's decay, skip and step bias (models/ssm.py);
#: the ``*_qscale`` siblings (:func:`matmul_weight`) are matched by suffix
_FLOAT32_AT_USE = ("router", "router_bias", "A_log", "D", "dt_bias")


def serving_cast_leaves(params, cfg: TransformerConfig) -> dict:
    """Which leaves :func:`serving_weights` casts: ``{index into
    jax.tree.leaves(params): leaf}`` for every floating leaf wider than
    ``cfg.dtype`` whose use sites cast it to ``cfg.dtype`` anyway.
    Integer leaves (int8 weights), their ``*_qscale`` scales and the
    MoE router stay as they are: their math is float32 at use."""
    dt = jnp.dtype(cfg.dtype)
    wide = {}
    for i, (path, leaf) in enumerate(
            jax.tree_util.tree_flatten_with_path(params)[0]):
        name = str(getattr(path[-1], "key", ""))
        if (jnp.issubdtype(leaf.dtype, jnp.floating)
                and leaf.dtype.itemsize > dt.itemsize
                and not name.endswith(QUANT_SCALE_SUFFIX)
                and name not in _FLOAT32_AT_USE):
            wide[i] = leaf
    return wide


@partial(jax.jit, static_argnames=("dt",))
def _cast_leaves(leaves, dt):
    return [a.astype(dt) for a in leaves]


def serving_weights(params, cfg: TransformerConfig):
    """The tree a server holds: ``params`` with every leaf of
    :func:`serving_cast_leaves` cast to ``cfg.dtype``, once, in one
    program. The use sites (:func:`matmul_weight`, the embedding
    gather, ``_rmsnorm``) round the same float32 values to the same
    ``cfg.dtype`` on every call; ``astype`` to the dtype a leaf already
    has emits nothing, so a program over this tree computes the same
    bits without the per-call copy of every weight. Training keeps the
    float32 masters and casts at use.

    Nothing to cast (a float32 config, a tree already cast) returns
    ``params`` itself: no copy, no dispatch, so the call is idempotent
    and several engines over one tree can share one result. Untouched
    leaves are the caller's own arrays; a cast leaf keeps its input's
    sharding (an elementwise program)."""
    wide = serving_cast_leaves(params, cfg)
    if not wide:
        return params
    leaves, treedef = jax.tree.flatten(params)
    for i, a in zip(wide, _cast_leaves(list(wide.values()),
                                       jnp.dtype(cfg.dtype))):
        leaves[i] = a
    return jax.tree.unflatten(treedef, leaves)


def scoped(name: str):
    """``jax.named_scope(name)`` around every call of the decorated
    function. The names are the phases a device trace is read by
    (docs/observability.md): metadata of the operations, never part of
    the program. A scope is made anew for each call: one
    ``jax.named_scope`` object used as a decorator keeps the enclosing
    name stack on itself, which two threads tracing at once would
    share."""
    def decorate(fn):
        @wraps(fn)
        def inside(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inside
    return decorate


def _rmsnorm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps).astype(x.dtype)) * scale.astype(x.dtype)


def apply_rope(x, positions, cfg: TransformerConfig):
    """Rotary position embedding: rotate each (even, odd-half) feature
    pair of ``x`` (..., T, heads, head_dim) by angle pos·theta^(-2i/d).
    ``positions``: (..., T) int32 GLOBAL positions — scores then depend
    only on relative distance, which is what lets the same weights serve
    any context layout (ring/ulysses shards, KV-cache decode steps).
    Rotation is computed in f32 and cast back (bf16 angle resolution is
    not enough at long range)."""
    Dh = x.shape[-1]
    half = Dh // 2
    inv_freq = cfg.rope_theta ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., T, half)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def scaled(x, multiplier: float):
    """``x`` times one of the config's multipliers, in ``x``'s dtype; a
    multiplier of 1 emits nothing, so a model without them lowers to the
    program it lowered to before they existed."""
    return x if multiplier == 1.0 else x * jnp.asarray(multiplier, x.dtype)


def project_qkv(h, lp, cfg: TransformerConfig):
    """Fused qkv projection + head split, GQA-narrow K/V (kv_heads, not
    yet expanded). THE qkv layout definition — shared by the training
    layer (_layer) and the decode path (models/decode.py) so the two can
    never disagree on the split or head order. ``h``: (..., d_model);
    returns q (..., n_heads, Dh), k/v (..., kv_heads, Dh)."""
    lead = h.shape[:-1]
    dt = h.dtype
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    h = scaled(h, cfg.attention_in_multiplier)
    qkv = jnp.dot(h, matmul_weight(lp, "wqkv", dt))  # column-parallel
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    q = q.reshape(*lead, H, Dh)
    k = scaled(k, cfg.key_multiplier).reshape(*lead, Hkv, Dh)
    if cfg.qk_norm:   # over each head's Dh, before the rotation
        with jax.named_scope("qk_norm"):
            q = _rmsnorm(q, lp["q_norm"], cfg.norm_eps)
            k = _rmsnorm(k, lp["k_norm"], cfg.norm_eps)
    return q, k, v.reshape(*lead, Hkv, Dh)


def _attention(q, k, v, cfg: TransformerConfig, mesh):
    """Dispatch to the configured attention impl. ring/ulysses wrap the
    rank-local kernels in ``shard_map`` over (dp, sp, tp) — sequence
    travels the ``sp`` ring while heads stay tensor-sharded. A model with
    ``cfg.block_len`` runs unsharded under the block mask."""
    if cfg.block_len:
        if cfg.attention == "flash":
            from hpc_patterns_tpu.ops import flash_attention

            return flash_attention(q, k, v, mask_block=cfg.block_len)
        return full_attention(q, k, v, causal=True,
                              mask_block=cfg.block_len)
    if cfg.attention == "flash":
        from hpc_patterns_tpu.ops import flash_attention

        if mesh is None:
            return flash_attention(q, k, v, causal=True)
        if mesh_axis_size(mesh, cfg.axis_sp) > 1:
            raise ValueError(
                "attention='flash' needs the sequence unsharded (sp=1); "
                "use 'ring_flash' to run the Pallas kernel per ring step "
                "over a sharded sequence"
            )
        # sequence unsharded: the kernel runs per-(dp, tp) shard on the
        # full local sequence
        spec = resolve_spec(P(cfg.batch_axes, None, cfg.axis_tp, None), mesh,
                            cfg.mesh_axes)
        return shard_map(
            partial(flash_attention, causal=True), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
        )(q, k, v)
    if cfg.attention == "full" or mesh is None:
        return full_attention(q, k, v, causal=True)
    spec = resolve_spec(P(cfg.batch_axes, cfg.axis_sp, cfg.axis_tp, None), mesh,
                        cfg.mesh_axes)
    base, _, variant = cfg.attention.partition("_")
    local_impl = variant or "dense"
    impl_fn = ulysses_attention if base == "ulysses" else ring_attention
    fn = partial(impl_fn, axis=cfg.axis_sp, causal=True, impl=local_impl)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def _moe_block(h, lp, cfg: TransformerConfig, mesh, with_stats=False):
    """Top-k routed experts over the ep axis (parallel/moe.py; k =
    cfg.n_experts_top_k, 1 = Switch). Returns (out, aux_loss), plus the
    kept fraction when ``with_stats`` (the telemetry moe_drop_rates
    surfaces)."""
    from hpc_patterns_tpu.parallel import moe

    B, T, D = h.shape
    k = cfg.n_experts_top_k

    def resolve_dispatch(n_local, cap):
        if cfg.moe_dispatch != "auto":
            return cfg.moe_dispatch
        # scatter once the one-hot (N, E, C) tensors stop being small:
        # measured equal-or-faster on chip at small shapes (182.6-196.3
        # vs 199.4 ms/step at 4k tokens, adjacent runs) and strictly
        # enabling at scale (the 16k-token config OOMs under einsum,
        # trains at 436.8 ms/step under scatter) — einsum remains the
        # oracle form and the tiny-shape default. The footprint counts
        # BOTH live one-hots (dispatch and combine) at their choice-major
        # (k*N, E, C) f32 shape — not one (N, E, C) tensor, which
        # undercounted by 2k and flipped to scatter late
        return ("scatter"
                if 2 * k * n_local * cfg.n_experts * cap * 4 > 16 << 20
                else "einsum")

    if mesh is None:
        # capacity scales with k: top-k routes k·N assignments, so the
        # slot budget is k·N·cf/E (GShard's sizing; k=1 is unchanged)
        cap = moe.default_capacity(B * T * k, cfg.n_experts,
                                   cfg.capacity_factor)
        out = moe.moe_dense(
            h.reshape(B * T, D), lp["router"], lp["w1"], lp["w2"],
            capacity=cap, top_k=k, with_stats=with_stats,
            dispatch=resolve_dispatch(B * T, cap),
        )
        return (out[0].reshape(B, T, D), *out[1:])

    sp, ep = cfg.axis_sp, cfg.axis_ep
    bx = cfg.batch_axes
    b_size = math.prod(mesh_axis_size(mesh, ax) for ax in bx)
    # tokens shard over the batch axes AND ep for the MoE block: ep must
    # partition the routing/FFN work, not replicate it (the reshard in
    # and out is XLA's, riding ICI). When the batch doesn't divide
    # batch*ep, fall back to batch-only token sharding (ep still
    # partitions the experts; routing work is then replicated across ep).
    batch_over_ep = B % (b_size * mesh_axis_size(mesh, ep)) == 0
    if not batch_over_ep and mesh_axis_size(mesh, ep) > 1:
        import warnings

        warnings.warn(
            f"moe: batch {B} does not divide batch_shards*ep "
            f"({b_size}*{mesh_axis_size(mesh, ep)}); routing runs "
            "replicated across ep (experts still partitioned) — pad the "
            "batch to recover partitioned routing",
            stacklevel=2,
        )
    b_shards = b_size * (mesh_axis_size(mesh, ep) if batch_over_ep else 1)
    n_local = (B // b_shards) * (T // mesh_axis_size(mesh, sp))
    cap = moe.default_capacity(n_local * k, cfg.n_experts,
                               cfg.capacity_factor)

    has = lambda ax: ax in mesh.axis_names

    disp = resolve_dispatch(n_local, cap)

    def local(hl, router, w1l, w2l):
        b, t, d = hl.shape
        if has(ep):
            y, aux, *st = moe.moe_ep(
                hl.reshape(b * t, d), router, w1l, w2l,
                axis=ep, capacity=cap, top_k=k, with_stats=with_stats,
                dispatch=disp,
            )
        else:  # no expert axis in this mesh: all experts local
            y, aux, *st = moe.moe_dense(
                hl.reshape(b * t, d), router, w1l, w2l, capacity=cap,
                top_k=k, with_stats=with_stats, dispatch=disp,
            )
        # moe_ep means aux over ep (as a comm axis); with tokens also
        # sharded on ep, fold every data axis for the global scalars
        scalars = [aux, *st]
        for ax in (*bx, sp):
            if has(ax):
                scalars = [lax.pmean(v, ax) for v in scalars]
        return (y.reshape(b, t, d), *scalars)

    tok_spec = (
        resolve_spec(P((*bx, ep), sp, None), mesh, cfg.mesh_axes)
        if has(ep) and batch_over_ep
        else resolve_spec(P(cfg.batch_axes, sp, None), mesh, cfg.mesh_axes)
    )
    out = shard_map(
        local,
        mesh=mesh,
        in_specs=(tok_spec, P(None, None),
                  resolve_spec(P(ep, None, None), mesh, cfg.mesh_axes),
                  resolve_spec(P(ep, None, None), mesh, cfg.mesh_axes)),
        out_specs=(tok_spec, P()) + ((P(),) if with_stats else ()),
        check_vma=False,  # all_to_all + pmean replication not VMA-provable
    )(h, lp["router"], lp["w1"], lp["w2"])
    return out


def _qkv_block(x, lp, cfg: TransformerConfig, mesh):
    """Pre-attention: norm + :func:`_qkv_heads`. Split out so
    remat_policy="split" can checkpoint it independently of the
    attention kernel."""
    return _qkv_heads(attn_norm(x, lp, cfg), lp, cfg, mesh)


@scoped("attn")
def _qkv_heads(h, lp, cfg: TransformerConfig, mesh):
    """The normed input's fused qkv projection + rope + the GQA
    narrow-vs-expand decision."""
    B, T, D = h.shape
    H = cfg.n_heads
    q, k, v = project_qkv(h, lp, cfg)
    if cfg.pos_embed == "rope":
        # global positions: the layer always sees the full sequence (the
        # sp shard_map lives inside _attention), so iota(T) is correct
        # under every sharding
        pos = lax.broadcasted_iota(jnp.int32, (T,), 0)
        q = apply_rope(q, pos, cfg)
        k = apply_rope(k, pos, cfg)
    if cfg.kv_heads != H:
        # GQA: every attention impl consumes the NARROW K/V (no expanded
        # copy in HBM — the group-factor memory/bandwidth saving; the
        # ring additionally circulates group-factor less K/V per step).
        # The only layout constraint here: with heads tensor-sharded, tp
        # must divide kv_heads so shards keep whole kv heads — else fall
        # back to jnp.repeat expansion. (ulysses has its own internal
        # per-rank fallback when its axis can't scatter the kv heads;
        # decode does its own grouped-cache attention, models/decode.py.)
        tp = max(mesh_axis_size(mesh, cfg.axis_tp), 1) if mesh is not None else 1
        narrow = cfg.kv_heads % tp == 0
        if not narrow:
            k = jnp.repeat(k, H // cfg.kv_heads, axis=2)
            v = jnp.repeat(v, H // cfg.kv_heads, axis=2)
    return q, k, v


def _post_attn(x, o, lp, cfg: TransformerConfig, mesh, act_spec):
    """Output projection + residual + pre-MLP norm: the first half of
    :func:`_post_block`, split out so split-remat can checkpoint it
    while the fused MLP kernel stays OUTSIDE the remat region (same
    reasoning as the attention kernel — a custom_vjp's residuals can't
    be saved by any policy from outside the call)."""
    B, T, D = x.shape
    dt = x.dtype
    with jax.named_scope("attn"):
        o = jnp.dot(o.reshape(B, T, cfg.attn_width),
                    matmul_weight(lp, "wo", dt))  # row-parallel
        x = x + o
        if mesh is not None:
            x = lax.with_sharding_constraint(x, act_spec)
    with jax.named_scope("mlp"):
        return x, _rmsnorm(x, lp["ln2_scale"], cfg.norm_eps)


@scoped("mlp")
def _mlp_fused(h, lp, cfg: TransformerConfig, mesh):
    """The Pallas fused MLP on ``h`` (post-norm activations). Single
    device runs the kernel directly; under a mesh it runs shard_mapped
    (a pallas_call does not GSPMD-partition): tokens stay
    (batch, sp)-sharded, w1/w2 enter column/row-sharded over tp, and
    the row-parallel psum closes the block — the manual spelling of
    exactly the collective XLA inserts for the einsum path."""
    from hpc_patterns_tpu.ops.fused_mlp import fused_mlp

    dt = h.dtype
    # dequant-at-entry for a quantized tree: the kernel wants dense
    # compute-dtype operands, so the int8-HBM-read win doesn't apply
    # here — correctness does
    w1 = matmul_weight(lp, "w1", dt)
    w2 = matmul_weight(lp, "w2", dt)
    if mesh is None:
        return fused_mlp(h, w1, w2)
    tp = cfg.axis_tp
    has_tp = mesh_axis_size(mesh, tp) > 1
    x_spec = resolve_spec(P(cfg.batch_axes, cfg.axis_sp, None), mesh,
                          cfg.mesh_axes)
    w1_spec = resolve_spec(P(None, tp), mesh, cfg.mesh_axes)
    w2_spec = resolve_spec(P(tp, None), mesh, cfg.mesh_axes)

    def local(h, w1, w2):
        y = fused_mlp(h, w1, w2)
        return lax.psum(y, tp) if has_tp else y

    return shard_map(
        local, mesh=mesh, in_specs=(x_spec, w1_spec, w2_spec),
        out_specs=x_spec,
        check_vma=False,  # pallas_call can't declare vma
    )(h, w1, w2)


def _post_block(x, o, lp, cfg: TransformerConfig, mesh, act_spec,
                with_stats=False):
    """Post-attention: output projection, residual, norm, mlp/moe.
    Returns (x, moe_aux) — with ``with_stats`` also the MoE kept
    fraction (1.0 for dense layers)."""
    dt = x.dtype

    def c(y, spec):
        return lax.with_sharding_constraint(y, spec) if mesh is not None else y

    x, h = _post_attn(x, o, lp, cfg, mesh, act_spec)
    with jax.named_scope("mlp"):
        if cfg.n_experts:
            h, aux, *st = _moe_block(h, lp, cfg, mesh,
                                     with_stats=with_stats)
            h = h.astype(dt)
        elif cfg.mlp_impl == "fused":
            h = _mlp_fused(h, lp, cfg, mesh).astype(dt)
            aux = jnp.zeros((), jnp.float32)
            st = [jnp.ones((), jnp.float32)] if with_stats else []
        else:
            h = jax.nn.gelu(jnp.dot(h, matmul_weight(lp, "w1", dt)))  # column-parallel
            h = jnp.dot(h, matmul_weight(lp, "w2", dt))  # row-parallel (psum by XLA)
            aux = jnp.zeros((), jnp.float32)
            st = [jnp.ones((), jnp.float32)] if with_stats else []
        return (c(x + h, act_spec), aux, *st)


@scoped("attn")
def attn_proj(o, lp, cfg: TransformerConfig, dt):
    """The attention's output projection: o (..., H, Dh) -> (..., D)."""
    o = jnp.dot(o.reshape(*o.shape[:-2], cfg.attn_width).astype(dt),
                matmul_weight(lp, "wo", dt))
    return scaled(o, cfg.attention_out_multiplier)


@scoped("mlp")
def gated_mlp(x, lp, cfg: TransformerConfig):
    """An "H" layer's close, x (..., D): the SiLU-gated MLP under its
    own pre-norm residual,
    ``x + (silu(h W_gate) * (h W_up)) W_down``."""
    dt = x.dtype
    m_gate, m_out = cfg.mlp_multipliers
    h = _rmsnorm(x, lp["ln2_scale"], cfg.norm_eps)
    with jax.named_scope("gate_up"):
        gate = scaled(jnp.dot(h, matmul_weight(lp, "w_gate", dt)), m_gate)
        h = jax.nn.silu(gate) * jnp.dot(h, matmul_weight(lp, "w_up", dt))
    with jax.named_scope("down"):
        return x + scaled(jnp.dot(h, matmul_weight(lp, "w_down", dt)), m_out)


@scoped("ssm")
def ssm_mixer(x, lp, cfg: TransformerConfig, last_pos=None):
    """An "M" layer over a whole sequence x (B, T, D): the Mamba-2 mixer
    in its chunked form under the pre-norm residual. Returns (x, (conv
    tail, S)): the state at ``last_pos`` (B,), default the last
    position."""
    from hpc_patterns_tpu.models import ssm

    h = _rmsnorm(x, lp["ln1_scale"], cfg.norm_eps)
    out, state = ssm.mamba_prefill(h, lp, cfg, last_pos)
    return x + out, state


@scoped("ssm")
def ssm_mixer_step(x, lp, cfg: TransformerConfig, state, active=None):
    """An "M" layer on one token a row, x (B, D), against the carried
    ``state``; rows where ``active`` is false keep theirs."""
    from hpc_patterns_tpu.models import ssm

    h = _rmsnorm(x, lp["ln1_scale"], cfg.norm_eps)
    out, state = ssm.mamba_step(h, lp, cfg, state, active)
    return x + out, state


@scoped("attn")
def attn_norm(x, lp, cfg: TransformerConfig):
    """The pre-norm of a layer that has attention; an "H" layer's Mamba
    half reads the same normed input."""
    return _rmsnorm(x, lp["ln1_scale"], cfg.norm_eps)


@scoped("ssm")
def ssm_branch(h, lp, cfg: TransformerConfig, last_pos=None):
    """An "H" layer's Mamba-2 half over a whole sequence, from the
    layer's normed input h (B, T, D): (what it adds to the residual,
    (conv tail, S) at ``last_pos``)."""
    from hpc_patterns_tpu.models import ssm

    return ssm.mamba_prefill(h, lp, cfg, last_pos)


@scoped("ssm")
def ssm_branch_step(h, lp, cfg: TransformerConfig, state, active=None):
    """An "H" layer's Mamba-2 half on one token a row, from the layer's
    normed input h (B, D), against the carried ``state``."""
    from hpc_patterns_tpu.models import ssm

    return ssm.mamba_step(h, lp, cfg, state, active)


@scoped("moe")
def moe_mixer(x, lp, cfg: TransformerConfig, valid=None):
    """An "E" layer, x (..., D): the LatentMoE layer on this chip's share
    of the experts under the pre-norm residual. ``valid`` (...,) bool:
    tokens that count (the others pick no expert). Returns (x, the
    route's stats, parallel/moe.ROUTE_STATS)."""
    from hpc_patterns_tpu.parallel import moe

    dt = x.dtype
    D = x.shape[-1]
    h = _rmsnorm(x, lp["ln1_scale"], cfg.norm_eps).reshape(-1, D)
    w = lambda name: matmul_weight(lp, name, dt)
    out, stats = moe.latent_moe(
        h, lp["router"], lp["router_bias"], w("w_down"), w("w_up"),
        w("w1"), w("w2"), w("ws1"), w("ws2"),
        held_start=cfg.moe_held_start, top_k=cfg.moe_top_k,
        scale=cfg.moe_scale,
        valid=None if valid is None else valid.reshape(-1))
    return x + out.reshape(x.shape).astype(dt), stats


@scoped("moe")
def routed_mlp(x, lp, cfg: TransformerConfig, valid=None):
    """An "R" layer's close, x (..., D): the routed SiLU-gated experts held
    here under their own pre-norm residual. ``valid`` (...,) bool: tokens
    that count (the others pick no expert). Returns (x, the route's stats,
    parallel/moe.ROUTE_STATS)."""
    from hpc_patterns_tpu.parallel import moe

    dt = x.dtype
    D = x.shape[-1]
    h = _rmsnorm(x, lp["ln2_scale"], cfg.norm_eps).reshape(-1, D)
    w = lambda name: matmul_weight(lp, name, dt)
    out, stats = moe.gated_moe(
        h, lp["router"], w("w_gate"), w("w_up"), w("w_down"),
        held_start=cfg.moe_held_start, top_k=cfg.moe_top_k,
        renorm=cfg.moe_renorm,
        valid=None if valid is None else valid.reshape(-1))
    return x + out.reshape(x.shape).astype(dt), stats


def _layer(x, lp, cfg: TransformerConfig, mesh, act_spec,
           split_remat: bool = False):
    """One pre-norm block: attn + mlp/moe, Megatron-sharded (wqkv/w1
    column, wo/w2 row — models/sharding.py), activations re-constrained
    after each collective-inducing matmul. Returns (x, moe_aux).

    ``split_remat``: checkpoint the qkv and post blocks separately,
    attention OUTSIDE any remat region — the flash kernel's custom_vjp
    residuals (out, lse) then persist to the backward and its forward
    runs exactly once (no policy can achieve this from outside the
    kernel call; see TransformerConfig.remat_policy)."""
    pre = partial(_qkv_block, cfg=cfg, mesh=mesh)
    post = partial(_post_block, cfg=cfg, mesh=mesh, act_spec=act_spec)
    fused_split = (split_remat and cfg.mlp_impl == "fused"
                   and not cfg.n_experts)
    if split_remat:
        # dots policy inside each block: elementwise interiors (rope,
        # norms, gelu) recompute, matmul outputs don't — recomputing
        # the qkv/mlp matmuls costs more than the HBM they free
        dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        pre = jax.checkpoint(pre, policy=dots)
        post = jax.checkpoint(post, policy=dots)
    q, k, v = pre(x, lp)
    with jax.named_scope("attn"):
        o = _attention(q, k, v, cfg, mesh)
    # named so remat_policy="attn" can pin it under whole-layer remat
    o = checkpoint_name(o, "attn_out")
    if fused_split:
        # like attention, the fused MLP kernel must live OUTSIDE the
        # remat region or its one-pass backward replays the forward:
        # checkpoint only the o-proj/residual/norm half, then run the
        # kernel on the saved norm output
        pa = jax.checkpoint(
            partial(_post_attn, cfg=cfg, mesh=mesh, act_spec=act_spec),
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
        x1, hn = pa(x, o, lp)
        with jax.named_scope("mlp"):
            h = _mlp_fused(hn, lp, cfg, mesh).astype(x.dtype)
            out = x1 + h
            if mesh is not None:
                out = lax.with_sharding_constraint(out, act_spec)
        return out, jnp.zeros((), jnp.float32)
    return post(x, o, lp)


def forward(params, tokens, cfg: TransformerConfig, mesh=None, *,
            return_aux: bool = False):
    """Logits for next-token prediction. ``tokens``: (batch, seq) int32.
    ``mesh``: the device mesh for sharding constraints + ring/ulysses
    attention; None = single-device (tests/oracle). With
    ``return_aux=True`` also returns the summed MoE load-balance loss
    (zeros for dense models)."""
    x, aux = forward_hidden(params, tokens, cfg, mesh)
    with jax.named_scope("head"):
        logits = head_logits(x, params, cfg)
    if return_aux:
        return logits, aux
    return logits


def head_logits(x, params, cfg: TransformerConfig):
    """float32 logits of final-norm hidden states x (..., D)."""
    logits = jnp.dot(x, matmul_weight(params, "lm_head", x.dtype))
    return scaled(logits.astype(jnp.float32), cfg.lm_head_multiplier)


@scoped("embed")
def _embed_tokens(params, tokens, cfg: TransformerConfig, mesh, dt):
    """Token + learned-position embedding lookup. Under fsdp the bf16
    working copies of the feature-sharded tables are constrained
    replicated BEFORE the gather — the explicit form of ZeRO-3's
    all-gather-weights-just-before-use. Without it the partitioner must
    inverse-reshard the batch-sharded activation cotangent into the
    feature-sharded table layout in the backward, which it can only do
    by "involuntary full rematerialization" (observed as
    spmd_partitioner warnings on the fsdp dryrun leg); the explicit
    replication compiles to a plain feature all-gather forward and a
    reduce-scatter backward instead."""
    T = tokens.shape[1]
    replicate = mesh is not None and cfg.fsdp
    emb = params["embed"].astype(dt)
    if replicate:
        emb = lax.with_sharding_constraint(
            emb, jax.sharding.NamedSharding(mesh, P())
        )
    x = scaled(emb[tokens], cfg.embedding_multiplier)
    if cfg.pos_embed == "learned":
        pos = params["pos_embed"].astype(dt)
        if replicate:
            pos = lax.with_sharding_constraint(
                pos, jax.sharding.NamedSharding(mesh, P())
            )
        x = x + pos[:T]
    return x


def forward_hidden(params, tokens, cfg: TransformerConfig, mesh=None):
    """The trunk of :func:`forward` WITHOUT the LM head: final-norm
    hidden states (B, T, d_model) in compute dtype, plus the summed MoE
    aux. The chunked loss consumes this so the (B, T, vocab) logits are
    never materialized."""
    dt = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    if mesh is not None:
        act_spec = jax.sharding.NamedSharding(
            mesh, resolve_spec(P(cfg.batch_axes, cfg.axis_sp, None), mesh,
                               cfg.mesh_axes)
        )
    else:
        act_spec = None
    x = _embed_tokens(params, tokens, cfg, mesh, dt)
    if mesh is not None:
        x = lax.with_sharding_constraint(x, act_spec)

    layer = partial(_layer, cfg=cfg, mesh=mesh, act_spec=act_spec)
    if cfg.remat:
        if cfg.remat_policy == "split":
            layer = partial(layer, split_remat=True)
        else:
            cp = jax.checkpoint_policies
            policy = {
                "nothing": None,
                "attn": cp.save_only_these_names("attn_out"),
                "dots": cp.dots_with_no_batch_dims_saveable,
                "dots_attn": cp.save_from_both_policies(
                    cp.dots_with_no_batch_dims_saveable,
                    cp.save_only_these_names("attn_out"),
                ),
            }[cfg.remat_policy]
            layer = jax.checkpoint(layer, policy=policy)

    if cfg.layer_pattern:
        if mesh is not None:
            raise ValueError(
                "a patterned model runs unsharded (mesh=None): the state "
                "and expert layers carry no sharding rules yet")
        for kind, lp in zip(cfg.layer_pattern, params["layers"]):
            if kind in "*R":   # "R": attention, then the routed experts
                q, k, v = _qkv_block(x, lp, cfg, None)
                x = x + attn_proj(_attention(q, k, v, cfg, None), lp, cfg,
                                  dt)
                if kind == "R":
                    x, _ = routed_mlp(x, lp, cfg)
            elif kind == "M":
                x, _ = ssm_mixer(x, lp, cfg)
            elif kind == "E":
                x, _ = moe_mixer(x, lp, cfg)
            else:   # "H": both mixers read the one normed input
                h = attn_norm(x, lp, cfg)
                q, k, v = _qkv_heads(h, lp, cfg, None)
                a = attn_proj(_attention(q, k, v, cfg, None), lp, cfg, dt)
                m, _ = ssm_branch(h, lp, cfg)
                x = gated_mlp(x + a + m, lp, cfg)
        auxes = jnp.zeros((), jnp.float32)
    elif cfg.scan_layers:
        x, auxes = lax.scan(lambda h, lp: layer(h, lp), x, params["layers"])
    else:
        aux_list = []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, aux_i = layer(x, lp)
            aux_list.append(aux_i)
        auxes = jnp.stack(aux_list)
    with jax.named_scope("head"):
        return (_rmsnorm(x, params["ln_f_scale"], cfg.norm_eps),
                jnp.sum(auxes))


def moe_drop_rates(params, tokens, cfg: TransformerConfig, mesh=None):
    """Per-layer MoE routing drop rate on this batch: (n_layers,) f32,
    the fraction of routed (token, choice) assignments that found no
    capacity slot. The visibility companion to the oracle tests —
    capacity drops during TRAINING are otherwise silent (they only show
    up as quality loss); train_app logs this alongside the loss. Uses
    the same forward math as training (routing is deterministic), no
    gradients."""
    if not cfg.n_experts:
        raise ValueError("moe_drop_rates needs an MoE config")
    dt = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    if mesh is not None:
        act_spec = jax.sharding.NamedSharding(
            mesh, resolve_spec(P(cfg.batch_axes, cfg.axis_sp, None), mesh,
                               cfg.mesh_axes)
        )
    else:
        act_spec = None
    x = _embed_tokens(params, tokens, cfg, mesh, dt)
    if mesh is not None:
        x = lax.with_sharding_constraint(x, act_spec)

    def body(h, lp):
        q, k, v = _qkv_block(h, lp, cfg, mesh)
        o = _attention(q, k, v, cfg, mesh)
        h, _aux, kept = _post_block(h, o, lp, cfg, mesh, act_spec,
                                    with_stats=True)
        return h, kept

    _, kepts = lax.scan(body, x, params["layers"])
    return 1.0 - kepts


def masked_causal_nll(logits, tokens):
    """Mean next-token NLL with the final position masked out — shared by
    loss_fn and the pipeline-parallel loss head (models/pp.py), so loss
    semantics can't drift between the two training paths."""
    B, T = tokens.shape
    targets = jnp.roll(tokens, -1, axis=1)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (lax.broadcasted_iota(jnp.int32, (B, T), 1) < T - 1).astype(nll.dtype)
    return jnp.sum(nll * mask) / jnp.sum(mask)


def chunked_masked_causal_nll(x, lm_head, tokens, *, chunk: int):
    """:func:`masked_causal_nll` computed WITHOUT ever materializing the
    (B, T, vocab) logits: a ``lax.scan`` over vocab chunks carries the
    online logsumexp state (running max, rescaled sumexp) and picks out
    each target's gold logit from the chunk that owns it — O(B·T·chunk)
    live memory instead of O(B·T·V). The scan body is rematted (saves
    only the small carry per chunk), so the backward recomputes each
    chunk's logits and the full f32 logits never exist in either pass
    — at long context this is THE memory wall: (B=1, T=65536, V=32768)
    f32 logits alone are 8 GB.

    ``x``: (B, T, d_model) final hidden states (forward_hidden);
    ``lm_head``: (d_model, V) in compute dtype; ``chunk`` must divide V.
    Numerically equal to the dense path (same f32 logit values, online
    logsumexp association), oracle-tested.
    """
    B, T = tokens.shape
    V = lm_head.shape[1]
    if V % chunk:
        raise ValueError(f"loss chunk {chunk} must divide vocab {V}")
    n_chunks = V // chunk
    targets = jnp.roll(tokens, -1, axis=1)
    w = lm_head.reshape(lm_head.shape[0], n_chunks, chunk)

    @jax.checkpoint
    def body(carry, wc_and_idx):
        m, s, gold = carry
        wc, c = wc_and_idx
        logits_c = jnp.dot(x, wc).astype(jnp.float32)  # (B, T, chunk)
        m_c = logits_c.max(axis=-1)
        m_new = jnp.maximum(m, m_c)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits_c - m_new[..., None]), axis=-1
        )
        local = targets - c * chunk
        in_chunk = (local >= 0) & (local < chunk)
        picked = jnp.take_along_axis(
            logits_c, jnp.clip(local, 0, chunk - 1)[..., None], axis=-1
        )[..., 0]
        gold = jnp.where(in_chunk, picked, gold)
        return (m_new, s, gold), None

    init = (
        jnp.full((B, T), -jnp.inf, jnp.float32),
        jnp.zeros((B, T), jnp.float32),
        jnp.zeros((B, T), jnp.float32),
    )
    (m, s, gold), _ = lax.scan(
        body, init,
        (jnp.moveaxis(w, 1, 0), jnp.arange(n_chunks)),
    )
    nll = m + jnp.log(s) - gold
    mask = (lax.broadcasted_iota(jnp.int32, (B, T), 1) < T - 1).astype(nll.dtype)
    return jnp.sum(nll * mask) / jnp.sum(mask)


def loss_fn(params, tokens, cfg: TransformerConfig, mesh=None):
    """Causal LM loss: predict token t+1 from prefix ≤ t (mean NLL).

    The full (batch, seq) token array feeds forward() and the final
    position is masked out of the loss — rather than slicing to seq-1 —
    so sequence shardings (seq % sp == 0) survive into the activations.
    """
    if cfg.loss_chunk:
        x, aux = forward_hidden(params, tokens, cfg, mesh)
        x = scaled(x, cfg.lm_head_multiplier)   # (x m) W = m (x W)
        # the head's matmul lives inside the chunked loss: one scope
        with jax.named_scope("loss"):
            loss = chunked_masked_causal_nll(
                x, matmul_weight(params, "lm_head", x.dtype), tokens,
                chunk=cfg.loss_chunk,
            )
    else:
        logits, aux = forward(params, tokens, cfg, mesh, return_aux=True)
        with jax.named_scope("loss"):
            loss = masked_causal_nll(logits, tokens)
    if cfg.n_experts:
        loss = loss + cfg.moe_aux_weight * aux
    return loss
