"""Mixture-of-experts with expert parallelism (EP) over a mesh axis.

Completes the parallelism menu of SURVEY.md §2.2 (EP listed as a
strategy the ring/pt2pt/collective primitives must be shaped for). The
communication pattern is the ``MPI_Alltoall`` the comm layer already
exposes (collectives.all_to_all — the same primitive as Ulysses): each
rank owns E/P experts; tokens are routed top-1 (Switch style), packed
into fixed ``capacity`` slots per (source rank, expert) — static shapes,
the XLA ground rule — exchanged with one all-to-all each way, processed
by the local experts' FFNs (batched einsum, MXU-shaped), and combined
with the router gates.

Drop semantics: tokens past an expert's per-source-rank capacity are
dropped (output contribution zero), exactly as in the dense oracle
:func:`moe_dense` with the same capacity — sharded and dense results are
numerically identical per token shard, which is what the §4.2-style
oracle test asserts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.comm import collectives, ring
from hpc_patterns_tpu.ops.grouped_matmul import grouped_matmul


def _dispatch_combine(x, router_w, n_experts: int, capacity: int,
                      top_k: int = 1):
    """Top-k routing tensors for local tokens x: (N, D).

    Returns (dispatch (N, E, C) f32 0/1, combine (N, E, C) f32 gate,
    aux_loss scalar, kept_frac scalar — the fraction of routed
    (token, choice) assignments that got a capacity slot; 1 - kept_frac
    is the drop rate the training telemetry reports). Position within
    an expert's capacity is assigned in token order (cumsum), the
    Switch transformer formulation; for ``top_k > 1`` the walk is
    CHOICE-major — every token's first choice claims its slot before
    any second choice competes (GShard's priority rule, so raising k
    never evicts a first-choice assignment) — and the k gates are
    renormalized to sum to one per token.
    """
    n = x.shape[0]
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)  # (N, E)
    if top_k == 1:
        expert = jnp.argmax(gates, axis=-1)  # (N,)
        onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.float32)
        # slot index of each token within its expert (0-based, token order)
        position = jnp.cumsum(onehot, axis=0) * onehot - 1.0  # -1 elsewhere
        kept = onehot * (position < capacity)  # overflow dropped
        pos_clamped = jnp.clip(position, 0, capacity - 1).astype(jnp.int32)
        slot_onehot = jax.nn.one_hot(pos_clamped, capacity, dtype=jnp.float32)
        dispatch = kept[..., None] * slot_onehot  # (N, E, C)
        top_gate = jnp.sum(gates * onehot, axis=-1)  # (N,)
        combine = dispatch * top_gate[:, None, None]
        first_frac = onehot.mean(axis=0)
        kept_frac = jnp.sum(kept) / n
    else:
        vals, idx = jax.lax.top_k(gates, top_k)           # (N, k)
        norm = vals / jnp.sum(vals, axis=-1, keepdims=True)
        oh = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)  # (N, k, E)
        flat = oh.transpose(1, 0, 2).reshape(top_k * n, n_experts)
        position = jnp.cumsum(flat, axis=0) * flat - 1.0
        kept = flat * (position < capacity)
        pos_clamped = jnp.clip(position, 0, capacity - 1).astype(jnp.int32)
        slot_onehot = jax.nn.one_hot(pos_clamped, capacity,
                                     dtype=jnp.float32)
        disp_choice = (kept[..., None] * slot_onehot).reshape(
            top_k, n, n_experts, capacity
        )
        dispatch = disp_choice.sum(0)  # choices hit distinct experts
        combine = jnp.einsum("knec,nk->nec", disp_choice, norm)
        first_frac = oh[:, 0].mean(axis=0)
        kept_frac = jnp.sum(kept) / (top_k * n)
    # Switch load-balancing auxiliary loss: E * sum_e f_e * P_e, with
    # f the FIRST-choice routing fraction (the k=1 definition; the
    # balance pressure targets the primary assignment)
    p = gates.mean(axis=0)
    aux = n_experts * jnp.sum(first_frac * p)
    return dispatch, combine, aux, kept_frac


def _scatter_dispatch(x, gates, n_experts: int, capacity: int,
                      top_k: int):
    """Sort/scatter routing: the O(N·D + E·C·D) replacement for the
    one-hot einsum dispatch, whose (N, E, C) tensors are O(N²·cf/E)
    and OOM a 16 GB chip near 16k tokens (builder-measured on an older
    toolchain). Same assignment semantics as the einsum path by
    construction: a STABLE argsort of the choice-major expert ids gives
    each (token, choice) the same within-expert rank the cumsum
    formulation computes, so the kept set and slot layout are
    identical (oracle-tested equal).

    Returns (xin (E, C, D), combine(out) -> y (N, D), aux, kept_frac).
    """
    n = x.shape[0]
    if top_k == 1:
        vals = jnp.max(gates, axis=-1, keepdims=True)       # (N, 1)
        idx = jnp.argmax(gates, axis=-1)[:, None]           # (N, 1)
        norm = jnp.ones_like(vals)
        first_frac = jax.nn.one_hot(idx[:, 0], n_experts,
                                    dtype=jnp.float32).mean(0)
        gate_per_choice = vals
    else:
        vals, idx = jax.lax.top_k(gates, top_k)             # (N, k)
        norm = vals / jnp.sum(vals, axis=-1, keepdims=True)
        first_frac = jax.nn.one_hot(idx[:, 0], n_experts,
                                    dtype=jnp.float32).mean(0)
        gate_per_choice = norm
    k = idx.shape[1]
    # choice-major flat (GShard priority: all first choices precede any
    # second choice), matching the einsum path's walk order
    expert_flat = idx.T.reshape(k * n)                      # (kN,)
    token_flat = jnp.tile(jnp.arange(n, dtype=jnp.int32), k)
    gate_flat = gate_per_choice.T.reshape(k * n)
    order = jnp.argsort(expert_flat, stable=True)
    sorted_e = expert_flat[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(n_experts),
                             side="left")
    rank = jnp.arange(k * n, dtype=jnp.int32) - start[sorted_e].astype(
        jnp.int32
    )
    keep = rank < capacity
    kept_frac = jnp.sum(keep) / (k * n)
    # dropped entries scatter to a trash row past the real slots
    slot = jnp.where(keep, sorted_e * capacity + rank,
                     n_experts * capacity)
    src_tok = token_flat[order]
    xin_flat = jnp.zeros((n_experts * capacity + 1, x.shape[1]), x.dtype)
    xin_flat = xin_flat.at[slot].set(x[src_tok])
    xin = xin_flat[:-1].reshape(n_experts, capacity, x.shape[1])

    gate_sorted = gate_flat[order]

    def combine(out):
        out_flat = out.reshape(n_experts * capacity, -1)
        picked = jnp.where(
            keep[:, None],
            out_flat[jnp.clip(slot, 0, n_experts * capacity - 1)], 0.0
        )
        y = jnp.zeros((n, out_flat.shape[1]), out_flat.dtype)
        return y.at[src_tok].add(picked * gate_sorted[:, None].astype(
            out_flat.dtype
        ))

    p_mean = gates.mean(axis=0)
    aux = n_experts * jnp.sum(first_frac * p_mean)
    return xin, combine, aux, kept_frac


def _route(x, router_w, n_experts: int, capacity: int, top_k: int,
           dispatch: str):
    """Shared routing front-end for moe_dense and moe_ep: resolve the
    dispatch form once and return ``(xin (E, C, D), combine(out) -> y,
    aux, kept_frac)`` — the one place the einsum/scatter selection and
    the router math live, so the two entry points cannot drift."""
    if dispatch == "scatter":
        logits = jnp.dot(x.astype(jnp.float32),
                         router_w.astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)
        return _scatter_dispatch(x, gates, n_experts, capacity, top_k)
    if dispatch == "einsum":
        disp, combine, aux, kept = _dispatch_combine(
            x, router_w, n_experts, capacity, top_k
        )
        # routing math stays f32; dispatch/FFN run in x's dtype
        xin = jnp.einsum("nec,nd->ecd", disp.astype(x.dtype), x)

        def combine_fn(out):
            return jnp.einsum("nec,ecd->nd", combine.astype(out.dtype),
                              out)

        return xin, combine_fn, aux, kept
    raise ValueError(f"dispatch {dispatch!r} not in ('einsum', 'scatter')")


def _expert_ffn(xin, w1, w2, activation=None):
    """Batched per-expert FFN: xin (E, C, D), w1 (E, D, F), w2 (E, F, D)."""
    act = activation or jax.nn.gelu
    h = act(jnp.einsum("ecd,edf->ecf", xin, w1.astype(xin.dtype)))
    return jnp.einsum("ecf,efd->ecd", h, w2.astype(xin.dtype))


def default_capacity(n_tokens: int, n_experts: int,
                     capacity_factor: float = 1.25) -> int:
    return max(1, int(n_tokens * capacity_factor / n_experts))


def moe_dense(x, router_w, w1, w2, *, capacity: int, activation=None,
              top_k: int = 1, with_stats: bool = False,
              dispatch: str = "einsum"):
    """Single-device oracle: all E experts local. x: (N, D); w1: (E, D,
    F); w2: (E, F, D). Returns (y (N, D), aux_loss), plus the kept
    fraction when ``with_stats`` (drop rate = 1 - kept).

    ``dispatch``: "einsum" (one-hot (N, E, C) tensors — the teaching/
    oracle form, O(N²·cf/E) memory) or "scatter" (stable-sort routing,
    O(N + E·C) — same assignments by construction, the at-scale form).
    """
    E = w1.shape[0]
    xin, combine_fn, aux, kept = _route(x, router_w, E, capacity, top_k,
                                        dispatch)
    out = _expert_ffn(xin, w1, w2, activation)
    y = combine_fn(out)
    if with_stats:
        return y.astype(x.dtype), aux, kept
    return y.astype(x.dtype), aux


def moe_ep(x, router_w, w1_local, w2_local, *, axis: str, capacity: int,
           activation=None, top_k: int = 1, with_stats: bool = False,
           dispatch: str = "einsum"):
    """Expert-parallel MoE layer (rank-local; run inside ``shard_map``).

    ``x``: (N_local, D) this rank's tokens. ``w1_local``/``w2_local``:
    (E/P, D, F)/(E/P, F, D) — this rank's expert shard. ``router_w``:
    (D, E) replicated. Two all-to-alls move (tokens→experts→tokens),
    riding ICI like every other collective in the framework (§2.3).
    Per-token results equal :func:`moe_dense` on the same token shard
    with the same capacity.
    """
    P = ring.axis_size(axis)
    e_local = w1_local.shape[0]
    E = e_local * P
    xin, combine_fn, aux, kept = _route(x, router_w, E, capacity, top_k,
                                        dispatch)
    # tokens to their experts' owners: (E, C, D) -> (E/P, P*C, D)
    xin = collectives.all_to_all(xin, axis, split_axis=0, concat_axis=1)
    out = _expert_ffn(xin, w1_local, w2_local, activation)
    # results back to the tokens' owners: (E/P, P*C, D) -> (E, C, D)
    out = collectives.all_to_all(out, axis, split_axis=1, concat_axis=0)
    y = combine_fn(out)
    # aux/kept are per-shard; average across ranks for global scalars
    aux = collectives.allreduce(aux, axis, "mean")
    if with_stats:
        return (y.astype(x.dtype), aux,
                collectives.allreduce(kept, axis, "mean"))
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# The drop-free route of an expert layer that holds a share of its experts
# ---------------------------------------------------------------------------

def relu2(x):
    """relu(x)^2, the non-gated activation of the latent experts."""
    return jnp.square(jax.nn.relu(x))


def silu(x):
    """x sigmoid(x), the gate's activation of the gated experts: computed
    in float32 and rounded once to ``x``'s dtype (inside the grouped
    product's kernel the chip's compiler refuses ``jax.nn.silu`` on
    bfloat16: its logistic mixes a float32 constant in)."""
    x32 = x.astype(jnp.float32)
    return (x32 / (1.0 + jnp.exp(-x32))).astype(x.dtype)


def sigmoid_route(h, router_w, router_b, *, top_k: int, scale: float):
    """Sigmoid scores over ALL experts, float32: the ``top_k`` chosen are
    the top of ``score + router_b`` (a selection bias: it picks, it does
    not weigh), the gates the chosen scores normalised to one and scaled.
    h (N, D) -> (idx (N, k) int32, gates (N, k) float32)."""
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                               router_w.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + router_b.astype(jnp.float32), top_k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    return idx.astype(jnp.int32), scale * g / jnp.sum(g, -1, keepdims=True)


def softmax_route(h, router_w, *, top_k: int, renorm: bool = True):
    """A softmax over ALL experts, float32: the ``top_k`` largest
    probabilities are chosen (ties by index, as ``lax.top_k``), the gates
    those probabilities, divided by their sum where ``renorm``.
    h (N, D) -> (idx (N, k) int32, gates (N, k) float32)."""
    p = jax.nn.softmax(jnp.dot(h.astype(jnp.float32),
                               router_w.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST), axis=-1)
    g, idx = jax.lax.top_k(p, top_k)
    if renorm:
        g = g / jnp.sum(g, -1, keepdims=True)
    return idx.astype(jnp.int32), g


#: what :func:`held_experts` reports beside its result, one int32 each:
#: picks computed here, tokens routed, the fullest held expert's picks,
#: held experts with at least one pick, and 1 (a call). Whole numbers, so
#: that running sums over layers and steps stay exact (they wrap, they do
#: not stall: read them as differences) and give means:
#: ``max_load * held / picks`` is the fullest expert over the mean load
ROUTE_STATS = ("picks", "tokens", "max_load", "touched", "calls")


def held_experts(x, idx, gates, w1, w2, *, held_start: int = 0,
                 activation=relu2, valid=None, w_gate=None):
    """The part of a routed layer that the experts held here give.

    x (N, d) tokens, idx / gates (N, k) from a route over all experts, w1
    (held, d, f) and w2 (held, f, d): experts ``held_start ..
    held_start + held`` of the layer, each ``w2 activation(w1 x)``. With
    ``w_gate`` (held, d, f) the experts are GATED, three stacks each:
    ``w2 (activation(w_gate x) * (w1 x))``, the two first products over
    the same sorted rows and their product taken between them and the
    last. Only the picks whose expert lies in
    that range are computed: the picks sort by held expert (a stable
    sort; the others sort behind every group) and two grouped products
    (ops/grouped_matmul) run over the groups, so the cost follows the
    picks and no pick is ever dropped, whatever the imbalance. What the
    absent experts would add is left out. ``valid`` (N,) bool: tokens that
    do not count (a bucket's padding, an idle row) pick nothing. Returns
    (y (N, d) float32, stats (len(ROUTE_STATS),) int32)."""
    N, k = idx.shape
    held = w1.shape[0]
    local = idx - held_start
    here = (local >= 0) & (local < held)
    if valid is not None:
        here = here & valid[:, None]
    e_flat = jnp.where(here, local, held).reshape(N * k)
    order = jnp.argsort(e_flat, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[e_flat].add(1)[:held]
    rows = x[order // k]                                  # (N k, d)
    if w_gate is None:
        mid = grouped_matmul(rows, w1.astype(x.dtype), sizes,
                             activation=activation)
    else:   # the rows behind every group stay unwritten through all three
        mid = (grouped_matmul(rows, w_gate.astype(x.dtype), sizes,
                              activation=activation)
               * grouped_matmul(rows, w1.astype(x.dtype), sizes))
    out = grouped_matmul(mid, w2.astype(x.dtype), sizes,
                         preferred_element_type=jnp.float32)
    # back to (token, choice) order: a gather, then the gated sum. The
    # products leave the rows behind every group unwritten: those picks
    # are masked before anything multiplies them
    back = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    out = jnp.where(here[..., None], out[back].reshape(N, k, -1), 0.0)
    y = jnp.sum(out * gates[..., None], axis=1)
    tokens = (jnp.int32(N) if valid is None
              else jnp.sum(valid, dtype=jnp.int32))
    stats = jnp.stack([
        jnp.sum(sizes), tokens, jnp.max(sizes),
        jnp.sum(sizes > 0, dtype=jnp.int32), jnp.int32(1)])
    return y, stats


def latent_moe(h, router_w, router_b, w_down, w_up, w1, w2, ws1, ws2, *,
               held_start: int, top_k: int, scale: float, valid=None):
    """A LatentMoE layer on its share of the experts: h (N, D) ->
    (out (N, D) in h's dtype, stats).

        idx, g = route(h)                       over all experts
        r   = sum_k g_k W2_k relu2(W1_k (h W_down))    the held picks
        out = r W_up + Ws2 relu2(Ws1 h)         the shared expert, whole

    The router and the shared expert read the hidden state; only the
    routed experts live in the latent width. On one chip the layer runs
    without its exchange: the other shares' parts are not here."""
    dt = h.dtype
    with jax.named_scope("route"):
        idx, gates = sigmoid_route(h, router_w, router_b, top_k=top_k,
                                   scale=scale)
    with jax.named_scope("latent"):
        u = jnp.dot(h, w_down.astype(dt))
    with jax.named_scope("experts"):
        r, stats = held_experts(u, idx, gates, w1, w2,
                                held_start=held_start, valid=valid)
    with jax.named_scope("latent"):
        routed = jnp.dot(r.astype(dt), w_up.astype(dt))
    with jax.named_scope("shared"):
        shared = jnp.dot(relu2(jnp.dot(h, ws1.astype(dt))), ws2.astype(dt))
    return routed + shared, stats


def gated_moe(h, router_w, w_gate, w_up, w_down, *, held_start: int,
              top_k: int, renorm: bool = True, valid=None,
              scale: float = 1.0, shared=None):
    """A layer of softmax-routed SiLU-gated experts on its share of them:
    h (N, D) -> (out (N, D) float32, stats).

        idx, g = softmax_route(h)               over all experts
        out = sum_k scale g_k Wd_k (silu(Wg_k h) * (Wu_k h))  the held picks
              + Ws_d (silu(Ws_g h) * (Ws_u h))  with ``shared``

    No latent width: the experts read the hidden state. ``shared`` =
    (Ws_g, Ws_u, Ws_d): one SiLU-gated expert that every token takes,
    whole on every chip, added as it is (scope ``shared``). With
    ``scale`` 1 and no shared expert nothing of either is emitted. On one
    chip the layer runs without its exchange: what the experts held
    elsewhere would add is not here."""
    with jax.named_scope("route"):
        idx, gates = softmax_route(h, router_w, top_k=top_k, renorm=renorm)
        if scale != 1.0:
            gates = gates * scale
    with jax.named_scope("experts"):
        out, stats = held_experts(h, idx, gates, w_up, w_down,
                                  held_start=held_start, activation=silu,
                                  valid=valid, w_gate=w_gate)
    if shared is not None:
        ws_gate, ws_up, ws_down = shared
        with jax.named_scope("shared"):
            mid = silu(jnp.dot(h, ws_gate)) * jnp.dot(h, ws_up)
            out = out + jnp.dot(mid, ws_down,
                                preferred_element_type=jnp.float32)
    return out, stats
