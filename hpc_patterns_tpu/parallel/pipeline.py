"""Pipeline parallelism: microbatch schedules over the pt2pt ring.

The reference's pairwise blocking Send/Recv between ring neighbors is
"the core of PP" (SURVEY.md §2.2): a pipeline stage boundary is exactly
one neighbor handoff per tick. This module turns that primitive
(comm.ring.ring_shift — deadlock-free ppermute, vs the reference's
even/odd ordering trick, allreduce-mpi-sycl.cpp:50-58) into two
schedules:

- :func:`pipeline_forward` — GPipe-style forward fill-drain: rank r runs
  stage r; microbatch m enters at tick m, reaches stage r at tick m+r,
  exits after M + P - 1 ticks.
- :func:`pipeline_train_1f1b` — the 1F1B training schedule: each stage
  runs its warmup forwards, then alternates one-forward-one-backward, so
  at most P - r microbatch activations are ever stashed on stage r
  (vs all M under GPipe) — the input stash here is sized min(P, M) and
  ring-indexed, the real 1F1B memory bound. Backward is recompute-based
  (``jax.vjp`` of the stage on the stashed input), the standard PP
  memory/FLOPs trade.

SPMD subtlety: inside ``shard_map`` every rank executes the same program,
so "is my buffer valid at this tick" is data (a mask), not control flow —
inactive (fill/drain bubble) ticks compute on garbage and mask the
result, the standard XLA-friendly formulation (static tick loop, no
data-dependent branching — SURVEY.md's XLA-semantics ground rule).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from hpc_patterns_tpu.comm import ring


def _varying(tree, axis: str):
    """Mark fresh (axis-invariant) arrays as varying over the shard_map
    axis, so they can carry through a lax.scan whose body mixes them
    with genuinely per-rank values (ring hops, rank-masked updates) —
    scan requires carry-in and carry-out VMA types to match."""
    return jax.tree.map(lambda a: lax.pcast(a, (axis,), to="varying"), tree)


def pipeline_forward(
    stage_fn: Callable,
    stage_params,
    x_microbatches,
    axis: str,
):
    """Run ``stage_fn(stage_params, x)`` as a P-stage pipeline over the
    mesh axis (rank-local; run inside ``shard_map``).

    ``stage_params``: this rank's stage parameters (stage r on rank r).
    ``x_microbatches``: (M, ...) microbatches — read on rank 0 (the
    pipeline entry); other ranks may pass zeros of the same shape.
    Returns (M, ...) outputs, valid on the LAST rank (rank size-1); other
    ranks return zeros — fetch the last-rank shard, or close the ring
    with one more hop if replication is wanted.
    """
    size = ring.axis_size(axis)
    me = ring.axis_index(axis)
    M = x_microbatches.shape[0]
    mb_shape = x_microbatches.shape[1:]

    # shape contract checked once up front (the handoff buffer is reused
    # every tick, so stages must be shape/dtype-preserving — project
    # in/out inside stage_fn)
    y_shape = jax.eval_shape(
        stage_fn, stage_params,
        jax.ShapeDtypeStruct(mb_shape, x_microbatches.dtype),
    )
    if not hasattr(y_shape, "shape"):
        raise ValueError(
            "stage_fn must return a single activation array; got a "
            f"{type(y_shape).__name__} — aux-returning (MoE) stages are "
            "only supported by pipeline_train_1f1b, which threads the "
            "aux through the backward"
        )
    if y_shape.shape != mb_shape or y_shape.dtype != x_microbatches.dtype:
        raise ValueError(
            f"stage_fn must preserve microbatch shape/dtype: "
            f"{mb_shape}/{x_microbatches.dtype} -> "
            f"{y_shape.shape}/{y_shape.dtype}"
        )

    buf = jnp.zeros(mb_shape, x_microbatches.dtype)  # incoming activation

    def tick_body(carry, tick):
        buf, outs = carry
        # entry rank injects microbatch `tick` during the fill window
        cur = jnp.where(me == 0, x_microbatches[jnp.clip(tick, 0, M - 1)],
                        buf)
        # stage r is active for microbatch (tick - r) in [0, M)
        active = jnp.logical_and(tick - me >= 0, tick - me < M)
        y = stage_fn(stage_params, cur)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # last stage banks its finished microbatch
        out_idx = jnp.clip(tick - (size - 1), 0, M - 1)
        bank = jnp.logical_and(active, me == size - 1)
        outs = outs.at[out_idx].set(jnp.where(bank, y, outs[out_idx]))
        # neighbor handoff (the SendRecvRing hop); last->0 wraps but rank 0
        # overwrites with its injection, so the wrap is harmless
        buf = ring.ring_shift(y, axis, 1)
        return (buf, outs), None

    outs = jnp.zeros((M, *mb_shape), x_microbatches.dtype)
    # scan, not a Python loop: the stage traces ONCE however long the
    # pipeline runs (compile cost independent of M and P)
    (buf, outs), _ = lax.scan(
        tick_body, _varying((buf, outs), axis), jnp.arange(M + size - 1)
    )
    return outs


def schedule_1f1b(P: int, M: int):
    """The 1F1B tick table (pure Python — testable without devices).

    Unit fwd/bwd costs. Returns ``(fwd, bwd)`` dicts mapping
    ``(stage, microbatch) -> tick``:

    - forward:  warmup ``t_f(r, m) = m + r`` for the first ``P - r``
      microbatches (streamed back-to-back), then steady-state
      ``t_f(r, m) = 2m + r`` — each forward follows the backward of
      microbatch ``m - (P - r)`` (the one-forward-one-backward
      alternation; earlier stages idle between warmup and their first
      backward, which is the 1F1B bubble).
    - backward: ``t_b(r, m) = 2P - 1 - r + 2m`` — microbatch m's
      backward leaves the last stage right after its forward and walks
      back one stage per tick.

    Properties (asserted by tests): per stage, no two ops share a tick;
    an activation is produced >= 1 tick before its consumer needs it;
    the number of stashed activations on stage r never exceeds
    ``min(P - r, M)`` — the 1F1B memory bound.
    """
    fwd = {}
    bwd = {}
    for r in range(P):
        for m in range(M):
            fwd[(r, m)] = m + r if m <= P - 1 - r else 2 * m + r
            bwd[(r, m)] = 2 * P - 1 - r + 2 * m
    return fwd, bwd


def pipeline_train_1f1b(
    stage_fn: Callable,
    stage_params,
    x_microbatches,
    targets,
    loss_fn: Callable,
    axis: str,
    *,
    loss_params=None,
    return_input_grads: bool = False,
    stage_aux_weight: float | None = None,
):
    """One 1F1B pipeline training pass (rank-local; run inside
    ``shard_map``): forward every microbatch through the P stages,
    seed each backward with d(loss)/dy on the last stage, and return
    this stage's accumulated parameter gradients.

    ``stage_fn(params, x) -> y`` must preserve the microbatch shape
    (project in/out inside); ``loss_fn(y, target) -> scalar`` is applied
    per microbatch on the LAST stage. ``x_microbatches``: (M, ...) read
    on rank 0; ``targets``: (M, ...) read on rank P-1 (other ranks pass
    same-shaped arrays). Returns ``(mean_loss, grads)`` where mean_loss
    is valid on the last rank (zeros elsewhere) and ``grads`` matches
    ``stage_params`` (this stage's gradient, summed over microbatches —
    divide by M upstream for a mean-loss gradient if desired; here the
    seed is grad of ``loss_fn`` itself per microbatch, accumulated).

    ``loss_params`` (optional): a pytree the last stage's loss head
    differentiates through — ``loss_fn(loss_params, y, target)`` — e.g.
    the LM head + final norm of a pipelined transformer; their gradient
    is returned too (nonzero on the last rank; psum over the axis to
    replicate). ``return_input_grads``: also return d(loss)/d(x_m) as an
    (M, ...) f32 array (nonzero on rank 0) — the hook for differentiating
    whatever produced the pipeline inputs (e.g. the embedding).
    ``stage_aux_weight`` (optional): when set, ``stage_fn`` returns
    ``(y, aux)`` with ``aux`` a scalar per-microbatch auxiliary loss
    (e.g. the MoE load-balance loss). The aux values are accumulated
    over this rank's forwards into ``extras["aux_sum"]`` (unweighted;
    psum over the axis and divide by M upstream), and each backward
    seeds the aux output's cotangent with ``stage_aux_weight``, so the
    returned parameter/input gradients include the weighted aux term —
    the auxiliary loss rides the existing 1F1B backward, no extra pass.

    With any option the return becomes ``(mean_loss, grads, extras)``
    with ``extras = {"loss_grads": ..., "input_grads": ..., "aux_sum":
    ...}`` (the requested keys only); plain calls keep the 2-tuple.

    Scheduling follows :func:`schedule_1f1b`; the input stash and the
    activation/cotangent mailboxes are ring-indexed with ``min(P, M)``
    slots — the 1F1B in-flight bound (GPipe would need all M).
    """
    P = ring.axis_size(axis)
    me = ring.axis_index(axis)
    M = x_microbatches.shape[0]
    mb_shape = x_microbatches.shape[1:]
    S = min(P, M)  # stash slots: the 1F1B in-flight bound
    f32 = jnp.float32

    in_stash = jnp.zeros((S, *mb_shape), x_microbatches.dtype)
    fwd_mail = jnp.zeros((S, *mb_shape), x_microbatches.dtype)
    bwd_mail = jnp.zeros((S, *mb_shape), f32)
    grads = jax.tree.map(lambda p: jnp.zeros(p.shape, f32), stage_params)
    loss_grads = (None if loss_params is None else jax.tree.map(
        lambda p: jnp.zeros(p.shape, f32), loss_params))
    in_grads = (jnp.zeros((M, *mb_shape), f32)
                if return_input_grads else None)
    loss_sum = jnp.zeros((), f32)
    has_aux = stage_aux_weight is not None
    aux_sum = jnp.zeros((), f32) if has_aux else None

    def eval_stage(params, x):
        """Uniform (y, aux) stage evaluation (aux = 0 when unused)."""
        if has_aux:
            y, aux = stage_fn(params, x)
            return y, aux.astype(f32)
        return stage_fn(params, x), jnp.zeros((), f32)

    def fwd_microbatch_at(t):
        """(m, valid) for this rank's forward at tick t (traced me)."""
        warm = t - me  # warmup: t_f = m + r
        warm_ok = jnp.logical_and(warm >= 0, warm <= P - 1 - me)
        steady = (t - me) // 2  # steady: t_f = 2m + r
        steady_ok = jnp.logical_and(
            (t - me) % 2 == 0, steady > P - 1 - me
        )
        m = jnp.where(warm_ok, warm, steady)
        ok = jnp.logical_and(
            jnp.logical_or(warm_ok, steady_ok),
            jnp.logical_and(m >= 0, m < M),
        )
        return m, ok

    def bwd_microbatch_at(t):
        num = t - (2 * P - 1 - me)
        m = num // 2
        ok = jnp.logical_and(
            jnp.logical_and(num >= 0, num % 2 == 0),
            m < M,
        )
        return m, ok

    def masked_bank(mail, m, ok, payload):
        slot = m % S
        cur = mail[slot]
        return mail.at[slot].set(
            jnp.where(ok, payload.astype(mail.dtype), cur)
        )

    def tick_body(carry, t, *, has_fwd, has_bwd):
        # one 1F1B tick. ``has_fwd``/``has_bwd`` are STATIC phase flags
        # (fixed per scan segment below): before tick P no rank can run
        # a backward (first is t_b(P-1, 0) = P), after tick 2M+P-3 no
        # rank forwards (last is t_f(P-1, M-1)) — the corresponding unit
        # is skipped entirely instead of emitting fully-masked compute.
        (in_stash, fwd_mail, bwd_mail, grads, loss_grads, in_grads,
         loss_sum, aux_sum) = carry
        is_last = me == P - 1

        if has_fwd:
            m_f, f_ok = fwd_microbatch_at(t)
            x_f = jnp.where(
                me == 0, x_microbatches[jnp.clip(m_f, 0, M - 1)],
                fwd_mail[m_f % S],
            )
            in_stash = masked_bank(in_stash, m_f, f_ok, x_f)
        if has_bwd:
            m_b, b_ok = bwd_microbatch_at(t)
            x_b = in_stash[m_b % S]

        if not has_bwd:
            # fwd-only tick: plain stage evaluation, no pullback, no loss
            y, aux = eval_stage(stage_params, x_f)
        else:
            # ONE stage evaluation serves both units: per stage, forward
            # and backward never share a tick (schedule invariant), so
            # select the input and run a single vjp — y is the forward's
            # output on f_ok ticks, the recomputed activation on b_ok
            x_sel = jnp.where(b_ok, x_b, x_f) if has_fwd else x_b
            (y, aux), pullback = jax.vjp(eval_stage, stage_params, x_sel)

            tgt = targets[jnp.clip(m_b, 0, M - 1)]
            if loss_params is None:
                loss_m, dloss = jax.value_and_grad(loss_fn)(
                    y.astype(f32), tgt
                )
            else:
                loss_m, (dlp, dloss) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1)
                )(loss_params, y.astype(f32), tgt)
                lp_mask = jnp.logical_and(b_ok, is_last).astype(f32)
                loss_grads = jax.tree.map(
                    lambda g, d: g + lp_mask * d.astype(f32), loss_grads, dlp
                )
            dy = jnp.where(is_last, dloss, bwd_mail[m_b % S]).astype(y.dtype)
            # aux cotangent: the weighted auxiliary loss enters this
            # microbatch's backward here. Without aux the cotangent must
            # stay a plain (axis-invariant) zero to match eval_stage's
            # constant-zero aux output VMA type
            daux = (
                jnp.where(b_ok, jnp.float32(stage_aux_weight), 0.0)
                if has_aux else jnp.zeros((), f32)
            )
            dparams, dx = pullback((dy, daux))
            b_mask = b_ok.astype(f32)
            grads = jax.tree.map(
                lambda g, d: g + b_mask * d.astype(f32), grads, dparams
            )
            if return_input_grads:
                take = jnp.logical_and(b_ok, me == 0)
                idx = jnp.clip(m_b, 0, M - 1)
                in_grads = in_grads.at[idx].set(
                    jnp.where(take, dx.astype(f32), in_grads[idx])
                )
            loss_sum = loss_sum + jnp.where(
                jnp.logical_and(b_ok, is_last), loss_m, 0.0
            )
        if has_aux and has_fwd:
            # aux belongs to the FORWARD microbatch (f_ok and b_ok never
            # coincide on one stage, so a backward tick's recomputed aux
            # is not double-counted)
            aux_sum = aux_sum + jnp.where(f_ok, aux, 0.0)

        # ---- neighbor handoffs (masked payloads; only phases that can
        # carry data hop): the activation hops forward, the cotangent
        # hops backward, each tagged with its microbatch index
        if has_fwd:
            y_send = jnp.where(f_ok, y, jnp.zeros_like(y))
            y_recv = ring.ring_shift(y_send, axis, 1)
            mf_recv = ring.ring_shift(
                jnp.stack([m_f, f_ok.astype(m_f.dtype)]), axis, 1
            )
            fwd_mail = masked_bank(
                fwd_mail, mf_recv[0],
                jnp.logical_and(mf_recv[1] == 1, me != 0), y_recv,
            )
        if has_bwd:
            dx_send = jnp.where(b_ok, dx.astype(f32),
                                jnp.zeros(mb_shape, f32))
            dx_recv = ring.ring_shift(dx_send, axis, -1)
            mb_recv = ring.ring_shift(
                jnp.stack([m_b, b_ok.astype(m_b.dtype)]), axis, -1
            )
            bwd_mail = masked_bank(
                bwd_mail, mb_recv[0],
                jnp.logical_and(mb_recv[1] == 1, me != P - 1), dx_recv,
            )
        return (in_stash, fwd_mail, bwd_mail, grads, loss_grads, in_grads,
                loss_sum, aux_sum), None

    # three lax.scan segments with static phase flags — the stage traces
    # a constant number of times (one plain eval + two vjps) however
    # large M and P are, vs one trace per tick under a Python loop:
    #   [0, P)            fwd only (fill; no backward can exist yet)
    #   [P, 2M+P-2)       mixed 1F1B steady state (empty when M == 1)
    #   [2M+P-2, n_ticks) bwd only (drain; no forward remains)
    n_ticks = 2 * M + 2 * P - 3 + 1
    carry = _varying(
        (in_stash, fwd_mail, bwd_mail, grads, loss_grads, in_grads,
         loss_sum, aux_sum),
        axis,
    )
    segments = (
        (0, P, True, False),
        (P, max(2 * M + P - 2, P), True, True),
        (max(2 * M + P - 2, P), n_ticks, False, True),
    )
    for t0, t1, hf, hb in segments:
        if t1 > t0:
            carry, _ = lax.scan(
                functools.partial(tick_body, has_fwd=hf, has_bwd=hb),
                carry, jnp.arange(t0, t1),
            )
    (in_stash, fwd_mail, bwd_mail, grads, loss_grads, in_grads,
     loss_sum, aux_sum) = carry

    mean_loss = jnp.where(me == P - 1, loss_sum / M, 0.0)
    extras = {}
    if loss_params is not None:
        extras["loss_grads"] = loss_grads
    if return_input_grads:
        extras["input_grads"] = in_grads
    if has_aux:
        extras["aux_sum"] = aux_sum
    if extras:
        return mean_loss, grads, extras
    return mean_loss, grads
