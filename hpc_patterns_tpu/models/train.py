"""Training step: jit-compiled, sharded, donated.

The full step — forward (bf16), loss, backward, optax update — under one
``jit`` over the mesh: XLA lays every collective (attention-ring
ppermutes, TP psums, DP gradient all-reduce) onto ICI from the sharding
annotations alone, the §2.3 "GPU-aware, no host staging" property at
training scale. Master params/opt state stay f32 and are donated, so the
update is in-place in HBM.

Sharding flows from the *data*: params are placed with
models/sharding.py rules, optax moments inherit those shardings at init
(zeros_like preserves sharding), tokens are placed with batch_sharding —
jit then propagates from its inputs, with the activation constraints in
forward() pinning the interior. No separate opt-state sharding spec to
maintain.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax
from jax import lax

from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness import trace as tracelib
from hpc_patterns_tpu.memory import kinds as kindslib
from hpc_patterns_tpu.models import sharding as shardlib
from hpc_patterns_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    loss_fn,
    scoped,
)


def record_step_metrics(step: int, loss: float, dt_s: float,
                        tokens: int) -> None:
    """Per-step training telemetry into the process-wide registry
    (harness/metrics.py; no-op when disabled): loss/step-time/throughput
    gauges, a step-time histogram split by phase — step 0 is the
    compile-dominated step, so it lands in a ``train.compile_s`` gauge
    instead of polluting the steady-state ``train.step_s`` percentiles
    (the warmup-vs-timed discipline of harness.timing applied to the
    training loop)."""
    m = metricslib.get_metrics()
    if not m.enabled:
        return
    m.counter("train.steps").inc()
    m.gauge("train.loss").set(loss)
    m.gauge("train.step_time_s").set(dt_s)
    if dt_s > 0:
        m.gauge("train.tokens_per_s").set(tokens / dt_s)
    if step == 0:
        m.gauge("train.compile_s").set(dt_s)
    else:
        m.histogram("train.step_s").observe(dt_s)


def make_optimizer(learning_rate: float = 3e-4, weight_decay: float = 0.01,
                   grad_clip: float = 1.0, *, warmup_steps: int = 0,
                   total_steps: int = 0, schedule: str = "constant"):
    """adamw + global-norm clip, with the standard LR schedules:
    ``constant`` (default), or ``cosine`` — linear warmup over
    ``warmup_steps`` then cosine decay to 10% of peak at
    ``total_steps`` (required for cosine)."""
    if schedule == "constant":
        lr = (
            optax.linear_schedule(0.0, learning_rate, warmup_steps)
            if warmup_steps else learning_rate
        )
    elif schedule == "cosine":
        if total_steps <= warmup_steps:
            raise ValueError(
                f"cosine needs total_steps > warmup_steps, got "
                f"{total_steps} <= {warmup_steps}"
            )
        lr = optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=learning_rate,
            warmup_steps=warmup_steps, decay_steps=total_steps,
            end_value=0.1 * learning_rate,
        )
    else:
        raise ValueError(f"schedule {schedule!r} not in (constant, cosine)")
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(lr, weight_decay=weight_decay),
    )


def memory_kind_shardings(tree, kind: str):
    """Shardings of ``tree``'s (concrete) leaves retargeted to a JAX
    memory kind — the L2 allocator axis (SURVEY.md §2, ``-H/-D/-S``)
    applied to training state. Delegates to the single definition in
    ``memory/kinds.py`` (the residency subsystem's probe/sharding
    home); this name stays for its existing callers."""
    return kindslib.memory_kind_shardings(tree, kind)


def offload_opt_state(opt_state, kind: str = "pinned_host"):
    """Move the optimizer state to host memory. Adam moments are 2x the
    (f32) parameter footprint and are touched once per step — parking
    them in host RAM frees that HBM for batch/model/sequence headroom,
    at the cost of streaming them over PCIe each step. Pair with
    ``make_train_step(..., offload_opt_example=...)``.

    Gated on the SHARED placement probe (memory/kinds.py): a backend
    that cannot actually place buffers in ``kind`` gets the input back
    UNCHANGED with a printed note — previously this path paid the
    ``device_put`` (and on some backends raised) while delivering none
    of the offload's benefit, and callers could not tell."""
    leaves = jax.tree.leaves(opt_state)
    device = next(iter(leaves[0].devices())) if leaves else None
    if not kindslib.memory_kind_placement_works(device, kind):
        print(f"note: backend has no usable {kind!r} memory kind; "
              "optimizer state left in place (no offload benefit "
              "available here)")
        return opt_state
    return jax.device_put(opt_state, memory_kind_shardings(opt_state, kind))


def offload_shardings(opt_state_host):
    """(host_shardings, hbm_shardings) for a host-resident opt state —
    THE pull/push targets of the offloaded update, shared by
    make_train_step and the training benchmark so the streaming
    strategy cannot drift between what ships and what is measured."""
    host_sh = jax.tree.map(lambda x: x.sharding, opt_state_host)
    return host_sh, memory_kind_shardings(opt_state_host, "device")


def offload_example_shardings(example):
    """:func:`offload_shardings`, tolerant of the probe-gated identity
    fallback: when :func:`offload_opt_state` left the state IN PLACE
    (no usable pinned_host on this backend), the tiers collapse onto
    one memory — both targets are the example's own shardings, so the
    step's staging still runs as same-memory copies instead of dying
    inside ``with_memory_kind("device")`` with an error that looks
    unrelated to the note the user was shown. ONE definition for every
    step builder taking an ``offload_opt_example`` (make_train_step,
    pp.make_pp_train_step)."""
    leaves = jax.tree.leaves(example)
    pinned = bool(leaves) and all(
        getattr(x.sharding, "memory_kind", None) == "pinned_host"
        for x in leaves)
    if pinned:
        return offload_shardings(example)
    host_sh = jax.tree.map(lambda x: x.sharding, example)
    return host_sh, host_sh


def make_train_step(cfg: TransformerConfig, mesh=None, optimizer=None,
                    accum_steps: int = 1, offload_opt_example=None,
                    residency=None):
    """Returns jitted ``step(params, opt_state, tokens) -> (loss, params,
    opt_state)`` with param/opt-state donation (in-place HBM update).

    ``accum_steps > 1`` splits the batch into that many micro-batches
    and accumulates gradients over a ``lax.scan`` before the single
    optimizer update — same numbers as the big batch (mean of
    micro-means over equal splits), at 1/accum_steps the activation
    memory: the train-side memory lever alongside remat.

    ``offload_opt_example``: a host-resident optimizer state (from
    :func:`offload_opt_state`) whose shardings tell the step where the
    state lives — the update then pulls it to HBM, applies, and pushes
    it back, all inside the one jit (XLA schedules the transfers).

    ``residency``: a :class:`hpc_patterns_tpu.memory.ResidencyManager`
    — routes the offload through the tiered-memory subsystem instead
    of the in-jit all-or-nothing move: the host->HBM pull is
    DISPATCHED before the gradient phase and hides under it
    (accumulation-phase prefetch, with a measured ``mem.prefetch``
    window and overlap fraction), the update consumes the pulled
    state, and the push back to host rides a ``mem.evict`` window
    (docs/memory.md). Requires ``offload_opt_example``. Numerics are
    the single-jit path's (same gradient and update ops, staged).

    Pass ``params``/``opt_state`` created by :func:`init_train_state`
    (sharded when ``mesh`` is given); the same code path is the
    single-device oracle when ``mesh`` is None (the §4 test strategy:
    distributed result must match the local one).
    """
    optimizer = optimizer or make_optimizer()
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    grad_fn = jax.value_and_grad(partial(loss_fn, cfg=cfg, mesh=mesh))
    if offload_opt_example is not None:
        host_sh, hbm_sh = offload_example_shardings(offload_opt_example)
    else:
        host_sh = hbm_sh = None

    def accum_grads(params, tokens):
        if accum_steps == 1:
            return grad_fn(params, tokens)
        B = tokens.shape[0]
        if B % accum_steps:
            raise ValueError(
                f"batch {B} must divide by accum_steps {accum_steps}"
            )
        micro = tokens.reshape(accum_steps, B // accum_steps, -1)

        def accum(carry, mb):
            loss_sum, g_sum = carry
            loss, g = grad_fn(params, mb)
            return (
                loss_sum + loss,
                jax.tree.map(jnp.add, g_sum, g),
            ), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (loss, grads), _ = lax.scan(
            accum, (jnp.zeros((), jnp.float32), zeros), micro
        )
        scale = 1.0 / accum_steps
        return loss * scale, jax.tree.map(lambda g: g * scale, grads)

    if residency is not None:
        if offload_opt_example is None:
            raise ValueError(
                "residency streaming needs offload_opt_example (a "
                "host-resident opt state from offload_opt_state)")
        return _make_streamed_step(optimizer, accum_grads, host_sh,
                                   hbm_sh, residency)

    def step(params, opt_state, tokens):
        if hbm_sh is not None:
            opt_state = jax.device_put(opt_state, hbm_sh)
        loss, grads = accum_grads(params, tokens)
        with jax.named_scope("update"):   # clip + AdamW + apply
            updates, opt_state = optimizer.update(grads, opt_state,
                                                  params)
            params = optax.apply_updates(params, updates)
        if host_sh is not None:
            opt_state = jax.device_put(opt_state, host_sh)
        return loss, params, opt_state

    if host_sh is not None:
        # declare the host residency of the opt-state input/output so
        # donation pairs host buffers with host buffers
        jitted = jax.jit(
            step, donate_argnums=(0, 1),
            in_shardings=(None, host_sh, None),
            out_shardings=(None, None, host_sh),
        )
    else:
        jitted = jax.jit(step, donate_argnums=(0, 1))
    # under --trace, the flight recorder stamps a compile event (with
    # the triggering batch shapes) every time a call grows the jit
    # cache — a recompiling training loop is visible on the timeline
    # instead of showing up only as a slow step; without a recorder
    # the wrapper is a passthrough call. exec_memory stays off: the
    # AOT memory_analysis pass is a second full compile of the step
    # (use trace.record_executable_memory at an explicit AOT site)
    return tracelib.instrument_jit(jitted, "train.step")


def _make_streamed_step(optimizer, accum_grads, host_sh, hbm_sh,
                        residency):
    """The residency-managed offloaded step: two jits staged around
    the manager's instrumented transfers (see ``make_train_step``'s
    ``residency`` doc). The pull DISPATCHES first, the gradient-
    accumulation jit runs over it, and the pull's completion is
    OBSERVED (blocked) while that phase still executes — so the wait
    that remains is exactly the transfer time the accumulation failed
    to hide, and the ``mem.prefetch`` window + overlap fraction report
    it instead of asserting it."""
    import jax as _jax

    leaves = _jax.tree.leaves(host_sh)
    pinned = bool(leaves) and all(
        getattr(s, "memory_kind", None) == "pinned_host"
        for s in leaves)
    if not pinned:
        # degraded tier (no real pinned_host — offload_opt_state left
        # the state in place): the tiers collapse onto one memory, the
        # staging/measurement pipeline still runs — the CPU test shape
        hbm_sh = host_sh
    accum_jit = tracelib.instrument_jit(jax.jit(accum_grads),
                                        "train.accum")

    @scoped("update")
    def apply_update(params, grads, opt_state):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state

    # params + opt state donate (the in-place HBM update, as in the
    # fused step); grads do not — only some of their buffers could
    # alias an output, and the partial-donation warning would spam
    # every caller for a marginal win
    apply_jit = tracelib.instrument_jit(
        jax.jit(apply_update, donate_argnums=(0, 2)), "train.apply")

    def step(params, opt_state, tokens):
        import time

        # close the PREVIOUS step's mem.evict window first (its push
        # had a whole step to land, so this block is cheap) — without
        # it a traced run retains every step's host opt-state copy in
        # the manager's open-window list, unbounded
        residency.drain()
        opt_dev, handle = residency.pull_payload(
            opt_state, shardings=hbm_sh,
            attrs={"consumer": "train.accum"})
        t_acc0 = time.perf_counter()
        loss, grads = accum_jit(params, tokens)
        # observe the ACCUMULATION's completion first: the consumer
        # window must end when the hiding compute ended. Stamping it
        # after also waiting out the pull would extend the window over
        # the exposed wait and read ~100% overlap for a transfer the
        # accumulation barely covered — the one number this exists to
        # catch on chip
        jax.block_until_ready(loss)
        t_acc1 = time.perf_counter()
        # now the pull: any wait that remains is the UNHIDDEN time
        jax.block_until_ready(opt_dev)
        residency.complete_pull(handle,
                                chunk_windows=((t_acc0, t_acc1),))
        params, opt_dev = apply_jit(params, grads, opt_dev)
        opt_host = residency.push_payload(
            opt_dev, shardings=host_sh,
            attrs={"consumer": "train.apply"})
        return loss, params, opt_host

    return step


def init_train_state(key, cfg: TransformerConfig, mesh=None, optimizer=None):
    """(params, opt_state): f32 master params placed per the sharding
    rules; optax state inherits the placement (zeros_like preserves
    sharding).

    With a mesh, init runs *under jit with sharded out_shardings*, so
    each device materializes only its own shards — no single device ever
    holds the full f32 copy (the point of TP at flagship scale)."""
    optimizer = optimizer or make_optimizer()
    if mesh is None:
        params = init_params(key, cfg)
    else:
        # jaxlint: disable=recompile-hazard — init-time one-shot (once
        # per train state); out_shardings close over the runtime mesh
        params = jax.jit(
            lambda k: init_params(k, cfg),
            out_shardings=shardlib.param_shardings(mesh, cfg),
        )(key)
    opt_state = optimizer.init(params)
    return params, opt_state


def make_batch(key, cfg: TransformerConfig, batch: int, seq: int, mesh=None):
    """Synthetic token batch (benchmark fuel), sharded when mesh given."""
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab, jnp.int32)
    if mesh is not None:
        tokens = jax.device_put(tokens, shardlib.batch_sharding(mesh, cfg))
    return tokens
