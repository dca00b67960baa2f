"""The plain reference of the training step: float32 loss, gradients by
``jax.grad`` through the plain block, global-norm clip and AdamW written
out. One sequence at a time (the batch mean is the mean of the rows'
means, every row masking its last position), each layer recomputed in the
backward pass, so that it fits beside its own optimizer state. Imports
nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import weights
from chipbench.reference import transformer as ref

B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8


def row_loss(params, tokens, m, lowp=None, head_rows=1024):
    """Mean next-token NLL of one sequence (T,), last position masked."""
    T = tokens.shape[0]
    x = params["embed"][tokens]
    step = jax.checkpoint(lambda x, lw: (ref.block(x, lw, m, lowp), None))
    x, _ = lax.scan(step, x, params["layers"])
    x = ref.rmsnorm(x, params["ln_f_scale"])
    targets = jnp.roll(tokens, -1)

    @jax.checkpoint
    def rows(args):
        xr, tr = args
        z = ref.mm(xr, params["lm_head"], lowp)
        return jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, tr[:, None], axis=-1)[:, 0]

    head_rows = min(head_rows, T)
    n = T // head_rows
    nll = lax.map(rows, (x.reshape(n, head_rows, -1),
                         targets.reshape(n, head_rows))).reshape(T)
    mask = jnp.arange(T) < T - 1
    return jnp.sum(jnp.where(mask, nll, 0.0)) / (T - 1)


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _row_grad(params, grads, loss, tokens, *, m, lowp):
    l, g = jax.value_and_grad(row_loss)(params, tokens, dict(m), lowp)
    return jax.tree.map(jnp.add, grads, g), loss + l


@functools.partial(jax.jit, static_argnames=("lr", "wd", "clip"),
                   donate_argnums=(0, 1, 2, 3))
def _adamw(params, grads, mu, nu, count, *, lr, wd, clip):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-30))
    grads = jax.tree.map(lambda g: g * scale, grads)
    mu = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, mu, grads)
    nu = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, nu, grads)
    c = count + 1
    def upd(p, a, b):
        mh = a / (1 - B1 ** c)
        vh = b / (1 - B2 ** c)
        return p - lr * (mh / (jnp.sqrt(vh) + ADAM_EPS) + wd * p)
    return jax.tree.map(upd, params, mu, nu), grads, mu, nu


@jax.jit
def leaf_norms(tree):
    """Norm of every leaf; a stacked leaf gives one norm per layer."""
    def one(path, a):
        stacked = any(getattr(k, "key", None) == "layers" for k in path)
        axes = tuple(range(1, a.ndim)) if stacked else None
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                axis=axes)).reshape(-1)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return jnp.concatenate([one(p, a) for p, a in flat])


SAMPLE_STRIDE = 61


@jax.jit
def leaf_sample(tree):
    """Every SAMPLE_STRIDE-th element of every leaf, as one vector: small
    enough to keep while the other side is computed, large enough
    (about 11 million elements here) that a relative difference over it
    reads like one over the whole tree."""
    return jnp.concatenate([a.reshape(-1)[::SAMPLE_STRIDE].astype(jnp.float32)
                            for a in jax.tree.leaves(tree)])


@jax.jit
def diff(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def three_steps(seed: int, m: dict, batches, opt: dict, *, lowp=None,
                half_batch=False):
    """Losses of the steps, leaf norms and a strided sample of the first
    clipped gradient, and leaf norms of the parameters' change after the
    last. ``batches``:
    (B, T) int arrays. ``half_batch`` plants the fault "half of the batch
    left out, the mean taken over the rest"."""
    key = weights.seed_key(seed)
    fm = tuple(sorted(m.items()))
    build = jax.jit(lambda k: weights.build(k, m))
    params = build(key)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    mu, nu = zeros(params), zeros(params)
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        rows = batch[:len(batch) // 2] if half_batch else batch
        grads, loss = zeros(params), jnp.zeros((), jnp.float32)
        for row in rows:
            grads, loss = _row_grad(params, grads, loss, jnp.asarray(row),
                                    m=fm, lowp=lowp)
        inv = 1.0 / len(rows)
        grads = jax.tree.map(lambda g: g * inv, grads)
        losses.append(float(loss) * inv)
        params, clipped, mu, nu = _adamw(
            params, grads, mu, nu, i, lr=opt["learning_rate"],
            wd=opt["weight_decay"], clip=opt["grad_clip"])
        if i == 0:
            first_grad = leaf_norms(clipped)
            grad_sample = jax.device_get(leaf_sample(clipped))
        del clipped, grads
    change = leaf_norms(diff(params, build(key)))
    return {"losses": losses, "grad_norms": jax.device_get(first_grad),
            "grad_sample": grad_sample,
            "change_norms": jax.device_get(change)}
