"""Micro-benchmark: flash attention fwd / fwd+bwd on the real chip.

Usage: python benchmarks/bench_flash.py [T ...]

Per-pass device time via the repo's differencing protocol
(harness.timing.amortized_seconds): the kernel is iterated inside ONE
dispatch with lax.fori_loop (output fed back as q so iterations chain),
then timed at two iteration counts and differenced — dispatch/readback
latency cancels.
"""

import functools
import sys

import jax
import jax.numpy as jnp
from jax import lax

from hpc_patterns_tpu.harness.timing import amortized_seconds
from hpc_patterns_tpu.ops import flash_attention
from hpc_patterns_tpu.parallel.ring_attention import full_attention


def fwd_looper(attn, q, k, v, n):
    def body(_, acc):
        out = attn(acc, k, v)
        return out.astype(acc.dtype)

    # scalar readback: the host round-trip cost must not depend on T
    return jnp.sum(lax.fori_loop(0, n, body, q).astype(jnp.float32))


def bwd_looper(attn, q, k, v, n):
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2))

    def body(_, acc):
        dq, dk, dv = grad(acc, k, v)
        # consume dk/dv or XLA dead-code-eliminates the dK/dV pass and
        # the timed "fwd+bwd" silently drops a third of the backward
        return (dq + 1e-6 * (acc + jnp.sum(dk) + jnp.sum(dv))).astype(acc.dtype)

    return jnp.sum(lax.fori_loop(0, n, body, q).astype(jnp.float32))


ITERS = 256


def per_pass(looper, attn, q, k, v, iters=None):
    iters = iters or ITERS
    jitted = jax.jit(
        functools.partial(looper, attn), static_argnums=(3,)
    )
    return amortized_seconds(
        lambda n: jitted(q, k, v, n), iters=iters, repetitions=3,
        base_iters=iters // 2,
    )


def main():
    from hpc_patterns_tpu import compile_cache

    compile_cache.enable()
    global ITERS
    for a in sys.argv[1:]:
        if a.startswith("--iters="):
            ITERS = int(a.split("=")[1])
    Ts = [int(a) for a in sys.argv[1:] if not a.startswith("-")] or [4096, 8192]
    B, H, D = 1, 8, 128
    Hkv = H
    for a in sys.argv[1:]:
        if a.startswith("--kv="):
            Hkv = int(a.split("=")[1])
    for T in Ts:
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (B, T, H, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, T, Hkv, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, T, Hkv, D), jnp.bfloat16)

        # same workload both sides: causal (full_attention defaults to
        # causal=False — leaving it off would time half the work for
        # flash and inflate the speedup ~2x)
        flash = lambda q, k, v: flash_attention(q, k, v, causal=True)
        dense = lambda q, k, v: full_attention(q, k, v, causal=True)

        tf = per_pass(fwd_looper, flash, q, k, v)
        tfg = per_pass(bwd_looper, flash, q, k, v)
        # causal fwd: 2 matmuls x 2*T^2*D MACs x 1/2 triangle
        flops_fwd = 2 * 2 * B * H * T * T * D * 0.5
        print(f"T={T:6d} flash fwd {tf*1e3:8.3f} ms "
              f"({flops_fwd/tf/1e12:5.1f} TF/s)  fwd+bwd {tfg*1e3:8.3f} ms "
              f"({3.5*flops_fwd/tfg/1e12:5.1f} TF/s)")
        if T <= 8192 and "--flash-only" not in sys.argv and Hkv == H:
            td = per_pass(fwd_looper, dense, q, k, v)
            tdg = per_pass(bwd_looper, dense, q, k, v)
            print(f"         dense fwd {td*1e3:8.3f} ms "
                  f"({flops_fwd/td/1e12:5.1f} TF/s)  fwd+bwd {tdg*1e3:8.3f} ms"
                  f"  (flash speedup fwd {td/tf:4.2f}x, fwd+bwd {tdg/tfg:4.2f}x)")


if __name__ == "__main__":
    main()
