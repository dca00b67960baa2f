"""Fused-MLP kernel decomposition bench: fwd / fwd+bwd vs XLA dense.

Standalone numbers DIAGNOSE (which pass is slow, which blocks help);
only benchmarks/bench_train.py in-situ A/Bs DECIDE (the microbench-lies
rule, ROADMAP.md Design 9).

Usage: python benchmarks/bench_mlp.py [--n=16384] [--d=1024] [--f=4096]
"""

import sys

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.harness.timing import amortized_seconds
from hpc_patterns_tpu.ops.fused_mlp import fused_mlp


def arg(name, default, cast):
    for a in sys.argv[1:]:
        if a.startswith(f"--{name}="):
            return cast(a.split("=", 1)[1])
    return default


def main():
    from hpc_patterns_tpu import compile_cache

    compile_cache.enable()
    on_tpu = jax.default_backend() == "tpu"
    N = arg("n", 16384 if on_tpu else 64, int)
    D = arg("d", 1024 if on_tpu else 16, int)
    F = arg("f", 4096 if on_tpu else 32, int)
    iters = arg("iters", 32 if on_tpu else 2, int)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (N, D), dt)
    w1 = jax.random.normal(ks[1], (D, F), dt) * 0.02
    w2 = jax.random.normal(ks[2], (F, D), dt) * 0.02

    flops_fwd = 2 * 2 * N * D * F
    flops_bwd = flops_fwd + 5 * 2 * N * D * F  # fwd + 5 bwd matmuls

    def dense(x, w1, w2):
        return jnp.dot(jax.nn.gelu(jnp.dot(x, w1)), w2)

    def bench(tag, f, flops):
        def run(n):
            def body(c, _):
                return f(c, w1, w2), None
            out, _ = jax.lax.scan(body, x, None, length=n)
            # SCALAR readback: a (N, D) result is ~30 MB per forced
            # completion — the readback would drown the per-iteration
            # difference entirely
            return jnp.sum(out[0].astype(jnp.float32))

        runj = jax.jit(run, static_argnums=0)
        t = amortized_seconds(lambda n: runj(n), iters=iters,
                              repetitions=3, base_iters=iters // 2)
        tf = flops / t / 1e12 if t > 0 else float("nan")
        print(f"{tag}: {t * 1e3:.3f} ms  {tf:.1f} TF/s", flush=True)
        return t

    def grad_of(mlp):
        # ALL THREE grads consumed (argnums=0 alone would let XLA drop
        # the dW transposes from the dense leg while the pallas backward
        # computes them unconditionally — a ~40% flops-crediting bias):
        # dx carries the scan, dW folds in as a broadcast epsilon
        g = jax.grad(lambda x, w1, w2: jnp.sum(mlp(x, w1, w2) ** 2),
                     argnums=(0, 1, 2))

        def f(x, w1, w2):
            dx, dw1g, dw2g = g(x, w1, w2)
            return dx + (jnp.sum(dw1g[0]) + jnp.sum(dw2g[0])) * 1e-12
        return f

    bench("dense fwd     ", lambda x, w1, w2: dense(x, w1, w2), flops_fwd)
    bench("dense fwd+bwd ", grad_of(dense), flops_bwd)
    for bt, bf in ((512, 512), (1024, 512), (512, 1024), (1024, 1024),
                   (2048, 1024)):
        fm = lambda x, w1, w2, bt=bt, bf=bf: fused_mlp(
            x, w1, w2, block_t=bt, block_f=bf)
        try:
            bench(f"fused({bt:4d},{bf:4d}) fwd", fm, flops_fwd)
            bench(f"fused({bt:4d},{bf:4d}) f+b", grad_of(fm), flops_bwd)
        except Exception as e:
            print(f"fused({bt},{bf}): FAILED {type(e).__name__}: "
                  f"{str(e)[:160]}", flush=True)


if __name__ == "__main__":
    main()
