"""Decode token-step benchmark: flash-decode kernel vs the XLA gather
path, at a controlled cache length.

Usage: python benchmarks/bench_decode.py [--prompt=N] [--kv=N]

Protocol: the cache is built once (flash-mode prefill — the gather
path's dense prefill cannot even run an 8k prompt), then each impl's
``decode_step`` is iterated inside ONE dispatch with ``lax.fori_loop``
(greedy token fed back, position advancing, cache updated in place) and
timed with the repo's differencing protocol
(harness.timing.amortized_seconds) — dispatch/readback latency cancels,
leaving pure per-token device time. The prompt length sets the live
cache prefix: the flash kernel's HBM traffic scales with it; the
gather path's with the allocated max_len.
"""

import functools
import sys

import jax
import jax.numpy as jnp
from jax import lax

from hpc_patterns_tpu.harness.timing import amortized_seconds
from hpc_patterns_tpu.models import TransformerConfig
from hpc_patterns_tpu.models.decode import decode_step, prefill
from hpc_patterns_tpu.models.transformer import init_params


def arg(name, default, cast=int):
    for a in sys.argv[1:]:
        if a.startswith(f"--{name}="):
            return cast(a.split("=", 1)[1])
    return default


def main():
    from hpc_patterns_tpu import compile_cache

    compile_cache.enable()
    on_tpu = jax.default_backend() == "tpu"
    prompt_len = arg("prompt", 8064 if on_tpu else 96)
    slack = arg("slack", 512 if on_tpu else 32)  # decode room in cache
    batch = arg("batch", 8 if on_tpu else 2)
    iters = arg("iters", 128 if on_tpu else 8)
    base = dict(
        vocab=arg("vocab", 32768 if on_tpu else 256),
        d_model=arg("d", 1024 if on_tpu else 64),
        n_heads=arg("heads", 8 if on_tpu else 4),
        n_layers=arg("layers", 8 if on_tpu else 2),
        d_ff=arg("ff", 4096 if on_tpu else 128),
        max_seq=prompt_len + slack,
        dtype="bfloat16" if on_tpu else "float32",
        n_kv_heads=arg("kv", 0),
        kv_cache_dtype=arg("cache", "compute", str),
    )
    impls = [a.split("=", 1)[1] for a in sys.argv[1:]
             if a.startswith("--impl=")] or ["flash", "gather"]

    cfg0 = TransformerConfig(**base, decode_attn="flash")
    params = init_params(jax.random.PRNGKey(0), cfg0)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg0.vocab, "int32"
    )
    logits, cache = jax.jit(
        lambda p, t: prefill(p, t, cfg0, prompt_len + slack)
    )(params, prompt)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    jax.block_until_ready(cache)

    t_step = {}
    for impl in impls:
        if impl == "paged":
            # block-table cache: the pool allocates prompt+slack pages,
            # NOT the declared maximum — the capacity row runs where the
            # equivalent linear allocation would not fit
            from hpc_patterns_tpu.models.decode import (
                init_paged_cache,
                paged_decode_step,
                paged_prefill,
            )

            page = arg("page", 512 if on_tpu else 16)
            # pages fetched per kernel grid step (0 = the kernel's
            # auto: match the linear 2048-row block). --ppstep=1 is
            # the round-4 one-page-per-step form for the gap sweep
            ppstep = arg("ppstep", 0) or None
            pages = -(-(prompt_len + slack) // page)
            pcache = init_paged_cache(cfg0, batch, pages, page)
            _, pcache = jax.jit(
                lambda p, t, c: paged_prefill(p, t, cfg0, c, page)
            )(params, prompt, pcache)
            jax.block_until_ready(pcache)

            @functools.partial(jax.jit, static_argnums=(3,))
            def run_paged(params, cache, tok, n):
                def body(_, carry):
                    cache, pos, tok = carry
                    logits, cache = paged_decode_step(
                        params, cache, pos, tok, cfg0,
                        identity_layout=True, pages_per_step=ppstep,
                    )
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return cache, pos + 1, nxt

                _, _, tok = lax.fori_loop(
                    0, n, body, (cache, jnp.int32(prompt_len), tok)
                )
                return tok

            t = amortized_seconds(
                lambda n: run_paged(params, pcache, first, n),
                iters=iters, repetitions=3, base_iters=iters // 2,
            )
            t_step[impl] = t
            pool_tok = pages * page
            print(f"impl=paged   pool={batch}x{pool_tok} (page {page}, "
                  f"ppstep {ppstep or 'auto'}) "
                  f"B={batch} kv={cfg0.kv_heads}: {t * 1e3:6.3f} "
                  f"ms/token-step ({batch / t:,.0f} tok/s)")
            continue
        cfg = TransformerConfig(**base, decode_attn=impl)

        @functools.partial(jax.jit, static_argnums=(3,))
        def run_n(params, cache, tok, n):
            def body(_, carry):
                cache, pos, tok = carry
                logits, cache = decode_step(params, cache, pos, tok, cfg)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return cache, pos + 1, nxt
            # position resets each call so the streamed prefix length is
            # constant across iteration counts (the differencing needs
            # per-step cost to be stationary)
            _, _, tok = lax.fori_loop(
                0, n, body, (cache, jnp.int32(prompt_len), tok)
            )
            return tok

        t = amortized_seconds(
            lambda n: run_n(params, cache, first, n),
            iters=iters, repetitions=3, base_iters=iters // 2,
        )
        t_step[impl] = t
        print(f"impl={impl:7s} cache={prompt_len} B={batch} "
              f"kv={cfg.kv_heads}: {t * 1e3:6.3f} ms/token-step "
              f"({batch / t:,.0f} tok/s)")
    if len(t_step) == 2:
        a, b = impls
        print(f"speedup {b}->{a}: {t_step[b] / t_step[a]:.2f}x")


if __name__ == "__main__":
    main()
