"""Speculative decoding gamma sweep vs plain decode, greedy and
sampling verify, on the real chip.

Protocol: per-token time by generation differencing — each
configuration generates N and N/2 tokens in ONE jitted call each
(prefill + the whole decode/verify loop live inside), both
completion-forced; the difference divided by N/2 cancels prefill,
compile, and dispatch/readback latency. Single-token steps at small
widths are launch-bound; min-of-reps and adjacent measurement are the
mitigations.

Usage: python benchmarks/bench_speculative.py [--n=256] [--temp=0.8]
                                              [--pair=DIR]

``--pair``: load an ALIGNED draft/target pair built by
benchmarks/make_draft_pair.py instead of independent random weights —
the honest envelope (random weights inflate greedy acceptance via
repetition loops and deflate sampling acceptance via independence).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from hpc_patterns_tpu.harness.timing import measure_forced
from hpc_patterns_tpu.models import TransformerConfig
from hpc_patterns_tpu.models.decode import generate
from hpc_patterns_tpu.models.speculative import speculative_generate
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
    n = arg("n", 256 if on_tpu else 16)
    temp = arg("temp", 0.8, float)
    top_k = arg("topk", 40)
    base = dict(
        vocab=32768 if on_tpu else 256,
        d_model=1024 if on_tpu else 64,
        n_heads=8 if on_tpu else 4,
        n_layers=8 if on_tpu else 2,
        d_ff=4096 if on_tpu else 128,
        dtype="bfloat16" if on_tpu else "float32",
        n_kv_heads=2 if on_tpu else 0,
        pos_embed="rope",
    )
    gammas = (2, 4, 8)
    max_len = 128 + n + max(gammas) + 1
    pair = arg("pair", "", str)
    if pair:
        from hpc_patterns_tpu.utils.checkpoint import restore_params

        with open(os.path.join(pair, "META.json")) as f:
            meta = json.load(f)
        cfg = TransformerConfig(**{**meta["target_cfg"],
                                   "max_seq": max_len})
        dcfg = TransformerConfig(**{**meta["draft_cfg"],
                                    "max_seq": max_len})
        params, _ = restore_params(os.path.join(pair, "target"))
        dparams, _ = restore_params(os.path.join(pair, "draft"))
        acc = meta.get("acceptance", {})
        print(f"aligned pair from {pair}: greedy-agree "
              f"{acc.get('aligned_greedy', float('nan')):.3f} "
              f"E[min(p,q)] {acc.get('aligned_minpq', float('nan')):.3f} "
              f"(random baseline {acc.get('random_greedy', float('nan')):.3f}"
              f"/{acc.get('random_minpq', float('nan')):.3f})",
              flush=True)
        # prompt drawn from the SAME markov process the pair was
        # trained on (seed=0 transition table) via a DISJOINT sample
        # path — acceptance on-distribution without train-set reuse
        from make_draft_pair import markov_corpus

        corpus = markov_corpus(cfg.vocab, 8192, draw_seed=777)
        prompt = jax.numpy.asarray(corpus[:128], "int32")[None, :]
    else:
        cfg = TransformerConfig(**base, max_seq=max_len)
        dcfg = TransformerConfig(**{
            **base,
            "d_model": 256 if on_tpu else 32,
            "n_layers": 2 if on_tpu else 1,
            "d_ff": 1024 if on_tpu else 64,
            "n_heads": 4 if on_tpu else 2,
            "n_kv_heads": 2 if on_tpu else 0,
        }, max_seq=max_len)
        params = init_params(jax.random.PRNGKey(0), cfg)
        dparams = init_params(jax.random.PRNGKey(1), dcfg)
        prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 128), 0,
                                    cfg.vocab, "int32")
    key = jax.random.PRNGKey(3)

    def per_token(fn):
        t_full = measure_forced(lambda: fn(n), repetitions=3).min_s
        t_half = measure_forced(lambda: fn(n // 2), repetitions=3).min_s
        return max(t_full - t_half, 0.0) / (n - n // 2)

    for label, kwargs in (("greedy", {}),
                          (f"temp={temp}/top{top_k}",
                           {"key": key, "temperature": temp,
                            "top_k": top_k})):
        t_plain = per_token(
            lambda m: generate(params, prompt, cfg, m, **kwargs)
        )
        print(f"plain {label}: {t_plain * 1e3:.3f} ms/token", flush=True)
        for gamma in gammas:
            t = per_token(
                lambda m: speculative_generate(
                    params, cfg, dparams, dcfg, prompt, m, gamma=gamma,
                    **kwargs)
            )
            print(f"spec  {label} gamma={gamma}: {t * 1e3:.3f} ms/token "
                  f"({t_plain / t:.2f}x)", flush=True)

    # --batched=B: the per-row-progress ragged impl vs the vmap-lifted
    # per-row loops, greedy, same heterogeneous batch (the measured
    # wall-clock note verdict item 7 asks for)
    bsz = arg("batched", 0)
    if bsz:
        from hpc_patterns_tpu.models.speculative import (
            speculative_generate_batched,
        )

        if pair:
            # heterogeneous on-distribution rows: per-row acceptance
            # varies, which is exactly what per-row progress is for
            import numpy as _np

            corpus = markov_corpus(cfg.vocab, 8192 + bsz * 512,
                                   draw_seed=778)
            prompts = jax.numpy.asarray(_np.stack(
                [corpus[i * 512:i * 512 + 128] for i in range(bsz)]),
                "int32")
        else:
            prompts = jax.random.randint(jax.random.PRNGKey(4),
                                         (bsz, 128), 0, cfg.vocab,
                                         "int32")
        for impl in ("ragged", "vmap"):
            t = per_token(lambda m: speculative_generate_batched(
                params, cfg, dparams, dcfg, prompts, m, gamma=4,
                impl=impl))
            print(f"spec batched[{impl}] B={bsz} gamma=4: "
                  f"{t * 1e3:.3f} ms/batch-token "
                  f"({bsz / t / 1e3:.2f}k tok/s)", flush=True)


if __name__ == "__main__":
    main()
