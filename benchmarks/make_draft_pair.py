"""Build an ALIGNED draft/target pair for honest speculative numbers.

Round 4's speculative envelope was measured on independent random
weights — greedy acceptance inflated by degenerate repetition loops,
sampling acceptance deflated by model independence (the builder's own
caveat). This script produces the real thing:

1. generate a LEARNABLE corpus (order-1 Markov chain with a sparse,
   seeded transition table — uniform-random tokens would leave nothing
   for either model to agree about);
2. train the target on it briefly (models/train.make_train_step);
3. make the draft by LAYER TRUNCATION of the trained target (first
   draft_layers layers + the target's own embed/norm/head — the
   classic self-draft recipe) and DISTILL it: KL(target || draft) on
   corpus windows, target frozen;
4. save both checkpoints (+ META.json) for bench_speculative --pair=;
5. report the analytic acceptance diagnostics on held-out windows —
   greedy top-1 agreement and E[sum min(p_draft, p_target)] (the
   Leviathan expected acceptance under sampling) — for the aligned
   pair AND the round-4 random-draft baseline, so the table shows
   exactly what alignment buys.

Usage:
  python benchmarks/make_draft_pair.py --out=pair_dir
      [--steps=400] [--distill-steps=400] [--draft-layers=2]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp
import optax

from hpc_patterns_tpu.models import TransformerConfig, forward
from hpc_patterns_tpu.models.train import (
    init_train_state,
    make_optimizer,
    make_train_step,
)
from hpc_patterns_tpu.models.transformer import init_params
from hpc_patterns_tpu.utils.checkpoint import save_checkpoint


def arg(name, default, cast=int):
    for a in sys.argv[1:]:
        if a.startswith(f"--{name}="):
            return cast(a.split("=", 1)[1])
    return default


def markov_corpus(vocab: int, n_tokens: int, seed: int = 0,
                  branching: int = 8, draw_seed: int | None = None):
    """Order-1 Markov stream: every token has ``branching`` plausible
    successors (Zipf-ish weights). Learnable structure with entropy low
    enough that a small draft can agree with a bigger target.

    ``seed`` fixes the TRANSITION TABLE (the process); ``draw_seed``
    (default: seed) fixes the sample path — held-out data and
    benchmark prompts must come from the SAME process as training
    (same seed) but a DISJOINT path (different draw_seed), or the
    acceptance numbers are train-set figures / off-distribution."""
    rng = np.random.RandomState(seed)
    succ = rng.randint(0, vocab, size=(vocab, branching))
    w = 1.0 / np.arange(1, branching + 1)
    w /= w.sum()
    draw_rng = np.random.RandomState(
        seed if draw_seed is None else draw_seed)
    out = np.empty(n_tokens, np.int32)
    tok = draw_rng.randint(vocab)
    draws = draw_rng.choice(branching, size=n_tokens, p=w)
    for i in range(n_tokens):
        tok = succ[tok, draws[i]]
        out[i] = tok
    return out


def windows(corpus, batch, seq, rng):
    starts = rng.randint(0, len(corpus) - seq - 1, size=batch)
    return jnp.asarray(
        np.stack([corpus[s:s + seq] for s in starts]), jnp.int32)


def truncate_draft(params, cfg: TransformerConfig,
                   dcfg: TransformerConfig):
    """Draft = the target's first dcfg.n_layers layers + its embed/
    final-norm/head, verbatim (same widths — only depth shrinks)."""
    sliced = jax.tree.map(lambda a: a[:dcfg.n_layers], params["layers"])
    draft = dict(params)
    draft["layers"] = sliced
    return jax.tree.map(jnp.array, draft)


def acceptance_stats(params, cfg, dparams, dcfg, corpus, rng, *,
                     batch=8, seq=128, temp=0.8):
    """Held-out diagnostics: greedy top-1 agreement rate and the
    Leviathan expected sampling acceptance E[sum_v min(p, q)] (both
    models' next-token distributions on the same real-context rows)."""
    toks = windows(corpus, batch, seq, rng)
    lt = forward(params, toks, cfg)[:, :-1].astype(jnp.float32)
    ld = forward(dparams, toks, dcfg)[:, :-1].astype(jnp.float32)
    greedy = float(jnp.mean(jnp.argmax(lt, -1) == jnp.argmax(ld, -1)))
    p = jax.nn.softmax(lt / temp, -1)
    q = jax.nn.softmax(ld / temp, -1)
    accept = float(jnp.mean(jnp.sum(jnp.minimum(p, q), -1)))
    return greedy, accept


def main():
    from hpc_patterns_tpu import compile_cache

    compile_cache.enable()
    on_tpu = jax.default_backend() == "tpu"
    out = arg("out", "draft_pair", str)
    steps = arg("steps", 400 if on_tpu else 30)
    dsteps = arg("distill-steps", 400 if on_tpu else 30)
    batch = arg("batch", 16 if on_tpu else 4)
    seq = arg("seq", 256 if on_tpu else 32)
    n_corpus = arg("corpus", 2_000_000 if on_tpu else 60_000)
    base = dict(
        vocab=arg("vocab", 32768 if on_tpu else 256),
        d_model=arg("d", 1024 if on_tpu else 64),
        n_heads=8 if on_tpu else 4,
        d_ff=arg("ff", 4096 if on_tpu else 128),
        dtype="bfloat16" if on_tpu else "float32",
        n_kv_heads=2 if on_tpu else 0,
        pos_embed="rope",
        max_seq=arg("max-seq", 2048 if on_tpu else 256),
    )
    cfg = TransformerConfig(**base, n_layers=arg("layers", 8 if on_tpu
                                                 else 2))
    dcfg = TransformerConfig(**base, n_layers=arg(
        "draft-layers", 2 if on_tpu else 1))

    print(f"corpus: order-1 markov, {n_corpus} tokens", flush=True)
    corpus = markov_corpus(cfg.vocab, n_corpus)
    rng = np.random.RandomState(1)

    # --- 1. train the target
    params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg)
    step = make_train_step(cfg)
    t0 = time.time()
    for i in range(steps):
        loss, params, opt_state = step(params, opt_state,
                                       windows(corpus, batch, seq, rng))
        if i % max(1, steps // 5) == 0 or i == steps - 1:
            print(f"target step {i}: loss {float(loss):.4f}", flush=True)
    print(f"target trained: {time.time() - t0:.1f}s", flush=True)

    # --- 2. draft by truncation + distillation (target frozen)
    draft = truncate_draft(params, cfg, dcfg)
    opt = make_optimizer(1e-3)
    dopt = opt.init(draft)

    @jax.jit
    def distill_step(draft, dopt, toks):
        tlog = forward(params, toks, cfg).astype(jnp.float32)
        tprob = jax.nn.softmax(tlog, -1)

        def loss_fn(dp):
            dlog = forward(dp, toks, dcfg).astype(jnp.float32)
            return -jnp.mean(
                jnp.sum(tprob * jax.nn.log_softmax(dlog, -1), -1))

        loss, g = jax.value_and_grad(loss_fn)(draft)
        upd, dopt = opt.update(g, dopt, draft)
        return loss, optax.apply_updates(draft, upd), dopt

    t0 = time.time()
    for i in range(dsteps):
        dl, draft, dopt = distill_step(draft, dopt,
                                       windows(corpus, batch, seq, rng))
        if i % max(1, dsteps // 5) == 0 or i == dsteps - 1:
            print(f"distill step {i}: CE {float(dl):.4f}", flush=True)
    print(f"draft distilled: {time.time() - t0:.1f}s", flush=True)

    # --- 3. diagnostics: aligned pair vs the round-4 random baseline,
    # on a genuinely held-out path — SAME transition table (the
    # process both models learned), DISJOINT sample path (draw_seed)
    held_corpus = markov_corpus(cfg.vocab, 50_000, draw_seed=31337)
    held = np.random.RandomState(99)
    g_a, a_a = acceptance_stats(params, cfg, draft, dcfg, held_corpus,
                                held)
    rand_draft = init_params(jax.random.PRNGKey(7), dcfg)
    g_r, a_r = acceptance_stats(params, cfg, rand_draft, dcfg,
                                held_corpus, held)
    print(f"acceptance (held-out): aligned greedy-agree {g_a:.3f} "
          f"E[min(p,q)] {a_a:.3f} | random-draft greedy-agree "
          f"{g_r:.3f} E[min(p,q)] {a_r:.3f}", flush=True)

    # --- 4. save the pair
    os.makedirs(out, exist_ok=True)
    save_checkpoint(os.path.join(out, "target"), params, opt_state)
    save_checkpoint(os.path.join(out, "draft"), draft, dopt)
    meta = {
        "target_cfg": {**base, "n_layers": cfg.n_layers},
        "draft_cfg": {**base, "n_layers": dcfg.n_layers},
        "steps": steps, "distill_steps": dsteps,
        "acceptance": {"aligned_greedy": g_a, "aligned_minpq": a_a,
                       "random_greedy": g_r, "random_minpq": a_r},
        "corpus": {"kind": "markov1", "tokens": n_corpus},
    }
    with open(os.path.join(out, "META.json"), "w") as f:
        json.dump(meta, f, indent=1)
    print(f"pair saved to {out}/ (META.json has the diagnostics)")


if __name__ == "__main__":
    main()
