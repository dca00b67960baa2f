"""Eval app: perplexity of a checkpoint (or fresh params) over a corpus.

Completes the model lifecycle triad (train_app → eval_app → generate):
sequential windows from a memmap token file (or synthetic fuel), the
masked causal NLL shared with training (transformer.masked_causal_nll —
eval and train loss semantics cannot drift), jitted forward only, mean
NLL → perplexity. Self-validating: NLL must be finite, and an untrained
model's perplexity must be within a factor of the uniform bound (vocab)
— the analytic-oracle idea applied to evaluation.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.apps import common
from hpc_patterns_tpu.harness import RunLog, Verdict
from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness.cli import base_parser
from hpc_patterns_tpu.models import TransformerConfig, init_params
from hpc_patterns_tpu.models.transformer import loss_fn


def build_parser():
    p = base_parser(__doc__.splitlines()[0])
    p.add_argument("--data", default=None, metavar="TOKENS.bin",
                   help="raw binary token file (sequential windows); "
                        "default: synthetic fuel")
    p.add_argument("--data-dtype", default="uint16",
                   choices=["uint16", "uint32", "int32"])
    p.add_argument("--checkpoint-dir", default=None,
                   help="restore params saved by train_app "
                        "--checkpoint-dir; default: fresh init")
    p.add_argument("--batches", type=int, default=8)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-kv-heads", type=int, default=0)
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("--attention", default="full")
    p.add_argument("--pos-embed", default="learned",
                   choices=["learned", "rope"])
    p.add_argument("--loss-chunk", type=int, default=0, metavar="C",
                   help="online-logsumexp NLL over vocab chunks of C "
                        "(must divide --vocab): the (B,T,V) f32 logits "
                        "never materialize — evaluate long sequences at "
                        "full vocabulary (0 = dense)")
    return p


def run(args) -> int:
    log = RunLog(args.log, truncate=not args.log_append)
    if common.refuse_backend(args, log):
        return 1
    try:
        cfg = TransformerConfig(
            vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
            n_layers=args.n_layers, d_ff=4 * args.d_model, max_seq=args.seq,
            attention=args.attention, n_kv_heads=args.n_kv_heads,
            pos_embed=args.pos_embed, loss_chunk=args.loss_chunk,
        )
    except ValueError as e:
        log.print(f"ERROR: {e}")
        log.print("FAILURE")
        return 1
    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.checkpoint_dir:
        from hpc_patterns_tpu.utils.checkpoint import restore_params

        try:
            restored, step = restore_params(args.checkpoint_dir)
        except (FileNotFoundError, ValueError, KeyError) as e:
            log.print(f"ERROR: cannot restore {args.checkpoint_dir}: {e}")
            log.print("FAILURE")
            return 1
        want = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
        got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), restored)
        if want != got:
            log.print("ERROR: checkpoint shapes/dtypes do not match the "
                      "CLI model config (wrong --d-model/--n-layers/"
                      "--vocab/--pos-embed?)")
            log.print("FAILURE")
            return 1
        params = restored
        log.print(f"restored step {step} from {args.checkpoint_dir}")

    if args.data:
        from hpc_patterns_tpu.utils.data import memmap_tokens

        source = memmap_tokens(args.data, batch=args.batch, seq=args.seq,
                               dtype=args.data_dtype, steps=args.batches,
                               sequential=True, vocab=cfg.vocab)
    else:
        from hpc_patterns_tpu.utils.data import synthetic_tokens

        source = synthetic_tokens(jax.random.PRNGKey(1), batch=args.batch,
                                  seq=args.seq, vocab=cfg.vocab,
                                  steps=args.batches)

    # loss_fn owns the dense-vs-chunked branch (cfg.loss_chunk), so eval
    # and train NLL semantics cannot drift; no experts here, so the MoE
    # aux term loss_fn would add is identically zero
    nll_fn = jax.jit(lambda p, t: loss_fn(p, t, cfg))
    m = metricslib.get_metrics()
    nlls = []
    for b in source:
        with m.span("eval.batch"):
            # float() blocks on the device, closing the span honestly
            nlls.append(float(nll_fn(params, jnp.asarray(b))))
    mean_nll = sum(nlls) / len(nlls)
    ppl = math.exp(mean_nll)
    m.gauge("eval.mean_nll").set(mean_nll)
    m.gauge("eval.perplexity").set(ppl)

    finite = all(math.isfinite(x) for x in nlls)
    if args.checkpoint_dir is None:
        # untrained params ~ uniform predictor: ppl near vocab, both
        # bounds checked (an impossibly low fresh-init ppl means a
        # masking/leakage bug, not a good model)
        sane = cfg.vocab / 20 <= ppl <= 20 * cfg.vocab
    else:
        # a real checkpoint must beat (or at worst match) uniform
        sane = 1.0 < ppl <= 20 * cfg.vocab
    ok = finite and sane
    log.emit(kind="result", name="eval", success=ok, batches=len(nlls),
             mean_nll=mean_nll, perplexity=ppl, vocab=cfg.vocab)
    log.print(f"eval {len(nlls)} batches: nll {mean_nll:.4f}, "
              f"perplexity {ppl:.1f} (vocab {cfg.vocab})")
    verdict = Verdict(success=ok, messages=("SUCCESS" if ok else "FAILURE",))
    log.print(verdict.summary_line())
    return verdict.exit_code


def main(argv=None) -> int:
    return common.run_instrumented(run, build_parser().parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
