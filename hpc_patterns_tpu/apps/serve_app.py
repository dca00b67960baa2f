"""Serve app: continuous batching over the paged KV cache, validated.

Completes the lifecycle triad's serving leg as a CLI: a stream of
requests with varied prompt lengths (``--prompt-mix``) and budgets
served through models/serving.ContinuousBatcher (page free-list,
bucketed admission, overlapped prefill, per-row sampling), then EVERY
sequence validated token-exact against its standalone
``paged_generate`` — greedy AND sampled (per-request key streams keep
sampled serving standalone-exact); draft-assisted sampling is the one
law-only combination (its distribution oracle lives in
tests/test_serving.py). On the TPU, where bf16 matmuls round
differently at the engine's batch geometry than at B=1, a greedy
stream that is not token-exact is judged by the teacher-forced
precision law against the float32 reference instead
(models/quantization.emitted_stream_law), and the result says which
oracle held. The reference's benchmark-IS-the-test
discipline (SURVEY.md §4: the binary measures its own claim and exits
SUCCESS/FAILURE). Reports tokens/s, the admission-bubble fraction,
and the prefill compile count (bounded by the bucket ladder); with
``--static-compare``, the static-batching baseline wall clock.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.apps import common
from hpc_patterns_tpu.harness import RunLog, Verdict
from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness.cli import (
    add_autofit_arg,
    add_explain_args,
    add_kv_dtype_arg,
    add_serving_args,
    base_parser,
    explain_enabled,
    load_autofit,
    parse_buckets,
    resolve_kv_cache_dtype,
)
from hpc_patterns_tpu.harness import explain as explainlib
from hpc_patterns_tpu.harness import reqtrace as reqtracelib
from hpc_patterns_tpu.models import TransformerConfig, init_params


def build_parser():
    p = base_parser(__doc__.splitlines()[0])
    add_serving_args(p)
    add_autofit_arg(p)
    add_explain_args(p)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=2,
                   help="concurrent rows in the pool")
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--chunk", type=int, default=4,
                   help="decode steps per jitted dispatch (admission "
                        "granularity)")
    p.add_argument("--prompt-len", type=int, default=12)
    p.add_argument("--prompt-mix", action="store_true",
                   help="vary prompt lengths 1/2..1x of --prompt-len "
                        "(the mixed-length stream the bucket ladder "
                        "exists for)")
    p.add_argument("--budget", type=int, default=12,
                   help="max new tokens per request (actual budgets "
                        "vary 1/4..1x)")
    p.add_argument("--pool-pages", type=int, default=0,
                   help="shared arena size (0 = slots * pages needed "
                        "for prompt+budget)")
    p.add_argument("--eos-id", type=int, default=-1,
                   help=">= 0: end rows early at this token")
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--n-kv-heads", type=int, default=0)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--pos-embed", default="learned",
                   choices=["learned", "rope", "none"])
    # the shared serving-precision knob; bf16 = the config's default
    # compute dtype with a scale-free cache (the pre-knob behavior)
    add_kv_dtype_arg(p, default="bf16")
    p.add_argument("--checkpoint-dir", default=None,
                   help="serve a trained checkpoint (train_app "
                        "--checkpoint-dir); default: fresh init")
    p.add_argument("--draft-pair", default=None, metavar="DIR",
                   help="serve an aligned draft/target pair "
                        "(benchmarks/make_draft_pair.py): speculative "
                        "rounds inside the engine — rows advance "
                        "1..gamma+1 tokens per dispatch (overrides the "
                        "model-dim flags with the pair's configs)")
    p.add_argument("--gamma", type=int, default=4,
                   help="draft proposals per round with --draft-pair")
    p.add_argument("--static-compare", action="store_true",
                   help="also time static batching (batches of "
                        "--slots padded to the batch max budget)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="enable the flight recorder (implies --trace) "
                        "and write the Chrome-trace JSON timeline here "
                        "at exit — one flag from serving run to "
                        "Perfetto-loadable timeline")
    return p


def run(args) -> int:
    log = RunLog(args.log, truncate=not args.log_append)
    if common.refuse_backend(args, log):
        return 1
    from hpc_patterns_tpu.models.decode import paged_generate
    from hpc_patterns_tpu.models.serving import ContinuousBatcher

    need = args.prompt_len + args.budget
    try:
        buckets = parse_buckets(args.prompt_buckets, args.prompt_len)
        if args.autofit is not None:
            # the fitted ladder replaces the default 'auto' ladder;
            # an explicit --prompt-buckets value still wins
            from hpc_patterns_tpu.harness import autofit as autofitlib

            fitted = load_autofit(args.autofit)
            fitted_buckets = autofitlib.ladder_from(
                fitted, max_seq=args.prompt_len)
            if (args.prompt_buckets.strip().lower() == "auto"
                    and fitted_buckets is not None):
                buckets = fitted_buckets
                log.print(f"autofit ladder from {args.autofit}: "
                          f"{list(buckets)}")
    except (OSError, ValueError, argparse.ArgumentTypeError) as e:
        log.print(f"ERROR: {e}")
        log.print("FAILURE")
        return 1
    draft_params = draft_cfg = None
    if args.draft_pair and args.checkpoint_dir:
        log.print("ERROR: --draft-pair serves the pair's own target "
                  "checkpoint; --checkpoint-dir would be silently "
                  "ignored — pass one or the other")
        log.print("FAILURE")
        return 1
    if args.draft_pair and args.kv_dtype != "bf16":
        log.print("ERROR: --draft-pair serves from the pair's own "
                  "compute-dtype caches (META.json configs); "
                  f"--kv-dtype {args.kv_dtype} would be silently "
                  "ignored — drop it or serve without the pair")
        log.print("FAILURE")
        return 1
    # off-TPU serving takes the pure-XLA gather route on BOTH branches
    # (the pallas kernels interpret per grid point there); the result
    # record names the route that ran
    on_tpu = jax.devices()[0].platform == "tpu"
    attn = "flash" if on_tpu else "gather"
    try:
        if args.draft_pair:
            import json
            import os

            from hpc_patterns_tpu.utils.checkpoint import restore_params

            with open(os.path.join(args.draft_pair, "META.json")) as f:
                meta = json.load(f)
            cfg = TransformerConfig(**{**meta["target_cfg"],
                                       "max_seq": need,
                                       "decode_attn": attn})
            draft_cfg = TransformerConfig(**{**meta["draft_cfg"],
                                             "max_seq": need,
                                             "decode_attn": attn})
            params, _ = restore_params(
                os.path.join(args.draft_pair, "target"))
            draft_params, _ = restore_params(
                os.path.join(args.draft_pair, "draft"))
            log.print(f"aligned pair from {args.draft_pair} "
                      f"(gamma={args.gamma})")
        else:
            compute_dt, kv_dt = resolve_kv_cache_dtype(
                args.kv_dtype, note=log.print)
            cfg = TransformerConfig(
                vocab=args.vocab, d_model=args.d_model,
                n_heads=args.n_heads, n_layers=args.n_layers,
                d_ff=4 * args.d_model, max_seq=need,
                n_kv_heads=args.n_kv_heads, pos_embed=args.pos_embed,
                kv_cache_dtype=kv_dt,
                **({"dtype": compute_dt} if compute_dt else {}),
                decode_attn=attn,
            )
    except (ValueError, FileNotFoundError, KeyError) as e:
        log.print(f"ERROR: {e}")
        log.print("FAILURE")
        return 1
    if args.requests < 1 or args.slots < 1 or args.budget < 1:
        log.print("ERROR: --requests/--slots/--budget must be >= 1")
        log.print("FAILURE")
        return 1
    if not args.draft_pair:
        params = init_params(jax.random.PRNGKey(0), cfg)
        if args.checkpoint_dir:
            from hpc_patterns_tpu.utils.checkpoint import restore_params

            try:
                params, step = restore_params(args.checkpoint_dir)
                log.print(
                    f"restored step {step} from {args.checkpoint_dir}")
            except (FileNotFoundError, ValueError, KeyError) as e:
                log.print(f"ERROR: cannot restore "
                          f"{args.checkpoint_dir}: {e}")
                log.print("FAILURE")
                return 1

    # the engine owns the sizing rule (incl. speculative slack and the
    # bucket-padded prefill length — the pool must hold the padded
    # prompt even when the budget alone would need fewer pages)
    from hpc_patterns_tpu.models.serving import pad_to_bucket

    try:
        padded_max = pad_to_bucket(buckets, args.prompt_len)
    except ValueError as e:
        log.print(f"ERROR: {e}")
        log.print("FAILURE")
        return 1
    pages_per_seq = ContinuousBatcher.pages_needed(
        args.prompt_len, args.budget, args.page_size,
        gamma=args.gamma if draft_params is not None else None,
        padded_len=padded_max)
    pool_pages = args.pool_pages or args.slots * pages_per_seq
    rng = np.random.RandomState(7)
    reqs = []
    for _ in range(args.requests):
        plen = (int(rng.randint(max(1, args.prompt_len // 2),
                                args.prompt_len + 1))
                if args.prompt_mix else args.prompt_len)
        prompt = rng.randint(0, cfg.vocab, size=plen).astype(np.int32)
        budget = int(rng.choice([max(1, args.budget // 4),
                                 max(1, args.budget // 2), args.budget]))
        reqs.append((prompt, budget))
    total_budget = sum(b for _, b in reqs)
    sampled = args.temperature > 0.0
    spec = draft_params is not None

    def serve():
        # constructor/submit ValueErrors (bad gamma, vocab mismatch,
        # oversize request) keep the clean ERROR/FAILURE contract too,
        # not just run()'s RuntimeError
        try:
            eng = ContinuousBatcher(
                params, cfg, slots=args.slots, pool_pages=pool_pages,
                pages_per_seq=pages_per_seq, page_size=args.page_size,
                chunk=args.chunk,
                eos_id=args.eos_id if args.eos_id >= 0 else None,
                draft_params=draft_params, draft_cfg=draft_cfg,
                gamma=args.gamma, emit=log.emit,
                prompt_buckets=buckets, overlap=not args.no_overlap,
                temperature=args.temperature, top_k=args.top_k,
                seed=args.seed,
            )
            ids = [eng.submit(p, b) for p, b in reqs]
            got = eng.run()
        except (ValueError, RuntimeError) as e:
            return None, None, str(e)
        return {i: got[sid] for i, sid in enumerate(ids)}, eng, None

    # warmup (compiles) — keep its records out of the registry: its
    # TTFT would be compile-dominated and its counters would double
    # every request (the warmup-vs-timed discipline of harness.timing)
    from hpc_patterns_tpu.models.serving import prefill_cache_size

    m = metricslib.get_metrics()
    prev_enabled = m.enabled
    m.enabled = False
    compiles0 = prefill_cache_size()  # other engines, this process
    try:
        out, _, err = serve()
    finally:
        m.enabled = prev_enabled
    if err is not None:
        log.print(f"ERROR: {err}")
        log.print("FAILURE")
        return 1
    # THIS engine's admission-prefill compiles (cold); the measured
    # run below must add none (warm)
    compiles_cold = prefill_cache_size() - compiles0
    compiles_before = prefill_cache_size()
    if explain_enabled(args):
        # fresh recorder for the MEASURED run only: the warm-up run
        # above reused the same seq ids, and one recorder is one id
        # space (the bench-leg reconfigure discipline)
        reqtracelib.configure(enabled=True)
    t0 = time.perf_counter()
    with metricslib.span("serve.measure"):
        out, eng, _ = serve()
    dt = time.perf_counter() - t0
    served = sum(len(v) for v in out.values())
    bubble = eng.last_bubble_frac
    compiles_warm = prefill_cache_size() - compiles_before
    metricslib.get_metrics().gauge("serve.tokens_per_s").set(served / dt)

    # the oracle: every sequence token-exact vs standalone paged decode
    # with the SAME per-request key/temperature (truncated at eos when
    # enabled — same rule the engine applies). Draft-assisted sampling
    # is the one law-only combination (the rejection-sampling rounds
    # preserve the emitted law, not the draws — its distribution
    # oracle lives in tests/test_serving.py); it gets a bounds check.
    exact = True
    for i, (prompt, budget) in enumerate(reqs):
        if sampled and spec:
            ok_i = (1 <= len(out[i]) <= budget
                    and np.all(out[i] >= 0)
                    and np.all(out[i] < cfg.vocab))
            if not ok_i:
                exact = False
                log.print(f"OUT-OF-BOUNDS seq {i}: {out[i][:8]}...")
            continue
        want = np.asarray(paged_generate(
            params, jnp.asarray(prompt)[None, :], cfg, budget,
            page_size=args.page_size,
            key=eng.request_key(i) if sampled else None,
            temperature=args.temperature, top_k=args.top_k))[0]
        if args.eos_id >= 0 and np.any(want == args.eos_id):
            want = want[:int(np.argmax(want == args.eos_id)) + 1]
        if not np.array_equal(out[i], want):
            exact = False
            log.print(f"MISMATCH seq {i}: engine {out[i][:8]}... vs "
                      f"standalone {want[:8]}...")
    # bound: cold compiles ≤ ladder rungs (x2 with a draft pair — the
    # draft prefill compiles per rung under its own config), and the
    # warm measured run adds none
    law = None
    if not exact and on_tpu and not sampled:
        # bf16 on the MXU is not batch-geometry invariant: the 8-slot
        # ragged engine and the B=1 standalone decode flip near-tie
        # argmaxes against each other, so equality is not a law here.
        # The law that is: every emitted token, teacher-forced through
        # the float32 reference (stated as such in the result — this is
        # the repo's precision law, not a loosened equality)
        from hpc_patterns_tpu.models.quantization import (
            emitted_stream_law,
        )

        law = emitted_stream_law(params, cfg, [p for p, _ in reqs],
                                 [out[i] for i in range(len(reqs))])
        try:
            law.check()
        except AssertionError as e:
            log.print(f"PRECISION-LAW VIOLATION: {e}")
            law = None
    oracle = "exact" if exact else "law" if law is not None else "mismatch"
    max_compiles = (len(buckets) * (2 if spec else 1)
                    if buckets is not None else None)
    bounded = (compiles_warm == 0 and
               (max_compiles is None or compiles_cold <= max_compiles))
    if not bounded:
        log.print(f"COMPILE-BOUND VIOLATION: {compiles_cold} cold + "
                  f"{compiles_warm} warm prefill compiles vs ladder "
                  f"bound {max_compiles} (warm must add none)")
    ok = oracle != "mismatch" and bounded and served > 0
    log.emit(kind="result", name="serve", success=ok,
             decode_attn=attn, oracle=oracle,
             law_greedy_agreement=law.greedy_agreement if law else None,
             law_tv_max=law.tv_max if law else None,
             requests=args.requests, slots=args.slots,
             pool_pages=pool_pages, page_size=args.page_size,
             chunk=args.chunk, served_tokens=served,
             tokens_per_s=served / dt, oracle_exact=exact,
             bubble_frac=bubble, prefill_compiles=compiles_cold,
             prefill_compiles_warm=compiles_warm,
             prompt_buckets=list(buckets) if buckets else None,
             temperature=args.temperature, top_k=args.top_k,
             overlap=not args.no_overlap)
    mode = ("draft+sampled law" if sampled and spec
            else "sampled exact" if sampled else "exact")
    log.print(f"serve[{args.slots} slots, pool {pool_pages}p x "
              f"{args.page_size}] {args.requests} reqs, {served} tokens "
              f"(budget {total_budget}): {dt:.3f}s, "
              f"{served / dt:,.1f} tok/s, bubble {bubble:.1%}, "
              f"{compiles_cold} prefill compiles"
              f"{f' (ladder {len(buckets)})' if buckets else ''}"
              f"{f' +{compiles_warm} warm' if compiles_warm else ''}, "
              f"decode_attn={attn}, "
              f"oracle[{mode}] {'ok' if exact else 'MISMATCH'}")
    if law is not None:
        log.print(f"oracle[precision law] ok: greedy agreement "
                  f"{law.greedy_agreement:.4f} with the float32 "
                  f"reference over {law.steps} teacher-forced steps; "
                  f"largest TV distance a flip implies: {law.tv_max:.2e}")

    rtr = reqtracelib.active()
    if rtr is not None:
        snap = rtr.snapshot(eng.stats)
        log.emit(kind="reqtrace", **snap)
        dig = explainlib.digest([snap])
        log.print(explainlib.format_explain(dig))
        if args.explain_out:
            import json as _json
            from pathlib import Path as _Path

            _Path(args.explain_out).write_text(
                _json.dumps(dig) + "\n")
            log.print(f"explain digest -> {args.explain_out}")

    if args.static_compare:
        def run_static():
            # static batching of a mixed-length stream: batches of
            # `slots` in arrival order; rows inside a batch group by
            # prompt length (rectangular batches only) and every row
            # pays the batch's LONGEST budget — the fragmentation +
            # padding waste the engine exists to remove
            o = {}
            skey = jax.random.PRNGKey(args.seed)
            for i in range(0, args.requests, args.slots):
                batch = reqs[i:i + args.slots]
                run_len = max(b for _, b in batch)
                bylen: dict[int, list] = {}
                for j, (p, b) in enumerate(batch):
                    bylen.setdefault(len(p), []).append((i + j, p, b))
                for group in bylen.values():
                    prompts = jnp.asarray(
                        np.stack([p for _, p, _ in group]))
                    toks = np.asarray(paged_generate(
                        params, prompts, cfg, run_len,
                        page_size=args.page_size,
                        key=skey if sampled else None,
                        temperature=args.temperature,
                        top_k=args.top_k))
                    for j, (idx, _, b) in enumerate(group):
                        o[idx] = toks[j, :b]
            return o

        run_static()  # warmup
        t0 = time.perf_counter()
        run_static()
        ts = time.perf_counter() - t0
        log.print(f"static batching: {ts:.3f}s "
                  f"({served / ts:,.1f} tok/s) — engine/static "
                  f"{ts / dt:.2f}x")

    verdict = Verdict(success=ok, messages=("SUCCESS" if ok else "FAILURE",))
    log.print(verdict.summary_line())
    return verdict.exit_code


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.trace_out:
        args.trace = True
    try:
        return common.run_instrumented(run, args)
    finally:
        # ANY exit path writes the timeline (run_instrumented leaves
        # the per-run recorder installed): a crashed serving run still
        # produces a loadable artifact showing where it died
        if args.trace_out:
            from hpc_patterns_tpu.harness import trace as tracelib

            rec = tracelib.get_tracer()
            if rec is not None and rec.enabled:
                out = rec.export(args.trace_out)
                print(f"trace timeline: {out} (open in Perfetto / "
                      "chrome://tracing)")


if __name__ == "__main__":
    sys.exit(main())
