"""Continuous batching vs static batching: serving throughput.

Usage: python benchmarks/bench_serving.py [--n=N] [--slots=S] [--chunk=K]
         [--mix=0|1] [--buckets=auto|none|16,32,...] [--overlap=0|1]
         [--temp=T] [--topk=K] [--smoke] [--scenario] [--plane]
         [--migration=dma|device_put|wire]
         [--elastic] [--offload] [--shared] [--quant] [--fit]
         [--autofit=config.json] [--fit-out=PATH]
         [--kv-dtype=f32|bf16|int8|fp8] [--quant-weights]

``--fit``: the AUTOFIT row (round 16) — observability becomes
control. A prefill-heavy long-tail stream is served once by the
default-ladder engine with its ``emit`` stream recorded to a RunLog
JSONL, ``harness/autofit.py`` fits a versioned FittedConfig from that
profile (the SAME fitter the CLI ``python -m
hpc_patterns_tpu.harness.autofit`` runs), and the A/B re-serves the
stream default vs ``ContinuousBatcher.from_fitted``. The fitted
ladder's expected padding must STRICTLY beat the default's
(deterministic, before any wall clock), every sequence on both legs
is byte-exact vs standalone decode, and the headline keys
``fitted_goodput_tok_s`` / ``autofit_gain_frac`` are captured by
``bench.py`` and gated by ``harness/regress.py``
(docs/observability.md "from diagnosis to control").
``--autofit=config.json`` replays an existing FittedConfig instead of
recording (e.g. one fitted from a chip trace); on the plain
rows it applies the fitted ladder in place of the 'auto' default.

``--elastic``: the ELASTIC-PLANE row (round 14) — one diurnal
open-loop ramp under seeded replica-death chaos through a FIXED
2-replica plane (a death there ends in shedding) and the autoscaled
``serving_plane/autoscaler.ElasticServingPlane`` (SLO-feedback
scale-up on warm residency-pulled params, checkpoint resume after the
death, drain-by-migration on the way down). The autoscaled plane's
per-class SLO attainment must STRICTLY exceed the static plane's on
the same replayed schedule, every served stream is byte-exact vs
standalone decode (greedy AND sampled — the sampled leg exercises
the per-row key-state checkpoint), and warm spin-up must beat a cold
``init_params`` + engine build. Headline keys
``elastic_slo_attainment`` / ``goodput_per_replica_round`` are
captured by ``bench.py`` and gated by ``harness/regress.py``
(docs/serving_plane.md "Elastic plane").

``--quant`` / ``--kv-dtype``: the QUANTIZED-DECODE row (round 13) —
the stream served from an int8/fp8 KV pool (one-byte pages + per-row
scales, ``decode_attn="paged_flash"``-ready), optionally with int8
per-channel weights (``--quant-weights``). TWO oracles before any
number: token-identical to standalone decode WITHIN the precision,
and the teacher-forced precision law (greedy top-1 agreement +
TV-distance bounds, models/quantization.py) ACROSS precisions.
Headline keys ``quant_goodput_tok_s`` / ``kv_pool_bytes_frac`` (pool
bytes vs a bf16 pool at equal residents — int8/fp8 land ~0.53) are
captured by ``bench.py`` and gated by ``harness/regress.py``.
``--kv-dtype`` also threads through ``--offload``/``--plane`` so the
gate sees the compound win (double effective HBM, half the migration
bytes); ``--shared`` refuses quantized pools loudly (prefix sharing
needs exact KV pages — docs/quantization.md).

``--shared``: the PREFIX-SHARING row (round 12) — one shared-prefix
open-loop stream (template pool + conversation-tree turns,
``harness/loadgen.make_shared_prefix_schedule``) through a
private-pages engine and the sharing-aware arena
(``prefix_cache=True``: radix match at admission, matched pages
mapped read-only + refcounted, tail-only prefill). Token-identical
to private pages (oracle before any number), ``prefill_skip_frac``
asserted > 0.3 on the template mix, and the headline keys
``shared_goodput_tok_s`` / ``prefill_skip_frac`` are captured into
``bench.py``'s detail and gated by ``harness/regress.py``
(docs/prefix_cache.md).

``--offload``: the TIERED-MEMORY row (round 11) — the same stream
through an all-HBM engine and an engine whose HBM pool is capped well
below the working set, fronting a host-resident pool via the
residency manager (``hpc_patterns_tpu/memory/``): cold rows page out
at chunk boundaries, swapped rows prefetch back with the pull
dispatched before the decode chunk. Token-identical to the all-HBM
engine (oracle before any number), the cap must force REAL eviction,
and the headline keys ``offload_goodput_tok_s`` /
``prefetch_overlap_frac`` are captured into ``bench.py``'s detail and
gated by ``harness/regress.py`` (docs/memory.md).

``--plane``: the SERVING-PLANE row (round 10) — one open-loop stream
through a single engine, a homogeneous 2-replica router plane, and
the disaggregated 1-prefill/1-decode plane with KV-page migration
overlapped behind the decode chunk (``hpc_patterns_tpu/
serving_plane/``). The bucket ladder is FIT from the stream's
observed prompt lengths (``serving.fit_bucket_ladder``) and must beat
the default ladder's expected padding; every leg is oracle-exact
(migrated rows included) before any number prints.
``--migration dma|device_put|wire`` picks the 1p/1d leg's KV-handoff
transport (round 17): ``dma`` routes bundles over the fused paired
remote-DMA kernel (``comm/migration_dma.py``, forces per-device
replica placement), ``wire`` round-trips the socket byte codec.
Headline keys ``plane_goodput_tok_s`` / ``kv_migration_overlap_frac``
/ ``dma_migration_overlap_frac`` / ``migration_bytes_per_round`` are
captured into ``bench.py``'s detail and gated by
``harness/regress.py``.

``--scenario``: the ROBUSTNESS row (round 8) — an OPEN-loop two-class
stream (harness/loadgen.py) served under page pressure that forces
preemption-and-resume, with a seeded stalled-host chaos injection
(harness/chaos.py) perturbing the engine loop, reporting **goodput**
(SLO-attained tok/s, harness/slo.py) NEXT TO raw tok/s plus the
preemption/shed counts — and the engine must STILL beat clean static
batching. The oracle extends to the degraded path: every served
sequence (including preempted-and-resumed ones) must be token-exact vs
standalone paged_generate before any number is reported.
``--smoke --scenario`` is the CI shape (tier-1,
tests/test_bench_serving.py); the full shape is the chip row, whose
``serving_goodput_tok_s`` / ``serving_degraded_bubble_frac`` keys are
gated by
``harness/regress.py`` like every other headline. The timed leg also
runs under request-scoped lifecycle tracing (harness/reqtrace.py),
enforcing the coverage invariant in-run (untracked share < 5%) and
capturing ``attribution_coverage_frac`` / ``ttft_p99_queue_share``;
``--explain=1`` (or ``--explain-out=PATH``) renders the per-class
tail-attribution table (harness/explain.py) after the goodput row.

The capacity story measured on the REALISTIC stream: N requests with
VARIED prompt lengths (``--mix``, default on) and varied generation
budgets, served (a) statically — batches of ``slots`` rows in arrival
order, rows grouped by prompt length into rectangular sub-batches
(fragmentation), every row paying the longest budget in its batch
(padding) — vs (b) the ContinuousBatcher with the production levers
on: prompt-length BUCKETING (admission prefill compiles bounded by the
ladder size, not the stream's distinct lengths) and OVERLAPPED
admission (prefills enqueue behind the in-flight decode chunk).

Reported per engine run: tokens/s, the admission-bubble fraction
(host admission time exposed with no decode in flight), and the
prefill compile count with the ladder bound it must respect.

Oracle on every run (benchmark-IS-the-test): the engine's per-sequence
tokens must equal standalone paged_generate — same per-request key in
sampled mode — before any number is reported, and the compile count
must not exceed the bucket ladder size.

``--smoke``: the CI shape (seconds on the 8-device CPU mesh) —
tests/test_bench_serving.py runs it in tier-1 and asserts the engine
beats static on the mixed workload.

On-chip protocol note: the engine's host loop pays one dispatch and one
readback per chunk; ``--chunk`` amortizes it (the dispatch-amortization
discipline of benchmarks/bench_decode.py). Static batching runs each
sub-batch's whole scan in one dispatch — the comparison is honest
serving reality for both.
"""

import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.harness import budget as budgetlib
from hpc_patterns_tpu.harness import chaos as chaoslib
from hpc_patterns_tpu.harness import explain as explainlib
from hpc_patterns_tpu.harness import loadgen
from hpc_patterns_tpu.harness import reqtrace as reqtracelib
from hpc_patterns_tpu.harness import slo
from hpc_patterns_tpu.models import TransformerConfig
from hpc_patterns_tpu.models.decode import paged_generate
from hpc_patterns_tpu.models.serving import (
    ContinuousBatcher,
    EngineCore,
    bucket_ladder,
    expected_padding,
    fit_bucket_ladder,
    pad_to_bucket,
    prefill_cache_size,
)
from hpc_patterns_tpu.models.transformer import init_params


def arg(name, default, cast=int):
    for a in sys.argv[1:]:
        if a.startswith(f"--{name}="):
            v = a.split("=", 1)[1]
            if cast is bool:  # bool("0") is True; parse it properly
                return v.lower() not in ("0", "false", "no", "")
            return cast(v)
        if a == f"--{name}":
            if cast is not bool:
                raise SystemExit(
                    f"--{name} needs =VALUE (space-separated values "
                    "are not supported by this parser)")
            return True
    return default


def run_bench(*, n, slots, chunk, page_size, prompt_len, max_budget,
              cfg, params, mix=True, buckets="auto", overlap=True,
              temperature=0.0, top_k=0, seed=0, reps=1, quiet=False):
    """One engine-vs-static comparison; returns the metrics dict.
    ``buckets``: 'auto' (ladder over prompt_len), 'none', or a tuple.
    ``reps``: timed repetitions per mode, MIN taken — the shared-host
    CI box is noisy and min-of-reps is the standard load-spike shield.
    Raises AssertionError if the oracle or the compile bound fails."""
    out = print if not quiet else (lambda *a, **k: None)
    if isinstance(buckets, str):
        # 'auto' / 'none' / '8,16,32' — the same resolver the CLI
        # serving surfaces use (harness.cli)
        from hpc_patterns_tpu.harness.cli import parse_buckets

        buckets = parse_buckets(buckets, prompt_len)
    rng = np.random.RandomState(7)
    # the production-shaped stream: prompt lengths spread 1/2..1x, and
    # LONG-TAIL budgets (most requests short, a fifth at the max) —
    # static pays fragmentation (rectangular length groups) AND padding
    # (every row pays its batch's longest budget, usually the max);
    # the engine pays each row's own length and budget
    lengths = ([prompt_len // 2, (3 * prompt_len) // 4, prompt_len]
               if mix else [prompt_len])
    reqs = []
    for _ in range(n):
        t = int(rng.choice(lengths))
        prompt = rng.randint(0, cfg.vocab, size=t).astype(np.int32)
        budget = int(rng.choice(
            [max(1, max_budget // 8), max(1, max_budget // 4),
             max_budget],
            p=[0.5, 0.3, 0.2]))
        reqs.append((prompt, budget))
    total_tokens = sum(b for _, b in reqs)

    pages_per_seq = max(
        ContinuousBatcher.pages_needed(len(p), b, page_size,
                                       padded_len=pad_to_bucket(
                                           buckets, len(p)))
        for p, b in reqs)

    # --- static batching: batches of `slots` in arrival order; rows
    # group by prompt length into rectangular sub-batches, every row
    # pays the batch-max budget
    def run_static():
        outs = {}
        for i in range(0, n, slots):
            batch = reqs[i:i + slots]
            run_len = max(b for _, b in batch)
            bylen = {}
            for j, (p, b) in enumerate(batch):
                bylen.setdefault(len(p), []).append((i + j, p, b))
            for group in bylen.values():
                prompts = jnp.asarray(np.stack([p for _, p, _ in group]))
                toks = np.asarray(paged_generate(
                    params, prompts, cfg, run_len, page_size=page_size))
                for j, (idx, _, b) in enumerate(group):
                    outs[idx] = toks[j, :b]
        return outs

    def make_engine():
        return ContinuousBatcher(
            params, cfg, slots=slots, pool_pages=slots * pages_per_seq,
            pages_per_seq=pages_per_seq, page_size=page_size,
            chunk=chunk, prompt_buckets=buckets, overlap=overlap,
            temperature=temperature, top_k=top_k, seed=seed,
        )

    def run_engine():
        eng = make_engine()
        ids = [eng.submit(p, b) for p, b in reqs]
        got = eng.run()
        return {i: got[sid] for i, sid in enumerate(ids)}, eng

    # warmup (compiles) then timed runs
    compiles_before = prefill_cache_size()  # other engines, this process
    run_static()
    run_engine()
    compiles_warm = prefill_cache_size()
    t_static = t_engine = float("inf")
    bubble = None
    for _ in range(reps):
        t0 = time.perf_counter()
        static_out = run_static()
        t_static = min(t_static, time.perf_counter() - t0)
        t0 = time.perf_counter()
        engine_out, eng = run_engine()
        te = time.perf_counter() - t0
        if te < t_engine:
            # keep the bubble fraction of the rep whose time is
            # reported — mixing min-time with another rep's bubble
            # would pair numbers from different runs
            t_engine, bubble = te, eng.last_bubble_frac
    compiles = prefill_cache_size()

    # oracle before any number is believed: engine rows standalone-exact
    # (same per-request key when sampling), compile count inside the
    # ladder bound, and a WARM engine added no prefill compiles at all
    for i, (prompt, b) in enumerate(reqs):
        want = np.asarray(paged_generate(
            params, jnp.asarray(prompt)[None], cfg, b,
            page_size=page_size,
            key=eng.request_key(i) if temperature > 0 else None,
            temperature=temperature, top_k=top_k))[0]
        np.testing.assert_array_equal(engine_out[i], want,
                                      err_msg=f"engine seq {i}")
        if temperature <= 0:
            np.testing.assert_array_equal(
                static_out[i], want[:len(static_out[i])],
                err_msg=f"static seq {i}")
    assert compiles == compiles_warm, (
        f"warm engine recompiled prefill: {compiles_warm} -> {compiles}")
    distinct = len({len(p) for p, _ in reqs})
    compiles = compiles - compiles_before  # this bench's engine only
    if buckets is not None:
        assert compiles <= len(buckets), (
            f"{compiles} prefill compiles > ladder size {len(buckets)}")

    out(f"serving[{'mixed' if mix else 'uniform'}]: n={n} slots={slots} "
        f"chunk={chunk} prompt<={prompt_len} ({distinct} lengths) "
        f"budgets<={max_budget} tokens={total_tokens} "
        f"buckets={buckets if buckets else 'off'} "
        f"overlap={'on' if overlap else 'off'}")
    out(f"  static  : {t_static:.3f}s  "
        f"{total_tokens / t_static:,.1f} tok/s")
    out(f"  engine  : {t_engine:.3f}s  "
        f"{total_tokens / t_engine:,.1f} tok/s  "
        f"bubble {bubble:.1%}  prefill compiles {compiles}"
        f"{f' (ladder {len(buckets)})' if buckets else ''}")
    out(f"  engine/static speedup: {t_static / t_engine:.3f}x "
        "(oracle-exact)")
    return {
        "t_static": t_static, "t_engine": t_engine,
        "tokens": total_tokens,
        "tokens_per_s_static": total_tokens / t_static,
        "tokens_per_s_engine": total_tokens / t_engine,
        "speedup": t_static / t_engine,
        "bubble_frac": bubble,
        "prefill_compiles": compiles,
        "ladder": len(buckets) if buckets else None,
        "distinct_lengths": distinct,
    }


def smoke_config():
    """The CI shape: a model big enough that DEVICE work (static's
    padding + fragmentation waste vs the engine's own-budget rows)
    dominates host dispatch on the 8-device CPU mesh, with the serving
    gather route so neither side pays pallas interpret cost — ONE
    definition shared by the CLI ``--smoke`` and the tier-1 pytest
    (tests/test_bench_serving.py) so they cannot drift. Engine wins
    ~2.5x here;
    the pytest asserts > 1 with that margin as the noise shield."""
    cfg = TransformerConfig(
        vocab=256, d_model=256, n_heads=4, n_layers=2, d_ff=1024,
        max_seq=256, dtype="float32", decode_attn="gather",
    )
    return dict(n=16, slots=4, chunk=16, page_size=16, prompt_len=32,
                max_budget=192, reps=2, cfg=cfg,
                params=init_params(jax.random.PRNGKey(0), cfg))


SCENARIO_CLASSES = (
    # interactive: the SLO-bearing class — tight-ish first-token and
    # per-token targets, sheds if it queues absurdly long
    loadgen.PriorityClass("interactive", 0, weight=0.4,
                          ttft_slo_s=3.0, tpot_slo_s=1.0,
                          deadline_s=30.0),
    # batch: throughput filler — no latency target, preemptible
    loadgen.PriorityClass("batch", 1, weight=0.6),
)


def scenario_smoke_config():
    """The CI chaos scenario (tier-1 via tests/test_bench_serving.py):
    a DETERMINISTIC staged schedule — two long batch requests take the
    pool at t=0, two interactive requests arrive mid-run and cannot
    get pages without EVICTING a batch row — plus two seeded
    engine-stall injections. Staged (not sampled) so the preemption
    trigger is structural, not a lucky draw; the seeded-random shapes
    are the full scenario's job (scenario_full_config)."""
    base = smoke_config()
    inter, batch = SCENARIO_CLASSES
    # two long batch rows take the pool at t=0 (free pages drop below
    # an interactive's need BY CONSTRUCTION, so the first interactive
    # arrival must preempt); a third batch row and the interactive
    # wave interleave in ARRIVAL order so that in static batching both
    # of the first two batches mix a 160-budget row with short rows —
    # every short row in them pays the 160-step run_len (padding) and
    # the length split doubles the scans (fragmentation). The engine
    # preempts one batch row, serves the wave at its own budgets, and
    # resumes the victim
    schedule = loadgen.staged_schedule([
        (0.00, batch, 32, 160),
        (0.00, batch, 32, 160),
        (0.05, inter, 16, 16),
        (0.10, batch, 32, 160),
        (0.15, inter, 16, 24),
        (0.20, inter, 16, 16),
        (0.25, inter, 16, 24),
        (0.30, inter, 16, 16),
    ], spec={"name": "smoke-chaos"})
    return dict(
        cfg=base["cfg"], params=base["params"], page_size=16,
        slots=3, chunk=8, schedule=schedule,
        classes=SCENARIO_CLASSES,
        # pool: room for the two batch rows (12 pages each) plus ONE
        # spare page — an arriving interactive row (2 pages) is starved
        # by construction and must preempt
        pool_pages=25, pages_per_seq=12,
        buckets=bucket_ladder(192),
        chaos_spec="stall:at=3,delay_ms=50;stall:at=9,delay_ms=50",
        # the high-water backoff stays off in the smoke: its pool is
        # sized to the page for the preemption trigger, and a reserve
        # would re-order the staged admissions (the full config runs
        # with the reserve on)
        admit_highwater=1.0,
    )


def scenario_full_config(on_tpu: bool):
    """The re-grounding shape: a seeded BURSTY open-loop stream (the
    admission-control stressor) over the same two classes, sized so
    bursts oversubscribe the pool and preemption/backoff do real work."""
    cfg = TransformerConfig(
        vocab=32768 if on_tpu else 256,
        d_model=1024 if on_tpu else 256,
        n_heads=8 if on_tpu else 4,
        n_layers=8 if on_tpu else 2,
        d_ff=4096 if on_tpu else 1024,
        max_seq=1024 if on_tpu else 256,
        dtype="bfloat16" if on_tpu else "float32",
        decode_attn="flash" if on_tpu else "gather",
    )
    prompt_top = 128 if on_tpu else 32
    budget_top = 256 if on_tpu else 128
    schedule = loadgen.make_schedule(
        32, rate_rps=16.0, classes=SCENARIO_CLASSES,
        prompt_lens=(prompt_top // 2, prompt_top),
        budgets=(budget_top // 8, budget_top // 2, budget_top),
        budget_probs=(0.5, 0.3, 0.2),
        process="bursty", seed=7, burst_factor=8.0,
        mean_quiet_s=0.5, mean_burst_s=0.2)
    page = 256 if on_tpu else 16
    pps = ContinuousBatcher.pages_needed(
        prompt_top, budget_top, page, padded_len=prompt_top)
    return dict(
        cfg=cfg, params=init_params(jax.random.PRNGKey(0), cfg),
        page_size=page, slots=8 if on_tpu else 4, chunk=16,
        schedule=schedule, classes=SCENARIO_CLASSES,
        # ~2.5 concurrent max-size rows' worth of pages for 4-8 slots:
        # bursts starve the arena and exercise eviction + backoff
        pool_pages=int(2.5 * pps), pages_per_seq=pps,
        buckets=bucket_ladder(prompt_top + budget_top),
        chaos_spec="stall:at=5,delay_ms=80,every=12",
        admit_highwater=0.95,
    )


def run_scenario(*, cfg, params, schedule, classes, page_size, slots,
                 chunk, pool_pages, pages_per_seq, buckets,
                 chaos_spec=None, admit_highwater=0.95, quiet=False,
                 explain=False, explain_out=None):
    """One robustness row: the open-loop schedule through (a) clean
    static batching (closed-loop, arrival order — the baseline that
    ignores arrival gaps, generous to static) and (b) the engine with
    priority admission, preemption-and-resume, SLO accounting, and the
    seeded chaos faults ACTIVE. The engine must beat static anyway,
    and every served sequence — preempted-and-resumed included — must
    be token-exact vs standalone paged_generate before any number is
    believed. Returns the metrics dict (goodput next to tok/s)."""
    out = print if not quiet else (lambda *a, **k: None)
    rng = np.random.RandomState(13)
    prompts = {r.index: rng.randint(0, cfg.vocab, size=r.prompt_len)
               .astype(np.int32) for r in schedule.requests}
    total_tokens = sum(r.max_new for r in schedule.requests)
    targets = slo.targets_from_classes(classes)

    def run_static():
        outs = {}
        reqs = [(prompts[r.index], r.max_new) for r in schedule.requests]
        for i in range(0, len(reqs), slots):
            batch = reqs[i:i + slots]
            run_len = max(b for _, b in batch)
            bylen = {}
            for j, (p, b) in enumerate(batch):
                bylen.setdefault(len(p), []).append((i + j, p, b))
            for group in bylen.values():
                arr = jnp.asarray(np.stack([p for _, p, _ in group]))
                toks = np.asarray(paged_generate(
                    params, arr, cfg, run_len, page_size=page_size))
                for j, (idx, _, b) in enumerate(group):
                    outs[idx] = toks[j, :b]
        return outs

    def run_engine():
        eng = ContinuousBatcher(
            params, cfg, slots=slots, pool_pages=pool_pages,
            pages_per_seq=pages_per_seq, page_size=page_size,
            chunk=chunk, prompt_buckets=buckets, preempt=True,
            admit_highwater=admit_highwater, slo=targets,
        )
        arrivals = [
            (r.t_arrival_s, dict(prompt=prompts[r.index],
                                 max_new=r.max_new, seq_id=r.index,
                                 priority=r.priority,
                                 deadline_s=r.deadline_s))
            for r in schedule.requests
        ]
        got = eng.run(arrivals=arrivals)
        return got, eng

    def prewarm_rungs():
        # resumed prompts land on ladder rungs the ORIGINAL stream
        # never visits (prompt + generated-so-far pads upward), and
        # WHICH rung depends on when the preemption fired — so the
        # warmup run cannot be trusted to have compiled them. Prefill
        # every rung once (budget-1 rows through a 1-slot engine
        # sharing this config's _prefill_one cache) so the timed leg
        # measures scheduling, not a mid-run XLA compile.
        # the SAME pool geometry as the scenario engine: _prefill_one
        # compiles key on the cache shapes too, so a differently-sized
        # pool would warm a parallel cache line and change nothing
        eng = ContinuousBatcher(
            params, cfg, slots=1, pool_pages=pool_pages,
            pages_per_seq=pages_per_seq, page_size=page_size, chunk=1,
            prompt_buckets=buckets)
        for rung in buckets:
            for plen in (rung, rung - 1):
                if plen < 1 or pad_to_bucket(buckets, plen) != rung:
                    continue
                if ContinuousBatcher.pages_needed(
                        plen, 1, page_size,
                        padded_len=rung) <= pages_per_seq:
                    eng.submit(np.zeros(plen, np.int32), 1)
                    eng.run()
                    break

    compiles_before = prefill_cache_size()
    # warmup (compiles; the chaos faults stay off so the warm cache is
    # the same one a clean run builds), then the timed legs — the
    # engine leg runs UNDER the seeded faults, static runs clean
    run_static()
    prewarm_rungs()
    run_engine()
    t0 = time.perf_counter()
    static_out = run_static()
    t_static = time.perf_counter() - t0
    chaoslib.configure(chaos_spec)  # also clears the injection log
    # request-scoped lifecycle tracing (harness/reqtrace.py) is ALWAYS
    # on for the timed leg: the attribution keys are gated per round,
    # so coverage regressions surface even without --explain. Fresh
    # recorder — the warmup leg reused the same seq_ids.
    reqtracelib.configure(enabled=True)
    try:
        t0 = time.perf_counter()
        engine_out, eng = run_engine()
        t_engine = time.perf_counter() - t0
        stalls = [e for e in chaoslib.injections()
                  if e["site"] == "engine_round"]
        req_snap = reqtracelib.active().snapshot(eng.stats)
    finally:
        chaoslib.reset()
        reqtracelib.reset()
    compiles = prefill_cache_size() - compiles_before

    # oracle before any number is believed — the DEGRADED path included:
    # a preempted-and-resumed row must be byte-identical to standalone
    rep = eng.last_slo
    for r in schedule.requests:
        if eng.stats[r.index]["outcome"] != "ok":
            continue  # shed: empty output by contract
        want = np.asarray(paged_generate(
            params, jnp.asarray(prompts[r.index])[None], cfg, r.max_new,
            page_size=page_size))[0]
        np.testing.assert_array_equal(
            engine_out[r.index], want, err_msg=f"engine seq {r.index}")
        np.testing.assert_array_equal(
            static_out[r.index], want[:len(static_out[r.index])],
            err_msg=f"static seq {r.index}")
    assert compiles <= len(buckets), (
        f"{compiles} prefill compiles > ladder {len(buckets)} — "
        "resumed prompts left the bucket ladder")

    # tail attribution over the timed leg: the coverage invariant is
    # ENFORCED in-run — finished requests whose segment tilings leave
    # more than 5% of wall time untracked mean a stamp site went
    # missing, and the table below could no longer be believed
    dig = explainlib.digest([req_snap])
    assert dig["coverage_frac"] >= 0.95, (
        f"request-trace coverage {dig['coverage_frac']:.3f} < 0.95 — "
        "segment tilings leak untracked time (harness/reqtrace.py "
        "stamp site missing?)")
    # segment SLO budgets (harness/budget.py): did any ONE lifecycle
    # segment alone blow a class's target? The loud section rides
    # --explain; the count rides the result row either way
    breaches = budgetlib.evaluate(req_snap, targets)

    tot = rep["total"]
    served_tokens = tot["tokens"]
    result = {
        "t_static": t_static, "t_engine": t_engine,
        "tokens": total_tokens, "served_tokens": served_tokens,
        "tokens_per_s_static": total_tokens / t_static,
        "tokens_per_s_engine": served_tokens / t_engine,
        "speedup": (served_tokens / t_engine) / (total_tokens / t_static),
        "goodput_tok_s": tot["goodput_tok_s"] * eng._serve_s / t_engine
        if eng._serve_s else 0.0,
        "attained_frac": tot["attained_frac"],
        "preemptions": tot["preemptions"], "shed": tot["shed"],
        "bubble_frac": eng.last_bubble_frac,
        "stall_injections": len(stalls),
        "stall_injected_s": sum(e["delay_s"] for e in stalls),
        "prefill_compiles": compiles, "ladder": len(buckets),
        "attribution_coverage_frac": dig["coverage_frac"],
        "ttft_p99_queue_share": dig["ttft_p99_queue_share"],
        "tpot_p99_stall_share": dig["tpot_p99_stall_share"],
        "budget_breaches": len(breaches),
        "schedule": schedule.spec,
    }
    out(f"scenario[{schedule.spec.get('process', '?')}]: "
        f"n={schedule.n} slots={slots} chunk={chunk} "
        f"pool={pool_pages}p tokens={total_tokens} "
        f"chaos={chaos_spec or 'off'}")
    out(f"  static  : {t_static:.3f}s  "
        f"{result['tokens_per_s_static']:,.1f} tok/s (clean)")
    out(f"  engine  : {t_engine:.3f}s  "
        f"{result['tokens_per_s_engine']:,.1f} tok/s  "
        f"goodput {result['goodput_tok_s']:,.1f} tok/s  "
        f"bubble {result['bubble_frac']:.1%}  "
        f"preempted {result['preemptions']}  shed {result['shed']}  "
        f"stalls {result['stall_injections']} "
        f"(+{result['stall_injected_s'] * 1e3:.0f}ms)")
    out(f"  engine/static speedup under chaos: "
        f"{result['speedup']:.3f}x (oracle-exact incl. resumed rows)")
    out("  " + slo.format_slo(rep).replace("\n", "\n  "))
    if explain:
        out("  " + explainlib.format_explain(dig).replace("\n", "\n  "))
        out("  " + budgetlib.format_budget(breaches)
            .replace("\n", "\n  "))
    if explain_out:
        import json
        from pathlib import Path

        Path(explain_out).write_text(json.dumps(dig) + "\n")
        out(f"  explain digest -> {explain_out}")
    return result


def offload_smoke_config():
    """The CI tiered-memory shape (tier-1 via
    tests/test_bench_serving.py): the smoke model, an HBM pool capped
    well below the stream's working set (REAL eviction by
    construction, asserted), a deterministic cold-after-N rotation
    policy, and a host pool big enough for everything paged out."""
    base = smoke_config()
    return dict(cfg=base["cfg"], params=base["params"], n=8, slots=4,
                chunk=16, page_size=16, prompt_len=32, max_budget=96,
                hbm_frac=0.5, cold_n=2)


def offload_full_config(on_tpu: bool):
    """The re-grounding shape: the scenario model with a long-context
    stream whose KV exceeds the HBM cap ~2.5x — the 131k-offload-row
    regime generalized from a one-shot training trick to a serving
    policy knob."""
    base = scenario_full_config(on_tpu)
    prompt_top = 256 if on_tpu else 32
    budget_top = 512 if on_tpu else 128
    return dict(cfg=base["cfg"], params=base["params"],
                n=24 if on_tpu else 8, slots=8 if on_tpu else 4,
                chunk=16, page_size=256 if on_tpu else 16,
                prompt_len=prompt_top, max_budget=budget_top,
                hbm_frac=0.4, cold_n=3)


def run_offload(*, cfg, params, n, slots, chunk, page_size, prompt_len,
                max_budget, hbm_frac, cold_n, quiet=False):
    """The tiered-memory row: the same stream through (a) an all-HBM
    engine (pool sized to the whole working set — the baseline and
    the ORACLE) and (b) a constrained engine whose HBM pool is capped
    at ``hbm_frac`` of that, fronting a host-resident pool through
    the residency manager (``hpc_patterns_tpu/memory/``) — cold rows
    page out at chunk boundaries, swapped rows prefetch back with the
    pull dispatched before the decode chunk. The constrained engine's
    outputs must be TOKEN-IDENTICAL to the all-HBM engine's (and to
    standalone ``paged_generate``) before any number is believed, and
    the cap must have forced real evictions. Reports
    ``offload_goodput_tok_s`` (SLO-attained tok/s of the constrained
    engine) and ``prefetch_overlap_frac`` (measured fraction of
    prefetch-window time hidden under the decode chunk), the two keys
    ``bench.py`` captures and ``harness/regress.py`` gates."""
    from hpc_patterns_tpu.memory import ColdAfterNPolicy, ResidencyManager

    out = print if not quiet else (lambda *a, **k: None)
    rng = np.random.RandomState(7)
    lengths = [prompt_len // 2, (3 * prompt_len) // 4, prompt_len]
    reqs = []
    for _ in range(n):
        t = int(rng.choice(lengths))
        prompt = rng.randint(0, cfg.vocab, size=t).astype(np.int32)
        budget = int(rng.choice(
            [max(1, max_budget // 2), max_budget], p=[0.4, 0.6]))
        reqs.append((prompt, budget))
    total_tokens = sum(b for _, b in reqs)
    buckets = bucket_ladder(prompt_len)
    targets = slo.targets_from_classes(SCENARIO_CLASSES)

    pages_per_seq = max(
        ContinuousBatcher.pages_needed(len(p), b, page_size,
                                       padded_len=pad_to_bucket(
                                           buckets, len(p)))
        for p, b in reqs)
    full_pool = slots * pages_per_seq
    hbm_pool = max(pages_per_seq, int(full_pool * hbm_frac))
    assert hbm_pool < full_pool, (
        f"hbm_frac {hbm_frac} does not constrain the pool "
        f"({hbm_pool} vs {full_pool}) — nothing would evict")

    def run_full():
        eng = ContinuousBatcher(
            params, cfg, slots=slots, pool_pages=full_pool,
            pages_per_seq=pages_per_seq, page_size=page_size,
            chunk=chunk, prompt_buckets=buckets, slo=targets)
        ids = [eng.submit(p, b) for p, b in reqs]
        got = eng.run()
        return {i: got[s] for i, s in enumerate(ids)}, eng

    def run_tiered():
        mgr = ResidencyManager(host_blocks=2 * full_pool,
                               policy=ColdAfterNPolicy(cold_n))
        eng = ContinuousBatcher(
            params, cfg, slots=slots, pool_pages=hbm_pool,
            pages_per_seq=pages_per_seq, page_size=page_size,
            chunk=chunk, prompt_buckets=buckets, slo=targets,
            residency=mgr)
        ids = [eng.submit(p, b) for p, b in reqs]
        got = eng.run()
        return {i: got[s] for i, s in enumerate(ids)}, eng, mgr

    # warmup (compiles), then the timed legs
    run_full()
    run_tiered()
    t0 = time.perf_counter()
    full_out, full_eng = run_full()
    t_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    tier_out, tier_eng, mgr = run_tiered()
    t_tier = time.perf_counter() - t0

    # oracle before any number is believed: the constrained-HBM engine
    # is token-identical to the all-HBM one AND to standalone decode,
    # and the cap really forced the tier to do work
    for i, (prompt, b) in enumerate(reqs):
        want = np.asarray(paged_generate(
            params, jnp.asarray(prompt)[None], cfg, b,
            page_size=page_size))[0]
        np.testing.assert_array_equal(full_out[i], want,
                                      err_msg=f"all-HBM seq {i}")
        np.testing.assert_array_equal(tier_out[i], want,
                                      err_msg=f"tiered seq {i}")
    assert mgr.swap_outs > 0 and mgr.swap_ins > 0, (
        f"HBM cap {hbm_pool}/{full_pool} pages forced no paging — "
        "the row measured nothing")

    tot_full = full_eng.last_slo["total"]
    tot_tier = tier_eng.last_slo["total"]
    overlap = mgr.prefetch_overlap_frac or 0.0
    result = {
        "t_full": t_full, "t_tiered": t_tier, "tokens": total_tokens,
        "tokens_per_s_full": total_tokens / t_full,
        "tokens_per_s_tiered": total_tokens / t_tier,
        "full_goodput_tok_s": tot_full["goodput_tok_s"]
        * full_eng._serve_s / t_full if t_full > 0 else 0.0,
        "offload_goodput_tok_s": tot_tier["goodput_tok_s"]
        * tier_eng._serve_s / t_tier if t_tier > 0 else 0.0,
        "prefetch_overlap_frac": overlap,
        "swap_outs": mgr.swap_outs, "swap_ins": mgr.swap_ins,
        "prefetch_bytes": mgr.prefetch_bytes,
        "hbm_pool": hbm_pool, "full_pool": full_pool,
        "bubble_frac": tier_eng.last_bubble_frac,
    }
    out(f"offload: n={n} slots={slots} chunk={chunk} "
        f"hbm={hbm_pool}p of {full_pool}p working set "
        f"(host pool {2 * full_pool}p) tokens={total_tokens}")
    out(f"  all-HBM : {t_full:.3f}s  "
        f"{result['tokens_per_s_full']:,.1f} tok/s  "
        f"goodput {result['full_goodput_tok_s']:,.1f}")
    out(f"  tiered  : {t_tier:.3f}s  "
        f"{result['tokens_per_s_tiered']:,.1f} tok/s  "
        f"goodput {result['offload_goodput_tok_s']:,.1f}  "
        f"swaps {mgr.swap_outs}/{mgr.swap_ins}  "
        f"prefetch overlap {overlap:.1%}")
    out(f"  capacity {t_full / t_tier:.3f}x wall cost for "
        f"{full_pool / hbm_pool:.1f}x pool oversubscription "
        "(token-identical, oracle-exact)")
    return result


def slo_budget_smoke_config():
    """The CI segment-budget shape (tier-1 via
    tests/test_bench_serving.py): a deliberately TINY model (the
    tests/test_reqtrace.py attribution geometry, seconds on CPU) on a
    5-request stream through a 2-resident tiered pool with a seeded
    ``slow_host_transfer`` — every pull eats a known synthetic delay,
    so the decode-phase stall is injected into ONE mechanism and the
    budget evaluator must blame exactly that mechanism. The knobs are
    sized so the ``prefetch_wait`` allowance sits well under one
    injected delay while every other segment's allowance sits well
    over anything the stream can spend."""
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq=128,
                            dtype="float32", decode_attn="gather")
    params = init_params(jax.random.PRNGKey(5), cfg)
    return dict(cfg=cfg, params=params, n=5, prompt_len=8,
                max_budget=24, page_size=8, chunk=4, slots=5,
                hbm_seqs=2, cold_n=2, delay_ms=60,
                ttft_slo_s=5.0, tpot_slo_s=0.08)


#: the seeded-stall budget: prefetch_wait may eat 2% of the decode
#: allowance (0.02 * 0.08s * 23 tokens ≈ 37ms — LESS than one 60ms
#: injected transfer delay), everything else is allowed most of the
#: target — so the injected chaos breaches its own bucket and no other
SLO_BUDGET_SEEDED = budgetlib.SLOBudget(
    ttft_shares={"queued": 0.9, "admit_wait": 0.9, "untracked": 0.5},
    tpot_shares={"prefetch_wait": 0.02, "swapped_out": 0.9,
                 "preempted": 0.9, "migrating": 0.9, "untracked": 0.5},
)


def _tiered_stall_leg(*, cfg, params, n, prompt_len, max_budget,
                      page_size, chunk, slots, hbm_seqs, cold_n,
                      delay_ms, prefetch_depth=None,
                      min_resident_rounds=1, emit=None):
    """One tiered stream under a seeded ``slow_host_transfer`` with
    request tracing on: an HBM pool sized for ``hbm_seqs`` of the
    ``n``-row working set forces the cold-after-N rotation to page,
    and every host->HBM pull eats the injected delay. Returns
    ``(outs, eng, mgr, snapshot, fired)`` — the shared chassis of the
    ``--slo-budget`` row and the ``--fit`` blame A/B."""
    from hpc_patterns_tpu.memory import ColdAfterNPolicy, ResidencyManager

    pps = ContinuousBatcher.pages_needed(prompt_len, max_budget,
                                         page_size)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab, size=prompt_len)
               .astype(np.int32) for _ in range(n)]
    if delay_ms > 0:
        chaoslib.configure(f"slow_host_transfer:delay_ms={delay_ms}")
    reqtracelib.configure(enabled=True)
    try:
        mgr = ResidencyManager(host_blocks=n * pps,
                               policy=ColdAfterNPolicy(cold_n),
                               min_resident_rounds=min_resident_rounds,
                               prefetch_depth=prefetch_depth)
        eng = ContinuousBatcher(
            params, cfg, slots=slots, pool_pages=hbm_seqs * pps,
            pages_per_seq=pps, page_size=page_size, chunk=chunk,
            residency=mgr, emit=emit)
        ids = [eng.submit(p, max_budget) for p in prompts]
        got = eng.run()
        fired = [e for e in chaoslib.injections()
                 if e["site"] == "host_transfer"]
        snap = reqtracelib.active().snapshot(eng.stats)
    finally:
        chaoslib.reset()
        reqtracelib.reset()
    return {i: got[s] for i, s in enumerate(ids)}, eng, mgr, snap, fired


def run_slo_budget(*, cfg, params, n, prompt_len, max_budget,
                   page_size, chunk, slots, hbm_seqs, cold_n,
                   delay_ms, ttft_slo_s, tpot_slo_s, quiet=False,
                   explain=False):
    """The segment-budget row: seeded chaos must land in the budget
    bucket it was injected into. A ``slow_host_transfer`` run is
    evaluated against :data:`SLO_BUDGET_SEEDED` and the row ASSERTS
    the breach set is exactly ``{"prefetch_wait"}`` — the injected
    mechanism blamed, nothing else smeared — and that the inter-token
    digest attributes a nonzero stall share to the same decode phase.
    Outputs stay oracle-exact vs standalone decode (paging + chaos
    change WHEN tokens arrive, never WHICH). Reports
    ``tpot_p99_stall_share`` and ``budget_breach_segments``, the two
    keys ``bench.py`` captures and ``harness/regress.py`` gates."""
    out = print if not quiet else (lambda *a, **k: None)
    leg = dict(cfg=cfg, params=params, n=n, prompt_len=prompt_len,
               max_budget=max_budget, page_size=page_size, chunk=chunk,
               slots=slots, hbm_seqs=hbm_seqs, cold_n=cold_n)
    # warmup (compiles) with the delay off; the judged leg runs seeded
    _tiered_stall_leg(**leg, delay_ms=0)
    t0 = time.perf_counter()
    outs, eng, mgr, snap, fired = _tiered_stall_leg(
        **leg, delay_ms=delay_ms)
    wall = time.perf_counter() - t0

    # oracle before any number is believed
    rng = np.random.RandomState(11)
    for i in range(n):
        prompt = rng.randint(0, cfg.vocab, size=prompt_len) \
            .astype(np.int32)
        want = np.asarray(paged_generate(
            params, jnp.asarray(prompt)[None], cfg, max_budget,
            page_size=page_size))[0]
        np.testing.assert_array_equal(outs[i], want,
                                      err_msg=f"budget seq {i}")
    assert mgr.swap_outs > 0 and fired, (
        f"seeded stall row paged nothing (swap_outs={mgr.swap_outs}, "
        f"injections={len(fired)}) — the row measured nothing")

    targets = {0: slo.SLOTarget(ttft_s=ttft_slo_s, tpot_s=tpot_slo_s)}
    breaches = budgetlib.evaluate(snap, targets, SLO_BUDGET_SEEDED)
    segs = budgetlib.breached_segments(breaches)
    assert segs == {"prefetch_wait"}, (
        f"seeded slow_host_transfer breached {sorted(segs)} — chaos "
        "must land in the budget bucket it was injected into")
    dig = explainlib.digest([snap])
    assert dig["tpot_p99_stall_share"] > 0.0, (
        "inter-token digest attributes no stall time to a run whose "
        "every pull was seeded slow")

    result = {
        "wall_s": wall, "n": n,
        "tokens": n * max_budget,
        "swap_outs": mgr.swap_outs,
        "stall_injections": len(fired),
        "stall_injected_s": sum(e["delay_s"] for e in fired),
        "attribution_coverage_frac": dig["coverage_frac"],
        "tpot_p99_stall_share": dig["tpot_p99_stall_share"],
        "budget_breach_segments": sorted(segs),
        "budget_breaches": len(breaches),
    }
    out(f"slo-budget: n={n} hbm={hbm_seqs}/{n} seqs resident "
        f"chaos=slow_host_transfer:{delay_ms}ms "
        f"targets ttft={ttft_slo_s}s tpot={tpot_slo_s}s")
    out(f"  stream  : {wall:.3f}s  swaps {mgr.swap_outs}  "
        f"injected {result['stall_injected_s'] * 1e3:.0f}ms over "
        f"{len(fired)} pull(s) (oracle-exact)")
    out(f"  tpot p99-gap stall share "
        f"{dig['tpot_p99_stall_share']:.0%}  coverage "
        f"{dig['coverage_frac']:.1%}")
    out("  " + budgetlib.format_budget(breaches).replace("\n", "\n  "))
    if explain:
        out("  " + explainlib.format_explain(dig)
            .replace("\n", "\n  "))
    return result


def shared_smoke_config():
    """The CI prefix-sharing shape (tier-1 via
    tests/test_bench_serving.py): the smoke model on a template-pool +
    conversation-tree stream (2 templates × per-request tails, a
    quarter of arrivals extending an earlier prompt), small enough for
    seconds on the CPU mesh, shared enough that the matched span is
    well past the 0.3 skip-fraction floor the row asserts."""
    base = smoke_config()
    return dict(cfg=base["cfg"], params=base["params"], n=16, slots=4,
                chunk=8, page_size=16, n_templates=2, template_len=32,
                tail_lens=(4, 8, 12), budgets=(16, 32),
                tree_frac=0.25, rate_rps=200.0, seed=12)


def shared_full_config(on_tpu: bool):
    """The chip shape (never yet run on one): the scenario
    model on a heavier template mix — on chip the first real-HBM
    number for the dedup'd arena. The decode route is pinned to
    "gather": prefix sharing mirrors the einsum prefill path, and the
    engine refuses flash configs whose page-multiple rungs would send
    monolithic prefills through the Pallas kernel instead (the
    constructor guard) — decode_attn is a dispatch knob, so the
    scenario params are reused as-is."""
    base = scenario_full_config(on_tpu)
    cfg = dataclasses.replace(base["cfg"], decode_attn="gather")
    return dict(cfg=cfg, params=base["params"],
                n=48 if on_tpu else 24, slots=8 if on_tpu else 4,
                chunk=16, page_size=256 if on_tpu else 16,
                n_templates=3, template_len=512 if on_tpu else 32,
                tail_lens=(16, 32, 64) if on_tpu else (4, 8, 12),
                budgets=(64, 128) if on_tpu else (16, 32),
                tree_frac=0.25, rate_rps=64.0, seed=12)


def run_shared(*, cfg, params, n, slots, chunk, page_size, n_templates,
               template_len, tail_lens, budgets, tree_frac, rate_rps,
               seed=12, quiet=False):
    """The prefix-sharing row (round 12): ONE shared-prefix open-loop
    stream (harness/loadgen.make_shared_prefix_schedule — template
    pool + conversation-tree turns) served by (a) a PRIVATE-pages
    engine (every request prefills its full prompt) and (b) the
    SHARING-AWARE arena (``prefix_cache=True``: radix match at
    admission, matched pages mapped read-only, tail-only prefill).
    The ORACLE runs before any number: both engines token-identical
    to standalone ``paged_generate`` per request — sharing must be
    invisible in the tokens. Reports ``shared_goodput_tok_s``
    (SLO-attained tok/s of the sharing engine) and
    ``prefill_skip_frac`` (fraction of submitted prompt tokens whose
    prefill the radix match skipped — asserted > 0.3 on the template
    mix), the two keys ``bench.py`` captures and ``harness/regress.py``
    gates (docs/prefix_cache.md)."""
    schedule = loadgen.make_shared_prefix_schedule(
        n, rate_rps=rate_rps, classes=SCENARIO_CLASSES,
        n_templates=n_templates, template_len=template_len,
        tail_lens=tail_lens, budgets=budgets, tree_frac=tree_frac,
        seed=seed)
    out = print if not quiet else (lambda *a, **k: None)
    prompts = {r.index: loadgen.materialize_prompt(schedule, r.index,
                                                   cfg.vocab)
               for r in schedule.requests}
    targets = slo.targets_from_classes(SCENARIO_CLASSES)
    # an ALIGNED ladder (multiples of the page size, which the sharing
    # engine requires aligned to decode.PREFIX_ALIGN) fit to the
    # stream: sharing is rung-keyed, so rungs double as sharing scopes
    lengths = [p.size for p in prompts.values()]
    buckets = tuple(sorted({-(-int(L) // page_size) * page_size
                            for L in lengths}))
    pages_per_seq = max(
        ContinuousBatcher.pages_needed(
            len(prompts[r.index]), r.max_new, page_size,
            padded_len=pad_to_bucket(buckets, len(prompts[r.index])))
        for r in schedule.requests)
    pool_pages = slots * pages_per_seq
    total_tokens = sum(r.max_new for r in schedule.requests)
    arrivals = [
        (r.t_arrival_s, dict(prompt=prompts[r.index],
                             max_new=r.max_new, seq_id=r.index,
                             priority=r.priority,
                             deadline_s=r.deadline_s))
        for r in schedule.requests
    ]

    def run_one(share: bool):
        eng = ContinuousBatcher(
            params, cfg, slots=slots, pool_pages=pool_pages,
            pages_per_seq=pages_per_seq, page_size=page_size,
            chunk=chunk, prompt_buckets=buckets, slo=targets,
            prefix_cache=share)
        got = eng.run(arrivals=list(arrivals))
        return got, eng

    # warmup + best-of-reps: open-loop pacing means admission grouping
    # (and with it the (matched, rung) tail-prefill jit variants) can
    # differ run to run, so one warmup cannot guarantee the timed leg
    # compiles nothing — min-of-reps (the harness timing discipline)
    # keeps a stray in-leg XLA compile out of the GATED goodput number;
    # the (t, outputs, engine) triple stays from the same rep so the
    # SLO math is consistent with the wall time it divides by
    def best_of(share: bool, reps: int = 2):
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            got, eng = run_one(share)
            dt = time.perf_counter() - t0
            if best is None or dt < best[0]:
                best = (dt, got, eng)
        return best

    run_one(False)
    run_one(True)
    t_priv, priv_out, priv_eng = best_of(False)
    t_shr, shr_out, shr_eng = best_of(True)

    # oracle before any number is believed: sharing is invisible in
    # the tokens — both engines equal standalone paged decode
    for r in schedule.requests:
        want = np.asarray(paged_generate(
            params, jnp.asarray(prompts[r.index])[None], cfg,
            r.max_new, page_size=page_size))[0]
        np.testing.assert_array_equal(priv_out[r.index], want,
                                      err_msg=f"private seq {r.index}")
        np.testing.assert_array_equal(shr_out[r.index], want,
                                      err_msg=f"shared seq {r.index}")
    skip = shr_eng.prefill_skip_frac
    assert skip > 0.3, (
        f"prefill_skip_frac {skip:.3f} <= 0.3 on the template mix — "
        "the radix match is not finding the shared prefixes")
    assert shr_eng._prefix.hits > 0, "no prefix-cache hit fired"

    tot_priv = priv_eng.last_slo["total"]
    tot_shr = shr_eng.last_slo["total"]
    result = {
        "t_private": t_priv, "t_shared": t_shr, "tokens": total_tokens,
        "tokens_per_s_private": total_tokens / t_priv,
        "tokens_per_s_shared": total_tokens / t_shr,
        "private_goodput_tok_s": tot_priv["goodput_tok_s"]
        * priv_eng._serve_s / t_priv if t_priv > 0 else 0.0,
        "shared_goodput_tok_s": tot_shr["goodput_tok_s"]
        * shr_eng._serve_s / t_shr if t_shr > 0 else 0.0,
        "prefill_skip_frac": skip,
        "prefix_hits": shr_eng._prefix.hits,
        "prefix_misses": shr_eng._prefix.misses,
        "ladder": buckets, "pool_pages": pool_pages,
        "bubble_frac": shr_eng.last_bubble_frac,
        "schedule": schedule.spec,
    }
    out(f"shared-prefix: n={n} slots={slots} chunk={chunk} "
        f"templates={n_templates}x{template_len} tree={tree_frac:.0%} "
        f"pool={pool_pages}p tokens={total_tokens}")
    out(f"  private : {t_priv:.3f}s  "
        f"{result['tokens_per_s_private']:,.1f} tok/s  "
        f"goodput {result['private_goodput_tok_s']:,.1f}")
    out(f"  shared  : {t_shr:.3f}s  "
        f"{result['tokens_per_s_shared']:,.1f} tok/s  "
        f"goodput {result['shared_goodput_tok_s']:,.1f}  "
        f"skip {skip:.1%}  hits {shr_eng._prefix.hits}/"
        f"{shr_eng._prefix.hits + shr_eng._prefix.misses}")
    out(f"  prefill skipped {skip:.1%} of prompt tokens "
        "(token-identical to private pages, oracle-exact)")
    return result


def quantized_smoke_config():
    """The CI quantized-decode shape (tier-1 via
    tests/test_bench_serving.py): the smoke model served with a
    quantized KV pool — small enough for seconds on the CPU mesh, big
    enough that the pool-bytes fraction is geometry-dominated (the
    scale pools' overhead shows honestly)."""
    base = smoke_config()
    return dict(cfg=base["cfg"], params=base["params"], n=8, slots=4,
                chunk=16, page_size=16, prompt_len=32, max_budget=64,
                kv_dtype="int8")


def quantized_full_config(on_tpu: bool):
    """The chip shape (never yet run on one): the scenario
    model with the attention-route RACE on — the quantized stream runs
    once on the gather route and once on ``paged_flash``
    (ops/paged_attention.py) at real VMEM limits. The interpret-mode
    ~10x penalty that forced serving onto the gather route off-TPU is
    exactly the number the chip race replaces."""
    base = scenario_full_config(on_tpu)
    prompt_top = 128 if on_tpu else 32
    budget_top = 256 if on_tpu else 96
    return dict(cfg=base["cfg"], params=base["params"],
                n=24 if on_tpu else 12, slots=8 if on_tpu else 4,
                chunk=16, page_size=256 if on_tpu else 16,
                prompt_len=prompt_top, max_budget=budget_top,
                kv_dtype="int8", race_attn=on_tpu)


def run_quantized(*, cfg, params, n, slots, chunk, page_size,
                  prompt_len, max_budget, kv_dtype="int8",
                  quant_weights=False, race_attn=False, quiet=False):
    """The quantized-decode row (round 13): one mixed stream served by
    (a) the compute-dtype baseline engine and (b) an engine whose KV
    pool stores ``kv_dtype`` (int8/fp8 one-byte pages + per-row f32
    scales; ``quant_weights`` additionally runs the int8
    per-output-channel weight path through every decode matmul,
    models/quantization.py).

    TWO oracles before any number is believed:

    - **exact within the precision**: the quantized engine's tokens
      equal standalone ``paged_generate`` under the SAME quantized
      config — quantization changes the math, never the scheduling;
    - **the precision law across precisions**
      (:func:`hpc_patterns_tpu.models.quantization.precision_law`):
      teacher-forced greedy top-1 agreement and TV-distance bounds vs
      the baseline precision — token identity cannot hold across
      precisions, so the law is the contract (docs/quantization.md).

    Reports ``quant_goodput_tok_s`` (SLO-attained tok/s of the
    quantized engine) and ``kv_pool_bytes_frac`` (quantized pool bytes
    / a bf16 pool at EQUAL geometry — the capacity headline; int8 and
    fp8 land ~0.53, i.e. the residency manager's host tier, the
    migration wire, and the prefix arena's resident count all roughly
    double), the two keys ``bench.py`` captures and
    ``harness/regress.py`` gates. ``race_attn``: also time the
    quantized stream on ``decode_attn="paged_flash"`` vs the gather
    route (the chip leg; pointless under interpret mode)."""
    from hpc_patterns_tpu.harness.cli import resolve_kv_cache_dtype
    from hpc_patterns_tpu.models.quantization import (
        precision_law,
        quantize_weights_int8,
    )

    out = print if not quiet else (lambda *a, **k: None)
    compute_dt, kv = resolve_kv_cache_dtype(kv_dtype, note=out)
    if kv == "compute":
        raise SystemExit(
            f"--quant needs a quantized --kv-dtype (int8/fp8), got "
            f"{kv_dtype!r} — the compute-dtype rows are the ordinary "
            "serving benches")
    over = {"kv_cache_dtype": kv}
    if compute_dt:
        over["dtype"] = compute_dt
    cfg_q = dataclasses.replace(cfg, **over)
    params_q = quantize_weights_int8(params) if quant_weights else params

    rng = np.random.RandomState(7)
    lengths = [prompt_len // 2, (3 * prompt_len) // 4, prompt_len]
    reqs = []
    for _ in range(n):
        t = int(rng.choice(lengths))
        prompt = rng.randint(0, cfg.vocab, size=t).astype(np.int32)
        budget = int(rng.choice(
            [max(1, max_budget // 2), max_budget], p=[0.4, 0.6]))
        reqs.append((prompt, budget))
    total_tokens = sum(b for _, b in reqs)
    buckets = bucket_ladder(prompt_len)
    targets = slo.targets_from_classes(SCENARIO_CLASSES)
    pages_per_seq = max(
        ContinuousBatcher.pages_needed(len(p), b, page_size,
                                       padded_len=pad_to_bucket(
                                           buckets, len(p)))
        for p, b in reqs)
    pool = slots * pages_per_seq

    # the precision LAW gate first — broken dequant must fail before
    # any throughput number exists (TV toward 1, agreement toward 1/V)
    law_prompts = np.stack([
        rng.randint(0, cfg.vocab, size=prompt_len).astype(np.int32)
        for _ in range(4)])
    law = precision_law(params, cfg, params_q, cfg_q, law_prompts,
                        steps=8)
    law.check()

    def run_one(c, p):
        eng = ContinuousBatcher(
            p, c, slots=slots, pool_pages=pool,
            pages_per_seq=pages_per_seq, page_size=page_size,
            chunk=chunk, prompt_buckets=buckets, slo=targets)
        ids = [eng.submit(pr, b) for pr, b in reqs]
        got = eng.run()
        return {i: got[s] for i, s in enumerate(ids)}, eng

    def timed(c, p):
        run_one(c, p)  # warmup (compiles)
        t0 = time.perf_counter()
        got, eng = run_one(c, p)
        return time.perf_counter() - t0, got, eng

    t_base, base_out, base_eng = timed(cfg, params)
    t_q, q_out, q_eng = timed(cfg_q, params_q)

    # exact oracle WITHIN the precision: the quantized engine must be
    # token-identical to standalone quantized decode — and the
    # baseline to baseline decode — before any number is believed
    for i, (prompt, b) in enumerate(reqs):
        want = np.asarray(paged_generate(
            params, jnp.asarray(prompt)[None], cfg, b,
            page_size=page_size))[0]
        np.testing.assert_array_equal(base_out[i], want,
                                      err_msg=f"baseline seq {i}")
        want_q = np.asarray(paged_generate(
            params_q, jnp.asarray(prompt)[None], cfg_q, b,
            page_size=page_size))[0]
        np.testing.assert_array_equal(q_out[i], want_q,
                                      err_msg=f"quantized seq {i}")

    # pool bytes at EQUAL geometry: the quantized pool vs a bf16 pool
    # (the capacity headline — measured from real allocations, scale
    # pools included, table excluded on both sides)
    from hpc_patterns_tpu.models.decode import init_paged_cache

    def pool_bytes(c):
        cache = init_paged_cache(c, slots, pages_per_seq, page_size,
                                 pool_pages=pool + 1)
        return sum(int(arr.nbytes) for name, pools in cache.items()
                   if name != "table" for arr in pools)

    bf16_cfg = dataclasses.replace(cfg, dtype="bfloat16",
                                   kv_cache_dtype="compute")
    q_bytes = pool_bytes(cfg_q)
    bf16_bytes = pool_bytes(bf16_cfg)
    bytes_frac = q_bytes / bf16_bytes

    tot_base = base_eng.last_slo["total"]
    tot_q = q_eng.last_slo["total"]
    result = {
        "t_baseline": t_base, "t_quant": t_q, "tokens": total_tokens,
        "tokens_per_s_baseline": total_tokens / t_base,
        "tokens_per_s_quant": total_tokens / t_q,
        "baseline_goodput_tok_s": tot_base["goodput_tok_s"]
        * base_eng._serve_s / t_base if t_base > 0 else 0.0,
        "quant_goodput_tok_s": tot_q["goodput_tok_s"]
        * q_eng._serve_s / t_q if t_q > 0 else 0.0,
        "kv_pool_bytes_frac": bytes_frac,
        "kv_pool_bytes": q_bytes, "bf16_pool_bytes": bf16_bytes,
        "kv_dtype": kv, "quant_weights": bool(quant_weights),
        "greedy_agreement": law.greedy_agreement,
        "tv_mean": law.tv_mean, "tv_max": law.tv_max,
        "baseline_bubble_frac": base_eng.last_bubble_frac,
        "quant_bubble_frac": q_eng.last_bubble_frac,
    }
    out(f"quantized[{kv}{'+w8' if quant_weights else ''}]: n={n} "
        f"slots={slots} chunk={chunk} pool={pool}p "
        f"tokens={total_tokens}")
    out(f"  baseline : {t_base:.3f}s  "
        f"{result['tokens_per_s_baseline']:,.1f} tok/s  goodput "
        f"{result['baseline_goodput_tok_s']:,.1f}  bubble "
        f"{result['baseline_bubble_frac']:.1%}")
    out(f"  {kv:<9}: {t_q:.3f}s  "
        f"{result['tokens_per_s_quant']:,.1f} tok/s  goodput "
        f"{result['quant_goodput_tok_s']:,.1f}  bubble "
        f"{result['quant_bubble_frac']:.1%}")
    out(f"  kv pool bytes {q_bytes:,} = {bytes_frac:.3f}x the bf16 "
        f"pool ({bf16_bytes:,}) at equal residents")
    out(f"  precision law: greedy agreement "
        f"{law.greedy_agreement:.3f}, TV mean {law.tv_mean:.4f} / "
        f"max {law.tv_max:.4f} over {law.steps} teacher-forced steps "
        "(oracle-exact within the precision)")

    if race_attn:
        # the kernel race per precision: the SAME quantized stream on
        # the gather route vs the exact-softmax paged kernel — a chip
        # number (interpret mode would measure the ~10x per-grid-point
        # host cost, not the kernel)
        cfg_pf = dataclasses.replace(cfg_q, decode_attn="paged_flash")
        cfg_ga = dataclasses.replace(cfg_q, decode_attn="gather")
        t_ga, ga_out, _ = timed(cfg_ga, params_q)
        t_pf, pf_out, _ = timed(cfg_pf, params_q)
        # the route-parity claim ON THIS BACKEND: the exact-softmax
        # kernel mirrors the gather math. Interpret mode holds that
        # BITWISE even for quantized pools (test-pinned), so any token
        # flip fails loudly; on chip a quantized pool's dequant
        # multiply order may legally differ by a ULP
        # (ops/paged_attention.py), so the tolerance tier allows
        # near-tie argmax flips but pins agreement — a broken kernel
        # sends agreement toward vocab-random, not 0.999
        n_tok = n_flip = 0
        for i in sorted(pf_out):
            a, b = np.asarray(pf_out[i]), np.asarray(ga_out[i])
            n_tok += a.size
            n_flip += int(np.sum(a != b))
        if jax.default_backend() == "tpu":
            agreement = 1.0 - n_flip / max(n_tok, 1)
            assert agreement >= 0.999, (
                f"route race token agreement {agreement:.4f} < 0.999 "
                f"({n_flip}/{n_tok} flips) — beyond ULP near-tie "
                "divergence, the paged_flash kernel is broken here")
        else:
            assert n_flip == 0, (
                f"paged_flash vs gather: {n_flip}/{n_tok} token "
                "mismatches in interpret mode (the bitwise contract)")
        result["tokens_per_s_gather"] = total_tokens / t_ga
        result["tokens_per_s_paged_flash"] = total_tokens / t_pf
        out(f"  route race [{kv}]: gather "
            f"{result['tokens_per_s_gather']:,.1f} tok/s vs "
            f"paged_flash {result['tokens_per_s_paged_flash']:,.1f} "
            f"tok/s ({t_ga / t_pf:.2f}x)")
    return result


ELASTIC_CLASSES = (
    # generous latency targets: attainment in the CI shape is decided
    # by SERVING (a shed never attains), not by wall-clock jitter —
    # the deterministic margin the elastic-vs-static comparison gates
    loadgen.PriorityClass("interactive", 0, weight=0.5,
                          ttft_slo_s=30.0, tpot_slo_s=5.0),
    loadgen.PriorityClass("batch", 1, weight=0.5),
)


def elastic_smoke_config():
    """The CI elastic shape (tier-1 via tests/test_bench_serving.py):
    the smoke model on a diurnal open-loop ramp whose front-loaded
    peak oversubscribes a 2-replica plane, with a seeded
    ``die:replica=1`` chaos fault killing one replica while it
    provably holds in-flight rows. DELIBERATELY on the chaos
    scenario's engine geometry (slots/pool/ladder/chunk of
    ``scenario_smoke_config`` — same pool shapes, same rungs, same
    prompt/budget points): the suite runs the scenario row first, so
    every greedy jit variant the elastic legs touch is already warm
    and the tier-1 cost is serving, not compiling. The sampled leg
    is smaller still (its sampling variants are the one fresh
    compile family)."""
    base = smoke_config()
    return dict(cfg=base["cfg"], params=base["params"], n=10, slots=3,
                chunk=8, page_size=16, prompt_len=32, max_budget=24,
                rate_rps=200.0, period_s=0.4, depth=0.8, seed=17,
                die_replica=1, die_at=2, sampled_n=4,
                # the scenario geometry (see docstring): ladder top
                # 192, 12-page table, 25-page arena per replica
                ladder_top=192, pages_per_seq=12, pool_pages=25,
                budgets=(16, 24))


def elastic_full_config(on_tpu: bool):
    """The chip shape (never yet run on one): the scenario
    model on a longer diurnal ramp — on chip the first real number
    for warm spin-up (host->HBM param paging at real DMA rates vs a
    real on-device init) and for the elastic plane's goodput-per-
    replica-round at chip throughput."""
    base = scenario_full_config(on_tpu)
    prompt_top = 128 if on_tpu else 32
    budget_top = 256 if on_tpu else 64
    return dict(cfg=base["cfg"], params=base["params"],
                n=32 if on_tpu else 20, slots=4 if on_tpu else 2,
                chunk=16, page_size=256 if on_tpu else 16,
                prompt_len=prompt_top, max_budget=budget_top,
                rate_rps=24.0, period_s=1.0, depth=0.8, seed=17,
                die_replica=1, die_at=3, sampled_n=6)


def run_elastic(*, cfg, params, n, slots, chunk, page_size, prompt_len,
                max_budget, rate_rps, period_s, depth, seed=17,
                die_replica=1, die_at=2, sampled_n=5,
                ladder_top=None, pages_per_seq=None, pool_pages=None,
                budgets=None, quiet=False):
    """The ELASTIC-PLANE row (round 14): one diurnal open-loop ramp
    under replica-death chaos, served by (a) a FIXED 2-replica plane
    (a death there ends in shedding — the ROADMAP's nobody-closes-
    the-loop baseline) and (b) the autoscaled
    :class:`~hpc_patterns_tpu.serving_plane.autoscaler.
    ElasticServingPlane` — SLO-feedback scale-up on warm
    residency-pulled params, checkpoint resume after the death, drain
    via migration on the way down.

    The robustness verdict, asserted before any number is believed:

    - the seeded ``die`` fault FIRED on both legs and the static
      plane's victim held in-flight rows (the fault did real damage);
    - the static plane demonstrably SHEDS (``shed_on_death >= 1``)
      while the elastic plane serves everything (nothing shed);
    - the elastic plane's per-class SLO attainment STRICTLY exceeds
      the static plane's on the same replayed schedule;
    - every served stream — death-resumed and drain-migrated rows
      included — is byte-exact vs standalone ``paged_generate``,
      GREEDY and (on the sampled leg, via the checkpointed key state)
      SAMPLED;
    - warm spin-up (the ``plane.spinup`` window's host span) is
      measurably faster than a cold ``init_params`` + engine build.

    Reports ``elastic_slo_attainment`` and
    ``goodput_per_replica_round`` (SLO-attained tokens per live
    replica-round — efficiency, not just peak), the two keys
    ``bench.py`` captures and ``harness/regress.py`` gates."""
    from hpc_patterns_tpu.serving_plane.autoscaler import (
        Autoscaler,
        AutoscalerPolicy,
        ElasticServingPlane,
        WarmParamPool,
    )
    from hpc_patterns_tpu.serving_plane.router import (
        Replica,
        ServingPlane,
    )

    out = print if not quiet else (lambda *a, **k: None)
    schedule = loadgen.make_schedule(
        n, rate_rps=rate_rps, classes=ELASTIC_CLASSES,
        prompt_lens=(prompt_len // 2, prompt_len),
        budgets=budgets or (max(1, max_budget // 2), max_budget),
        process="diurnal", seed=seed, period_s=period_s, depth=depth)
    rng = np.random.RandomState(seed + 1)
    prompts = {r.index: rng.randint(0, cfg.vocab, size=r.prompt_len)
               .astype(np.int32) for r in schedule.requests}
    targets = slo.targets_from_classes(ELASTIC_CLASSES)
    # the ladder covers prompt + budget: a death-resume's prompt is
    # the original plus everything already emitted, and a resume that
    # left the ladder could not re-admit anywhere (the run_scenario
    # sizing rule). ``ladder_top``/``pages_per_seq``/``pool_pages``
    # override to share another row's engine geometry (the smoke
    # rides the scenario row's warm jit caches)
    buckets = bucket_ladder(ladder_top or (prompt_len + max_budget))
    if pages_per_seq is None:
        pages_per_seq = max(
            EngineCore.pages_needed(r.prompt_len, r.max_new, page_size,
                                    padded_len=pad_to_bucket(
                                        buckets, r.prompt_len))
            for r in schedule.requests)
    pool = pool_pages or slots * pages_per_seq
    chaos_spec = (f"die:replica={die_replica},at={die_at},"
                  "site=replica_round")
    policy = AutoscalerPolicy(min_replicas=2, max_replicas=4,
                              up_queue=1.5, down_queue=0.25,
                              cooldown_rounds=3, window=4)

    def mk_engine(p, **skw):
        return EngineCore(
            p, cfg, slots=slots, pool_pages=pool,
            pages_per_seq=pages_per_seq, page_size=page_size,
            chunk=chunk, prompt_buckets=buckets, **skw)

    def arrivals(sched):
        return [(r.t_arrival_s,
                 dict(prompt=prompts[r.index], max_new=r.max_new,
                      priority=r.priority, deadline_s=r.deadline_s))
                for r in sched.requests]

    def run_static():
        plane = ServingPlane(
            [Replica(mk_engine(params), name=f"r{i}")
             for i in range(2)], slo=targets)
        chaoslib.configure(chaos_spec)
        try:
            got = plane.run(arrivals=arrivals(schedule))
            died = [e for e in chaoslib.injections()
                    if e["kind"] == "die"]
        finally:
            chaoslib.reset()
        assert died, "the seeded replica-death fault never fired"
        return got, plane

    def run_autoscaled(**skw):
        pool_w = WarmParamPool(params)
        plane = ElasticServingPlane(
            [Replica(mk_engine(params, **skw), name=f"r{i}")
             for i in range(2)],
            engine_factory=lambda p: mk_engine(p, **skw),
            warm_pool=pool_w,
            autoscaler=Autoscaler(policy), slo=targets)
        chaoslib.configure(chaos_spec)
        try:
            got = plane.run(arrivals=arrivals(schedule))
            died = [e for e in chaoslib.injections()
                    if e["kind"] == "die"]
        finally:
            chaoslib.reset()
        assert died, "the seeded replica-death fault never fired"
        return got, plane

    # no dedicated warmup leg: every GATED number here is wall-clock
    # free (attainment fractions; goodput per replica-ROUND — the
    # wall cancels out of attained_tokens / replica_rounds), so an
    # in-leg compile cannot move the gate. The tier-1 smoke
    # additionally rides the scenario row's warm caches by sharing
    # its engine geometry (elastic_smoke_config), and the one timed
    # claim (warm spin-up < cold init) compiles nothing on either
    # side (device_put vs eager init_params; pool allocation common).
    static_out, static = run_static()
    elastic_out, elastic = run_autoscaled()

    # the fault did real damage: the static victim held in-flight
    # rows, so the fixed plane SHEDS — the degraded mode this row
    # exists to beat — while the elastic plane serves everything
    assert static.deaths and elastic.deaths, "no replica died"
    assert static.shed_on_death >= 1, (
        "the static plane's dead replica held nothing — the death "
        "perturbed neither leg, the comparison measured nothing")
    assert elastic.shed_on_death == 0, (
        f"elastic plane shed {elastic.shed_on_death} on the death it "
        "exists to absorb")
    assert len(elastic.spinup_s) >= 1, "the autoscaler never scaled up"

    # oracle before any number is believed — death-resumed rows
    # included: every served stream byte-exact vs standalone (GREEDY;
    # the sampled leg below covers the key-checkpoint path)
    oracle: dict = {}

    def check(outs, plane):
        for r in schedule.requests:
            ps = plane.stats.get(r.index)
            if ps is None or ps.get("outcome") != "ok":
                continue
            want = oracle.get(r.index)
            if want is None:
                want = oracle[r.index] = np.asarray(paged_generate(
                    params, jnp.asarray(prompts[r.index])[None], cfg,
                    r.max_new, page_size=page_size))[0]
            np.testing.assert_array_equal(
                outs[r.index], want, err_msg=f"seq {r.index}")

    check(static_out, static)
    check(elastic_out, elastic)
    for r in schedule.requests:
        assert elastic.stats[r.index]["outcome"] == "ok", (
            f"elastic plane failed to serve seq {r.index}: "
            f"{elastic.stats[r.index]}")

    att_static = static.last_slo["total"]["attained_frac"]
    att_elastic = elastic.last_slo["total"]["attained_frac"]
    assert att_elastic > att_static, (
        f"autoscaled attainment {att_elastic:.3f} does not exceed "
        f"static {att_static:.3f} — the loop closed nothing")

    # warm spin-up vs cold init: the plane.spinup span (pull parked
    # host params + build the engine on them) against a cold
    # init_params + engine build — min-of-2 each side, the standard
    # load-spike shield
    def cold_once():
        t0 = time.perf_counter()
        p = init_params(jax.random.PRNGKey(0), cfg)
        eng = mk_engine(p)
        jax.block_until_ready((p, eng.temps))
        return time.perf_counter() - t0

    cold_init_s = min(cold_once() for _ in range(2))
    warm_spinup_s = min(elastic.spinup_s)
    assert warm_spinup_s < cold_init_s, (
        f"warm spin-up {warm_spinup_s * 1e3:.1f}ms not faster than "
        f"cold init {cold_init_s * 1e3:.1f}ms — the residency-backed "
        "pool bought nothing")

    # the SAMPLED leg: a smaller stream through sampled engines — the
    # death-resume must continue each stream from the CHECKPOINTED
    # key state, byte-exact vs standalone with the same request key.
    # Submitted UP FRONT (not open-loop): the leg exists to pin the
    # key checkpoint under death, so the victim must STRUCTURALLY
    # hold in-flight rows when the fault fires — the greedy legs own
    # the open-loop ramp realism
    sprompts = [rng.randint(0, cfg.vocab,
                            size=int(rng.choice([prompt_len // 2,
                                                 prompt_len])))
                .astype(np.int32) for _ in range(sampled_n)]
    sbudget = max(2 * chunk, max_budget // 4)
    skw = dict(temperature=0.7, top_k=8, seed=0)
    pool_s = WarmParamPool(params)
    es = ElasticServingPlane(
        [Replica(mk_engine(params, **skw), name=f"s{i}")
         for i in range(2)],
        engine_factory=lambda p: mk_engine(p, **skw),
        warm_pool=pool_s,
        autoscaler=Autoscaler(policy), slo=targets)
    chaoslib.configure("die:replica=0,at=1,site=replica_round")
    try:
        sids = [es.submit(p, sbudget) for p in sprompts]
        got_s = es.run()
    finally:
        chaoslib.reset()
    assert es.deaths, "sampled-leg death never fired"
    assert es.resumed, (
        "the sampled-leg victim held no in-flight rows — the key-"
        "checkpoint path went unexercised")
    key_src = es.replicas[1].engine
    for sid, p in zip(sids, sprompts):
        assert es.stats[sid]["outcome"] == "ok", (
            f"sampled seq {sid}: {es.stats[sid]}")
        want = np.asarray(paged_generate(
            params, jnp.asarray(p)[None], cfg, sbudget,
            page_size=page_size, key=key_src.request_key(sid),
            temperature=0.7, top_k=8))[0]
        np.testing.assert_array_equal(
            got_s[sid], want, err_msg=f"sampled seq {sid}")

    gppr = elastic.goodput_per_replica_round or 0.0
    per_class = {
        prio: {"static": static.last_slo["classes"]
               .get(prio, {}).get("attained_frac"),
               "elastic": elastic.last_slo["classes"]
               .get(prio, {}).get("attained_frac")}
        for prio in sorted({c.priority for c in ELASTIC_CLASSES})
    }
    result = {
        "elastic_slo_attainment": att_elastic,
        "static_slo_attainment": att_static,
        "per_class_attainment": per_class,
        "goodput_per_replica_round": gppr,
        "static_goodput_per_replica_round":
            static.goodput_per_replica_round or 0.0,
        "static_shed_on_death": static.shed_on_death,
        "elastic_shed_on_death": elastic.shed_on_death,
        "spinups": len(elastic.spinup_s),
        "warm_spinup_s": warm_spinup_s,
        "cold_init_s": cold_init_s,
        "resumed": sorted(elastic.resumed),
        "drained": list(elastic.drained),
        "replica_rounds": elastic.replica_rounds,
        "static_replica_rounds": static.replica_rounds,
        "sampled_resumed": sorted(es.resumed),
        "schedule": schedule.spec,
    }
    out(f"elastic: n={n} slots={slots} chunk={chunk} pool={pool}p "
        f"diurnal(period={period_s}s depth={depth}) chaos="
        f"{chaos_spec}")
    out(f"  static  : attained {att_static:.1%}  shed-on-death "
        f"{static.shed_on_death}  replica-rounds "
        f"{static.replica_rounds}")
    out(f"  elastic : attained {att_elastic:.1%}  shed-on-death 0  "
        f"spinups {len(elastic.spinup_s)}  resumed "
        f"{sorted(elastic.resumed)}  replica-rounds "
        f"{elastic.replica_rounds}")
    out(f"  warm spin-up {warm_spinup_s * 1e3:.1f}ms vs cold init "
        f"{cold_init_s * 1e3:.1f}ms "
        f"({cold_init_s / warm_spinup_s:.1f}x)")
    out(f"  goodput/replica-round {gppr:,.2f} tok (static "
        f"{result['static_goodput_per_replica_round']:,.2f})")
    out("  oracle-exact on every served stream, greedy AND sampled "
        "(death-resumed rows included)")
    return result


def plane_smoke_config():
    """The CI plane shape (tier-1 via tests/test_bench_serving.py): a
    seeded open-loop two-class stream through (a) one engine, (b) a
    2-replica homogeneous plane, (c) the disaggregated 1-prefill/
    1-decode plane — small enough for seconds on the CPU mesh, long
    enough that most migrations land behind an in-flight decode chunk
    (the overlap floor the tier-1 test pins)."""
    base = smoke_config()
    return dict(cfg=base["cfg"], params=base["params"], n=12,
                slots=3, chunk=8, page_size=16, prompt_len=32,
                max_budget=64, rate_rps=200.0, seed=11)


def plane_full_config(on_tpu: bool):
    """The re-grounding shape: the scenario model at a heavier stream."""
    base = scenario_full_config(on_tpu)
    prompt_top = 128 if on_tpu else 32
    budget_top = 256 if on_tpu else 128
    return dict(cfg=base["cfg"], params=base["params"], n=32,
                slots=8 if on_tpu else 4, chunk=16,
                page_size=256 if on_tpu else 16,
                prompt_len=prompt_top, max_budget=budget_top,
                rate_rps=32.0, seed=11,
                # per-chip replica placement (own weight copy, real
                # cross-device KV migration) is a chip-leg claim; the
                # CPU's virtual devices share one host
                place_on_devices=on_tpu)


def devices_share_host(devs) -> bool:
    """True when the 'distinct' devices replicas were placed on are
    virtual shards of ONE host (the CPU mesh under
    ``--xla_force_host_platform_device_count``): placement still pins
    arrays and exercises the real transfer paths, but every copy
    crosses the same memory — so cross-device timings on such a mesh
    are mechanism proofs, not speed claims. The plane row prints this
    loudly instead of letting the CPU numbers impersonate a chip
    result (tests/test_bench_serving.py pins the detection)."""
    if len(devs) < 2:
        return False
    return (all(d.platform == "cpu" for d in devs)
            or len({d.process_index for d in devs}) == 1
            and all(d.platform == "cpu" for d in devs))


def run_plane(*, cfg, params, n, slots, chunk, page_size, prompt_len,
              max_budget, rate_rps, seed=11, place_on_devices=False,
              migration="device_put", quiet=False):
    """The serving-plane row: one open-loop stream through three legs
    — single engine (the baseline), a homogeneous 2-replica plane
    (router + least-loaded placement), and the disaggregated
    1-prefill/1-decode plane (KV migration overlapped behind the
    decode chunk). Every leg's served sequences are token-exact vs
    standalone ``paged_generate`` before any number is believed; the
    ladder is FIT from the stream's observed prompt lengths
    (serving.fit_bucket_ladder — the round-6 autotuning item) and must
    beat the default ladder's expected padding.

    ``migration`` selects the 1p/1d leg's KV-handoff transport
    (``--migration dma|device_put|wire``, router.MIGRATION_TRANSPORTS);
    ``dma`` forces per-device placement (the paired remote-DMA kernel
    needs distinct chips) even when ``place_on_devices`` is off.
    Reports ``plane_goodput_tok_s`` (2-replica leg),
    ``kv_migration_overlap_frac``, ``dma_migration_overlap_frac`` and
    ``migration_bytes_per_round`` (1p/1d leg) — the keys ``bench.py``
    captures and ``harness/regress.py`` gates."""
    from hpc_patterns_tpu.serving_plane.router import (
        MIGRATION_TRANSPORTS,
        Replica,
        ServingPlane,
    )

    if migration not in MIGRATION_TRANSPORTS:
        raise SystemExit(
            f"--migration {migration!r} not in "
            f"{'/'.join(MIGRATION_TRANSPORTS)}")

    out = print if not quiet else (lambda *a, **k: None)
    rng = np.random.RandomState(13)
    schedule = loadgen.make_schedule(
        n, rate_rps=rate_rps, classes=SCENARIO_CLASSES,
        prompt_lens=(prompt_len // 4, prompt_len // 2, prompt_len),
        budgets=(max(1, max_budget // 8), max(1, max_budget // 2),
                 max_budget),
        budget_probs=(0.5, 0.3, 0.2), process="poisson", seed=seed)
    prompts = {r.index: rng.randint(0, cfg.vocab, size=r.prompt_len)
               .astype(np.int32) for r in schedule.requests}
    targets = slo.targets_from_classes(SCENARIO_CLASSES)

    # bucket-ladder autotuning from the OBSERVED prompt-length
    # distribution (open since round 6): the fit ladder must beat the
    # shape-blind default on expected padding, and both router and
    # engines run it
    lengths = [r.prompt_len for r in schedule.requests]
    default_ladder = bucket_ladder(prompt_len)
    buckets = fit_bucket_ladder(lengths, len(default_ladder),
                                max_len=prompt_len)
    pad_fit = expected_padding(buckets, lengths)
    pad_default = expected_padding(default_ladder, lengths)
    assert pad_fit <= pad_default, (
        f"fit ladder {buckets} pads worse than default "
        f"{default_ladder}: {pad_fit:.2f} vs {pad_default:.2f}")

    pages_per_seq = max(
        EngineCore.pages_needed(r.prompt_len, r.max_new, page_size,
                                padded_len=pad_to_bucket(
                                    buckets, r.prompt_len))
        for r in schedule.requests)
    pool = slots * pages_per_seq

    def mk_engine(device=None):
        # with a device, the replica gets its OWN weight copy there
        # (the multi-chip serving shape: one replica per chip, KV
        # migration a real cross-device copy). Off by default on the
        # CPU smoke: the virtual devices share one host, so placement
        # only adds copies — the chip leg is where it means something.
        import contextlib

        p = (jax.device_put(params, device) if device is not None
             else params)
        ctx = (jax.default_device(device) if device is not None
               else contextlib.nullcontext())
        with ctx:
            return EngineCore(
                p, cfg, slots=slots, pool_pages=pool,
                pages_per_seq=pages_per_seq, page_size=page_size,
                chunk=chunk, prompt_buckets=buckets)

    def arrivals():
        return [(r.t_arrival_s,
                 dict(prompt=prompts[r.index], max_new=r.max_new,
                      priority=r.priority, deadline_s=r.deadline_s))
                for r in schedule.requests]

    def run_single():
        eng = ContinuousBatcher(
            params, cfg, slots=slots, pool_pages=pool,
            pages_per_seq=pages_per_seq, page_size=page_size,
            chunk=chunk, prompt_buckets=buckets, slo=targets)
        got = eng.run(arrivals=arrivals())
        return got, eng

    # the DMA tier needs replicas on distinct devices — force
    # placement for it even on the CPU mesh (mechanism proof there;
    # devices_share_host() below keeps the wording honest)
    placed = place_on_devices or migration == "dma"

    def run_plane_leg(roles):
        devs = jax.devices() if placed else []
        replicas = []
        for i, role in enumerate(roles):
            dev = devs[i % len(devs)] if len(devs) > 1 else None
            replicas.append(Replica(mk_engine(dev), name=f"r{i}",
                                    role=role, device=dev))
        plane = ServingPlane(replicas, slo=targets,
                             migration=migration)
        got = plane.run(arrivals=arrivals())
        return got, plane

    oracle_cache: dict = {}

    def check(outs):
        # the standalone oracle depends only on (prompt, budget) —
        # identical across the three legs, so compute each once
        for r in schedule.requests:
            if len(outs.get(r.index, ())) == 0:
                continue  # shed: empty by contract
            want = oracle_cache.get(r.index)
            if want is None:
                want = oracle_cache[r.index] = np.asarray(
                    paged_generate(
                        params, jnp.asarray(prompts[r.index])[None],
                        cfg, r.max_new, page_size=page_size))[0]
            np.testing.assert_array_equal(
                outs[r.index], want, err_msg=f"plane seq {r.index}")

    # warmup (compiles shared across engines — one jit cache per
    # static config), then the timed legs
    run_single()
    t0 = time.perf_counter()
    single_out, single = run_single()
    t_single = time.perf_counter() - t0
    t0 = time.perf_counter()
    plane_out, plane2 = run_plane_leg(["both", "both"])
    t_plane = time.perf_counter() - t0
    t0 = time.perf_counter()
    disagg_out, disagg = run_plane_leg(["prefill", "decode"])
    t_disagg = time.perf_counter() - t0
    check(single_out)
    check(plane_out)
    check(disagg_out)
    assert disagg.migrations > 0, "disaggregated leg migrated nothing"

    tot1 = single.last_slo["total"]
    tot2 = plane2.last_slo["total"]
    totd = disagg.last_slo["total"]
    overlap = disagg.last_kv_migration_overlap_frac or 0.0
    dma_overlap = disagg.last_dma_migration_overlap_frac
    shared_host = placed and devices_share_host(jax.devices())
    result = {
        "t_single": t_single, "t_plane": t_plane, "t_disagg": t_disagg,
        "single_goodput_tok_s": tot1["goodput_tok_s"]
        * single._serve_s / t_single if t_single > 0 else 0.0,
        "plane_goodput_tok_s": tot2["goodput_tok_s"]
        * plane2._serve_s / t_plane if t_plane > 0 else 0.0,
        "disagg_goodput_tok_s": totd["goodput_tok_s"]
        * disagg._serve_s / t_disagg if t_disagg > 0 else 0.0,
        "kv_migration_overlap_frac": overlap,
        # DMA-tier-only overlap: None unless bundles actually rode the
        # paired kernel — a fallback cannot impersonate the DMA tier
        "dma_migration_overlap_frac": dma_overlap,
        "migration_bytes_per_round": disagg.migration_bytes_per_round,
        "migration_transport": migration,
        "migration_transports": dict(disagg.migration_transports),
        "placement_shares_host": shared_host,
        "migrations": disagg.migrations,
        "shed": tot2["shed"] + totd["shed"],
        "ladder_fit": list(buckets),
        "ladder_default": list(default_ladder),
        "expected_padding_fit": pad_fit,
        "expected_padding_default": pad_default,
        "schedule": schedule.spec,
    }
    out(f"plane: n={n} slots={slots}x chunk={chunk} "
        f"pool={pool}p ladder fit {buckets} "
        f"(E[pad] {pad_fit:.1f} vs default {pad_default:.1f})")
    out(f"  single    : {t_single:.3f}s  "
        f"{result['single_goodput_tok_s']:,.1f} goodput tok/s")
    out(f"  2-replica : {t_plane:.3f}s  "
        f"{result['plane_goodput_tok_s']:,.1f} goodput tok/s  "
        f"(routed {tot2['n']} reqs, shed {tot2['shed']})")
    out(f"  1p/1d     : {t_disagg:.3f}s  "
        f"{result['disagg_goodput_tok_s']:,.1f} goodput tok/s  "
        f"migrations {disagg.migrations}  "
        f"kv overlap {overlap:.1%}  transport {migration} "
        f"{dict(disagg.migration_transports)}  "
        + (f"dma overlap {dma_overlap:.1%}  "
           if dma_overlap is not None else "")
        + f"{result['migration_bytes_per_round']:,.0f} B/round")
    if shared_host:
        out("  NOTE: replicas placed on VIRTUAL devices sharing one "
            "host — cross-device copies are mechanism proofs, not "
            "bandwidth numbers (run the chip leg for those)")
    out("  oracle-exact on all three legs (migrated rows included)")
    return result


def fit_smoke_config():
    """The CI autofit shape (tier-1 via tests/test_bench_serving.py):
    the smoke model on a prefill-heavy long-tail stream whose bulk
    (60%) sits at a prompt length the default power-of-two ladder pads
    badly (40 -> 64, +60% prefill work on those rows) — the regime the
    fitted ladder exists for. Small decode budgets keep the row
    prefill-dominated so the padding win is visible in wall clock, and
    the shared smoke cfg/params ride the suite's warm decode caches."""
    base = smoke_config()
    return dict(cfg=base["cfg"], params=base["params"], n=16, slots=4,
                chunk=16, page_size=16, max_budget=32, reps=2,
                lengths=(16, 40, 64), length_probs=(0.2, 0.6, 0.2))


def fit_full_config(on_tpu: bool):
    """The chip shape (never yet run on one): the scenario
    model on the same long-tail length mix scaled to chip prompts —
    fit once from the recorded stream, then the fitted ladder must
    beat the default on real HBM prefills."""
    base = scenario_full_config(on_tpu)
    top = 512 if on_tpu else 64
    return dict(cfg=base["cfg"], params=base["params"],
                n=32 if on_tpu else 16, slots=8 if on_tpu else 4,
                chunk=16, page_size=256 if on_tpu else 16,
                max_budget=256 if on_tpu else 32, reps=2,
                lengths=(top // 4, (5 * top) // 8, top),
                length_probs=(0.2, 0.6, 0.2))


def run_fitted(*, cfg, params, n, slots, chunk, page_size, max_budget,
               lengths, length_probs, reps=2, autofit_path=None,
               fit_out=None, quiet=False):
    """The AUTOFIT row (round 16): observability becomes control. One
    long-tail stream served three times:

    1. the RECORDING leg — the default-ladder engine, untimed, with
       its ``emit`` stream captured to a RunLog JSONL (the profile
       artifact a production run would already have);
    2. ``harness.autofit`` fits a FittedConfig from that JSONL through
       the REAL ingestion path (``fit_paths`` -> ``dumps_config`` ->
       ``load_fitted`` round trip, exactly what the CLI does);
    3. the A/B — the default-ladder engine vs
       ``ContinuousBatcher.from_fitted`` on the SAME stream and pool
       geometry, warmed then timed min-of-reps;
    4. the BLAME A/B — a seeded decode-stall stream (the
       ``--slo-budget`` chassis) recorded, blame-fitted, and
       re-served under the fitted residency; asserts the fitter
       blames the injected ``prefetch_wait`` mechanism and that the
       blamed segment's pooled p99-gap-band share STRICTLY shrinks
       under the fitted config (attribution closed into control).

    Deterministic win first: the fitted ladder's expected padding must
    be STRICTLY below the default's on the observed lengths (the DP
    fitter's contract — no wall clock involved). Oracle before any
    number: every sequence on BOTH legs byte-exact vs standalone
    ``paged_generate``. Reports ``fitted_goodput_tok_s`` and
    ``autofit_gain_frac`` (fitted/default - 1), the two keys
    ``bench.py`` captures and ``harness/regress.py`` gates.

    ``autofit_path``: skip the recording leg and apply an existing
    FittedConfig (e.g. one fitted from a chip trace);
    ``fit_out``: also copy the fitted config JSON here."""
    import tempfile

    from hpc_patterns_tpu.harness import autofit as autofitlib
    from hpc_patterns_tpu.harness.runlog import RunLog

    out = print if not quiet else (lambda *a, **k: None)
    rng = np.random.RandomState(7)
    reqs = []
    for _ in range(n):
        t = int(rng.choice(lengths, p=length_probs))
        prompt = rng.randint(0, cfg.vocab, size=t).astype(np.int32)
        budget = int(rng.choice(
            [max(1, max_budget // 8), max(1, max_budget // 4),
             max_budget],
            p=[0.5, 0.3, 0.2]))
        reqs.append((prompt, budget))
    total_tokens = sum(b for _, b in reqs)
    obs_lengths = [len(p) for p, _ in reqs]
    default_ladder = bucket_ladder(max(obs_lengths))

    def mk_engine(buckets, pages, *, emit=None):
        return ContinuousBatcher(
            params, cfg, slots=slots, pool_pages=slots * pages,
            pages_per_seq=pages, page_size=page_size, chunk=chunk,
            prompt_buckets=buckets, emit=emit)

    def serve(eng):
        ids = [eng.submit(p, b) for p, b in reqs]
        got = eng.run()
        return {i: got[s] for i, s in enumerate(ids)}

    with tempfile.TemporaryDirectory() as tmp:
        cfg_path = os.path.join(tmp, "fitted.json")
        if autofit_path is None:
            # recording leg: the profile run the fitter consumes —
            # default config, untimed, emit -> RunLog JSONL
            log_path = os.path.join(tmp, "profile.jsonl")
            pages_rec = max(
                ContinuousBatcher.pages_needed(
                    len(p), b, page_size,
                    padded_len=pad_to_bucket(default_ladder, len(p)))
                for p, b in reqs)
            serve(mk_engine(default_ladder, pages_rec,
                            emit=RunLog(log_path).emit))
            fitted = autofitlib.fit_paths([log_path])
            with open(cfg_path, "w") as f:
                f.write(autofitlib.dumps_config(fitted))
        else:
            cfg_path = autofit_path
        # the round trip every consumer uses (CLI parity)
        fitted = autofitlib.load_fitted(cfg_path)
        if fit_out:
            with open(fit_out, "w") as f:
                f.write(autofitlib.dumps_config(fitted))
        fitted_ladder = autofitlib.ladder_from(fitted,
                                               max_seq=cfg.max_seq)
    assert fitted_ladder is not None, (
        "the fitted config carries no ladder — the recording leg "
        "emitted no serve_admit records")

    # the deterministic win BEFORE any wall clock: the DP fit must
    # strictly beat the shape-blind default on the observed lengths
    pad_fit = expected_padding(fitted_ladder, obs_lengths)
    pad_default = expected_padding(default_ladder, obs_lengths)
    assert pad_fit < pad_default, (
        f"fitted ladder {fitted_ladder} does not beat default "
        f"{default_ladder}: E[pad] {pad_fit:.2f} vs {pad_default:.2f}")

    # the A/B shares ONE pool geometry (sized for whichever ladder
    # pads a length worse) so the comparison is ladder-only
    pages_per_seq = max(
        ContinuousBatcher.pages_needed(
            len(p), b, page_size,
            padded_len=max(pad_to_bucket(default_ladder, len(p)),
                           pad_to_bucket(fitted_ladder, len(p))))
        for p, b in reqs)

    def mk_fitted():
        eng = ContinuousBatcher.from_fitted(
            params, cfg, fitted, slots=slots,
            pool_pages=slots * pages_per_seq,
            pages_per_seq=pages_per_seq, page_size=page_size,
            chunk=chunk)
        assert eng.prompt_buckets == fitted_ladder, (
            "from_fitted did not apply the fitted ladder")
        return eng

    # warmup (compiles), then min-of-reps timed legs; the timed runs
    # must add no prefill compiles
    serve(mk_engine(default_ladder, pages_per_seq))
    serve(mk_fitted())
    compiles_warm = prefill_cache_size()
    t_default = t_fitted = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        default_out = serve(mk_engine(default_ladder, pages_per_seq))
        t_default = min(t_default, time.perf_counter() - t0)
        t0 = time.perf_counter()
        fitted_out = serve(mk_fitted())
        t_fitted = min(t_fitted, time.perf_counter() - t0)
    assert prefill_cache_size() == compiles_warm, (
        "a timed leg recompiled prefill — the warmup missed a rung")

    # oracle before any number is believed: both legs byte-exact vs
    # standalone decode (the fitted ladder changes padding, never
    # tokens)
    for i, (prompt, b) in enumerate(reqs):
        want = np.asarray(paged_generate(
            params, jnp.asarray(prompt)[None], cfg, b,
            page_size=page_size))[0]
        np.testing.assert_array_equal(default_out[i], want,
                                      err_msg=f"default seq {i}")
        np.testing.assert_array_equal(fitted_out[i], want,
                                      err_msg=f"fitted seq {i}")

    # the BLAME A/B (attribution becomes control): a decode-stall
    # stream — the --slo-budget chassis, seeded slow_host_transfer
    # under a thrashing 2-resident tier — is RECORDED (emit stream +
    # its reqtrace snapshot, the same two inputs a production RunLog
    # carries), fitted, and re-served under the blame-fitted
    # residency. Two asserts close the loop: the fitter must blame
    # the injected mechanism (tpot/prefetch_wait, not the queued-
    # dominated TTFT shape every saturated stream shows), and the
    # blamed segment's pooled p99-gap-band share must STRICTLY
    # shrink under the fitted config.
    bcfg = slo_budget_smoke_config()
    bleg = dict(cfg=bcfg["cfg"], params=bcfg["params"], n=bcfg["n"],
                prompt_len=bcfg["prompt_len"],
                max_budget=bcfg["max_budget"],
                page_size=bcfg["page_size"], chunk=bcfg["chunk"],
                slots=bcfg["slots"], hbm_seqs=bcfg["hbm_seqs"],
                cold_n=bcfg["cold_n"])
    _tiered_stall_leg(**bleg, delay_ms=0)  # warmup (compiles)
    blame_records: list = []
    outs_rec, _be, _bm, snap_rec, _bf = _tiered_stall_leg(
        **bleg, delay_ms=bcfg["delay_ms"],
        emit=lambda **kw: blame_records.append(kw))
    blame_records.append(dict(snap_rec, kind="reqtrace"))
    bfit = autofitlib.fit(blame_records)
    blame = bfit.get("blame")
    assert blame and blame["axis"] == "tpot" \
        and blame["dominant"] == "prefetch_wait", (
        f"blame fitter read the seeded decode stall as {blame} — the "
        "injected mechanism must be the one blamed")
    bres = bfit.get("residency") or {}
    outs_bfit, _be2, _bm2, snap_fit, _bf2 = _tiered_stall_leg(
        **bleg, delay_ms=bcfg["delay_ms"],
        prefetch_depth=bres.get("prefetch_depth"),
        min_resident_rounds=int(bres.get("min_resident_rounds") or 1))
    for i in sorted(outs_rec):
        np.testing.assert_array_equal(
            outs_bfit[i], outs_rec[i],
            err_msg=f"blame-fitted leg diverged on seq {i}")
    blame_share_default = float(blame["share"])
    blame_share_fitted = float(
        (explainlib.digest([snap_fit])["tpot_p99_band_shares"] or {})
        .get("prefetch_wait", 0.0))
    assert blame_share_fitted < blame_share_default, (
        f"blame-fitted config did not shrink the blamed segment: "
        f"prefetch_wait p99-band share {blame_share_default:.3f} -> "
        f"{blame_share_fitted:.3f}")

    gain = t_default / t_fitted - 1.0
    result = {
        "t_default": t_default, "t_fitted": t_fitted,
        "tokens": total_tokens,
        "default_goodput_tok_s": total_tokens / t_default,
        "fitted_goodput_tok_s": total_tokens / t_fitted,
        "autofit_gain_frac": gain,
        "ladder_default": list(default_ladder),
        "ladder_fitted": list(fitted_ladder),
        "expected_padding_default": pad_default,
        "expected_padding_fitted": pad_fit,
        "blame_segment": blame["dominant"],
        "blame_share_default": blame_share_default,
        "blame_share_fitted": blame_share_fitted,
        "config_sections": sorted(
            k for k in ("ladder", "residency", "placement",
                        "autoscaler", "blame") if fitted.get(k)),
    }
    out(f"autofit: n={n} slots={slots} chunk={chunk} "
        f"lengths={sorted(set(obs_lengths))} tokens={total_tokens} "
        f"({'replayed ' + autofit_path if autofit_path else 'fitted from recording leg'})")
    out(f"  default : {t_default:.3f}s  "
        f"{result['default_goodput_tok_s']:,.1f} tok/s  ladder "
        f"{list(default_ladder)}  E[pad] {pad_default:.1f}")
    out(f"  fitted  : {t_fitted:.3f}s  "
        f"{result['fitted_goodput_tok_s']:,.1f} tok/s  ladder "
        f"{list(fitted_ladder)}  E[pad] {pad_fit:.1f}")
    out(f"  autofit gain {gain:+.1%} wall clock, E[pad] "
        f"{pad_default:.1f} -> {pad_fit:.1f} tokens/req "
        "(oracle-exact, strict padding win asserted)")
    out(f"  blame   : {blame['axis']}.{blame['dominant']} "
        f"p99-band share {blame_share_default:.0%} -> "
        f"{blame_share_fitted:.0%} under "
        f"{blame['actions']} (strict shrink asserted)")
    return result


def _apply_kv_dtype(conf, kv_dtype):
    """Thread a ``--kv-dtype`` value into a serving-bench config dict
    (the compound rows: --offload/--plane run their whole scenario on
    the quantized pool, so the gate sees quantization MULTIPLY the
    other levers — double the effective HBM under residency, half the
    migration bytes on the plane). Resolution (fp8 degrade included)
    goes through the ONE shared resolver (harness.cli)."""
    if not kv_dtype:
        return conf
    from hpc_patterns_tpu.harness.cli import resolve_kv_cache_dtype

    compute_dt, kv = resolve_kv_cache_dtype(kv_dtype)
    over = {"kv_cache_dtype": kv}
    if compute_dt:
        over["dtype"] = compute_dt
    conf = dict(conf)
    conf["cfg"] = dataclasses.replace(conf["cfg"], **over)
    return conf


def main():
    from hpc_patterns_tpu import compile_cache

    compile_cache.enable()
    kv_dtype = arg("kv-dtype", None, str)
    if kv_dtype:
        from hpc_patterns_tpu.harness.cli import KV_DTYPE_CHOICES

        kv_dtype = kv_dtype.strip().lower()
        if kv_dtype not in KV_DTYPE_CHOICES:
            # validate BEFORE any mode branches: --shared's quantized
            # refusal and --quant's resolver must only ever see legal
            # values, so a typo reads as a typo, not as a precision
            # policy message or a resolver traceback
            raise SystemExit(
                f"--kv-dtype must be one of {KV_DTYPE_CHOICES}, got "
                f"{kv_dtype!r}")
    if arg("quant", False, bool):
        if arg("smoke", False, bool):
            conf = quantized_smoke_config()
        else:
            conf = quantized_full_config(jax.default_backend() == "tpu")
        if kv_dtype:
            conf["kv_dtype"] = kv_dtype
        conf["quant_weights"] = arg("quant-weights", False, bool)
        run_quantized(**conf)
        return
    if arg("shared", False, bool):
        if kv_dtype and kv_dtype not in ("f32", "bf16"):
            # the documented refusal, surfaced HERE instead of deep in
            # the engine constructor: prefix sharing needs exact KV
            # pages (the monolithic prefill attends to unquantized
            # K/V, so shared dequantized pages break bitwise parity —
            # models/serving.py, docs/quantization.md)
            raise SystemExit(
                f"--shared refuses --kv-dtype {kv_dtype}: prefix "
                "sharing needs exact KV pages — the monolithic "
                "prefill attends to unquantized K/V and quantizes "
                "only for storage, so a tail computed from "
                "dequantized shared pages could not be bit-identical "
                "(docs/quantization.md); run --quant for the "
                "quantized row or --shared at f32/bf16")
        if arg("smoke", False, bool):
            run_shared(**_apply_kv_dtype(shared_smoke_config(),
                                         kv_dtype))
        else:
            run_shared(**_apply_kv_dtype(shared_full_config(
                jax.default_backend() == "tpu"), kv_dtype))
        return
    if arg("offload", False, bool):
        if arg("smoke", False, bool):
            run_offload(**_apply_kv_dtype(offload_smoke_config(),
                                          kv_dtype))
        else:
            run_offload(**_apply_kv_dtype(offload_full_config(
                jax.default_backend() == "tpu"), kv_dtype))
        return
    if arg("elastic", False, bool):
        if arg("smoke", False, bool):
            run_elastic(**elastic_smoke_config())
        else:
            run_elastic(**elastic_full_config(
                jax.default_backend() == "tpu"))
        return
    if arg("slo-budget", False, bool):
        # one shape on every backend: the row's value is the seeded
        # attribution assert (chaos lands in its own budget bucket),
        # not throughput — the injected delay dwarfs the model math
        # either way. NOT --budget: that flag is the plain row's
        # token budget.
        run_slo_budget(**slo_budget_smoke_config(),
                       explain=arg("explain", False, bool))
        return
    if arg("fit", False, bool):
        if arg("smoke", False, bool):
            conf = fit_smoke_config()
        else:
            conf = fit_full_config(jax.default_backend() == "tpu")
        run_fitted(**conf, autofit_path=arg("autofit", None, str),
                   fit_out=arg("fit-out", None, str))
        return
    if arg("plane", False, bool):
        mig = arg("migration", "device_put", str)
        if arg("smoke", False, bool):
            conf = _apply_kv_dtype(plane_smoke_config(), kv_dtype)
        else:
            conf = _apply_kv_dtype(plane_full_config(
                jax.default_backend() == "tpu"), kv_dtype)
        # --trace/--log ride the apps' shared instrumentation session
        # (the DMA-migration row traced, so the
        # plane.kv_migration windows + algorithm="dma" fingerprints
        # land in a flight-recorder snapshot like the launched tier's)
        from types import SimpleNamespace

        from hpc_patterns_tpu.apps import common

        ns = SimpleNamespace(trace=arg("trace", False, bool),
                             metrics=False,
                             log=arg("log", None, str),
                             trace_capacity=None)
        common.run_instrumented(
            lambda _a: (run_plane(**conf, migration=mig), 0)[1], ns)
        return
    if arg("scenario", False, bool):
        # --explain/--explain-out mirror the shared CLI pair
        # (harness/cli.py add_explain_args) through this parser, the
        # same way --autofit and --kv-dtype are mirrored
        exp = dict(explain=(arg("explain", False, bool)
                            or bool(arg("explain-out", None, str))),
                   explain_out=arg("explain-out", None, str))
        if arg("smoke", False, bool):
            run_scenario(**scenario_smoke_config(), **exp)
        else:
            run_scenario(**scenario_full_config(
                jax.default_backend() == "tpu"), **exp)
        return
    def resolve_autofit_buckets(buckets, max_seq):
        # --autofit on the plain rows: the fitted ladder replaces the
        # default 'auto' ladder (an explicit --buckets value wins) —
        # the SAME precedence the CLI serving surfaces apply
        path = arg("autofit", None, str)
        if not path or buckets != "auto":
            return buckets
        from hpc_patterns_tpu.harness import autofit as autofitlib

        fb = autofitlib.ladder_from(autofitlib.load_fitted(path),
                                    max_seq=max_seq)
        return fb if fb is not None else buckets

    if arg("smoke", False, bool):
        conf = smoke_config()
        run_bench(**conf,
                  overlap=bool(arg("overlap", 1)),
                  buckets=resolve_autofit_buckets(
                      arg("buckets", "auto", str),
                      conf["cfg"].max_seq))
        return
    on_tpu = jax.default_backend() == "tpu"
    n = arg("n", 32 if on_tpu else 16)
    slots = arg("slots", 8 if on_tpu else 4)
    chunk = arg("chunk", 16)
    page_size = arg("page", 256 if on_tpu else 16)
    prompt_len = arg("prompt", 512 if on_tpu else 32)
    max_budget = arg("budget", 512 if on_tpu else 192)
    cfg = TransformerConfig(
        vocab=arg("vocab", 32768 if on_tpu else 256),
        d_model=arg("d", 1024 if on_tpu else 256),
        n_heads=arg("heads", 8 if on_tpu else 4),
        n_layers=arg("layers", 8 if on_tpu else 2),
        d_ff=arg("ff", 4096 if on_tpu else 1024),
        max_seq=prompt_len + max_budget,
        dtype="bfloat16" if on_tpu else "float32",
        kv_cache_dtype=arg("cache", "compute", str),
        # off-TPU the serving surfaces take the pure-XLA gather route:
        # a pallas_call runs in interpret mode there, paying per-grid
        # host cost that swamps both sides of the comparison
        decode_attn="flash" if on_tpu else arg("attn", "gather", str),
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    run_bench(n=n, slots=slots, chunk=chunk, page_size=page_size,
              prompt_len=prompt_len, max_budget=max_budget,
              cfg=cfg, params=params,
              mix=bool(arg("mix", 1)),
              buckets=resolve_autofit_buckets(
                  arg("buckets", "auto", str), cfg.max_seq),
              overlap=bool(arg("overlap", 1)),
              temperature=arg("temp", 0.0, float),
              top_k=arg("topk", 0))


if __name__ == "__main__":
    main()
