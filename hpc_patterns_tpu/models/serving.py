"""Continuous batching: a serving loop over the ragged paged cache.

The round-4 machinery (per-sequence positions, per-row pool writes,
page-table indirection — models/decode.py) provided the building
blocks; this module is the loop that makes them a serving system, the
vLLM-style capacity story:

- a **page free-list**: the pool is a shared arena; each admitted
  sequence takes exactly the pages its prompt + budget needs and
  returns them on completion;
- **admission**: new sequences enter as soon as pages free up —
  batch slots don't wait for the whole batch to finish (the static-
  batching waste: every row pays the longest row's wall clock);
- **per-row completion**: on-device ``pos``/``limit`` cursors let every
  row advance at its own length; budget exhaustion and (optional) EOS
  end a row independently of its neighbors.

TPU shape of the loop: the inner stepper is ONE jit containing a
``lax.scan`` over ``chunk`` tokens (iteration-level scheduling
quantized to ``chunk``) — host work and dispatch latency amortize over
the chunk, exactly the reference's amortize-the-submit-path discipline
(SURVEY.md §3.1's repetition loop). Finished rows stop advancing
INSIDE the chunk (their ``pos`` freezes at ``limit``; the frozen write
re-targets the row's own last slot, which the row still owns), so a
chunk never writes past a row's allocation. Idle slots point their
table row at a dedicated TRASH page and their writes land there —
garbage in, never read, discarded.

Weights: training keeps float32 masters and casts at use; an engine
holds its weights in ``cfg.dtype``. :class:`EngineCore` passes the
tree it is handed (and the draft's) through
``transformer.serving_weights`` once, at construction, so no prefill
and no decode chunk copies a weight stack (``engine.weight_bytes`` and
the ``serve.weights_cast`` span record the cast; int8 trees, float32
configs and trees already cast are held as they come). The programs
compute the same bits either way: the use sites' ``astype`` to a
leaf's own dtype emits nothing.

Production shape (round 6), three coupled levers:

- **prompt-length bucketing**: prompts pad to a small ladder of
  lengths (:func:`bucket_ladder`), so admission prefill compiles are
  bounded by the LADDER size, not the number of distinct prompt
  lengths in the stream (causality keeps the true-prefix K/V and the
  last-real-token logits exact — decode.prefill's ``last_pos`` route);
- **overlapped admission**: the decode chunk is DISPATCHED first and
  admissions (table upload, prefill, first-token pick) are enqueued
  behind it — JAX async dispatch keeps the device queue fed while the
  host does admission work, and the first-token readback is deferred
  to the next sync point instead of stalling the loop per admission.
  The admission-bubble fraction (host admission time exposed with no
  decode work in flight) is measured per ``run()`` and emitted through
  the metrics registry;
- **sampling in the engine**: per-row temperature and per-row PRNG key
  streams (``temperature``/``top_k``/``seed``; per-request overrides
  via :meth:`ContinuousBatcher.submit`). Each row consumes its key
  exactly as a standalone ``paged_generate(..., key=request_key(sid))``
  would, so SAMPLED serving is token-identical to standalone sampling
  — the same oracle discipline as greedy mode, not a weaker
  distributional claim. Draft-assisted serving samples through the
  shared speculative accept/resample (models/speculative.paged_round),
  which preserves the law but not the draws — its oracle is
  distributional.

Robustness shape (round 8) — the scenario layer for traffic that does
not cooperate:

- **priority classes + admission control**: requests carry a
  ``priority`` (lower number = more important); admission serves
  classes in priority order, a ``admit_highwater`` mark makes fresh
  admissions back off before the pool is exhausted (headroom reserved
  for resumes), and requests with a queue ``deadline_s`` are SHED once
  it expires instead of silently aging;
- **preemption-and-resume under memory pressure** (``preempt=True``):
  when a higher-priority request cannot get pages, the lowest-priority
  victim is EVICTED at a chunk boundary — its generated tokens and
  (sampled mode) its per-row key state snapshot to host, its pages
  return to the arena — and later RESUMED through the ordinary prefill
  path with prompt = original prompt + generated-so-far. Causality
  makes the resumed cache exactly the uninterrupted one, and the
  split/pick order of ``_admit_row`` matches ``_chunk_step``'s, so a
  preempted-and-resumed sequence's tokens are BYTE-IDENTICAL to an
  uninterrupted run with the same request key (oracle-tested);
- **open-loop serving** (``run(arrivals=...)``): requests enter on the
  schedule's clock (harness/loadgen.py), not on completion — overload
  builds queues and blows deadlines where a closed loop would just
  slow down;
- **SLO accounting** (``slo={priority: harness.slo.SLOTarget}``):
  per-class TTFT/TPOT tracking against declared targets; after each
  run ``last_slo`` carries the attainment rollup and goodput
  (SLO-attained tok/s) lands next to raw tok/s in the metrics
  registry;
- **chaos hook**: each scheduler round probes
  ``harness.chaos.maybe_inject("engine_round", ...)`` so a seeded
  stalled-host fault perturbs the real loop (and shows up as bubble in
  the trace rollups).

Serving-plane shape (round 10) — the engine core / transport split:

- :class:`EngineCore` is the engine CORE — batching, paging, sampling,
  preemption, and the per-round scheduler (:meth:`EngineCore.
  service_round`) — with no opinion about where requests come from;
- :class:`ContinuousBatcher` is the single-process SUBMISSION
  TRANSPORT over it: the classic ``submit()``/``run()`` loop
  (open-loop arrivals, bounded runs, the SLO rollup tail). Its
  behavior is byte-identical to the pre-split engine;
- the multi-replica serving plane (``hpc_patterns_tpu/serving_plane/``)
  drives the SAME core through its router: N replicas each own an
  :class:`EngineCore` and the router is just another transport. KV
  MIGRATION (prefill/decode disaggregation) lives here as the core
  primitives :meth:`EngineCore.export_migration` /
  :meth:`EngineCore.install_migration`: a migrated request is
  structurally a RESUME on another replica — the exported row state
  (cursors, sampling key, KV pages) re-enters a peer engine exactly
  where the donor left off, so the resume oracle extends to the
  disaggregated path byte-for-byte (docs/serving_plane.md).

Tiered-memory shape (round 11) — the HBM arena as a cache:

- ``EngineCore(residency=...)`` (a :class:`hpc_patterns_tpu.memory.
  ResidencyManager`) fronts a larger HOST-resident pool with the HBM
  page arena: under page pressure, policy-chosen victim rows PAGE OUT
  to the host tier at a chunk boundary (the :meth:`EngineCore.
  _detach_row` snapshot — KV bytes move, nothing is recomputed) and
  swapped rows prefetch back with the pull dispatched BEFORE the
  decode chunk and the install landing behind it (the overlapped-
  admission discipline, measured as ``mem.prefetch`` windows). So
  admission consults the manager instead of failing at
  ``free_pages == 0`` — context length and batch become a policy
  knob (docs/memory.md).

Prefix-sharing shape (round 12) — the sharing-aware arena:

- ``EngineCore(prefix_cache=True)`` puts a radix prefix index
  (:class:`hpc_patterns_tpu.memory.RadixPrefixCache`) over the paged
  pool with REFCOUNTED page ownership: admission longest-prefix-
  matches the prompt against every chain already resident at its
  bucket rung, maps the matched pages read-only into the new row's
  table, and prefills ONLY the tail — the hottest KV bytes (shared
  system prompts, few-shot templates, conversation trees) live ONCE
  in the arena instead of N times, and TTFT skips the matched span's
  compute (``serve.prefill_skip_frac``). Copy-on-write is resolved AT
  ADMISSION: the boundary page (the first the row may write) is
  always private by construction, and interior shared pages are never
  rewritten — decode writes start at the prompt's own tail
  (docs/prefix_cache.md has the full COW rule and the rung-keyed
  bitwise-parity story).

Correctness contract (oracle-tested): every admitted sequence's
emitted tokens are exactly ``paged_generate``'s for the same prompt,
budget, and (when sampling) per-request key, regardless of what was
scheduled around it — including sequences preempted and resumed along
the way, sequences prefilled on one engine and decoded on another
(the serving-plane migration oracle, tests/test_serving_plane.py),
sequences paged through the host tier and back
(tests/test_residency_serving.py), and sequences served through
shared prefix pages (tests/test_prefix_cache.py — greedy AND
sampled, under preemption and migration).

Reference lineage: the benchmark-IS-the-test discipline
(aurora.mpich.miniapps/src/CMakeLists.txt:39-50) — the benchmark's
serving cells (chipbench/drivers/serve.py) hold every run's served
tokens against a plain reference before a number counts.
"""

from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass, field, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from hpc_patterns_tpu.harness import chaos as chaoslib
from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness import reqtrace as reqtracelib
from hpc_patterns_tpu.harness import slo as slolib
from hpc_patterns_tpu.harness import trace as tracelib
from hpc_patterns_tpu.memory.prefix_cache import RadixPrefixCache
from hpc_patterns_tpu.models.decode import (
    PREFIX_ALIGN,
    STATE_KEYS,
    _pick,
    _topk_mask,
    init_paged_cache,
    paged_block_step,
    paged_decode_step,
    paged_prefill,
    paged_tail_prefill,
)
from hpc_patterns_tpu.models.transformer import (
    TransformerConfig,
    scoped,
    serving_cast_leaves,
    serving_weights,
)


def bucket_ladder(max_len: int, *, lo: int = 16,
                  growth: float = 2.0) -> tuple[int, ...]:
    """A power-of-two-ish prompt-length ladder covering 1..``max_len``:
    rungs ``lo, lo*growth, ...`` with the top rung clamped to
    ``max_len`` (so no rung pads past the longest legal prompt). The
    ladder size — not the stream's distinct-length count — bounds the
    engine's admission-prefill compiles."""
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    if lo < 1 or growth <= 1.0:
        raise ValueError(f"need lo >= 1 and growth > 1, got {lo}/{growth}")
    rungs = []
    r = lo
    while r < max_len:
        rungs.append(r)
        r = max(int(r * growth), r + 1)
    rungs.append(max_len)
    return tuple(rungs)


def fit_bucket_ladder(lengths, max_rungs: int, *,
                      max_len: int | None = None) -> tuple[int, ...]:
    """Fit a prompt-length ladder to an OBSERVED length sample: up to
    ``max_rungs`` rungs minimizing the expected padding waste
    ``E[rung(len) - len]`` over the sample — the data-driven
    counterpart of :func:`bucket_ladder`'s shape-blind powers of two
    (open since round 6; the serving plane's router and the plane
    benchmark fit their ladder from a loadgen sample before building
    replicas). Exact DP over the distinct observed lengths (optimal
    rungs always sit ON sample points: lowering a rung to the largest
    length it covers only removes padding), O(U^2 * R) for U distinct
    lengths. ``max_len``: extend the top rung to cover prompts up to
    this length even if the sample never reached it. Also reachable as
    ``bucket_ladder.fit`` (the constructor spelling)."""
    lengths = [int(t) for t in lengths]
    if not lengths or min(lengths) < 1:
        raise ValueError("fit_bucket_ladder needs a nonempty sample of "
                         "positive lengths")
    if max_rungs < 1:
        raise ValueError(f"max_rungs must be >= 1, got {max_rungs}")
    counts: dict[int, int] = {}
    for t in lengths:
        counts[t] = counts.get(t, 0) + 1
    if max_len is not None and max_len > max(counts):
        counts[int(max_len)] = counts.get(int(max_len), 0)
    cand = sorted(counts)
    n_cand = len(cand)
    cnt = np.asarray([counts[c] for c in cand], np.int64)
    val = np.asarray(cand, np.int64)
    pc = np.concatenate([[0], np.cumsum(cnt)])
    pv = np.concatenate([[0], np.cumsum(cnt * val)])

    def seg_waste(i: int, j: int) -> int:
        # lengths cand[i..j] all pad up to cand[j]
        return int(val[j] * (pc[j + 1] - pc[i]) - (pv[j + 1] - pv[i]))

    r_max = min(max_rungs, n_cand)
    inf = float("inf")
    # dp[r][j]: min waste covering cand[0..j] with r rungs, top = cand[j]
    dp = [[inf] * n_cand for _ in range(r_max + 1)]
    back = [[-1] * n_cand for _ in range(r_max + 1)]
    for j in range(n_cand):
        dp[1][j] = seg_waste(0, j)
    for r in range(2, r_max + 1):
        for j in range(r - 1, n_cand):
            best, bi = inf, -1
            for i in range(r - 2, j):
                w = dp[r - 1][i] + seg_waste(i + 1, j)
                if w < best:
                    best, bi = w, i
            dp[r][j], back[r][j] = best, bi
    # the ladder must cover the sample max: chains end at the top cand
    r_best = min(range(1, r_max + 1), key=lambda r: dp[r][n_cand - 1])
    rungs, j, r = [], n_cand - 1, r_best
    while r >= 1 and j >= 0:
        rungs.append(int(val[j]))
        j = back[r][j]
        r -= 1
    return tuple(sorted(rungs))


bucket_ladder.fit = fit_bucket_ladder


def expected_padding(buckets, lengths) -> float:
    """Mean padded-minus-true tokens per prompt for ``lengths`` under
    ``buckets`` (None = exact lengths, zero padding) — the objective
    :func:`fit_bucket_ladder` minimizes, exposed so ladders can be
    compared (the fit-beats-default pin in tests/test_serving_plane.py
    and the plane benchmark's ladder report)."""
    lengths = [int(t) for t in lengths]
    if not lengths:
        return 0.0
    return float(sum(pad_to_bucket(buckets, t) - t
                     for t in lengths)) / len(lengths)


def pad_to_bucket(buckets, prompt_len: int) -> int:
    """The padded prefill length: the smallest ladder rung that fits
    (the exact length when ``buckets`` is None). THE single pad rule —
    the engine pads admissions with it and pool-sizing callers
    (serve_app) must size with the same function, or
    ``pages_needed`` desynchronizes from what admission writes."""
    if buckets is None:
        return prompt_len
    for rung in sorted(buckets):
        if rung >= prompt_len:
            return int(rung)
    raise ValueError(
        f"prompt length {prompt_len} above the bucket-ladder top "
        f"{max(buckets)}; extend prompt_buckets"
    )


@dataclass
class Request:
    """One sequence to serve: ``prompt`` (T,) int32, up to ``max_new``
    generated tokens (fewer if ``eos_id`` fires). ``t_submit`` stamps
    queue entry so admission can attribute time-to-first-token.
    ``temperature``/``key``: per-request sampling overrides (None =
    the engine's defaults; the default key is
    ``ContinuousBatcher.request_key(seq_id)``). ``priority``: lower
    number = more important (admission order; preemption eligibility).
    ``deadline_s``: queue-time shedding deadline relative to submit
    (None = never shed). ``resume_prefix``: internal — tokens this
    request already emitted before being preempted; its prompt then
    already carries them, and the engine prepends them to the output."""
    prompt: np.ndarray
    max_new: int
    seq_id: int = -1
    t_submit: float = 0.0
    temperature: float | None = None
    key: jax.Array | None = None
    priority: int = 0
    deadline_s: float | None = None
    resume_prefix: np.ndarray | None = None


@dataclass
class MigrationBundle:
    """One row's complete serving state, detached from its engine —
    what a prefill-role replica hands a decode-role replica (the
    serving plane's KV handoff, docs/serving_plane.md). Contains
    everything :meth:`EngineCore.install_migration` needs to continue
    the row EXACTLY where the donor stopped: the per-row cursors
    (``pos``/``limit``), the current token, the post-admission sampling
    key state, the per-row temperature, and the row's KV pages gathered
    from the donor's pool (``pages_payload``: {cache key: per-layer
    arrays with leading dim ``n_pages``} — device arrays on the
    in-process path, numpy on the wire). A migrated request is
    structurally a RESUME on another replica, so the round-8 resume
    oracle extends to it byte-for-byte. ``seq`` is the plane-assigned
    migration sequence number: both sides fingerprint it into the
    collective schedule chain, which is how a router/replica desync is
    caught at merge time."""
    seq_id: int
    prompt: np.ndarray       # THIS admission's (possibly resume) prompt
    out: list                # tokens emitted so far (prefix included)
    prefix: list             # tokens emitted before THIS admission
    budget: int
    pos: int
    limit: int
    token: int               # current device token (== out[-1])
    key: np.ndarray          # (2,) uint32 post-admission key state
    temp: float              # effective per-row temperature
    temp_override: float | None
    priority: int
    deadline_s: float | None
    t_submit: float
    t_first: float | None
    preemptions: int
    n_pages: int
    page_size: int
    pages_payload: dict
    seq: int = -1            # plane-assigned migration sequence number
    #: the admission rung (bucket-padded length) the row prefilled at —
    #: the KEY a prefix-sharing destination resolves against: prefix
    #: K/V bytes are rung-stamped (docs/prefix_cache.md), so only a
    #: same-rung cached chain is bit-identical to this payload. 0 =
    #: unknown (pre-round-12 bundles; destinations then materialize)
    rung: int = 0
    #: leading tokens whose pages hold PURE-PROMPT K/V (page-aligned,
    #: = (prompt_len // page_size) * page_size): the span a destination
    #: with a warm prefix cache may resolve to its own shared pages
    #: instead of installing the payload — byte-exact either way
    prefix_len: int = 0
    #: how the payload reached (or will reach) the installing replica:
    #: "local" (never left the exporting engine), "device_put" (host
    #: -staged cross-device copy), "dma" (the fused remote-DMA pair,
    #: comm/migration_dma.py), "wire" (the socket codec). The router
    #: fingerprints this into the collective schedule's
    #: ``kv_migration`` entries as the ``algorithm`` field
    transport: str = "local"
    #: request-lifecycle segment history (harness/reqtrace.py) carried
    #: across the handoff so the destination's attribution does not
    #: start fresh — the same backward-compatible pattern as
    #: ``transport``: None when the donor traced nothing; an ABSENT
    #: key on a legacy wire artifact decodes to one ``untracked``
    #: segment (reqtrace.LEGACY_SEGMENTS)
    segments: tuple | None = None


@dataclass
class _Slot:
    seq_id: int = -1
    pages: list = field(default_factory=list)
    prompt_len: int = 0
    budget: int = 0
    out: list = field(default_factory=list)
    active: bool = False
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_dispatch: float = 0.0  # admission-dispatch trace stamp
    first_dev: jax.Array | None = None  # pending first-token readback
    prompt: np.ndarray | None = None  # THIS admission's unpadded prompt
    priority: int = 0
    deadline_s: float | None = None
    temp_override: float | None = None
    prefix: list = field(default_factory=list)  # pre-preemption tokens
    padded_len: int = 0      # the admission rung this row prefilled at
    shared_pages: int = 0    # leading table entries mapped SHARED
    cursor: int = 0          # a diffusion row's block start, host mirror


@partial(jax.jit,
         static_argnames=("cfg", "chunk", "eos_id", "greedy", "top_k",
                          "mesh"),
         donate_argnums=(1, 2, 3, 4, 5))
def _chunk_step(params, cache, pos, limit, tokens, keys, temps, *, cfg,
                chunk, eos_id, greedy, top_k, mesh):
    """``chunk`` ragged decode steps in one trace: rows advance while
    ``pos < limit``; an emitted ``eos_id`` pulls the row's limit down
    to its current end. Emits the picked token per step (valid where
    the step was active). eos_id < 0 disables EOS. Module-level jit
    (static config) so every engine instance with the same config
    shares one compilation.

    ``greedy`` (static) picks argmax; otherwise each row samples from
    its OWN key stream (``keys`` (B, 2) uint32) at its OWN temperature
    (``temps`` (B,)), advancing the key only on active steps — the
    exact split/pick sequence of decode._generation_scan per row, which
    is what makes sampled serving token-identical to standalone
    ``paged_generate`` with the same per-request key."""

    def step(carry, _):
        cache, pos, limit, tok, keys = carry
        active = pos < limit
        logits, cache = paged_decode_step(params, cache, pos, tok, cfg,
                                          mesh=mesh, active=active)
        with jax.named_scope("sample"):
            if greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                split2 = jax.vmap(jax.random.split)(keys)  # (B, 2, 2)
                masked = _topk_mask(logits, top_k) / temps[:, None]
                nxt = jax.vmap(
                    lambda l, k: jax.random.categorical(k, l[None, :],
                                                        axis=-1)[0]
                )(masked, split2[:, 1]).astype(jnp.int32)
                keys = jnp.where(active[:, None], split2[:, 0], keys)
            nxt = jnp.where(active, nxt, tok)
        if eos_id >= 0:
            limit = jnp.where(active & (nxt == eos_id),
                              jnp.minimum(limit, pos + 1), limit)
        pos = jnp.where(active, pos + 1, pos)
        return (cache, pos, limit, nxt, keys), nxt

    (cache, pos, limit, tokens, keys), out = lax.scan(
        step, (cache, pos, limit, tokens, keys), None, length=chunk
    )
    return cache, pos, limit, tokens, keys, out


#: how a denoising forward chooses the masked positions it settles
UNMASK_RULES = ("static", "dynamic")

#: the running sums a block engine keeps on the device (int32, whole
#: numbers that wrap: read differences), :meth:`EngineCore.diffusion_stats`
DIFFUSION_STATS = ("forwards", "blocks", "tokens")


@scoped("unmask")
def _unmask(logits, msk, *, rule: str, steps: int, threshold: float):
    """What one denoising forward settles. ``logits`` (rows, B, vocab)
    float32 at the block's positions, ``msk`` (rows, B) the positions
    still masked. Every position's candidate is its argmax and its
    confidence that token's softmax probability; among the masked ones,
    ``static`` settles the ``ceil(B / steps)`` most confident (ties by
    index, as ``lax.top_k``), ``dynamic`` every one above ``threshold``
    and at least the most confident. Returns (candidates (rows, B) int32,
    settle (rows, B) bool, inside ``msk``)."""
    B = msk.shape[1]
    best = jnp.max(logits, axis=-1)
    cand = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    conf = jnp.exp(best - jax.nn.logsumexp(logits, axis=-1))
    masked_conf = jnp.where(msk, conf, -1.0)
    at = jnp.arange(B, dtype=jnp.int32)
    if rule == "static":
        _, first = lax.top_k(masked_conf, -(-B // steps))        # (rows, n)
        settle = jnp.any(first[:, :, None] == at, axis=1)
    else:
        top = jnp.argmax(masked_conf, axis=-1)
        settle = (conf > threshold) | (at == top[:, None])
    return cand, settle & msk


@partial(jax.jit,
         static_argnames=("cfg", "forwards", "rule", "steps", "threshold"),
         donate_argnums=(1, 2, 4, 5, 6, 7, 8))
def _block_chunk(params, cache, pos, limit, blk, msk, fidx, nfw, dstats, *,
                 cfg, forwards, rule, steps, threshold):
    """``forwards`` block forwards in one trace, for a model that
    generates by diffusion over blocks (``cfg.block_len`` = B). A row
    carries its block between forwards: ``blk`` (rows, B) the tokens
    (``cfg.mask_id`` where none is settled), ``msk`` the positions still
    masked, ``fidx`` the index of the forward that settled each (-1: given
    by the prompt), ``nfw`` the denoising forwards its block has had. In
    each forward every live row (``pos < limit``; ``pos`` the block's
    start) runs :func:`paged_block_step` over its block and then either

    - still holds masks: this was a DENOISING forward, which settles part
      of them (:func:`_unmask`); its K/V write is provisional; or
    - holds none: this was its COMMIT forward, whose K/V write stands.
      The block's tokens and ``fidx`` go to the output, the row moves on
      by B positions and lays a fresh block of masks.

    Rows are ragged in position and in phase; a row whose block reaches
    ``limit`` is done after that block's commit (the host cuts the block
    at the limit). Returns the carried arrays and, a forward, (the
    block's tokens, its ``fidx``, committed (rows,) bool)."""
    B = cfg.block_len
    at = jnp.arange(B, dtype=jnp.int32)

    def step(carry, _):
        cache, pos, blk, msk, fidx, nfw, dstats = carry
        active = pos < limit
        logits, cache = paged_block_step(params, cache, pos, blk, cfg,
                                         active=active)
        cand, settle = _unmask(logits, msk, rule=rule, steps=steps,
                               threshold=threshold)
        masked = jnp.any(msk, axis=-1)
        commit = active & ~masked
        settle = settle & (active & masked)[:, None]
        out = (blk, fidx, commit)
        with jax.named_scope("kv_commit"):
            # the cursor passes the block: its last write is the stored one
            handed = jnp.sum(commit[:, None] & (fidx >= 0)
                             & (pos[:, None] + at < limit[:, None]),
                             dtype=jnp.int32)
            pos = jnp.where(commit, pos + B, pos)
            fresh = commit[:, None]
            blk = jnp.where(fresh, cfg.mask_id, jnp.where(settle, cand, blk))
            msk = fresh | (msk & ~settle)
            fidx = jnp.where(fresh, -1,
                             jnp.where(settle, nfw[:, None], fidx))
            nfw = jnp.where(commit, 0, nfw + (active & masked))
        dstats = dstats + jnp.stack([
            jnp.sum(active, dtype=jnp.int32),
            jnp.sum(commit, dtype=jnp.int32), handed])
        return (cache, pos, blk, msk, fidx, nfw, dstats), out

    carry, out = lax.scan(step, (cache, pos, blk, msk, fidx, nfw, dstats),
                          None, length=forwards)
    return (*carry, out)


@partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5))
def _admit_block_row(pos, limit, blk, msk, fidx, nfw, slot, start, end,
                     first, given):
    """A diffusion row's admission bookkeeping in one dispatch: the cursor
    at the first block that is not whole in the prompt (``start``), the
    limit at prompt + budget (``end``), and that block laid: ``first``
    (B,) the prompt's remainder (``given`` tokens) then masks."""
    B = blk.shape[1]
    pos = pos.at[slot].set(start)
    limit = limit.at[slot].set(end)
    blk = blk.at[slot].set(first)
    msk = msk.at[slot].set(jnp.arange(B, dtype=jnp.int32) >= given)
    fidx = fidx.at[slot].set(-1)
    nfw = nfw.at[slot].set(0)
    return pos, limit, blk, msk, fidx, nfw


@partial(jax.jit,
         static_argnames=("cfg", "dcfg", "gamma", "rounds", "eos_id",
                          "greedy", "top_k", "mesh"),
         donate_argnums=(2, 3, 4, 5, 6, 7))
def _spec_chunk(params, dparams, cache, dcache, pos, limit, cur, key,
                temps, *, cfg, dcfg, gamma, rounds, eos_id, greedy,
                top_k, mesh=None):
    """``rounds`` draft-assisted serving rounds in ONE dispatch: each
    round is THE shared speculative round body
    (models/speculative.paged_round — one acceptance/emit definition
    for the engine and speculative_generate_batched) at each row's own
    cursor, advancing 1..gamma+1 tokens per round. Budget and EOS
    truncation happen ON DEVICE between rounds (``adv`` clamps at the
    row's limit; an emitted eos pulls the limit to the row's end), so
    the host pays one round trip per ``rounds`` — the draft-mode
    counterpart of _chunk_step's dispatch amortization. Rows at their
    limit run at a clamped cursor (garbage lands in pages they own or
    the trash page).

    ``greedy`` (static) keeps the provably-token-exact acceptance;
    otherwise the rounds run paged_round's LIVE rejection-sampling path
    (speculative._accept_resample) from ``key``, one split per round,
    at per-row ``temps`` — same emitted law as target-only sampling,
    different draws (the distribution oracle's territory). Returns
    (cache, dcache, pos, limit, cur, key, emits, advs): per-round
    tokens (rounds, B, gamma+1) and valid counts (rounds, B) for the
    host to append."""
    from hpc_patterns_tpu.models.speculative import paged_round

    B = pos.shape[0]
    rows = jnp.arange(B)

    def one_round(carry, _):
        cache, dcache, pos, limit, cur, key = carry
        active = pos < limit
        pos_eff = jnp.where(active, pos, 0)
        key, sub = jax.random.split(key)  # greedy: unused, DCE'd
        cache, dcache, a, emit, _ = paged_round(
            params, cfg, dparams, dcfg, cache, dcache, pos_eff, cur,
            gamma, sub, greedy, top_k, temps, mesh=mesh)
        adv = jnp.where(active,
                        jnp.minimum(a + 1, limit - pos), 0)
        if eos_id >= 0:
            k = jnp.arange(gamma + 1)[None, :]
            is_eos = (emit == eos_id) & (k < adv[:, None])
            has = jnp.any(is_eos, axis=1)
            first = jnp.argmax(is_eos, axis=1)
            adv = jnp.where(has, first + 1, adv)
        new_cur = emit[rows, jnp.clip(adv - 1, 0, gamma)]
        cur = jnp.where(adv > 0, new_cur, cur)
        pos = pos + adv
        if eos_id >= 0:
            limit = jnp.where(has, pos, limit)
        return (cache, dcache, pos, limit, cur, key), (emit, adv)

    (cache, dcache, pos, limit, cur, key), (emits, advs) = lax.scan(
        one_round, (cache, dcache, pos, limit, cur, key), None,
        length=rounds)
    return cache, dcache, pos, limit, cur, key, emits, advs


@partial(jax.jit, static_argnames=("cfg", "page_size", "mesh"),
         donate_argnums=(3,))
def _prefill_one(params, prompt, last_pos, cache_one, *, cfg, page_size,
                 mesh):
    """One-row prefill through the shared pool (jitted; compiles per
    distinct PADDED prompt length — the engine's bucket ladder bounds
    that count, see ``prompt_buckets``). ``last_pos`` (traced) redirects
    the returned logits to the last REAL token of a padded prompt.
    ``cache_one`` is donated: the pool IS the capacity lever, so
    admissions must not double it."""
    return paged_prefill(params, prompt, cfg, cache_one, page_size,
                         mesh=mesh, last_pos=last_pos)


@partial(jax.jit,
         static_argnames=("cfg", "page_size", "n_prefix_pages", "mesh"),
         donate_argnums=(3,))
def _tail_prefill_one(params, tail, last_rel, cache_one, *, cfg,
                      page_size, n_prefix_pages, mesh):
    """One-row TAIL prefill through the shared pool — the sharing-aware
    admission's compute half (:func:`~hpc_patterns_tpu.models.decode.
    paged_tail_prefill`): the row's first ``n_prefix_pages`` table
    entries point at SHARED pages whose K/V a same-rung admission
    already wrote, so only the tail positions are computed and only
    the tail pages written. ``last_rel`` (traced) is the true last
    token's offset into the tail. ``cache_one`` is donated like
    :func:`_prefill_one`'s — the pool IS the capacity lever. Compiles
    per (matched page count, padded tail length) — bounded by
    pages_per_seq × the ladder size (see ``tail_prefill_cache_size``)."""
    return paged_tail_prefill(params, tail, cfg, cache_one, page_size,
                              n_prefix_pages, mesh=mesh,
                              last_pos=last_rel)


def _held_weights(params, cfg: TransformerConfig):
    """``serving_weights(params, cfg)`` under the ``serve.weights_cast``
    span, and the span's attributes as the engine's own record
    (``weight_bytes``): ``leaves`` cast, their ``bytes_in`` and
    ``bytes_out``. All zero for a tree that is held as it came."""
    wide = serving_cast_leaves(params, cfg).values()
    size = jnp.dtype(cfg.dtype).itemsize
    record = {"leaves": len(wide),
              "bytes_in": sum(a.nbytes for a in wide),
              "bytes_out": sum(a.size * size for a in wide)}
    with metricslib.span("serve.weights_cast", **record):
        return serving_weights(params, cfg), record


def prefill_cache_size() -> int:
    """Compiled admission-prefill variants in this process (the jit
    cache of :func:`_prefill_one`) — THE compile-count observable the
    bucket-ladder claim is asserted against
    (tests/test_serving.py). One entry per distinct (padded
    length, config) pair across every engine in the process. A
    consumer of the flight recorder's shared probe
    (harness.trace.jit_cache_size), which compile_watch diffs to stamp
    per-compile events on the trace timeline — strict mode, because
    the ladder-bound assertions gate on this number and a silently
    missing probe would read as the passing value 0."""
    return tracelib.jit_cache_size(_prefill_one, strict=True)


def tail_prefill_cache_size() -> int:
    """Compiled TAIL-prefill variants (:func:`_tail_prefill_one`) in
    this process — the sharing engine's compile-count observable: one
    entry per distinct (matched page count, padded tail length,
    config), bounded by pages_per_seq × ladder size. Strict for the
    same reason as :func:`prefill_cache_size`."""
    return tracelib.jit_cache_size(_tail_prefill_one, strict=True)


@partial(jax.jit, static_argnames=("eos_id", "greedy", "top_k"),
         donate_argnums=(0, 1, 2, 3, 4, 11))
def _admit_row(pos, limit, tokens, keys, temps, logits, key, temp, slot,
               true_len, budget, state=None, row_state=None, *, eos_id,
               greedy, top_k):
    """All device-side admission bookkeeping in ONE dispatch: pick the
    first token from the prefill logits (the same split/pick sequence
    decode._generation_scan opens with, so sampled rows stay
    standalone-exact), seed the row's cursors, and pull the limit to
    ``true_len`` when the row is already done (budget 1, or the first
    token IS eos) — all decided on device, so admission never forces a
    host readback. ``slot``/``true_len``/``budget`` ride as traced
    scalars: one compilation serves every admission.

    ``state`` (donated) / ``row_state``: a patterned model's per-row
    state (decode.STATE_KEYS) and the one row the prefill left; the row
    is installed at ``slot`` over whatever the slot's last tenant left
    there, so a reused slot starts from its own prompt alone."""
    if state is not None:
        with jax.named_scope("state_write"):
            state = jax.tree.map(
                lambda a, r: lax.dynamic_update_slice_in_dim(
                    a, r.astype(a.dtype), slot, axis=0), state, row_state)
    newk, sub = jax.random.split(key)
    first = _pick(logits, sub, temp, greedy, top_k)[0]
    # budget b emits 1 token at admit + (lim - true_len) from chunks
    lim = true_len + budget - 1
    if eos_id >= 0:
        lim = jnp.where(first == eos_id, true_len, lim)
    pos = pos.at[slot].set(true_len)
    limit = limit.at[slot].set(lim)
    tokens = tokens.at[slot].set(first)
    keys = keys.at[slot].set(newk)
    temps = temps.at[slot].set(temp)
    return pos, limit, tokens, keys, temps, first, state


@partial(jax.jit, donate_argnums=(0,))
def _install_pages(pool, idx, payload):
    """Scatter a migrated row's gathered pages into this engine's pool
    at its newly allocated page ids — the device half of
    :meth:`EngineCore.install_migration`. ``pool`` is donated (the pool
    IS the capacity lever; an install must not double it), and the
    scatter enqueues behind an in-flight decode chunk exactly like an
    overlapped admission's table upload. Compiles per (pool shape,
    payload page-count) — bounded by the engines' page geometries."""
    return pool.at[idx].set(payload)


class EngineCore:
    """Serve a stream of :class:`Request`s through ``slots`` concurrent
    rows of one paged pool — the engine CORE (batching, paging,
    sampling, preemption, migration), shared by the single-process
    :class:`ContinuousBatcher` transport and the multi-replica serving
    plane (``hpc_patterns_tpu/serving_plane/``).

    ``pool_pages``: the shared arena size (pages; one extra trash page
    is appended internally). ``pages_per_seq``: table width = the max
    pages any single sequence may hold (size requests with
    :meth:`pages_needed`). ``chunk``: decode steps per jitted dispatch
    — admission/eviction happen at chunk boundaries (larger amortizes
    host+dispatch; 1 = immediate). ``eos_id`` optionally ends rows
    early. ``mesh``: tp-sharded serving — pools/kernel shard exactly
    like ``paged_generate(..., mesh=...)``.

    ``prompt_buckets``: the prompt-length ladder (sorted ints; see
    :func:`bucket_ladder`). Prompts right-pad to the smallest rung
    that fits, so admission-prefill compiles are bounded by the ladder
    size instead of the stream's distinct lengths (the padding K/V is
    causally invisible and overwritten as the row generates). None =
    exact lengths (one compile per distinct length).

    ``overlap``: dispatch the decode chunk BEFORE doing admissions, so
    table uploads + prefills + first-token picks enqueue behind the
    in-flight chunk instead of stalling it (JAX async dispatch); the
    first-token host readback defers to the next sync point. The
    exposed (un-overlapped) admission time is reported as
    ``last_bubble_frac`` and the ``serve.admit_bubble_frac`` gauge.

    ``temperature``/``top_k``/``seed``: sampling in the engine.
    temperature <= 0 (default) is greedy — the token-exact serving
    oracle. temperature > 0 samples per row from per-request key
    streams (default ``request_key(seq_id)``); a row's emitted tokens
    are then EXACTLY ``paged_generate(prompt, budget,
    key=request_key(sid), temperature=..., top_k=...)``'s — same
    oracle, sampled mode. Per-request ``temperature``/``key`` override
    at :meth:`submit` (sampling engines only).

    ``draft_params``/``draft_cfg``/``gamma``: draft-assisted serving —
    speculative ROUNDS (draft proposes gamma, target verifies in one
    ragged extend; rows advance 1..gamma+1 tokens at their own
    acceptance). ``chunk`` here means ROUNDS per jitted dispatch
    (budget/EOS truncation runs on device between rounds), so
    admission/eviction happen every chunk·(1..gamma+1) tokens.
    Composes with ``mesh``: draft steps ride the shard_map
    paged-kernel route, the ragged extend partitions via GSPMD (tp
    must divide BOTH models' kv_heads). With ``temperature > 0`` the
    rounds run the live rejection-sampling acceptance — emitted law
    exactly target-only sampling, draws not reproducible row-wise
    (the distribution oracle covers it).

    ``preempt``: allow eviction of a lower-priority active row when a
    higher-priority (numerically smaller) request cannot get pages —
    the victim's tokens and key state snapshot to host at a chunk
    boundary, its pages return to the arena, and it re-enters through
    the ordinary prefill path with prompt = original + generated, so
    its final output is byte-identical to an uninterrupted run.
    ``admit_highwater``: fraction of pool pages FRESH admissions may
    fill (1.0 = off); the remainder is headroom reserved for resumes
    (fresh admissions back off, resumes bypass the mark). ``slo``:
    ``{priority: harness.slo.SLOTarget}`` — enables per-class
    TTFT/TPOT tracking; after each :meth:`run`, ``last_slo`` holds the
    attainment rollup (goodput next to raw tok/s) and the
    ``serve.goodput_tok_s``/``serve.tok_s`` gauges are set. Per-request
    outcomes accumulate in ``stats`` either way.

    ``residency``: a :class:`hpc_patterns_tpu.memory.ResidencyManager`
    — tiered HBM<->host paging: the pool becomes a CACHE over the
    manager's host tier, cold/demanded rows page out at chunk
    boundaries and prefetch back under the decode chunk, and the
    constrained engine stays token-identical to an all-HBM one
    (docs/memory.md; draft-assisted engines refuse it — the draft
    cache's row state would have to tier too).

    ``prefix_cache``: the SHARING-AWARE arena (round 12,
    docs/prefix_cache.md) — a radix prefix index over admitted
    prompts plus refcounted page ownership. Admission longest-prefix-
    matches the prompt at its bucket rung, maps the matched pages
    READ-ONLY into the row's table, and prefills ONLY the tail
    (:func:`_tail_prefill_one`); every release path decrefs instead
    of freeing. Token-identical to a private-pages engine, greedy AND
    sampled — the match is RUNG-KEYED because prefix K/V bytes depend
    on the prefill's row count, and the tail prefill mirrors the
    monolithic einsum prefill bit for bit (the parity contract in
    :func:`~hpc_patterns_tpu.models.decode.paged_tail_prefill`).
    Requires an aligned bucket ladder; refuses quantized KV and draft
    engines. Composes with preemption/shed (decref, re-match on
    resume), migration (bundles carry prefix refs a warm destination
    resolves — or it materializes), and residency (shared pages are
    pinned while a second reader is resident).

    A model that generates BY DIFFUSION OVER BLOCKS (``cfg.block_len`` =
    B) goes through the same arena, admission and round loop, with a
    block a row a step in place of a token: admission prefills the
    prompt's whole blocks under the block mask and takes no token from
    it; ``chunk`` counts block FORWARDS a dispatch (:func:`_block_chunk`);
    a row hands out up to B tokens at each of its commits and its first
    tokens' readback is its ``serve.first_token``. ``unmask_rule``
    ("static": the ``ceil(B / unmask_steps)`` most confident masked
    positions a denoising forward; "dynamic": every one whose confidence
    passes ``unmask_threshold``, at least one) says what a denoising
    forward settles. Greedy only; speculation, prefix sharing,
    preemption, residency, migration, an end token and a mesh are refused
    with the mechanism that is missing. :meth:`diffusion_stats` hands a
    caller the forwards, blocks and tokens so far.
    """

    def __init__(self, params, cfg: TransformerConfig, *, slots: int,
                 pool_pages: int, pages_per_seq: int, page_size: int,
                 chunk: int = 8, eos_id: int | None = None, mesh=None,
                 draft_params=None, draft_cfg: TransformerConfig | None
                 = None, gamma: int = 4, emit=None,
                 prompt_buckets=None, overlap: bool = True,
                 temperature: float = 0.0, top_k: int = 0,
                 seed: int = 0, preempt: bool = False,
                 admit_highwater: float = 1.0,
                 slo: dict[int, slolib.SLOTarget] | None = None,
                 residency=None, prefix_cache: bool = False,
                 unmask_rule: str = "static", unmask_steps: int = 2,
                 unmask_threshold: float = 0.9):
        if cfg.block_len:
            # what a row that carries a block between forwards cannot do
            # yet, each by the mechanism that is missing
            for on, what, why in (
                (draft_params is not None, "draft_params",
                 "a block step already settles several positions a "
                 "forward; a draft model's proposals have no place in it"),
                (prefix_cache, "prefix_cache",
                 "prefix K/V under the block mask depends on where the "
                 "prompt's whole blocks end, and the tail prefill is "
                 "causal and dense-only"),
                (preempt, "preempt",
                 "a preempted row resumes by prefilling prompt + output; "
                 "that needs the resume to land on a block boundary and "
                 "the block in flight (tokens, masks, forward indices) "
                 "snapshotted"),
                (residency is not None, "residency",
                 "swap-out detaches a row's cursors and current token; a "
                 "diffusion row's block in flight has no place in the "
                 "bundle"),
                (temperature > 0.0, "temperature > 0",
                 "a sampled unmasking draws each settled token and keeps "
                 "the confidence of the draw; only the greedy pick "
                 "(argmax, its softmax probability) is written"),
                (eos_id is not None, "eos_id",
                 "an end token inside a block would have to cut the block "
                 "and the row on the device; a row ends at its budget"),
                (mesh is not None, "mesh",
                 "the block step runs unsharded (a patterned model)"),
            ):
                if on:
                    raise ValueError(
                        f"{what} with a block-diffusion model (block_len "
                        f"{cfg.block_len}): {why}")
            if unmask_rule not in UNMASK_RULES:
                raise ValueError(
                    f"unmask_rule {unmask_rule!r} not in {UNMASK_RULES}")
            if not 1 <= unmask_steps <= cfg.block_len:
                raise ValueError(
                    f"unmask_steps {unmask_steps} outside [1, block_len "
                    f"{cfg.block_len}]")
            if page_size % cfg.block_len or any(
                    int(r) % cfg.block_len for r in prompt_buckets or ()):
                raise ValueError(
                    f"block_len {cfg.block_len} must divide page_size "
                    f"{page_size} and every rung {prompt_buckets}: a "
                    "block never straddles a page or a rung's end")
        if cfg.layer_pattern:
            # what a model with per-row recurrent state cannot do yet,
            # each by the mechanism that is missing
            for on, what, why in (
                (prefix_cache, "prefix_cache",
                 "a shared prefix would need the recurrent state AT the "
                 "prefix's end kept beside its pages (a state snapshot "
                 "per cached chain), and the tail prefill to start from "
                 "it"),
                (preempt, "preempt",
                 "a preempted row resumes by prefilling prompt + output "
                 "again, which rebuilds the recurrent state by the "
                 "chunked form: equal to the stepped state only to "
                 "rounding, where a resumed row has to continue exactly "
                 "as the uninterrupted one; that needs the row's state "
                 "snapshotted at eviction"),
                (residency is not None, "residency",
                 "swap-out moves a row's pages to the host tier; its "
                 "recurrent state has no page and no host pool yet"),
                (draft_params is not None, "draft_params",
                 "a rejected draft token must rewind the recurrence, "
                 "which needs a state checkpoint per speculated "
                 "position"),
            ):
                if on:
                    raise ValueError(
                        f"{what} with a patterned model "
                        f"({cfg.layer_pattern!r}): {why}")
        if draft_params is not None:
            if draft_cfg is None:
                raise ValueError("draft_params needs draft_cfg")
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError("draft/target vocab mismatch")
            if gamma < 1:
                raise ValueError(f"gamma must be >= 1, got {gamma}")
        if not 0 <= top_k <= cfg.vocab:
            raise ValueError(f"top_k {top_k} outside [0, vocab]")
        if prompt_buckets is not None:
            rungs = tuple(sorted({int(b) for b in prompt_buckets}))
            if not rungs or rungs[0] < 1:
                raise ValueError(
                    f"prompt_buckets must be positive ints, {rungs}")
            if rungs[-1] > cfg.max_seq:
                raise ValueError(
                    f"bucket rung {rungs[-1]} exceeds max_seq "
                    f"{cfg.max_seq} (padded prompts must still fit)")
            prompt_buckets = rungs
        if not 0.0 < admit_highwater <= 1.0:
            raise ValueError(
                f"admit_highwater must be in (0, 1], got {admit_highwater}")
        if prefix_cache:
            # the sharing-aware arena's byte-exactness preconditions
            # (docs/prefix_cache.md): rung-keyed chains need a ladder;
            # SIMD-stable GEMM row counts need aligned rungs and pages;
            # the tail prefill mirrors the EINSUM attention route and
            # attends to exact (not re-quantized) prefix K/V
            if draft_params is not None:
                raise ValueError(
                    "prefix sharing does not compose with draft-"
                    "assisted serving: the draft cache's pages would "
                    "need their own refcounted sharing tier")
            if cfg.kv_cache_dtype != "compute":
                raise ValueError(
                    f"prefix sharing needs exact KV pages but "
                    f"kv_cache_dtype={cfg.kv_cache_dtype!r}: the "
                    "monolithic prefill attends to unquantized K/V "
                    "and quantizes only for storage, so a tail "
                    "computed from dequantized shared pages could not "
                    "be bit-identical to it — serve quantized KV with "
                    "prefix_cache=False, or keep sharing on a "
                    "compute-dtype pool (docs/quantization.md)")
            if prompt_buckets is None:
                raise ValueError(
                    "prefix sharing is RUNG-KEYED (prefix K/V bytes "
                    "depend on the prefill row count): pass "
                    "prompt_buckets so admissions land on shared rungs")
            if page_size % PREFIX_ALIGN or any(
                    r % PREFIX_ALIGN for r in prompt_buckets):
                raise ValueError(
                    f"prefix sharing needs page_size {page_size} and "
                    f"every rung {prompt_buckets} aligned to "
                    f"{PREFIX_ALIGN} (bitwise GEMM row stability — "
                    "models/decode.PREFIX_ALIGN)")
            if cfg.decode_attn == "flash" and any(
                    r % 128 == 0 for r in prompt_buckets):
                raise ValueError(
                    "prefix sharing mirrors the einsum prefill route; "
                    "a flash-attn config with 128-multiple rungs would "
                    "send monolithic prefills through the Pallas "
                    "kernel instead — use off-multiple rungs or "
                    "decode_attn='gather'")
        self.prompt_buckets = prompt_buckets
        self.overlap = bool(overlap)
        self.preempt = bool(preempt)
        self.admit_highwater = float(admit_highwater)
        self.slo = slo
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.greedy = self.temperature <= 0.0
        base, spec = jax.random.split(jax.random.PRNGKey(seed))
        self._req_key_base = base
        self._spec_key = spec
        self.draft_cfg = draft_cfg
        self.gamma = gamma
        # speculative rounds touch positions up to pos+gamma; the page
        # allocation (NOT max_seq) must cover the overshoot
        self.spec_slack = gamma + 1 if draft_params is not None else 0
        # whatever this engine's process traces or compiles from here on
        # is kept in tracelib.compile_events(), a jit.event marker each
        # when spans are mirrored: also the eager pieces no watch wraps
        tracelib.install_monitoring_listener()
        # the engine holds its weights in the compute dtype: one cast
        # here instead of one in every prefill and every decode chunk
        # (the caller's tree is read, never donated or deleted)
        self.params, self.weight_bytes = _held_weights(params, cfg)
        self.draft_params = (
            None if draft_params is None
            else _held_weights(draft_params, draft_cfg)[0])
        self.cfg = cfg
        self.slots = slots
        self.page_size = page_size
        self.pages_per_seq = pages_per_seq
        self.chunk = chunk
        self.eos_id = -1 if eos_id is None else int(eos_id)
        self.mesh = mesh
        self.trash = pool_pages  # the appended trash page's id
        table = np.full((slots, pages_per_seq), self.trash, np.int32)
        self.cache = init_paged_cache(
            cfg, slots, pages_per_seq, page_size,
            pool_pages=pool_pages + 1, table=jnp.asarray(table),
        )
        #: bytes of per-row recurrent state held beside the pools
        self.state_bytes = sum(
            int(a.nbytes) for k in STATE_KEYS for a in self.cache.get(k, ()))
        #: bytes of K/V (scales included) one token holds, all layers
        self.kv_bytes_per_token = sum(
            int(a.nbytes) for k in ("k", "v", "k_scale", "v_scale")
            for a in self.cache.get(k, ())) // ((pool_pages + 1) * page_size)
        #: what ``_count_route`` last saw of the expert route's sums
        self._route_seen = None
        if draft_params is not None:
            # the draft pool mirrors the target's page geometry and
            # SHARES the page table (one allocation decision serves
            # both caches)
            self.dcache = init_paged_cache(
                draft_cfg, slots, pages_per_seq, page_size,
                pool_pages=pool_pages + 1, table=jnp.asarray(table),
            )
        self.free_pages = list(range(pool_pages))
        self.pool_pages = pool_pages  # arena size (trash page excluded)
        # the sharing-aware arena (round 12): a radix prefix index over
        # admitted prompts plus per-page refcounts — a page is owned by
        # every row whose table maps it AND by the cache chain that
        # indexes it; release paths DECREF (never free) and the page
        # returns to free_pages only at refcount 0 (docs/prefix_cache.md)
        self._prefix = RadixPrefixCache(page_size) if prefix_cache \
            else None
        self._page_refs: dict[int, int] = {}
        self._match_memo: tuple | None = None
        self._prefill_skip_tokens = 0
        self._prefill_total_tokens = 0
        self._table = table  # host mirror
        self.pos = jnp.zeros((slots,), jnp.int32)
        self.limit = jnp.zeros((slots,), jnp.int32)
        self.tokens = jnp.zeros((slots,), jnp.int32)
        self.keys = jnp.zeros((slots, 2), jnp.uint32)
        self.temps = jnp.ones((slots,), jnp.float32)
        if cfg.block_len:
            # a diffusion row's block between forwards (_block_chunk);
            # ``pos`` is the block's start, ``chunk`` counts FORWARDS
            B = cfg.block_len
            self.unmask = dict(rule=unmask_rule, steps=int(unmask_steps),
                               threshold=float(unmask_threshold))
            self.blk = jnp.full((slots, B), cfg.mask_id, jnp.int32)
            self.msk = jnp.ones((slots, B), bool)
            self.fidx = jnp.full((slots, B), -1, jnp.int32)
            self.nfw = jnp.zeros((slots,), jnp.int32)
            self.dstats = jnp.zeros((len(DIFFUSION_STATS),), jnp.int32)
            self._dstats_seen = None
        self._slots = [_Slot() for _ in range(slots)]
        self._pending: list[int] = []  # admitted, first token unread
        self._queue: list[Request] = []
        self.finished: dict[int, np.ndarray] = {}
        self._next_id = 0
        self.last_bubble_frac = 0.0  # of the most recent run()
        # per-request outcome table (harness/slo.py's input): t_submit /
        # t_first / t_finish / tokens / priority / outcome ("ok"|"shed")
        # / preemptions, keyed by seq_id; survives across runs
        self.stats: dict[int, dict] = {}
        self.last_slo: dict | None = None  # attainment of the last run
        self._serve_s = 0.0  # cumulative run() wall time (goodput base)
        # chunk-window host stamps for the serving plane's migration-
        # overlap accounting; off on the single-process path (the
        # plane flips it on for decode-role replicas)
        self.track_chunk_windows = False
        self.chunk_windows: deque = deque(maxlen=8192)
        # tiered residency (hpc_patterns_tpu/memory/): the HBM pool as
        # a cache over a larger host pool — admission consults the
        # manager instead of failing at free_pages == 0; cold rows
        # page out at chunk boundaries and page back in with the pull
        # dispatched BEFORE the decode chunk (docs/memory.md)
        self.residency = residency
        self._swapped: dict[int, MigrationBundle] = {}
        #: pulls in flight: (host bundle, device payload, window handle)
        self._prefetching: list[tuple] = []
        #: installed this round, window completion pending
        self._installed_prefetch: list[tuple] = []
        self._external_demand = 0  # router-signaled install pressure
        #: scheduler rounds served: the ``round`` every span of one
        #: round carries (docs/observability.md)
        self._round = 0
        if residency is not None:
            if draft_params is not None:
                raise ValueError(
                    "draft-assisted engines do not page: the draft "
                    "cache's row state would have to tier too")
            # the overlap proof needs the chunk windows to intersect
            self.track_chunk_windows = True
            # per-page payload bytes (every non-table pool, all
            # layers): the manager's block accounting unit
            self._page_nbytes = sum(
                int(arr.nbytes) // (pool_pages + 1)
                for name, pools in self.cache.items() if name != "table"
                for arr in pools)
        else:
            self._page_nbytes = 0
        # observability hook (the framework's metrics/logging
        # subsystem, SURVEY.md §5): a callable taking keyword fields —
        # pass harness.RunLog.emit for JSONL records of admissions,
        # completions, and queue waits; None = silent
        self._emit = emit or (lambda **kw: None)

    @classmethod
    def from_fitted(cls, params, cfg: TransformerConfig, fitted, **kw):
        """Build an engine from a :mod:`hpc_patterns_tpu.harness.autofit`
        ``FittedConfig`` (the dict, as ``autofit.load_fitted`` returns
        it): the fitted prompt ladder becomes ``prompt_buckets``
        (clamped to this model's ``max_seq``), everything else passes
        through unchanged. An explicit ``prompt_buckets=`` kwarg wins —
        the caller's hand-tuned ladder outranks the fit."""
        from hpc_patterns_tpu.harness import autofit as autofitlib

        fitted = autofitlib.validate_fitted(fitted)
        if kw.get("prompt_buckets") is None:
            buckets = autofitlib.ladder_from(fitted, max_seq=cfg.max_seq)
            if buckets is not None:
                kw["prompt_buckets"] = buckets
        return cls(params, cfg, **kw)

    # -- admission ---------------------------------------------------------

    @staticmethod
    def pages_needed(prompt_len: int, max_new: int, page_size: int, *,
                     gamma: int | None = None,
                     padded_len: int | None = None) -> int:
        """Pages one request holds in this engine: prompt + budget,
        plus the speculative overshoot slack (gamma+1) when a draft
        serves, OR the bucket-padded prefill length if that reaches
        further — THE sizing rule; callers building their own pools
        (serve_app) must use it rather than re-deriving the slack."""
        slack = (gamma + 1) if gamma is not None else 0
        span = max(prompt_len + max_new + slack, padded_len or 0)
        return -(-span // page_size)

    def _bucket_len(self, prompt_len: int) -> int:
        return pad_to_bucket(self.prompt_buckets, prompt_len)

    def _pages_for(self, prompt_len: int, max_new: int) -> int:
        return self.pages_needed(
            prompt_len, max_new, self.page_size,
            gamma=self.gamma if self.draft_params is not None else None,
            padded_len=self._bucket_len(prompt_len))

    # -- the sharing-aware arena (refcounted pages + radix index) ----------

    def _alloc_pages(self, n: int) -> list[int]:
        """Take ``n`` pages from the free list at refcount 1 (host-list
        bookkeeping only). The caller checked capacity."""
        pages = [self.free_pages.pop() for _ in range(n)]
        if self._prefix is not None:
            for p in pages:
                self._page_refs[p] = 1
        return pages

    def _incref_pages(self, pages) -> None:
        for p in pages:
            self._page_refs[p] += 1

    def _decref_pages(self, pages) -> None:
        """THE release path: drop one reference per page, freeing only
        at zero — completion, preemption, shed, migration-out, swap-out
        and cache eviction all funnel here, so a page another row (or
        the prefix index) still maps can never be handed out twice.
        Plain engines (no cache) keep the original free-list append."""
        if self._prefix is None:
            self.free_pages.extend(pages)
            return
        for p in pages:
            r = self._page_refs[p] - 1
            if r:
                self._page_refs[p] = r
            else:
                del self._page_refs[p]
                self.free_pages.append(p)

    def _prefix_match(self, prompt) -> list[int]:
        """Longest-cached-prefix page ids for ``prompt`` at ITS rung —
        the admission-match decision (a host trie walk; no device op
        anywhere near it). Capped at ``(T-1) // page_size`` pages so
        the tail always keeps the last true token: the first-token
        logits must be COMPUTED over the tail, never looked up. PURE
        peek: no LRU touch (a queued request that never admits must
        not keep its chain hot — an admission stamps its chain via
        ``_insert_prefix``) and no hit/miss accounting (that moves
        only when a match becomes an admission, ``count_match`` in
        :meth:`_admit`)."""
        if self._prefix is None:
            return []
        T = int(prompt.size)
        return self._prefix.match(
            prompt, self._bucket_len(T),
            max_pages=(T - 1) // self.page_size, touch=False)

    def _memo_match(self, req: Request) -> list[int]:
        """``_prefix_match`` with a ONE-round, one-entry memo: the
        queue head is sized up to three times per round (the
        preemption policy, the residency balance, and the admission
        pass) — each a full-prompt tobytes + trie walk on the
        dispatch-critical path. The memo is keyed by request identity
        and cleared at ``service_round`` entry; within a round the
        head's chain cannot be invalidated between uses (every
        reclaim in the round keeps the head's own chain, preemption
        and swap-out decref without touching the trie, and inserts
        only add nodes)."""
        memo = self._match_memo
        if memo is not None and memo[0] is req:
            return memo[1]
        chain = self._prefix_match(req.prompt)
        self._match_memo = (req, chain)
        return chain

    def _request_need(self, req: Request) -> int:
        """PRIVATE pages this request needs right now: the full sizing
        rule minus whatever a prefix match would map shared — the
        number admissibility, preemption, and residency demand all
        charge (the capacity win is exactly this subtraction)."""
        need = self._pages_for(req.prompt.size, req.max_new)
        if self._prefix is not None:
            need -= len(self._memo_match(req))
        return need

    def _insert_prefix(self, prompt, rung: int, pages) -> None:
        """Publish an admission's full-prompt pages into the radix
        index (host trie insert): pages ``[0, T // page_size)`` hold
        pure-prompt K/V computed at ``rung``, bitwise what any
        same-rung admission would prefill, so future prompts sharing
        the prefix map them instead of re-prefilling. Newly indexed
        pages take the cache's own arena reference."""
        if self._prefix is None:
            return
        n_full = int(prompt.size) // self.page_size
        if n_full:
            self._incref_pages(
                self._prefix.insert(prompt, rung, pages[:n_full]))

    def _reclaim_cache_pages(self, need: int, fresh: bool,
                             keep=()) -> int:
        """Free LRU cache-only pages (refcount 1 — no row maps them)
        until a ``need``-page request could admit: the raw free count
        and, for fresh admissions, the high-water mark (cached pages
        count as used until reclaimed). ``keep``: the requesting
        prompt's OWN matched chain — evicting it would free pages only
        to grow the same request's private need by exactly as many
        (the ``need`` the caller computed assumed the match), a
        self-defeating reclaim. Partial progress kept — the victims()
        philosophy. Host bookkeeping only."""
        if self._prefix is None:
            return 0
        reserved = self._reserved_prefetch_pages()
        shortfall = need - (len(self.free_pages) - reserved)
        if fresh:
            used = self.pool_pages - len(self.free_pages) + reserved
            hw_cap = self.admit_highwater * self.pool_pages
            shortfall = max(shortfall, math.ceil(used + need - hw_cap))
        if shortfall <= 0:
            return 0
        kept = set(keep)
        freed = self._prefix.evict(
            shortfall,
            lambda p: p not in kept
            and self._page_refs.get(p, 0) == 1)
        self._decref_pages(freed)
        return len(freed)

    def _row_swappable(self, slot: int) -> bool:
        """May the residency manager page this row out? NOT while
        another row maps any of its pages (pin-while-shared: net of
        the cache's own reference, refcount >= 2 means a second reader
        would be left pointing at pages whose bytes are mid-flight).
        Cache-only references don't block — those pages simply STAY
        resident and shareable while the row's private pages move.
        Runs once per active slot per round (the pin loop), so
        membership goes through the O(1) ``has_page`` probe rather
        than materializing the cache's page set."""
        if self._prefix is None:
            return True
        for p in self._slots[slot].pages:
            if (self._page_refs.get(p, 0)
                    - (1 if self._prefix.has_page(p) else 0)) >= 2:
                return False
        return True

    def _row_freeable_pages(self, slot: int) -> int:
        """Pages an eviction of this row would ACTUALLY free (refcount
        1) — the preemption feasibility math must not count shared
        pages it cannot reclaim."""
        if self._prefix is None:
            return len(self._slots[slot].pages)
        return sum(1 for p in self._slots[slot].pages
                   if self._page_refs.get(p, 0) == 1)

    @property
    def prefill_skip_frac(self) -> float:
        """Fraction of submitted prompt tokens whose prefill was
        SKIPPED via a prefix match — the headline capacity/TTFT
        observable (``serve.prefill_skip_frac``;
        tests/test_prefix_cache.py pins it on a template mix)."""
        if not self._prefill_total_tokens:
            return 0.0
        return self._prefill_skip_tokens / self._prefill_total_tokens

    def release_prefix_cache(self) -> None:
        """Drop every cached chain and return cache-only pages to the
        arena (rows keep their own references) — engine teardown and
        the tests' arena-drain helper."""
        if self._prefix is not None:
            self._decref_pages(self._prefix.clear())

    def request_key(self, seq_id: int) -> jax.Array:
        """The per-request PRNG key a default (key=None) submit gets:
        the standalone-reproduction handle. A sampled row's served
        tokens equal ``paged_generate(prompt, budget,
        key=request_key(sid), temperature=engine.temperature,
        top_k=engine.top_k)`` exactly (non-draft engines)."""
        return jax.random.fold_in(self._req_key_base, seq_id)

    def submit(self, prompt, max_new: int, seq_id: int | None = None, *,
               temperature: float | None = None, key=None,
               priority: int = 0, deadline_s: float | None = None,
               resume_prefix=None) -> int:
        """Enqueue a sequence; returns its id. Tokens appear in
        ``finished[id]`` once served. ``temperature``/``key``: per-row
        sampling overrides (sampling engines only; key defaults to
        :meth:`request_key`). ``priority``: lower = more important
        (admission order; with ``preempt=True``, may evict
        numerically-higher classes under page pressure).
        ``deadline_s``: shed the request (empty output, outcome
        ``"shed"``) if still queued this long after submit.
        ``resume_prefix``: tokens this request already emitted
        elsewhere — ``prompt`` must then be the original prompt plus
        those tokens, and the engine prepends them to the output
        instead of re-emitting (the cross-replica resume path: the
        serving-plane router re-queues a dead replica's in-flight
        requests on survivors through this; within one engine,
        preemption builds its resume Requests directly)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(f"prompt must be 1-D nonempty, {prompt.shape}")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if key is not None and self.greedy:
            raise ValueError(
                "per-request key needs a sampling engine (construct "
                "with temperature > 0); a greedy engine never consumes "
                "key streams and would silently ignore it"
            )
        if temperature is not None:
            if self.greedy:
                raise ValueError(
                    "per-request temperature needs a sampling engine "
                    "(construct with temperature > 0); greedy engines "
                    "compile the argmax path only"
                )
            if temperature <= 0.0:
                raise ValueError(
                    f"per-request temperature must be > 0, got "
                    f"{temperature}")
        padded = self._bucket_len(int(prompt.size))  # raises off-ladder
        need = self._pages_for(prompt.size, max_new)
        if need > self.pages_per_seq:
            raise ValueError(
                f"prompt {prompt.size} + budget {max_new} (+ spec "
                f"slack {self.spec_slack}; bucket pad {padded}) needs "
                f"{need} pages > pages_per_seq {self.pages_per_seq}"
            )
        if max(prompt.size + max_new, padded) > self.cfg.max_seq:
            raise ValueError(
                f"prompt {prompt.size} + budget {max_new} (bucket pad "
                f"{padded}) exceeds max_seq {self.cfg.max_seq}"
            )
        sid = self._next_id if seq_id is None else seq_id
        if (sid in self.finished
                or any(r.seq_id == sid for r in self._queue)
                or sid in self._swapped
                or any(b.seq_id == sid for b, _, _ in self._prefetching)
                or any(s.active and s.seq_id == sid
                       for s in self._slots)):
            raise ValueError(
                f"seq_id {sid} already queued/active/finished — outputs "
                "would silently merge under one key"
            )
        self._next_id = max(self._next_id, sid) + 1
        if resume_prefix is not None:
            resume_prefix = np.asarray(resume_prefix, np.int32)
            if resume_prefix.size > prompt.size:
                raise ValueError(
                    f"resume_prefix ({resume_prefix.size} tokens) longer "
                    f"than the prompt ({prompt.size}) that must carry it")
        now = time.perf_counter()
        self._queue.append(Request(prompt, max_new, sid, t_submit=now,
                                   temperature=temperature, key=key,
                                   priority=int(priority),
                                   deadline_s=deadline_s,
                                   resume_prefix=resume_prefix))
        self.stats[sid] = {
            "priority": int(priority), "t_submit": now, "t_first": None,
            "t_finish": None, "tokens": 0, "outcome": None,
            "preemptions": 0, "token_ts": [],
        }
        rtr = reqtracelib.active()
        if rtr is not None:
            rtr.begin_request(sid, now)
        metricslib.get_metrics().gauge("serve.queue_depth").set(
            len(self._queue))
        return sid

    def _queue_order(self) -> list[int]:
        """Queue indices in admission order: priority class first
        (lower number = more important), resumes before fresh arrivals
        within a class (a preempted row's pages were taken FROM it; it
        re-enters ahead of new same-class work), FCFS within that."""
        return sorted(
            range(len(self._queue)),
            key=lambda qi: (self._queue[qi].priority,
                            self._queue[qi].resume_prefix is None, qi))

    def _shed_expired(self) -> None:
        """Admission control, shed side: queued FRESH requests whose
        ``deadline_s`` expired are dropped with an empty output and
        outcome ``"shed"`` (resumes are exempt — their tokens are
        already paid for and preemption guarantees re-admission).
        Host-list bookkeeping only: no device op, nothing dispatched."""
        if not any(req.deadline_s is not None
                   and req.resume_prefix is None
                   for req in self._queue):
            return  # deadline-free traffic: the common fast path
        now = time.perf_counter()
        kept = []
        for req in self._queue:
            if (req.deadline_s is None or req.resume_prefix is not None
                    or now - req.t_submit <= req.deadline_s):
                kept.append(req)
                continue
            self.finished[req.seq_id] = np.zeros((0,), np.int32)
            rec = self.stats.get(req.seq_id)
            if rec is not None:
                rec["outcome"] = "shed"
                rec["t_finish"] = now
            rtr = reqtracelib.active()
            if rtr is not None:
                rtr.finish_request(req.seq_id, now, final="shed")
            self._emit(kind="serve_shed", seq_id=req.seq_id,
                       priority=req.priority,
                       waited_s=now - req.t_submit,
                       deadline_s=req.deadline_s)
            m = metricslib.get_metrics()
            if m.enabled:
                m.counter("serve.shed").inc()
        self._queue = kept
        metricslib.get_metrics().gauge("serve.queue_depth").set(
            len(self._queue))

    def _try_admit(self, overlapped: bool = False) -> int:
        """ONE admission pass per scheduler round: shed, then walk the
        queue in admission order — priority classes first, resumes
        before fresh arrivals within a class, FCFS with skip inside
        that (a large request does not block a small one behind it —
        the documented head-of-line tradeoff) — admitting every
        request that fits. Fresh admissions respect
        ``admit_highwater``: past the mark they back off and stay
        queued (headroom for resumes); resumes bypass it. One shed
        scan and one order sort per ROUND (the admission window is the
        measured bubble; bookkeeping must not inflate it). In a
        private-pages engine admissions only consume slots/pages, so
        a request skipped earlier in the pass cannot become
        admissible later in it and the single sorted walk decides
        exactly what a per-admission re-sort would. With the sharing
        arena that is one-round approximate: a later candidate's
        cache reclaim frees pages, and each admission publishes
        chains that can shrink an earlier-skipped request's private
        need — such a request waits for the next round's pass (it
        keeps its place in the admission order, so nothing starves;
        re-walking the queue per admission would put the trie work
        back in the admission window). Returns the number
        admitted."""
        with metricslib.span("serve.admit_pass", round=self._round):
            return self._admit_pass(overlapped)

    def _admit_pass(self, overlapped: bool) -> int:
        self._shed_expired()
        # one pass-start stamp: every request seated THIS round closes
        # its queued segment here — the span from pass start to its
        # own dispatch-complete is its share of the admission bubble
        t_pass = (time.perf_counter()
                  if reqtracelib.active() is not None else None)
        order = [self._queue[qi] for qi in self._queue_order()]
        admitted = 0
        for req in order:
            free_slot = next(
                (i for i, s in enumerate(self._slots) if not s.active),
                None)
            if free_slot is None:
                break
            fresh = req.resume_prefix is None
            # PRIVATE pages only: a prefix match maps the rest shared
            # (the sharing arena's capacity win); cache-only pages are
            # reclaimed LRU first when the request would not fit —
            # never the request's own matched chain
            chain = self._memo_match(req)
            need = (self._pages_for(req.prompt.size, req.max_new)
                    - len(chain))
            if self._prefix is not None:
                self._reclaim_cache_pages(need, fresh, keep=chain)
            # ONE admissibility definition (_admissible): the policy
            # _maybe_preempt predicts with must be the one applied here
            if not self._admissible(need, fresh=fresh):
                continue
            # identity-keyed removal BEFORE _admit (whose telemetry
            # reads the queue depth): Request is a value dataclass
            # holding ndarrays, so list.remove/__eq__ would be both
            # ambiguous and wrong here
            self._queue = [r for r in self._queue if r is not req]
            self._admit(free_slot, req, overlapped, chain=chain,
                        t_pass=t_pass)
            admitted += 1
        return admitted

    def _admit(self, slot: int, req: Request, overlapped: bool,
               chain: list[int] | None = None,
               t_pass: float | None = None):
        """Dispatch-only admission: every device op (table upload,
        prefill, first-token pick, cursor seeding) enqueues without a
        host readback, so an in-flight decode chunk is never stalled.
        The first token's readback is deferred to
        :meth:`_resolve_pending` at the loop's next sync point.

        Sharing-aware (``prefix_cache=True``): the longest cached
        prefix chain at this prompt's rung maps READ-ONLY into the
        row's leading table entries (incref, no bytes move, no
        compute), private pages are allocated only for the rest, and
        the prefill computes ONLY the tail (:func:`_tail_prefill_one`
        — bit-identical to the monolithic prefill by the rung-keyed
        parity contract). ``chain`` is the matched chain the caller's
        admissibility math already walked (``_try_admit`` sized
        ``need`` and ran the reclaim against it — re-matching here
        would both repeat the trie walk in the admission window and
        let the two walks drift); the hit/miss observables are folded
        in once, here, where the match actually becomes an admission.
        The match/map decisions are host trie walks; nothing here
        reads a device value."""
        if chain is None:
            chain = self._prefix_match(req.prompt)
        m = len(chain)
        if self._prefix is not None:
            self._prefix.count_match(m)
        need = self._pages_for(req.prompt.size, req.max_new)
        self._incref_pages(chain)
        pages = chain + self._alloc_pages(need - m)
        if self.residency is not None:
            self.residency.register_group(
                req.seq_id, need, need * self._page_nbytes,
                tier="hbm", priority=req.priority)
        row = np.full((self.pages_per_seq,), self.trash, np.int32)
        row[:need] = pages
        self._table[slot] = row
        self.cache["table"] = self._upload_table()
        T = int(req.prompt.size)
        padded = self._bucket_len(T)
        prompt = req.prompt
        if padded > T:
            # right-pad to the bucket rung: causality keeps the true
            # prefix exact; the pad K/V is cursor-masked garbage inside
            # pages the row owns, overwritten as the row generates
            prompt = np.concatenate(
                [prompt, np.zeros(padded - T, np.int32)])
        # one-row prefill THROUGH the shared pool: the scatter touches
        # only this row's pages (compiles once per bucket rung)
        # (the rows of recurrent state stay behind: the prefill hands
        # back this admission's one row, _admit_row installs it)
        one = {k: v for k, v in self.cache.items() if k not in STATE_KEYS}
        # fresh upload from the host mirror, NOT a slice of the device
        # table: a full-range slice can alias the same buffer, and
        # _prefill_one donates its table — an alias would delete the
        # engine's live table with it
        one["table"] = self._upload_table(slot)
        M = m * self.page_size
        span_attrs = dict(prompt_len=T, padded_len=padded, matched=M,
                          seq_id=req.seq_id, slot=slot,
                          overlapped=overlapped)
        if self.state_bytes:
            span_attrs["state_bytes"] = self.state_bytes // self.slots
        # whole pages from the matched prefix on, every layer's K and V
        span_attrs["kv_bytes"] = lambda: (
            -(-(padded - M) // self.page_size) * self.page_size
            * self.kv_bytes_per_token)
        # from the instant the request was due to this dispatch
        span_attrs["queued_ms"] = lambda: (
            time.perf_counter() - req.t_submit) * 1e3
        if m:
            # tail-only prefill: positions [M, padded) computed against
            # the mapped prefix pages; the matched span's compute AND
            # page writes are skipped — the TTFT lever the skip-frac
            # gauge measures
            tail = prompt[M:]
            with metricslib.span("serve.prefill", **span_attrs), \
                    tracelib.compile_watch("serving._tail_prefill_one",
                                           _tail_prefill_one,
                                           padded_len=padded, matched=M):
                logits, out = _tail_prefill_one(
                    self.params, jnp.asarray(tail)[None, :],
                    jnp.int32(T - 1 - M), one,
                    cfg=self.cfg, page_size=self.page_size,
                    n_prefix_pages=m, mesh=self.mesh,
                )
        else:
            with metricslib.span("serve.prefill", **span_attrs), \
                    tracelib.compile_watch("serving._prefill_one",
                                           _prefill_one,
                                           padded_len=padded):
                # a diffusion row's prompt pass covers its WHOLE blocks
                # (what lies behind them is padding to it)
                Bk = self.cfg.block_len
                logits, out = _prefill_one(
                    self.params, jnp.asarray(prompt)[None, :],
                    jnp.int32((T // Bk * Bk if Bk else T) - 1), one,
                    cfg=self.cfg, page_size=self.page_size,
                    mesh=self.mesh,
                )
        row_state = {k: out.pop(k) for k in STATE_KEYS if k in out}
        for k, v in out.items():
            if k != "table":
                self.cache[k] = v
        # publish this admission's full-prompt pages (matched chain +
        # newly prefilled) so the NEXT same-rung prompt shares them
        self._insert_prefix(req.prompt, padded, pages)
        self._prefill_total_tokens += T
        self._prefill_skip_tokens += M
        if self.draft_params is not None:
            self.dcache["table"] = self._upload_table()
            done = dict(self.dcache)
            done["table"] = self._upload_table(slot)
            with tracelib.compile_watch("serving._prefill_one[draft]",
                                        _prefill_one,
                                        padded_len=padded):
                _, dout = _prefill_one(
                    self.draft_params, jnp.asarray(prompt)[None, :],
                    jnp.int32(T - 1), done, cfg=self.draft_cfg,
                    page_size=self.page_size, mesh=self.mesh,
                )
            for k, v in dout.items():
                if k != "table":
                    self.dcache[k] = v
        first_dev = None
        # the install donates the cursors, which the chunk in flight may
        # still own: a host span that can wait for the device
        install = metricslib.span("serve.admit_row", seq_id=req.seq_id,
                                  slot=slot)
        if self.cfg.block_len:
            # no token comes out of the prompt pass: the row's first block
            # is the prompt's remainder, then masks
            Bk = self.cfg.block_len
            start = T // Bk * Bk
            first = np.full((Bk,), self.cfg.mask_id, np.int32)
            first[:T - start] = req.prompt[start:]
            with install:
                (self.pos, self.limit, self.blk, self.msk, self.fidx,
                 self.nfw) = _admit_block_row(
                    self.pos, self.limit, self.blk, self.msk, self.fidx,
                    self.nfw, slot, start, T + req.max_new,
                    jnp.asarray(first), T - start)
            self._slots[slot].cursor = start
            self.stats[req.seq_id]["blocks"] = []
        else:
            key = req.key if req.key is not None else self.request_key(
                req.seq_id)
            temp = (req.temperature if req.temperature is not None
                    else self.temperature)
            state = ({k: self.cache[k] for k in row_state}
                     if row_state else None)
            with install:
                (self.pos, self.limit, self.tokens, self.keys, self.temps,
                 first_dev, state) = _admit_row(
                    self.pos, self.limit, self.tokens, self.keys,
                    self.temps, logits, key, jnp.float32(max(temp, 1e-6)),
                    slot, T, req.max_new, state, row_state or None,
                    eos_id=self.eos_id, greedy=self.greedy,
                    top_k=self.top_k)
            if state is not None:
                self.cache.update(state)
        st = self._slots[slot]
        st.seq_id, st.pages, st.prompt_len = req.seq_id, pages, T
        st.budget = req.max_new
        st.out, st.active = [], True
        st.first_dev = first_dev
        st.t_submit = req.t_submit
        st.t_admit = time.perf_counter()
        st.prompt = req.prompt
        st.priority = req.priority
        st.deadline_s = req.deadline_s
        st.temp_override = req.temperature
        st.prefix = ([] if req.resume_prefix is None
                     else [int(t) for t in req.resume_prefix])
        st.padded_len = padded
        st.shared_pages = m
        rec = tracelib.active()
        if rec is not None:
            # all admission device work (table upload, prefill, first-
            # token pick) is now enqueued; the first-token readback in
            # _resolve_pending closes this window. Per-slot SUBTRACK
            # (track=slot+1): overlapped admissions run concurrently
            # with the decode chunk (track 0) by design, and Chrome
            # sync slices on one track must nest
            st.t_dispatch = rec.mark_dispatch(
                "serve.admit", {"seq_id": req.seq_id, "slot": slot,
                                "padded_len": padded,
                                "overlapped": overlapped},
                track=slot + 1)
        if first_dev is not None:
            self._pending.append(slot)
        self._emit(kind="serve_admit", seq_id=req.seq_id, slot=slot,
                   pages=need, prompt_len=T, padded_len=padded,
                   budget=req.max_new, overlapped=overlapped,
                   free_pages=len(self.free_pages),
                   queued=len(self._queue), priority=req.priority,
                   resumed=req.resume_prefix is not None,
                   matched_tokens=M, shared_pages=m)
        mx = metricslib.get_metrics()
        if mx.enabled:
            mx.gauge("serve.queue_depth").set(len(self._queue))
            mx.gauge("serve.free_pages").set(len(self.free_pages))
            if self.state_bytes:
                mx.gauge("engine.state_bytes").set(self.state_bytes)
            mx.gauge("engine.kv_bytes_per_token").set(
                self.kv_bytes_per_token)
            mx.counter("serve.admitted").inc()
            if overlapped:
                mx.counter("serve.admit_overlapped").inc()
            if m:
                mx.counter("serve.prefix_matched_pages").inc(m)
                mx.counter("serve.prefill_skip_tokens").inc(M)
        rtr = reqtracelib.active()
        if rtr is not None:
            # queued (or preempted, for a resume) closed at the pass
            # start; admit_wait covers the host admission work up to
            # dispatch-complete; prefill runs until the first-token
            # readback in _resolve_pending
            rtr.stamp_transition(
                req.seq_id, "admit_wait",
                st.t_admit if t_pass is None else t_pass)
            rtr.stamp_transition(req.seq_id, "prefill")

    def _resolve_pending(self):
        """Host bookkeeping deferred from :meth:`_admit`: read back the
        first tokens (by now computed behind — or overlapped with — the
        decode chunk), stamp TTFT, and finish rows that were done at
        admission (budget 1, or eos as the first token; the device-side
        limit already froze them out of the chunks)."""
        for slot in self._pending:
            st = self._slots[slot]
            with metricslib.span("serve.first_token", seq_id=st.seq_id,
                                 slot=slot):
                first = int(jax.device_get(st.first_dev))
            st.first_dev = None
            # a resumed row's output re-opens with everything it had
            # already emitted before preemption (its prompt carries
            # those tokens, so the device never re-emits them)
            st.out = list(st.prefix) + [first]
            rec = tracelib.active()
            if rec is not None and st.t_dispatch:
                # the readback IS completion: the admission's device
                # work (prefill + first-token pick) is done by now
                rec.mark_complete("serve.admit", st.t_dispatch,
                                  {"seq_id": st.seq_id, "slot": slot},
                                  track=slot + 1)
                st.t_dispatch = 0.0
            now = time.perf_counter()
            rec_s = self.stats.get(st.seq_id)
            resumed = bool(st.prefix)
            if rec_s is not None and rec_s["t_first"] is None:
                rec_s["t_first"] = now
            if rec_s is not None:
                # first-token availability instant (the inter-token
                # digest's window endpoints; a resume stamps only its
                # NEW token — prefix stamps rode the earlier life)
                rec_s.setdefault("token_ts", []).append(now)
            rtr = reqtracelib.active()
            if rtr is not None:
                rtr.stamp_transition(st.seq_id, "decode", now)
            m = metricslib.get_metrics()
            if m.enabled and not resumed:
                # prefill emitted the first token: its readback IS
                # first-token availability (TTFT counted from submit;
                # a resume keeps its ORIGINAL first-token time — the
                # user saw it before the preemption)
                ttft = now - (st.t_submit or now)
                m.histogram("serve.ttft_s").observe(ttft)
                if self.slo is not None:
                    m.histogram(
                        f"serve.ttft_s.p{st.priority}").observe(ttft)
            if (self.eos_id >= 0 and first == self.eos_id) \
                    or st.budget == 1:
                self._finish(slot)
        self._pending.clear()

    # -- completion --------------------------------------------------------

    def _release_slot(self, slot: int):
        """Return a row's pages to the arena and reset its cursors —
        the shared tail of completion AND eviction. The table upload is
        dispatch-only; pos/limit zeroing freezes the row out of future
        chunks (stale keys/temps in an inactive row are never
        consumed). Pages DECREF, never free: a page the prefix index
        or another row still maps stays allocated (the sharing arena's
        one release rule)."""
        st = self._slots[slot]
        with metricslib.span("serve.release", slot=slot,
                             pages=len(st.pages)):
            self._decref_pages(st.pages)
            self._table[slot] = self.trash
            self.cache["table"] = self._upload_table()
            if self.draft_params is not None:
                self.dcache["table"] = self._upload_table()
            self._slots[slot] = _Slot()
            self.pos = self.pos.at[slot].set(0)
            self.limit = self.limit.at[slot].set(0)

    def _upload_table(self, slot: int | None = None):
        """The host's page table (``slot``'s one row of it) as a fresh
        device array, dispatch-only: every upload of the table goes
        through here, under its own span."""
        rows = self._table if slot is None else self._table[slot:slot + 1]
        with metricslib.span("serve.table_upload", bytes=rows.nbytes):
            return jnp.asarray(rows)

    def _residency_release(self, seq_id: int) -> None:
        """Drop a row's blocks from the residency accounting (it
        finished, was preempted back to the queue, or migrated away).
        No-op without a manager."""
        if self.residency is not None:
            self.residency.release_group(seq_id)

    def _finish(self, slot: int):
        st = self._slots[slot]
        with metricslib.span("serve.finish", seq_id=st.seq_id, slot=slot,
                             tokens=len(st.out)):
            self._residency_release(st.seq_id)
            self.finished[st.seq_id] = np.asarray(st.out, np.int32)
            self._emit(kind="serve_finish", seq_id=st.seq_id, slot=slot,
                       tokens=len(st.out), pages_freed=len(st.pages))
            now = time.perf_counter()
            rec_s = self.stats.get(st.seq_id)
            if rec_s is not None:
                rec_s["t_finish"] = now
                rec_s["tokens"] = len(st.out)
                rec_s["outcome"] = "ok"
            rtr = reqtracelib.active()
            if rtr is not None:
                rtr.finish_request(st.seq_id, now)
            m = metricslib.get_metrics()
            if m.enabled:
                dt = now - st.t_admit
                m.histogram("serve.per_token_s").observe(
                    dt / max(1, len(st.out)))
                if self.slo is not None and rec_s is not None \
                        and rec_s["t_first"] is not None and len(st.out) > 1:
                    m.histogram(f"serve.tpot_s.p{st.priority}").observe(
                        (now - rec_s["t_first"]) / (len(st.out) - 1))
                m.counter("serve.finished").inc()
                m.counter("serve.tokens").inc(len(st.out))
                # shared pages don't free with the row — count only what
                # the release will actually return to the arena
                m.gauge("serve.free_pages").set(
                    len(self.free_pages) + self._row_freeable_pages(slot))
            self._release_slot(slot)

    # -- preemption --------------------------------------------------------

    def _reserved_prefetch_pages(self) -> int:
        """Pages spoken for by pulls in flight (dispatched host->HBM
        prefetches whose install has not happened yet): admissions and
        preemption must not hand them to someone else, or the staged
        swap-in starves behind the very traffic it yielded to."""
        return sum(b.n_pages for b, _, _ in self._prefetching)

    def _admissible(self, need: int, fresh: bool) -> bool:
        """Would a request needing ``need`` pages admit right now?
        (free slot + free pages + the fresh-admission high-water mark
        — the same three checks :meth:`_try_admit` applies). Pages and
        slots reserved for in-flight prefetch installs are not free —
        and for the high-water math they count as USED: the staged
        swap-in will occupy them at install, and a fresh admission
        that squeaked under the mark meanwhile would breach the
        headroom the mark reserves."""
        free_slots = sum(1 for s in self._slots if not s.active)
        if free_slots <= len(self._prefetching):
            return False
        reserved = self._reserved_prefetch_pages()
        if need > len(self.free_pages) - reserved:
            return False
        if fresh:
            used = self.pool_pages - len(self.free_pages) + reserved
            if used + need > self.admit_highwater * self.pool_pages:
                return False
        return True

    def _can_resume(self, slot: int) -> bool:
        """Is this active row safely evictable? Its resume request
        (prompt = this admission's prompt + tokens generated since)
        must fit the bucket ladder, the per-sequence table width, and
        the arena — a victim whose resume could never re-admit must
        not be evicted. Host bookkeeping only; no device op."""
        st = self._slots[slot]
        if not st.active or slot in self._pending or st.prompt is None:
            return False
        emitted = len(st.out) - len(st.prefix)
        remaining = st.budget - emitted
        if remaining < 1:
            return False  # about to finish; nothing left to resume
        resumed_len = int(st.prompt.size) + emitted
        if self.prompt_buckets is not None \
                and resumed_len > max(self.prompt_buckets):
            return False
        pages = self._pages_for(resumed_len, remaining)
        return pages <= min(self.pages_per_seq, self.pool_pages)

    def _maybe_preempt(self):
        """Preemption policy, decision half (runs at a chunk boundary,
        nothing in flight): when the most urgent waiting request cannot
        be admitted for lack of pages, evict strictly-lower-priority
        victims — lowest class first, most recently admitted first
        within a class (least sunk latency) — until it fits or no
        eligible victim remains. Only the head of the admission order
        is served per round (starvation-free: it stays the head until
        admitted)."""
        # shed first: an already-expired request must not evict a
        # victim only to be dropped by the admission pass right after
        self._shed_expired()
        if not self._queue:
            return
        order = self._queue_order()
        req = self._queue[order[0]]
        # private pages only — the head's match maps the rest shared
        chain = self._memo_match(req)
        need = (self._pages_for(req.prompt.size, req.max_new)
                - len(chain))
        fresh = req.resume_prefix is None
        if self._prefix is not None:
            # cache-only pages are strictly cheaper to free than a
            # victim's eviction-and-resume round trip: reclaim first
            self._reclaim_cache_pages(need, fresh, keep=chain)
        if self._admissible(need, fresh):
            return  # ordinary admission will take it this round
        victims = [
            v for v in sorted(
                (i for i, s in enumerate(self._slots)
                 if s.active and s.priority > req.priority),
                key=lambda i: (-self._slots[i].priority,
                               -self._slots[i].t_admit))
            if self._can_resume(v)
        ]
        # feasibility BEFORE the first eviction: would evicting EVERY
        # eligible victim actually admit the head? Pages held by
        # non-victim rows (same-or-higher priority) still count toward
        # the fresh high-water cap, so a head they keep over the mark
        # must not trigger evictions — the victim's resume bypasses the
        # mark and re-admits the same round, and the next round evicts
        # it again: an evict/re-prefill thrash loop that collapses
        # goodput while the head stays stuck regardless
        # (refcount-aware: a victim's SHARED pages don't free with it)
        freeable = sum(self._row_freeable_pages(v) for v in victims)
        if need > len(self.free_pages) + freeable:
            return
        if fresh:
            used_after = (self.pool_pages - len(self.free_pages)
                          - freeable)
            if used_after + need > self.admit_highwater * self.pool_pages:
                return
        for v in victims:
            if self._admissible(need, fresh):
                break
            self._preempt(v, for_sid=req.seq_id)

    def _preempt(self, slot: int, for_sid: int | None = None):
        """Evict one active row: snapshot its generated tokens and (in
        sampled mode) its per-row key state to host, return its pages
        to the arena, and re-queue it as a RESUME request whose prompt
        is this admission's prompt + the tokens generated since.
        Causality makes the resumed prefill's cache exactly the
        uninterrupted one, and ``_admit_row`` consumes the snapshot key
        with the same split/pick order ``_chunk_step`` would have — so
        the resumed row's remaining tokens are byte-identical to never
        having been preempted (the oracle in tests/test_serving.py)."""
        st = self._slots[slot]
        new = st.out[len(st.prefix):]
        remaining = st.budget - len(new)
        key = None
        if not self.greedy:
            # jaxlint: disable=host-sync-in-dispatch — eviction IS a
            # deliberate sync point: it runs at a chunk boundary with
            # the victim's last chunk already collected, and the key
            # snapshot is the resume contract (np.array COPIES — the
            # device_get view aliases a buffer _chunk_step donates)
            key = jnp.asarray(np.array(jax.device_get(self.keys))[slot])
        # jaxlint: disable=host-sync-in-dispatch — host-list packing,
        # not a device readback: st.out/new are plain Python ints the
        # collected chunks already materialized
        new_arr = np.asarray(new, np.int32)
        prompt = (np.concatenate([st.prompt, new_arr])
                  if new else st.prompt)
        req = Request(prompt, remaining, st.seq_id,
                      t_submit=st.t_submit,
                      temperature=st.temp_override, key=key,
                      priority=st.priority, deadline_s=st.deadline_s,
                      # jaxlint: disable=host-sync-in-dispatch — same
                      # host-list packing as the prompt above
                      resume_prefix=np.asarray(st.out, np.int32))
        rec_s = self.stats.get(st.seq_id)
        if rec_s is not None:
            rec_s["preemptions"] += 1
        rtr = reqtracelib.active()
        if rtr is not None:
            # decode closes; preempted spans the wait for re-admission
            # (the resume's _admit transitions it to admit_wait)
            rtr.stamp_transition(st.seq_id, "preempted")
        self._emit(kind="serve_preempt", seq_id=st.seq_id, slot=slot,
                   tokens_done=len(st.out), remaining=remaining,
                   pages_freed=len(st.pages), priority=st.priority,
                   for_seq_id=for_sid)
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("serve.preempted").inc()
            m.gauge("serve.free_pages").set(
                len(self.free_pages) + self._row_freeable_pages(slot))
        self._residency_release(st.seq_id)
        self._release_slot(slot)
        self._queue.append(req)
        if m.enabled:
            m.gauge("serve.queue_depth").set(len(self._queue))

    # -- the loop ----------------------------------------------------------

    def _dispatch_chunk(self):
        """Enqueue one ``chunk`` dispatch for the currently active rows
        and return the in-flight handle (participants, their start
        cursors, the un-read token block) — no readback here."""
        with metricslib.span("serve.cursor_sync", site="dispatch",
                             round=self._round):
            # a true COPY, not np.asarray: on CPU that returns a
            # zero-copy view of the device buffer, and _chunk_step
            # DONATES it — an executable that honors the donation
            # (cache-loaded ones do) overwrites the "snapshot" in place
            # with the post-chunk cursors
            # jaxlint: disable=host-sync-in-dispatch — the copy is the
            # PR 2 donation-alias fix; it syncs only on the PREVIOUS
            # chunk's cursors, which _collect_chunk already resolved
            pos_start = np.array(self.pos)
        parts = [i for i, s in enumerate(self._slots) if s.active]
        with metricslib.span("serve.decode_dispatch", chunk=self.chunk,
                             rows=len(parts), round=self._round,
                             # the live rows' positions, summed: what the
                             # chunk's first step attends over, and the
                             # pages of a K/V head it fetches for that
                             ctx_tokens=lambda: pos_start[parts].sum(),
                             kv_pages=lambda: (
                                 pos_start[parts] // self.page_size + 1).sum()
                             ), \
                tracelib.compile_watch("serving._chunk_step",
                                       _chunk_step, chunk=self.chunk):
            (self.cache, self.pos, self.limit, self.tokens, self.keys,
             out) = _chunk_step(
                self.params, self.cache, self.pos, self.limit,
                self.tokens, self.keys, self.temps,
                cfg=self.cfg, chunk=self.chunk, eos_id=self.eos_id,
                greedy=self.greedy, top_k=self.top_k, mesh=self.mesh,
            )
        rec = tracelib.active()
        t_disp = (rec.mark_dispatch(
            "serve.chunk", {"chunk": self.chunk, "rows": len(parts)})
            if rec is not None else 0.0)
        return parts, pos_start, out, t_disp

    def _collect_chunk(self, inflight):
        parts, pos_start, out, t_disp = inflight
        with metricslib.span("serve.decode_round", chunk=self.chunk):
            out = np.asarray(out)  # (chunk, slots); readback = sync
        rec = tracelib.active()
        if rec is not None and t_disp:
            # readback resolved: the dispatch→completion window is the
            # chunk's device time + queueing, a slice on the device
            # track; host gaps between slices are admission bubbles
            rec.mark_complete("serve.chunk", t_disp,
                              {"chunk": self.chunk, "rows": len(parts)})
        with metricslib.span("serve.cursor_sync", site="collect",
                             round=self._round):
            limit_new = np.asarray(self.limit)
        if metricslib.get_metrics().enabled:
            self._count_route()
        # the chunk's tokens all became host-visible at THIS readback —
        # one shared availability instant (honest: intra-chunk device
        # timing is invisible; the inter-token digest tiles stall
        # segments over the gaps BETWEEN these instants)
        now = time.perf_counter()
        with metricslib.span("serve.collect_rows", rows=len(parts),
                             round=self._round):
            for i in parts:
                st = self._slots[i]
                if not st.active:
                    continue
                valid = int(np.clip(limit_new[i] - pos_start[i], 0,
                                    self.chunk))
                st.out.extend(int(t) for t in out[:valid, i])
                rec_s = self.stats.get(st.seq_id)
                if rec_s is not None and valid:
                    rec_s.setdefault("token_ts", []).extend([now] * valid)
                if pos_start[i] + valid >= limit_new[i]:
                    self._finish(i)

    def _dispatch_block(self):
        """:meth:`_dispatch_chunk` for a block-diffusion model: ``chunk``
        block FORWARDS of :func:`_block_chunk` in one dispatch. The live
        rows' block starts are the host's own mirror (``_Slot.cursor``):
        nothing is read back here."""
        parts = [i for i, s in enumerate(self._slots) if s.active]
        # the stored positions the live rows attend over beside their own
        # blocks
        ctx = sum(self._slots[i].cursor for i in parts)
        # the pages of a K/V head a forward fetches: up to its block's end
        end = self.cfg.block_len - 1
        with metricslib.span("serve.decode_dispatch", chunk=self.chunk,
                             forwards=self.chunk, rows=len(parts),
                             block=self.cfg.block_len, round=self._round,
                             ctx_tokens=ctx,
                             kv_pages=lambda: sum(
                                 (self._slots[i].cursor + end)
                                 // self.page_size + 1 for i in parts)), \
                tracelib.compile_watch("serving._block_chunk",
                                       _block_chunk, forwards=self.chunk):
            (self.cache, self.pos, self.blk, self.msk, self.fidx, self.nfw,
             self.dstats, out) = _block_chunk(
                self.params, self.cache, self.pos, self.limit, self.blk,
                self.msk, self.fidx, self.nfw, self.dstats, cfg=self.cfg,
                forwards=self.chunk, **self.unmask)
        rec = tracelib.active()
        t_disp = (rec.mark_dispatch(
            "serve.chunk", {"chunk": self.chunk, "rows": len(parts)})
            if rec is not None else 0.0)
        return parts, ctx, out, t_disp

    def _collect_block(self, inflight):
        """:meth:`_collect_chunk` for a block-diffusion model: every block
        a row committed in the chunk hands out up to B tokens at once (its
        given positions and those at or past the row's limit are cut), all
        with this readback's one availability instant; the readback that
        brings a request's first tokens is its ``serve.first_token``. The
        whole blocks and the forward that settled each position are kept
        as ``stats[seq_id]["blocks"]`` ((2, B) arrays: what a replay of
        the (block, forward) states needs)."""
        parts, ctx, out, t_disp = inflight
        with metricslib.span("serve.decode_round", chunk=self.chunk):
            toks, fidx, commit = (np.asarray(a) for a in out)   # the sync
        rec = tracelib.active()
        if rec is not None and t_disp:
            rec.mark_complete("serve.chunk", t_disp,
                              {"chunk": self.chunk, "rows": len(parts)})
        if metricslib.get_metrics().enabled:
            self._count_route()
            self._count_diffusion()
        B = self.cfg.block_len
        now = time.perf_counter()
        with metricslib.span("serve.collect_rows", rows=len(parts),
                             round=self._round):
            for i in parts:
                st = self._slots[i]
                if not st.active:
                    continue
                end = st.prompt_len + st.budget
                rec_s = self.stats.get(st.seq_id)
                new = 0
                for f in np.nonzero(commit[:, i])[0]:
                    lo = max(0, st.prompt_len - st.cursor)
                    hi = min(B, end - st.cursor)
                    st.out.extend(int(t) for t in toks[f, i, lo:hi])
                    new += hi - lo
                    st.cursor += B
                    if rec_s is not None:
                        rec_s["blocks"].append(
                            np.stack([toks[f, i], fidx[f, i]]))
                if new and len(st.out) == new:
                    self._first_tokens(i, now)
                if rec_s is not None and new:
                    rec_s.setdefault("token_ts", []).extend([now] * new)
                if st.cursor >= end:
                    self._finish(i)
        self._emit(kind="serve_block_chunk", round=self._round,
                   rows=len(parts), ctx_tokens=ctx, forwards=self.chunk,
                   blocks=int(commit[:, parts].sum()))

    def _first_tokens(self, slot: int, now: float) -> None:
        """A diffusion request's first tokens reached the host (none comes
        out of its prompt pass): what :meth:`_resolve_pending` records for
        a first token, at the chunk readback that brought them."""
        st = self._slots[slot]
        with metricslib.span("serve.first_token", seq_id=st.seq_id,
                             slot=slot):
            pass   # an instant: the chunk's readback was the wait
        rec = tracelib.active()
        if rec is not None and st.t_dispatch:
            rec.mark_complete("serve.admit", st.t_dispatch,
                              {"seq_id": st.seq_id, "slot": slot},
                              track=slot + 1)
            st.t_dispatch = 0.0
        rec_s = self.stats.get(st.seq_id)
        if rec_s is not None and rec_s["t_first"] is None:
            rec_s["t_first"] = now
        rtr = reqtracelib.active()
        if rtr is not None:
            rtr.stamp_transition(st.seq_id, "decode", now)
        m = metricslib.get_metrics()
        if m.enabled:
            m.histogram("serve.ttft_s").observe(now - (st.t_submit or now))

    def diffusion_stats(self):
        """A block engine's running sums read to the host now
        (``DIFFUSION_STATS``: forwards run by live rows, blocks committed,
        tokens handed out), None for a model that decodes a token a step.
        int32 that wrap: take differences."""
        return (np.asarray(self.dstats) if self.cfg.block_len else None)

    def _count_diffusion(self) -> None:
        """The engine's diffusion counters (docs/observability.md), from
        what the sums grew by since the last look; as
        :meth:`_count_route`, after a chunk's readback."""
        new = self.diffusion_stats()
        old, self._dstats_seen = self._dstats_seen, new
        forwards, blocks, tokens = (
            int(v) for v in new - (0 if old is None else old))
        mx = metricslib.get_metrics()
        mx.counter("diffusion.forwards").inc(forwards)
        mx.counter("diffusion.blocks").inc(blocks)
        mx.counter("diffusion.tokens").inc(tokens)
        if forwards:
            mx.gauge("diffusion.tokens_per_forward").set(tokens / forwards)

    def route_stats(self):
        """The expert route's running sums read to the host now (rows:
        prefills, decode steps; columns: ``parallel/moe.ROUTE_STATS``),
        None for a model without expert layers. int32 that wrap: take
        differences. Waits for the programs still adding to them."""
        stats = self.cache.get("moe_stats")
        return None if stats is None else np.asarray(stats)

    def _count_route(self) -> None:
        """The engine's route counters (docs/observability.md), from what
        the sums grew by since the last look. After a chunk's readback:
        every program that adds to them (the chunk, and the prefills
        whose first tokens ``_resolve_pending`` already read) has
        finished, so the read waits for nothing."""
        new = self.route_stats()
        if new is None:
            return
        old, self._route_seen = self._route_seen, new
        grew = new - (0 if old is None else old)   # int32: wraps rightly
        picks, tokens = grew.sum(axis=0)[:2]
        mx = metricslib.get_metrics()
        mx.counter("moe.local_picks").inc(int(picks))
        mx.counter("moe.tokens").inc(int(tokens))
        d_picks, _, max_load, touched, calls = (int(v) for v in grew[1])
        if calls > 0:   # decode steps: fullest held expert over the mean
            mx.gauge("moe.load_max_over_mean").set(
                max_load * self.cfg.experts_held / max(d_picks, 1))
            mx.gauge("moe.experts_touched").set(touched / calls)

    def _dispatch_spec(self):
        """``chunk`` draft-assisted rounds per dispatch: budget/EOS
        truncation happens on device between rounds (_spec_chunk), so
        over-acceptance beyond a limit is discarded there and the
        caches' stale rows get overwritten when the cursor re-crosses
        them (the speculative invariant)."""
        parts = [i for i, s in enumerate(self._slots) if s.active]
        with metricslib.span("serve.spec_dispatch", rounds=self.chunk,
                             gamma=self.gamma), \
                tracelib.compile_watch("serving._spec_chunk",
                                       _spec_chunk, rounds=self.chunk,
                                       gamma=self.gamma):
            (self.cache, self.dcache, self.pos, self.limit, self.tokens,
             self._spec_key, emits, advs) = _spec_chunk(
                self.params, self.draft_params, self.cache, self.dcache,
                self.pos, self.limit, self.tokens, self._spec_key,
                self.temps,
                cfg=self.cfg, dcfg=self.draft_cfg, gamma=self.gamma,
                rounds=self.chunk, eos_id=self.eos_id,
                greedy=self.greedy, top_k=self.top_k, mesh=self.mesh,
            )
        rec = tracelib.active()
        t_disp = (rec.mark_dispatch(
            "serve.spec_chunk",
            {"rounds": self.chunk, "gamma": self.gamma,
             "rows": len(parts)}) if rec is not None else 0.0)
        return parts, None, (emits, advs), t_disp

    def _collect_spec(self, inflight):
        parts, _, (emits, advs), t_disp = inflight
        with metricslib.span("serve.spec_round", rounds=self.chunk,
                             gamma=self.gamma):
            emits = np.asarray(emits)  # (rounds, slots, gamma+1)
            advs = np.asarray(advs)    # (rounds, slots)
        rec = tracelib.active()
        if rec is not None and t_disp:
            rec.mark_complete("serve.spec_chunk", t_disp,
                              {"rounds": self.chunk,
                               "rows": len(parts)})
        with metricslib.span("serve.cursor_sync", site="collect",
                             round=self._round):
            pos_np = np.asarray(self.pos)
            limit_np = np.asarray(self.limit)
        now = time.perf_counter()
        with metricslib.span("serve.collect_rows", rows=len(parts),
                             round=self._round):
            for i in parts:
                st = self._slots[i]
                if not st.active:
                    continue
                accepted = 0
                for k in range(advs.shape[0]):
                    v = int(advs[k, i])
                    if v:
                        st.out.extend(int(t) for t in emits[k, i, :v])
                        accepted += v
                rec_s = self.stats.get(st.seq_id)
                if rec_s is not None and accepted:
                    rec_s.setdefault("token_ts", []).extend([now] * accepted)
                if pos_np[i] >= limit_np[i]:
                    self._finish(i)

    def service_round(self, *, decode: bool = True, chaos_index=None,
                      pre_collect=None) -> dict:
        """ONE scheduler round — the core's unit of work, shared by
        :meth:`ContinuousBatcher.run` and the serving plane's router
        (which interleaves rounds across replicas): chaos probe,
        preemption policy, decode-chunk dispatch (overlap mode:
        FIRST, so admissions enqueue behind it), one admission pass,
        deferred first-token readbacks, collect.

        ``decode=False`` is the PREFILL-ROLE round: admissions run
        (table upload, bucket-padded prefill, first-token pick) but no
        decode chunk is ever dispatched — admitted rows park at their
        first token awaiting :meth:`export_migration`. ``pre_collect``:
        called with ``overlapped`` (True iff a decode chunk is in
        flight) AFTER admissions and BEFORE the chunk readback — the
        plane installs arrived KV migrations here, so the install's
        device work enqueues behind the in-flight chunk exactly like an
        overlapped admission. Returns ``{"admitted", "exposed_s"
        (admission host time with nothing in flight), "stalled" (queue
        waits but nothing admitted and nothing runs — the transport
        decides whether that is a deadlock), "active"}``.

        The whole round is one ``serve.round`` span, its phases the
        children (docs/observability.md has the tree)."""
        self._round += 1
        with metricslib.span("serve.round", round=self._round,
                             rows=lambda: self.active_count,
                             queued=len(self._queue)):
            return self._service_round(decode, chaos_index, pre_collect)

    def _service_round(self, decode: bool, chaos_index,
                       pre_collect) -> dict:
        if chaos_index is not None and chaoslib.active() is not None:
            chaoslib.maybe_inject("engine_round", chaos_index)
        # fresh round, fresh head-match memo (_memo_match): the memo's
        # validity argument is scoped to one round's mutations
        self._match_memo = None
        if self.preempt:
            with metricslib.span("serve.preempt_policy",
                                 round=self._round):
                self._maybe_preempt()
        if self.residency is not None:
            self.residency.begin_round()
            for si, s in enumerate(self._slots):
                if s.active:
                    self.residency.touch_group(s.seq_id)
                    if self._prefix is not None:
                        # pin-while-shared: a row whose pages another
                        # row maps (refcount >= 2 net of the cache's
                        # own reference) must not page to host while
                        # the reader is resident — the manager's
                        # victim selection skips pinned groups
                        self.residency.pin_group(
                            s.seq_id, not self._row_swappable(si))
            # pulls for swapped rows dispatch BEFORE the decode chunk:
            # the host->HBM copies fly while the chunk computes, and
            # the install lands behind it at the pre_collect position
            self._dispatch_prefetch()
        spec = self.draft_params is not None
        dispatch = self._dispatch_spec if spec else self._dispatch_chunk
        collect = self._collect_spec if spec else self._collect_chunk
        if self.cfg.block_len:   # a block a row a step, not a token
            dispatch, collect = self._dispatch_block, self._collect_block
        inflight = None
        t_chunk0 = 0.0
        if decode and self.overlap and any(s.active for s in self._slots):
            inflight = dispatch()
            t_chunk0 = time.perf_counter()
        t0 = time.perf_counter()
        admitted = self._try_admit(overlapped=inflight is not None)
        self._resolve_pending()
        exposed_s = 0.0
        stalled = False
        if inflight is None:
            exposed_s = time.perf_counter() - t0
            if decode and any(s.active for s in self._slots):
                inflight = dispatch()
                t_chunk0 = time.perf_counter()
            elif not any(s.active for s in self._slots):
                stalled = (bool(self._queue) and not admitted
                           and not self._swapped
                           and not self._prefetching)
        if self.residency is not None:
            self._install_prefetched(inflight is not None)
        if pre_collect is not None:
            pre_collect(inflight is not None)
        if inflight is not None:
            # children: the readback (serve.decode_round /
            # serve.spec_round), the second one of the cursors
            # (serve.cursor_sync), the walk over the rows
            # (serve.collect_rows, a finished row's serve.finish in it)
            with metricslib.span("serve.collect", rows=len(inflight[0]),
                                 round=self._round):
                collect(inflight)
            if self.track_chunk_windows:
                # host-clock (dispatch, readback-resolved) stamps of
                # this chunk — the serving plane intersects migration
                # windows with these to PROVE the KV handoff hid
                # behind decode compute (kv_migration_overlap_frac)
                self.chunk_windows.append(
                    (t_chunk0, time.perf_counter()))
        if self.residency is not None:
            # round boundary: the chunk is collected, nothing in
            # flight — observe this round's prefetch completions, then
            # run the eviction policy (cold + demanded rows page out)
            self._complete_prefetches()
            self._residency_balance()
        return {"admitted": admitted, "exposed_s": exposed_s,
                "stalled": stalled,
                "active": any(s.active for s in self._slots)}

    # -- router-facing load observables ------------------------------------

    @property
    def free_page_count(self) -> int:
        return len(self.free_pages)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def active_count(self) -> int:
        return sum(1 for s in self._slots if s.active)

    def has_work(self) -> bool:
        return (bool(self._queue) or bool(self._swapped)
                or bool(self._prefetching)
                or any(s.active for s in self._slots))

    def request_pages(self, n_pages: int) -> None:
        """External install pressure (the serving-plane router waiting
        to land a migration bundle): ask the residency manager to free
        ``n_pages`` at this round's balance point. No-op without a
        manager — the caller then waits for ordinary completions."""
        self._external_demand = max(self._external_demand, int(n_pages))

    def would_fit(self, prompt_len: int, max_new: int) -> bool:
        """Could this engine EVER serve the request (table width, pool
        size, ladder, max_seq) — the router's placement feasibility
        check, distinct from :meth:`_admissible`'s right-now check."""
        try:
            padded = self._bucket_len(int(prompt_len))
        except ValueError:
            return False
        need = self._pages_for(prompt_len, max_new)
        return (need <= min(self.pages_per_seq, self.pool_pages)
                and max(prompt_len + max_new, padded) <= self.cfg.max_seq)

    # -- migration (the serving plane's KV handoff) ------------------------

    def migration_admissible(self, n_pages: int) -> bool:
        """Could :meth:`install_migration` of an ``n_pages`` bundle
        succeed right now? Free slot + free pages (minus in-flight
        prefetch reservations); migrations bypass the fresh-admission
        high-water mark for the same reason resumes do — their tokens
        are already paid for."""
        free_slots = sum(1 for s in self._slots if not s.active)
        return (free_slots > len(self._prefetching)
                and n_pages <= len(self.free_pages)
                - self._reserved_prefetch_pages()
                and n_pages <= self.pages_per_seq)

    def exportable_slots(self) -> list[int]:
        """Active rows whose first token is resolved and whose budget
        is not yet exhausted — what a prefill-role replica offers the
        router for migration after a ``decode=False`` round."""
        return [i for i, s in enumerate(self._slots)
                if s.active and i not in self._pending]

    def _no_state_migration(self) -> None:
        if self.cfg.block_len:
            raise ValueError(
                "migration with a block-diffusion model (block_len "
                f"{self.cfg.block_len}): a MigrationBundle carries a row's "
                "pages, cursor and current token; the block in flight "
                "(tokens, masks, forward indices) has no place in it yet")
        if self.state_bytes:
            raise ValueError(
                "migration with a patterned model "
                f"({self.cfg.layer_pattern!r}): a MigrationBundle carries "
                "a row's pages and cursors; the row's recurrent state "
                "would have to travel with them and has no wire form yet")

    def _detach_row(self, slot: int) -> MigrationBundle:
        """Detach one active row into a :class:`MigrationBundle` and
        release its slot/pages — the snapshot half SHARED by
        :meth:`export_migration` (the plane's KV handoff) and the
        residency manager's swap-out (the host-tier eviction): both
        are "this row continues elsewhere", they differ only in where
        the pages go and in the bookkeeping around them.

        Runs at a chunk boundary with the row's device work resolved
        (a prefill-role engine never has a chunk in flight), so the
        cursor/key snapshot is a DELIBERATE sync point — the same
        contract as preemption's snapshot, and the same copy
        discipline: ``np.array`` COPIES, because the device_get view
        aliases buffers a later ``_chunk_step`` donates. The KV pages
        are GATHERED device-side (``pool[idx]`` — a new buffer, no
        host readback of K/V anywhere on the in-process path)."""
        self._no_state_migration()
        st = self._slots[slot]
        if not st.active or slot in self._pending or st.prompt is None:
            raise ValueError(f"slot {slot} has no exportable row")
        if self.draft_params is not None:
            raise ValueError(
                "draft-assisted engines do not migrate: the draft "
                "cache's row state would have to move too")
        # jaxlint: disable=host-sync-in-dispatch — the export snapshot
        # IS a deliberate sync point at a chunk boundary (the resume
        # contract, same as _preempt's key snapshot); np.array COPIES
        pos = int(np.array(jax.device_get(self.pos))[slot])
        # jaxlint: disable=host-sync-in-dispatch — same snapshot
        limit = int(np.array(jax.device_get(self.limit))[slot])
        # jaxlint: disable=host-sync-in-dispatch — same snapshot
        token = int(np.array(jax.device_get(self.tokens))[slot])
        # jaxlint: disable=host-sync-in-dispatch — same snapshot
        key = np.array(jax.device_get(self.keys))[slot].copy()
        # jaxlint: disable=host-sync-in-dispatch — same snapshot
        temp = float(np.array(jax.device_get(self.temps))[slot])
        idx = jnp.asarray(st.pages, dtype=jnp.int32)
        payload = {
            name: tuple(pool[idx] for pool in pools)
            for name, pools in self.cache.items() if name != "table"
        }
        rec_s = self.stats.get(st.seq_id)
        bundle = MigrationBundle(
            seq_id=st.seq_id, prompt=st.prompt, out=list(st.out),
            prefix=list(st.prefix), budget=st.budget, pos=pos,
            limit=limit, token=token, key=key, temp=temp,
            temp_override=st.temp_override, priority=st.priority,
            deadline_s=st.deadline_s, t_submit=st.t_submit,
            t_first=(rec_s or {}).get("t_first"),
            preemptions=int((rec_s or {}).get("preemptions") or 0),
            n_pages=len(st.pages), page_size=self.page_size,
            pages_payload=payload,
            # prefix-resolution metadata: the leading full-prompt pages
            # hold pure-prompt K/V computed at this rung — a sharing
            # destination with the same chain cached maps its own pages
            # for that span instead of installing (byte-exact either
            # way, docs/prefix_cache.md)
            rung=int(st.padded_len),
            prefix_len=((st.prompt_len // self.page_size)
                        * self.page_size if st.padded_len else 0),
        )
        self._release_slot(slot)
        return bundle

    def export_migration(self, slot: int) -> MigrationBundle:
        """Detach one active row for a CROSS-ENGINE handoff — the
        donor half of the serving plane's KV migration (see
        :meth:`_detach_row` for the snapshot contract). The row's
        stats outcome closes as ``"migrated"``: its story continues in
        another engine's table."""
        bundle = self._detach_row(slot)
        rec_s = self.stats.get(bundle.seq_id)
        if rec_s is not None:
            rec_s["outcome"] = "migrated"
        rtr = reqtracelib.active()
        if rtr is not None:
            # decode closes into an open `migrating` segment; the copy
            # rides the bundle so the installer closes it on ITS side
            bundle.segments = rtr.export_history(bundle.seq_id)
        self._residency_release(bundle.seq_id)
        self._emit(kind="serve_migrate_out", seq_id=bundle.seq_id,
                   slot=slot, pages=bundle.n_pages,
                   tokens_done=len(bundle.out))
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("serve.migrated_out").inc()
        return bundle

    def export_swapped(self, seq_id: int) -> MigrationBundle:
        """Export a row currently parked in the HOST tier — the
        cross-TIER migration path: an exported bundle gathers pages
        from wherever they live, so the plane can migrate a row the
        residency manager had swapped out without first paging it back
        in. The payload normalizes to host numpy (the wire codec's
        form; it was already host-resident — a deliberate readback of
        bytes the device no longer owns)."""
        if self.residency is None or seq_id not in self._swapped:
            raise ValueError(
                f"seq_id {seq_id} is not swapped out of this engine")
        bundle = self._swapped.pop(seq_id)
        payload = {
            name: tuple(np.asarray(jax.device_get(a)) for a in arrs)
            for name, arrs in bundle.pages_payload.items()
        }
        bundle = replace(bundle, pages_payload=payload)
        rec_s = self.stats.get(seq_id)
        if rec_s is not None:
            rec_s["outcome"] = "migrated"
        rtr = reqtracelib.active()
        if rtr is not None:
            # the open `swapped_out` segment closes into `migrating`
            bundle.segments = rtr.export_history(seq_id)
        self._residency_release(seq_id)
        self._emit(kind="serve_migrate_out", seq_id=seq_id, slot=-1,
                   pages=bundle.n_pages, tokens_done=len(bundle.out),
                   tier="host")
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("serve.migrated_out").inc()
        return bundle

    def install_migration(self, bundle: MigrationBundle) -> int:
        """Continue a migrated row in THIS engine — the receiver half
        of the KV handoff. Dispatch-only: the table upload, the page
        scatters (:func:`_install_pages`, donated pools), and the
        cursor/key seeding all enqueue without a host readback, so an
        in-flight decode chunk is never stalled (the plane calls this
        from ``service_round``'s ``pre_collect`` hook — behind the
        chunk, the overlapped-admission discipline). Returns the slot.

        Byte-exactness: the installed cursors/key/temp are the donor's
        post-admission state and the KV pages are numerically
        identical, so the next ``_chunk_step`` consumes exactly what
        the donor's would have — the migrated row's remaining tokens
        equal a colocated engine's (the disaggregation oracle)."""
        if self.draft_params is not None:
            raise ValueError("draft-assisted engines do not migrate")
        if bundle.page_size != self.page_size:
            raise ValueError(
                f"page_size mismatch: bundle {bundle.page_size} vs "
                f"engine {self.page_size} — pools are not layout-"
                "compatible across different page sizes")
        if not self.migration_admissible(bundle.n_pages):
            raise ValueError(
                f"migration of {bundle.n_pages} page(s) not admissible "
                f"(free slots {sum(1 for s in self._slots if not s.active)}, "
                f"free pages {len(self.free_pages)})")
        if bundle.seq_id in self.finished \
                or any(r.seq_id == bundle.seq_id for r in self._queue) \
                or bundle.seq_id in self._swapped \
                or any(b.seq_id == bundle.seq_id
                       for b, _, _ in self._prefetching) \
                or any(s.active and s.seq_id == bundle.seq_id
                       for s in self._slots):
            raise ValueError(
                f"seq_id {bundle.seq_id} already known to this engine")
        slot = self._attach_row(bundle)
        if self.residency is not None:
            self.residency.register_group(
                bundle.seq_id, bundle.n_pages,
                bundle.n_pages * self._page_nbytes,
                tier="hbm", priority=bundle.priority)
        self._emit(kind="serve_migrate_in", seq_id=bundle.seq_id,
                   slot=slot, pages=bundle.n_pages, seq=bundle.seq,
                   tokens_done=len(bundle.out))
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("serve.migrated_in").inc()
            m.gauge("serve.free_pages").set(len(self.free_pages))
        return slot

    def _attach_row(self, bundle: MigrationBundle) -> int:
        """Seat a detached row in this engine — the dispatch-only
        install half SHARED by :meth:`install_migration` (cross-engine
        handoff) and the residency manager's swap-in (the prefetched
        host-tier row returning to HBM). Admissibility is the
        CALLER's to have checked. Returns the slot."""
        self._no_state_migration()
        slot = next(i for i, s in enumerate(self._slots) if not s.active)
        # jaxlint: disable=host-sync-in-dispatch — host-list packing of
        # the wire bundle's prompt, not a device readback (the same
        # contract as _preempt's resume-Request packing)
        prompt = np.asarray(bundle.prompt, np.int32)
        # prefix resolution (sharing destinations): the bundle names
        # the page-aligned span of pure-prompt K/V and the rung it was
        # computed at — when this engine's radix index has that exact
        # chain, the span maps to the CACHED pages (incref; bitwise
        # the same bytes, same-rung determinism) and only the rest of
        # the payload installs. A cold cache materializes everything:
        # byte-exact either way.
        resolved: list[int] = []
        if self._prefix is not None and bundle.rung \
                and bundle.prefix_len:
            resolved = self._prefix.match(
                prompt[:bundle.prefix_len], bundle.rung,
                max_pages=bundle.prefix_len // self.page_size)
        m = len(resolved)
        self._incref_pages(resolved)
        pages = resolved + self._alloc_pages(bundle.n_pages - m)
        row = np.full((self.pages_per_seq,), self.trash, np.int32)
        row[:bundle.n_pages] = pages
        self._table[slot] = row
        self.cache["table"] = self._upload_table()
        if m < bundle.n_pages:
            idx = jnp.asarray(pages[m:], dtype=jnp.int32)
            for name, pools in list(self.cache.items()):
                if name == "table":
                    continue
                payload = bundle.pages_payload[name]
                self.cache[name] = tuple(
                    _install_pages(
                        pool, idx,
                        jnp.asarray(pl)[m:] if m else jnp.asarray(pl))
                    for pool, pl in zip(pools, payload))
        self.pos = self.pos.at[slot].set(jnp.int32(bundle.pos))
        self.limit = self.limit.at[slot].set(jnp.int32(bundle.limit))
        self.tokens = self.tokens.at[slot].set(jnp.int32(bundle.token))
        self.keys = self.keys.at[slot].set(
            jnp.asarray(bundle.key, jnp.uint32))
        self.temps = self.temps.at[slot].set(jnp.float32(bundle.temp))
        st = self._slots[slot]
        st.seq_id = bundle.seq_id
        st.pages = pages
        st.prompt_len = int(prompt.size)
        st.budget = bundle.budget
        st.out = list(bundle.out)
        st.prefix = list(bundle.prefix)
        st.active = True
        st.t_submit = bundle.t_submit
        st.t_admit = time.perf_counter()
        st.prompt = prompt
        st.priority = bundle.priority
        st.deadline_s = bundle.deadline_s
        st.temp_override = bundle.temp_override
        st.padded_len = int(bundle.rung)
        st.shared_pages = m
        if bundle.rung:
            # warm this engine's index with the installed chain: the
            # next same-rung prompt sharing the prefix maps it here
            self._insert_prefix(prompt, int(bundle.rung), pages)
        prior = self.stats.get(bundle.seq_id)
        self.stats[bundle.seq_id] = {
            "priority": bundle.priority, "t_submit": bundle.t_submit,
            "t_first": bundle.t_first, "t_finish": None,
            "tokens": 0, "outcome": None,
            "preemptions": bundle.preemptions,
            # token availability stamps survive a LOCAL swap-out/in (the
            # gap across the stall is exactly what the inter-token
            # digest tiles); a migration install starts empty — the
            # donor's stamps are engine-local wall clock, not wire state
            "token_ts": list(prior.get("token_ts") or [])
            if prior is not None else [],
        }
        rtr = reqtracelib.active()
        if rtr is not None:
            # the round-18 half of "starts fresh": t_submit/t_first/
            # preemptions survived the handoff since round 14 (the
            # stats rebuild above), but the lifecycle history did not
            # — adopt the bundle's carried segments (swap-in bundles
            # carry None and keep the LOCAL history; a legacy wire
            # artifact decoded to one untracked span) and open decode
            rtr.install_history(bundle.seq_id, bundle.segments,
                                t=st.t_admit,
                                t_submit=bundle.t_submit)
        return slot


    # -- tiered residency (HBM <-> host paging, memory/residency.py) --------

    def _swap_out(self, slot: int) -> None:
        """Page one active row out to the HOST tier: detach it (the
        :meth:`_detach_row` chunk-boundary snapshot — pages gathered
        device-side, cursors/key to host, slot + HBM pages freed) and
        move the gathered payload to host memory through the manager
        (its ``mem.evict`` window; async on a real pinned-host tier).
        The row is NOT re-prefilled on return — its KV bytes come back
        exactly, which is why swap is strictly cheaper than preemption
        and byte-exactness is free."""
        st = self._slots[slot]
        sid = st.seq_id
        bundle = self._detach_row(slot)
        host_payload = self.residency.push_payload(
            bundle.pages_payload,
            attrs={"seq_id": sid, "pages": bundle.n_pages})
        self._swapped[sid] = replace(bundle,
                                     pages_payload=host_payload)
        rtr = reqtracelib.active()
        if rtr is not None:
            rtr.stamp_transition(sid, "swapped_out")
        self.residency.retier_group(sid, "host")
        self._emit(kind="serve_swap_out", seq_id=sid, slot=slot,
                   pages=bundle.n_pages, tokens_done=len(bundle.out),
                   free_pages=len(self.free_pages))
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("serve.swapped_out").inc()
            m.gauge("serve.free_pages").set(len(self.free_pages))

    def _dispatch_prefetch(self) -> None:
        """Dispatch host->HBM pulls for swapped rows that will fit —
        BEFORE the round's decode chunk, so the transfer flies under
        it (the PR 2 overlapped-admission / PR 9 migration
        discipline). Admission order: priority class first, swap-out
        order (FIFO) within a class, with skip — a big parked row must
        not starve smaller ones behind it. Pulled pages/slots are
        RESERVED (:meth:`_reserved_prefetch_pages`) until the install
        lands in ``pre_collect``."""
        if not self._swapped:
            return
        free_pages = (len(self.free_pages)
                      - self._reserved_prefetch_pages())
        free_slots = (sum(1 for s in self._slots if not s.active)
                      - len(self._prefetching))
        # a STRICTLY more urgent queued class outranks the swap-in: the
        # freed arena goes to admission this round, not to pulling a
        # less important row back (same class: the swapped row wins —
        # its tokens are already paid for, the resume-before-fresh rule)
        q_min = min((r.priority for r in self._queue), default=None)
        # the manager's fitted prefetch depth (autofit): cap in-flight
        # pulls so exposed transfers never stack — None = unlimited,
        # the pre-fit behavior
        depth = getattr(self.residency, "prefetch_depth", None)
        for sid, bundle in sorted(self._swapped.items(),
                                  key=lambda kv: kv[1].priority):
            if depth is not None and len(self._prefetching) >= depth:
                break
            if free_slots < 1:
                break
            if q_min is not None and q_min < bundle.priority:
                break
            if bundle.n_pages > free_pages:
                continue
            rtr = reqtracelib.active()
            if rtr is not None:
                # stamped BEFORE the pull dispatch so an injected
                # slow_host_transfer lands inside prefetch_wait — the
                # chaos-attribution teeth contract
                rtr.stamp_transition(sid, "prefetch_wait")
            payload, handle = self.residency.pull_payload(
                bundle.pages_payload,
                attrs={"seq_id": sid, "pages": bundle.n_pages})
            self._prefetching.append((bundle, payload, handle))
            del self._swapped[sid]
            free_pages -= bundle.n_pages
            free_slots -= 1
            self._emit(kind="serve_prefetch", seq_id=sid,
                       pages=bundle.n_pages)

    def _install_prefetched(self, overlapped: bool) -> None:
        """Seat arrived prefetches back into the arena — the
        ``pre_collect`` position: BEHIND the in-flight decode chunk
        when there is one (``overlapped``), exactly like an overlapped
        admission or a migration install. A bundle that cannot seat
        yet (its reserved slot/pages raced an admission) stays staged
        for the next round — its device payload keeps."""
        if not self._prefetching:
            return
        still = []
        for bundle, payload, handle in self._prefetching:
            free_slots = sum(1 for s in self._slots if not s.active)
            if free_slots < 1 or bundle.n_pages > len(self.free_pages):
                still.append((bundle, payload, handle))
                continue
            slot = self._attach_row(
                replace(bundle, pages_payload=payload))
            self.residency.retier_group(bundle.seq_id, "hbm")
            self._installed_prefetch.append((bundle, handle))
            self._emit(kind="serve_swap_in", seq_id=bundle.seq_id,
                       slot=slot, pages=bundle.n_pages,
                       overlapped=overlapped)
            m = metricslib.get_metrics()
            if m.enabled:
                m.counter("serve.swapped_in").inc()
        self._prefetching = still

    def _complete_prefetches(self) -> None:
        """Close this round's installed prefetch windows at an
        OBSERVED completion and fold their overlap against the decode
        chunk windows into the manager's ``prefetch_overlap_frac`` —
        the Perfetto-visible proof that the pull hid under the chunk."""
        if not self._installed_prefetch:
            return
        # jaxlint: disable=host-sync-in-dispatch — completion
        # measurement at the round boundary (the chunk readback already
        # happened); the window must not close before the install's
        # device work it claims to cover has finished
        jax.block_until_ready(self.temps)
        # NON-destructive filter: on a plane replica the router's
        # migration-overlap accounting prunes and reads this same
        # deque — popping here would delete windows its still-open
        # migrations intersect (and vice versa would understate the
        # overlap fractions). The deque's maxlen bounds memory.
        floor = min(h[3] for _, h in self._installed_prefetch)
        windows = [w for w in self.chunk_windows if w[1] >= floor]
        for _bundle, handle in self._installed_prefetch:
            self.residency.complete_pull(handle, chunk_windows=windows)
        self._installed_prefetch.clear()

    def _residency_balance(self) -> None:
        """Eviction decision, end of round (chunk collected, nothing
        in flight — the same boundary preemption snapshots at): free
        enough HBM for the most urgent DEMAND — the head queued
        request that could not admit, the oldest swapped row waiting
        its turn back in, or router-signaled install pressure
        (:meth:`request_pages`) — by paging policy-chosen victims to
        host; then proactively page out whatever the policy calls cold
        (``ColdAfterNPolicy``). This is how ``free_pages == 0`` became
        a policy knob instead of a refusal."""
        r = self.residency
        avail = len(self.free_pages) - self._reserved_prefetch_pages()
        # pages a victim would ACTUALLY free: shared pages stay with
        # their other readers / the prefix index, so the planning
        # credit uses the refcount-aware count where a slot exists
        slot_of = {s.seq_id: i for i, s in enumerate(self._slots)
                   if s.active}
        sizes = {g.group: (self._row_freeable_pages(slot_of[g.group])
                           if g.group in slot_of else g.n_blocks)
                 for g in r.groups("hbm")}
        victims: list = []

        def planned_avail():
            # pages already slated to free by THIS pass's earlier
            # picks count toward later demands — without the credit,
            # co-occurring demands over-evict and the surplus victims
            # pay a gratuitous host round trip each
            return avail + sum(sizes.get(v, 0) for v in victims)

        # (a) router-signaled install pressure: any victim class
        demand = self._external_demand
        self._external_demand = 0
        if demand > planned_avail():
            victims += r.victims(demand - planned_avail(),
                                 exclude=victims)
        # (b) the head queued request that cannot admit: it may only
        # displace STRICTLY less urgent residents (the preemption
        # victim rule, paging instead of re-prefilling) — a same-class
        # arrival waits for completions, exactly as it would without a
        # manager, so there is no evict/pull-back thrash loop
        if self._queue:
            req = self._queue[self._queue_order()[0]]
            need = self._request_need(req)
            fresh = req.resume_prefix is None
            if not self._admissible(need, fresh=fresh):
                # size the eviction to the BINDING constraint of the
                # _admissible check that failed: raw pages, and — for
                # fresh heads — the admit_highwater cap too (evicting
                # only to the page shortfall would leave a
                # highwater-blocked head queued while the victims paid
                # the host round trip for nothing)
                shortfall = need - planned_avail()
                if fresh:
                    # mirror _admissible's high-water accounting:
                    # reserved prefetch pages count as used, pages
                    # already slated to free this pass do not
                    used = (self.pool_pages - len(self.free_pages)
                            + self._reserved_prefetch_pages()
                            - (planned_avail() - avail))
                    hw_cap = self.admit_highwater * self.pool_pages
                    # host float math (math.ceil of plain ints/floats,
                    # no device value anywhere near it)
                    shortfall = max(shortfall,
                                    math.ceil(used + need - hw_cap))
                free_slots = (sum(1 for s in self._slots
                                  if not s.active)
                              - len(self._prefetching))
                if shortfall <= 0 and free_slots < 1:
                    # the binding failure is the SLOT, not pages: any
                    # single victim frees a whole slot (its pages ride
                    # along) — without this a slot-bound urgent head
                    # waited behind plentiful pages it could not use
                    shortfall = 1
                if shortfall > 0:
                    victims += r.victims(shortfall, exclude=victims,
                                         min_priority=req.priority + 1)
        # (c) the next swapped row due back in (priority class first,
        # swap-out order within it — sorted is stable over insertion):
        # rotation within same-or-less-urgent classes, so a parked row
        # never displaces a more important resident
        if self._swapped and not victims:
            head = sorted(self._swapped.values(),
                          key=lambda b: b.priority)[0]
            if head.n_pages > avail:
                victims += r.victims(head.n_pages - avail,
                                     exclude=victims,
                                     min_priority=head.priority)
        cold = r.cold_groups(exclude=victims)
        for sid in victims + cold:
            slot = next((i for i, s in enumerate(self._slots)
                         if s.active and s.seq_id == sid), None)
            if slot is None or slot in self._pending:
                continue
            if not r.can_host(len(self._slots[slot].pages)):
                # earlier picks in THIS pass consumed the host tier's
                # remaining room — skip, never raise mid-balance
                continue
            if sid in cold and sid not in victims \
                    and sum(1 for s in self._slots if s.active) <= 1:
                # proactive cold paging never empties the arena: one
                # row keeps decoding, so next round's pulls still have
                # a chunk to hide under (demand evictions are exempt —
                # their consumer needs the pages regardless)
                continue
            self._swap_out(slot)


class ContinuousBatcher(EngineCore):
    """The single-process serving engine: :class:`EngineCore` plus the
    classic submission transport — ``submit()`` requests, then
    :meth:`run` until everything drains. The serving plane drives the
    same core through its router instead (one EngineCore per replica);
    this class exists so the single-process path keeps its pre-split
    surface byte-identically."""

    def _submit_due(self, pending_arrivals, due: int, t_run0: float):
        """Submit the first ``due`` arrivals of the schedule."""
        for _ in range(due):
            t_arr, kw = pending_arrivals.popleft()
            asked = kw.get("seq_id")
            with metricslib.span(
                    "serve.submit",
                    seq_id=self._next_id if asked is None else asked,
                    # how long after the instant it was due
                    late_ms=lambda: (time.perf_counter() - t_run0
                                     - t_arr) * 1e3):
                sid = self.submit(**kw)
            # the request entered on the SCHEDULE's clock, not when
            # the loop got around to draining it: TTFT, deadlines, and
            # the goodput must charge the queueing delay the
            # user actually experienced (the drain can lag a whole
            # chunk round or an injected stall behind the arrival
            # instant)
            t_abs = t_run0 + t_arr
            self._queue[-1].t_submit = t_abs
            self.stats[sid]["t_submit"] = t_abs
            rtr = reqtracelib.active()
            if rtr is not None:
                # the queued segment starts where t_submit does, or
                # the drain lag would finalize as a leading untracked
                # gap
                rtr.restamp_submit(sid, t_abs)

    def run(self, *, arrivals=None, max_rounds: int | None = None):
        """Serve until queue, slots, and (open-loop) arrivals drain.
        Returns ``finished``: {seq_id: np.ndarray of emitted tokens
        (<= max_new; ends at eos_id when enabled)}.

        Loop shape (``overlap=True``): DISPATCH the chunk for the rows
        already running, then do this round's admissions behind it —
        the table uploads, bucket-padded prefills, and first-token
        picks all enqueue while the chunk executes, and the chunk's
        readback is the sync point that also resolves them. Admission
        host time with no decode in flight (the first wave, or an
        admission-only iteration) is the ADMISSION BUBBLE; its fraction
        of the run lands in ``last_bubble_frac`` and the
        ``serve.admit_bubble_frac`` gauge. ``overlap=False`` keeps the
        serial order (admit, then decode) — the measurable baseline.

        ``arrivals``: OPEN-loop traffic — ``(t_rel_s, submit_kwargs)``
        pairs; each is submitted once the run clock passes its arrival
        instant (``harness/loadgen.py`` schedules replay this way —
        see ``chipbench/drivers/serve.py``). The loop idles
        in bounded sleeps when nothing is servable but arrivals remain:
        open-loop means traffic comes on the USERS' clock, so overload
        builds queues (and sheds / preempts) instead of slowing the
        offered load. ``max_rounds``: return after this many scheduler
        rounds — state parks at a chunk boundary and a later ``run()``
        continues (the staged-scenario and preemption-test handle); a
        bounded run never idle-waits for a future arrival (undelivered
        arrivals are dropped — re-pass them to the continuing call).

        Robustness hooks per round: the chaos injector's
        ``engine_round`` site fires first (a seeded stalled-host fault
        pauses the real loop), then the preemption policy runs at the
        chunk boundary (nothing in flight), then the ordinary
        dispatch/admit/collect round."""
        t_run0 = time.perf_counter()
        t_exposed = 0.0
        pending_arrivals = (deque(sorted(arrivals, key=lambda a: a[0]))
                            if arrivals else None)
        chaos_on = chaoslib.active() is not None
        rounds = 0
        while True:
            if pending_arrivals:
                now_rel = time.perf_counter() - t_run0
                due = 0
                while due < len(pending_arrivals) \
                        and pending_arrivals[due][0] <= now_rel:
                    due += 1
                if due:
                    # only a drain that submits is a phase: an empty
                    # look at the schedule gets no span
                    with metricslib.span("serve.arrivals", n=due):
                        self._submit_due(pending_arrivals, due, t_run0)
            if not self.has_work():
                if not pending_arrivals:
                    break
                if max_rounds is not None:
                    # a bounded run parks at the chunk boundary — it
                    # must not block idling for a future arrival
                    break
                # open-loop idle: nothing servable until the next
                # arrival — wait on the schedule's clock, boundedly
                wait = pending_arrivals[0][0] - (time.perf_counter()
                                                 - t_run0)
                with metricslib.span("serve.idle_wait"):
                    time.sleep(min(max(wait, 0.0), 0.005))
                continue
            if max_rounds is not None and rounds >= max_rounds:
                break
            rounds += 1
            r = self.service_round(
                chaos_index=rounds - 1 if chaos_on else None)
            t_exposed += r["exposed_s"]
            if r["stalled"]:
                raise RuntimeError(
                    "serving deadlock: waiting requests but no "
                    "admissible slot/pages (pool too small for "
                    "the smallest waiting request, or "
                    "admit_highwater leaves it no headroom)"
                )
        total = time.perf_counter() - t_run0
        if self.residency is not None:
            self.residency.drain()  # close any open mem.evict windows
        self.last_bubble_frac = (t_exposed / total) if total > 0 else 0.0
        self._serve_s += total
        m = metricslib.get_metrics()
        if m.enabled:
            m.gauge("serve.admit_bubble_frac").set(self.last_bubble_frac)
            m.gauge("serve.prefill_compiles").set(prefill_cache_size())
            if self._prefix is not None:
                m.gauge("serve.prefill_skip_frac").set(
                    self.prefill_skip_frac)
        if self.slo is not None:
            # goodput (SLO-attained tok/s) lands NEXT TO raw tok/s —
            # the whole point of declaring targets; the base is the
            # engine's cumulative serve time so re-used engines stay
            # consistent across waves
            self.last_slo = slolib.attainment(self.stats, self.slo,
                                              self._serve_s)
            if m.enabled:
                tot = self.last_slo["total"]
                m.gauge("serve.tok_s").set(tot["tok_s"])
                m.gauge("serve.goodput_tok_s").set(tot["goodput_tok_s"])
        return self.finished
