"""Driver ``serve``: open-loop traffic through ``ContinuousBatcher.run``.

The window is one call of the program's own entry,
``ContinuousBatcher(params, cfg, ...).run(arrivals=...)``: admission, the
page arena, bucketed flash prefill, chunked paged decode, the greedy pick.
The benchmark makes the weights and the traffic, stamps what the engine
already exposes (``stats``, ``last_bubble_frac``, the ``emit`` hook), and
after the window compares a seeded sample of what was served with the
plain reference.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import traffic, weights
from chipbench.reference import transformer as ref

from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.models import transformer as progmodel
from hpc_patterns_tpu.models.serving import ContinuousBatcher, pad_to_bucket

WARM_ID0 = 1 << 30   # sequence ids of the warm-up wave, clear of the window's


class Engine(ContinuousBatcher):
    """The program's engine, with two host instants kept that it drops:
    when ``submit`` really ran (``run`` restamps ``t_submit`` to the
    schedule's clock; how late the loop drained an arrival is the load
    generator's metric) and when each admission was dispatched (the
    ``emit`` hook's ``serve_admit`` record)."""

    def __init__(self, *a, **kw):
        self.real_submit: dict[int, float] = {}
        self.admissions: list[tuple] = []   # (seq_id, instant, rung, true)
        super().__init__(*a, emit=self._record, **kw)

    def _record(self, **kw):
        if kw.get("kind") == "serve_admit":
            self.admissions.append((kw["seq_id"], time.perf_counter(),
                                    kw["padded_len"], kw["prompt_len"]))

    def submit(self, *a, **kw):
        sid = super().submit(*a, **kw)
        self.real_submit[sid] = time.perf_counter()
        return sid


def model_config(config: dict, engine: dict) -> progmodel.TransformerConfig:
    m = weights.model_dims(config)
    return progmodel.TransformerConfig(
        vocab=m["V"], d_model=m["D"], n_heads=m["H"], n_layers=m["L"],
        d_ff=m["F"], n_kv_heads=m["Hkv"],
        max_seq=config["max_position_embeddings"], dtype="bfloat16",
        attention="flash", pos_embed="rope", rope_theta=m["theta"],
        decode_attn=engine["decode_attn"])


def _build_int8(key, m: dict):
    """The seed's weights in the program's own int8 layout (what
    ``quantize_weights_int8`` gives: int8 values, a float32 scale per
    output channel under ``<name>_qscale``), quantized layer by layer so
    that the float32 stack never exists."""
    q, sfx = progmodel._quantize_channels, progmodel.QUANT_SCALE_SUFFIX

    def one(i):
        lw = weights.layer(key, m, i)
        for name in progmodel.QUANTIZED_LAYER_WEIGHTS:
            lw[name], lw[name + sfx] = q(lw[name])
        return lw

    top = weights.top(key, m)
    top["lm_head"], top["lm_head" + sfx] = q(top["lm_head"])
    return {**top, "layers": lax.map(one, jnp.arange(m["L"]))}


def make_params(seed: int, m: dict, control: str | None):
    """The served weights, on the device, in one jitted call."""
    key = weights.seed_key(seed)
    if control == "int8":   # the program's own lower-precision path
        return jax.jit(lambda k: _build_int8(k, m))(key)
    return jax.jit(lambda k: weights.build(k, m))(key)


def build_engine(ctx):
    eng = ctx.cell["engine"]
    m = weights.model_dims(ctx.config)
    metricslib.configure(enabled=False, mirror_traces=ctx.tracer.enabled)
    params = make_params(ctx.seed, m, ctx.control)
    return Engine(
        params, model_config(ctx.config, eng), slots=eng["slots"],
        pool_pages=eng["pool_pages"], pages_per_seq=eng["pages_per_seq"],
        page_size=eng["page_size"], chunk=eng["chunk"],
        prompt_buckets=eng["prompt_buckets"], overlap=eng["overlap"])


def warm(engine: Engine, requests, vocab: int) -> None:
    """Every shape the window uses: one prefill per bucket rung that the
    traffic reaches, the admit pick and the decode chunk."""
    rungs = sorted({pad_to_bucket(engine.prompt_buckets, len(r.prompt))
                    for r in requests})
    rng = np.random.default_rng(0)
    new = engine.chunk + 2   # the pick, one whole chunk, one step more
    for i, b in enumerate(rungs):
        engine.submit(rng.integers(0, vocab, size=b - new, dtype=np.int32),
                      new, seq_id=WARM_ID0 + len(engine.finished) + i)
    engine.run()
    jax.block_until_ready(engine.cache["k"][0])
    engine.admissions.clear()


def serve_window(engine: Engine, requests, tracer=None):
    """The schedule replayed open-loop through the program's ``run``,
    which returns once every request has drained. Returns what it
    finished, the host instant at which the measured window opened (the
    lead-in's last request is due then) and the instant ``run`` came
    back."""
    lead = -min(0.0, min(r.due_s for r in requests))
    arrivals = [(r.due_s + lead, {"prompt": r.prompt, "max_new": r.max_new,
                                  "seq_id": r.index}) for r in requests]
    t0 = time.perf_counter() + lead
    if tracer is not None:
        tracer.begin(t0)
        tracer.run_in_thread()
    finished = engine.run(arrivals=arrivals)
    t1 = time.perf_counter()
    if tracer is not None:
        tracer.finish()
    return finished, t0, t1


def summarize(engine: Engine, requests, finished) -> dict:
    """Per-request times in ms of the measured requests, all from the
    instant a request was DUE on the schedule's clock. A shed or failed
    request counts as failed, not as fast."""
    stats = engine.stats
    admit_at = {sid: t for sid, t, _, _ in engine.admissions}
    out = {"ttft": [], "tpot": [], "late": [], "queue": [], "failed": 0}
    for r in (r for r in requests if r.measured):
        s = stats.get(r.index)
        if not (s is not None and s["outcome"] == "ok"
                and s["t_first"] is not None
                and len(finished.get(r.index, ())) == r.max_new):
            out["failed"] += 1
            continue
        due = s["t_submit"]
        out["ttft"].append((s["t_first"] - due) * 1e3)
        out["late"].append((engine.real_submit[r.index] - due) * 1e3)
        out["queue"].append((admit_at[r.index] - due) * 1e3)
        if s["tokens"] >= 2:
            out["tpot"].append((s["t_finish"] - s["t_first"])
                               / (s["tokens"] - 1) * 1e3)
    return out


def serving_gap(seed: int, m: dict, served, *, lowp=None) -> float:
    """The widest gap by which a token's logit lies below the reference's
    best. ``served``: (prompt, tokens) pairs. With ``lowp`` the tokens
    judged are those the reference in that precision puts first at the
    same positions (a control, which need not decode)."""
    seqs, rows = [], []
    for prompt, toks in served:
        seqs.append(np.concatenate([prompt, toks[:-1]]))
        rows.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks)))
    logits = ref.logits_at(seed, m, seqs, rows)
    picked = [jnp.asarray(t, jnp.int32) for _, t in served]
    if lowp:
        low = ref.logits_at(seed, m, seqs, rows, lowp=lowp)
        picked = [jnp.argmax(z, axis=-1).astype(jnp.int32) for z in low]
    worst = 0.0
    for z, t in zip(logits, picked):
        gap = jnp.max(z, axis=-1) - jnp.take_along_axis(
            z, t[:, None], axis=-1)[:, 0]
        worst = max(worst, float(jnp.max(gap)))
    return worst


def sample_served(requests, finished, seed: int, n: int):
    """A seeded sample of the finished requests, the longest in it."""
    done = [r for r in requests
            if r.measured and len(finished.get(r.index, ())) > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(finished[r.index]))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 9]))
    rest = [r for r in done if r is not longest]
    pick = [rest[i] for i in rng.permutation(len(rest))[:max(0, n - 1)]]
    return [(r.prompt, np.asarray(finished[r.index]))
            for r in [longest] + pick]


def run(ctx) -> dict:
    cell = ctx.cell
    m = weights.model_dims(ctx.config)
    engine = build_engine(ctx)
    requests = traffic.serving_requests(cell["traffic"], m["V"], ctx.seed,
                                        ctx.seconds)
    warm(engine, requests, m["V"])
    finished, t0, t1 = serve_window(engine, requests, ctx.tracer)
    per = summarize(engine, requests, finished)
    device = ctx.device_report()
    p95 = lambda v: traffic.percentile(v, 95) if v else None
    facts = {
        "loadgen_late_p95_ms": p95(per["late"]),
        "queue_p95_ms": p95(per["queue"]),
        "admit_bubble_pct": 100.0 * engine.last_bubble_frac,
        "window_wall_s": t1 - t0,
        "chunk": engine.chunk,
        # one row per admission, in order: host instant, rung, true length
        "admissions": [(t, pad, true) for _, t, pad, true
                       in engine.admissions],
        # per request: prompt length and the host instants at which its
        # tokens became visible (the first is the prefill's)
        "token_instants": [(len(r.prompt), engine.stats[r.index]["token_ts"])
                           for r in requests if r.index in engine.stats],
    }
    end_to_end = {"ttft_p95_ms": p95(per["ttft"]) or float("nan"),
                  "tpot_p95_ms": p95(per["tpot"]) or float("nan"),
                  "setup_s": t0 - ctx.t_process_start}
    # the comparison comes after the window and the memory reading, with
    # the program's state freed
    served = sample_served(requests, finished, ctx.seed,
                           cell["check"]["sample"])
    engine.params = engine.cache = None
    del engine
    lowp = (ctx.control[4:] if (ctx.control or "").startswith("ref-")
            else None)   # a control: the reference in that precision
    gap = serving_gap(ctx.seed, m, served, lowp=lowp) if served else None
    checks = [("served_logit_gap", gap, cell["check"]["gap_limit"]),
              ("requests_failed", float(per["failed"]), 0.0)]
    return {"end_to_end": end_to_end, "facts": facts,
            "attempted": sum(r.measured for r in requests),
            "failed": per["failed"],
            "checks": checks, "device": device}
