"""Driver ``serve_laguna``: ``drivers/serve.py``'s window for the
``laguna`` layers on one chip's share of a two-chip expert-parallel
deployment: window and global attention side by side, each with its own
head count and rotation and a per-head output gate, the window layers'
K/V in a ring of pages a slot beside the global layers' arena; the leading
dense layer; a softmax top-10 of 256 SiLU-gated experts, 128 of them held
here, with a shared expert.

The engine, the warm-up, the window, the per-request times and the sample
are ``serve.py``'s own, and the comparison is ``serve_hybrid``'s form
(three numbers over every sampled position's gap); this file brings the
model's configuration, its weights in the program's layout, the plain
reference, and, in a traced run, one record a chunk of the rows' positions
and of what the route's sums grew by (the per-layer metrics count the
traced chunks by them).
"""

from __future__ import annotations

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench import weights_laguna as weights
from chipbench.drivers.serve import (Engine, sample_served, serve_window,
                                     summarize, warm)
from chipbench.drivers.serve_hybrid import gap_checks, gap_stats, route_facts
from chipbench.reference import laguna as ref

from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.models import transformer as progmodel


def model_config(config: dict, engine: dict) -> progmodel.TransformerConfig:
    kinds = getattr(progmodel, "LAYER_KINDS", {})
    if not (getattr(kinds.get("W"), "window", False) and "D" in kinds):
        # a program from before the window layers: say so, at once
        print("chipbench: refused: this program has no window-attention "
              "layer kind with a ring cache (LAYER_KINDS 'W') and no "
              "global-attention dense layer kind ('D')", file=sys.stderr)
        raise SystemExit(2)
    m = weights.model_dims(config)
    theta, dims, yarn = m["rope_g"]
    return progmodel.TransformerConfig(
        vocab=m["V"], d_model=m["D"], n_heads=m["H"], n_kv_heads=m["Hkv"],
        attn_head_dim=m["Dh"], n_layers=m["L"], d_ff=m["Fd"],
        max_seq=config["max_position_embeddings"], dtype="bfloat16",
        attention="flash", pos_embed="rope", rope_theta=theta,
        rope_partial=dims / m["Dh"], rope_yarn=yarn,
        decode_attn=engine["decode_attn"], layer_pattern=m["pattern"],
        norm_eps=m["eps"], window=m["window"], window_heads=m["Hw"],
        window_rope_theta=m["rope_w"][0], attn_gate=True,
        moe_experts=m["E"], moe_held=m["held"], moe_held_start=m["held0"],
        moe_top_k=m["k"], moe_d_ff=m["F"], moe_shared_d_ff=m["Fs"],
        moe_scale=m["scale"], moe_renorm=m["renorm"])


class RingEngine(Engine):
    """``serve.py``'s engine; with ``log_sums`` on (a traced run), each
    chunk's readback is followed by a record of its live rows' positions
    at dispatch, the steps each took, and what the route's sums have grown
    to: a readback of its own, which a timed run does not make."""

    log_sums = False

    def __init__(self, *a, **kw):
        self.chunk_log: list[dict] = []
        super().__init__(*a, **kw)

    def _collect_chunk(self, inflight):
        if not self.log_sums:
            return super()._collect_chunk(inflight)
        parts, pos_start = inflight[0], inflight[1]
        limit = np.array(self.limit)   # the chunk's own: before releases
        super()._collect_chunk(inflight)
        self.chunk_log.append({
            "t": time.perf_counter(),
            "pos": [int(pos_start[i]) for i in parts],
            "steps": [int(np.clip(limit[i] - pos_start[i], 0, self.chunk))
                      for i in parts],
            "route": self.route_stats()[1].astype(np.int64)})


def chunk_facts(log, window: int) -> list[dict]:
    """A record a chunk: its readback instant; its rows' decode steps;
    the keys those steps attend over in a global layer (a step at
    position p sees p + 1) and in a window layer (at most ``window``);
    and what the route's decode sums grew by over it (the first chunk
    logged has no chunk before it to differ from)."""
    out = []
    for before, r in zip(log, log[1:]):
        picks, _, _, touched, calls = (int(v) for v
                                       in r["route"] - before["route"])
        keys = [p + 1 + s for p, n in zip(r["pos"], r["steps"])
                for s in range(n)]
        out.append({"t": r["t"], "rows": len(r["pos"]), "steps": len(keys),
                    "keys": sum(keys),
                    "keys_window": sum(min(c, window) for c in keys),
                    "picks": picks, "touched": touched, "calls": calls})
    return out


def make_params(seed: int, m: dict):
    """The served share of the weights, on the device: one jitted call a
    layer and one for the embedding and the head, each leaf rounded to
    bfloat16 as it is made."""
    key = weights.seed_key(seed)
    top = jax.jit(lambda k: weights.build_top(k, m, jnp.bfloat16))(key)
    layers = tuple(
        jax.jit(partial(weights.build_layer, m=m, index=i,
                        dtype=jnp.bfloat16))(key)
        for i in range(m["L"]))
    return {**top, "layers": layers}


def build_engine(ctx):
    eng = ctx.cell["engine"]
    m = weights.model_dims(ctx.config)
    # first what a program without the window layers refuses, before any
    # weight
    cfg = model_config(ctx.config, eng)
    metricslib.configure(enabled=False, mirror_traces=ctx.tracer.enabled)
    params = make_params(ctx.seed, m)
    engine = RingEngine(
        params, cfg, slots=eng["slots"], pool_pages=eng["pool_pages"],
        pages_per_seq=eng["pages_per_seq"], page_size=eng["page_size"],
        chunk=eng["chunk"], prompt_buckets=eng["prompt_buckets"],
        overlap=eng["overlap"])
    engine.log_sums = ctx.tracer.enabled
    return engine


def serving_gap(seed: int, m: dict, served, tail_above: float, *,
                lowp=None, pad_to=512) -> dict:
    """``serve_hybrid.serving_gap`` over this model's reference: at every
    sampled position, the reference's largest logit less its logit of the
    served token. ``served``: (prompt, tokens) pairs. With ``lowp`` the
    tokens judged are those the reference in that precision puts first
    at the same positions (a control, which need not decode), and the
    served ones' stats ride along as ``program``. ``one_wrong``: every
    token of a request replaced by its neighbour in the vocabulary."""
    seqs, rows = [], []
    for prompt, toks in served:
        seqs.append(np.concatenate([prompt, toks[:-1]]))
        rows.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks)))
    logits = ref.logits_at(seed, m, seqs, rows, pad_to=pad_to)
    best = [np.asarray(jnp.max(z, axis=-1)) for z in logits]

    def gaps(picked):
        return [b - np.asarray(jnp.take_along_axis(
            z, jnp.asarray(t, jnp.int32)[:, None], axis=-1)[:, 0])
            for z, b, t in zip(logits, best, picked)]

    tokens = [np.asarray(t) for _, t in served]
    out = {"judged": gap_stats(gaps(tokens), tail_above),
           "one_wrong": gap_stats(gaps([(t + 1) % m["V"] for t in tokens]),
                                  tail_above)}
    if lowp:
        low = ref.logits_at(seed, m, seqs, rows, lowp=lowp, pad_to=pad_to)
        out["program"] = out["judged"]
        out["judged"] = gap_stats(
            gaps([np.asarray(jnp.argmax(z, axis=-1)) for z in low]),
            tail_above)
    return out


def run(ctx) -> dict:
    cell = ctx.cell
    m = weights.model_dims(ctx.config)
    engine = build_engine(ctx)
    requests = traffic.serving_requests(cell["traffic"], m["V"], ctx.seed,
                                        ctx.seconds)
    warm(engine, requests, m["V"])
    engine.chunk_log.clear()
    stats0 = engine.route_stats()
    finished, t0, t1 = serve_window(engine, requests, ctx.tracer)
    stats1 = engine.route_stats()
    per = summarize(engine, requests, finished)
    device = ctx.device_report()
    p95 = lambda v: traffic.percentile(v, 95) if v else None
    facts = {
        "loadgen_late_p95_ms": p95(per["late"]),
        "queue_p95_ms": p95(per["queue"]),
        "admit_bubble_pct": 100.0 * engine.last_bubble_frac,
        "window_wall_s": t1 - t0,
        "chunk": engine.chunk,
        "slots": engine.slots,
        "admissions": [(t, pad, true) for _, t, pad, true
                       in engine.admissions],
        "token_instants": [(len(r.prompt), engine.stats[r.index]["token_ts"])
                           for r in requests if r.index in engine.stats],
        "chunks": chunk_facts(engine.chunk_log, m["window"]),
        **route_facts(stats0, stats1, m["held"]),
    }
    end_to_end = {"ttft_p95_ms": p95(per["ttft"]) or float("nan"),
                  "tpot_p95_ms": p95(per["tpot"]) or float("nan"),
                  "setup_s": t0 - ctx.t_process_start}
    served = sample_served(requests, finished, ctx.seed,
                           cell["check"]["sample"])
    engine.params = engine.cache = None
    del engine
    lowp = (ctx.control[4:] if (ctx.control or "").startswith("ref-")
            else None)   # a control: the reference in that precision
    check = cell["check"]
    gap = (serving_gap(ctx.seed, m, served, check["tail_above"], lowp=lowp,
                       pad_to=cell["traffic"]["max_total"])
           if served else {})
    checks = gap_checks(gap.get("judged", {}), check) + [
        ("requests_failed", float(per["failed"]), 0.0)]
    return {"end_to_end": end_to_end, "facts": facts,
            "attempted": sum(r.measured for r in requests),
            "failed": per["failed"],
            "checks": checks, "device": device,
            "readings": {"gaps": gap,
                         **{k: v for k, v in facts.items()
                            if k.startswith("moe_")}}}
