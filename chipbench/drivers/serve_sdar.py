"""Driver ``serve_sdar``: ``drivers/serve.py``'s window for the
``sdar_moe`` layer (GQA attention with q/k head norms, then a softmax
top-8 of 128 SiLU-gated experts) served by DIFFUSION OVER BLOCKS: a step
settles part of a block of 4 positions a row, not one token a row.

The engine, the warm-up, the window, the per-request times and the sample
are ``serve.py``'s own; this file brings the model's configuration, its
weights in the program's layout, one record a chunk of what the engine's
sums grew by (a traced run's per-layer metrics count the traced chunks by
them), and the comparison: every (block, forward) state the engine
recorded for the sampled requests, replayed through the plain reference.
"""

from __future__ import annotations

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench import weights_sdar as weights
from chipbench.drivers.serve import (Engine, sample_served, serve_window,
                                     summarize, warm)
from chipbench.drivers.serve_hybrid import gap_stats, route_facts
from chipbench.reference import sdar as ref

from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.models import transformer as progmodel


def model_config(config: dict, engine: dict) -> progmodel.TransformerConfig:
    if not hasattr(progmodel.TransformerConfig, "block_len"):
        # a program from before the block step: say so, at once
        print("chipbench: refused: this program has no layer kind with "
              "routed gated experts and no generation by diffusion over "
              "blocks (TransformerConfig.block_len)", file=sys.stderr)
        raise SystemExit(2)
    m = weights.model_dims(config)
    return progmodel.TransformerConfig(
        vocab=m["V"], d_model=m["D"], n_heads=m["H"], n_kv_heads=m["Hkv"],
        attn_head_dim=m["Dh"], n_layers=m["L"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"], dtype="bfloat16",
        attention="flash", pos_embed="rope", rope_theta=m["theta"],
        decode_attn=engine["decode_attn"], layer_pattern="R" * m["L"],
        norm_eps=m["eps"], qk_norm=True, moe_experts=m["E"],
        moe_top_k=m["k"], moe_d_ff=m["F"], moe_renorm=m["renorm"],
        block_len=m["B"], mask_id=m["mask_id"])


def unmask_rule(config: dict) -> dict:
    """What a denoising forward settles, from the configuration's
    ``generation`` group: the reference's keyword arguments."""
    gen = config["generation"]
    return {"rule": gen["remasking"].removeprefix("low_confidence_"),
            "steps": gen["denoising_steps"],
            "threshold": gen["confidence_threshold"]}


def unmask_args(config: dict) -> dict:
    """The same as the engine's constructor arguments."""
    return {f"unmask_{k}": v for k, v in unmask_rule(config).items()}


class BlockEngine(Engine):
    """``serve.py``'s engine; with ``log_sums`` on (a traced run), each
    chunk's ``serve_block_chunk`` record is kept with what the route's and
    the diffusion sums have grown to at its readback."""

    log_sums = False

    def __init__(self, *a, **kw):
        self.chunk_log: list[dict] = []
        super().__init__(*a, **kw)

    def _record(self, **kw):
        super()._record(**kw)
        if kw.get("kind") == "serve_block_chunk" and self.log_sums:
            self.chunk_log.append({
                "t": time.perf_counter(), "rows": kw["rows"],
                "ctx_tokens": kw["ctx_tokens"],
                "route": self.route_stats()[1].astype(np.int64),
                "diffusion": self.diffusion_stats().astype(np.int64)})


def chunk_facts(log) -> list[dict]:
    """A record a chunk: its readback instant, the rows live and their
    stored positions at its dispatch, and what the sums grew by over it
    (the first chunk logged has no chunk before it to differ from)."""
    out = []
    for before, r in zip(log, log[1:]):
        picks, _, _, touched, calls = (int(v) for v
                                       in r["route"] - before["route"])
        forwards, blocks, tokens = (int(v) for v in r["diffusion"]
                                    - before["diffusion"])
        out.append({"t": r["t"], "rows": r["rows"],
                    "ctx_tokens": r["ctx_tokens"], "forwards": forwards,
                    "blocks": blocks, "tokens": tokens, "picks": picks,
                    "touched": touched, "calls": calls})
    return out


def make_params(seed: int, m: dict):
    """The weights on the device, in one jitted call, each leaf rounded
    to bfloat16 as it is made."""
    fm = ref._freeze(m)
    return jax.jit(lambda k: weights.build(k, dict(fm), jnp.bfloat16))(
        weights.seed_key(seed))


def build_engine(ctx):
    eng = ctx.cell["engine"]
    m = weights.model_dims(ctx.config)
    # first what a program without the block step refuses, before any weight
    cfg = model_config(ctx.config, eng)
    metricslib.configure(enabled=False, mirror_traces=ctx.tracer.enabled)
    params = make_params(ctx.seed, m)
    engine = BlockEngine(
        params, cfg, slots=eng["slots"], pool_pages=eng["pool_pages"],
        pages_per_seq=eng["pages_per_seq"], page_size=eng["page_size"],
        chunk=eng["chunk"], prompt_buckets=eng["prompt_buckets"],
        overlap=eng["overlap"], **unmask_args(ctx.config))
    engine.log_sums = ctx.tracer.enabled
    return engine


def block_requests(mix: dict, m: dict, seed: int, seconds: float):
    """The cell's requests: ids uniform in the vocabulary but the mask id,
    and every output rounded up to whole blocks (down where that would
    pass ``max_total``)."""
    B, M = m["B"], m["mask_id"]
    out = []
    for r in traffic.serving_requests(mix, m["V"] - 1, seed, seconds):
        new = -(-r.max_new // B) * B
        if len(r.prompt) + new > mix["max_total"]:
            new -= B
        out.append(dataclasses.replace(
            r, prompt=(r.prompt + (r.prompt >= M)).astype(np.int32),
            max_new=new))
    return out


def replay_gaps(seed: int, m: dict, served, rule: dict, check: dict, *,
                lowp=None) -> dict:
    """The two families of gaps over the sampled requests. ``served``:
    (prompt, blocks (G, 2, B)) pairs as the engine recorded them. Returns
    ``{"token": {"judged": gap_stats, ...}, "pick": {...}}``
    (``rule``: :func:`unmask_rule`): with
    ``lowp`` the tokens and the picks of positions judged are those the
    reference in that precision makes at the same states (a control,
    which need not decode), and the served ones' stats ride along as
    ``program``. ``one_wrong``: every served token replaced by its
    neighbour in the vocabulary; ``backwards``: the least confident
    positions settled first."""
    plans = [ref.replay_plan(p, list(b), m) for p, b in served]
    hidden = ref.replay_hidden(seed, m, plans)   # the layers, once
    nums = ref.replay_head(seed, m, plans, hidden)

    def stats(per_plan, tail_above):
        kept = [g for g in per_plan if len(g)]
        return gap_stats(kept, tail_above) if kept else {}

    def judged(nums, picked=None):
        pairs = [ref.replay_gaps(p, best, lse, at, picked=None if picked
                                 is None else picked[i], **rule)
                 for i, (p, (best, lse, _, at)) in enumerate(zip(plans,
                                                                 nums))]
        return (stats([t for t, _ in pairs], check["token"]["tail_above"]),
                stats([k for _, k in pairs], check["pick"]["tail_above"]))

    token, pick = judged(nums)
    wrong = ref.replay_head(seed, m, plans, hidden, targets=[
        np.stack([(c["tokens"] + 1) % m["V"] for c in p["copies"]])
        for p in plans])
    # the LEAST confident positions settled first: what an engine that
    # ignores the order reads
    backwards = [np.stack([ref.take_by_confidence(
        lse[c] - best[c], cp["fidx"] >= cp["forward"], **rule)
        for c, cp in enumerate(p["copies"])])
        for p, (best, lse, _, _) in zip(plans, nums)]
    out = {"token": {"judged": token, "one_wrong": judged(wrong)[0]},
           "pick": {"judged": pick,
                    "backwards": judged(nums, backwards)[1]}}
    if lowp:
        low = ref.replay_numbers(seed, m, plans, lowp=lowp)
        picked = [ref.control_picks(p, best, lse, **rule)
                  for p, (best, lse, _, _) in zip(plans, low)]
        own = ref.replay_head(seed, m, plans, hidden,
                              targets=[arg for _, _, arg, _ in low])
        token_low, pick_low = judged(own, picked)
        out["token"].update(program=token, judged=token_low)
        out["pick"].update(program=pick, judged=pick_low)
    return out


def gap_checks(gaps: dict, check: dict) -> list:
    """[(name, value, limit)]: mean, tail share and widest of each family
    of gaps against the cell's ``check`` block."""
    out = []
    for family, name in (("token", "settled_token_gap"),
                         ("pick", "position_pick_gap")):
        j, lim = gaps.get(family, {}).get("judged", {}), check[family]
        out += [(f"{name}_mean", j.get("mean"), lim["mean_limit"]),
                (f"{name}_tail_share", j.get("tail_share"),
                 lim["tail_share_limit"]),
                (f"{name}_widest", j.get("widest"), lim["widest_limit"])]
    return out


def run(ctx) -> dict:
    cell = ctx.cell
    m = weights.model_dims(ctx.config)
    engine = build_engine(ctx)
    requests = block_requests(cell["traffic"], m, ctx.seed, ctx.seconds)
    warm(engine, requests, m["V"] - 1)
    route0, sums0 = engine.route_stats(), engine.diffusion_stats()
    finished, t0, t1 = serve_window(engine, requests, ctx.tracer)
    route1, sums1 = engine.route_stats(), engine.diffusion_stats()
    per = summarize(engine, requests, finished)
    device = ctx.device_report()
    p95 = lambda v: traffic.percentile(v, 95) if v else None
    forwards, blocks, tokens = (int(v) for v in sums1.astype(np.int64)
                                - sums0.astype(np.int64))
    facts = {
        "loadgen_late_p95_ms": p95(per["late"]),
        "queue_p95_ms": p95(per["queue"]),
        "admit_bubble_pct": 100.0 * engine.last_bubble_frac,
        "window_wall_s": t1 - t0,
        "chunk": engine.chunk,           # block forwards a dispatch
        "slots": engine.slots,
        "admissions": [(t, pad, true) for _, t, pad, true
                       in engine.admissions],
        "token_instants": [(len(r.prompt), engine.stats[r.index]["token_ts"])
                           for r in requests if r.index in engine.stats],
        "block_chunks": chunk_facts(engine.chunk_log),
        "diffusion_forwards": forwards, "diffusion_blocks": blocks,
        "diffusion_tokens": tokens,
        **route_facts(route0, route1, m["E"]),
    }
    if forwards:
        facts["diffusion_tokens_per_forward"] = tokens / forwards
    end_to_end = {"ttft_p95_ms": p95(per["ttft"]) or float("nan"),
                  "tpot_p95_ms": p95(per["tpot"]) or float("nan"),
                  "setup_s": t0 - ctx.t_process_start}
    # the comparison comes after the window and the memory reading, with
    # the program's state freed. A request's blocks go through the sample
    # as one (positions, 2) array: its tokens and their forward indices
    recorded = {
        r.index: np.stack(engine.stats[r.index]["blocks"]).transpose(
            0, 2, 1).reshape(-1, 2)
        for r in requests if len(finished.get(r.index, ())) > 0}
    served = [(p, a.reshape(-1, m["B"], 2).transpose(0, 2, 1))
              for p, a in sample_served(requests, recorded, ctx.seed,
                                        cell["check"]["sample"])]
    engine.params = engine.cache = None
    del engine
    lowp = (ctx.control[4:] if (ctx.control or "").startswith("ref-")
            else None)   # a control: the reference in that precision
    check = cell["check"]
    gaps = (replay_gaps(ctx.seed, m, served, unmask_rule(ctx.config),
                        check, lowp=lowp) if served else {})
    checks = gap_checks(gaps, check) + [
        ("requests_failed", float(per["failed"]), 0.0)]
    return {"end_to_end": end_to_end, "facts": facts,
            "attempted": sum(r.measured for r in requests),
            "failed": per["failed"],
            "checks": checks, "device": device,
            "readings": {"gaps": gaps,
                         **{k: v for k, v in facts.items()
                            if k.startswith(("moe_", "diffusion_"))}}}
