"""Driver ``serve_hybrid``: ``drivers/serve.py``'s window for a patterned
model (``nemotron_h``: Mamba-2, attention and LatentMoE layers on one
chip's share of the experts and of the vocabulary).

The engine, the warm-up, the window, the per-request times and the sample
are ``serve.py``'s own; this file brings the model's configuration, its
weights in the program's layout, the comparison with its own plain
reference, and the expert route's counters as facts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench import weights_nemotron_h as weights
from chipbench.drivers.serve import (Engine, sample_served, serve_window,
                                     summarize, warm)
from chipbench.reference import nemotron_h as ref

from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.models import transformer as progmodel


def model_config(config: dict, engine: dict) -> progmodel.TransformerConfig:
    m = weights.model_dims(config)
    return progmodel.TransformerConfig(
        vocab=m["V"], d_model=m["D"], n_heads=m["H"], n_kv_heads=m["Hkv"],
        n_layers=m["L"], d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"], dtype="bfloat16",
        attention="flash", pos_embed="none", decode_attn=engine["decode_attn"],
        layer_pattern=m["pattern"], norm_eps=m["eps"],
        ssm_heads=m["Hm"], ssm_head_dim=m["P"], ssm_groups=m["G"],
        ssm_state=m["N"], ssm_conv=m["K"], ssm_chunk=m["Q"],
        moe_experts=m["E"], moe_held=m["held"], moe_held_start=m["held0"],
        moe_top_k=m["k"], moe_latent=m["R"], moe_d_ff=m["F"],
        moe_shared_d_ff=m["Fs"], moe_scale=m["scale"])


def make_params(seed: int, m: dict):
    """The served share of the weights, on the device, in one jitted
    call, each leaf rounded to bfloat16 as it is made."""
    fm = ref._freeze(m)
    return jax.jit(lambda k: weights.build(k, dict(fm), jnp.bfloat16))(
        weights.seed_key(seed))


def build_engine(ctx):
    eng = ctx.cell["engine"]
    m = weights.model_dims(ctx.config)
    # first what a program without the pattern refuses, before any weight
    cfg = model_config(ctx.config, eng)
    metricslib.configure(enabled=False, mirror_traces=ctx.tracer.enabled)
    params = make_params(ctx.seed, m)
    return Engine(
        params, cfg, slots=eng["slots"], pool_pages=eng["pool_pages"],
        pages_per_seq=eng["pages_per_seq"], page_size=eng["page_size"],
        chunk=eng["chunk"], prompt_buckets=eng["prompt_buckets"],
        overlap=eng["overlap"])


#: gap sizes whose counts a control run reports (``readings``), so that
#: ``tail_above`` can be set from the program's and the control's tails
GAP_LADDER = (0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0, 1.5, 2.0, 3.0)


def gap_stats(gaps, tail_above: float) -> dict:
    """Of one run's gaps (a float array a sampled request): ``mean`` over
    every position, ``tail_share`` = the share of positions whose gap is
    above ``tail_above``, ``widest``; and as readings the ladder's counts
    and the smallest widest gap of any one request."""
    flat = np.concatenate(gaps)
    return {"mean": float(flat.mean()),
            "tail_share": float((flat > tail_above).mean()),
            "widest": float(flat.max()), "positions": int(flat.size),
            "above": {str(x): int((flat > x).sum()) for x in GAP_LADDER},
            "widest_of_a_request_min": float(min(g.max() for g in gaps))}


def serving_gap(seed: int, m: dict, served, tail_above: float, *,
                lowp=None, pad_to=512) -> dict:
    """How far the served tokens lie below the reference's best, at every
    sampled position: a position's gap = the reference's largest logit
    less its logit of the served token. ``served``: (prompt, tokens)
    pairs. Returns ``{"judged": gap_stats, ...}``: with ``lowp`` the
    tokens judged are those the reference in that precision puts first
    at the same positions (a control, which need not decode), and the
    served ones' stats ride along as ``program``. ``one_wrong``: the same
    positions with every token of a request replaced by its neighbour in
    the vocabulary (what a slot that hands out wrong tokens reads).

    Three numbers are judged (PERF.md section 2 has the readings). The
    MEAN separates precisions: the router picks 22 of 512 experts, and
    rounding anywhere before it flips the pick at the boundary for some
    tokens in a hundred a layer, in bfloat16 and in the float32 reference
    with bfloat16 operands alike; a flipped token lies some tenths below
    whatever the precision, so ``serve.py``'s widest gap reads the flips
    and separates no precision from the next. The TAIL SHARE counts how
    many positions lie that far below. The WIDEST gap is held under what
    one wrong token reads (some units), which the other two cannot see."""
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


def gap_checks(judged: dict, check: dict) -> list:
    """[(name, value, limit)] of the cell's ``check`` block over
    ``gap_stats``: every value has to lie at or under its limit."""
    return [("served_logit_gap", judged.get("mean"), check["gap_limit"]),
            ("served_gap_tail_share", judged.get("tail_share"),
             check["tail_share_limit"]),
            ("served_gap_widest", judged.get("widest"),
             check["widest_limit"])]


def route_facts(before, after, held: int) -> dict:
    """The expert route's counters over the window, from what the
    engine's sums grew by (rows: prefills, decode steps; columns: picks
    computed here, tokens routed, the fullest held expert's picks,
    experts touched, calls: whole numbers). Nothing where there are no
    sums."""
    if after is None:
        return {}
    d = np.asarray(after, np.int64) - (0 if before is None
                                       else np.asarray(before, np.int64))
    out = {}
    for row, name in ((0, "prefill"), (1, "decode")):
        picks, tokens, max_load, touched, calls = (int(v) for v in d[row])
        if tokens > 0:
            out[f"moe_picks_per_token_{name}"] = picks / tokens
        if calls > 0 and picks > 0:   # the fullest expert over the mean
            out[f"moe_load_max_over_mean_{name}"] = max_load * held / picks
        if calls > 0:
            out[f"moe_experts_touched_{name}"] = touched / calls
    tok = int(d[0][1] + d[1][1])
    if tok > 0:
        out["moe_local_picks_per_token"] = int(d[0][0] + d[1][0]) / tok
    return out


def run(ctx) -> dict:
    cell = ctx.cell
    m = weights.model_dims(ctx.config)
    engine = build_engine(ctx)
    requests = traffic.serving_requests(cell["traffic"], m["V"], ctx.seed,
                                        ctx.seconds)
    warm(engine, requests, m["V"])
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
