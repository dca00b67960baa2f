"""Driver ``serve_falcon_h1``: ``drivers/serve.py``'s window for the
``falcon_h1`` block (attention and Mamba-2 side by side in every layer, a
gated MLP, the published multipliers) at the whole vocabulary.

The engine, the warm-up, the window, the per-request times and the sample
are ``serve.py``'s own; this file brings the model's configuration, its
weights in the program's layout and the comparison with its own plain
reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench import weights_falcon_h1 as weights
from chipbench.drivers.serve import (Engine, sample_served, serve_window,
                                     summarize, warm)
from chipbench.drivers.serve_hybrid import gap_stats
from chipbench.reference import falcon_h1 as ref

from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.models import transformer as progmodel


def model_config(config: dict, engine: dict) -> progmodel.TransformerConfig:
    m = weights.model_dims(config)
    return progmodel.TransformerConfig(
        vocab=m["V"], d_model=m["D"], n_heads=m["H"], n_kv_heads=m["Hkv"],
        attn_head_dim=m["Dh"], n_layers=m["L"], d_ff=m["F"],
        max_seq=config["max_position_embeddings"], dtype="bfloat16",
        attention="flash", pos_embed="rope", rope_theta=m["theta"],
        decode_attn=engine["decode_attn"], layer_pattern="H" * m["L"],
        norm_eps=m["eps"], ssm_heads=m["Hm"], ssm_head_dim=m["P"],
        ssm_groups=m["G"], ssm_state=m["N"], ssm_conv=m["K"],
        ssm_chunk=m["Q"],
        embedding_multiplier=m["m_embed"],
        attention_in_multiplier=m["m_attn_in"], key_multiplier=m["m_key"],
        attention_out_multiplier=m["m_attn_out"],
        ssm_in_multiplier=m["m_ssm_in"], ssm_multipliers=m["m_ssm"],
        ssm_out_multiplier=m["m_ssm_out"], mlp_multipliers=m["m_mlp"],
        lm_head_multiplier=m["m_head"])


def make_params(seed: int, m: dict):
    """The weights on the device, in one jitted call, each leaf rounded
    to bfloat16 as it is made."""
    fm = ref._freeze(m)
    return jax.jit(lambda k: weights.build(k, dict(fm), jnp.bfloat16))(
        weights.seed_key(seed))


def build_engine(ctx):
    eng = ctx.cell["engine"]
    m = weights.model_dims(ctx.config)
    # first what a program without the block refuses, before any weight
    cfg = model_config(ctx.config, eng)
    metricslib.configure(enabled=False, mirror_traces=ctx.tracer.enabled)
    params = make_params(ctx.seed, m)
    return Engine(
        params, cfg, slots=eng["slots"], pool_pages=eng["pool_pages"],
        pages_per_seq=eng["pages_per_seq"], page_size=eng["page_size"],
        chunk=eng["chunk"], prompt_buckets=eng["prompt_buckets"],
        overlap=eng["overlap"])


def serving_gap(seed: int, m: dict, served, *, lowp=None, pad_to=512) -> dict:
    """How far the served tokens lie below the reference's best, at every
    sampled position: a position's gap = the reference's largest logit
    less its logit of the served token. ``served``: (prompt, tokens)
    pairs. Returns ``{"judged": gap_stats, ...}``: with ``lowp`` the
    tokens judged are those the reference in that precision puts first at
    the same positions (a control, which need not decode), and the served
    ones' stats ride along as ``program``. ``one_wrong``: the same
    positions with every token replaced by its neighbour in the
    vocabulary (what a slot that hands out wrong tokens reads)."""
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
    out = {"judged": gap_stats(gaps(tokens), 0.5),
           "one_wrong": gap_stats(gaps([(t + 1) % m["V"] for t in tokens]),
                                  0.5)}
    if lowp:
        low = ref.logits_at(seed, m, seqs, rows, lowp=lowp, pad_to=pad_to)
        out["program"] = out["judged"]
        out["judged"] = gap_stats(
            gaps([np.asarray(jnp.argmax(z, axis=-1)) for z in low]), 0.5)
    return out


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
        "slots": engine.slots,
        "admissions": [(t, pad, true) for _, t, pad, true
                       in engine.admissions],
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
    check = cell["check"]
    gap = (serving_gap(ctx.seed, m, served, lowp=lowp,
                       pad_to=cell["traffic"]["max_total"])
           if served else {})
    judged = gap.get("judged", {})
    checks = [("served_gap_widest", judged.get("widest"),
               check["widest_limit"]),
              ("served_logit_gap", judged.get("mean"), check["gap_limit"]),
              ("requests_failed", float(per["failed"]), 0.0)]
    return {"end_to_end": end_to_end, "facts": facts,
            "attempted": sum(r.measured for r in requests),
            "failed": per["failed"],
            "checks": checks, "device": device,
            "readings": {"gaps": gap}}
