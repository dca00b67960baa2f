"""Required operations and bytes of the ``laguna`` layers served token by
token, from the configuration's sizes and the run's facts: true tokens and
live rows only (no bucket padding, no idle rows), the keys a window layer's
position sees (at most ``sliding_window``), the picks on the experts held
here, recomputation not counted.

A decode step of a row pays, a layer, its attention's matmuls (qkv, the
per-head gate, o: the kind's own heads), the router and the shared expert
(routed layers) or the dense MLP (the leading layer), its picks on the
held experts, and the head. The driver logs one record a chunk in a traced
run (``facts["chunks"]``): the host instant of its readback, the rows'
decode steps, the keys they attend over in a global and in a window layer,
and what the route's decode sums grew by over it, so that the traced
chunks are counted by what THEY did and not by the window's mean.
"""

from __future__ import annotations

from chipbench.counts import flash_attention, window

ITEM = 2    # bytes of a bfloat16 weight or activation


def dims(config: dict) -> dict:
    L = config["num_hidden_layers"]
    D, Dh, Hkv = (config["hidden_size"], config["head_dim"],
                  config["num_key_value_heads"])
    heads = config["num_attention_heads_per_layer"][:L]
    kinds = [("W" if a == "sliding_attention" else
              "D" if f == "dense" else "R")
             for a, f in zip(config["layer_types"][:L],
                             config["mlp_layer_types"][:L])]
    E = config["published"]["num_experts"]
    F, Fs, Fd = (config["moe_intermediate_size"],
                 config["shared_expert_intermediate_size"],
                 config["intermediate_size"])
    # parameters that sit in a matmul, a layer: attention (qkv, gate, o)
    attn = [D * (h + 2 * Hkv) * Dh + D * h + h * Dh * D for h in heads]
    mlp = [3 * D * Fd if k == "D" else D * E + 3 * D * Fs for k in kinds]
    return {
        "D": D, "Dh": Dh, "Hkv": Hkv, "L": L, "V": config["vocab_size"],
        "kinds": kinds, "heads": heads, "E": E,
        "held": config["num_experts"], "k": config["num_experts_per_tok"],
        "F": F, "W": config["sliding_window"],
        "pA": attn, "pM": mlp, "pX": 3 * D * F,
        "routed": sum(k != "D" for k in kinds),
    }


def layer_params(config: dict, index: int) -> int:
    """Every parameter of layer ``index`` held here: its matmuls', its
    held experts' and its two norms' scales."""
    d = dims(config)
    held = d["held"] * d["pX"] if d["kinds"][index] != "D" else 0
    return d["pA"][index] + d["pM"][index] + held + 2 * d["D"]


def model_params(config: dict) -> int:
    """The configuration as served: its layers, the embedding, the head
    and the final norm."""
    d = dims(config)
    return (sum(layer_params(config, i) for i in range(d["L"]))
            + 2 * d["V"] * d["D"] + d["D"])


def kv_bytes_token(config: dict) -> int:
    """K and V of one position, one layer."""
    d = dims(config)
    return 2 * d["Hkv"] * d["Dh"] * ITEM


def _dense_token(d: dict) -> float:
    """Operations of one position outside the held experts and the
    attention's scores, no head: 2 a matmul parameter."""
    return 2.0 * (sum(d["pA"]) + sum(d["pM"]))


def _scores(d: dict, keys: float, keys_window: float) -> float:
    """q.K^T and p.V of one layer set over ``keys`` (a global layer's) and
    ``keys_window`` (a window layer's) attended keys: 4 Dh a key a head."""
    return sum(4.0 * d["Dh"] * h * (keys_window if k == "W" else keys)
               for k, h in zip(d["kinds"], d["heads"]))


def _window_pairs(T: int, W: int) -> int:
    """Keys all T positions of a prompt see in a window layer."""
    return T * (T + 1) // 2 if T <= W else W * (W + 1) // 2 + (T - W) * W


def prefill_flops(config: dict, tokens: int, picks_per_token: float) -> float:
    """A prompt of ``tokens`` true tokens, its head at the last one: its
    dense matmuls, ``picks_per_token`` held picks a token and routed layer,
    and the scores of causal pairs (global) and window pairs (window)."""
    d = dims(config)
    pairs = tokens * (tokens + 1) / 2
    return (tokens * (_dense_token(d)
                      + d["routed"] * picks_per_token * 2.0 * d["pX"])
            + _scores(d, pairs, _window_pairs(tokens, d["W"]))
            + 2.0 * d["D"] * d["V"])


def chunks_traced(facts, n: int | None = None):
    """The records of the chunks the trace holds: those read back inside
    the traced window or, given how many chunk programs the trace shows,
    that many from the first read back after its start (the device runs
    them in order)."""
    rows = sorted(facts.get("chunks", ()), key=lambda r: r["t"])
    t_start, t_stop = facts["trace_host_window"]
    if n is None:
        return [r for r in rows if t_start <= r["t"] <= t_stop]
    first = next((i for i, r in enumerate(rows) if r["t"] >= t_start),
                 len(rows))
    first = max(0, min(first, len(rows) - n))
    return rows[first:first + n]


def prefill_work(facts, config, n_events):
    """The prefills inside the traced window, at their TRUE lengths; the
    picks a prompt token made on the held experts, from the window's
    sums."""
    picks = facts.get("moe_picks_per_token_prefill")
    if picks is None:
        return 0, 0
    return sum(prefill_flops(config, true, picks) for _, _, true
               in window.admissions_traced(facts, n_events)), 0


def decode_work(facts, config, n_events):
    """The decode steps of the ``n_events`` traced chunk programs: every
    step's dense matmuls and head, the keys it saw, and the picks the
    route's sums grew by over exactly those chunks."""
    d = dims(config)
    flops = 0.0
    for r in chunks_traced(facts, n_events):
        flops += (r["steps"] * (_dense_token(d) + 2.0 * d["D"] * d["V"])
                  + 2.0 * r["picks"] * d["pX"]
                  + _scores(d, r["keys"], r["keys_window"]))
    return flops, 0


def experts_decode_work(facts, config, n_events):
    """The ``grouped_matmul`` calls of the traced chunks (three a routed
    layer and step): ``picks`` (token, expert) pairs over (layer, step)
    calls that touched ``touched`` held experts in all; a touched
    expert's three matrices are read once a call, a pick's row read twice
    and its result written in float32."""
    d = dims(config)
    rows = chunks_traced(facts, n_events // (3 * d["routed"]
                                             * facts["chunk"]))
    if not rows:
        return 0, 0
    picks = sum(r["picks"] for r in rows)
    touched = sum(r["touched"] for r in rows)
    return (2.0 * picks * d["pX"],
            touched * d["pX"] * ITEM
            + picks * (2 * d["D"] * ITEM + 2 * d["F"] * ITEM + 4 * d["D"]))


def flash_fwd_work(facts, config, n_events):
    """The ``flash_fwd`` calls of the traced prefills, one a layer, at the
    prompt's TRUE tokens: a global layer's over the causal triangle, a
    window layer's over the pairs inside the window; each reads q, k, v and
    writes o and the row log-sum-exp."""
    d = dims(config)
    flops = nbytes = 0
    for _, _, T in window.admissions_traced(facts, n_events // d["L"]):
        for kind, h in zip(d["kinds"], d["heads"]):
            f, b = flash_attention.fwd(T, h, d["Hkv"], d["Dh"])
            if kind == "W":
                f = 4 * d["Dh"] * h * _window_pairs(T, d["W"])
            flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def flash_decode_paged_work(facts, config, n_events):
    """The ``flash_decode_paged`` calls of the traced chunks, one a layer
    and step: a row's query heads against the keys it sees (a window
    layer's: at most ``sliding_window``), its keys and values read once
    (K/V heads, not query heads), q read and o written."""
    d = dims(config)
    flops = nbytes = 0.0
    for r in chunks_traced(facts, n_events // (d["L"] * facts["chunk"])):
        flops += _scores(d, r["keys"], r["keys_window"])
        for kind, h in zip(d["kinds"], d["heads"]):
            keys = r["keys_window"] if kind == "W" else r["keys"]
            nbytes += ITEM * (2 * keys * d["Hkv"] * d["Dh"]
                              + 2 * r["steps"] * h * d["Dh"])
    return flops, nbytes


def window_kv_share(config: dict, kv_pages: float, win_pages: float):
    """Of the K/V bytes the traced chunks' first steps fetch, the window
    layers' share in percent: ``kv_pages`` and ``win_pages`` are the pages
    of one K/V head a global and a window layer fetch (the
    ``serve.decode_dispatch`` attributes, summed); every page holds the
    same bytes."""
    d = dims(config)
    n_win = sum(k == "W" for k in d["kinds"])
    win = n_win * win_pages
    total = win + (d["L"] - n_win) * kv_pages
    return 100.0 * win / total if total > 0 else None
