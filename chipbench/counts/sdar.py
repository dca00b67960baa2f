"""Required operations and bytes of the ``sdar_moe`` layer served by
diffusion over blocks, from the configuration's sizes and the run's facts:
true tokens and live rows only (no bucket padding, no idle rows),
recomputation not counted.

A block FORWARD runs B positions a live row: every position pays the
attention's and the router's matmuls, its 8 picks' three expert matrices
and the head; the forward reads the weights of the experts it touched
once a layer, whatever the live rows. The driver logs one record a chunk
(``facts["block_chunks"]``): the host instant of its readback, the rows
live and their stored positions at its dispatch, and what the engine's
sums grew by over it (``diffusion_stats``, the decode row of
``route_stats``), so that the traced chunks are counted by what THEY did
and not by the window's mean.
"""

from __future__ import annotations

from chipbench.counts import flash_attention, window

ITEM = 2    # bytes of a bfloat16 weight or activation


def dims(config: dict) -> dict:
    D, F = config["hidden_size"], config["moe_intermediate_size"]
    H, Hkv, Dh = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    return {
        "D": D, "V": config["vocab_size"], "L": config["num_hidden_layers"],
        "E": config["num_experts"], "k": config["num_experts_per_tok"],
        "F": F, "H": H, "Hkv": Hkv, "Dh": Dh,
        "B": config["generation"]["block_length"],
        # parameters that sit in a matmul: a layer's attention (qkv and
        # o), its router, ONE expert (gate, up, down)
        "pA": D * (H + 2 * Hkv) * Dh + H * Dh * D,
        "pR": D * config["num_experts"],
        "pX": 3 * D * F,
    }


def layer_params(config: dict) -> int:
    """Every parameter of one layer: the matmuls', the two norms' and the
    q/k norms' scales."""
    d = dims(config)
    return (d["pA"] + d["pR"] + d["E"] * d["pX"] + 2 * d["D"] + 2 * d["Dh"])


def model_params(config: dict) -> int:
    """The configuration as served: its layers, the embedding, the head
    and the final norm."""
    d = dims(config)
    return d["L"] * layer_params(config) + 2 * d["V"] * d["D"] + d["D"]


def kv_bytes_token(config: dict) -> int:
    """K and V of one position, all layers."""
    d = dims(config)
    return d["L"] * 2 * d["Hkv"] * d["Dh"] * ITEM


def token_flops(d: dict, context: float) -> float:
    """Forward of one position at ``context`` attended keys, no head:
    2 a matmul parameter (the router's, the attention's, k experts')."""
    return d["L"] * (2.0 * (d["pA"] + d["pR"] + d["k"] * d["pX"])
                     + 4.0 * context * d["H"] * d["Dh"])


def prefill_flops(config: dict, tokens: int) -> float:
    """A prompt of ``tokens`` true tokens under the block mask (a
    position's keys: half the prompt on average); no head."""
    return tokens * token_flops(dims(config), tokens / 2)


def forward_flops(config: dict, row_forwards: float, context: float) -> float:
    """``row_forwards`` block forwards of one row each, B positions with
    the head at every one, at ``context`` keys a position on average."""
    d = dims(config)
    return row_forwards * d["B"] * (token_flops(d, context)
                                    + 2.0 * d["D"] * d["V"])


def chunks_traced(facts, n: int | None = None):
    """The records of the chunks the trace holds: those read back inside
    the traced window or, given how many chunk programs the trace shows,
    that many from the first read back after its start (the device runs
    them in order)."""
    rows = sorted(facts.get("block_chunks", ()), key=lambda r: r["t"])
    t_start, t_stop = facts["trace_host_window"]
    if n is None:
        return [r for r in rows if t_start <= r["t"] <= t_stop]
    first = next((i for i, r in enumerate(rows) if r["t"] >= t_start),
                 len(rows))
    first = max(0, min(first, len(rows) - n))
    return rows[first:first + n]


def _mean_context(r, d) -> float:
    """Keys a position of this chunk's forwards attends over: the live
    rows' stored positions at dispatch and their own block."""
    return r["ctx_tokens"] / max(r["rows"], 1) + d["B"]


def prefill_work(facts, config, n_events):
    """The prefills inside the traced window, at their TRUE lengths."""
    return sum(prefill_flops(config, true) for _, _, true
               in window.admissions_traced(facts, n_events)), 0


def decode_work(facts, config, n_events):
    """The block forwards of the ``n_events`` traced chunk programs, by
    the forwards their live rows ran."""
    d = dims(config)
    return sum(forward_flops(config, r["forwards"], _mean_context(r, d))
               for r in chunks_traced(facts, n_events)), 0


def _experts_work(d, picks: float, touched: float):
    """``picks`` (token, expert) pairs over (layer, forward) calls that
    touched ``touched`` experts in all: three grouped products a pick;
    a touched expert's three matrices are read once a call, a pick's row
    read twice and its result written in float32."""
    return (2.0 * picks * d["pX"],
            touched * d["pX"] * ITEM
            + picks * (2 * d["D"] * ITEM + 2 * d["F"] * ITEM + 4 * d["D"]))


def experts_decode_work(facts, config, n_events):
    """The ``grouped_matmul`` calls of the traced chunks (three a layer
    and forward): the picks and the experts touched that the route's sums
    grew by over exactly those chunks."""
    d = dims(config)
    per_chunk = 3 * d["L"] * facts["chunk"]
    rows = chunks_traced(facts, n_events // per_chunk)
    if not rows or any("picks" not in r for r in rows):
        return 0, 0
    return _experts_work(d, sum(r["picks"] for r in rows),
                         sum(r["touched"] for r in rows))


def experts_prefill_work(facts, config, n_events):
    """The ``grouped_matmul`` calls of the traced prefills (three a
    layer): 8 picks a true token a layer; the experts a prefill touches
    a layer from the window's sums (nearly all 128 at any length)."""
    touched = facts.get("moe_experts_touched_prefill")
    picks = facts.get("moe_picks_per_token_prefill")
    if touched is None or picks is None:
        return 0, 0
    d = dims(config)
    rows = window.admissions_traced(facts, n_events // (3 * d["L"]))
    tokens = sum(true // d["B"] * d["B"] for _, _, true in rows)
    return _experts_work(d, tokens * picks * d["L"],
                         len(rows) * d["L"] * touched)


def flash_fwd_work(facts, config, n_events):
    """The ``flash_fwd`` calls of the traced prefills: one a layer, over
    the prompt's true tokens under the block mask (the causal triangle
    and B - 1 keys a row more: counted as the triangle)."""
    d = dims(config)
    flops = nbytes = 0
    for _, _, true in window.admissions_traced(facts, n_events // d["L"]):
        f, b = flash_attention.fwd(true, d["H"], d["Hkv"], d["Dh"])
        flops, nbytes = flops + d["L"] * f, nbytes + d["L"] * b
    return flops, nbytes


def flash_decode_paged_work(facts, config, n_events):
    """The ``flash_decode_paged`` calls of the traced chunks: one a layer
    and forward, B queries a live row against its stored keys and its own
    block; a row's keys and values are read ONCE for its B queries."""
    d = dims(config)
    per_chunk = d["L"] * facts["chunk"]
    flops = nbytes = 0.0
    for r in chunks_traced(facts, n_events // per_chunk):
        keys = r["forwards"] * _mean_context(r, d)   # summed over forwards
        flops += d["L"] * 4.0 * keys * d["B"] * d["H"] * d["Dh"]
        nbytes += d["L"] * ITEM * (2 * keys * d["Hkv"] * d["Dh"]
                                   + 2 * r["forwards"] * d["B"]
                                   * d["H"] * d["Dh"])
    return flops, nbytes
