"""Required operations and bytes of the ``nemotron_h`` pattern on one
chip's share, from the configuration's sizes and the run's facts: true
tokens only (no bucket padding, no idle rows), recomputation not counted.

An ``E`` layer counts the picks the route's counter reports (a token's 22
picks fall on all 512 experts; about a quarter of them on the 128 held
here), not 22; a decode step's bytes are the weights of the experts it
touched plus every other weight plus the recurrent state read and written.
"""

from __future__ import annotations

from chipbench.counts import flash_attention, paged_decode, window

ITEM = 2    # bytes of a bfloat16 weight or activation
STATE = 4   # bytes of a float32 state element


def dims(config: dict) -> dict:
    D = config["hidden_size"]
    Hm, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    H, Hkv, Dh = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    R, F = config["moe_latent_size"], config["moe_intermediate_size"]
    Fs = config["moe_shared_expert_intermediate_size"]
    pattern = config["hybrid_override_pattern"]
    di = Hm * P
    return {
        "D": D, "V": config["vocab_size"], "Q": config["chunk_size"],
        "K": config["conv_kernel"],
        "nM": pattern.count("M"), "nA": pattern.count("*"),
        "nE": pattern.count("E"), "held": config["n_routed_experts"],
        "Hm": Hm, "P": P, "G": G, "N": N, "di": di,
        "conv_dim": di + 2 * G * N, "H": H, "Hkv": Hkv, "Dh": Dh,
        # parameters that sit in a matmul, a layer of each kind
        "pM": D * (2 * di + 2 * G * N + Hm) + di * D,
        "pA": D * (H + 2 * Hkv) * Dh + H * Dh * D,
        "pE": D * config["published"]["n_routed_experts"] + 2 * D * R
        + 2 * D * Fs,
        "pX": 2 * R * F, "R": R,          # one expert; the latent width
        "state": Hm * P * N,              # elements of S, a layer and row
    }


def ssd_flops_token(d: dict) -> float:
    """The chunked recurrence, one token of one ``M`` layer: inside its
    chunk (C.B and the masked product over the Q/2 positions before it, on
    average), its part of the chunk's state and the read of the carried
    one."""
    return (d["Q"] * (d["G"] * d["N"] + d["Hm"] * d["P"])
            + 4.0 * d["state"])


def step_flops_row(d: dict) -> float:
    """One step of the recurrence, one row of one ``M`` layer: the decay,
    the outer product's add and the read-out over S."""
    return 5.0 * d["state"]


def token_flops(d: dict, picks: float, context: float) -> float:
    """Forward of one token at ``context`` attended positions, no head:
    2 a matmul parameter, the experts by the picks computed here."""
    return (2.0 * (d["nM"] * d["pM"] + d["nA"] * d["pA"]
                   + d["nE"] * (d["pE"] + picks * d["pX"]))
            + d["nA"] * 4.0 * context * d["H"] * d["Dh"])


def prefill_flops(config: dict, tokens: int, picks: float) -> float:
    """A prompt of ``tokens`` true tokens; the head at the last alone."""
    d = dims(config)
    return (tokens * (token_flops(d, picks, tokens / 2)
                      + d["nM"] * ssd_flops_token(d))
            + 2.0 * d["D"] * d["V"])


def decode_flops(config: dict, context: int, picks: float) -> float:
    d = dims(config)
    return (token_flops(d, picks, context) + d["nM"] * step_flops_row(d)
            + 2.0 * d["D"] * d["V"])


def _picks(facts, phase: str):
    """Picks computed here a routed token, from the route's counter; a
    run without it (a program without the route) has nothing to count."""
    return facts.get(f"moe_picks_per_token_{phase}")


def prefill_work(facts, config, n_events):
    picks = _picks(facts, "prefill")
    if picks is None:
        return 0, 0
    return sum(prefill_flops(config, true, picks) for _, _, true
               in window.admissions_traced(facts, n_events)), 0


def decode_work(facts, config, n_events):
    picks = _picks(facts, "decode")
    if picks is None:
        return 0, 0
    return sum(decode_flops(config, c, picks)
               for lens in window.decode_steps_traced(facts) for c in lens), 0


def ssm_scan_prefill_work(facts, config, n_events):
    """Scope ``ssm/scan`` of the traced prefills: the chunked recurrence
    of every ``M`` layer over the true tokens; reads x, B, C and dt,
    writes y and the final state."""
    d = dims(config)
    flops = nbytes = 0.0
    for _, _, true in window.admissions_traced(facts, n_events):
        flops += d["nM"] * true * ssd_flops_token(d)
        nbytes += d["nM"] * (true * (d["conv_dim"] + d["di"]) * ITEM
                             + true * d["Hm"] * 4 + d["state"] * STATE)
    return flops, nbytes


def ssm_step_decode_work(facts, config, n_events):
    """Scope ``ssm/step`` of the traced chunks: one step of the recurrence
    a live row and ``M`` layer; S is read and written once."""
    d = dims(config)
    rows = sum(len(lens) for lens in window.decode_steps_traced(facts))
    return (d["nM"] * rows * step_flops_row(d),
            d["nM"] * rows * (2 * d["state"] * STATE
                              + (d["conv_dim"] + d["di"]) * 4))


def _experts_work(d, tokens, calls, picks, touched):
    """``tokens`` routed tokens (a layer) over ``calls`` (layer, program
    step) pairs: two grouped products a pick; the touched experts' weights
    are read once a call, a pick's latent row in and out once."""
    n_picks = tokens * picks * d["nE"]
    return (2.0 * n_picks * d["pX"],
            calls * touched * d["pX"] * ITEM + n_picks * 2 * d["R"] * ITEM)


def moe_experts_prefill_work(facts, config, n_events):
    """The grouped products (``ragged-dot`` events: two an ``E`` layer)
    of the traced prefills."""
    picks, touched = (_picks(facts, "prefill"),
                      facts.get("moe_experts_touched_prefill"))
    if picks is None or touched is None:
        return 0, 0
    d = dims(config)
    rows = window.admissions_traced(facts, n_events // (2 * d["nE"]))
    return _experts_work(d, sum(true for _, _, true in rows),
                         d["nE"] * len(rows), picks, touched)


def moe_experts_decode_work(facts, config, n_events):
    """The grouped products of the traced chunks: a step reads the
    weights of the experts it touched, whatever the live rows."""
    picks, touched = (_picks(facts, "decode"),
                      facts.get("moe_experts_touched_decode"))
    if picks is None or touched is None:
        return 0, 0
    d = dims(config)
    steps = window.decode_steps_traced(facts)
    return _experts_work(d, sum(len(lens) for lens in steps),
                         d["nE"] * len(steps), picks, touched)


def _heads(d):
    return d["H"], d["Hkv"], d["Dh"]


def flash_fwd_prefill_work(facts, config, n_events):
    """The ``flash_fwd`` calls of the traced prefills: one an ``*`` layer
    (the accepted ``flash_attention.prefill_work`` counts one a layer of
    the configuration), at the rung the prompt was padded to."""
    d = dims(config)
    flops = nbytes = 0
    for _, rung, _ in window.admissions_traced(facts, n_events // d["nA"]):
        f, b = flash_attention.fwd(rung, *_heads(d))
        flops, nbytes = flops + d["nA"] * f, nbytes + d["nA"] * b
    return flops, nbytes


def flash_decode_paged_work(facts, config, n_events):
    """The ``flash_decode_paged`` calls of the traced chunks: one an ``*``
    layer and step, the live rows' contexts."""
    d = dims(config)
    flops = nbytes = 0
    for lens in window.decode_steps_traced(facts):
        f, b = paged_decode.step(lens, *_heads(d))
        flops, nbytes = flops + d["nA"] * f, nbytes + d["nA"] * b
    return flops, nbytes
