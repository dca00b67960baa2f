"""Required operations and bytes of the ``falcon_h1`` block, from the
configuration's sizes and the run's facts: true tokens only (no bucket
padding, no idle rows), recomputation not counted.

Every layer holds both mixers, so a prefilled token pays attention at its
context AND the chunked recurrence; a decode step's row reads its K/V
(12,288 B a cached token over the six layers) and reads and writes its
state S (4.19 MB a layer) beside every weight.
"""

from __future__ import annotations

from chipbench.counts import flash_attention, paged_decode, window

ITEM = 2    # bytes of a bfloat16 weight or activation
STATE = 4   # bytes of a float32 state element


def dims(config: dict) -> dict:
    D, F = config["hidden_size"], config["intermediate_size"]
    Hm, P = config["mamba_n_heads"], config["mamba_d_head"]
    G, N = config["mamba_n_groups"], config["mamba_d_state"]
    H, Hkv, Dh = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    di = Hm * P
    return {
        "D": D, "V": config["vocab_size"], "L": config["num_hidden_layers"],
        "Q": config["mamba_chunk_size"], "K": config["mamba_d_conv"],
        "Hm": Hm, "P": P, "G": G, "N": N, "di": di,
        "conv_dim": di + 2 * G * N, "H": H, "Hkv": Hkv, "Dh": Dh,
        # parameters that sit in a matmul, one layer: attention (qkv and
        # o), the mixer (in and out), the gated MLP's three
        "pA": D * (H + 2 * Hkv) * Dh + H * Dh * D,
        "pM": D * (2 * di + 2 * G * N + Hm) + di * D,
        "pF": 3 * D * F,
        "state": Hm * P * N,              # elements of S, a layer and row
        "kv_token": 2 * Hkv * Dh * ITEM,  # bytes of K and V, a layer
    }


def layer_params(config: dict) -> int:
    """Every parameter of one layer: the matmuls', the convolution's
    (weights and bias), the per-head leaves (dt_bias, A_log, D), the
    grouped norm's scale and the two norms'."""
    d = dims(config)
    return (d["pA"] + d["pM"] + d["pF"] + (d["K"] + 1) * d["conv_dim"]
            + 3 * d["Hm"] + d["di"] + 2 * d["D"])


def weight_bytes_step(config: dict) -> int:
    """What a decode step reads of the weights: every layer's matmuls and
    the head (the embedding is a gather of the live rows)."""
    d = dims(config)
    return (d["L"] * (d["pA"] + d["pM"] + d["pF"]) + d["D"] * d["V"]) * ITEM


def ssd_flops_token(d: dict) -> float:
    """The chunked recurrence, one token of one layer: inside its chunk
    (C.B and the masked product over the Q/2 positions before it, on
    average), its part of the chunk's state and the read of the carried
    one."""
    return (d["Q"] * (d["G"] * d["N"] + d["Hm"] * d["P"])
            + 4.0 * d["state"])


def step_flops_row(d: dict) -> float:
    """One step of the recurrence, one row of one layer: the decay, the
    outer product's add and the read-out over S."""
    return 5.0 * d["state"]


def token_flops(d: dict, context: float) -> float:
    """Forward of one token at ``context`` attended positions, no head
    and no recurrence: 2 a matmul parameter, q.K^T and p.V."""
    return d["L"] * (2.0 * (d["pA"] + d["pM"] + d["pF"])
                     + 4.0 * context * d["H"] * d["Dh"])


def prefill_flops(config: dict, tokens: int) -> float:
    """A prompt of ``tokens`` true tokens; the head at the last alone."""
    d = dims(config)
    return (tokens * (token_flops(d, tokens / 2)
                      + d["L"] * ssd_flops_token(d))
            + 2.0 * d["D"] * d["V"])


def decode_flops(config: dict, context: int) -> float:
    d = dims(config)
    return (token_flops(d, context) + d["L"] * step_flops_row(d)
            + 2.0 * d["D"] * d["V"])


def prefill_work(facts, config, n_events):
    """The prefills inside the traced window, at their TRUE lengths."""
    return sum(prefill_flops(config, true) for _, _, true
               in window.admissions_traced(facts, n_events)), 0


def decode_work(facts, config, n_events):
    """The tokens decoded by the chunks read back in the traced window."""
    return sum(decode_flops(config, c)
               for lens in window.decode_steps_traced(facts) for c in lens), 0


def ssm_scan_prefill_work(facts, config, n_events):
    """Scope ``ssm/scan`` of the traced prefills: the chunked recurrence
    of every layer over the true tokens; reads x, B, C and dt, writes y
    and the final state."""
    d = dims(config)
    flops = nbytes = 0.0
    for _, _, true in window.admissions_traced(facts, n_events):
        flops += d["L"] * true * ssd_flops_token(d)
        nbytes += d["L"] * (true * (d["conv_dim"] + d["di"]) * ITEM
                            + true * d["Hm"] * 4 + d["state"] * STATE)
    return flops, nbytes


def ssm_step_decode_work(facts, config, n_events):
    """Scope ``ssm/step`` of the traced chunks: one step of the recurrence
    a live row and layer; S is read and written once."""
    d = dims(config)
    rows = sum(len(lens) for lens in window.decode_steps_traced(facts))
    return (d["L"] * rows * step_flops_row(d),
            d["L"] * rows * (2 * d["state"] * STATE
                             + (d["conv_dim"] + d["di"]) * 4))


def _heads(d):
    return d["H"], d["Hkv"], d["Dh"]


def flash_fwd_prefill_work(facts, config, n_events):
    """The ``flash_fwd`` calls of the traced prefills: one a layer, over
    the prompt's true tokens (the rung's padding is the kernel's cost,
    not the mathematics')."""
    d = dims(config)
    flops = nbytes = 0
    for _, _, true in window.admissions_traced(facts, n_events // d["L"]):
        f, b = flash_attention.fwd(true, *_heads(d))
        flops, nbytes = flops + d["L"] * f, nbytes + d["L"] * b
    return flops, nbytes


def flash_decode_paged_work(facts, config, n_events):
    """The ``flash_decode_paged`` calls of the traced chunks: one a layer
    and step, the live rows' contexts."""
    d = dims(config)
    flops = nbytes = 0
    for lens in window.decode_steps_traced(facts):
        f, b = paged_decode.step(lens, *_heads(d))
        flops, nbytes = flops + d["L"] * f, nbytes + d["L"] * b
    return flops, nbytes


def kv_over_state(config: dict, ctx_tokens: float, rows: float,
                  chunk: int) -> float | None:
    """K/V bytes read over state bytes moved by the decode steps of the
    chunks whose ``serve.decode_dispatch`` spans summed to ``ctx_tokens``
    (the live rows' positions at dispatch) and ``rows``: a row's k-th
    step of a chunk attends over its position + k, and reads and writes
    its S and its convolution tail in every layer."""
    d = dims(config)
    if rows <= 0:
        return None
    kv = d["L"] * d["kv_token"] * chunk * (ctx_tokens
                                           + rows * (chunk - 1) / 2)
    state = d["L"] * chunk * rows * 2 * (
        d["state"] * STATE + (d["K"] - 1) * d["conv_dim"] * ITEM)
    return kv / state
