"""Operations and bytes of one decode step's attention against a paged KV
cache, from the rows' context lengths alone."""

from __future__ import annotations

from chipbench.counts import window


def step(context_lens, H: int, Hkv: int, Dh: int, itemsize: int = 2):
    """One layer, one step, one query a row: q.K^T and p.V over each
    row's context (4 c Dh H operations); reads the row's keys and values
    once (GQA: Hkv heads, not H) and q, writes o."""
    ctx = sum(int(c) for c in context_lens)
    rows = len(context_lens)
    flops = 4 * ctx * Dh * H
    nbytes = (2 * ctx * Hkv * Dh + 2 * rows * H * Dh) * itemsize
    return flops, nbytes


def chunk_work(facts, config, n_events):
    """The paged-decode calls of the chunks read back inside the traced
    window: one call a layer and step."""
    H = config["num_attention_heads"]
    Hkv = config["num_key_value_heads"]
    Dh = config["hidden_size"] // H
    L = config["num_hidden_layers"]
    flops = nbytes = 0
    for lens in window.decode_steps_traced(facts):
        f, b = step(lens, H, Hkv, Dh)
        flops, nbytes = flops + L * f, nbytes + L * b
    return flops, nbytes
