"""Model operations of the block, from the configuration's sizes: what a
forward or a training step needs, recomputation not counted."""

from __future__ import annotations

from chipbench.counts import window


def matmul_params(config: dict, head: bool = True) -> int:
    """Parameters that sit in a matmul: per layer qkv, o and the two MLP
    matrices, and the untied head. The embedding is a gather."""
    D = config["hidden_size"]
    H = config["num_attention_heads"]
    kvw = 2 * config["num_key_value_heads"] * (D // H)
    per_layer = D * (D + kvw) + D * D + 2 * D * config["intermediate_size"]
    return (config["num_hidden_layers"] * per_layer
            + (D * config["vocab_size"] if head else 0))


def attention_flops_token(config: dict, context: float) -> float:
    """Forward attention operations of one token that attends to
    ``context`` positions, all layers: q.K^T and p.V."""
    return 4.0 * context * config["hidden_size"] * config["num_hidden_layers"]


def train_flops_token(config: dict, seq: int) -> float:
    """Forward plus backward (twice the forward) of one token of a causal
    sequence of ``seq``: 6 a matmul parameter, and attention at the mean
    context seq / 2."""
    return 6.0 * matmul_params(config) + 3.0 * attention_flops_token(
        config, seq / 2)


def prefill_flops(config: dict, tokens: int) -> float:
    """Forward of a prompt of ``tokens`` true tokens; the head runs at
    the last position alone."""
    D, V = config["hidden_size"], config["vocab_size"]
    return (2.0 * matmul_params(config, head=False) * tokens + 2.0 * D * V
            + tokens * attention_flops_token(config, tokens / 2))


def decode_flops(config: dict, context: int) -> float:
    """Forward of one decoded token at ``context`` cached positions."""
    return 2.0 * matmul_params(config) + attention_flops_token(config, context)


def train_work(facts, config, n_events):
    """The whole window's steps (time is the window's wall time)."""
    tokens = facts["steps"] * facts["batch"] * facts["seq"]
    return tokens * train_flops_token(config, facts["seq"]), 0


def prefill_work(facts, config, n_events):
    """The prefills inside the traced window, at their TRUE lengths."""
    return sum(prefill_flops(config, true) for _, _, true
               in window.admissions_traced(facts, n_events)), 0


def decode_work(facts, config, n_events):
    """The tokens decoded by the chunks read back in the traced window."""
    return sum(decode_flops(config, c)
               for lens in window.decode_steps_traced(facts) for c in lens), 0
