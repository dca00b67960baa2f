"""Operations and bytes of causal attention, from shapes alone: what the
mathematics needs, whichever kernel computes it."""

from __future__ import annotations

from chipbench.counts import window


def fwd(T: int, H: int, Hkv: int, Dh: int, itemsize: int = 2):
    """One sequence's causal forward: QK^T and PV over the lower
    triangle (2 matmuls x 2 T^2 Dh H / 2); reads q, k, v, writes o and
    the row log-sum-exp (float32)."""
    flops = 2 * T * T * Dh * H
    nbytes = (2 * T * H * Dh + 2 * T * Hkv * Dh) * itemsize + 4 * T * H
    return flops, nbytes


def bwd(T: int, H: int, Hkv: int, Dh: int, itemsize: int = 2):
    """The backward the mathematics needs: dV, dP, dQ, dK, four matmuls
    over the triangle, twice the forward. Recomputing the scores, as a
    flash backward does, is the kernel's choice and is not counted.
    Reads q, k, v, o, do and the log-sum-exp, writes dq, dk, dv."""
    flops = 2 * fwd(T, H, Hkv, Dh)[0]
    nbytes = (4 * T * H * Dh + 4 * T * Hkv * Dh) * itemsize + 4 * T * H
    return flops, nbytes


def _dims(config):
    H = config["num_attention_heads"]
    return H, config["num_key_value_heads"], config["hidden_size"] // H


def prefill_work(facts, config, n_events):
    """The forward kernels of the prefills inside the traced window: one
    call a layer, at the rung the prompt was padded to."""
    L = config["num_hidden_layers"]
    flops = nbytes = 0
    for _, rung, _ in window.admissions_traced(facts, n_events // L):
        f, b = fwd(rung, *_dims(config))
        flops, nbytes = flops + L * f, nbytes + L * b
    return flops, nbytes


def train_work(facts, config, n_events):
    """Forward and backward kernels of the traced steps: ``n_events``
    counts both kinds, one of each a layer and step; each call holds the
    step's whole batch."""
    L = config["num_hidden_layers"]
    steps = n_events // (2 * L)
    f1, b1 = fwd(facts["seq"], *_dims(config))
    f2, b2 = bwd(facts["seq"], *_dims(config))
    per_step = L * facts["batch"]
    return steps * per_step * (f1 + f2), steps * per_step * (b1 + b2)
