"""The precision-law oracle: what "correct" means across precisions.

Token identity is the serving oracle WITHIN a precision (an int8-KV
engine is token-identical to int8-KV standalone decode — same math
both sides, tests/test_quantization.py pins it). ACROSS precisions it
cannot hold: a quantized cache or weight set perturbs every logit, so
the contract is a LAW bound instead — the same oracle shape PR 2 used
for draft-assisted sampling, applied to precision:

- **greedy top-1 agreement**: the fraction of TEACHER-FORCED steps
  whose argmax token matches the reference precision's. Teacher-forced
  (both variants walk the REFERENCE's token stream) because
  free-running agreement compounds: one near-tie flip early makes
  every later token trivially different, which measures drift, not
  quantization error;
- **total-variation distance**: ``0.5 * sum |softmax_a - softmax_b|``
  per teacher-forced step — the distributional distance sampling
  inherits, reported as mean and max over the walk.

The tier-1 tests (tests/test_quantization.py) run this oracle at the
bounds of :data:`DEFAULT_BOUNDS`, one case per precision (int8/fp8 KV,
int8 weights, and the composed forms), and show that a broken dequant
fails it. docs/quantization.md has the full matrix.

The same law judges a token stream an engine ALREADY emitted
(:func:`emitted_stream_law`): on the TPU, bf16 matmuls round
differently at different batch geometries, so the 8-slot ragged engine
and a B=1 standalone decode flip near-tie argmaxes against each other
(measured on a v5e at d=1024 with random weights: 4 of 8 sequences) —
token identity between them is not a law there, teacher-forced
agreement with the float32 reference is.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from functools import partial

from hpc_patterns_tpu.models.decode import decode_step, prefill
from hpc_patterns_tpu.models.transformer import (  # noqa: F401  (re-export)
    QUANT_SCALE_SUFFIX,
    TransformerConfig,
    forward,
    matmul_weight,
    quantize_weights_int8,
)

#: the law bounds the serving benches gate on (comfortably above the
#: measured smoke-scale values — agreement ~0.95+, mean TV ~0.01 —
#: tight enough that a broken dequant path, which sends TV toward 1,
#: cannot pass)
DEFAULT_BOUNDS = {
    "greedy_agreement_min": 0.85,
    "tv_mean_max": 0.05,
    "tv_max_max": 0.15,
}


@dataclasses.dataclass(frozen=True)
class PrecisionLaw:
    """One oracle run's verdict (:func:`precision_law`)."""
    greedy_agreement: float
    tv_mean: float
    tv_max: float
    steps: int

    def check(self, bounds: dict | None = None) -> None:
        """Raise AssertionError naming the violated bound (the
        benches call this before believing any quantized number)."""
        b = {**DEFAULT_BOUNDS, **(bounds or {})}
        assert self.greedy_agreement >= b["greedy_agreement_min"], (
            f"precision law: greedy top-1 agreement "
            f"{self.greedy_agreement:.3f} < "
            f"{b['greedy_agreement_min']} over {self.steps} "
            "teacher-forced steps")
        assert self.tv_mean <= b["tv_mean_max"], (
            f"precision law: mean TV distance {self.tv_mean:.4f} > "
            f"{b['tv_mean_max']}")
        assert self.tv_max <= b["tv_max_max"], (
            f"precision law: max TV distance {self.tv_max:.4f} > "
            f"{b['tv_max_max']}")


def precision_law(params_ref, cfg_ref: TransformerConfig, params_q,
                  cfg_q: TransformerConfig, prompts, steps: int = 8,
                  ) -> PrecisionLaw:
    """Teacher-forced precision-law measurement between a REFERENCE
    precision (``params_ref``/``cfg_ref``) and a QUANTIZED variant
    (``params_q``/``cfg_q`` — quantized KV config, int8 weights from
    :func:`quantize_weights_int8`, or both). ``prompts``: (B, T) int32.

    Both variants prefill the same prompts and then walk ``steps``
    decode steps along the REFERENCE's greedy continuation, comparing
    the step logits' argmax and softmax TV at every position — each
    step an independent judgment of the quantization error at that
    state, no compounding. The linear cache route is used (one
    prefill + unrolled steps); KV-precision effects show up from the
    first decode step because prefill quantizes the stored K/V."""
    prompts = jnp.asarray(prompts, jnp.int32)
    B, T = prompts.shape
    need = T + steps
    if need > min(cfg_ref.max_seq, cfg_q.max_seq):
        raise ValueError(
            f"prompt {T} + steps {steps} exceeds max_seq "
            f"{min(cfg_ref.max_seq, cfg_q.max_seq)}")
    la, cache_a = prefill(params_ref, prompts, cfg_ref, need)
    lb, cache_b = prefill(params_q, prompts, cfg_q, need)
    agree, tvs = [], []
    pos = T
    for step in range(steps):
        pa = jax.nn.softmax(la, axis=-1)
        pb = jax.nn.softmax(lb, axis=-1)
        tvs.append(0.5 * np.abs(np.asarray(pa) - np.asarray(pb))
                   .sum(axis=-1))
        ref_tok = jnp.argmax(la, axis=-1).astype(jnp.int32)
        agree.append(np.asarray(
            ref_tok == jnp.argmax(lb, axis=-1).astype(jnp.int32)))
        if step == steps - 1:
            break  # the last judged logits need no successor state
        # BOTH variants consume the reference's token (teacher forcing)
        la, cache_a = decode_step(params_ref, cache_a, jnp.int32(pos),
                                  ref_tok, cfg_ref)
        lb, cache_b = decode_step(params_q, cache_b, jnp.int32(pos),
                                  ref_tok, cfg_q)
        pos += 1
    return PrecisionLaw(
        greedy_agreement=float(np.mean(agree)),
        tv_mean=float(np.mean(tvs)),
        tv_max=float(np.max(tvs)),
        steps=steps,
    )


@partial(jax.jit, static_argnames=("cfg",))
def _reference_walk(params, tokens, cfg: TransformerConfig):
    """Per position: the float32 reference's argmax token, its
    probability, and the full softmax — one dense causal forward, so
    every position is judged on the exact prefix before it."""
    with jax.default_matmul_precision("highest"):
        logits = forward(params, tokens, cfg)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), probs


def emitted_stream_law(params, cfg: TransformerConfig, prompts,
                       emitted) -> PrecisionLaw:
    """The precision law for token streams an engine already emitted:
    each stream ``emitted[i]`` (greedy continuation of ``prompts[i]``)
    is walked TEACHER-FORCED through the float32 reference — one dense
    ``forward`` at ``attention="full"``, highest matmul precision, no
    kernel, no cache, no batching in common with the engine — and every
    emitted token is judged against the reference's argmax on the
    engine's own prefix, so one near-tie flip costs one step, not the
    rest of the stream.

    ``greedy_agreement`` is the fraction of emitted tokens that ARE the
    reference argmax. The engine's distributions are not observable, so
    the TV entries are the lower bound a flip implies: where the engine
    chose ``e`` over the reference's ``r``, any distribution with
    argmax ``e`` is at least ``(p_ref(r) - p_ref(e)) / 2`` away from the
    reference in total variation (0 where they agree). A stream from a
    broken engine disagrees almost everywhere; rounding flips only
    near-ties. Check with :meth:`PrecisionLaw.check`."""
    ref_cfg = dataclasses.replace(cfg, dtype="float32", attention="full",
                                  kv_cache_dtype="compute", remat=False)
    rows = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(e, np.int32)[:-1]])
            for p, e in zip(prompts, emitted)]
    width = max(len(r) for r in rows)
    if width > ref_cfg.max_seq:
        raise ValueError(
            f"prompt + emitted {width + 1} exceeds max_seq "
            f"{ref_cfg.max_seq}")
    tokens = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        tokens[i, :len(r)] = r  # right padding: causal, so inert
    top, probs = _reference_walk(params, jnp.asarray(tokens), ref_cfg)
    agree, tvs = [], []
    for i, (p, e) in enumerate(zip(prompts, emitted)):
        at = np.arange(len(e)) + len(p) - 1  # position predicting e[t]
        e = np.asarray(e, np.int32)
        ref = np.asarray(top[i, at])
        p_row = probs[i, at]
        margin = (np.asarray(p_row[np.arange(len(e)), ref])
                  - np.asarray(p_row[np.arange(len(e)), e]))
        agree.append(ref == e)
        tvs.append(0.5 * margin)
    agree = np.concatenate(agree)
    tvs = np.concatenate(tvs)
    return PrecisionLaw(
        greedy_agreement=float(agree.mean()),
        tv_mean=float(tvs.mean()),
        tv_max=float(tvs.max()),
        steps=int(agree.size),
    )
