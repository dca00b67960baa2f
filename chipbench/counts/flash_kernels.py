"""Operations and bytes of the flash kernels of a training step, each
kernel by its own name: the forward (``flash_fwd``) and the fused
backward (``flash_bwd_fused``) apart, where ``flash_attention.train_work``
counts the two together."""

from __future__ import annotations

from chipbench.counts.flash_attention import _dims, bwd, fwd


def _calls(kernel, facts, config, n_events):
    """``n_events`` calls of one kernel, one a layer and step, each
    holding the step's whole batch."""
    f, b = kernel(facts["seq"], *_dims(config))
    return n_events * facts["batch"] * f, n_events * facts["batch"] * b


def train_fwd_work(facts, config, n_events):
    return _calls(fwd, facts, config, n_events)


def train_bwd_work(facts, config, n_events):
    return _calls(bwd, facts, config, n_events)
