"""Driver ``train``: the program's jitted training step, fed by its loader.

Set-up builds ONE object -- the step from ``make_train_step`` with its
state -- drives it through its first three steps on the window's own feed
(a seeded token file streamed through ``utils/data.PrefetchLoader``), and
hands that same object to the window. The plain reference follows those
three steps after the window has closed.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import traffic, weights
from chipbench.reference import train as reftrain

from hpc_patterns_tpu.models.train import make_optimizer, make_train_step
from hpc_patterns_tpu.models.transformer import TransformerConfig
from hpc_patterns_tpu.utils import data as datalib

CHECK_STEPS = 3


def model_config(config: dict, tr: dict) -> TransformerConfig:
    m = weights.model_dims(config)
    return TransformerConfig(
        vocab=m["V"], d_model=m["D"], n_heads=m["H"], n_layers=m["L"],
        d_ff=m["F"], n_kv_heads=m["Hkv"], max_seq=tr["seq"],
        dtype="bfloat16", attention=tr["attention"], pos_embed="rope",
        rope_theta=m["theta"], remat=tr["remat"],
        remat_policy=tr["remat_policy"], loss_chunk=tr["loss_chunk"])


def corpus(cell: dict, vocab: int, seed: int) -> np.ndarray:
    return traffic.token_stream(cell["trainer"]["corpus_tokens"], vocab, seed)


def reference_batches(stream: np.ndarray, batch: int, seq: int, steps: int):
    """The windows a sequential walk of the corpus gives, read from the
    stream itself and not through the program's loader."""
    flat = stream[:steps * batch * seq]
    return list(flat.reshape(steps, batch, seq))


def leaf_gaps(got, want):
    """Every leaf's gap between two norms, against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(want, float(np.median(want)))


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that can be compared, each a gap; the cell's file says
    which are held to a limit. ``grad_diff_gap`` is the one number that is
    a norm of a difference (over a strided sample of the whole first
    gradient, relative to the reference's): rounding in a lower precision
    is zero-mean and moves no norm, so only a difference tells bfloat16
    from fp8. Leaves whose gradient is nought to
    rounding in the reference (under a thousandth of the median leaf's)
    move under Adam by round-off alone: they are left out of the change,
    by that rule and not by name."""
    g = np.asarray(ref["grad_norms"], np.float64)
    moved = g >= 1e-3 * np.median(g)
    loss = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    grad = leaf_gaps(prog["grad_norms"], g)
    change = leaf_gaps(prog["change_norms"], ref["change_norms"])[moved]
    gs = np.asarray(ref["grad_sample"], np.float64)
    grad_diff = np.linalg.norm(np.asarray(prog["grad_sample"], np.float64)
                               - gs) / np.linalg.norm(gs)
    return {"loss_gap": float(max(loss)), "first_loss_gap": float(loss[0]),
            "grad_diff_gap": float(grad_diff),
            "grad_norm_gap": float(grad.max()),
            "grad_norm_gap_median": float(np.median(grad)),
            "change_norm_gap": float(change.max()),
            "change_norm_gap_median": float(np.median(change)),
            "leaf_gaps": {"grad": grad.tolist(), "change": change.tolist()}}


def run(ctx) -> dict:
    cell, tr = ctx.cell, ctx.cell["trainer"]
    m = weights.model_dims(ctx.config)
    cfg = model_config(ctx.config, tr)
    B, T = tr["batch"], tr["seq"]
    key = weights.seed_key(ctx.seed)
    optimizer = make_optimizer(tr["learning_rate"], tr["weight_decay"],
                               tr["grad_clip"])
    step = make_train_step(cfg, optimizer=optimizer)
    build = jax.jit(lambda k: weights.build(k, m))
    params = build(key)
    opt_state = jax.jit(optimizer.init)(params)

    stream = corpus(cell, m["V"], ctx.seed)
    ctx.data_dir.mkdir(parents=True, exist_ok=True)
    path = ctx.data_dir / "tokens.bin"
    datalib.write_token_file(path, stream, dtype="uint16")
    feed = iter(datalib.PrefetchLoader(datalib.memmap_tokens(
        path, batch=B, seq=T, dtype="uint16", sequential=True,
        vocab=m["V"]), depth=2))

    # the first steps, through the window's own call and feed
    prog = {"losses": []}
    for i in range(CHECK_STEPS):
        loss, params, opt_state = step(params, opt_state, next(feed))
        prog["losses"].append(float(loss))
        if i == 0:
            # the gradient as the optimizer got it: Adam's mu after one
            # step is (1 - b1) times it
            grad = jax.tree.map(lambda a: a / (1.0 - reftrain.B1),
                                opt_state[1][0].mu)
            prog["grad_norms"] = jax.device_get(reftrain.leaf_norms(grad))
            prog["grad_sample"] = jax.device_get(reftrain.leaf_sample(grad))
            del grad
    prog["change_norms"] = jax.device_get(
        reftrain.leaf_norms(reftrain.diff(params, build(key))))

    step_s = []
    t0 = time.perf_counter()
    ctx.tracer.begin(t0)
    while True:
        ts = time.perf_counter()
        loss, params, opt_state = step(params, opt_state, next(feed))
        last = float(loss)          # the readback closes the step
        now = time.perf_counter()
        step_s.append(now - ts)
        ctx.tracer.poll()
        if now - t0 >= ctx.seconds:
            break
    t1 = now
    ctx.tracer.finish()
    feed.close()
    device = ctx.device_report()
    steps = len(step_s)
    facts = {
        "train_step_p50_ms": traffic.percentile(step_s, 50) * 1e3,
        "train_peak_hbm_GB": device["memory_peak_bytes"] / 1e9,
        "window_wall_s": t1 - t0, "steps": steps, "batch": B, "seq": T,
    }
    end_to_end = {"train_tok_s": steps * B * T / (t1 - t0),
                  "setup_s": t0 - ctx.t_process_start}
    del params, opt_state, feed
    batches = reference_batches(stream, B, T, CHECK_STEPS)
    opt = {k: tr[k] for k in ("learning_rate", "weight_decay", "grad_clip")}
    ref = reftrain.three_steps(ctx.seed, m, batches, opt)
    if ctx.control:   # a control or a fault, in the program's place
        half = ctx.control == "half-batch"
        prog = reftrain.three_steps(ctx.seed, m, batches, opt,
                                    lowp=None if half else ctx.control,
                                    half_batch=half)
    numbers = compare(prog, ref)
    checks = [(n, numbers[n], lim) for n, lim in cell["check"].items()]
    checks.append(("last_loss_finite", 0.0 if np.isfinite(last) else 1.0, 0.0))
    return {"end_to_end": end_to_end, "facts": facts, "attempted": steps,
            "failed": 0, "checks": checks, "device": device,
            "readings": numbers}
