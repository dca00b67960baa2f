"""Step profile: trace N training steps and print the op_profile
category breakdown.

Builds the same step as benchmarks/bench_train.py (same args), runs a
warmup, traces a few steps with jax.profiler, and parses the trace via
xprof's op_profile converter into (category, % of device time, MXU
utilization) rows.

Usage: python benchmarks/profile_train.py [--seq=N] [--steps=8] [...]
"""

import glob
import json
import sys
import tempfile

import jax
from jax import lax

from hpc_patterns_tpu.models import TransformerConfig
from hpc_patterns_tpu.models.train import (
    init_train_state,
    make_batch,
    make_optimizer,
)
from hpc_patterns_tpu.models.transformer import loss_fn
from functools import partial
import optax


def arg(name, default, cast):
    for a in sys.argv[1:]:
        if a.startswith(f"--{name}="):
            return cast(a.split("=", 1)[1])
    return default


def _print_tree(prog, min_pct=0.5, top_children=3):
    """Category rows of one program node (this xprof's op_profile JSON:
    byProgramExcludeIdle -> program -> category -> op)."""
    total = prog.get("metrics", {}).get("rawTime", 1) or 1
    cats = sorted(prog.get("children", []),
                  key=lambda c: -c.get("metrics", {}).get("rawTime", 0))
    print(f"{'category / top ops':48s} {'%time':>6s} {'mxu%':>6s} "
          f"{'membw%':>7s}")
    for c in cats:
        m = c.get("metrics", {})
        pct = 100.0 * m.get("rawTime", 0) / total
        if pct < min_pct:
            continue
        bw = (m.get("bandwidthUtils") or [0])[0] * 100.0
        print(f"{c.get('name', '?')[:48]:48s} {pct:6.1f} "
              f"{m.get('flops', 0) * 100:6.1f} {bw:7.1f}")
        ops = sorted(c.get("children", []),
                     key=lambda x: -x.get("metrics", {}).get("rawTime", 0))
        for cc in ops[:top_children]:
            cm = cc.get("metrics", {})
            cbw = (cm.get("bandwidthUtils") or [0])[0] * 100.0
            print(f"  {cc.get('name', '?')[:46]:46s} "
                  f"{100.0 * cm.get('rawTime', 0) / total:6.1f} "
                  f"{cm.get('flops', 0) * 100:6.1f} {cbw:7.1f}")


def main():
    from hpc_patterns_tpu import compile_cache

    compile_cache.enable()
    on_tpu = jax.default_backend() == "tpu"
    cfg = TransformerConfig(
        vocab=arg("vocab", 32768 if on_tpu else 256, int),
        d_model=arg("d", 1024 if on_tpu else 64, int),
        n_heads=arg("heads", 8 if on_tpu else 4, int),
        n_layers=arg("layers", 8 if on_tpu else 2, int),
        d_ff=arg("ff", 4096 if on_tpu else 128, int),
        max_seq=arg("seq", 2048 if on_tpu else 64, int),
        dtype="bfloat16",
        attention=arg("attn", "flash" if on_tpu else "full", str),
        remat=bool(arg("remat", 1, int)),
        n_kv_heads=arg("kv", 0, int),
        loss_chunk=arg("chunk", 0, int),
        remat_policy=arg("rp", "split", str),
        pos_embed=arg("pos", "learned", str),
        mlp_impl=arg("mlp", "dense", str),
    )
    batch = arg("batch", 8 if on_tpu else 2, int)
    steps = arg("steps", 8, int)
    optimizer = make_optimizer()
    params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg,
                                         optimizer=optimizer)
    tokens = make_batch(jax.random.PRNGKey(1), cfg, batch, cfg.max_seq)

    @partial(jax.jit, static_argnums=(2,))
    def run_t(carry, tokens, n):
        def one_step(carry, _):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(partial(loss_fn, cfg=cfg))(
                params, tokens
            )
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), loss

        _, losses = lax.scan(one_step, carry, None, length=n)
        return losses[-1]

    # warmup/compile outside the trace
    jax.block_until_ready(run_t((params, opt_state), tokens, steps))
    logdir = tempfile.mkdtemp(prefix="hpcpat_prof_")
    with jax.profiler.trace(logdir):
        jax.block_until_ready(run_t((params, opt_state), tokens, steps))

    xspace = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not xspace:
        print(f"no xplane under {logdir}")
        return
    from xprof.convert import raw_to_tool_data

    data, _ = raw_to_tool_data.xspace_to_tool_data(
        [xspace[-1]], "op_profile", params={}
    )
    prof = json.loads(data) if isinstance(data, (str, bytes)) else data
    progs = prof.get("byProgramExcludeIdle", {}).get("children", [])
    if not progs:
        print(f"no programs in op_profile (trace dir {logdir})")
        return
    prog = max(progs, key=lambda p: p.get("metrics", {}).get("rawTime", 0))
    m = prog.get("metrics", {})
    bw = (m.get("bandwidthUtils") or [0])[0] * 100.0
    print(f"config: T={cfg.max_seq} B={batch} kv={cfg.n_kv_heads} "
          f"remat={cfg.remat}/{cfg.remat_policy} chunk={cfg.loss_chunk} "
          f"pos={cfg.pos_embed} mlp={cfg.mlp_impl} steps={steps}")
    print(f"program {prog.get('name', '?')}: flops-util "
          f"{m.get('flops', 0) * 100:.1f}%  hbm-bw {bw:.1f}%  "
          f"(trace dir {logdir})")
    _print_tree(prog)


if __name__ == "__main__":
    main()
