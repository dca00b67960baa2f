"""Driver ``allreduce``: the source paper's sweep, message size against
bus bandwidth, through the program's ``Communicator``.

One process drives the cell's chips. The window cycles round-robin over
the size ladder and, at each size, over the algorithms; each call is the
communicator's compiled closure for that shape (``jit_allreduce`` -- what
``Communicator.allreduce`` runs, without its per-call re-trace) and ends
in ``block_until_ready``. Inputs of every size stay live on the chips,
with the newest output of every (size, algorithm) and one kept from a
cycle drawn from the seed; those are compared once the window has closed.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from chipbench import weights
from chipbench.reference import allreduce as refar

from hpc_patterns_tpu import topology
from hpc_patterns_tpu.comm.communicator import Communicator

SAMPLE_CYCLES = 16   # the kept cycle is drawn from the first this many


def bus_bytes(nbytes: int, world: int) -> float:
    """Bytes each chip must send for an allreduce of ``nbytes`` a rank:
    the ring limit 2(n-1)/n (apps/common.allreduce_bus_bandwidth_gbps)."""
    return nbytes * 2 * (world - 1) / world


def make_inputs(seed: int, comm: Communicator, sizes, dtype):
    """Every size's (ranks, n) input, one row on each chip, made there
    from the seed in one jitted call."""
    key = weights.seed_key(seed)

    def build(k):   # rank-local: each chip makes its own row
        r = lax.axis_index(comm.axis)
        return [refar.rank_input(k, r, n, dtype)[None] for n in sizes]

    row = P(comm.axis, None)
    return jax.jit(shard_map(build, mesh=comm.mesh, in_specs=P(),
                             out_specs=[row] * len(sizes)))(key)


def run(ctx) -> dict:
    cell, cfgd = ctx.cell, ctx.config
    world = cfgd["ranks"]
    lad = cfgd["log2_elements_per_rank"]
    sizes = [1 << e for e in range(lad["lo"], lad["hi"] + 1, lad["step"])]
    algorithms = list(cfgd["algorithms"])
    dtype = jnp.dtype(ctx.control or cfgd["dtype"])
    mesh = topology.make_mesh({"x": world}, ctx.devices[:world])
    comm = Communicator(mesh, "x")
    inputs = make_inputs(ctx.seed, comm, sizes, dtype)
    calls = [(i, a, comm.jit_allreduce(inputs[i], a))
             for i in range(len(sizes)) for a in algorithms]
    newest = {}
    for i, a, fn in calls:   # warm every shape: compile, first run
        newest[i, a] = jax.block_until_ready(fn(inputs[i]))
    keep_cycle = int(np.random.default_rng(
        np.random.SeedSequence([int(ctx.seed), 5])).integers(SAMPLE_CYCLES))
    kept = {}
    itemsize = dtype.itemsize
    spent = {k: [0.0, 0] for k in newest}   # seconds, calls per pair
    bus = 0.0
    cycle = 0
    t0 = time.perf_counter()
    ctx.tracer.begin(t0)
    while True:
        for i, a, fn in calls:
            ts = time.perf_counter()
            out = jax.block_until_ready(fn(inputs[i]))
            dt = time.perf_counter() - ts
            spent[i, a][0] += dt
            spent[i, a][1] += 1
            bus += bus_bytes(sizes[i] * itemsize, world)
            newest[i, a] = out
            if cycle == keep_cycle:
                kept[i, a] = out
        cycle += 1
        now = time.perf_counter()
        ctx.tracer.poll()
        if now - t0 >= ctx.seconds:
            break
    t1 = now
    ctx.tracer.finish()
    device = ctx.device_report()
    n_calls = sum(c for _, c in spent.values())
    small = [spent[i, a] for i, a, _ in calls
             if sizes[i] * itemsize <= cell["small_bytes"]]
    top = len(sizes) - 1
    top_s = sum(spent[top, a][0] for a in algorithms)
    top_n = sum(spent[top, a][1] for a in algorithms)
    top_bw = bus_bytes(sizes[top] * itemsize, world) * top_n / top_s
    facts = {
        "allreduce_small_us": 1e6 * sum(s for s, _ in small)
        / sum(c for _, c in small),
        "allreduce_large_ici_pct": 100.0 * top_bw
        / ctx.peaks["ici_bytes_per_s"] if "ici_bytes_per_s" in ctx.peaks
        else None,
        "window_wall_s": t1 - t0, "cycles": cycle, "calls": n_calls,
        "per_pair_us": {f"{sizes[i]}:{a}": 1e6 * s / c
                        for (i, a), (s, c) in spent.items()},
    }
    end_to_end = {"allreduce_busbw": bus / (t1 - t0) / 1e9,
                  "setup_s": t0 - ctx.t_process_start}
    # the comparison: every answer still held, against the sum made again
    # from the seed; inputs go first, they are not needed for it
    del inputs, calls
    wrong, missing = 0, len(newest) - len(kept)
    for outs in (kept, newest):
        while outs:   # each answer is freed once it has been compared
            _, out = outs.popitem()
            wrong += refar.wrong_elements(ctx.seed, out, world)
    checks = [("wrong_elements", float(wrong), 0.0),
              ("answers_missing", float(missing), 0.0)]
    return {"end_to_end": end_to_end, "facts": facts, "attempted": n_calls,
            "failed": 0, "checks": checks, "device": device}
