"""Stencil miniapp: distributed 1-D diffusion with halo exchange.

The BASELINE.json config "SYCL+OMP shared-USM stencil with halo
exchange" as a self-validating benchmark: a periodic 3-point Jacobi
diffusion, domain sharded over the mesh, ghost cells exchanged per step
via ``ppermute`` (comm/halo.py), the whole step loop inside ONE jitted
``lax.fori_loop`` so the halo transfers pipeline against the stencil
compute (no host round-trip per step — the XLA-semantics ground rule).

Validation oracles (SURVEY.md §4.2 style):
1. conservation — periodic diffusion preserves the domain sum exactly
   (up to fp tolerance);
2. single-device replay — the sharded result must equal the unsharded
   loop bit-for-fp-bit-close.

Reports per-step time and halo bandwidth.
"""

from __future__ import annotations

import sys

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from hpc_patterns_tpu.apps import common
from hpc_patterns_tpu.comm import halo
from hpc_patterns_tpu.comm.communicator import record_collective_bandwidth
from hpc_patterns_tpu.harness import RunLog, Verdict, measure
from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness.cli import add_msg_size_args, base_parser
from jax import shard_map
from hpc_patterns_tpu.harness.timing import blocking, max_across_processes


def build_parser():
    p = base_parser(__doc__.splitlines()[0])
    add_msg_size_args(p)
    p.set_defaults(log2_elements=22)  # stencil default: 4M cells
    p.add_argument("--steps", type=int, default=64, help="Jacobi steps per run")
    p.add_argument("--world", type=int, default=-1, help="ranks; -1 = all devices")
    p.add_argument("--alpha", type=float, default=0.25)
    return p


def run(args) -> int:
    log = RunLog(args.log, truncate=not args.log_append)
    comm = common.make_communicator(args.backend, args.world)
    if common.refuse_backend(args, log, comm.mesh.devices.flat):
        return 1
    mesh, axis = comm.mesh, comm.axis
    world = comm.size
    n = 1 << args.log2_elements  # global domain size (2**p, like -p)
    n += (-n) % world
    steps = args.steps
    alpha = args.alpha

    key = jax.random.PRNGKey(0)
    u0 = jax.random.uniform(key, (n,), jnp.float32)
    u0_sharded = jax.device_put(u0, NamedSharding(mesh, P(axis)))

    def local_loop(u):
        return lax.fori_loop(
            0, steps, lambda _, v: halo.jacobi_step(v, axis, alpha=alpha), u
        )

    stepper = jax.jit(
        shard_map(local_loop, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    )

    result = measure(
        blocking(stepper, u0_sharded),
        repetitions=args.repetitions, warmup=args.warmup, label="stencil",
    )
    out = stepper(u0_sharded)

    # oracles over addressable shards only, so multi-process launches
    # (apps/launch.py) validate per rank like the reference's per-rank
    # asserts; u0 and the dense replay are identical on every process.
    # oracle 2: single-device replay
    def dense_step(v):
        return (1 - 2 * alpha) * v + alpha * (jnp.roll(v, 1) + jnp.roll(v, -1))

    want = np.asarray(
        # jaxlint: disable=recompile-hazard — one-shot dense oracle per
        # run(); closes over the run's steps/alpha args
        jax.jit(lambda v: lax.fori_loop(0, steps, lambda _, w: dense_step(w), v))(u0)
    )
    shards = out.addressable_shards
    matches = all(
        bool(np.allclose(np.asarray(s.data), want[s.index], atol=1e-5))
        for s in shards
    )
    # oracle 1: conservation (periodic diffusion preserves the sum) —
    # local shard sums, summed across processes
    local_sum = sum(float(np.asarray(s.data).sum()) for s in shards)
    total = common.reduce_across_processes(local_sum, np.sum)
    conserved = bool(np.isclose(total, float(np.asarray(u0).sum()), rtol=1e-4))

    ok = common.all_processes_agree(conserved and matches)
    per_step = max_across_processes(result.min_s) / steps
    halo_bytes = 2 * 4 * world  # 2 directions × f32 per rank, per step
    record_collective_bandwidth("halo", halo_bytes, per_step)
    metricslib.get_metrics().gauge("stencil.step_us").set(per_step * 1e6)
    log.emit(
        kind="result", name="stencil", success=ok, world=world,
        elements=n, steps=steps, per_step_us=per_step * 1e6,
        conserved=conserved, matches_dense=matches,
    )
    log.print(
        f"stencil world={world} n={n} steps={steps}: "
        f"{per_step * 1e6:.2f} us/step "
        f"(halo {halo_bytes}B/step) conserved={conserved} dense-match={matches}"
    )
    if ok:
        rows_per_rank = n // world
        for s in shards:
            log.print(f"Passed {(s.index[0].start or 0) // rows_per_rank}")
    verdict = Verdict(success=ok, messages=("SUCCESS" if ok else "FAILURE",))
    log.print(verdict.summary_line())
    return verdict.exit_code


def main(argv=None) -> int:
    return common.run_instrumented(run, build_parser().parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
