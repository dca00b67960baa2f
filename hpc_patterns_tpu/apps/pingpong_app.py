"""Point-to-point ping-pong: pt2pt latency/bandwidth between mesh pairs.

The BASELINE.json "2-rank device-buffer ping-pong" config, i.e. the
reference's paired blocking ``MPI_Send/MPI_Recv`` with even/odd ordering
(allreduce-mpi-sycl.cpp:50-58) run as a standalone benchmark. On TPU the
pair exchange is one ``lax.ppermute`` with the involution permutation
r ↔ r^1, riding ICI between mesh neighbors.

Sweeps message sizes ``--min-p .. -p`` (default 3..25, the 8 B–256 MiB
band of the BASELINE 8B–8GB axis that fits a dev box), reporting per-size
round-trip latency and per-rank bandwidth. Validation oracle: after two
exchanges every buffer is back home (ppermute with an involution applied
twice is the identity).
"""

from __future__ import annotations

import sys

import numpy as np

from hpc_patterns_tpu.apps import common
from hpc_patterns_tpu.comm.communicator import record_collective_bandwidth
from hpc_patterns_tpu.dtypes import get_traits
from hpc_patterns_tpu.harness import RunLog, Verdict, measure
from hpc_patterns_tpu.harness.cli import (
    add_msg_size_args,
    add_sweep_args,
    base_parser,
)
from hpc_patterns_tpu.harness.timing import blocking, max_across_processes


def build_parser():
    p = base_parser(__doc__.splitlines()[0])
    add_msg_size_args(p)
    add_sweep_args(p)
    p.add_argument("--world", type=int, default=-1, help="ranks; -1 = all devices")
    return p


def run(args) -> int:
    log = RunLog(args.log, truncate=not args.log_append)
    if args.min_p > args.log2_elements:
        # an empty sweep must not be a vacuous SUCCESS
        log.print(f"ERROR: --min-p {args.min_p} > -p {args.log2_elements}")
        log.print("FAILURE")
        return 1
    comm = common.make_communicator(args.backend, args.world, even=True)
    if common.refuse_backend(args, log, comm.mesh.devices.flat):
        return 1
    if comm.size < 2:
        log.print("SKIP: ping-pong needs >= 2 devices (even ranks, "
                  "allreduce-mpi-sycl.cpp:95-97)")
        log.print("SUCCESS")  # precondition skip, not a failure
        return 0
    traits = get_traits(args.dtype)
    all_ok = True
    for p in range(args.min_p, args.log2_elements + 1):
        n = 1 << p
        x = comm.rank_filled(n, traits.dtype)
        exchange = comm.jit_pingpong(x)
        result = measure(
            blocking(exchange, x), repetitions=args.repetitions,
            warmup=args.warmup, label="pingpong",
        )
        elapsed = max_across_processes(result.min_s)
        # validation: one hop moves rank r's data to r^1; rank_filled
        # makes row r the constant r, so the oracle is analytic and each
        # process checks only the rows it can address (multi-process
        # launches validate per rank, like the reference's per-rank
        # asserts)
        out = exchange(x)
        ok = all(
            bool(np.all(np.asarray(row) == (r ^ 1)))
            for r, row in common.local_rows(out)
        )
        ok = common.all_processes_agree(ok)
        all_ok &= ok
        nbytes = n * traits.itemsize
        record_collective_bandwidth("pingpong", nbytes, elapsed,
                                    latency_us=elapsed * 1e6)
        log.emit(
            kind="result",
            name=f"pingpong[p={p}]",
            success=ok,
            elements=n,
            bytes_per_rank=nbytes,
            latency_us=elapsed * 1e6,
            bandwidth_gbps=nbytes / elapsed / 1e9 if elapsed > 0 else float("inf"),
        )
        log.print(
            f"pingpong n=2^{p}: {elapsed * 1e6:.2f} us, "
            f"{nbytes / elapsed / 1e9:.3f} GB/s {'ok' if ok else 'MISMATCH'}"
        )
    verdict = Verdict(success=all_ok, messages=("SUCCESS" if all_ok else "FAILURE",))
    log.print(verdict.summary_line())
    return verdict.exit_code


def main(argv=None) -> int:
    return common.run_instrumented(run, build_parser().parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
