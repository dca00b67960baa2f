"""Allreduce miniapp: ring vs library collective on a TPU mesh.

TPU-native rebuild of the reference's three allreduce miniapps
(allreduce-mpi-sycl.cpp, allreduce-usm/map-mpi-omp-offload.cpp — C5–C7
in SURVEY.md). Reproduced semantics:

- ``-a`` switches from the hand ring to the library collective
  (allreduce-mpi-sycl.cpp:122-124 → here ``lax.psum``); additionally
  ``--algorithm ring_chunked`` selects the bandwidth-optimal two-phase
  ring the reference's teaching ring approximates.
- ``-p N`` → 2**N elements per rank, default 25 (:99,125-128).
- ``-H/-D/-S`` allocator axis → JAX memory kinds (:104-131); host kind
  falls back to device with a logged note when a non-TPU backend lacks
  it (on the TPU a rejected kind is a failure).
- rank-valued init (:33-41), analytic oracle size(size−1)/2 validated
  elementwise on the host (:192-204), per-rank "Passed r" lines (:206).
- wall-clock timed region, MAX across processes (:170-190), min over
  repetitions; compile excluded by warm-up (SURVEY.md §7(d)).
- dtype axis via ``--dtype`` ≙ the typed CTest variants
  (mpi-sycl/CMakeLists.txt:4-5, float+int).

Reported: elapsed seconds, algorithm bandwidth, and ring-normalized bus
bandwidth (the BASELINE.json headline metric).
"""

from __future__ import annotations

import functools
import sys

import numpy as np

from hpc_patterns_tpu.harness.timing import blocking

from hpc_patterns_tpu.apps import common
from hpc_patterns_tpu.comm.communicator import record_collective_bandwidth
from hpc_patterns_tpu.dtypes import get_traits
from hpc_patterns_tpu.harness import RunLog, Verdict, correctness_verdict, measure
from hpc_patterns_tpu.harness.cli import (
    add_memory_kind_args,
    add_msg_size_args,
    add_sweep_args,
    base_parser,
)
from hpc_patterns_tpu.harness.timing import max_across_processes


def build_parser():
    p = base_parser(__doc__.splitlines()[0])
    add_msg_size_args(p)
    add_memory_kind_args(p)
    p.add_argument(
        "-a",
        "--allreduce",
        action="store_true",
        help="use the library collective (reference -a → MPI_Allreduce)",
    )
    p.add_argument(
        "--algorithm",
        default=None,
        choices=["ring", "ring_chunked", "collective", "fused"],
        help="explicit algorithm (overrides -a; default ring, like the "
             "reference; 'fused' = the device-initiated in-kernel "
             "remote-DMA ring, comm/fused.py)",
    )
    p.add_argument(
        "--world",
        type=int,
        default=-1,
        help="ranks (mesh size); -1 = all devices (mpirun -np analog)",
    )
    p.add_argument(
        "--sweep",
        action="store_true",
        help="sweep message sizes --min-p..-p for each algorithm "
             "(ring, ring_chunked, collective unless --algorithm/-a "
             "narrows it), emitting one validated JSONL result per "
             "point — the GB/s-vs-size curve of the BASELINE metric "
             "(reference protocol: allreduce-mpi-sycl.cpp:99,125-128)",
    )
    add_sweep_args(p)
    return p


def resolve_algorithm(args) -> str:
    if args.algorithm:
        return args.algorithm
    return "collective" if args.allreduce else "ring"


def run(args) -> int:
    log = RunLog(args.log, truncate=not args.log_append)
    comm = common.make_communicator(args.backend, args.world, even=True)
    if common.refuse_backend(args, log, comm.mesh.devices.flat):
        return 1
    if args.sweep:
        return run_sweep(args, log, comm)
    return _run_point(args, log, comm, resolve_algorithm(args),
                      args.log2_elements)


def run_sweep(args, log, comm) -> int:
    """Message-size sweep per algorithm: every point is a full validated
    run (analytic oracle + "Passed r" lines), and every point emits a
    JSONL result record — together the busbw-vs-size curve. On world=1
    the ring degenerates to a copy and the bandwidths are NOT a
    collective measurement; the records carry the world size so readers
    can tell."""
    if args.min_p > args.log2_elements:
        log.print(f"ERROR: --min-p {args.min_p} > -p {args.log2_elements}")
        log.print("FAILURE")
        return 1
    if args.algorithm or args.allreduce:
        algorithms = [resolve_algorithm(args)]
    else:
        algorithms = ["ring", "ring_chunked", "collective", "fused"]
    n_ok = n_total = 0
    kind_cache: dict = {}  # memory-kind probe result, shared across points
    budget = _hbm_budget_bytes()
    for algorithm in algorithms:
        for p in range(args.min_p, args.log2_elements + 1):
            nbytes = (1 << p) * get_traits(args.dtype).itemsize
            if budget and 3 * nbytes > budget:
                # GB-scale guard: a point needs input + output + one
                # transient copy live (~3x). Skipping is LOUD — a curve
                # that silently stops reads as "measured everything"
                log.print(
                    f"skipped {algorithm} p={p}: ~{3 * nbytes >> 20} MiB "
                    f"working set exceeds HBM budget {budget >> 20} MiB"
                )
                continue
            n_total += 1
            code = _run_point(args, log, comm, algorithm, p,
                              kind_cache=kind_cache)
            n_ok += code == 0
    # n_total == 0 (every point skipped by the headroom guard) is a
    # FAILURE: a run that measured nothing must not read as green
    ok = n_ok == n_total and n_total > 0
    log.print(f"sweep: {n_ok}/{n_total} points passed "
              f"(world={comm.size}, p={args.min_p}..{args.log2_elements}, "
              f"algorithms={','.join(algorithms)})")
    log.print("SUCCESS" if ok else "FAILURE")
    return 0 if ok else 1


def _device_mismatches(shard_data, i: int, expected_scalar: float,
                       traits) -> int:
    """Elementwise oracle check for row ``i`` of a (rows, n) shard,
    reduced ON DEVICE to a mismatch count (same tolerance rule as
    dtypes.validate_allreduce). The row slice AND the elementwise
    compare happen inside one jit as a chunked scan, so the live
    transient is one chunk — a GB-scale point cannot afford a
    materialized row copy or a row-sized |diff| temp next to the
    input/output buffers (a 4 GiB point would need ~13 GiB)."""
    import jax
    import jax.numpy as jnp

    exact = traits.exact_sum
    tol = (0.0 if exact
           else traits.tolerance + 1e-6 * abs(float(expected_scalar)))
    n = shard_data.shape[-1]
    chunk = 1 << 24
    n_chunks = max(1, n // chunk)
    while n % n_chunks:
        n_chunks -= 1

    @functools.partial(jax.jit, static_argnums=(1,))
    def count(data, i):
        def body(c, piece):
            if exact:
                # integer dtypes compare exactly IN the integer dtype —
                # float promotion would round away small deltas
                bad = jnp.sum(piece != jnp.asarray(expected_scalar,
                                                   piece.dtype))
            else:
                bad = jnp.sum(jnp.abs(piece - float(expected_scalar)) > tol)
            return c + bad, None
        c, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.int32),
            data[i].reshape(n_chunks, n // n_chunks),
        )
        return c

    return int(count(shard_data, i))


def _hbm_budget_bytes() -> int | None:
    """Per-device memory budget for the sweep's working-set guard:
    bytes_limit minus what is already in use, from the backend's own
    accounting. None when the backend doesn't report memory stats (then
    the sweep runs unguarded, as before)."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        in_use = stats.get("bytes_in_use", 0)
        return limit - in_use if limit else None
    except Exception:  # noqa: BLE001 — stats are a best-effort guard
        return None


def _run_point(args, log, comm, algorithm: str, log2_elements: int,
               kind_cache: dict | None = None) -> int:
    world = comm.size
    n = 1 << log2_elements
    traits = get_traits(args.dtype)
    if algorithm == "ring_chunked" and n % world:
        # chunked ring needs size | n; pad up like any real collective would
        n += world - n % world

    memory_kind = None if args.memory_kind == "device" else args.memory_kind
    if kind_cache is not None and memory_kind is not None:
        # sweep mode: the probe outcome is invariant across points, so
        # resolve once instead of re-probing (and re-logging) 75 times
        memory_kind = kind_cache.get("kind", memory_kind)
    x = comm.rank_filled(n, traits.dtype)
    step = comm.jit_allreduce(x, algorithm)
    if memory_kind is not None:
        # probe by *executing* once: backends can advertise a memory kind
        # (addressable_memories) yet reject collectives on it
        try:
            xh = comm.shard(x, memory_kind)
            step_h = comm.jit_allreduce(xh, algorithm)
            import jax

            jax.block_until_ready(step_h(xh))
            x, step = xh, step_h
        except Exception as e:  # noqa: BLE001 — any backend rejection falls back
            if comm.mesh.devices.flat[0].platform == "tpu":
                raise  # the TPU has the kind: a rejection is a failure
            log.print(
                f"note: memory kind {memory_kind!r} unsupported here "
                f"({type(e).__name__}); using device"
            )
            memory_kind = None
    if kind_cache is not None:
        kind_cache["kind"] = memory_kind

    result = measure(
        blocking(step, x), repetitions=args.repetitions, warmup=args.warmup,
        label=f"allreduce.{algorithm}",
    )
    elapsed = max_across_processes(result.min_s)

    # per-rank validation on addressable shards only: in a multi-process
    # launch (apps/launch.py) each process asserts its own ranks'
    # buffers, exactly as each MPI rank validates its own VC
    # (allreduce-mpi-sycl.cpp:192-206); the verdict is the cross-process
    # AND of the local ones (vacuously true for a process the even-trim
    # left without ranks — some other process owns every row)
    out = step(x)
    ok_local = True
    # GB-scale rows are not worth a host readback; validate those with
    # a device-side elementwise comparison reduced to a mismatch count
    # — the same oracle, readback shrunk to one scalar. Small rows keep
    # the reference's host-side loop (allreduce-mpi-sycl.cpp:192-204).
    on_device = n * traits.itemsize > 256 << 20
    if on_device:
        import jax

        jax.block_until_ready(out)
        x.delete()  # free the input: validation only reads the output
        for shard in out.addressable_shards:
            lead = shard.index[0] if shard.index else slice(0, 1)
            start = lead.start or 0
            for i in range(shard.data.shape[0]):
                r = start + i
                bad = _device_mismatches(
                    shard.data, i, comm.expected_allreduce_value(), traits
                )
                log.print(f"Passed {r}" if bad == 0 else
                          f"rank {r}: {bad}/{n} elements wrong "
                          "(device-side oracle)")
                ok_local &= bad == 0
    else:
        for r, row in common.local_rows(out):
            v = correctness_verdict(np.asarray(row),
                                    comm.expected_allreduce_value(),
                                    dtype=traits.dtype, rank=r)
            log.print(f"Passed {r}" if v.success else v.messages[0])
            ok_local &= v.success
    ok = common.all_processes_agree(ok_local)
    verdict = Verdict(success=ok, messages=("SUCCESS" if ok else "FAILURE",))

    nbytes = n * traits.itemsize
    busbw = common.allreduce_bus_bandwidth_gbps(nbytes, elapsed, world)
    record_collective_bandwidth(f"allreduce.{algorithm}", nbytes, elapsed,
                                busbw_gbps=busbw)
    log.result(
        f"allreduce[{algorithm}]",
        verdict,
        world=world,
        elements=n,
        dtype=traits.dtype.name,
        bytes_per_rank=nbytes,
        elapsed_s=elapsed,
        algbw_gbps=nbytes / elapsed / 1e9 if elapsed > 0 else float("inf"),
        busbw_gbps=busbw,
        memory_kind=memory_kind or "device",
    )
    log.print(
        f"{algorithm} world={world} n=2^{log2_elements} {traits.dtype.name}: "
        f"{elapsed * 1e3:.3f} ms, busbw {busbw:.2f} GB/s"
    )
    log.print(verdict.summary_line())
    return verdict.exit_code


def main(argv=None) -> int:
    return common.run_instrumented(run, build_parser().parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
