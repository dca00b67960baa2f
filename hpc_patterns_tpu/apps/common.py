"""Shared app scaffolding: device/mesh resolution and reporting units.

The reference duplicates this in every main() (device pick at
allreduce-mpi-sycl.cpp:135-152, world-size guard at :95-97, reporting at
:185-206); apps here share one implementation.
"""

from __future__ import annotations

import os
from typing import Callable

import jax

from hpc_patterns_tpu import topology
from hpc_patterns_tpu.comm import Communicator


def device_header() -> dict:
    """What this process runs on, as jax reports it: versions,
    ``platform``, ``device_kind`` and device count of the default
    backend. Every app prints it as its first line and writes it as its
    first ``--log`` record, so no result line is read without its
    device."""
    import jaxlib

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    devices = jax.devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def refuse_backend(args, log, devices=None) -> bool:
    """The shared ``--backend`` check every app's ``run()`` makes:
    ``--backend X`` given and the devices the app will use are not
    platform X -> ``ERROR``/``FAILURE`` (True: the caller returns 1).
    ``devices``: the ones the app placed explicitly; None for an app
    that runs on jax's default device. Asking for the chip and not
    getting it is an error, never a quiet run somewhere else."""
    backend = getattr(args, "backend", None)
    if not backend:
        return False
    if devices is None:
        devices = jax.devices()[:1]
    got = sorted({d.platform for d in devices})
    if got and all(p.startswith(backend) for p in got):
        return False
    log.print(f"ERROR: --backend {backend} was asked for, but this run "
              f"would use {got or 'no'} devices")
    log.print("FAILURE")
    return True


def run_instrumented(run_fn: Callable[[object], int], args, *,
                     join_rendezvous: bool = True) -> int:
    """The shared session every app main() runs through.

    Before the app: place the persistent compile cache
    (``compile_cache.enable``), join a launcher rendezvous when one is
    in the environment (apps/launch.py ≙ mpirun; init is the MPI_Init
    analog and must precede the first device query —
    ``join_rendezvous=False`` for an app whose ranks talk over their
    own sockets), print the device header (:func:`device_header`) and,
    with ``--log``, start the log with it as a ``kind=device`` record
    (the app's own ``RunLog`` then appends).

    Around the app: install a fresh process-wide metrics registry AND
    flight recorder from ``--metrics``/``--trace`` (both no-ops without
    their flag — the disabled fast path). A device discovery that finds
    nothing of the platform asked for (``topology.TopologyError``) ends
    the run with the apps' ``ERROR``/``FAILURE`` protocol, exit 1.

    After the app, on ANY exit path: print which mode every Pallas
    kernel was traced in and append the closing records to ``--log`` —
    one ``kind=kernels`` (``ops.tiling.kernel_modes``: compiled vs
    interpreted, per kernel), one ``kind=metrics`` (aggregated by
    `python -m hpc_patterns_tpu.harness.report`) and one ``kind=trace``
    (exported to Chrome-trace JSON by `python -m
    hpc_patterns_tpu.harness.trace`). Appending (never truncating)
    keeps the app's own records: the snapshots are the log's closing
    records, like run.sh's trailing grep summary.

    Distributed handoff: a traced run under apps/launch.py additionally
    writes its recorder snapshot to the launcher-provided
    ``HPCPAT_TRACE_DIR`` as ``rank<id>.trace.json`` (independent of
    ``--log`` — the launcher, not the child, owns the merged artifact),
    where the launcher collects every rank's ring for the clock-aligned
    merge (harness/collect.py)."""
    from hpc_patterns_tpu import compile_cache
    from hpc_patterns_tpu.harness import metrics, trace
    from hpc_patterns_tpu.harness.runlog import RunLog
    from hpc_patterns_tpu.ops import tiling

    compile_cache.enable()
    if join_rendezvous:
        topology.init_distributed_from_env()
    header = device_header()
    print("device: " + " ".join(f"{k}={v}" for k, v in header.items()),
          flush=True)
    log_path = getattr(args, "log", None)
    if log_path:
        RunLog(log_path, truncate=not getattr(args, "log_append", False)
               ).emit(kind="device", **header)
        args.log_append = True  # the app appends below the header

    # mirror_traces stays off here: profiling.maybe_trace toggles it
    # (and restores it) around the actual traced region, so spans only
    # pay for TraceAnnotation while a trace is live
    m = metrics.configure(enabled=getattr(args, "metrics", False))
    trace_kw = {}
    if getattr(args, "trace_capacity", None):
        trace_kw["capacity"] = args.trace_capacity
    rec = trace.configure(enabled=getattr(args, "trace", False),
                          **trace_kw)
    try:
        return run_fn(args)
    except topology.TopologyError as e:
        print(f"ERROR: {e}")
        print("FAILURE")
        return 1
    finally:
        modes = tiling.kernel_modes()
        if modes:
            print("pallas kernels: " + " ".join(
                k + "=" + "+".join(mode for mode, n in counts.items() if n)
                for k, counts in modes.items()), flush=True)
        # ONE snapshot serves both sinks: the --log record and the
        # per-rank handoff file must carry identical events and clock
        # anchors (the offline re-merge from --log files and the
        # launcher's merge would otherwise disagree)
        trace_dir = os.environ.get(topology.ENV_TRACE_DIR)
        rec_snap = (rec.snapshot()
                    if rec.enabled and (log_path or trace_dir) else None)
        if log_path:
            log = RunLog(log_path, truncate=False)
            log.emit(kind="kernels", modes=modes)
            if m.enabled:
                log.emit(kind="metrics", **m.snapshot())
            if rec.enabled:
                log.emit(kind="trace", **rec_snap)
        if rec.enabled and trace_dir:
            trace.write_rank_snapshot(rec, trace_dir, snapshot=rec_snap)


def _trace_recorder():
    """The active flight recorder, or None — lazy so apps that never
    enable tracing don't pay the harness import here."""
    from hpc_patterns_tpu.harness import trace as tracelib

    return tracelib.active()


def make_communicator(
    backend: str | None, world: int, *, even: bool = False, axis: str = "x"
) -> Communicator:
    """Build the app's communicator: all (or ``world``) devices of the
    chosen backend on a 1-D mesh.

    ``world=-1`` (auto) uses every device — the miniapps' mpirun -np
    choice made explicit. ``even=True`` reproduces the reference's
    even-rank-count precondition (allreduce-mpi-sycl.cpp:95-97) by
    dropping the odd device out, rather than failing, because a 1-chip
    dev box is the common case here.

    Joins a launcher rendezvous first when one is in the environment
    (apps/launch.py ≙ mpirun; init is the MPI_Init analog), so the
    device list is the GLOBAL multi-process view. A traced
    multi-process run then records a sync anchor off a global barrier
    (all ranks exit within the release-propagation window), which the
    cross-rank merge uses to align per-rank clocks tighter than wall
    time — every rank runs the same command line, so either all ranks
    reach the barrier or none does (the SPMD invariant).
    """
    topology.init_distributed_from_env()
    rec = _trace_recorder()
    if rec is not None and jax.process_count() > 1:
        # barrier = a cross-process allgather: no process receives the
        # gathered value before every process contributed, so the
        # returns cluster inside the release-propagation window. The
        # same primitive reduce_across_processes uses.
        import numpy as np
        from jax.experimental import multihost_utils

        multihost_utils.process_allgather(np.float64(0.0))
        rec.mark_sync("make_communicator")
    devices = topology.get_devices(backend)
    if world == -1:
        world = len(devices)
    if world > len(devices):
        raise topology.TopologyError(
            f"world {world} > {len(devices)} visible devices"
        )
    if even and world % 2 and world > 1:
        world -= 1
    mesh = topology.make_mesh({axis: world}, devices[:world])
    return Communicator(mesh, axis)


def device_placement(*trees) -> list[dict]:
    """Where the work actually sits: per addressable device, the bytes
    of each tree's shards it holds and the allocator's
    ``bytes_in_use`` — what shows that a mesh run is spread over its
    devices rather than parked on one. One entry per device that holds
    any shard, ``shard_bytes`` in ``trees`` order."""
    held: dict = {}
    for i, tree in enumerate(trees):
        for leaf in jax.tree.leaves(tree):
            for shard in leaf.addressable_shards:
                per_tree = held.setdefault(shard.device, [0] * len(trees))
                per_tree[i] += shard.data.nbytes
    return [
        {"device": d.id, "shard_bytes": per_tree,
         "bytes_in_use": (d.memory_stats() or {}).get("bytes_in_use")}
        for d, per_tree in sorted(held.items(), key=lambda kv: kv[0].id)
    ]


def allreduce_bus_bandwidth_gbps(nbytes: int, seconds: float, world: int) -> float:
    """Bus bandwidth for an allreduce: algbw · 2(size−1)/size.

    The standard ring-limit normalization, so numbers are comparable
    across world sizes — the BASELINE.json "allreduce GB/s" metric.
    Degenerates to 0 for world=1 (no wire traffic).
    """
    if seconds <= 0:
        return float("inf")
    return (nbytes / seconds / 1e9) * (2 * (world - 1) / world)


def local_rows(global_array) -> list[tuple[int, "jax.Array"]]:
    """(rank, row) pairs this process can address, for a (size, ...) array
    sharded one row per rank. In multi-process runs each process
    validates only its own ranks' buffers — exactly the reference's
    per-rank validation (allreduce-mpi-sycl.cpp:192-206); single-process
    it is every row."""
    rows = []
    for shard in global_array.addressable_shards:
        lead = shard.index[0] if shard.index else slice(0, 1)
        start = lead.start or 0
        data = shard.data
        for i in range(data.shape[0]):
            rows.append((start + i, data[i]))
    return sorted(rows, key=lambda rv: rv[0])


def reduce_across_processes(value: float, op=None) -> float:
    """Reduce a host scalar across processes (default max — the
    reference's MPI_Allreduce(MAX) timing convention). Single-process:
    identity. The one allgather-and-reduce implementation shared by the
    app verdicts; harness.timing.max_across_processes is its
    harness-layer twin."""
    import numpy as np

    if jax.process_count() == 1:
        return float(value)
    from jax.experimental import multihost_utils

    op = np.max if op is None else op
    return float(op(multihost_utils.process_allgather(np.float64(value))))


def all_processes_agree(ok: bool) -> bool:
    """Cross-process AND of a local verdict (the reference MAX-reduces
    times and each rank asserts its own buffer; a distributed SUCCESS
    needs every rank's assert to hold). Single-process: identity."""
    return reduce_across_processes(0.0 if ok else 1.0) == 0.0


def supports_memory_kind(kind: str) -> bool:
    """Whether the backend exposes the given JAX memory kind (TPU has
    pinned_host + device; CPU meshes typically only the default).
    Delegates to the single probe home (memory/kinds.py)."""
    from hpc_patterns_tpu.memory import kinds as kindslib

    return kindslib.supports_memory_kind(kind)
