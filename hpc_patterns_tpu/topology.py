"""Device discovery and topology — TPU-native analog of the reference's
``aurora.mpich.miniapps/src/include/devices.hpp`` (C8 in SURVEY.md).

The reference provides (devices.hpp:6-59):
- platform-prefix device lookup (``get_devices(target)``, devices.hpp:6-13)
- "device fission": partition each GPU into NUMA tiles via
  ``create_sub_devices<partition_by_affinity_domain>`` with whole-GPU
  fallback (devices.hpp:28-38)
- rank->device mapping: modulo round-robin when ranks > devices
  (devices.hpp:47), contiguous block split when devices >= ranks
  (devices.hpp:49-53)

TPU-native equivalents here:
- device lookup over ``jax.devices()`` filtered by platform
- "fission" = the chip -> core topology JAX already exposes (each TPU core
  is a device), plus grouping helpers by host/slice so meshes can be laid
  out so collectives ride ICI, not DCN
- the same two rank->device policies, reused for mesh construction
- :func:`make_mesh` — the central entry point: build a
  ``jax.sharding.Mesh`` with named axes (dp/sp/tp/...) over the devices,
  the TPU analog of MPI communicators.
"""

from __future__ import annotations

import dataclasses
import math
import os
import warnings
from collections import defaultdict
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


class TopologyError(RuntimeError):
    """Raised when no usable device topology exists.

    Analog of the reference's fail-fast no-device error
    (allreduce-mpi-sycl.cpp:137-141).
    """


def get_devices(platform: str | None = None) -> list[jax.Device]:
    """All addressable devices, optionally filtered by platform prefix.

    Analog of ``get_devices(target)`` (devices.hpp:6-13), which filters
    SYCL platforms by name prefix; here the "platform" is the JAX backend
    name ("tpu", "cpu", "gpu").
    """
    devices = list(jax.devices())
    if platform is not None:
        devices = [d for d in devices if d.platform.startswith(platform)]
    if not devices:
        raise TopologyError(
            f"no devices for platform prefix {platform!r}; "
            f"available: {sorted({d.platform for d in jax.devices()})}"
        )
    return devices


@dataclasses.dataclass(frozen=True)
class CoreInfo:
    """Chip/core facts for one device — the TPU analog of the
    reference's sub-device (NUMA-tile) introspection (devices.hpp:29-38).

    ``num_cores`` > 1 with one device = a megacore chip (v4/v5p: two
    cores fused behind one device — XLA schedules across them; no
    finer software partition exists). Multiple devices sharing
    ``coords`` = per-core devices of one chip (v2/v3)."""

    device: jax.Device
    kind: str
    coords: tuple | None  # chip position in the slice, if exposed
    core_on_chip: int | None
    num_cores: int  # cores fused behind this device (1 = plain core)

    @property
    def megacore(self) -> bool:
        return self.num_cores > 1

    @classmethod
    def of(cls, d: jax.Device) -> "CoreInfo":
        coords = getattr(d, "coords", None)
        return cls(
            device=d,
            kind=getattr(d, "device_kind", d.platform),
            coords=tuple(coords) if coords is not None else None,
            core_on_chip=getattr(d, "core_on_chip", None),
            num_cores=int(getattr(d, "num_cores", 1) or 1),
        )


def core_topology(
    devices: Sequence[jax.Device] | None = None,
) -> list[CoreInfo]:
    """Per-device chip/core introspection (see :class:`CoreInfo`)."""
    if devices is None:
        devices = get_devices()
    return [CoreInfo.of(d) for d in devices]


def group_by_chip(
    devices: Sequence[jax.Device] | None = None,
) -> dict[tuple, list[jax.Device]]:
    """Group devices by physical chip: devices sharing (process, coords)
    are cores of one chip (v2/v3 style); one device per key means the
    chip IS the finest unit (v5e) or a fused megacore (v4/v5p)."""
    if devices is None:
        devices = get_devices()
    groups: dict[tuple, list[jax.Device]] = defaultdict(list)
    for d in devices:
        coords = getattr(d, "coords", None)
        key = (
            (d.process_index, tuple(coords))
            if coords is not None
            else (d.process_index, ("dev", d.id))
        )
        groups[key].append(d)
    return dict(groups)


def fission(devices: Sequence[jax.Device] | None = None) -> list[jax.Device]:
    """Expose the finest-grained compute units as devices.

    The reference's fission splits each GPU into NUMA tiles, falling back
    to whole GPUs when sub-devices are unsupported (devices.hpp:28-38).
    On TPU, JAX already enumerates the finest software-visible unit
    (v2/v3: one device per core, grouped by chip via
    :func:`group_by_chip`; v5e: one core per chip; v4/v5p: a megacore
    chip is ONE device — XLA schedules across the fused cores and no
    finer partition exists, which :func:`core_topology` reports as
    ``megacore=True``/``num_cores=2``). So this returns the devices
    as-is — the reference's whole-GPU fallback semantics — with the
    sub-device structure available from the introspection helpers.
    It never fails.
    """
    if devices is None:
        devices = get_devices()
    return list(devices)


def assign_device(rank: int, size: int, devices: Sequence[jax.Device]) -> jax.Device:
    """Map an SPMD rank to a device with the reference's two policies.

    - ranks > devices: modulo round-robin — ``rank % n`` (devices.hpp:47)
    - devices >= ranks: contiguous block split, rank r owns block
      ``[r * n//size, (r+1) * n//size)`` and uses its first device
      (devices.hpp:49-53)
    """
    if size <= 0 or rank < 0 or rank >= size:
        raise ValueError(f"bad rank/size: {rank}/{size}")
    n = len(devices)
    if n == 0:
        raise TopologyError("no devices to assign")
    if size > n:
        return devices[rank % n]
    block = n // size
    return devices[rank * block]


def devices_for_rank(rank: int, size: int, devices: Sequence[jax.Device]) -> list[jax.Device]:
    """The full device block owned by ``rank`` under the block policy."""
    if size <= 0 or rank < 0 or rank >= size:
        raise ValueError(f"bad rank/size: {rank}/{size}")
    n = len(devices)
    if size > n:
        return [devices[rank % n]]
    block = n // size
    return list(devices[rank * block : (rank + 1) * block])


def group_by_host(devices: Sequence[jax.Device] | None = None) -> dict[int, list[jax.Device]]:
    """Group devices by owning process/host (ICI domain approximation).

    Within one host/slice, collectives ride ICI; across hosts they may
    cross DCN. Mesh layouts should put fast axes (tp/sp) inside a group.
    """
    if devices is None:
        devices = get_devices()
    groups: dict[int, list[jax.Device]] = defaultdict(list)
    for d in devices:
        groups[d.process_index].append(d)
    return dict(groups)


# Slice-topology override for environments without real multi-slice
# hardware (HPCPAT_SLICE_GROUPING): "process" treats each OS process as
# one slice (apps/launch.py sets it so a -np N launch IS an N-slice
# system and the DCN-axis collectives cross real process boundaries);
# "process:a,b,..." maps process id -> slice id (several processes per
# slice); "devices:K" groups by device id in runs of K (single-process
# synthetic slices for tests). Every process computes the same grouping
# from the same env value — the SPMD invariant group_by_slice must keep.
ENV_SLICE_GROUPING = "HPCPAT_SLICE_GROUPING"


def _slice_id_fn():
    spec = os.environ.get(ENV_SLICE_GROUPING)
    if not spec:
        return lambda d: getattr(d, "slice_index", 0)
    kind, _, arg = spec.partition(":")
    if kind == "process":
        if not arg:
            return lambda d: d.process_index
        try:
            mapping = [int(s) for s in arg.split(",")]
        except ValueError as e:
            raise TopologyError(
                f"{ENV_SLICE_GROUPING}={spec!r}: 'process:map' wants "
                "comma-separated integers"
            ) from e

        def by_process(d):
            if d.process_index >= len(mapping):
                raise TopologyError(
                    f"{ENV_SLICE_GROUPING}={spec!r} maps "
                    f"{len(mapping)} processes; device {d} is from "
                    f"process {d.process_index}"
                )
            return mapping[d.process_index]

        return by_process
    if kind == "devices":
        try:
            k = int(arg)
        except ValueError:
            k = 0
        if k < 1:
            raise TopologyError(
                f"{ENV_SLICE_GROUPING}={spec!r}: 'devices:K' needs a "
                "positive integer K"
            )
        return lambda d: d.id // k
    raise TopologyError(
        f"{ENV_SLICE_GROUPING}={spec!r}: want 'process[:map]' or "
        "'devices:K'"
    )


def group_by_slice(devices: Sequence[jax.Device] | None = None) -> dict[int, list[jax.Device]]:
    """Group devices by TPU slice (multi-slice = DCN between groups).
    ``HPCPAT_SLICE_GROUPING`` overrides the hardware ``slice_index`` —
    see :data:`ENV_SLICE_GROUPING`."""
    if devices is None:
        devices = get_devices()
    slice_id = _slice_id_fn()
    groups: dict[int, list[jax.Device]] = defaultdict(list)
    for d in devices:
        groups[slice_id(d)].append(d)
    return dict(groups)


def is_multihost() -> bool:
    return jax.process_count() > 1


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Multi-host runtime init — the ``MPI_Init`` analog (SURVEY.md §2.3:
    ``jax.distributed.initialize`` replaces MPI_Init, mesh axes replace
    communicators).

    On TPU pods the arguments come from the environment automatically;
    explicit args cover CPU/GPU clusters (coordinator address ≙ the
    mpirun rendezvous). Idempotent: returns False when already
    initialized or single-process (the reference's guard style,
    allreduce-mpi-sycl.cpp:91-97), True when initialization happened.
    """
    explicit = any(
        a is not None for a in (coordinator_address, num_processes, process_id)
    )
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        return True
    except (RuntimeError, ValueError):
        if explicit:
            # the operator asked for a specific rendezvous: a failure is
            # a real failure (N silent single-host copies otherwise)
            raise
        # nothing discoverable from the environment — the common
        # dev-box case; callers proceed single-host
        return False


def cpu_worker_env(base: Mapping[str, str], n_devices: int) -> dict:
    """Environment for a child process that must run as a CPU SPMD worker
    with ``n_devices`` virtual devices instead of attaching real TPU
    hardware. The single source of truth for the CPU-forcing recipe,
    shared by apps/launch.py (the mpirun -np analog) and the
    self-bootstrapping multi-chip dry run (__graft_entry__).
    """
    env = dict(base)
    env["JAX_PLATFORMS"] = "cpu"
    # cross-process CPU computations need a collectives backend: jaxlib
    # builds default to none and then reject multi-process executables
    # outright ("Multiprocess computations aren't implemented on the
    # CPU backend"), so a worker that exists to be one rank of many
    # must ask for gloo. setdefault: an operator's explicit choice
    # (e.g. "mpi") wins.
    env.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
    # override (not inherit) any existing device-count flag — e.g. the
    # test conftest's 8 — so n_devices is what it says
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def pump_lines(prefix: str, stream, sink) -> None:
    """Echo ``stream`` to ``sink`` line by line (with ``prefix``) until
    EOF, flushing each line — the output pump for child SPMD workers,
    shared by apps/launch.py and the self-bootstrapping dry run so
    progress is visible while a child compiles."""
    for line in iter(stream.readline, ""):
        sink.write(f"{prefix}{line}")
        sink.flush()
    stream.close()


# env rendezvous protocol set by apps/launch.py (the local mpirun -np
# analog); one process per "host", CPU devices standing in for chips
ENV_COORDINATOR = "HPCPAT_COORDINATOR"
ENV_NUM_PROCESSES = "HPCPAT_NUM_PROCESSES"
ENV_PROCESS_ID = "HPCPAT_PROCESS_ID"
# per-rank flight-recorder handoff: when the launcher sets this to a
# directory, every traced child (--trace) writes its closing recorder
# snapshot there as rank<id>.trace.json for the launcher to collect and
# merge (harness/collect.py) — the distributed-trace file protocol
ENV_TRACE_DIR = "HPCPAT_TRACE_DIR"


def process_env_info(environ=None) -> tuple[int, int, int]:
    """``(process_id, num_processes, slice_id)`` for THIS process, from
    the launcher env protocol when present (the same variables
    :func:`init_distributed_from_env` consumes, so the answer is right
    even before jax.distributed is initialized), falling back to the
    live jax runtime, then to the single-process identity. ``slice_id``
    applies a process-keyed :data:`ENV_SLICE_GROUPING` override to the
    process id (device-keyed specs don't determine a per-process slice).

    This is what stamps flight-recorder snapshots with their rank
    (harness/trace.py), so cross-rank merges know whose timeline each
    ring is without trusting file names.
    """
    env = os.environ if environ is None else environ
    pid_s = env.get(ENV_PROCESS_ID)
    if pid_s is not None:
        pid = int(pid_s)
        nprocs = int(env.get(ENV_NUM_PROCESSES, 1))
    else:
        try:
            pid, nprocs = jax.process_index(), jax.process_count()
        except Exception:  # noqa: BLE001 — backends may not be up yet
            pid, nprocs = 0, 1
    slice_id = 0
    spec = env.get(ENV_SLICE_GROUPING)
    if spec:
        kind, _, arg = spec.partition(":")
        if kind == "process":
            if not arg:
                slice_id = pid
            else:
                try:
                    mapping = [int(s) for s in arg.split(",")]
                    if pid < len(mapping):
                        slice_id = mapping[pid]
                except ValueError:
                    pass  # malformed spec: group_by_slice raises; a
                    # telemetry stamp just falls back to slice 0
    return pid, nprocs, slice_id


def init_distributed_from_env(environ=None) -> bool:
    """Join the rendezvous described by ``HPCPAT_COORDINATOR`` /
    ``HPCPAT_NUM_PROCESSES`` / ``HPCPAT_PROCESS_ID`` (exported by
    ``apps/launch.py``, the ``mpirun -np`` analog — the reference's apps
    learn their rank the same way, from the launcher via MPI_Init).

    No-op (False) when the variables are absent or the runtime is
    already initialized; True when this call joined the rendezvous.
    Called by app scaffolding (apps/common.py) so every miniapp is
    launchable both standalone and under the launcher, like the
    reference's binaries under ctest/mpirun.
    """
    env = os.environ if environ is None else environ
    coord = env.get(ENV_COORDINATOR)
    if not coord or jax.distributed.is_initialized():
        return False
    # the launcher recipe (cpu_worker_env) requests a CPU collectives
    # backend via env, but jax flags don't read env vars — apply it
    # here, before the first device touch creates the CPU client (a
    # client built with collectives=none rejects every multi-process
    # computation outright)
    impl = env.get("JAX_CPU_COLLECTIVES_IMPLEMENTATION")
    if impl:
        jax.config.update("jax_cpu_collectives_implementation", impl)
    return init_distributed(
        coord,
        int(env[ENV_NUM_PROCESSES]),
        int(env[ENV_PROCESS_ID]),
    )


@dataclasses.dataclass(frozen=True)
class TopologyInfo:
    """A summary of the visible device topology (for logs and verdicts)."""

    platform: str
    n_devices: int
    n_hosts: int
    n_slices: int
    coords: tuple | None  # chip coords of device 0, if exposed

    @classmethod
    def detect(cls) -> "TopologyInfo":
        ds = get_devices()
        d0 = ds[0]
        return cls(
            platform=d0.platform,
            n_devices=len(ds),
            n_hosts=jax.process_count(),
            n_slices=len(group_by_slice(ds)),
            coords=getattr(d0, "coords", None),
        )


def _factor_axes(n_devices: int, axes: Mapping[str, int]) -> dict[str, int]:
    """Resolve -1 ("auto", the reference's CLI sentinel, sycl_con.cpp:179-232)
    axis sizes so the product equals ``n_devices``."""
    sizes = dict(axes)
    for k, v in sizes.items():
        if v != -1 and v < 1:
            raise TopologyError(f"axis {k!r} has invalid size {v} (use -1 for auto)")
    auto = [k for k, v in sizes.items() if v == -1]
    fixed = math.prod(v for v in sizes.values() if v != -1)
    if n_devices % fixed != 0:
        raise TopologyError(
            f"mesh axes {dict(axes)} do not divide {n_devices} devices"
        )
    rest = n_devices // fixed
    if not auto:
        if fixed != n_devices:
            raise TopologyError(
                f"mesh axes {dict(axes)} use {fixed} devices but {n_devices} exist"
            )
        return sizes
    # Give the remainder to the first auto axis, 1 to the others.
    for k in auto[1:]:
        sizes[k] = 1
    sizes[auto[0]] = rest
    return sizes


def make_mesh(
    axes: Mapping[str, int],
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a named-axis device mesh — the TPU analog of the reference's
    MPI communicator + rank->device map (devices.hpp:22-59).

    ``axes`` maps axis name -> size; a size of -1 means "auto" (fill with
    the remaining devices), mirroring the reference CLI's -1 sentinels.
    Axis order matters: later axes vary fastest over the device list, so
    put the most communication-heavy axes (tp, then sp) last to keep their
    collectives on adjacent devices (ICI, not DCN).
    """
    if devices is None:
        devices = get_devices()
    sizes = _factor_axes(len(devices), axes)
    names = tuple(sizes.keys())
    shape = tuple(sizes[k] for k in names)
    axis_types = _auto_axis_types(len(names))
    try:
        # jax picks an ICI-friendly physical layout for the axis shape
        return jax.make_mesh(shape, names, devices=tuple(devices),
                             axis_types=axis_types)
    except ValueError as e:
        # e.g. a device subset that is not a contiguous block of the
        # physical topology: the flat order still works, but axis
        # neighbors may no longer be ICI neighbors — say so
        warnings.warn(
            f"jax.make_mesh could not lay {dict(sizes)} out on "
            f"{len(devices)} devices ({e}); using the flat device order",
            stacklevel=2)
        return Mesh(np.asarray(devices).reshape(shape), names,
                    axis_types=axis_types)


def _auto_axis_types(n: int) -> tuple:
    """Auto axis types for mesh construction: the framework uses
    with_sharding_constraint / shard_map-style GSPMD, not the Explicit
    sharding-in-types mode."""
    return (jax.sharding.AxisType.Auto,) * n


def single_device_mesh(axes: Sequence[str] = ("dp",)) -> Mesh:
    """A trivial 1-device mesh so every code path also runs on one chip."""
    d = get_devices()[0]
    shape = (1,) * len(axes)
    return Mesh(np.asarray([d]).reshape(shape), tuple(axes))


def hybrid_device_layout(
    dcn_axes: Mapping[str, int],
    ici_axes: Mapping[str, int],
    devices: Sequence[jax.Device] | None = None,
) -> tuple[np.ndarray, tuple[str, ...]]:
    """Order devices for a multi-slice mesh: DCN axes vary across
    slices, ICI axes within one. Returns ``(device_array, axis_names)``
    with DCN axes leading (slowest-varying), so any sharding over an
    ICI axis touches devices of a single slice and its collectives
    ride ICI, while DCN axes (typically ``dp``) pay the slow link only
    for their own collectives — the SURVEY §2.3 ICI/DCN mapping.

    ``-1`` sizes auto-fill as in :func:`make_mesh`; the DCN product
    must equal the slice count, the ICI product the per-slice device
    count (slices must be equal-sized).
    """
    if devices is None:
        devices = get_devices()
    groups = group_by_slice(devices)
    slice_ids = sorted(groups)
    per_slice = {s: len(groups[s]) for s in slice_ids}
    if len(set(per_slice.values())) != 1:
        raise TopologyError(
            f"slices are unequal ({per_slice}); a hybrid mesh needs "
            "equal-sized slices"
        )
    n_slices = len(slice_ids)
    n_per = per_slice[slice_ids[0]]
    dcn_sizes = _factor_axes(n_slices, dcn_axes)
    ici_sizes = _factor_axes(n_per, ici_axes)
    overlap = set(dcn_sizes) & set(ici_sizes)
    if overlap:
        raise TopologyError(f"axes {sorted(overlap)} appear in both "
                            "dcn_axes and ici_axes")
    # slice-major order: row s = slice s's devices (each row is one ICI
    # domain), then fold rows into the DCN shape and columns into ICI
    arr = np.array(
        [groups[s] for s in slice_ids], dtype=object
    ).reshape(*dcn_sizes.values(), *ici_sizes.values())
    return arr, (*dcn_sizes.keys(), *ici_sizes.keys())


def make_hybrid_mesh(
    dcn_axes: Mapping[str, int],
    ici_axes: Mapping[str, int],
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Multi-slice :class:`Mesh`: DCN axes across slices, ICI axes
    within (see :func:`hybrid_device_layout`). On a single slice this
    degenerates to ``make_mesh`` with the DCN axes sized 1."""
    arr, names = hybrid_device_layout(dcn_axes, ici_axes, devices)
    return Mesh(arr, names, axis_types=_auto_axis_types(len(names)))
