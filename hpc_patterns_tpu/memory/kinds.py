"""Memory-kind probes and shardings — THE single source of truth.

Before round 11 three modules each carried their own copy of this
knowledge: ``concurrency/commands.py`` probed whether host<->device
memory-kind transfers actually execute (``_memory_kind_transfers_work``
/ ``_kind_sharding``), ``models/train.py`` retargeted tree shardings to
a kind (``memory_kind_shardings``), and ``apps/common.py`` answered the
advertise-level question (``supports_memory_kind``). Three copies of
"does this backend really have a host tier?" is how the
``offload_opt_state`` gap happened (an unsupported backend paid the
``device_put`` for no benefit) — so the helpers live HERE and the old
call sites delegate.

Three distinct questions, three probes — backends genuinely differ at
each level (this container's CPU exposes ``unpinned_host`` only; other
XLA:CPU builds advertise ``pinned_host`` yet reject the jitted
transfer at runtime):

- :func:`supports_memory_kind` — is the kind ADVERTISED in
  ``addressable_memories()``? (cheap; placement may still fail)
- :func:`memory_kind_placement_works` — does ``jax.device_put`` into
  the kind actually succeed? (what :func:`~hpc_patterns_tpu.models.
  train.offload_opt_state` needs)
- :func:`memory_kind_transfers_work` — does the full jitted
  host<->device round trip execute? (what the concurrency copy
  commands and the residency manager's pinned-host tier need)

Each probe runs once per (platform, kind) and is memoized; the probe
executes the SAME cached transfer program (:func:`move_to_kind`) the
real transfer paths dispatch, so it proves the executable that ships.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def kind_sharding(device, kind: str):
    """Single-device sharding targeting a JAX memory kind — the
    allocator axis as a placement (SURVEY.md §2, ``-H/-D``)."""
    return jax.sharding.SingleDeviceSharding(device, memory_kind=kind)


def memory_kind_shardings(tree, kind: str):
    """Shardings of ``tree``'s (concrete) leaves retargeted to a JAX
    memory kind — the L2 allocator axis applied to a whole state tree
    (training opt state, a gathered KV payload)."""
    return jax.tree.map(lambda x: x.sharding.with_memory_kind(kind), tree)


_MOVE_CACHE: dict[tuple, object] = {}


def move_to_kind(device, kind: str):
    """Cached jitted transfer program targeting ``kind`` on ``device``
    — every copy of the same direction shares one compile (the
    concurrency autotuner alone builds several probe commands per
    run, and the residency manager moves many blocks per round)."""
    key = (device, kind)
    if key not in _MOVE_CACHE:
        _MOVE_CACHE[key] = jax.jit(
            lambda x: x, out_shardings=kind_sharding(device, kind)
        )
    return _MOVE_CACHE[key]


def supports_memory_kind(kind: str, device=None) -> bool:
    """Whether the backend ADVERTISES the given memory kind (TPU has
    pinned_host + device; CPU meshes typically only the default).
    Advertise-level only — placement can still fail; see
    :func:`memory_kind_placement_works`."""
    try:
        device = device if device is not None else jax.devices()[0]
        memories = device.addressable_memories()
    except Exception:
        return False
    return any(m.kind == kind for m in memories)


_PLACEMENT_PROBE: dict[tuple[str, str], bool] = {}


def memory_kind_placement_works(device=None,
                                kind: str = "pinned_host") -> bool:
    """Whether ``jax.device_put`` INTO ``kind`` succeeds on this
    backend — the gate for one-way offloads (``offload_opt_state``):
    a backend that rejects the placement must return the input
    unchanged instead of paying a doomed transfer. Memoized per
    (platform, kind). The TPU has the kind: a rejection there is an
    error and propagates."""
    device = device if device is not None else jax.devices()[0]
    key = (device.platform, kind)
    if key not in _PLACEMENT_PROBE:
        try:
            if not supports_memory_kind(kind, device):
                raise ValueError(f"no {kind} memory")
            tiny = jax.device_put(jnp.zeros((8,), jnp.float32),
                                  kind_sharding(device, kind))
            jax.block_until_ready(tiny)
            _PLACEMENT_PROBE[key] = True
        except Exception:
            if device.platform == "tpu":
                raise
            _PLACEMENT_PROBE[key] = False
    return _PLACEMENT_PROBE[key]


_TRANSFER_PROBE: dict[str, bool] = {}


def memory_kind_transfers_work(device=None) -> bool:
    """Whether host<->device memory-kind transfers actually *execute*
    on this backend. Backends can advertise ``pinned_host`` in
    ``addressable_memories`` yet reject placement or the jitted
    transfer at runtime (XLA:CPU builds have done both), so probe by
    running one tiny round trip, memoized per platform. The probe
    executes the SAME cached transfer program real copy commands and
    residency-manager pulls use (a fresh ``jax.jit`` here would
    re-trace on every probe — jaxlint: recompile-hazard — and prove a
    different executable than the one that ships). On the TPU a
    failed round trip is an error and propagates."""
    device = device if device is not None else jax.devices()[0]
    key = device.platform
    if key not in _TRANSFER_PROBE:
        try:
            if not supports_memory_kind("pinned_host", device):
                raise ValueError("no pinned_host memory")
            tiny = jax.device_put(jnp.zeros((8,), jnp.float32),
                                  kind_sharding(device, "pinned_host"))
            moved = move_to_kind(device, "device")(tiny)
            jax.block_until_ready(moved)
            _TRANSFER_PROBE[key] = True
        except Exception:
            if device.platform == "tpu":
                raise
            _TRANSFER_PROBE[key] = False
    return _TRANSFER_PROBE[key]
