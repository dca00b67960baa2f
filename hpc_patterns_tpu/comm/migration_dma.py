"""Device-side KV migration: the serving plane's handoff as a paired
remote-DMA kernel on the fused tier.

The plane's other two transports stage the bundle through the host —
``migrate_pages`` is a cross-device ``device_put`` (XLA picks the
route), the launched plane ships base64 over TCP. This module moves
the handoff *into* a Pallas kernel: one SPMD ``pallas_call`` over a
2-device mesh ``[src, dst]`` in which the source rank
``make_async_remote_copy``-s the bundle's KV pages (and scale pools,
when the cache is quantized) chunk-by-chunk straight into the
destination rank's output buffer — the GPU-initiated-communication
direction (Intel SHMEM, arXiv 2409.20476; stream-aware MPI, arXiv
2306.15773) applied to the TPU's ICI. Byte-exactness is the plane's
existing migration oracle: prefill→migrate→decode equals the colocated
engine, greedy and sampled, at every pool dtype.

Slot discipline (the pallaslint ledger audits this file like the ring
kernels): every page chunk gets a DEDICATED send/recv semaphore pair
(no alternating-buffer hazard — each chunk reads a distinct input
slice and lands in a distinct output slice), all recvs are awaited
before the first send-wait, and every DMA's send semaphore is drained
before the kernel returns, so no transfer outlives its scratch.

Symmetry note: both ranks run the same program, so the destination
issues the mirror-image copy back into the source's buffer. That
back-copy is the source's own payload (the kernel is an exchange), is
byte-inert, and keeps the kernel a single SPMD program — the form the
dma-discharge interpreter and Mosaic's collective matcher both accept.

On the chip the exchange kernel does not lower yet (jax 0.9.0 refuses a
``collective_id`` without a barrier-semaphore handshake — ROADMAP.md
Speed 8); that ``ValueError`` is not a :class:`MigrationDmaError`, so
the router's fallback ladder does not swallow it.

Entry points mirror the socket plane's (``serving_plane/service.py``):
:func:`send_migration` runs on the dispatch side and returns the
bundle re-homed to the destination device with ``transport="dma"``;
:func:`recv_migration` is the install-side acceptance check. Both are
dispatch-critical under jaxlint's host-sync rule — neither reads a
device value back.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hpc_patterns_tpu.ops.tiling import (
    collective_id as _registered_collective_id,
    resolve_interpret,
)

#: the transient 2-device mesh axis the send/recv pair binds
MIGRATION_AXIS = "_mig"

#: pages per DMA chunk: small enough that a chunk's landing overlaps
#: the next chunk's issue, large enough to amortize descriptor cost
PAGE_CHUNK = 4

#: compiled-path VMEM budget: input payload slab + the same-shape
#: output buffer live in VMEM simultaneously (2x the payload), which
#: :func:`dma_reachable`'s byte gate keeps under this cap — benchmark
#: pool shapes are ~MBs (pallaslint's estimator prices the same 2x)
_VMEM_LIMIT = 64 * 1024 * 1024


class MigrationDmaError(RuntimeError):
    """The DMA transport cannot serve this (src, dst, payload) — the
    router's loud-fallback ladder catches exactly this type and drops
    to ``device_put`` (then wire)."""


def dma_reachable(src_device, dst_device) -> tuple[bool, str]:
    """(ok, reason): can the paired kernel run between these two
    devices? Needs two DISTINCT committed devices on one platform —
    device-less (host-shared) replicas and cross-platform pairs fall
    back. A True verdict still leaves the per-bundle VMEM byte gate in
    :func:`send_migration`."""
    if src_device is None or dst_device is None:
        return False, "replica has no committed device (host-shared)"
    if src_device == dst_device:
        return False, "src and dst share one device (colocated)"
    if src_device.platform != dst_device.platform:
        return (False, f"cross-platform pair "
                f"({src_device.platform} -> {dst_device.platform})")
    return True, ""


# one compiled exchange per (devices, shape, dtype, chunking, mode):
# migrations repeat the same pool geometry every round, so the plane
# pays one trace per payload shape, not one per bundle
_XFER_CACHE: dict = {}


def _exchange_fn(src_device, dst_device, n_pages: int, row: int,
                 dtype, page_chunk: int, interpret: bool):
    key = (src_device.id, dst_device.id, n_pages, row, str(dtype),
           page_chunk, interpret)
    hit = _XFER_CACHE.get(key)
    if hit is not None:
        return hit
    chunks = -(-n_pages // page_chunk)
    mesh = Mesh(np.asarray([src_device, dst_device]), (MIGRATION_AXIS,))
    cid = _registered_collective_id("comm.fused.migration")

    def kernel(x_ref, o_ref, send_sem, recv_sem):
        me = lax.axis_index(MIGRATION_AXIS)
        dst = lax.rem(me + 1, 2)
        dmas = []
        for c in range(chunks):
            lo = c * page_chunk
            span = min(page_chunk, n_pages - lo)
            d = pltpu.make_async_remote_copy(
                src_ref=x_ref.at[pl.ds(lo, span)],
                dst_ref=o_ref.at[pl.ds(lo, span)],
                send_sem=send_sem.at[c], recv_sem=recv_sem.at[c],
                device_id=dst,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            d.start()
            dmas.append(d)
        for d in dmas:
            d.wait_recv()
        for d in dmas:
            d.wait_send()

    def local(l):
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n_pages, row), dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.SemaphoreType.DMA((chunks,)),
                            pltpu.SemaphoreType.DMA((chunks,))],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True, collective_id=cid,
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(l[0])
        return out[None]

    spec = P(MIGRATION_AXIS, None, None)
    fn = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=spec, out_specs=spec,
        check_vma=False,  # the Pallas interpreter's internals carry no vma
    ))
    sharding = NamedSharding(mesh, spec)
    _XFER_CACHE[key] = (fn, sharding)
    return fn, sharding


def _transfer_array(arr, src_device, dst_device, *, page_chunk: int,
                    interpret: bool):
    """One payload array (leading dim = pages) DMA'd src -> dst;
    returns the destination-committed copy with the original shape."""
    shape = arr.shape
    n_pages = int(shape[0])
    row = int(np.prod(shape[1:], dtype=np.int64)) if len(shape) > 1 else 1
    if n_pages == 0 or row == 0:
        return jax.device_put(arr, dst_device)
    if 2 * arr.nbytes > _VMEM_LIMIT:
        raise MigrationDmaError(
            f"payload slab {arr.nbytes} B needs "
            f"{2 * arr.nbytes} B VMEM (> {_VMEM_LIMIT} B budget)")
    fn, sharding = _exchange_fn(src_device, dst_device, n_pages, row,
                                arr.dtype, page_chunk, interpret)
    x = jnp.reshape(arr, (n_pages, row))
    # both ranks hold a same-shape slab: the source's is the payload,
    # the destination's is the (overwritten) landing buffer
    x2 = jax.device_put(jnp.stack([x, jnp.zeros_like(x)]), sharding)
    out = fn(x2)
    shard = [s.data for s in out.addressable_shards
             if s.device == dst_device][0]
    return jnp.reshape(shard, shape)


def send_migration(bundle, src_device, dst_device, *,
                   page_chunk: int = PAGE_CHUNK,
                   interpret: bool | None = None):
    """DMA every payload array of ``bundle`` (K/V pools and, when the
    cache is quantized, their scale pools — whatever keys
    ``export_migration`` gathered) from ``src_device`` to
    ``dst_device`` through the paired kernel, and return the bundle
    re-homed there with ``transport="dma"``. Raises
    :class:`MigrationDmaError` when the pair is not DMA-reachable or a
    slab exceeds the VMEM budget — the router's fallback ladder."""
    ok, reason = dma_reachable(src_device, dst_device)
    if not ok:
        raise MigrationDmaError(f"not DMA-reachable: {reason}")
    interpret = resolve_interpret(interpret, "migration_dma")
    payload = {
        name: tuple(
            _transfer_array(a, src_device, dst_device,
                            page_chunk=page_chunk, interpret=interpret)
            for a in arrs)
        for name, arrs in bundle.pages_payload.items()
    }
    return replace(bundle, pages_payload=payload, transport="dma")


def recv_migration(bundle, device):
    """Install-side acceptance check (the socket plane's
    ``recv_migration`` analog): the bundle must have arrived over the
    DMA transport with every payload array already committed to the
    installing replica's device — device METADATA checks only, no
    readback (this runs inside the decode replica's dispatch path)."""
    if bundle.transport != "dma":
        raise MigrationDmaError(
            f"bundle seq {bundle.seq} arrived with "
            f"transport={bundle.transport!r}, expected 'dma'")
    if device is None:
        raise MigrationDmaError(
            "installing replica has no committed device")
    for name, arrs in bundle.pages_payload.items():
        for i, a in enumerate(arrs):
            devs = getattr(a, "devices", None)
            if devs is None or device not in a.devices():
                raise MigrationDmaError(
                    f"payload {name}[{i}] of bundle seq {bundle.seq} "
                    f"not resident on installing device {device}")
    return bundle
