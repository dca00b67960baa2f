"""Device-initiated fused ring collectives: Pallas remote-DMA kernels.

The host-driven :class:`~hpc_patterns_tpu.comm.communicator.Communicator`
paths dispatch a collective, wait for it, and only then run the
consumer — the reference repo's MPI shape. This module moves the ring
*into* the kernel: each step's neighbor transfer is a
``pltpu.make_async_remote_copy`` issued by the device itself, and the
local combine (the accumulate, the output write, the consuming matmul)
runs while the next transfer is in flight. The payoff Intel SHMEM
(arxiv 2409.20476) and DiOMP (2506.02486) measure for device-initiated
communication, on the TPU's ICI.

Every function here is **rank-local** (run inside ``shard_map``, like
:mod:`~hpc_patterns_tpu.comm.ring`); array-level entry points live on
the ``Communicator`` (``allreduce(algorithm="fused")``,
``allgather_matmul``, ``allreduce_into``), which keeps the host-driven
routes as the byte-exact oracles.

Kernel catalog:

- :func:`fused_allreduce` — two-phase ring allreduce (reduce-scatter +
  all-gather) in ONE kernel: the per-chunk accumulate happens in
  registers between the recv-wait and the next send, and the gather
  phase forwards each landing chunk onward *before* copying it into the
  output, so the forward hop rides under the output write. Chunk
  geometry and combine order mirror :func:`ring.ring_allreduce_chunked`
  exactly — the two are bitwise-equal, which is what the parity suite
  asserts.
- :func:`allreduce_into` — the same kernel with a fused epilogue: a
  bias add and/or an elementwise function applied to each reduced chunk
  AS IT LANDS (the reduction's consumer never sees a separate pass).
- :func:`allgather_matmul` — ring all-gather where every arriving shard
  immediately feeds a matmul tile against the local weight panel while
  the shard is simultaneously forwarded to the next neighbor — the
  dataflow ``parallel/ring_attention.py`` runs at the XLA level,
  dropped into a single kernel.
- :func:`fused_permute` / :func:`fused_ring_shift` — device-initiated
  ``lax.ppermute``: one remote DMA per rank, pair list validated by
  :func:`ring.check_permutation` (shardlint's ``unchecked-permutation``
  rule audits this entry point like it audits ``ppermute``).

Execution modes:

- **interpret** (default off-TPU): jax's dma-discharge interpreter maps
  each remote copy onto a lockstep ``all_gather`` + select, so the full
  dataflow — schedules, chunk indices, combines, epilogues — runs and
  is oracle-checked on the 8-device CPU mesh. Semaphores are inert
  arithmetic there, so the *synchronization protocol* (slot lifetimes,
  send-reuse waits, the drain discipline) is proven off-chip by the
  pallaslint semaphore ledger (``analysis/pallas_rules.py``, review
  time) and the strict-semaphore shim the parity battery runs under
  (``analysis/runtime.strict_semaphores``, trace time); what stays
  hardware-empirical is Mosaic's lowering and real DMA rates
  (ROADMAP.md Speed 8).
- **compiled** (TPU): the same kernel lowered by Mosaic; neighbor ids
  ride ``DeviceIdType.LOGICAL`` scalars. Not yet: jax 0.9.0 refuses to
  lower these kernels on the chip (``collective_id has to be
  unspecified or None when not using a custom barrier`` — they carry
  an id but no barrier-semaphore handshake; ROADMAP.md Speed 8), and
  the refusal propagates to the caller.

Multi-axis meshes: jax's dma-discharge rule (and the LOGICAL id space)
supports a single named mesh axis, so the kernels always run under a
shard_map binding ONE flat axis. A ring over one axis of a multi-axis
mesh is expressed as a :class:`RingGeometry` — the flat-id stride
between consecutive ring positions, computed from the mesh coordinates
(row-major device order, so axis ``i`` of sizes ``s`` has stride
``prod(s[i+1:])``). Every kernel takes ``geometry=`` and computes its
logical neighbor as ``flat_id + (next_pos - pos) * stride``; ranks that
share a ring position are replicas and run the identical schedule (the
parity suite pins their outputs bitwise-equal). The Communicator routes
multi-axis meshes through :func:`mesh_ring_geometry` / ``flat_mesh``
automatically — docs/comm.md walks the neighbor math.

VMEM footprint: the whole local shard plus ~2x its chunk working set
must fit VMEM (no grid streaming yet — benchmark shapes to ~MBs). The
wrapper pads the scatter axis to ``size * lane``-divisible width and
slices the pad back off; zero padding is combine-neutral for sum.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hpc_patterns_tpu.comm import ring
from hpc_patterns_tpu.ops.tiling import (
    collective_id as _registered_collective_id,
    default_interpret,
    resolve_interpret,
)

#: reduce ops the fused ring implements. ``prod`` is deliberately
#: absent: the host path's ``collectives._pprod`` is an all-gather+
#:  reduce FALLBACK (XLA has no native pprod), and silently routing
#: "fused prod" onto a sum-shaped ring would return wrong data, not
#: raise — see :func:`_check_op`.
FUSED_REDUCE_OPS = frozenset({"sum"})

#: chunk-width alignment on the compiled path (TPU lane width); 1 under
#: interpret so CPU parity shapes stay un-inflated
_TPU_LANE = 128

#: compiled-path VMEM budget: the whole local shard + two chunk-slot
#: arrays live in VMEM (no grid streaming yet), which passes Mosaic's
#: 16 MB default scoped limit at benchmark shapes; well under the
#: physical budget (the fused-MLP kernels use the same override)
_VMEM_LIMIT = 100 * 1024 * 1024

#: the single flat axis name every multi-axis routing binds (module
#: docstring): shard_map over ``flat_mesh(mesh)`` with this axis, ring
#: neighbors computed by :class:`RingGeometry` from mesh coordinates
FLAT_AXIS = "_fusedflat"


@dataclasses.dataclass(frozen=True)
class RingGeometry:
    """How one logical ring sits inside a flat device ordering.

    ``axis`` is the (single) mesh axis name the kernel's shard_map
    binds; ``size`` the ring length; ``stride`` the flat-id distance
    between consecutive ring positions; ``total`` the flat mesh size.
    The identity geometry (``stride=1, total=size``) is the classic
    1-D mesh and reproduces the original kernels' traces exactly; a
    multi-axis ring (from :func:`mesh_ring_geometry`) has
    ``total > size`` and every ``total // size`` flat ranks sharing a
    ring position compute identical (replicated) results."""

    axis: str
    size: int
    stride: int = 1
    total: int | None = None

    def __post_init__(self):
        if self.total is None:
            object.__setattr__(self, "total", self.size * self.stride)
        if self.size < 1 or self.stride < 1:
            raise ValueError(f"degenerate ring geometry: {self}")
        if self.total % (self.size * self.stride):
            raise ValueError(
                f"flat size {self.total} not divisible by "
                f"size*stride = {self.size * self.stride}: {self}")

    @property
    def identity(self) -> bool:
        return self.stride == 1 and self.total == self.size

    # -- in-kernel (traced) --------------------------------------------
    def me_and_right(self):
        """(ring position, right-neighbor FLAT id) — computed INSIDE
        the kernel body (a pallas kernel cannot capture traced values
        from the caller; ``lax.axis_index`` is legal in-kernel). The
        position indexes chunks; the flat id feeds ``device_id``."""
        me = lax.axis_index(self.axis)
        if self.identity:
            return me, lax.rem(me + 1, self.size)
        pos = lax.rem(me // self.stride, self.size)
        dst = me + (lax.rem(pos + 1, self.size) - pos) * self.stride
        return pos, dst

    def flat_index(self):
        """The rank's FLAT id (traced, in-kernel) — indexes per-rank
        SMEM tables like :func:`fused_permute`'s destination table."""
        return lax.axis_index(self.axis)

    # -- host-side (static) --------------------------------------------
    def positions(self) -> list[int]:
        """Ring position of every flat id — the take-index that expands
        a ``(size, ...)`` global array to its ``(total, ...)``
        replicated layout."""
        return [(f // self.stride) % self.size for f in range(self.total)]

    def ring_ids(self) -> list[int]:
        """One representative flat id per ring position (the fold-back
        selection after a flat-mesh collective)."""
        return [p * self.stride for p in range(self.size)]

    def flat_dst_table(self, dst_by_pos: Sequence[int]) -> list[int]:
        """Expand a position-level permutation destination table to
        flat ids: each flat rank sends to the SAME-replica rank of its
        position's destination."""
        out = []
        for f in range(self.total):
            pos = (f // self.stride) % self.size
            out.append(f + (int(dst_by_pos[pos]) - pos) * self.stride)
        return out


def mesh_ring_geometry(mesh, axis: str) -> RingGeometry:
    """The :class:`RingGeometry` of ring ``axis`` inside ``mesh``'s
    row-major flat device order: stride = product of the axis sizes to
    its RIGHT (``mesh.devices`` is C-ordered), bound under
    :data:`FLAT_AXIS` on :func:`flat_mesh`."""
    names = list(mesh.axis_names)
    if axis not in names:
        raise ValueError(f"axis {axis!r} not in mesh axes {names}")
    sizes = [int(mesh.shape[a]) for a in names]
    i = names.index(axis)
    stride = int(math.prod(sizes[i + 1:]))
    return RingGeometry(axis=FLAT_AXIS, size=sizes[i], stride=stride,
                        total=int(math.prod(sizes)))


def flat_mesh(mesh):
    """``mesh`` re-expressed as a 1-D mesh over :data:`FLAT_AXIS` in
    the same (row-major) device order — the mesh the multi-axis fused
    route shard_maps over."""
    from jax.sharding import Mesh

    return Mesh(mesh.devices.flatten(), (FLAT_AXIS,))


def _resolve_geometry(axis: str, geometry: RingGeometry | None, *,
                      shift: int = 1) -> RingGeometry:
    """Default (``geometry=None``) is the identity ring over ``axis``
    — the original single-axis behavior, ring size validated exactly
    like before. An explicit geometry carries a static size, so the
    same pair-list sanitizer runs on ring positions."""
    if geometry is None:
        return RingGeometry(axis=axis, size=_ring_size(axis, shift=shift))
    if geometry.axis != axis:
        raise ValueError(
            f"geometry axis {geometry.axis!r} != bound axis {axis!r}")
    ring.check_permutation(ring._ring_perm(geometry.size, shift),
                           geometry.size)
    return geometry


def _check_op(op: str) -> None:
    if op not in FUSED_REDUCE_OPS:
        raise ValueError(
            f"fused allreduce implements {sorted(FUSED_REDUCE_OPS)}, "
            f"got {op!r} — notably 'prod' must stay on the host path "
            "(collectives.allreduce op='prod'), whose all-gather "
            "fallback is the only exact route"
        )


def ring_layout(shape: Sequence[int], size: int, *,
                interpret: bool | None = None
                ) -> tuple[int, int, int, int]:
    """Chunk geometry shared by the kernels, their wrappers, and the
    parity tests: ``(m, n, cn, n_pad)`` for a local shard ``shape``
    flattened to ``(m, n)`` rows x cols. ``cn`` is the ring chunk
    width — ``ceil(n / size)`` rounded up to the lane multiple on the
    compiled path — and ``n_pad = size * cn`` is the padded column
    count the two-phase ring runs over. Tests build the byte-exact
    host oracle (``ring_allreduce_chunked`` over the padded array) from
    the same numbers, so wrapper and oracle can never disagree on
    geometry."""
    if interpret is None:
        interpret = default_interpret()
    shape = tuple(shape)
    if not shape:
        shape = (1,)
    n = shape[-1]
    m = math.prod(shape[:-1]) if len(shape) > 1 else 1
    lane = 1 if interpret else _TPU_LANE
    cn = max(1, -(-n // size))
    cn = -(-cn // lane) * lane
    return m, n, cn, size * cn


def _ring_size(axis: str, *, shift: int = 1) -> int:
    """Validated ring size: the static pair list is built and checked
    exactly like :func:`ring.ring_shift`'s — the deadlock/zero-fill
    sanitizer applies to the device-initiated ring the same as to
    ``ppermute``."""
    size = ring.axis_size(axis)
    perm = ring._ring_perm(size, shift)
    ring.check_permutation(perm, size)
    return size


def _remote_copy(src, dst, send_sem, recv_sem, device_id):
    """One device-initiated neighbor hop. Scalar LOGICAL ids: identical
    lowering on Mosaic (returned as-is) and under the dma-discharge
    interpreter (which rejects the tuple form)."""
    return pltpu.make_async_remote_copy(
        src_ref=src, dst_ref=dst, send_sem=send_sem, recv_sem=recv_sem,
        device_id=device_id,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )


# ---------------------------------------------------------------------------
# fused_permute: device-initiated ppermute
# ---------------------------------------------------------------------------


def fused_permute(x, axis: str, perm, *, interpret: bool | None = None,
                  collective_id: int | None = None,
                  geometry: RingGeometry | None = None):
    """``lax.ppermute`` with the transfer issued by the device: rank
    ``s`` DMAs its shard straight into rank ``d``'s buffer for every
    ``(s, d)`` in ``perm``. The pair list passes
    :func:`ring.check_permutation` first (full permutation required —
    ppermute's silent zero-fill has no fused analog: every rank waits
    on exactly one incoming copy). ``collective_id``: kernels that may
    run CONCURRENTLY on chip (e.g. the K and V shifts of one
    ring-attention step) must carry distinct ids — same-id collective
    kernels share barrier state. Pass an id from
    :func:`ops.tiling.collective_id` (never a hand-picked integer —
    pallaslint flags magic ids); None takes this kernel's registered
    default. ``geometry``: a multi-axis ring (``perm`` is over ring
    POSITIONS; every replica rank of a position sends to the matching
    replica of the destination position)."""
    if collective_id is None:
        collective_id = _registered_collective_id("comm.fused.permute")
    g = (geometry if geometry is not None
         else RingGeometry(axis=axis, size=ring.axis_size(axis)))
    if g.axis != axis:
        raise ValueError(
            f"geometry axis {g.axis!r} != bound axis {axis!r}")
    size = g.size
    perm = [(int(s), int(d)) for s, d in perm]
    ring.check_permutation(perm, size)
    if size == 1:
        return x
    interpret = resolve_interpret(interpret, "fused_permute")
    dst_table = [0] * size
    for s, d in perm:
        dst_table[s] = d

    shape = x.shape
    x2 = x.reshape(max(1, math.prod(shape[:-1]) if len(shape) > 1 else 1),
                   shape[-1] if shape else 1)
    dsts = jnp.asarray(g.flat_dst_table(dst_table),
                       jnp.int32).reshape(g.total, 1)

    def kernel(dst_ref, x_ref, o_ref, send_sem, recv_sem):
        me = g.flat_index()
        dma = _remote_copy(x_ref, o_ref, send_sem, recv_sem,
                           dst_ref[me, 0])
        dma.start()
        dma.wait()

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                             collective_id=collective_id),
        interpret=interpret,
    )(dsts, x2)
    return out.reshape(shape)


def fused_ring_shift(x, axis: str, shift: int = 1, *,
                     interpret: bool | None = None,
                     collective_id: int | None = None,
                     geometry: RingGeometry | None = None):
    """Device-initiated :func:`ring.ring_shift`: rank r's shard lands on
    rank ``(r + shift) % size`` via one in-kernel remote DMA."""
    size = geometry.size if geometry is not None else ring.axis_size(axis)
    perm = ring._ring_perm(size, shift)
    ring.check_permutation(perm, size)
    return fused_permute(x, axis, perm, interpret=interpret,
                         collective_id=collective_id, geometry=geometry)


# ---------------------------------------------------------------------------
# fused_allreduce / allreduce_into: two-phase ring in one kernel
# ---------------------------------------------------------------------------


def _epilogue_write(o_ref, b_ref, epilogue, chunk_idx, cn, value):
    """out[:, chunk] = epilogue(value (+ bias chunk)) — the fused
    consumer applied as the chunk lands; elementwise, so chunkwise
    application equals whole-array application bit for bit."""
    if b_ref is not None:
        value = value + b_ref[:, pl.ds(chunk_idx * cn, cn)]
    if epilogue is not None:
        value = epilogue(value)
    # an epilogue computing in a wider dtype lands back in the
    # collective's dtype (the size==1 early exit matches)
    o_ref[:, pl.ds(chunk_idx * cn, cn)] = value.astype(o_ref.dtype)


def fused_allreduce(x, axis: str, *, op: str = "sum",
                    bias=None, epilogue: Callable | None = None,
                    interpret: bool | None = None,
                    geometry: RingGeometry | None = None):
    """Ring allreduce(sum) with the schedule run inside one Pallas
    kernel (module docstring). Rank-local: call inside ``shard_map``
    over ``axis``. Bitwise-equal to
    ``ring.ring_allreduce_chunked`` over the :func:`ring_layout`-padded
    array (the parity suite's oracle). ``bias``/``epilogue`` fuse a
    reduction consumer into the gather phase — see
    :func:`allreduce_into`. ``geometry``: run the ring over one axis of
    a multi-axis mesh (replica ranks reduce redundantly, bitwise-equal
    — the Communicator's multi-axis route)."""
    _check_op(op)
    g = _resolve_geometry(axis, geometry)
    size = g.size
    shape = x.shape
    if size == 1:
        # same dtype discipline as the kernel path: bias joins in x's
        # dtype, the epilogue's result lands back in it
        out = x if bias is None else x + jnp.asarray(bias, x.dtype)
        if epilogue is not None:
            out = epilogue(out)
        return out.astype(x.dtype)
    interpret = resolve_interpret(interpret, "fused_allreduce")
    m, n, cn, n_pad = ring_layout(shape, size, interpret=interpret)
    x2 = x.reshape(m, n)
    if n_pad != n:
        x2 = jnp.pad(x2, ((0, 0), (0, n_pad - n)))
    b2 = None
    if bias is not None:
        b2 = jnp.broadcast_to(jnp.asarray(bias, x.dtype),
                              shape).reshape(m, n)
        if n_pad != n:
            b2 = jnp.pad(b2, ((0, 0), (0, n_pad - n)))

    def kernel(*refs):
        if b2 is not None:
            x_ref, b_ref, o_ref = refs[:3]
            scratch = refs[3:]
        else:
            x_ref, o_ref = refs[:2]
            b_ref = None
            scratch = refs[2:]
        (rs_recv, sendbuf, ag_recv, rs_recv_sem, send_sem,
         ag_recv_sem, ag_send_sem) = scratch
        me, dst = g.me_and_right()

        def chunk(j):
            return x_ref[:, pl.ds(j * cn, cn)]

        # --- phase 1: ring reduce-scatter -------------------------------
        # identical chunk walk to ring.ring_reduce_scatter: send chunk
        # (me+size-1-s), accumulate the arriving partial as mine+incoming
        sendbuf[0] = chunk(lax.rem(me + size - 1, size))
        dmas = []
        d = _remote_copy(sendbuf.at[0], rs_recv.at[0],
                         send_sem.at[0], rs_recv_sem.at[0], dst)
        d.start()
        dmas.append(d)
        for s in range(1, size):
            dmas[s - 1].wait_recv()
            slot = s % 2
            if s >= 2:
                # the DMA that read this send buffer two steps ago must
                # have drained before the buffer is rewritten
                dmas[s - 2].wait_send()
            sendbuf[slot] = (chunk(lax.rem(me + size - 1 - s, size))
                             + rs_recv[s - 1])
            if s < size - 1:
                d = _remote_copy(sendbuf.at[slot], rs_recv.at[s],
                                 send_sem.at[slot], rs_recv_sem.at[s],
                                 dst)
                d.start()
                dmas.append(d)
        red_slot = (size - 1) % 2  # fully-reduced chunk ``me``

        # --- phase 2: ring all-gather, forward-before-write -------------
        # dedicated ag_recv slots, NOT rs_recv: a gather-phase write
        # into a reduce-scatter slot could land before the (slower)
        # neighbor's phase-1 read of it — nothing orders my phase-1
        # completion after the neighbor's consumption, only after its
        # step-0 send. Distinct buffers make the phases race-free.
        ag = _remote_copy(sendbuf.at[red_slot], ag_recv.at[0],
                          ag_send_sem.at[0], ag_recv_sem.at[0], dst)
        ag.start()
        ag_dmas = [ag]
        # own chunk written while the first hop flies
        _epilogue_write(o_ref, b_ref, epilogue, me, cn,
                        sendbuf[red_slot])
        for s in range(1, size):
            ag_dmas[s - 1].wait_recv()
            if s < size - 1:
                # forward the landing chunk onward FIRST; the output
                # write below then overlaps the in-flight hop
                d = _remote_copy(ag_recv.at[s - 1], ag_recv.at[s],
                                 ag_send_sem.at[s], ag_recv_sem.at[s],
                                 dst)
                d.start()
                ag_dmas.append(d)
            src = lax.rem(me + size - s, size)
            _epilogue_write(o_ref, b_ref, epilogue, src, cn,
                            ag_recv[s - 1])
        # no DMA may outlive the kernel's scratch. The loop already
        # consumed dmas[0..size-3]'s send sems (the slot-reuse waits);
        # only the LAST reduce-scatter send is still outstanding — a
        # second wait on a consumed sem would deadlock the compiled
        # kernel (one signal per DMA).
        dmas[-1].wait_send()
        for d in ag_dmas:
            d.wait_send()

    operands = [x2] if b2 is None else [x2, b2]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n_pad), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * len(operands),
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((size - 1, m, cn), x.dtype),   # phase-1 recv slots
            pltpu.VMEM((2, m, cn), x.dtype),          # alternating sends
            pltpu.VMEM((size - 1, m, cn), x.dtype),   # phase-2 recv slots
            pltpu.SemaphoreType.DMA((size - 1,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((size - 1,)),
            pltpu.SemaphoreType.DMA((size - 1,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            collective_id=_registered_collective_id(
                "comm.fused.allreduce"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*operands)
    if n_pad != n:
        out = out[:, :n]
    return out.reshape(shape)


def allreduce_into(x, axis: str, *, bias=None,
                   epilogue: Callable | None = None,
                   interpret: bool | None = None,
                   geometry: RingGeometry | None = None):
    """Allreduce with its consumer fused into the gather phase: each
    reduced chunk gets ``epilogue(chunk + bias)`` applied AS THE DMA
    LANDS — the reduction's consumer (a bias add, an activation) costs
    no separate pass over the array. ``epilogue`` must be elementwise
    (chunkwise application is asserted byte-equal to whole-array
    application by the parity suite)."""
    return fused_allreduce(x, axis, bias=bias, epilogue=epilogue,
                           interpret=interpret, geometry=geometry)


# ---------------------------------------------------------------------------
# allgather_matmul: each arriving shard feeds the next matmul tile
# ---------------------------------------------------------------------------


def allgather_matmul(x, w, axis: str, *, interpret: bool | None = None,
                     geometry: RingGeometry | None = None):
    """``all_gather(x) @ w`` with the gather ring inside the kernel:
    at step ``s`` the shard that just arrived is forwarded to the next
    neighbor and THEN multiplied against the local weight panel — the
    matmul tile runs while the next shard is on the wire. Rank-local;
    ``x``: (m, k) rows shard, ``w``: (k, n) local panel; returns
    ``(size*m, n)`` with row-block ``j`` equal to rank j's
    ``x @ w`` — the ring-attention dataflow as one kernel."""
    if x.ndim != 2 or w.ndim != 2 or x.shape[1] != w.shape[0]:
        raise ValueError(
            f"allgather_matmul wants x (m, k) @ w (k, n), got "
            f"{x.shape} @ {w.shape}"
        )
    g = _resolve_geometry(axis, geometry)
    size = g.size
    m, k = x.shape
    n = w.shape[1]
    if size == 1:
        return jnp.dot(x, w, preferred_element_type=jnp.float32
                       ).astype(x.dtype)
    interpret = resolve_interpret(interpret, "allgather_matmul")

    def kernel(x_ref, w_ref, o_ref, buf, send_sem, recv_sem):
        me, dst = g.me_and_right()

        def tile(block, j):
            o_ref[pl.ds(j * m, m), :] = jnp.dot(
                block, w_ref[...], preferred_element_type=jnp.float32
            ).astype(o_ref.dtype)

        dmas = [_remote_copy(x_ref, buf.at[0], send_sem.at[0],
                             recv_sem.at[0], dst)]
        dmas[0].start()
        # local tile computes while the first shard flies
        tile(x_ref[...], me)
        for s in range(1, size):
            dmas[s - 1].wait_recv()
            if s < size - 1:
                d = _remote_copy(buf.at[s - 1], buf.at[s],
                                 send_sem.at[s], recv_sem.at[s], dst)
                d.start()
                dmas.append(d)
            # the arriving shard's tile overlaps the hop just started
            tile(buf[s - 1], lax.rem(me + size - s, size))
        for d in dmas:
            d.wait_send()

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((size * m, n), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 2,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((size - 1, m, k), x.dtype),
            pltpu.SemaphoreType.DMA((size - 1,)),
            pltpu.SemaphoreType.DMA((size - 1,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            collective_id=_registered_collective_id(
                "comm.fused.allgather_matmul")),
        interpret=interpret,
    )(x, w)


def allgather_matmul_reference(x, w, axis: str):
    """The host-driven oracle for :func:`allgather_matmul`: XLA
    all-gather completes, THEN the tiles compute (no overlap), with the
    identical per-block dot shape/accumulation so the comparison is
    bitwise. Rank-local."""
    size = ring.axis_size(axis)
    gathered = lax.all_gather(x, axis, tiled=False)  # (size, m, k)
    blocks = [
        jnp.dot(lax.index_in_dim(gathered, j, keepdims=False), w,
                preferred_element_type=jnp.float32).astype(x.dtype)
        for j in range(size)
    ]
    return jnp.concatenate(blocks, axis=0)
