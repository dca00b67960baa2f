"""Array-level communicator: mesh axis ≙ MPI communicator.

The reference's miniapp mains wire device buffers to MPI calls per rank
(allreduce-mpi-sycl.cpp:88-207). Here one process drives all local TPU
devices, so the per-rank view is created by ``shard_map``: a
:class:`Communicator` binds a mesh axis and exposes collectives over
global ``jax.Array``\\ s whose leading dimension is sharded on that axis —
row r of the global array is rank r's buffer, exactly the miniapp's
``VA/VB/VC`` per-rank layout.

Every operation jit-compiles a ``shard_map`` closure (cached per shape/
dtype/algorithm); on TPU the collectives run on HBM shards over ICI with
no host staging.
"""

from __future__ import annotations

import os
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hpc_patterns_tpu.analysis import runtime as analysis_runtime
from hpc_patterns_tpu.comm import collectives, fused, ring
from hpc_patterns_tpu.harness import chaos as chaoslib
from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness import trace as tracelib
from jax import shard_map

Algorithm = Literal["collective", "ring", "ring_chunked", "fused"]


def _ready_in_span(result, op: str = "collective", seq: int | None = None,
                   axis: str | None = None, algorithm: str | None = None):
    """Block before an open span exits so it measures collective
    completion, not async dispatch — the shard_map call returns an
    unready array. Only when a span actually records (metrics, trace
    mirroring, or the flight recorder); the disabled path stays fully
    async. With a recorder, the dispatch→completion window also lands
    as a ``comm.<op>`` slice on the device track, separating wire time
    from the host time around it; ``seq`` (the per-communicator
    collective counter) rides in the slice args so the cross-rank merge
    (harness/collect.py) can match the N ranks' windows of the SAME
    collective and measure its skew.

    Every eager collective is ALSO fingerprinted into the per-rank
    schedule hash chain (analysis/runtime.py) before the wait —
    whenever anything can consume the chain: a live flight recorder
    (the chain rides trace snapshots to the cross-rank merge) or a
    launcher-exported ``HPCPAT_TRACE_DIR`` (the per-record progress
    file is what names which collective a hung rank is stuck in, so
    it must engage even when the child wasn't run with ``--trace``).
    Reading ``.shape``/``.dtype`` off the unready array does not
    block, and with neither consumer present nothing is recorded —
    the disabled path stays fully async and byte-identical."""
    m = metricslib.get_metrics()
    rec = tracelib.active()
    if seq is not None and (
            rec is not None
            or analysis_runtime.ENV_TRACE_DIR in os.environ):
        analysis_runtime.record_collective(
            op, seq, shape=getattr(result, "shape", None),
            dtype=str(getattr(result, "dtype", "")) or None, axis=axis,
            algorithm=algorithm)
    if not (m.enabled or m.mirror_traces or rec is not None):
        return result
    if rec is not None:
        attrs = None if seq is None else {"seq": seq}
        t_disp = rec.mark_dispatch(f"comm.{op}", args=attrs)
        # jaxlint: disable=host-sync-in-dispatch — measures completion,
        # not dispatch (PR 1 review decision); only reached with a
        # recorder/metrics active, the disabled path stays fully async
        jax.block_until_ready(result)
        rec.mark_complete(f"comm.{op}", t_disp, args=attrs)
    else:
        # jaxlint: disable=host-sync-in-dispatch — same contract as
        # above: the recording span must not exit before the wire time
        # it claims to measure has elapsed
        jax.block_until_ready(result)
    return result


def _inject_chaos(seq: int) -> None:
    """Chaos injection, straggler site — called by every collective
    method BEFORE the shard_map closure is even built, so the injected
    delay precedes the dispatch itself: the straggler's device work for
    collective ``seq`` genuinely starts late (the other ranks stretch
    waiting for it), and the skew evidence in the cross-rank merge is
    the real perturbation, not an artifact of marker placement. One
    cached-config read when no chaos is active."""
    if chaoslib.active() is not None:
        chaoslib.maybe_inject("collective", seq)


def record_collective_bandwidth(op: str, nbytes: int, seconds: float,
                                **attrs) -> None:
    """Per-collective bandwidth gauge + latency histogram in the
    process-wide metrics registry (no-op when disabled): the
    observability layer's view of the BASELINE bandwidth metrics, so a
    sweep's ``kind=metrics`` snapshot carries the same numbers the
    per-point ``kind=result`` records do. ``attrs`` become gauges too
    (e.g. ``busbw_gbps=...`` for the ring-normalized form)."""
    m = metricslib.get_metrics()
    if not m.enabled or seconds <= 0:
        return
    m.gauge(f"comm.{op}.bandwidth_gbps").set(nbytes / seconds / 1e9)
    m.histogram(f"comm.{op}.s").observe(seconds)
    for key, value in attrs.items():
        m.gauge(f"comm.{op}.{key}").set(value)

# allreduce algorithm table: library collective vs hand-built rings vs
# the device-initiated fused ring — the comparison the reference exists
# to make (SURVEY.md §2.3(b)), extended one rung down the stack.
_ALLREDUCE = {
    "collective": lambda x, axis: collectives.allreduce(x, axis, "sum"),
    "ring": ring.ring_allreduce,
    # chunk over the trailing (data) axis — the leading axis is the
    # 1-row rank dimension inside shard_map
    "ring_chunked": lambda x, axis: ring.ring_allreduce_chunked(
        x, axis, scatter_axis=x.ndim - 1
    ),
    # the ring schedule run INSIDE a Pallas kernel (remote DMA per
    # step); byte-exact vs ring_chunked over the padded layout —
    # comm/fused.py. Sum only: _check_op guards the _pprod fallback.
    "fused": lambda x, axis: fused.fused_allreduce(x, axis),
}


class Communicator:
    """Collectives over one named axis of a mesh.

    ``Communicator(mesh, "x")`` plays the role of ``MPI_COMM_WORLD`` in
    the miniapps; ``size`` is ``MPI_Comm_size``. Arrays passed in must
    have a leading dimension equal to ``size`` (one row per rank); they
    are sharded onto the axis automatically if not already.
    """

    def __init__(self, mesh: Mesh, axis: str = "x"):
        if axis not in mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        # jitted rank_filled initializers by (n, dtype): sweeps call it
        # once per point, and a fresh jax.jit per call re-traces every
        # time (jaxlint: recompile-hazard)
        self._rank_filled_cache: dict = {}
        # jitted allreduce closures by (shape, dtype, ALGORITHM):
        # benchmark sweeps race algorithms at one shape, and a cache
        # missing the algorithm key would thrash one slot per point
        # (each jit_allreduce call re-tracing the loser)
        self._jit_allreduce_cache: dict = {}
        # allgather_matmul closures, same keying discipline — the
        # fused-vs-collective bench times the eager method per rep
        self._agmm_cache: dict = {}
        # per-communicator collective counter: every eager collective
        # call takes the next value, and since all ranks of an SPMD
        # program issue the identical collective sequence, (span name,
        # seq) identifies THE SAME collective across ranks — what the
        # cross-rank trace merge fans its skew arrows over. Incremented
        # unconditionally (one integer add; the disabled trace path
        # stays byte-identical in recorded output).
        self._seq = 0

    def _next_seq(self) -> int:
        s = self._seq
        self._seq += 1
        return s

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]

    def row_sharding(self, ndim: int, memory_kind: str | None = None) -> NamedSharding:
        """Sharding that puts row r on rank r (leading dim over the axis).

        ``memory_kind`` maps the reference's USM allocator axis
        (``-H/-D``, allreduce-mpi-sycl.cpp:104-131) onto JAX memory
        kinds: ``"pinned_host"`` ≙ host USM, ``"device"``/None ≙ device
        USM (HBM)."""
        spec = P(self.axis, *([None] * (ndim - 1)))
        if memory_kind is None:
            return NamedSharding(self.mesh, spec)
        return NamedSharding(self.mesh, spec, memory_kind=memory_kind)

    def shard(self, x, memory_kind: str | None = None) -> jax.Array:
        """Place a (size, ...) array with one row per rank — the analog of
        each rank allocating + initializing its device buffer
        (allreduce-mpi-sycl.cpp:154-164)."""
        x = jnp.asarray(x)
        if x.shape[0] != self.size:
            raise ValueError(
                f"leading dim {x.shape[0]} != communicator size {self.size}"
            )
        return jax.device_put(x, self.row_sharding(x.ndim, memory_kind))

    def _shmap(self, fn, x, out_specs=None):
        spec = P(self.axis, *([None] * (jnp.ndim(x) - 1)))
        out = out_specs if out_specs is not None else spec
        mapped = shard_map(fn, mesh=self.mesh, in_specs=spec, out_specs=out)
        return jax.jit(mapped)

    # -- collectives over (size, n) arrays --------------------------------

    def _fused_route(self):
        """``(mesh, axis, geometry)`` the fused kernels run over. The
        kernels bind LOGICAL neighbor ids under ONE named axis (jax's
        dma-discharge rule and the logical id space are both
        single-axis), so a 1-D mesh runs as-is (geometry ``None`` —
        the identity ring) and a multi-axis mesh runs over its FLAT
        1-axis view with ring neighbors computed from mesh coordinates
        (:func:`fused.mesh_ring_geometry` — stride = product of the
        axis sizes to the right). Ranks sharing a ring position are
        replicas: each reduces its own copy, bitwise-identically, and
        :meth:`_fused_shmap` folds one representative row back per
        position."""
        if len(self.mesh.axis_names) == 1:
            return self.mesh, self.axis, None
        return (fused.flat_mesh(self.mesh), fused.FLAT_AXIS,
                fused.mesh_ring_geometry(self.mesh, self.axis))

    def _fused_shmap(self, mk_per_rank, *xs):
        """One jitted closure around a fused kernel over operands
        ``xs`` (each with the leading rank dim). ``mk_per_rank`` gets
        ``(axis, geometry)`` and returns the rank-local function —
        single-axis meshes shard_map it directly, multi-axis meshes
        take-expand every operand onto the flat mesh (row ``f`` =
        ring-position row ``pos(f)``), run the kernel, and fold the
        representative rows back, all inside the same jit (one compile
        per cache key, same as the 1-D route)."""
        mesh, axis, g = self._fused_route()
        per_rank = mk_per_rank(axis, g)
        specs = tuple(P(axis, *([None] * (jnp.ndim(v) - 1)))
                      for v in xs)
        mapped = shard_map(
            per_rank, mesh=mesh,
            in_specs=specs if len(specs) > 1 else specs[0],
            out_specs=specs[0],
            check_vma=False,  # the Pallas interpreter's internals carry no vma
        )
        if g is None:
            return jax.jit(mapped)
        idx = jnp.asarray(g.positions())
        sel = jnp.asarray(g.ring_ids())
        shardings = tuple(NamedSharding(mesh, s) for s in specs)

        def run(*vals):
            expanded = [
                jax.device_put(jnp.take(v, idx, axis=0), s)
                for v, s in zip(vals, shardings)]
            return jnp.take(mapped(*expanded), sel, axis=0)

        return jax.jit(run)

    def allreduce(self, x, algorithm: Algorithm = "collective") -> jax.Array:
        """Elementwise sum across ranks; every row of the result holds the
        sum (MPI_Allreduce semantics, allreduce-mpi-sycl.cpp:61-67 for
        ``"collective"``; the :173-182 hand ring for ``"ring"``;
        two-phase bandwidth-optimal ring for ``"ring_chunked"``; the
        same two-phase ring as device-initiated in-kernel remote DMA
        for ``"fused"`` — comm/fused.py, docs/comm.md; on a multi-axis
        mesh the fused route runs over the flat view with
        coordinate-computed neighbors, :meth:`_fused_route`)."""
        seq = self._next_seq()
        _inject_chaos(seq)
        with metricslib.span("comm.allreduce", algorithm=algorithm):
            if algorithm == "fused":
                result = self.jit_allreduce(x, algorithm)(x)
            else:
                impl = _ALLREDUCE[algorithm]
                result = self._shmap(
                    lambda local: impl(local, self.axis), x)(x)
            return _ready_in_span(
                result,
                op=f"allreduce.{algorithm}", seq=seq, axis=self.axis,
                algorithm=algorithm)

    def jit_allreduce(self, x, algorithm: Algorithm = "collective"):
        """The compiled allreduce closure for ``x``'s shape — what a
        benchmark should time (compile excluded per SURVEY.md §7(d)).
        Cached per (shape, dtype, axis, algorithm): an algorithm sweep
        at one shape gets one traced closure per algorithm instead of
        re-tracing whichever it asked for last (the axis key is
        redundant per instance — the communicator binds one axis — but
        pins the multi-axis sweep discipline the fused-route tests
        assert)."""
        key = (jnp.shape(x), str(jnp.result_type(x)), self.axis,
               algorithm)
        fn = self._jit_allreduce_cache.get(key)
        if fn is None:
            if algorithm == "fused":
                fn = self._fused_shmap(
                    lambda axis, g: (lambda local: fused.fused_allreduce(
                        local, axis, geometry=g)), x)
            else:
                impl = _ALLREDUCE[algorithm]
                fn = self._shmap(lambda local: impl(local, self.axis), x)
            self._jit_allreduce_cache[key] = fn
        return fn

    def pingpong(self, x) -> jax.Array:
        """Pairwise even/odd exchange: row r swaps with row r^1 — the
        pt2pt ping-pong config of BASELINE.json."""
        seq = self._next_seq()
        _inject_chaos(seq)
        with metricslib.span("comm.pingpong"):
            return _ready_in_span(self.jit_pingpong(x)(x),
                                  op="pingpong", seq=seq,
                                  axis=self.axis)

    def jit_pingpong(self, x):
        """Compiled pairwise-exchange closure (for timing loops)."""
        return self._shmap(lambda l: ring.pairwise_exchange(l, self.axis), x)

    def sendrecv_ring(self, x, shift: int = 1) -> jax.Array:
        """One ring hop: row r moves to row (r+shift) % size
        (SendRecvRing, allreduce-mpi-sycl.cpp:43-59)."""
        seq = self._next_seq()
        _inject_chaos(seq)
        with metricslib.span("comm.sendrecv_ring", shift=shift):
            return _ready_in_span(self._shmap(
                lambda l: ring.ring_shift(l, self.axis, shift), x)(x),
                op="sendrecv_ring", seq=seq, axis=self.axis)

    def all_gather(self, x) -> jax.Array:
        """Every rank receives every row: (size, n) -> (size, size, n)."""
        fn = lambda l: collectives.all_gather(l, self.axis, tiled=False).squeeze(1)[None]
        spec = P(self.axis, None, *([None] * (jnp.ndim(x) - 1)))
        seq = self._next_seq()
        _inject_chaos(seq)
        with metricslib.span("comm.all_gather"):
            return _ready_in_span(self._shmap(fn, x, out_specs=spec)(x),
                                  op="all_gather", seq=seq,
                                  axis=self.axis)

    def reduce_scatter(self, x) -> jax.Array:
        """(size, size*n) rows -> (size, n): rank r gets chunk r of the sum."""
        fn = lambda l: collectives.reduce_scatter(l, self.axis, scatter_axis=jnp.ndim(x) - 1)
        seq = self._next_seq()
        _inject_chaos(seq)
        with metricslib.span("comm.reduce_scatter"):
            return _ready_in_span(self._shmap(
                fn, x,
                out_specs=P(self.axis, *([None] * (jnp.ndim(x) - 1))))(x),
                op="reduce_scatter", seq=seq, axis=self.axis)

    def all_to_all(self, x) -> jax.Array:
        """Row r's chunk c goes to row c's chunk r (MPI_Alltoall)."""
        fn = lambda l: collectives.all_to_all(
            l, self.axis, split_axis=jnp.ndim(x) - 1, concat_axis=jnp.ndim(x) - 1
        )
        seq = self._next_seq()
        _inject_chaos(seq)
        with metricslib.span("comm.all_to_all"):
            return _ready_in_span(self._shmap(fn, x)(x),
                                  op="all_to_all", seq=seq,
                                  axis=self.axis)

    # -- fused collective+consumer ops (comm/fused.py) --------------------

    def allgather_matmul(self, x, w,
                         algorithm: str = "fused") -> jax.Array:
        """``all_gather(x) @ w`` with per-rank weight panels: ``x`` is
        (size, m, k) — row r is rank r's activation block — and ``w``
        is (size, k, n) — row r is rank r's panel; the result row r is
        ``gathered_x @ w[r]`` of shape (size*m, n).

        ``algorithm="fused"`` runs the gather ring inside one Pallas
        kernel, each arriving shard feeding a matmul tile while the
        next shard is on the wire; ``"collective"`` is the host-driven
        oracle (XLA all-gather completes, then the tiles compute) with
        identical per-tile accumulation, so the two are bitwise-equal
        — the parity the fused suite asserts."""
        if algorithm not in ("fused", "collective"):
            raise ValueError(
                f"allgather_matmul algorithm {algorithm!r} not in "
                "('fused', 'collective')")
        if jnp.ndim(x) != 3 or jnp.ndim(w) != 3:
            raise ValueError(
                f"want x (size, m, k) and w (size, k, n), got "
                f"{jnp.shape(x)} and {jnp.shape(w)}")
        key = (jnp.shape(x), str(jnp.result_type(x)), jnp.shape(w),
               str(jnp.result_type(w)), self.axis, algorithm)
        fn = self._agmm_cache.get(key)
        if fn is None:
            if algorithm == "fused":
                fn = self._fused_shmap(
                    lambda axis, g: (
                        lambda xl, wl: fused.allgather_matmul(
                            xl[0], wl[0], axis, geometry=g)[None]),
                    x, w)
            else:
                def per_rank(xl, wl):
                    return fused.allgather_matmul_reference(
                        xl[0], wl[0], self.axis)[None]

                spec = P(self.axis, None, None)
                fn = jax.jit(shard_map(per_rank, mesh=self.mesh,
                                       in_specs=(spec, spec),
                                       out_specs=spec))
            self._agmm_cache[key] = fn
        seq = self._next_seq()
        _inject_chaos(seq)
        with metricslib.span("comm.allgather_matmul",
                             algorithm=algorithm):
            return _ready_in_span(
                fn(self.shard(x), self.shard(w)),
                op=f"allgather_matmul.{algorithm}", seq=seq,
                axis=self.axis, algorithm=algorithm)

    def allreduce_into(self, x, bias=None, epilogue=None,
                       algorithm: str = "fused") -> jax.Array:
        """Allreduce(sum) with its consumer fused in: every row of the
        result holds ``epilogue(sum_ranks(x) + bias)``. On the
        ``"fused"`` route the bias add/epilogue are applied to each
        reduced chunk AS ITS DMA LANDS (no separate pass);
        ``"collective"`` is the host-driven oracle (psum, then the
        epilogue as ordinary XLA ops). ``epilogue`` must be
        elementwise — chunkwise application is what makes the fused
        route exact."""
        if algorithm not in ("fused", "collective"):
            raise ValueError(
                f"allreduce_into algorithm {algorithm!r} not in "
                "('fused', 'collective')")
        row_bias = None
        if bias is not None:
            row_bias = jnp.asarray(bias, jnp.result_type(x))

        def per_rank_collective(local):
            out = collectives.allreduce(local, self.axis, "sum")
            if row_bias is not None:
                out = out + row_bias
            if epilogue is not None:
                out = epilogue(out)
            # same dtype contract as the fused route (whose chunk
            # writes land in the collective's dtype): a widening
            # epilogue must not make the two routes diverge
            return out.astype(local.dtype)

        seq = self._next_seq()
        _inject_chaos(seq)
        with metricslib.span("comm.allreduce_into", algorithm=algorithm):
            if algorithm == "fused":
                fn = self._fused_shmap(
                    lambda axis, g: (lambda local: fused.allreduce_into(
                        local, axis, bias=row_bias, epilogue=epilogue,
                        geometry=g)), x)
                result = fn(x)
            else:
                result = self._shmap(per_rank_collective, x)(x)
            return _ready_in_span(
                result,
                op=f"allreduce_into.{algorithm}", seq=seq,
                axis=self.axis, algorithm=algorithm)

    # -- miniapp-style buffer init ---------------------------------------

    def rank_filled(self, n: int, dtype="float32") -> jax.Array:
        """The miniapp's ``Initialize``: rank r's buffer filled with r
        (allreduce-mpi-sycl.cpp:33-41), so the allreduce oracle is
        ``size*(size-1)/2`` (:192-204). Built shard-wise (no host
        materialization of the global array)."""

        fill = self._rank_filled_cache.get((n, str(dtype)))
        if fill is None:

            def init(_):
                r = ring.axis_index(self.axis)
                return jnp.full((1, n), r, dtype=dtype)

            spec = P(self.axis, None)
            fill = jax.jit(
                shard_map(init, mesh=self.mesh, in_specs=spec,
                          out_specs=spec)
            )
            self._rank_filled_cache[(n, str(dtype))] = fill
        token = self.shard(np.zeros((self.size, 1), np.int8))
        return fill(token)

    def expected_allreduce_value(self) -> float:
        """The analytic oracle: Σ ranks = size(size-1)/2."""
        return self.size * (self.size - 1) / 2
