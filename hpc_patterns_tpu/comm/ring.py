"""Ring engine: neighbor-exchange collectives built on ``lax.ppermute``.

TPU-native rebuild of the reference's hand-rolled ring
(``SendRecvRing`` + step-wise accumulate + buffer swap,
allreduce-mpi-sycl.cpp:43-59,173-182). The reference's even/odd blocking
send/recv ordering exists only to avoid MPI deadlock; ``ppermute`` is a
deadlock-free collective permute, so the *schedule* (who talks to whom,
what is combined per step) is what is reproduced, not the ordering trick.

Everything here is a **rank-local** function meant to run inside
``shard_map``: it takes the local shard and a mesh axis name, the way the
reference's per-rank functions take a device buffer and a communicator.
On TPU the permutes ride ICI between mesh neighbors; XLA lowers them to
collective-permute with no host staging ("GPU-aware" semantics, §2.3).

This ring engine is deliberately API-shaped as a reusable primitive
(SURVEY.md §5 "long-context"): per-step neighbor shift + local combine +
buffer rotation is exactly the ring-attention / context-parallel
dataflow, and :mod:`hpc_patterns_tpu.parallel.ring_attention` builds on
:func:`ring_schedule` directly.
"""

from __future__ import annotations

from collections import Counter
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax


def axis_size(axis: str) -> int:
    """World size of a mesh axis, inside shard_map (MPI_Comm_size
    analog) — a Python int, usable in loop bounds and shapes."""
    return lax.axis_size(axis)


def axis_index(axis: str):
    """This shard's rank on ``axis`` (MPI_Comm_rank analog); traced value."""
    return lax.axis_index(axis)


def _ring_perm(size: int, shift: int) -> list[tuple[int, int]]:
    """Static source->dest pairs sending each rank's data ``shift`` to the
    right (shift may be negative)."""
    return [(i, (i + shift) % size) for i in range(size)]


def check_permutation(pairs, size: int, *, allow_partial: bool = False) -> None:
    """Deadlock/race sanitizer for ppermute schedules.

    The reference avoids deadlock *by construction* (even/odd blocking
    ordering, allreduce-mpi-sycl.cpp:50-58) and has no checker
    (SURVEY.md §5 "race detection: None"). ppermute is deadlock-free by
    design, but a malformed permutation silently drops or duplicates
    data (XLA zero-fills destinations with no incoming pair); this
    closes that gap: indices in range, no rank twice as source or
    destination, and — unless ``allow_partial`` — every rank exactly
    once as both (a true permutation). Raises ValueError. O(n).
    """
    srcs, dsts = [], []
    for s, d in pairs:
        if not (0 <= s < size and 0 <= d < size):
            raise ValueError(f"pair ({s}, {d}) out of range for size {size}")
        srcs.append(s)
        dsts.append(d)
    by_name = (("sources", srcs), ("destinations", dsts))
    for name, idxs in by_name:
        counts = Counter(idxs)
        dups = sorted(x for x, c in counts.items() if c > 1)
        if dups:
            raise ValueError(
                f"malformed permutation: duplicate {name} {dups} — data "
                "would be dropped/duplicated"
            )
    if not allow_partial:
        for name, idxs in by_name:
            missing = sorted(set(range(size)) - set(idxs))
            if missing:
                raise ValueError(
                    f"partial permutation: ranks {missing} missing from "
                    f"{name} — ppermute would zero-fill their buffers "
                    "(pass allow_partial=True if intended)"
                )


def ring_shift(x, axis: str, shift: int = 1):
    """Shift local data ``shift`` ranks around the ring.

    The TPU analog of one ``SendRecvRing(src, dest, rank, right, left, n)``
    step (allreduce-mpi-sycl.cpp:43-59): rank r's buffer lands on rank
    ``(r + shift) % size``. Deadlock-free by construction (collective
    permute), unlike the reference which needs even/odd send/recv
    ordering (:50-58).
    """
    size = axis_size(axis)
    perm = _ring_perm(size, shift)
    check_permutation(perm, size)
    return lax.ppermute(x, axis, perm)


def pairwise_exchange(x, axis: str):
    """Even/odd partner swap: rank r exchanges with rank ``r ^ 1``.

    The ping-pong pattern (BASELINE.json pt2pt config; the reference's
    paired blocking Send/Recv, allreduce-mpi-sycl.cpp:50-58). Requires an
    even axis size, matching the miniapps' even-rank-count precondition
    (allreduce-mpi-sycl.cpp:95-97).
    """
    size = axis_size(axis)
    if size % 2:
        raise ValueError(f"pairwise_exchange needs an even axis size, got {size}")
    perm = [(i, i ^ 1) for i in range(size)]
    check_permutation(perm, size)
    return lax.ppermute(x, axis, perm)


def ring_schedule(
    x,
    axis: str,
    step_fn: Callable,
    *,
    steps: int | None = None,
    shift: int = 1,
    carry=None,
):
    """The generic ring dataflow: ``steps`` rounds of (shift buffer one
    neighbor over, combine locally).

    Reproduces the reference's ring loop shape (allreduce-mpi-sycl.cpp:
    177-181): ``for s in 1..size-1: SendRecvRing; swap(VA,VB); Accumulate``
    — here the "swap" is functional (the shifted value *is* the next
    buffer) and "Accumulate" is ``step_fn``.

    ``step_fn(carry, incoming, step)`` -> new carry. ``incoming`` at step
    ``s`` is the shard originally held by rank ``(r - s*shift) % size``.
    The loop is a static Python loop over a static ``steps`` (size-1 by
    default) so XLA can pipeline permutes against the combines — a
    ``fori_loop`` would also work but hides the unrolled overlap from the
    scheduler at small world sizes.
    """
    size = axis_size(axis)
    if steps is None:
        steps = size - 1
    buf = x
    if carry is None:
        carry = x
    for s in range(1, steps + 1):
        buf = ring_shift(buf, axis, shift)
        carry = step_fn(carry, buf, s)
    return carry


def ring_allreduce(x, axis: str):
    """Allreduce(SUM) as a (size-1)-step ring of neighbor exchanges —
    the reference's hand-rolled algorithm (allreduce-mpi-sycl.cpp:173-182)
    rebuilt on ``ppermute``.

    Every rank ends with the elementwise sum over all ranks, same as
    ``MPI_Allreduce``; the analytic oracle ``size*(size-1)/2`` for
    rank-valued inputs holds (:192-204). Moves the *full* buffer each
    step: (size-1) * n elements on the wire per rank — the bandwidth cost
    the reference's ring pays. See :func:`ring_allreduce_chunked` for the
    bandwidth-optimal two-phase version.
    """
    return ring_schedule(x, axis, lambda acc, incoming, _s: acc + incoming)


def ring_reduce_scatter(x, axis: str, *, scatter_axis: int = 0):
    """Reduce-scatter as a (size-1)-step chunked ring.

    Phase 1 of the bandwidth-optimal allreduce: the local buffer is split
    into ``size`` chunks along ``scatter_axis``; each step sends the
    partially-reduced chunk one neighbor right and accumulates the chunk
    arriving from the left. Rank r ends holding chunk r fully reduced.
    Wire cost: n * (size-1)/size per rank — the reason rings win at large
    message sizes (the ring-vs-collective comparison of BASELINE.json).
    """
    size = axis_size(axis)
    me = lax.axis_index(axis)
    if x.shape[scatter_axis] % size:
        raise ValueError(
            f"scatter axis length {x.shape[scatter_axis]} not divisible by {size}"
        )
    chunks = jnp.split(x, size, axis=scatter_axis)
    # Walk the ring: at step s, rank r sends the chunk destined for rank
    # (r - s) and receives+accumulates the one destined for (r - s - 1)...
    # equivalently: send chunk index (me - s + 1), recv (me - s). Static
    # loop with a dynamic chunk select keeps shapes static under jit.
    stacked = jnp.stack(chunks)  # (size, chunk...)
    send = lax.dynamic_index_in_dim(stacked, (me + size - 1) % size, keepdims=False)
    for s in range(1, size):
        incoming = ring_shift(send, axis, 1)
        idx = (me + size - 1 - s) % size
        mine = lax.dynamic_index_in_dim(stacked, idx, keepdims=False)
        send = mine + incoming
    # send now holds chunk ``me`` fully reduced.
    return send


def ring_all_gather(x, axis: str, *, gather_axis: int = 0, tiled: bool = False):
    """All-gather as a (size-1)-step ring (phase 2 of two-phase allreduce).

    Each step forwards the chunk received last step; after size-1 steps
    every rank holds every chunk. ``tiled=False`` stacks a new leading
    axis; ``tiled=True`` concatenates along ``gather_axis`` (XLA
    ``all_gather`` convention, kept so this is a drop-in for
    ``lax.all_gather``).
    """
    size = axis_size(axis)
    me = lax.axis_index(axis)
    pieces = [x]
    buf = x
    for _ in range(size - 1):
        buf = ring_shift(buf, axis, 1)
        pieces.append(buf)
    # pieces[s] came from rank (me - s); roll into global rank order so
    # position j holds rank j's chunk on every rank.
    stacked = jnp.stack(pieces)  # (size, ...), index s = rank (me - s)
    ranks = (me - jnp.arange(size)) % size  # position->source rank
    inv = jnp.zeros((size,), dtype=ranks.dtype).at[ranks].set(jnp.arange(size))
    ordered = jnp.take(stacked, inv, axis=0)
    if not tiled:
        return ordered
    parts = [lax.index_in_dim(ordered, i, keepdims=False) for i in range(size)]
    return jnp.concatenate(parts, axis=gather_axis)


def ring_allreduce_chunked(x, axis: str, *, scatter_axis: int = 0):
    """Bandwidth-optimal allreduce: ring reduce-scatter + ring all-gather.

    2·n·(size-1)/size wire bytes per rank vs the naive ring's n·(size-1)
    — the textbook ring allreduce the reference's miniapp is a teaching
    version of. This is the variant raced against ``lax.psum`` in the
    miniapp's ring-vs-collective benchmark (§2.3 requirement (b)).
    """
    reduced = ring_reduce_scatter(x, axis, scatter_axis=scatter_axis)
    return ring_all_gather(reduced, axis, gather_axis=scatter_axis, tiled=True)


def ring_pipeline(xs: Sequence, axis: str, stage_fn: Callable, *, shift: int = 1):
    """Neighbor handoff skeleton for pipeline-parallel stage boundaries:
    apply ``stage_fn`` locally, then pass activations one rank over (the
    pt2pt pattern of SURVEY.md §2.2 "Pairwise pt2pt (the core of PP)").
    """
    ys = stage_fn(*xs) if isinstance(xs, (tuple, list)) else stage_fn(xs)
    return jax.tree.map(lambda t: ring_shift(t, axis, shift), ys)
