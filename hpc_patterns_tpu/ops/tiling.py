"""Shared Pallas tiling/lowering helpers.

Every Pallas call site in the tree shares three decisions — how to
shrink a requested block to fit an off-size length, whether the kernel
runs compiled or interpreted (and the record of which it was), and
which ``collective_id`` it carries. One module owns them so a kernel
added tomorrow cannot disagree with the kernels that exist today.

The module also owns the **collective-id registry**
(:func:`collective_id`): every remote-DMA kernel that may run
concurrently with another must carry a distinct ``collective_id`` —
same-id kernels share barrier/DMA state on chip, and a collision hangs
or corrupts silently (interpret mode never exercises it). The ids used
to be hand-numbered 0-4 across ``comm/fused.py`` and
``parallel/ring_attention.py`` by convention; the registry assigns
them by NAME, so a collision is impossible by construction, and
pallaslint's ``collective-id-collision`` rule flags any site that
bypasses it with a magic number.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

#: name -> collective_id. Seeded with the historical 0-4 assignment so
#: the wire ids of the shipped kernels never move; new names derive
#: their id from the NAME itself (below), so every host of an SPMD job
#: computes the same id regardless of which kernel warms up first.
#: Names are dotted module-ish paths — the registry's job is
#: distinctness, the name's job is greppability.
_COLLECTIVE_IDS: dict[str, int] = {
    "comm.fused.permute": 0,
    "comm.fused.allreduce": 1,
    "comm.fused.allgather_matmul": 2,
    "parallel.ring_attention.kshift": 3,
    "parallel.ring_attention.vshift": 4,
    # the serving plane's device-side KV handoff (comm/migration_dma):
    # seeded so the exchange kernel's wire id is stable across hosts
    # from day one, like the original five
    "comm.fused.migration": 5,
}

#: new ids live in [_ID_FLOOR, _ID_FLOOR + _ID_SPAN): above the seeded
#: block, inside int32 (the CompilerParams field), with enough space
#: that name-hash collisions are a rename away from impossible
_ID_FLOOR = 16
_ID_SPAN = (1 << 20) - _ID_FLOOR


def _derived_id(name: str) -> int:
    import hashlib

    digest = hashlib.sha256(name.encode()).digest()
    return _ID_FLOOR + int.from_bytes(digest[:8], "big") % _ID_SPAN


def collective_id(name: str) -> int:
    """The registered ``collective_id`` for ``name``. Unseeded names
    get a name-derived id — a pure function of the string, so ids
    agree across hosts/processes whatever order kernels first run in
    (order-dependent assignment would be the cross-host wire mismatch
    this registry exists to prevent). Two kernels that may run
    concurrently simply register distinct names; nobody ever picks an
    integer. A hash collision between two registered names raises
    loudly (rename one) instead of silently sharing barrier state."""
    if name not in _COLLECTIVE_IDS:
        new_id = _derived_id(name)
        taken = {v: k for k, v in _COLLECTIVE_IDS.items()}
        if new_id in taken:
            raise ValueError(
                f"collective_id hash collision: {name!r} and "
                f"{taken[new_id]!r} both derive id {new_id} — rename "
                f"one (any change to the string re-rolls the id)")
        _COLLECTIVE_IDS[name] = new_id
    return _COLLECTIVE_IDS[name]


def registered_collective_ids() -> dict[str, int]:
    """Snapshot of the registry (tests assert distinctness and the
    pinned historical assignments)."""
    return dict(_COLLECTIVE_IDS)


def default_interpret() -> bool:
    """The tree-wide interpret default: compiled on TPU, interpreted
    everywhere else (the 8-device CPU mesh the test suite runs on)."""
    return jax.default_backend() != "tpu"


#: kernel name -> {"compiled": n, "interpret": n} — how many times each
#: Pallas wrapper was TRACED in each mode this process (tracing happens
#: once per jit compile, persistent-cache hits included). The apps
#: print it and write it to ``--log`` so a run can prove which mode
#: reached every kernel instead of inferring it from the platform.
_KERNEL_MODES: dict[str, dict[str, int]] = {}


def resolve_interpret(interpret: bool | None, kernel: str) -> bool:
    """Resolve a wrapper's ``interpret=None`` through
    :func:`default_interpret` and record the mode ``kernel`` was traced
    in — the single place a Pallas wrapper decides compiled vs
    interpreted."""
    if interpret is None:
        interpret = default_interpret()
    modes = _KERNEL_MODES.setdefault(kernel, {"compiled": 0, "interpret": 0})
    modes["interpret" if interpret else "compiled"] += 1
    return interpret


def kernel_modes() -> dict[str, dict[str, int]]:
    """Snapshot of the per-kernel trace-mode counts."""
    return {k: dict(v) for k, v in sorted(_KERNEL_MODES.items())}


def live_rows(active, n: int):
    """``(rows, count)`` for a kernel whose grid walks the live rows only:
    the indices where ``active`` ((n,) bool) holds, first, as (n,) int32
    for scalar prefetch (the tail is 0: never visited), and how many they
    are, the grid's extent. ``None`` is every row, a static count. The
    same ``active`` gives the same operations wherever it is asked, so
    XLA keeps one list a step however many layers ask."""
    if active is None:
        return jnp.arange(n, dtype=jnp.int32), n
    rows = jnp.nonzero(active, size=n, fill_value=0)[0].astype(jnp.int32)
    return rows, jnp.sum(active, dtype=jnp.int32)


def fit_block_divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= ``cap``: an off-size length
    gets a smaller even tile instead of a raw ValueError mid-trace.
    Always succeeds (1 divides everything; tiny blocks are slow, not
    wrong — Mosaic pads unaligned tiles). The fused-MLP fitting rule."""
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


def fit_block_pow2(block: int, n: int, *, floor: int = 128) -> int:
    """Clamp ``block`` to ``n`` and halve until it divides, floored at
    ``floor`` (the TPU lane width — smaller blocks would break tiling
    and waste the MXU). Lengths that no floor-multiple divides still
    fail the caller's validation — pad upstream. The flash-attention
    fitting rule (streamed kernels want big blocks; grid-step overhead
    amortizes over them)."""
    block = min(block, n)
    while n % block and block >= 2 * floor:
        block //= 2
    return block
