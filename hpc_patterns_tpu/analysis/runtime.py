"""Runtime complements to the static rules: donation poisoning, the
collective schedule verifier, and the strict-semaphore interpret shim.

Three helpers live here, each the belt-and-braces RUNTIME check behind
a static rule family:

**Donation poisoning** (:func:`poison_donated`, behind
``donation-alias``). The hazard (round 6's "poisoned cache"): on CPU a
freshly-built executable often does NOT honor a donation, so a
zero-copy host view of a donated input keeps reading stable values and
the bug passes every test — until a cache-loaded (or TPU) executable
honors the donation and mutates the view in place, corrupting whatever
bookkeeping was built on it. ``poison_donated`` removes the luck: it
wraps a jitted function and, after each call completes, overwrites
every donated input buffer that the executable did NOT alias into an
output with a sentinel byte pattern. Wiring: ``tests/conftest.py``
installs the wrappers around the serving engine's jitted entry points
for ``tests/test_serving.py`` (always) and for the whole suite under
``HPC_PATTERNS_POISON_DONATED=1``.

**Collective schedule verification** (:class:`CollectiveSchedule`,
behind ``collective-divergence``/``collective-order``). The hazard is
the reference suite's silent MPI deadlock: SPMD ranks disagreeing on
which collective comes next hang with no error. Statically the
shardlint rules forbid the divergence-shaped code; at runtime every
eager ``Communicator`` collective (and every recorder-traced
``harness.timing.measure`` repetition) is fingerprinted into a
per-rank hash chain over ``(op, seq, shape, dtype, axis)``. The
running digest is stamped into flight-recorder snapshots
(``harness/trace.py``) and cross-checked at merge time
(``harness/collect.py``): equal digests PROVE the rank schedules
matched; on mismatch the merge names the first divergent
``(rank, op, seq)``. Under ``apps/launch.py`` the chain additionally
persists a tiny per-rank progress file on every record, so a TIMED-OUT
rank's position is readable post-mortem — a hang reads as "rank 2 is
at allreduce#17, rank 0 at sendrecv_ring#17" instead of a bare timeout.

**Strict semaphores** (:func:`strict_semaphores`, behind
``dma-sem-balance``/``dma-slot-reuse``). The hazard is PR 8's
chip-only class: interpret mode serializes DMAs and leaves semaphores
inert, so a double-waited send sem or an undrained DMA passes every
CPU test and deadlocks on silicon. Under the shim, every
``make_async_copy``/``make_async_remote_copy`` built while a
``pallas_call`` kernel body traces is counted — starts and waits per
semaphore channel, plus per-descriptor wait multiplicity — and the
ledger must balance exactly at kernel-body exit or the TEST fails
(:class:`SemaphoreBalanceError`), not the chip session. Wiring:
``tests/test_fused_comm.py`` installs it module-wide, so the whole
fused parity battery re-proves the sync protocol on every run.

This module is import-light on purpose (stdlib only; jax is imported
inside the poison helpers): the schedule verifier must be usable from
jax-free launcher children and from ``harness/trace.py``, whose
disabled path stays jax-free at import time.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import json
import os
import threading
from collections import deque

#: sentinel byte: 0xAB patterns decode to huge-magnitude garbage in
#: every dtype we serve (int32 -1414812757, implausible floats), so a
#: poisoned read corrupts comparisons instead of looking plausible
SENTINEL_BYTE = 0xAB

#: env names mirroring ``topology.ENV_TRACE_DIR`` / ``ENV_PROCESS_ID``
#: — duplicated as literals so this module stays importable without
#: jax (topology imports jax at module scope); tests assert the pair
#: stays in sync with topology's constants.
ENV_TRACE_DIR = "HPCPAT_TRACE_DIR"
ENV_PROCESS_ID = "HPCPAT_PROCESS_ID"

#: chain entries retained per process (the digest always covers the
#: FULL history; the window only bounds what a snapshot can name)
SCHEDULE_WINDOW = 4096


# ---------------------------------------------------------------------------
# collective schedule verifier
# ---------------------------------------------------------------------------


class CollectiveSchedule:
    """Per-rank hash chain over collective fingerprints.

    ``record(op, seq, ...)`` folds one fingerprint into the running
    digest: ``digest_k = H(digest_{k-1} | op | seq | shape | dtype |
    axis)``. Two ranks of an SPMD program that issued the identical
    collective sequence therefore hold the identical digest — one
    string comparison at merge time proves N whole schedules matched —
    while the retained entry window lets a mismatch be localized to
    the first divergent ``(op, seq)``.
    """

    def __init__(self, *, window: int = SCHEDULE_WINDOW):
        self._lock = threading.Lock()
        self.window = window
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.n = 0
            self.digest = ""
            self.entries: deque = deque(maxlen=self.window)

    def record(self, op: str, seq: int, *, shape=None, dtype=None,
               axis=None, algorithm=None) -> dict:
        # ``algorithm`` joined the fingerprint with the fused-collective
        # route (PR 8): a rank running the host-driven path while its
        # peers run the in-kernel ring is a schedule divergence even
        # when (op, seq, shape) agree — the wire protocols differ.
        fp = (f"{op}|{int(seq)}|{tuple(shape) if shape is not None else ()}"
              f"|{dtype or ''}|{axis or ''}|{algorithm or ''}")
        with self._lock:
            digest = hashlib.sha256(
                f"{self.digest}\x1f{fp}".encode()).hexdigest()[:16]
            entry = {
                "i": self.n, "op": str(op), "seq": int(seq),
                "shape": list(shape) if shape is not None else None,
                "dtype": str(dtype) if dtype is not None else None,
                "axis": str(axis) if axis is not None else None,
                "algorithm": (str(algorithm) if algorithm is not None
                              else None),
                "digest": digest,
            }
            self.digest = digest
            self.entries.append(entry)
            self.n += 1
        return entry

    @property
    def last(self) -> dict | None:
        return self.entries[-1] if self.entries else None

    def snapshot(self) -> dict:
        """JSON-able chain state — the ``collectives`` field of a
        flight-recorder snapshot (``harness/trace.py``), cross-checked
        rank-against-rank by ``harness/collect.py``."""
        with self._lock:
            return {
                "n": self.n,
                "digest": self.digest,
                "window": self.window,
                "entries": [dict(e) for e in self.entries],
            }


_schedule = CollectiveSchedule()


def collective_schedule() -> CollectiveSchedule:
    """The process-wide chain (one per rank in a launch)."""
    return _schedule


def reset_collective_schedule() -> None:
    """Fresh chain — ``harness.trace.configure`` calls this so every
    instrumented run's chain starts at the same genesis on every rank."""
    _schedule.reset()


def _progress_path(trace_dir: str, process_id: int) -> str:
    return os.path.join(trace_dir, f"rank{process_id:05d}.sched.json")


def record_collective(op: str, seq: int, *, shape=None, dtype=None,
                      axis=None, algorithm=None) -> dict:
    """Fingerprint one collective into the process chain.

    Called at ISSUE time (before the wait): ``comm/communicator.py``
    records every eager collective — host-driven AND fused-kernel
    routes, with ``algorithm`` in the fingerprint so the fast path is
    never invisible to the verifier — and ``harness/timing.py`` every
    traced timed repetition. Under a launcher (``HPCPAT_TRACE_DIR``
    exported by ``apps/launch.py --trace-out``) each record also
    persists the chain head to ``rank<id>.sched.json`` — that write is
    what makes a HUNG rank diagnosable: the rank never reaches its
    trace-snapshot handoff, but the collective it is stuck in is
    already on disk for the launcher's timeout report."""
    entry = _schedule.record(op, seq, shape=shape, dtype=dtype, axis=axis,
                             algorithm=algorithm)
    trace_dir = os.environ.get(ENV_TRACE_DIR)
    if trace_dir:
        try:
            pid = int(os.environ.get(ENV_PROCESS_ID) or 0)
        except ValueError:
            pid = 0
        # payload built from THIS call's entry (not a re-read of the
        # shared chain head): concurrent recorders each write a
        # self-consistent (last, n, digest) triple
        payload = {
            "process_id": pid,
            "n": entry["i"] + 1,
            "digest": entry["digest"],
            "last": {"i": entry["i"], "op": entry["op"],
                     "seq": entry["seq"]},
        }
        path = _progress_path(trace_dir, pid)
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        try:
            # write-then-rename: a rank killed mid-write (the timeout
            # path's proc.kill()) must not leave a truncated file —
            # the straggler whose position the hang report exists to
            # print is exactly the rank most likely to die mid-write
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
        except OSError:
            pass  # forensics are best-effort; never fail the collective
    return entry


# ---------------------------------------------------------------------------
# donation poisoning
# ---------------------------------------------------------------------------


def _buffer_ptrs(leaf) -> list[tuple[int, int]]:
    """(pointer, nbytes) per addressable shard; [] when the backend
    hides them (the helper is then inert, never wrong)."""
    out = []
    try:
        for shard in leaf.addressable_shards:
            db = shard.data
            out.append((db.unsafe_buffer_pointer(), db.nbytes))
    except Exception:  # noqa: BLE001 - best-effort probe
        return []
    return out


def poison_donated(fn, donate_argnums, *, sentinel: int = SENTINEL_BYTE):
    """Wrap jitted ``fn`` so donated inputs die loudly after each call.

    After ``fn(*args)`` completes (outputs blocked on), every jax leaf
    of each ``args[i]`` for ``i in donate_argnums`` is overwritten with
    ``sentinel`` bytes — unless the executable aliased that buffer into
    an output (donation honored: poisoning would corrupt the result;
    the aliasing itself already invalidates stale host views) or jax
    deleted it. The wrapper forwards ``__wrapped__``, so
    ``harness.trace.jit_cache_size`` / ``compile_watch`` (and through
    them ``serving.prefill_cache_size``) keep probing the real jit.

    ``wrapper.poison_count`` accumulates poisoned buffers — tests
    assert on it to prove the hook engaged rather than silently
    no-op'ing.
    """
    donate_argnums = tuple(donate_argnums)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        import jax

        out = fn(*args, **kwargs)
        leaves_out = jax.tree_util.tree_leaves(out)
        for leaf in leaves_out:
            jax.block_until_ready(leaf)
        out_ptrs = {
            ptr
            for leaf in leaves_out
            if isinstance(leaf, jax.Array)
            for ptr, _ in _buffer_ptrs(leaf)
        }
        for i in donate_argnums:
            if i >= len(args):
                continue
            for leaf in jax.tree_util.tree_leaves(args[i]):
                if not isinstance(leaf, jax.Array):
                    continue
                try:
                    if leaf.is_deleted():
                        continue
                except Exception:  # noqa: BLE001
                    continue
                for ptr, nbytes in _buffer_ptrs(leaf):
                    if ptr in out_ptrs or nbytes == 0:
                        continue
                    ctypes.memset(ptr, sentinel, nbytes)
                    wrapper.poison_count += 1
        return out

    wrapper.poison_count = 0
    # functools.wraps already set __wrapped__ = fn; make the contract
    # explicit since the trace probe depends on it
    wrapper.__wrapped__ = fn
    return wrapper


#: the serving engine's donating jit entry points and their donated
#: positions — MUST mirror the donate_argnums in models/serving.py
#: (tests/test_analysis.py asserts they stay in sync)
SERVING_POISON_TARGETS: dict[str, tuple[int, ...]] = {
    "_chunk_step": (1, 2, 3, 4, 5),
    "_spec_chunk": (2, 3, 4, 5, 6, 7),
    "_prefill_one": (3,),
    "_admit_row": (0, 1, 2, 3, 4, 11),
    # the serving plane's KV-handoff install scatter (round 10): the
    # pool is donated — an aliased host view of it would be the exact
    # PR 2 bug class resurfacing on the migration path
    "_install_pages": (0,),
    # the prefix-sharing tail prefill (round 12): donates the pool like
    # _prefill_one — an aliased view of a SHARED page would corrupt
    # every reader at once, so the poison harness must cover it
    "_tail_prefill_one": (3,),
}


# ---------------------------------------------------------------------------
# strict-semaphore interpret shim
# ---------------------------------------------------------------------------


class SemaphoreBalanceError(AssertionError):
    """A kernel's DMA semaphore ledger failed to balance: a descriptor
    waited twice on one channel, or starts != waits at kernel exit.
    In interpret mode this is invisible (semaphores are inert
    arithmetic); on chip it is a deadlock or a race."""


class _KernelFrame:
    """Per-kernel-trace DMA accounting."""

    def __init__(self, name: str):
        self.name = name
        self.remote_starts = 0
        self.local_starts = 0
        self.send_waits = 0
        self.recv_waits = 0
        self.local_waits = 0
        # best-effort per-semaphore-slot counts: key -> [starts, waits]
        self.per_key: dict = {}
        self.keyed_ok = True

    def key_count(self, key, slot: int, delta: int) -> None:
        if key is None:
            self.keyed_ok = False
            return
        entry = self.per_key.setdefault(key, [0, 0])
        entry[slot] += delta

    def check(self) -> None:
        problems = []
        if self.remote_starts != self.send_waits:
            problems.append(
                f"{self.remote_starts} remote start(s) vs "
                f"{self.send_waits} send wait(s)")
        if self.remote_starts != self.recv_waits:
            problems.append(
                f"{self.remote_starts} remote start(s) vs "
                f"{self.recv_waits} recv wait(s)")
        if self.local_starts != self.local_waits:
            problems.append(
                f"{self.local_starts} local start(s) vs "
                f"{self.local_waits} wait(s)")
        if self.keyed_ok:
            for key, (starts, waits) in sorted(self.per_key.items()):
                if starts != waits:
                    problems.append(
                        f"sem {key}: {starts} signal(s), "
                        f"{waits} wait(s)")
        if problems:
            raise SemaphoreBalanceError(
                f"kernel {self.name!r}: DMA semaphore ledger did not "
                f"balance at kernel exit — " + "; ".join(problems)
                + ". Interpret mode hides this (semaphores are "
                "inert); on chip it deadlocks or races.")


def _sem_fingerprint(sem) -> tuple | None:
    """Best-effort stable identity for a semaphore operand at trace
    time: (base ref id, transform repr). None when the structure is
    unrecognizable — the ledger then falls back to channel totals."""
    try:
        base = getattr(sem, "ref", sem)
        transforms = getattr(sem, "transforms", ())
        return (id(base), str(transforms))
    except Exception:  # noqa: BLE001 - defensive: jax internals move
        return None


class _CountedDMA:
    """Proxy over a pallas async-copy descriptor: forwards everything,
    counts starts/waits, and fails fast on a per-descriptor
    double-wait (the PR 8 drain bug's exact shape)."""

    def __init__(self, real, frame: _KernelFrame, remote: bool,
                 send_key, recv_key):
        self._real = real
        self._frame = frame
        self._remote = remote
        self._send_key = send_key
        self._recv_key = recv_key
        self._send_waits = 0
        self._recv_waits = 0

    def start(self, *args, **kwargs):
        f = self._frame
        if self._remote:
            f.remote_starts += 1
            f.key_count(self._send_key, 0, 1)
            f.key_count(self._recv_key, 0, 1)
        else:
            f.local_starts += 1
            f.key_count(self._recv_key, 0, 1)
        return self._real.start(*args, **kwargs)

    def _count_wait(self, channel: str):
        f = self._frame
        if channel == "send":
            self._send_waits += 1
            f.send_waits += 1
            f.key_count(self._send_key, 1, 1)
            if self._send_waits > 1:
                raise SemaphoreBalanceError(
                    f"kernel {f.name!r}: descriptor send semaphore "
                    f"waited {self._send_waits} times — one signal "
                    f"per DMA; the second wait deadlocks on chip "
                    f"(the PR 8 drain double-wait)")
        else:
            self._recv_waits += 1
            if self._remote:
                f.recv_waits += 1
            else:
                f.local_waits += 1
            f.key_count(self._recv_key, 1, 1)
            if self._recv_waits > 1:
                raise SemaphoreBalanceError(
                    f"kernel {f.name!r}: descriptor recv semaphore "
                    f"waited {self._recv_waits} times — one signal "
                    f"per DMA; the second wait deadlocks on chip")

    def wait(self, *args, **kwargs):
        if self._remote:
            self._count_wait("send")
        self._count_wait("recv")
        return self._real.wait(*args, **kwargs)

    def wait_send(self, *args, **kwargs):
        self._count_wait("send")
        return self._real.wait_send(*args, **kwargs)

    def wait_recv(self, *args, **kwargs):
        self._count_wait("recv")
        return self._real.wait_recv(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._real, name)


class StrictSemaphores:
    """Context manager installing the strict-semaphore shim (module
    docstring). ``kernels_checked`` counts kernel traces that carried
    DMA activity — tests assert it is nonzero so the shim provably
    engaged (an already-warm trace cache would otherwise skip every
    kernel body; pair with ``jax.clear_caches()``)."""

    def __init__(self):
        self.kernels_checked = 0
        self._frames: list[_KernelFrame] = []
        self._originals: list[tuple] = []

    # -- patch targets ---------------------------------------------------

    def __enter__(self):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        shim = self

        real_local = pltpu.make_async_copy
        real_remote = pltpu.make_async_remote_copy
        real_call = pl.pallas_call

        def counted_local(*args, **kwargs):
            real = real_local(*args, **kwargs)
            frame = shim._frames[-1] if shim._frames else None
            if frame is None:
                return real
            sem = kwargs.get("sem", args[2] if len(args) > 2 else None)
            return _CountedDMA(real, frame, remote=False,
                               send_key=None,
                               recv_key=_sem_fingerprint(sem))

        def counted_remote(*args, **kwargs):
            real = real_remote(*args, **kwargs)
            frame = shim._frames[-1] if shim._frames else None
            if frame is None:
                return real
            send = kwargs.get("send_sem",
                              args[2] if len(args) > 2 else None)
            recv = kwargs.get("recv_sem",
                              args[3] if len(args) > 3 else None)
            return _CountedDMA(real, frame, remote=True,
                               send_key=_sem_fingerprint(send),
                               recv_key=_sem_fingerprint(recv))

        def checked_call(kernel, *args, **kwargs):
            if not callable(kernel):  # pragma: no cover - defensive
                return real_call(kernel, *args, **kwargs)
            name = getattr(kernel, "__name__", None) or getattr(
                getattr(kernel, "func", None), "__name__", "kernel")

            @functools.wraps(kernel if hasattr(kernel, "__name__")
                             else (lambda: None))
            def body(*refs, **kw):
                frame = _KernelFrame(name)
                shim._frames.append(frame)
                try:
                    out = kernel(*refs, **kw)
                finally:
                    shim._frames.pop()
                # balance asserted on the SUCCESS path only: an
                # exception unwinding through the body must surface
                # itself, not a secondary ledger complaint
                if (frame.remote_starts or frame.local_starts
                        or frame.send_waits or frame.recv_waits
                        or frame.local_waits):
                    shim.kernels_checked += 1
                    frame.check()
                return out

            return real_call(body, *args, **kwargs)

        self._originals = [
            (pltpu, "make_async_copy", real_local),
            (pltpu, "make_async_remote_copy", real_remote),
            (pl, "pallas_call", real_call),
        ]
        pltpu.make_async_copy = counted_local
        pltpu.make_async_remote_copy = counted_remote
        pl.pallas_call = checked_call
        return self

    def __exit__(self, *exc):
        for obj, attr, original in self._originals:
            setattr(obj, attr, original)
        self._originals = []
        return False


def strict_semaphores() -> StrictSemaphores:
    """The strict-semaphore interpret shim as a context manager::

        with strict_semaphores() as ledger:
            jax.clear_caches()       # force kernel re-traces
            run_the_parity_battery()
        assert ledger.kernels_checked > 0

    Every kernel body traced inside the context has its DMA semaphore
    ledger balance-checked at exit; imbalance raises
    :class:`SemaphoreBalanceError` in the TEST, not on the chip."""
    return StrictSemaphores()


def install_serving_poison():
    """Swap the serving module's jitted entry points for poisoned
    wrappers; returns an ``uninstall()`` restoring the originals.
    Import stays local so merely importing this module never drags the
    models package in."""
    from hpc_patterns_tpu.models import serving

    originals = {}
    for name, argnums in SERVING_POISON_TARGETS.items():
        originals[name] = getattr(serving, name)
        setattr(serving, name, poison_donated(originals[name], argnums))

    def uninstall():
        for name, fn in originals.items():
            setattr(serving, name, fn)

    return uninstall
