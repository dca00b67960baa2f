"""Timing protocol: warm-up + min-over-repetitions wall clock.

Reproduces the reference's measurement protocol (SURVEY.md section 6):
- min over N repetitions (sycl_con.cpp:114, default 10 at :182;
  NUM_REPETION 2 in omp_con.cpp:22) as the noise-control estimator;
- "best theoretical serial" = sum of per-command minima
  (sycl_con.cpp:117-119);
- per-rank wall clock, MAX-reduced across ranks for distributed runs
  (allreduce-mpi-sycl.cpp:188-190) — here :func:`max_across_processes`.

TPU-specific addition the reference didn't need: the first call under jit
pays XLA compilation (~seconds), so measurement *must* warm up first and
block on dispatch (`jax.block_until_ready`) — SURVEY.md section 7 "hard
parts" (d).

When the native extension is built (native/hpcpat.cpp), the min/mean/std
reduction runs in C++; the pure-Python fallback is numerically identical.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax

from hpc_patterns_tpu.analysis import runtime as _runtimelib
from hpc_patterns_tpu.harness import chaos as chaoslib
from hpc_patterns_tpu.harness import metrics as metricslib


@dataclasses.dataclass(frozen=True)
class TimingResult:
    times_s: tuple[float, ...]

    @property
    def min_s(self) -> float:
        return min(self.times_s)

    @property
    def mean_s(self) -> float:
        return sum(self.times_s) / len(self.times_s)

    @property
    def max_s(self) -> float:
        return max(self.times_s)

    def bandwidth_gbps(self, nbytes: int) -> float:
        return bandwidth_gbps(nbytes, self.min_s)


def bandwidth_gbps(nbytes: int, seconds: float) -> float:
    if seconds <= 0:
        return float("inf")
    return nbytes / seconds / 1e9


def measure(
    fn: Callable[[], object],
    *,
    repetitions: int = 10,
    warmup: int = 1,
    label: str = "measure",
) -> TimingResult:
    """Time ``fn`` with the reference's protocol: ``warmup`` untimed calls
    (absorbing XLA compilation), then ``repetitions`` timed calls; the
    caller consumes :attr:`TimingResult.min_s`.

    ``fn`` must block until its device work completes; wrap JAX work so it
    ends in ``jax.block_until_ready``. Use :func:`blocking` for that.

    With the metrics registry enabled (``--metrics``), the warmup and
    timed phases become ``<label>.warmup`` / ``<label>.timed`` spans and
    every repetition lands in the ``<label>.rep_s`` histogram — the
    per-phase attribution that separates compile-absorbing warmup from
    the numbers a verdict consumes. With a flight recorder installed
    (``--trace``), each timed repetition additionally lands as a
    ``<label>`` dispatch→completion window on the device track carrying
    its ``seq`` index: in a multi-process launch every rank times the
    same repetitions, so the cross-rank merge (harness/collect.py) can
    match rank A's rep k against rank B's rep k and draw the skew fan.
    Disabled (the default), this is the identical code path as always:
    no spans, no records, no extra work.

    Chaos (harness/chaos.py): each timed repetition probes the
    ``collective`` injection site at its ``seq`` index — the timed rep
    IS the collective loop of the launched benchmarks (the same
    identification PR 5 made for the skew fan), so a seeded straggler
    rank is late in exactly the windows the cross-rank merge measures.
    One cached-config read per rep when no chaos is active.
    """
    if repetitions < 1:
        raise ValueError("repetitions must be >= 1")
    chaos_on = chaoslib.active() is not None
    m = metricslib.get_metrics()
    # the instrumented path also engages when a flight recorder is
    # installed (--trace): the warmup/timed spans then land on the
    # timeline even without --metrics (the histogram writes stay no-ops)
    if not (m.enabled or m.mirror_traces
            or metricslib._trace_sink is not None):
        for _ in range(warmup):
            fn()
        times = []
        for seq in range(repetitions):
            if chaos_on:
                chaoslib.maybe_inject("collective", seq)
                with chaoslib.suppress("collective"):
                    t0 = time.perf_counter()
                    fn()
                    times.append(time.perf_counter() - t0)
            else:
                t0 = time.perf_counter()
                fn()
                times.append(time.perf_counter() - t0)
        return TimingResult(tuple(_native_identity(times)))
    from hpc_patterns_tpu.harness import trace as tracelib

    rec = tracelib.active()
    with m.span(f"{label}.warmup", repetitions=warmup):
        for _ in range(warmup):
            fn()
    hist = m.histogram(f"{label}.rep_s")
    times = []
    with m.span(f"{label}.timed", repetitions=repetitions):
        for seq in range(repetitions):
            if chaos_on:
                # the straggler site: inject BEFORE the dispatch marker
                # so the delayed rank's window STARTS late — the shape
                # a genuinely slow rank has in the skew fan
                chaoslib.maybe_inject("collective", seq)
            if rec is not None:
                # fingerprint the rep into the per-rank schedule hash
                # chain (analysis/runtime.py) BEFORE dispatching: every
                # rank times the same repetitions, so the chains match
                # iff the rank schedules did — and a rank that hangs
                # inside rep k has already persisted k to the launcher
                # (the recorder-gated path keeps untraced timing loops
                # byte-identical)
                _runtimelib.record_collective(label, seq)
                t_disp = rec.mark_dispatch(label, args={"seq": seq})
            t0 = time.perf_counter()
            if chaos_on:
                # the rep owns the collective site: an eager collective
                # inside fn() must not re-inject the same fault
                with chaoslib.suppress("collective"):
                    fn()
            else:
                fn()  # blocking by contract: completion, not dispatch
            dt = time.perf_counter() - t0
            if rec is not None:
                rec.mark_complete(label, t_disp, args={"seq": seq})
            hist.observe(dt)
            times.append(dt)
    return TimingResult(tuple(_native_identity(times)))


def _native_identity(times: Sequence[float]) -> Sequence[float]:
    """Round-trip the samples through the native stats engine when it is
    available, so the C++ path is exercised everywhere timing is used."""
    try:
        from hpc_patterns_tpu.interop import native

        if native.available():
            return native.stats_roundtrip(times)
    except Exception:
        pass
    return times


def blocking(fn: Callable[..., object], *args, **kwargs) -> Callable[[], object]:
    """Wrap a JAX computation into a zero-arg blocking thunk for measure()."""

    def thunk():
        return jax.block_until_ready(fn(*args, **kwargs))

    return thunk


def measure_forced(
    fn: Callable[[], object],
    *,
    repetitions: int = 5,
    warmup: int = 1,
    label: str = "measure_forced",
) -> TimingResult:
    """Like :func:`measure`, but forces completion by reading the result
    back to the host (``np.asarray``).

    The host readback is the completion fence: the value cannot reach
    the host before every op it depends on has run, whatever the
    client's ``block_until_ready`` resolves on. ``fn`` must return the
    array whose value depends on all timed work.
    """
    import numpy as np

    def forced():
        np.asarray(fn())

    return measure(forced, repetitions=repetitions, warmup=warmup,
                   label=label)


def amortized_seconds(
    run_with_iters: Callable[[int], object],
    *,
    iters: int = 64,
    repetitions: int = 5,
    warmup: int = 1,
    base_iters: int = 1,
    label: str = "amortized",
) -> float:
    """Per-iteration device time via differencing: run the workload with
    ``iters`` internal repetitions and with ``base_iters``, both
    completion-forced, and return ``(t_iters - t_base) / (iters - base)``.

    This cancels dispatch/readback latency and any per-call constant,
    leaving pure steady-state device time — the reference's min-of-reps
    protocol for work so short that wall-clocking a single dispatch
    measures the dispatch.
    ``run_with_iters(n)`` must return an array depending on all n
    iterations (e.g. a Pallas kernel looping n passes internally).

    The default ``base_iters=1`` suits fast per-iteration work; when
    dispatch-latency *variance* rivals the difference being measured,
    pick a large base (e.g. ``iters // 2``) so
    both timed calls are device-time-dominated and the noise divides by a
    large (iters - base).
    """
    if iters < 2:
        raise ValueError("iters must be >= 2")
    if not 1 <= base_iters < iters:
        raise ValueError(f"need 1 <= base_iters < iters, got {base_iters}")
    t_many = measure_forced(
        lambda: run_with_iters(iters), repetitions=repetitions, warmup=warmup,
        label=f"{label}.many",
    ).min_s
    t_base = measure_forced(
        lambda: run_with_iters(base_iters), repetitions=repetitions,
        warmup=warmup, label=f"{label}.base",
    ).min_s
    return max(t_many - t_base, 0.0) / (iters - base_iters)


def max_across_processes(seconds: float) -> float:
    """Cross-process MAX of a local elapsed time, the distributed timing
    convention of allreduce-mpi-sycl.cpp:188-190 (MPI_Allreduce(MAX)).

    Single-process (the common JAX SPMD case: one process drives all local
    devices) returns the input unchanged.
    """
    if jax.process_count() == 1:
        return seconds
    import numpy as np
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(np.float64(seconds))
    return float(np.max(gathered))
