"""On-chip DMA/compute overlap benchmark (the Pallas heart of C1).

The reference's concurrency suite asks: do independent copy and compute
commands *actually overlap* on one device (sycl_con.cpp:84-115)? On TPU
the equivalent boundary is HBM↔VMEM DMA vs VPU compute inside a kernel
(SURVEY.md §2.2 "intra-device stream parallelism": Pallas double-buffered
DMA/compute overlap stands in for H2D/D2H-vs-kernel overlap), and —
unlike host wall-clock games — it is measurable honestly even through a
high-latency dispatch path, because the whole experiment is ONE kernel.

Modes, all computing a checksum over the same chunk-walk (the
correctness oracle where compute participates):

in-direction (HBM→VMEM ≙ M2D) vs compute:
- ``overlap``  — double-buffered: DMA of chunk i+1 in flight while the
  busy-wait chain runs on chunk i (the out-of-order-queue analog)
- ``serial``   — DMA chunk i, wait, compute chunk i (the reference's
  serial baseline, sycl_con.cpp:101-106)
- ``dma``      — in-DMAs only (per-command baseline for M2D)
- ``compute``  — busy-wait only (per-command baseline for C)

out-direction (VMEM→HBM ≙ D2M) vs compute:
- ``overlap_out`` — compute chunk i into a slot, start its writeback,
  only wait for that slot's previous writeback before reusing it
- ``serial_out``  — compute, write back, wait, every chunk
- ``dma_out``     — writebacks only (per-command baseline for D2M)

DMA vs DMA (≙ M2D + D2M concurrently, two DMA queues):
- ``pair_overlap`` — per chunk, start the in-copy and the out-copy
  together, then wait both
- ``pair_serial``  — in-copy start+wait, then out-copy start+wait

``tripcount`` (compute per chunk) and ``passes`` (repetitions over the
whole array, amortizing fixed overheads inside the kernel) are runtime
SMEM scalars, so the C12 autotuner balances DMA vs compute without
recompiles. Speedup/verdict math reuses the shared rules
(harness.verdict.concurrency_verdict).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hpc_patterns_tpu.concurrency.kernels import FMA_UNROLL
from hpc_patterns_tpu.ops.tiling import resolve_interpret

MODES = (
    "overlap", "serial", "dma", "compute", "compute2",
    "overlap_out", "serial_out", "dma_out",
    "pair_overlap", "pair_serial",
)
_OUT_BUF_MODES = ("overlap_out", "serial_out", "dma_out",
                  "pair_overlap", "pair_serial")


def _chain(acc, trips, salt):
    # ``salt`` (pass-index-derived) keeps every pass's chain distinct so
    # the compiler cannot hoist the loop body out of the pass loop.
    add = jnp.float32(0.5) + salt

    def body(_, a):
        for _ in range(FMA_UNROLL):
            a = a * jnp.float32(0.9999999) + add
        return a

    return lax.fori_loop(0, trips, body, acc)


def _make_in_kernel(mode: str, num_chunks: int):
    """in-direction modes: overlap | serial | dma | compute | compute2.

    ``compute2`` is the C+C pair: TWO independent busy-wait chains per
    chunk (one per scratch slot, distinct salts). They share the one
    sequential core, so per-pass time ≈ 2x a single chain at the SAME
    tripcount — which is what the resource-aware verdict floor expects.
    (Comparing one chain at 2x trips instead is not equivalent: per-trip
    cost is measurably nonlinear in tripcount on real chips.)"""
    do_dma = mode in ("overlap", "serial", "dma")
    do_compute = mode in ("overlap", "serial", "compute", "compute2")

    def kernel(scalar_ref, hbm_ref, out_ref):
        trips = scalar_ref[0]
        passes = scalar_ref[1]

        def body(scratch, sem):
            def get_dma(slot, chunk):
                return pltpu.make_async_copy(
                    hbm_ref.at[chunk], scratch.at[slot], sem.at[slot]
                )

            def one_pass(p, checksum):
                if mode == "overlap":
                    # warm-up DMA for this pass's first chunk
                    get_dma(0, 0).start()

                def chunk_step(i, csum):
                    slot = lax.rem(i, 2)
                    if mode == "overlap":

                        @pl.when(i + 1 < num_chunks)
                        def _():
                            get_dma(1 - slot, i + 1).start()

                        get_dma(slot, i).wait()
                    elif do_dma:
                        dma = get_dma(slot, i)
                        dma.start()
                        dma.wait()
                    if do_compute:
                        salt = (p * num_chunks + i).astype(jnp.float32) * jnp.float32(1e-7)
                        acc = _chain(scratch[slot], trips, salt)
                        # fold EVERY chunk into the checksum so the oracle
                        # (overlap == serial) covers every DMA'd block, not
                        # just the last one
                        csum = csum + acc[:8]
                        if mode == "compute2":
                            acc2 = _chain(scratch[1 - slot], trips,
                                          salt + jnp.float32(0.5))
                            csum = csum + acc2[:8]
                    return csum

                return lax.fori_loop(0, num_chunks, chunk_step, checksum)

            out_ref[:] = lax.fori_loop(
                0, passes, one_pass, jnp.zeros((8, 128), jnp.float32)
            )

        chunk_shape = hbm_ref.shape[1:]
        pl.run_scoped(
            body,
            scratch=pltpu.VMEM((2, *chunk_shape), jnp.float32),
            sem=pltpu.SemaphoreType.DMA((2,)),
        )

    return kernel


def _make_out_kernel(mode: str, num_chunks: int):
    """out-direction modes: overlap_out | serial_out | dma_out.
    The writeback (VMEM→HBM ≙ D2M) and the busy-wait chain are
    INDEPENDENT commands, exactly as in the reference (its copy and
    compute touch unrelated buffers): both read the seeded scratch slot,
    nothing writes it, so there is no hazard — ``overlap_out`` lets the
    writeback fly under the chunk's compute, ``serial_out`` waits it out
    first. Semaphore slots bound the queue to two in-flight writebacks."""
    do_compute = mode in ("overlap_out", "serial_out")

    def kernel(scalar_ref, hbm_ref, out_ref, hbm_out_ref):
        trips = scalar_ref[0]
        passes = scalar_ref[1]

        def body(scratch, sem):
            # deterministic seeds: the chain's input must not be whatever
            # the previous kernel left in VMEM, or the serial/overlap
            # checksum oracle can't hold
            scratch[0] = jnp.full(scratch.shape[1:], 0.25, jnp.float32)
            scratch[1] = jnp.full(scratch.shape[1:], 0.75, jnp.float32)

            def put_dma(slot, chunk):
                return pltpu.make_async_copy(
                    scratch.at[slot], hbm_out_ref.at[chunk], sem.at[slot]
                )

            def one_pass(p, checksum):
                def chunk_step(i, csum):
                    slot = lax.rem(i, 2)
                    if mode == "overlap_out":
                        # free this sem slot (DMA issued two chunks ago)
                        @pl.when(i >= 2)
                        def _():
                            put_dma(slot, i - 2).wait()
                    dma = put_dma(slot, i)
                    dma.start()
                    if mode != "overlap_out":
                        dma.wait()
                    if do_compute:
                        salt = (p * num_chunks + i).astype(jnp.float32) * jnp.float32(1e-7)
                        acc = _chain(scratch[slot], trips, salt)
                        csum = csum + acc[:8]
                    return csum

                csum = lax.fori_loop(0, num_chunks, chunk_step, checksum)
                if mode == "overlap_out":
                    # drain the last two in-flight writebacks
                    put_dma(lax.rem(num_chunks - 2, 2), num_chunks - 2).wait()
                    put_dma(lax.rem(num_chunks - 1, 2), num_chunks - 1).wait()
                return csum

            out_ref[:] = lax.fori_loop(
                0, passes, one_pass, jnp.zeros((8, 128), jnp.float32)
            )

        chunk_shape = hbm_ref.shape[1:]
        pl.run_scoped(
            body,
            scratch=pltpu.VMEM((2, *chunk_shape), jnp.float32),
            sem=pltpu.SemaphoreType.DMA((2,)),
        )

    return kernel


def _make_pair_kernel(mode: str, num_chunks: int):
    """pair modes: a copy-through — chunk i streams HBM→VMEM (≙ M2D),
    then VMEM→HBM (≙ D2M). ``pair_overlap`` pipelines the two directions
    across chunks (in-copy of i+1 flies while the out-copy of i drains,
    both DMA paths busy); ``pair_serial`` completes each copy before
    starting the next. Checksum reads every in-copied chunk."""

    def kernel(scalar_ref, hbm_ref, out_ref, hbm_out_ref):
        passes = scalar_ref[1]

        def body(scratch, sem_in, sem_out):
            def get_dma(slot, chunk):
                return pltpu.make_async_copy(
                    hbm_ref.at[chunk], scratch.at[slot], sem_in.at[slot]
                )

            def put_dma(slot, chunk):
                return pltpu.make_async_copy(
                    scratch.at[slot], hbm_out_ref.at[chunk], sem_out.at[slot]
                )

            def one_pass(p, checksum):
                if mode == "pair_overlap":
                    get_dma(0, 0).start()

                def chunk_step(i, csum):
                    slot = lax.rem(i, 2)
                    if mode == "pair_overlap":
                        # the out-copy of chunk i-1 reads slot 1-slot;
                        # it must land before in-copy i+1 overwrites it
                        @pl.when(i >= 1)
                        def _():
                            put_dma(1 - slot, i - 1).wait()

                        @pl.when(i + 1 < num_chunks)
                        def _():
                            get_dma(1 - slot, i + 1).start()

                        get_dma(slot, i).wait()
                        put_dma(slot, i).start()
                    else:
                        get = get_dma(slot, i)
                        get.start()
                        get.wait()
                        put = put_dma(slot, i)
                        put.start()
                        put.wait()
                    return csum + scratch[slot][:8]

                csum = lax.fori_loop(0, num_chunks, chunk_step, checksum)
                if mode == "pair_overlap":
                    put_dma(lax.rem(num_chunks - 1, 2), num_chunks - 1).wait()
                return csum

            out_ref[:] = lax.fori_loop(
                0, passes, one_pass, jnp.zeros((8, 128), jnp.float32)
            )

        chunk_shape = hbm_ref.shape[1:]
        pl.run_scoped(
            body,
            scratch=pltpu.VMEM((2, *chunk_shape), jnp.float32),
            sem_in=pltpu.SemaphoreType.DMA((2,)),
            sem_out=pltpu.SemaphoreType.DMA((2,)),
        )

    return kernel


def _make_kernel(mode: str, num_chunks: int):
    if mode in ("overlap", "serial", "dma", "compute", "compute2"):
        return _make_in_kernel(mode, num_chunks)
    if mode in ("overlap_out", "serial_out", "dma_out"):
        return _make_out_kernel(mode, num_chunks)
    return _make_pair_kernel(mode, num_chunks)


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def _run(hbm_array, tripcount, passes, *, mode: str, interpret: bool):
    num_chunks = hbm_array.shape[0]
    scalars = jnp.asarray([tripcount, passes], jnp.int32)
    out_shape = [jax.ShapeDtypeStruct((8, 128), jnp.float32)]
    out_specs = [pl.BlockSpec(memory_space=pltpu.VMEM)]
    if mode in _OUT_BUF_MODES:
        # writeback target stays in HBM; written only by manual DMA
        out_shape.append(
            jax.ShapeDtypeStruct(hbm_array.shape, hbm_array.dtype)
        )
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    results = pl.pallas_call(
        _make_kernel(mode, num_chunks),
        out_shape=tuple(out_shape),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),  # stays in HBM; DMA'd manually
        ],
        out_specs=tuple(out_specs),
        interpret=interpret,
    )(scalars, hbm_array)
    return results[0] if isinstance(results, (tuple, list)) else results


def overlap_run(
    hbm_array,
    *,
    mode: str,
    tripcount: int = 64,
    passes: int = 1,
    interpret: bool | None = None,
):
    """Run one variant over ``hbm_array`` of shape (num_chunks, rows, 128)
    float32; returns the (8, 128) checksum tile (identical across modes
    that compute — the oracle for tests)."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    interpret = resolve_interpret(interpret, f"overlap_pipeline.{mode}")
    if hbm_array.ndim != 3 or hbm_array.shape[2] != 128 or hbm_array.shape[1] % 8:
        raise ValueError(
            f"want (num_chunks, 8k rows, 128) float32, got {hbm_array.shape}"
        )
    if hbm_array.shape[0] < 2 and mode == "overlap_out":
        raise ValueError("overlap_out needs >= 2 chunks")
    return _run(
        hbm_array, jnp.int32(tripcount), jnp.int32(passes),
        mode=mode, interpret=interpret,
    )


def make_hbm_array(num_chunks: int = 64, chunk_rows: int = 512, seed: int = 0):
    """The HBM working set: (num_chunks, chunk_rows, 128) float32. Values
    in [0, 1) so the busy-wait chain stays bounded."""
    key = jax.random.PRNGKey(seed)
    return jax.random.uniform(
        key, (num_chunks, chunk_rows, 128), jnp.float32
    )


def per_pass_seconds(
    hbm_array,
    mode: str,
    tripcount: int,
    *,
    cal_passes: int = 1000,
    repetitions: int = 3,
    target_s: float = 1.0,
    max_passes: int = 120_000,
):
    """Steady-state seconds per pass of ``mode``, honest through
    high-latency dispatch: a differenced calibration pair sizes the
    measurement to ~``target_s`` of device time, then
    harness.timing.amortized_seconds differences two device-dominated
    pass counts so dispatch-latency jitter divides by tens of thousands
    of passes. The concurrency app's on-chip engine calls it
    (``apps/concurrency_app.py``)."""
    from hpc_patterns_tpu.harness.timing import amortized_seconds, measure_forced

    run = lambda p: overlap_run(hbm_array, mode=mode, tripcount=tripcount,
                                passes=p)
    t_two = measure_forced(lambda: run(2 * cal_passes), repetitions=1).min_s
    t_one = measure_forced(lambda: run(cal_passes), repetitions=1).min_s
    est = (t_two - t_one) / cal_passes
    if est <= 0:
        # noise ate the difference; the latency-biased single-call
        # estimate only shrinks the pass count, never the reading
        est = max(t_two / (2 * cal_passes), 1e-7)
    hi = int(min(max(target_s / est, 2 * cal_passes), max_passes))
    return amortized_seconds(run, iters=hi, repetitions=repetitions,
                             base_iters=hi // 2)


def balance_tripcount(per_pass, copy_time_s, compute_mode, trips, *,
                      max_trips=4096, rounds=2):
    """Refine ``trips`` until the compute chain's per-pass time matches
    ``copy_time_s`` (the C12 balance step, sycl_con.cpp:257-268 — linear
    T(trips), iterated because one probe's noise would leave the commands
    unbalanced). Returns ``(trips, t_compute)``, measured with
    ``per_pass(mode, trips)``. The concurrency app's on-chip engine
    calls it; ``tests/test_concurrency.py`` pins the clamp."""
    t_comp = per_pass(compute_mode, trips)
    for _ in range(rounds):
        if t_comp <= 0 or copy_time_s <= 0:
            break
        new_trips = min(max(1, int(trips * copy_time_s / t_comp)), max_trips)
        if abs(new_trips - trips) <= max(2, trips // 10):
            break
        trips = new_trips
        t_comp = per_pass(compute_mode, trips)
    return trips, t_comp
