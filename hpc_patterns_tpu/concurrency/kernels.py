"""Pallas compute kernels for the concurrency suite.

The reference's compute command is ``busy_wait`` (sycl_con.cpp:26-33): a
parallel_for where every work-item runs ``64 * tripcount`` dependent FMAs
— pure ALU work with a tunable duration and a checkable result. The TPU
rebuild keeps both properties:

- duration ∝ ``tripcount``, passed as a *runtime* scalar (SMEM) so the
  autotuner (C12) can re-balance without recompiling;
- a dependent FMA chain on the VPU (8×128 lanes), so XLA cannot fold the
  loop away and the kernel occupies the compute unit while DMAs fly.

On non-TPU backends the same kernel runs through the Pallas interpreter,
so tests exercise the identical code path on the CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hpc_patterns_tpu.ops.tiling import resolve_interpret

# FMAs per work-item per trip, matching the reference's unrolled factor 64
# (sycl_con.cpp:29-31: eight outer * eight inner in the original).
FMA_UNROLL = 8


def _busy_wait_kernel(trip_ref, x_ref, o_ref):
    trips = trip_ref[0]

    def body(_, acc):
        # Dependent multiply-adds: each feeds the next, so the chain
        # cannot be vectorized away across iterations; constants keep the
        # value bounded (fixed point of a*c1+c2 is ~ -c2/(c1-1) ~ 5e6).
        for _ in range(FMA_UNROLL):
            acc = acc * jnp.float32(0.9999999) + jnp.float32(0.5)
        return acc

    o_ref[:] = lax.fori_loop(0, trips, body, x_ref[:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _busy_wait_call(x, tripcount, *, interpret=False):
    # tripcount arrives as a raw host scalar and is wrapped to its
    # (1,) SMEM shape HERE, under the trace — wrapping at the call
    # site (`jnp.int32(tripcount)`, the pre-jaxlint form) was an extra
    # eager dispatch on the submit path per command
    return pl.pallas_call(
        _busy_wait_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(jnp.asarray(tripcount, jnp.int32).reshape(1), x)


def busy_wait(x, tripcount, *, interpret: bool | None = None):
    """Run the busy-wait chain over ``x`` for ``tripcount`` trips.

    ``x`` must be float32 with a TPU-tileable trailing shape (pad to
    (8k, 128) — see :func:`compute_buffer`). ``tripcount`` is a runtime
    scalar: changing it does NOT recompile (the reference re-runs its
    autotuner the same way, sycl_con.cpp:257-268).
    """
    return _busy_wait_call(
        x, tripcount, interpret=resolve_interpret(interpret, "busy_wait"))


def compute_buffer(n_elements: int, device=None):
    """A VMEM-friendly float32 buffer of >= ``n_elements``, shaped
    (rows, 128) with rows a multiple of 8 (the float32 min tile).

    The analog of the compute command's ``malloc_device`` buffer
    (sycl_con.cpp:64-73); the reference sizes it by the device's first
    sub-group size (:168-172) — the TPU natural unit is one (8, 128)
    vector register tile.
    """
    rows = max(8, -(-n_elements // 128))
    rows += (-rows) % 8
    x = jnp.zeros((rows, 128), jnp.float32)
    if device is not None:
        x = jax.device_put(x, device)
    return jax.block_until_ready(x)


def busy_wait_reference(x, tripcount):
    """Pure-jnp oracle for tests: same recurrence, no Pallas."""
    acc = jnp.asarray(x, jnp.float32)
    for _ in range(int(tripcount) * FMA_UNROLL):
        acc = acc * jnp.float32(0.9999999) + jnp.float32(0.5)
    return acc
