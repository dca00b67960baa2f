"""Device-side zero-copy proofs: buffer aliasing ON the accelerator.

The host-side bridges (zero_copy.py) prove same-address-space sharing
between runtimes; this module proves the *device-context* leg the
reference demonstrates with OMP and SYCL kernels sharing one Level-Zero
context (interop_omp_ze_sycl.cpp:81-101): XLA writing a computation's
output INTO an existing device buffer with no copy —

- :func:`donation_alias_proof` — plain ``jit`` with ``donate_argnums``:
  the output reuses the input's HBM buffer;
- :func:`pallas_alias_proof` — a Pallas kernel with
  ``input_output_aliases={0: 0}``: the kernel's output ref IS the
  input's buffer (the in-place kernel form the reference's
  ``is_device_ptr`` OMP kernel takes, :95-99).

Proof forms, strongest available per backend:

1. **pointer identity** (``unsafe_buffer_pointer``) where the PJRT
   client exposes raw device pointers (the CPU client and the local
   TPU client both do);
2. **the compiled executable's aliasing contract** on a client that
   refuses raw pointers:
   ``memory_analysis().alias_size_in_bytes`` covering the entire
   output, the ``input_output_alias`` entry in the compiled HLO, and
   the donated input being invalidated by the run. This is the
   contract XLA *enforces* when it executes — a compiler guarantee,
   not a runtime sample.

Every proof also validates values (the reference's assert style).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def buffer_pointer(arr) -> int | None:
    """Raw device-buffer address, or None where the PJRT client
    refuses to expose one. On the ``tpu`` platform the local client
    exposes pointers, so a refusal there is an error, not a reason to
    accept the weaker proof."""
    buf = arr.addressable_shards[0].data
    try:
        return int(buf.unsafe_buffer_pointer())
    except Exception:  # noqa: BLE001 — client-specific refusal
        if next(iter(buf.devices())).platform == "tpu":
            raise
        return None


def _run_aliased(f, x):
    """Compile, extract the aliasing contract, run with donation, and
    collect every form of evidence available on this backend."""
    compiled = f.lower(x).compile()
    ma = compiled.memory_analysis()
    contract = dict(
        alias_bytes=int(ma.alias_size_in_bytes),
        output_bytes=int(ma.output_size_in_bytes),
        hlo_alias="input_output_alias={" in compiled.as_text(),
    )
    ptr_in = buffer_pointer(x)
    out = jax.block_until_ready(f(x))
    ptr_out = buffer_pointer(out)
    evidence = dict(
        contract,
        contract_ok=(
            contract["hlo_alias"]
            and contract["alias_bytes"] == contract["output_bytes"] > 0
        ),
        pointer_ok=(
            None if ptr_in is None or ptr_out is None else ptr_in == ptr_out
        ),
        input_invalidated=bool(x.is_deleted()),
    )
    return out, evidence


def donation_alias_proof(n: int = 1 << 14):
    """jit + donation writing in place: returns (ok, evidence dict).

    ok = values correct AND the donated input was consumed AND the
    strongest available aliasing evidence holds (pointer identity when
    readable, else the compiled aliasing contract).
    """
    x = jax.block_until_ready(jnp.full((n,), 2.0, jnp.float32))
    f = jax.jit(lambda v: v * 3 + 1, donate_argnums=0)
    out, ev = _run_aliased(f, x)
    values_ok = bool(jnp.all(out == 7.0).item())
    alias_ok = ev["pointer_ok"] if ev["pointer_ok"] is not None else ev["contract_ok"]
    ev["values_ok"] = values_ok
    return bool(values_ok and alias_ok and ev["input_invalidated"]), ev


def pallas_alias_proof(rows: int = 8, cols: int = 128):
    """Pallas ``input_output_aliases`` + donation: the kernel's output
    lands in the input's HBM buffer. Returns (ok, evidence dict).

    Off the TPU (CPU tests) the kernel runs in interpret mode; the
    jit-level donation and the compiled aliasing contract are still
    real, which is what is being proven.
    """
    from jax.experimental import pallas as pl

    from hpc_patterns_tpu.ops.tiling import resolve_interpret

    interpret = resolve_interpret(None, "pallas_alias_proof")

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + 1.0

    x = jax.block_until_ready(
        jnp.arange(rows * cols, dtype=jnp.float32).reshape(rows, cols)
    )
    want = np.asarray(x) * 2.0 + 1.0
    f = jax.jit(
        lambda v: pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype),
            input_output_aliases={0: 0},
            interpret=interpret,
        )(v),
        donate_argnums=0,
    )
    out, ev = _run_aliased(f, x)
    values_ok = bool(np.allclose(np.asarray(out), want))
    alias_ok = ev["pointer_ok"] if ev["pointer_ok"] is not None else ev["contract_ok"]
    ev["values_ok"] = values_ok
    ev["interpret"] = interpret
    return bool(values_ok and alias_ok and ev["input_invalidated"]), ev
