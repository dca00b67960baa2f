"""Known-bad: kernels whose literal-resolvable VMEM working set
already exceeds their budget — the PR 8 overflow shape, which passes
interpret mode (no VMEM exists there) and fails at Mosaic lowering on
the chip, on budgeted chip time. The vmem-budget rule judges ONLY the
literal lower bound (blocks + scratch it can resolve from constants);
symbolic shapes are ``--vmem-report``'s territory."""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _accum_kernel(x_ref, o_ref, acc_ref):
    o_ref[...] = x_ref[...] + acc_ref[...]


def scratch_over_default_limit(x):
    """A 64 MiB f32 scratch against Mosaic's 16 MiB default scoped
    limit: 4096·4096·4 bytes of accumulator nobody sized."""
    return pl.pallas_call(  # EXPECT: vmem-budget
        _accum_kernel,
        out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
        in_specs=[pl.BlockSpec((128, 128), lambda i: (0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        grid=(1,),
        scratch_shapes=[pltpu.VMEM((4096, 4096), jnp.float32)],
    )(x)


def scratch_over_declared_limit(x):
    """An explicit (small) vmem_limit_bytes the literal scratch still
    blows through: the declared budget is the contract, and 8 MiB of
    f32 double-buffer does not fit 4 MiB of it."""
    return pl.pallas_call(  # EXPECT: vmem-budget
        _accum_kernel,
        out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
        in_specs=[pl.BlockSpec((128, 128), lambda i: (0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        grid=(1,),
        scratch_shapes=[pltpu.VMEM((2, 1024, 1024), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * 1024 * 1024),
    )(x)
