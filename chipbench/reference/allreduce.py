"""The plain reference of the allreduce: each rank's expected output is the
sum of every rank's input, made again from the seed on the device that
holds the row and summed there. No collective, nothing of ``comm/``, no
buffer the program wrote. Inputs are whole numbers below 2**12, so a
float32 sum of four of them is exact and the comparison's limit is 0
(bfloat16 holds whole numbers only up to 2**8: the control's sums differ).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import weights

VALUE_BOUND = 1 << 12


def rank_input(key, rank, n: int, dtype=jnp.float32):
    """Rank ``rank``'s input buffer of ``n`` elements."""
    k = jax.random.fold_in(jax.random.fold_in(key, n), rank)
    return jax.random.randint(k, (n,), 0, VALUE_BOUND, jnp.int32).astype(dtype)


@functools.partial(jax.jit, static_argnames=("n", "ranks"))
def _mismatches(key, row, *, n, ranks):
    want = lax.fori_loop(   # one rank's buffer live at a time
        0, ranks, lambda r, acc: acc + rank_input(key, r, n),
        jnp.zeros((n,), jnp.float32))
    return jnp.sum(row.astype(jnp.float32) != want)


def wrong_elements(seed: int, output, ranks: int) -> int:
    """How many elements of ``output`` (ranks, n), one row on each chip,
    differ from the sum, counted on the chip that holds the row."""
    key = weights.seed_key(seed)
    n = output.shape[1]
    bad = 0
    for shard in output.addressable_shards:
        with jax.default_device(shard.device):
            k = jax.device_put(key, shard.device)
            for row in shard.data:
                bad += int(_mismatches(k, row, n=n, ranks=ranks))
    return bad
