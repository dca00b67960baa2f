"""Tests for the communication backend (C5 parity + §2.3).

Every test runs 8-way SPMD on the virtual CPU mesh (conftest), closing
the reference's hardware-only testing gap (SURVEY.md §4). Oracles are the
reference's: allreduce of rank-valued buffers == size(size-1)/2
(allreduce-mpi-sycl.cpp:192-204), elementwise, every rank.
"""

import jax

from jax import shard_map
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from hpc_patterns_tpu.comm import Communicator, collectives, ring
from hpc_patterns_tpu.harness import correctness_verdict

WORLD = 8
N = 64


@pytest.fixture(scope="module")
def comm():
    from hpc_patterns_tpu import topology

    return Communicator(topology.make_mesh({"x": WORLD}), "x")


def rows(dtype=np.float32):
    """Rank-valued buffers: row r filled with r (the miniapp's Initialize)."""
    return np.repeat(np.arange(WORLD, dtype=dtype)[:, None], N, axis=1)


ORACLE = WORLD * (WORLD - 1) / 2  # 28


@pytest.mark.parametrize("algorithm", ["collective", "ring", "ring_chunked"])
@pytest.mark.parametrize("dtype", ["float32", "int32", "bfloat16"])
def test_allreduce_all_algorithms_match_oracle(comm, algorithm, dtype):
    x = comm.shard(rows(np.dtype(dtype) if dtype != "bfloat16" else jnp.bfloat16))
    out = np.asarray(comm.allreduce(x, algorithm))
    assert out.shape == (WORLD, N)
    # every rank (row) must hold the full sum — MPI_Allreduce semantics
    v = correctness_verdict(out, ORACLE, dtype=dtype)
    assert v.success, v.messages


def test_allreduce_algorithms_agree_on_random_data(comm):
    x = comm.shard(np.random.default_rng(0).normal(size=(WORLD, N)).astype(np.float32))
    ref = np.asarray(comm.allreduce(x, "collective"))
    for alg in ["ring", "ring_chunked"]:
        # rings reduce in a different association order than XLA's
        # all-reduce; only bitwise-order-independent math would match exactly
        np.testing.assert_allclose(
            np.asarray(comm.allreduce(x, alg)), ref, rtol=1e-5, atol=1e-6
        )


def test_ring_chunked_requires_divisible_chunks(comm):
    x = comm.shard(np.ones((WORLD, WORLD + 1), np.float32))
    with pytest.raises(ValueError, match="not divisible"):
        comm.allreduce(x, "ring_chunked")


def test_rank_filled_and_oracle(comm):
    x = np.asarray(comm.rank_filled(N))
    np.testing.assert_array_equal(x, rows())
    assert comm.expected_allreduce_value() == ORACLE


def test_pingpong_swaps_even_odd_pairs(comm):
    out = np.asarray(comm.pingpong(comm.shard(rows())))
    expect = rows()[[r ^ 1 for r in range(WORLD)]]
    np.testing.assert_array_equal(out, expect)


def test_sendrecv_ring_shift(comm):
    x = comm.shard(rows())
    out = np.asarray(comm.sendrecv_ring(x, 1))
    # rank r's data lands on rank r+1: row r now holds r-1's values
    np.testing.assert_array_equal(out, rows()[(np.arange(WORLD) - 1) % WORLD])
    back = np.asarray(comm.sendrecv_ring(x, -1))
    np.testing.assert_array_equal(back, rows()[(np.arange(WORLD) + 1) % WORLD])


def test_all_gather_every_rank_sees_all_rows(comm):
    out = np.asarray(comm.all_gather(comm.shard(rows())))
    assert out.shape == (WORLD, WORLD, N)
    for r in range(WORLD):
        np.testing.assert_array_equal(out[r], rows())


def test_reduce_scatter_chunks(comm):
    data = np.random.default_rng(1).normal(size=(WORLD, WORLD * 4)).astype(np.float32)
    out = np.asarray(comm.reduce_scatter(comm.shard(data)))
    assert out.shape == (WORLD, 4)
    total = data.sum(axis=0)
    for r in range(WORLD):
        np.testing.assert_allclose(out[r], total[r * 4 : (r + 1) * 4], rtol=1e-5)


def test_all_to_all_transpose(comm):
    data = np.arange(WORLD * WORLD, dtype=np.float32).reshape(WORLD, WORLD)
    out = np.asarray(comm.all_to_all(comm.shard(data)))
    np.testing.assert_array_equal(out, data.T)


def test_shard_rejects_bad_leading_dim(comm):
    with pytest.raises(ValueError, match="leading dim"):
        comm.shard(np.ones((WORLD + 1, N)))
    with pytest.raises(ValueError, match="not in mesh"):
        Communicator(comm.mesh, "nope")


# -- in-shard_map primitives (ring engine reused by parallel/) -----------


def shmap(fn, mesh, n_in=1):
    spec = P("x", None)
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in, out_specs=spec)
    )


def test_ring_schedule_generic_combine(comm):
    # max over the ring == pmax: exercises ring_schedule with a non-sum op
    def per_rank(local):
        return ring.ring_schedule(local, "x", lambda acc, inc, _s: jnp.maximum(acc, inc))

    x = comm.shard(rows())
    out = np.asarray(shmap(per_rank, comm.mesh)(x))
    np.testing.assert_array_equal(out, np.full((WORLD, N), WORLD - 1, np.float32))


def test_ring_reduce_scatter_and_all_gather_inverse(comm):
    data = np.random.default_rng(2).normal(size=(WORLD, WORLD * 8)).astype(np.float32)

    def per_rank(local):
        chunk = ring.ring_reduce_scatter(local[0], "x")  # (8,)
        return ring.ring_all_gather(chunk, "x", tiled=True)[None]

    out = np.asarray(shmap(per_rank, comm.mesh)(comm.shard(data)))
    total = data.sum(axis=0)
    for r in range(WORLD):
        np.testing.assert_allclose(out[r], total, rtol=1e-5)


def test_pairwise_exchange_needs_even_world():
    from hpc_patterns_tpu import topology

    mesh3 = topology.make_mesh({"y": -1})  # 8, even: build an odd submesh
    devs = jax.devices()[:3]
    import numpy as _np
    from jax.sharding import Mesh

    mesh_odd = Mesh(_np.asarray(devs), ("x",))

    def per_rank(local):
        return ring.pairwise_exchange(local, "x")

    with pytest.raises(ValueError, match="even axis size"):
        shard_map(
            per_rank, mesh=mesh_odd, in_specs=P("x", None), out_specs=P("x", None)
        )(jnp.ones((3, 4)))


def test_collectives_broadcast_and_ops(comm):
    x = comm.shard(rows())

    def bcast(local):
        return collectives.broadcast(local, "x", root=3)

    out = np.asarray(shmap(bcast, comm.mesh)(x))
    np.testing.assert_array_equal(out, np.full((WORLD, N), 3, np.float32))

    def pmaxmin(local):
        return collectives.allreduce(local, "x", "max") + collectives.allreduce(
            local, "x", "min"
        )

    out = np.asarray(shmap(pmaxmin, comm.mesh)(x))
    np.testing.assert_array_equal(out, np.full((WORLD, N), WORLD - 1, np.float32))

    with pytest.raises(ValueError, match="unknown reduce op"):
        collectives.allreduce(jnp.ones(4), "x", "xor")
