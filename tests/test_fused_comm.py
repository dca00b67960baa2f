"""Parity suite for the device-initiated fused ring collectives
(``comm/fused.py``).

Every fused kernel runs under Pallas interpret mode on the virtual CPU
mesh (conftest) and is compared BYTE-EXACT against its host-driven
oracle: ``fused_allreduce`` against ``ring.ring_allreduce_chunked``
over the identical padded chunk layout (same combine order, so floats
match bitwise, not just to tolerance), ``allgather_matmul`` against
the gather-then-tiles reference, ``fused_permute`` against
``lax.ppermute``. The dtype axis (float32 / bfloat16 / int32), the
non-power-of-two and non-divisible shard shapes, and every ring size a
submesh of the 8-device mesh offers are all swept, because each is a
distinct way for chunk bookkeeping to go wrong silently.
"""


import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from hpc_patterns_tpu.analysis import runtime as analysis_runtime
from hpc_patterns_tpu.comm import Communicator, fused, ring

WORLD = 8

# the Pallas interpreter evaluates kernel bodies without vma types, so
# an interpreted remote-DMA kernel cannot trace under shard_map's vma
# check (jax's own error says to pass check_vma=False); values are what
# this battery compares, bit for bit
shard_map = functools.partial(jax.shard_map, check_vma=False)


@pytest.fixture(scope="module", autouse=True)
def strict_sems():
    """The strict-semaphore interpret shim over the WHOLE battery:
    every fused kernel traced by these tests has its DMA semaphore
    ledger balance-asserted at kernel exit (analysis/runtime.py) — so
    the bug class PR 8 caught by eyeball (double-waited send sems,
    undrained DMAs) fails here, in one test, not on silicon. No cache
    clear: every test builds FRESH jit wrappers, which always
    re-trace, so the kernel bodies run through the patched
    ``pallas_call`` regardless (a mid-suite ``jax.clear_caches()``
    would cost the rest of tier-1 its warm traces). Engagement is
    asserted by ``test_strict_shim_is_engaged``, a selected test —
    not at teardown, where a ``-k``-filtered run that traces no
    kernel would fail spuriously."""
    with analysis_runtime.strict_semaphores() as ledger:
        yield ledger


def test_strict_shim_is_engaged(strict_sems):
    """Proof the shim is live over this module: tracing one fused
    kernel must increment the ledger's checked-kernel count — an
    inert shim would silently void the whole battery's sync-protocol
    guarantee."""
    before = strict_sems.kernels_checked
    mesh = submesh(4)
    x = jnp.arange(4 * 2 * 8, dtype=jnp.float32).reshape(8, 8)
    out = shmap(lambda l: fused.fused_allreduce(l, "x"), mesh)(x)
    jax.block_until_ready(out)
    assert strict_sems.kernels_checked > before


@pytest.fixture(scope="module")
def comm():
    from hpc_patterns_tpu import topology

    return Communicator(topology.make_mesh({"x": WORLD}), "x")


def submesh(size: int) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:size]), ("x",))


def shmap(fn, mesh, n_in=1, out_specs=P("x", None)):
    spec = P("x", None)
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in,
                             out_specs=out_specs))


def rand(rng, size, n, dtype):
    x = (rng.normal(size=(size, n)) * 8).astype(np.float32)
    if dtype == "int32":
        return x.astype(np.int32)
    return jnp.asarray(x).astype(dtype)


def host_ring_oracle(mesh, x, n):
    """The byte-exact host-driven oracle: pad the scatter axis to the
    SAME chunk layout the fused wrapper uses (fused.ring_layout), run
    the host two-phase ring, slice the pad back off. Identical chunk
    walk + combine order == identical bytes, every dtype."""
    size = mesh.shape["x"]
    _, _, _, n_pad = fused.ring_layout((1, n), size, interpret=True)
    xp = jnp.pad(jnp.asarray(x), ((0, 0), (0, n_pad - n)))
    out = shmap(
        lambda l: ring.ring_allreduce_chunked(l, "x", scatter_axis=1),
        mesh)(xp)
    return np.asarray(out)[:, :n]


class TestFusedAllreduceParity:
    # 40 = non-divisible by 8 and by 3; covers the pad-and-slice path
    # on most sizes and the divisible path on size 2/4/5
    @pytest.mark.parametrize("size", [2, 3, 4, 5, 6, 7, 8])
    def test_every_ring_size_matches_host_ring(self, size):
        mesh = submesh(size)
        x = rand(np.random.default_rng(size), size, 40, "float32")
        got = np.asarray(
            shmap(lambda l: fused.fused_allreduce(l, "x"), mesh)(x))
        np.testing.assert_array_equal(got, host_ring_oracle(mesh, x, 40))

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
    @pytest.mark.parametrize("n", [64, 65])
    def test_dtypes_and_shapes_match_host_ring(self, comm, dtype, n):
        # 64 divides the 8-ring exactly; 65 exercises padding
        x = rand(np.random.default_rng(1), WORLD, n, dtype)
        got = np.asarray(
            shmap(lambda l: fused.fused_allreduce(l, "x"), comm.mesh)(x))
        np.testing.assert_array_equal(
            got, host_ring_oracle(comm.mesh, x, n))

    def test_matches_collective_to_tolerance(self, comm):
        # the library collective reduces in a different association
        # order — allclose, not equal, is the right claim
        x = rand(np.random.default_rng(2), WORLD, 64, "float32")
        got = np.asarray(comm.allreduce(comm.shard(x), "fused"))
        ref = np.asarray(comm.allreduce(comm.shard(x), "collective"))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_communicator_route_is_the_kernel(self, comm):
        x = rand(np.random.default_rng(3), WORLD, 40, "float32")
        got = np.asarray(comm.allreduce(comm.shard(x), "fused"))
        np.testing.assert_array_equal(
            got, host_ring_oracle(comm.mesh, x, 40))

    def test_int32_sum_is_exact(self, comm):
        x = rand(np.random.default_rng(4), WORLD, 40, "int32")
        got = np.asarray(comm.allreduce(comm.shard(x), "fused"))
        want = x.sum(axis=0, dtype=np.int32)
        np.testing.assert_array_equal(got,
                                      np.broadcast_to(want, got.shape))


class TestAllreduceInto:
    def test_bias_and_epilogue_fused_exactly(self, comm):
        rng = np.random.default_rng(5)
        x = rand(rng, WORLD, 40, "float32")
        bias = rng.normal(size=(40,)).astype(np.float32)
        got = np.asarray(comm.allreduce_into(
            comm.shard(x), bias=bias, epilogue=jax.nn.relu,
            algorithm="fused"))
        want = np.maximum(host_ring_oracle(comm.mesh, x, 40) + bias, 0)
        np.testing.assert_array_equal(got, want)

    def test_widening_epilogue_keeps_dtype_on_both_routes(self, comm):
        # an epilogue computing in f32 must land back in the
        # collective's dtype on BOTH routes — the oracle-pair
        # contract. int32 input: the reduction is order-exact, so the
        # routes must agree to the byte even through the widen+round
        x = rand(np.random.default_rng(13), WORLD, 32, "int32")
        widen = lambda v: v.astype(jnp.float32) * 1.5  # noqa: E731
        got = comm.allreduce_into(comm.shard(x), epilogue=widen,
                                  algorithm="fused")
        ref = comm.allreduce_into(comm.shard(x), epilogue=widen,
                                  algorithm="collective")
        assert got.dtype == ref.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_host_route_agrees_to_tolerance(self, comm):
        rng = np.random.default_rng(6)
        x = rand(rng, WORLD, 64, "float32")
        bias = rng.normal(size=(64,)).astype(np.float32)
        got = np.asarray(comm.allreduce_into(
            comm.shard(x), bias=bias, algorithm="fused"))
        ref = np.asarray(comm.allreduce_into(
            comm.shard(x), bias=bias, algorithm="collective"))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


class TestAllgatherMatmul:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
    def test_matches_reference_bitwise(self, comm, dtype):
        rng = np.random.default_rng(7)
        x = np.asarray(rand(rng, WORLD, 3 * 16, dtype)).reshape(
            WORLD, 3, 16)
        w = np.asarray(rand(rng, WORLD, 16 * 8, dtype)).reshape(
            WORLD, 16, 8)
        got = np.asarray(comm.allgather_matmul(x, w, "fused"))
        want = np.asarray(comm.allgather_matmul(x, w, "collective"))
        assert got.shape == (WORLD, WORLD * 3, 8)
        np.testing.assert_array_equal(got, want)

    def test_reference_math(self, comm):
        # the host route itself against a plain numpy contraction
        rng = np.random.default_rng(8)
        x = rng.normal(size=(WORLD, 2, 16)).astype(np.float32)
        w = rng.normal(size=(WORLD, 16, 4)).astype(np.float32)
        out = np.asarray(comm.allgather_matmul(x, w, "collective"))
        gathered = x.reshape(WORLD * 2, 16)
        for r in range(WORLD):
            np.testing.assert_allclose(out[r], gathered @ w[r],
                                       rtol=1e-5, atol=1e-6)

    def test_rejects_bad_shapes(self, comm):
        with pytest.raises(ValueError, match="size, m, k"):
            comm.allgather_matmul(np.ones((WORLD, 4)),
                                  np.ones((WORLD, 4, 4)))
        with pytest.raises(ValueError, match="not in"):
            comm.allgather_matmul(np.ones((WORLD, 2, 4)),
                                  np.ones((WORLD, 4, 4)),
                                  algorithm="ring")


class TestFusedPermute:
    def test_ring_shift_matches_ppermute(self, comm):
        x = rand(np.random.default_rng(9), WORLD, 24, "float32")
        for shift in (1, -1, 3):
            got = np.asarray(shmap(
                lambda l: fused.fused_ring_shift(l, "x", shift),
                comm.mesh)(x))
            want = np.asarray(shmap(
                lambda l: ring.ring_shift(l, "x", shift),
                comm.mesh)(x))
            np.testing.assert_array_equal(got, want)

    def test_arbitrary_permutation(self, comm):
        # pairwise swap (the ping-pong pattern) through the fused route
        x = rand(np.random.default_rng(10), WORLD, 24, "float32")
        perm = [(i, i ^ 1) for i in range(WORLD)]
        ring.check_permutation(perm, WORLD)
        got = np.asarray(shmap(
            lambda l: fused.fused_permute(l, "x", perm), comm.mesh)(x))
        np.testing.assert_array_equal(
            got, np.asarray(x)[[r ^ 1 for r in range(WORLD)]])

    def test_high_rank_blocks_roundtrip(self, comm):
        # 4-D K/V-block shape, the ring-attention payload
        x = np.random.default_rng(11).normal(
            size=(WORLD, 2, 4, 3, 8)).astype(np.float32)
        got = np.asarray(jax.jit(shard_map(
            lambda l: fused.fused_ring_shift(l, "x", 1), mesh=comm.mesh,
            in_specs=P("x"), out_specs=P("x")))(x))
        np.testing.assert_array_equal(
            got, x[(np.arange(WORLD) - 1) % WORLD])

    def test_malformed_pairs_rejected(self, comm):
        with pytest.raises(ValueError, match="duplicate"):
            shmap(lambda l: fused.fused_permute(
                l, "x", [(i, 0) for i in range(WORLD)]), comm.mesh)(
                    np.ones((WORLD, 8), np.float32))


class TestRingAttentionFusedShift:
    def test_fused_shift_matches_ppermute_bitwise(self, comm):
        from hpc_patterns_tpu import parallel

        rng = np.random.default_rng(12)
        q, k, v = (rng.normal(size=(2, WORLD * 4, 2, 8)
                              ).astype(np.float32) for _ in range(3))
        spec = P(None, "x", None, None)

        def run(shift_impl):
            fn = jax.jit(shard_map(
                lambda a, b, c: parallel.ring_attention(
                    a, b, c, "x", causal=True, shift_impl=shift_impl),
                mesh=comm.mesh, in_specs=(spec,) * 3, out_specs=spec))
            return np.asarray(fn(q, k, v))

        np.testing.assert_array_equal(run("fused"), run("ppermute"))

    def test_rejects_unknown_shift_impl(self):
        from hpc_patterns_tpu import parallel

        with pytest.raises(ValueError, match="shift_impl"):
            parallel.ring_attention(
                jnp.ones((1, 8, 1, 4)), jnp.ones((1, 8, 1, 4)),
                jnp.ones((1, 8, 1, 4)), "x", shift_impl="nope")


class TestGuardsAndCaching:
    def test_fused_prod_refused(self):
        with pytest.raises(ValueError, match="prod"):
            fused.fused_allreduce(jnp.ones((2, 2)), "x", op="prod")

    def test_jit_allreduce_one_compile_per_key(self, comm):
        """The satellite claim: sweeping algorithms at one shape holds
        ONE traced closure per (shape, dtype, algorithm) — repeated
        calls return the same object and its jit cache stays at 1."""
        from hpc_patterns_tpu.harness.trace import jit_cache_size

        x = comm.shard(np.ones((WORLD, 32), np.float32))
        fns = {}
        for alg in ("fused", "collective", "ring", "ring_chunked"):
            f1 = comm.jit_allreduce(x, alg)
            f2 = comm.jit_allreduce(x, alg)
            assert f1 is f2, alg
            jax.block_until_ready(f1(x))
            jax.block_until_ready(f1(x))
            assert jit_cache_size(f1, strict=True) == 1, alg
            fns[alg] = f1
        assert len(set(map(id, fns.values()))) == 4
        # a different shape gets its own slot, old keys stay warm
        y = comm.shard(np.ones((WORLD, 16), np.float32))
        assert comm.jit_allreduce(y, "fused") is not fns["fused"]
        assert comm.jit_allreduce(x, "fused") is fns["fused"]


# every factorization the 8-device mesh offers, paired with each of
# its axes — the full (mesh, ring) product the multi-axis lift claims
MULTIAXIS_CASES = [
    pytest.param(axes, axis, id=f"{'x'.join(map(str, axes.values()))}-{axis}")
    for axes in ({"a": 2, "b": 4}, {"a": 4, "b": 2},
                 {"a": 2, "b": 2, "c": 2})
    for axis in axes
]


def multiaxis_host_oracle(mesh, axis, x, n):
    """:func:`host_ring_oracle` generalized to one axis of a
    multi-axis mesh: the host two-phase ring runs on the REAL mesh
    (XLA's discharge-free path has no single-axis restriction), padded
    to the identical fused chunk layout."""
    from jax.sharding import NamedSharding

    size = mesh.shape[axis]
    _, _, _, n_pad = fused.ring_layout((1, n), size, interpret=True)
    xp = jnp.pad(jnp.asarray(x), ((0, 0), (0, n_pad - n)))
    spec = P(axis, None)
    fn = jax.jit(shard_map(
        lambda l: ring.ring_allreduce_chunked(l, axis, scatter_axis=1),
        mesh=mesh, in_specs=spec, out_specs=spec))
    out = fn(jax.device_put(xp, NamedSharding(mesh, spec)))
    return np.asarray(out)[:, :n]


class TestMultiAxisFused:
    """The multi-axis lift: the fused kernels run over one axis of a
    2-D torus / multi-slice mesh via the flat-mesh route (neighbor ids
    from mesh coordinates — fused.RingGeometry), bitwise-equal to the
    host ring running natively on the multi-axis mesh."""

    @pytest.mark.parametrize("axes,axis", MULTIAXIS_CASES)
    def test_fused_allreduce_matches_host_ring(self, axes, axis):
        from hpc_patterns_tpu import topology

        mesh = topology.make_mesh(axes)
        c = Communicator(mesh, axis)
        x = rand(np.random.default_rng(c.size), c.size, 40, "float32")
        got = np.asarray(c.allreduce(c.shard(x), "fused"))
        np.testing.assert_array_equal(
            got, multiaxis_host_oracle(mesh, axis, x, 40))

    @pytest.mark.parametrize("axes,axis", MULTIAXIS_CASES)
    def test_fused_ring_shift_matches_host_shift(self, axes, axis):
        from jax.sharding import NamedSharding

        from hpc_patterns_tpu import topology

        mesh = topology.make_mesh(axes)
        g = fused.mesh_ring_geometry(mesh, axis)
        fm = fused.flat_mesh(mesh)
        x = rand(np.random.default_rng(7), g.size, 24, "float32")

        spec = P(fused.FLAT_AXIS, None)
        fn = jax.jit(shard_map(
            lambda l: fused.fused_ring_shift(l, fused.FLAT_AXIS,
                                             geometry=g),
            mesh=fm, in_specs=spec, out_specs=spec))
        xf = jax.device_put(
            jnp.take(jnp.asarray(x), jnp.asarray(g.positions()), axis=0),
            NamedSharding(fm, spec))
        full = np.asarray(fn(xf))

        rspec = P(axis, None)
        host = jax.jit(shard_map(
            lambda l: ring.ring_shift(l, axis, 1),
            mesh=mesh, in_specs=rspec, out_specs=rspec))
        want = np.asarray(host(jax.device_put(
            jnp.asarray(x), NamedSharding(mesh, rspec))))

        np.testing.assert_array_equal(full[g.ring_ids()], want)
        # replica discipline: every flat rank sharing a ring position
        # computed the identical row, bit for bit
        pos = g.positions()
        for f in range(g.total):
            np.testing.assert_array_equal(
                full[f], full[pos[f] * g.stride])

    def test_allgather_matmul_multiaxis_matches_reference(self):
        from hpc_patterns_tpu import topology

        mesh = topology.make_mesh({"a": 2, "b": 4})
        c = Communicator(mesh, "b")
        rng = np.random.default_rng(3)
        x = rng.normal(size=(4, 2, 8)).astype(np.float32)
        w = rng.normal(size=(4, 8, 4)).astype(np.float32)
        got = np.asarray(c.allgather_matmul(x, w, "fused"))
        ref = np.asarray(c.allgather_matmul(x, w, "collective"))
        np.testing.assert_array_equal(got, ref)

    def test_jit_cache_one_compile_per_shape_dtype_axis(self):
        """The sweep-discipline pin: on ONE multi-axis mesh, a
        communicator per axis holds one compiled fused closure per
        (shape, dtype, axis) — repeat calls hit the same wrapper and
        its jit cache stays at 1, so an axis sweep never thrashes."""
        from hpc_patterns_tpu import topology
        from hpc_patterns_tpu.harness.trace import jit_cache_size

        mesh = topology.make_mesh({"a": 2, "b": 4})
        for axis in ("a", "b"):
            c = Communicator(mesh, axis)
            x = c.shard(np.ones((c.size, 32), np.float32))
            f1 = c.jit_allreduce(x, "fused")
            assert c.jit_allreduce(x, "fused") is f1, axis
            jax.block_until_ready(f1(x))
            jax.block_until_ready(f1(x))
            assert jit_cache_size(f1, strict=True) == 1, axis
            key = ((c.size, 32), "float32", axis, "fused")
            assert key in c._jit_allreduce_cache, axis


class TestScheduleFingerprints:
    def test_fused_route_fingerprinted_with_algorithm(self, comm,
                                                      tmp_path,
                                                      monkeypatch):
        """The verifier must not go blind on the fast path: an eager
        fused allreduce under an exported trace dir records the same
        (op, seq, shape, dtype, axis) chain entry as the host paths,
        plus the algorithm field that joined the fingerprint."""
        from hpc_patterns_tpu.analysis import runtime as art

        monkeypatch.setenv(art.ENV_TRACE_DIR, str(tmp_path))
        monkeypatch.setenv(art.ENV_PROCESS_ID, "0")
        art.reset_collective_schedule()
        x = comm.shard(np.ones((WORLD, 24), np.float32))
        comm.allreduce(x, "fused")
        comm.allreduce(x, "collective")
        sched = art.collective_schedule().snapshot()
        assert sched["n"] == 2
        e_fused, e_coll = sched["entries"]
        assert e_fused["op"] == "allreduce.fused"
        assert e_fused["algorithm"] == "fused"
        assert e_fused["shape"] == [WORLD, 24]
        assert e_fused["axis"] == "x"
        assert e_coll["algorithm"] == "collective"
        assert e_coll["seq"] == e_fused["seq"] + 1
        # and two chains that differ ONLY in algorithm diverge
        a = art.CollectiveSchedule()
        b = art.CollectiveSchedule()
        a.record("allreduce", 0, shape=(8, 4), dtype="float32",
                 axis="x", algorithm="fused")
        b.record("allreduce", 0, shape=(8, 4), dtype="float32",
                 axis="x", algorithm="collective")
        assert a.digest != b.digest
        art.reset_collective_schedule()
