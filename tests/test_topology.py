"""Tests for topology (C8 parity: devices.hpp rank->device policies,
fission fallback, mesh construction)."""

import jax

from jax import shard_map
import pytest

from hpc_patterns_tpu import topology


def test_get_devices_platform_filter():
    ds = topology.get_devices("cpu")
    assert len(ds) == 8
    with pytest.raises(topology.TopologyError):
        topology.get_devices("nonexistent-platform")


def test_fission_never_fails():
    # reference semantics: finest partition, whole-device fallback
    # (devices.hpp:28-38)
    assert len(topology.fission()) == 8
    assert topology.fission([]) == []


def test_core_topology_introspection():
    infos = topology.core_topology()
    assert len(infos) == 8
    for info in infos:
        assert info.num_cores >= 1
        assert isinstance(info.kind, str)
        # CPU devices are plain single cores, never megacore
        assert not info.megacore

    # synthetic megacore (v4/v5p-style: one device, two fused cores)
    class _Mega:
        platform = "tpu"
        device_kind = "TPU v4"
        coords = (0, 0, 0)
        core_on_chip = 0
        num_cores = 2
        process_index = 0
        id = 0

    (mega,) = topology.core_topology([_Mega()])
    assert mega.megacore and mega.num_cores == 2


def test_group_by_chip():
    # CPU devices expose no coords: every device is its own "chip"
    groups = topology.group_by_chip()
    assert len(groups) == 8
    assert all(len(v) == 1 for v in groups.values())

    # synthetic v2/v3-style chip: two per-core devices sharing coords
    class _Core:
        platform = "tpu"
        process_index = 0

        def __init__(self, i, core):
            self.id = i
            self.coords = (0, 0, 0)
            self.core_on_chip = core

    groups = topology.group_by_chip([_Core(0, 0), _Core(1, 1)])
    assert len(groups) == 1
    (devs,) = groups.values()
    assert len(devs) == 2


def test_assign_device_modulo_when_oversubscribed():
    # ranks > devices -> rank % n (devices.hpp:47)
    ds = topology.get_devices()
    n = len(ds)
    for rank in range(2 * n):
        assert topology.assign_device(rank, 2 * n, ds) == ds[rank % n]


def test_assign_device_block_when_undersubscribed():
    # devices >= ranks -> contiguous blocks (devices.hpp:49-53)
    ds = topology.get_devices()  # 8
    assert topology.assign_device(0, 2, ds) == ds[0]
    assert topology.assign_device(1, 2, ds) == ds[4]
    assert topology.devices_for_rank(1, 2, ds) == list(ds[4:8])
    assert topology.devices_for_rank(0, 4, ds) == list(ds[0:2])


def test_assign_device_bad_args():
    ds = topology.get_devices()
    with pytest.raises(ValueError):
        topology.assign_device(3, 2, ds)
    with pytest.raises(topology.TopologyError):
        topology.assign_device(0, 1, [])


def test_make_mesh_explicit_and_auto():
    m = topology.make_mesh({"dp": 2, "tp": 4})
    assert m.shape == {"dp": 2, "tp": 4}
    # -1 auto sentinel (sycl_con.cpp CLI convention)
    m = topology.make_mesh({"dp": -1, "tp": 2})
    assert m.shape == {"dp": 4, "tp": 2}
    m = topology.make_mesh({"a": -1, "b": -1, "c": 2})
    assert m.shape == {"a": 4, "b": 1, "c": 2}


def test_make_mesh_rejects_nondividing():
    with pytest.raises(topology.TopologyError):
        topology.make_mesh({"dp": 3})
    with pytest.raises(topology.TopologyError):
        topology.make_mesh({"dp": 2})  # uses 2 of 8 with no auto axis


def test_single_device_mesh_and_info():
    m = topology.single_device_mesh(("dp", "tp"))
    assert m.shape == {"dp": 1, "tp": 1}
    info = topology.TopologyInfo.detect()
    assert info.n_devices == 8
    assert info.platform == "cpu"
    assert info.n_hosts == 1


def test_group_by_host():
    groups = topology.group_by_host()
    assert sum(len(v) for v in groups.values()) == 8
    assert set(groups) == {jax.devices()[0].process_index}


class _FakeDev:
    """Synthetic device carrying a slice_index (CPU devices are all
    slice 0, so multi-slice layouts are tested with these)."""

    def __init__(self, i, slice_index):
        self.id = i
        self.slice_index = slice_index

    def __repr__(self):
        return f"d{self.id}@s{self.slice_index}"


class TestHybridMesh:
    def test_layout_dcn_across_slices(self):
        # 2 slices x 4 devices: dp must span slices, tp/sp stay inside
        devs = [_FakeDev(i, i // 4) for i in range(8)]
        arr, names = topology.hybrid_device_layout(
            {"dp": -1}, {"sp": 2, "tp": 2}, devs
        )
        assert names == ("dp", "sp", "tp")
        assert arr.shape == (2, 2, 2)
        # every (sp, tp) plane = one slice; dp index = slice index
        for d in range(2):
            slices = {dev.slice_index for dev in arr[d].ravel()}
            assert slices == {d}

    def test_layout_guards(self):
        devs = [_FakeDev(i, i // 4) for i in range(8)]
        with pytest.raises(topology.TopologyError, match="both"):
            topology.hybrid_device_layout({"dp": 2}, {"dp": 4}, devs)
        with pytest.raises(topology.TopologyError):
            # dcn product != slice count
            topology.hybrid_device_layout({"dp": 4}, {"tp": 4}, devs)
        uneven = [_FakeDev(i, 0 if i < 5 else 1) for i in range(8)]
        with pytest.raises(topology.TopologyError, match="unequal"):
            topology.hybrid_device_layout({"dp": 2}, {"tp": -1}, uneven)

    def test_mesh_runs_collectives_per_domain(self, monkeypatch):
        # real Mesh over the CPU devices with two SYNTHETIC slices:
        # psum over the ici axis must stay inside one fake slice
        # (device rows 0-3 / 4-7), psum over dcn crosses them
        import numpy as np
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        ds = topology.get_devices()
        fake_groups = {0: ds[:4], 1: ds[4:]}
        # the production slice-override path (no monkeypatching): the
        # same env protocol apps/launch.py --slices uses cross-process
        monkeypatch.setenv(topology.ENV_SLICE_GROUPING, "devices:4")
        mesh = topology.make_hybrid_mesh({"dp": -1}, {"tp": -1}, ds)
        assert mesh.shape == {"dp": 2, "tp": 4}
        # row d of the mesh = fake slice d
        for d in range(2):
            assert list(mesh.devices[d]) == list(fake_groups[d])

        x = jnp.arange(8.0)
        got = jax.jit(shard_map(
            lambda v: jax.lax.psum(v, "tp"),
            mesh=mesh, in_specs=P(("dp", "tp")), out_specs=P(("dp", "tp")),
        ))(x)
        # tp-psum folds within each slice: rows 0-3 sum to 6, 4-7 to 22
        want = np.repeat([6.0, 22.0], 4)
        np.testing.assert_allclose(np.asarray(got), want)

    def test_slice_grouping_env(self, monkeypatch):
        ds = topology.get_devices()
        monkeypatch.setenv(topology.ENV_SLICE_GROUPING, "devices:2")
        assert sorted(topology.group_by_slice(ds)) == [0, 1, 2, 3]
        # process mapping: all CPU devices are process 0 -> slice 0
        monkeypatch.setenv(topology.ENV_SLICE_GROUPING, "process:0,1")
        assert set(topology.group_by_slice(ds)) == {0}
        monkeypatch.setenv(topology.ENV_SLICE_GROUPING, "process")
        assert set(topology.group_by_slice(ds)) == {0}
        monkeypatch.setenv(topology.ENV_SLICE_GROUPING, "banana")
        with pytest.raises(topology.TopologyError, match="SLICE_GROUPING"):
            topology.group_by_slice(ds)

    def test_single_slice_degenerates(self):
        mesh = topology.make_hybrid_mesh({"dp": -1}, {"tp": 8})
        assert mesh.shape == {"dp": 1, "tp": 8}


class TestProcessEnvInfo:
    # the flight-recorder snapshot stamp: env protocol first (right
    # even before jax.distributed initializes), jax runtime fallback

    def test_launcher_env_wins(self):
        env = {topology.ENV_PROCESS_ID: "2",
               topology.ENV_NUM_PROCESSES: "4"}
        assert topology.process_env_info(env) == (2, 4, 0)

    def test_slice_id_from_process_mapping(self):
        env = {topology.ENV_PROCESS_ID: "3",
               topology.ENV_NUM_PROCESSES: "4",
               topology.ENV_SLICE_GROUPING: "process:0,0,1,1"}
        assert topology.process_env_info(env) == (3, 4, 1)

    def test_slice_id_process_identity(self):
        env = {topology.ENV_PROCESS_ID: "1",
               topology.ENV_NUM_PROCESSES: "2",
               topology.ENV_SLICE_GROUPING: "process"}
        assert topology.process_env_info(env) == (1, 2, 1)

    def test_device_keyed_grouping_does_not_apply(self):
        env = {topology.ENV_PROCESS_ID: "1",
               topology.ENV_NUM_PROCESSES: "2",
               topology.ENV_SLICE_GROUPING: "devices:4"}
        assert topology.process_env_info(env) == (1, 2, 0)

    def test_jax_fallback_single_process(self):
        assert topology.process_env_info({}) == (0, 1, 0)


def test_cpu_worker_env_requests_gloo_collectives():
    # a CPU worker exists to be one rank of many: without a collectives
    # backend the CPU client rejects every multi-process computation
    env = topology.cpu_worker_env({}, 2)
    assert env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] == "gloo"
    # an operator's explicit choice survives
    env = topology.cpu_worker_env(
        {"JAX_CPU_COLLECTIVES_IMPLEMENTATION": "mpi"}, 2)
    assert env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] == "mpi"
