"""Interop suite tests (C10): native bindings, zero-copy proofs, app."""

import numpy as np
import pytest

import jax.numpy as jnp

from hpc_patterns_tpu.interop import native, zero_copy

pytestmark = pytest.mark.skipif(
    not (native.available() or native.build()),
    reason="native library unavailable",
)


class TestNativeBindings:
    def test_stats_matches_numpy(self):
        xs = [3.0, 1.0, 2.0, 5.0]
        got = native.stats(xs)
        assert got["min"] == 1.0 and got["max"] == 5.0
        np.testing.assert_allclose(got["mean"], np.mean(xs))
        np.testing.assert_allclose(got["std"], np.std(xs))

    def test_roundtrip_identity(self):
        xs = [0.1, 0.2, 0.3]
        assert native.stats_roundtrip(xs) == xs

    @pytest.mark.parametrize("alignment", [128, 4096, 1 << 21])
    def test_aligned_alloc(self, alignment):
        buf = native.AlignedBuffer(100, alignment=alignment)
        assert buf.address % alignment == 0
        view = buf.as_numpy()
        assert view.shape == (100,) and view.dtype == np.float32

    def test_fill_iota_validate(self):
        buf = native.AlignedBuffer(64)
        buf.fill(7.0)
        assert buf.validate(7.0) == -1
        buf.as_numpy()[10] = 8.0
        assert buf.validate(7.0) == 10  # first bad index, like the
        # reference's elementwise loop (allreduce-mpi-sycl.cpp:192-204)
        buf.iota(0.0, 2.0)
        np.testing.assert_allclose(buf.as_numpy()[:4], [0, 2, 4, 6])

    def test_ring_plan_matches_python(self):
        from hpc_patterns_tpu.comm.ring import _ring_perm

        for size in (2, 4, 8):
            for shift in (1, -1, 3):
                assert native.ring_plan(size, shift) == _ring_perm(size, shift)

    def test_ring_phases_cover_all_ranks_once(self):
        even = native.ring_phase_senders(8, 0)
        odd = native.ring_phase_senders(8, 1)
        assert sorted(even + odd) == list(range(8))
        assert all(r % 2 == 0 for r in even) and all(r % 2 == 1 for r in odd)


class TestZeroCopy:
    def test_numpy_jax_roundtrip_pointer_identity(self):
        # XLA aliases only >=64B-aligned imports — use the native
        # allocator (the reason it exists; see zero_copy.numpy_to_jax)
        buf = native.AlignedBuffer(256, alignment=128)
        buf.iota(0.0, 1.0)
        x = buf.as_numpy()
        arr, zc = zero_copy.numpy_to_jax(x)
        assert zc, "aligned numpy->jax must alias on CPU"
        back, zc2 = zero_copy.jax_to_numpy(arr)
        assert zc2
        np.testing.assert_array_equal(back, x)

    def test_unaligned_numpy_falls_back_to_copy(self):
        x = np.arange(257, dtype=np.float32)[1:]  # force 4B-offset storage
        arr, zc = zero_copy.numpy_to_jax(x)
        assert not zc  # copied, values still right
        np.testing.assert_array_equal(np.asarray(arr), x)

    def test_jax_torch_bridge(self):
        torch = pytest.importorskip("torch")
        import jax

        arr = jax.device_put(
            jnp.arange(64, dtype=jnp.float32), jax.devices("cpu")[0]
        )
        arr = jax.block_until_ready(arr)
        t, zc = zero_copy.jax_to_torch(arr)
        assert zc and isinstance(t, torch.Tensor)
        back, zc2 = zero_copy.torch_to_jax(t)
        assert zc2
        np.testing.assert_array_equal(np.asarray(back), np.asarray(arr))

    def test_view_outlives_buffer(self):
        """Regression: views keep the C allocation alive (no
        use-after-free when the AlignedBuffer is dropped first)."""
        import gc

        view = native.AlignedBuffer(64).as_numpy()  # buffer unreferenced
        gc.collect()
        view[:] = 1.0  # would corrupt freed heap without the owner ref
        assert view.sum() == 64.0

    def test_native_to_jax_chain(self):
        buf = native.AlignedBuffer(128)
        buf.iota(1.0, 1.0)
        arr, zc = zero_copy.native_to_jax(buf)
        assert zc
        np.testing.assert_allclose(
            np.asarray(arr), np.arange(1, 129, dtype=np.float32)
        )


class TestDeviceAliasing:
    def test_donation_writes_in_place(self):
        from hpc_patterns_tpu.interop import device

        ok, ev = device.donation_alias_proof(4096)
        assert ok, ev
        # CPU backend exposes raw pointers: identity must be proven,
        # not just the compiled contract
        assert ev["pointer_ok"] is True
        assert ev["contract_ok"] and ev["input_invalidated"]

    def test_pallas_input_output_alias(self):
        from hpc_patterns_tpu.interop import device

        ok, ev = device.pallas_alias_proof()
        assert ok, ev
        assert ev["pointer_ok"] is True
        assert ev["alias_bytes"] == ev["output_bytes"] > 0


class TestInteropApp:
    def test_app_passes(self, capsys, tmp_path):
        import json

        from hpc_patterns_tpu.apps import interop_app

        try:
            import torch  # noqa: F401 — app skips its torch legs without it

            min_passed = 7
        except ImportError:
            min_passed = 5
        log = tmp_path / "run.jsonl"
        code = interop_app.main(["-n", "4096", "--log", str(log)])
        out = capsys.readouterr().out
        assert code == 0, out
        assert "SUCCESS" in out
        assert out.count("Passed") >= min_passed
        # every app names its device first — on stdout and in the log —
        # and closes the log with the mode each Pallas kernel ran in
        first = out.splitlines()[0]
        assert first.startswith("device: jax=")
        for field in ("jaxlib=", "libtpu=", "platform=cpu",
                      "device_kind=cpu", "device_count=8"):
            assert field in first
        recs = [json.loads(l) for l in log.read_text().splitlines()]
        assert recs[0]["kind"] == "device"
        assert recs[0]["device_count"] == 8
        modes = [r for r in recs if r["kind"] == "kernels"][-1]["modes"]
        assert modes["pallas_alias_proof"]["interpret"] >= 1
        assert modes["pallas_alias_proof"]["compiled"] == 0

    @pytest.mark.slow  # compiles + embeds CPython, runs XLA in-process
    def test_native_driver_leg(self, capsys):
        from hpc_patterns_tpu.apps import interop_app

        code = interop_app.main(["-n", "4096", "--native-driver"])
        out = capsys.readouterr().out
        assert code == 0, out
        assert "native C++ XLA driver" in out
        assert "[driver] SUCCESS" in out
