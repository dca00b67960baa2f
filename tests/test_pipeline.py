"""On-chip DMA/compute pipeline tests (the Pallas side of C1).

Timing claims are TPU-only (``concurrency_app`` on the chip; no benchmark
cell holds them yet: PERF.md section 7, ``overlap-1chip``); here the
interpreter validates the kernel *semantics*: all computing variants
produce the identical checksum (the reference's self-validation idea,
SURVEY.md §4.2), scalars are runtime (no recompiles), and the
amortized-timing protocol is sane.
"""

import numpy as np
import pytest

from hpc_patterns_tpu.concurrency import pipeline
from hpc_patterns_tpu.harness.timing import amortized_seconds


@pytest.fixture(scope="module")
def hbm():
    return pipeline.make_hbm_array(4, 8, seed=1)


class TestOverlapKernel:
    def test_overlap_matches_serial_checksum(self, hbm):
        a = pipeline.overlap_run(hbm, mode="overlap", tripcount=3, passes=2)
        b = pipeline.overlap_run(hbm, mode="serial", tripcount=3, passes=2)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_checksum_depends_on_data(self, hbm):
        other = pipeline.make_hbm_array(4, 8, seed=2)
        a = pipeline.overlap_run(hbm, mode="serial", tripcount=3)
        b = pipeline.overlap_run(other, mode="serial", tripcount=3)
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_tripcount_changes_result(self, hbm):
        a = pipeline.overlap_run(hbm, mode="serial", tripcount=1)
        b = pipeline.overlap_run(hbm, mode="serial", tripcount=4)
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_dma_and_compute_modes_run(self, hbm):
        for mode in ("dma", "compute", "compute2"):
            out = pipeline.overlap_run(hbm, mode=mode, tripcount=2)
            assert np.asarray(out).shape == (8, 128)

    def test_out_direction_checksum_parity(self, hbm):
        # overlap_out's writeback flies under compute; the chain result
        # must be identical to the strictly-serialized walk
        a = pipeline.overlap_run(hbm, mode="overlap_out", tripcount=3, passes=2)
        b = pipeline.overlap_run(hbm, mode="serial_out", tripcount=3, passes=2)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_pair_modes_checksum_parity(self, hbm):
        # the copy-through pipeline must read the same chunks as the
        # strictly-serialized in/out walk
        a = pipeline.overlap_run(hbm, mode="pair_overlap", passes=2)
        b = pipeline.overlap_run(hbm, mode="pair_serial", passes=2)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_dma_out_mode_runs(self, hbm):
        out = pipeline.overlap_run(hbm, mode="dma_out", tripcount=1)
        assert np.asarray(out).shape == (8, 128)

    def test_bad_mode_and_shape(self, hbm):
        with pytest.raises(ValueError, match="mode"):
            pipeline.overlap_run(hbm, mode="warp")
        import jax.numpy as jnp

        with pytest.raises(ValueError, match="128"):
            pipeline.overlap_run(jnp.zeros((2, 8, 64)), mode="serial")


class TestAmortizedTiming:
    def test_differencing_recovers_per_iter_cost(self):
        import time

        def fake_run(iters):
            time.sleep(0.002 * iters + 0.01)  # per-iter cost + fixed latency
            return np.zeros(1)

        per = amortized_seconds(fake_run, iters=10, repetitions=2, warmup=0)
        assert 0.001 < per < 0.004  # ~2 ms, latency term cancelled

    def test_rejects_single_iter(self):
        with pytest.raises(ValueError):
            amortized_seconds(lambda n: np.zeros(1), iters=1)

    def test_negative_difference_clamps_to_zero(self):
        def noisy(iters):
            return np.zeros(1)

        assert amortized_seconds(noisy, iters=4, repetitions=1, warmup=0) >= 0.0
