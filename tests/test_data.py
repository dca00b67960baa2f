"""Prefetch loader tests + pipeline-parallel TRAINING (gradient) test."""

import numpy as np
import pytest

import jax

from jax import shard_map
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from hpc_patterns_tpu import parallel
from hpc_patterns_tpu.utils.data import PrefetchLoader, synthetic_tokens


class TestPrefetchLoader:
    def test_yields_all_batches_in_order(self):
        batches = [np.full((4,), i, np.float32) for i in range(10)]
        out = list(PrefetchLoader(batches, depth=3))
        assert len(out) == 10
        for i, b in enumerate(out):
            assert float(b[0]) == i
            assert isinstance(b, jax.Array)

    def test_worker_error_propagates(self):
        def bad():
            yield np.zeros(2)
            raise RuntimeError("corrupt shard")

        with pytest.raises(RuntimeError, match="corrupt shard"):
            list(PrefetchLoader(bad()))

    def test_custom_placer(self):
        dev = jax.devices()[0]
        loader = PrefetchLoader(
            [np.zeros((2,), np.float32)], place=lambda b: jax.device_put(b, dev)
        )
        (out,) = list(loader)
        assert out.devices() == {dev}

    def test_depth_validation(self):
        with pytest.raises(ValueError):
            PrefetchLoader([], depth=0)

    def test_synthetic_tokens_shapes(self):
        batches = list(synthetic_tokens(
            jax.random.PRNGKey(0), batch=2, seq=8, vocab=100, steps=3
        ))
        assert len(batches) == 3
        assert all(b.shape == (2, 8) for b in batches)
        assert all(0 <= b.min() and b.max() < 100 for b in batches)


class TestTokenFiles:
    def _file(self, tmp_path, n=1000, dtype="uint16"):
        from hpc_patterns_tpu.utils.data import write_token_file

        toks = np.arange(n)  # token value == file position
        path = tmp_path / "toks.bin"
        write_token_file(path, toks, dtype)
        return path, toks

    def test_memmap_windows_are_file_slices(self, tmp_path):
        from hpc_patterns_tpu.utils.data import memmap_tokens

        path, toks = self._file(tmp_path)
        for batch in memmap_tokens(path, batch=4, seq=16, steps=3, seed=1):
            assert batch.shape == (4, 16) and batch.dtype == np.int32
            for row in batch:
                # value == position, so a window is valid iff contiguous
                start = int(row[0])
                np.testing.assert_array_equal(row, toks[start:start + 16])

    def test_sequential_walk_covers_in_order(self, tmp_path):
        from hpc_patterns_tpu.utils.data import memmap_tokens

        path, toks = self._file(tmp_path)
        it = memmap_tokens(path, batch=2, seq=8, steps=2, sequential=True)
        a = next(it)
        np.testing.assert_array_equal(a[0], toks[0:8])
        np.testing.assert_array_equal(a[1], toks[8:16])

    def test_range_and_size_validation(self, tmp_path):
        from hpc_patterns_tpu.utils.data import (
            memmap_tokens,
            write_token_file,
        )

        with pytest.raises(ValueError, match="range"):
            write_token_file(tmp_path / "x.bin", [70000], "uint16")
        path, _ = self._file(tmp_path, n=10)
        with pytest.raises(ValueError, match="tokens"):
            next(memmap_tokens(path, batch=1, seq=32))
        with pytest.raises(ValueError, match="vocab"):
            next(memmap_tokens(path, batch=2, seq=4, vocab=5))

    def test_last_token_reachable(self, tmp_path):
        from hpc_patterns_tpu.utils.data import memmap_tokens

        # n == seq: exactly one window, covering the whole file
        path, toks = self._file(tmp_path, n=8)
        batch = next(memmap_tokens(path, batch=2, seq=8))
        np.testing.assert_array_equal(batch[0], toks)
        np.testing.assert_array_equal(batch[1], toks)


class TestAccumAndSchedules:
    @pytest.mark.slow  # two multi-step compiled train loops
    def test_accum_matches_big_batch(self):
        from hpc_patterns_tpu.models import TransformerConfig
        from hpc_patterns_tpu.models.train import (
            init_train_state,
            make_train_step,
        )

        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, max_seq=16, dtype="float32")
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64,
                                    "int32")
        p0, s0 = init_train_state(jax.random.PRNGKey(0), cfg)
        loss_a, pa, _ = make_train_step(cfg)(p0, s0, tokens)
        p1, s1 = init_train_state(jax.random.PRNGKey(0), cfg)
        loss_b, pb, _ = make_train_step(cfg, accum_steps=4)(p1, s1, tokens)
        np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    def test_cosine_schedule_validates(self):
        from hpc_patterns_tpu.models.train import make_optimizer

        with pytest.raises(ValueError, match="total_steps"):
            make_optimizer(schedule="cosine", warmup_steps=10, total_steps=5)
        make_optimizer(schedule="cosine", warmup_steps=2, total_steps=10)
        with pytest.raises(ValueError, match="schedule"):
            make_optimizer(schedule="linear")

    def test_accum_validation(self):
        from hpc_patterns_tpu.models import TransformerConfig
        from hpc_patterns_tpu.models.train import make_train_step

        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, max_seq=16, dtype="float32")
        with pytest.raises(ValueError, match="accum_steps"):
            make_train_step(cfg, accum_steps=0)


class TestPipelineTraining:
    @pytest.mark.slow  # two multi-step compiled training runs
    def test_pipeline_gradients_match_sequential(self, mesh8):
        """PP must work for training, not just inference: gradients
        through the ring handoffs equal the sequential model's."""
        M, B, F = 4, 2, 8
        x = jax.random.normal(jax.random.PRNGKey(0), (M, B, F))
        ws = jax.random.normal(jax.random.PRNGKey(1), (8, F, F)) / 4

        def stage(w, h):
            return jnp.tanh(jnp.dot(h, w))

        def seq_loss(ws):
            h = x
            for r in range(8):
                h = stage(ws[r], h)
            return jnp.mean(jnp.square(h))

        def pp_loss(ws):
            def local(x_all, w):
                outs = parallel.pipeline_forward(stage, w[0], x_all, "x")
                me = jax.lax.axis_index("x")
                # loss lives on the last stage; psum broadcasts it
                mine = jnp.where(me == 7, jnp.mean(jnp.square(outs)), 0.0)
                return jax.lax.psum(mine, "x")[None]

            per_rank = shard_map(
                local, mesh=mesh8,
                in_specs=(P(), P("x", None, None)),
                out_specs=P("x"),
            )(x, ws)
            return per_rank[0]

        want = jax.grad(seq_loss)(ws)
        got = jax.jit(jax.grad(pp_loss))(ws)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        # losses agree too
        assert float(pp_loss(ws)) == pytest.approx(float(seq_loss(ws)), rel=1e-5)
