"""Parallelism-strategy tests: every sharded result must equal the
single-device oracle (the analytic-validation style of SURVEY.md §4.2),
run as 8-way SPMD on the CPU mesh (conftest.py)."""

import warnings

import numpy as np
import pytest

import jax

from jax import shard_map
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from hpc_patterns_tpu import parallel
from hpc_patterns_tpu.parallel.ring_attention import full_attention

B, T, H, D = 2, 32, 8, 16  # global seq T sharded 8 ways -> 4 per rank


def _qkv(key, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def _shmap_seq(mesh, fn, *arrays, axis="x"):
    """Run a rank-local attention fn over sequence-sharded (dim 1) inputs."""
    spec = P(None, axis, None, None)
    mapped = shard_map(
        fn, mesh=mesh, in_specs=(spec,) * len(arrays), out_specs=spec
    )
    return jax.jit(mapped)(*arrays)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, mesh8, causal):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        got = _shmap_seq(
            mesh8,
            lambda q, k, v: parallel.ring_attention(q, k, v, "x", causal=causal),
            q, k, v,
        )
        want = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_bf16_inputs(self, mesh8):
        q, k, v = _qkv(jax.random.PRNGKey(1), jnp.bfloat16)
        got = _shmap_seq(
            mesh8, lambda q, k, v: parallel.ring_attention(q, k, v, "x"), q, k, v
        )
        want = full_attention(q, k, v)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_impl_matches_full_attention(self, mesh8, causal):
        q, k, v = _qkv(jax.random.PRNGKey(2))
        got = _shmap_seq(
            mesh8,
            lambda q, k, v: parallel.ring_attention(
                q, k, v, "x", causal=causal, impl="flash"
            ),
            q, k, v,
        )
        want = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_flash_impl_grad_matches_oracle(self, mesh8):
        q, k, v = _qkv(jax.random.PRNGKey(3))
        spec = P(None, "x", None, None)
        ringed = shard_map(
            lambda q, k, v: parallel.ring_attention(
                q, k, v, "x", causal=True, impl="flash"
            ),
            mesh=mesh8, in_specs=(spec,) * 3, out_specs=spec,
        )
        g_got = jax.jit(jax.grad(
            lambda q, k, v: ringed(q, k, v).sum(), argnums=(0, 1, 2)
        ))(q, k, v)
        g_want = jax.grad(
            lambda q, k, v: full_attention(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g_got, g_want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    def test_rejects_bad_impl(self, mesh8):
        with pytest.raises(ValueError, match="impl"):
            shard_map(
                lambda q: parallel.ring_attention(q, q, q, "x", impl="nope"),
                mesh=mesh8,
                in_specs=P(None, "x", None, None),
                out_specs=P(None, "x", None, None),
            )(jnp.zeros((B, T, H, D)))

    def test_rejects_bad_rank(self, mesh8):
        with pytest.raises(ValueError, match="head_dim"):
            shard_map(
                lambda q: parallel.ring_attention(q, q, q, "x"),
                mesh=mesh8, in_specs=P("x"), out_specs=P("x"),
            )(jnp.zeros((8, D)))


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, mesh8, causal):
        q, k, v = _qkv(jax.random.PRNGKey(2))
        got = _shmap_seq(
            mesh8,
            lambda q, k, v: parallel.ulysses_attention(q, k, v, "x", causal=causal),
            q, k, v,
        )
        want = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_impl_matches_full_attention(self, mesh8, causal):
        q, k, v = _qkv(jax.random.PRNGKey(4))
        got = _shmap_seq(
            mesh8,
            lambda q, k, v: parallel.ulysses_attention(
                q, k, v, "x", causal=causal, impl="flash"
            ),
            q, k, v,
        )
        want = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_flash_impl_grad_matches_oracle(self, mesh8):
        # flash's custom VJP composed with the all-to-all backward
        q, k, v = _qkv(jax.random.PRNGKey(5))
        spec = P(None, "x", None, None)
        mapped = shard_map(
            lambda q, k, v: parallel.ulysses_attention(
                q, k, v, "x", causal=True, impl="flash"
            ),
            mesh=mesh8, in_specs=(spec,) * 3, out_specs=spec,
        )
        g_got = jax.jit(jax.grad(
            lambda q, k, v: mapped(q, k, v).sum(), argnums=(0, 1, 2)
        ))(q, k, v)
        g_want = jax.grad(
            lambda q, k, v: full_attention(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g_got, g_want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    def test_heads_must_divide(self, mesh8):
        q = jnp.zeros((B, T, 6, D))  # 6 heads, 8 ranks
        with pytest.raises(Exception, match="divisible|not divisible"):
            _shmap_seq(
                mesh8, lambda q, k, v: parallel.ulysses_attention(q, k, v, "x"),
                q, q, q,
            )


class TestGQANarrowKV:
    """GQA with NARROW K/V (kv_heads < heads) through every impl — each
    must equal the expanded-K/V oracle exactly (no expansion happens
    inside; the oracle builds it explicitly)."""

    def _gqa_qkv(self, key, hkv, h=H, dtype=jnp.float32):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, T, h, D), dtype)
        k = jax.random.normal(ks[1], (B, T, hkv, D), dtype)
        v = jax.random.normal(ks[2], (B, T, hkv, D), dtype)
        return q, k, v

    def _want(self, q, k, v, causal=True):
        g = q.shape[2] // k.shape[2]
        return full_attention(
            q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2),
            causal=causal,
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_full_attention_grouped(self, causal):
        q, k, v = self._gqa_qkv(jax.random.PRNGKey(10), hkv=2)
        got = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self._want(q, k, v, causal)),
            atol=2e-5,
        )

    @pytest.mark.parametrize("impl", ["dense", "flash"])
    def test_ring_narrow_kv(self, mesh8, impl):
        # the narrow K/V block is what circulates: group-factor less
        # ppermute traffic per step
        q, k, v = self._gqa_qkv(jax.random.PRNGKey(11), hkv=2)
        got = _shmap_seq(
            mesh8,
            lambda q, k, v: parallel.ring_attention(
                q, k, v, "x", causal=True, impl=impl
            ),
            q, k, v,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self._want(q, k, v)), atol=2e-5
        )

    @pytest.mark.slow  # grad-through-GQA also covered in test_ops
    def test_ring_narrow_kv_grad(self, mesh8):
        q, k, v = self._gqa_qkv(jax.random.PRNGKey(12), hkv=2)
        spec = P(None, "x", None, None)
        ringed = shard_map(
            lambda q, k, v: parallel.ring_attention(
                q, k, v, "x", causal=True, impl="flash"
            ),
            mesh=mesh8, in_specs=(spec,) * 3, out_specs=spec,
        )
        g_got = jax.jit(jax.grad(
            lambda q, k, v: ringed(q, k, v).sum(), argnums=(0, 1, 2)
        ))(q, k, v)
        g_want = jax.grad(
            lambda q, k, v: self._want(q, k, v).sum(), argnums=(0, 1, 2)
        )(q, k, v)
        for a, b in zip(g_got, g_want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    @pytest.mark.parametrize("impl", ["dense", "flash"])
    def test_ulysses_narrow_kv_scatter(self, mesh8, impl):
        # kv_heads divides the axis: the narrow K/V ride the all-to-alls
        # — and do so SILENTLY (a warning here would mean the expansion
        # fallback stole the narrow-K/V win from a conforming config)
        q, k, v = self._gqa_qkv(jax.random.PRNGKey(13), hkv=8, h=16)
        with warnings.catch_warnings():
            warnings.filterwarnings("error", message=".*expanding K/V.*")
            got = _shmap_seq(
                mesh8,
                lambda q, k, v: parallel.ulysses_attention(
                    q, k, v, "x", causal=True, impl=impl
                ),
                q, k, v,
            )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self._want(q, k, v)), atol=2e-5
        )

    @pytest.mark.slow  # expansion fallback = pre-GQA path, stable
    def test_ulysses_narrow_kv_fallback(self, mesh8):
        # kv_heads does NOT divide the axis: expansion fallback, same
        # math, and LOUD — the lost narrow-K/V exchange saving must not
        # be silent
        q, k, v = self._gqa_qkv(jax.random.PRNGKey(14), hkv=2)
        with pytest.warns(UserWarning, match="expanding K/V"):
            got = _shmap_seq(
                mesh8,
                lambda q, k, v: parallel.ulysses_attention(
                    q, k, v, "x", causal=True
                ),
                q, k, v,
            )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self._want(q, k, v)), atol=2e-5
        )


class TestTensorParallel:
    def test_tp_mlp_matches_dense(self, mesh8):
        key = jax.random.PRNGKey(3)
        k1, k2, k3 = jax.random.split(key, 3)
        x = jax.random.normal(k1, (4, 16))
        w1 = jax.random.normal(k2, (16, 64)) / 4
        w2 = jax.random.normal(k3, (64, 16)) / 8
        want = jnp.dot(jax.nn.gelu(jnp.dot(x, w1)), w2)

        for algorithm in ("collective", "ring"):
            got = jax.jit(
                shard_map(
                    lambda x, a, b: parallel.tp_mlp(x, a, b, axis="x",
                                                    algorithm=algorithm),
                    mesh=mesh8,
                    in_specs=(P(), P(None, "x"), P("x", None)),
                    out_specs=P(),
                    # the ppermute ring is replicated by construction but
                    # VMA can't prove it (only psum infers replication)
                    check_vma=(algorithm == "collective"),
                )
            )(x, w1, w2)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-4
            )

    def test_row_parallel_scatter_matches_allreduce_shard(self, mesh8):
        key = jax.random.PRNGKey(4)
        x = jax.random.normal(key, (8, 64))
        w = jax.random.normal(jax.random.PRNGKey(5), (64, 32)) / 8
        want = jnp.dot(x, w)  # then sharded on last dim

        got = jax.jit(
            shard_map(
                lambda xl, wl: parallel.tensor.row_parallel_scatter(
                    xl, wl, axis="x"
                ),
                mesh=mesh8,
                in_specs=(P(None, "x"), P("x", None)),
                out_specs=P(None, "x"),
            )
        )(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)

    def test_bad_algorithm(self):
        with pytest.raises(ValueError, match="unknown algorithm"):
            parallel.row_parallel(jnp.zeros((2, 2)), jnp.zeros((2, 2)),
                                  axis="x", algorithm="smoke_signals")


class TestPipeline:
    def test_pipeline_equals_sequential_stages(self, mesh8):
        M, F = 6, 16
        key = jax.random.PRNGKey(6)
        x = jax.random.normal(key, (M, 4, F))
        # stage r: affine with stage-specific weights (stacked, sharded on x)
        ws = jax.random.normal(jax.random.PRNGKey(7), (8, F, F)) / 4

        def stage(w, h):
            return jnp.tanh(jnp.dot(h, w))

        got_all = jax.jit(
            shard_map(
                lambda x, w: parallel.pipeline_forward(
                    stage, w[0], x, "x"
                )[None],
                mesh=mesh8,
                in_specs=(P(), P("x", None, None)),
                out_specs=P("x"),
            )
        )(x, ws)
        got = np.asarray(got_all)[-1]  # outputs valid on the last rank

        want = x
        for r in range(8):
            want = stage(ws[r], want)
        np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)


class TestPipeline1F1B:
    def test_schedule_invariants(self):
        for P_, M in ((2, 2), (4, 8), (8, 8), (3, 7)):
            fwd, bwd = parallel.schedule_1f1b(P_, M)
            for r in range(P_):
                # no two ops of one stage share a tick
                ticks = [fwd[(r, m)] for m in range(M)] + \
                        [bwd[(r, m)] for m in range(M)]
                assert len(set(ticks)) == len(ticks), (P_, M, r)
                # activations arrive before their consumer needs them
                if r + 1 < P_:
                    for m in range(M):
                        assert fwd[(r, m)] < fwd[(r + 1, m)], (P_, M, r, m)
                # cotangents walk back one stage per tick
                if r > 0:
                    for m in range(M):
                        assert bwd[(r, m)] < bwd[(r - 1, m)], (P_, M, r, m)
                # 1F1B memory bound: stashed (forwarded, not yet
                # backwarded) microbatches never exceed min(P - r, M)
                events = sorted(
                    [(fwd[(r, m)], 1) for m in range(M)]
                    + [(bwd[(r, m)], -1) for m in range(M)]
                )
                live = peak = 0
                for _, delta in events:
                    live += delta
                    peak = max(peak, live)
                assert peak <= min(P_ - r, M), (P_, M, r, peak)

    def test_grads_match_sequential_oracle(self, mesh8):
        M, B, F = 8, 2, 8
        x = jax.random.normal(jax.random.PRNGKey(8), (M, B, F))
        tgt = jax.random.normal(jax.random.PRNGKey(9), (M, B, F))
        ws = jax.random.normal(jax.random.PRNGKey(10), (8, F, F)) / 3

        def stage(w, h):
            return jnp.tanh(jnp.dot(h, w))

        def loss_fn(y, t):
            return jnp.mean((y - t) ** 2)

        def local(x, t, w):
            loss, grads = parallel.pipeline_train_1f1b(
                stage, w[0], x, t, loss_fn, "x"
            )
            return loss[None], grads[None]

        loss, grads = jax.jit(
            shard_map(
                local,
                mesh=mesh8,
                in_specs=(P(), P(), P("x", None, None)),
                out_specs=(P("x"), P("x", None, None)),
            )
        )(x, tgt, ws)

        # oracle: the same 8-stage net, differentiated end-to-end
        def full_loss(ws):
            total = 0.0
            for m in range(M):
                h = x[m]
                for r in range(8):
                    h = stage(ws[r], h)
                total = total + loss_fn(h, tgt[m])
            return total

        want_g = jax.grad(full_loss)(ws)
        want_loss = full_loss(ws) / M

        # loss valid on the last rank only
        np.testing.assert_allclose(float(np.asarray(loss)[-1]),
                                   float(want_loss), rtol=1e-5)
        got_g = np.asarray(grads).reshape(8, F, F)
        np.testing.assert_allclose(got_g, np.asarray(want_g), atol=1e-4)


class TestPPTPPermute:
    # fast-tier coverage of the pp x tp packed-qkv column permutation
    # (the slow-tier pp x tp oracles in test_pp_model.py exercise it in
    # situ): permute -> contiguous tp split must hand each rank its own
    # [q_r|k_r|v_r] sections, and unpermute must invert exactly
    def test_roundtrip_and_block_layout(self):
        from hpc_patterns_tpu.models import TransformerConfig
        from hpc_patterns_tpu.models.pp import (
            tp_permute_wqkv,
            tp_unpermute_wqkv,
        )

        cfg = TransformerConfig(vocab=32, d_model=8, n_heads=4,
                                n_kv_heads=2, n_layers=2, d_ff=16,
                                max_seq=8, dtype="float32")
        tp = 2
        L, D = cfg.n_layers, cfg.d_model
        S = cfg.kv_heads * cfg.head_dim
        w = jnp.arange(L * D * (D + 2 * S), dtype=jnp.float32).reshape(
            L, D, D + 2 * S)
        perm = tp_permute_wqkv(w, cfg, tp)
        assert perm.shape == w.shape
        np.testing.assert_array_equal(
            np.asarray(tp_unpermute_wqkv(perm, cfg, tp)), np.asarray(w))
        # rank r's contiguous block == [q_r | k_r | v_r]
        q, k, v = np.split(np.asarray(w), [D, D + S], axis=-1)
        Dl, Sl = D // tp, S // tp
        for r, blk in enumerate(np.split(np.asarray(perm), tp, axis=-1)):
            np.testing.assert_array_equal(
                blk,
                np.concatenate(
                    [q[..., r * Dl:(r + 1) * Dl],
                     k[..., r * Sl:(r + 1) * Sl],
                     v[..., r * Sl:(r + 1) * Sl]], axis=-1),
            )
