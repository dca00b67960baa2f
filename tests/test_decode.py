"""KV-cache decoding vs the re-run-forward oracle (§4 style: the
incremental path must reproduce the batched one exactly)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.models import TransformerConfig, forward, init_params
from hpc_patterns_tpu.models.decode import (
    decode_step,
    greedy_generate,
    init_cache,
    prefill,
)

BASE = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=32, dtype="float32")


def _setup(batch=2, seed=0, **over):
    cfg = TransformerConfig(**{**BASE, **over})
    params = init_params(jax.random.PRNGKey(seed), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, 8), 0,
                                cfg.vocab, jnp.int32)
    return cfg, params, prompt


def _oracle_generate(params, prompt, cfg, new_tokens):
    """Greedy decode by re-running the full forward on the growing
    sequence — O(T^2) but trivially correct."""
    seq = prompt
    out = []
    for _ in range(new_tokens):
        logits = forward(params, seq, cfg)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


class TestPrefill:
    def test_last_logits_match_forward(self):
        cfg, params, prompt = _setup()
        logits, cache = prefill(params, prompt, cfg, max_len=16)
        want = forward(params, prompt, cfg)[:, -1]
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   atol=1e-5)
        assert len(cache["k"]) == 2  # per-layer buffers (in-place DUS)
        assert cache["k"][0].shape == (2, 4, 16, 8)

    def test_bad_lengths_rejected(self):
        cfg, params, prompt = _setup()
        with pytest.raises(ValueError, match="max_len"):
            prefill(params, prompt, cfg, max_len=4)  # < prompt
        with pytest.raises(ValueError, match="max_len"):
            prefill(params, prompt, cfg, max_len=cfg.max_seq + 1)


class TestDecodeStep:
    def test_incremental_logits_match_forward(self):
        # feed the prompt token-by-token through the cache; every step's
        # logits must equal the batched forward's logits at that position
        cfg, params, prompt = _setup()
        B, T = prompt.shape
        want = forward(params, prompt, cfg)  # (B, T, V)
        cache = init_cache(cfg, B, max_len=T)
        for t in range(T):
            logits, cache = decode_step(params, cache, jnp.int32(t),
                                        prompt[:, t], cfg)
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(want[:, t]), atol=1e-4,
                err_msg=f"position {t}",
            )


class TestGenerate:
    # fast tier keeps the baseline, GQA, and rope+GQA variants; the
    # rest (MoE routing, bf16 ties, plain rope — subsumed by rope+GQA)
    # are slow-tier
    @pytest.mark.parametrize("over", [
        {},
        {"n_kv_heads": 2},                  # GQA: grouped cache attention
        # MoE: decode routes drop-free, so the oracle forward must be
        # drop-free too (capacity_factor = n_experts => capacity =
        # token count); batch 4 actually exercises same-step routing
        # contention, which a capacity-limited decode would fail
        pytest.param({"n_experts": 2, "capacity_factor": 2.0},
                     marks=pytest.mark.slow),
        # top-2 routing must serve with top-2 too (a top-1 decode of a
        # top-k-trained model silently diverges from forward)
        {"n_experts": 2, "n_experts_top_k": 2, "capacity_factor": 2.0},
        pytest.param({"dtype": "bfloat16"}, marks=pytest.mark.slow),
        # post-rope keys in the cache
        pytest.param({"pos_embed": "rope"}, marks=pytest.mark.slow),
        {"pos_embed": "rope", "n_kv_heads": 2},
    ])
    @pytest.mark.parametrize(
        "seed", [0, pytest.param(7, marks=pytest.mark.slow)]
    )
    def test_matches_oracle(self, over, seed):
        cfg, params, prompt = _setup(batch=4, seed=seed, **over)
        got = greedy_generate(params, prompt, cfg, new_tokens=6)
        want = _oracle_generate(params, prompt, cfg, 6)
        assert got.shape == (4, 6)
        if over.get("dtype") == "bfloat16":
            # bf16: tiny logit diffs between the two association orders
            # may flip an argmax tie; demand near-total agreement
            agree = float(np.mean(np.asarray(got) == np.asarray(want)))
            assert agree >= 0.9, f"agreement {agree}"
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_single_token(self):
        cfg, params, prompt = _setup()
        got = greedy_generate(params, prompt, cfg, new_tokens=1)
        want = _oracle_generate(params, prompt, cfg, 1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_length_guards(self):
        cfg, params, prompt = _setup()
        with pytest.raises(ValueError, match="new_tokens"):
            greedy_generate(params, prompt, cfg, 0)
        with pytest.raises(ValueError, match="max_seq"):
            greedy_generate(params, prompt, cfg, cfg.max_seq)


class TestShardedServing:
    def test_tp_sharded_params_decode_exactly(self, mesh_dp_sp_tp):
        # serving-side tensor parallelism is pure GSPMD: Megatron-sharded
        # params flow through the decode einsums with XLA inserting the
        # tp collectives; tokens must be bit-identical to local decode
        from hpc_patterns_tpu.models.sharding import shard_params

        # decode_attn="gather": sharded serving rides GSPMD-partitioned
        # einsums (a pallas_call does not auto-partition); tokens must
        # still match the (default, flash-kernel) local decode exactly
        cfg, params, prompt = _setup()
        want = np.asarray(greedy_generate(params, prompt, cfg, 6))
        gcfg = TransformerConfig(**{**BASE, "decode_attn": "gather"})
        p_sh = shard_params(params, mesh_dp_sp_tp, gcfg)
        got = np.asarray(jax.device_get(
            greedy_generate(p_sh, prompt, gcfg, 6)
        ))
        np.testing.assert_array_equal(got, want)

    def test_tp_flash_kernel_decodes_exactly(self, mesh_dp_sp_tp):
        # the flash decode/prefill kernels under tp: shard_map manual
        # partition over whole kv-head blocks (round-4 route) — tokens
        # must match the unsharded flash decode exactly, so tp serving
        # keeps the kernel's position-proportional cache traffic
        from hpc_patterns_tpu.models.sharding import shard_params

        cfg, params, prompt = _setup(n_heads=4, n_kv_heads=2)
        want = np.asarray(greedy_generate(params, prompt, cfg, 6))
        p_sh = shard_params(params, mesh_dp_sp_tp, cfg)
        got = np.asarray(jax.device_get(
            greedy_generate(p_sh, prompt, cfg, 6, mesh=mesh_dp_sp_tp)
        ))
        np.testing.assert_array_equal(got, want)

    def test_tp_flash_int8_cache_decodes_exactly(self, mesh_dp_sp_tp):
        # int8 KV cache composes with the tp shard_map route (the
        # per-row scales shard with their kv heads)
        from hpc_patterns_tpu.models.sharding import shard_params

        cfg, params, prompt = _setup(n_heads=4, n_kv_heads=2,
                                     kv_cache_dtype="int8")
        want = np.asarray(greedy_generate(params, prompt, cfg, 6))
        p_sh = shard_params(params, mesh_dp_sp_tp, cfg)
        got = np.asarray(jax.device_get(
            greedy_generate(p_sh, prompt, cfg, 6, mesh=mesh_dp_sp_tp)
        ))
        np.testing.assert_array_equal(got, want)

    def test_tp_paged_generate_token_exact(self, mesh_dp_sp_tp):
        # the round-4 serving wins compose: PAGED cache x tp shard_map
        # — pools kv-head-sharded, the paged kernel manual-partitioned,
        # tokens identical to the unsharded paged decode (= generate)
        from hpc_patterns_tpu.models.decode import paged_generate
        from hpc_patterns_tpu.models.sharding import shard_params

        cfg, params, prompt = _setup(n_heads=4, n_kv_heads=2)
        want = np.asarray(paged_generate(params, prompt, cfg, 6,
                                         page_size=8))
        np.testing.assert_array_equal(
            want, np.asarray(greedy_generate(params, prompt, cfg, 6)))
        p_sh = shard_params(params, mesh_dp_sp_tp, cfg)
        got = np.asarray(jax.device_get(paged_generate(
            p_sh, prompt, cfg, 6, page_size=8, mesh=mesh_dp_sp_tp)))
        np.testing.assert_array_equal(got, want)

    def test_tp_paged_int8_token_exact(self, mesh_dp_sp_tp):
        # all three serving levers at once: paged pools + int8 pages +
        # tp (scale pools shard with their kv heads)
        from hpc_patterns_tpu.models.decode import paged_generate
        from hpc_patterns_tpu.models.sharding import shard_params

        cfg, params, prompt = _setup(n_heads=4, n_kv_heads=2,
                                     kv_cache_dtype="int8")
        want = np.asarray(paged_generate(params, prompt, cfg, 6,
                                         page_size=8))
        p_sh = shard_params(params, mesh_dp_sp_tp, cfg)
        got = np.asarray(jax.device_get(paged_generate(
            p_sh, prompt, cfg, 6, page_size=8, mesh=mesh_dp_sp_tp)))
        np.testing.assert_array_equal(got, want)

    def test_tp_paged_ragged_step_token_exact(self, mesh_dp_sp_tp):
        # ragged per-sequence positions through the SHARDED paged step:
        # logits must match the unsharded ragged step exactly
        from hpc_patterns_tpu.models.decode import (
            init_paged_cache,
            paged_decode_step,
            paged_prefill,
        )
        from hpc_patterns_tpu.models.sharding import shard_params

        cfg, params, prompt = _setup(n_heads=4, n_kv_heads=2)
        cache = init_paged_cache(cfg, 2, pages_per_seq=3, page_size=8)
        _, cache = paged_prefill(params, prompt, cfg, cache, 8)
        pos = jnp.array([8, 9], jnp.int32)
        tok = jnp.array([1, 2], jnp.int32)
        want, want_cache = paged_decode_step(params, cache, pos, tok, cfg)
        p_sh = shard_params(params, mesh_dp_sp_tp, cfg)
        sc = init_paged_cache(cfg, 2, pages_per_seq=3, page_size=8)
        _, sc = paged_prefill(p_sh, prompt, cfg, sc, 8,
                              mesh=mesh_dp_sp_tp)
        got, got_cache = paged_decode_step(p_sh, sc, pos, tok, cfg,
                                           mesh=mesh_dp_sp_tp)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        for a, b in zip(jax.tree.leaves(got_cache),
                        jax.tree.leaves(want_cache)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
        # ``active`` rides replicated like ``pos``: the live row's logits
        # are the same, whatever the idle row's came to
        live = jnp.array([False, True])
        one, _ = paged_decode_step(p_sh, sc, pos, tok, cfg,
                                   mesh=mesh_dp_sp_tp, active=live)
        np.testing.assert_allclose(np.asarray(one[1]), np.asarray(want[1]),
                                   atol=1e-5)

    def test_tp_paged_rejects_indivisible_kv_heads(self, mesh_dp_sp_tp):
        from hpc_patterns_tpu.models.decode import (
            init_paged_cache,
            paged_decode_step,
        )

        cfg, params, _ = _setup(n_heads=4, n_kv_heads=1)
        cache = init_paged_cache(cfg, 2, pages_per_seq=2, page_size=8)
        with pytest.raises(ValueError, match="kv_heads"):
            paged_decode_step(params, cache, jnp.int32(0),
                              jnp.array([1, 2], jnp.int32), cfg,
                              mesh=mesh_dp_sp_tp)

    def test_tp_not_dividing_kv_heads_warns_and_falls_back(
            self, mesh_dp_sp_tp):
        # tp=2 cannot split kv_heads=1: the flash request must warn and
        # serve on the gather path, still token-exact
        from hpc_patterns_tpu.models.sharding import shard_params

        cfg, params, prompt = _setup(n_heads=4, n_kv_heads=1)
        want = np.asarray(greedy_generate(params, prompt, cfg, 6))
        p_sh = shard_params(params, mesh_dp_sp_tp, cfg)
        with pytest.warns(UserWarning, match="falls back to the gather"):
            got = np.asarray(jax.device_get(
                greedy_generate(p_sh, prompt, cfg, 6, mesh=mesh_dp_sp_tp)
            ))
        np.testing.assert_array_equal(got, want)


class TestSampling:
    def test_top_k_1_is_greedy(self):
        from hpc_patterns_tpu.models.decode import generate

        cfg, params, prompt = _setup()
        greedy = greedy_generate(params, prompt, cfg, 5)
        sampled = generate(params, prompt, cfg, 5,
                           key=jax.random.PRNGKey(3), temperature=1.0,
                           top_k=1)
        np.testing.assert_array_equal(np.asarray(greedy),
                                      np.asarray(sampled))

    def test_sampling_valid_and_key_dependent(self):
        from hpc_patterns_tpu.models.decode import generate

        cfg, params, prompt = _setup()
        a = generate(params, prompt, cfg, 8, key=jax.random.PRNGKey(0),
                     temperature=1.0)
        b = generate(params, prompt, cfg, 8, key=jax.random.PRNGKey(1),
                     temperature=1.0)
        for t in (a, b):
            arr = np.asarray(t)
            assert arr.shape == (2, 8)
            assert arr.min() >= 0 and arr.max() < cfg.vocab
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_sampling_needs_key(self):
        from hpc_patterns_tpu.models.decode import generate

        cfg, params, prompt = _setup()
        with pytest.raises(ValueError, match="PRNG key"):
            generate(params, prompt, cfg, 2, temperature=1.0)


class TestInt8KVCache:
    def test_flash_matches_gather_on_int8(self):
        # implementation equality to f32 rounding: the kernel folds the
        # scales AFTER its dots (lane-major), the gather path before —
        # same math, different f32 association, so compare step LOGITS
        # within tight tolerance (bitwise token equality would be a
        # latent argmax-tie flake)
        cfg, params, prompt = _setup(kv_cache_dtype="int8")
        gcfg = TransformerConfig(**{**BASE, "kv_cache_dtype": "int8",
                                    "decode_attn": "gather"})
        _, cache = prefill(params, prompt, cfg, 16)
        tok = jnp.array([1, 2], jnp.int32)
        lf, _ = decode_step(params, cache, jnp.int32(8), tok, cfg)
        lg, _ = decode_step(params, cache, jnp.int32(8), tok, gcfg)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(lg),
                                   atol=1e-4)

    def test_int8_close_to_full_precision(self):
        # per-row int8 quantization: the step logits stay close to the
        # full-precision cache's (the quantization error bound), and
        # the cache is half the bytes
        cfg, params, prompt = _setup()
        qcfg = TransformerConfig(**{**BASE, "kv_cache_dtype": "int8"})
        _, cache_f = prefill(params, prompt, cfg, 16)
        _, cache_q = prefill(params, prompt, qcfg, 16)
        assert cache_q["k"][0].dtype == jnp.int8
        assert cache_f["k"][0].dtype == jnp.dtype(cfg.dtype)
        tok = jnp.array([1, 2], jnp.int32)
        lf, _ = decode_step(params, cache_f, jnp.int32(8), tok, cfg)
        lq, _ = decode_step(params, cache_q, jnp.int32(8), tok, qcfg)
        scale = np.abs(np.asarray(lf)).max()
        err = np.abs(np.asarray(lf) - np.asarray(lq)).max() / scale
        assert err < 0.05, err

    def test_int8_generate_agrees(self):
        cfg, params, prompt = _setup()
        qcfg = TransformerConfig(**{**BASE, "kv_cache_dtype": "int8"})
        want = np.asarray(greedy_generate(params, prompt, cfg, 8))
        got = np.asarray(greedy_generate(params, prompt, qcfg, 8))
        agree = float((want == got).mean())
        assert agree >= 0.75, agree  # argmax flips only near ties

    def test_bad_cache_dtype_rejected(self):
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            TransformerConfig(**{**BASE, "kv_cache_dtype": "int4"})


class TestSpeculative:
    """Greedy speculative decoding must emit EXACTLY the target's own
    greedy tokens — whatever the draft is (the acceptance rule only
    short-circuits agreement; disagreements are replaced by the
    target's token)."""

    @pytest.mark.parametrize("over", [
        {},
        {"pos_embed": "rope", "n_kv_heads": 2},  # the flagship serving
        # config: vectorized rope over chunk positions + the grouped
        # 5-axis extend einsum must stay oracle-exact too
    ])
    @pytest.mark.parametrize("gamma", [1, 3, 5])
    def test_token_identical_to_greedy(self, gamma, over):
        from hpc_patterns_tpu.models.speculative import speculative_generate

        cfg, params, prompt = _setup(batch=1, **over)
        # a DIFFERENT (smaller, differently-seeded) model drafts
        dcfg = TransformerConfig(**{**BASE, **over, "d_model": 16,
                                    "d_ff": 32, "n_layers": 1,
                                    "n_heads": 2,
                                    "n_kv_heads": min(
                                        2, over.get("n_kv_heads", 0))})
        dparams = init_params(jax.random.PRNGKey(42), dcfg)
        want = np.asarray(greedy_generate(params, prompt, cfg, 10))
        got = np.asarray(speculative_generate(
            params, cfg, dparams, dcfg, prompt, 10, gamma=gamma
        ))
        np.testing.assert_array_equal(got, want)

    def test_self_draft_is_still_exact(self):
        # target drafting for itself: maximal acceptance, same tokens
        from hpc_patterns_tpu.models.speculative import speculative_generate

        cfg, params, prompt = _setup(batch=1)
        want = np.asarray(greedy_generate(params, prompt, cfg, 8))
        got = np.asarray(speculative_generate(
            params, cfg, params, cfg, prompt, 8, gamma=4
        ))
        np.testing.assert_array_equal(got, want)

    def test_guards(self):
        from hpc_patterns_tpu.models.speculative import speculative_generate

        cfg, params, prompt = _setup(batch=2)
        with pytest.raises(ValueError, match="batch 1"):
            speculative_generate(params, cfg, params, cfg, prompt, 4)
        cfg1, params1, prompt1 = _setup(batch=1)
        bad = TransformerConfig(**{**BASE, "vocab": 32})
        with pytest.raises(ValueError, match="vocab"):
            speculative_generate(params1, cfg1, init_params(
                jax.random.PRNGKey(1), bad), bad, prompt1, 4)
        with pytest.raises(ValueError, match="PRNG key"):
            speculative_generate(params1, cfg1, params1, cfg1, prompt1, 4,
                                 temperature=0.8)


class TestSpeculativeSampling:
    """Rejection-sampling speculative decoding: the emitted tokens must
    be distributed EXACTLY as target-only sampling at the same
    temperature/top_k (Leviathan-style accept/resample). The primitive
    is pinned against the analytic law; the end-to-end path against its
    deterministic (top_k=1) limit."""

    def test_accept_resample_marginal_is_target(self):
        # fixed synthetic q (draft) and p (target) rows: over many
        # rounds, the FIRST emitted token (props[0] if accepted, else
        # the residual draw) must have marginal law exactly p_0 — the
        # defining property of the accept/resample rule
        from hpc_patterns_tpu.models.speculative import _accept_resample

        V, gamma, M = 6, 2, 20000
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.dirichlet(np.ones(V), size=gamma),
                        jnp.float32)
        p = jnp.asarray(rng.dirichlet(np.ones(V), size=gamma + 1),
                        jnp.float32)

        def draw(key):
            kq, kr = jax.random.split(key)
            props = jax.vmap(
                lambda k, row: jax.random.categorical(k, jnp.log(row))
            )(jax.random.split(kq, gamma), q).astype(jnp.int32)
            a, nxt = _accept_resample(kr, props, q, p)
            return jnp.where(a >= 1, props[0], nxt)

        keys = jax.random.split(jax.random.PRNGKey(1), M)
        firsts = np.asarray(jax.jit(jax.vmap(draw))(keys))
        emp = np.bincount(firsts, minlength=V) / M
        tv = 0.5 * np.abs(emp - np.asarray(p[0])).sum()
        assert tv < 0.02, (tv, emp, np.asarray(p[0]))

    def test_accept_resample_bonus_row_when_draft_matches(self):
        # q == p rows: every proposal accepts (ratio 1), the residual is
        # empty, and the closing token must fall back to a draw from the
        # bonus row p_gamma
        from hpc_patterns_tpu.models.speculative import _accept_resample

        V, gamma, M = 6, 2, 20000
        rng = np.random.default_rng(2)
        p = jnp.asarray(rng.dirichlet(np.ones(V), size=gamma + 1),
                        jnp.float32)
        q = p[:gamma]

        def draw(key):
            kq, kr = jax.random.split(key)
            props = jax.vmap(
                lambda k, row: jax.random.categorical(k, jnp.log(row))
            )(jax.random.split(kq, gamma), q).astype(jnp.int32)
            a, nxt = _accept_resample(kr, props, q, p)
            return a, nxt

        keys = jax.random.split(jax.random.PRNGKey(3), M)
        a, nxt = jax.jit(jax.vmap(draw))(keys)
        assert int(np.asarray(a).min()) == gamma  # all accepted, always
        emp = np.bincount(np.asarray(nxt), minlength=V) / M
        tv = 0.5 * np.abs(emp - np.asarray(p[gamma])).sum()
        assert tv < 0.02, tv

    def test_top_k_1_sampling_equals_greedy(self):
        # top_k=1 collapses both warped distributions to the argmax
        # point mass: the sampling path must emit exactly the greedy
        # speculative (= greedy target) tokens, end to end
        from hpc_patterns_tpu.models.speculative import speculative_generate

        cfg, params, prompt = _setup(batch=1)
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2})
        dparams = init_params(jax.random.PRNGKey(42), dcfg)
        want = np.asarray(greedy_generate(params, prompt, cfg, 10))
        got = np.asarray(speculative_generate(
            params, cfg, dparams, dcfg, prompt, 10, gamma=3,
            key=jax.random.PRNGKey(7), temperature=0.9, top_k=1,
        ))
        np.testing.assert_array_equal(got, want)

    def test_batched_sampling_rows_run_independently(self):
        # B=2 sampled rows: finite tokens in range, and each row equals
        # its per-sequence call with the same per-row key fold
        from hpc_patterns_tpu.models.speculative import (
            speculative_generate_batched,
        )

        cfg, params, prompt = _setup(batch=2)
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2})
        dparams = init_params(jax.random.PRNGKey(42), dcfg)
        got = np.asarray(speculative_generate_batched(
            params, cfg, dparams, dcfg, prompt, 8, gamma=2,
            key=jax.random.PRNGKey(5), temperature=0.8, top_k=4,
        ))
        assert got.shape == (2, 8)
        assert got.min() >= 0 and got.max() < cfg.vocab


class TestExtendStep:
    @pytest.mark.parametrize("over", [
        {},
        {"pos_embed": "rope"},
        {"n_kv_heads": 2},
    ])
    def test_extend_matches_sequential_steps(self, over):
        # one c-token extend == c single-token decode_steps: same
        # logits at every position, same cache contents
        cfg, params, prompt = _setup(**over)
        B, T = prompt.shape
        _, cache_a = prefill(params, prompt, cfg, 16)
        _, cache_b = prefill(params, prompt, cfg, 16)
        chunk = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
        from hpc_patterns_tpu.models.decode import extend_step

        le, cache_a = extend_step(params, cache_a, jnp.int32(T), chunk, cfg)
        for j in range(3):
            lj, cache_b = decode_step(params, cache_b, jnp.int32(T + j),
                                      chunk[:, j], cfg)
            np.testing.assert_allclose(np.asarray(le[:, j]),
                                       np.asarray(lj), atol=2e-4,
                                       err_msg=f"chunk position {j}")
        for a, b in zip(jax.tree.leaves(cache_a), jax.tree.leaves(cache_b)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=2e-5)


class TestSpeculativeBatched:
    def test_batched_rows_match_greedy(self):
        from hpc_patterns_tpu.models.speculative import (
            speculative_generate_batched,
        )

        cfg, params, _ = _setup(batch=1)
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2})
        dparams = init_params(jax.random.PRNGKey(42), dcfg)
        prompts = jax.random.randint(jax.random.PRNGKey(9), (3, 8), 0,
                                     cfg.vocab, jnp.int32)
        want = np.asarray(greedy_generate(params, prompts, cfg, 10))
        got = np.asarray(speculative_generate_batched(
            params, cfg, dparams, dcfg, prompts, 10, gamma=3
        ))
        np.testing.assert_array_equal(got, want)

    def test_batched_impls_agree_greedy(self):
        # the per-row-progress ragged impl and the round-3 vmap impl
        # must emit identical greedy tokens (both == target greedy) on
        # heterogeneous rows whose acceptance rates differ — rows
        # advancing at different per-round strides is the point
        from hpc_patterns_tpu.models.speculative import (
            speculative_generate_batched,
        )

        cfg, params, _ = _setup(batch=1)
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2})
        dparams = init_params(jax.random.PRNGKey(42), dcfg)
        # one row is the target's own prompt style, one is constant,
        # one adversarial — acceptance will differ row to row
        prompts = jnp.stack([
            jax.random.randint(jax.random.PRNGKey(9), (8,), 0,
                               cfg.vocab, jnp.int32),
            jnp.full((8,), 3, jnp.int32),
            jnp.arange(8, dtype=jnp.int32) * 7 % cfg.vocab,
        ])
        want = np.asarray(greedy_generate(params, prompts, cfg, 12))
        for impl in ("ragged", "vmap"):
            got = np.asarray(speculative_generate_batched(
                params, cfg, dparams, dcfg, prompts, 12, gamma=4,
                impl=impl))
            np.testing.assert_array_equal(got, want, err_msg=impl)

    def test_batched_ragged_sampling_in_range(self):
        from hpc_patterns_tpu.models.speculative import (
            speculative_generate_batched,
        )

        cfg, params, prompt = _setup(batch=2)
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2})
        dparams = init_params(jax.random.PRNGKey(42), dcfg)
        got = np.asarray(speculative_generate_batched(
            params, cfg, dparams, dcfg, prompt, 8, gamma=2,
            key=jax.random.PRNGKey(5), temperature=0.8, top_k=4,
            impl="ragged"))
        assert got.shape == (2, 8)
        assert got.min() >= 0 and got.max() < cfg.vocab

    def test_batched_ragged_tp_matches_greedy(self, mesh_dp_sp_tp):
        # the ragged impl under tp: draft steps ride the shard_map
        # paged-kernel route, the ragged extend partitions via GSPMD —
        # tokens must equal unsharded target greedy exactly
        from hpc_patterns_tpu.models.sharding import shard_params
        from hpc_patterns_tpu.models.speculative import (
            speculative_generate_batched,
        )

        cfg, params, prompt = _setup(batch=2, n_heads=4, n_kv_heads=2)
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2})
        dparams = init_params(jax.random.PRNGKey(42), dcfg)
        want = np.asarray(greedy_generate(params, prompt, cfg, 8))
        p_sh = shard_params(params, mesh_dp_sp_tp, cfg)
        d_sh = shard_params(dparams, mesh_dp_sp_tp, dcfg)
        got = np.asarray(jax.device_get(speculative_generate_batched(
            p_sh, cfg, d_sh, dcfg, prompt, 8, gamma=2,
            mesh=mesh_dp_sp_tp)))
        np.testing.assert_array_equal(got, want)

    def test_batched_ragged_int8_matches_greedy(self):
        # int8 pools through the ragged impl: the paged extend
        # quantizes chunk writes and dequantizes the gather, so the
        # output must equal the target's own int8 greedy decode
        from hpc_patterns_tpu.models.speculative import (
            speculative_generate_batched,
        )

        cfg, params, prompt = _setup(batch=2, kv_cache_dtype="int8")
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2,
                                    "kv_cache_dtype": "int8"})
        dparams = init_params(jax.random.PRNGKey(42), dcfg)
        want = np.asarray(greedy_generate(params, prompt, cfg, 8))
        got = np.asarray(speculative_generate_batched(
            params, cfg, dparams, dcfg, prompt, 8, gamma=2))
        np.testing.assert_array_equal(got, want)


class TestPagedExtend:
    @pytest.mark.parametrize("over", [
        {},
        {"pos_embed": "rope"},
        {"n_kv_heads": 2},
        {"kv_cache_dtype": "int8"},
    ])
    def test_ragged_extend_matches_sequential_ragged_steps(self, over):
        # one c-token RAGGED extend == c sequential ragged paged
        # decode_steps: same logits at every chunk position, same pool
        # contents — with every row at a DIFFERENT starting length
        from hpc_patterns_tpu.models.decode import (
            init_paged_cache,
            paged_decode_step,
            paged_extend_step,
            paged_prefill,
        )

        cfg, params, prompt = _setup(**over)
        pos = jnp.array([8, 9], jnp.int32)  # row 1 one past row 0
        chunk = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
        ca = init_paged_cache(cfg, 2, pages_per_seq=3, page_size=8)
        cb = init_paged_cache(cfg, 2, pages_per_seq=3, page_size=8)
        _, ca = paged_prefill(params, prompt, cfg, ca, 8)
        _, cb = paged_prefill(params, prompt, cfg, cb, 8)
        # row 1 needs its position-8 row filled before starting at 9
        _, cb = paged_decode_step(params, cb, jnp.array([12, 8],
                                                       jnp.int32),
                                  jnp.array([0, 9], jnp.int32), cfg)
        _, ca = paged_decode_step(params, ca, jnp.array([12, 8],
                                                       jnp.int32),
                                  jnp.array([0, 9], jnp.int32), cfg)
        le, ca = paged_extend_step(params, ca, pos, chunk, cfg)
        for j in range(3):
            lj, cb = paged_decode_step(params, cb, pos + j,
                                       chunk[:, j], cfg)
            np.testing.assert_allclose(np.asarray(le[:, j]),
                                       np.asarray(lj), atol=2e-5,
                                       err_msg=f"chunk position {j}")
        for a, b in zip(jax.tree.leaves(ca), jax.tree.leaves(cb)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    def test_guards(self):
        from hpc_patterns_tpu.models.decode import (
            init_paged_cache,
            paged_extend_step,
        )

        cfg, params, _ = _setup()
        cache = init_paged_cache(cfg, 2, pages_per_seq=2, page_size=8)
        with pytest.raises(ValueError, match="capacity"):
            paged_extend_step(params, cache, jnp.array([14, 3],
                                                       jnp.int32),
                              jnp.zeros((2, 3), jnp.int32), cfg)
        with pytest.raises(ValueError, match="per-row"):
            paged_extend_step(params, cache, jnp.int32(3),
                              jnp.zeros((2, 3), jnp.int32), cfg)


class TestPagedCache:
    """Block-table (paged) KV serving: the paged kernel must reproduce
    the linear kernel exactly through ANY page permutation, and
    paged_generate must be token-identical to generate — the capacity
    lever changes allocation, never tokens."""

    def test_paged_kernel_matches_linear_permuted_table(self):
        from hpc_patterns_tpu.ops.flash_decode import (
            flash_decode_attention,
            flash_decode_paged,
        )

        B, H, Hkv, D, P, pages = 2, 4, 2, 8, 16, 4
        S = P * pages
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
        kc = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
        vc = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
        pos = jnp.int32(37)  # mid-page, pages beyond never fetched
        want = flash_decode_attention(q, kc, vc, pos)

        perm = np.random.default_rng(0).permutation(B * pages)
        table = jnp.asarray(perm.reshape(B, pages), jnp.int32)
        pool_k = jnp.zeros((B * pages, Hkv, P, D), jnp.float32)
        pool_v = jnp.zeros_like(pool_k)
        for b in range(B):
            for j in range(pages):
                pool_k = pool_k.at[perm[b * pages + j]].set(
                    kc[b, :, j * P:(j + 1) * P])
                pool_v = pool_v.at[perm[b * pages + j]].set(
                    vc[b, :, j * P:(j + 1) * P])
        got = flash_decode_paged(q, pool_k, pool_v, table, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
        # every pages_per_step unroll (1 = the round-4 one-page-per-
        # grid-step form; 3 = ragged last group; auto > pages clamps)
        # walks the same permuted table to the same numbers — scalar
        # and ragged positions both
        rpos = jnp.array([37, 52], jnp.int32)
        want_r = flash_decode_attention(q, kc, vc, jnp.int32(52))
        for u in (1, 2, 3, None):
            got_u = flash_decode_paged(q, pool_k, pool_v, table, pos,
                                       pages_per_step=u)
            np.testing.assert_allclose(np.asarray(got_u),
                                       np.asarray(want), atol=1e-6,
                                       err_msg=f"unroll={u}")
            got_ur = flash_decode_paged(q, pool_k, pool_v, table, rpos,
                                        pages_per_step=u)
            np.testing.assert_allclose(np.asarray(got_ur[0]),
                                       np.asarray(want[0]), atol=1e-6,
                                       err_msg=f"ragged row0 unroll={u}")
            np.testing.assert_allclose(np.asarray(got_ur[1]),
                                       np.asarray(want_r[1]), atol=1e-6,
                                       err_msg=f"ragged row1 unroll={u}")

    @pytest.mark.parametrize("over", [
        {},
        {"pos_embed": "rope", "n_kv_heads": 2},  # flagship serving
    ])
    def test_paged_generate_token_exact(self, over):
        from hpc_patterns_tpu.models.decode import paged_generate

        cfg, params, prompt = _setup(**over)
        want = np.asarray(greedy_generate(params, prompt, cfg, 8))
        got = np.asarray(paged_generate(params, prompt, cfg, 8,
                                        page_size=8))
        np.testing.assert_array_equal(got, want)

    def test_paged_sampling_same_draws(self):
        # same key, same warp, bitwise-identical attention: the paged
        # path must emit the SAME sampled tokens as the linear path
        from hpc_patterns_tpu.models.decode import generate, paged_generate

        cfg, params, prompt = _setup()
        key = jax.random.PRNGKey(11)
        want = np.asarray(generate(params, prompt, cfg, 8, key=key,
                                   temperature=0.9, top_k=8))
        got = np.asarray(paged_generate(params, prompt, cfg, 8,
                                        page_size=8, key=key,
                                        temperature=0.9, top_k=8))
        np.testing.assert_array_equal(got, want)

    def test_allocation_tracks_need_not_max(self):
        # the capacity contract: pages allocate for prompt+new_tokens,
        # not cfg.max_seq — at max_seq=32 and 16 needed tokens the pool
        # is half the linear cache
        from hpc_patterns_tpu.models.decode import init_paged_cache

        cfg, params, prompt = _setup()  # max_seq 32
        cache = init_paged_cache(cfg, 2, pages_per_seq=2, page_size=8)
        pool_tokens = cache["k"][0].shape[0] * cache["k"][0].shape[2]
        assert pool_tokens == 2 * 2 * 8  # B * pages * page_size
        assert pool_tokens < 2 * cfg.max_seq

    def test_guards(self):
        from hpc_patterns_tpu.models.decode import (
            init_paged_cache,
            paged_generate,
        )

        cfg, params, prompt = _setup()
        with pytest.raises(ValueError, match="pages"):
            paged_generate(params, prompt, cfg, 8, page_size=8,
                           pages_per_seq=1)
        with pytest.raises(ValueError, match="entries"):
            from hpc_patterns_tpu.ops.flash_decode import (
                flash_decode_paged,
            )

            flash_decode_paged(
                jnp.zeros((2, 4, 8)), jnp.zeros((4, 4, 8, 8)),
                jnp.zeros((4, 4, 8, 8)),
                jnp.zeros((2, 2), jnp.int32),
                jnp.zeros((3,), jnp.int32),  # ragged pos != batch
            )

    @pytest.mark.parametrize("over", [{}, {"kv_cache_dtype": "int8"}])
    def test_identity_write_path_matches_scatter(self, over):
        # the in-place DUS fast path (identity table) must produce the
        # same logits/cache as the general scatter write — for bf16 AND
        # int8 pools (the scale-pool writes have both branches too)
        from hpc_patterns_tpu.models.decode import (
            init_paged_cache,
            paged_decode_step,
            paged_prefill,
        )

        cfg, params, prompt = _setup(**over)
        cache = init_paged_cache(cfg, 2, pages_per_seq=3, page_size=8)
        _, cache = paged_prefill(params, prompt, cfg, cache, 8)
        tok = jnp.array([1, 2], jnp.int32)
        l_scatter, c_scatter = paged_decode_step(
            params, cache, jnp.int32(8), tok, cfg)
        l_dus, c_dus = paged_decode_step(
            params, cache, jnp.int32(8), tok, cfg, identity_layout=True)
        np.testing.assert_allclose(np.asarray(l_scatter),
                                   np.asarray(l_dus), atol=1e-6)
        for a, b in zip(jax.tree.leaves(c_scatter),
                        jax.tree.leaves(c_dus)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_int8_pages_match_int8_linear(self):
        # int8 pools + scale pools: the paged path must reproduce the
        # int8 LINEAR flash path exactly (same per-row quantization,
        # same lane-folded dequant math, page indirection on both the
        # values and the scales)
        from hpc_patterns_tpu.models.decode import generate, paged_generate

        cfg, params, prompt = _setup(kv_cache_dtype="int8")
        want = np.asarray(generate(params, prompt, cfg, 8))
        got = np.asarray(paged_generate(params, prompt, cfg, 8,
                                        page_size=8))
        np.testing.assert_array_equal(got, want)

    def test_undersized_pool_default_table_rejected(self):
        # a default table over an undersized pool would alias pages
        # across sequences (silent K/V clobbering): must raise
        from hpc_patterns_tpu.models.decode import init_paged_cache

        cfg, _, _ = _setup()
        with pytest.raises(ValueError, match="pool_pages"):
            init_paged_cache(cfg, 2, pages_per_seq=2, page_size=8,
                             pool_pages=2)

    def test_prompt_within_a_page_of_max_seq(self):
        # page padding must not trip prefill's max_len <= max_seq guard:
        # prompt 17 + 3 new at max_seq 20 fits, though t_pad = 32 > 20
        from hpc_patterns_tpu.models.decode import paged_generate

        cfg = TransformerConfig(**{**BASE, "max_seq": 20})
        params = init_params(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                                    cfg.vocab, jnp.int32)
        want = np.asarray(greedy_generate(params, prompt, cfg, 3))
        got = np.asarray(paged_generate(params, prompt, cfg, 3,
                                        page_size=16))
        np.testing.assert_array_equal(got, want)

    def test_oversized_pool_identity_falls_back_to_scatter(self):
        # pool_pages > batch*pages_per_seq with an explicit identity
        # table: the DUS view layout would disagree with the table's
        # row numbering, so the fast path must fall through to the
        # scatter and stay token-exact
        from hpc_patterns_tpu.models.decode import (
            init_paged_cache,
            paged_decode_step,
            paged_prefill,
        )

        cfg, params, prompt = _setup()
        ident = jnp.arange(4, dtype=jnp.int32).reshape(2, 2)
        big = init_paged_cache(cfg, 2, pages_per_seq=2, page_size=8,
                               pool_pages=6, table=ident)
        exact = init_paged_cache(cfg, 2, pages_per_seq=2, page_size=8)
        _, big = paged_prefill(params, prompt, cfg, big, 8)
        _, exact = paged_prefill(params, prompt, cfg, exact, 8)
        tok = jnp.array([1, 2], jnp.int32)
        l_big, _ = paged_decode_step(params, big, jnp.int32(8), tok, cfg,
                                     identity_layout=True)
        l_exact, _ = paged_decode_step(params, exact, jnp.int32(8), tok,
                                       cfg, identity_layout=True)
        np.testing.assert_allclose(np.asarray(l_big), np.asarray(l_exact),
                                   atol=1e-6)

    def test_identity_promise_verified_for_concrete_table(self):
        # identity_layout=True with a PERMUTED concrete table over an
        # exact-size pool must raise — taking the DUS path there would
        # write to the wrong pool rows and corrupt other sequences' K/V
        from hpc_patterns_tpu.models.decode import (
            init_paged_cache,
            paged_decode_step,
        )

        cfg, params, _ = _setup()
        perm = jnp.array([[1, 0], [3, 2]], jnp.int32)
        cache = init_paged_cache(cfg, 2, pages_per_seq=2, page_size=8,
                                 table=perm)
        tok = jnp.array([1, 2], jnp.int32)
        with pytest.raises(ValueError, match="identity"):
            paged_decode_step(params, cache, jnp.int32(0), tok, cfg,
                              identity_layout=True)

    def test_past_capacity_concrete_pos_rejected(self):
        # direct (eager) callers with a concrete position past
        # pages_per_seq*page_size get the capacity guard paged_generate
        # provides — scalar and ragged forms both
        from hpc_patterns_tpu.models.decode import (
            init_paged_cache,
            paged_decode_step,
        )

        cfg, params, _ = _setup()
        cache = init_paged_cache(cfg, 2, pages_per_seq=2, page_size=8)
        tok = jnp.array([1, 2], jnp.int32)
        with pytest.raises(ValueError, match="capacity"):
            paged_decode_step(params, cache, jnp.int32(16), tok, cfg)
        with pytest.raises(ValueError, match="capacity"):
            paged_decode_step(params, cache,
                              jnp.array([3, 16], jnp.int32), tok, cfg)


class TestSpeculativeSharded:
    def test_tp_speculative_greedy_token_exact(self, mesh_dp_sp_tp):
        # speculative decoding under tp: prefills and draft steps ride
        # the shard_map flash route, the verify extend rides GSPMD —
        # tokens must equal the unsharded speculative (= plain greedy)
        from hpc_patterns_tpu.models.sharding import shard_params
        from hpc_patterns_tpu.models.speculative import speculative_generate

        cfg, params, prompt = _setup(batch=1, n_heads=4, n_kv_heads=2)
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2,
                                    "n_kv_heads": 2})
        dparams = init_params(jax.random.PRNGKey(42), dcfg)
        want = np.asarray(greedy_generate(params, prompt, cfg, 8))
        p_sh = shard_params(params, mesh_dp_sp_tp, cfg)
        dp_sh = shard_params(dparams, mesh_dp_sp_tp, dcfg)
        got = np.asarray(jax.device_get(speculative_generate(
            p_sh, cfg, dp_sh, dcfg, prompt, 8, gamma=3,
            mesh=mesh_dp_sp_tp,
        )))
        np.testing.assert_array_equal(got, want)


class TestRaggedPaged:
    @pytest.mark.parametrize("over", [
        {},
        {"pos_embed": "rope", "n_kv_heads": 2},  # flagship serving:
        # per-row rope rotation + the GQA grid-row mapping
        # (r // hkv_per_row) both ride the ragged path
        {"kv_cache_dtype": "int8"},  # quantized + ragged: per-row
        # positions through the scale-indirected kernel path
    ])
    def test_ragged_positions_per_row_oracle(self, over):
        # RAGGED serving: two sequences at different live lengths decode
        # in ONE paged step with a (B,) position vector; each row's
        # logits must equal its own single-sequence linear-flash decode
        from hpc_patterns_tpu.models.decode import (
            init_paged_cache,
            paged_decode_step,
        )

        cfg, params, _ = _setup(**over)
        P, pages = 8, 3
        Hkv, Dh = cfg.kv_heads, cfg.head_dim
        lens = (6, 11)
        prompts = [
            jax.random.randint(jax.random.PRNGKey(10 + i), (1, n), 0,
                               cfg.vocab, jnp.int32)
            for i, n in enumerate(lens)
        ]
        tok = jnp.array([3, 5], jnp.int32)

        want = []
        lins = []
        for i, p in enumerate(prompts):
            _, lin = prefill(params, p, cfg, pages * P)
            lins.append(lin)
            logits, _ = decode_step(params, lin, jnp.int32(lens[i]),
                                    tok[i:i + 1], cfg)
            want.append(np.asarray(logits[0]))

        # shared pool: each row's prefix pages placed at the identity
        # rows (b * pages + j)
        cache = init_paged_cache(cfg, 2, pages, P)
        pools = {n: list(cache[n]) for n in cache if n != "table"}
        for l in range(cfg.n_layers):
            for b in range(2):
                for name, pool in pools.items():
                    lin_l = lins[b][name][l]
                    if lin_l.ndim == 4:  # values (1, Hkv, S, D)
                        chunks = lin_l.reshape(
                            Hkv, pages, P, Dh).transpose(1, 0, 2, 3)
                    else:  # int8 scales (1, Hkv, S) -> (pages, Hkv, 1, P)
                        chunks = lin_l.reshape(
                            Hkv, pages, P).transpose(1, 0, 2)[:, :, None, :]
                    pool[l] = pool[l].at[
                        b * pages:(b + 1) * pages].set(chunks)
        cache = {**{n: tuple(p) for n, p in pools.items()},
                 "table": cache["table"]}

        pos = jnp.asarray(lens, jnp.int32)
        got, _ = paged_decode_step(params, cache, pos, tok, cfg)
        for b in range(2):
            np.testing.assert_allclose(np.asarray(got[b]), want[b],
                                       atol=1e-5, err_msg=f"row {b}")


def _window_pool_write(pool, page_ids, offset, rows):
    """The scatter as the parent of PR 31 wrote it, the K/V-head axis a
    window of the update: the oracle of :func:`_pool_write`'s values."""
    return pool.at[page_ids, :, offset, :].set(rows.astype(pool.dtype))


def _window_scale_write(pool, page_ids, offset, rows):
    return pool.at[page_ids, :, 0, offset].set(rows.astype(pool.dtype))


class TestPoolWrite:
    """``decode._pool_write`` indexes the K/V-head axis so that the TPU
    compiler scatters in the kernel's layout; the values are the window
    form's, to the last bit."""

    POOL, HKV, P, D = 9, 4, 8, 16     # the last page is the trash page

    @staticmethod
    def _draw(seed, shape, dtype):
        key = jax.random.PRNGKey(seed)
        if dtype == "int8":
            return jax.random.randint(key, shape, -127, 128, jnp.int8)
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    def _pool(self, dtype):
        return self._draw(0, (self.POOL, self.HKV, self.P, self.D), dtype)

    def _rows(self, n, dtype):
        # float32 rows whatever the floating pool: the write rounds them
        return self._draw(1, (n, self.HKV, self.D),
                          "int8" if dtype == "int8" else "float32")

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
    @pytest.mark.parametrize("form", ["ragged", "one_cursor", "sharded"])
    def test_rows_equal_the_window_form_to_the_last_bit(self, form, dtype):
        """Ragged offsets; the scalar cursor all rows share (off the
        identity layout it scatters too); and the form kept for pools
        sharded on the head axis."""
        from hpc_patterns_tpu.models.decode import _pool_write

        pool = self._pool(dtype)
        ids = jnp.array([3, 0, 7, 5, 1], jnp.int32)
        off = (jnp.int32(5) if form == "one_cursor"
               else jnp.array([7, 0, 3, 3, 5], jnp.int32))
        rows = self._rows(5, dtype)
        got = jax.jit(lambda p: _pool_write(
            p, ids, None, off, rows, 2, False,
            tp=2 if form == "sharded" else 1))(pool)
        want = _window_pool_write(pool, ids, off, rows)
        assert got.dtype == pool.dtype and got.shape == pool.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # and nothing else moved: 5 rows of HKV x D differ at most
        changed = np.asarray(got != pool).any(axis=-1)
        assert changed.sum() <= 5 * self.HKV

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
    def test_idle_rows_aimed_at_one_trash_page(self, dtype):
        """The engine points every idle slot at the trash page, position
        0: a scatter may keep any one of the rows that collide there. The
        live rows' pages, and the trash page off that position, are the
        window form's bits."""
        from hpc_patterns_tpu.models.decode import _pool_write

        trash = self.POOL - 1
        pool = self._pool(dtype)
        ids = jnp.array([2, trash, trash, 6, trash], jnp.int32)
        off = jnp.array([4, 0, 0, 1, 0], jnp.int32)
        rows = self._rows(5, dtype)
        got = np.asarray(_pool_write(pool, ids, None, off, rows, 2, False))
        want = np.asarray(_window_pool_write(pool, ids, off, rows))
        np.testing.assert_array_equal(got[:trash], want[:trash])
        np.testing.assert_array_equal(got[trash, :, 1:], want[trash, :, 1:])
        aimed = np.asarray(rows.astype(pool.dtype))[[1, 2, 4]]
        assert any(np.array_equal(got[trash, :, 0], r) for r in aimed)

    def test_scale_rows_equal_the_window_form(self):
        from hpc_patterns_tpu.models.decode import _scale_write

        pool = self._draw(3, (self.POOL, self.HKV, 1, self.P), "float32")
        ids = jnp.array([3, 0, 7, 5], jnp.int32)
        off = jnp.array([7, 0, 3, 3], jnp.int32)
        rows = self._draw(4, (4, self.HKV), "float32")
        got = _scale_write(pool, ids, None, off, rows, 2, False)
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(_window_scale_write(pool, ids, off, rows)))

    def test_tp_ragged_step_gains_no_collective(self, mesh_dp_sp_tp,
                                                monkeypatch):
        """Pools sharded on the K/V-head axis: the compiled ragged step
        must hold no collective that the window form's did not. (An index
        into the sharded axis would make GSPMD gather each pool.)"""
        import re

        from hpc_patterns_tpu.models import decode as D
        from hpc_patterns_tpu.models.sharding import shard_params

        cfg, params, prompt = _setup(n_heads=4, n_kv_heads=2)
        p_sh = shard_params(params, mesh_dp_sp_tp, cfg)
        sc = D.init_paged_cache(cfg, 2, pages_per_seq=3, page_size=8)
        _, sc = D.paged_prefill(p_sh, prompt, cfg, sc, 8,
                                mesh=mesh_dp_sp_tp)
        pos = jnp.array([8, 9], jnp.int32)
        tok = jnp.array([1, 2], jnp.int32)

        def collectives():
            text = jax.jit(lambda p, c: D.paged_decode_step(
                p, c, pos, tok, cfg, mesh=mesh_dp_sp_tp)).lower(
                    p_sh, sc).compile().as_text()
            return {kind: len(re.findall(rf" {kind}(-start)?\(", text))
                    for kind in ("all-gather", "all-reduce",
                                 "collective-permute", "all-to-all")}

        ours = collectives()
        real = D._pool_write
        monkeypatch.setattr(
            D, "_pool_write",
            lambda pool, ids, page, off, rows, pages, identity, tp=1:
            _window_pool_write(pool, ids, off, rows))
        assert ours == collectives()
        # what the test guards against is visible to it: the single-chip
        # form on the sharded pools does gather them
        monkeypatch.setattr(
            D, "_pool_write",
            lambda pool, ids, page, off, rows, pages, identity, tp=1:
            real(pool, ids, page, off, rows, pages, identity))
        assert collectives()["all-gather"] > ours["all-gather"]


def _parent_softmax_block(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref,
                          acc_ref, block_start, pos, scale, quantized):
    """The oracle of the kernels' arithmetic: ``_softmax_block`` as PR 34
    left it, two float32 ``Precision.HIGHEST`` products over the page cast
    to float32 (K transposed)."""
    from jax import lax

    from hpc_patterns_tpu.ops.flash_decode import _NEG_INF

    q = q_ref[:].astype(jnp.float32)
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                precision=lax.Precision.HIGHEST) * scale
    if quantized:
        s = s * ks_ref[:].astype(jnp.float32)
    k_pos = block_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos <= pos, s, _NEG_INF)
    m = m_ref[:]
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    rescale = jnp.exp(m - m_new)
    m_ref[:] = m_new
    l_ref[:] = l_ref[:] * rescale + p.sum(axis=-1, keepdims=True)
    if quantized:
        p = p * vs_ref[:].astype(jnp.float32)
    acc_ref[:] = acc_ref[:] * rescale + jnp.dot(
        p, v, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )


def _assert_within_1e6(got, want, err_msg=""):
    """``got`` within 1e-6 of ``want``, relative to ``want``'s scale: the
    bound the exact products are held to against the HIGHEST ones (the
    same partial products summed in another order)."""
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max(),
                               err_msg=err_msg)


def _parent_kernel_paged(pos_ref, table_ref, q_ref, *rest, scale, page_size,
                         unroll, quantized, hkv_per_row):
    """The paged kernel as it stood before the fetches followed the data
    (PR 32's tree), with PR 34's arithmetic: ``unroll`` page blocks a grid
    step as separate refs, every row walked, pages past the position
    clamped to the last."""
    from jax.experimental import pallas as pl

    from hpc_patterns_tpu.ops.flash_decode import _NEG_INF

    del table_ref
    U = unroll
    k_refs, rest = rest[:U], rest[U:]
    v_refs, rest = rest[:U], rest[U:]
    ks_refs = vs_refs = (None,) * U
    if quantized:
        ks_refs, rest = rest[:U], rest[U:]
        vs_refs, rest = rest[:U], rest[U:]
    o_ref, m_ref, l_ref, acc_ref = rest
    g, d = q_ref.shape
    si = pl.program_id(1)
    pos = (pos_ref[pl.program_id(0) // hkv_per_row] if hkv_per_row
           else pos_ref[0])

    @pl.when(si == 0)
    def _():
        m_ref[:] = jnp.full((g, 1), _NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros((g, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((g, d), jnp.float32)

    for j in range(U):
        start = (si * U + j) * page_size

        @pl.when(start <= pos)
        def _(j=j, start=start):
            _parent_softmax_block(q_ref, k_refs[j], v_refs[j], ks_refs[j],
                                  vs_refs[j], m_ref, l_ref, acc_ref, start,
                                  pos, scale, quantized)

    @pl.when(si == pl.num_programs(1) - 1)
    def _():
        o_ref[:] = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)


def _parent_flash_decode_paged(q, k_pool, v_pool, table, pos, *,
                               k_scale_pool=None, v_scale_pool=None, scale,
                               pages_per_step):
    """The oracle of :class:`TestPagedFetchSchedule`: the parent's
    ``flash_decode_paged``, interpreted."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    _, Hkv, P, _ = k_pool.shape
    pages = table.shape[1]
    g = H // Hkv
    quantized = k_scale_pool is not None
    ragged = jnp.ndim(pos) == 1
    U = max(1, min(pages_per_step, pages))

    def page_idx(j):
        def f(r, si, pos_ref, table_ref):
            b = r // Hkv
            live = jnp.minimum(si * U + j, pos_ref[b if ragged else 0] // P)
            return table_ref[b * pages + live], r % Hkv, 0, 0
        return f

    row = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    in_specs = [row((None, g, D), lambda r, si, pos, tab: (r, 0, 0))]
    in_specs += [row((None, None, P, D), page_idx(j)) for j in range(U)] * 2
    operands = [k_pool] * U + [v_pool] * U
    if quantized:
        in_specs += [row((None, None, 1, P), page_idx(j))
                     for j in range(U)] * 2
        operands += [k_scale_pool] * U + [v_scale_pool] * U
    out = pl.pallas_call(
        functools.partial(_parent_kernel_paged, scale=scale, page_size=P,
                          unroll=U, quantized=quantized,
                          hkv_per_row=Hkv if ragged else 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * Hkv, -(-pages // U)),
            in_specs=in_specs,
            out_specs=row((None, g, D), lambda r, si, pos, tab: (r, 0, 0)),
            scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                            pltpu.VMEM((g, 1), jnp.float32),
                            pltpu.VMEM((g, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, g, D), jnp.float32),
        interpret=True,
    )(jnp.asarray(pos, jnp.int32).reshape(B if ragged else 1),
      table.reshape(-1), q.reshape(B * Hkv, g, D), *operands)
    return out.reshape(B, H, D)


class TestPagedFetchSchedule:
    """``flash_decode_paged`` visits the rows that are live and, of each,
    the pages up to its position: whatever ``active``, ``pos`` and the
    table hold, a live row's output is the parent kernel's (the float32
    HIGHEST products) to 1e-6, the call's over every row to the bit (the
    same updates in the same order) and the gather route's to rounding,
    and a row that is not live comes out as zeros."""

    P, D, PAGES, B = 8, 16, 16, 6
    # a page's first row and its last, early and on the last page of 16
    ENDS = (0, 7, 8, 23, 120, 127)

    def _draw(self, case, group, kv_heads, dtype, fold, q_dtype="float32"):
        P, D, pages, B = self.P, self.D, self.PAGES, self.B
        rng = np.random.default_rng(case)
        n_pool = B * pages + 1                    # the last: the trash page
        shape = (n_pool, kv_heads, P, D)
        f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        pools = {"k": f32(*shape), "v": f32(*shape)}
        if dtype == "int8":
            from hpc_patterns_tpu.models.decode import _quantize_rows
            for name in ("k", "v"):
                rows, scales = _quantize_rows(pools[name], "int8")
                pools[name] = rows
                pools[name + "_scale"] = jnp.swapaxes(
                    scales.reshape(n_pool, kv_heads, P, 1), 2, 3)
        else:
            pools = {n: p.astype(dtype) for n, p in pools.items()}
        q = (f32(B, fold, group // fold * kv_heads, D) if fold > 1
             else f32(B, group * kv_heads, D)).astype(q_dtype)
        table = jnp.asarray(rng.permutation(n_pool - 1).reshape(B, pages),
                            jnp.int32)
        return rng, q, pools, table

    # q float32 takes the split of q; bfloat16, as the serving path hands
    # it (project_qkv / apply_rope return the compute dtype), one product
    @pytest.mark.parametrize(
        "case,group,kv_heads,dtype,pages_per_step,pos_form,q_dtype", [
            (0, 5, 4, "bfloat16", 8, "ragged", "float32"),
            (1, 5, 2, "int8", 1, "ragged", "float32"),
            (2, 5, 4, "bfloat16", 3, 0, "float32"),
            (3, 12, 2, "bfloat16", 8, "ragged", "float32"),
            (4, 12, 2, "int8", 3, "ragged", "float32"),
            (5, 12, 4, "bfloat16", 1, 7, "float32"),
            (6, 12, 2, "int8", 8, 8, "float32"),
            (7, 16, 2, "bfloat16", 8, "ragged", "float32"),
            (8, 16, 4, "int8", 8, "ragged", "float32"),
            (9, 16, 2, "bfloat16", 3, 120, "float32"),
            (10, 16, 4, "int8", 1, 127, "float32"),
            (11, 32, 4, "bfloat16", 8, "ragged", "float32"),
            (12, 32, 2, "bfloat16", 1, "ragged", "float32"),
            (13, 32, 4, "bfloat16", 3, "ragged", "float32"),
            (14, 5, 4, "bfloat16", 8, "ragged", "bfloat16"),
            (15, 5, 4, "int8", 3, "ragged", "bfloat16"),
            (16, 12, 2, "bfloat16", 8, "ragged", "bfloat16"),
            (17, 12, 2, "int8", 8, 127, "bfloat16"),
            (18, 16, 2, "bfloat16", 3, "ragged", "bfloat16"),
            (19, 16, 2, "int8", 1, "ragged", "bfloat16"),
            (20, 32, 4, "bfloat16", 8, "ragged", "bfloat16"),
            (21, 32, 4, "int8", 3, "ragged", "bfloat16"),
        ])
    def test_live_rows_equal_the_parent_kernel_and_idle_rows_are_zero(
            self, case, group, kv_heads, dtype, pages_per_step, pos_form,
            q_dtype):
        from types import SimpleNamespace

        from hpc_patterns_tpu.models.decode import _paged_attend_gather
        from hpc_patterns_tpu.ops.flash_decode import (
            flash_decode_paged, flash_decode_paged_block, fold_block,
            unfold_block)

        fold = 4 if group == 32 else 1    # a block of 4 folded into 8
        B = self.B
        rng, q, pools, table = self._draw(case, group, kv_heads, dtype, fold,
                                          q_dtype)
        scale = self.D ** -0.5
        kw = dict(k_scale_pool=pools.get("k_scale"),
                  v_scale_pool=pools.get("v_scale"), scale=scale,
                  pages_per_step=pages_per_step)
        if pos_form == "ragged":
            pos = jnp.asarray(rng.permutation(self.ENDS), jnp.int32)
            if fold > 1:                  # block starts: multiples of 4
                pos = pos // fold * fold
        else:
            pos = jnp.int32(pos_form)
        if fold > 1:
            attend = lambda **k: flash_decode_paged_block(
                q, pools["k"], pools["v"], table, pos, **kw, **k)
            last, flat = pos + (fold - 1), fold_block(q, kv_heads)
            back = lambda o: unfold_block(o, fold, kv_heads)
        else:
            attend = lambda **k: flash_decode_paged(
                q, pools["k"], pools["v"], table, pos, **kw, **k)
            last, flat, back = pos, q, lambda o: o
        parent = np.asarray(back(_parent_flash_decode_paged(
            flat, pools["k"], pools["v"], table, last, **kw)))
        gather = np.asarray(back(_paged_attend_gather(
            flat, pools["k"], pools["v"], pools.get("k_scale"),
            pools.get("v_scale"), table, last,
            SimpleNamespace(kv_heads=kv_heads, head_dim=self.D), scale)))

        every = np.asarray(attend())
        _assert_within_1e6(every, parent)
        np.testing.assert_allclose(every, gather, atol=2e-5)
        half = np.zeros(B, bool)
        half[rng.permutation(B)[:B // 2]] = True
        for live in (np.ones(B, bool), np.zeros(B, bool),
                     np.arange(B) == case % B, half):
            got = np.asarray(attend(active=jnp.asarray(live)))
            np.testing.assert_array_equal(got[live], every[live])
            assert not got[~live].any(), "an idle row's output is not zero"

    @pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
    def test_nothing_past_a_live_rows_position_is_used(self, dtype):
        """Every page no live row owns up to its position, the trash page
        among them, holds NaN, and the idle rows' table rows point at
        those: what the live rows get is finite and the clean run's."""
        from hpc_patterns_tpu.ops.flash_decode import flash_decode_paged

        B, P = self.B, self.P
        rng, q, pools, table = self._draw(33, 12, 2, dtype, 1)
        pos = jnp.asarray(rng.permutation(self.ENDS), jnp.int32)
        live = np.arange(B) % 2 == 0
        owned = np.zeros(pools["k"].shape[0], bool)
        for b in np.flatnonzero(live):
            owned[np.asarray(table)[b, :int(pos[b]) // P + 1]] = True
        # a NaN where a pool can hold one: an int8 page's in its scales
        dirty = {n: (jnp.where(owned[:, None, None, None], p, jnp.nan)
                     if jnp.issubdtype(p.dtype, jnp.floating) else p)
                 for n, p in pools.items()}
        run = lambda p, t: np.asarray(flash_decode_paged(
            q, p["k"], p["v"], t, pos, active=jnp.asarray(live),
            k_scale_pool=p.get("k_scale"), v_scale_pool=p.get("v_scale"),
            pages_per_step=3))
        clean = run(pools, table)
        # the idle rows own nothing: the trash page, as the engine leaves it
        strewn = jnp.where(jnp.asarray(live)[:, None], table,
                           pools["k"].shape[0] - 1)
        got = run(dirty, strewn)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, clean)


def _one_page(block, q, k, v, scales, state, start, pos, scale):
    """``block`` (a ``_softmax_block``) run once, interpreted, over one
    page from the online-softmax state ``state`` = (m, l, acc): returns
    the state it leaves."""
    from jax.experimental import pallas as pl

    quantized = scales is not None
    n_in = 5 if quantized else 3

    def kernel(*refs):
        ins, (m_in, l_in, a_in), (m, l, acc) = (
            refs[:n_in], refs[n_in:n_in + 3], refs[n_in + 3:])
        ks, vs = ins[3:] if quantized else (None, None)
        m[:], l[:], acc[:] = m_in[:], l_in[:], a_in[:]
        block(ins[0], ins[1], ins[2], ks, vs, m, l, acc, start, pos, scale,
              quantized)

    return pl.pallas_call(
        kernel, interpret=True,
        out_shape=tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                        for x in state),
    )(q, k, v, *(scales or ()), *state)


class TestExactProducts:
    """``_softmax_block``'s two products are the float32 HIGHEST products'
    nonzero terms in one bfloat16 product each: the values are HIGHEST's
    to 1e-6 (the same three partial products of p's pieces summed in
    another order), and the split of p into three bfloat16 pieces loses
    nothing. The scores where q is bfloat16 are HIGHEST's one nonzero
    product; on the chip both are one MXU product (equal to the bit there:
    PERF.md section 6, PR 35), but this CPU sums a bfloat16 dot in another
    order than a float32 one, so here they are held to 1e-6 as well."""

    P, D = 16, 16

    @pytest.mark.parametrize("low", [0.0, 20.0, 40.0])
    def test_the_split_of_p_is_exact(self, low):
        """hi + mid + lo == p in float32 for p in (0, 1]: what exp leaves
        after the running max, down to e^-65 (and 1 itself). Below 2^-102
        the last piece can be a subnormal, which is flushed, as in
        HIGHEST's own split: a weight 1e-31 of the row's largest."""
        from hpc_patterns_tpu.ops.flash_decode import _split3

        rng = np.random.default_rng(int(low))
        p = np.exp(-rng.uniform(low, low + 25.0, 4096)).astype(np.float32)
        p[:4] = (1.0, np.nextafter(np.float32(1), np.float32(0)),
                 np.float32(2.0 ** -126), np.float32(0.1))
        pieces = _split3(jnp.asarray(p))
        assert all(x.dtype == jnp.bfloat16 for x in pieces)
        hi, mid, lo = (np.asarray(x, np.float32) for x in pieces)
        np.testing.assert_array_equal((hi + mid) + lo, p)
        np.testing.assert_array_equal(hi + (mid + lo), p)

    @pytest.mark.parametrize("q_dtype", ["bfloat16", "float32"])
    def test_scores_are_the_highest_product(self, q_dtype):
        from jax import lax

        from hpc_patterns_tpu.ops.flash_decode import _exact_dot

        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.standard_normal((12, 128)), q_dtype)
        k = jnp.asarray(rng.standard_normal((256, 128)), jnp.bfloat16)
        got = np.asarray(_exact_dot(q, k, (1, 1)))
        want = np.asarray(jnp.dot(q.astype(jnp.float32),
                                  k.astype(jnp.float32).T,
                                  precision=lax.Precision.HIGHEST))
        _assert_within_1e6(got, want)

    @pytest.mark.parametrize("group", [5, 12, 16, 32])
    @pytest.mark.parametrize("form", ["bfloat16", "q float32", "int8"])
    def test_one_page_update_is_the_parents(self, group, form):
        """One online-softmax update from a running state, the row's
        position inside the page: m, l (every score goes into l) and acc
        the parent's to 1e-6."""
        from hpc_patterns_tpu.ops.flash_decode import _softmax_block

        P, D = self.P, self.D
        rng = np.random.default_rng(group)
        f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        q = f32(group, D).astype("float32" if form == "q float32"
                                 else "bfloat16")
        k, v, scales = f32(P, D), f32(P, D), None
        if form == "int8":
            k, v = (jnp.asarray(rng.integers(-127, 128, (P, D)), jnp.int8)
                    for _ in range(2))
            scales = (jnp.abs(f32(1, P)) / 64, jnp.abs(f32(1, P)) / 64)
        else:
            k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        state = (f32(group, 1), jnp.abs(f32(group, 1)) + 1, f32(group, D))
        args = (q, k, v, scales, state, 3 * P, 3 * P + 9, D ** -0.5)
        m, l, acc = _one_page(_softmax_block, *args)
        pm, pl_, pacc = _one_page(_parent_softmax_block, *args)
        _assert_within_1e6(m, pm)
        _assert_within_1e6(l, pl_)
        _assert_within_1e6(acc, pacc)
        assert not np.array_equal(np.asarray(m), np.asarray(state[0]))

    @pytest.mark.parametrize("group", [5, 12, 16, 32])
    @pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
    def test_the_linear_kernel_holds_the_same_bounds(self, monkeypatch,
                                                     group, dtype):
        """``flash_decode_attention`` shares the arithmetic: against
        itself with the parent's ``_softmax_block`` swapped in, at a
        position inside its second block."""
        from hpc_patterns_tpu.models.decode import _quantize_rows
        from hpc_patterns_tpu.ops import flash_decode as F

        B, Hkv, S, D = 2, 2, 64, self.D
        rng = np.random.default_rng(group + 100)
        f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        q = f32(B, group * Hkv, D).astype(jnp.bfloat16)
        kc, vc, kw = f32(B, Hkv, S, D), f32(B, Hkv, S, D), {}
        if dtype == "int8":
            (kc, ks), (vc, vs) = (_quantize_rows(c, "int8") for c in (kc, vc))
            kw = dict(k_scale=ks, v_scale=vs)
        else:
            kc, vc = kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16)
        run = lambda: np.asarray(F.flash_decode_attention(
            q, kc, vc, jnp.int32(45), block_s=32, **kw))
        got = run()
        monkeypatch.setattr(F, "_softmax_block", _parent_softmax_block)
        _assert_within_1e6(got, run())
