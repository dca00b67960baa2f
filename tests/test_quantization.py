"""Quantized decode (round 13): the paged_flash kernel parity battery,
the precision-law oracles, and the quantized-pool round trips.

Three claim tiers, one file:

- **route parity** (interpret mode): ``decode_attn="paged_flash"``
  (ops/paged_attention.py) reproduces the gather route — BITWISE on
  compute-dtype (f32/bf16) pools, tight tolerance on quantized
  (int8/fp8) ones, across page counts, partial last pages, permuted
  tables, ragged positions, bucket rungs, and tp shards;
- **the precision law** (models/quantization.py): token identity
  cannot hold ACROSS precisions, so quantized KV and int8 weights are
  pinned by teacher-forced greedy top-1 agreement + TV-distance
  bounds — and the oracle has teeth (a broken dequant fails it);
- **round trips**: quantized pools survive preemption-and-resume,
  migration (wire codec bit-identical, scales included), and the
  residency tier — with the byte accounting showing the capacity win
  (pushes move the QUANTIZED bytes, ~0.53x a bf16 pool).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.models import TransformerConfig, init_params
from hpc_patterns_tpu.models.decode import (
    _paged_attend_gather,
    _quantize_rows,
    init_paged_cache,
    paged_generate,
    paged_tail_prefill,
)
from hpc_patterns_tpu.models.quantization import (
    precision_law,
    quantize_weights_int8,
)
from hpc_patterns_tpu.models.serving import ContinuousBatcher, EngineCore
from hpc_patterns_tpu.models.transformer import (
    QUANT_SCALE_SUFFIX,
    matmul_weight,
    serving_weights,
)
from hpc_patterns_tpu.ops.paged_attention import paged_attention_decode

BASE = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=64, dtype="float32", decode_attn="gather")


def _setup(**over):
    cfg = TransformerConfig(**{**BASE, **over})
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _quantized_pools(key, n_pool, Hkv, P, D, kv_dtype):
    """Random pools in the requested storage dtype, with the per-row
    scale pools the quantized family carries (None for compute)."""
    kk, kv = jax.random.split(key)
    k = jax.random.normal(kk, (n_pool, Hkv, P, D), jnp.float32)
    v = jax.random.normal(kv, (n_pool, Hkv, P, D), jnp.float32)
    if kv_dtype in ("float32", "bfloat16"):
        dt = jnp.dtype(kv_dtype)
        return k.astype(dt), v.astype(dt), None, None
    qk, sk = _quantize_rows(k.reshape(-1, D), kv_dtype)
    qv, sv = _quantize_rows(v.reshape(-1, D), kv_dtype)
    return (qk.reshape(n_pool, Hkv, P, D),
            qv.reshape(n_pool, Hkv, P, D),
            sk.reshape(n_pool, Hkv, 1, P),
            sv.reshape(n_pool, Hkv, 1, P))


class TestPagedFlashKernelParity:
    """The interpret-mode parity battery: the exact-softmax kernel vs
    ``_paged_attend_gather`` on identical pools. Compute dtypes assert
    BITWISE equality (the kernel mirrors the gather math term for
    term); quantized dtypes are held to tight tolerance — the contract
    tier, since the dequant multiply order is the one place a backend
    may legally differ."""

    CFG = TransformerConfig(**BASE)

    def _battery(self, kv_dtype, pages, pos, *, permute=False, B=2,
                 Hkv=2, H=4, D=8, P=16):
        key = jax.random.PRNGKey(hash((kv_dtype, pages)) % (2 ** 31))
        q = jax.random.normal(key, (B, H, D), jnp.float32)
        kp, vp, ks, vs = _quantized_pools(
            jax.random.fold_in(key, 1), B * pages, Hkv, P, D, kv_dtype)
        ids = np.arange(B * pages, dtype=np.int32)
        if permute:
            ids = np.random.default_rng(3).permutation(ids)
        table = jnp.asarray(ids.reshape(B, pages), jnp.int32)
        cfg = dataclasses.replace(self.CFG, n_kv_heads=Hkv)
        scale = 1.0 / D ** 0.5
        want = _paged_attend_gather(q, kp, vp, ks, vs, table, pos, cfg,
                                    scale)
        got = paged_attention_decode(q, kp, vp, table, pos,
                                     k_scale_pool=ks, v_scale_pool=vs,
                                     scale=scale)
        if kv_dtype in ("float32", "bfloat16"):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
        else:
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want), atol=1e-6)

    @pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16",
                                          "int8", "fp8"])
    @pytest.mark.parametrize("pages,pos", [
        (1, 9),     # single partial page
        (4, 37),    # mid-table, partial last live page
    ])
    def test_matches_gather_scalar_pos(self, kv_dtype, pages, pos):
        self._battery(kv_dtype, pages, jnp.int32(pos))

    @pytest.mark.parametrize("pages,pos", [
        (1, 0),     # single page, first position
        (4, 63),    # exactly full table
        (7, 40),    # live prefix well short of the allocation
    ])
    def test_matches_gather_grid_edges(self, pages, pos):
        # the grid-geometry edges need one dtype (the clamp/mask logic
        # is dtype-blind; the dtype sweep above covers the dequant)
        self._battery("float32", pages, jnp.int32(pos))

    @pytest.mark.parametrize("kv_dtype", ["float32", "int8", "fp8"])
    def test_matches_gather_ragged_and_permuted(self, kv_dtype):
        # ragged per-row positions over a PERMUTED table: each row
        # clamps/masks by its own fill, pages anywhere in the pool
        self._battery(kv_dtype, 4, jnp.array([5, 50], jnp.int32),
                      permute=True)

    def test_guards(self):
        q = jnp.zeros((2, 4, 8), jnp.float32)
        kp = jnp.zeros((8, 2, 16, 8), jnp.float32)
        table = jnp.zeros((2, 4), jnp.int32)
        sc = jnp.zeros((8, 2, 1, 16), jnp.float32)
        with pytest.raises(ValueError, match="refuses"):
            paged_attention_decode(q, kp, kp, table, jnp.int32(0),
                                   k_scale_pool=sc, v_scale_pool=sc)
        with pytest.raises(ValueError, match="needs"):
            paged_attention_decode(q, kp.astype(jnp.int8),
                                   kp.astype(jnp.int8), table,
                                   jnp.int32(0))
        with pytest.raises(ValueError, match="come together"):
            paged_attention_decode(q, kp.astype(jnp.int8),
                                   kp.astype(jnp.int8), table,
                                   jnp.int32(0), k_scale_pool=sc)
        with pytest.raises(ValueError, match="table rows"):
            paged_attention_decode(q, kp, kp, table[:1], jnp.int32(0))

    def test_mask_constant_matches_flash_routes(self):
        # the kernel cannot import ring_attention's constant (circular
        # via comm.ring -> ops) so it respells it; the bitwise
        # route-parity contract requires the spellings never drift
        from hpc_patterns_tpu.ops.paged_attention import (
            _NEG_INF as kernel_neg_inf,
        )
        from hpc_patterns_tpu.parallel.ring_attention import (
            _NEG_INF as flash_neg_inf,
        )

        assert kernel_neg_inf == flash_neg_inf


class TestPagedFlashRoute:
    """End to end through ``paged_decode_step``: swapping
    ``decode_attn`` between "gather" and "paged_flash" must not change
    a token — the prefill bytes are identical (paged_flash prefills on
    the einsum route like gather) and the kernel mirrors the step
    math."""

    @pytest.mark.parametrize("kv_dtype", ["compute", "int8", "fp8"])
    def test_token_identical_to_gather(self, kv_dtype):
        cfg, params = _setup(kv_cache_dtype=kv_dtype)
        pf = dataclasses.replace(cfg, decode_attn="paged_flash")
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 9), 0,
                                    cfg.vocab, jnp.int32)
        want = np.asarray(paged_generate(params, prompt, cfg, 8,
                                         page_size=8))
        got = np.asarray(paged_generate(params, prompt, pf, 8,
                                        page_size=8))
        np.testing.assert_array_equal(got, want)

    def test_sampled_draws_identical_to_gather(self):
        cfg, params = _setup(kv_cache_dtype="int8")
        pf = dataclasses.replace(cfg, decode_attn="paged_flash")
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 9), 0,
                                    cfg.vocab, jnp.int32)
        key = jax.random.PRNGKey(5)
        want = np.asarray(paged_generate(
            params, prompt, cfg, 6, page_size=8, key=key,
            temperature=0.7, top_k=16))
        got = np.asarray(paged_generate(
            params, prompt, pf, 6, page_size=8, key=key,
            temperature=0.7, top_k=16))
        np.testing.assert_array_equal(got, want)

    def test_engine_rung_coverage_oracle(self):
        # the serving route: a bucket ladder spreads admissions over
        # rungs (partial pages, varied table spans) and every served
        # sequence must equal standalone decode under the SAME config
        cfg, params = _setup(kv_cache_dtype="int8",
                             decode_attn="paged_flash")
        rng = np.random.RandomState(4)
        reqs = [(rng.randint(0, cfg.vocab,
                             size=int(rng.choice([5, 9, 14])))
                 .astype(np.int32), int(rng.choice([3, 7])))
                for _ in range(4)]
        eng = ContinuousBatcher(
            params, cfg, slots=2, pool_pages=12, pages_per_seq=6,
            page_size=8, chunk=2, prompt_buckets=(8, 16))
        ids = [eng.submit(p, b) for p, b in reqs]
        got = eng.run()
        for i, (p, b) in enumerate(reqs):
            want = np.asarray(paged_generate(
                params, jnp.asarray(p)[None], cfg, b, page_size=8))[0]
            np.testing.assert_array_equal(got[ids[i]], want,
                                          err_msg=f"seq {i}")

    def test_tp_sharded_token_exact(self, mesh_dp_sp_tp):
        # the shard_map manual partition (whole kv-head blocks per
        # rank) over the paged_flash kernel — tokens identical to the
        # unsharded run, quantized pool included
        from hpc_patterns_tpu.models.sharding import shard_params

        cfg, params = _setup(n_kv_heads=2, kv_cache_dtype="int8",
                             decode_attn="paged_flash")
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 9), 0,
                                    cfg.vocab, jnp.int32)
        want = np.asarray(paged_generate(params, prompt, cfg, 6,
                                         page_size=8))
        p_sh = shard_params(params, mesh_dp_sp_tp, cfg)
        got = np.asarray(jax.device_get(paged_generate(
            p_sh, prompt, cfg, 6, page_size=8, mesh=mesh_dp_sp_tp)))
        np.testing.assert_array_equal(got, want)


class TestPrecisionLaw:
    """The cross-precision contract: teacher-forced greedy agreement
    and TV-distance bounds per precision — and proof the oracle can
    actually fail."""

    PROMPTS = np.arange(3 * 12, dtype=np.int32).reshape(3, 12) % 60

    @pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
    def test_kv_precision_within_bounds(self, kv_dtype):
        cfg, params = _setup()
        qcfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
        law = precision_law(params, cfg, params, qcfg, self.PROMPTS,
                            steps=4)
        law.check()
        assert law.steps == 4

    def test_weight_quant_within_bounds(self):
        cfg, params = _setup()
        qp = quantize_weights_int8(params)
        law = precision_law(params, cfg, qp, cfg, self.PROMPTS,
                            steps=4)
        law.check()

    def test_composed_kv_and_weights_within_bounds(self):
        cfg, params = _setup()
        qcfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
        qp = quantize_weights_int8(params)
        precision_law(params, cfg, qp, qcfg, self.PROMPTS,
                      steps=4).check()

    def test_oracle_has_teeth(self):
        # a broken dequant path (scales silently doubled) must FAIL
        # the law — otherwise the gate is a rubber stamp
        cfg, params = _setup()
        qp = quantize_weights_int8(params)
        broken = dict(qp)
        layers = dict(qp["layers"])
        layers["wqkv" + QUANT_SCALE_SUFFIX] = (
            layers["wqkv" + QUANT_SCALE_SUFFIX] * 2.0)
        broken["layers"] = layers
        law = precision_law(params, cfg, broken, cfg, self.PROMPTS,
                            steps=4)
        with pytest.raises(AssertionError, match="precision law"):
            law.check()

    def test_guards(self):
        cfg, params = _setup()
        with pytest.raises(ValueError, match="max_seq"):
            precision_law(params, cfg, params, cfg,
                          np.zeros((1, 60), np.int32), steps=8)


class TestQuantizedWeights:
    def test_structure_and_dequant_bound(self):
        cfg, params = _setup()
        qp = quantize_weights_int8(params)
        for name in ("wqkv", "wo", "w1", "w2"):
            w = qp["layers"][name]
            s = qp["layers"][name + QUANT_SCALE_SUFFIX]
            assert w.dtype == jnp.int8
            assert s.shape == w.shape[:1] + w.shape[2:]  # (L, d_out)
            # per-channel symmetric quantization error <= scale / 2
            orig = np.asarray(params["layers"][name], np.float32)
            deq = np.asarray(w, np.float32) * np.asarray(s)[:, None, :]
            assert np.all(np.abs(deq - orig)
                          <= np.asarray(s)[:, None, :] * 0.5 + 1e-7)
        assert qp["lm_head"].dtype == jnp.int8
        assert qp["embed"].dtype == params["embed"].dtype  # not a GEMM
        # dequant-at-use lands in the compute dtype
        got = matmul_weight(qp["layers"], "wo", jnp.float32)
        assert got.dtype == jnp.float32

    def test_accessor_is_identity_for_plain_params(self):
        cfg, params = _setup()
        w = matmul_weight(params["layers"], "wo", jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(w), np.asarray(params["layers"]["wo"],
                                      np.float32))

    def test_serving_weights_leave_int8_and_scales_alone(self):
        # an engine over an int8 tree in a bfloat16 config: the int8
        # values and their float32 scales are the caller's own arrays
        # (matmul_weight multiplies them in float32; a bfloat16 scale
        # would be another result); the embedding and the norm scales,
        # which the use sites cast to cfg.dtype, are cast once
        cfg, params = _setup(dtype="bfloat16")
        qp = quantize_weights_int8(params)
        held = serving_weights(qp, cfg)
        flat = lambda t: {jax.tree_util.keystr(k): v for k, v in
                          jax.tree_util.tree_flatten_with_path(t)[0]}
        before, after = flat(qp), flat(held)
        assert before.keys() == after.keys()
        cast = sorted(k for k in before if after[k] is not before[k])
        assert cast == sorted(["['embed']", "['pos_embed']",
                               "['ln_f_scale']",
                               "['layers']['ln1_scale']",
                               "['layers']['ln2_scale']"])
        for k in cast:
            assert after[k].dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(after[k], np.float32),
                np.asarray(before[k].astype(jnp.bfloat16), np.float32))
        assert {after[k].dtype for k in after
                if k.endswith(QUANT_SCALE_SUFFIX + "']")} == {
                    jnp.dtype(jnp.float32)}
        eng = EngineCore(qp, cfg, slots=2, pool_pages=4, pages_per_seq=2,
                         page_size=8)
        assert eng.weight_bytes["leaves"] == len(cast)
        assert eng.params["lm_head"] is qp["lm_head"]

    def test_moe_refused(self):
        cfg = TransformerConfig(**{**BASE, "n_experts": 2})
        params = init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="MoE"):
            quantize_weights_int8(params)

    def _manual_dequant(self, qp):
        """The tree matmul_weight would produce at every site, with the
        scale keys dropped — running it through the model must then be
        IDENTICAL to running the quantized tree (same values feed the
        same dots), which catches any site still on the raw
        ``.astype`` spelling (it would apply ~±127 int8 magnitudes)."""
        layers = dict(qp["layers"])
        for name in ("wqkv", "wo", "w1", "w2"):
            layers[name] = matmul_weight(layers, name, jnp.float32)
            del layers[name + QUANT_SCALE_SUFFIX]
        deq = dict(qp, layers=layers)
        deq["lm_head"] = matmul_weight(deq, "lm_head", jnp.float32)
        del deq["lm_head" + QUANT_SCALE_SUFFIX]
        return deq

    def test_every_matmul_site_dequantizes(self):
        # the training-layer forward (wqkv/wo/w1/w2 + lm_head), the
        # chunked loss head, and the ragged-extend step (speculative
        # verification reads its logits) all serve the quantized tree
        from hpc_patterns_tpu.models.decode import (
            init_paged_cache,
            paged_extend_step,
            paged_prefill,
        )
        from hpc_patterns_tpu.models.transformer import forward, loss_fn

        cfg, params = _setup()
        qp = quantize_weights_int8(params)
        deq = self._manual_dequant(qp)
        tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0,
                                    cfg.vocab)
        np.testing.assert_array_equal(
            np.asarray(forward(qp, tokens, cfg)),
            np.asarray(forward(deq, tokens, cfg)))
        chunked = dataclasses.replace(cfg, loss_chunk=4)
        np.testing.assert_array_equal(
            np.asarray(loss_fn(qp, tokens, chunked)),
            np.asarray(loss_fn(deq, tokens, chunked)))
        prompt = tokens[:, :8]
        chunk = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
        la = lb = None
        for p, store in ((qp, "a"), (deq, "b")):
            ca = init_paged_cache(cfg, 2, pages_per_seq=3, page_size=8)
            _, ca = paged_prefill(p, prompt, cfg, ca, 8)
            logits, _ = paged_extend_step(
                p, ca, jnp.array([8, 8], jnp.int32), chunk, cfg)
            la, lb = (logits, lb) if store == "a" else (la, logits)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    def test_pp_refuses_quantized_tree(self):
        from hpc_patterns_tpu.models.pp import pp_loss_and_grads

        cfg, params = _setup()
        qp = quantize_weights_int8(params)
        with pytest.raises(ValueError, match="int8-quantized"):
            pp_loss_and_grads(qp, jnp.zeros((2, 8), jnp.int32), cfg,
                              None, microbatches=1)


class TestQuantizedRoundTrips:
    """Preemption, migration, and the residency tier with quantized
    pools: the scales travel WITH their pages through every detach/
    attach path, bit-identically."""

    def _standalone(self, params, cfg, prompt, max_new):
        return np.asarray(paged_generate(
            params, jnp.asarray(prompt, jnp.int32)[None, :], cfg,
            max_new, page_size=8))[0]

    @pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
    def test_preempt_resume_token_exact(self, kv_dtype):
        cfg, params = _setup(kv_cache_dtype=kv_dtype)
        eng = ContinuousBatcher(
            params, cfg, slots=2, pool_pages=4, pages_per_seq=4,
            page_size=8, chunk=2, preempt=True,
            prompt_buckets=(8, 16, 24, 32))
        pA = np.arange(5, dtype=np.int32)
        pB = np.arange(8, dtype=np.int32) + 7
        a = eng.submit(pA, 20, priority=1)  # takes all 4 pages
        eng.run(max_rounds=3)
        b = eng.submit(pB, 4, priority=0)   # starved -> evicts A
        got = eng.run()
        assert eng.stats[a]["preemptions"] == 1
        np.testing.assert_array_equal(
            got[a], self._standalone(params, cfg, pA, 20))
        np.testing.assert_array_equal(
            got[b], self._standalone(params, cfg, pB, 4))

    @pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
    def test_migration_wire_roundtrip_bit_identical(self, kv_dtype):
        from hpc_patterns_tpu.serving_plane.migration import (
            bundle_from_wire,
            bundle_to_wire,
        )

        cfg, params = _setup(kv_cache_dtype=kv_dtype)
        donor = EngineCore(params, cfg, slots=1, pool_pages=6,
                           pages_per_seq=6, page_size=8, chunk=2,
                           prompt_buckets=(16,))
        prompt = np.arange(9, dtype=np.int32)
        donor.submit(prompt, 6)
        donor.service_round(decode=False)
        bundle = donor.export_migration(donor.exportable_slots()[0])
        # the wire carries dtype + scales: every payload leaf (int8 or
        # fp8 values AND the f32 scale pools) round-trips bit-exact
        assert {"k", "v", "k_scale", "v_scale"} <= set(
            bundle.pages_payload)
        wire = bundle_to_wire(bundle)
        back = bundle_from_wire(wire)
        for name, arrs in bundle.pages_payload.items():
            for a0, a1 in zip(arrs, back.pages_payload[name]):
                a0 = np.asarray(jax.device_get(a0))
                assert a0.dtype == a1.dtype
                np.testing.assert_array_equal(a0.view(np.uint8),
                                              a1.view(np.uint8),
                                              err_msg=name)
        dest = EngineCore(params, cfg, slots=1, pool_pages=6,
                          pages_per_seq=6, page_size=8, chunk=2,
                          prompt_buckets=(16,))
        dest.install_migration(back)
        while dest.has_work():
            dest.service_round()
        np.testing.assert_array_equal(
            dest.finished[bundle.seq_id],
            self._standalone(params, cfg, prompt, 6))

    def test_residency_moves_quantized_bytes(self):
        # the compound win the residency tier inherits: pushes move
        # the QUANTIZED bytes, so host-tier traffic (and with it the
        # prefetch windows) shrinks to ~0.53x of bf16 — asserted from
        # the manager's own byte counters on the SAME schedule
        from hpc_patterns_tpu.memory import (
            ColdAfterNPolicy,
            ResidencyManager,
        )

        def run_tier(kv_dtype):
            # a real head_dim (64): the per-page ratio is
            # 0.5 + itemsize(scale)/(2·head_dim), so a toy head_dim
            # would hide the win behind the scale-pool overhead
            cfg, params = _setup(
                d_model=64, n_heads=1,
                kv_cache_dtype=kv_dtype,
                **({"dtype": "bfloat16"} if kv_dtype == "compute"
                   else {}))
            mgr = ResidencyManager(host_blocks=64,
                                   policy=ColdAfterNPolicy(2))
            eng = ContinuousBatcher(
                params, cfg, slots=2, pool_pages=8, pages_per_seq=4,
                page_size=8, chunk=2, prompt_buckets=(8, 16),
                residency=mgr)
            rng = np.random.RandomState(5)
            reqs = [(rng.randint(0, cfg.vocab, size=7)
                     .astype(np.int32), 12) for _ in range(4)]
            ids = [eng.submit(p, b) for p, b in reqs]
            got = eng.run()
            for i, (p, b) in enumerate(reqs):
                np.testing.assert_array_equal(
                    got[ids[i]], self._standalone(params, cfg, p, b))
            return eng, mgr

        eng_q, mgr_q = run_tier("int8")
        eng_b, mgr_b = run_tier("compute")  # bf16 pool
        assert mgr_q.swap_outs > 0, "cap forced no paging"
        # per-page accounting: the quantized page is ~0.53x the bf16
        # page (values halve, f32 scales ride at D-times smaller)
        frac = eng_q._page_nbytes / eng_b._page_nbytes
        assert frac <= 0.55, frac
        # and the transfer pipeline moved quantized bytes, not a
        # dequantized copy — same schedule, same block counts
        assert mgr_q.swap_outs == mgr_b.swap_outs
        assert mgr_q.evict_bytes <= 0.55 * mgr_b.evict_bytes
        if mgr_b.prefetch_bytes:
            assert (mgr_q.prefetch_bytes
                    <= 0.55 * mgr_b.prefetch_bytes)

    def test_pool_bytes_at_equal_residents_from_real_allocations(self):
        # the capacity claim on what is allocated, not on shapes: the
        # int8 pools with their float32 scale pools, against bfloat16
        # pools of the same slots and pages (the table left out on
        # both sides), at the head_dim of the test above
        def pool_bytes(**over):
            cfg, _ = _setup(d_model=64, n_heads=1, **over)
            cache = init_paged_cache(cfg, 2, 4, 8, pool_pages=9)
            return sum(int(arr.nbytes) for name, pools in cache.items()
                       if name != "table" for arr in pools)

        q = pool_bytes(kv_cache_dtype="int8")
        b = pool_bytes(dtype="bfloat16")
        assert 0.5 < q / b <= 0.55, (q, b)


class TestRefusalsAndProbe:
    @pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
    def test_tail_prefill_refusal_stays_loud(self, kv_dtype):
        # satellite pin: the sharing path keeps refusing quantized
        # pools, and the message names the knob and the reason
        cfg, params = _setup(kv_cache_dtype=kv_dtype)
        cache = init_paged_cache(cfg, 1, 4, 8)
        with pytest.raises(ValueError) as ei:
            paged_tail_prefill(params, jnp.zeros((1, 8), jnp.int32),
                               cfg, cache, 8, 1)
        msg = str(ei.value)
        assert "kv_cache_dtype" in msg and kv_dtype in msg
        assert "docs/quantization.md" in msg

    @pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
    def test_prefix_cache_refusal_stays_loud(self, kv_dtype):
        cfg, params = _setup(kv_cache_dtype=kv_dtype)
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            EngineCore(params, cfg, slots=1, pool_pages=4,
                       pages_per_seq=4, page_size=8,
                       prompt_buckets=(8,), prefix_cache=True)

    def test_config_accepts_and_rejects(self):
        TransformerConfig(**{**BASE, "kv_cache_dtype": "fp8"})
        TransformerConfig(**{**BASE, "decode_attn": "paged_flash"})
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            TransformerConfig(**{**BASE, "kv_cache_dtype": "int4"})
        with pytest.raises(ValueError, match="decode_attn"):
            TransformerConfig(**{**BASE, "decode_attn": "paged"})

    def test_supports_fp8_probe_is_cached_bool(self):
        from hpc_patterns_tpu import dtypes

        got = dtypes.supports_fp8()
        assert isinstance(got, bool)
        assert dtypes.supports_fp8() is got  # memoized

    def test_kv_dtype_resolver_shared_definition(self):
        from hpc_patterns_tpu import dtypes
        from hpc_patterns_tpu.harness.cli import (
            KV_DTYPE_CHOICES,
            resolve_kv_cache_dtype,
        )

        assert KV_DTYPE_CHOICES == ("f32", "bf16", "int8", "fp8")
        assert resolve_kv_cache_dtype("f32") == ("float32", "compute")
        assert resolve_kv_cache_dtype("bf16") == ("bfloat16",
                                                  "compute")
        assert resolve_kv_cache_dtype("int8") == (None, "int8")
        # the degrade path: a backend without fp8 lands on int8 WITH a
        # note (never a deep XLA error)
        notes = []
        prev = dtypes._FP8_SUPPORT
        try:
            dtypes._FP8_SUPPORT = False
            assert resolve_kv_cache_dtype(
                "fp8", note=notes.append) == (None, "int8")
            assert notes and "degrading" in notes[0]
            dtypes._FP8_SUPPORT = True
            assert resolve_kv_cache_dtype("fp8") == (None, "fp8")
        finally:
            dtypes._FP8_SUPPORT = prev
        with pytest.raises(Exception, match="kv-dtype"):
            resolve_kv_cache_dtype("int4")


class TestEmittedStreamLaw:
    """The precision law applied to a stream an engine already emitted
    (what serve_app falls back to on the TPU, where bf16 rounding is
    not batch-geometry invariant)."""

    def _setup(self):
        import numpy as np

        from hpc_patterns_tpu.models import TransformerConfig, init_params
        from hpc_patterns_tpu.models.decode import paged_generate

        cfg = TransformerConfig(vocab=256, d_model=64, n_heads=4,
                                n_layers=2, d_ff=256, max_seq=48,
                                decode_attn="gather")
        params = init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        prompts = rng.randint(0, 256, size=(3, 9)).astype(np.int32)
        outs = np.asarray(paged_generate(
            params, jnp.asarray(prompts), cfg, 16, page_size=16))
        return params, cfg, list(prompts), list(outs), rng

    def test_greedy_stream_agrees_and_a_foreign_one_does_not(self):
        from hpc_patterns_tpu.models.quantization import (
            emitted_stream_law,
        )

        params, cfg, prompts, outs, rng = self._setup()
        law = emitted_stream_law(params, cfg, prompts, outs)
        assert law.steps == 48 and law.greedy_agreement >= 0.95
        law.check()
        # a flipped last token costs exactly its own step, and the flip
        # implies a positive TV lower bound
        flipped = [o.copy() for o in outs]
        flipped[0][-1] = (flipped[0][-1] + 1) % 256
        one = emitted_stream_law(params, cfg, prompts, flipped)
        assert one.greedy_agreement == pytest.approx(
            law.greedy_agreement - 1 / 48)
        assert one.tv_max > 0
        # a stream that is not this model's is refused by name
        foreign = [rng.randint(0, 256, size=16).astype("int32")
                   for _ in outs]
        with pytest.raises(AssertionError, match="greedy top-1 agreement"):
            emitted_stream_law(params, cfg, prompts, foreign).check()
