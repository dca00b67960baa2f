"""Continuous batching (models/serving.py): every sequence admitted
through the shared-pool engine must emit exactly the tokens its
standalone paged_generate emits — regardless of what was scheduled
around it, what chunk size amortized the dispatch, how often its pages
were recycled, what bucket rung padded its prompt, or (in sampled
mode) what its neighbors drew from their own key streams. Draft-
assisted SAMPLING is the one law-only surface: the rejection-sampling
rounds preserve the emitted distribution, not the draws — its oracle
is distributional."""

from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness import trace as tracelib
from hpc_patterns_tpu.models import TransformerConfig, init_params
from hpc_patterns_tpu.models import serving
from hpc_patterns_tpu.models.decode import paged_generate
from hpc_patterns_tpu.models.serving import (
    ContinuousBatcher,
    EngineCore,
    bucket_ladder,
    prefill_cache_size,
)

BASE = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=64, dtype="float32")


def _setup(**over):
    cfg = TransformerConfig(**{**BASE, **over})
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _standalone(params, cfg, prompt, max_new, **kw):
    return np.asarray(paged_generate(
        params, jnp.asarray(prompt, jnp.int32)[None, :], cfg, max_new,
        page_size=8, **kw))[0]


def _requests(cfg, n, seed=1):
    """n requests with varied prompt lengths and budgets."""
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        t = int(rng.choice([5, 8, 11]))
        prompt = rng.randint(0, cfg.vocab, size=t).astype(np.int32)
        reqs.append((prompt, int(rng.choice([3, 6, 9]))))
    return reqs


class TestContinuousBatching:
    @pytest.mark.parametrize("chunk", [1, 4])
    def test_every_sequence_matches_standalone(self, chunk):
        # 6 requests through 2 slots and a pool with room for ~2 rows:
        # admission waits on freed pages, rows complete at their own
        # budgets, and each output must equal standalone paged decode
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=3, page_size=8,
                                chunk=chunk)
        reqs = _requests(cfg, 6)
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        assert sorted(got) == sorted(ids)
        for sid, (prompt, max_new) in zip(ids, reqs):
            want = _standalone(params, cfg, prompt, max_new)
            np.testing.assert_array_equal(got[sid], want,
                                          err_msg=f"seq {sid}")
        # the arena drained back to empty
        assert sorted(eng.free_pages) == list(range(6))

    def test_single_slot_serializes_exactly(self):
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=1, pool_pages=3,
                                pages_per_seq=3, page_size=8, chunk=2)
        reqs = _requests(cfg, 4, seed=3)
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        for sid, (prompt, max_new) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, prompt, max_new))

    def test_int8_pages_compose(self):
        cfg, params = _setup(kv_cache_dtype="int8")
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=3, page_size=8, chunk=4)
        reqs = _requests(cfg, 4, seed=5)
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        for sid, (prompt, max_new) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, prompt, max_new))

    def test_eos_truncates_like_standalone_prefix(self):
        # pick the eos id from a standalone run's interior so it WILL
        # fire mid-generation; the engine must emit exactly the prefix
        # through that first occurrence
        cfg, params = _setup()
        prompt = np.arange(5, dtype=np.int32)
        full = _standalone(params, cfg, prompt, 9)
        eos = int(full[3])
        first = int(np.argmax(full == eos))
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=3, page_size=8, chunk=2,
                                eos_id=eos)
        sid = eng.submit(prompt, 9)
        got = eng.run()[sid]
        np.testing.assert_array_equal(got, full[:first + 1])

    def test_engine_reuse_across_runs(self):
        # a drained engine accepts a second wave: pages/slots/cursors
        # reset cleanly and the second run's outputs are exact too.
        # (True mid-run admission — new requests entering while rows
        # are generating — is covered by the 6-requests/2-slots test,
        # where 4 requests queue behind active rows.)
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=3, page_size=8, chunk=4)
        r1 = _requests(cfg, 2, seed=7)
        ids1 = [eng.submit(p, m) for p, m in r1]
        eng.run()
        r2 = _requests(cfg, 2, seed=9)
        ids2 = [eng.submit(p, m) for p, m in r2]
        got = eng.run()
        for sid, (prompt, max_new) in zip(ids1 + ids2, r1 + r2):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, prompt, max_new))

    @pytest.mark.parametrize("gamma", [2, 4])
    def test_draft_assisted_matches_standalone(self, gamma):
        # speculative decoding INSIDE the engine: the draft proposes,
        # the target verifies per round, rows advance 1..gamma+1 tokens
        # per dispatch at their own acceptance — and every sequence is
        # STILL token-exact vs its standalone paged decode (greedy
        # speculative == greedy target, the serving oracle)
        from hpc_patterns_tpu.models.transformer import init_params as ip

        cfg, params = _setup()
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2})
        dparams = ip(jax.random.PRNGKey(42), dcfg)
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=8,
                                pages_per_seq=4, page_size=8,
                                draft_params=dparams, draft_cfg=dcfg,
                                gamma=gamma)
        reqs = _requests(cfg, 5, seed=11)
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        for sid, (prompt, max_new) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, prompt, max_new),
                err_msg=f"seq {sid} gamma={gamma}")
        assert sorted(eng.free_pages) == list(range(8))

    def test_draft_assisted_self_draft_accepts_everything(self):
        # target drafting for itself: every proposal accepted, rows
        # advance gamma+1 per round, output still exact
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=8,
                                pages_per_seq=4, page_size=8,
                                draft_params=params, draft_cfg=cfg,
                                gamma=3)
        prompt = np.arange(5, dtype=np.int32)
        sid = eng.submit(prompt, 9)
        got = eng.run()[sid]
        np.testing.assert_array_equal(
            got, _standalone(params, cfg, prompt, 9))

    def test_draft_assisted_eos(self):
        cfg, params = _setup()
        prompt = np.arange(5, dtype=np.int32)
        full = _standalone(params, cfg, prompt, 9)
        eos = int(full[3])
        first = int(np.argmax(full == eos))
        eng = ContinuousBatcher(params, cfg, slots=1, pool_pages=4,
                                pages_per_seq=4, page_size=8,
                                draft_params=params, draft_cfg=cfg,
                                gamma=2, eos_id=eos)
        sid = eng.submit(prompt, 9)
        got = eng.run()[sid]
        np.testing.assert_array_equal(got, full[:first + 1])

    def test_draft_assisted_int8_matches_standalone(self):
        # all three serving levers at once: draft-assisted rounds over
        # int8 page pools — still token-exact vs standalone int8 paged
        from hpc_patterns_tpu.models.transformer import init_params as ip

        cfg, params = _setup(kv_cache_dtype="int8")
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2,
                                    "kv_cache_dtype": "int8"})
        dparams = ip(jax.random.PRNGKey(42), dcfg)
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=8,
                                pages_per_seq=4, page_size=8,
                                draft_params=dparams, draft_cfg=dcfg,
                                gamma=2)
        reqs = _requests(cfg, 4, seed=13)
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        for sid, (prompt, max_new) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, prompt, max_new))

    def test_draft_assisted_tp_matches_standalone(self, mesh_dp_sp_tp):
        # draft-assisted rounds under tp: the engine's pools shard on
        # kv heads, draft kernel steps shard_map, the extend rides
        # GSPMD — still token-exact vs unsharded standalone
        from hpc_patterns_tpu.models.sharding import shard_params
        from hpc_patterns_tpu.models.transformer import init_params as ip

        cfg, params = _setup(n_heads=4)  # kv_heads 4, tp=2 divides
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2})
        dparams = ip(jax.random.PRNGKey(42), dcfg)
        p_sh = shard_params(params, mesh_dp_sp_tp, cfg)
        d_sh = shard_params(dparams, mesh_dp_sp_tp, dcfg)
        eng = ContinuousBatcher(p_sh, cfg, slots=2, pool_pages=8,
                                pages_per_seq=4, page_size=8,
                                draft_params=d_sh, draft_cfg=dcfg,
                                gamma=2, mesh=mesh_dp_sp_tp)
        reqs = _requests(cfg, 3, seed=17)
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        for sid, (prompt, max_new) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, prompt, max_new))

    def test_draft_guards(self):
        cfg, params = _setup()
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2})
        from hpc_patterns_tpu.models.transformer import init_params as ip

        dparams = ip(jax.random.PRNGKey(42), dcfg)
        with pytest.raises(ValueError, match="draft_cfg"):
            ContinuousBatcher(params, cfg, slots=1, pool_pages=3,
                              pages_per_seq=3, page_size=8,
                              draft_params=dparams)

    def test_telemetry_events(self):
        # the observability hook records every admission and
        # completion with page accounting (the metrics/logging
        # subsystem applied to serving)
        cfg, params = _setup()
        events = []
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=3, page_size=8, chunk=2,
                                emit=lambda **kw: events.append(kw))
        reqs = _requests(cfg, 3, seed=21)
        ids = [eng.submit(p, m) for p, m in reqs]
        eng.run()
        admits = [e for e in events if e["kind"] == "serve_admit"]
        finishes = [e for e in events if e["kind"] == "serve_finish"]
        assert sorted(e["seq_id"] for e in admits) == sorted(ids)
        assert sorted(e["seq_id"] for e in finishes) == sorted(ids)
        for e, (prompt, max_new) in zip(sorted(admits,
                                               key=lambda e: e["seq_id"]),
                                        reqs):
            assert e["prompt_len"] == len(prompt)
            assert e["budget"] == max_new
        for e in finishes:
            assert e["tokens"] >= 1 and e["pages_freed"] >= 1

    def test_guards(self):
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=1, pool_pages=2,
                                pages_per_seq=3, page_size=8)
        with pytest.raises(ValueError, match="pages_per_seq"):
            eng.submit(np.arange(20, dtype=np.int32), 20)
        with pytest.raises(ValueError, match="max_new"):
            eng.submit(np.arange(4, dtype=np.int32), 0)
        # needs 3 pages but the pool only has 2: deadlock, loudly
        eng.submit(np.arange(10, dtype=np.int32), 8)
        with pytest.raises(RuntimeError, match="deadlock"):
            eng.run()


class TestBucketedAdmission:
    def test_ladder(self):
        assert bucket_ladder(12, lo=4) == (4, 8, 12)
        assert bucket_ladder(100, lo=16) == (16, 32, 64, 100)
        assert bucket_ladder(8) == (8,)  # lo above max: one rung
        with pytest.raises(ValueError, match="max_len"):
            bucket_ladder(0)
        with pytest.raises(ValueError, match="growth"):
            bucket_ladder(64, growth=1.0)

    def test_compile_count_bounded_and_exact(self):
        # TEN distinct prompt lengths through a THREE-rung ladder: the
        # admission-prefill jit cache (prefill_cache_size — one entry
        # per distinct padded length x config) may grow by at most the
        # ladder size, and every bucket-padded sequence must still be
        # token-exact vs standalone (causality keeps the true prefix
        # independent of the padding; last_pos redirects the logits).
        # d_ff=68 makes the config unique in this process, so the
        # cache delta belongs to THIS engine alone.
        cfg, params = _setup(d_ff=68)
        ladder = bucket_ladder(12, lo=4)
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=8,
                                pages_per_seq=4, page_size=8, chunk=4,
                                prompt_buckets=ladder)
        rng = np.random.RandomState(2)
        reqs = [(rng.randint(0, cfg.vocab, size=t).astype(np.int32), 5)
                for t in range(1, 11)]  # every length 1..10
        before = prefill_cache_size()
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        assert prefill_cache_size() - before <= len(ladder)
        for sid, (prompt, max_new) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, prompt, max_new),
                err_msg=f"seq {sid} len {len(prompt)}")
        # a SECOND wave re-uses the warm rungs: zero new compiles
        before = prefill_cache_size()
        ids2 = [eng.submit(p, m, seq_id=100 + i)
                for i, (p, m) in enumerate(reqs)]
        got = eng.run()
        assert prefill_cache_size() == before
        for sid, (prompt, max_new) in zip(ids2, reqs):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, prompt, max_new))

    def test_bucketed_draft_assisted_exact(self):
        # bucket padding composes with speculative rounds: the draft
        # prefill pads to the same rung, and greedy draft-assisted
        # serving stays token-exact
        from hpc_patterns_tpu.models.transformer import init_params as ip

        cfg, params = _setup()
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2})
        dparams = ip(jax.random.PRNGKey(42), dcfg)
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=10,
                                pages_per_seq=5, page_size=8,
                                draft_params=dparams, draft_cfg=dcfg,
                                gamma=2, prompt_buckets=(4, 8, 12))
        reqs = _requests(cfg, 4, seed=19)
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        for sid, (prompt, max_new) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, prompt, max_new))

    def test_ladder_guards(self):
        cfg, params = _setup()
        with pytest.raises(ValueError, match="max_seq"):
            ContinuousBatcher(params, cfg, slots=1, pool_pages=4,
                              pages_per_seq=4, page_size=8,
                              prompt_buckets=(8, 100))
        eng = ContinuousBatcher(params, cfg, slots=1, pool_pages=4,
                                pages_per_seq=4, page_size=8,
                                prompt_buckets=(8,))
        with pytest.raises(ValueError, match="ladder"):
            eng.submit(np.arange(9, dtype=np.int32), 4)  # above top rung

    def test_pages_cover_padded_prefill(self):
        # a 1-token prompt padded to rung 8 with budget 1 needs a page
        # for the PAD region too — pages_needed must size for the
        # padded length, or the prefill would scatter past the row's
        # pages
        assert ContinuousBatcher.pages_needed(1, 1, 8, padded_len=8) == 1
        assert ContinuousBatcher.pages_needed(1, 1, 8, padded_len=16) == 2
        assert ContinuousBatcher.pages_needed(9, 8, 8, padded_len=16) == 3

    def test_from_fitted_applies_the_ladder_and_an_explicit_one_wins(self):
        # the fitted ladder becomes prompt_buckets, clamped to this
        # model's max_seq (64); the caller's own ladder outranks it
        from hpc_patterns_tpu.harness import autofit

        cfg, params = _setup()
        fitted = autofit.fit([
            {"kind": "serve_admit", "seq_id": i, "slot": 0,
             "prompt_len": t, "padded_len": t, "priority": 0}
            for i, t in enumerate([16] * 4 + [40] * 12 + [100] * 4)])
        kw = dict(slots=1, pool_pages=8, pages_per_seq=8, page_size=8)
        eng = ContinuousBatcher.from_fitted(params, cfg, fitted, **kw)
        assert eng.prompt_buckets == autofit.ladder_from(fitted,
                                                         max_seq=64)
        assert 40 in eng.prompt_buckets and max(eng.prompt_buckets) == 64
        eng = ContinuousBatcher.from_fitted(params, cfg, fitted,
                                            prompt_buckets=(8, 32), **kw)
        assert eng.prompt_buckets == (8, 32)


class TestSampledServing:
    def test_sampled_token_exact_vs_standalone(self):
        # sampling in the engine is NOT a weaker distributional claim:
        # each row consumes its own key stream exactly as standalone
        # paged_generate(key=request_key(sid)) does, so served tokens
        # are identical draw-for-draw — scheduling independence holds
        # for sampled serving too
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=3, page_size=8, chunk=3,
                                temperature=0.8, top_k=8, seed=3)
        reqs = _requests(cfg, 6, seed=23)
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        for sid, (prompt, max_new) in zip(ids, reqs):
            want = _standalone(params, cfg, prompt, max_new,
                               key=eng.request_key(sid),
                               temperature=0.8, top_k=8)
            np.testing.assert_array_equal(got[sid], want,
                                          err_msg=f"seq {sid}")

    def test_sampled_with_buckets_exact(self):
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=8,
                                pages_per_seq=4, page_size=8, chunk=4,
                                temperature=1.1, top_k=0, seed=5,
                                prompt_buckets=(4, 8, 12))
        reqs = _requests(cfg, 5, seed=29)
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        for sid, (prompt, max_new) in zip(ids, reqs):
            want = _standalone(params, cfg, prompt, max_new,
                               key=eng.request_key(sid),
                               temperature=1.1)
            np.testing.assert_array_equal(got[sid], want)

    def test_per_request_overrides(self):
        # a per-request temperature/key overrides the engine defaults,
        # and the standalone reproduction uses exactly those
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=3, page_size=8,
                                temperature=0.7, top_k=0, seed=9)
        prompt = np.arange(6, dtype=np.int32)
        my_key = jax.random.PRNGKey(777)
        sid_default = eng.submit(prompt, 7)
        sid_custom = eng.submit(prompt, 7, temperature=1.5, key=my_key)
        got = eng.run()
        np.testing.assert_array_equal(
            got[sid_default],
            _standalone(params, cfg, prompt, 7,
                        key=eng.request_key(sid_default),
                        temperature=0.7))
        np.testing.assert_array_equal(
            got[sid_custom],
            _standalone(params, cfg, prompt, 7, key=my_key,
                        temperature=1.5))

    def test_greedy_engine_rejects_per_request_temperature(self):
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=1, pool_pages=3,
                                pages_per_seq=3, page_size=8)
        with pytest.raises(ValueError, match="sampling engine"):
            eng.submit(np.arange(4, dtype=np.int32), 4, temperature=0.9)
        with pytest.raises(ValueError, match="sampling engine"):
            eng.submit(np.arange(4, dtype=np.int32), 4,
                       key=jax.random.PRNGKey(1))
        with pytest.raises(ValueError, match="> 0"):
            ContinuousBatcher(params, cfg, slots=1, pool_pages=3,
                              pages_per_seq=3, page_size=8,
                              temperature=0.9).submit(
                np.arange(4, dtype=np.int32), 4, temperature=-1.0)


class TestOverlappedAdmission:
    def test_overlap_output_identical_to_serial(self):
        # overlapped admission is a SCHEDULING change only: the same
        # stream through overlap=True and overlap=False engines emits
        # identical tokens, and the exposed-admission (bubble) fraction
        # is recorded on both
        cfg, params = _setup()
        reqs = _requests(cfg, 8, seed=31)
        outs, bubbles = [], []
        for overlap in (True, False):
            eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                    pages_per_seq=3, page_size=8,
                                    chunk=2, overlap=overlap)
            ids = [eng.submit(p, m) for p, m in reqs]
            got = eng.run()
            outs.append([got[sid] for sid in ids])
            bubbles.append(eng.last_bubble_frac)
        for a, b in zip(*outs):
            np.testing.assert_array_equal(a, b)
        assert all(0.0 <= b <= 1.0 for b in bubbles)

    def test_admit_telemetry_has_overlap_fields(self):
        # first wave admits with nothing in flight (exposed — the
        # bubble); a request admitted into a freed slot while the OTHER
        # row runs is enqueued ahead of that row's next chunk
        cfg, params = _setup()
        events = []
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=8,
                                pages_per_seq=4, page_size=8, chunk=2,
                                prompt_buckets=(8, 12),
                                emit=lambda **kw: events.append(kw))
        eng.submit(np.arange(5, dtype=np.int32), 2)   # finishes fast
        eng.submit(np.arange(5, dtype=np.int32), 8)   # keeps running
        eng.submit(np.arange(5, dtype=np.int32), 2)   # admitted mid-run
        eng.run()
        admits = [e for e in events if e["kind"] == "serve_admit"]
        assert [e["seq_id"] for e in admits] == [0, 1, 2]
        for e in admits:
            assert e["padded_len"] == 8 and e["prompt_len"] == 5
        # no chunk is in flight when an admission is enqueued: the third
        # goes AHEAD of the chunk that carries the running row
        assert [e["overlapped"] for e in admits] == [False, False, False]
        assert [e["ahead"] for e in admits] == [False, False, True]


def _through_eos(tokens, eos):
    """What an engine with ``eos`` emits of a standalone run's tokens:
    everything up to and including the first end token."""
    hit = np.flatnonzero(tokens == eos)
    return tokens[:hit[0] + 1] if hit.size else tokens


class TestAdmissionsAheadOfTheChunk:
    """``overlap=True``: a round enqueues its admissions, then the chunk
    for every live row (the admitted ones included), then reads the
    first tokens back; the chunk's start cursors are the host's mirror
    of ``pos``, written wherever ``pos`` is."""

    def test_the_pos_mirror_is_the_devices_after_every_round(self):
        # one run through two slots with an end token, a budget-1 row
        # finished at admission in a round with a live row, a migrated
        # row installed in the slot that row freed in the same round (a
        # slot the chunk in flight still carries), a preemption and a
        # resume, and every slot reused
        cfg, params = _setup()
        eos = 34   # first emitted by A at its 14th token, after its resume
        pA = np.array([59, 3, 39, 9, 19], np.int32)
        pB = np.arange(5, dtype=np.int32) + 20
        pM = np.arange(6, dtype=np.int32) + 30
        pP = np.arange(8, dtype=np.int32) + 7
        geometry = dict(slots=2, pool_pages=6, pages_per_seq=4,
                        page_size=8, chunk=2, eos_id=eos,
                        prompt_buckets=(8, 16, 24, 32))
        donor = EngineCore(params, cfg, **geometry)
        donor.submit(pM, 6, seq_id=50)
        donor.service_round(decode=False)
        bundle = donor.export_migration(donor.exportable_slots()[0])
        events = []
        eng = ContinuousBatcher(params, cfg, preempt=True,
                                emit=lambda **kw: events.append(kw),
                                **geometry)
        installed = []

        def round_(pre_collect=None):
            eng.service_round(pre_collect=pre_collect)
            live = [i for i, s in enumerate(eng._slots) if s.active]
            np.testing.assert_array_equal(eng._pos_host[live],
                                          np.asarray(eng.pos)[live])

        a = eng.submit(pA, 20, priority=1)
        round_()
        b = eng.submit(pB, 1, priority=1)
        round_(lambda overlapped: installed.append(
            eng.install_migration(bundle)))
        p = eng.submit(pP, 4)
        while eng.has_work():
            round_()
        admits = [e for e in events if e["kind"] == "serve_admit"]
        slot_of = {e["seq_id"]: e["slot"] for e in admits}
        assert installed == [slot_of[b]]
        assert [e["seq_id"] for e in admits] == [a, b, p, a]
        assert [e["ahead"] for e in admits] == [False, True, True, True]
        assert eng.stats[a]["preemptions"] == 1
        for sid, prompt, budget in ((a, pA, 20), (b, pB, 1), (50, pM, 6),
                                    (p, pP, 4)):
            want = _through_eos(_standalone(params, cfg, prompt, budget),
                                eos)
            np.testing.assert_array_equal(eng.finished[sid], want,
                                          err_msg=f"seq {sid}")
        assert len(eng.finished[a]) == 14
        assert sorted(eng.free_pages) == list(range(6))

    @pytest.mark.parametrize("overlap", [True, False])
    def test_an_admission_is_enqueued_before_the_chunk_that_carries_it(
            self, monkeypatch, overlap):
        # with a row live, the admitted row's prefill and install are
        # enqueued before this round's chunk, which carries the row: its
        # second token comes out of the same round. overlap=False reads
        # the first token before the chunk's dispatch
        calls = []

        def logged(name, fn):
            def call(*a, **kw):
                calls.append(name)
                return fn(*a, **kw)
            return call

        for name in ("_prefill_one", "_admit_row", "_chunk_step"):
            monkeypatch.setattr(serving, name,
                                logged(name, getattr(serving, name)))
        monkeypatch.setattr(
            EngineCore, "_resolve_pending",
            logged("first_tokens", EngineCore._resolve_pending))
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=3, page_size=8, chunk=2,
                                overlap=overlap)
        eng.submit(np.arange(5, dtype=np.int32), 8)
        eng.service_round()
        calls.clear()
        b = eng.submit(np.arange(6, dtype=np.int32) + 9, 8)
        eng.service_round()
        chunk_then_read = (["_chunk_step", "first_tokens"] if overlap
                           else ["first_tokens", "_chunk_step"])
        assert calls == ["_prefill_one", "_admit_row"] + chunk_then_read
        [row] = [s for s in eng._slots if s.seq_id == b]
        assert len(row.out) == 3   # the first token and the chunk's two

    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    def test_tokens_are_the_serial_orders_and_standalone(self, sampled):
        cfg, params = _setup()
        kw = dict(temperature=0.8, top_k=8, seed=3) if sampled else {}
        reqs = _requests(cfg, 7, seed=37)
        outs = []
        for overlap in (True, False):
            eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                    pages_per_seq=3, page_size=8, chunk=2,
                                    overlap=overlap, **kw)
            ids = [eng.submit(p, m) for p, m in reqs]
            got = eng.run()
            outs.append([got[sid] for sid in ids])
        for sid, ((prompt, max_new), x, y) in enumerate(zip(reqs, *outs)):
            want = _standalone(
                params, cfg, prompt, max_new,
                **(dict(key=eng.request_key(sid), temperature=0.8, top_k=8)
                   if sampled else {}))
            np.testing.assert_array_equal(x, want, err_msg=f"seq {sid}")
            np.testing.assert_array_equal(y, want, err_msg=f"seq {sid}")

    def test_the_admit_ahead_counter_counts_admissions_ahead_of_live_rows(
            self):
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=8,
                                pages_per_seq=4, page_size=8, chunk=2,
                                prompt_buckets=(8, 12))
        for budget in (2, 8, 2):
            eng.submit(np.arange(5, dtype=np.int32), budget)
        m = metricslib.configure(enabled=True)
        try:
            eng.run()
            counters = m.snapshot()["counters"]
        finally:
            metricslib.configure(enabled=False)
        assert counters["serve.admitted"] == 3
        assert counters["serve.admit_ahead"] == 1
        assert "serve.admit_overlapped" not in counters

    def test_the_drivers_warm_up_leaves_nothing_to_compile_in_the_window(
            self):
        # the benchmark's own warm-up (chipbench/drivers/serve.warm) on a
        # tiny engine, then a window whose admissions go ahead of live
        # rows: nothing is traced, lowered or compiled in the window
        from chipbench.drivers import serve as driver

        cfg, params = _setup()
        eng = driver.Engine(params, cfg, slots=2, pool_pages=8,
                            pages_per_seq=4, page_size=8, chunk=2,
                            prompt_buckets=(8, 16), overlap=True)
        rng = np.random.RandomState(41)
        prompts = [rng.randint(0, cfg.vocab, size=n).astype(np.int32)
                   for n in (5, 11, 7, 14, 6)]
        driver.warm(eng, [SimpleNamespace(prompt=p) for p in prompts],
                    cfg.vocab)
        compiled = prefill_cache_size()
        n0 = len(tracelib.compile_events())
        admits = []
        record = eng._emit
        eng._emit = lambda **kw: (admits.append(kw.get("ahead")),
                                  record(**kw))
        eng.run(arrivals=[(0.0, dict(prompt=p, max_new=m, seq_id=i))
                          for i, (p, m) in enumerate(zip(prompts,
                                                         (12, 3, 5, 4, 6)))])
        assert sorted(eng.finished) == [0, 1, 2, 3, 4] + sorted(
            sid for sid in eng.finished if sid >= driver.WARM_ID0)
        assert any(admits)
        assert [(kind, fn) for _, kind, _, fn in
                tracelib.compile_events()[n0:]
                if kind in ("trace", "lower", "backend_compile")] == []
        assert prefill_cache_size() == compiled


class TestPreemptionAndResume:
    """The preempt/resume oracle: a sequence evicted under forced page
    starvation and later resumed must emit BYTE-IDENTICAL tokens to an
    uninterrupted standalone run with the same request key — greedy
    and sampled. The starvation is structural (pool sized one page
    short of the high-priority arrival), not a timing accident."""

    def _starved(self, cfg, params, events=None, **over):
        # 4-page pool; the low-priority victim takes all 4, the
        # 8-token-prompt high-priority arrival needs 2 — page-starved
        # by construction until the victim is evicted
        return ContinuousBatcher(
            params, cfg, slots=2, pool_pages=4, pages_per_seq=4,
            page_size=8, chunk=2, preempt=True,
            prompt_buckets=(8, 16, 24, 32),
            emit=(lambda **kw: events.append(kw)) if events is not None
            else None, **over)

    def test_preempted_and_resumed_tokens_exact_greedy(self):
        cfg, params = _setup()
        events = []
        eng = self._starved(cfg, params, events)
        pA = np.arange(5, dtype=np.int32)
        pB = np.arange(8, dtype=np.int32) + 7
        a = eng.submit(pA, 20, priority=1)  # needs all 4 pages
        eng.run(max_rounds=3)               # A mid-generation
        b = eng.submit(pB, 4, priority=0)   # starved -> must evict A
        got = eng.run()
        pre = [e for e in events if e["kind"] == "serve_preempt"]
        assert [e["seq_id"] for e in pre] == [a]
        assert pre[0]["for_seq_id"] == b
        assert eng.stats[a]["preemptions"] == 1
        # the oracle: byte-identical to never having been preempted
        np.testing.assert_array_equal(got[a], _standalone(params, cfg,
                                                          pA, 20))
        np.testing.assert_array_equal(got[b], _standalone(params, cfg,
                                                          pB, 4))
        # the arena drained; the resumed admission was flagged as such
        assert sorted(eng.free_pages) == list(range(4))
        resumed = [e for e in events
                   if e["kind"] == "serve_admit" and e["resumed"]]
        assert [e["seq_id"] for e in resumed] == [a]
        # the resume's prompt is the original plus what was emitted,
        # and it pads to a rung: a resume never leaves the ladder (so
        # never compiles a prefill of its own length)
        assert resumed[0]["prompt_len"] > len(pA)
        assert resumed[0]["padded_len"] in eng.prompt_buckets

    def test_preempted_and_resumed_sampled_key_stream_exact(self):
        # the sharper half of the oracle: the victim's PER-ROW KEY
        # STATE snapshots at eviction and the resume consumes it with
        # the same split/pick order — so even SAMPLED draws are
        # byte-identical to the uninterrupted standalone run
        cfg, params = _setup()
        eng = self._starved(cfg, params, temperature=0.8, top_k=8,
                            seed=3)
        pA = np.arange(5, dtype=np.int32)
        pB = np.arange(8, dtype=np.int32) + 7
        a = eng.submit(pA, 20, priority=1)
        eng.run(max_rounds=3)
        b = eng.submit(pB, 4, priority=0)
        got = eng.run()
        assert eng.stats[a]["preemptions"] == 1
        np.testing.assert_array_equal(
            got[a], _standalone(params, cfg, pA, 20,
                                key=eng.request_key(a),
                                temperature=0.8, top_k=8))
        np.testing.assert_array_equal(
            got[b], _standalone(params, cfg, pB, 4,
                                key=eng.request_key(b),
                                temperature=0.8, top_k=8))

    def test_equal_priority_never_preempts(self):
        # preemption is a PRIORITY mechanism, not a fairness one: an
        # equal-priority arrival waits for pages like round 6 always did
        cfg, params = _setup()
        events = []
        eng = self._starved(cfg, params, events)
        pA = np.arange(5, dtype=np.int32)
        a = eng.submit(pA, 12, priority=1)
        eng.run(max_rounds=2)
        b = eng.submit(np.arange(8, dtype=np.int32), 4, priority=1)
        got = eng.run()
        assert not [e for e in events if e["kind"] == "serve_preempt"]
        assert eng.stats[a]["preemptions"] == 0
        np.testing.assert_array_equal(got[a], _standalone(params, cfg,
                                                          pA, 12))

    def test_priority_order_admission(self):
        # both queued up front: the high-priority request admits FIRST
        # even though the low-priority one was submitted earlier
        cfg, params = _setup()
        events = []
        eng = ContinuousBatcher(params, cfg, slots=1, pool_pages=3,
                                pages_per_seq=3, page_size=8, chunk=2,
                                emit=lambda **kw: events.append(kw))
        lo = eng.submit(np.arange(5, dtype=np.int32), 4, priority=2)
        hi = eng.submit(np.arange(5, dtype=np.int32), 4, priority=0)
        eng.run()
        admits = [e["seq_id"] for e in events
                  if e["kind"] == "serve_admit"]
        assert admits == [hi, lo]

    def test_shed_expired_deadline(self):
        # a queued request whose deadline lapses is SHED: empty output,
        # outcome "shed", telemetry event — not silent starvation
        cfg, params = _setup()
        events = []
        eng = ContinuousBatcher(params, cfg, slots=1, pool_pages=3,
                                pages_per_seq=3, page_size=8, chunk=2,
                                emit=lambda **kw: events.append(kw))
        a = eng.submit(np.arange(5, dtype=np.int32), 9)
        b = eng.submit(np.arange(5, dtype=np.int32), 4,
                       deadline_s=0.0)  # expires while a serves
        got = eng.run()
        assert eng.stats[b]["outcome"] == "shed"
        assert got[b].size == 0
        assert [e["seq_id"] for e in events
                if e["kind"] == "serve_shed"] == [b]
        np.testing.assert_array_equal(
            got[a], _standalone(params, cfg,
                                np.arange(5, dtype=np.int32), 9))

    def test_highwater_defers_fresh_admissions(self):
        # admit_highwater reserves headroom: the second fresh request
        # would push used pages past the mark, so it waits for the
        # first to finish even though pages are nominally free
        cfg, params = _setup()
        events = []
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=3, page_size=8, chunk=2,
                                admit_highwater=0.5,
                                emit=lambda **kw: events.append(kw))
        a = eng.submit(np.arange(5, dtype=np.int32), 9)   # 2 pages
        b = eng.submit(np.arange(5, dtype=np.int32), 9)   # would be 4>3
        got = eng.run()
        admits = [e for e in events if e["kind"] == "serve_admit"]
        # b admitted only after a freed its pages: never 2 concurrent
        assert admits[1]["free_pages"] >= 4
        for sid in (a, b):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg,
                                      np.arange(5, dtype=np.int32), 9))
        with pytest.raises(ValueError, match="admit_highwater"):
            ContinuousBatcher(params, cfg, slots=1, pool_pages=3,
                              pages_per_seq=3, page_size=8,
                              admit_highwater=0.0)

    def test_infeasible_head_never_evicts(self):
        # a fresh high-priority request whose need exceeds the
        # high-water cap can NEVER admit — preempting for it would
        # thrash lower classes through re-prefills every round and
        # still end stuck. The engine must leave the victims alone,
        # serve them to completion, and then fail LOUDLY.
        cfg, params = _setup()
        events = []
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=6, page_size=8, chunk=2,
                                preempt=True, admit_highwater=0.5,
                                emit=lambda **kw: events.append(kw))
        pA = np.arange(5, dtype=np.int32)
        a = eng.submit(pA, 9, priority=1)       # 2 pages <= cap 3
        eng.run(max_rounds=2)
        eng.submit(np.arange(10, dtype=np.int32), 16,
                   priority=0)                  # 4 pages > cap 3: stuck
        with pytest.raises(RuntimeError, match="admit_highwater"):
            eng.run()
        assert not [e for e in events if e["kind"] == "serve_preempt"]
        np.testing.assert_array_equal(
            eng.finished[a], _standalone(params, cfg, pA, 9))

    def test_non_victim_pages_over_the_cap_never_evict(self):
        # the thrash shape: the head is kept over the high-water cap
        # by pages that belong to SAME-or-higher-priority rows, so
        # evicting the lower-priority victim could never admit it —
        # the victim's resume would bypass the mark, re-admit the same
        # round, and be evicted again next round, forever. The
        # feasibility check must count only victim pages as freeable.
        cfg, params = _setup()
        events = []
        eng = ContinuousBatcher(params, cfg, slots=3, pool_pages=8,
                                pages_per_seq=4, page_size=8, chunk=2,
                                preempt=True,
                                emit=lambda **kw: events.append(kw))
        pA = np.arange(5, dtype=np.int32)
        a = eng.submit(pA, 20, priority=0)   # 4 pages, non-victim
        b = eng.submit(pA, 9, priority=2)    # 2 pages, the only victim
        eng.run(max_rounds=2)                # both active (used 6/8)
        # the operator tightens the mark mid-run: cap drops to 4.8 —
        # a fresh p1 head (2 pages) now reads used 6 + 2 > 4.8, and
        # even with b evicted the p0 row alone keeps 4 + 2 > 4.8
        eng.admit_highwater = 0.6
        c = eng.submit(pA, 9, priority=1)
        eng.run(max_rounds=4)
        assert not [e for e in events if e["kind"] == "serve_preempt"]
        got = eng.run()  # a and b drain; c admits into the empty pool
        for sid, budget in ((a, 20), (b, 9), (c, 9)):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, pA, budget))
        assert eng.stats[b]["preemptions"] == 0

    def test_bounded_run_parks_instead_of_waiting_for_arrivals(self):
        import time as _time

        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=1, pool_pages=3,
                                pages_per_seq=3, page_size=8, chunk=2)
        t0 = _time.perf_counter()
        eng.run(arrivals=[(30.0, dict(prompt=np.arange(4, dtype=np.int32),
                                      max_new=2))],
                max_rounds=1)
        # parks immediately: must not idle-wait the 30s arrival out
        assert _time.perf_counter() - t0 < 5.0

    def test_stats_and_slo_rollup(self):
        from hpc_patterns_tpu.harness import slo as slolib

        cfg, params = _setup()
        targets = {0: slolib.SLOTarget(ttft_s=60.0, tpot_s=60.0)}
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=3, page_size=8, chunk=2,
                                slo=targets)
        ids = [eng.submit(p, m) for p, m in _requests(cfg, 4, seed=41)]
        eng.run()
        assert eng.last_slo is not None
        tot = eng.last_slo["total"]
        assert tot["served"] == 4 and tot["shed"] == 0
        # absurdly loose targets: everything attains, goodput == raw
        assert tot["attained"] == 4
        assert tot["goodput_tok_s"] == pytest.approx(tot["tok_s"])
        for sid in ids:
            rec = eng.stats[sid]
            assert rec["outcome"] == "ok"
            assert rec["t_submit"] <= rec["t_first"] <= rec["t_finish"]
            assert rec["tokens"] == len(eng.finished[sid])

    def test_open_loop_arrivals_replay(self):
        # run(arrivals=...) submits on the schedule's clock; outputs
        # stay oracle-exact and stats carry every arrival
        cfg, params = _setup()
        eng = ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                                pages_per_seq=3, page_size=8, chunk=2)
        reqs = _requests(cfg, 4, seed=43)
        arrivals = [
            (0.02 * i, dict(prompt=p, max_new=m, seq_id=100 + i))
            for i, (p, m) in enumerate(reqs)
        ]
        got = eng.run(arrivals=arrivals)
        for i, (p, m) in enumerate(reqs):
            np.testing.assert_array_equal(
                got[100 + i], _standalone(params, cfg, p, m))
        assert all(eng.stats[100 + i]["outcome"] == "ok"
                   for i in range(4))


class TestDraftSampledDistribution:
    def test_draft_assisted_sampling_preserves_law(self):
        # the distribution oracle for the one law-only serving mode:
        # draft-assisted SAMPLED serving emits tokens whose law equals
        # target-only sampling (Leviathan accept/resample), though the
        # draws differ. Protocol: N requests, same prompt, budget 2 —
        # token[0] comes from the prefill pick (per-request key: its
        # law is trivially exact), token[1] from a LIVE rejection-
        # sampling round against an INDEPENDENT draft (low acceptance,
        # so the resample branch is exercised). The empirical
        # distribution of token[1] must match the exact mixture law
        # q = mean_i p_warped(. | prompt, t0_i) computed from the
        # target's own logits. Deterministic given the seeds.
        from hpc_patterns_tpu.models import forward
        from hpc_patterns_tpu.models.decode import _topk_mask
        from hpc_patterns_tpu.models.transformer import init_params as ip

        temp, top_k, n_req = 1.0, 4, 160
        cfg, params = _setup()
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2})
        dparams = ip(jax.random.PRNGKey(1234), dcfg)
        prompt = np.arange(5, dtype=np.int32)
        pps = ContinuousBatcher.pages_needed(5, 2, 8, gamma=2)
        eng = ContinuousBatcher(params, cfg, slots=4,
                                pool_pages=4 * pps, pages_per_seq=pps,
                                page_size=8, chunk=2,
                                draft_params=dparams, draft_cfg=dcfg,
                                gamma=2, temperature=temp, top_k=top_k,
                                seed=11)
        ids = [eng.submit(prompt, 2) for _ in range(n_req)]
        got = eng.run()
        firsts = np.array([got[sid][0] for sid in ids])
        seconds = np.array([got[sid][1] for sid in ids])

        def warped_next(seq):
            logits = np.asarray(forward(
                params, jnp.asarray(seq, jnp.int32)[None, :], cfg))[0, -1]
            masked = np.asarray(_topk_mask(jnp.asarray(logits), top_k))
            z = (masked / temp) - masked.max()
            p = np.exp(z)
            p[~np.isfinite(p)] = 0.0
            return p / p.sum()

        law = {}
        q = np.zeros(cfg.vocab)
        for t0 in firsts:
            t0 = int(t0)
            if t0 not in law:
                law[t0] = warped_next(np.append(prompt, t0))
            q += law[t0]
        q /= n_req
        emp = np.bincount(seconds, minlength=cfg.vocab) / n_req
        tv = 0.5 * np.abs(emp - q).sum()
        assert tv < 0.2, (
            f"draft-assisted sampled law diverged: TV {tv:.3f} "
            f"(support emp {np.count_nonzero(emp)}, "
            f"law {np.count_nonzero(q > 1e-6)})")


def _weight_casts(fn, params, *args):
    """Every ``convert_element_type`` in ``fn``'s jaxpr (sub-jaxprs
    included) whose operand is float32, has two or more dimensions and
    the shape of a weight leaf or of one layer of a stacked leaf."""
    shapes = set()
    for leaf in jax.tree.leaves(params):
        shapes |= {leaf.shape, leaf.shape[1:]}

    def subjaxprs(value):
        if hasattr(value, "eqns"):
            yield value
        elif hasattr(value, "jaxpr"):
            yield from subjaxprs(value.jaxpr)
        elif isinstance(value, (tuple, list)):
            for v in value:
                yield from subjaxprs(v)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            aval = eqn.invars[0].aval if eqn.invars else None
            if (eqn.primitive.name == "convert_element_type"
                    and aval.dtype == jnp.float32 and aval.ndim >= 2
                    and aval.shape in shapes):
                yield aval.shape
            for value in eqn.params.values():
                for sub in subjaxprs(value):
                    yield from walk(sub)

    return list(walk(jax.make_jaxpr(fn)(params, *args).jaxpr))


class TestHeldWeights:
    """The engine holds its weights in ``cfg.dtype``
    (transformer.serving_weights): cast once at construction, the same
    bits in every program after it."""

    # slots != n_layers and rungs unlike any weight axis: an
    # activation's shape never passes for a weight's in _weight_casts
    GEOM = dict(slots=3, pool_pages=9, pages_per_seq=3, page_size=8)

    def _programs(self, cfg):
        from hpc_patterns_tpu.models.decode import init_paged_cache
        from hpc_patterns_tpu.models.serving import (
            _chunk_step,
            _prefill_one,
        )

        B, pps, page = 3, 3, 8
        prompt = jnp.arange(16, dtype=jnp.int32)[None, :] % cfg.vocab

        def prefill(tree):
            one = init_paged_cache(cfg, 1, pps, page)
            return _prefill_one(tree, prompt, jnp.int32(12), one, cfg=cfg,
                                page_size=page, mesh=None)

        def chunk(tree):
            cache = init_paged_cache(cfg, B, pps, page)
            return _chunk_step(
                tree, cache, jnp.array([3, 9, 0], jnp.int32),
                jnp.array([12, 11, 0], jnp.int32),
                jnp.array([5, 7, 0], jnp.int32),
                jnp.zeros((B, 2), jnp.uint32), jnp.ones((B,), jnp.float32),
                cfg=cfg, chunk=4, eos_id=-1, greedy=True, top_k=0,
                mesh=None)

        return {"prefill": prefill, "chunk": chunk}

    @pytest.mark.parametrize("tree", ["plain", "int8"])
    def test_programs_bit_equal_over_cast_tree(self, tree):
        # what the engine served before it cast (the float32 tree, cast
        # at use in every program) against what it serves now
        from hpc_patterns_tpu.models.transformer import (
            quantize_weights_int8,
            serving_weights,
        )

        cfg, params = _setup(dtype="bfloat16", decode_attn="gather")
        if tree == "int8":
            params = quantize_weights_int8(params)
        held = serving_weights(params, cfg)
        assert held is not params
        for name, program in self._programs(cfg).items():
            before, after = program(params), program(held)
            for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
                assert a.dtype == b.dtype, name
                np.testing.assert_array_equal(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    err_msg=name)

    def test_bfloat16_engine_matches_standalone(self):
        # end to end: the engine over its cast tree against standalone
        # paged decode over the float32 tree (which casts at use)
        cfg, params = _setup(dtype="bfloat16", decode_attn="gather")
        eng = ContinuousBatcher(params, cfg, chunk=4, **self.GEOM)
        reqs = _requests(cfg, 4, seed=3)
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        for sid, (prompt, max_new) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, prompt, max_new))

    @pytest.mark.parametrize("program", ["prefill", "chunk"])
    def test_no_weight_cast_left_in_program(self, program):
        # the test that would have caught the cost: over the float32
        # tree every program re-casts every matrix; over engine.params
        # no weight-shaped float32 operand is converted at all
        cfg, params = _setup(dtype="bfloat16", decode_attn="gather")
        eng = ContinuousBatcher(params, cfg, **self.GEOM)
        fn = self._programs(cfg)[program]
        cast_at_use = _weight_casts(fn, params)
        # embed, lm_head and the four matrices of the layer body
        assert len(cast_at_use) >= 6, cast_at_use
        assert _weight_casts(fn, eng.params) == []

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_weight_bytes_record(self, dtype):
        cfg, params = _setup(dtype=dtype)
        eng = ContinuousBatcher(params, cfg, **self.GEOM)
        n_leaves = len(jax.tree.leaves(params))
        total = sum(a.nbytes for a in jax.tree.leaves(params))
        if dtype == "float32":
            assert eng.params is params
            assert eng.weight_bytes == {"leaves": 0, "bytes_in": 0,
                                        "bytes_out": 0}
        else:
            assert eng.weight_bytes == {"leaves": n_leaves,
                                        "bytes_in": total,
                                        "bytes_out": total // 2}
            assert all(a.dtype == jnp.bfloat16
                       for a in jax.tree.leaves(eng.params))
            # the caller's tree is read, not consumed
            assert all(a.dtype == jnp.float32 and not a.is_deleted()
                       for a in jax.tree.leaves(params))

    def test_weights_cast_span(self):
        # the counter that says the mechanism engaged, in the repo's
        # own tracing: one serve.weights_cast span a held tree
        from hpc_patterns_tpu.harness import metrics as metricslib

        cfg, params = _setup(dtype="bfloat16")
        try:
            m = metricslib.configure(enabled=True)
            ContinuousBatcher(params, cfg, **self.GEOM)
            hist = m.snapshot()["histograms"]
        finally:
            metricslib.configure(enabled=False)
        assert hist["span.serve.weights_cast"]["count"] == 1

    def test_draft_held_in_draft_dtype(self):
        cfg, params = _setup()
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32,
                                    "n_layers": 1, "n_heads": 2,
                                    "dtype": "bfloat16"})
        dparams = init_params(jax.random.PRNGKey(42), dcfg)
        eng = ContinuousBatcher(params, cfg, draft_params=dparams,
                                draft_cfg=dcfg, gamma=2, **self.GEOM)
        assert eng.params is params  # float32 config: held as it came
        assert all(a.dtype == jnp.bfloat16
                   for a in jax.tree.leaves(eng.draft_params))

    @pytest.mark.parametrize("case", ["float32_config", "already_cast"])
    def test_nothing_to_cast_returns_the_argument(self, case):
        from hpc_patterns_tpu.models.transformer import serving_weights

        cfg, params = _setup(
            dtype="float32" if case == "float32_config" else "bfloat16")
        if case == "already_cast":
            params = serving_weights(params, cfg)
        assert serving_weights(params, cfg) is params

    def test_cast_keeps_sharding(self, mesh_dp_sp_tp):
        from hpc_patterns_tpu.models.sharding import shard_params
        from hpc_patterns_tpu.models.transformer import serving_weights

        cfg, params = _setup(dtype="bfloat16")
        sharded = shard_params(params, mesh_dp_sp_tp, cfg)
        held = serving_weights(sharded, cfg)
        split = 0
        for a, b in zip(jax.tree.leaves(sharded), jax.tree.leaves(held)):
            assert b.dtype == jnp.bfloat16
            assert b.sharding.is_equivalent_to(a.sharding, a.ndim)
            split += not a.sharding.is_fully_replicated
        assert split >= 4  # the tp rules did shard the matrices
