"""Serving plane (hpc_patterns_tpu/serving_plane/): the disaggregation
oracle and the router mechanics.

The load-bearing claim: a request routed prefill-replica →
KV-migration → decode-replica emits BYTE-IDENTICAL tokens to the same
request on a colocated single engine — greedy and sampled — because a
migrated request is structurally a resume on another replica (the
round-8 oracle machinery extended across engines). Everything else
(placement policies, per-replica accounting, ladder autotuning, the
wire codec) is pinned around that."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.models import TransformerConfig, init_params
from hpc_patterns_tpu.models.decode import paged_generate
from hpc_patterns_tpu.models.serving import (
    ContinuousBatcher,
    EngineCore,
    bucket_ladder,
    expected_padding,
    fit_bucket_ladder,
)
from hpc_patterns_tpu.serving_plane.migration import (
    bundle_from_wire,
    bundle_to_wire,
)
from hpc_patterns_tpu.serving_plane.router import Replica, ServingPlane

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
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, cfg.vocab,
                         size=int(rng.choice([5, 8, 11])))
             .astype(np.int32),
             int(rng.choice([3, 6, 9]))) for _ in range(n)]


ENG = dict(slots=2, pool_pages=8, pages_per_seq=4, page_size=8,
           chunk=2)


class TestDisaggregationOracle:
    def test_prefill_migrate_decode_exact_greedy(self):
        # 1 prefill + 1 decode replica: every request crosses the KV
        # handoff, and every output must equal the colocated engine's
        cfg, params = _setup()
        plane = ServingPlane([
            Replica(EngineCore(params, cfg, **ENG), name="p",
                    role="prefill"),
            Replica(EngineCore(params, cfg, **ENG), name="d",
                    role="decode"),
        ])
        reqs = _requests(cfg, 5)
        ids = [plane.submit(p, m) for p, m in reqs]
        got = plane.run()
        assert sorted(got) == sorted(ids)
        assert plane.migrations >= len(reqs)
        # unplaced replicas: every bundle counted as a local handoff,
        # none under the DMA tier, and no DMA overlap number claimed
        assert dict(plane.migration_transports) \
            == {"local": plane.migrations}
        assert plane.last_dma_migration_overlap_frac is None
        assert plane.migration_bytes_per_round > 0
        for rid, (p, m) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[rid], _standalone(params, cfg, p, m),
                err_msg=f"rid {rid}")
        # both arenas drained back to empty
        for r in plane.replicas:
            assert sorted(r.engine.free_pages) == list(range(8))

    def test_prefill_migrate_decode_exact_sampled(self):
        # sampled mode: the migrated key state must continue the donor
        # row's stream exactly — same per-request key as standalone
        cfg, params = _setup()
        skw = dict(temperature=0.8, top_k=8, seed=0)
        plane = ServingPlane([
            Replica(EngineCore(params, cfg, **ENG, **skw), name="p",
                    role="prefill"),
            Replica(EngineCore(params, cfg, **ENG, **skw), name="d",
                    role="decode"),
        ])
        reqs = _requests(cfg, 4, seed=5)
        ids = [plane.submit(p, m) for p, m in reqs]
        got = plane.run()
        key_src = plane.replicas[0].engine
        for rid, (p, m) in zip(ids, reqs):
            want = _standalone(params, cfg, p, m,
                               key=key_src.request_key(rid),
                               temperature=0.8, top_k=8)
            np.testing.assert_array_equal(got[rid], want,
                                          err_msg=f"rid {rid}")

    def test_migrated_row_eos_still_truncates(self):
        # EOS state rides the migrated limit cursor: pick an eos id
        # from a standalone run's interior, serve through the plane
        cfg, params = _setup()
        prompt = np.arange(5, dtype=np.int32)
        full = _standalone(params, cfg, prompt, 9)
        eos = int(full[3])
        first = int(np.argmax(full == eos))
        plane = ServingPlane([
            Replica(EngineCore(params, cfg, **ENG, eos_id=eos),
                    name="p", role="prefill"),
            Replica(EngineCore(params, cfg, **ENG, eos_id=eos),
                    name="d", role="decode"),
        ])
        rid = plane.submit(prompt, 9)
        got = plane.run()[rid]
        np.testing.assert_array_equal(got, full[:first + 1])

    def test_open_loop_arrivals_through_the_plane(self):
        cfg, params = _setup()
        plane = ServingPlane([
            Replica(EngineCore(params, cfg, **ENG), name="p",
                    role="prefill"),
            Replica(EngineCore(params, cfg, **ENG), name="d",
                    role="decode"),
        ])
        reqs = _requests(cfg, 3, seed=9)
        arrivals = [(0.002 * i, dict(prompt=p, max_new=m))
                    for i, (p, m) in enumerate(reqs)]
        got = plane.run(arrivals=arrivals)
        assert sorted(got) == [0, 1, 2]
        for rid, (p, m) in zip(range(3), reqs):
            np.testing.assert_array_equal(
                got[rid], _standalone(params, cfg, p, m))


class TestRouterMechanics:
    def test_homogeneous_round_robin_spreads_and_stays_exact(self):
        cfg, params = _setup()
        plane = ServingPlane(
            [Replica(EngineCore(params, cfg, **ENG), name=f"r{i}")
             for i in range(2)],
            policy="round_robin")
        reqs = _requests(cfg, 4, seed=3)
        ids = [plane.submit(p, m) for p, m in reqs]
        got = plane.run()
        assert {plane.stats[r]["replica"] for r in ids} == {"r0", "r1"}
        for rid, (p, m) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[rid], _standalone(params, cfg, p, m))

    def test_least_loaded_prefers_free_pages(self):
        cfg, params = _setup()
        big = Replica(EngineCore(params, cfg, slots=2, pool_pages=12,
                                 pages_per_seq=4, page_size=8,
                                 chunk=2), name="big")
        small = Replica(EngineCore(params, cfg, **ENG), name="small")
        plane = ServingPlane([small, big], policy="least_loaded")
        rid = plane.submit(np.arange(5, dtype=np.int32), 3)
        assert plane.stats[rid]["replica"] == "big"
        plane.run()

    def test_plane_slo_rollup_spans_replicas(self):
        from hpc_patterns_tpu.harness import slo as slolib

        cfg, params = _setup()
        plane = ServingPlane(
            [Replica(EngineCore(params, cfg, **ENG), name="p",
                     role="prefill"),
             Replica(EngineCore(params, cfg, **ENG), name="d",
                     role="decode")],
            slo={0: slolib.SLOTarget()})
        reqs = _requests(cfg, 3, seed=11)
        for p, m in reqs:
            plane.submit(p, m)
        plane.run()
        tot = plane.last_slo["total"]
        assert tot["n"] == 3 and tot["served"] == 3
        assert tot["tokens"] == sum(m for _, m in reqs)
        assert tot["goodput_tok_s"] == tot["tok_s"] > 0
        # migrated requests are judged once, end to end: t_first came
        # from the prefill replica, t_finish from the decode replica
        for rec in plane.stats.values():
            assert rec["t_first"] is not None
            assert rec["t_finish"] >= rec["t_first"]

    def test_validation_guards(self):
        from hpc_patterns_tpu.harness import slo as slolib  # noqa: F401

        cfg, params = _setup()
        mk = lambda **kw: EngineCore(params, cfg, **ENG, **kw)
        with pytest.raises(ValueError, match="unique"):
            ServingPlane([Replica(mk(), name="x"),
                          Replica(mk(), name="x")])
        with pytest.raises(ValueError, match="policy"):
            ServingPlane([Replica(mk())], policy="nope")
        with pytest.raises(ValueError, match="disagrees on"):
            ServingPlane([Replica(mk(), name="a"),
                          Replica(mk(temperature=0.5), name="b")])
        with pytest.raises(ValueError, match="different"):
            ServingPlane([
                Replica(mk(temperature=0.5), name="a"),
                Replica(mk(temperature=0.5, seed=1), name="b")])
        with pytest.raises(ValueError, match="decode-capable"):
            ServingPlane([Replica(mk(), role="prefill")])
        with pytest.raises(ValueError, match="no live replica"):
            plane = ServingPlane([Replica(mk(), name="a")])
            plane.submit(np.arange(40, dtype=np.int32), 30)

    def test_submit_rejects_rows_no_decode_replica_can_hold(self):
        # a prefill-routed row LEAVES via migration: if no decode
        # replica's table can hold its pages, submit must reject it
        # up front instead of parking it forever (the mid-stream
        # plane-deadlock shape)
        cfg, params = _setup()
        plane = ServingPlane([
            Replica(EngineCore(params, cfg, **ENG), name="p",
                    role="prefill"),
            Replica(EngineCore(params, cfg, slots=2, pool_pages=4,
                               pages_per_seq=2, page_size=8, chunk=2),
                    name="d", role="decode"),
        ])
        with pytest.raises(ValueError, match="decode-capable"):
            plane.submit(np.arange(10, dtype=np.int32), 10)  # 3 pages
        # a row that fits both sides still serves end to end
        rid = plane.submit(np.arange(5, dtype=np.int32), 3)
        got = plane.run()
        np.testing.assert_array_equal(
            got[rid],
            _standalone(params, cfg, np.arange(5, dtype=np.int32), 3))


def _pinned_plane(cfg, params, migration, eng_kw=None, n_reqs=4,
                  seed=1):
    """1 prefill + 1 decode replica pinned to DISTINCT devices (the
    multi-chip serving shape on the CPU mesh) with the requested
    KV-handoff transport, plus the request list they'll serve."""
    d = jax.devices()[:2]
    replicas = []
    for i, role in enumerate(("prefill", "decode")):
        with jax.default_device(d[i]):
            p = jax.device_put(params, d[i])
            eng = EngineCore(p, cfg, **{**ENG, **(eng_kw or {})})
        replicas.append(Replica(eng, name=role[0], role=role,
                                device=d[i]))
    return (ServingPlane(replicas, migration=migration),
            _requests(cfg, n_reqs, seed=seed))


class TestDmaMigration:
    """The round-17 transport tier: ``ServingPlane(migration="dma")``
    routes every KV handoff over the fused paired remote-DMA kernel
    (comm/migration_dma.py) — and must stay byte-exact vs the
    colocated engine AND vs the wire-codec path, greedy and sampled,
    at every pool dtype, with the DMA ledger proving no silent
    fallback impersonated the kernel route."""

    @pytest.mark.parametrize(
        "over", [{}, {"dtype": "bfloat16"},
                 {"kv_cache_dtype": "int8"}, {"kv_cache_dtype": "fp8"}],
        ids=["f32", "bf16", "int8", "fp8"])
    def test_dma_plane_exact_greedy_every_pool_dtype(self, over):
        cfg, params = _setup(**over)
        plane, reqs = _pinned_plane(cfg, params, "dma")
        ids = [plane.submit(p, m) for p, m in reqs]
        got = plane.run()
        # every handoff rode the kernel — the transports Counter and
        # the DMA-only overlap ledger both say so
        assert plane.migration_transports["dma"] == len(reqs)
        assert sum(plane.migration_transports.values()) == len(reqs)
        assert plane.last_dma_migration_overlap_frac is not None
        assert plane.migration_bytes_per_round > 0
        for rid, (p, m) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[rid], _standalone(params, cfg, p, m),
                err_msg=f"rid {rid}")

    def test_dma_plane_exact_sampled(self):
        cfg, params = _setup()
        skw = dict(temperature=0.8, top_k=8, seed=0)
        plane, reqs = _pinned_plane(cfg, params, "dma", eng_kw=skw,
                                    seed=5)
        ids = [plane.submit(p, m) for p, m in reqs]
        got = plane.run()
        assert plane.migration_transports["dma"] == len(reqs)
        key_src = plane.replicas[0].engine
        for rid, (p, m) in zip(ids, reqs):
            want = _standalone(params, cfg, p, m,
                               key=key_src.request_key(rid),
                               temperature=0.8, top_k=8)
            np.testing.assert_array_equal(got[rid], want,
                                          err_msg=f"rid {rid}")

    def test_dma_matches_wire_path(self):
        # the two extreme transports (device-side kernel vs byte
        # codec) must agree token for token on the same stream
        cfg, params = _setup()
        outs = {}
        for mig in ("dma", "wire"):
            plane, reqs = _pinned_plane(cfg, params, mig, seed=3)
            ids = [plane.submit(p, m) for p, m in reqs]
            got = plane.run()
            assert plane.migration_transports[mig] == len(reqs)
            outs[mig] = [got[r] for r in ids]
        for i, (a, b) in enumerate(zip(outs["dma"], outs["wire"])):
            np.testing.assert_array_equal(a, b, err_msg=f"req {i}")

    def test_schedule_chain_fingerprints_resolved_transport(self):
        # the CollectiveSchedule's kv_migration entries carry the
        # RESOLVED algorithm — a fallback is visible in the chain,
        # not just the logs
        from hpc_patterns_tpu.analysis import runtime as art
        from hpc_patterns_tpu.harness import trace as tracelib

        cfg, params = _setup()
        tracelib.configure(enabled=True)  # fresh recorder + chain
        try:
            plane, reqs = _pinned_plane(cfg, params, "dma", n_reqs=2)
            ids = [plane.submit(p, m) for p, m in reqs]
            plane.run()
            algos = [e.get("algorithm") for e in art._schedule.entries
                     if e["op"] == "kv_migration"]
        finally:
            # also resets the chain — read the entries BEFORE this
            tracelib.configure(enabled=False)
        assert algos and set(algos) == {"dma"}

    def test_fallback_to_device_put_is_loud(self):
        # device-less (host-shared) replicas cannot serve DMA: the
        # plane still serves exactly, but warns, counts the fallback,
        # and reports NO dma overlap number (None, not a value
        # measured on the wrong transport)
        cfg, params = _setup()
        plane = ServingPlane([
            Replica(EngineCore(params, cfg, **ENG), name="p",
                    role="prefill"),
            Replica(EngineCore(params, cfg, **ENG), name="d",
                    role="decode"),
        ], migration="dma")
        reqs = _requests(cfg, 3)
        ids = [plane.submit(p, m) for p, m in reqs]
        with pytest.warns(RuntimeWarning, match="fell back"):
            got = plane.run()
        assert plane.migration_transports["dma"] == 0
        assert plane.last_dma_migration_overlap_frac is None
        for rid, (p, m) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[rid], _standalone(params, cfg, p, m))

    def test_unknown_transport_rejected(self):
        cfg, params = _setup()
        with pytest.raises(ValueError, match="migration transport"):
            ServingPlane([Replica(EngineCore(params, cfg, **ENG))],
                         migration="carrier-pigeon")


class TestReplicaDeathStaticPlane:
    """The FIXED plane's degraded mode under ``die:replica=N`` chaos
    (the in-process ``replica_round`` site): a death ends in SHEDDING
    — counted in the SLO table and ``shed_on_death``, never silent —
    which is exactly the baseline the elastic plane
    (serving_plane/autoscaler.py, tests/test_autoscaler.py) beats."""

    def test_death_sheds_counted_survivors_stay_exact(self):
        from hpc_patterns_tpu.harness import chaos as chaoslib
        from hpc_patterns_tpu.harness import slo as slolib

        cfg, params = _setup()
        reqs = _requests(cfg, 4, seed=21)
        chaoslib.configure("die:replica=1,at=1,site=replica_round")
        try:
            plane = ServingPlane(
                [Replica(EngineCore(params, cfg, **ENG), name=f"r{i}")
                 for i in range(2)],
                slo={0: slolib.SLOTarget()})
            ids = [plane.submit(p, m) for p, m in reqs]
            got = plane.run()
            died = [e for e in chaoslib.injections()
                    if e["kind"] == "die"]
        finally:
            chaoslib.reset()
        # the fault fired against the replica ORDINAL and was logged
        assert died and died[0]["rank"] == 1
        assert plane.deaths == ["r1"]
        # every request resolved: the dead replica's rows are SHED
        # (empty output, outcome in the table), the survivor's stay
        # byte-exact — nothing dropped silently
        assert plane.shed_on_death >= 1
        outcomes = {plane.stats[r]["outcome"] for r in ids}
        assert outcomes == {"ok", "shed"}
        for rid, (p, m) in zip(ids, reqs):
            if plane.stats[rid]["outcome"] == "ok":
                np.testing.assert_array_equal(
                    got[rid], _standalone(params, cfg, p, m))
            else:
                assert len(got[rid]) == 0
        # attainment shows the damage: shed never attains
        tot = plane.last_slo["total"]
        assert tot["shed"] == plane.shed_on_death
        assert tot["attained_frac"] < 1.0


class TestMigrationPrimitives:
    def test_export_install_guards(self):
        cfg, params = _setup()
        src = EngineCore(params, cfg, **ENG)
        dst = EngineCore(params, cfg, **{**ENG, "page_size": 16})
        src.submit(np.arange(5, dtype=np.int32), 4)
        src.service_round(decode=False)
        [slot] = src.exportable_slots()
        b = src.export_migration(slot)
        with pytest.raises(ValueError, match="page_size"):
            dst.install_migration(b)
        with pytest.raises(ValueError, match="no exportable row"):
            src.export_migration(slot)  # already released

    def test_migrated_seq_id_collision_refused(self):
        cfg, params = _setup()
        src = EngineCore(params, cfg, **ENG)
        dst = EngineCore(params, cfg, **ENG)
        src.submit(np.arange(5, dtype=np.int32), 4, seq_id=7)
        dst.submit(np.arange(5, dtype=np.int32), 4, seq_id=7)
        src.service_round(decode=False)
        b = src.export_migration(src.exportable_slots()[0])
        with pytest.raises(ValueError, match="already known"):
            dst.install_migration(b)

    def test_wire_codec_roundtrips_bit_identical(self):
        cfg, params = _setup()
        src = EngineCore(params, cfg, **ENG, temperature=0.7, seed=0)
        dst = EngineCore(params, cfg, **ENG, temperature=0.7, seed=0)
        prompt = np.arange(6, dtype=np.int32)
        src.submit(prompt, 5)
        src.service_round(decode=False)
        b = src.export_migration(src.exportable_slots()[0])
        b.seq = 3
        wire = bundle_to_wire(b)
        b2 = bundle_from_wire(wire)
        assert b2.seq == 3 and b2.pos == b.pos and b2.limit == b.limit
        # the transport field (round 17) crosses the codec: the dict
        # carries the bundle's value, and a PRE-transport-field
        # artifact (no key) decodes as "wire" — it crossed a socket by
        # definition, so old recorded handoffs still load
        assert wire["transport"] == b.transport
        assert b2.transport == b.transport
        legacy = dict(wire)
        del legacy["transport"]
        assert bundle_from_wire(legacy).transport == "wire"
        np.testing.assert_array_equal(b2.key, np.asarray(b.key))
        for name, arrs in b.pages_payload.items():
            for a, a2 in zip(arrs, b2.pages_payload[name]):
                np.testing.assert_array_equal(np.asarray(a), a2)
        # and the rehydrated bundle still continues byte-exactly
        dst.install_migration(b2)
        while dst.has_work():
            dst.service_round()
        want = _standalone(params, cfg, prompt, 5,
                           key=src.request_key(0), temperature=0.7)
        np.testing.assert_array_equal(dst.finished[0], want)

    def test_draft_engines_refuse_roles_and_migration(self):
        cfg, params = _setup()
        dcfg = TransformerConfig(**{**BASE, "d_model": 16, "d_ff": 32})
        dparams = init_params(jax.random.PRNGKey(1), dcfg)
        eng = EngineCore(params, cfg, **ENG, draft_params=dparams,
                         draft_cfg=dcfg)
        with pytest.raises(ValueError, match="draft"):
            Replica(eng, role="prefill")
        eng.submit(np.arange(5, dtype=np.int32), 4)
        eng.service_round(decode=False)
        with pytest.raises(ValueError, match="draft"):
            eng.export_migration(eng.exportable_slots()[0])

    def test_resume_prefix_submit_path(self):
        # the cross-replica resume the router uses after a replica
        # death: prompt = original + emitted, prefix prepended — the
        # continuation must equal the uninterrupted run (greedy)
        cfg, params = _setup()
        prompt = np.arange(7, dtype=np.int32)
        full = _standalone(params, cfg, prompt, 8)
        cut = 3
        eng = ContinuousBatcher(params, cfg, **ENG)
        eng.submit(np.concatenate([prompt, full[:cut]]), 8 - cut,
                   seq_id=0, resume_prefix=full[:cut])
        got = eng.run()[0]
        np.testing.assert_array_equal(got, full)
        with pytest.raises(ValueError, match="longer"):
            eng.submit(np.arange(2, dtype=np.int32), 3,
                       resume_prefix=np.arange(5, dtype=np.int32))


class TestLadderAutotune:
    def test_fit_beats_default_on_long_tail(self):
        # the round-6 open item's pin: a long-tail mix must fit a
        # ladder with STRICTLY less expected padding than the default
        rng = np.random.RandomState(0)
        lengths = (list(rng.choice([7, 9, 11, 13], size=400))
                   + list(rng.choice([100, 240], size=20)))
        default = bucket_ladder(256)
        fit = fit_bucket_ladder(lengths, max_rungs=len(default),
                                max_len=256)
        assert expected_padding(fit, lengths) \
            < expected_padding(default, lengths)
        assert max(fit) >= 256  # still covers every legal prompt
        assert len(fit) <= len(default)

    def test_fit_is_optimal_on_small_cases(self):
        # brute-force check: the DP must match exhaustive search
        import itertools

        lengths = [2, 2, 5, 9, 9, 9, 14]
        cand = sorted(set(lengths))
        for r in (1, 2, 3):
            fit = fit_bucket_ladder(lengths, r)
            best = min(
                (expected_padding(c + (max(cand),), lengths)
                 for k in range(r)
                 for c in itertools.combinations(cand[:-1], k)),
                default=None)
            assert expected_padding(fit, lengths) == pytest.approx(best)

    def test_fit_guards_and_degenerates(self):
        assert fit_bucket_ladder([5, 5, 5], 3) == (5,)
        assert fit_bucket_ladder([3], 1, max_len=10) == (10,)
        with pytest.raises(ValueError):
            fit_bucket_ladder([], 2)
        with pytest.raises(ValueError):
            fit_bucket_ladder([4], 0)
        # the constructor spelling is attached to bucket_ladder
        assert bucket_ladder.fit is fit_bucket_ladder

    def test_engine_runs_fit_ladder(self):
        # "router and engine use it": an engine built on a fit ladder
        # serves the sample it was fit to, oracle-exact
        cfg, params = _setup()
        reqs = _requests(cfg, 4, seed=13)
        fit = fit_bucket_ladder([len(p) for p, _ in reqs], 3)
        eng = ContinuousBatcher(params, cfg, **ENG,
                                prompt_buckets=fit)
        ids = [eng.submit(p, m) for p, m in reqs]
        got = eng.run()
        for sid, (p, m) in zip(ids, reqs):
            np.testing.assert_array_equal(
                got[sid], _standalone(params, cfg, p, m))
