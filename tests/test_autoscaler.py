"""The elastic serving plane (serving_plane/autoscaler.py).

Two tiers. The CONTROLLER battery is jax-free and instant: the
Autoscaler is a pure function of its signal sequence, so hysteresis
(no flap at a steady boundary load), cooldown, the min/max clamps,
and determinism (same signals -> same decision log) pin directly.
The PLANE battery drives real engines on the tiny test model: an
involuntary replica death resumes every in-flight stream on survivors
byte-exact — greedy AND sampled (the checkpointed per-row key state)
— with a warm spin-up backfilling capacity, and a voluntary
scale-down DRAINS: queued work re-routes, in-flight rows migrate
through the PR 9 export/install path, nothing sheds."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.harness import chaos as chaoslib
from hpc_patterns_tpu.harness import slo as slolib
from hpc_patterns_tpu.models import TransformerConfig, init_params
from hpc_patterns_tpu.models.decode import paged_generate
from hpc_patterns_tpu.models.serving import EngineCore
from hpc_patterns_tpu.serving_plane.autoscaler import (
    Autoscaler,
    AutoscalerPolicy,
    ElasticServingPlane,
    Signals,
    WarmParamPool,
)
from hpc_patterns_tpu.serving_plane.router import Replica, ServingPlane


def sig(round_no, replicas, queued, *, attained=0, judged=0):
    return Signals(round=round_no, replicas=replicas, queued=queued,
                   active=0, attained=attained, judged=judged)


class TestAutoscalerPolicy:
    """The pure controller: jax-free, instant."""

    def test_scales_up_on_queue_pressure(self):
        a = Autoscaler(AutoscalerPolicy(min_replicas=1, max_replicas=4,
                                        up_queue=2.0, window=2))
        assert a.observe(sig(1, 2, 2)).action == "hold"  # mean 1.0
        assert a.observe(sig(2, 2, 10)).action == "up"   # mean 3.0

    def test_no_flap_at_steady_boundary_load(self):
        # pressure sitting EXACTLY on either threshold holds forever:
        # up only fires strictly above up_queue, down strictly below
        # down_queue — the hysteresis band is the no-flap guarantee
        p = AutoscalerPolicy(min_replicas=1, max_replicas=4,
                             up_queue=2.0, down_queue=1.0,
                             cooldown_rounds=0, window=1)
        a = Autoscaler(p)
        for r in range(20):
            assert a.observe(sig(r, 2, 4)).action == "hold"  # == up
        for r in range(20, 40):
            assert a.observe(sig(r, 2, 2)).action == "hold"  # == down
        # and anywhere inside the band holds too
        for r in range(40, 60):
            assert a.observe(sig(r, 2, 3)).action == "hold"

    def test_down_requires_empty_queue_and_recovered_attainment(self):
        p = AutoscalerPolicy(min_replicas=1, max_replicas=4,
                             down_queue=1.0, down_attainment=0.95,
                             cooldown_rounds=0, window=1)
        a = Autoscaler(p)
        # queue empty but attainment below the recovery bar: hold
        # (capacity is only returned once the SLO recovered)
        d = a.observe(sig(1, 3, 0, attained=8, judged=10))
        assert d.action == "up"  # 0.8 < up_attainment 0.9
        a2 = Autoscaler(p)
        d = a2.observe(sig(1, 3, 0, attained=10, judged=10))
        assert d.action == "down"

    def test_cooldown_blocks_consecutive_actions(self):
        p = AutoscalerPolicy(min_replicas=1, max_replicas=8,
                             up_queue=1.0, cooldown_rounds=3, window=1)
        a = Autoscaler(p)
        assert a.observe(sig(1, 2, 20)).action == "up"
        # pressure stays high, but the cooldown holds the next 3
        for r in range(2, 5):
            d = a.observe(sig(r, 3, 20))
            assert d.action == "hold" and "cooldown" in d.reason
        assert a.observe(sig(5, 3, 20)).action == "up"

    def test_min_clamp_outranks_cooldown(self):
        # a death below the floor must be replaceable THIS round, not
        # after waiting out the cooldown of the action that preceded it
        p = AutoscalerPolicy(min_replicas=2, max_replicas=4,
                             up_queue=1.0, cooldown_rounds=5, window=1)
        a = Autoscaler(p)
        assert a.observe(sig(1, 2, 20)).action == "up"
        d = a.observe(sig(2, 1, 0))  # replica died below min
        assert d.action == "up" and "min_replicas" in d.reason

    def test_max_clamp(self):
        p = AutoscalerPolicy(min_replicas=1, max_replicas=2,
                             up_queue=1.0, cooldown_rounds=0, window=1)
        a = Autoscaler(p)
        for r in range(10):
            assert a.observe(sig(r, 2, 50)).action == "hold"

    def test_attainment_drop_scales_up_without_queues(self):
        p = AutoscalerPolicy(min_replicas=1, max_replicas=4,
                             up_attainment=0.9, cooldown_rounds=0,
                             window=2)
        a = Autoscaler(p)
        d = a.observe(sig(1, 2, 0, attained=1, judged=4))
        assert d.action == "up" and "attainment" in d.reason

    def test_deterministic_given_signal_sequence(self):
        # the replay contract: the same signal trajectory produces the
        # same decision log, bit for bit
        p = AutoscalerPolicy(min_replicas=1, max_replicas=4,
                             up_queue=2.0, down_queue=0.5,
                             cooldown_rounds=2, window=3)
        rng = np.random.RandomState(3)
        trail = [sig(r, int(rng.randint(1, 5)), int(rng.randint(0, 12)),
                     attained=int(rng.randint(0, 4)), judged=3)
                 for r in range(40)]
        a, b = Autoscaler(p), Autoscaler(p)
        for s in trail:
            a.observe(s)
            b.observe(s)
        assert a.decisions == b.decisions

    def test_policy_validation(self):
        with pytest.raises(ValueError, match="min_replicas"):
            AutoscalerPolicy(min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError, match="hysteresis"):
            AutoscalerPolicy(up_queue=1.0, down_queue=1.0)
        with pytest.raises(ValueError, match="attainment"):
            AutoscalerPolicy(up_attainment=0.99, down_attainment=0.9)
        with pytest.raises(ValueError, match="window"):
            AutoscalerPolicy(window=0)


BASE = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=64, dtype="float32")
ENG = dict(slots=2, pool_pages=8, pages_per_seq=4, page_size=8,
           chunk=2)


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(**BASE)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _standalone(params, cfg, prompt, max_new, **kw):
    return np.asarray(paged_generate(
        params, jnp.asarray(prompt, jnp.int32)[None, :], cfg, max_new,
        page_size=8, **kw))[0]


def _elastic(cfg, params, *, n_replicas=2, policy=None, **skw):
    pool = WarmParamPool(params)
    factory = lambda p: EngineCore(p, cfg, **ENG, **skw)  # noqa: E731
    return ElasticServingPlane(
        [Replica(EngineCore(params, cfg, **ENG, **skw), name=f"r{i}")
         for i in range(n_replicas)],
        engine_factory=factory, warm_pool=pool,
        autoscaler=Autoscaler(policy or AutoscalerPolicy(
            min_replicas=n_replicas, max_replicas=n_replicas + 1,
            up_queue=1.5, cooldown_rounds=2)),
        slo={0: slolib.SLOTarget()})


class TestElasticPlane:
    def test_death_resume_byte_exact_greedy_and_spinup(self, setup):
        cfg, params = setup
        rng = np.random.RandomState(1)
        reqs = [(rng.randint(0, 64, size=6).astype(np.int32), 6)
                for _ in range(4)]
        chaoslib.configure("die:replica=1,at=1,site=replica_round")
        try:
            plane = _elastic(cfg, params)
            ids = [plane.submit(p, m) for p, m in reqs]
            got = plane.run()
            died = [e for e in chaoslib.injections()
                    if e["kind"] == "die"]
        finally:
            chaoslib.reset()
        assert died and died[0]["rank"] == 1  # the replica ordinal
        assert plane.deaths == ["r1"]
        assert plane.shed_on_death == 0 and plane.resumed
        # the min-clamp replaced the dead replica on WARM params and
        # the spin-up span was measured
        assert len(plane.spinup_s) >= 1
        assert all(s > 0 for s in plane.spinup_s)
        # every request attains: the fixed plane's death sheds and
        # reads below 1 (test_serving_plane.py, TestReplicaDeath)
        assert plane.last_slo["total"]["attained_frac"] == 1.0
        for rid, (p, m) in zip(ids, reqs):
            assert plane.stats[rid]["outcome"] == "ok"
            np.testing.assert_array_equal(
                got[rid], _standalone(params, cfg, p, m),
                err_msg=f"rid {rid}")

    def test_death_resume_byte_exact_sampled_key_checkpoint(self, setup):
        # the PR 9 remainder: an INVOLUNTARY death resumes sampled
        # streams byte-exact because the plane checkpoints each row's
        # post-chunk key state every round — the resume seeds
        # _admit_row with it, exactly like a preemption snapshot
        cfg, params = setup
        skw = dict(temperature=0.8, top_k=8, seed=0)
        rng = np.random.RandomState(5)
        reqs = [(rng.randint(0, 64, size=6).astype(np.int32), 8)
                for _ in range(4)]
        chaoslib.configure("die:replica=0,at=1,site=replica_round")
        try:
            plane = _elastic(cfg, params, **skw)
            ids = [plane.submit(p, m) for p, m in reqs]
            got = plane.run()
        finally:
            chaoslib.reset()
        assert plane.deaths == ["r0"] and plane.resumed
        assert plane.shed_on_death == 0
        key_src = plane.replicas[1].engine
        for rid, (p, m) in zip(ids, reqs):
            assert plane.stats[rid]["outcome"] == "ok"
            np.testing.assert_array_equal(
                got[rid],
                _standalone(params, cfg, p, m,
                            key=key_src.request_key(rid),
                            temperature=0.8, top_k=8),
                err_msg=f"rid {rid}")
        # teeth: the resumed streams must include a row that had
        # already emitted tokens (a fresh re-run would diverge there
        # without the key checkpoint)
        assert any(plane.stats[r]["preemptions"] > 0
                   for r in plane.resumed)

    def test_scale_down_drains_by_migration_nothing_sheds(self, setup):
        # a voluntary drain: the victim stops receiving routing, its
        # in-flight rows EXPORT to survivors (PR 9 path), and it
        # retires once empty — byte-exact, zero shed
        cfg, params = setup
        rng = np.random.RandomState(9)
        reqs = [(rng.randint(0, 64, size=6).astype(np.int32), 12)
                for _ in range(3)]
        plane = _elastic(
            cfg, params, n_replicas=3,
            policy=AutoscalerPolicy(min_replicas=1, max_replicas=3,
                                    up_queue=50.0, down_queue=49.0,
                                    cooldown_rounds=0, window=1))
        ids = [plane.submit(p, m) for p, m in reqs]
        got = plane.run()
        assert plane.drained and plane.retired
        assert plane.shed_on_death == 0
        assert plane.migrations >= 1  # in-flight rows moved, not shed
        for rid, (p, m) in zip(ids, reqs):
            assert plane.stats[rid]["outcome"] == "ok"
            np.testing.assert_array_equal(
                got[rid], _standalone(params, cfg, p, m),
                err_msg=f"rid {rid}")

    def test_drain_never_strands_a_role(self, setup):
        # the last prefill-capable replica is not a drain candidate
        cfg, params = setup
        pool = WarmParamPool(params)
        plane = ElasticServingPlane(
            [Replica(EngineCore(params, cfg, **ENG), name="p",
                     role="prefill"),
             Replica(EngineCore(params, cfg, **ENG), name="d",
                     role="decode")],
            engine_factory=lambda p: EngineCore(p, cfg, **ENG),
            warm_pool=pool,
            autoscaler=Autoscaler(AutoscalerPolicy(
                min_replicas=1, max_replicas=2, up_queue=50.0,
                down_queue=49.0, cooldown_rounds=0, window=1)),
            slo={0: slolib.SLOTarget()})
        rid = plane.submit(np.arange(5, dtype=np.int32), 3)
        got = plane.run()
        assert not plane.drained  # neither role may be stranded
        np.testing.assert_array_equal(
            got[rid],
            _standalone(params, cfg, np.arange(5, dtype=np.int32), 3))

    def test_spinup_window_recorded_under_trace(self, setup):
        from hpc_patterns_tpu.harness import trace as tracelib

        cfg, params = setup
        rng = np.random.RandomState(11)
        reqs = [(rng.randint(0, 64, size=6).astype(np.int32), 6)
                for _ in range(4)]
        from hpc_patterns_tpu.serving_plane.autoscaler import (
            SPINUP_TRACK_BASE,
            SPINUP_TRACKS,
        )

        tracelib.configure(enabled=True)
        chaoslib.configure("die:replica=1,at=1,site=replica_round")
        try:
            plane = _elastic(cfg, params)
            for p, m in reqs:
                plane.submit(p, m)
            plane.run()
            events = list(tracelib.active().events)
        finally:
            chaoslib.reset()
            tracelib.configure(enabled=False)
        assert len(plane.spinup_s) >= 1
        # each spin-up is one dispatch→completion window on the
        # spinup subtrack band (between migration 64.. and mem 80..)
        wins = [e for e in events
                if e[0] == "X" and e[2] == "plane.spinup"]
        assert len(wins) == len(plane.spinup_s)
        lo = tracelib.TID_DEVICE + SPINUP_TRACK_BASE
        assert all(lo <= e[4] < lo + SPINUP_TRACKS for e in wins)
        assert all(e[5] > 0 for e in wins)  # a real measured span

    def test_warm_pool_is_residency_backed(self, setup):
        cfg, params = setup
        pool = WarmParamPool(params)
        # the parked copy lives in the HOST tier of a real manager
        assert pool.manager.host_blocks_used() > 0
        before = pool.manager.prefetch_bytes
        payload, handle = pool.pull()
        jax.block_until_ready(payload)
        pool.complete(handle)
        assert pool.manager.prefetch_bytes > before
        # pulled bytes are the parked bytes, exactly
        for a, b in zip(jax.tree.leaves(payload),
                        jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
