"""Request-scoped tracing (harness/reqtrace.py + harness/explain.py):
the coverage invariant and the attribution teeth.

THE claim of round 18: a finished request's lifecycle segments tile
``[t_submit, t_finish]`` exactly — through preemption-and-resume,
swap-out/prefetch, and cross-replica migration (greedy AND sampled) —
with every unclaimed span surfacing as an explicit ``untracked``
segment, and a seeded chaos delay landing in the bucket that names its
cause. The history rides the MigrationBundle and the wire codec as a
backward-compatible field (absent key -> one ``untracked`` segment),
so a migrated request's destination-side record never starts fresh.
Disabled, the tracer must be invisible: same tokens, no recorder."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.harness import chaos as chaoslib
from hpc_patterns_tpu.harness import explain as explainlib
from hpc_patterns_tpu.harness import reqtrace
from hpc_patterns_tpu.harness import trace as tracelib
from hpc_patterns_tpu.models import TransformerConfig, init_params
from hpc_patterns_tpu.models.decode import paged_generate
from hpc_patterns_tpu.models.serving import ContinuousBatcher, EngineCore
from hpc_patterns_tpu.serving_plane.migration import (
    bundle_from_wire,
    bundle_to_wire,
)
from hpc_patterns_tpu.serving_plane.router import Replica, ServingPlane

BASE = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=64, dtype="float32")
ENG = dict(slots=2, pool_pages=8, pages_per_seq=4, page_size=8,
           chunk=2)


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(**BASE)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(autouse=True)
def _clean_tracer():
    reqtrace.reset()
    yield
    reqtrace.reset()


def _standalone(params, cfg, prompt, max_new, **kw):
    return np.asarray(paged_generate(
        params, jnp.asarray(prompt, jnp.int32)[None, :], cfg, max_new,
        page_size=8, **kw))[0]


def _coverage(rtr, stats, sid):
    st = stats[sid]
    return reqtrace.coverage_frac(rtr.segments(sid) or (),
                                  st["t_submit"], st["t_finish"])


def _kinds(rtr, sid):
    return [k for k, *_ in rtr.segments(sid)]


class TestSegmentMechanics:
    def test_transitions_tile_without_gaps(self):
        rtr = reqtrace.ReqTrace()
        rtr.begin_request(7, 1.0)
        rtr.stamp_transition(7, "admit_wait", 2.0)
        rtr.stamp_transition(7, "prefill", 2.5)
        rtr.stamp_transition(7, "decode", 3.0)
        rtr.finish_request(7, 5.0)
        tiled, untracked = reqtrace.finalize(rtr.segments(7), 1.0, 5.0)
        assert untracked == 0.0
        assert [s[0] for s in tiled] == [
            "queued", "admit_wait", "prefill", "decode"]
        # the tiling is exact: spans sum to the request's whole life
        assert sum(s[2] - s[1] for s in tiled) == pytest.approx(4.0)

    def test_gap_becomes_explicit_untracked(self):
        # a stamp site that went missing leaves a gap; finalize turns
        # it into a measured untracked segment, never silence
        segs = [["queued", 0.0, 1.0, None], ["decode", 3.0, 4.0, None]]
        tiled, untracked = reqtrace.finalize(segs, 0.0, 4.0)
        assert [s[0] for s in tiled] == ["queued", "untracked", "decode"]
        assert untracked == pytest.approx(2.0)
        assert reqtrace.coverage_frac(segs, 0.0, 4.0) == pytest.approx(
            0.5)

    def test_unresolved_ends_clamp_into_span(self):
        # open t1 resolves to t_finish; None t0 (the legacy decode)
        # resolves to the cursor; everything clamps into the life
        segs = [["untracked", None, None, None]]
        tiled, untracked = reqtrace.finalize(segs, 2.0, 6.0)
        assert tiled == [["untracked", 2.0, 6.0, None]]
        assert untracked == pytest.approx(4.0)

    def test_empty_history_is_all_untracked(self):
        tiled, untracked = reqtrace.finalize((), 0.0, 3.0)
        assert tiled == [["untracked", 0.0, 3.0, None]]
        assert untracked == pytest.approx(3.0)

    def test_shed_marker_survives_zero_length(self):
        rtr = reqtrace.ReqTrace()
        rtr.begin_request(1, 0.0)
        rtr.finish_request(1, 2.0, final="shed")
        tiled, _ = reqtrace.finalize(rtr.segments(1), 0.0, 2.0)
        assert tiled[-1][0] == "shed"
        assert tiled[-1][1] == tiled[-1][2] == 2.0

    def test_rebegin_continues_one_life(self):
        # the plane's death-resume resubmits the SAME id: one user-
        # visible life, one tiling — a re-begin must not wipe history
        rtr = reqtrace.ReqTrace()
        rtr.begin_request(4, 0.0)
        rtr.stamp_transition(4, "prefill", 1.0)
        rtr.begin_request(4, 2.0)
        assert [k for k, *_ in rtr.segments(4)] == [
            "queued", "prefill", "queued"]

    def test_restamp_submit_moves_start_back_only(self):
        rtr = reqtrace.ReqTrace()
        rtr.begin_request(2, 5.0)
        rtr.restamp_submit(2, 3.0)
        assert rtr.segments(2)[0][1] == 3.0
        rtr.restamp_submit(2, 9.0)  # never forward
        assert rtr.segments(2)[0][1] == 3.0

    def test_annotate_open_tags_current_segment(self):
        rtr = reqtrace.ReqTrace()
        rtr.begin_request(3, 0.0)
        rtr.stamp_transition(3, "migrating", 1.0)
        rtr.annotate_open(3, seq=11)
        assert rtr.segments(3)[-1][3] == {"seq": 11}

    def test_active_is_none_by_default(self):
        assert reqtrace.active() is None
        rtr = reqtrace.configure(enabled=True)
        assert reqtrace.active() is rtr
        reqtrace.configure(enabled=False)
        assert reqtrace.active() is None


class TestCoverageInvariant:
    """The tiling holds through every degraded path the engine owns."""

    def test_plain_serve_full_coverage(self, setup):
        cfg, params = setup
        reqtrace.configure(enabled=True)
        eng = ContinuousBatcher(params, cfg, **ENG)
        ids = [eng.submit(np.arange(5 + i, dtype=np.int32), 6)
               for i in range(4)]
        eng.run()
        rtr = reqtrace.active()
        for sid in ids:
            assert _coverage(rtr, eng.stats, sid) >= 0.999
            assert _kinds(rtr, sid) == [
                "queued", "admit_wait", "prefill", "decode"]

    @pytest.mark.parametrize("sampled", [False, True])
    def test_preempt_resume_tiles_exactly(self, setup, sampled):
        # the starved shape (test_serving.py): the victim's history
        # must carry preempted -> re-admission -> resumed decode with
        # zero untracked time, greedy AND sampled
        cfg, params = setup
        kw = (dict(temperature=0.8, top_k=8, seed=3) if sampled
              else {})
        reqtrace.configure(enabled=True)
        eng = ContinuousBatcher(
            params, cfg, slots=2, pool_pages=4, pages_per_seq=4,
            page_size=8, chunk=2, preempt=True,
            prompt_buckets=(8, 16, 24, 32), **kw)
        pA = np.arange(5, dtype=np.int32)
        pB = np.arange(8, dtype=np.int32) + 7
        a = eng.submit(pA, 20, priority=1)
        eng.run(max_rounds=3)
        b = eng.submit(pB, 4, priority=0)
        got = eng.run()
        assert eng.stats[a]["preemptions"] == 1
        rtr = reqtrace.active()
        assert _coverage(rtr, eng.stats, a) >= 0.999
        assert _coverage(rtr, eng.stats, b) >= 0.999
        kinds = _kinds(rtr, a)
        assert "preempted" in kinds
        # the resume re-enters through admission, not through a wipe
        assert kinds.index("preempted") < len(kinds) - 1
        assert kinds.count("prefill") == 2
        np.testing.assert_array_equal(
            got[a], _standalone(params, cfg, pA, 20, **(
                dict(key=eng.request_key(a), temperature=0.8, top_k=8)
                if sampled else {})))

    @pytest.mark.parametrize("sampled", [False, True])
    def test_plane_migration_tiles_exactly(self, setup, sampled):
        # 1 prefill + 1 decode replica: every request crosses the KV
        # handoff and its ONE history spans both engines — the
        # satellite bugfix (destination record must not start fresh)
        cfg, params = setup
        kw = (dict(temperature=0.8, top_k=8, seed=0) if sampled
              else {})
        reqtrace.configure(enabled=True)
        plane = ServingPlane([
            Replica(EngineCore(params, cfg, **ENG, **kw), name="p",
                    role="prefill"),
            Replica(EngineCore(params, cfg, **ENG, **kw), name="d",
                    role="decode"),
        ])
        rng = np.random.RandomState(5)
        reqs = [(rng.randint(0, cfg.vocab, size=8).astype(np.int32), 6)
                for _ in range(3)]
        rids = [plane.submit(p, m) for p, m in reqs]
        plane.run()
        assert plane.migrations >= len(rids)
        rtr = reqtrace.active()
        for rid in rids:
            assert _coverage(rtr, plane.stats, rid) >= 0.999
            kinds = _kinds(rtr, rid)
            # donor-side life PRECEDES the handoff in the one history
            assert kinds.index("prefill") < kinds.index("migrating")
            assert kinds[-1] == "decode"
            # the router tagged the migration seq for the merge's
            # flow arrows
            mig = [s for s in rtr.segments(rid)
                   if s[0] == "migrating"]
            assert all(isinstance(s[3], dict) and "seq" in s[3]
                       for s in mig)

    def test_disabled_path_identical_tokens_no_recorder(self, setup):
        # --trace-off byte-identical: same tokens with the tracer off
        # and on, and the off path never installs a recorder
        cfg, params = setup
        rng = np.random.RandomState(2)
        reqs = [(rng.randint(0, cfg.vocab, size=8).astype(np.int32), 6)
                for _ in range(3)]

        def serve():
            eng = ContinuousBatcher(params, cfg, **ENG)
            ids = [eng.submit(p, m) for p, m in reqs]
            return {s: eng.run()[s] for s in ids}

        assert reqtrace.active() is None
        off = serve()
        reqtrace.configure(enabled=True)
        on = serve()
        for s in off:
            np.testing.assert_array_equal(off[s], on[s])
        reqtrace.reset()
        assert reqtrace.active() is None


class TestChaosAttribution:
    """The teeth: a seeded delay must land in the bucket that names
    its cause, within tolerance — not smear into a neighbor."""

    def test_stall_lands_in_queued(self, setup):
        # slots=1: seq1 waits queued while seq0 decodes; the seeded
        # engine_round stall delays seq1's admission, so the injected
        # time must show up inside seq1's queued segment
        cfg, params = setup
        delay_ms = 80
        warm = ContinuousBatcher(params, cfg, slots=1, pool_pages=4,
                                 pages_per_seq=4, page_size=8, chunk=2)
        warm.submit(np.arange(5, dtype=np.int32), 8)
        warm.run()  # absorb XLA compiles outside the timed claim
        reqtrace.configure(enabled=True)
        chaoslib.configure(f"stall:at=1,delay_ms={delay_ms}")
        try:
            eng = ContinuousBatcher(params, cfg, slots=1, pool_pages=4,
                                    pages_per_seq=4, page_size=8,
                                    chunk=2)
            eng.submit(np.arange(5, dtype=np.int32), 8)
            s1 = eng.submit(np.arange(6, dtype=np.int32), 4)
            eng.run()
            inj = [e for e in chaoslib.injections()
                   if e["site"] == "engine_round"]
            assert inj, "seeded stall never fired"
            delay_s = sum(e["delay_s"] for e in inj)
            rtr = reqtrace.active()
            queued = sum(t1 - t0 for k, t0, t1, _ in rtr.segments(s1)
                         if k == "queued")
            assert queued >= delay_s, (
                f"stall delay {delay_s}s missing from queued "
                f"({queued}s)")
            assert _coverage(rtr, eng.stats, s1) >= 0.999
        finally:
            chaoslib.reset()

    @staticmethod
    def _tiered_leg(delay_s):
        """Five requests through a tier that holds two of them, every
        host->HBM pull eating ``delay_s`` (0: no fault): ``(eng, mgr,
        ids, injections at host_transfer, the tracer)``."""
        from hpc_patterns_tpu.memory import (
            ColdAfterNPolicy,
            ResidencyManager,
        )

        cfg = TransformerConfig(**{**BASE, "max_seq": 128,
                                   "decode_attn": "gather",
                                   "n_heads": 2})
        params = init_params(jax.random.PRNGKey(0), cfg)
        pps = ContinuousBatcher.pages_needed(8, 24, 8)
        reqtrace.configure(enabled=True)
        if delay_s:
            chaoslib.configure(
                f"slow_host_transfer:delay_ms={int(delay_s * 1e3)}")
        try:
            mgr = ResidencyManager(host_blocks=5 * pps,
                                   policy=ColdAfterNPolicy(2))
            eng = ContinuousBatcher(
                params, cfg, slots=5, pool_pages=2 * pps,
                pages_per_seq=pps, page_size=8, chunk=4,
                residency=mgr)
            rng = np.random.RandomState(3)
            ids = [eng.submit(rng.randint(0, cfg.vocab, size=8)
                              .astype(np.int32), 24) for _ in range(5)]
            eng.run()
            fired = [e for e in chaoslib.injections()
                     if e["site"] == "host_transfer"]
            return eng, mgr, ids, fired, reqtrace.active()
        finally:
            chaoslib.reset()

    def test_slow_host_transfer_lands_in_prefetch_wait(self, setup):
        # the tiered path: a seeded host_transfer delay must widen the
        # prefetch_wait segment it sits inside (the residency window
        # discipline of test_residency_serving, per-request form)
        delay_s = 0.06
        eng, mgr, ids, fired, rtr = self._tiered_leg(delay_s)
        assert mgr.swap_outs > 0
        assert fired
        waits = [t1 - t0 for sid in ids
                 for k, t0, t1, _ in rtr.segments(sid)
                 if k == "prefetch_wait" and t1 is not None]
        assert waits and max(waits) >= delay_s
        swapped = [sid for sid in ids
                   if "swapped_out" in _kinds(rtr, sid)]
        assert swapped
        for sid in ids:
            assert _coverage(rtr, eng.stats, sid) >= 0.999

    def test_slow_host_transfer_breaches_its_own_budget_line_only(
            self, setup):
        # the segment budget end to end (harness/budget.py): the same
        # seeded stream judged against a budget whose prefetch_wait
        # line (0.02 x 80 ms x 23 tokens = 37 ms) is under ONE injected
        # 60 ms pull and whose every other line is most of a generous
        # target: the breach set is that line and no other, and the
        # inter-token digest gives the stall a share
        from hpc_patterns_tpu.harness import budget as budgetlib
        from hpc_patterns_tpu.harness import slo

        self._tiered_leg(0)  # compiles outside the judged leg
        reqtrace.reset()
        eng, mgr, ids, fired, rtr = self._tiered_leg(0.06)
        assert mgr.swap_outs > 0 and len(fired) >= mgr.swap_outs
        snap = rtr.snapshot(eng.stats)
        budget = budgetlib.SLOBudget(
            ttft_shares={"queued": 0.9, "admit_wait": 0.9,
                         "untracked": 0.5},
            tpot_shares={"prefetch_wait": 0.02, "swapped_out": 0.9,
                         "preempted": 0.9, "migrating": 0.9,
                         "untracked": 0.5})
        targets = {0: slo.SLOTarget(ttft_s=5.0, tpot_s=0.08)}
        breaches = budgetlib.evaluate(snap, targets, budget)
        assert budgetlib.breached_segments(breaches) \
            == {"prefetch_wait"}
        assert len(breaches) == 1
        dig = explainlib.digest([snap])
        assert 0.0 < dig["tpot_p99_stall_share"] <= 1.0
        assert dig["coverage_frac"] >= 0.95


class TestHistoryTransport:
    def _bundle(self, setup):
        cfg, params = setup
        reqtrace.configure(enabled=True)
        eng = EngineCore(params, cfg, **ENG)
        eng.submit(np.arange(8, dtype=np.int32), 6)
        eng.service_round(decode=False)
        [slot] = eng.exportable_slots()
        return eng.export_migration(slot)

    def test_bundle_carries_history(self, setup):
        bundle = self._bundle(setup)
        assert bundle.segments is not None
        kinds = [s[0] for s in bundle.segments]
        assert kinds[0] == "queued" and kinds[-1] == "migrating"
        # exported copies are immutable-shaped tuples, JSON-able
        json.dumps(bundle.segments)

    def test_wire_roundtrip_preserves_segments(self, setup):
        bundle = self._bundle(setup)
        back = bundle_from_wire(bundle_to_wire(bundle))
        assert back.segments == tuple(
            tuple(s) for s in bundle.segments)

    def test_wire_null_means_donor_traced_nothing(self, setup):
        bundle = self._bundle(setup)
        wire = bundle_to_wire(bundle)
        wire["segments"] = None
        assert bundle_from_wire(wire).segments is None

    def test_legacy_wire_absent_key_decodes_to_untracked(self, setup):
        # the backward-compat contract (the PR 17 transport pattern):
        # a pre-round-18 artifact has NO segments key — the reader
        # must decode it to one untracked span, not None, so the
        # donor-side life is a measured number on the receiver
        bundle = self._bundle(setup)
        wire = bundle_to_wire(bundle)
        del wire["segments"]
        assert bundle_from_wire(wire).segments \
            == reqtrace.LEGACY_SEGMENTS

    def test_legacy_install_resolves_to_untracked_span(self):
        # a legacy bundle's whole donor life lands as one untracked
        # segment from t_submit to the install instant, then decode
        rtr = reqtrace.ReqTrace()
        rtr.install_history(9, reqtrace.LEGACY_SEGMENTS, t=4.0,
                            t_submit=1.0)
        tiled, untracked = reqtrace.finalize(rtr.segments(9), 1.0, 6.0)
        assert [s[0] for s in tiled] == ["untracked", "decode"]
        assert untracked == pytest.approx(3.0)

    def test_install_prefers_local_history(self):
        # in-process the recorder is shared: the live history carries
        # the router's seq annotation, which the bundle's exported
        # copy predates — install must keep the richer local one
        rtr = reqtrace.ReqTrace()
        rtr.begin_request(5, 0.0)
        carried = rtr.export_history(5, 1.0)
        rtr.annotate_open(5, seq=3)
        rtr.install_history(5, carried, t=2.0, t_submit=0.0)
        mig = [s for s in rtr.segments(5) if s[0] == "migrating"]
        assert mig[0][3] == {"seq": 3}


class TestPerfettoLane:
    def test_finished_history_mirrors_onto_request_lane(self, setup):
        # with a flight recorder active, finish mirrors the resolved
        # segments as cat="request" X slices on the request's own tid
        cfg, params = setup
        tracelib.configure(enabled=True)
        reqtrace.configure(enabled=True)
        try:
            eng = ContinuousBatcher(params, cfg, **ENG)
            sid = eng.submit(np.arange(5, dtype=np.int32), 4)
            eng.run()
            rec = tracelib.active()
            lane = [ev for ev in rec.events
                    if ev[0] == "X" and ev[1] == "request"]
            assert {ev[2] for ev in lane} >= {
                "queued", "prefill", "decode"}
            tids = {ev[4] for ev in lane}
            assert tids == {tracelib.TID_REQUEST + sid}
            assert all(ev[6]["seq_id"] == sid for ev in lane)
        finally:
            tracelib.configure(enabled=False)


class TestSnapshotAndExplain:
    def _served_snapshot(self, setup):
        cfg, params = setup
        reqtrace.configure(enabled=True)
        eng = ContinuousBatcher(params, cfg, **ENG)
        ids = [eng.submit(np.arange(5 + i, dtype=np.int32), 6,
                          priority=i % 2) for i in range(4)]
        eng.run()
        return reqtrace.active().snapshot(eng.stats)

    def test_snapshot_payload_and_coverage(self, setup):
        snap = self._served_snapshot(setup)
        assert snap["n"] == 4
        assert snap["coverage_frac"] >= 0.999
        json.dumps(snap)  # the kind=reqtrace record must be JSON-able
        entry = next(iter(snap["requests"].values()))
        assert {"priority", "t_submit", "t_first", "t_finish",
                "segments", "outcome"} <= set(entry)

    def test_digest_shares_sum_and_gate_scalars(self, setup):
        snap = self._served_snapshot(setup)
        dig = explainlib.digest([snap])
        assert dig["n"] == 4
        assert dig["coverage_frac"] >= 0.999
        assert 0.0 <= dig["ttft_p99_queue_share"] <= 1.0
        assert set(dig["classes"]) == {0, 1}
        for cls in dig["classes"].values():
            assert cls["n_band"] >= 1
            # window-weighted shares are a partition of attributed time
            assert sum(cls["band_shares"].values()) == pytest.approx(
                1.0, abs=1e-6)
        assert len(dig["worst"]) <= explainlib.WORST_N
        ttfts = [r["ttft_s"] for r in dig["worst"]]
        assert ttfts == sorted(ttfts, reverse=True)

    def test_format_names_the_tail_bucket(self, setup):
        snap = self._served_snapshot(setup)
        text = explainlib.format_explain(explainlib.digest([snap]))
        assert "request forensics" in text
        assert "p99-TTFT band" in text
        assert "queued" in text  # the dominant bucket is named

    def test_cli_exit_codes_and_digest_out(self, setup, tmp_path,
                                           capsys):
        from hpc_patterns_tpu.harness.runlog import RunLog

        snap = self._served_snapshot(setup)
        log = tmp_path / "run.jsonl"
        RunLog(str(log)).emit(kind="reqtrace", **snap)
        out = tmp_path / "dig.json"
        assert explainlib.main([str(log), "-o", str(out)]) == 0
        printed = capsys.readouterr().out
        assert "request forensics" in printed
        dig = json.loads(out.read_text())
        assert dig["n"] == 4
        # a log with no reqtrace records exits 2, loudly
        empty = tmp_path / "empty.jsonl"
        RunLog(str(empty)).emit(kind="metrics")
        assert explainlib.main([str(empty)]) == 2


class TestInterTokenDigest:
    """The decode-phase half of the digest: per-token availability
    stamps tile the same canonical segments over INTER-TOKEN windows,
    so 'tpot p99 missed' comes pre-attributed like the TTFT band
    does. Edge shapes the tiling must survive: shed-only streams (no
    tokens at all), single-token responses (no gap), and
    migration-install histories (segments without stamps — donor
    token instants are engine-local wall clock, so installs start
    empty)."""

    @staticmethod
    def _snap(entries):
        return {"n": len(entries), "coverage_frac": 1.0,
                "requests": {str(i): e
                             for i, e in enumerate(entries)}}

    def test_gap_tiling_attributes_the_stall(self):
        # stamps at 1.0/1.1/3.0: the long gap crosses a 1.8s
        # prefetch_wait span -> gap shares sum to 1.0 and the pooled
        # p99 band blames the stall mechanism
        e = {"priority": 0, "t_submit": 0.0, "t_first": 1.0,
             "t_finish": 3.0, "tokens": 3, "outcome": "ok",
             "segments": [["queued", 0.0, 1.0, None],
                          ["decode", 1.0, 1.1, None],
                          ["prefetch_wait", 1.1, 2.9, None],
                          ["decode", 2.9, 3.0, None]],
             "token_ts": [1.0, 1.1, 3.0]}
        dig = explainlib.digest([self._snap([e])])
        tp = dig["tpot"]
        assert tp["n_gaps"] == 2 and tp["n_band"] == 1
        assert sum(tp["band_shares"].values()) == pytest.approx(1.0)
        assert dig["tpot_p99_band_shares"]["prefetch_wait"] \
            == pytest.approx(1.8 / 1.9)
        assert dig["tpot_p99_stall_share"] \
            == pytest.approx(1.8 / 1.9)
        # the per-class section carries the same pool
        assert dig["classes"][0]["tpot"]["n_gaps"] == 2
        text = explainlib.format_explain(dig)
        assert "inter-token gaps" in text
        assert "prefetch_wait" in text

    def test_shed_only_stream_has_no_gaps_and_zero_stall_share(self):
        e = {"priority": 0, "t_submit": 0.0, "t_first": None,
             "t_finish": 1.0, "tokens": 0, "outcome": "shed",
             "segments": [["queued", 0.0, 0.5, None],
                          ["shed", 0.5, 0.5, None]],
             "token_ts": None}
        dig = explainlib.digest([self._snap([e])])
        assert dig["tpot"]["n_gaps"] == 0
        assert dig["tpot_p99_stall_share"] == 0.0
        assert dig["tpot_p99_band_shares"] == {}
        assert dig["tpot"]["gap"]["p99"] is None
        explainlib.format_explain(dig)  # renders without a tpot line

    def test_single_token_response_has_no_inter_token_window(self):
        e = {"priority": 0, "t_submit": 0.0, "t_first": 1.0,
             "t_finish": 1.0, "tokens": 1, "outcome": "ok",
             "segments": [["prefill", 0.0, 1.0, None]],
             "token_ts": [1.0]}
        dig = explainlib.digest([self._snap([e])])
        assert dig["tpot"]["n_gaps"] == 0
        assert dig["tpot_p99_stall_share"] == 0.0

    def test_migration_install_history_without_stamps_digests(self):
        # a migrated request's install carries full segments but an
        # empty stamp list (donor instants are engine-local): the
        # TTFT half still attributes, the TPOT half stays silent
        e = {"priority": 0, "t_submit": 0.0, "t_first": 0.5,
             "t_finish": 2.0, "tokens": 8, "outcome": "ok",
             "segments": [["queued", 0.0, 0.4, None],
                          ["prefill", 0.4, 0.5, None],
                          ["decode", 0.5, 1.0, None],
                          ["migrating", 1.0, 1.5, None],
                          ["decode", 1.5, 2.0, None]],
             "token_ts": None}
        dig = explainlib.digest([self._snap([e])])
        assert dig["tpot"]["n_gaps"] == 0
        assert dig["tpot_p99_stall_share"] == 0.0
        assert dig["ttft_p99_band_shares"]["queued"] \
            == pytest.approx(0.8)

    def test_engine_snapshot_carries_monotone_token_stamps(self, setup):
        # the producer half: a served stream's stats rows stamp one
        # instant per collected token, nondecreasing, first stamp at
        # t_first — and the snapshot serializes them
        cfg, params = setup
        reqtrace.configure(enabled=True)
        eng = ContinuousBatcher(params, cfg, **ENG)
        ids = [eng.submit(np.arange(5 + i, dtype=np.int32), 6)
               for i in range(3)]
        eng.run()
        snap = reqtrace.active().snapshot(eng.stats)
        for sid in ids:
            entry = snap["requests"][str(sid)]
            ts = entry["token_ts"]
            assert len(ts) == entry["tokens"]
            assert ts == sorted(ts)
            assert ts[0] == pytest.approx(entry["t_first"])
            assert ts[-1] <= entry["t_finish"] + 1e-6
        dig = explainlib.digest([snap])
        assert dig["tpot"]["n_gaps"] >= 3
        assert sum(dig["tpot"]["band_shares"].values()) \
            == pytest.approx(1.0)
