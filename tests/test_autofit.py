"""Tier-1 pins for autofit (harness/autofit.py) — observability
becomes control.

Four claims, each on hand-built fixtures with KNOWN optima (the fitters
are pure functions of the records, so the tests need no device):

- determinism: the same records fit to bit-identical config bytes, and
  the CLI round-trips them through ``--emit`` / ``load_fitted``;
- each section fitter lands on the fixture's known optimum (ladder
  rungs at the observed lengths, priority policy when two classes
  paged, inverse-pressure placement weights, hysteresis bands that
  never flap on the recorded trajectory);
- the offline threshold replay holds steady on a boundary trajectory
  (the flap the hysteresis band exists to prevent);
- fitted against default, on records alone: the fitted ladder, read
  back through the config file, pads the recorded stream strictly
  less than the default ladder by the engine's own
  ``expected_padding``; the blamed share is the digest's p99-band
  share and falls when the wait does.
"""

import json
from types import SimpleNamespace

import pytest

from hpc_patterns_tpu.harness import autofit
from hpc_patterns_tpu.harness import explain
from hpc_patterns_tpu.models.serving import bucket_ladder, expected_padding


# ---------------------------------------------------------------------------
# fixtures: hand-built record streams with known optima


def admit(prompt_len, padded_len=None, priority=0, seq_id=0):
    return {"kind": "serve_admit", "seq_id": seq_id, "slot": 0,
            "prompt_len": prompt_len,
            "padded_len": padded_len or prompt_len,
            "priority": priority}


def trace_rec(events):
    """One ``kind=trace`` record; events as the recorder's 7-tuples
    (ph, cat, name, ts, tid, dur, args) — JSON round-trips them as
    lists, which is what read_records hands the fitters."""
    return {"kind": "trace", "events": [list(e) for e in events]}


def metrics_rec(gauges):
    return {"kind": "metrics",
            "gauges": {k: {"last": v, "min": v, "max": v, "n": 2}
                       for k, v in gauges.items()}}


def attain(round_, replicas, queued, attained, judged, active=0):
    return {"kind": "plane_attainment", "round": round_,
            "replicas": replicas, "queued": queued, "active": active,
            "attained_round": attained, "judged_round": judged}


def ladder_records():
    # 60% of the mass at 40, which the shape-blind default ladder
    # (16, 32, 64) pads to 64: the known optimum puts a rung AT 40
    lengths = [16] * 4 + [40] * 12 + [64] * 4
    return [admit(t, seq_id=i) for i, t in enumerate(lengths)]


def paging_records(*, overlap=True):
    # two priority classes paged; 8 pulls across 4 seqs (2.0/seq, past
    # the 1.5 thrash bar); pull windows either fully hidden under the
    # chunk union (overlap=True) or fully exposed after it
    recs = [admit(16, priority=p % 2, seq_id=p) for p in range(4)]
    recs += [{"kind": "serve_swap_out", "seq_id": s} for s in range(4)]
    recs += [{"kind": "serve_prefetch", "seq_id": s % 4}
             for s in range(8)]
    chunks = [("X", "serve", "serve.chunk", 10.0 * i, 0, 10.0, None)
              for i in range(4)]
    t0 = 5.0 if overlap else 100.0
    pulls = [("X", "mem", "mem.prefetch", t0 + 2.0 * i, 0, 4.0, None)
             for i in range(3)]  # peak concurrency 2
    recs.append(trace_rec(chunks + pulls))
    recs.append(metrics_rec({"mem.hbm_pages": 6.0,
                             "mem.host_pages": 2.0}))
    return recs


# ---------------------------------------------------------------------------


class TestDeterminism:
    def test_same_records_fit_to_identical_bytes(self):
        recs = (ladder_records() + paging_records()
                + [attain(i, 2, 2, 4, 4) for i in range(8)])
        a = autofit.dumps_config(autofit.fit(recs))
        b = autofit.dumps_config(autofit.fit(recs))
        assert a == b
        assert json.loads(a)["kind"] == autofit.FITTED_KIND

    def test_cli_emit_is_deterministic_and_loadable(self, tmp_path,
                                                    capsys):
        log = tmp_path / "run.jsonl"
        log.write_text("".join(json.dumps(r) + "\n"
                               for r in ladder_records()))
        out1, out2 = tmp_path / "a.json", tmp_path / "b.json"
        assert autofit.main([str(log), "--emit", str(out1)]) == 0
        assert autofit.main([str(log), "--emit", str(out2)]) == 0
        capsys.readouterr()
        assert out1.read_bytes() == out2.read_bytes()
        fitted = autofit.load_fitted(out1)
        assert fitted["version"] == autofit.FITTED_VERSION
        assert fitted["ladder"] is not None

    def test_unreadable_input_exits_2(self, tmp_path, capsys):
        assert autofit.main([str(tmp_path / "nope.jsonl")]) == 2
        capsys.readouterr()

    def test_load_rejects_wrong_kind_and_version(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"kind": "not_a_config", "version": 1}))
        with pytest.raises(ValueError, match="kind"):
            autofit.load_fitted(p)
        p.write_text(json.dumps({"kind": autofit.FITTED_KIND,
                                 "version": 999}))
        with pytest.raises(ValueError, match="version"):
            autofit.load_fitted(p)

    def test_empty_input_fits_all_null_sections(self):
        fitted = autofit.fit([])
        for section in ("ladder", "residency", "placement",
                        "autoscaler"):
            assert fitted[section] is None
        # an all-null config is still a valid, loadable config
        autofit.validate_fitted(json.loads(autofit.dumps_config(fitted)))


class TestLadderFit:
    def test_rung_lands_on_the_dominant_length(self):
        section = autofit.fit_ladder(ladder_records())
        assert 40 in section["buckets"]
        # the fit can only remove padding: the default is feasible
        assert (section["expected_padding"]
                <= section["default_expected_padding"])
        # and on THIS mixture it strictly wins (60% of mass padded
        # 40 -> 64 by the default)
        assert (section["expected_padding"]
                < section["default_expected_padding"])

    def test_no_admits_fits_nothing(self):
        assert autofit.fit_ladder(paging_records()[4:]) is None

    def test_ladder_from_clamps_to_max_seq(self):
        fitted = autofit.fit(ladder_records())
        full = autofit.ladder_from(fitted)
        assert full is not None and max(full) == 64
        clamped = autofit.ladder_from(fitted, max_seq=40)
        assert max(clamped) == 40
        assert autofit.ladder_from({"ladder": None}) is None


class TestResidencyFit:
    def test_never_paged_fits_nothing(self):
        assert autofit.fit_residency(ladder_records()) is None

    def test_two_classes_and_thrash_raise_the_floor(self):
        section = autofit.fit_residency(paging_records())
        assert section["policy"] == "priority"
        # 8 pulls / 4 seqs = 2.0 > 1.5: the anti-thrash floor
        assert section["min_resident_rounds"] == 2
        assert section["observed"]["pulls_per_seq"] == 2.0

    def test_hidden_pulls_keep_observed_depth(self):
        section = autofit.fit_residency(paging_records(overlap=True))
        # the three staggered 4s pulls peak at 2 in flight, all hidden
        # under the chunk union
        assert section["prefetch_depth"] == 2
        assert section["observed"]["prefetch_overlap_frac"] == 1.0

    def test_exposed_pulls_cap_depth_at_one(self):
        section = autofit.fit_residency(paging_records(overlap=False))
        assert section["prefetch_depth"] == 1
        assert section["observed"]["prefetch_overlap_frac"] == 0.0


class TestPlacementFit:
    def test_uniform_queues_pick_round_robin(self):
        recs = [metrics_rec({"plane.a.queue_depth": 2.0,
                             "plane.b.queue_depth": 2.0})]
        section = autofit.fit_placement(recs)
        assert section["policy"] == "round_robin"
        assert section["weights"]["a"] == section["weights"]["b"]

    def test_skewed_queues_weight_the_idle_replica(self):
        recs = [metrics_rec({"plane.a.queue_depth": 0.0,
                             "plane.b.queue_depth": 8.0})]
        section = autofit.fit_placement(recs)
        assert section["policy"] == "weighted"
        assert section["weights"]["a"] > section["weights"]["b"]
        assert abs(sum(section["weights"].values()) - 1.0) < 1e-6
        assert section["source"] == "queue_depth_gauges"

    def test_busy_rollup_fallback_weights_the_idle_rank(self):
        recs = [{"kind": "trace_merged",
                 "busy": {"0": {"busy_frac": 0.9},
                          "1": {"busy_frac": 0.3}}}]
        section = autofit.fit_placement(recs)
        assert section["source"] == "busy_rollup"
        assert section["weights"]["1"] > section["weights"]["0"]

    def test_no_signal_fits_nothing(self):
        assert autofit.fit_placement(ladder_records()) is None


class TestAutoscalerFit:
    def test_short_trajectory_fits_nothing(self):
        recs = [attain(i, 2, 2, 4, 4) for i in range(3)]
        assert autofit.fit_autoscaler(recs) is None

    def test_fitted_bands_never_flap_on_the_recorded_trajectory(self):
        # a steady boundary load: queued-per-replica sits at 2.0 every
        # round with attainment at 1.0 — the trajectory the hysteresis
        # band exists for. The fitted candidate must replay with zero
        # flaps, and re-replaying it must reproduce the fit's verdict.
        recs = [attain(i, 2, 4, 4, 4) for i in range(12)]
        section = autofit.fit_autoscaler(recs)
        assert section["replay"]["flaps"] == 0
        from hpc_patterns_tpu.serving_plane.autoscaler import (
            AutoscalerPolicy,
        )
        pol = AutoscalerPolicy(
            min_replicas=section["min_replicas"],
            max_replicas=section["max_replicas"],
            up_queue=section["up_queue"],
            down_queue=section["down_queue"],
            up_attainment=section["up_attainment"],
            down_attainment=section["down_attainment"],
            cooldown_rounds=section["cooldown_rounds"],
            window=section["window"])
        decisions = autofit.replay(autofit._trajectory(recs), pol)
        assert autofit.flap_count(decisions) == 0
        assert len(decisions) == 12

    def test_flap_count_counts_direction_reversals(self):
        def d(*actions):
            return [SimpleNamespace(action=a) for a in actions]

        assert autofit.flap_count(d("hold", "hold")) == 0
        assert autofit.flap_count(d("up", "hold", "up")) == 0
        assert autofit.flap_count(d("up", "down", "up")) == 2
        assert autofit.flap_count(d("up", "hold", "down")) == 1


class TestConsumers:
    def test_autoscaler_policy_from_fitted_applies_bands(self):
        from hpc_patterns_tpu.serving_plane.autoscaler import (
            AutoscalerPolicy,
        )

        recs = [attain(i, 2, 4, 4, 4) for i in range(12)]
        fitted = autofit.fit(recs)
        pol = AutoscalerPolicy.from_fitted(fitted, max_replicas=8)
        section = fitted["autoscaler"]
        assert pol.up_queue == section["up_queue"]
        assert pol.window == section["window"]
        # operator overrides win over the fit
        assert pol.max_replicas == 8

    def test_residency_manager_from_fitted_applies_depth(self):
        from hpc_patterns_tpu.memory import ResidencyManager

        fitted = autofit.fit(paging_records(overlap=True))
        mgr = ResidencyManager.from_fitted(fitted, host_blocks=4)
        assert mgr.prefetch_depth == 2


def stall_entry(*, queued_to=1.0, wait=(1.1, 2.9), t_finish=3.0,
                token_ts=(1.0, 1.1, 3.0), priority=0):
    """One finished request whose inter-token tail is dominated by a
    ``prefetch_wait`` span: token stamps at 1.0/1.1/then the finish,
    with the wait segment filling (most of) the long gap."""
    segs = [["queued", 0.0, queued_to, None],
            ["decode", queued_to, wait[0], None],
            ["prefetch_wait", wait[0], wait[1], None],
            ["decode", wait[1], t_finish, None]]
    return {"priority": priority, "t_submit": 0.0,
            "t_first": float(token_ts[0]), "t_finish": t_finish,
            "tokens": len(token_ts), "outcome": "ok",
            "preemptions": 0, "segments": segs,
            "token_ts": list(token_ts)}


def reqtrace_rec(entries):
    return {"kind": "reqtrace", "n": len(entries),
            "coverage_frac": 1.0,
            "requests": {str(i): e for i, e in enumerate(entries)}}


class TestBlameFit:
    def test_decode_stall_outranks_the_queued_ttft_shape(self):
        # queued fills the ENTIRE TTFT window (share 1.0, the default
        # look of any saturated open-loop stream) yet the decode-phase
        # stall still wins: precedence, not max-share
        blame = autofit.fit_blame([reqtrace_rec([stall_entry()])])
        assert blame["axis"] == "tpot"
        assert blame["dominant"] == "prefetch_wait"
        assert blame["candidates"]["ttft.queued"] == pytest.approx(
            1.0, abs=1e-6)
        assert blame["share"] >= autofit.MIN_BLAME_SHARE

    def test_stall_actions_raise_the_antithrash_floor(self):
        blame = autofit.fit_blame([reqtrace_rec([stall_entry()])])
        assert blame["actions"]["min_resident_rounds"] \
            == autofit.BLAME_RESIDENT_ROUNDS
        # one parked row, no stacked waits -> deepen (floor 2)
        assert blame["actions"]["prefetch_depth"] == 2
        assert blame["observed"]["stacked_waits_peak"] == 1

    def test_stacked_waits_cap_depth_at_one(self):
        # two requests whose wait spans overlap in wall time: exposed
        # transfers piled onto one host, the fit serializes them
        entries = [stall_entry(), stall_entry(wait=(1.2, 2.8))]
        blame = autofit.fit_blame([reqtrace_rec(entries)])
        assert blame["dominant"] == "prefetch_wait"
        assert blame["observed"]["stacked_waits_peak"] == 2
        assert blame["actions"]["prefetch_depth"] == 1

    def test_no_decode_stall_blames_the_queue(self):
        # same request with the stall segment replaced by decode and
        # an even token cadence: only the TTFT queued share is left
        e = stall_entry()
        e["segments"] = [["queued", 0.0, 1.0, None],
                         ["decode", 1.0, 3.0, None]]
        e["token_ts"] = [1.0, 2.0, 3.0]
        blame = autofit.fit_blame([reqtrace_rec([e])])
        assert (blame["axis"], blame["dominant"]) == ("ttft", "queued")
        assert blame["actions"] == {"up_queue": 1}

    def test_admit_wait_blamed_when_queued_is_quiet(self):
        e = stall_entry()
        e["segments"] = [["queued", 0.0, 0.1, None],
                         ["admit_wait", 0.1, 1.0, None],
                         ["decode", 1.0, 3.0, None]]
        e["token_ts"] = [1.0, 2.0, 3.0]
        blame = autofit.fit_blame([reqtrace_rec([e])])
        assert blame["dominant"] == "admit_wait"
        assert blame["actions"] == {"admit_highwater": 1.0}

    def test_below_threshold_blames_nobody(self):
        # every candidate under MIN_BLAME_SHARE: an untracked-heavy
        # history with an even cadence leaves no segment dominant
        e = stall_entry()
        e["segments"] = [["queued", 0.0, 0.2, None],
                         ["admit_wait", 0.2, 0.4, None]]
        e["token_ts"] = [1.0, 2.0, 3.0]
        blame = autofit.fit_blame([reqtrace_rec([e])])
        assert blame["dominant"] is None and blame["axis"] is None
        assert blame["actions"] == {}

    def test_no_reqtrace_records_means_no_blame(self):
        assert autofit.fit_blame(ladder_records()) is None
        assert autofit.fit(ladder_records())["blame"] is None

    def test_fit_threads_blame_into_the_residency_section(self):
        # paging signals alone fit depth from the trace overlap; the
        # digest proves a request's p99 PAID for the exposed pull, so
        # the blame actions override the signal fit
        recs = paging_records(overlap=True) \
            + [reqtrace_rec([stall_entry()])]
        fitted = autofit.fit(recs)
        res = fitted["residency"]
        assert res["min_resident_rounds"] \
            == autofit.BLAME_RESIDENT_ROUNDS
        assert res["prefetch_depth"] \
            == fitted["blame"]["actions"]["prefetch_depth"]
        assert fitted["source"]["n_reqtrace"] == 1
        # the blamed fit is still deterministic, byte for byte
        assert autofit.dumps_config(autofit.fit(recs)) \
            == autofit.dumps_config(autofit.fit(recs))


class TestFittedAgainstDefault:
    def test_fitted_ladder_pads_the_recorded_stream_strictly_less(
            self, tmp_path):
        # the consumer's path: fit -> config file -> load -> ladder,
        # judged by the engine's own padding arithmetic, not by the
        # numbers the fitter reports about itself
        recs = ladder_records()
        lengths = [r["prompt_len"] for r in recs]
        path = tmp_path / "fitted.json"
        path.write_text(autofit.dumps_config(autofit.fit(recs)))
        fitted = autofit.ladder_from(autofit.load_fitted(path),
                                     max_seq=256)
        default = bucket_ladder(max(lengths))
        assert default == (16, 32, 64)
        assert max(fitted) >= max(lengths)  # every prompt has a rung
        assert (expected_padding(fitted, lengths)
                < expected_padding(default, lengths))

    def test_blamed_share_is_the_digests_and_shrinks_with_the_wait(self):
        # what the fitter blames is the digest's pooled p99-gap-band
        # share of the same records, so a stream re-served under the
        # fitted residency can be judged by the same number: a pull
        # that hides most of its wait reads a strictly smaller share
        exposed = reqtrace_rec([stall_entry()])
        blame = autofit.fit_blame([exposed])
        assert (blame["axis"], blame["dominant"]) \
            == ("tpot", "prefetch_wait")
        band = explain.digest([exposed])["tpot_p99_band_shares"]
        assert blame["share"] == pytest.approx(band["prefetch_wait"],
                                               abs=1e-6)
        hidden = reqtrace_rec([stall_entry(wait=(1.1, 1.4))])
        share_hidden = (explain.digest([hidden])["tpot_p99_band_shares"]
                        .get("prefetch_wait", 0.0))
        assert share_hidden < blame["share"]
