"""Tier-1 smoke for the bench regression gate (harness/regress.py).

Runs over a five-round trajectory written into ``tmp_path`` in the
round schema ``bench.py --gate`` writes: three healthy captures (the
newest on a machine whose DMA rate read ~11% low), one that died with
a traceback (``parsed`` null, rc=1) and one whose backend never came
up (``detail.degenerate``). The gate must pass on that history — the
two dead captures skipped by name, not failed — and must fail with a
table naming the metric when the newest comparable round is degraded
beyond tolerance. This is the machine check that keeps
``bench.py --gate`` honest without a chip.
"""

import json
from pathlib import Path

import pytest

from hpc_patterns_tpu.harness import regress


def _capture(value, vs_baseline, dma_gbps):
    return {"metric": "onchip_overlap_speedup", "value": value,
            "unit": "x", "vs_baseline": vs_baseline,
            "detail": {"dma_gbps": dma_gbps, "degenerate": False,
                       "backend": "tpu"}}


#: (n, rc, parsed) — the shape of a real capture history
TRAJECTORY = (
    (1, 0, _capture(1.86, 1.21, 577.0)),
    (2, 0, _capture(1.87, 1.22, 579.5)),
    (3, 0, _capture(1.77, 1.15, 512.6)),   # healthy, slow-DMA machine
    (4, 1, None),                          # died with a traceback
    (5, 0, {"metric": "onchip_overlap_speedup", "value": 0.0,
            "unit": "x", "vs_baseline": 0.0,
            "detail": {"degenerate": True, "backend": "unavailable",
                       "error": "TimeoutError: backend init"}}),
)


@pytest.fixture()
def trajectory(tmp_path):
    paths = []
    for n, rc, parsed in TRAJECTORY:
        p = tmp_path / f"BENCH_r{n:02d}.json"
        p.write_text(json.dumps({"n": n, "cmd": "python bench.py",
                                 "rc": rc, "tail": "", "parsed": parsed}))
        paths.append(str(p))
    return paths


class TestRecordedTrajectory:
    def test_gate_passes_on_healthy_trajectory(self, trajectory, capsys):
        assert regress.main(trajectory) == 0
        out = capsys.readouterr().out
        assert "GATE: PASS" in out
        # the dead captures are skipped by name, not silently
        assert "skipped" in out
        assert "r4" in out and "r5" in out

    def test_dead_captures_are_skipped(self, trajectory):
        recs = [regress.load_round(p) for p in trajectory]
        usable = [r for r in recs if regress.comparable(r)]
        skipped = [r for r in recs if not regress.comparable(r)]
        # r04 (parsed null) and r05 (detail.degenerate) must be out
        assert {r["n"] for r in skipped} == {4, 5}
        assert all(isinstance(r["parsed"], dict) for r in usable)

    def test_synthetic_degradation_fails_naming_the_metric(
            self, trajectory, capsys):
        # degrade the newest COMPARABLE round's headline value beyond
        # tolerance; the gate must exit nonzero and name the metric
        recs = [(p, regress.load_round(p)) for p in trajectory]
        newest = max((pr for pr in recs if regress.comparable(pr[1])),
                     key=lambda pr: pr[1]["n"])
        path, rec = newest
        rec["parsed"]["value"] *= 0.7  # -30%, well past 10%
        rec.pop("_path")
        Path(path).write_text(json.dumps(rec))
        assert regress.main(trajectory) == 1
        out = capsys.readouterr().out
        assert "GATE: FAIL" in out
        assert "REGRESSION" in out
        assert "headline value" in out

    def test_dma_rate_is_informational_not_gated(self, trajectory,
                                                 capsys):
        # r03's DMA rate reads ~11% under r02's (512.6 vs 579.5): that
        # must be REPORTED but must not fail the gate
        assert regress.main(trajectory) == 0
        out = capsys.readouterr().out
        assert "session health" in out
        assert "info" in out

    def test_no_coverage_loss_on_consistent_keys(self, trajectory,
                                                 capsys):
        # every comparable round carries the same keys, so nothing has
        # been "lost"
        assert regress.main(trajectory) == 0
        assert "coverage loss" not in capsys.readouterr().out


class TestGateMechanics:
    def _round(self, tmp_path, n, value, vs_baseline=1.0, detail=None,
               parsed=True):
        rec = {"n": n, "cmd": "test", "rc": 0, "tail": ""}
        rec["parsed"] = (
            {"metric": "m", "value": value, "unit": "x",
             "vs_baseline": vs_baseline, "detail": detail or {}}
            if parsed else None)
        p = tmp_path / f"BENCH_r{n:02d}.json"
        p.write_text(json.dumps(rec))
        return str(p)

    def test_within_tolerance_passes(self, tmp_path, capsys):
        files = [self._round(tmp_path, 1, 2.0),
                 self._round(tmp_path, 2, 1.85)]  # -7.5% < 10%
        assert regress.main(files) == 0
        capsys.readouterr()

    def test_beyond_tolerance_fails(self, tmp_path, capsys):
        files = [self._round(tmp_path, 1, 2.0),
                 self._round(tmp_path, 2, 1.7)]  # -15%
        assert regress.main(files) == 1
        capsys.readouterr()

    def test_tolerance_flag(self, tmp_path, capsys):
        files = [self._round(tmp_path, 1, 2.0),
                 self._round(tmp_path, 2, 1.7)]
        assert regress.main(files + ["--tolerance", "0.2"]) == 0
        capsys.readouterr()

    def test_newest_degenerate_falls_back_to_prior(self, tmp_path,
                                                   capsys):
        files = [self._round(tmp_path, 1, 2.0),
                 self._round(tmp_path, 2, 1.95),
                 self._round(tmp_path, 3, 0.0,
                             detail={"degenerate": True})]
        # r3 measured nothing: r2 vs r1 is the comparison, and passes
        assert regress.main(files) == 0
        out = capsys.readouterr().out
        assert "r3" in out and "skipped" in out

    def test_improvement_against_best_not_last(self, tmp_path, capsys):
        # best prior is r1 (2.0), not the weaker r2: a slow newest
        # round must be judged against the trajectory's best
        files = [self._round(tmp_path, 1, 2.0),
                 self._round(tmp_path, 2, 1.0),
                 self._round(tmp_path, 3, 1.7)]
        assert regress.main(files) == 1
        capsys.readouterr()

    def test_lower_better_metric(self, tmp_path, capsys):
        files = [
            self._round(tmp_path, 1, 2.0,
                        detail={"serving_bubble_frac": 0.10}),
            self._round(tmp_path, 2, 2.0,
                        detail={"serving_bubble_frac": 0.30}),
        ]
        # 0.10 -> 0.30 is past 10% relative + 0.05 absolute slack
        assert regress.main(files) == 1
        out = capsys.readouterr().out
        assert "serving_bubble_frac" in out

    def test_backend_mismatch_gates_nothing(self, tmp_path, capsys):
        # a CPU-fallback capture must not "regress" against the TPU
        # trajectory — mismatched-backend priors are set aside
        files = [self._round(tmp_path, 1, 2.0,
                             detail={"backend": "tpu"}),
                 self._round(tmp_path, 2, 0.9,
                             detail={"backend": "cpu"})]
        assert regress.main(files) == 0
        out = capsys.readouterr().out
        assert "nothing to gate" in out

    def test_same_backend_still_gates(self, tmp_path, capsys):
        files = [self._round(tmp_path, 1, 2.0,
                             detail={"backend": "tpu"}),
                 self._round(tmp_path, 2, 0.9,
                             detail={"backend": "cpu"}),
                 self._round(tmp_path, 3, 1.5,
                             detail={"backend": "tpu"})]
        # r3 gates against r1 (tpu), r2 is set aside: -25% fails
        assert regress.main(files) == 1
        capsys.readouterr()

    def test_single_comparable_round_passes(self, tmp_path, capsys):
        files = [self._round(tmp_path, 1, 2.0),
                 self._round(tmp_path, 2, 0.0, parsed=False)]
        assert regress.main(files) == 0
        capsys.readouterr()

    def test_coverage_loss_warns_but_passes(self, tmp_path, capsys):
        # r1 carried serving_tok_s; r2 silently lost the measurement:
        # gate still exits 0 (the value didn't regress — it vanished)
        # but the loss is named on stdout AND stderr
        files = [self._round(tmp_path, 1, 2.0,
                             detail={"serving_tok_s": 100.0}),
                 self._round(tmp_path, 2, 2.0)]
        assert regress.main(files) == 0
        captured = capsys.readouterr()
        assert "coverage loss" in captured.out
        assert "serving_tok_s" in captured.out
        assert "r1" in captured.out
        assert "coverage loss" in captured.err

    def test_no_coverage_warning_when_keys_consistent(self, tmp_path,
                                                      capsys):
        files = [self._round(tmp_path, 1, 2.0,
                             detail={"serving_tok_s": 100.0}),
                 self._round(tmp_path, 2, 2.0,
                             detail={"serving_tok_s": 110.0})]
        assert regress.main(files) == 0
        captured = capsys.readouterr()
        assert "coverage loss" not in captured.out
        assert captured.err == ""

    def test_ungated_keys_never_flag_coverage_loss(self, tmp_path,
                                                   capsys):
        # dma_gbps is informational (session health): its absence is
        # not lost gate coverage
        files = [self._round(tmp_path, 1, 2.0,
                             detail={"dma_gbps": 500.0}),
                 self._round(tmp_path, 2, 2.0)]
        assert regress.main(files) == 0
        assert "coverage loss" not in capsys.readouterr().out

    def test_changed_headline_metric_is_not_coverage_loss(self, tmp_path,
                                                          capsys):
        # a round that switched headline metric is a different
        # trajectory (extract_metrics already refuses to compare it),
        # not a capture that lost keys
        r1 = {"n": 1, "cmd": "t", "rc": 0, "tail": "",
              "parsed": {"metric": "old_metric", "value": 2.0,
                         "vs_baseline": 1.0,
                         "detail": {"serving_tok_s": 100.0}}}
        r2 = {"n": 2, "cmd": "t", "rc": 0, "tail": "",
              "parsed": {"metric": "new_metric", "value": 2.0,
                         "vs_baseline": 1.0, "detail": {}}}
        files = []
        for rec in (r1, r2):
            p = tmp_path / f"BENCH_r{rec['n']:02d}.json"
            p.write_text(json.dumps(rec))
            files.append(str(p))
        assert regress.main(files) == 0
        assert "coverage loss" not in capsys.readouterr().out

    def test_unreadable_input_exits_2(self, tmp_path, capsys):
        bad = tmp_path / "nope.json"
        assert regress.main([str(bad)]) == 2
        capsys.readouterr()

    def test_bad_tolerance_exits_2(self, tmp_path, capsys):
        f = self._round(tmp_path, 1, 2.0)
        assert regress.main([f, "--tolerance", "1.5"]) == 2
        capsys.readouterr()


class TestQuantizedSpecs:
    def test_quantized_keys_are_gated_and_covered(self):
        # the round-13 gated keys exist, gate in the right direction,
        # and — being gated — ride the coverage-loss warning like
        # every other headline (a capture that silently drops
        # quant_goodput_tok_s warns instead of reading as green)
        by_path = {s.path: s for s in regress.SPECS}
        g = by_path["detail.quant_goodput_tok_s"]
        assert g.gated and g.direction == "higher"
        f = by_path["detail.kv_pool_bytes_frac"]
        assert f.gated and f.direction == "lower"
        assert f.abs_slack <= 0.05  # dtype geometry: tight band
        b = by_path["detail.quant_bubble_frac"]
        assert b.gated and b.direction == "lower"


class TestElasticSpecs:
    def test_elastic_keys_are_gated_and_covered(self):
        # the round-14 gated keys exist, gate in the right direction,
        # and — being gated — ride the coverage-loss warning like
        # every other headline (a capture that silently drops
        # elastic_slo_attainment warns instead of reading as green)
        by_path = {s.path: s for s in regress.SPECS}
        a = by_path["detail.elastic_slo_attainment"]
        assert a.gated and a.direction == "higher"
        assert a.abs_slack <= 0.05  # a fraction near 1.0: tight band
        g = by_path["detail.goodput_per_replica_round"]
        assert g.gated and g.direction == "higher"
        assert g.abs_slack == 0.0


class TestAutofitSpecs:
    def test_autofit_keys_are_gated_and_covered(self):
        # the round-16 gated keys exist, gate in the right direction,
        # and — being gated — ride the coverage-loss warning like
        # every other headline (a capture that silently drops
        # fitted_goodput_tok_s warns instead of reading as green)
        by_path = {s.path: s for s in regress.SPECS}
        g = by_path["detail.fitted_goodput_tok_s"]
        assert g.gated and g.direction == "higher"
        assert g.abs_slack == 0.0
        f = by_path["detail.autofit_gain_frac"]
        assert f.gated and f.direction == "higher"
        # the gain is a RATIO of two wall clocks: scheduler noise must
        # not fail the gate (a wrong fitter fails the row's own strict
        # padding assertion instead, surfacing as coverage loss here)
        assert f.abs_slack >= 0.03


class TestReqtraceSpecs:
    def test_reqtrace_keys_direction_and_gating(self):
        # round 18: coverage GATES (higher, tight band — a missing
        # stamp site leaks untracked time and regresses here); the p99
        # queue share is informational — where the tail went is
        # load-shape dependent, so it prints drift without failing
        # the gate
        by_path = {s.path: s for s in regress.SPECS}
        c = by_path["detail.attribution_coverage_frac"]
        assert c.gated and c.direction == "higher"
        assert c.abs_slack <= 0.02
        q = by_path["detail.ttft_p99_queue_share"]
        assert not q.gated and q.direction == "lower"


class TestStrictCoverage:
    _round = TestGateMechanics._round

    def test_default_mode_warns_and_passes(self, tmp_path, capsys):
        # without the flag, coverage loss stays a warning: exit 0,
        # WARNING on stderr (the pre-existing contract)
        files = [self._round(tmp_path, 1, 2.0,
                             detail={"serving_tok_s": 100.0}),
                 self._round(tmp_path, 2, 2.0)]
        assert regress.main(files) == 0
        captured = capsys.readouterr()
        assert "WARNING" in captured.err
        assert "coverage loss" in captured.err

    def test_strict_mode_fails_on_coverage_loss(self, tmp_path, capsys):
        # --strict-coverage turns the same loss into a failure: exit 1
        # with ERROR severity naming the key and the round that last
        # carried it
        files = [self._round(tmp_path, 1, 2.0,
                             detail={"serving_tok_s": 100.0}),
                 self._round(tmp_path, 2, 2.0)]
        assert regress.main(files + ["--strict-coverage"]) == 1
        captured = capsys.readouterr()
        assert "ERROR" in captured.err
        assert "serving_tok_s" in captured.err
        assert "r1" in captured.err

    def test_strict_mode_passes_when_coverage_holds(self, tmp_path,
                                                    capsys):
        files = [self._round(tmp_path, 1, 2.0,
                             detail={"serving_tok_s": 100.0}),
                 self._round(tmp_path, 2, 2.0,
                             detail={"serving_tok_s": 110.0})]
        assert regress.main(files + ["--strict-coverage"]) == 0
        assert capsys.readouterr().err == ""
