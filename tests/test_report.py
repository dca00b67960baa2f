"""Report CLI smoke (harness/report.py) + the no-op guard.

The fixture is a checked-in two-snapshot sweep log
(tests/fixtures/report_fixture.jsonl) with known bucket counts, so the
aggregation rules — counters sum, gauges last-wins with min/max across
snapshots, histograms merge — are pinned against a stable input, and a
bucket-layout change cannot slip through unnoticed.
"""

import json
from pathlib import Path

import pytest

from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness import report
from hpc_patterns_tpu.harness.metrics import bucket_index, bucket_value

FIXTURE = Path(__file__).parent / "fixtures" / "report_fixture.jsonl"


@pytest.fixture(autouse=True)
def _fresh_registry():
    yield
    metricslib.configure(enabled=False)


class TestReportFixture:
    def test_aggregate_merges_snapshots(self):
        agg = report.aggregate(report.load_records([FIXTURE]))
        assert agg["n_snapshots"] == 2
        assert agg["results"] == (1, 1)
        # counters sum across snapshots
        assert agg["counters"]["train.steps"] == 30
        # gauges: last value from the later snapshot, min/max across
        g = agg["gauges"]["train.loss"]
        assert g.last == 3.2 and g.min == 3.2 and g.max == 6.9
        # histograms merge bucket counts: 50x1ms + 45x10ms + 5x100ms
        h = agg["histograms"]["span.measure.timed"]
        assert h.count == 100
        assert h.percentile(50) == bucket_value(bucket_index(0.001))
        assert h.percentile(95) == bucket_value(bucket_index(0.01))
        assert h.percentile(100) == 0.1

    def test_cli_smoke(self, capsys):
        rc = report.main([str(FIXTURE)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "merged 2 metrics snapshot(s)" in out
        assert "1 SUCCESS / 1 FAILURE" in out
        assert "train.steps" in out and "30" in out
        assert "span.measure.timed" in out

    def test_histogram_table_carries_p99(self, capsys):
        # SLO accounting judges tails; the per-phase table must show
        # them (p50/p95/p99/max since round 8). The fixture's
        # 50x1ms + 45x10ms + 5x100ms merge puts p99 in the 100ms
        # bucket where p95 still reads 10ms — the tail IS the signal
        agg = report.aggregate(report.load_records([FIXTURE]))
        h = agg["histograms"]["span.measure.timed"]
        # rank 99 lands in the 100ms bucket (95 at 10ms) — clamped to
        # the observed max per the percentile contract
        assert h.percentile(99) == 0.1
        assert h.percentile(99) > 2 * h.percentile(95)
        assert report.PERCENTILES == (50.0, 95.0, 99.0)
        rc = report.main([str(FIXTURE)])
        out = capsys.readouterr().out
        assert rc == 0 and "p99" in out

    def test_cli_no_metrics_records(self, tmp_path, capsys):
        # a plain runlog (no --metrics run) still gets a result summary
        path = tmp_path / "plain.jsonl"
        path.write_text(json.dumps(
            {"kind": "result", "name": "x", "success": True}) + "\n")
        rc = report.main([str(path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "no kind=metrics snapshots" in out

    def test_kind_analysis_record_is_surfaced(self, tmp_path, capsys):
        # the jaxlint verdict (analysis --log) renders next to the
        # runtime rollups — one line per record, rule counts included
        path = tmp_path / "gate.jsonl"
        path.write_text("\n".join([
            json.dumps({"kind": "result", "name": "x", "success": True}),
            json.dumps({"kind": "analysis", "ok": False, "findings": 2,
                        "suppressed": 6, "baselined": 0, "files": 67,
                        "by_rule": {"donation-alias": 2}}),
        ]) + "\n")
        agg = report.aggregate(report.load_records([path]))
        assert agg["analyses"][0]["findings"] == 2
        rc = report.main([str(path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "analysis: FINDINGS — 2 finding(s)" in out
        assert "donation-alias=2" in out and "6 suppressed" in out

    def test_kind_trace_merged_record_is_surfaced(self, tmp_path,
                                                  capsys):
        # the launcher's cross-rank rollup (launch.py --trace-out /
        # harness.collect --log) renders as one digest line: rank
        # count, matched collectives, worst skew, straggler
        path = tmp_path / "merged.jsonl"
        path.write_text(json.dumps({
            "kind": "trace_merged", "num_processes": 2, "ranks": [0, 1],
            "n_ranks": 2, "n_events": 36, "n_matched": 3,
            "n_unmatched": 0,
            "align": {"method": "sync", "offsets_s": {},
                      "drift_bound_s": 0.0, "wall_disagreement_s": 0.0,
                      "residual_s": 0.0},
            "skew": {"allreduce.ring": {"n": 3,
                                        "max_start_skew_s": 0.000966,
                                        "mean_start_skew_s": 0.0005,
                                        "max_dur_skew_s": 0.0014}},
            "stragglers": {"0": {"last": 2, "of": 3},
                           "1": {"last": 1, "of": 3}},
            "busy": {"0": {"busy_frac": 0.5, "bubble_frac": 0.5,
                           "window_s": 1.0}},
            "out": "merged.json",
        }) + "\n")
        rc = report.main([str(path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "trace_merged: 2 rank(s), 3 collective(s) matched" in out
        assert "clock align: sync" in out
        assert "max start skew 0.966 ms (allreduce.ring)" in out
        assert "straggler rank 0 (2/3 last)" in out
        assert "merged.json" in out

    def test_trace_merged_schedule_verdict_is_rendered(self, tmp_path,
                                                       capsys):
        # the desync check travels in the trace_merged record; the
        # digest line must say at a glance whether the ranks PROVABLY
        # ran the same collective program — and name the break if not
        def rec(schedule):
            return {
                "kind": "trace_merged", "n_ranks": 2, "n_matched": 0,
                "n_unmatched": 0, "num_processes": 2, "ranks": [0, 1],
                "n_events": 0,
                "align": {"method": "sync"}, "skew": {},
                "stragglers": {}, "busy": {}, "schedule": schedule,
            }

        path = tmp_path / "merged.jsonl"
        path.write_text("\n".join([
            json.dumps(rec({"verdict": "consistent", "n_collectives": 5,
                            "n_ranks_recorded": 2, "digest": "ab12"})),
            json.dumps(rec({"verdict": "divergent", "n_collectives": 3,
                            "n_ranks_recorded": 2,
                            "first_divergence": {
                                "index": 17,
                                "ranks": {"0": {"op": "allreduce",
                                                "seq": 17},
                                          "1": {"op": "sendrecv_ring",
                                                "seq": 17}}}})),
        ]) + "\n")
        assert report.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "schedules consistent (5 collectives)" in out
        assert "SCHEDULE DIVERGENCE at #17" in out

    def test_cli_empty_input_fails(self, tmp_path, capsys):
        path = tmp_path / "empty.jsonl"
        path.write_text("")
        assert report.main([str(path)]) == 2
        capsys.readouterr()

    def test_layout_mismatch_skips_histograms(self, tmp_path, capsys):
        # a snapshot written under a different bucket layout cannot have
        # its bucket counts merged (indices mean different values);
        # counters/gauges are layout-independent and still merge
        records = report.load_records([FIXTURE])
        old = json.loads(json.dumps(
            next(r for r in records if r.get("kind") == "metrics")))
        old["bucket_layout"] = {"lo_decade": -6, "hi_decade": 3,
                                "per_decade": 8}
        path = tmp_path / "mixed.jsonl"
        path.write_text("".join(json.dumps(r) + "\n"
                                for r in records + [old]))
        agg = report.aggregate(report.load_records([path]))
        assert agg["n_snapshots"] == 3
        assert agg["n_layout_skipped"] == 1
        # histograms hold only the two current-layout snapshots
        assert agg["histograms"]["span.measure.timed"].count == 100
        # counters still summed across all three
        assert agg["counters"]["train.steps"] == 30 + old["counters"][
            "train.steps"]
        assert "different bucket layout" in report.format_report(agg)
        capsys.readouterr()

    def test_load_records_skips_truncated_line(self, tmp_path):
        # a crashed run can truncate its final record mid-write
        path = tmp_path / "torn.jsonl"
        path.write_text(json.dumps({"kind": "result", "success": True})
                        + '\n{"kind": "metr')
        records = report.load_records([path])
        assert len(records) == 1


class TestNoopGuard:
    def test_disabled_metrics_add_zero_records(self, tmp_path, capsys):
        """The tier-1 protection: without --metrics, an instrumented
        run writes exactly the records it always wrote — the registry
        is inert and no kind=metrics snapshot appears."""
        from hpc_patterns_tpu.harness.runlog import RunLog
        from hpc_patterns_tpu.harness.timing import measure
        from hpc_patterns_tpu.models.train import record_step_metrics

        m = metricslib.configure(enabled=False)
        log = RunLog(tmp_path / "run.jsonl")
        measure(lambda: None, repetitions=2, warmup=1, label="guard")
        record_step_metrics(0, 1.0, 0.1, 64)
        with metricslib.span("phase"):
            pass
        log.emit(kind="result", name="guard", success=True)
        records = [json.loads(l) for l in
                   (tmp_path / "run.jsonl").read_text().splitlines()]
        assert [r["kind"] for r in records] == ["result"]
        snap = m.snapshot()
        assert snap["counters"] == snap["gauges"] == snap["histograms"] == {}
        capsys.readouterr()

    def test_run_instrumented_disabled_emits_nothing(self, tmp_path):
        import argparse

        from hpc_patterns_tpu.apps import common
        from hpc_patterns_tpu.harness.runlog import RunLog

        path = tmp_path / "app.jsonl"
        args = argparse.Namespace(metrics=False, log=str(path))

        def fake_app(a):
            RunLog(a.log, truncate=not a.log_append).emit(
                kind="result", name="app", success=True)
            return 0

        assert common.run_instrumented(fake_app, args) == 0
        kinds = [json.loads(l)["kind"]
                 for l in path.read_text().splitlines()]
        # the session's own bracket (device header first, kernel modes
        # last) and nothing from the disabled registry
        assert kinds == ["device", "result", "kernels"]

    def test_run_instrumented_enabled_appends_snapshot(self, tmp_path):
        import argparse

        from hpc_patterns_tpu.apps import common
        from hpc_patterns_tpu.harness.runlog import RunLog

        path = tmp_path / "app.jsonl"
        args = argparse.Namespace(metrics=True, log=str(path))

        def fake_app(a):
            log = RunLog(a.log, truncate=not a.log_append)
            metricslib.get_metrics().counter("app.work").inc(7)
            log.emit(kind="result", name="app", success=True)
            return 0

        assert common.run_instrumented(fake_app, args) == 0
        records = [json.loads(l)
                   for l in path.read_text().splitlines()]
        assert [r["kind"] for r in records] == [
            "device", "result", "kernels", "metrics"]
        assert records[-1]["counters"]["app.work"] == 7
        # and report aggregates the app log end to end
        agg = report.aggregate(records)
        assert agg["counters"]["app.work"] == 7


class TestBudgetRecords:
    def test_kind_slo_budget_renders_the_breach_table(self, tmp_path,
                                                      capsys):
        # budget.publish writes one kind=slo_budget record per
        # breached (class, axis, segment); the report renders them as
        # the per-class table, severity-sorted within a class
        path = tmp_path / "run.jsonl"
        path.write_text("\n".join([
            json.dumps({"kind": "slo_budget", "priority": 1,
                        "axis": "ttft", "segment": "queued",
                        "share": 0.5, "allowance_s": 0.25,
                        "n": 4, "breached": 1, "worst_s": 0.41,
                        "worst_seq_id": 9}),
            json.dumps({"kind": "slo_budget", "priority": 0,
                        "axis": "tpot", "segment": "prefetch_wait",
                        "share": 0.35, "allowance_s": 0.037,
                        "n": 5, "breached": 4, "worst_s": 0.133,
                        "worst_seq_id": 3}),
        ]) + "\n")
        agg = report.aggregate(report.load_records([path]))
        assert len(agg["budgets"]) == 2
        rc = report.main([str(path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert ("slo budget breaches: 2 "
                "(class axis segment: worst/allowance, count)") in out
        # class 0 sorts first; fields land in the labeled columns
        rows = [ln for ln in out.splitlines()
                if "prefetch_wait" in ln or "queued" in ln]
        assert "prefetch_wait" in rows[0] and "queued" in rows[1]
        assert "133ms" in rows[0] and "37ms" in rows[0]
        assert "4/5" in rows[0]
        assert "41" in rows[1].replace("410ms", "410")

    def test_no_budget_records_no_table(self, capsys):
        rc = report.main([str(FIXTURE)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "slo budget breaches" not in out
