"""Tier-1 pins for segment SLO budgets (harness/budget.py).

The evaluator is a pure function of one reqtrace snapshot and the
per-class targets, so every axis rule is pinned on hand-built
tilings with known spends; the publish half is pinned against a
captured emit stream and the metrics registry; the end-to-end claim
— seeded chaos breaches the budget bucket it was injected into and
NO other — is pinned through the real engine in
tests/test_reqtrace.py (TestChaosAttribution, on the tiered stream
that file already compiles).
"""

import pytest

from hpc_patterns_tpu.harness import budget as budgetlib
from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness import slo


def entry(*, segments, t_submit=0.0, t_first=1.0, t_finish=3.0,
          tokens=3, priority=0):
    return {"priority": priority, "t_submit": t_submit,
            "t_first": t_first, "t_finish": t_finish,
            "tokens": tokens, "outcome": "ok", "preemptions": 0,
            "segments": segments}


def snap(entries):
    return {"n": len(entries), "coverage_frac": 1.0,
            "requests": {str(i): e for i, e in enumerate(entries)}}


TARGETS = {0: slo.SLOTarget(ttft_s=1.0, tpot_s=0.1)}


class TestEvaluate:
    def test_ttft_axis_judges_the_submit_to_first_window(self):
        # queued eats 0.9s of a 1.0s TTFT target: past the 0.5 share
        # allowance (0.5s), inside every other budget line
        e = entry(segments=[["queued", 0.0, 0.9, None],
                            ["prefill", 0.9, 1.0, None],
                            ["decode", 1.0, 3.0, None]])
        breaches = budgetlib.evaluate(snap([e]), TARGETS)
        assert budgetlib.breached_segments(breaches) == {"queued"}
        (b,) = breaches
        assert (b["axis"], b["priority"]) == ("ttft", 0)
        assert b["worst_s"] == pytest.approx(0.9)
        assert b["allowance_s"] == pytest.approx(0.5)
        assert b["kind"] == budgetlib.BUDGET_KIND

    def test_tpot_axis_scales_allowance_with_token_count(self):
        # prefetch_wait eats 1.0s of the decode phase; the allowance
        # is share * tpot * (tokens-1) = 0.35 * 0.1 * 2 = 70ms
        e = entry(segments=[["prefill", 0.0, 1.0, None],
                            ["decode", 1.0, 1.5, None],
                            ["prefetch_wait", 1.5, 2.5, None],
                            ["decode", 2.5, 3.0, None]])
        breaches = budgetlib.evaluate(snap([e]), TARGETS)
        assert budgetlib.breached_segments(breaches) \
            == {"prefetch_wait"}
        (b,) = breaches
        assert b["axis"] == "tpot"
        assert b["worst_s"] == pytest.approx(1.0)
        assert b["allowance_s"] == pytest.approx(0.07)

    def test_single_token_response_skips_the_tpot_axis(self):
        # tokens < 2: no inter-token interval exists, so even a huge
        # decode-phase stall has no per-token yardstick to breach
        e = entry(segments=[["prefill", 0.0, 1.0, None],
                            ["prefetch_wait", 1.0, 3.0, None]],
                  tokens=1)
        assert budgetlib.evaluate(snap([e]), TARGETS) == []

    def test_within_allowance_is_silent(self):
        e = entry(segments=[["queued", 0.0, 0.3, None],
                            ["prefill", 0.3, 1.0, None],
                            ["decode", 1.0, 3.0, None]])
        assert budgetlib.evaluate(snap([e]), TARGETS) == []

    def test_unbudgeted_segment_and_untargeted_class_never_breach(self):
        # decode has no budget line; priority 7 has no SLO target
        e1 = entry(segments=[["decode", 0.0, 3.0, None]])
        e2 = entry(segments=[["queued", 0.0, 3.0, None]], priority=7)
        assert budgetlib.evaluate(snap([e1, e2]), TARGETS) == []

    def test_inflight_request_has_no_finalized_window(self):
        e = entry(segments=[["queued", 0.0, None, None]],
                  t_first=None, t_finish=None)
        assert budgetlib.evaluate(snap([e]), TARGETS) == []

    def test_untracked_gap_is_itself_budgeted(self):
        # a bare 0.9s hole before t_first: finalize tiles it as
        # untracked, and the tight 0.15 share alarms on it
        e = entry(segments=[["prefill", 0.9, 1.0, None],
                            ["decode", 1.0, 3.0, None]])
        breaches = budgetlib.evaluate(snap([e]), TARGETS)
        assert budgetlib.breached_segments(breaches) == {"untracked"}

    def test_aggregates_per_class_and_tracks_the_worst(self):
        mild = entry(segments=[["queued", 0.0, 0.6, None],
                               ["decode", 0.6, 3.0, None]])
        bad = entry(segments=[["queued", 0.0, 0.9, None],
                              ["decode", 0.9, 3.0, None]])
        ok = entry(segments=[["queued", 0.0, 0.2, None],
                             ["decode", 0.2, 3.0, None]])
        (b,) = budgetlib.evaluate(snap([mild, bad, ok]), TARGETS)
        assert (b["n"], b["breached"]) == (3, 2)
        assert b["worst_s"] == pytest.approx(0.9)
        assert b["worst_seq_id"] == 1

    def test_custom_budget_and_slo_duck_typing(self):
        # a zero-allowance custom budget breaches on any spend; the
        # evaluator reads ttft_slo_s-style attrs when present
        class Tgt:
            ttft_slo_s = 1.0
            tpot_slo_s = 0.1

        tight = budgetlib.SLOBudget(ttft_shares={"prefill": 0.01})
        e = entry(segments=[["prefill", 0.0, 1.0, None],
                            ["decode", 1.0, 3.0, None]])
        breaches = budgetlib.evaluate(snap([e]), {0: Tgt()}, tight)
        assert budgetlib.breached_segments(breaches) == {"prefill"}

    def test_breaches_sort_by_class_axis_and_severity(self):
        big = entry(segments=[["queued", 0.0, 0.95, None],
                              ["prefetch_wait", 1.0, 3.0, None]])
        rows = budgetlib.evaluate(snap([big]), TARGETS)
        assert [(b["axis"], b["segment"]) for b in rows] == [
            ("tpot", "prefetch_wait"), ("ttft", "queued")]


class TestPublishAndFormat:
    def test_publish_emits_records_and_bumps_counters(self):
        e = entry(segments=[["queued", 0.0, 0.9, None],
                            ["decode", 0.9, 3.0, None]])
        breaches = budgetlib.evaluate(snap([e]), TARGETS)
        emitted = []
        metricslib.configure(enabled=True)
        try:
            budgetlib.publish(breaches,
                              emit=lambda **kw: emitted.append(kw))
            m = metricslib.get_metrics().snapshot()
        finally:
            metricslib.configure(enabled=False)
        assert [r["kind"] for r in emitted] == ["slo_budget"]
        assert emitted[0]["segment"] == "queued"
        assert m["counters"]["budget.breach.queued"] == 1

    def test_publish_without_emit_or_metrics_is_a_noop(self):
        e = entry(segments=[["queued", 0.0, 0.9, None],
                            ["decode", 0.9, 3.0, None]])
        budgetlib.publish(budgetlib.evaluate(snap([e]), TARGETS))

    def test_format_names_the_breach_or_says_all_clear(self):
        assert "within allowance" in budgetlib.format_budget([])
        e = entry(segments=[["queued", 0.0, 0.9, None],
                            ["decode", 0.9, 3.0, None]])
        text = budgetlib.format_budget(
            budgetlib.evaluate(snap([e]), TARGETS))
        assert "SLO BUDGET BREACHES" in text
        assert "queued" in text and "900ms" in text and "500ms" in text
