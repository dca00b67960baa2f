"""Bench regression gate over ``BENCH_r*.json`` round files.

A round file is one capture (``bench.py``'s one-line JSON verdict
wrapped in the round schema — ``{"n", "cmd", "rc", "tail",
"parsed"}``); ``bench.py --gate`` writes the next one. This module is
the machine check that reads the trajectory back: parse the trajectory, compare the NEWEST comparable
round's headline numbers against the BEST prior round, and exit
nonzero with a readable table when any gated metric degrades beyond
tolerance.

What is compared (when present in a round's ``parsed`` payload):

- ``value`` / ``vs_baseline`` — the capture's headline (the on-chip
  overlap speedup today; any future ``bench.py`` headline rides the
  same keys);
- serving numbers under ``detail`` (``serving_tok_s`` higher-better,
  ``serving_bubble_frac`` / ``serving_prefill_compiles`` lower-better)
  and ``allreduce_busbw_gbps`` — the production-serving headline set;
- ``detail.dma_gbps`` is reported but NOT gated: it is the capture's
  own DMA-rate telemetry, which moves with the machine the capture
  ran on rather than with the code — a slow capture must down-weight
  the ratio's interpretation, not fail the gate.

Rounds that measured nothing are excluded, not failed: ``parsed`` null
(a capture that died with a traceback) or ``detail.degenerate`` true
(a capture whose backend never came up) mean the ENVIRONMENT broke,
and a gate that fails on a dead backend would train everyone to
ignore it. They
are listed as skipped; the newest round that actually measured is what
gates.

Coverage loss warns (stderr + table): when the newest round LACKS a
gated key that a prior comparable same-headline round carried (e.g.
``detail.serving_tok_s`` silently dropping out of a capture), that is
a lost measurement, not a pass — value-only gating would never notice.
The gate still exits 0 (the round may legitimately skip a subsystem),
but the warning makes the day a key disappears visible;
``--strict-coverage`` promotes it to a gate failure for CI legs where
every subsystem is expected to capture.

Usage::

    python -m hpc_patterns_tpu.harness.regress BENCH_r0*.json
    python bench.py --gate        # capture a new round, then gate it

Exit 0: no regression (or nothing to compare). 1: regression, table on
stdout names the metric. 2: unreadable input.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path
from typing import Any

DEFAULT_TOLERANCE = 0.10  # 10% relative


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    """One headline metric: where it lives in ``parsed`` (dot path),
    which direction is good, whether it gates (vs. informational), and
    an absolute slack added to the relative tolerance band (so
    near-zero lower-better values like bubble fractions don't turn a
    0.001 → 0.002 wobble into a 2x 'regression')."""
    path: str
    direction: str  # "higher" | "lower"
    gated: bool = True
    abs_slack: float = 0.0
    label: str | None = None

    @property
    def name(self) -> str:
        return self.label or self.path


SPECS: tuple[MetricSpec, ...] = (
    MetricSpec("value", "higher", label="headline value"),
    MetricSpec("vs_baseline", "higher"),
    MetricSpec("detail.dma_gbps", "higher", gated=False,
               label="dma_gbps (session health)"),
    MetricSpec("detail.serving_tok_s", "higher"),
    MetricSpec("detail.serving_bubble_frac", "lower", abs_slack=0.05),
    MetricSpec("detail.serving_prefill_compiles", "lower", abs_slack=1),
    MetricSpec("detail.allreduce_busbw_gbps", "higher"),
    # the robustness row (bench_serving --scenario): goodput is the
    # SLO-attained tok/s of the chaos scenario — a scheduling change
    # that keeps raw tok/s but blows the latency targets regresses
    # HERE; degraded-mode bubble gets the same near-zero slack as the
    # clean bubble fraction
    MetricSpec("detail.serving_goodput_tok_s", "higher"),
    MetricSpec("detail.serving_degraded_bubble_frac", "lower",
               abs_slack=0.05),
    # the device-initiated fused-collective row (comm/fused.py, PR 8):
    # fused ring allreduce bus bandwidth, and the fraction of the
    # host-driven gather-then-matmul time the fused allgather_matmul
    # hides under in-flight remote DMAs. The overlap fraction is
    # legitimately ~0 on the CPU smoke (the dma-discharge interpreter
    # serializes), so it gets the same near-zero absolute slack as the
    # bubble fractions.
    MetricSpec("detail.fused_allreduce_gbps", "higher"),
    MetricSpec("detail.allreduce_overlap_frac", "higher",
               abs_slack=0.05),
    # the serving-plane row (bench_serving --plane, round 10): plane
    # goodput is the SLO-attained tok/s of the 2-replica router run,
    # and the migration-overlap fraction is the measured share of each
    # KV-handoff window hidden under the destination's in-flight
    # decode chunk (serving_plane/router.py) — the disaggregation
    # claim in one number. Overlap varies with the stream's cold
    # starts, so it carries a wider absolute slack than the bubbles.
    MetricSpec("detail.plane_goodput_tok_s", "higher"),
    MetricSpec("detail.kv_migration_overlap_frac", "higher",
               abs_slack=0.10),
    # the device-side migration tier (round 17): the overlap fraction
    # measured ONLY over bundles that rode the fused paired remote-DMA
    # kernel (ServingPlane(migration="dma") — the router's DMA ledger
    # is None when nothing did, so a silent fallback to device_put
    # reads as coverage loss here, never as a passing number measured
    # on the wrong transport). Same cold-start wobble as the other
    # overlap fractions, same wider absolute slack. Bytes-per-round is
    # the dataplane pressure the tier carries — transport-invariant
    # workload geometry, so its band is tight: a bundle that silently
    # grows (a scale pool duplicated, a payload staged twice) regresses
    # here even when the wall clock forgives it.
    MetricSpec("detail.dma_migration_overlap_frac", "higher",
               abs_slack=0.10),
    # the Σ-bytes numerator is exact; the per-round denominator wobbles
    # with scheduler timing (a fast box drains the stream in fewer
    # rounds and the ratio RISES) — the absolute slack covers roughly
    # one round's worth of smoke-shape payload on top of the relative
    # band so only a real payload-size change (not a round-count
    # wobble) trips the gate
    MetricSpec("detail.migration_bytes_per_round", "lower",
               abs_slack=2048),
    # the tiered-memory row (bench_serving --offload, round 11):
    # constrained-HBM goodput is the SLO-attained tok/s of an engine
    # serving a working set ~2x its HBM pool through the residency
    # manager (token-identical to all-HBM — a capacity claim, not an
    # approximation), and the prefetch-overlap fraction is the
    # measured share of each host->HBM pull hidden under the decode
    # chunk. Overlap varies with rotation timing like the plane's
    # migration overlap, so it carries the same wider absolute slack.
    MetricSpec("detail.offload_goodput_tok_s", "higher"),
    MetricSpec("detail.prefetch_overlap_frac", "higher",
               abs_slack=0.10),
    # the prefix-sharing row (bench_serving --shared, round 12):
    # shared goodput is the SLO-attained tok/s of the sharing-aware
    # arena on the template/conversation-tree mix (token-identical to
    # private pages — a capacity/TTFT claim, not an approximation),
    # and the prefill-skip fraction is the measured share of prompt
    # tokens the radix match kept out of the prefill. The skip
    # fraction is a property of the MIX more than the engine, so it
    # carries the same wider absolute slack as the overlap fractions.
    MetricSpec("detail.shared_goodput_tok_s", "higher"),
    MetricSpec("detail.prefill_skip_frac", "higher",
               abs_slack=0.10),
    # the quantized-decode row (bench_serving --quant, round 13):
    # quantized goodput is the SLO-attained tok/s of an int8-KV engine
    # (both precision oracles — exact-within-precision and the
    # teacher-forced TV/greedy law — pass before the number exists),
    # and the pool-bytes fraction is the measured quantized-pool bytes
    # over a bf16 pool at equal residents. The fraction is pure
    # dtype geometry (~0.53), so its band is tight: a scale-pool
    # layout change that silently doubles the overhead regresses here.
    MetricSpec("detail.quant_goodput_tok_s", "higher"),
    MetricSpec("detail.kv_pool_bytes_frac", "lower", abs_slack=0.02),
    MetricSpec("detail.quant_bubble_frac", "lower", abs_slack=0.05),
    # the elastic-plane row (bench_serving --elastic, round 14):
    # attainment is the autoscaled plane's per-class SLO fraction on
    # the diurnal-ramp-under-replica-death scenario (the bench itself
    # asserts it strictly exceeds the fixed plane's before the number
    # exists — here the gate holds the trajectory: an autoscaler
    # change that starts shedding regresses attainment), and
    # goodput-per-replica-round is SLO-attained tokens per live
    # replica-round — the EFFICIENCY direction, so over-provisioning
    # into a green attainment still regresses. Attainment is a
    # fraction near 1.0; the small absolute slack absorbs a single
    # judgment flipping on a loaded CI box.
    MetricSpec("detail.elastic_slo_attainment", "higher",
               abs_slack=0.05),
    MetricSpec("detail.goodput_per_replica_round", "higher"),
    # the autofit row (bench_serving --fit, round 16): fitted goodput
    # is the tok/s of an engine configured by harness/autofit.py from
    # the recording leg's own RunLog (the fitted ladder's expected
    # padding is asserted strictly below the default's before the
    # number exists), and the gain fraction is fitted/default - 1 on
    # the same stream and pool geometry. The gain is a small ratio of
    # two wall clocks on a shared CI box, so it carries an absolute
    # slack wide enough that scheduler noise cannot fail the gate —
    # the fitter going WRONG shows up as the row's own strict-padding
    # assertion (coverage loss here), not as a small gain wobble.
    MetricSpec("detail.fitted_goodput_tok_s", "higher"),
    MetricSpec("detail.autofit_gain_frac", "higher", abs_slack=0.05),
    # the request-forensics row (bench_serving --scenario under
    # harness/reqtrace.py, round 18): coverage is the fraction of
    # finished-request wall time the lifecycle-segment tilings account
    # for — the row asserts >= 0.95 in-run, so the gate holds the
    # TRAJECTORY with a tight band (a new engine transition that
    # forgets its stamp site leaks `untracked` time and regresses here
    # before anyone reads a wrong attribution table). The p99 queue
    # share is WHERE the tail went, not how big it is — load-shape
    # dependent and legitimately mobile, so informational: the gate
    # prints the drift, the attribution table explains it.
    MetricSpec("detail.attribution_coverage_frac", "higher",
               abs_slack=0.02),
    MetricSpec("detail.ttft_p99_queue_share", "lower", gated=False,
               abs_slack=0.10,
               label="ttft_p99_queue_share (tail attribution)"),
    # the segment-budget row (bench_serving --slo-budget, round 20):
    # the stall share is what fraction of the pooled p99 inter-token
    # gap band the seeded slow_host_transfer run spends in decode-
    # stall segments — seeded physics, but the share rides scheduler
    # timing on a shared CI box, so the band is wide; it GROWING past
    # the slack means decode stalls got structurally worse (or a new
    # stall mechanism joined the band). The breach-segment count is
    # structural: the row asserts the set is exactly {prefetch_wait}
    # in-run, so any count above 1 means attribution smeared out of
    # the injected mechanism — zero slack.
    MetricSpec("detail.tpot_p99_stall_share", "lower",
               abs_slack=0.15,
               label="tpot_p99_stall_share (inter-token tail)"),
    MetricSpec("detail.budget_breach_segments", "lower",
               abs_slack=0.0),
)


def _dig(obj: Any, path: str):
    for part in path.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return obj


def load_round(path: str | Path) -> dict[str, Any]:
    with open(path) as f:
        rec = json.load(f)
    rec["_path"] = str(path)
    return rec


def comparable(rec: dict[str, Any]) -> bool:
    """A round that actually measured something: parsed verdict present
    and not self-declared degenerate (the backend never came up)."""
    parsed = rec.get("parsed")
    if not isinstance(parsed, dict):
        return False
    detail = parsed.get("detail")
    if isinstance(detail, dict) and detail.get("degenerate"):
        return False
    return True


def extract_metrics(rec: dict[str, Any]) -> dict[str, tuple[MetricSpec, float]]:
    """{metric name: (spec, value)} for every spec present in the
    round. Keyed by the capture's metric name too, so trajectories that
    change headline metric (onchip overlap -> something else) never
    compare apples to oranges."""
    parsed = rec["parsed"]
    prefix = parsed.get("metric", "?")
    out: dict[str, tuple[MetricSpec, float]] = {}
    for spec in SPECS:
        v = _dig(parsed, spec.path)
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        out[f"{prefix}:{spec.name}"] = (spec, float(v))
    return out


@dataclasses.dataclass
class Row:
    name: str
    best_prior: float
    best_round: int
    newest: float
    delta_frac: float  # signed: + means improved in the good direction
    gated: bool
    failed: bool


def compare(rounds: list[dict[str, Any]],
            tolerance: float = DEFAULT_TOLERANCE) -> dict[str, Any]:
    """Newest comparable round vs the best prior comparable round,
    metric by metric. Returns {rows, newest, skipped, n_prior}; rows is
    empty when fewer than two rounds measured anything."""
    rounds = sorted(rounds, key=lambda r: r.get("n", 0))
    usable = [r for r in rounds if comparable(r)]
    skipped = [r for r in rounds if not comparable(r)]
    if len(usable) < 2:
        return {"rows": [], "newest": usable[-1] if usable else None,
                "skipped": skipped, "n_prior": max(0, len(usable) - 1),
                "coverage_loss": []}
    newest, prior = usable[-1], usable[:-1]
    # same-backend rounds only: a CPU-fallback capture gated against
    # the TPU trajectory would always "regress" — that is a backend
    # mismatch, not a perf change, so those priors are set aside (and
    # an all-mismatched history gates nothing rather than lying)
    backend = _dig(newest["parsed"], "detail.backend")
    if backend is not None:
        mismatched = [r for r in prior
                      if _dig(r["parsed"], "detail.backend")
                      not in (None, backend)]
        if mismatched:
            skipped = skipped + mismatched
            prior = [r for r in prior if r not in mismatched]
    if not prior:
        return {"rows": [], "newest": newest, "skipped": skipped,
                "n_prior": 0, "coverage_loss": []}
    new_metrics = extract_metrics(newest)
    # coverage-loss check: a gated key that prior comparable rounds
    # carried but the newest lacks is NOT a pass — the capture lost a
    # measurement (detail.serving_tok_s silently dropping out reads as
    # green under value-only gating). Same-prefix priors only: a round
    # that changed its headline metric is a different trajectory, not
    # lost coverage. Warn, don't fail: the round may legitimately not
    # exercise that subsystem, and the human owns that call.
    lost: dict[str, int] = {}  # lost key -> last round that carried it
    new_prefix = newest["parsed"].get("metric", "?")
    for r in prior:
        if r["parsed"].get("metric", "?") != new_prefix:
            continue
        for name, (spec, _v) in extract_metrics(r).items():
            if spec.gated and name not in new_metrics:
                lost[name] = max(lost.get(name, 0), r.get("n", 0))
    coverage_loss = sorted(lost.items())
    rows: list[Row] = []
    for name, (spec, new_v) in sorted(new_metrics.items()):
        prior_vals = []
        for r in prior:
            got = extract_metrics(r).get(name)
            if got is not None:
                prior_vals.append((got[1], r.get("n", 0)))
        if not prior_vals:
            continue
        if spec.direction == "higher":
            best, best_n = max(prior_vals)
            floor = best * (1.0 - tolerance) - spec.abs_slack
            failed = spec.gated and new_v < floor
            delta = (new_v - best) / abs(best) if best else 0.0
        else:
            best, best_n = min(prior_vals)
            ceil = best * (1.0 + tolerance) + spec.abs_slack
            failed = spec.gated and new_v > ceil
            delta = (best - new_v) / abs(best) if best else 0.0
        rows.append(Row(name, best, best_n, new_v, delta, spec.gated,
                        failed))
    return {"rows": rows, "newest": newest, "skipped": skipped,
            "n_prior": len(prior), "coverage_loss": coverage_loss}


def format_table(result: dict[str, Any], tolerance: float) -> str:
    lines = []
    newest = result["newest"]
    if result["skipped"]:
        names = ", ".join(
            f"r{r.get('n', '?')}" for r in result["skipped"])
        lines.append("skipped (degenerate/unparsed/backend-mismatched "
                     f"capture): {names}")
    if newest is None:
        lines.append("no comparable rounds — nothing to gate")
        return "\n".join(lines)
    if not result["rows"]:
        lines.append(
            f"newest comparable round r{newest.get('n', '?')} "
            f"({newest['_path']}) has no prior round to compare "
            "against — nothing to gate")
        return "\n".join(lines)
    lines.append(
        f"newest comparable round r{newest.get('n', '?')} "
        f"({newest['_path']}) vs best of {result['n_prior']} prior "
        f"round(s), tolerance {tolerance:.0%}:")
    lines.append("")
    lines.append(f"{'metric':<44} {'best prior':>12} {'newest':>12} "
                 f"{'delta':>8}  status")
    for row in result["rows"]:
        status = ("REGRESSION" if row.failed
                  else "ok" if row.gated else "info")
        lines.append(
            f"{row.name:<44} {row.best_prior:>12.4g} "
            f"(r{row.best_round}) {row.newest:>12.4g} "
            f"{row.delta_frac:>+7.1%}  {status}")
    for name, last_n in result.get("coverage_loss", []):
        lines.append("")
        lines.append(
            f"WARNING: coverage loss — gated key {name!r} (last "
            f"carried by r{last_n}) is absent from "
            f"r{newest.get('n', '?')}: the capture lost a "
            "measurement, not passed it")
    n_fail = sum(r.failed for r in result["rows"])
    lines.append("")
    lines.append("GATE: " + (f"FAIL ({n_fail} regression(s))" if n_fail
                             else "PASS"))
    return "\n".join(lines)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("rounds", nargs="+",
                   help="bench round files, e.g. BENCH_r0*.json")
    p.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                   help="relative degradation allowed before the gate "
                        f"fails (default {DEFAULT_TOLERANCE:.0%} — wide "
                        "enough for session-to-session chip noise, "
                        "narrow enough to catch a real fast-path "
                        "regression)")
    p.add_argument("--strict-coverage", action="store_true",
                   help="fail (exit 1) on coverage loss instead of "
                        "warning: a gated key that prior rounds "
                        "carried but the newest lacks becomes a gate "
                        "failure — for CI legs where every subsystem "
                        "is expected to capture")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if not 0 <= args.tolerance < 1:
        print(f"ERROR: --tolerance must be in [0, 1), got "
              f"{args.tolerance}", file=sys.stderr)
        return 2
    try:
        rounds = [load_round(p) for p in args.rounds]
    except (OSError, json.JSONDecodeError) as e:
        print(f"ERROR: {e}", file=sys.stderr)
        return 2
    result = compare(rounds, tolerance=args.tolerance)
    print(format_table(result, args.tolerance))
    coverage_loss = result.get("coverage_loss", [])
    for name, last_n in coverage_loss:
        # stderr too: CI logs that only keep stderr still surface it
        severity = "ERROR" if args.strict_coverage else "WARNING"
        print(f"{severity}: coverage loss — gated key {name!r} absent "
              f"from the newest round (last carried by r{last_n})",
              file=sys.stderr)
    if any(r.failed for r in result["rows"]):
        return 1
    if args.strict_coverage and coverage_loss:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
