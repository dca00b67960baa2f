"""jaxlint core: findings, suppressions, baseline, and the rule engine.

The analyzer's job is ahead-of-time hazard detection for the bug
classes this repo has actually paid for on hardware: the PR 2
"poisoned cache" was a zero-copy ``np.asarray`` host view of a buffer
a donated jit arg later mutated in place — statically detectable, and
only *diagnosable* after the fact by the flight recorder
(harness/trace.py). The reference suites are self-validating at RUN
time (every ``concurency/`` binary exits SUCCESS/FAILURE); jaxlint is
the same discipline moved to REVIEW time, the ahead-of-time hazard
checking the offloading-runtime literature leans on for device-memory
lifetime and ordering bugs (DiOMP-Offloading, Intel SHMEM — PAPERS.md).

Model:

- a :class:`Rule` inspects one parsed module (:class:`ModuleInfo`) and
  yields :class:`Finding`\\ s — ``file:line:col``, rule id, message,
  and a fix hint;
- ``# jaxlint: disable=<rule>[,<rule>]`` suppresses findings on its
  own line (trailing comment) or the next line (standalone comment).
  The rule name is MANDATORY and must be a registered rule: a bare or
  unknown ``disable`` is itself a finding (``bad-suppression``), so
  suppressions can't rot silently;
- a baseline file (``--baseline``) tolerates known findings by exact
  ``(path, rule, line)`` — the escape hatch for adopting the analyzer
  on a dirty tree. This repo's policy (ISSUE 4) is fix-or-suppress,
  so the shipped tree carries NO baseline;
- the driver walks ``*.py`` files, runs every registered rule, and
  partitions findings into live / suppressed / baselined.

Everything here is stdlib ``ast`` + ``tokenize``: the analyzer never
imports the code under analysis, so it runs in milliseconds and can't
be crashed (or biased) by import-time side effects.
"""

from __future__ import annotations

import ast
import io
import json
import re
import tokenize
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Iterable, Iterator

# Functions whose bodies are dispatch-critical (host-sync rule) when no
# @dispatch_critical marker is present: the serving engine's overlapped
# dispatch/admission path, and the eager collective completion helper.
# A host readback in any of these stalls the device queue the whole
# design exists to keep fed.
DEFAULT_DISPATCH_CRITICAL = frozenset({
    "_dispatch_chunk",
    "_dispatch_spec",
    "_admit",
    "_admit_row",
    "_try_admit",
    "_admit_pass",       # _try_admit's body, under its serve.admit_pass span
    "_ready_in_span",
    # the round-8 robustness entry points: preemption decision/eviction,
    # shedding, and the admission high-water check all run inside the
    # serving loop at chunk boundaries — a stray host sync there stalls
    # the very pipeline preemption exists to keep fed (the one
    # DELIBERATE sync, the eviction snapshot, carries a justified
    # suppression in models/serving.py)
    "_maybe_preempt",
    "_preempt",
    "_shed_expired",
    "_queue_order",
    "_admissible",
    "_can_resume",
    # the round-10 serving-plane hot paths: one scheduler round, the
    # router's migration export/transfer dispatch, and the KV-handoff
    # install all run with (or behind) an in-flight decode chunk — a
    # stray host sync there exposes exactly the handoff latency the
    # plane exists to hide. The DELIBERATE syncs (the export snapshot,
    # the completion measurement closing a migration window) carry
    # justified suppressions in models/serving.py and
    # serving_plane/router.py.
    "service_round",
    "_service_round",    # service_round's body, under its serve.round span
    "export_migration",
    "install_migration",
    "_dispatch_migration",
    "_install_pending",
    "_complete_migrations",
    # the round-11 tiered-memory paths: the residency manager's
    # prefetch/evict transfer pipeline and the serving engine's swap
    # machinery all run with (or ahead of) an in-flight decode chunk —
    # a stray host sync there serializes exactly the host<->HBM
    # latency the tier exists to hide. The DELIBERATE syncs (the
    # numpy-fallback host tier, the round-boundary window completions,
    # the swap-out cursor snapshot inside _detach_row) carry justified
    # suppressions in memory/residency.py and models/serving.py.
    "_dispatch_prefetch",
    "_install_prefetched",
    "_complete_prefetches",
    "_residency_balance",
    "_swap_out",
    "pull_payload",
    "push_payload",
    "_close_ripe_evicts",
    # the shared detach/attach primitives under export_migration /
    # install_migration / swap (round 11 refactor): the deliberate
    # chunk-boundary snapshot inside _detach_row carries the same
    # justified suppressions export_migration's body did before it
    # was hoisted
    "_detach_row",
    "_attach_row",
    # the round-12 prefix-sharing paths: the radix admission match,
    # the shared-page map/incref, the tail prefill, the decref release
    # funnel, and the cache reclaim all run inside the admission window
    # (with or behind an in-flight decode chunk) — they are HOST trie/
    # list work by design, and a stray device readback there (e.g.
    # reading cursors to "check" a match) stalls exactly the prefill
    # the cache exists to skip
    "_prefix_match",
    "_memo_match",
    "_request_need",
    "_insert_prefix",
    "_alloc_pages",
    "_incref_pages",
    "_decref_pages",
    "_reclaim_cache_pages",
    "_row_swappable",
    "_row_freeable_pages",
    # the round-13 quantized-decode paths: KV quantize/dequant and the
    # weight dequant accessor run INSIDE the traced step (pure jnp by
    # design), and the scale-pool write rides the same dispatch as the
    # page write — a host readback of a scale anywhere here (e.g.
    # float(scale.max()) to "sanity-check" a row before the write)
    # syncs the decode chunk on exactly the bytes quantization exists
    # to shrink
    "_quantize_rows",
    "_dequant",
    "_scale_write",
    "matmul_weight",
    # the round-14 elastic-plane paths: the scaling decision, the warm
    # spin-up, the drain's export loop, and death recovery all run at
    # the plane's round boundary with survivor chunks about to
    # dispatch — a stray host sync there stalls every replica's next
    # round behind one controller tick. The DELIBERATE syncs (the
    # spin-up's completion measurement, the checkpoint's round-
    # boundary key snapshot, the resume's host-list packing) carry
    # justified suppressions in serving_plane/autoscaler.py and
    # serving_plane/service.py.
    "_autoscale_round",
    "_spin_up",
    "_begin_drain",
    "_drain_step",
    "_kill_replica",
    "_recover_casualties",
    "_resume_request",
    "_route_again",
    "_checkpoint_replica",
    "_probe_replica_chaos",
    "_shed_request",
    # the round-16 autofit-apply paths: from_fitted constructors swap
    # in the fitted ladder/weights/thresholds right before serving
    # starts, and the per-round attainment gauge (_judge_window /
    # _emit_attainment) runs inside the router's service round with
    # replica chunks in flight — both must stay pure host dict/list
    # work; a device readback there would stall the very first chunks
    # the fitted config exists to speed up
    "from_fitted",
    "ladder_from",
    "_judge_window",
    "_emit_attainment",
    # the round-17 device-side migration paths: the fused DMA pair's
    # send dispatch and the recv-side landing check both run inside
    # the router's handoff window, behind the destination's in-flight
    # decode chunk — a host readback there (e.g. np.asarray of a page
    # slab to "verify" the copy) drags the payload back through the
    # host and forfeits exactly the device-to-device hop the tier
    # exists to buy. The transport resolution (_resolve_transport)
    # rides the same dispatch. (service.py's same-named socket
    # functions are pure host wire work and stay clean by
    # construction.)
    "send_migration",
    "recv_migration",
    "_resolve_transport",
    # the round-18 request-trace stamp paths: every lifecycle stamp
    # (harness/reqtrace.py) fires inside an engine or router
    # transition the batcher already owns — admission, preemption,
    # swap-out, migration export/install — with decode chunks in
    # flight. A stamp is a perf_counter read plus host list work by
    # contract; a device readback smuggled into one (np.asarray of
    # engine.pos to "enrich" a segment) turns the observability layer
    # itself into the tail it exists to explain.
    "begin_request",
    "stamp_transition",
    "finish_request",
    "export_history",
    "install_history",
    "restamp_submit",
})

# rule names are kebab-case identifiers; anything after the last name
# (the mandatory one-line justification, set off by any other char) is
# ignored by the parser but required by review convention
_SUPPRESS_RE = re.compile(
    r"#\s*jaxlint:\s*disable"
    r"(?:\s*=\s*(?P<rules>[A-Za-z0-9_-]+(?:\s*,\s*[A-Za-z0-9_-]+)*))?"
)


@dataclass(frozen=True)
class Finding:
    """One hazard: ``rule`` id, location, message, and a fix hint."""

    rule: str
    path: str
    line: int
    col: int
    message: str
    hint: str = ""

    @property
    def key(self) -> tuple[str, str, int]:
        """Baseline identity: exact (path, rule, line)."""
        return (self.path, self.rule, self.line)

    def format(self) -> str:
        loc = f"{self.path}:{self.line}:{self.col}"
        text = f"{loc}: {self.rule}: {self.message}"
        if self.hint:
            text += f"\n    hint: {self.hint}"
        return text


@dataclass
class ModuleInfo:
    """One parsed module plus the lookups every rule wants."""

    path: str
    source: str
    tree: ast.Module
    #: first-segment import aliases, e.g. {"np": "numpy",
    #: "jnp": "jax.numpy", "partial": "functools.partial"}
    aliases: dict[str, str] = field(default_factory=dict)
    #: child -> parent for every node (recompile rule needs it)
    parents: dict[ast.AST, ast.AST] = field(default_factory=dict)

    @classmethod
    def parse(cls, path: str | Path, source: str | None = None
              ) -> "ModuleInfo":
        path = str(path)
        if source is None:
            source = Path(path).read_text()
        tree = ast.parse(source, filename=path)
        info = cls(path=path, source=source, tree=tree)
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                info.parents[child] = node
            if isinstance(node, ast.Import):
                for a in node.names:
                    info.aliases[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    info.aliases[a.asname or a.name] = (
                        f"{node.module}.{a.name}")
        return info

    def resolve(self, node: ast.AST) -> str | None:
        """Canonical dotted name of a Name/Attribute chain, with the
        first segment resolved through the module's import aliases —
        ``np.asarray`` -> ``numpy.asarray``, ``jnp.asarray`` ->
        ``jax.numpy.asarray`` — so rules match semantics, not spelling.
        None for anything that isn't a plain dotted chain."""
        parts: list[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        parts.append(node.id)
        parts.reverse()
        parts[0] = self.aliases.get(parts[0], parts[0])
        return ".".join(parts)


class Rule:
    """One hazard class. Subclasses set ``name``/``hint`` and implement
    :meth:`check` over a parsed module."""

    name: str = "?"
    #: one-line description for --list-rules and the docs catalog
    summary: str = ""
    #: rule family for --list-rules grouping: jaxlint (Python-level),
    #: shardlint (SPMD), pallaslint (in-kernel), contractlint
    #: (cross-module producer/consumer contracts)
    family: str = "jaxlint"
    hint: str = ""

    def check(self, mod: ModuleInfo, config: "AnalysisConfig"
              ) -> Iterable[Finding]:
        raise NotImplementedError

    def finding(self, mod: ModuleInfo, node: ast.AST, message: str,
                hint: str | None = None) -> Finding:
        return Finding(
            rule=self.name, path=mod.path,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            message=message,
            hint=self.hint if hint is None else hint,
        )


@dataclass
class AnalysisConfig:
    """Tunables threaded to every rule."""

    dispatch_critical: frozenset[str] = DEFAULT_DISPATCH_CRITICAL
    #: rule names to run; None = all registered
    select: frozenset[str] | None = None


_REGISTRY: dict[str, Rule] = {}


def register(rule_cls: type[Rule]) -> type[Rule]:
    """Class decorator adding a rule to the global registry."""
    rule = rule_cls()
    if rule.name in _REGISTRY:
        raise ValueError(f"duplicate rule name {rule.name!r}")
    _REGISTRY[rule.name] = rule
    return rule_cls


def registered_rules() -> dict[str, Rule]:
    # rules.py / pallas_rules.py / contract_rules.py self-register on
    # import; import lazily so core stays importable without the rule
    # set (the runtime helper's case)
    from hpc_patterns_tpu.analysis import contract_rules  # noqa: F401
    from hpc_patterns_tpu.analysis import pallas_rules  # noqa: F401
    from hpc_patterns_tpu.analysis import rules  # noqa: F401

    return dict(_REGISTRY)


# -- suppressions ----------------------------------------------------------


def parse_suppressions(
    mod: ModuleInfo, known_rules: frozenset[str]
) -> tuple[dict[int, set[str]], list[Finding]]:
    """``# jaxlint: disable=<rule>``: {line: {rules}} plus the
    bad-suppression findings for bare/unknown forms. A trailing comment
    covers its own line; a standalone comment covers the next CODE line
    (justifications may continue over following comment lines)."""
    by_line: dict[int, set[str]] = {}
    bad: list[Finding] = []
    try:
        tokens = tokenize.generate_tokens(
            io.StringIO(mod.source).readline)
        comments = [t for t in tokens if t.type == tokenize.COMMENT]
    except tokenize.TokenError:  # pragma: no cover - ast parsed it
        return by_line, bad
    lines = mod.source.splitlines()
    for tok in comments:
        m = _SUPPRESS_RE.search(tok.string)
        if not m:
            continue
        line = tok.start[0]
        names = [r.strip() for r in (m.group("rules") or "").split(",")
                 if r.strip()]
        standalone = lines[line - 1][: tok.start[1]].strip() == ""
        target = line
        if standalone:
            target = line + 1
            while target <= len(lines) and (
                    not lines[target - 1].strip()
                    or lines[target - 1].lstrip().startswith("#")):
                target += 1
        if not names:
            bad.append(Finding(
                rule="bad-suppression", path=mod.path, line=line,
                col=tok.start[1],
                message="jaxlint: disable without a rule name",
                hint="name the rule: # jaxlint: disable=<rule> — blanket "
                     "suppressions hide new hazard classes",
            ))
            continue
        unknown = [n for n in names if n not in known_rules]
        for n in unknown:
            bad.append(Finding(
                rule="bad-suppression", path=mod.path, line=line,
                col=tok.start[1],
                message=f"jaxlint: disable of unknown rule {n!r}",
                hint="registered rules: "
                     + ", ".join(sorted(known_rules)),
            ))
        by_line.setdefault(target, set()).update(
            n for n in names if n in known_rules)
    return by_line, bad


# -- baseline --------------------------------------------------------------


def load_baseline(path: str | Path) -> set[tuple[str, str, int]]:
    """Known-finding keys from a baseline JSON (see
    :func:`write_baseline`)."""
    data = json.loads(Path(path).read_text())
    return {
        (f["path"], f["rule"], int(f["line"]))
        for f in data.get("findings", [])
    }


def write_baseline(path: str | Path, findings: list[Finding]) -> None:
    data = {
        "comment": "jaxlint baseline — tolerated findings by exact "
                   "(path, rule, line); regenerate with "
                   "--write-baseline. Repo policy is fix-or-suppress: "
                   "this file should stay empty or absent.",
        "findings": [
            {"path": f.path, "rule": f.rule, "line": f.line,
             "message": f.message}
            for f in findings
        ],
    }
    Path(path).write_text(json.dumps(data, indent=2) + "\n")


# -- driver ----------------------------------------------------------------


@dataclass
class Report:
    """One analysis run: live findings plus everything accounted away."""

    findings: list[Finding] = field(default_factory=list)
    suppressed: list[Finding] = field(default_factory=list)
    baselined: list[Finding] = field(default_factory=list)
    n_files: int = 0

    @property
    def ok(self) -> bool:
        return not self.findings

    def by_rule(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for f in self.findings:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        return counts


def iter_python_files(paths: Iterable[str | Path]) -> Iterator[Path]:
    """``*.py`` under each path (a file is taken as-is), skipping
    ``__pycache__``/hidden dirs, in sorted order for stable output."""
    for p in paths:
        p = Path(p)
        if p.is_file():
            yield p
            continue
        for f in sorted(p.rglob("*.py")):
            if any(part.startswith((".", "__pycache__"))
                   for part in f.parts[len(p.parts):-1]):
                continue
            yield f


def analyze_file(
    path: str | Path,
    config: AnalysisConfig | None = None,
    rules: dict[str, Rule] | None = None,
) -> tuple[list[Finding], list[Finding]]:
    """(live, suppressed) findings for one file. Syntax errors become a
    single ``parse-error`` finding: an unparseable file is a file the
    analyzer is blind to, which CI must not read as clean."""
    config = config or AnalysisConfig()
    rules = rules if rules is not None else registered_rules()
    # suppression validity is judged against the FULL registry: running
    # a rule subset (--select) must not turn a valid suppression of an
    # unselected rule into a bad-suppression finding
    known = frozenset(rules) | {"parse-error"}
    if config.select is not None:
        rules = {k: v for k, v in rules.items() if k in config.select}
    try:
        mod = ModuleInfo.parse(path)
    except SyntaxError as e:
        return [Finding(
            rule="parse-error", path=str(path), line=e.lineno or 1,
            col=e.offset or 0, message=f"unparseable: {e.msg}",
            hint="jaxlint cannot vouch for a file it cannot parse",
        )], []
    suppress_map, bad = parse_suppressions(mod, known)
    raw: list[Finding] = list(bad)
    if config.select is not None:
        # hygiene findings respect the selection too (parse-error
        # always survives: a blind file is never a clean file)
        raw = [f for f in raw if f.rule in config.select]
    for rule in rules.values():
        raw.extend(rule.check(mod, config))
    live: list[Finding] = []
    suppressed: list[Finding] = []
    seen: set[tuple[str, int, int]] = set()
    for f in sorted(raw, key=lambda f: (f.line, f.col, f.rule)):
        # rules walking nested defs can visit a statement from both
        # the outer and the inner function — one hazard, one finding
        if (f.rule, f.line, f.col) in seen:
            continue
        seen.add((f.rule, f.line, f.col))
        # bad-suppression is never itself suppressible — the escape
        # hatch must not have an escape hatch
        if (f.rule != "bad-suppression"
                and f.rule in suppress_map.get(f.line, ())):
            suppressed.append(f)
        else:
            live.append(f)
    return live, suppressed


def run_paths(
    paths: Iterable[str | Path],
    config: AnalysisConfig | None = None,
    baseline: set[tuple[str, str, int]] | None = None,
) -> Report:
    """Analyze every file under ``paths``; the CLI's engine."""
    report = Report()
    rules = registered_rules()
    for f in iter_python_files(paths):
        live, suppressed = analyze_file(f, config, rules)
        report.n_files += 1
        report.suppressed.extend(suppressed)
        for finding in live:
            if baseline and finding.key in baseline:
                report.baselined.append(finding)
            else:
                report.findings.append(finding)
    return report
