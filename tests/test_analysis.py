"""jaxlint (hpc_patterns_tpu.analysis): golden fixture findings,
suppression semantics, the CI gate over the live package, and the
runtime donation-poison helper.

The fixture corpus under ``tests/fixtures/analysis/`` is the rule
catalog's executable form: one known-bad and one known-clean file per
rule, with expected findings marked line-exact by ``EXPECT: <rule>``
trailing comments — the golden comparison reads the markers, so a
fixture edit can't silently desynchronize from its expectations.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.analysis import __main__ as cli
from hpc_patterns_tpu.analysis import core, runtime
from hpc_patterns_tpu.analysis.core import AnalysisConfig, ModuleInfo
from hpc_patterns_tpu.analysis.rules import _donor_table

FIXTURES = Path(__file__).parent / "fixtures" / "analysis"
PACKAGE = Path(__file__).resolve().parent.parent / "hpc_patterns_tpu"

_EXPECT_RE = re.compile(r"EXPECT:\s*([a-z\-]+(?:\s*,\s*[a-z\-]+)*)")


def _expected_findings() -> dict[tuple[str, int], set[str]]:
    """{(fixture name, line): {rules}} parsed from EXPECT markers."""
    expected: dict[tuple[str, int], set[str]] = {}
    for f in sorted(FIXTURES.glob("*.py")):
        for lineno, line in enumerate(f.read_text().splitlines(), 1):
            m = _EXPECT_RE.search(line)
            if m:
                expected[(f.name, lineno)] = {
                    r.strip() for r in m.group(1).split(",")}
    return expected


def _actual_findings() -> dict[tuple[str, int], set[str]]:
    report = core.run_paths([FIXTURES])
    actual: dict[tuple[str, int], set[str]] = {}
    for f in report.findings:
        actual.setdefault((Path(f.path).name, f.line), set()).add(f.rule)
    return actual


class TestGoldenFixtures:
    def test_findings_match_expect_markers_exactly(self):
        expected, actual = _expected_findings(), _actual_findings()
        assert expected, "fixture corpus lost its EXPECT markers"
        missing = {k: v for k, v in expected.items() if k not in actual}
        extra = {k: v for k, v in actual.items() if k not in expected}
        assert not missing and not extra, (
            f"missing={missing} extra={extra}")
        for key in expected:
            assert actual[key] == expected[key], (
                f"{key}: expected {expected[key]}, got {actual[key]}")

    def test_every_rule_demonstrated_by_a_caught_fixture(self):
        # the acceptance criterion: every hazard rule fires on the
        # corpus — the minimized PR 2 donation-alias replica AND the
        # minimized rank-branched-collective deadlock replica included
        caught = {r for rules in _actual_findings().values()
                  for r in rules}
        assert {"donation-alias", "host-sync-in-dispatch",
                "recompile-hazard", "prng-key-reuse",
                "tracer-leak", "collective-divergence",
                "collective-order", "unchecked-permutation",
                "spec-mismatch",
                # the pallaslint family (PR 13): every PR 8 chip-only
                # bug shape has a caught minimized replica
                "dma-sem-balance", "dma-slot-reuse",
                "collective-id-collision", "kernel-dtype-cast",
                "vmem-budget",
                # the contractlint family (PR 19): every stringly
                # producer/consumer seam has a caught drift replica
                "gate-key-orphan", "record-kind-drift",
                "wire-field-compat", "track-band-collision",
                "chaos-site-drift"} <= caught

    def test_rank_branched_deadlock_replica_is_caught_at_the_branch(self):
        live, _ = core.analyze_file(
            FIXTURES / "bad_collective_divergence.py")
        div = [f for f in live if f.rule == "collective-divergence"]
        assert len(div) == 3  # branch, early return, rank-sized loop
        src = (FIXTURES / "bad_collective_divergence.py").read_text()
        flagged = src.splitlines()[div[0].line - 1]
        assert "process_index" in flagged  # anchored at the branch

    def test_pr2_reproducer_is_caught_at_the_view_line(self):
        live, _ = core.analyze_file(
            FIXTURES / "bad_donation_alias.py")
        donation = [f for f in live if f.rule == "donation-alias"]
        assert donation, "the PR 2 reproducer must be flagged"
        src = (FIXTURES / "bad_donation_alias.py").read_text()
        flagged_line = src.splitlines()[donation[0].line - 1]
        assert "np.asarray(self.pos)" in flagged_line

    def test_clean_fixtures_stay_clean(self):
        for f in sorted(FIXTURES.glob("clean_*.py")):
            live, suppressed = core.analyze_file(f)
            assert not live, f"{f.name}: {[x.format() for x in live]}"
            assert not suppressed

    def test_findings_carry_location_and_hint(self):
        live, _ = core.analyze_file(FIXTURES / "bad_recompile.py")
        f = live[0]
        assert f.line > 0 and f.path.endswith("bad_recompile.py")
        assert f.hint  # every shipped rule must suggest the fix
        assert f"{f.path}:{f.line}" in f.format()


class TestSuppression:
    def test_named_suppressions_silence_and_are_counted(self):
        live, suppressed = core.analyze_file(FIXTURES / "suppressed.py")
        assert {f.rule for f in suppressed} == {
            "recompile-hazard", "host-sync-in-dispatch"}
        assert len(suppressed) == 2

    def test_bare_and_unknown_disable_are_findings(self):
        live, _ = core.analyze_file(FIXTURES / "suppressed.py")
        bad = [f for f in live if f.rule == "bad-suppression"]
        assert len(bad) == 2  # one bare, one unknown-rule
        # and the hazards under them stay LIVE
        assert sum(1 for f in live if f.rule == "recompile-hazard") == 2

    def test_standalone_suppression_skips_comment_lines(self):
        # the suppressed.py standalone form has a two-line
        # justification between the directive and the code
        _, suppressed = core.analyze_file(FIXTURES / "suppressed.py")
        assert any(f.rule == "host-sync-in-dispatch"
                   for f in suppressed)

    def test_bad_suppression_is_not_itself_suppressible(self, tmp_path):
        f = tmp_path / "m.py"
        f.write_text("x = 1  # jaxlint: disable  # jaxlint: disable\n")
        live, suppressed = core.analyze_file(f)
        assert any(x.rule == "bad-suppression" for x in live)


class TestEngine:
    def test_syntax_error_is_a_finding_not_a_crash(self, tmp_path):
        f = tmp_path / "broken.py"
        f.write_text("def f(:\n")
        live, _ = core.analyze_file(f)
        assert [x.rule for x in live] == ["parse-error"]

    def test_alias_resolution_sees_through_import_spellings(self):
        mod = ModuleInfo.parse(
            "m.py", "import numpy as xyz\nv = xyz.asarray(q)\n")
        call = mod.tree.body[1].value
        assert mod.resolve(call.func) == "numpy.asarray"

    def test_select_runs_only_named_rules(self):
        cfg = AnalysisConfig(select=frozenset({"prng-key-reuse"}))
        report = core.run_paths([FIXTURES], cfg)
        assert set(report.by_rule()) == {"prng-key-reuse"}

    def test_nested_function_hazard_reported_once(self, tmp_path):
        # rules walking nested defs see inner statements from both the
        # outer and inner function — the engine dedupes to one finding
        f = tmp_path / "nested.py"
        f.write_text(
            "from functools import partial\n"
            "import jax\n"
            "import numpy as np\n"
            "@partial(jax.jit, donate_argnums=(0,))\n"
            "def step(x):\n"
            "    return x\n"
            "def outer():\n"
            "    def inner(y):\n"
            "        v = np.asarray(y)\n"
            "        step(y)\n"
            "        return v.sum()\n"
            "    return inner\n")
        live, _ = core.analyze_file(f)
        assert [x.rule for x in live] == ["donation-alias"]

    def test_baseline_roundtrip_tolerates_known_findings(self, tmp_path):
        base = tmp_path / "baseline.json"
        report = core.run_paths([FIXTURES])
        core.write_baseline(base, report.findings)
        again = core.run_paths([FIXTURES],
                               baseline=core.load_baseline(base))
        assert not again.findings
        assert len(again.baselined) == len(report.findings)
        assert json.loads(base.read_text())["findings"]


class TestCLI:
    def test_ci_exits_nonzero_on_fixture_corpus(self, capsys):
        assert cli.main([str(FIXTURES), "--ci"]) == 1
        out = capsys.readouterr().out
        assert "donation-alias" in out and "jaxlint:" in out

    def test_ci_exits_zero_on_live_package(self, capsys):
        # THE tier-1 gate: the shipped tree is clean (fix-or-suppress
        # policy — no baseline file exists in the repo)
        assert cli.main([str(PACKAGE), "--ci"]) == 0
        out = capsys.readouterr().out
        assert "0 finding(s)" in out
        assert not (Path(__file__).resolve().parent.parent
                    / "jaxlint_baseline.json").exists()

    def test_default_paths_cover_the_package(self, capsys):
        assert cli.main(["--ci"]) == 0
        # the default target is the package dir: same file count as
        # pointing at it explicitly
        n = re.search(r"across (\d+) file",
                      capsys.readouterr().out).group(1)
        assert int(n) > 50

    def test_non_ci_mode_reports_but_exits_zero(self):
        assert cli.main([str(FIXTURES)]) == 0

    def test_select_rejects_unknown_rule_names(self, capsys):
        # a typo'd --select must not run zero rules and read clean
        assert cli.main([str(FIXTURES), "--ci",
                         "--select", "donation_alias"]) == 2
        assert "unknown rule(s)" in capsys.readouterr().err

    def test_log_appends_kind_analysis_record(self, tmp_path, capsys):
        log = tmp_path / "run.jsonl"
        log.write_text('{"kind": "result", "success": true}\n')
        cli.main([str(FIXTURES), "--log", str(log)])
        records = [json.loads(l) for l in
                   log.read_text().splitlines()]
        assert records[0]["kind"] == "result"  # appended, not truncated
        rec = records[-1]
        assert rec["kind"] == "analysis" and rec["ok"] is False
        assert rec["findings"] > 0 and rec["suppressed"] == 2
        assert rec["by_rule"]["donation-alias"] >= 1

    def test_list_rules_prints_catalog(self, capsys):
        assert cli.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in ("donation-alias", "host-sync-in-dispatch",
                     "recompile-hazard", "prng-key-reuse",
                     "tracer-leak", "collective-divergence",
                     "collective-order", "unchecked-permutation",
                     "spec-mismatch", "dma-sem-balance",
                     "dma-slot-reuse", "collective-id-collision",
                     "kernel-dtype-cast", "vmem-budget",
                     "gate-key-orphan", "record-kind-drift",
                     "wire-field-compat", "track-band-collision",
                     "chaos-site-drift"):
            assert rule in out

    def test_list_rules_groups_by_family(self, capsys):
        # the catalog is grouped: one header per rule family, every
        # family header before its first rule line
        assert cli.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for family in ("jaxlint:", "shardlint:", "pallaslint:",
                       "contractlint:"):
            assert family in out
        lines = out.splitlines()
        contract = lines.index("contractlint:")
        section = {l.split()[0] for l in lines[contract + 1:]
                   if l.startswith("  ")}
        assert section == {"gate-key-orphan", "record-kind-drift",
                           "wire-field-compat",
                           "track-band-collision",
                           "chaos-site-drift"}


class TestBurnDownPins:
    """Regression pins for the analyzer's first full-package run: the
    true-positive fixes stay fixed."""

    def test_interop_app_jits_are_module_level(self):
        from hpc_patterns_tpu.apps import interop_app

        # hoisted wrappers: same object on every access = one trace
        # cache for the life of the process (the pre-fix form rebuilt
        # them inside run())
        assert interop_app._double is interop_app._double
        x = jnp.ones((8,), jnp.float32)
        np.testing.assert_allclose(np.asarray(interop_app._double(x)),
                                   2.0)
        np.testing.assert_allclose(np.asarray(interop_app._triple(x)),
                                   3.0)

    def test_rank_filled_reuses_its_jit(self, mesh8):
        from hpc_patterns_tpu.comm.communicator import Communicator
        from hpc_patterns_tpu.harness import trace as tracelib

        c = Communicator(mesh8, "x")
        a = c.rank_filled(16)
        b = c.rank_filled(16)
        assert len(c._rank_filled_cache) == 1
        fill = next(iter(c._rank_filled_cache.values()))
        # one compiled variant despite two calls
        assert tracelib.jit_cache_size(fill, strict=True) == 1
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c.rank_filled(32)
        assert len(c._rank_filled_cache) == 2

    def test_busy_wait_single_wrap_matches_oracle(self):
        from hpc_patterns_tpu.concurrency import kernels

        x = kernels.compute_buffer(8 * 128)
        got = kernels.busy_wait(x, 3)
        want = kernels.busy_wait_reference(x, 3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)
        # tripcount is a runtime scalar: new values must NOT add
        # compiled variants (the autotuner contract)
        from hpc_patterns_tpu.harness import trace as tracelib

        n0 = tracelib.jit_cache_size(kernels._busy_wait_call,
                                     strict=True)
        kernels.busy_wait(x, 7)
        assert tracelib.jit_cache_size(kernels._busy_wait_call,
                                       strict=True) == n0


class TestPoisonDonated:
    def test_poison_breaks_stale_zero_copy_views(self):
        f = jax.jit(lambda v: v + 1, donate_argnums=(0,))
        x = jax.block_until_ready(jnp.arange(64, dtype=jnp.int32))
        view = np.asarray(x)  # zero-copy on CPU: the PR 2 shape
        orig = view.copy()
        pf = runtime.poison_donated(f, (0,))
        y = pf(x)
        # correctness preserved...
        np.testing.assert_array_equal(np.asarray(y), orig + 1)
        # ...and the stale view now reads EITHER the donated-in-place
        # output (donation honored) or the sentinel (poisoned): never
        # the comfortable pre-call values the bug class relies on
        assert not np.array_equal(view, orig)
        if pf.poison_count:
            assert view.view(np.uint32)[0] == 0xABABABAB

    def test_poison_skips_output_aliased_buffers(self):
        # identity-ish pytree: some leaves may alias outputs; the
        # helper must never corrupt what the caller receives
        f = jax.jit(lambda d: {"a": d["a"] * 2, "b": d["b"]},
                    donate_argnums=(0,))
        d = {"a": jnp.ones((16,)), "b": jnp.zeros((16,))}
        jax.block_until_ready(d)
        pf = runtime.poison_donated(f, (0,))
        out = pf(d)
        np.testing.assert_array_equal(np.asarray(out["a"]), 2.0)
        np.testing.assert_array_equal(np.asarray(out["b"]), 0.0)

    def test_wrapper_forwards_the_jit_cache_probe(self):
        from hpc_patterns_tpu.harness import trace as tracelib

        f = jax.jit(lambda v: v * 3, donate_argnums=(0,))
        pf = runtime.poison_donated(f, (0,))
        pf(jnp.ones((4,)))
        assert tracelib.jit_cache_size(pf, strict=True) == 1

    def test_targets_mirror_serving_donate_argnums(self):
        # SERVING_POISON_TARGETS must track models/serving.py — read
        # the donate_argnums straight out of the source with the
        # analyzer's own donor table (dogfood)
        serving_py = PACKAGE / "models" / "serving.py"
        donors = _donor_table(ModuleInfo.parse(serving_py))
        for name, argnums in runtime.SERVING_POISON_TARGETS.items():
            assert donors[name]["donate_argnums"] == argnums, name

    def test_install_serving_poison_roundtrip(self):
        from hpc_patterns_tpu.models import serving

        before = {n: getattr(serving, n)
                  for n in runtime.SERVING_POISON_TARGETS}
        uninstall = runtime.install_serving_poison()
        try:
            for n in runtime.SERVING_POISON_TARGETS:
                assert getattr(serving, n) is not before[n]
                assert getattr(serving, n).__wrapped__ is before[n]
        finally:
            uninstall()
        for n in runtime.SERVING_POISON_TARGETS:
            assert getattr(serving, n) is before[n]


class TestShardlintRules:
    """Engine-level behaviors of the collective-divergence rule family
    that the fixture corpus doesn't pin line-exact."""

    def _live(self, src, tmp_path):
        f = tmp_path / "m.py"
        f.write_text(src)
        live, _ = core.analyze_file(f)
        return live

    def test_taint_flows_through_assignment_chains(self, tmp_path):
        live = self._live(
            "from jax import lax\n"
            "def f(comm, x):\n"
            "    me = lax.axis_index('x')\n"
            "    is_root = me == 0\n"
            "    if is_root:\n"
            "        return comm.allreduce(x)\n"
            "    return comm.sendrecv_ring(x)\n",
            tmp_path)
        assert [x.rule for x in live] == ["collective-divergence"]

    def test_launcher_env_rank_read_is_a_rank_source(self, tmp_path):
        live = self._live(
            "import os\n"
            "def f(comm, x):\n"
            "    if int(os.environ['HPCPAT_PROCESS_ID']) == 0:\n"
            "        comm.allreduce(x)\n",
            tmp_path)
        assert [x.rule for x in live] == ["collective-divergence"]

    def test_rank_guarded_raise_is_exempt(self, tmp_path):
        # precondition checks kill the job loudly; they are not the
        # quiet-deadlock shape the rule hunts
        live = self._live(
            "import jax\n"
            "def f(comm, x, size):\n"
            "    if jax.process_index() >= size:\n"
            "        raise ValueError('rank out of range')\n"
            "    return comm.allreduce(x)\n",
            tmp_path)
        assert not live

    def test_nested_uniform_branch_counts_once_not_twice(self, tmp_path):
        # a data-dependent inner branch whose arms issue the SAME
        # collective must not flatten to [allreduce, allreduce] and
        # fake a divergence against the else-arm's single allreduce
        live = self._live(
            "import jax\n"
            "def f(comm, x, c):\n"
            "    if jax.process_index() == 0:\n"
            "        if c:\n"
            "            y = comm.allreduce(x)\n"
            "        else:\n"
            "            y = comm.allreduce(-x)\n"
            "    else:\n"
            "        y = comm.allreduce(x * 2)\n"
            "    return y\n",
            tmp_path)
        assert not live

    def test_unjudgeable_nested_branch_abstains(self, tmp_path):
        # an inner UNIFORM branch whose arms genuinely differ (an
        # algorithm switch) makes the outer comparison unjudgeable:
        # abstain rather than guess — and rather than false-positive
        live = self._live(
            "import jax\n"
            "def f(comm, x, use_ring):\n"
            "    if jax.process_index() == 0:\n"
            "        if use_ring:\n"
            "            y = comm.sendrecv_ring(x)\n"
            "        else:\n"
            "            y = comm.all_gather(x)\n"
            "    else:\n"
            "        y = comm.allreduce(x)\n"
            "    return y\n",
            tmp_path)
        assert not live

    def test_order_rule_needs_same_multiset(self, tmp_path):
        # different op SETS across arms is an algorithm switch, not a
        # reordering — neither order nor divergence (uniform predicate)
        live = self._live(
            "def f(comm, x, fast):\n"
            "    if fast:\n"
            "        return comm.allreduce(x)\n"
            "    return comm.reduce_scatter(x)\n",
            tmp_path)
        assert not live

    def test_spec_checks_skip_open_world_modules(self, tmp_path):
        # a module building meshes from caller-provided axis names can
        # never have its spec literals judged (topology.py's shape)
        live = self._live(
            "from jax.sharding import Mesh, PartitionSpec as P\n"
            "def f(devs, names):\n"
            "    mesh = Mesh(devs, names)\n"
            "    return P('anything', None)\n",
            tmp_path)
        assert not live

    def test_ppermute_check_in_another_scope_does_not_count(self, tmp_path):
        live = self._live(
            "from jax import lax\n"
            "from hpc_patterns_tpu.comm.ring import check_permutation\n"
            "def checker(pairs, size):\n"
            "    check_permutation(pairs, size)\n"
            "def f(x, pairs):\n"
            "    return lax.ppermute(x, 'x', pairs)\n",
            tmp_path)
        assert [x.rule for x in live] == ["unchecked-permutation"]


class TestCollectiveSchedule:
    """The runtime verifier's hash chain: equality means equal
    schedules, any fingerprint field divergence changes the digest,
    and the launcher progress-file protocol works without jax."""

    def test_identical_records_identical_digests(self):
        a, b = runtime.CollectiveSchedule(), runtime.CollectiveSchedule()
        for s in (a, b):
            s.record("allreduce.ring", 0, shape=(2, 8),
                     dtype="float32", axis="x")
            s.record("sendrecv_ring", 1, shape=(2, 8),
                     dtype="float32", axis="x")
        assert a.digest == b.digest
        assert a.n == b.n == 2
        assert a.last["op"] == "sendrecv_ring"

    def test_every_fingerprint_field_feeds_the_digest(self):
        base = dict(shape=(2, 8), dtype="float32", axis="x")
        digests = set()
        for op, seq, kw in [
            ("allreduce.ring", 0, base),
            ("sendrecv_ring", 0, base),                  # op differs
            ("allreduce.ring", 1, base),                 # seq differs
            ("allreduce.ring", 0, {**base, "shape": (2, 16)}),
            ("allreduce.ring", 0, {**base, "dtype": "int32"}),
            ("allreduce.ring", 0, {**base, "axis": "y"}),
        ]:
            s = runtime.CollectiveSchedule()
            s.record(op, seq, **kw)
            digests.add(s.digest)
        assert len(digests) == 6

    def test_window_bounds_entries_not_the_digest(self):
        s = runtime.CollectiveSchedule(window=4)
        for i in range(10):
            s.record("op", i)
        assert s.n == 10
        assert len(s.entries) == 4
        assert s.entries[0]["i"] == 6  # absolute indices survive
        full = runtime.CollectiveSchedule()
        for i in range(10):
            full.record("op", i)
        assert s.digest == full.digest  # digest covers full history

    def test_progress_file_written_under_launcher_env(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv(runtime.ENV_TRACE_DIR, str(tmp_path))
        monkeypatch.setenv(runtime.ENV_PROCESS_ID, "3")
        runtime.reset_collective_schedule()
        try:
            runtime.record_collective("allreduce.ring", 7,
                                      shape=(2, 8), dtype="float32",
                                      axis="x")
            rec = json.loads(
                (tmp_path / "rank00003.sched.json").read_text())
            assert rec["process_id"] == 3 and rec["n"] == 1
            assert rec["last"] == {"i": 0, "op": "allreduce.ring",
                                   "seq": 7}
            assert rec["digest"]
        finally:
            runtime.reset_collective_schedule()

    def test_env_names_mirror_topology_constants(self):
        # runtime duplicates the literals to stay importable without
        # jax; the pair must never drift from the launcher protocol
        from hpc_patterns_tpu import topology

        assert runtime.ENV_TRACE_DIR == topology.ENV_TRACE_DIR
        assert runtime.ENV_PROCESS_ID == topology.ENV_PROCESS_ID

    def test_eager_communicator_collectives_are_fingerprinted(
            self, mesh8):
        from hpc_patterns_tpu.comm.communicator import Communicator
        from hpc_patterns_tpu.harness import trace as tracelib

        # recording engages only when something can consume the chain
        # (a live recorder, or a launcher trace dir) — configure()
        # also resets the chain to genesis
        tracelib.configure(enabled=True)
        try:
            comm = Communicator(mesh8, "x")
            x = comm.rank_filled(8)
            comm.allreduce(x)
            comm.sendrecv_ring(x)
            sched = runtime.collective_schedule()
            assert [e["op"] for e in sched.entries] == [
                "allreduce.collective", "sendrecv_ring"]
            e = sched.entries[0]
            assert e["seq"] == 0 and e["axis"] == "x"
            assert e["shape"] == [8, 8]
            assert e["dtype"] == "float32"
        finally:
            tracelib.configure(enabled=False)

    def test_untraced_eager_collectives_stay_unrecorded(self, mesh8,
                                                        monkeypatch):
        # the disabled-path contract: no recorder, no launcher trace
        # dir -> no lock, no hash, no entry (byte-identical hot path)
        from hpc_patterns_tpu.comm.communicator import Communicator
        from hpc_patterns_tpu.harness import trace as tracelib

        monkeypatch.delenv(runtime.ENV_TRACE_DIR, raising=False)
        tracelib.configure(enabled=False)
        comm = Communicator(mesh8, "x")
        comm.allreduce(comm.rank_filled(4))
        assert runtime.collective_schedule().n == 0

    def test_trace_snapshot_stamps_the_chain(self):
        from hpc_patterns_tpu.harness import trace as tracelib

        runtime.reset_collective_schedule()
        try:
            runtime.record_collective("allreduce.ring", 0)
            snap = tracelib.TraceRecorder(enabled=True).snapshot()
            assert snap["collectives"]["n"] == 1
            assert snap["collectives"]["digest"]
            assert snap["collectives"]["entries"][0]["op"] == \
                "allreduce.ring"
        finally:
            runtime.reset_collective_schedule()

    def test_trace_configure_resets_the_chain(self):
        from hpc_patterns_tpu.harness import trace as tracelib

        runtime.record_collective("anything", 0)
        tracelib.configure(enabled=False)
        assert runtime.collective_schedule().n == 0


class TestMarker:
    def test_dispatch_critical_is_a_noop_marker(self):
        from hpc_patterns_tpu.analysis import dispatch_critical

        def g(x):
            return x + 1

        assert dispatch_critical(g) is g


class TestPallasLedger:
    """Engine-level behaviors of the semaphore-ledger abstract
    interpreter (analysis/pallas_rules.py) beyond the line-exact
    fixture corpus."""

    def _ledger(self, path):
        from hpc_patterns_tpu.analysis import pallas_rules as pr

        return pr.ledger_findings(ModuleInfo.parse(path))

    def test_live_kernel_tier_is_clean(self):
        # the burn-down target: the fused rings, the flash/paged/MLP
        # kernels, and the on-chip pipeline all balance
        for rel in ("comm/fused.py", "concurrency/pipeline.py",
                    "concurrency/kernels.py", "ops/flash_attention.py",
                    "ops/flash_decode.py", "ops/fused_mlp.py",
                    "ops/paged_attention.py"):
            findings = self._ledger(PACKAGE / rel)
            assert not findings, (rel, [(k, n.lineno, m)
                                        for k, n, m in findings])

    def test_fused_kernels_are_analyzed_not_abstained(self):
        # 0 findings must mean "proved balanced", not "gave up": the
        # interpreter must actually record DMA signals for every
        # fused kernel root
        from hpc_patterns_tpu.analysis import pallas_rules as pr

        mod = ModuleInfo.parse(PACKAGE / "comm" / "fused.py")
        roots = pr._kernel_roots(mod)
        assert len(roots) == 3  # permute, allreduce, allgather_matmul
        signals = {"n": 0}
        orig = pr._KernelRun._signal

        def counting(self, key, node, _orig=orig):
            signals["n"] += 1
            return _orig(self, key, node)

        pr._KernelRun._signal = counting
        try:
            for fn in roots:
                before = signals["n"]
                assert pr._analyze_kernel(mod, fn) == []
                assert signals["n"] > before, (
                    f"kernel at line {fn.lineno} abstained")
        finally:
            pr._KernelRun._signal = orig

    def test_model_ring_covers_the_drain_bug_threshold(self):
        # the PR 8 drain double-wait manifests at size >= 3; the
        # modeled ring must be past it or the fixture could pass
        from hpc_patterns_tpu.analysis import pallas_rules as pr

        assert pr.MODEL_RING >= 3

    def test_drain_double_wait_anchored_at_the_drain(self):
        live, _ = core.analyze_file(FIXTURES / "bad_pallas_dma.py")
        balance = [f for f in live if f.rule == "dma-sem-balance"]
        assert balance, "the PR 8 drain replica must be flagged"
        src = (FIXTURES / "bad_pallas_dma.py").read_text()
        flagged = src.splitlines()[balance[0].line - 1]
        assert "wait_send" in flagged  # the re-wait, not the loop head

    def test_phase_crossed_recv_names_both_sem_families(self):
        live, _ = core.analyze_file(FIXTURES / "bad_pallas_dma.py")
        reuse = [f for f in live if f.rule == "dma-slot-reuse"
                 and "semaphore families" in f.message]
        assert len(reuse) == 1
        assert "rs_sem" in reuse[0].message
        assert "ag_sem" in reuse[0].message

    def test_opaque_loop_with_dma_abstains_not_guesses(self, tmp_path):
        # a DMA under a loop the interpreter cannot unroll (opaque
        # iterable, not a range) must produce silence, not findings
        f = tmp_path / "m.py"
        f.write_text(
            "from jax.experimental import pallas as pl\n"
            "from jax.experimental.pallas import tpu as pltpu\n"
            "def run(x, schedule):\n"
            "    def kernel(x_ref, o_ref, buf, sem):\n"
            "        for hop in schedule:\n"
            "            d = pltpu.make_async_copy(\n"
            "                x_ref, buf.at[0], sem.at[0])\n"
            "            d.start()\n"
            "    return pl.pallas_call(kernel, out_shape=x)(x)\n")
        live, _ = core.analyze_file(f)
        assert not live

    def test_mode_switch_predicates_stay_consistent(self, tmp_path):
        # a factory kernel branching on one opaque subject must not
        # fork into impossible combinations (mode == 'a' AND
        # mode == 'b') and fake an imbalance — the pipeline.py shape
        f = tmp_path / "m.py"
        f.write_text(
            "from jax.experimental import pallas as pl\n"
            "from jax.experimental.pallas import tpu as pltpu\n"
            "def make(mode):\n"
            "    def kernel(x_ref, o_ref, buf, sem):\n"
            "        d = pltpu.make_async_copy(x_ref, buf.at[0],\n"
            "                                  sem.at[0])\n"
            "        if mode == 'eager':\n"
            "            d.start()\n"
            "            d.wait()\n"
            "        if mode != 'eager':\n"
            "            pass\n"
            "    return kernel\n"
            "def run(x, mode):\n"
            "    return pl.pallas_call(make(mode), out_shape=x)(x)\n")
        live, _ = core.analyze_file(f)
        assert not live

    def test_magic_collective_id_flagged_registry_call_not(self,
                                                           tmp_path):
        f = tmp_path / "m.py"
        f.write_text(
            "from hpc_patterns_tpu.ops.tiling import collective_id\n"
            "def a(params):\n"
            "    return params(collective_id=7)\n"
            "def b(params):\n"
            "    return params(\n"
            "        collective_id=collective_id('x.y'))\n")
        live, _ = core.analyze_file(f)
        assert [x.rule for x in live] == ["collective-id-collision"]
        assert "7" in live[0].message

    def test_duplicate_registry_names_collide(self, tmp_path):
        # two call sites registering the SAME name is the shared-id
        # bug wearing the registry's clothes — still flagged
        f = tmp_path / "m.py"
        f.write_text(
            "from hpc_patterns_tpu.ops.tiling import collective_id\n"
            "def a(params):\n"
            "    return params(collective_id=collective_id('k'))\n"
            "def b(params):\n"
            "    return params(collective_id=collective_id('k'))\n")
        live, _ = core.analyze_file(f)
        assert [x.rule for x in live] == ["collective-id-collision"]
        assert "'k'" in live[0].message


class TestCollectiveIdRegistry:
    def test_historical_ids_are_pinned(self):
        # the shipped kernels' wire ids must never move: 0-4 as
        # hand-numbered before the registry existed
        from hpc_patterns_tpu.ops import tiling

        ids = tiling.registered_collective_ids()
        assert ids["comm.fused.permute"] == 0
        assert ids["comm.fused.allreduce"] == 1
        assert ids["comm.fused.allgather_matmul"] == 2
        assert ids["parallel.ring_attention.kshift"] == 3
        assert ids["parallel.ring_attention.vshift"] == 4

    def test_new_names_get_distinct_ids_idempotently(self):
        from hpc_patterns_tpu.ops import tiling

        a = tiling.collective_id("test.registry.alpha")
        b = tiling.collective_id("test.registry.beta")
        assert a != b
        assert tiling.collective_id("test.registry.alpha") == a
        ids = tiling.registered_collective_ids()
        assert len(set(ids.values())) == len(ids)  # never a collision

    def test_new_ids_are_name_derived_not_order_derived(self):
        # every host of an SPMD job must compute the same id for a
        # name regardless of which kernel warms up first — the id is
        # a pure function of the string, above the seeded block
        from hpc_patterns_tpu.ops import tiling

        a = tiling._derived_id("test.order.a")
        b = tiling._derived_id("test.order.b")
        assert a != b
        assert min(a, b) >= tiling._ID_FLOOR
        assert tiling.collective_id("test.order.b") == b  # b first
        assert tiling.collective_id("test.order.a") == a
        assert tiling._derived_id("test.order.a") == a  # deterministic

    def test_registry_names_globally_unique_across_package(self):
        # the cross-module half of collective-id-collision: the lint
        # rule is per-module by engine design, so the whole-package
        # invariant — no two call sites registering one name — is
        # pinned here instead
        import ast as astmod

        registry_fns = ("collective_id", "_registered_collective_id")
        sites: dict[str, list[str]] = {}
        for path in sorted(PACKAGE.rglob("*.py")):
            tree = astmod.parse(path.read_text())
            for node in astmod.walk(tree):
                if not (isinstance(node, astmod.Call) and node.args
                        and isinstance(node.args[0], astmod.Constant)):
                    continue
                # both spellings count: bare collective_id(...) and
                # tiling.collective_id(...) (the attribute form
                # parallel/ring_attention.py uses)
                func = node.func
                name = (func.id if isinstance(func, astmod.Name)
                        else func.attr
                        if isinstance(func, astmod.Attribute) else "")
                if name in registry_fns:
                    sites.setdefault(str(node.args[0].value), []).append(
                        f"{path.name}:{node.lineno}")
        assert sites, "the registry call sites vanished"
        dupes = {k: v for k, v in sites.items() if len(v) > 1}
        assert not dupes, dupes


class TestVmemEstimator:
    """The budget estimator (analysis/vmem.py): the paged_flash golden
    bound, full-package coverage, and the literal lower-bound rule."""

    def test_paged_flash_row_reproduces_the_docs_bound(self):
        # docs/quantization.md: the gather scratch holds the whole
        # allocated span — pages·P·D of pool dtype for K and V each.
        # At S_alloc = pages·P = 16384, D = 128 that is 4 MiB for int8
        # pools (plus the two (1, pages·P) f32 scale rows)
        from hpc_patterns_tpu.analysis import vmem

        mod = ModuleInfo.parse(PACKAGE / "ops" / "paged_attention.py")
        (est,) = vmem.estimate_module(mod)
        assert est.kernel == "_paged_attention_kernel"
        bindings = {"pages": 128, "P": 128, "D": 128}
        spans = [c for c in est.components
                 if c.label.startswith("scratch")]
        assert len(spans) == 4  # K span, V span, 2 scale rows
        kv_bytes = 0
        scale_bytes = 0
        for c in spans:
            n, assumed = vmem.q_value(c.quantity, bindings)
            assert not assumed, (c.label, assumed)
            if c.dtype_bytes == 4:       # the f32 scale rows
                scale_bytes += n * 4
            else:                        # pool-dtype spans at int8
                kv_bytes += n * 1
        assert kv_bytes == 2 * 16384 * 128          # 4 MiB exactly
        assert scale_bytes == 2 * 16384 * 4
        # and at the f32 default the same spans blow the 16 MB scoped
        # limit — the documented "f32 pools belong on the streaming
        # route", now a number instead of a sentence
        total, _ = est.model_bytes(bindings)
        assert total > est.limit_bytes

    def test_every_package_pallas_call_gets_a_numeric_row(self):
        # the acceptance criterion: per-kernel byte totals for EVERY
        # pallas_call under model bindings — no silent gaps
        from hpc_patterns_tpu.analysis import vmem

        ests = vmem.estimate_paths([PACKAGE])
        by_file = {Path(e.path).name for e in ests}
        assert {"fused.py", "pipeline.py", "kernels.py", "device.py",
                "flash_attention.py", "flash_decode.py",
                "fused_mlp.py", "paged_attention.py"} <= by_file
        assert len(ests) >= 12
        for est in ests:
            total, _ = est.model_bytes()
            assert total > 0, (est.kernel, est.path)

    def test_explicit_vmem_limit_is_read(self):
        from hpc_patterns_tpu.analysis import vmem

        mod = ModuleInfo.parse(PACKAGE / "comm" / "fused.py")
        ests = {e.line: e for e in vmem.estimate_module(mod)}
        limits = {e.limit_bytes for e in ests.values()
                  if not e.limit_default}
        assert 100 * 1024 * 1024 in limits  # fused.py's _VMEM_LIMIT

    def test_lower_bound_rule_needs_literals(self, tmp_path):
        # symbolic shapes never fire the rule (the report's job), and
        # a literal overflow always does
        f = tmp_path / "m.py"
        f.write_text(
            "import jax, jax.numpy as jnp\n"
            "from jax.experimental import pallas as pl\n"
            "from jax.experimental.pallas import tpu as pltpu\n"
            "def k(x_ref, o_ref, acc):\n"
            "    o_ref[...] = x_ref[...]\n"
            "def sym(x, n):\n"
            "    return pl.pallas_call(k, out_shape=x,\n"
            "        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],\n"
            "    )(x)\n"
            "def lit(x):\n"
            "    return pl.pallas_call(k, out_shape=x,\n"
            "        scratch_shapes=[\n"
            "            pltpu.VMEM((8192, 8192), jnp.float32)],\n"
            "    )(x)\n")
        live, _ = core.analyze_file(f)
        assert [x.rule for x in live] == ["vmem-budget"]
        assert "268,435,456" in live[0].message

    def test_unrelated_scope_never_resolves_runtime_dims(self,
                                                         tmp_path):
        # scope correctness: another function's local ``n = 8192``
        # (or a module constant shadowed by a parameter) must not
        # resolve this kernel's RUNTIME ``n`` into a literal — that
        # would fire the CI-gating rule on correct code
        f = tmp_path / "m.py"
        f.write_text(
            "import jax, jax.numpy as jnp\n"
            "from jax.experimental import pallas as pl\n"
            "from jax.experimental.pallas import tpu as pltpu\n"
            "n = 8192\n"
            "def unrelated():\n"
            "    m = 8192\n"
            "    return m\n"
            "def k(x_ref, o_ref, acc):\n"
            "    o_ref[...] = x_ref[...]\n"
            "def run_param(x, n):\n"
            "    return pl.pallas_call(k, out_shape=x,\n"
            "        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],\n"
            "    )(x)\n"
            "def run_other(x, m):\n"
            "    return pl.pallas_call(k, out_shape=x,\n"
            "        scratch_shapes=[pltpu.VMEM((m, m), jnp.float32)],\n"
            "    )(x)\n")
        live, _ = core.analyze_file(f)
        assert not live

    def test_format_table_names_assumed_symbols(self):
        from hpc_patterns_tpu.analysis import vmem

        ests = vmem.estimate_paths([PACKAGE / "ops"])
        table = vmem.format_vmem_table(ests, root=PACKAGE.parent)
        assert "_paged_attention_kernel" in table
        assert "ASSUMED" in table  # runtime dtypes are never silent
        assert "vmem bytes" in table

    def test_vmem_summary_is_json_able(self):
        from hpc_patterns_tpu.analysis import vmem

        ests = vmem.estimate_paths([PACKAGE / "comm"])
        summary = vmem.vmem_summary(ests)
        json.dumps(summary)
        assert summary["kernels"] == len(ests) >= 3
        assert all(r["bytes"] > 0 for r in summary["rows"])


class TestStrictSemaphores:
    """The strict-semaphore interpret shim (analysis/runtime.py): the
    PR 8 balance bug class fails at TRACE time under the shim. The
    fused parity battery runs under it module-wide
    (tests/test_fused_comm.py); these pin the shim's own semantics."""

    def _run_kernel(self, kernel, mesh8, extra_scratch=2):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        x = jnp.arange(8 * 2 * 8, dtype=jnp.float32).reshape(16, 8)

        def run(v):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype),
                in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                scratch_shapes=[pltpu.VMEM(v.shape, v.dtype)]
                + [pltpu.SemaphoreType.DMA] * extra_scratch,
                interpret=True,
            )(v)

        # interpreted kernels carry no vma types (see test_fused_comm)
        f = jax.jit(shard_map(run, mesh=mesh8, in_specs=P("x"),
                              out_specs=P("x"), check_vma=False))
        return jax.block_until_ready(f(x))

    def test_balanced_kernel_passes_and_is_counted(self, mesh8):
        from jax import lax
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_ref, o_ref, buf, send_sem, recv_sem):
            me = lax.axis_index("x")
            d = pltpu.make_async_remote_copy(
                src_ref=x_ref, dst_ref=o_ref, send_sem=send_sem,
                recv_sem=recv_sem, device_id=lax.rem(me + 1, 8),
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            d.start()
            d.wait()

        with runtime.strict_semaphores() as ledger:
            self._run_kernel(kernel, mesh8)
        assert ledger.kernels_checked == 1

    def test_undrained_send_fails_at_trace_time(self, mesh8):
        from jax import lax
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_ref, o_ref, buf, send_sem, recv_sem):
            me = lax.axis_index("x")
            d = pltpu.make_async_remote_copy(
                src_ref=x_ref, dst_ref=buf, send_sem=send_sem,
                recv_sem=recv_sem, device_id=lax.rem(me + 1, 8),
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            d.start()
            d.wait_recv()          # BUG: the send is never waited
            o_ref[...] = buf[...]

        with runtime.strict_semaphores():
            with pytest.raises(runtime.SemaphoreBalanceError,
                               match="send wait"):
                self._run_kernel(kernel, mesh8)

    def test_drain_double_wait_fails_at_trace_time(self, mesh8):
        # the PR 8 drain bug's exact shape: one descriptor's send
        # semaphore waited twice
        from jax import lax
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_ref, o_ref, buf, send_sem, recv_sem):
            me = lax.axis_index("x")
            d = pltpu.make_async_remote_copy(
                src_ref=x_ref, dst_ref=o_ref, send_sem=send_sem,
                recv_sem=recv_sem, device_id=lax.rem(me + 1, 8),
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            d.start()
            d.wait()
            d.wait_send()          # BUG: one signal per DMA

        with runtime.strict_semaphores():
            with pytest.raises(runtime.SemaphoreBalanceError,
                               match="waited 2 times"):
                self._run_kernel(kernel, mesh8)

    def test_local_copy_balance_is_checked_too(self, mesh8):
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_ref, o_ref, buf, sem, _unused):
            d = pltpu.make_async_copy(x_ref, buf, sem)
            d.start()              # BUG: never waited
            o_ref[...] = x_ref[...]

        with runtime.strict_semaphores():
            with pytest.raises(runtime.SemaphoreBalanceError,
                               match="local start"):
                self._run_kernel(kernel, mesh8)

    def test_shim_uninstalls_cleanly(self):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        before = (pltpu.make_async_copy, pltpu.make_async_remote_copy,
                  pl.pallas_call)
        with runtime.strict_semaphores():
            assert pl.pallas_call is not before[2]
        assert (pltpu.make_async_copy, pltpu.make_async_remote_copy,
                pl.pallas_call) == before


class TestContractlint:
    """Whole-tree producer/consumer verification (contractlint): the
    static tables agree with the live tree, and the motivating
    deleted-emitter shape is caught at the surviving consumer."""

    def test_deleted_emitter_replica_flagged_at_the_gate_row(self):
        # the minimized "consumer whose emitter was renamed away"
        # replica: the finding anchors at the surviving string read,
        # exactly where its EXPECT marker sits
        path = FIXTURES / "bad_gate_key_orphan.py"
        live, _ = core.analyze_file(path)
        orphans = [f for f in live if f.rule == "gate-key-orphan"]
        assert orphans, "the deleted-emitter replica must be flagged"
        lines = path.read_text().splitlines()
        gate_rows = [f for f in orphans
                     if 'gauges.get("engine.tokens_per_s")'
                     in lines[f.line - 1]]
        assert gate_rows, "finding must anchor at the consumer's line"
        assert "EXPECT: gate-key-orphan" in lines[gate_rows[0].line - 1]

    def test_fixture_worlds_are_self_contained(self):
        # a fixture under tests/fixtures/ is its own single-module
        # tree: its tables must not bleed into (or read from) the
        # live repo tables
        from hpc_patterns_tpu.analysis import contracts

        mod = core.ModuleInfo.parse(
            FIXTURES / "bad_record_kind_drift.py")
        t = contracts.tables_for(mod)
        assert set(t.kinds_produced) == {"engine_round", "engine_debug"}
        assert t.root == ""  # not resolved to the repo checkout

    def test_live_wire_codec_declares_required_fields(self):
        # REQUIRED_WIRE_FIELDS is the explicit absent-intolerance
        # contract: direct indexing in from_wire is legal only for
        # declared fields
        from hpc_patterns_tpu.serving_plane import migration

        assert "seq_id" in migration.REQUIRED_WIRE_FIELDS
        assert "payload" in migration.REQUIRED_WIRE_FIELDS

    def test_live_track_bands_registry_is_collision_free(self):
        from hpc_patterns_tpu.harness import trace as tracelib

        bands = sorted(tracelib.TRACK_BANDS.items(),
                       key=lambda kv: kv[1][0])
        for (_, (b0, n0)), (_, (b1, _)) in zip(bands, bands[1:]):
            assert b0 + n0 <= b1, f"bands overlap: {bands}"
        # the three migrated modules unpack from the registry
        from hpc_patterns_tpu.memory import residency
        from hpc_patterns_tpu.serving_plane import autoscaler, service

        assert (service.MIG_TRACK_BASE, service.MIG_TRACKS) \
            == tracelib.track_band("migration")
        assert (autoscaler.SPINUP_TRACK_BASE, autoscaler.SPINUP_TRACKS) \
            == tracelib.track_band("spinup")
        assert (residency.MEM_TRACK_BASE, residency.MEM_TRACKS) \
            == tracelib.track_band("residency")

    def test_contract_report_renders_every_section(self, capsys):
        assert cli.main(["--contract-report"]) == 0
        out = capsys.readouterr().out
        assert "contractlint report over" in out
        for section in ("metric names consumed by string",
                        "RunLog record kinds",
                        "device-subtrack bands",
                        "chaos contract"):
            assert section in out
        # the live tree is burned down: every string-consumed metric
        # has a producer. (The record-kind section may show residue
        # from deliberate test fabrications — those carry rule-layer
        # suppressions.)
        assert "gate keys" not in out
        assert "MISSING PRODUCER" not in out
