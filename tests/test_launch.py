"""Multi-process launches: the mpirun -np analog end to end.

The reference's distributed tests are `mpirun -np 4 ./app` CTest cases
(src/CMakeLists.txt:39-50). Here apps/launch.py spawns real OS
processes joined via jax.distributed over a local coordinator, CPU
devices standing in for chips — cross-process collectives,
cross-process MAX timing, and per-rank validation all run for real
(SURVEY.md §4's hardware-free-testing gap, closed at the process
level too).

Tiering: the broad app matrix stays in the slow tier (each case boots
2 jax processes); the distributed-flight-recorder acceptance (ONE
2-process launch) and the jax-free launcher-mechanics cases run tier-1
— the rung-4 contract must hold without `--slow`."""

import json
import sys

import pytest

from hpc_patterns_tpu.apps import launch

slow = pytest.mark.slow  # per-class: this module is no longer all-slow


def _launch(app_args, np_=2, devices=2, slices=0):
    return launch.main([
        "-np", str(np_), "--cpu-devices-per-proc", str(devices),
        *(["--slices", str(slices)] if slices else []), "--",
        sys.executable, "-m", *app_args,
    ])


@slow
class TestLaunch:
    def test_allreduce_ring_4_ranks_2_processes(self, capsys):
        code = _launch(["hpc_patterns_tpu.apps.allreduce_app", "-p", "8",
                        "--repetitions", "2", "--warmup", "1"])
        out = capsys.readouterr().out
        assert code == 0, out
        # every global rank validated, split across the two processes
        for r in range(4):
            assert f"Passed {r}" in out
        assert "world=4" in out

    def test_pingpong_across_processes(self, capsys):
        code = _launch(["hpc_patterns_tpu.apps.pingpong_app", "-p", "6",
                        "--min-p", "6", "--repetitions", "2",
                        "--warmup", "1"])
        out = capsys.readouterr().out
        assert code == 0, out
        assert "ok" in out

    def test_train_dp_across_processes(self, capsys):
        # the flagship train step as true multi-process SPMD: dp=4 over
        # 2 OS processes, gradient all-reduce crossing the process
        # boundary
        code = _launch(["hpc_patterns_tpu.apps.train_app", "--dp", "4",
                        "--steps", "2", "--batch", "8", "--seq", "32",
                        "--d-model", "32", "--n-layers", "1",
                        "--vocab", "128"])
        out = capsys.readouterr().out
        assert code == 0, out

    def test_train_pp_stages_in_separate_processes(self, capsys):
        # 1F1B pipeline with each stage living in a different OS process
        code = _launch(["hpc_patterns_tpu.apps.train_app", "--pp", "2",
                        "--steps", "2", "--batch", "4",
                        "--microbatches", "2", "--seq", "32",
                        "--d-model", "32", "--n-layers", "2",
                        "--vocab", "128"], devices=1)
        out = capsys.readouterr().out
        assert code == 0, out

    def test_train_dcn_dp_slices_across_processes(self, capsys):
        # the multi-slice hybrid-mesh path with REAL process boundaries:
        # --slices 2 makes each OS process one "slice" (the production
        # HPCPAT_SLICE_GROUPING protocol, not a monkeypatch), so the
        # --dcn-dp gradient psum is a genuine DCN-analog collective
        # crossing processes while the tp collectives stay
        # slice-internal (each process's own 4 devices)
        code = _launch(["hpc_patterns_tpu.apps.train_app", "--dcn-dp",
                        "--dp", "-1", "--tp", "2", "--steps", "2",
                        "--batch", "4", "--seq", "32",
                        "--d-model", "32", "--n-layers", "1",
                        "--vocab", "128"], devices=4, slices=2)
        out = capsys.readouterr().out
        assert code == 0, out
        assert "SUCCESS" in out

    def test_train_pp_dcn_dp_slices_across_processes(self, capsys):
        # pp x dcn-dp: the 1F1B stage ppermutes stay slice-internal
        # (each process's own devices) while the once-per-step dp
        # gradient pmean crosses the OS process boundary
        code = _launch(["hpc_patterns_tpu.apps.train_app", "--dcn-dp",
                        "--dp", "-1", "--pp", "2", "--steps", "2",
                        "--batch", "4", "--microbatches", "2",
                        "--seq", "32", "--d-model", "32",
                        "--n-layers", "2", "--vocab", "128"],
                       devices=4, slices=2)
        out = capsys.readouterr().out
        assert code == 0, out
        assert "SUCCESS" in out and "dcn-dp=2" in out

    def test_train_pp_tp_across_processes(self, capsys):
        # Megatron tp inside pipeline stages with the mesh spanning two
        # OS processes: the per-layer tp psums (f/g) and the sharded
        # loss head's reductions run as true cross-process collectives
        code = _launch(["hpc_patterns_tpu.apps.train_app", "--pp", "2",
                        "--tp", "2", "--steps", "2", "--batch", "4",
                        "--microbatches", "2", "--seq", "32",
                        "--d-model", "32", "--n-heads", "4",
                        "--n-layers", "2", "--vocab", "128"])
        out = capsys.readouterr().out
        assert code == 0, out
        assert "SUCCESS" in out and "tp=2" in out

    def test_train_sp_ring_attention_across_processes(self, capsys):
        # ring attention with the sp axis spanning both OS processes:
        # the per-step K/V ppermute crosses the process boundary
        code = _launch(["hpc_patterns_tpu.apps.train_app", "--sp", "4",
                        "--attention", "ring_flash", "--steps", "2",
                        "--batch", "2", "--seq", "32",
                        "--d-model", "32", "--n-layers", "1",
                        "--vocab", "128"])
        out = capsys.readouterr().out
        assert code == 0, out

class TestLauncherMechanics:
    # jax-free children: tier-1 (no backend boot, sub-second cases)

    def test_failure_propagates(self, capsys):
        # a child that exits nonzero must fail the launch (ctest contract)
        code = launch.main([
            "-np", "2", "--",
            sys.executable, "-c", "import sys; sys.exit(3)",
        ])
        out = capsys.readouterr().out
        assert code == 1
        assert "FAILURE" in out

    def test_no_command_is_an_error(self, capsys):
        assert launch.main(["-np", "2"]) == 2
        capsys.readouterr()

    def test_timeout_names_hung_ranks_with_last_output(self, capsys):
        # rank 1 exits immediately; rank 0's pid makes it hang — the
        # timeout report must name ONLY the hung rank and quote its
        # last printed line (what a deadlocked collective debug needs)
        code = launch.main([
            "-np", "2", "--timeout", "2", "--",
            sys.executable, "-c",
            "import os, sys, time\n"
            "pid = int(os.environ['HPCPAT_PROCESS_ID'])\n"
            "print(f'entering collective {pid}', flush=True)\n"
            "time.sleep(0 if pid == 1 else 60)\n",
        ])
        out = capsys.readouterr().out
        assert code == 1
        assert "1/2 rank(s) had not exited" in out
        assert "rank 0: last output: [0] entering collective 0" in out
        assert "rank 1: last" not in out

    def test_timeout_still_harvests_written_traces(self, tmp_path,
                                                   capsys):
        # a hung run is still debuggable: ranks that already handed off
        # their snapshot merge; the hung rank is reported as missing
        snap = {
            "kind": "trace",
            "clock": {"mono0": 0.0, "wall0": 0.0,
                      "mono1": 1.0, "wall1": 1.0},
            "process": {"process_id": 1, "num_processes": 2,
                        "slice_id": 0},
            "sync": [], "capacity": 8, "n_events": 0, "n_dropped": 0,
            "by_cat": {}, "compile": {"count": 0, "total_s": 0.0},
            "mem": {"peak_live_bytes": 0}, "events": [],
        }
        out = tmp_path / "merged.json"
        code = launch.main([
            "-np", "2", "--timeout", "3",
            "--trace-out", str(out),
            "--trace-dir", str(tmp_path / "ranks"),
            "--log", str(tmp_path / "run.jsonl"), "--",
            sys.executable, "-c",
            "import json, os, sys, time\n"
            "pid = int(os.environ['HPCPAT_PROCESS_ID'])\n"
            "d = os.environ['HPCPAT_TRACE_DIR']\n"
            f"snap = {snap!r}\n"
            "if pid == 1:\n"
            "    with open(os.path.join(d, 'rank00001.trace.json'), 'w') as f:\n"
            "        json.dump(snap, f)\n"
            "    sys.exit(0)\n"
            "time.sleep(60)\n",
        ])
        printed = capsys.readouterr().out
        assert code == 1
        assert "timeout" in printed
        assert "only 1/2 rank snapshot(s) harvested" in printed
        assert out.exists()  # the partial merge still landed
        recs = [json.loads(l)
                for l in (tmp_path / "run.jsonl").read_text().splitlines()]
        assert recs[-1]["kind"] == "trace_merged"
        assert recs[-1]["n_ranks"] == 1
        # the hung rank is a TIMEOUT in the fault record, not a
        # worker death — the launcher's own kill must not read as the
        # chaos 'die' signature
        assert recs[-1]["faults"] == {"0": "timeout", "1": "clean"}


class TestCollectiveScheduleLaunch:
    """The desync check, divergent side (tier-1): a deliberately
    divergent worker pair must be named with the exact first-divergent
    (rank, op, seq) at merge time, and a hung worker's last fingerprint
    must surface in the timeout report. The workers drive the REAL
    per-rank recording path (analysis/runtime.py + the trace handoff)
    without booting a jax mesh, so both cases stay tier-1 fast."""

    def test_divergent_worker_named_with_first_divergent_op_seq(
            self, tmp_path, capsys):
        out, log = tmp_path / "merged.json", tmp_path / "run.jsonl"
        worker = (
            "import os\n"
            "from hpc_patterns_tpu.analysis import runtime as rt\n"
            "from hpc_patterns_tpu.harness import trace\n"
            "pid = int(os.environ['HPCPAT_PROCESS_ID'])\n"
            "rec = trace.TraceRecorder(enabled=True)\n"
            "rt.reset_collective_schedule()\n"
            "kw = dict(shape=(2, 8), dtype='float32', axis='x')\n"
            "rt.record_collective('allreduce.collective', 0, **kw)\n"
            "if pid == 0:\n"
            "    rt.record_collective('allreduce.collective', 1, **kw)\n"
            "else:\n"
            "    rt.record_collective('sendrecv_ring', 1, **kw)\n"
            "trace.write_rank_snapshot(rec, os.environ['HPCPAT_TRACE_DIR'])\n"
        )
        code = launch.main([
            "-np", "2", "--trace-out", str(out), "--log", str(log),
            "--", sys.executable, "-c", worker,
        ])
        printed = capsys.readouterr().out
        assert code == 0, printed
        assert "COLLECTIVE SCHEDULE DIVERGENCE at #1" in printed
        assert "rank 0 is at allreduce.collective#1" in printed
        assert "rank 1 is at sendrecv_ring#1" in printed
        recs = [json.loads(l) for l in log.read_text().splitlines()]
        sched = [r for r in recs
                 if r["kind"] == "trace_merged"][0]["schedule"]
        assert sched["verdict"] == "divergent"
        fd = sched["first_divergence"]
        assert fd["index"] == 1
        assert fd["ranks"]["0"] == {"op": "allreduce.collective",
                                    "seq": 1}
        assert fd["ranks"]["1"] == {"op": "sendrecv_ring", "seq": 1}

    def test_timeout_prints_each_ranks_last_fingerprint(
            self, tmp_path, capsys):
        # rank 0 hangs INSIDE its second collective (never reaches the
        # trace handoff); the per-record progress file is what lets the
        # timeout report say WHICH collective it is stuck at — the
        # "rank 0 is at allreduce#17" read of a deadlocked run
        out = tmp_path / "merged.json"
        worker = (
            "import os, sys, time\n"
            "from hpc_patterns_tpu.analysis import runtime as rt\n"
            "pid = int(os.environ['HPCPAT_PROCESS_ID'])\n"
            "rt.record_collective('allreduce.collective', 16)\n"
            "if pid == 1:\n"
            "    rt.record_collective('sendrecv_ring', 17)\n"
            "    sys.exit(0)\n"
            "rt.record_collective('allreduce.collective', 17)\n"
            "time.sleep(60)\n"
        )
        code = launch.main([
            "-np", "2", "--timeout", "8",
            "--trace-out", str(out),
            "--trace-dir", str(tmp_path / "ranks"),
            "--", sys.executable, "-c", worker,
        ])
        printed = capsys.readouterr().out
        assert code == 1
        assert "rank 0: is at allreduce.collective#17" in printed
        assert "2 collective(s) issued" in printed
        assert "rank 1 (exited): was at sendrecv_ring#17" in printed


class TestChaosLaunch:
    """Chaos scenarios verified THROUGH the rollups (tier-1, jax-free
    workers driving the real recording paths): the injected straggler
    is the rank the straggler table names, a chaos-killed worker's
    fault kind lands in the rank report while the survivors' traces
    still merge, and a transient failure recovers under the launcher's
    bounded retry."""

    def test_straggler_rank_named_by_merged_rollup(self, tmp_path,
                                                   capsys):
        # HPCPAT_CHAOS (via --chaos) delays every collective on rank 1
        # by 150ms in the same pre-dispatch position the Communicator
        # hot path injects at; the merged rollup must NAME rank 1 from
        # the windows — straggler table, skew fan — and the schedule
        # verifier must stay consistent (a straggler is late, not
        # divergent). A file barrier kills process-spawn skew so the
        # injected delay dominates the timeline.
        out, log = tmp_path / "merged.json", tmp_path / "run.jsonl"
        worker = (
            "import os, time\n"
            "from hpc_patterns_tpu.harness import chaos, trace\n"
            "from hpc_patterns_tpu.analysis import runtime as rt\n"
            "pid = int(os.environ['HPCPAT_PROCESS_ID'])\n"
            "d = os.environ['HPCPAT_TRACE_DIR']\n"
            "rec = trace.TraceRecorder(enabled=True)\n"
            "rt.reset_collective_schedule()\n"
            "open(os.path.join(d, f'ready{pid}'), 'w').close()\n"
            "while not all(os.path.exists(os.path.join(d, f'ready{q}'))\n"
            "              for q in (0, 1)):\n"
            "    time.sleep(0.005)\n"
            "for seq in range(3):\n"
            "    chaos.maybe_inject('collective', seq)\n"
            "    t = rec.mark_dispatch('comm.allreduce', {'seq': seq})\n"
            "    rt.record_collective('allreduce.collective', seq,\n"
            "                         shape=(2, 8), dtype='float32',\n"
            "                         axis='x')\n"
            "    time.sleep(0.01)\n"
            "    rec.mark_complete('comm.allreduce', t, {'seq': seq})\n"
            "trace.write_rank_snapshot(rec, d)\n"
        )
        code = launch.main([
            "-np", "2", "--trace-out", str(out), "--log", str(log),
            "--trace-dir", str(tmp_path / "ranks"),
            "--chaos", "straggler:rank=1,delay_ms=150",
            "--", sys.executable, "-c", worker,
        ])
        printed = capsys.readouterr().out
        assert code == 0, printed
        assert ("straggler: rank 1 finished last in 3/3 matched "
                "collective(s)") in printed
        assert "collective schedules consistent across 2 rank(s)" in printed
        recs = [json.loads(l) for l in log.read_text().splitlines()]
        rollup = [r for r in recs if r["kind"] == "trace_merged"][0]
        assert rollup["stragglers"]["1"]["last"] == 3
        assert rollup["stragglers"]["0"]["last"] == 0
        # the skew fan carries the injected delay, not just its sign
        skew = rollup["skew"]["comm.allreduce"]
        assert skew["max_start_skew_s"] > 0.1
        assert rollup["schedule"]["verdict"] == "consistent"

    def test_worker_death_fault_kind_and_partial_merge(self, tmp_path,
                                                       capsys):
        # a chaos-killed worker (SIGKILL at collective 1 — no exit
        # handler, exactly an OOM-killed rank) must land in the rank
        # report WITH its fault kind and last collective fingerprint,
        # and the surviving rank's trace must still merge
        out, log = tmp_path / "merged.json", tmp_path / "run.jsonl"
        worker = (
            "import os, time\n"
            "from hpc_patterns_tpu.harness import chaos, trace\n"
            "from hpc_patterns_tpu.analysis import runtime as rt\n"
            "pid = int(os.environ['HPCPAT_PROCESS_ID'])\n"
            "rec = trace.TraceRecorder(enabled=True)\n"
            "rt.reset_collective_schedule()\n"
            "for seq in range(3):\n"
            "    rt.record_collective('allreduce.collective', seq)\n"
            "    chaos.maybe_inject('collective', seq)\n"
            "trace.write_rank_snapshot(rec,\n"
            "                          os.environ['HPCPAT_TRACE_DIR'])\n"
        )
        code = launch.main([
            "-np", "2", "--trace-out", str(out), "--log", str(log),
            "--trace-dir", str(tmp_path / "ranks"),
            "--chaos", "die:rank=1,at=1",
            "--", sys.executable, "-c", worker,
        ])
        printed = capsys.readouterr().out
        assert code == 1
        assert "rank 1: fault: killed (SIGKILL)" in printed
        # the progress file names the collective it died inside
        assert "rank 1: was at allreduce.collective#1" in printed
        assert "only 1/2 rank snapshot(s) harvested" in printed
        assert "FAILURE" in printed
        recs = [json.loads(l) for l in log.read_text().splitlines()]
        rollup = [r for r in recs if r["kind"] == "trace_merged"][0]
        assert rollup["n_ranks"] == 1  # the survivor merged anyway
        assert rollup["faults"] == {"0": "clean",
                                    "1": "killed (SIGKILL)"}

    def test_bad_chaos_spec_is_an_error(self, capsys):
        assert launch.main([
            "-np", "1", "--chaos", "stragler:delay_ms=1", "--",
            sys.executable, "-c", "pass",
        ]) == 2
        assert "bad --chaos spec" in capsys.readouterr().out

    def test_bounded_retry_recovers_transient_failure(self, tmp_path,
                                                      capsys):
        # each rank fails its FIRST attempt (marker file protocol) and
        # succeeds the second: --retry 1 must relaunch after backoff
        # and exit 0; without retries the same launch fails
        marker = tmp_path / "attempt"
        worker = (
            "import os, sys\n"
            f"m = {str(marker)!r} + os.environ['HPCPAT_PROCESS_ID']\n"
            "if not os.path.exists(m):\n"
            "    open(m, 'w').close()\n"
            "    sys.exit(3)\n"
            "sys.exit(0)\n"
        )
        code = launch.main([
            "-np", "2", "--retry", "1", "--retry-backoff", "0.1",
            "--", sys.executable, "-c", worker,
        ])
        printed = capsys.readouterr().out
        assert code == 0, printed
        assert "rank 0: fault: exit 3" in printed
        assert "retrying launch (attempt 2/2)" in printed
        assert "FAILURE" in printed and "SUCCESS" in printed


class TestDistributedTraceMerge:
    """The rung-4 acceptance, tier-1: ONE 2-process launch of the
    allreduce miniapp under --trace must produce a Perfetto-valid
    merged timeline with one pid lane per rank, flow events linking the
    two ranks' windows of each timed collective, a skew/straggler
    rollup on stdout, and a kind=trace_merged record harness.report
    renders."""

    @pytest.fixture(scope="class")
    def merged_run(self, tmp_path_factory):
        tmp = tmp_path_factory.mktemp("dtrace")
        out, log = tmp / "merged.json", tmp / "run.jsonl"
        import io
        from contextlib import redirect_stdout

        buf = io.StringIO()
        with redirect_stdout(buf):
            code = launch.main([
                "-np", "2", "--cpu-devices-per-proc", "1",
                "--trace-out", str(out), "--log", str(log), "--",
                sys.executable, "-m",
                "hpc_patterns_tpu.apps.allreduce_app", "-p", "8",
                "--repetitions", "3", "--warmup", "1", "--trace",
            ])
        return code, out, log, buf.getvalue()

    def test_exit_0_and_rollup_printed(self, merged_run):
        code, _out, _log, printed = merged_run
        assert code == 0, printed
        assert "max start skew" in printed
        assert "clock align: sync" in printed  # barrier anchor taken

    def test_collective_schedules_verified_consistent(self, merged_run):
        # the desync check, clean side: both ranks' fingerprint chains
        # (analysis/runtime.py) carry the same digest, so the merge
        # PROVES the rank schedules matched rather than assuming SPMD
        code, _out, log, printed = merged_run
        assert code == 0, printed
        assert "collective schedules consistent across 2 rank(s)" in printed
        recs = [json.loads(l) for l in log.read_text().splitlines()]
        sched = [r for r in recs
                 if r["kind"] == "trace_merged"][0]["schedule"]
        assert sched["verdict"] == "consistent"
        assert sched["n_ranks_recorded"] == 2
        assert sched["n_collectives"] >= 3  # the timed reps at least
        assert sched["digest"]

    def test_merged_json_is_perfetto_valid_with_2_lanes(self, merged_run):
        code, out, _log, printed = merged_run
        assert code == 0, printed
        chrome = json.loads(out.read_text())  # strict JSON
        evs = chrome["traceEvents"]
        assert {e["pid"] for e in evs if e["ph"] != "M"} == {0, 1}
        names = {e["args"]["name"] for e in evs
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert names == {"rank 0/2", "rank 1/2"}
        # B/E pairs stay balanced per (pid, tid) lane after the merge
        stacks = {}
        for e in evs:
            if e["ph"] == "B":
                stacks.setdefault((e["pid"], e["tid"]), []).append(e["name"])
            elif e["ph"] == "E":
                assert stacks[(e["pid"], e["tid"])].pop() == e["name"]
        assert all(not s for s in stacks.values())

    def test_flow_events_link_collective_pairs(self, merged_run):
        code, out, _log, printed = merged_run
        assert code == 0, printed
        evs = json.loads(out.read_text())["traceEvents"]
        flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
        assert flows, "no flow events in merged trace"
        by_id = {}
        for e in flows:
            by_id.setdefault(e["id"], []).append(e)
        crossing = [c for c in by_id.values()
                    if len({e["pid"] for e in c}) >= 2]
        assert crossing, "no flow chain crosses rank lanes"

    def test_report_renders_the_desync_verdict(self, merged_run, capsys):
        code, _out, log, printed = merged_run
        assert code == 0, printed
        from hpc_patterns_tpu.harness import report

        assert report.main([str(log)]) == 0
        out = capsys.readouterr().out
        assert "schedules consistent" in out

    def test_trace_merged_record_and_report(self, merged_run, capsys):
        code, _out, log, printed = merged_run
        assert code == 0, printed
        recs = [json.loads(l) for l in log.read_text().splitlines()]
        merged = [r for r in recs if r["kind"] == "trace_merged"]
        assert len(merged) == 1
        rec = merged[0]
        assert rec["n_ranks"] == 2 and rec["n_matched"] >= 1
        assert rec["align"]["method"] == "sync"
        assert "allreduce" in " ".join(rec["skew"])
        from hpc_patterns_tpu.harness import report

        assert report.main([str(log)]) == 0
        out = capsys.readouterr().out
        assert "trace_merged: 2 rank(s)" in out


class TestServingPlaneLaunch:
    """The launched serving plane (round 10), stub tier: real launcher
    processes, real sockets, real trace/schedule recording — stub
    token generators, so the router's mechanics (placement, KV-handoff
    forwarding, replica death recovery, shed accounting) run tier-1 in
    seconds."""

    def test_disaggregated_stub_plane_traced_merge(self, tmp_path,
                                                   capsys):
        # router + 1 prefill + 1 decode replica: the launch must exit
        # 0 with the stub oracle green, the merged trace must carry
        # the verdict "consistent" (donor and receiver fingerprinted
        # the identical kv_migration schedule), and the KV-handoff
        # flow arrows must thread the two replica LANES
        out, log = tmp_path / "merged.json", tmp_path / "run.jsonl"
        code = launch.main([
            "-np", "3", "--timeout", "60",
            "--trace-out", str(out), "--log", str(log), "--",
            sys.executable, "-m", "hpc_patterns_tpu.apps.plane_app",
            "--stub", "--roles", "prefill,decode",
            "--rdv", str(tmp_path / "rdv"), "--requests", "6",
            "--rate", "10000", "--trace",
        ])
        printed = capsys.readouterr().out
        assert code == 0, printed
        assert "PLANE SUCCESS" in printed
        assert "migrations=6" in printed
        assert "collective schedules consistent across 2 rank(s)" \
            in printed
        merged = json.loads(out.read_text())
        flows = [e for e in merged["traceEvents"]
                 if e.get("cat") == "collective"
                 and e.get("name") == "plane.kv_migration"]
        assert {e["ph"] for e in flows} == {"s", "f"}
        assert len({e["pid"] for e in flows}) == 2  # two replica lanes
        windows = [e for e in merged["traceEvents"]
                   if e.get("name") == "plane.kv_migration"
                   and e.get("ph") == "X"]
        assert len({e["pid"] for e in windows}) == 2
        recs = [json.loads(line)
                for line in log.read_text().splitlines()]
        sched = [r for r in recs
                 if r["kind"] == "trace_merged"][0]["schedule"]
        assert sched["verdict"] == "consistent"
        assert sched["n_collectives"] == 6

    def test_replica_death_resumes_on_survivors(self, tmp_path,
                                                capsys):
        # die chaos targets ONE replica of three (site=replica_round);
        # the router must re-queue its in-flight requests as resumes
        # on survivors — byte-checked by the stub oracle — with the
        # lost replica named in the rank report and on the
        # trace_merged record, and nothing shed silently. The stream
        # is SAMPLED (round 14, the PR 9 remainder): stub tokens come
        # from an evolving per-row key CHAIN, the round replies
        # checkpoint the chain state, and the router hands it back on
        # the death-resume — the oracle walks the chain from key_0,
        # so a resume that LOST the key restarts the chain and
        # diverges at its first resumed token (teeth; the greedy stub
        # oracle stays covered by the disaggregated test above)
        out, log = tmp_path / "merged.json", tmp_path / "run.jsonl"
        code = launch.main([
            "-np", "4", "--timeout", "60",
            "--chaos", "die:replica=2,at=3,site=replica_round",
            "--trace-out", str(out), "--log", str(log), "--",
            sys.executable, "-m", "hpc_patterns_tpu.apps.plane_app",
            "--stub", "--roles", "both,both,both",
            "--rdv", str(tmp_path / "rdv"), "--requests", "9",
            "--rate", "10000", "--budget", "16",
            "--temperature", "0.7", "--trace",
        ])
        printed = capsys.readouterr().out
        assert code == 1  # a rank died: the launch fails loudly...
        assert "PLANE SUCCESS" in printed  # ...but the PLANE recovered
        assert "replica 2 died" in printed
        assert "deaths=[2]" in printed
        # every re-queued stream finished byte-exact (the stub oracle
        # inside PLANE SUCCESS) and nothing was dropped silently:
        # served + shed must account for all 9
        assert "served 9/9" in printed
        assert "resumed=[" in printed and "resumed=[]" not in printed
        # the rank report names the lost replica with its fault kind
        assert "rank 2: fault: killed (SIGKILL)" in printed
        recs = [json.loads(line)
                for line in log.read_text().splitlines()]
        tm = [r for r in recs if r["kind"] == "trace_merged"][0]
        assert tm["faults"]["2"] == "killed (SIGKILL)"
        assert tm["faults"]["0"] == "clean"
