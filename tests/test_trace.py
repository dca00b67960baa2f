"""Tests for the flight recorder (harness/trace.py).

The timeline contract: ring-buffer overflow keeps the NEWEST events
with B/E pairs still balanced, exports are valid Chrome-trace JSON
(every B matched, per-thread timestamps monotonic), the compile
watcher stamps a forced recompile exactly once, and the disabled path
allocates nothing per span (the same no-op guard discipline as
tests/test_metrics.py — the tier-1 protection).
"""

import json
import time

import pytest

from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness import trace as tracelib
from hpc_patterns_tpu.harness.trace import TraceRecorder


@pytest.fixture(autouse=True)
def _fresh_state():
    # the production default: no recorder, disabled registry — never
    # leak enablement into other tests
    yield
    tracelib.configure(enabled=False)
    metricslib.configure(enabled=False)


def _spans(chrome):
    return [e for e in chrome["traceEvents"]
            if e.get("cat") == "span"]


class TestRingBuffer:
    def test_overflow_keeps_newest_events(self):
        rec = TraceRecorder(capacity=10, mem_interval_s=float("inf"))
        for i in range(40):
            rec.span_begin(f"s{i}", {})
            rec.span_end(f"s{i}")  # 2 events per span, 80 total
        assert len(rec.events) == 10
        assert rec.n_events == 80
        names = {ev[2] for ev in rec.events}
        # the newest span survives, the oldest is long gone
        assert "s39" in names
        assert "s0" not in names
        assert rec.snapshot()["n_dropped"] == 70

    def test_balanced_export_across_eviction_edge(self):
        # evict an outer B while keeping its E: the orphan E must not
        # reach the export (Perfetto rejects unmatched ends)
        rec = TraceRecorder(capacity=4)
        rec.span_begin("outer", {})
        rec.span_begin("inner", {})
        rec.span_end("inner")
        rec.span_begin("tail", {})
        rec.span_end("tail")
        rec.span_end("outer")  # outer's B was evicted by now
        spans = _spans(rec.to_chrome())
        b = [e["name"] for e in spans if e["ph"] == "B"]
        e = [e["name"] for e in spans if e["ph"] == "E"]
        assert sorted(b) == sorted(e)
        assert "outer" not in b  # dropped whole, not half

    def test_open_span_synthesizes_end(self):
        rec = TraceRecorder(capacity=16)
        rec.span_begin("still_open", {})
        spans = _spans(rec.to_chrome())
        assert [e["ph"] for e in spans] == ["B", "E"]
        assert spans[1]["ts"] >= spans[0]["ts"]

    def test_overlapping_device_windows_use_subtracks(self):
        # admission windows overlap the decode chunk by design; Chrome
        # sync slices on ONE track must nest, so concurrent windows go
        # to per-slot subtracks and the export labels them distinctly
        rec = TraceRecorder(capacity=64)
        t_chunk = rec.mark_dispatch("serve.chunk", track=0)
        t_admit = rec.mark_dispatch("serve.admit", track=1)
        rec.mark_complete("serve.chunk", t_chunk, track=0)
        rec.mark_complete("serve.admit", t_admit, track=1)  # overlaps
        chrome = rec.to_chrome()
        xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
        assert len({e["tid"] for e in xs}) == 2
        labels = {e["args"]["name"] for e in chrome["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "thread_name"}
        assert "device (dispatch→completion)" in labels
        assert "device (admit slot 0)" in labels


class TestChromeExport:
    def test_export_is_valid_chrome_trace(self, tmp_path):
        rec = tracelib.configure(enabled=True)
        m = metricslib.configure(enabled=True)
        with m.span("outer", chunk=4):
            with m.span("inner"):
                time.sleep(0.001)
        t0 = rec.mark_dispatch("work", {"n": 1})
        rec.mark_complete("work", t0)
        rec.compile_event("fn", 0.01, args={"shapes": ["f32[2]"]})
        rec.counter("mem", {"live_bytes": 123.0})
        path = rec.export(tmp_path / "t.trace.json")
        chrome = json.loads(path.read_text())  # strict JSON
        evs = chrome["traceEvents"]
        # every B has a matching E, LIFO order per thread
        stacks = {}
        for e in evs:
            if e["ph"] == "B":
                stacks.setdefault(e["tid"], []).append(e["name"])
            elif e["ph"] == "E":
                assert stacks[e["tid"]].pop() == e["name"]
        assert all(not s for s in stacks.values())
        # timestamps monotonic per thread, nonnegative microseconds
        by_tid = {}
        for e in evs:
            if e["ph"] == "M":
                continue
            assert e["ts"] >= 0
            assert e["ts"] >= by_tid.get(e["tid"], 0.0)
            by_tid[e["tid"]] = e["ts"]
        # the four tracks are distinct: host spans, device, compile,
        # memory counters
        cats = {e.get("cat") for e in evs if e["ph"] != "M"}
        assert {"span", "device", "compile", "counter"} <= cats
        tids = {e.get("cat"): e["tid"] for e in evs if e["ph"] != "M"}
        assert len(set(tids.values())) == 4
        # X slices carry durations; the counter carries its value
        xs = [e for e in evs if e["ph"] == "X"]
        assert xs and all("dur" in e for e in xs)
        c = next(e for e in evs if e["ph"] == "C")
        assert c["args"]["live_bytes"] == 123.0

    def test_cli_roundtrip_from_runlog(self, tmp_path, capsys):
        from hpc_patterns_tpu.harness.runlog import RunLog

        rec = tracelib.configure(enabled=True)
        m = metricslib.configure(enabled=True)
        with m.span("phase"):
            pass
        log = RunLog(tmp_path / "run.jsonl")
        log.emit(kind="trace", **rec.snapshot())
        out = tmp_path / "out.trace.json"
        assert tracelib.main([str(tmp_path / "run.jsonl"),
                              "-o", str(out)]) == 0
        chrome = json.loads(out.read_text())
        names = [e["name"] for e in chrome["traceEvents"]
                 if e.get("cat") == "span"]
        assert names == ["phase", "phase"]
        capsys.readouterr()

    def test_cli_no_trace_records_errors(self, tmp_path, capsys):
        (tmp_path / "empty.jsonl").write_text(
            '{"kind": "result", "success": true}\n')
        assert tracelib.main([str(tmp_path / "empty.jsonl")]) == 2
        capsys.readouterr()

    def test_cli_multi_file_gets_distinct_pid_lanes(self, tmp_path,
                                                    capsys):
        # two runlogs from two (single-process) runs must NOT collapse
        # onto one pid lane — each source file gets its own, labeled
        from hpc_patterns_tpu.harness.runlog import RunLog

        m = metricslib.configure(enabled=True)
        files = []
        for name in ("a.jsonl", "b.jsonl"):
            rec = tracelib.configure(enabled=True)
            with m.span("phase"):
                pass
            log = RunLog(tmp_path / name)
            log.emit(kind="trace", **rec.snapshot())
            files.append(str(tmp_path / name))
        out = tmp_path / "multi.trace.json"
        assert tracelib.main([*files, "-o", str(out)]) == 0
        capsys.readouterr()
        chrome = json.loads(out.read_text())
        meta = [e for e in chrome["traceEvents"]
                if e["ph"] == "M" and e["name"] == "process_name"]
        assert len({e["pid"] for e in meta}) == 2
        assert {e["args"]["name"] for e in meta} == \
            {"a.jsonl", "b.jsonl"}
        spans = [e for e in chrome["traceEvents"]
                 if e.get("cat") == "span"]
        assert len({e["pid"] for e in spans}) == 2


class TestCompileWatcher:
    def test_forced_recompile_counted_exactly_once(self):
        import jax
        import jax.numpy as jnp

        rec = tracelib.configure(enabled=True)

        f = jax.jit(lambda x: x * 2)
        with tracelib.compile_watch("unit.f", f, tag="a"):
            f(jnp.ones((3,)))
        first = rec.compile_count
        assert first >= 1  # the explicit hook; the jax.monitoring
        # listener may add backend events on top
        hook_events = [ev for ev in rec.events
                       if ev[1] == "compile" and ev[2] == "unit.f"]
        assert len(hook_events) == 1
        assert hook_events[0][6]["new_variants"] == 1

        # warm call: same shape, NO new compile event
        with tracelib.compile_watch("unit.f", f, tag="a"):
            f(jnp.ones((3,)))
        assert len([ev for ev in rec.events
                    if ev[1] == "compile" and ev[2] == "unit.f"]) == 1

        # forced recompile: new shape grows the cache — exactly one
        # more hook event
        with tracelib.compile_watch("unit.f", f, tag="b"):
            f(jnp.ones((5,)))
        hook_events = [ev for ev in rec.events
                       if ev[1] == "compile" and ev[2] == "unit.f"]
        assert len(hook_events) == 2

    def test_instrument_jit_records_shapes(self):
        import jax
        import jax.numpy as jnp

        rec = tracelib.configure(enabled=True)
        g = tracelib.instrument_jit(jax.jit(lambda x: x + 1), "unit.g")
        g(jnp.ones((4,)))
        g(jnp.ones((4,)))  # warm
        events = [ev for ev in rec.events
                  if ev[1] == "compile" and ev[2] == "unit.g"]
        assert len(events) == 1
        assert events[0][6]["shapes"] == ["float32[4]"]

    def test_prefill_cache_size_uses_shared_probe(self):
        from hpc_patterns_tpu.models import serving

        n = serving.prefill_cache_size()
        assert n == tracelib.jit_cache_size(serving._prefill_one)
        assert isinstance(n, int)

    def test_strict_probe_raises_on_missing_cache_size(self):
        # the bucket-ladder assertions gate on this count and 0 reads
        # as success — a vanished probe must raise, not return 0
        def not_jitted():
            pass

        assert tracelib.jit_cache_size(not_jitted) == 0
        with pytest.raises(AttributeError):
            tracelib.jit_cache_size(not_jitted, strict=True)

    def test_one_compile_counted_once_in_rollup(self):
        # the same compilation is seen by BOTH the backend listener
        # and the named hook; only the listener bumps the rollup, so
        # report.py's "N compiles" is the true XLA compile count
        import jax
        import jax.numpy as jnp

        rec = tracelib.configure(enabled=True)
        f = jax.jit(lambda x: x * 3)
        with tracelib.compile_watch("unit.once", f):
            f(jnp.ones((6,)))
        hook = [ev for ev in rec.events
                if ev[1] == "compile" and ev[2] == "unit.once"]
        backend = [ev for ev in rec.events
                   if ev[2] == "xla.backend_compile"]
        assert len(hook) == 1 and len(backend) >= 1
        # rollup == backend events, hook slices are annotations
        assert rec.compile_count == len(backend)

    def test_monitoring_listener_feeds_recorder(self):
        import jax
        import jax.numpy as jnp

        rec = tracelib.configure(enabled=True)
        jax.jit(lambda x: x - 7)(jnp.ones((2,)))
        backend = [ev for ev in rec.events
                   if ev[2] == "xla.backend_compile"]
        assert backend  # the process-wide listener saw the compile


class TestDisabledPath:
    def test_disabled_span_is_shared_nullcontext(self):
        tracelib.configure(enabled=False)
        m = metricslib.configure(enabled=False)
        # trace off + metrics off: span() must return the SAME object
        # every call — the no-op fast path allocates nothing per span
        assert m.span("x") is m.span("y")

    def test_disabled_compile_watch_is_shared_nullcontext(self):
        tracelib.configure(enabled=False)
        assert tracelib.compile_watch("a", None) is \
            tracelib.compile_watch("b", None)

    def test_disabled_recorder_records_nothing(self):
        rec = tracelib.configure(enabled=False)
        m = metricslib.configure(enabled=True)  # metrics alone
        with m.span("s"):
            pass
        assert rec.n_events == 0
        assert tracelib.active() is None

    def test_trace_without_metrics_records_events_not_histograms(self):
        rec = tracelib.configure(enabled=True)
        m = metricslib.configure(enabled=False)
        with m.span("only_traced"):
            pass
        assert m.snapshot()["histograms"] == {}
        assert any(ev[2] == "only_traced" for ev in rec.events)

    def test_configure_detaches_sink(self):
        tracelib.configure(enabled=True)
        assert metricslib._trace_sink is not None
        tracelib.configure(enabled=False)
        assert metricslib._trace_sink is None


class TestRunInstrumented:
    def test_trace_flag_appends_kind_trace_record(self, tmp_path):
        import argparse

        from hpc_patterns_tpu.apps import common
        from hpc_patterns_tpu.harness.runlog import RunLog

        path = tmp_path / "app.jsonl"
        args = argparse.Namespace(metrics=False, trace=True,
                                  trace_capacity=None, log=str(path))

        def fake_app(a):
            with metricslib.span("app.phase"):
                pass
            RunLog(a.log, truncate=not a.log_append).emit(
                kind="result", name="app", success=True)
            return 0

        assert common.run_instrumented(fake_app, args) == 0
        records = [json.loads(l)
                   for l in path.read_text().splitlines()]
        assert [r["kind"] for r in records] == [
            "device", "result", "kernels", "trace"]
        trace_rec = records[-1]
        assert trace_rec["by_cat"].get("span", 0) >= 2
        # the record is itself exportable
        chrome = tracelib.chrome_from_snapshots([trace_rec])
        assert any(e["name"] == "app.phase"
                   for e in chrome["traceEvents"])

    def test_no_flags_appends_nothing(self, tmp_path):
        import argparse

        from hpc_patterns_tpu.apps import common
        from hpc_patterns_tpu.harness.runlog import RunLog

        path = tmp_path / "app.jsonl"
        args = argparse.Namespace(metrics=False, trace=False,
                                  trace_capacity=None, log=str(path))

        def fake_app(a):
            RunLog(a.log, truncate=not a.log_append).emit(
                kind="result", name="app", success=True)
            return 0

        assert common.run_instrumented(fake_app, args) == 0
        kinds = [json.loads(l)["kind"]
                 for l in path.read_text().splitlines()]
        assert kinds == ["device", "result", "kernels"]


class TestDistributedHandoff:
    """The per-rank capture protocol (rung 4's capture half): snapshots
    carry process identity + dual clock anchors + sync anchors, and a
    traced child under HPCPAT_TRACE_DIR hands its ring to the launcher
    as rank<id>.trace.json (the merge half lives in test_collect.py)."""

    def test_snapshot_carries_process_and_dual_clock_anchors(self):
        rec = TraceRecorder(capacity=8)
        snap = rec.snapshot()
        proc = snap["process"]
        assert proc["process_id"] == 0 and proc["num_processes"] == 1
        c = snap["clock"]
        assert c["mono1"] >= c["mono0"] and c["wall1"] >= c["wall0"]
        # the two anchor pairs agree on the offset (same clocks here)
        assert (c["wall1"] - c["mono1"]) == pytest.approx(
            c["wall0"] - c["mono0"], abs=0.05)

    def test_snapshot_reads_launcher_env_protocol(self, monkeypatch):
        monkeypatch.setenv("HPCPAT_PROCESS_ID", "3")
        monkeypatch.setenv("HPCPAT_NUM_PROCESSES", "4")
        monkeypatch.setenv("HPCPAT_SLICE_GROUPING", "process:0,0,1,1")
        snap = TraceRecorder(capacity=8).snapshot()
        assert snap["process"] == {"process_id": 3, "num_processes": 4,
                                   "slice_id": 1}

    def test_mark_sync_anchors_survive_eviction(self):
        rec = TraceRecorder(capacity=2)
        rec.mark_sync("make_communicator")
        for i in range(10):  # overflow the ring
            rec.span_begin(f"s{i}", {})
            rec.span_end(f"s{i}")
        snap = rec.snapshot()
        assert len(snap["sync"]) == 1
        assert snap["sync"][0]["name"] == "make_communicator"
        assert snap["sync"][0]["mono"] <= snap["clock"]["mono1"]

    def test_write_rank_snapshot(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HPCPAT_PROCESS_ID", "1")
        monkeypatch.setenv("HPCPAT_NUM_PROCESSES", "2")
        rec = TraceRecorder(capacity=8)
        rec.span_begin("x", {})
        rec.span_end("x")
        path = tracelib.write_rank_snapshot(rec, tmp_path)
        assert path == tmp_path / "rank00001.trace.json"
        snap = json.loads(path.read_text())
        assert snap["kind"] == "trace"
        assert snap["process"]["process_id"] == 1
        assert len(snap["events"]) == 2

    def test_run_instrumented_hands_off_under_env(self, tmp_path,
                                                  monkeypatch):
        import argparse

        from hpc_patterns_tpu.apps import common

        monkeypatch.setenv("HPCPAT_TRACE_DIR", str(tmp_path))
        args = argparse.Namespace(metrics=False, trace=True,
                                  trace_capacity=None, log=None)
        assert common.run_instrumented(lambda a: 0, args) == 0
        files = list(tmp_path.glob("rank*.trace.json"))
        assert len(files) == 1

    def test_no_handoff_without_trace_flag(self, tmp_path, monkeypatch):
        import argparse

        from hpc_patterns_tpu.apps import common

        monkeypatch.setenv("HPCPAT_TRACE_DIR", str(tmp_path))
        args = argparse.Namespace(metrics=False, trace=False,
                                  trace_capacity=None, log=None)
        assert common.run_instrumented(lambda a: 0, args) == 0
        assert list(tmp_path.glob("rank*.trace.json")) == []


class TestMemorySampling:
    def test_sample_memory_records_counter(self):
        import jax.numpy as jnp

        rec = tracelib.configure(enabled=True)
        keep = jnp.ones((128,))  # noqa: F841 — held live on purpose
        sample = rec.sample_memory()
        assert sample is not None
        assert sample["live_bytes"] >= keep.nbytes
        assert rec.peak_live_bytes >= keep.nbytes
        counters = [ev for ev in rec.events if ev[0] == "C"]
        assert counters

    def test_record_executable_memory(self):
        import jax
        import jax.numpy as jnp

        rec = tracelib.configure(enabled=True)
        compiled = jax.jit(lambda x: x @ x).lower(
            jnp.ones((8, 8))).compile()
        vals = tracelib.record_executable_memory("unit.mm", compiled)
        if vals is None:
            pytest.skip("backend has no memory_analysis")
        assert any(ev[2] == "exec_mem.unit.mm" for ev in rec.events)


class TestMaybeTraceRestoration:
    def test_maybe_trace_restores_on_raise(self, tmp_path):
        # the satellite guarantee: an exception inside the traced
        # region must not leave the global registry permanently
        # mirroring spans into TraceAnnotations
        from hpc_patterns_tpu.harness.profiling import maybe_trace

        m = metricslib.configure(enabled=False)
        assert m.mirror_traces is False
        with pytest.raises(RuntimeError):
            with maybe_trace(True, str(tmp_path / "tr")):
                assert m.mirror_traces is True
                raise RuntimeError("boom inside traced region")
        assert m.mirror_traces is False

    def test_maybe_trace_restores_preexisting_true(self, tmp_path):
        from hpc_patterns_tpu.harness.profiling import maybe_trace

        m = metricslib.configure(enabled=False)
        m.mirror_traces = True  # e.g. an enclosing trace
        with maybe_trace(True, str(tmp_path / "tr")):
            pass
        assert m.mirror_traces is True
