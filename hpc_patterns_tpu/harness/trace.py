"""Flight recorder: per-event trace timeline with Chrome-trace export.

The metrics registry (harness/metrics.py) aggregates phase times into
fixed-bucket histograms — a snapshot can say the admission bubble is
12% of the run, but not WHICH decode chunk it landed on or whether a
recompile caused it. This module is the next observability rung: a
bounded ring-buffer recorder of individual timestamped events, ordered
in time, with compile and memory causes attached — the per-event
timeline that overlap attribution needs (PAPERS.md: stream-aware
message passing analyzes overlap from event timelines, not summary
statistics).

Event sources, all zero-cost when disabled:

- **spans** — every ``Metrics.span()`` begin/end feeds the recorder
  when one is installed (the existing instrumentation points become
  timeline tracks for free); nesting paths and attrs ride along.
- **device markers** — dispatch vs. completion instants from the
  serving engine's chunk loop (``ContinuousBatcher._dispatch_chunk`` /
  ``_resolve_pending``) and the eager ``Communicator`` collectives, so
  host bubbles are visually separable from device time: the window
  between a dispatch marker and its completion is drawn as a slice on
  a synthetic "device" track.
- **compile events** — a process-wide ``jax.monitoring`` duration
  listener (every trace, lowering, backend compile and cache load, kept
  in the bounded :func:`compile_events` log and mirrored as ``jit.event``
  marker spans; the recorder counts the backend compiles) plus
  explicit :func:`compile_watch` / :func:`instrument_jit` hooks at the
  jit entry points (models/decode.py, models/serving.py,
  models/train.py) that attach the FUNCTION NAME and triggering arg
  shapes a bare backend event cannot know. ``serving.prefill_cache_
  size()`` consumes the same :func:`jit_cache_size` probe.
- **memory samples** — per-device live-buffer bytes via
  ``jax.live_arrays()`` at span boundaries (throttled), plus
  compiled-executable ``memory_analysis()`` peaks where the backend
  supports it (:func:`record_executable_memory`).

The ring buffer is bounded (``capacity`` events, oldest evicted), so a
long serving run records its most recent window instead of growing
without bound; the export pass re-balances B/E pairs across the
eviction edge so the JSON is always loadable.

Export is ``chrome://tracing`` JSON (Perfetto-loadable): spans as B/E
pairs on per-thread tracks, device windows and compiles as complete
(X) slices on their own tracks, memory as Counter events. Two routes:

- live: ``TraceRecorder.export(path)`` (serve_app ``--trace-out``);
- offline: the recorder's snapshot lands as one ``kind=trace`` RunLog
  record (apps/common.run_instrumented, under ``--trace --log``), and
  ``python -m hpc_patterns_tpu.harness.trace run.jsonl -o out.json``
  rebuilds the Chrome JSON from it; ``harness.report`` summarizes the
  same records;
- distributed: a traced child of apps/launch.py also writes the
  snapshot to the launcher-provided ``HPCPAT_TRACE_DIR``
  (:func:`write_rank_snapshot`; stamped with process identity, dual
  clock anchors, and barrier sync anchors), and harness/collect.py
  merges every rank's ring into ONE clock-aligned timeline with
  cross-rank skew/straggler rollups — rung 4 of the ladder.

Like metrics.py, this module is jax-free at import time: jax is only
touched inside enabled-path helpers (memory sampling, the monitoring
listener), so the disabled path costs one module-global None check.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import threading
import time
from collections import deque
from pathlib import Path
from typing import Any

from hpc_patterns_tpu.harness import metrics as metricslib

# Synthetic track ids for events that are not host-thread work; real
# thread ids are far below this range on Linux (pids) and far above on
# macOS — collisions only relabel a track, never corrupt events.
# Device windows get SUBTRACKS (TID_DEVICE + track): Chrome-trace sync
# slices on one tid must nest properly, and overlapped admissions are
# concurrent with the decode chunk BY DESIGN — each admission slot
# renders on its own subtrack so overlapping windows stay valid.
TID_DEVICE = 1 << 20
TID_COMPILE = 1 << 21
TID_COUNTER = (1 << 21) + 1
# Request lifecycle lanes (round 18, harness/reqtrace.py): one
# subtrack PER REQUEST (TID_REQUEST + seq_id), each tiled wall-to-wall
# with that request's lifecycle segments — the Perfetto view of the
# coverage invariant, threaded by flow arrows into the migration/
# device windows at merge time (harness/collect.py).
TID_REQUEST = 1 << 22

# The single declared source of device-SUBTRACK bands (offsets added
# to TID_DEVICE): ``name -> (base, count)``, half-open width. Every
# module that owns a band unpacks it with :func:`track_band` instead
# of hand-picking integers — contractlint's ``track-band-collision``
# flags literal ``*_TRACK_BASE`` assignments and out-of-band
# ``track=`` literals, the same registry discipline pallaslint
# applies to collective ids. Bands: the decode chunk itself, the
# overlapped-admission slots (one per admit row), the KV-migration
# lanes (serving_plane/service.py), the warm spin-up lanes
# (serving_plane/autoscaler.py), and the host<->HBM residency lanes
# (memory/residency.py).
TRACK_BANDS: dict[str, tuple[int, int]] = {
    "decode": (0, 1),
    "admit": (1, 63),
    "migration": (64, 8),
    "spinup": (72, 8),
    "residency": (80, 8),
}


def track_band(name: str) -> tuple[int, int]:
    """``(base, count)`` for a declared subtrack band; the ONLY
    sanctioned way for a module to learn its band's offsets."""
    return TRACK_BANDS[name]


def _track_label(tid: int) -> str:
    if tid == TID_COMPILE:
        return "compile"
    if tid == TID_COUNTER:
        return "memory"
    if tid == TID_DEVICE:
        return "device (dispatch→completion)"
    if TID_DEVICE < tid < TID_COMPILE:
        track = tid - TID_DEVICE
        for name, (base, count) in TRACK_BANDS.items():
            if base <= track < base + count:
                # admit keeps its historic "slot" wording (slot N
                # rides subtrack N+1; track 0 is the decode chunk)
                if name == "admit":
                    return f"device (admit slot {track - base})"
                return f"device ({name} lane {track - base})"
        return f"device (subtrack {track})"
    if tid >= TID_REQUEST:
        return f"request {tid - TID_REQUEST}"
    return f"host thread {tid}"

DEFAULT_CAPACITY = 16384


class TraceRecorder:
    """Bounded ring-buffer event recorder.

    Events are compact tuples ``(ph, cat, name, ts, tid, dur, args)``:
    ``ph`` is the Chrome phase (B/E/i/X/C), ``cat`` the event kind
    (span/device/compile/counter), ``ts`` a ``time.perf_counter``
    stamp, ``dur`` only for X slices. ``t0_wall``/``t0_mono`` anchor
    the monotonic stamps to wall time so exports can be correlated
    with log timestamps.
    """

    def __init__(self, *, enabled: bool = True,
                 capacity: int = DEFAULT_CAPACITY,
                 mem_interval_s: float = 0.05):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.enabled = enabled
        self.capacity = capacity
        self.events: deque = deque(maxlen=capacity)
        self.n_events = 0  # total recorded, incl. evicted
        self.t0_wall = time.time()
        self.t0_mono = time.perf_counter()
        self.mem_interval_s = mem_interval_s
        # first sample one interval after construction, not at t=0
        self._last_mem_sample = self.t0_mono
        self._lock = threading.Lock()
        # cross-rank alignment anchors: monotonic stamps taken right
        # after a moment all ranks agree is (near-)simultaneous — a
        # barrier exit (apps/common.make_communicator records one).
        # They survive ring eviction like the rollup counters.
        self.sync_anchors: list[dict[str, Any]] = []
        # rollup counters that survive ring eviction (the snapshot's
        # summary must not shrink when old events fall off the ring)
        self.compile_count = 0
        self.compile_total_s = 0.0
        self.peak_live_bytes = 0

    # -- primitive ---------------------------------------------------------

    def _push(self, ph: str, cat: str, name: str, ts: float, tid: int,
              dur: float | None = None,
              args: dict[str, Any] | None = None) -> None:
        self.events.append((ph, cat, name, ts, tid, dur, args))
        self.n_events += 1

    # -- span feed (installed as metrics._trace_sink) ----------------------

    def span_begin(self, path: str, attrs: dict[str, Any],
                   ts: float | None = None) -> None:
        self._push("B", "span", path,
                   time.perf_counter() if ts is None else ts,
                   threading.get_ident(),
                   args=dict(attrs) if attrs else None)

    def span_end(self, path: str, ts: float | None = None) -> None:
        self._push("E", "span", path,
                   time.perf_counter() if ts is None else ts,
                   threading.get_ident())
        self.maybe_sample_memory()

    # -- device markers ----------------------------------------------------

    def mark_dispatch(self, name: str,
                      args: dict[str, Any] | None = None,
                      track: int = 0) -> float:
        """Instant marker: device work for ``name`` was enqueued NOW
        (async dispatch — the device may start later). Returns the
        stamp to hand to :meth:`mark_complete`. ``track`` selects a
        device SUBTRACK (``TID_DEVICE + track``): windows that may
        overlap in time — an admission's window (dispatch to its first
        token's readback) and a decode chunk's — must live on
        different subtracks, because
        Chrome-trace sync slices on one track must nest."""
        ts = time.perf_counter()
        self._push("i", "device", f"{name}.dispatch", ts,
                   TID_DEVICE + track, args=args)
        return ts

    def mark_complete(self, name: str, t_dispatch: float,
                      args: dict[str, Any] | None = None,
                      track: int = 0) -> None:
        """Completion observed (a readback or block_until_ready
        resolved): draw the dispatch→completion window as one slice on
        the device (sub)track. Host gaps BETWEEN these slices are
        bubbles. Pass the same ``track`` as the dispatch."""
        ts = time.perf_counter()
        self._push("X", "device", name, t_dispatch, TID_DEVICE + track,
                   dur=ts - t_dispatch, args=args)

    def mark_request_segment(self, seq_id: int, kind: str, t0: float,
                             t1: float,
                             args: dict[str, Any] | None = None
                             ) -> None:
        """One finished lifecycle segment on a request's own lane
        (``TID_REQUEST + seq_id``) — reqtrace mirrors a request's
        whole history here at finish, so the per-request tiling is a
        first-class Perfetto track next to the device windows it
        explains. Retrospective X slices: both stamps are ordinary
        host perf_counter instants already taken by the stamp sites
        (no clock read, no readback — this runs inside the serving
        loop's finish path)."""
        self._push("X", "request", kind, t0,
                   TID_REQUEST + int(seq_id), dur=t1 - t0,
                   args={**(args or {}), "seq_id": int(seq_id)})

    def mark_sync(self, name: str) -> float:
        """Record a cross-rank sync anchor: call this immediately after
        a global barrier returns. All ranks exit a barrier within a
        small window (bounded by its release propagation), so their
        anchors of the same name+index are treated as simultaneous by
        the cross-rank merge (harness/collect.py), tightening clock
        alignment beyond what wall-clock anchors give on hosts with
        skewed clocks. Returns the monotonic stamp."""
        ts = time.perf_counter()
        self.sync_anchors.append({"name": name, "mono": ts})
        return ts

    # -- compile events ----------------------------------------------------

    def compile_event(self, name: str, dur_s: float,
                      args: dict[str, Any] | None = None,
                      t_end: float | None = None,
                      count: bool = True) -> None:
        """One compilation: an X slice of ``dur_s`` on the compile
        track ending at ``t_end`` (now by default). ``args`` carries
        whatever the hook knows — function name, triggering arg shapes
        (:func:`compile_watch`) or the raw jax.monitoring event name.

        ``count=False`` records the slice WITHOUT bumping the
        ``compile.count/total_s`` rollups: one real compilation is
        seen twice — by the jax.monitoring backend listener (pure XLA
        time, the canonical counter) AND by the named compile_watch /
        instrument_jit hook (name + shapes, call wall time) — and the
        hooks pass count=False so the rollup counts each compile
        once."""
        t_end = time.perf_counter() if t_end is None else t_end
        self._push("X", "compile", name, t_end - dur_s, TID_COMPILE,
                   dur=dur_s, args=args)
        if count:
            self.compile_count += 1
            self.compile_total_s += dur_s

    # -- memory samples ----------------------------------------------------

    def counter(self, name: str, values: dict[str, float]) -> None:
        self._push("C", "counter", name, time.perf_counter(),
                   TID_COUNTER, args=dict(values))

    def sample_memory(self) -> dict[str, float] | None:
        """Per-device live-buffer bytes via ``jax.live_arrays()``,
        recorded as a Counter event. Multi-device arrays attribute
        ``nbytes / n_devices`` to each holder. Returns the sample (or
        None when jax is unavailable / not yet imported — sampling
        must never be the thing that first initializes a backend)."""
        if "jax" not in sys.modules:
            return None
        try:
            import jax

            per_dev: dict[str, float] = {}
            total = 0
            for arr in jax.live_arrays():
                nbytes = int(getattr(arr, "nbytes", 0))
                total += nbytes
                devs = tuple(arr.devices())
                if not devs:
                    continue
                share = nbytes / len(devs)
                for d in devs:
                    key = f"live_bytes.{d}"
                    per_dev[key] = per_dev.get(key, 0.0) + share
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            return None
        sample = {"live_bytes": float(total), **per_dev}
        self.counter("mem", sample)
        self.peak_live_bytes = max(self.peak_live_bytes, total)
        return sample

    def maybe_sample_memory(self) -> None:
        """Throttled :meth:`sample_memory` — called at span boundaries,
        so at most one ``live_arrays()`` walk per ``mem_interval_s``."""
        now = time.perf_counter()
        if now - self._last_mem_sample < self.mem_interval_s:
            return
        with self._lock:
            if now - self._last_mem_sample < self.mem_interval_s:
                return
            self._last_mem_sample = now
        self.sample_memory()

    # -- snapshot / export -------------------------------------------------

    def _balanced_events(self) -> list[tuple]:
        """Buffer contents with span B/E pairs re-balanced across the
        ring's eviction edge: an E whose B was evicted is dropped, a B
        still open at snapshot time gets a synthesized E at the last
        stamp — so every exported B has a matching E, always."""
        events = list(self.events)
        out: list[tuple] = []
        stacks: dict[int, list[str]] = {}
        max_ts = self.t0_mono
        for ev in events:
            ph, cat, name, ts, tid = ev[0], ev[1], ev[2], ev[3], ev[4]
            max_ts = max(max_ts, ts + (ev[5] or 0.0))
            if ph == "B":
                stacks.setdefault(tid, []).append(name)
            elif ph == "E":
                stack = stacks.get(tid)
                if not stack or stack[-1] != name:
                    continue  # orphan: its B fell off the ring
                stack.pop()
            out.append(ev)
        for tid, stack in stacks.items():
            for name in reversed(stack):
                out.append(("E", "span", name, max_ts, tid, None, None))
        return out

    def snapshot(self) -> dict[str, Any]:
        """JSON-able recorder state — the payload of the ``kind=trace``
        RunLog record AND of the per-rank handoff file
        (:func:`write_rank_snapshot`). ``events`` is the balanced ring
        contents in compact list form; the summary fields survive
        eviction. ``clock`` carries TWO monotonic↔wall anchor pairs
        (construction and snapshot time) so the cross-rank merge can
        estimate each rank's clock offset and bound its drift;
        ``process`` stamps whose timeline this is (launcher env
        protocol first, live jax runtime second — see
        ``topology.process_env_info``); ``collectives`` carries the
        rank's collective-schedule hash chain for the merge-time
        desync check."""
        events = self._balanced_events()
        by_cat: dict[str, int] = {}
        for ev in events:
            by_cat[ev[1]] = by_cat.get(ev[1], 0) + 1
        process_id, num_processes, slice_id = _process_info()
        # the collective schedule hash chain (analysis/runtime.py):
        # every eager Communicator collective and traced timing rep
        # fingerprinted as (op, seq, shape, dtype, axis). The merge
        # (harness/collect.py) cross-checks the chains rank-against-
        # rank — equal digests PROVE the SPMD schedules matched; on
        # mismatch the first divergent (rank, op, seq) is named.
        # analysis.runtime is import-light (stdlib only), so this
        # costs no jax import.
        try:
            from hpc_patterns_tpu.analysis import runtime as _runtimelib

            collectives = _runtimelib.collective_schedule().snapshot()
        except Exception:  # noqa: BLE001 — the stamp is best-effort
            collectives = None
        return {
            "clock": {"wall0": self.t0_wall, "mono0": self.t0_mono,
                      "wall1": time.time(),
                      "mono1": time.perf_counter()},
            "process": {"process_id": process_id,
                        "num_processes": num_processes,
                        "slice_id": slice_id},
            "sync": [dict(a) for a in self.sync_anchors],
            "capacity": self.capacity,
            "n_events": self.n_events,
            "n_dropped": max(0, self.n_events - len(self.events)),
            "by_cat": by_cat,
            "compile": {"count": self.compile_count,
                        "total_s": self.compile_total_s},
            "mem": {"peak_live_bytes": self.peak_live_bytes},
            "collectives": collectives,
            "events": [list(ev) for ev in events],
        }

    def to_chrome(self) -> dict[str, Any]:
        return chrome_from_snapshots([self.snapshot()])

    def export(self, path: str | Path) -> Path:
        """Write the Chrome-trace JSON (Perfetto: open → this file)."""
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        with path.open("w") as f:
            json.dump(self.to_chrome(), f)
        return path


def _process_info() -> tuple[int, int, int]:
    """(process_id, num_processes, slice_id) via topology's env-first
    resolution; (0, 1, 0) when topology/jax are unavailable — a
    snapshot must never fail for lack of a distributed runtime."""
    try:
        from hpc_patterns_tpu import topology

        return topology.process_env_info()
    except Exception:  # noqa: BLE001 — telemetry stamp, best-effort
        return 0, 1, 0


def rank_snapshot_path(trace_dir: str | Path, process_id: int) -> Path:
    """The per-rank handoff file for ``process_id`` under the
    launcher-provided ``HPCPAT_TRACE_DIR`` — one JSON object per file,
    the ``kind=trace`` snapshot verbatim. Width-padded so a shell glob
    lists ranks in order."""
    return Path(trace_dir) / f"rank{process_id:05d}.trace.json"


def write_rank_snapshot(rec: TraceRecorder, trace_dir: str | Path,
                        snapshot: dict[str, Any] | None = None
                        ) -> Path | None:
    """Write ``rec``'s snapshot to its per-rank file under
    ``trace_dir`` (the ``HPCPAT_TRACE_DIR`` handoff: the launcher sets
    the env var, every traced child writes here at exit, the launcher
    collects and merges — harness/collect.py). Pass ``snapshot`` when
    one was already taken for another sink (the ``--log`` record), so
    the rank file and the log record carry the SAME events and clock
    anchors. Returns the path, or None when the write failed (a full
    disk must not turn a successful run into a failure; the launcher
    reports missing rank files)."""
    snap = dict(rec.snapshot() if snapshot is None else snapshot)
    snap["kind"] = "trace"
    path = rank_snapshot_path(trace_dir, snap["process"]["process_id"])
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        with path.open("w") as f:
            json.dump(snap, f)
    except OSError as e:
        print(f"WARNING: could not write per-rank trace {path}: {e}",
              file=sys.stderr)
        return None
    return path


def chrome_from_snapshots(snaps: list[dict[str, Any]],
                          pid: int = 1) -> dict[str, Any]:
    """Chrome-trace JSON from one or more ``kind=trace`` snapshots.

    Spans become B/E pairs on per-thread tracks, device windows and
    compiles X slices on their synthetic tracks, memory samples Counter
    events. Timestamps are microseconds since the FIRST snapshot's
    monotonic anchor; multiple snapshots from one process merge on a
    shared clock (their anchors differ only by configure time)."""
    if not snaps:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    # anchor at the earliest event START (an X slice recorded at its
    # end can begin before the recorder's construction stamp — e.g. a
    # compile already in flight when tracing was enabled); Chrome
    # timestamps must be nonnegative
    mono0 = min(float(s["clock"]["mono0"]) for s in snaps)
    for s in snaps:
        for ev in s.get("events", []):
            mono0 = min(mono0, float(ev[3]))
    trace_events: list[dict[str, Any]] = []
    tids_seen: set[int] = set()
    for snap in snaps:
        for ev in snap.get("events", []):
            ph, cat, name, ts, tid, dur, args = ev
            tids_seen.add(int(tid))
            rec: dict[str, Any] = {
                "name": name, "cat": cat, "ph": ph,
                "ts": (float(ts) - mono0) * 1e6,
                "pid": pid, "tid": int(tid),
            }
            if ph == "X":
                rec["dur"] = (dur or 0.0) * 1e6
            if ph == "i":
                rec["s"] = "t"  # thread-scoped instant arrow
            if ph == "C":
                rec["args"] = {k: v for k, v in (args or {}).items()}
            elif args:
                rec["args"] = {k: str(v) for k, v in args.items()}
            trace_events.append(rec)
    meta = [
        {"name": "process_name", "ph": "M", "pid": pid,
         "args": {"name": "hpc_patterns_tpu"}},
    ]
    for tid in sorted(tids_seen):
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": _track_label(tid)}})
    return {"traceEvents": meta + trace_events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# process-wide recorder + the metrics-span sink hookup
# ---------------------------------------------------------------------------

_recorder: TraceRecorder | None = None


def get_tracer() -> TraceRecorder | None:
    return _recorder


def active() -> TraceRecorder | None:
    """The enabled recorder, or None — THE fast-path check every hook
    makes (one module-global read; the disabled path never allocates)."""
    rec = _recorder
    if rec is not None and rec.enabled:
        return rec
    return None


def configure(*, enabled: bool = False,
              capacity: int = DEFAULT_CAPACITY,
              mem_interval_s: float = 0.05) -> TraceRecorder:
    """Install a FRESH process-wide recorder (apps call this once per
    run via ``--trace``; run_instrumented mirrors metrics.configure).
    Enabling also installs the recorder as the metrics-span sink and
    registers the jax.monitoring compile listener; disabling detaches
    the sink so ``Metrics.span()`` returns to its no-op fast path."""
    global _recorder
    _recorder = TraceRecorder(enabled=enabled, capacity=capacity,
                              mem_interval_s=mem_interval_s)
    metricslib._trace_sink = _recorder if enabled else None
    # fresh recorder = fresh collective schedule chain: every rank of a
    # launch configures at app start, so the chains all start from the
    # same genesis and index the run's collectives identically
    try:
        from hpc_patterns_tpu.analysis import runtime as _runtimelib

        _runtimelib.reset_collective_schedule()
    except Exception:  # noqa: BLE001
        pass
    if enabled:
        install_monitoring_listener()
    return _recorder


# ---------------------------------------------------------------------------
# compile watchers
# ---------------------------------------------------------------------------

_monitoring_installed = False

# what jax.monitoring reports of a compilation, by the short name the
# ``jit.event`` marker and :func:`compile_events` carry. A cache load is
# reported INSIDE its backend_compile event (jax times
# compile_or_get_cached whole), so the two must not be added up; the
# seconds of a trace are its own (the listener takes nested traces off).
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
COMPILE_LOG_CAPACITY = 4096

# (host perf_counter instant at the event's end, short name, seconds,
# the function jax names or ""): kept whether or not anything listens,
# because the listener runs only when jax traces or compiles
_compile_log: deque = deque(maxlen=COMPILE_LOG_CAPACITY)


def compile_events() -> list[tuple[float, str, float, str]]:
    """Every trace, lowering, backend compile and cache load jax has
    reported since :func:`install_monitoring_listener`, oldest first and
    at most ``COMPILE_LOG_CAPACITY`` of them: what to read after a run
    that was slower than its neighbours."""
    return list(_compile_log)


def _monitoring_listener(event: str, duration: float, **kw) -> None:
    kind = JIT_EVENTS.get(event)
    if kind is None:
        return
    secs, fn = float(duration), str(kw.get("fun_name", ""))
    now = time.perf_counter()
    if kind == "trace":
        # jax reports a jitted function traced inside another one first,
        # then the outer one with the inner's time in its own: keep each
        # trace's SELF seconds, so that the log's seconds add up
        for t, k, inner, _ in reversed(_compile_log):
            if t <= now - duration:
                break
            if k == "trace":
                secs -= inner
        secs = max(secs, 0.0)
    _compile_log.append((now, kind, secs, fn))
    rec = active()
    if rec is not None and kind == "backend_compile":
        # the one event counted: trace and lowering would triple-count
        # a single compilation
        rec.compile_event("xla.backend_compile", secs,
                          args={"event": event})
    if rec is not None or metricslib.get_metrics().mirror_traces:
        # a marker, as compile_watch's: it opens when the event has
        # ended, under whatever span encloses the call that compiled
        with metricslib.span("jit.event", event=kind, secs=secs, fn=fn):
            pass


def install_monitoring_listener() -> bool:
    """Register the ``jax.monitoring`` duration listener exactly once
    per process (``configure(enabled=True)`` and every ``EngineCore``
    ask for it). It runs only when jax traces or compiles, so leaving
    it registered costs a steady loop nothing — registration is
    deliberately never undone (jax's unregister API is private and the
    listener list is append-only in practice)."""
    global _monitoring_installed
    if _monitoring_installed:
        return True
    try:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _monitoring_listener)
    except Exception:  # noqa: BLE001 — tracing is best-effort
        return False
    _monitoring_installed = True
    return True


def jit_cache_size(fn, *, strict: bool = False) -> int:
    """Compiled-variant count of a jitted callable. THE compile-count
    probe: compile_watch diffs it around calls, and
    ``serving.prefill_cache_size()`` is its longest-standing consumer
    (the bucket-ladder bound observable).

    Default (telemetry) mode returns 0 when the wrapper exposes no
    ``_cache_size`` — a missing probe must not crash a traced run.
    ``strict=True`` raises instead: callers whose CLAIM is the count
    (the bucket-ladder assertions gate on it, and 0 is exactly the
    value they would read as success) must fail loudly if a jax
    upgrade renames the private probe."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        inner = getattr(fn, "__wrapped__", None)
        probe = getattr(inner, "_cache_size", None)
    if probe is None:
        if strict:
            raise AttributeError(
                f"{fn!r} exposes no _cache_size probe (jax private "
                "API moved?) — the compile-count observable would "
                "silently read 0")
        return 0
    if strict:
        return int(probe())
    try:
        return int(probe())
    except Exception:  # noqa: BLE001
        return 0


_NULL = contextlib.nullcontext()


class _CompileWatch:
    """Context manager diffing a jitted fn's cache size around a call:
    growth means THIS call compiled, and the call's wall time is the
    compile-dominated cost the event records (the backend listener has
    the pure-XLA time; this hook contributes function name + shapes).
    One probe, two sinks: the flight recorder's compile track (``rec``)
    and, when the registry mirrors spans into the profiler, a
    ``jit.compiled`` marker span on the device trace's own clock."""

    __slots__ = ("rec", "name", "fn", "attrs", "n0", "t0")

    def __init__(self, rec: TraceRecorder | None, name: str, fn,
                 attrs: dict[str, Any]):
        self.rec, self.name, self.fn, self.attrs = rec, name, fn, attrs

    def __enter__(self):
        self.n0 = jit_cache_size(self.fn)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        grew = jit_cache_size(self.fn) - self.n0
        if grew > 0:
            if self.rec is not None:
                # count=False: the backend listener already counted
                # this compilation; the hook's job is the name + shapes
                self.rec.compile_event(self.name, dt, count=False,
                                       args={**self.attrs,
                                             "new_variants": grew})
            if metricslib.get_metrics().mirror_traces:
                # a marker, not a duration: it opens when the watched
                # call has returned, under whatever span encloses it
                with metricslib.span("jit.compiled", fn=self.name,
                                     **self.attrs):
                    pass
        return False


def compile_watch(name: str, fn, **attrs):
    """``with compile_watch("serving._prefill_one", _prefill_one,
    padded_len=32): _prefill_one(...)`` — records a compile event iff
    the call grew ``fn``'s jit cache: on the flight recorder when one
    is active, as a mirrored ``jit.compiled`` span when the metrics
    registry mirrors into the profiler. With neither, the disabled
    path returns a shared nullcontext (nothing allocated per call)."""
    rec = active()
    if rec is None and not metricslib.get_metrics().mirror_traces:
        return _NULL
    return _CompileWatch(rec, name, fn, attrs)


def _shape_strs(args) -> list[str]:
    out = []
    for a in args:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None:
            out.append(f"{dtype}{list(shape)}")
    return out


def record_executable_memory(name: str, compiled) -> dict | None:
    """Compiled-executable memory peaks (``memory_analysis()``) as a
    Counter event, where the backend supports it (TPU reports real HBM
    peaks; CPU reports code/temp sizes; some backends raise — then
    this records nothing and returns None)."""
    rec = active()
    if rec is None:
        return None
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        return None
    if ma is None:
        return None
    vals = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes",
                 "peak_memory_in_bytes"):
        v = getattr(ma, attr, None)
        if isinstance(v, (int, float)) and v is not None:
            vals[attr] = float(v)
    if not vals:
        return None
    rec.counter(f"exec_mem.{name}", vals)
    return vals


def instrument_jit(fn, name: str, *, exec_memory: bool = False):
    """Wrap a jitted callable so every call that grows its jit cache
    records a compile event (name, triggering arg shapes, wall time;
    ``count=False`` — the backend listener is the canonical counter).
    With no recorder active the wrapper is a single global read +
    passthrough call.

    ``exec_memory=True`` additionally captures the executable's
    ``memory_analysis()`` peaks on each fresh-compile call via an AOT
    ``lower().compile()``. That AOT pass is a FULL second backend
    compilation (measured: the jit call cache does not serve it), so
    it is opt-in and only sane for functions whose compile is cheap
    relative to the insight; big entry points (the train step) leave
    it off and use :func:`record_executable_memory` at an explicit AOT
    site instead."""

    def wrapped(*args, **kwargs):
        rec = active()
        if rec is None:
            return fn(*args, **kwargs)
        n0 = jit_cache_size(fn)
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        if jit_cache_size(fn) > n0:
            rec.compile_event(name, dt, count=False,
                              args={"shapes": _shape_strs(args)})
            if exec_memory:
                try:
                    record_executable_memory(
                        name, fn.lower(*args, **kwargs).compile())
                except Exception:  # noqa: BLE001 — donated args may
                    pass           # be consumed; peaks are extras
        return out

    wrapped.__wrapped__ = fn
    wrapped.__name__ = getattr(fn, "__name__", name)
    return wrapped


# ---------------------------------------------------------------------------
# CLI: kind=trace RunLog records -> Chrome-trace JSON
# ---------------------------------------------------------------------------

def load_trace_snapshots(paths) -> list[dict[str, Any]]:
    """Every ``kind=trace`` record across the given runlog JSONL files
    (unparseable lines skipped, same tolerance as harness.report).
    Each record is annotated with its ``_source`` path so the export
    can keep records from different files on different pid lanes."""
    snaps = []
    for path in paths:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("kind") == "trace":
                    rec.setdefault("_source", str(path))
                    snaps.append(rec)
    return snaps


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="Export kind=trace runlog records as Chrome-trace "
                    "JSON (load in Perfetto / chrome://tracing)")
    p.add_argument("logs", nargs="+",
                   help="runlog JSONL file(s) from a --trace --log run")
    p.add_argument("-o", "--out", default=None,
                   help="output path (default: <first log>.trace.json)")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        snaps = load_trace_snapshots(args.logs)
    except OSError as e:
        print(f"ERROR: {e}", file=sys.stderr)
        return 2
    if not snaps:
        print("ERROR: no kind=trace records in input (run apps with "
              "--trace --log to record them)", file=sys.stderr)
        return 2
    out = Path(args.out) if args.out else Path(
        args.logs[0]).with_suffix(".trace.json")
    # the merge path (harness/collect.py) assigns one pid lane per
    # source process/file with process_name metadata — records from
    # different runlog files no longer collapse onto a single lane
    from hpc_patterns_tpu.harness import collect as collectlib

    chrome = collectlib.merge(snaps)["chrome"]
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("w") as f:
        json.dump(chrome, f)
    n_ev = len(chrome["traceEvents"])
    n_lanes = len({e["pid"] for e in chrome["traceEvents"]})
    n_comp = sum(s.get("compile", {}).get("count", 0) for s in snaps)
    dropped = sum(s.get("n_dropped", 0) for s in snaps)
    print(f"{out}: {n_ev} trace events from {len(snaps)} snapshot(s) "
          f"on {n_lanes} pid lane(s) ({n_comp} compiles, {dropped} "
          f"evicted by the ring) — open in Perfetto (ui.perfetto.dev) "
          f"or chrome://tracing")
    return 0


if __name__ == "__main__":
    sys.exit(main())
