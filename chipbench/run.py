"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run, and the only one that touches JAX. Everything that
belongs to one cell, configuration, driver, per-layer metric or reader is
a file found by the name in ``BENCHMARK.json``; this file names none.
The last line of standard output is the result's JSON object.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse
import importlib
import json
import sys
import threading
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent
TRACE_DIR = ROOT / ".cache" / "chipbench_trace"
DATA_DIR = ROOT / ".cache" / "chipbench_data"   # what a driver writes


class Refused(RuntimeError):
    """The run may not happen here (no chip, unknown device, bad name)."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_peaks(device_kind: str) -> dict:
    """The peaks of this device. An unknown device is an error, never a
    default: a share of a peak read against another chip's is no number."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise Refused(f"device kind {device_kind!r} is not in "
                      f"chipbench/peaks.json ({sorted(table)})")
    return table[device_kind]


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise Refused(f"workload {name!r} is not in BENCHMARK.json "
                  f"({[w['name'] for w in bench['workloads']]})")


def metrics_of(bench: dict, section: str, cell: str) -> list[dict]:
    """The metrics of ``section`` that this cell reports: those without a
    ``workloads`` key, and those that list the cell."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


@dataclass
class TraceWindow:
    """A few steady seconds under ``jax.profiler``: starts ``after_s``
    into the measured window and lasts ``length_s``. A driver that owns
    its loop polls ``poll()`` between steps; one that hands the window to
    a blocking call uses ``run_in_thread()``. Host instants are
    ``time.perf_counter`` values."""
    enabled: bool
    after_s: float = 3.0
    length_s: float = 4.0
    t_start: float | None = None   # host instants of the traced window
    t_stop: float | None = None
    _t0: float | None = None
    _thread: threading.Thread | None = None
    _cancel: threading.Event = field(default_factory=threading.Event)

    def begin(self, t0: float) -> None:
        """The measured window opened at host instant ``t0``."""
        self._t0 = t0

    def poll(self) -> None:
        if not self.enabled or self._t0 is None or self.t_stop is not None:
            return
        import jax
        now = time.perf_counter()
        if self.t_start is None and now - self._t0 >= self.after_s:
            import shutil
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # TraceMe spans, not every call
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self.t_start = time.perf_counter()
        elif self.t_start is not None and now - self.t_start >= self.length_s:
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()

    def run_in_thread(self) -> None:
        def loop():
            while self.t_stop is None and not self._cancel.is_set():
                self.poll()
                time.sleep(0.02)
        if self.enabled:
            self._thread = threading.Thread(target=loop, daemon=True)
            self._thread.start()

    def finish(self) -> None:
        """Close a trace the window ended under, and join the thread."""
        self._cancel.set()
        if self._thread is not None:
            self._thread.join(timeout=120)
        if self.enabled and self.t_start is not None and self.t_stop is None:
            import jax
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()

    def xplane(self) -> Path:
        found = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise RuntimeError(f"no .xplane.pb under {TRACE_DIR}")
        return found[-1]


@dataclass
class Context:
    cell: dict          # chipbench/workloads/<cell>.json
    config: dict        # the configuration's file
    entry: dict         # the cell's entry in BENCHMARK.json
    seed: int
    seconds: float
    devices: list
    peaks: dict
    tracer: TraceWindow
    t_process_start: float = T_PROCESS_START
    control: str | None = None   # control runs only: the lower precision
    data_dir: Path = DATA_DIR

    def device_report(self) -> dict:
        """Read after the window and before the reference runs: a
        process's peak never falls again."""
        return device_report(self.devices, len(self.devices))


def device_report(devices, n: int) -> dict:
    peak = 0
    for d in devices[:n]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": n, "memory_peak_bytes": peak}


def look_for_chip(chips: int):
    """The devices this cell runs on, or ``Refused``: a measurement path
    that finds no chip fails, it does not fall back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found platform {devices[0].platform!r}, "
                      "not 'tpu': the benchmark measures only on the chip")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    return devices[:chips], load_peaks(devices[0].device_kind)


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool,
             devices, peaks: dict, *, control=None, readings=False,
             trace_after_s: float = 3.0, trace_length_s: float = 4.0) -> dict:
    """Everything of a run after the look for a chip: the driver, the
    per-layer readers, the result object. Tests call this on the CPU with
    the timed path broken underneath."""
    entry = find_cell(bench, name)
    # a test's bench may point at a fixture; BENCHMARK.json never does
    cell = load_json(ROOT / entry["file"] if "file" in entry
                     else HERE / "workloads" / f"{name}.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = load_json(ROOT / cfg_entry["file"])
    tracer = TraceWindow(trace, after_s=min(trace_after_s, seconds * 0.25),
                         length_s=min(trace_length_s, seconds * 0.5))
    ctx = Context(cell=cell, config=config, entry=entry, seed=seed,
                  seconds=seconds, devices=list(devices), peaks=peaks,
                  tracer=tracer, control=control)
    driver = importlib.import_module(f"chipbench.drivers.{cell['driver']}")
    out = driver.run(ctx)   # window, memory reading, then the comparison
    checks = out["checks"]  # [(name, value, limit)], value <= limit passes
    correct = all(v is not None and v <= lim for _, v, lim in checks)
    device = out["device"]
    if trace:
        from chipbench import reduce as reducelib
        tr = reducelib.load_xplane(tracer.xplane())
        facts = dict(out["facts"], trace_host_window=(tracer.t_start,
                                                      tracer.t_stop))
        values = {}
        for mt in metrics_of(bench, "per_layer", name):
            spec = load_json(HERE / "metrics" / f"{mt['name']}.json")
            reader = importlib.import_module(
                f"chipbench.readers.{spec['reader']}")
            v = reader.read(spec.get("args", {}), tr, facts, config, peaks)
            if v is not None:   # nothing to read: leave the metric out
                values[mt["name"]] = {"value": float(v), "unit": mt["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = tr.breakdown()
    else:
        e2e = out["end_to_end"]
        values = {mt["name"]: {"value": float(e2e[mt["name"]]),
                               "unit": mt["unit"]}
                  for mt in metrics_of(bench, "end_to_end", name)}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": values,
              "device": device}
    if trace:
        result["breakdown"] = breakdown
    if readings:   # chipbench/control.py reads every number, judged or not
        result["readings"] = out.get("readings")
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        bench = load_json(ROOT / "BENCHMARK.json")
        entry = find_cell(bench, args.workload)
        from hpc_patterns_tpu import compile_cache
        compile_cache.enable()
        devices, peaks = look_for_chip(entry["chips"])
    except (Refused, ImportError, FileNotFoundError) as e:
        print(f"chipbench: refused: {e}", file=sys.stderr)
        return 2
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices, peaks)
    for n, c in result["checks"].items():
        print(f"check {n}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
