"""A second reading of a run's trace, for what ``reduce.load_xplane``
drops: the attributes of the program's host spans, and the framework path
of the device's operations.

``reduce.Trace`` keeps an event's name, start and duration. The program's
spans (``harness/metrics.span`` mirrored as ``TraceAnnotation``) carry
attributes as the event's stats, and an ``XLA Ops`` event's metadata
carries the path of ``jax.named_scope`` names the operation was traced
under. ``jax.profiler.ProfileData`` shows an event's own stats and not its
metadata's, so the file is decoded here, with ``google.protobuf`` and a
description of the five messages of ``xplane.proto`` that are read.

Everything after ``load`` works on plain lists, so the readers are checked
in the tests on a recorded slice (``SpanTrace.from_json``).
``python3 chipbench/spans.py [dir]`` prints what a trace holds: the host
spans by path, the stat names of the device's lines, the scopes' self
times; ``--json SECONDS out.json [--from S]`` records a slice.
"""

from __future__ import annotations

import bisect
import functools
import json
import re
import sys
import time
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))

from chipbench import reduce  # noqa: E402

TRACE_DIR = ROOT / ".cache" / "chipbench_trace"   # where run.py traces to

# the stat of an operation's metadata that holds its framework path and,
# after a last colon, the operation's type, which JAX leaves empty:
# "jit(step)/jvp()/while/body/closed_call/attn/dot_general:"
PATH_STAT = "tf_op"
WRAPPED = re.compile(r"^(?:\w+\()+|\)+$")   # jvp(attn), transpose(jvp(attn))
# parts of a path that JAX puts there itself (looking at a trace by hand)
PLUMBING = {"", "while", "body", "cond", "closed_call", "checkpoint",
            "rematted_computation", "pjit", "branch_0_fun", "branch_1_fun"}


@dataclass(frozen=True)
class Span:
    """One host event: a ``TraceAnnotation`` of the program, or one of
    the runtime's own. ``path`` is the event's whole name; a program
    span's is the ``/``-joined nesting ``metrics.span`` gives it."""
    thread: str
    path: str
    start: float
    dur: float
    attrs: dict

    @property
    def name(self) -> str:
        return self.path.rsplit("/", 1)[-1]

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass(frozen=True)
class Op:
    """One event of a chip's ``XLA Ops`` or ``XLA Modules`` line."""
    plane: str
    line: str
    name: str
    start: float
    dur: float
    path: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur

    def scopes(self) -> list[str]:
        """The path's components with the transformations' wrappers
        taken off: ``transpose(jvp(attn))`` is ``attn``."""
        return [WRAPPED.sub("", c) for c in self.path.split("/")]


class SpanTrace:
    def __init__(self, spans, ops):
        self.spans = sorted(spans, key=lambda s: (s.start, -s.dur))
        self.ops = sorted(ops, key=lambda o: (o.plane, o.line, o.start,
                                              -o.dur))

    # -- host spans ----------------------------------------------------------

    def named(self, name: str) -> list[Span]:
        """The spans whose own name (the path's last part) is ``name``."""
        return [s for s in self.spans if s.name == name]

    def innermost(self, names) -> list[tuple[float, float, str]]:
        """The timeline of (start, end, name) pieces in which the
        innermost span among ``names`` is ``name``: of the spans that
        cover an instant, the one that began last."""
        own = [s for s in self.spans if s.name in names and s.dur > 0]
        cuts = sorted({t for s in own for t in (s.start, s.end)})
        out, live, i = [], [], 0
        for lo, hi in zip(cuts, cuts[1:]):
            while i < len(own) and own[i].start <= lo:
                live.append(own[i])
                i += 1
            live = [s for s in live if s.end > lo]
            if live:
                top = max(live, key=lambda s: s.start)
                if out and out[-1][2] == top.name and out[-1][1] == lo:
                    out[-1] = (out[-1][0], hi, top.name)
                else:
                    out.append((lo, hi, top.name))
        return out

    # -- device operations ---------------------------------------------------

    def device_planes(self) -> list[str]:
        return sorted({o.plane for o in self.ops})

    def has_paths(self) -> bool:
        return any(o.path for o in self.ops if o.line == reduce.OPS_LINE)

    def programs(self, pattern: str, plane: str) -> list[Op]:
        rx = re.compile(pattern)
        return [o for o in self.ops if o.plane == plane
                and o.line == reduce.MODULES_LINE and rx.search(o.name)]

    def self_times(self, plane: str, within=None):
        """(operation, self seconds) of the plane's operations: an
        event's time less the events nested inside it. ``within``:
        (start, end) pairs of programs; an operation outside all of
        them is left out."""
        ops = [o for o in self.ops
               if o.plane == plane and o.line == reduce.OPS_LINE]
        if within is not None:
            iv = sorted(within)
            keep = []
            for o in ops:
                i = bisect.bisect_right(iv, (o.start, float("inf"))) - 1
                if i >= 0 and o.end <= iv[i][1] + 1e-9:
                    keep.append(o)
            ops = keep
        out, stack = [], []
        for o in ops:
            while stack and stack[-1][0].end <= o.start:
                done, child = stack.pop()
                out.append((done, max(done.dur - child, 0.0)))
            if stack:
                stack[-1][1] += o.dur
            stack.append([o, 0.0])
        out.extend((done, max(done.dur - child, 0.0))
                   for done, child in stack)
        return out

    # -- the same events as the first reading has them -------------------------

    def as_trace(self) -> reduce.Trace:
        E = reduce.Event
        return reduce.Trace(
            [E(o.plane, o.line, o.name, o.start, o.dur) for o in self.ops]
            + [E("/host:CPU", s.thread, s.path, s.start, s.dur)
               for s in self.spans])

    # -- recorded slices -------------------------------------------------------

    def to_json(self) -> str:
        paths = sorted({o.path for o in self.ops})
        at = {p: i for i, p in enumerate(paths)}
        return json.dumps({
            "spans": [[s.thread, s.path, s.start, s.dur, s.attrs]
                      for s in self.spans],
            "paths": paths,
            "ops": [[o.plane, o.line, o.name, o.start, o.dur, at[o.path]]
                    for o in self.ops]})

    @classmethod
    def from_json(cls, text: str) -> "SpanTrace":
        d = json.loads(text)
        return cls([Span(*row) for row in d["spans"]],
                   [Op(*row[:5], d["paths"][row[5]]) for row in d["ops"]])


# ---------------------------------------------------------------------------
# the file
# ---------------------------------------------------------------------------

@functools.cache
def _xspace_class():
    """``XSpace`` of tsl/profiler/protobuf/xplane.proto, described here
    field by field (only what is read) so that nothing but the installed
    ``google.protobuf`` is needed to parse it."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    I64, U64, STR, DBL, MSG = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING,
                               F.TYPE_DOUBLE, F.TYPE_MESSAGE)
    messages = {
        "XStat": [("metadata_id", 1, I64), ("double_value", 2, DBL),
                  ("uint64_value", 3, U64), ("int64_value", 4, I64),
                  ("str_value", 5, STR), ("ref_value", 7, U64)],
        "XEvent": [("metadata_id", 1, I64), ("offset_ps", 2, I64),
                   ("duration_ps", 3, I64), ("stats", 4, "XStat")],
        "XLine": [("name", 2, STR), ("timestamp_ns", 3, I64),
                  ("events", 4, "XEvent")],
        "XEventMetadata": [("id", 1, I64), ("name", 2, STR),
                           ("stats", 5, "XStat")],
        "XStatMetadata": [("id", 1, I64), ("name", 2, STR)],
        # the two maps of a plane, as the wire has them: repeated entries
        "EventMetadataEntry": [("key", 1, I64),
                               ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, I64),
                              ("value", 2, "XStatMetadata")],
        "XPlane": [("name", 2, STR), ("lines", 3, "XLine"),
                   ("event_metadata", 4, "EventMetadataEntry"),
                   ("stat_metadata", 5, "StatMetadataEntry")],
        "XSpace": [("planes", 1, "XPlane")],
    }
    repeated = {"stats", "events", "lines", "event_metadata",
                "stat_metadata", "planes"}
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench", syntax="proto3")
    for name, fields in messages.items():
        m = fd.message_type.add(name=name)
        for fname, number, kind in fields:
            f = m.field.add(name=fname, number=number)
            f.label = (F.LABEL_REPEATED if fname in repeated
                       else F.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type, f.type_name = MSG, f".chipbench.{kind}"
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.XSpace"))


def _stats(stats, stat_names) -> dict:
    """An event's or a metadata's stats by name. A reference stat points
    at another stat's name, which is how strings are interned."""
    out = {}
    for s in stats:
        if s.str_value:
            v = s.str_value
        elif s.ref_value:
            v = stat_names.get(s.ref_value, s.ref_value)
        elif s.double_value:
            v = s.double_value
        else:
            v = s.int64_value or s.uint64_value
        out[stat_names.get(s.metadata_id, str(s.metadata_id))] = v
    return out


def parse(path) -> "SpanTrace":
    space = _xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    spans, ops = [], []
    for plane in space.planes:
        device = bool(reduce.DEVICE_PLANE.match(plane.name))
        if not (device or reduce.HOST_PLANE.match(plane.name)):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        op_path: dict[int, str] = {}
        for line in plane.lines:
            if device and line.name not in (reduce.OPS_LINE,
                                            reduce.MODULES_LINE):
                continue
            for ev in line.events:
                md = meta[ev.metadata_id]
                start = (line.timestamp_ns + ev.offset_ps * 1e-3) * 1e-9
                dur = ev.duration_ps * 1e-12
                if not device:
                    if dur > 0:
                        spans.append(Span(line.name, md.name, start, dur,
                                          _stats(ev.stats, stat_names)))
                    continue
                if ev.metadata_id not in op_path:
                    tf_op = _stats(md.stats, stat_names).get(PATH_STAT, "")
                    op_path[ev.metadata_id] = str(tf_op).rsplit(":", 1)[0]
                ops.append(Op(plane.name, line.name,
                              md.name[:reduce.NAME_CHARS], start, dur,
                              op_path[ev.metadata_id]))
    return SpanTrace(spans, ops)


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int) -> "SpanTrace":
    t0 = time.perf_counter()
    st = parse(path)
    print(f"spans: second load of {Path(path).name} took "
          f"{time.perf_counter() - t0:.2f} s ({len(st.spans)} host events, "
          f"{len(st.ops)} device events)", file=sys.stderr)
    return st


def load(path) -> "SpanTrace":
    """The trace at ``path``, read once however many metrics ask."""
    return _load(str(path), Path(path).stat().st_mtime_ns)


def current(trace_dir: Path = TRACE_DIR):
    """The newest trace under ``trace_dir``, found as ``run.TraceWindow
    .xplane`` finds it; ``None`` where there is none."""
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        return None
    try:
        return load(found[-1])
    except ImportError as e:   # no google.protobuf: a run's other metrics
        print(f"spans: {e}; the metrics over spans and scopes are left out",
              file=sys.stderr)   # must not fail for it
        return None


# ---------------------------------------------------------------------------
# looking at one by hand
# ---------------------------------------------------------------------------

def main(argv) -> int:
    root = (Path(argv[0]) if argv and not argv[0].startswith("--")
            else TRACE_DIR)
    path = sorted(root.glob("plugins/profile/*/*.xplane.pb"))[-1]
    # stat names first, from the file itself: what a device event and its
    # metadata carry
    space = _xspace_class()()
    space.ParseFromString(path.read_bytes())
    for plane in space.planes:
        if not reduce.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        for line in plane.lines:
            if not line.events:
                continue
            ev = max(line.events, key=lambda e: e.duration_ps)
            md = meta[ev.metadata_id]
            print(f"{plane.name} {line.name!r}: {len(line.events)} events; "
                  f"the longest: {md.name[:100]!r}\n    its stats "
                  f"{_stats(ev.stats, stat_names)}\n    its metadata's "
                  f"{_stats(md.stats, stat_names)}")
    st = load(path)
    by: dict[str, list] = {}
    for s in st.spans:
        if s.attrs or "/" in s.path or "." in s.path:
            by.setdefault(s.path, []).append(s)
    print("host spans with attributes or a path:")
    for p, ss in sorted(by.items(), key=lambda kv: -sum(
            s.dur for s in kv[1]))[:40]:
        print(f"  {sum(s.dur for s in ss):9.4f} s {len(ss):6d} x  {p[:110]}"
              f"  {ss[0].attrs}")
    for plane in st.device_planes()[:1]:
        scopes: dict[str, float] = {}
        for o, t in st.self_times(plane):
            # the program, then the parts of the path that are names
            # somebody gave: not JAX's own, not the primitive at the end
            parts = o.scopes()
            key = " ".join([parts[0] or "(no path)", "/".join(
                c for c in parts[1:-1] if c not in PLUMBING)])
            scopes[key] = scopes.get(key, 0.0) + t
        print(f"{plane}: self seconds by program and named scopes")
        for k, v in sorted(scopes.items(), key=lambda kv: -kv[1])[:40]:
            print(f"  {v:9.4f} s  {k[:120]}")
    if "--json" in argv:
        i = argv.index("--json")
        lo = min(e.start for e in st.ops + st.spans)
        if "--from" in argv:
            lo += float(argv[argv.index("--from") + 1])
        hi = lo + float(argv[i + 1])
        keep = SpanTrace(
            [s for s in st.spans if lo <= s.start and s.end <= hi],
            [o for o in st.ops if lo <= o.start and o.end <= hi])
        Path(argv[i + 2]).write_text(keep.to_json())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
