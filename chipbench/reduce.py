"""From a profiler trace to numbers: busy and idle time of the device,
device time per named event, idle gaps by what the host was doing.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes with
nothing but JAX. Everything after that works on plain ``Event`` lists, so
the reduction is checked in the tests on a small recorded trace
(``Trace.from_json``). A TPU trace has one plane per chip
(``/device:TPU:n``) whose ``XLA Ops`` line holds the operations (a loop
nests its body's operations inside its own event) and whose
``XLA Modules`` line holds one event per executed program; host threads
are lines of the ``/host:CPU`` plane, and a ``TraceAnnotation`` is an
event there. All times are seconds on the trace's own clock.
"""

from __future__ import annotations

import bisect
import json
import re
from dataclasses import dataclass

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = re.compile(r"^/host:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


NAME_CHARS = 160   # a TPU operation's name is its whole HLO text


def short(name: str) -> str:
    """An operation's own name: the HLO text up to `` = ``."""
    return name.split(" = ", 1)[0][:80]


class NothingToRead(LookupError):
    """An event a metric needs is not in the trace."""


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


def union_length(intervals) -> float:
    """Total length covered by (start, end) pairs."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals, lo: float, hi: float):
    """The parts of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    def __init__(self, events):
        self.events = sorted(events, key=lambda e: (e.plane, e.line, e.start,
                                                    -e.dur))
        self.device_planes = sorted({e.plane for e in self.events
                                     if DEVICE_PLANE.match(e.plane)})
        if not self.device_planes:
            raise NothingToRead("the trace holds no /device:TPU:n plane")
        timed = [e for e in self.events if e.dur > 0]
        self.t_lo = min(e.start for e in timed)
        self.t_hi = max(e.end for e in timed)

    # -- selections --------------------------------------------------------

    def on(self, line: str, pattern: str | None = None, plane=None,
           within: str | None = None):
        """Device events on ``line`` whose name matches ``pattern``;
        ``within`` keeps those that ran inside a program (an event of
        the modules' line) whose name matches it."""
        rx = re.compile(pattern) if pattern else None
        out = [e for e in self.events
               if e.line == line and DEVICE_PLANE.match(e.plane)
               and (plane is None or e.plane == plane)
               and (rx is None or rx.search(e.name))]
        if within is None:
            return out
        spans: dict[str, list] = {}
        for m in self.on(MODULES_LINE, within):
            spans.setdefault(m.plane, []).append((m.start, m.end))
        keep = []
        for e in out:
            iv = spans.get(e.plane, ())
            i = bisect.bisect_right(iv, (e.start, float("inf"))) - 1
            if i >= 0 and e.end <= iv[i][1] + 1e-9:
                keep.append(e)
        return keep

    def host_events(self):
        return [e for e in self.events
                if HOST_PLANE.match(e.plane) and e.dur > 0]

    # -- busy and idle -----------------------------------------------------

    def window_s(self) -> float:
        return self.t_hi - self.t_lo

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per = [union_length((e.start, e.end)
                            for e in self.on(OPS_LINE, plane=p))
               for p in self.device_planes]
        busy = sum(per) / len(per)
        if busy <= 0:
            raise NothingToRead("no operation ran on the device in the trace")
        return busy

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # -- device time per named event ----------------------------------------

    def device_time(self, line: str, pattern: str,
                    within: str | None = None) -> tuple[float, int]:
        """(seconds, events) of the events on ``line`` whose name matches,
        averaged over the chips. A pattern that matches nothing raises: a
        metric whose event is gone must not read 0."""
        ev = self.on(line, pattern, within=within)
        if not ev:
            raise NothingToRead(f"no event matches {pattern!r} on {line!r}"
                                + (f" within {within!r}" if within else ""))
        n = len(self.device_planes)
        return sum(e.dur for e in ev) / n, len(ev) // n

    def self_times(self) -> dict[str, float]:
        """Self time per operation name (an event's time minus the events
        nested inside it), averaged over the chips."""
        out: dict[str, float] = {}
        for p in self.device_planes:
            stack = []
            for e in self.on(OPS_LINE, plane=p):
                while stack and stack[-1][0].end <= e.start:
                    done, child = stack.pop()
                    k = short(done.name)
                    out[k] = out.get(k, 0.0) + done.dur - child
                if stack:
                    stack[-1][1] += e.dur
                stack.append([e, 0.0])
            for done, child in stack:
                k = short(done.name)
                out[k] = out.get(k, 0.0) + done.dur - child
        return {k: max(v, 0.0) / len(self.device_planes)
                for k, v in out.items()}

    # -- idle gaps by what the host was doing --------------------------------

    def idle_gaps(self, longest: int = 200) -> dict[str, float]:
        """The longest idle gaps of the first chip, each given to the
        shortest host event that covers at least half of it."""
        p = self.device_planes[0]
        gaps = gaps_of([(e.start, e.end) for e in self.on(OPS_LINE, plane=p)],
                       self.t_lo, self.t_hi)
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:longest]
        host = self.host_events()
        h_start = np.array([h.start for h in host])
        h_end = np.array([h.end for h in host])
        h_dur = h_end - h_start
        out: dict[str, float] = {}
        for s, e in gaps:
            name = "no host span"
            if host:
                cover = np.minimum(e, h_end) - np.maximum(s, h_start)
                ok = np.flatnonzero(cover >= 0.5 * (e - s))
                if ok.size:
                    name = short(host[ok[np.argmin(h_dur[ok])]].name)
            out[name] = out.get(name, 0.0) + (e - s)
        return out

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:10] if v > 0]
        return {"device_ops": top(self.self_times()),
                "idle_gaps": top(self.idle_gaps())}

    # -- recorded traces ------------------------------------------------------

    def to_json(self) -> str:
        return json.dumps([[e.plane, e.line, e.name, e.start, e.dur]
                           for e in self.events])

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        return cls(Event(*row) for row in json.loads(text))


def load_xplane(path) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    events = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not (device or HOST_PLANE.match(plane.name)):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if ev.duration_ns <= 0 and not device:
                    continue
                events.append(Event(plane.name, line.name,
                                    ev.name[:NAME_CHARS],
                                    ev.start_ns * 1e-9,
                                    ev.duration_ns * 1e-9))
    return Trace(events)
