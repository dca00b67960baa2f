"""Look at a trace by hand: planes, lines, and the names that take the
most time on each line. ``python3 chipbench/inspect_trace.py [dir]``
reads the newest ``.xplane.pb`` under ``dir`` (default: where ``run.py
--trace 1`` writes); ``--json N out.json`` also records the first ``N``
seconds as the plain event list the tests' fixture is made of."""

from __future__ import annotations

import sys
from collections import defaultdict
from pathlib import Path


def main(argv) -> int:
    from jax.profiler import ProfileData
    root = Path(argv[0]) if argv and not argv[0].startswith("--") \
        else Path(__file__).resolve().parent.parent / ".cache/chipbench_trace"
    path = sorted(root.glob("plugins/profile/*/*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            by = defaultdict(lambda: [0, 0.0])
            n, lo, hi, sample = 0, None, None, None
            for ev in line.events:
                n += 1
                by[ev.name][0] += 1
                by[ev.name][1] += ev.duration_ns * 1e-9
                lo = ev.start_ns if lo is None else min(lo, ev.start_ns)
                end = ev.start_ns + ev.duration_ns
                hi = end if hi is None else max(hi, end)
                if sample is None:
                    sample = dict(ev.stats)
            if not n:
                continue
            print(f"  LINE {line.name!r}: {n} events, "
                  f"[{lo * 1e-9:.6f}, {hi * 1e-9:.6f}] s; first stats {sample}")
            for name, (c, s) in sorted(by.items(),
                                       key=lambda kv: -kv[1][1])[:25]:
                print(f"    {s:10.6f} s {c:7d} x  {name[:140]}")
    if "--json" in argv:
        from chipbench import reduce
        i = argv.index("--json")
        tr = reduce.load_xplane(path)
        cut = tr.t_lo + float(argv[i + 1])
        keep = reduce.Trace(e for e in tr.events if e.end <= cut)
        Path(argv[i + 2]).write_text(keep.to_json())
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    sys.exit(main(sys.argv[1:]))
