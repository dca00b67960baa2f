"""The first chip's idle seconds inside the traced window, by what the
program's host loop was doing, over the window, in percent.

``args["classes"]`` maps a class to a list of span names. Every idle
instant goes to the innermost span, among all the names of all the
classes, that covers it; ``args["report"]`` names the class whose share is
the metric. What no such span covers stays unattributed: it is printed to
stderr beside the classes, and the classes and it add up to the idle
share that ``idle_pct`` reads from the same trace. A trace without a span
named ``args["given"]`` is of a program whose loop is not spanned, and
gives nothing: a few spans alone would leave most of the idle time
unattributed and read as a small share."""

import sys

from chipbench import reduce, spans


def shares(classes: dict, trace, st) -> dict | None:
    """Seconds of the first chip's idle time under each class, and under
    none (``None``'s entry)."""
    of = {n: c for c, names in classes.items() for n in names}
    pieces = st.innermost(set(of))
    if not pieces:
        return None
    plane = trace.device_planes[0]
    gaps = reduce.gaps_of([(e.start, e.end) for e in trace.on(
        reduce.OPS_LINE, plane=plane)], trace.t_lo, trace.t_hi)
    out = {c: 0.0 for c in classes}
    out[None] = sum(e - s for s, e in gaps)
    i = 0
    for s, e in gaps:
        while i < len(pieces) and pieces[i][1] <= s:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < e:
            lo, hi, name = pieces[j]
            cover = min(e, hi) - max(s, lo)
            out[of[name]] += cover
            out[None] -= cover
            j += 1
    return out


def compute(args, trace, st):
    if not st.named(args["given"]):
        return None
    got = shares(args["classes"], trace, st)
    if got is None:
        return None
    window = trace.window_s()
    listed = ", ".join(f"{c} {100 * v / window:.3f}" for c, v in got.items()
                       if c is not None)
    print(f"idle_under: {listed}, unattributed "
          f"{100 * got[None] / window:.3f} of idle "
          f"{100 * sum(got.values()) / window:.3f} (% of the window)",
          file=sys.stderr)
    return 100.0 * got[args["report"]] / window


def read(args, trace, facts, config, peaks):
    st = spans.current()
    return None if st is None else compute(args, trace, st)
