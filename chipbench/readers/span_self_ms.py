"""Host time, in ms, of the program's spans named ``args["span"]``, all of
them added up, less the spans named in ``args["less"]`` that lie inside one
of them (by path and by time: a child whose parent began before the trace
did is not taken off a sum that lacks the parent), over the number of spans
named ``args["per"]`` (by default the span itself): a phase's own host time
a round. No such span, or none to divide by, gives nothing."""

import bisect

from chipbench import spans


def compute(args, st):
    own = sorted(st.named(args["span"]), key=lambda s: s.start)
    if not own:
        return None
    starts = [s.start for s in own]
    inside = f"/{args['span']}/"
    total = sum(s.dur for s in own)
    for name in args.get("less", ()):
        for c in st.named(name):
            i = bisect.bisect_right(starts, c.start) - 1
            if inside in f"/{c.path}" and i >= 0 and c.end <= own[i].end:
                total -= c.dur
    n = len(st.named(args["per"])) if "per" in args else len(own)
    return 1e3 * total / n if n else None


def read(args, trace, facts, config, peaks):
    st = spans.current()
    return None if st is None else compute(args, st)
