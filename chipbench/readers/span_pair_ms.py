"""Mean time, in ms, from the start of a span named ``args["from"]`` to
the end of the span named ``args["to"]`` that carries the same value of
the attribute ``args["key"]`` (the first such span that ends after the
start). A value that only one of the two carries is left out; no pair
gives nothing."""

import statistics

from chipbench import spans


def compute(args, st):
    key = args["key"]
    ends: dict = {}
    for s in st.named(args["to"]):
        if key in s.attrs:
            ends.setdefault(s.attrs[key], []).append(s.end)
    waits = []
    for s in st.named(args["from"]):
        after = [e for e in ends.get(s.attrs.get(key), ()) if e >= s.start]
        if after:
            waits.append(min(after) - s.start)
    return 1e3 * statistics.fmean(waits) if waits else None


def read(args, trace, facts, config, peaks):
    st = spans.current()
    return None if st is None else compute(args, st)
