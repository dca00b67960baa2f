"""Duration, in ms, of the program's host spans named ``args["span"]``:
their median (``args["stat"]`` ``"median"``) or their mean. With
``args["per_child"]`` the spans' whole time is divided by the number of
spans of that name nested in them (by path): host time a child, not a
span. No such span in the trace gives nothing."""

import statistics

from chipbench import spans


def compute(args, st):
    own = st.named(args["span"])
    if not own:
        return None
    durs = [s.dur for s in own]
    if "per_child" in args:
        inside = f"/{args['span']}/"
        n = sum(1 for s in st.named(args["per_child"])
                if inside in f"/{s.path}")
        return 1e3 * sum(durs) / n if n else None
    pick = statistics.median if args.get("stat") == "median" \
        else statistics.fmean
    return 1e3 * pick(durs)


def read(args, trace, facts, config, peaks):
    st = spans.current()
    return None if st is None else compute(args, st)
