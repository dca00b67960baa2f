"""A statistic of one attribute of the program's host spans named
``args["span"]``: the mean of ``args["attr"]`` over them, or their sum
(``args["stat"]`` ``"sum"``), times ``args["scale"]`` (seconds to ms).
``args["where"]`` maps an attribute to the values a span must carry to be
counted. A span without the attribute (a program that does not stamp it)
gives nothing. No such span gives nothing either, unless the trace holds a
span named ``args["given"]``: the program then emits such spans, none
fired, and the reading is 0."""

import statistics

from chipbench import spans


def compute(args, st):
    own = [s for s in st.named(args["span"])
           if all(s.attrs.get(k) in ok
                  for k, ok in args.get("where", {}).items())]
    if not own:
        return 0.0 if "given" in args and st.named(args["given"]) else None
    if any(args["attr"] not in s.attrs for s in own):
        return None
    values = [float(s.attrs[args["attr"]]) for s in own]
    pick = sum if args.get("stat") == "sum" else statistics.fmean
    return args.get("scale", 1.0) * pick(values)


def read(args, trace, facts, config, peaks):
    st = spans.current()
    return None if st is None else compute(args, st)
