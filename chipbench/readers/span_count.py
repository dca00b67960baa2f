"""How many host spans are named ``args["span"]``. A count of none is a
reading (0) only where the trace shows that the program emits spans at
all: it must hold a span named ``args["given"]``; otherwise nothing."""

from chipbench import spans


def compute(args, st):
    if not st.named(args["given"]):
        return None
    return float(len(st.named(args["span"])))


def read(args, trace, facts, config, peaks):
    st = spans.current()
    return None if st is None else compute(args, st)
