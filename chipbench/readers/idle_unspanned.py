"""The first chip's idle seconds inside the traced window that lie under
NO span of the program, over the window, in percent: what ``idle_under``
prints as unattributed. ``args["classes"]`` and ``args["given"]`` are
``idle_under``'s: the classes' shares and this one add up to the idle
share that ``idle_pct`` reads from the same trace."""

from chipbench import spans
from chipbench.readers import idle_under


def compute(args, trace, st):
    if not st.named(args["given"]):
        return None
    got = idle_under.shares(args["classes"], trace, st)
    if got is None:
        return None
    return 100.0 * got[None] / trace.window_s()


def read(args, trace, facts, config, peaks):
    st = spans.current()
    return None if st is None else compute(args, trace, st)
