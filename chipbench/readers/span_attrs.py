"""A number computed from attributes of the program's host spans named
``args["span"]``: each attribute of ``args["attrs"]`` is summed over the
spans, and the sums go, after the configuration and in that order, to the
function ``args["counts"]`` names (``module.function`` under
``chipbench/counts``), followed by the run's facts that ``args["facts"]``
lists. No such span in the trace, or one without an attribute asked for (a
program that does not stamp it), gives nothing."""

import importlib

from chipbench import spans


def compute(args, st, facts, config):
    own = st.named(args["span"])
    if not own or any(a not in s.attrs for s in own for a in args["attrs"]):
        return None
    sums = [sum(float(s.attrs[a]) for s in own) for a in args["attrs"]]
    module, fn = args["counts"].rsplit(".", 1)
    count = getattr(importlib.import_module(f"chipbench.counts.{module}"), fn)
    return count(config, *sums, *(facts[k] for k in args.get("facts", ())))


def read(args, trace, facts, config, peaks):
    st = spans.current()
    return None if st is None else compute(args, st, facts, config)
