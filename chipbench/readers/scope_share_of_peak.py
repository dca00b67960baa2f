"""Work of a scope over its device self time over the chip's peak, in
percent: ``share_of_peak``'s arithmetic with ``scope_ms``'s clock.

``args["scope"]`` is a path of ``jax.named_scope`` names (``"ssm/scan"``:
the scope ``scan`` inside ``ssm``); an operation counts when its framework
path holds those names in a row. Only operations inside the events of
``XLA Modules`` matching ``args["program"]`` count; the divisor is the sum
of their self times over every such event, and the number of those events
goes to ``args["counts"]`` (``module.function`` under ``chipbench/counts``)
with the run's facts. ``args["bound"]``: ``"flops"`` or ``"roofline"`` (the
larger of operations over peak operations and bytes over peak bandwidth).
Nothing to read (no paths, no such program or scope, a program without the
counter the count needs) gives nothing, never 0.
"""

import importlib
import sys

from chipbench import spans


def _holds(parts, want) -> bool:
    n = len(want)
    return any(parts[i:i + n] == want for i in range(len(parts) - n + 1))


def compute(args, st, facts, config, peaks):
    if not st.has_paths():
        return None
    want = args["scope"].split("/")
    seconds, runs = 0.0, 0
    for plane in st.device_planes():
        programs = st.programs(args["program"], plane)
        runs += len(programs)
        seconds += sum(t for o, t in st.self_times(
            plane, within=[(p.start, p.end) for p in programs])
            if _holds(o.scopes(), want))
    if not runs or seconds <= 0:
        return None
    module, fn = args["counts"].rsplit(".", 1)
    count = getattr(importlib.import_module(f"chipbench.counts.{module}"), fn)
    flops, nbytes = count(facts, config, runs)
    if flops <= 0 and nbytes <= 0:
        return None
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    if args["bound"] == "flops":
        least = t_flops
    else:
        least = max(t_flops, t_bytes)
        print(f"roofline {args['counts']}: bound by "
              f"{'operations' if t_flops >= t_bytes else 'bytes'} "
              f"({t_flops:.6f} s against {t_bytes:.6f} s)", file=sys.stderr)
    return 100.0 * least / seconds


def read(args, trace, facts, config, peaks):
    st = spans.current()
    return None if st is None else compute(args, st, facts, config, peaks)
