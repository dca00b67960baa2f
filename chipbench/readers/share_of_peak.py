"""Work over time over the chip's peak, in percent.

``args["counts"]`` names the function (``module.function`` under
``chipbench/counts``) that gives the operations and bytes the work needs.
``args["time"]`` is ``"window"`` (the driver's whole window, host clock)
or a ``{"line", "pattern"}`` selection of trace events whose device time
is the divisor. ``args["bound"]``: ``"flops"`` for a share of the
compute peak (an MFU), ``"roofline"`` for the larger of operations over
peak operations and bytes over peak bandwidth. Nothing to read gives
nothing, never 0.
"""

import importlib
import sys

from chipbench.reduce import NothingToRead


def read(args, trace, facts, config, peaks):
    module, fn = args["counts"].rsplit(".", 1)
    count = getattr(importlib.import_module(f"chipbench.counts.{module}"), fn)
    if args["time"] == "window":
        seconds, n = facts["window_wall_s"], 0
    else:
        try:
            seconds, n = trace.device_time(
                args["time"]["line"], args["time"]["pattern"],
                args["time"].get("within"))
        except NothingToRead:
            return None
    flops, nbytes = count(facts, config, n)
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
