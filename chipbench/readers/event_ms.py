"""Mean device time, in ms, of the events on ``args["line"]`` whose name
matches ``args["pattern"]``; divided by ``facts[args["per"]]`` where a
program runs that many steps in one event."""

from chipbench.reduce import NothingToRead


def read(args, trace, facts, config, peaks):
    try:
        seconds, n = trace.device_time(args["line"], args["pattern"],
                                       args.get("within"))
    except NothingToRead:
        return None
    return 1e3 * seconds / n / (facts[args["per"]] if "per" in args else 1)
