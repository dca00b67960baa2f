"""A number the driver measured itself (a host-clock time, a counter of
the program): ``args["key"]`` of the run's facts."""


def read(args, trace, facts, config, peaks):
    return facts.get(args["key"])
