"""The device's idle share of the traced window: 1 - busy union / window."""


def read(args, trace, facts, config, peaks):
    return 100.0 * trace.idle_share()
