"""Device self time, in ms, of the operations traced under a
``jax.named_scope``, per executed program.

An operation counts when a part of its framework path is one of
``args["scopes"]`` (a kernel's ``name=`` is such a part too); its self
time is its event's time less the events nested inside it, so a loop
gives its body's time to the body's scopes. Only operations inside the
events of ``XLA Modules`` matching ``args["program"]`` count, and the sum
is divided by the number of those events. A trace whose operations carry
no path, or no such program, gives nothing."""

from chipbench import spans


def compute(args, st):
    if not st.has_paths():
        return None
    want = set(args["scopes"])
    total = runs = 0
    for plane in st.device_planes():
        programs = st.programs(args["program"], plane)
        runs += len(programs)
        total += sum(t for o, t in st.self_times(
            plane, within=[(p.start, p.end) for p in programs])
            if want.intersection(o.scopes()))
    return 1e3 * total / runs if runs and total > 0 else None


def read(args, trace, facts, config, peaks):
    st = spans.current()
    return None if st is None else compute(args, st)
