"""Which of the driver's calls ran inside the traced window. The driver's
facts carry host instants (``time.perf_counter``); the trace's host window
is ``facts["trace_host_window"]`` on the same clock."""

from __future__ import annotations


def admissions_traced(facts, n: int):
    """The ``n`` admissions whose prefill the trace holds: the device
    runs them in order, so they are the first ``n`` dispatched from the
    trace's start on (the last ``n``, where fewer follow it)."""
    rows = sorted(facts["admissions"])
    t_start = facts["trace_host_window"][0]
    first = next((i for i, r in enumerate(rows) if r[0] >= t_start),
                 len(rows))
    first = max(0, min(first, len(rows) - n))
    return rows[first:first + n]


def decode_steps_traced(facts):
    """Context lengths of the rows of every decode step whose chunk was
    read back inside the traced window. A chunk's tokens share one
    readback instant; a row's k-th token of a chunk was decoded at
    context prompt + tokens before it."""
    t_start, t_stop = facts["trace_host_window"]
    by_chunk: dict[float, list[list[int]]] = {}
    for prompt_len, instants in facts["token_instants"]:
        run_at, k = None, 0
        for j, t in enumerate(instants):
            if j == 0:        # the prefill's token, no decode step
                continue
            k = k + 1 if t == run_at else 0
            run_at = t
            if t_start <= t <= t_stop:
                steps = by_chunk.setdefault(t, [])
                while len(steps) <= k:
                    steps.append([])
                steps[k].append(prompt_len + j)
    return [lens for steps in by_chunk.values() for lens in steps if lens]
