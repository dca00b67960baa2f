"""Known-bad: string-consumed metric names with no live producer —
the minimized replica of "a consumer whose emitter was deleted". The
gauge was renamed and the read kept the old name; the device-window
span is consumed and nothing dispatches it. contractlint flags the
surviving consumer at review time."""


def fit_engine(gauges, records):
    """An autofit-style consumer reading metric names by string."""
    # the gauge was renamed to engine.tok_s; this read kept the old name
    tok_s = gauges.get("engine.tokens_per_s")  # EXPECT: gate-key-orphan
    chunks = _windows(records, "engine.chunk")  # EXPECT: gate-key-orphan
    return tok_s, chunks


def _windows(records, name):
    return [r for r in records if r[0] == name]


def emit(metrics, engine_result):
    metrics.gauge("engine.tok_s", engine_result["tok_s"])
