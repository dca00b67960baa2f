"""Known-clean: every metric name and span name consumed here has a
live producer in the same tree. Zero findings expected."""


def fit_engine(gauges, records):
    """An autofit-style consumer reading metric names by string."""
    tok_s = gauges.get("engine.tok_s")
    chunks = _windows(records, "engine.chunk")
    return tok_s, chunks


def _windows(records, name):
    return [r for r in records if r[0] == name]


def emit(metrics, rec, engine_result, t0, t1):
    metrics.gauge("engine.tok_s", engine_result["tok_s"])
    rec.mark_dispatch("engine.chunk", t0)
    rec.mark_complete("engine.chunk", t1)
