"""The engine's span tree (models/serving.py through harness/metrics.span):
one ``serve.round`` a scheduler round with its phases as children down to
the statement that waits (the limits' readback, a table's upload, a
finished row's release), a request's spans under one ``seq_id``, the no-op
path when nothing listens, ``jit.compiled`` from compile_watch and
``jit.event`` from the jax.monitoring listener when the registry mirrors
into the profiler, and the listener's bounded log."""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness import trace as tracelib
from hpc_patterns_tpu.models import TransformerConfig, init_params
from hpc_patterns_tpu.models.serving import ContinuousBatcher, EngineCore

CFG = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
           max_seq=64, dtype="float32")


@pytest.fixture(autouse=True)
def _registry_off_afterwards():
    yield
    tracelib.configure(enabled=False)
    metricslib.configure(enabled=False)


def _engine(**kw):
    cfg = TransformerConfig(**CFG)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return ContinuousBatcher(params, cfg, slots=2, pool_pages=6,
                             pages_per_seq=3, page_size=8, chunk=2, **kw)


def _arrivals(n=4, late=1.0, first_id=100):
    """Requests due at once, and one due after the others have drained,
    so that the loop also idles (``late`` seconds: a warm engine serves
    the first three in a few hundredths)."""
    rng = np.random.RandomState(2)
    due = [0.0] * (n - 1) + [late]
    return [(t, dict(prompt=rng.randint(0, 64, size=5 + i).astype(np.int32),
                     max_new=4, seq_id=first_id + i))
            for i, t in enumerate(due)]


def _went_live(self, name, attrs):
    raise AssertionError(f"span {name!r} went live with nothing on")


class Sink:
    """What the flight recorder is to ``metrics.span``: begin and end of
    every span, with its path and attributes."""

    def __init__(self):
        self.begun = []   # (path, attrs)

    def span_begin(self, path, attrs, t0):
        self.begun.append((path, dict(attrs)))

    def span_end(self, path, t1):
        pass


@pytest.fixture(scope="module")
def recorded():
    """One open-loop run with the registry on and a sink installed."""
    sink = Sink()
    eng = _engine(preempt=True)
    eng.run(arrivals=_arrivals(late=0.0, first_id=0))   # every shape, warm
    m = metricslib.configure(enabled=True)
    metricslib._trace_sink = sink
    try:
        finished = eng.run(arrivals=_arrivals())
    finally:
        metricslib._trace_sink = None
    assert sorted(finished) == [0, 1, 2, 3, 100, 101, 102, 103]
    return sink.begun, m.snapshot()["histograms"]


FINISH = "serve.round/serve.collect/serve.collect_rows/serve.finish"
TREE = [
    "serve.arrivals",
    "serve.arrivals/serve.submit",
    "serve.idle_wait",
    "serve.round",
    "serve.round/serve.preempt_policy",
    "serve.round/serve.admit_pass",
    "serve.round/serve.admit_pass/serve.table_upload",
    "serve.round/serve.admit_pass/serve.prefill",
    "serve.round/serve.admit_pass/serve.admit_row",
    "serve.round/serve.first_token",
    "serve.round/serve.decode_dispatch",
    "serve.round/serve.collect",
    "serve.round/serve.collect/serve.decode_round",
    "serve.round/serve.collect/serve.cursor_sync",
    "serve.round/serve.collect/serve.collect_rows",
    FINISH,
    f"{FINISH}/serve.release",
    f"{FINISH}/serve.release/serve.table_upload",
]


@pytest.mark.parametrize("path", TREE)
def test_run_yields_the_span_tree(recorded, path):
    begun, histograms = recorded
    assert path in {p for p, _ in begun}
    assert histograms[f"span.{path}"]["count"] >= 1


def test_no_span_of_the_engine_lies_outside_the_tree(recorded):
    begun, _ = recorded
    assert {p for p, _ in begun} <= set(TREE)


def test_round_rises_by_one_a_round_and_children_share_it(recorded):
    begun, _ = recorded
    rounds = [a["round"] for p, a in begun if p == "serve.round"]
    assert rounds == list(range(rounds[0], rounds[0] + len(rounds)))
    assert len(rounds) >= 3
    current = None
    for path, attrs in begun:
        if path == "serve.round":
            current = attrs["round"]
        elif path.startswith("serve.round/") and "round" in attrs:
            assert attrs["round"] == current, path


@pytest.mark.parametrize("span,keys", [
    ("serve.arrivals", {"n"}),
    ("serve.arrivals/serve.submit", {"seq_id", "late_ms"}),
    ("serve.round/serve.admit_pass/serve.table_upload", {"bytes"}),
    ("serve.round/serve.admit_pass/serve.admit_row", {"seq_id", "slot"}),
    ("serve.round/serve.collect/serve.cursor_sync", {"site", "round"}),
    ("serve.round/serve.collect/serve.collect_rows", {"rows", "round"}),
    (FINISH, {"seq_id", "slot", "tokens"}),
    (f"{FINISH}/serve.release", {"slot", "pages"}),
    (f"{FINISH}/serve.release/serve.table_upload", {"bytes"}),
    ("serve.round", {"round", "rows", "queued"}),
    ("serve.round/serve.admit_pass/serve.prefill",
     {"seq_id", "slot", "overlapped", "ahead", "prompt_len", "padded_len",
      "matched", "queued_ms"}),
    ("serve.round/serve.first_token", {"seq_id", "slot"}),
    ("serve.round/serve.decode_dispatch",
     {"rows", "chunk", "round", "ctx_tokens", "kv_pages"}),
    ("serve.round/serve.collect", {"rows", "round"}),
])
def test_spans_carry_their_attributes(recorded, span, keys):
    begun, _ = recorded
    got = [a for p, a in begun if p == span]
    assert got
    for attrs in got:
        assert keys <= set(attrs), (span, attrs)


def test_the_new_attributes_say_what_they_name(recorded):
    begun, _ = recorded
    by = lambda path: [a for p, a in begun if p == path]
    assert {a["site"] for a in by(
        "serve.round/serve.collect/serve.cursor_sync")} == {"collect"}
    # a table of 2 slots x 3 pages of int32, or one row of it
    assert {a["bytes"] for p, a in begun
            if p.endswith("/serve.table_upload")} == {24, 12}
    # three requests were due at once and one a second later: each is
    # drained a little late, and waits from its due instant to its prefill
    late = [a["late_ms"] for a in by("serve.arrivals/serve.submit")]
    waited = [a["queued_ms"] for a in by(
        "serve.round/serve.admit_pass/serve.prefill")]
    assert len(late) == len(waited) == 4
    assert all(0.0 <= x < 1e3 for x in late + waited)
    assert sorted(a["tokens"] for a in by(FINISH)) == [4, 4, 4, 4]
    assert all(a["pages"] >= 1 for a in by(f"{FINISH}/serve.release"))
    rows = by("serve.round/serve.collect/serve.collect_rows")
    rounds = by("serve.round/serve.collect")
    assert [a["round"] for a in rows] == [a["round"] for a in rounds]


@pytest.mark.parametrize("name", ["serve.submit", "serve.prefill",
                                  "serve.admit_row", "serve.first_token",
                                  "serve.finish"])
def test_a_requests_spans_carry_its_one_seq_id(recorded, name):
    """From due to done under one identifier: each request has exactly
    one span of every kind, and they come in this order."""
    begun, _ = recorded
    ids = [a["seq_id"] for p, a in begun if p.endswith(f"/{name}")]
    assert sorted(ids) == [100, 101, 102, 103]
    order = {(a["seq_id"], p.rsplit("/", 1)[-1]): i
             for i, (p, a) in enumerate(begun) if "seq_id" in a}
    for sid in ids:
        assert (order[sid, "serve.submit"] < order[sid, "serve.prefill"]
                < order[sid, "serve.admit_row"]
                < order[sid, "serve.first_token"]
                < order[sid, "serve.finish"])


def test_every_prefill_has_a_first_token_of_the_same_request(recorded):
    begun, _ = recorded
    prefills = [a["seq_id"] for p, a in begun if p.endswith("/serve.prefill")]
    firsts = [a["seq_id"] for p, a in begun
              if p.endswith("/serve.first_token")]
    assert sorted(prefills) == sorted(firsts) == [100, 101, 102, 103]
    n = sum(a["n"] for p, a in begun if p == "serve.arrivals")
    assert n == 4


@pytest.mark.parametrize("overlap", [True, False])
def test_with_nothing_listening_every_site_takes_the_shared_nullcontext(
        monkeypatch, overlap):
    metricslib.configure(enabled=False)
    tracelib.configure(enabled=False)

    def rows(self):
        raise AssertionError("an attribute was computed for a dead span")

    monkeypatch.setattr(metricslib.Metrics, "_span", _went_live)
    monkeypatch.setattr(EngineCore, "active_count", property(rows))
    seen = []
    real = metricslib.Metrics.span

    def span(self, name, **attrs):
        got = real(self, name, **attrs)
        seen.append((name, got))
        return got

    monkeypatch.setattr(metricslib.Metrics, "span", span)
    eng = _engine(preempt=True, overlap=overlap)
    assert sorted(eng.run(arrivals=_arrivals())) == [100, 101, 102, 103]
    assert {n for n, _ in seen} >= {t.rsplit("/", 1)[-1] for t in TREE}
    assert all(got is metricslib._NULL_SPAN for _, got in seen)
    assert tracelib.compile_watch("a", None) is tracelib._NULL


@pytest.mark.parametrize("step,want", [("new rung", 1), ("warm rung", 1),
                                       ("second rung", 2)])
def test_compile_watch_mirrors_one_marker_a_compilation(step, want):
    m = metricslib.configure(enabled=True, mirror_traces=True)
    assert tracelib.active() is None   # no flight recorder: the registry
    f = jax.jit(lambda x: x * 3 + 1)
    shapes = {"new rung": [(3,)], "warm rung": [(3,), (3,)],
              "second rung": [(3,), (3,), (5,)]}[step]
    for shape in shapes:
        with metricslib.span("serve.prefill"), \
                tracelib.compile_watch("unit.f", f, padded_len=shape[0]):
            f(jnp.ones(shape))
    h = m.snapshot()["histograms"]
    assert h["span.serve.prefill/jit.compiled"]["count"] == want
    assert h["span.serve.prefill"]["count"] == len(shapes)


def test_compile_watch_marker_carries_fn_and_the_watchs_attributes(
        monkeypatch):
    metricslib.configure(enabled=False, mirror_traces=True)
    sink = Sink()
    monkeypatch.setattr(metricslib, "_trace_sink", sink)
    f = jax.jit(lambda x: x - 2)
    with tracelib.compile_watch("unit.g", f, padded_len=7):
        f(jnp.ones((7,)))
    # (with the listener installed, jit.event markers come before it)
    assert [b for b in sink.begun if b[0] != "jit.event"] == [
        ("jit.compiled", {"fn": "unit.g", "padded_len": 7})]


def test_a_lazy_attribute_is_called_once_when_the_span_is_live():
    calls = []
    m = metricslib.configure(enabled=True)
    with m.span("x", cost=lambda: calls.append(1) or 5):
        pass
    assert calls == [1]
    assert isinstance(metricslib.configure(enabled=False).span(
        "x", cost=lambda: calls.append(1)), contextlib.nullcontext)
    assert calls == [1]


# -- jit.event: what jax.monitoring reports, wherever it compiles -----------

def _jit_events(begun):
    return [(p, a) for p, a in begun if p.rsplit("/", 1)[-1] == "jit.event"]


def test_a_warm_round_fires_no_jit_event_and_a_cold_eager_op_fires_once(
        monkeypatch):
    """The engine's eager pieces (``.at[].set``, ``jnp.asarray``) sit
    under no compile_watch: one of them compiling inside a round is seen
    by the listener, under the span it happened in, once an event kind."""
    eng = _engine()
    eng.run(arrivals=_arrivals(late=0.0, first_id=0))   # every shape, warm
    x = jax.block_until_ready(jnp.ones((7, 3)))
    metricslib.configure(enabled=False, mirror_traces=True)
    sink = Sink()
    monkeypatch.setattr(metricslib, "_trace_sink", sink)
    n0 = len(tracelib.compile_events())
    eng.run(arrivals=_arrivals(late=0.0))
    assert _jit_events(sink.begun) == []
    assert len(tracelib.compile_events()) == n0

    release = type(eng)._release_slot
    cold = []

    def release_and_one_new_eager_program(self, slot):
        release(self, slot)
        if not cold:   # an op-by-op program this process has not compiled
            cold.append(jnp.arctan(x))

    monkeypatch.setattr(type(eng), "_release_slot",
                        release_and_one_new_eager_program)
    sink.begun.clear()
    eng.run(arrivals=_arrivals(late=0.0, first_id=200))
    fired = _jit_events(sink.begun)
    assert fired and all(p.startswith("serve.round/") for p, _ in fired)
    assert [a["event"] for _, a in fired] == ["trace", "lower",
                                              "backend_compile"]
    assert {a["fn"] for _, a in fired} == {"arctan", "jit(arctan)"}
    assert all(a["secs"] > 0 and isinstance(a["fn"], str) for _, a in fired)
    # the log holds the same events, each with its host instant
    logged = tracelib.compile_events()[n0:]
    assert [(k, s) for _, k, s, _ in logged] == [
        (a["event"], a["secs"]) for _, a in fired]
    assert [t for t, *_ in logged] == sorted(t for t, *_ in logged)
    # and the next round of the same engine is warm again
    sink.begun.clear()
    eng.run(arrivals=_arrivals(late=0.0, first_id=300))
    assert _jit_events(sink.begun) == []


def test_the_listener_logs_with_nothing_listening_and_marks_nothing(
        monkeypatch):
    metricslib.configure(enabled=False)
    tracelib.configure(enabled=False)
    assert tracelib.install_monitoring_listener()

    monkeypatch.setattr(metricslib.Metrics, "_span", _went_live)
    n0 = len(tracelib.compile_events())
    jax.jit(lambda x: x * 5 - 3)(jnp.ones((11,)))
    new = tracelib.compile_events()[n0:]
    assert {k for _, k, _, _ in new} >= {"trace", "lower", "backend_compile"}


def test_a_nested_traces_seconds_are_taken_off_the_outer_one(monkeypatch):
    """jax reports an inner jitted function first and the outer one with
    the inner's time inside its own: the log keeps self seconds, which add
    up to the time spent."""
    from collections import deque

    monkeypatch.setattr(tracelib, "_compile_log", deque(maxlen=64))
    clock = iter([10.0, 10.5, 11.0, 30.0])
    monkeypatch.setattr(tracelib.time, "perf_counter", lambda: next(clock))
    event = "/jax/core/compile/jaxpr_trace_duration"
    tracelib._monitoring_listener(event, 0.25, fun_name="before")
    tracelib._monitoring_listener(event, 0.3, fun_name="inner")
    tracelib._monitoring_listener(event, 0.75, fun_name="outer")
    tracelib._monitoring_listener(event, 2.0, fun_name="later")
    assert [(fn, s) for _, _, s, fn in tracelib.compile_events()] == [
        ("before", 0.25), ("inner", 0.3), ("outer", pytest.approx(0.45)),
        ("later", 2.0)]


def test_the_compile_log_is_bounded(monkeypatch):
    from collections import deque

    monkeypatch.setattr(tracelib, "_compile_log", deque(maxlen=5))
    for i in range(12):
        tracelib._monitoring_listener(
            "/jax/core/compile/backend_compile_duration", 0.5 + i,
            fun_name=f"f{i}")
    tracelib._monitoring_listener("/jax/some/other_event", 1.0)
    got = tracelib.compile_events()
    assert [(k, s, fn) for _, k, s, fn in got] == [
        ("backend_compile", 0.5 + i, f"f{i}") for i in range(7, 12)]
    assert tracelib._compile_log.maxlen == 5
    assert tracelib.COMPILE_LOG_CAPACITY >= 1024
