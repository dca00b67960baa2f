"""The engine's span tree (models/serving.py through harness/metrics.span):
one ``serve.round`` a scheduler round with its phases as children, the
no-op path when nothing listens, and ``jit.compiled`` from compile_watch
when the registry mirrors into the profiler."""

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


TREE = [
    "serve.arrivals",
    "serve.idle_wait",
    "serve.round",
    "serve.round/serve.preempt_policy",
    "serve.round/serve.admit_pass",
    "serve.round/serve.admit_pass/serve.prefill",
    "serve.round/serve.first_token",
    "serve.round/serve.decode_dispatch",
    "serve.round/serve.collect",
    "serve.round/serve.collect/serve.decode_round",
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
    ("serve.round", {"round", "rows", "queued"}),
    ("serve.round/serve.admit_pass/serve.prefill",
     {"seq_id", "slot", "overlapped", "prompt_len", "padded_len", "matched"}),
    ("serve.round/serve.first_token", {"seq_id", "slot"}),
    ("serve.round/serve.decode_dispatch",
     {"rows", "chunk", "round", "ctx_tokens", "kv_pages"}),
    ("serve.round/serve.collect", {"rows", "round"}),
])
def test_spans_carry_their_attributes(recorded, span, keys):
    begun, _ = recorded
    for attrs in (a for p, a in begun if p == span):
        assert keys <= set(attrs), (span, attrs)


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

    def live(self, name, attrs):
        raise AssertionError(f"span {name!r} went live with nothing on")

    def rows(self):
        raise AssertionError("an attribute was computed for a dead span")

    monkeypatch.setattr(metricslib.Metrics, "_span", live)
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
    assert sink.begun == [("jit.compiled",
                           {"fn": "unit.g", "padded_len": 7})]


def test_a_lazy_attribute_is_called_once_when_the_span_is_live():
    calls = []
    m = metricslib.configure(enabled=True)
    with m.span("x", cost=lambda: calls.append(1) or 5):
        pass
    assert calls == [1]
    assert isinstance(metricslib.configure(enabled=False).span(
        "x", cost=lambda: calls.append(1)), contextlib.nullcontext)
    assert calls == [1]
