"""chipbench/spans and the readers over it, on two recorded slices of
``--trace 1`` runs on a v5e (PR 24; operations under 0.25-0.4 ms thinned
out, names cut to 64 characters): three scheduler rounds of serve-code
with the engine's span tree, and two steps of train-4k whose operations
carry their framework path. Then one made by hand, a real trace made here
on the CPU, and a trace of a program that has none of the names."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import reduce, spans  # noqa: E402
from chipbench.readers import (idle_under, scope_ms, share_of_peak,  # noqa: E402
                               span_count, span_ms, span_pair_ms)
import manifest_rules as rules  # noqa: E402

FIX = ROOT / "tests/chipbench/fixtures"
METRICS = ROOT / "chipbench/metrics"
STEP = r"^jit_step\("
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TRAIN_CONFIG = {"num_hidden_layers": 4, "num_attention_heads": 24,
                "num_key_value_heads": 2, "hidden_size": 3072}
TRAIN_FACTS = {"seq": 4096, "batch": 4}


def args_of(metric: str) -> dict:
    return json.loads((METRICS / f"{metric}.json").read_text())["args"]


@pytest.fixture(scope="module")
def serve():
    return spans.SpanTrace.from_json(
        (FIX / "serve_spans_slice.json").read_text())


@pytest.fixture(scope="module")
def train():
    return spans.SpanTrace.from_json(
        (FIX / "train_spans_slice.json").read_text())


@pytest.fixture(scope="module")
def nameless():
    """PR 23's slice, of a program with three flat spans, kernels called
    ``%closed_call.N`` and no path: what the parent commit gives."""
    tr = reduce.Trace.from_json((FIX / "serve_trace_slice.json").read_text())
    return tr, spans.SpanTrace(
        [spans.Span(e.line, e.name, e.start, e.dur, {})
         for e in tr.host_events()],
        [spans.Op(e.plane, e.line, e.name, e.start, e.dur)
         for e in tr.events if reduce.DEVICE_PLANE.match(e.plane)])


# -- the slices themselves ----------------------------------------------------

def test_slice_round_trips_and_agrees_with_the_first_reading(serve):
    again = spans.SpanTrace.from_json(serve.to_json())
    assert again.spans == serve.spans and again.ops == serve.ops
    tr = serve.as_trace()
    assert tr.device_planes == ["/device:TPU:0"]
    assert tr.window_s() == pytest.approx(0.568677398)
    assert tr.busy_s() == pytest.approx(0.520311207)
    # 20 layers: a flash call a layer and prefill, a paged call a layer
    # and step of a chunk of 8 -- by the kernels' own names now
    assert tr.device_time(reduce.OPS_LINE, r"^%flash_fwd[.\d]* = ",
                          within=r"^jit__prefill_one\(")[1] == 40
    assert tr.device_time(reduce.OPS_LINE, r"^%flash_decode_paged[.\d]* = ",
                          within=r"^jit__chunk_step\(")[1] == 480
    with pytest.raises(reduce.NothingToRead):
        tr.device_time(reduce.OPS_LINE, r"^%closed_call[.\d]* = ")


@pytest.mark.parametrize("path,n", [
    ("serve.arrivals", 2), ("serve.round", 3),
    ("serve.round/serve.admit_pass", 3),
    ("serve.round/serve.admit_pass/serve.prefill", 2),
    ("serve.round/serve.first_token", 2),
    ("serve.round/serve.decode_dispatch", 3),
    ("serve.round/serve.collect", 3),
    ("serve.round/serve.collect/serve.decode_round", 3),
])
def test_serve_slice_holds_the_engines_tree(serve, path, n):
    got = [s for s in serve.spans if s.path == path]
    assert len(got) == n and all(s.name == path.rsplit("/", 1)[-1]
                                 for s in got)


def test_spans_keep_their_attributes(serve):
    r = serve.named("serve.round")
    assert [s.attrs["round"] for s in r] == [50, 51, 52]
    assert set(r[0].attrs) == {"round", "rows", "queued"}
    p = serve.named("serve.prefill")[0]
    assert p.attrs == {"prompt_len": 2673, "padded_len": 4096, "matched": 0,
                       "seq_id": 50, "slot": 1, "overlapped": "True"}
    assert {s.attrs["seq_id"] for s in serve.named("serve.first_token")} \
        == {s.attrs["seq_id"] for s in serve.named("serve.prefill")}


def test_operations_keep_their_framework_path(train):
    assert train.has_paths()
    fwd = next(o for o in train.ops if o.name.startswith("%flash_fwd"))
    assert fwd.path == ("jit(step)/jvp()/while/body/closed_call/attn/"
                        "flash_fwd/pallas_call")
    bwd = next(o for o in train.ops if o.name.startswith("%flash_bwd_fused"))
    assert bwd.scopes()[:2] == ["step", ""] and "attn" in bwd.scopes()
    assert spans.Op("", "", "", 0, 0, "a/transpose(jvp(mlp))/dot").scopes() \
        == ["a", "mlp", "dot"]


# -- the readers, each on the slice ---------------------------------------------

@pytest.mark.parametrize("metric,want", [
    ("round_p50_ms", 187.624337),
    ("admit_host_ms", 4.6948095),
    ("first_token_wait_ms", 214.5773645),
    ("serve_window_compiles", 0.0),
    ("serve_idle_host_pct", 100 * 0.027162146 / 0.568677398),
    ("serve_idle_readback_pct", 100 * 0.020401945 / 0.568677398),
    ("serve_idle_nowork_pct", 0.0),
])
def test_serving_metric_reads_the_slice(serve, monkeypatch, metric, want):
    spec = json.loads((METRICS / f"{metric}.json").read_text())
    reader = __import__(f"chipbench.readers.{spec['reader']}",
                        fromlist=["read"])
    monkeypatch.setattr(spans, "current", lambda: serve)
    got = reader.read(spec["args"], serve.as_trace(), {}, {}, {})
    assert got == pytest.approx(want, rel=1e-6, abs=1e-9)


@pytest.mark.parametrize("metric,want", [
    ("train_attn_ms", 109.85748), ("train_mlp_ms", 211.4345535),
    ("train_loss_ms", 121.5798475), ("train_update_ms", 29.808079),
])
def test_training_scope_reads_the_slice(train, monkeypatch, metric, want):
    monkeypatch.setattr(spans, "current", lambda: train)
    got = scope_ms.read(args_of(metric), None, {}, {}, {})
    assert got == pytest.approx(want, rel=1e-6)


def test_scopes_stay_inside_the_step(train):
    # the thinned slice keeps 88 % of a step's device time; the scopes are
    # disjoint, so they and embed cannot pass the program's own time
    program = {"program": STEP}
    parts = [scope_ms.compute({"scopes": [s], **program}, train)
             for s in ("attn", "mlp", "loss", "head", "update", "embed")]
    step_ms = 1e3 * train.programs(STEP, "/device:TPU:0")[0].dur
    assert 0.85 * step_ms < sum(parts) < step_ms
    assert scope_ms.compute({"scopes": ["loss", "head"], **program}, train) \
        == pytest.approx(parts[2] + parts[3])
    # a kernel's name= is a part of the path too: the kernels lie in attn
    kernels = scope_ms.compute(
        {"scopes": ["flash_fwd", "flash_bwd_fused"], **program}, train)
    assert kernels == pytest.approx(18.670329 + 29.7723395)
    assert kernels < parts[0]


@pytest.mark.parametrize("metric,seconds,flops_of_fwd", [
    ("flash_train_fwd_roofline", 0.035165504, 1),
    ("flash_train_bwd_roofline", 0.059544679, 2),
])
def test_kernel_rooflines_by_name(train, metric, seconds, flops_of_fwd):
    got = share_of_peak.read(args_of(metric), train.as_trace(), TRAIN_FACTS,
                             TRAIN_CONFIG, PEAKS)
    # 8 calls (4 layers, 2 steps), each the batch of 4 at T = 4096
    flops = 8 * 4 * flops_of_fwd * 2 * 4096 * 4096 * 128 * 24
    assert got == pytest.approx(100 * flops / 197e12 / seconds)
    assert 0 < got < 100


# what the accepted ``flash_train_roofline`` read before it was taken out
# (it read nothing once the kernels were named): both kernels' count over
# ``%closed_call``
SUMMED = {"counts": "flash_attention.train_work",
          "time": {"line": "XLA Ops", "pattern": r"^%closed_call[.\d]* = ",
                   "within": r"^jit_step\("},
          "bound": "roofline"}


def test_the_two_kernels_bracket_what_the_accepted_metric_summed(train):
    tr = train.as_trace()
    one = lambda m: share_of_peak.read(args_of(m), tr, TRAIN_FACTS,
                                       TRAIN_CONFIG, PEAKS)
    both = dict(SUMMED)
    both["time"] = dict(both["time"],
                        pattern=r"^%flash_(fwd|bwd_fused)[.\d]* = ")
    summed = share_of_peak.read(both, tr, TRAIN_FACTS, TRAIN_CONFIG, PEAKS)
    assert one("flash_train_fwd_roofline") < summed \
        < one("flash_train_bwd_roofline")
    # and the accepted pattern itself finds no %closed_call any more
    assert share_of_peak.read(SUMMED, tr, TRAIN_FACTS, TRAIN_CONFIG,
                              PEAKS) is None


def test_idle_classes_and_the_rest_are_the_idle_share(serve):
    tr = serve.as_trace()
    got = idle_under.shares(args_of("serve_idle_host_pct")["classes"], tr,
                            serve)
    assert got[None] == pytest.approx(0.0008021, rel=1e-4)
    assert sum(got.values()) == pytest.approx(tr.idle_share() * tr.window_s())
    assert got[None] < 0.2 * sum(got.values())


def test_the_three_idle_metrics_share_one_table_of_classes():
    tables = [args_of(f"serve_idle_{c}_pct") for c in
              ("host", "readback", "nowork")]
    assert tables[0]["classes"] == tables[1]["classes"] \
        == tables[2]["classes"]
    assert [t["report"] for t in tables] == ["host", "readback", "nowork"]
    names = [n for v in tables[0]["classes"].values() for n in v]
    assert len(names) == len(set(names))


# -- by hand ------------------------------------------------------------------------

def by_hand():
    S, O = spans.Span, spans.Op
    DEV, OPS, MODS = "/device:TPU:0", reduce.OPS_LINE, reduce.MODULES_LINE
    return spans.SpanTrace([
        S("main", "serve.round", 0.0, 6.0, {"round": 1}),
        S("main", "serve.round/serve.collect", 3.0, 3.0, {}),
        S("main", "serve.round/serve.collect/serve.decode_round", 3.5, 1.0,
          {}),
        S("main", "serve.idle_wait", 7.0, 1.0, {}),
        S("main", "serve.round", 8.5, 1.5, {"round": 2}),
        S("main", "serve.round/serve.admit_pass", 8.5, 1.0, {}),
        S("main", "serve.round/serve.admit_pass/serve.prefill", 8.6, 0.2,
          {"seq_id": 7}),
        S("main", "serve.round/serve.admit_pass/serve.prefill", 9.0, 0.2,
          {"seq_id": 8}),
        S("main", "serve.round/serve.first_token", 9.6, 0.3, {"seq_id": 7}),
        S("main", "serve.round/serve.admit_pass/serve.prefill/jit.compiled",
          9.19, 0.001, {"fn": "serving._prefill_one"}),
        S("other", "runtime", 0.0, 10.0, {}),
    ], [
        O(DEV, MODS, "jit_a(1)", 0.0, 4.0), O(DEV, MODS, "jit_a(1)", 9.0, 1.0),
        O(DEV, OPS, "%while.1 = loop", 0.0, 4.0, "jit(a)/while"),
        O(DEV, OPS, "%fusion.1 = f", 0.5, 1.0, "jit(a)/while/body/mlp/dot"),
        O(DEV, OPS, "%flash_fwd.3 = custom-call", 2.0, 1.5,
          "jit(a)/while/body/attn/flash_fwd/pallas_call"),
        O(DEV, OPS, "%fusion.2 = f", 9.0, 1.0,
          "jit(a)/transpose(jvp(mlp))/mul"),
    ])


CLASSES = {"host": ["serve.round", "serve.collect", "serve.admit_pass"],
           "readback": ["serve.decode_round"], "nowork": ["serve.idle_wait"]}


def test_every_idle_instant_goes_to_the_innermost_span():
    st = by_hand()
    # idle: [4, 9]. [4, 4.5] is under decode_round, [4.5, 6] under collect,
    # [6, 7] under no span, [7, 8] idle_wait, [8, 8.5] none, [8.5, 9] admit
    got = idle_under.shares(CLASSES, st.as_trace(), st)
    assert got == pytest.approx({"host": 1.5 + 0.5, "readback": 0.5,
                                 "nowork": 1.0, None: 1.5})
    assert idle_under.compute({"classes": CLASSES, "report": "nowork",
                               "given": "serve.round"},
                              st.as_trace(), st) == pytest.approx(10.0)
    pieces = st.innermost({"serve.round", "serve.collect"})
    assert pieces == pytest.approx([(0.0, 3.0, "serve.round"),
                                    (3.0, 6.0, "serve.collect"),
                                    (8.5, 10.0, "serve.round")])


@pytest.mark.parametrize("reader,args,want", [
    (span_ms, {"span": "serve.round"}, 3750.0),
    (span_ms, {"span": "serve.round", "stat": "median"}, 3750.0),
    (span_ms, {"span": "serve.admit_pass", "per_child": "serve.prefill"},
     500.0),
    (span_ms, {"span": "serve.collect", "per_child": "serve.prefill"}, None),
    (span_ms, {"span": "serve.gone"}, None),
    (span_pair_ms, {"from": "serve.prefill", "to": "serve.first_token",
                    "key": "seq_id"}, 1300.0),   # request 8 has no end yet
    (span_pair_ms, {"from": "serve.prefill", "to": "serve.first_token",
                    "key": "slot"}, None),
    (span_count, {"span": "jit.compiled", "given": "serve.round"}, 1.0),
    (span_count, {"span": "serve.shed", "given": "serve.round"}, 0.0),
    (span_count, {"span": "jit.compiled", "given": "train.step"}, None),
    (scope_ms, {"scopes": ["mlp"], "program": r"^jit_a\("}, 1000.0),
    (scope_ms, {"scopes": ["attn"], "program": r"^jit_a\("}, 750.0),
    (scope_ms, {"scopes": ["flash_fwd"], "program": r"^jit_a\("}, 750.0),
    (scope_ms, {"scopes": ["update"], "program": r"^jit_a\("}, None),
    (scope_ms, {"scopes": ["mlp"], "program": r"^jit_b\("}, None),
])
def test_reader_by_hand(reader, args, want):
    got = reader.compute(args, by_hand())
    assert got is None if want is None else got == pytest.approx(want)


# -- a program without the names, and no trace at all ---------------------------------

NEW = ["round_p50_ms", "admit_host_ms", "first_token_wait_ms",
       "serve_idle_host_pct", "serve_idle_readback_pct",
       "serve_idle_nowork_pct", "serve_window_compiles",
       "flash_train_fwd_roofline", "flash_train_bwd_roofline",
       "train_attn_ms", "train_mlp_ms", "train_loss_ms", "train_update_ms",
       "flash_fwd_prefill_roofline", "flash_decode_paged_roofline"]


@pytest.mark.parametrize("metric", NEW)
def test_on_the_parents_trace_a_new_metric_is_left_out(nameless, monkeypatch,
                                                       metric):
    tr, st = nameless
    spec = json.loads((METRICS / f"{metric}.json").read_text())
    reader = __import__(f"chipbench.readers.{spec['reader']}",
                        fromlist=["read"])
    monkeypatch.setattr(spans, "current", lambda: st)
    facts = {"admissions": [(1.0, 2048, 1500)], "token_instants": [],
             "trace_host_window": (0.0, 9.0), "chunk": 8, **TRAIN_FACTS}
    assert reader.read(spec["args"], tr, facts, TRAIN_CONFIG, PEAKS) is None
    monkeypatch.setattr(spans, "current", lambda: None)
    if spec["reader"] != "share_of_peak":
        assert reader.read(spec["args"], tr, facts, {}, PEAKS) is None


# the first benchmark's metrics, which stand before these (less those
# taken out)
FIRST = ["loadgen_late_p95_ms", "admit_bubble_pct", "queue_p95_ms",
        "prefill_dev_ms", "decode_dev_ms_tok", "prefill_mfu_pct",
        "decode_mfu_pct", "serve_idle_pct", "train_step_p50_ms",
        "train_mfu_pct", "train_peak_hbm_GB", "train_idle_pct",
        "allreduce_small_us", "allreduce_large_ici_pct", "allreduce_idle_pct"]


def test_new_entries_are_appended_and_name_a_layer_of_the_table():
    """Found by name after the first benchmark's, each in the one cell it
    came for first; a list may have grown since."""
    bench = rules.manifest()
    rules.check_own_after(bench, "per_layer", FIRST, NEW)
    for name in NEW:
        cell = "train-4k" if "train" in name else "serve-code"
        rules.check_entry(bench, name, cells=[cell])


# -- the file, from a real trace made here ----------------------------------------------

def test_parse_reads_a_mirrored_span_and_a_compilation_from_a_real_trace(
        tmp_path):
    import jax
    import jax.numpy as jnp
    from hpc_patterns_tpu.harness import metrics as metricslib
    from hpc_patterns_tpu.harness import trace as tracelib
    f = jax.jit(lambda x: jnp.tanh(x) * 2)
    metricslib.configure(enabled=False, mirror_traces=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with metricslib.span("serve.round", round=3, rows=lambda: 2):
            with metricslib.span("serve.prefill", seq_id=11), \
                    tracelib.compile_watch("unit.f", f, padded_len=5):
                f(jnp.ones((5,))).block_until_ready()
    finally:
        jax.profiler.stop_trace()
        metricslib.configure(enabled=False)
    st = spans.current(trace_dir=tmp_path)
    (r,) = st.named("serve.round")
    assert r.attrs == {"round": 3, "rows": 2}
    (p,) = st.named("serve.prefill")
    assert p.path == "serve.round/serve.prefill" and p.attrs == {"seq_id": 11}
    (c,) = st.named("jit.compiled")
    assert c.path == "serve.round/serve.prefill/jit.compiled"
    assert c.attrs == {"fn": "unit.f", "padded_len": 5}
    assert r.start <= p.start <= c.start and c.end <= p.end <= r.end
    assert st.ops == [] and not st.has_paths()   # no chip here
    assert spans.current(trace_dir=tmp_path / "none") is None
