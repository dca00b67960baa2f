"""PR 34's readers and metrics over the engine's finer span tree, on a
slice made by hand (``fixtures/round_spans_by_hand.json``: two scheduler
rounds in one traced second, every idle instant of the chip placed under
a known span, so each share can be reckoned on paper), on two slices
recorded from this PR's runs on the chip, on the accepted slice of a
program that has none of the new spans (what a parent commit gives), and
the manifest pinned as a PREFIX."""

import dataclasses
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import spans  # noqa: E402
from chipbench.readers import (idle_pct, idle_under, idle_unspanned,  # noqa: E402
                               span_attr_stat, span_self_ms)
import manifest_rules as rules  # noqa: E402

FIX = ROOT / "tests/chipbench/fixtures"
METRICS = ROOT / "chipbench/metrics"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
ACCEPTED = json.loads((FIX / "accepted-manifest-pr33.json").read_text())

SERVING = ["serve-code", "serve-chat", "serve-gen", "serve-assist",
           "serve-diffuse"]
# name: (unit, source, layer, moves, cells)
NEW = {
    "serve_idle_collect_pct": ("%", "device_trace", "device",
                               "tpot_p95_ms", SERVING),
    "serve_idle_cursor_sync_pct": ("%", "device_trace", "device",
                                   "tpot_p95_ms", SERVING[:-1]),
    "serve_idle_admit_pct": ("%", "device_trace", "device", "ttft_p95_ms",
                             SERVING),
    "serve_idle_upload_pct": ("%", "device_trace", "device", "tpot_p95_ms",
                              SERVING),
    "serve_idle_loop_pct": ("%", "device_trace", "device", "tpot_p95_ms",
                            SERVING),
    "serve_idle_unspanned_pct": ("%", "device_trace", "device",
                                 "tpot_p95_ms", SERVING),
    "collect_host_ms": ("ms", "program_span", "engine", "tpot_p95_ms",
                        SERVING),
    "cursor_sync_ms": ("ms", "program_span", "engine", "tpot_p95_ms",
                       SERVING[:-1]),
    "finish_ms": ("ms", "program_span", "engine", "tpot_p95_ms", SERVING),
    "submit_late_ms": ("ms", "program_span", "load generator",
                       "ttft_p95_ms", SERVING),
    "queue_wait_ms": ("ms", "program_span", "engine", "ttft_p95_ms",
                      SERVING),
    "serve_window_compile_ms": ("ms", "program_span", "model step",
                                "ttft_p95_ms", SERVING),
}
SPLIT = ["serve_idle_collect_pct", "serve_idle_cursor_sync_pct",
         "serve_idle_admit_pct", "serve_idle_upload_pct",
         "serve_idle_loop_pct"]   # what the accepted "host" class holds


def spec_of(metric: str) -> dict:
    return json.loads((METRICS / f"{metric}.json").read_text())


def read(metric: str, st, monkeypatch):
    spec = spec_of(metric)
    reader = __import__(f"chipbench.readers.{spec['reader']}",
                        fromlist=["read"])
    monkeypatch.setattr(spans, "current", lambda: st)
    return reader.read(spec["args"], st.as_trace(), {}, {}, {})


def without(st, *names):
    """The slice with every span of these names (and what lies under one
    by path) taken out."""
    cut = tuple(f"/{n}/" for n in names)
    return spans.SpanTrace(
        [s for s in st.spans
         if not any(c in f"/{s.path}/" for c in cut)], st.ops)


@pytest.fixture(scope="module")
def hand():
    return spans.SpanTrace.from_json(
        (FIX / "round_spans_by_hand.json").read_text())


@pytest.fixture(scope="module")
def recorded():
    """PR 24's recorded slice of serve-code: the engine's tree as the
    parent commit has it, without this PR's spans."""
    return spans.SpanTrace.from_json(
        (FIX / "serve_spans_slice.json").read_text())


@pytest.fixture(scope="module", params=["gen", "code"])
def recorded_now(request):
    """Two slices of this PR's ``--trace 1`` runs on a v5e, 0.45 s each
    (``.chipwork/probe.py --slice``: the program's spans that lie whole in
    the slice, the programs' events and the operations of 0.4 ms and more,
    names cut to 64 characters): seven rounds of serve-gen with a finished
    row, and one long round of serve-code with four admissions. A slice
    begins mid-round, as a trace does: some spans' parents are cut off."""
    return spans.SpanTrace.from_json(
        (FIX / f"round_spans_slice_{request.param}.json").read_text())


# -- the slice made by hand ------------------------------------------------------

def test_the_hand_made_slice_is_one_second_with_four_gaps(hand):
    tr = hand.as_trace()
    assert tr.window_s() == pytest.approx(1.0)
    assert tr.busy_s() == pytest.approx(0.83)
    assert idle_pct.read({}, tr, {}, {}, {}) == pytest.approx(17.0)


# seconds of the chip's 0.17 idle ones under each class, reckoned on paper
# from the fixture's intervals (the docstring of each case says where)
@pytest.mark.parametrize("metric,want", [
    # 0.42-0.445 under collect_rows, finish and release in turn
    ("serve_idle_collect_pct", 2.5),
    # 0.10-0.12 before the dispatch, 0.41-0.42 after the readback
    ("serve_idle_cursor_sync_pct", 3.0),
    # 0.90-0.93: a prefill, the row's install, the pass's own tail
    ("serve_idle_admit_pct", 3.0),
    # 0.445-0.45: the release's upload, innermost of four spans
    ("serve_idle_upload_pct", 0.5),
    # 0.12-0.14 a dispatch and the round's own time, 0.93-0.95 the round's
    ("serve_idle_loop_pct", 4.0),
    # 0.715-0.72 after the idle wait, 0.95-0.96 between two rounds
    ("serve_idle_unspanned_pct", 1.5),
    ("serve_idle_host_pct", 13.0),
    ("serve_idle_readback_pct", 1.0),
    ("serve_idle_nowork_pct", 1.5),
    # (0.15 + 0.06) s of collect less the readbacks inside them (0.059 +
    # 0.039; the one at 0.01 has no parent in the trace), over two rounds
    ("collect_host_ms", 56.0),
    # 0.02 + 0.01 + 0.001 + 0.005 s over two rounds
    ("cursor_sync_ms", 18.0),
    ("finish_ms", 40.0),
    ("submit_late_ms", 4.0),
    ("queue_wait_ms", 30.0),
    # trace 1 + lower 2 + backend compile 10 ms; the cache load's 4 ms lie
    # inside the backend compile's
    ("serve_window_compile_ms", 13.0),
    ("serve_window_compiles", 1.0),
])
def test_metric_reads_the_hand_made_slice(hand, monkeypatch, metric, want):
    assert read(metric, hand, monkeypatch) == pytest.approx(want, abs=1e-9)


def split_of(st, monkeypatch):
    got = {m: read(m, st, monkeypatch) for m in SPLIT + [
        "serve_idle_host_pct", "serve_idle_readback_pct",
        "serve_idle_nowork_pct", "serve_idle_unspanned_pct",
        "serve_idle_pct"]}
    assert all(v is not None and v >= 0.0 for v in got.values())
    return got, sum(got[m] for m in SPLIT)


@pytest.mark.parametrize("which", ["by hand", "recorded"])
def test_the_split_is_whole(which, hand, recorded, monkeypatch, capsys):
    """The identity that proves it: the five new classes are the accepted
    host class, and with the readback, the want of work and what lies
    under no span they are the chip's whole idle share; the last is what
    ``idle_under`` prints as unattributed."""
    st = hand if which == "by hand" else recorded
    got, five = split_of(st, monkeypatch)
    assert five == pytest.approx(got["serve_idle_host_pct"], abs=1e-9)
    assert (five + got["serve_idle_readback_pct"]
            + got["serve_idle_nowork_pct"] + got["serve_idle_unspanned_pct"]
            ) == pytest.approx(got["serve_idle_pct"], abs=1e-9)
    capsys.readouterr()
    read("serve_idle_loop_pct", st, monkeypatch)
    said = capsys.readouterr().err
    assert f"unattributed {got['serve_idle_unspanned_pct']:.3f} of idle" \
        in said


def test_on_a_trace_that_begins_mid_round_the_split_names_the_orphans(
        recorded_now, monkeypatch):
    """What the chip runs showed (PERF.md, PR 34): a span whose parents
    began before the trace did is under NO accepted name and under a new
    one, so the five classes exceed the accepted host class by exactly
    what the accepted classes leave unattributed and the new ones do not;
    the eight classes are the whole idle share all the same."""
    got, five = split_of(recorded_now, monkeypatch)
    assert (five + got["serve_idle_readback_pct"]
            + got["serve_idle_nowork_pct"] + got["serve_idle_unspanned_pct"]
            ) == pytest.approx(got["serve_idle_pct"], abs=1e-9)
    tr = recorded_now.as_trace()
    old = idle_under.shares(
        spec_of("serve_idle_host_pct")["args"]["classes"], tr, recorded_now)
    orphans = 100 * old[None] / tr.window_s() \
        - got["serve_idle_unspanned_pct"]
    assert orphans > 0.01
    assert five - got["serve_idle_host_pct"] == pytest.approx(orphans,
                                                              abs=1e-9)


@pytest.mark.parametrize("path", [
    "serve.round/serve.cursor_sync",
    "serve.round/serve.collect/serve.cursor_sync",
    "serve.round/serve.collect/serve.collect_rows",
    "serve.round/serve.collect/serve.collect_rows/serve.finish",
    "serve.round/serve.collect/serve.collect_rows/serve.finish/serve.release",
    "serve.round/serve.collect/serve.collect_rows/serve.finish/serve.release"
    "/serve.table_upload",
])
def test_recorded_slices_hold_the_finer_tree(recorded_now, path):
    assert any(s.path == path for s in recorded_now.spans)


def test_the_recorded_admissions_carry_one_seq_id_and_their_waits(
        monkeypatch):
    st = spans.SpanTrace.from_json(
        (FIX / "round_spans_slice_code.json").read_text())
    ids = lambda name: sorted(s.attrs["seq_id"] for s in st.named(name))
    assert ids("serve.prefill") == ids("serve.admit_row") == [59, 60, 61, 62]
    assert ids("serve.submit") == [61, 62]
    assert {s.attrs["bytes"] for s in st.named("serve.table_upload")} \
        == {2048, 64}   # 32 slots x 16 pages of int32, and one row
    assert read("queue_wait_ms", st, monkeypatch) == pytest.approx(
        38.00393277, rel=1e-6)
    assert read("submit_late_ms", st, monkeypatch) == pytest.approx(
        37.30740070, rel=1e-6)
    assert read("finish_ms", st, monkeypatch) == pytest.approx(4.558349,
                                                               rel=1e-6)
    assert read("serve_window_compile_ms", st, monkeypatch) == 0.0


def test_every_idle_metric_lists_every_class_and_no_compile_marker():
    classes = spec_of("serve_idle_collect_pct")["args"]["classes"]
    assert list(classes) == ["collect", "cursor_sync", "admit", "upload",
                             "loop", "readback", "nowork"]
    names = [n for c in classes.values() for n in c]
    assert len(names) == len(set(names))
    assert not {"jit.compiled", "jit.event"} & set(names)
    old = spec_of("serve_idle_host_pct")["args"]["classes"]
    assert classes["readback"] == old["readback"]
    assert classes["nowork"] == old["nowork"]
    # what the accepted host class names is split, none of it dropped
    assert set(old["host"]) - {"jit.compiled"} <= set(names)
    for m in SPLIT + ["serve_idle_unspanned_pct"]:
        args = spec_of(m)["args"]
        assert args["classes"] == classes and args["given"] == "serve.round"
        assert args.get("report", "collect") in classes


# -- where there is less to read ---------------------------------------------------

def test_a_class_without_a_span_reads_zero_where_the_loop_is_spanned(
        hand, recorded, monkeypatch):
    bare = without(hand, "serve.cursor_sync", "serve.table_upload")
    assert read("serve_idle_cursor_sync_pct", bare, monkeypatch) == 0.0
    assert read("serve_idle_upload_pct", bare, monkeypatch) == 0.0
    # their instants fall to the spans around them: nothing is lost
    assert read("serve_idle_loop_pct", bare, monkeypatch) == \
        pytest.approx(4.0 + 2.0)
    assert read("serve_idle_collect_pct", bare, monkeypatch) == \
        pytest.approx(2.5 + 1.0 + 0.5)
    # the parent's tree: the round's whole host share is collect + admit
    # + loop, and the finer classes read 0
    assert read("serve_idle_cursor_sync_pct", recorded, monkeypatch) == 0.0
    assert read("serve_idle_upload_pct", recorded, monkeypatch) == 0.0


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_trace_without_the_round_reads_nothing(hand, monkeypatch, metric):
    flat = spans.SpanTrace(
        [dataclasses.replace(s, path="something.else") for s in hand.spans],
        hand.ops)
    assert read(metric, flat, monkeypatch) is None


@pytest.mark.parametrize("metric", ["cursor_sync_ms", "finish_ms",
                                    "submit_late_ms", "queue_wait_ms",
                                    "serve_window_compile_ms"])
def test_the_parents_trace_gives_nothing_and_does_not_raise(
        recorded, monkeypatch, metric):
    """Its prefills carry no ``queued_ms``, it has no ``serve.submit``,
    ``serve.finish`` or ``serve.cursor_sync``, and without
    ``serve.collect_rows`` nothing says that it would report a
    compilation: no 0 is read where the program says nothing."""
    assert read(metric, recorded, monkeypatch) is None


def test_the_parents_trace_still_has_a_collects_own_time(recorded,
                                                         monkeypatch):
    got = read("collect_host_ms", recorded, monkeypatch)
    own = sum(s.dur for s in recorded.named("serve.collect")) - sum(
        s.dur for s in recorded.named("serve.decode_round"))
    assert got == pytest.approx(1e3 * own / 3)


def test_no_compilation_in_a_spanned_window_reads_zero(hand, monkeypatch):
    warm = without(hand, "jit.event", "jit.compiled")
    assert read("serve_window_compile_ms", warm, monkeypatch) == 0.0
    assert read("serve_window_compiles", warm, monkeypatch) == 0.0
    # only cache loads: the seconds are the backend compile's, not theirs
    loads = spans.SpanTrace(
        [s for s in hand.spans
         if s.name != "jit.event" or s.attrs["event"] == "cache_load"],
        hand.ops)
    assert read("serve_window_compile_ms", loads, monkeypatch) == 0.0


def test_span_self_ms_divides_by_itself_unless_told(hand):
    assert span_self_ms.compute({"span": "serve.collect"}, hand) == \
        pytest.approx(105.0)
    assert span_self_ms.compute(
        {"span": "serve.collect", "less": ["serve.decode_round",
                                           "serve.cursor_sync"]}, hand) == \
        pytest.approx(1e3 * (0.21 - 0.098 - 0.015) / 2)
    assert span_self_ms.compute(
        {"span": "serve.finish", "per": "serve.round"}, hand) == \
        pytest.approx(20.0)
    assert span_self_ms.compute({"span": "serve.collect",
                                 "per": "serve.nothing"}, hand) is None


def test_span_attr_stat_filters_sums_and_scales(hand):
    args = {"span": "jit.event", "attr": "secs", "stat": "sum"}
    assert span_attr_stat.compute(args, hand) == pytest.approx(0.017)
    assert span_attr_stat.compute(
        {**args, "where": {"event": ["cache_load"]}, "scale": 1e3},
        hand) == pytest.approx(4.0)
    assert span_attr_stat.compute(
        {**args, "where": {"event": ["nothing"]}}, hand) is None
    assert span_attr_stat.compute({"span": "serve.round", "attr": "rows"},
                                  hand) == pytest.approx(3.0)
    assert span_attr_stat.compute({"span": "serve.round", "attr": "late_ms"},
                                  hand) is None


def test_unspanned_is_idle_unders_own_remainder(hand):
    args = spec_of("serve_idle_unspanned_pct")["args"]
    shares = idle_under.shares(args["classes"], hand.as_trace(), hand)
    assert idle_unspanned.compute(args, hand.as_trace(), hand) == \
        pytest.approx(100 * shares[None])
    assert sum(shares.values()) == pytest.approx(0.17)


# -- the manifest: the accepted entries first ---------------------------------------

@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_the_accepted_entries_come_first_and_their_lists_only_grew(section):
    """A PREFIX is pinned (``accepted-manifest-pr33.json``, less the
    entries taken out since), so the next appended entry does not break
    this."""
    rules.check_prefix(MANIFEST, ACCEPTED, section)


def test_this_pr_adds_twelve_metrics_and_nothing_else():
    for section in ("configs", "workloads", "end_to_end"):
        rules.check_own_after(MANIFEST, section,
                              rules.names(ACCEPTED, section), [])
    rules.check_own_after(MANIFEST, "per_layer",
                          rules.names(ACCEPTED, "per_layer"), list(NEW))


@pytest.mark.parametrize("name", list(NEW))
def test_a_new_entry_agrees_with_its_file_and_names_an_accepted_layer(name):
    unit, source, layer, moves, cells = NEW[name]
    entry = rules.by_name(MANIFEST["per_layer"], name)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": moves}
    assert layer in {m["layer"] for m in ACCEPTED["per_layer"]}
    # the file's fields, its reader, and every cell it lists reporting
    # the end-to-end metric it moves; the list may have grown since
    rules.check_entry(MANIFEST, name, cells=cells)
