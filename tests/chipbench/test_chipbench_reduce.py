"""chipbench/reduce: the trace reduction on a small recorded trace (a
slice of a serve-code run on a v5e: one 2048-rung prefill, one decode
chunk; operations under 150 us thinned out) and on one made by hand."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import reduce  # noqa: E402
from chipbench.readers import event_ms, idle_pct, share_of_peak  # noqa: E402

SLICE = ROOT / "tests/chipbench/fixtures/serve_trace_slice.json"
PALLAS = r"^%closed_call[.\d]* = "
DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = reduce.OPS_LINE, reduce.MODULES_LINE


@pytest.fixture(scope="module")
def recorded():
    return reduce.Trace.from_json(SLICE.read_text())


def by_hand():
    E = reduce.Event
    return reduce.Trace([
        E(DEV, MODS, "jit_a(1)", 0.0, 4.0), E(DEV, MODS, "jit_b(2)", 6.0, 2.0),
        E(DEV, OPS, "%while.1 = loop", 0.0, 4.0),      # nests the next two
        E(DEV, OPS, "%fusion.1 = f", 0.5, 1.0),
        E(DEV, OPS, "%closed_call.3 = bf16[8] custom-call(x)", 2.0, 1.5),
        E(DEV, OPS, "%closed_call.3 = bf16[8] custom-call(x)", 6.0, 2.0),
        E(HOST, "main", "engine.admit", 3.9, 2.0),     # covers the gap
        E(HOST, "main", "outer", 0.0, 10.0),
        E(HOST, "main", "tail", 9.2, 0.8),      # under half of [8, 10]
    ])


def test_busy_is_the_union_not_the_sum():
    tr = by_hand()
    assert tr.window_s() == pytest.approx(10.0)
    assert tr.busy_s() == pytest.approx(6.0)     # [0,4] and [6,8]
    assert tr.idle_share() == pytest.approx(0.4)
    assert idle_pct.read({}, tr, {}, {}, {}) == pytest.approx(40.0)


def test_device_time_per_named_event_and_within_a_program():
    tr = by_hand()
    assert tr.device_time(OPS, PALLAS) == (pytest.approx(3.5), 2)
    assert tr.device_time(OPS, PALLAS, within=r"^jit_b\(") == (
        pytest.approx(2.0), 1)
    assert tr.device_time(MODS, r"^jit_a\(") == (pytest.approx(4.0), 1)


def test_self_time_takes_nested_operations_out_of_the_loop():
    st = by_hand().self_times()
    assert st["%while.1"] == pytest.approx(4.0 - 1.0 - 1.5)
    assert st["%closed_call.3"] == pytest.approx(3.5)


def test_idle_gaps_go_to_the_shortest_host_span_that_covers_them():
    gaps = by_hand().idle_gaps()
    assert gaps["engine.admit"] == pytest.approx(2.0)   # [4, 6]
    assert gaps["outer"] == pytest.approx(2.0)          # [8, 10]
    assert sum(gaps.values()) == pytest.approx(4.0)


def test_a_missing_event_raises_instead_of_reading_zero():
    tr = by_hand()
    with pytest.raises(reduce.NothingToRead):
        tr.device_time(OPS, r"^%flash_fwd")
    with pytest.raises(reduce.NothingToRead):
        tr.device_time(OPS, PALLAS, within=r"^jit_c\(")
    with pytest.raises(reduce.NothingToRead):
        reduce.Trace([reduce.Event(HOST, "main", "x", 0.0, 1.0)])
    # a reader that finds nothing returns nothing: the metric is left out
    args = {"line": OPS, "pattern": r"^%flash_fwd"}
    assert event_ms.read(args, tr, {}, {}, {}) is None
    assert share_of_peak.read(
        {"counts": "flash_attention.prefill_work", "bound": "roofline",
         "time": args}, tr, {}, {}, {}) is None


def test_recorded_slice_programs_and_kernels(recorded):
    assert recorded.device_planes == [DEV]
    assert recorded.device_time(MODS, r"^jit__prefill_one\(") == (
        pytest.approx(0.071307427), 1)
    assert recorded.device_time(MODS, r"^jit__chunk_step\(") == (
        pytest.approx(0.110994401), 1)
    # 20 layers: one flash call a layer in the prefill, one paged-decode
    # call a layer and step (8 steps) in the chunk
    assert recorded.device_time(OPS, PALLAS,
                                within=r"^jit__prefill_one\(") == (
        pytest.approx(0.006597832), 20)
    assert recorded.device_time(OPS, PALLAS, within=r"^jit__chunk_step\(") == (
        pytest.approx(0.014223594), 160)


def test_recorded_slice_busy_and_breakdown(recorded):
    assert recorded.window_s() == pytest.approx(0.190837993)
    assert recorded.busy_s() == pytest.approx(0.175485619)
    b = recorded.breakdown()
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][0] == "%while.2"
    assert all(len(n) <= 80 for n, _ in b["device_ops"] + b["idle_gaps"])
    assert event_ms.read(
        {"line": MODS, "pattern": r"^jit__chunk_step\(", "per": "chunk"},
        recorded, {"chunk": 8}, {}, {}) == pytest.approx(110.994401 / 8)


def test_share_of_peak_on_the_recorded_prefill(recorded):
    # one 2048-rung prefill: 20 flash calls of 2 T^2 Dh H operations
    config = {"num_hidden_layers": 20, "num_attention_heads": 24,
              "num_key_value_heads": 2, "hidden_size": 3072}
    facts = {"admissions": [(1.0, 2048, 1500)], "trace_host_window": (0.0, 9.0)}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    got = share_of_peak.read(
        {"counts": "flash_attention.prefill_work", "bound": "roofline",
         "time": {"line": OPS, "pattern": PALLAS,
                  "within": r"^jit__prefill_one\("}},
        recorded, facts, config, peaks)
    flops = 20 * 2 * 2048 * 2048 * 128 * 24
    assert got == pytest.approx(100 * flops / 197e12 / 0.006597832)
    assert 0 < got < 100
