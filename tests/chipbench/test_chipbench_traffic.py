"""chipbench/traffic: everything is a function of the seed alone."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import traffic  # noqa: E402

MIX = json.loads(
    (ROOT / "chipbench/workloads/serve-code.json").read_text())["traffic"]
BIG = 2**31 + 12345


def _same(a, b):
    return (len(a) == len(b) and all(
        x.due_s == y.due_s and x.max_new == y.max_new
        and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b)))


def test_requests_are_a_function_of_the_seed_alone():
    a = traffic.serving_requests(MIX, 49152, BIG, 40.0)
    b = traffic.serving_requests(MIX, 49152, BIG, 40.0)
    assert _same(a, b)


def test_two_seeds_differ_in_order_not_in_work():
    a = traffic.serving_requests(MIX, 49152, BIG, 40.0)
    b = traffic.serving_requests(MIX, 49152, BIG + 1, 40.0)
    assert not _same(a, b)
    lens = lambda rs: sorted(len(r.prompt) for r in rs if r.measured)
    assert lens(a) == lens(b)
    # the same cyclic sequence, cut elsewhere: request i of one seed is
    # request i + 1 of the next, sizes and gap alike
    size = lambda rs: [(len(r.prompt), r.max_new) for r in rs if r.measured]
    assert size(a)[1:] == size(b)[:-1]
    gaps = lambda seed: np.sort(np.diff(
        traffic.arrival_times(80, 2.0, seed), prepend=0.0))
    np.testing.assert_allclose(gaps(BIG), gaps(BIG + 1), rtol=1e-9)


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_prompt_plus_output_fits_the_window_of_4096(seed):
    rs = traffic.serving_requests(MIX, 49152, seed, 40.0)
    assert sum(r.measured for r in rs) == int(MIX["rate_rps"] * 40)
    assert all(len(r.prompt) + r.max_new <= MIX["max_total"] for r in rs)
    assert all(MIX["prompt"]["lo"] <= len(r.prompt) <= MIX["prompt"]["hi"]
               for r in rs)
    assert all(r.max_new >= 1 for r in rs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 49152 for r in rs)


def test_due_times_are_open_loop_and_inside_the_window():
    # the schedule is made before any request is served: it is a function
    # of (mix, seed, seconds) with no server in sight, rises, and ends
    # where the window does
    rs = traffic.serving_requests(MIX, 49152, 3, 40.0)
    due = [r.due_s for r in rs]
    assert all(b > a for a, b in zip(due, due[1:]))
    inside = [r.due_s for r in rs if r.measured]
    assert 0 < inside[0] and inside[-1] == pytest.approx(40.0)


def test_window_is_led_in_and_out_by_the_cycles_own_neighbours():
    rs = traffic.serving_requests(MIX, 49152, 3, 40.0)
    flags = [r.measured for r in rs]
    k_in, k_out = flags.index(True), flags[::-1].index(True)
    assert k_in == round(MIX["rate_rps"] * MIX["lead_in_s"])
    assert k_out == round(MIX["rate_rps"] * MIX["lead_out_s"])
    assert rs[k_in - 1].due_s == 0.0 and rs[0].due_s < 0
    size = lambda r: (len(r.prompt), r.max_new)
    inside = rs[k_in:len(rs) - k_out]
    # cyclic: what leads in is the window's own tail, what leads out its head
    assert [size(r) for r in rs[:k_in]] == [size(r) for r in inside[-k_in:]]
    assert [size(r) for r in rs[-k_out:]] == [size(r) for r in inside[:k_out]]


def test_bursty_arrivals_keep_the_mean_rate():
    t = traffic.arrival_times(200, 5.0, 11, process="bursty", burst_factor=4)
    assert abs(t[-1] - 40.0) < 1e-9
    assert np.diff(t).std() > 1.0 / 5.0   # burstier than Poisson's own


def test_token_stream_is_seeded():
    a = traffic.token_stream(1000, 49152, BIG)
    assert np.array_equal(a, traffic.token_stream(1000, 49152, BIG))
    assert not np.array_equal(a, traffic.token_stream(1000, 49152, BIG + 1))
