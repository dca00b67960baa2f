"""Traffic from a seed: arrival schedules and request lengths.

One general generator; a traffic mix is a data file of parameters
(``chipbench/workloads/<cell>.json``, key ``traffic``). The arrival
processes follow ``hpc_patterns_tpu/harness/loadgen.py`` (Poisson,
two-phase bursty), copied here so that the yardstick cannot move under a
later PR; this module imports nothing of the program and needs numpy only.

Every seed gets the SAME requests -- inter-arrival gaps and (prompt,
output) lengths are the quantiles of the stated distributions, shuffled
once into one fixed cyclic sequence -- in another order: the seed picks
where in the cycle the window starts (and the token ids). A seed then
changes neither how much work a window holds nor which long prompt meets
which burst, only where the cycle is cut, so runs with different seeds
spread nearly like runs with one. (Measured on the chip, PR 23: with a
fresh shuffle per seed, ttft_p95_ms of three seeds read 566, 627 and
775 ms; a tail of queueing times is a function of the order.)
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float        # instant on the schedule's clock, from window start
    prompt: np.ndarray  # int32 token ids
    max_new: int
    measured: bool = True   # False: led in or out, served but not timed


CYCLE = 0xC0DE   # the one shuffle that fixes the cyclic sequence


def _rng(seed: int, stream: int) -> np.random.Generator:
    # seeds run past 2**31: SeedSequence takes any non-negative integer
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _cycle(values: np.ndarray, stream: int, seed: int) -> np.ndarray:
    """The fixed cyclic order of ``values``, started where the seed says."""
    return np.roll(_rng(CYCLE, stream).permutation(values), -(int(seed)
                                                              % len(values)))


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: int, hi: int) -> np.ndarray:
    """The n mid-quantiles of a log-normal (median, sigma of the log),
    clipped to [lo, hi], as ints: a fixed multiset, whatever the seed."""
    nd = NormalDist()
    q = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(p)) for p in q])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)


def exponential_quantiles(n: int, mean: float) -> np.ndarray:
    """The n mid-quantiles of an exponential with this mean, rescaled so
    that they sum to exactly n * mean (the window's length is fixed)."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (n * mean / g.sum())


def arrival_times(n: int, rate_rps: float, seed: int, *,
                  process: str = "poisson", burst_factor: float = 4.0,
                  mean_quiet_s: float = 1.0,
                  mean_burst_s: float = 0.25) -> np.ndarray:
    """n due instants. ``poisson``: the exponential quantile gaps in the
    fixed cyclic order, started where the seed says. ``bursty``:
    loadgen's two-phase modulated Poisson (quiet phases at the base rate,
    burst phases at burst_factor x it) drawn once, its gaps cycled the
    same way, rescaled to the same mean rate."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    if process == "poisson":
        return np.cumsum(_cycle(exponential_quantiles(n, 1.0 / rate_rps),
                                1, seed))
    if process == "bursty":
        rng = _rng(CYCLE, 5)
        times: list[float] = []
        t, burst = 0.0, False
        while len(times) < n:
            phase = rng.exponential(mean_burst_s if burst else mean_quiet_s)
            rate = rate_rps * (burst_factor if burst else 1.0)
            u = t
            while True:
                u += rng.exponential(1.0 / rate)
                if u > t + phase or len(times) >= n:
                    break
                times.append(u)
            t += phase
            burst = not burst
        gaps = np.diff(np.asarray(times[:n]), prepend=0.0)
        out = np.cumsum(np.roll(gaps, -(int(seed) % n)))
        return out * (n / rate_rps / out[-1])
    raise ValueError(f"arrival process {process!r} not in (poisson, bursty)")


def serving_requests(params: dict, vocab: int, seed: int,
                     seconds: float) -> list[Request]:
    """The open-loop request list of one run: ``rate_rps * seconds``
    measured requests due inside (0, seconds], led in and out by
    unmeasured ones so that the window is a steady-state stretch of the
    cycle. ``params`` is the cell file's ``traffic`` object: rate_rps,
    arrivals, prompt {median, sigma, lo, hi}, output {median, sigma, lo,
    hi}, max_total, lead_in_s, lead_out_s.

    The lead-in is the cycle's requests just before the cut (the last of
    them due at 0) and the lead-out its first ones again, so every
    measured request keeps the neighbours it has in the cycle wherever
    the seed cuts it. ``Request.measured`` tells them apart; due times
    are relative to the window's start, the lead-in's negative."""
    rate = params["rate_rps"]
    n = max(1, int(math.floor(rate * seconds)))
    gaps = np.diff(arrival_times(n, rate, seed, **params.get(
        "arrivals", {"process": "poisson"})), prepend=0.0)
    p, o = params["prompt"], params["output"]
    plen = _cycle(lognormal_quantiles(n, p["median"], p["sigma"], p["lo"],
                                      p["hi"]), 2, seed)
    olen = _cycle(lognormal_quantiles(n, o["median"], o["sigma"], o["lo"],
                                      o["hi"]), 3, seed)
    olen = np.minimum(olen, params["max_total"] - plen)
    if olen.min() < 1:
        raise ValueError("max_total leaves a request no output token")
    k_in = min(n, int(round(rate * params.get("lead_in_s", 0.0))))
    k_out = min(n, int(round(rate * params.get("lead_out_s", 0.0))))
    # positions in the cycle: lead-in, the window, lead-out
    order = np.concatenate([np.arange(n - k_in, n), np.arange(n),
                            np.arange(k_out)])
    due = np.cumsum(gaps[order])
    due = due - due[k_in - 1] if k_in else due - 0.5 * due[0]
    tok = _rng(seed, 3)
    return [
        Request(i, float(due[i]),
                tok.integers(0, vocab, size=int(plen[c]), dtype=np.int32),
                int(olen[c]), bool(k_in <= i < k_in + n))
        for i, c in enumerate(order)
    ]


def token_stream(n_tokens: int, vocab: int, seed: int) -> np.ndarray:
    """A flat seeded token stream (the training cell's corpus file)."""
    return _rng(seed, 4).integers(0, vocab, size=n_tokens, dtype=np.int32)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), as a float."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))
