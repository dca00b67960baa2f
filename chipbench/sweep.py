"""Find a serving cell's knee, once, on the chip: the highest offered rate
with no growing backlog. One process, one engine, one window a rate.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3

Prints one JSON line a rate: the tails, how long the queue took to drain
after the last arrival was due, and the share of requests whose first
token came later than the window is long (a backlog that grows).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    from chipbench import run as runlib, traffic, weights
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = runlib.load_json(runlib.ROOT / "BENCHMARK.json")
    entry = runlib.find_cell(bench, args.workload)
    from hpc_patterns_tpu import compile_cache
    compile_cache.enable()
    devices, peaks = runlib.look_for_chip(entry["chips"])
    from chipbench.drivers import serve
    cell = runlib.load_json(runlib.HERE / "workloads" / f"{args.workload}.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = runlib.load_json(runlib.ROOT / cfg_entry["file"])
    ctx = runlib.Context(cell=cell, config=config, entry=entry, seed=args.seed,
                         seconds=args.seconds, devices=devices, peaks=peaks,
                         tracer=runlib.TraceWindow(False))
    m = weights.model_dims(config)
    engine = serve.build_engine(ctx)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell["traffic"], rate_rps=rate)
        reqs = [dataclasses.replace(r, index=r.index + (k + 1) * 100000)
                for r in traffic.serving_requests(mix, m["V"], args.seed + k,
                                                  args.seconds)]
        if k == 0:
            serve.warm(engine, reqs, m["V"])
        finished, t0, t1 = serve.serve_window(engine, reqs)
        per = serve.summarize(engine, reqs, finished)
        pc = lambda v, q: traffic.percentile(v, q) if v else None
        print(json.dumps({
            "rate_rps": rate, "requests": sum(r.measured for r in reqs),
            "failed": per["failed"],
            "ttft_p50_ms": pc(per["ttft"], 50), "ttft_p95_ms": pc(per["ttft"], 95),
            "tpot_p50_ms": pc(per["tpot"], 50), "tpot_p95_ms": pc(per["tpot"], 95),
            "queue_p95_ms": pc(per["queue"], 95),
            "drain_s": (t1 - t0) - reqs[-1].due_s,   # after the last due
            "ttft_last_quarter_p50_ms": pc(per["ttft"][-len(per["ttft"]) // 4:], 50),
            "out_tok_s": sum(len(v) for v in finished.values()) / (t1 - t0),
        }), flush=True)
        engine.finished.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
