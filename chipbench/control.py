"""Read the comparison's two ends on the chip, many seeds in one process.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> [--control <what>]

Without ``--control`` it reads the program's numbers (the lower reading:
the largest over a dozen seeds). With it, the control is put in the
program's place (the upper reading: the smallest over three seeds or
more). What a driver takes as ``--control``:

- ``serve``: ``int8`` (the program's own int8-weight path, served),
  ``ref-int8`` / ``ref-fp8`` (the plain reference in that precision, at
  the served positions);
- ``train``: ``int8`` / ``fp8`` (the plain reference in that precision),
  ``half-batch`` (the fault: half of the batch left out, the mean taken
  over the rest);
- ``allreduce``: ``bfloat16`` (the buffers and the sum in bfloat16).

The benchmark's own runs never come here. One JSON line a seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    from chipbench import run as runlib
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    bench = runlib.load_json(runlib.ROOT / "BENCHMARK.json")
    entry = runlib.find_cell(bench, args.workload)
    from hpc_patterns_tpu import compile_cache
    compile_cache.enable()
    devices, peaks = runlib.look_for_chip(entry["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        r = runlib.run_cell(bench, args.workload, seed, args.seconds, False,
                            devices, peaks, control=args.control, readings=True)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": r["correct"], "checks": r["checks"],
                          "readings": r.get("readings"),
                          "metrics": r["metrics"],
                          "memory_peak_bytes":
                          r["device"]["memory_peak_bytes"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
