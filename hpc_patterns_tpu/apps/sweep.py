"""Concurrency sweep harness — the rebuild of ``concurency/run.sh`` (C4).

The reference's harness (run.sh:4-15) sweeps the command matrix
{C C, C M2D, C D2M, M2D D2M} × {out_of_order, in_order}, re-runs each
passing configuration with ``--enable_profiling``, tees everything to
``run.log``, and greps a SUCCESS/FAILURE summary (:17-18).

Same behavior here, with the structured upgrades of SURVEY.md §5: the log
is JSONL (machine-readable) *and* the grep-able stdout contract is kept;
modes default to the TPU-meaningful pair (``async``, ``threads``).
"""

from __future__ import annotations

import sys

from hpc_patterns_tpu.apps import common, concurrency_app
from hpc_patterns_tpu.harness import RunLog
from hpc_patterns_tpu.harness.cli import base_parser

# run.sh:4's command matrix
DEFAULT_MATRIX = [["C", "C"], ["C", "M2D"], ["C", "D2M"], ["M2D", "D2M"]]


def build_parser():
    p = base_parser(__doc__.splitlines()[0])
    p.add_argument("--modes", nargs="*", default=None,
                   help="dispatch modes to sweep (run.sh sweeps out_of_order, "
                        "in_order); default: async+threads on a multi-device "
                        "backend, async alone on a single TPU (threads-style "
                        "dispatch cannot overlap on one sequential core)")
    p.add_argument("--engine", default="auto",
                   choices=["auto", "dispatch", "onchip"],
                   help="passed through to the concurrency app")
    p.add_argument("--copy-elements", type=int, default=-1)
    p.add_argument("--tripcount", type=int, default=-1)
    p.add_argument("--rule", default="sycl", choices=["sycl", "omp"])
    p.add_argument("--profile-on-success", action="store_true",
                   help="re-run passing configs under the profiler (run.sh:10-12)")
    return p


def run(args) -> int:
    log = RunLog(args.log, truncate=not args.log_append)  # harness owns the log
    app_parser = concurrency_app.build_parser()
    modes = args.modes
    if modes is None:
        from hpc_patterns_tpu import topology

        devices = topology.get_devices(args.backend)
        single_tpu = devices[0].platform == "tpu" and len(devices) == 1
        modes = ["async"] if single_tpu else ["async", "threads"]
    for commands in DEFAULT_MATRIX:
        for mode in modes:
            argv = [mode, *commands,
                    "--engine", args.engine,
                    "--copy-elements", str(args.copy_elements),
                    "--tripcount", str(args.tripcount),
                    "--rule", args.rule,
                    "--repetitions", str(args.repetitions),
                    "--warmup", str(args.warmup)]
            if args.backend:
                argv += ["--backend", args.backend]
            if args.log:
                argv += ["--log", args.log, "--log-append"]  # share our log
            log.print(f"=== {mode} {' '.join(commands)} ===")
            code = concurrency_app.run(app_parser.parse_args(argv))
            log.emit(kind="result", name=f"sweep[{mode}:{'+'.join(commands)}]",
                     success=code == 0, mode=mode, commands=commands)
            if code == 0 and args.profile_on_success:
                log.print(f"=== {mode} {' '.join(commands)} (profiling) ===")
                concurrency_app.run(app_parser.parse_args(argv + ["--enable_profiling"]))
    ok, bad = log.summary()
    return 0 if bad == 0 else 1


def main(argv=None) -> int:
    # one shared registry for the whole sweep: sub-apps run in-process
    # via concurrency_app.run, so their spans/gauges accumulate into
    # the harness's single closing kind=metrics snapshot
    return common.run_instrumented(run, build_parser().parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
