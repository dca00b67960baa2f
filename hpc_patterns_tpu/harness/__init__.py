"""Benchmark harness: timing protocol, verdict engine, config, run log.

The reference has no shared harness library — each C++ app hand-rolls its
own timing (std::chrono min-of-reps), verdict (SUCCESS/FAILURE exit codes)
and CLI (argv loops / getopt). SURVEY.md section 7 step 1 calls for
unifying them; this package is that unification.
"""

from hpc_patterns_tpu.harness.timing import TimingResult, measure, bandwidth_gbps  # noqa: F401
from hpc_patterns_tpu.harness.verdict import (  # noqa: F401
    Verdict,
    concurrency_verdict,
    correctness_verdict,
)
from hpc_patterns_tpu.harness.runlog import RunLog  # noqa: F401
from hpc_patterns_tpu.harness.metrics import (  # noqa: F401
    Metrics,
    configure as configure_metrics,
    get_metrics,
    span,
)
# harness.trace (the flight recorder) is deliberately NOT re-exported
# here: it is a `python -m` CLI, and importing it in the package
# __init__ would make runpy warn about double import. Use `from
# hpc_patterns_tpu.harness import trace` directly, as report.py and
# the apps do.
