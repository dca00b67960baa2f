"""jaxlint: static hazard analysis for the JAX patterns this repo has
been burned by — donation aliasing, dispatch-path host syncs, per-call
re-jits, PRNG key reuse, tracer leaks; (the shardlint family) the
SPMD collective-divergence class: rank-branched collective schedules,
reordered collective paths, unchecked ppermute pair lists, and
PartitionSpec/mesh inconsistencies; and (the pallaslint family) the
in-kernel DMA/semaphore/VMEM contract: semaphore-ledger imbalance,
scratch-slot reuse across live DMAs, collective-id collisions, dtype
holes, and VMEM budget overflows — the chip-only bug class interpret
mode cannot see (``pallas_rules.py`` / ``vmem.py``; runtime half:
``runtime.strict_semaphores``); and (the contractlint family) the
stringly-typed producer/consumer seams: metric names read by string
with no producer, RunLog record-kind drift, wire-codec field incompatibility,
Perfetto track-band collisions, and chaos site/kind typos — checked
whole-tree against merged extraction tables (``contracts.py`` /
``contract_rules.py``; ``--contract-report`` prints the tables).

Run it over the package (CI mode exits nonzero on any unsuppressed
finding)::

    python -m hpc_patterns_tpu.analysis --ci

The motivating incidents: PR 2's "poisoned cache" — a zero-copy
``np.asarray`` host view of a buffer that a donated jit arg later
mutated in place (``serving._dispatch_chunk``), caught at review time
by ``donation-alias`` — and the reference suite's silent MPI-ring
deadlock, where SPMD ranks disagree on which collective comes next,
caught by ``collective-divergence``. The recorder shows you the
bubble; jaxlint stops the next one.

Public surface:

- :func:`run_paths` / :class:`Report` / :class:`Finding` — the engine
  (hpc_patterns_tpu.analysis.core; rules in .rules self-register);
- :func:`dispatch_critical` — no-op marker decorator: the
  ``host-sync-in-dispatch`` rule treats any function carrying it as
  dispatch-critical, in addition to the configured name list;
- hpc_patterns_tpu.analysis.runtime — the RUNTIME complements:
  :func:`~hpc_patterns_tpu.analysis.runtime.poison_donated` clobbers
  donated inputs after each call so an aliasing bug the analyzer
  missed fails loudly in tests, and
  :class:`~hpc_patterns_tpu.analysis.runtime.CollectiveSchedule`
  fingerprints every eager collective into a per-rank hash chain that
  the cross-rank trace merge (harness/collect.py) verifies — and that
  names which collective a hung rank is stuck in on a launch timeout.
"""

from __future__ import annotations

from hpc_patterns_tpu.analysis.core import (  # noqa: F401
    AnalysisConfig,
    DEFAULT_DISPATCH_CRITICAL,
    Finding,
    Report,
    analyze_file,
    registered_rules,
    run_paths,
)


def dispatch_critical(fn):
    """Marker decorator: this function is on a dispatch-critical path
    (its job is to ENQUEUE device work, never to wait for it). Purely
    declarative — the wrapped function is returned unchanged — but the
    ``host-sync-in-dispatch`` rule audits every function carrying it,
    so the marker turns a design intention into a checked invariant."""
    return fn
