"""Test configuration: force an 8-device virtual CPU mesh.

The reference tests on real hardware only (mpirun -np 4, SURVEY.md
section 4); the gap it leaves — hardware-free multi-device testing — is
closed here with XLA's host-platform device-count override, so every
distributed code path runs as 8-way SPMD on CPU.

Must run before any jax import, hence module-level env mutation in
conftest (pytest imports conftest first).
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# persistent XLA compile cache: shard_map compiles dominate suite wall
# time; warm reruns skip them entirely (first/cold run is unchanged).
# Placed by the one helper every entry point uses
# (hpc_patterns_tpu/compile_cache.py): JAX_COMPILATION_CACHE_DIR when
# set, else a fixed sub-directory of the in-checkout cache keyed by
# the HOST CPU's feature set: XLA:CPU loads
# AOT cache entries compiled on a different machine with only a
# warning ("could lead to execution errors such as SIGILL"), and a
# stale cross-machine cache did exactly that — reproducible SIGABRTs
# mid-suite (round 5; fresh cache = 18/18 green on the same tests).


def _cpu_fingerprint() -> str:
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    import hashlib

                    return hashlib.sha1(line.encode()).hexdigest()[:12]
    except OSError:
        pass
    import platform

    return platform.machine() or "unknown"


# the jaxlib version joins the key: XLA:CPU loads entries from a
# different build with only a warning, and version drift risks more
# than the SIGILLs the CPU-flags fingerprint was added for. (Round 6
# note: six serving-test failures that vanished with a fresh cache
# looked like cache corruption but were a serving bug — a zero-copy
# np.asarray view of a buffer the engine then DONATED; cache-loaded
# executables honor the donation in place. Fixed in serving.py; the
# version keying stays as cheap defense-in-depth.)
import jaxlib  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from hpc_patterns_tpu import compile_cache  # noqa: E402

compile_cache.enable(subdir=f"cpu-{_cpu_fingerprint()}-{jax.__version__}"
                            f"-{jaxlib.__version__}")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import pytest  # noqa: E402


def _markexpr_selects_slow(markexpr: str) -> bool:
    """True when the ``-m`` expression can select a slow-marked item —
    evaluated with pytest's own expression engine, so parenthesized and
    oddly-spaced forms (``not (slow)``, ``not  slow``) resolve the same
    way pytest's selection will, instead of a regex approximation."""
    if not markexpr:
        return False
    try:
        from _pytest.mark.expression import Expression

        expr = Expression.compile(markexpr)
        # Two conditions, both required:
        # 1. satisfiable by SOME slow-marked item — modeled as an item
        #    marked only 'slow' and one marked 'slow' plus everything
        #    else, so conjunctions like "slow and tpu" count;
        # 2. the expression actually MENTIONS 'slow' — the tier is
        #    explicit opt-in, so "not tpu" (satisfiable by a slow-only
        #    item, but not asking for slow) keeps the fast tier.
        names = set()

        def matcher(name, extra):
            names.add(name)
            return name == "slow" or extra

        sat = any(
            bool(expr.evaluate(lambda n, e=extra: matcher(n, e)))
            for extra in (False, True)
        )
        return sat and "slow" in names
    except Exception:
        # unparseable expression (pytest will error on it anyway):
        # keep the skip wiring out of the way
        return "slow" in markexpr


def pytest_addoption(parser):
    parser.addoption(
        "--slow", action="store_true", default=False,
        help="include the slow tier (multi-process launches, big-model "
             "pipeline/MoE oracles); default tier targets < 5 min",
    )


def pytest_collection_modifyitems(config, items):
    # two-tier suite: `pytest -q` = fast tier (< 5 min on the 8-device
    # CPU mesh); `pytest -q --slow` (or `-m slow`) adds the rest. CI
    # runs both: `pytest -q && pytest -q -m slow`.
    # `-m slow` (and any expression a slow-marked item satisfies)
    # disables the skip; `-m "not slow"` and expressions that merely
    # contain the substring don't
    markexpr = config.getoption("-m") or ""
    if config.getoption("--slow") or _markexpr_selects_slow(markexpr):
        return
    skip = pytest.mark.skip(reason="slow tier (run with --slow or -m slow)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _poison_donated_serving(request):
    """Donation-poison harness (analysis/runtime.py): wraps the serving
    engine's donating jit entry points so a zero-copy host view of a
    donated buffer — the PR 2 "poisoned cache" bug class — fails
    LOUDLY on the CPU mesh instead of passing by backend luck (fresh
    CPU executables don't honor donations; cache-loaded ones do).

    Always on for tests/test_serving.py (the engine's oracle suite is
    exactly where an aliasing regression would otherwise hide) and
    tests/test_prefix_cache.py (a shared page aliased into a donated
    pool would corrupt EVERY reader at once — the highest-stakes
    surface for this bug class);
    ``HPC_PATTERNS_POISON_DONATED=1`` extends it to the whole suite."""
    if not (os.environ.get("HPC_PATTERNS_POISON_DONATED") == "1"
            or request.node.module.__name__ in ("test_serving",
                                                "test_prefix_cache")):
        yield
        return
    from hpc_patterns_tpu.analysis.runtime import install_serving_poison

    uninstall = install_serving_poison()
    try:
        yield
    finally:
        uninstall()


# Every live compiled executable keeps its JIT'd code pages mapped, and
# one full-suite process now compiles enough of them to exhaust the
# kernel's per-process map budget (vm.max_map_count, default 65530):
# the next mmap inside XLA's compiler fails and the process segfaults
# in backend_compile — observed at ~65k maps, ~85% through the fast
# tier, landing on whichever test happens to compile at that point.
# Dropping the jit caches unmaps retired executables (measured: 200
# small compiles cost ~600 maps; clear_caches + gc returns ~95% of
# them), and the persistent compile cache above makes the few
# re-compiles that follow cheap. The threshold leaves ~20k headroom —
# more than the heaviest single module allocates — so the guard fires
# at most a handful of times per run and never mid-test.
_MAP_PRESSURE_LIMIT = 45_000


def _memory_map_count() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:
        # non-Linux (no /proc): guard disabled — the platforms this
        # repo tests on are Linux, and macOS has no equivalent cap
        return 0


@pytest.fixture(autouse=True)
def _jax_map_pressure_guard():
    yield
    if _memory_map_count() > _MAP_PRESSURE_LIMIT:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def mesh8():
    from hpc_patterns_tpu import topology

    return topology.make_mesh({"x": 8})


@pytest.fixture(scope="session")
def mesh_dp_sp_tp():
    from hpc_patterns_tpu import topology

    return topology.make_mesh({"dp": 2, "sp": 2, "tp": 2})
