"""Unified CLI/config layer (C13 in SURVEY.md).

The reference uses three ad-hoc mechanisms — hand-rolled argv loops with a
``-1 = auto`` sentinel (sycl_con.cpp:179-232), getopt short options
``-haHDSp:`` (allreduce-mpi-sycl.cpp:106-131), and env vars
(allreduce-usm-mpi-omp-offload.cpp:121-124). SURVEY.md section 5 calls for
one layer with a ``--backend`` flag; this is it. All apps under
``hpc_patterns_tpu.apps`` build on :func:`base_parser`.

Kept semantics:
- ``-1`` means auto/autotune wherever a size is accepted
- ``-p N`` selects 2**N elements (allreduce-mpi-sycl.cpp:99,125-128),
  default 25 (~128 MiB of float32)
- memory-kind axis ``-H/-D`` maps host/device USM to JAX memory kinds
  ``pinned_host`` / ``device`` (``-S`` shared has no TPU analog and maps
  to device with a note)
- ``--repetitions`` (default 10, sycl_con.cpp:182; the reference also
  accepts a typo'd ``--repetitionss``, sycl_con.cpp:205 — not reproduced)
"""

from __future__ import annotations

import argparse

AUTO = -1


def base_parser(description: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=description)
    p.add_argument(
        "--backend",
        default=None,
        choices=["tpu", "cpu", "gpu"],
        help="platform filter for device discovery (default: whatever JAX has)",
    )
    p.add_argument(
        "--repetitions",
        type=int,
        default=10,
        help="timing repetitions; result is the min (sycl_con.cpp protocol)",
    )
    p.add_argument("--warmup", type=int, default=2, help="untimed warm-up calls (absorbs XLA compile)")
    p.add_argument("--log", default=None, help="write JSONL run log here (run.log analog)")
    p.add_argument(
        "--log-append",
        action="store_true",
        help="append to --log instead of truncating (for harness-invoked runs)",
    )
    p.add_argument(
        "--metrics",
        action="store_true",
        help="enable the metrics/span registry (harness/metrics.py); with "
             "--log, one final kind=metrics snapshot record is appended — "
             "aggregate with `python -m hpc_patterns_tpu.harness.report`",
    )
    p.add_argument(
        "--trace",
        action="store_true",
        help="enable the flight recorder (harness/trace.py): spans, "
             "device dispatch/completion markers, compile events, and "
             "memory samples land in a bounded ring buffer; with --log, "
             "one kind=trace snapshot record is appended — export to "
             "Chrome-trace JSON with "
             "`python -m hpc_patterns_tpu.harness.trace <log>`",
    )
    p.add_argument(
        "--trace-capacity",
        type=int,
        default=None,
        metavar="N",
        help="flight-recorder ring size in events (default 16384; "
             "oldest events evicted beyond it)",
    )
    return p


def add_serving_args(p: argparse.ArgumentParser) -> None:
    """The serving-engine knobs of the serving surface (serve_app):
    the prompt-length bucket ladder, the sampling mode, and the
    admission-overlap toggle."""
    p.add_argument(
        "--prompt-buckets",
        default="auto",
        help="prompt-length bucket ladder bounding admission-prefill "
             "compiles: 'auto' (power-of-two-ish ladder over the max "
             "prompt length, serving.bucket_ladder), 'none' (exact "
             "lengths — one compile per distinct length), or "
             "comma-separated rungs, e.g. '16,32,64'",
    )
    p.add_argument(
        "--temperature",
        type=float,
        default=0.0,
        help="sampling temperature (0 = greedy, the token-exact "
             "serving oracle; > 0 samples per-row key streams that "
             "stay standalone-exact)",
    )
    p.add_argument("--top-k", type=int, default=0,
                   help="top-k sampling truncation (0 = off)")
    p.add_argument("--seed", type=int, default=0,
                   help="base PRNG seed for per-request sampling keys")
    p.add_argument(
        "--no-overlap",
        action="store_true",
        help="disable overlapped admission (prefills serialize with "
             "decode chunks — the measurable baseline for the "
             "admission-bubble fraction)",
    )


#: the serving precision knob's legal values — ONE definition of what
#: "--kv-dtype fp8" means (tests/test_quantization.py pins it)
KV_DTYPE_CHOICES = ("f32", "bf16", "int8", "fp8")

#: --kv-dtype value -> (compute dtype override or None, kv_cache_dtype)
_KV_DTYPE_MAP = {
    "f32": ("float32", "compute"),
    "bf16": ("bfloat16", "compute"),
    "int8": (None, "int8"),
    "fp8": (None, "fp8"),
}


def add_kv_dtype_arg(p: argparse.ArgumentParser,
                     default: str = "f32") -> None:
    """The ``--kv-dtype`` serving-precision flag (serve_app), resolved
    by :func:`resolve_kv_cache_dtype`."""
    p.add_argument(
        "--kv-dtype",
        default=default,
        choices=list(KV_DTYPE_CHOICES),
        help="KV-cache precision: f32/bf16 store the compute dtype "
             "(scale-free); int8/fp8 store one byte per element with "
             "per-row dequant scales — half the pool bytes of bf16, a "
             "quarter of f32, dequantized in the kernel/einsum stream "
             "(docs/quantization.md). fp8 degrades to int8 with a "
             "note on backends without float8_e4m3fn support "
             "(dtypes.supports_fp8)",
    )


def resolve_kv_cache_dtype(spec: str, *, note=print):
    """Resolve a ``--kv-dtype`` value into ``(compute_dtype_override,
    kv_cache_dtype)`` — compute override None means "keep the config's
    dtype". The ONE degrade point: ``fp8`` on a backend that cannot
    execute the fp8 pipeline becomes ``int8`` with a LOUD note (the
    alternative is a deep XLA lowering error mid-serve), so every
    surface that accepts the knob degrades identically."""
    spec = (spec or "f32").strip().lower()
    if spec not in _KV_DTYPE_MAP:
        raise argparse.ArgumentTypeError(
            f"--kv-dtype must be one of {KV_DTYPE_CHOICES}, got "
            f"{spec!r}")
    compute, kv = _KV_DTYPE_MAP[spec]
    if kv == "fp8":
        from hpc_patterns_tpu import dtypes

        if not dtypes.supports_fp8():
            note("NOTE: backend cannot execute float8_e4m3fn "
                 "(dtypes.supports_fp8 probe failed) — degrading "
                 "--kv-dtype fp8 to int8 (same pool bytes, integer "
                 "grid instead of a floating one)")
            kv = "int8"
    return compute, kv


def parse_buckets(spec: str, max_prompt_len: int):
    """Resolve an ``--prompt-buckets`` value into a ladder tuple or
    None: 'none' disables bucketing, 'auto' builds the default ladder
    over ``max_prompt_len``, anything else is comma-separated rungs."""
    spec = (spec or "none").strip().lower()
    if spec == "none":
        return None
    if spec == "auto":
        from hpc_patterns_tpu.models.serving import bucket_ladder

        return bucket_ladder(max_prompt_len)
    try:
        return tuple(int(tok) for tok in spec.split(",") if tok.strip())
    except ValueError as e:
        raise argparse.ArgumentTypeError(
            f"--prompt-buckets must be 'auto', 'none', or "
            f"comma-separated ints, got {spec!r}") from e


def add_autofit_arg(p: argparse.ArgumentParser) -> None:
    """The shared ``--autofit`` flag: every serving surface that can
    consume a FittedConfig (serve_app, plane_app) ingests through the SAME
    :func:`load_autofit`, so a config fitted once applies identically
    everywhere."""
    p.add_argument(
        "--autofit",
        default=None,
        metavar="CONFIG",
        help="apply a FittedConfig JSON emitted by `python -m "
             "hpc_patterns_tpu.harness.autofit run.jsonl --emit "
             "CONFIG`: the fitted prompt ladder (and, where the "
             "surface has them, residency / placement / autoscaler "
             "knobs) replace the defaults; explicit flags still win",
    )


def add_explain_args(p: argparse.ArgumentParser) -> None:
    """The shared ``--explain``/``--explain-out`` pair: every serving
    surface (serve_app, plane_app) enables request-scoped lifecycle tracing
    (harness/reqtrace.py) the same way and renders the SAME
    per-class tail-attribution table (harness/explain.py) after its
    goodput row — where every p99 went, by lifecycle segment."""
    p.add_argument(
        "--explain",
        action="store_true",
        help="trace request lifecycle segments (queued/prefill/decode/"
             "admit_wait/preempted/swapped_out/prefetch_wait/"
             "migrating/shed) and print the per-class tail-"
             "attribution table; with --log, a kind=reqtrace record "
             "is appended for `python -m hpc_patterns_tpu.harness."
             "explain run.jsonl`",
    )
    p.add_argument(
        "--explain-out",
        default=None,
        metavar="PATH",
        help="also write the attribution digest as JSON "
             "(implies --explain)",
    )


def explain_enabled(args) -> bool:
    """Did this invocation ask for request tracing? (``--explain-out``
    implies ``--explain`` — writing the digest requires recording.)"""
    return bool(getattr(args, "explain", False)
                or getattr(args, "explain_out", None))


def load_autofit(path):
    """Load-and-validate a ``--autofit`` value (None passes through) —
    the one CLI ingestion point over ``autofit.load_fitted``."""
    if not path:
        return None
    from hpc_patterns_tpu.harness import autofit

    return autofit.load_fitted(path)


def add_msg_size_args(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "-p",
        "--log2-elements",
        type=int,
        default=25,
        help="message size = 2**p elements (default 25, ~128 MiB float32)",
    )
    p.add_argument("--dtype", default="float32", help="element dtype (dtypes.REGISTRY key)")


def _nonneg_int(s: str) -> int:
    v = int(s)
    if v < 0:
        raise argparse.ArgumentTypeError(f"must be >= 0, got {v}")
    return v


def add_sweep_args(p: argparse.ArgumentParser, default_min_p: int = 3) -> None:
    """The size-sweep start flag shared by the sweeping apps (pingpong,
    allreduce --sweep): sizes run 2**min_p .. 2**p."""
    p.add_argument(
        "--min-p",
        type=_nonneg_int,
        default=default_min_p,
        help=f"sweep start: 2**min_p elements (default {default_min_p})",
    )


def add_memory_kind_args(p: argparse.ArgumentParser) -> None:
    g = p.add_mutually_exclusive_group()
    g.add_argument(
        "-H",
        "--host",
        dest="memory_kind",
        action="store_const",
        const="pinned_host",
        help="buffers in host memory kind (reference -H, host USM)",
    )
    g.add_argument(
        "-D",
        "--device",
        dest="memory_kind",
        action="store_const",
        const="device",
        help="buffers in device HBM (reference -D, device USM; default)",
    )
    g.add_argument(
        "-S",
        "--shared",
        dest="memory_kind",
        action="store_const",
        const="device",
        help="reference -S shared USM; no TPU analog, treated as device",
    )
    p.set_defaults(memory_kind="device")
