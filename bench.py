"""Headline benchmark: on-chip DMA/compute overlap speedup.

The reference's headline claim is concurrent-kernel/copy overlap on one
device (concurency/sycl_con.cpp; BASELINE.json "concurrent-kernel overlap
%"). The TPU-native equivalent measured here: a Pallas double-buffered
HBM→VMEM pipeline (compute on chunk i while chunk i+1's DMA flies) vs the
serialized wait-then-compute walk of the same work
(hpc_patterns_tpu/concurrency/pipeline.py).

Protocol (all on-device; the host clock only ever sees differences):
- per-pass times via completion-forced differencing
  (harness.timing.amortized_seconds) — dispatch/readback latency cancels;
- C12-style autotune: tripcount set so compute/pass ≈ DMA/pass
  (sycl_con.cpp:257-268's balance step);
- verdict per the reference rule: PASS iff speedup > theoretical/1.3
  (sycl_con.cpp:279-296).

Prints ONE JSON line:
  {"metric": "onchip_overlap_speedup", "value": <speedup>, "unit": "x",
   "vs_baseline": <speedup / (theoretical_max / 1.3)>}
vs_baseline >= 1.0 means the overlap beats the reference's own PASS bar.

Exit status: 0 only for a capture that measured on a ``tpu`` backend
with every scenario row intact. A backend that is not ``tpu`` (there is
no interpreter fallback under this metric name), a degenerate capture
(a component measured nothing) or a scenario that raised still prints
its JSON line — the failure is a self-describing artifact — and then
exits non-zero.

``--gate``: capture as usual, write the result as the next
``BENCH_rNN.json`` round, then run the regression gate
(``python -m hpc_patterns_tpu.harness.regress``) over the trajectory —
exit nonzero if the new round degrades a headline metric beyond
tolerance, or if the capture itself failed.
"""

import json
import os
import select
import signal
import subprocess
import sys
import time

# jax + the pipeline module are imported inside the measurement child:
# the DEFAULT entry is a supervisor that never imports jax, runs the
# measurement in a child process and enforces the timeouts from outside
# — a backend attach that blocks in C code is beyond any in-process
# watchdog (a Python-level SIGALRM handler never fires there), and a
# parent that stays off jax leaves the chip to the one process that
# measures on it.
jax = None
pipeline = None

_UP_SENTINEL = "HPCPAT_BENCH_UP"

# 16 x (2048, 128) f32 = 16 MiB working set. Fewer, larger chunks than
# the DMA-granularity minimum: the ~0.3 us/chunk loop+semaphore cost is
# amortized 4x, which measured 1.87x overlap (vs 1.50x at 64x512) and
# pushes per-chunk DMA to ~650 GB/s.
NUM_CHUNKS = 16
CHUNK_ROWS = 2048
# probe with enough compute that the differenced probe calls are
# device-time-dominated, not dispatch-latency noise — a near-zero
# probe reading would otherwise blow up the balanced tripcount
PROBE_TRIPS = 64
MAX_TRIPS = 4096


# measurement protocol (calibrated pass counts, jitter-proof
# differencing) lives in pipeline.per_pass_seconds, shared with the
# concurrency app's on-chip engine
CAL_PASSES = 1000


def per_pass_seconds(x, mode, tripcount, cal_passes=CAL_PASSES):
    return pipeline.per_pass_seconds(x, mode, tripcount,
                                     cal_passes=cal_passes)


def _fused_collective_detail() -> dict:
    """Fused-ring-collective headline keys (comm/fused.py), captured in
    the same measurement child as the overlap headline:

    - ``fused_allreduce_gbps``: ring-normalized bus bandwidth of
      ``Communicator.allreduce(algorithm="fused")`` — the
      device-initiated in-kernel ring;
    - ``allreduce_overlap_frac``: 1 - t(fused allgather_matmul) /
      t(host-driven gather-then-matmul), i.e. the fraction of the
      serial route's time the fused kernel hides by computing each
      matmul tile while the next shard's remote DMA is in flight
      (clamped at 0);
    - ``allreduce_busbw_gbps``: the same busbw normalization measured
      on ``algorithm="collective"`` — the gated host-driven baseline
      row the fused number is judged against;
    - ``allreduce_gbps_by_algorithm``: the fused-vs-collective-vs-ring
      comparison row (informational, not gated).

    Returns {} on a single-device topology (no ring to run).
    """
    import numpy as np

    from hpc_patterns_tpu import topology
    from hpc_patterns_tpu.comm import Communicator

    if len(jax.devices()) < 2:
        return {}
    # per-rank elements: the fused kernel keeps the whole shard + two
    # chunk-slot arrays VMEM-resident (no grid streaming yet), so the
    # shard is 4 MiB — wire-dominated but ~4x inside the kernel's
    # VMEM budget
    n = 1 << 20
    reps = 10
    comm = Communicator(topology.make_mesh({"x": -1}), "x")
    x = comm.shard(np.ones((comm.size, n), np.float32))

    def best_seconds(fn, *args):
        jax.block_until_ready(fn(*args))  # compile + warm outside
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    gbps = {}
    nbytes = n * x.dtype.itemsize
    for alg in ("fused", "collective", "ring_chunked"):
        t = best_seconds(comm.jit_allreduce(x, alg), x)
        # ring busbw normalization: 2*S*(size-1)/size bytes per link
        gbps[alg] = 2 * nbytes * (comm.size - 1) / comm.size / t / 1e9

    m, k, n_w = 256, 1024, 1024
    xa = comm.shard(np.ones((comm.size, m, k), np.float32))
    w = comm.shard(np.ones((comm.size, k, n_w), np.float32))
    t_fused = best_seconds(
        lambda a, b: comm.allgather_matmul(a, b, "fused"), xa, w)
    t_host = best_seconds(
        lambda a, b: comm.allgather_matmul(a, b, "collective"), xa, w)
    return {
        "fused_allreduce_gbps": round(gbps["fused"], 3),
        # the gated host-driven baseline row: the same ring-busbw
        # normalization measured on algorithm="collective" (the
        # jax.lax.psum route the fused kernel is judged against)
        "allreduce_busbw_gbps": round(gbps["collective"], 3),
        "allreduce_overlap_frac": round(
            max(0.0, 1.0 - t_fused / t_host), 4) if t_host > 0 else 0.0,
        "allreduce_gbps_by_algorithm": {
            a: round(v, 3) for a, v in gbps.items()},
    }


def _serving_detail() -> dict:
    """Single-engine serving headline keys, captured in the same
    measurement child as the overlap headline:

    - ``serving_tok_s``: engine-window tok/s of the continuous
      batcher on ``bench_serving.run_bench``'s smoke shape
      (oracle-exact vs standalone decode before the number exists);
    - ``serving_bubble_frac``: host-gap fraction of that engine
      window — the overlapped-admission claim in one number;
    - ``serving_prefill_compiles``: distinct prefill compilations the
      bucket ladder admitted (a ladder regression shows up as a
      compile-count jump before it shows up in the wall clock).

    These three are the oldest gated keys in ``regress.py``'s table
    and were captured by hand (or not at all) until contractlint's
    ``gate-key-orphan`` flagged them as emitterless."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import bench_serving

    r = bench_serving.run_bench(**bench_serving.smoke_config(),
                                quiet=True)
    return {
        "serving_tok_s": round(r["tokens_per_s_engine"], 1),
        "serving_bubble_frac": round(r["bubble_frac"], 4),
        "serving_prefill_compiles": int(r["prefill_compiles"]),
    }


def _serving_plane_detail() -> dict:
    """Serving-plane headline keys (round 10), captured in the same
    measurement child as the overlap headline:

    - ``plane_goodput_tok_s``: SLO-attained tok/s of an open-loop
      stream routed across a homogeneous 2-replica plane;
    - ``kv_migration_overlap_frac``: the measured fraction of each
      KV-handoff window hidden under the destination replica's
      in-flight decode chunk in the disaggregated 1-prefill/1-decode
      shape (serving_plane/router.py);
    - ``dma_migration_overlap_frac`` / ``migration_bytes_per_round``
      (round 17): the same overlap measured on a second 1p/1d run
      whose handoffs ride the fused paired remote-DMA kernel
      (``ServingPlane(migration="dma")``, comm/migration_dma.py) —
      the router reports the DMA ledger only for bundles that
      actually rode the kernel, so a silent fallback shows up as
      coverage loss here, not as a wrong number — and the dispatched
      KV-payload bytes per plane round on that run.

    Runs ``bench_serving.run_plane``'s smoke shape (oracle-exact on
    every leg before any number is returned). Returns {} when there is
    nothing to run on."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import bench_serving

    r = bench_serving.run_plane(**bench_serving.plane_smoke_config(),
                                quiet=True)
    rd = bench_serving.run_plane(**bench_serving.plane_smoke_config(),
                                 migration="dma", quiet=True)
    detail = {
        "plane_goodput_tok_s": round(r["plane_goodput_tok_s"], 1),
        "kv_migration_overlap_frac": round(
            r["kv_migration_overlap_frac"], 4),
        "plane_migrations": r["migrations"],
        "migration_bytes_per_round": round(
            rd["migration_bytes_per_round"], 1),
    }
    if rd["dma_migration_overlap_frac"] is not None:
        detail["dma_migration_overlap_frac"] = round(
            rd["dma_migration_overlap_frac"], 4)
    return detail


def _offload_detail() -> dict:
    """Tiered-memory headline keys (round 11), captured in the same
    measurement child as the overlap headline:

    - ``offload_goodput_tok_s``: SLO-attained tok/s of an engine whose
      HBM pool is capped well below the stream's working set, fronting
      a host-resident pool through the residency manager
      (``hpc_patterns_tpu/memory/``) — token-identical to the all-HBM
      engine before the number exists;
    - ``prefetch_overlap_frac``: measured fraction of host->HBM
      prefetch-window time hidden under the in-flight decode chunk
      (the stream-aware offloaded-messaging claim, proved from trace
      windows).

    Runs ``bench_serving.run_offload``'s smoke shape (oracle-exact,
    real eviction asserted)."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import bench_serving

    r = bench_serving.run_offload(**bench_serving.offload_smoke_config(),
                                  quiet=True)
    return {
        "offload_goodput_tok_s": round(r["offload_goodput_tok_s"], 1),
        "prefetch_overlap_frac": round(r["prefetch_overlap_frac"], 4),
        "offload_swaps": r["swap_outs"],
    }


def _shared_prefix_detail() -> dict:
    """Prefix-sharing headline keys (round 12), captured in the same
    measurement child as the overlap headline:

    - ``shared_goodput_tok_s``: SLO-attained tok/s of a shared-prefix
      open-loop stream (template pool + conversation-tree turns)
      through the sharing-aware arena (``prefix_cache=True`` — radix
      match at admission, refcounted read-only page mapping, tail-only
      prefill), token-identical to a private-pages engine before the
      number exists;
    - ``prefill_skip_frac``: the fraction of submitted prompt tokens
      whose prefill the radix match skipped (asserted > 0.3 on the
      template mix inside the run).

    Runs ``bench_serving.run_shared``'s smoke shape."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import bench_serving

    r = bench_serving.run_shared(**bench_serving.shared_smoke_config(),
                                 quiet=True)
    return {
        "shared_goodput_tok_s": round(r["shared_goodput_tok_s"], 1),
        "prefill_skip_frac": round(r["prefill_skip_frac"], 4),
        "prefix_hits": r["prefix_hits"],
    }


def _elastic_detail() -> dict:
    """Elastic-plane headline keys (round 14), captured in the same
    measurement child as the overlap headline:

    - ``elastic_slo_attainment``: per-class SLO attainment of the
      autoscaled plane on a diurnal ramp under replica-death chaos —
      asserted STRICTLY above the fixed plane's on the same replayed
      schedule before the number exists (the fixed plane sheds);
    - ``goodput_per_replica_round``: SLO-attained tokens per live
      replica-round — the efficiency headline that rewards holding
      the SLO with fewer replica-rounds, not just holding it.

    Runs ``bench_serving.run_elastic``'s smoke shape (every served
    stream byte-exact greedy AND sampled, warm spin-up beat cold init,
    the death fault verified fired — all asserted inside)."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import bench_serving

    r = bench_serving.run_elastic(
        **bench_serving.elastic_smoke_config(), quiet=True)
    return {
        "elastic_slo_attainment": round(r["elastic_slo_attainment"], 4),
        "goodput_per_replica_round": round(
            r["goodput_per_replica_round"], 2),
        "elastic_spinups": r["spinups"],
        "warm_spinup_ms": round(r["warm_spinup_s"] * 1e3, 2),
        "cold_init_ms": round(r["cold_init_s"] * 1e3, 2),
    }


def _autofit_detail() -> dict:
    """Autofit headline keys (round 16), captured in the same
    measurement child as the overlap headline:

    - ``fitted_goodput_tok_s``: tok/s of an engine built by
      ``ContinuousBatcher.from_fitted`` from a FittedConfig that
      ``harness/autofit.py`` fitted off the recording leg's own RunLog
      JSONL — the observability-becomes-control loop closed end to
      end;
    - ``autofit_gain_frac``: fitted over default wall clock minus one
      on the same stream and pool geometry (the fitted ladder's
      expected padding is asserted STRICTLY below the default's before
      either number exists).

    Runs ``bench_serving.run_fitted``'s smoke shape (both legs
    byte-exact vs standalone decode)."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import bench_serving

    r = bench_serving.run_fitted(**bench_serving.fit_smoke_config(),
                                 quiet=True)
    return {
        "fitted_goodput_tok_s": round(r["fitted_goodput_tok_s"], 1),
        "autofit_gain_frac": round(r["autofit_gain_frac"], 4),
        "autofit_padding_default": round(
            r["expected_padding_default"], 2),
        "autofit_padding_fitted": round(r["expected_padding_fitted"], 2),
    }


def _reqtrace_detail() -> dict:
    """Request-forensics headline keys (round 18), captured in the
    same measurement child as the overlap headline:

    - ``attribution_coverage_frac``: fraction of finished-request wall
      time the lifecycle-segment tilings (harness/reqtrace.py) account
      for over the chaos scenario's timed leg — run_scenario already
      asserts it in-run at >= 0.95, so the gate watches for drift, not
      correctness;
    - ``ttft_p99_queue_share``: share of the p99 TTFT band's
      attribution window spent in the ``queued`` segment
      (harness/explain.py) — the "where did the p99 go" number,
      captured per round so tail regressions come pre-attributed.

    The same scenario run also yields the robustness row's gated
    keys — ``serving_goodput_tok_s`` (SLO-attained tok/s under
    chaos) and ``serving_degraded_bubble_frac`` (the degraded-mode
    engine bubble) — which had no emitter at all until contractlint's
    ``gate-key-orphan`` flagged the orphaned gate rows.

    Runs ``bench_serving.run_scenario``'s smoke shape (oracle-exact,
    chaos seeded)."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import bench_serving

    r = bench_serving.run_scenario(
        **bench_serving.scenario_smoke_config(), quiet=True)
    return {
        "attribution_coverage_frac": round(
            r["attribution_coverage_frac"], 4),
        "ttft_p99_queue_share": round(r["ttft_p99_queue_share"], 4),
        "serving_goodput_tok_s": round(r["goodput_tok_s"], 1),
        "serving_degraded_bubble_frac": round(r["bubble_frac"], 4),
    }


def _budget_detail() -> dict:
    """Segment-budget headline keys (round 20), the attribution
    loop's gate feed:

    - ``tpot_p99_stall_share``: share of the pooled p99 inter-token
      gap band spent in decode-stall segments
      (harness/explain.py TPOT_STALL_KINDS) over the seeded
      slow_host_transfer row — the "where did the inter-token tail
      go" number;
    - ``budget_breach_segments``: how many distinct segments breached
      their SLO-budget allowance (harness/budget.py) — run_slo_budget
      already asserts the set is exactly {"prefetch_wait"} in-run, so
      the gate watches the count for smear (a second breached segment
      means attribution leaked out of the injected mechanism).

    Runs ``bench_serving.run_slo_budget``'s one shape (oracle-exact,
    chaos seeded, breach set asserted inside)."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import bench_serving

    r = bench_serving.run_slo_budget(
        **bench_serving.slo_budget_smoke_config(), quiet=True)
    return {
        "tpot_p99_stall_share": round(r["tpot_p99_stall_share"], 4),
        "budget_breach_segments": len(r["budget_breach_segments"]),
    }


def _quantized_detail() -> dict:
    """Quantized-decode headline keys (round 13), captured in the same
    measurement child as the overlap headline:

    - ``quant_goodput_tok_s``: SLO-attained tok/s of an engine serving
      from an int8 KV pool (one-byte pages + per-row scales), gated
      only after BOTH oracles pass — token-identical to standalone
      decode within the precision, and the teacher-forced precision
      law (greedy top-1 agreement + TV-distance bounds,
      models/quantization.py) against the baseline precision;
    - ``kv_pool_bytes_frac``: measured quantized-pool bytes over a
      bf16 pool at equal residents (~0.53 — the capacity multiplier
      every tier inherits);
    - ``quant_bubble_frac``: the quantized engine's admission-bubble
      fraction (the per-precision bubble % the gate watches).

    Runs ``bench_serving.run_quantized``'s smoke shape."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import bench_serving

    r = bench_serving.run_quantized(
        **bench_serving.quantized_smoke_config(), quiet=True)
    return {
        "quant_goodput_tok_s": round(r["quant_goodput_tok_s"], 1),
        "kv_pool_bytes_frac": round(r["kv_pool_bytes_frac"], 4),
        "quant_bubble_frac": round(r["quant_bubble_frac"], 4),
    }


def _unavailable_line(err: BaseException) -> str:
    """Degenerate-capture verdict line for a backend that won't even
    initialize (value 0.0, never a pass, the error preserved)."""
    return json.dumps(
        {
            "metric": "onchip_overlap_speedup",
            "value": 0.0,
            "unit": "x",
            "vs_baseline": 0.0,
            "detail": {
                "degenerate": True,
                "backend": "unavailable",
                "error": f"{type(err).__name__}: {err}",
            },
        }
    )


def _emit_unavailable(err: BaseException) -> int:
    """Degenerate capture for a backend that won't initialize or is not
    a TPU, then exit status 1.

    The reference's binaries emit a machine-readable verdict in every
    failure mode (concurency/sycl_con.cpp:279-296). This path makes the
    failure a self-describing artifact: value 0.0, never a pass,
    backend "unavailable", the error preserved in detail.
    """
    print(
        _unavailable_line(err),
        flush=True,  # must reach the pipe before any teardown hang
    )
    return 1


def _capture_status(line: str) -> int:
    """Exit status a verdict line earns: 0 only when it parses, is not
    degenerate and carries no scenario ``*_error`` key."""
    try:
        detail = json.loads(line)["detail"]
    except (ValueError, KeyError, TypeError):
        return 1
    failed = detail.get("degenerate") or any(
        k.endswith("_error") for k in detail)
    return 1 if failed else 0


def _supervise() -> int:
    """Print the supervised capture's one verdict line; the exit status
    is the capture's own (:func:`_capture_status`)."""
    line = _supervised_capture()
    print(line)
    return _capture_status(line)


def _supervised_capture() -> str:
    """Run the measurement in a child process, enforcing timeouts from
    outside — the only guard that works when jax-import/backend-attach
    blocks in C code. ``HPCPAT_BENCH_INIT_TIMEOUT`` (default 600 s)
    bounds import+attach; ``HPCPAT_BENCH_TOTAL_TIMEOUT`` (default
    3600 s) bounds the whole capture — a backend can die
    MID-measurement, so both phases need a deadline. 0 disables either.
    Returns the one JSON verdict line (a degenerate ``_unavailable_line``
    when the child hung or died with no capture).
    """
    init_t = int(os.environ.get("HPCPAT_BENCH_INIT_TIMEOUT", "600"))
    total_t = int(os.environ.get("HPCPAT_BENCH_TOTAL_TIMEOUT", "3600"))
    env = dict(os.environ, HPCPAT_BENCH_CHILD="1")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        stdout=subprocess.PIPE, env=env,
    )
    # Raw-fd reads with our own line buffer: select() on the fd plus a
    # buffered readline() can block while a complete line already sits
    # in the text-layer buffer.
    fd = proc.stdout.fileno()
    start = time.monotonic()
    got_up = False
    json_line = None
    buf = b""
    timed_out = None

    def _consume(chunk):
        nonlocal buf, got_up, json_line
        buf += chunk
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            line = line.strip().decode("utf-8", "replace")
            if line == _UP_SENTINEL:
                got_up = True
            elif line:
                try:  # only a parseable verdict counts as the capture
                    json.loads(line)
                except ValueError:
                    continue
                json_line = line

    try:
        while True:
            deadlines = []
            if total_t > 0:
                deadlines.append(start + total_t)
            if not got_up and init_t > 0:
                deadlines.append(start + init_t)
            timeout = (max(0.0, min(deadlines) - time.monotonic())
                       if deadlines else None)
            r, _, _ = select.select([fd], [], [], timeout)
            if not r:
                phase = ("jax import / backend init" if not got_up
                         else "measurement")
                limit = init_t if not got_up else total_t
                timed_out = TimeoutError(
                    f"{phase} exceeded {limit}s (backend "
                    "unresponsive)")
                break
            chunk = os.read(fd, 65536)
            if not chunk:
                break  # child EOF
            _consume(chunk)
            if json_line is not None:
                # verdict in hand — don't wait out a teardown hang
                break
    finally:
        if proc.poll() is None:
            proc.kill()
    proc.wait()
    # drain anything the child managed to write before dying/being
    # killed — a capture that finished just before a teardown hang must
    # win over the timeout verdict. Non-blocking: a helper process
    # inheriting the pipe's write end could otherwise hold this read
    # open forever.
    try:
        os.set_blocking(fd, False)
        while True:
            chunk = os.read(fd, 65536)
            if not chunk:
                break
            _consume(chunk)
    except (BlockingIOError, OSError, ValueError):
        pass
    if json_line is not None:
        return json_line
    if timed_out is not None:
        return _unavailable_line(timed_out)
    return _unavailable_line(
        RuntimeError(f"measurement child exited rc={proc.returncode} "
                     "with no capture"))


def _run_gate(argv) -> int:
    """``bench.py --gate``: capture a new round, write it as the next
    ``BENCH_rNN.json``, then run the regression gate
    (hpc_patterns_tpu.harness.regress) over the whole trajectory and
    exit with ITS status (or the capture's own, when that failed) — so
    a measurement sequence fails loudly when the newest measured round
    degrades a headline metric.

    The gate subprocess runs with ``JAX_PLATFORMS=cpu``: regress itself
    is pure JSON math, but importing the package initializes jax, and
    the chip belongs to the measurement child alone.
    """
    import argparse
    import glob

    p = argparse.ArgumentParser(
        description="bench capture + regression gate")
    p.add_argument("--gate", action="store_true")
    p.add_argument("--rounds-glob", default="BENCH_r*.json",
                   help="trajectory files to gate against")
    p.add_argument("--out", default=None,
                   help="round file to write (default: next BENCH_rNN)")
    p.add_argument("--tolerance", type=float, default=None,
                   help="passed through to harness.regress")
    args = p.parse_args(argv)

    line = _supervised_capture()
    print(line, flush=True)
    try:
        parsed = json.loads(line)
    except ValueError:
        parsed = None
    here = os.path.dirname(os.path.abspath(__file__))
    prior = sorted(glob.glob(os.path.join(here, args.rounds_glob)))
    n = 0
    for path in prior:
        try:
            with open(path) as f:
                n = max(n, int(json.load(f).get("n", 0)))
        except (OSError, ValueError):
            continue
    n += 1
    # absolute: the gate subprocess runs with cwd=here, so a relative
    # --out from another cwd would otherwise point it at the wrong file
    out = os.path.abspath(args.out) if args.out else os.path.join(
        here, f"BENCH_r{n:02d}.json")
    with open(out, "w") as f:
        json.dump({"n": n, "cmd": "python bench.py --gate",
                   "rc": _capture_status(line),
                   "tail": line + "\n", "parsed": parsed}, f, indent=2)
    print(f"wrote round {n} -> {out}", flush=True)
    cmd = [sys.executable, "-m", "hpc_patterns_tpu.harness.regress",
           *prior, out]
    if args.tolerance is not None:
        cmd += ["--tolerance", str(args.tolerance)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        gate = subprocess.run(cmd, env=env, cwd=here,
                              timeout=300).returncode
    except subprocess.TimeoutExpired:
        print("ERROR: regression gate timed out", flush=True)
        return 1
    return gate or _capture_status(line)


def main() -> int:
    # Supervised by default; HPCPAT_BENCH_CHILD marks the measurement
    # child, HPCPAT_BENCH_SUPERVISE=0 opts out (e.g. under a debugger).
    if os.environ.get("HPCPAT_BENCH_CHILD") != "1" and "--gate" in sys.argv:
        return _run_gate(sys.argv[1:])
    if (os.environ.get("HPCPAT_BENCH_CHILD") != "1"
            and os.environ.get("HPCPAT_BENCH_SUPERVISE", "1") != "0"):
        return _supervise()

    # Belt-and-braces in-process watchdog for raise-style failures and
    # pure-Python hangs (covers the unsupervised mode too).
    global jax, pipeline
    init_timeout = int(os.environ.get("HPCPAT_BENCH_INIT_TIMEOUT", "600"))

    def _alarm(signum, frame):
        raise TimeoutError(
            f"jax import / backend init exceeded {init_timeout}s "
            "(backend unresponsive)"
        )

    try:
        if init_timeout > 0 and hasattr(signal, "SIGALRM"):
            signal.signal(signal.SIGALRM, _alarm)
            signal.alarm(init_timeout)
        import jax
        from hpc_patterns_tpu import compile_cache
        from hpc_patterns_tpu.concurrency import pipeline
        compile_cache.enable()
        backend = jax.default_backend()
    except Exception as err:  # init failure or hang — emit, don't crash
        return _emit_unavailable(err)
    finally:
        if init_timeout > 0 and hasattr(signal, "SIGALRM"):
            signal.alarm(0)
    if backend != "tpu":
        # a measurement path that finds no chip fails: an interpreter
        # run at toy shapes is not this metric
        return _emit_unavailable(RuntimeError(
            f"backend is {backend!r}, not 'tpu': nothing to measure"))
    # tell the supervisor the init phase is over — only when one is
    # listening (unsupervised stdout must stay a single JSON line)
    if os.environ.get("HPCPAT_BENCH_CHILD") == "1":
        print(_UP_SENTINEL, flush=True)

    measure_error = None
    try:
        x = jax.block_until_ready(
            pipeline.make_hbm_array(NUM_CHUNKS, CHUNK_ROWS))
        t_dma = per_pass_seconds(x, "dma", PROBE_TRIPS)
        t_comp_probe = per_pass_seconds(x, "compute", PROBE_TRIPS)
    except Exception as err:  # backend died mid-measurement
        measure_error = err
        t_dma = t_comp_probe = 0.0
        x = None
    if t_dma <= 0 or t_comp_probe <= 0:
        # probe measured nothing usable — don't autotune into a
        # pathological tripcount; fall through to the degenerate emitter
        trips, t_comp, t_serial, t_overlap = 0, 0.0, 0.0, 0.0
        raw_pairs = []
    else:
        try:
            # balance compute to DMA (the shared C12 balance step)
            trips = min(max(1, int(PROBE_TRIPS * t_dma / t_comp_probe)),
                        MAX_TRIPS)
            trips, t_comp = pipeline.balance_tripcount(
                lambda m, t: per_pass_seconds(x, m, t), t_dma,
                "compute", trips, max_trips=MAX_TRIPS,
            )

            # five (serial, overlap) pairs measured back to back, MEDIAN
            # ratio wins: machine conditions drift run to run, so the
            # two legs of a ratio must be temporally adjacent or the
            # speedup wobbles by several percent — and the median (unlike
            # a max-of-ratios) cannot be inflated by a lucky noise draw
            pairs = [
                p for p in (
                    (per_pass_seconds(x, "serial", trips),
                     per_pass_seconds(x, "overlap", trips))
                    for _ in range(5)
                ) if min(p) > 0
            ]
            raw_pairs = list(pairs)
            if pairs:
                pairs = sorted(pairs, key=lambda p: p[0] / p[1])
                t_serial, t_overlap = pairs[len(pairs) // 2]
            else:
                t_serial = t_overlap = 0.0
        except Exception as err:  # backend died mid-measurement
            measure_error = err
            trips, t_comp, t_serial, t_overlap = 0, 0.0, 0.0, 0.0
            raw_pairs = []

    # the scenario rows, each captured in this same measurement child
    # (every emitter asserts its own oracle before a number exists). A
    # row that raises does not sink the headline: its error lands under
    # ``<row>_error`` in the JSON line — and the exit status is then
    # non-zero (_capture_status), so a failed scenario is never a pass.
    scenario_rows = (
        # device-initiated allreduce + overlapped allgather-matmul
        ("fused_collective", _fused_collective_detail),
        # continuous-batcher tok/s, bubble fraction, prefill compiles
        ("serving", _serving_detail),
        # router goodput across 2 replicas + KV-migration overlap
        ("serving_plane", _serving_plane_detail),
        # constrained-HBM goodput + prefetch-under-chunk overlap
        ("offload", _offload_detail),
        # sharing-arena goodput + prefill-skip fraction
        ("shared_prefix", _shared_prefix_detail),
        # int8-KV goodput + pool-bytes fraction vs bf16
        ("quantized", _quantized_detail),
        # autoscaled-vs-static SLO attainment under replica-death chaos
        ("elastic", _elastic_detail),
        # profile-fitted config A/B
        ("autofit", _autofit_detail),
        # lifecycle-segment coverage + the p99 band's queued share
        ("reqtrace", _reqtrace_detail),
        # the seeded decode-stall stream's inter-token tail share
        ("budget", _budget_detail),
    )
    scenario_detail = {}
    for row, emitter in scenario_rows:
        try:
            scenario_detail.update(emitter())
        except Exception as err:  # noqa: BLE001 — reported, then rc != 0
            scenario_detail[f"{row}_error"] = (
                f"{type(err).__name__}: {err}")

    # any clamped-to-zero component means the run measured nothing usable
    degenerate = min(t_overlap, t_serial, t_dma, t_comp) <= 0
    if degenerate:
        # report "measured nothing", never a pass
        speedup, theoretical, vs_baseline = 0.0, 0.0, 0.0
    else:
        speedup = t_serial / t_overlap
        theoretical = (t_dma + t_comp) / max(t_dma, t_comp, 1e-12)
        vs_baseline = speedup / (theoretical / 1.3) if theoretical > 0 else 0.0
    nbytes = x.size * 4 if x is not None else 0
    line = json.dumps(
        {
            "metric": "onchip_overlap_speedup",
            "value": round(speedup, 4),
            "unit": "x",
            "vs_baseline": round(vs_baseline, 4),
            "detail": {
                "t_dma_us": round(t_dma * 1e6, 2),
                "t_compute_us": round(t_comp * 1e6, 2),
                "t_serial_us": round(t_serial * 1e6, 2),
                "t_overlap_us": round(t_overlap * 1e6, 2),
                "dma_gbps": round(nbytes / t_dma / 1e9, 1) if t_dma > 0 else None,
                "theoretical_max_speedup": round(theoretical, 4),
                "tripcount": trips,
                "degenerate": degenerate,
                "error": (f"{type(measure_error).__name__}: "
                          f"{measure_error}")
                if measure_error is not None else None,
                "backend": backend,
                "device_kind": jax.devices()[0].device_kind,
                "device_count": len(jax.devices()),
                **scenario_detail,
                # the five raw (serial, overlap) pairs, measurement
                # order — the distribution behind the median
                "pairs_us": [
                    [round(s * 1e6, 2), round(o * 1e6, 2)]
                    for s, o in raw_pairs
                ],
            },
        }
    )
    # the supervisor's drain only sees what reached the pipe: an
    # unflushed verdict dies with the child on a teardown hang
    print(line, flush=True)
    return _capture_status(line)


if __name__ == "__main__":
    sys.exit(main())
