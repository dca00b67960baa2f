#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call —
the trainer, the server and the pattern apps, each a normal CLI
invocation with ``--backend tpu`` — at the full width of the one model
the repo has (d=1024, L=8, H=8, d_ff=4096, vocab 32768, bf16; weights
random from a seed), and checks what comes out by the apps' own means:
their SUCCESS verdicts, the records they log, and that every Pallas
kernel on the path was traced with ``interpret=False``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the ranks-talk-to-each-other legs
    python chip_smoke.py --platform cpu --tiny   # same legs, toy sizes

One process per chip at a time: this parent never imports jax or the
package. It runs the legs as sequential children, which all place their
compile cache by ``hpc_patterns_tpu/compile_cache.py``
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``.cache/jax`` in the
checkout), so a second run on the same machine is warm.

Output: the device and versions first, one ``PASS``/``FAIL`` line per
leg with wall seconds, a summary line, and — only when every leg passed
on the platform asked for — one last JSON line
``{"ok": true, "device": {...}}``. Any other outcome exits non-zero and
prints no result line. ``--platform cpu`` is an opt-in the operator
types; the script never chooses it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
#: everything must be done, compilation included, inside the driver's
#: 1200 s; the margin is for the summary and the children's teardown
DEADLINE_S = 1150
APPS = "hpc_patterns_tpu.apps."

# the one model, at full width; depth and batch as the benches run it
FLAGSHIP = ["--d-model", "1024", "--n-layers", "8", "--n-heads", "8",
            "--vocab", "32768"]
TINY = ["--d-model", "64", "--n-layers", "2", "--n-heads", "4",
        "--vocab", "256"]
# linear warmup: at a constant 3e-4 the 5th step of this configuration
# spikes above the first (measured on the v5e and on the CPU alike — Adam's
# early steps), which a 5-step "loss went down" verdict lands on
TRAIN_STEPS = ["--attention", "flash", "--remat", "--steps", "5",
               "--warmup-steps", "8"]
QUICK = ["--repetitions", "2", "--warmup", "1"]
# an overlap verdict is a device timing; at toy sizes the concurrency
# legs run in serial mode: every command executes through the same
# engine, no speedup is judged
TINY_COPIES = ["--copy-elements", "4096", "--tripcount", "4"] + QUICK


class Leg:
    """One child: an app CLI, how long it may take, the Pallas kernels
    that must have run compiled on its path, and a check of its log."""

    def __init__(self, name, module, args, *, tiny_args=None, kernels=(),
                 check=None, timeout_s=420, gating=True):
        self.name, self.module = name, module
        self.args, self.tiny_args = args, tiny_args
        self.kernels, self.check = kernels, check
        self.timeout_s, self.gating = timeout_s, gating


def _result(records, name_prefix=""):
    rows = [r for r in records if r.get("kind") == "result"
            and str(r.get("name", "")).startswith(name_prefix)]
    if not rows:
        raise AssertionError(f"no result record {name_prefix!r}")
    return rows


def check_train(records, ctx):
    (r,) = _result(records, "train")
    steps = [s for s in records if s.get("kind") == "step"]
    losses = [s["loss"] for s in steps]
    assert r["success"] and len(losses) == 5, (r["success"], losses)
    assert all(math.isfinite(v) for v in losses), losses
    assert losses[-1] < losses[0], losses
    note = (f"loss {losses[0]:.3f}->{losses[-1]:.3f}, first step "
            f"{steps[0]['dt_s']:.1f}s (compile), later steps "
            f"{min(s['dt_s'] for s in steps[1:]):.3f}s")
    if r.get("mesh"):
        # the work is really spread: every device of the mesh holds a
        # parameter shard and a batch shard, and its allocator shows it
        n = math.prod(r["mesh"].values())
        placed = r["placement"]
        assert len(placed) == n, (n, placed)
        for p in placed:
            params_b, batch_b = p["shard_bytes"]
            assert params_b > 0 and batch_b > 0, p
            if ctx["platform"] == "tpu":
                assert p["bytes_in_use"] >= params_b, p
        note += f", {n} devices each hold params+batch shards"
    return note


def check_serve(records, ctx):
    (r,) = _result(records, "serve")
    assert r["success"], r
    assert r["prefill_compiles_warm"] == 0, r
    assert r["oracle"] in ("exact", "law"), r
    if ctx["platform"] == "tpu":
        assert r["decode_attn"] == "flash", r
    note = (f"{r['requests']} requests, {r['served_tokens']} tokens, "
            f"decode_attn={r['decode_attn']}, oracle={r['oracle']}")
    if r["oracle"] == "law":
        note += (f" (greedy agreement {r['law_greedy_agreement']:.4f} "
                 "with the f32 reference, teacher-forced)")
    return note


def check_concurrency(records, ctx):
    rows = _result(records, "concurrency")
    assert all(r["success"] for r in rows), rows
    if ctx["platform"] == "tpu":
        assert all(r.get("engine") == "onchip" for r in rows), rows
    return f"{len(rows)} verdict(s), engine={rows[0].get('engine', 'dispatch')}"


def check_sweep(records, ctx):
    rows = _result(records, "sweep[")
    assert len(rows) >= 4 and all(r["success"] for r in rows), rows
    return f"{len(rows)} configurations"


def check_allreduce(records, ctx):
    rows = _result(records, "allreduce[")
    assert all(r["success"] for r in rows), rows
    assert all(r["world"] == ctx["chips"] for r in rows), rows
    note = f"{len(rows)} point(s), world={rows[0]['world']}"
    if ctx["chips"] > 1:
        # ranks really talked: the ring-normalized bus bandwidth is
        # identically 0 at world=1 and must not be here
        assert all(r["busbw_gbps"] > 0 for r in rows), rows
        note += ", busbw > 0 at every point"
    return note


def check_success(name_prefix):
    def check(records, ctx):
        rows = _result(records, name_prefix)
        assert all(r["success"] for r in rows), rows
        return f"{len(rows)} verdict(s)"
    return check


ONE_CHIP = [
    Leg("train", "train_app",
        FLAGSHIP + ["--seq", "2048", "--batch", "8"] + TRAIN_STEPS,
        tiny_args=TINY + ["--seq", "128", "--batch", "2"] + TRAIN_STEPS,
        kernels=("flash_attention.fwd", "flash_attention.bwd"),
        check=check_train),
    Leg("serve", "serve_app",
        FLAGSHIP + ["--requests", "8", "--slots", "8", "--page-size",
                    "256", "--chunk", "16", "--prompt-len", "512",
                    "--budget", "128", "--prompt-mix"],
        tiny_args=TINY + ["--requests", "4", "--slots", "2",
                          "--prompt-mix"],
        kernels=("flash_decode_paged",), check=check_serve),
    Leg("concurrency", "concurrency_app", ["out_of_order", "C", "M2D"],
        tiny_args=["serial", "C", "M2D"] + TINY_COPIES,
        kernels=("overlap_pipeline.overlap",), check=check_concurrency),
    Leg("sweep", "sweep", [],
        tiny_args=["--modes", "serial"] + TINY_COPIES,
        kernels=("overlap_pipeline.overlap", "overlap_pipeline.overlap_out",
                 "overlap_pipeline.pair_overlap"),
        check=check_sweep),
    Leg("allreduce_ring", "allreduce_app", ["-p", "20"],
        tiny_args=["-p", "10"] + QUICK, check=check_allreduce),
    Leg("allreduce_collective", "allreduce_app", ["-p", "20", "-a"],
        tiny_args=["-p", "10", "-a"] + QUICK, check=check_allreduce),
    Leg("interop", "interop_app", [], tiny_args=[],
        kernels=("pallas_alias_proof",), check=check_success("interop")),
]


def _sweep(algorithm, p):
    return ["--sweep", "--min-p", "3", "-p", p, "--algorithm", algorithm]


FOUR_CHIPS = [
    *(Leg(f"allreduce_sweep_{alg}", "allreduce_app", _sweep(alg, "24"),
          tiny_args=_sweep(alg, "8") + QUICK, check=check_allreduce)
      for alg in ("ring", "ring_chunked", "collective")),
    Leg("pingpong", "pingpong_app", ["-p", "22"],
        tiny_args=["-p", "8"] + QUICK, check=check_success("pingpong")),
    Leg("train_dp2_tp2", "train_app",
        FLAGSHIP + ["--seq", "2048", "--batch", "8", "--dp", "2", "--tp",
                    "2"] + TRAIN_STEPS,
        tiny_args=TINY + ["--seq", "128", "--batch", "4", "--dp", "2",
                          "--tp", "2"] + TRAIN_STEPS,
        kernels=("flash_attention.fwd", "flash_attention.bwd"),
        check=check_train),
    # the device-initiated ring has never run compiled (PR 21: jax
    # refuses to lower it — collective_id without a barrier semaphore,
    # ROADMAP Speed 8): attempted LAST (a kernel that hangs must not
    # take the other legs with it) and reported either way, not gating.
    # The whole shard sits in VMEM, so the sweep stops at 2**18 elements.
    Leg("allreduce_sweep_fused", "allreduce_app", _sweep("fused", "18"),
        tiny_args=_sweep("fused", "8") + QUICK,
        kernels=("fused_allreduce",), check=check_allreduce,
        timeout_s=180, gating=False),
]


def child_env(platform: str, chips: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(HERE)] + [p for p in [env.get("PYTHONPATH")] if p])
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={chips}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env


def run_child(argv, env, timeout_s, stdout):
    """Run one child in its own process group; on timeout (or any exit
    of this parent, SIGTERM included — see main) the whole group is
    killed — nothing started here outlives the script."""
    proc = subprocess.Popen(argv, env=env, cwd=HERE, stdout=stdout,
                            stderr=subprocess.STDOUT,
                            start_new_session=True)
    try:
        return proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return None
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()


def probe_device(env, out_dir: Path) -> dict:
    """What jax finds, asked of a child (the parent must not hold the
    chip): ``apps.common.device_header`` as JSON."""
    code = ("import json; from hpc_patterns_tpu.apps.common import "
            "device_header; print('HEADER ' + json.dumps(device_header()))")
    path = out_dir / "probe.log"
    with path.open("w") as f:
        rc = run_child([sys.executable, "-c", code], env, 300, f)
    lines = [l for l in path.read_text().splitlines()
             if l.startswith("HEADER ")]
    if rc != 0 or not lines:
        tail = " | ".join(path.read_text().splitlines()[-3:])
        raise SystemExit(f"chip_smoke: device probe failed (rc={rc}): {tail}")
    return json.loads(lines[-1][len("HEADER "):])


def header_problem(header: dict, platform: str, chips: int) -> str | None:
    """Why this machine cannot run the smoke asked for, or None."""
    if header["platform"] != platform:
        return (f"jax's platform is {header['platform']!r}, "
                f"not {platform!r}")
    if header["device_count"] < chips:
        return (f"--chips {chips} asked for, "
                f"{header['device_count']} device(s) present")
    return None


def run_leg(leg: Leg, ctx: dict, env: dict, out_dir: Path,
            remaining_s: float) -> tuple[bool, str]:
    args = list(leg.tiny_args if ctx["tiny"] else leg.args)
    if leg.module in ("allreduce_app", "pingpong_app"):
        args += ["--world", str(ctx["chips"])]
    log = out_dir / f"{leg.name}.jsonl"
    argv = [sys.executable, "-m", APPS + leg.module, *args,
            "--backend", ctx["platform"], "--log", str(log)]
    print(f"  $ python -m {APPS}{leg.module} {' '.join(args)} "
          f"--backend {ctx['platform']}", flush=True)
    if log.exists():
        log.unlink()
    with (out_dir / f"{leg.name}.out").open("w") as f:
        rc = run_child(argv, env, min(leg.timeout_s, remaining_s), f)
    if rc is None:
        return False, "timed out and was killed"
    if rc != 0:
        tail = (out_dir / f"{leg.name}.out").read_text().splitlines()[-4:]
        return False, f"exit {rc}: " + " | ".join(tail)
    records = [json.loads(l) for l in log.read_text().splitlines()]
    try:
        device = next(r for r in records if r.get("kind") == "device")
        assert device["platform"] == ctx["platform"], device
        note = leg.check(records, ctx)
        modes = next(r for r in records
                     if r.get("kind") == "kernels")["modes"]
        if ctx["platform"] == "tpu":
            # interpret=False reached every kernel on the path: read
            # from what the wrappers recorded, not from the platform
            missing = [k for k in leg.kernels
                       if not modes.get(k, {}).get("compiled")]
            assert not missing, f"kernels never ran compiled: {missing}"
            interpreted = [k for k, m in modes.items() if m["interpret"]]
            assert not interpreted, f"interpreted on tpu: {interpreted}"
            if modes:
                note += "; compiled: " + ",".join(modes)
    except (AssertionError, KeyError, StopIteration, ValueError) as e:
        return False, f"check failed: {type(e).__name__}: {e}"
    return True, note


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: trainer, server and pattern apps on one "
                         "chip; 4: the multi-chip legs on one host")
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                    help="the platform the legs must run on (cpu is an "
                         "explicit opt-in for running the script's "
                         "logic without a chip)")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes (for --platform cpu)")
    ap.add_argument("--out", default=str(HERE / "chiprun_out" / "chip_smoke"),
                    help="directory for the legs' logs")
    args = ap.parse_args(argv)
    # a terminated parent must still reap its child (run_child's finally)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    if not (HERE / "hpc_patterns_tpu").is_dir():
        print("chip_smoke: FAIL: no hpc_patterns_tpu package beside "
              "this script — it drives the program, it is not one")
        return 1
    t_start = time.monotonic()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    env = child_env(args.platform, args.chips)
    header = probe_device(env, out_dir)
    print("chip_smoke: " + " ".join(f"{k}={v}" for k, v in header.items()),
          flush=True)
    problem = header_problem(header, args.platform, args.chips)
    if problem:
        print(f"chip_smoke: FAIL: {problem}")
        return 1

    ctx = {"platform": args.platform, "chips": args.chips,
           "tiny": args.tiny}
    legs = ONE_CHIP if args.chips == 1 else FOUR_CHIPS
    failed = []
    for leg in legs:
        remaining = DEADLINE_S - (time.monotonic() - t_start)
        t0 = time.monotonic()
        ok, note = ((False, "out of time before it started")
                    if remaining <= 5 else
                    run_leg(leg, ctx, env, out_dir, remaining))
        verdict = "PASS" if ok else "FAIL" if leg.gating else "FAIL(reported)"
        print(f"{verdict} {leg.name} {time.monotonic() - t0:.1f}s: {note}",
              flush=True)
        if not ok and leg.gating:
            failed.append(leg.name)
    wall = time.monotonic() - t_start
    n_gating = sum(leg.gating for leg in legs)
    print(f"chip_smoke: {n_gating - len(failed)}/{n_gating} legs passed "
          f"in {wall:.0f}s" + (f"; FAILED: {', '.join(failed)}"
                               if failed else ""), flush=True)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": header["platform"], "kind": header["device_kind"],
        "count": header["device_count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
