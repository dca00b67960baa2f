"""Serving-plane app: a router + N engine replicas under the launcher.

The launched tier of the disaggregated serving plane
(``hpc_patterns_tpu/serving_plane/``): ``apps/launch.py -np K`` starts
K processes of this app; rank 0 becomes the ROUTER, ranks 1..K-1
become REPLICAS (roles from ``--roles``, e.g. ``prefill,decode`` for
the disaggregated 1p/1d shape). Replicas publish ephemeral localhost
ports under ``--rdv`` (the hostfile analog), the router connects,
admits a seeded open-loop loadgen stream across them, forwards KV
handoffs from prefill- to decode-role replicas, and prints the SLO
table with GOODPUT next to raw tok/s plus a grep-able summary line.

Two engine tiers behind one protocol:

- ``--stub``: deterministic jax-free token generators — the plane's
  ROUTER mechanics (placement, migration forwarding, replica-death
  recovery, shed accounting) exercised in milliseconds; the router
  byte-checks every served stream against the stub's pure function,
  so even the failure drills are oracle-checked (tier-1,
  tests/test_launch.py).
- real engines (default): each replica boots a small model
  (identically seeded, so ``request_key`` agrees across replicas) and
  serves through :class:`~hpc_patterns_tpu.models.serving.EngineCore`.

Chaos composes through the launcher: ``--chaos
'die:replica=2,at=5,site=replica_round'`` kills ONE replica of many
mid-stream; the router re-queues its in-flight requests as resumes on
survivors (or counts them shed — never a silent drop), the rank
report names the lost replica with its fault kind, and the surviving
ranks' traces still merge. Under ``--trace`` + ``--trace-out``, both
sides of every KV handoff record matched ``plane.kv_migration``
windows and ``kv_migration`` schedule fingerprints: the merged
timeline threads flow arrows between the replica lanes and the
schedule verifier proves router and replicas agreed on the handoff
order (docs/serving_plane.md).

Usage (the tier-1 test shape)::

    python -m hpc_patterns_tpu.apps.launch -np 3 --trace-out m.json -- \\
        python -m hpc_patterns_tpu.apps.plane_app --stub \\
        --roles prefill,decode --rdv /tmp/rdv --requests 6 --trace
"""

from __future__ import annotations

import os
import sys
import time

from hpc_patterns_tpu.harness.cli import (
    add_autofit_arg,
    add_explain_args,
    base_parser,
    explain_enabled,
)


def build_parser():
    p = base_parser(__doc__.splitlines()[0])
    add_autofit_arg(p)
    add_explain_args(p)
    p.add_argument("--rdv", required=True,
                   help="rendezvous directory replicas publish their "
                        "listen addresses under (shared by all ranks)")
    p.add_argument("--roles", default="both",
                   help="comma-separated replica roles for ranks 1..N "
                        "(both|prefill|decode; short lists repeat "
                        "their last entry): 'prefill,decode' is the "
                        "disaggregated 1p/1d shape")
    p.add_argument("--stub", action="store_true",
                   help="jax-free deterministic stub engines (router-"
                        "mechanics tier; tokens byte-checked against "
                        "the stub's pure function)")
    p.add_argument("--policy", default="least_loaded",
                   choices=["least_loaded", "round_robin"],
                   help="router placement policy")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--rate", type=float, default=200.0,
                   help="open-loop Poisson arrival rate (req/s)")
    p.add_argument("--prompt-len", type=int, default=12)
    p.add_argument("--budget", type=int, default=12,
                   help="max new tokens per request")
    p.add_argument("--slots", type=int, default=2)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--pool-pages", type=int, default=0,
                   help="per-replica arena (0 = slots * pages/seq)")
    p.add_argument("--chunk", type=int, default=4)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="sampled serving (> 0): real replicas sample "
                        "per-row key streams; stub replicas run the "
                        "keyed hash chain — either way the round "
                        "replies carry per-row key state, so a "
                        "replica death resumes SAMPLED streams "
                        "byte-exact on survivors (the router's "
                        "resume checkpoint, docs/serving_plane.md)")
    p.add_argument("--plane-timeout", type=float, default=120.0,
                   help="router drain deadline / replica idle timeout")
    return p


def _roles_for(nreplicas: int, spec: str) -> list[str]:
    roles = [r.strip() for r in spec.split(",") if r.strip()]
    if not roles:
        roles = ["both"]
    for r in roles:
        if r not in ("both", "prefill", "decode"):
            raise ValueError(f"bad role {r!r}")
    while len(roles) < nreplicas:
        roles.append(roles[-1])
    return roles[:nreplicas]


def _schedule(args):
    """The seeded open-loop stream: Poisson arrivals over two priority
    classes (harness/loadgen.py), prompt CONTENT from a separate
    seeded rng — deterministic end to end, so the stub oracle and a
    chaos replay both see the exact same traffic."""
    import numpy as np

    from hpc_patterns_tpu.harness import loadgen

    classes = (
        loadgen.PriorityClass("interactive", 0, weight=0.5,
                              ttft_slo_s=30.0),
        loadgen.PriorityClass("batch", 1, weight=0.5),
    )
    sched = loadgen.make_schedule(
        args.requests, rate_rps=args.rate, classes=classes,
        prompt_lens=(max(1, args.prompt_len // 2), args.prompt_len),
        budgets=(max(1, args.budget // 2), args.budget),
        process="poisson", seed=args.seed)
    rng = np.random.RandomState(args.seed + 13)
    prompts = {r.index: [int(t) for t in rng.randint(0, 64,
                                                     size=r.prompt_len)]
               for r in sched.requests}
    arrivals = [
        (r.t_arrival_s, dict(prompt=prompts[r.index],
                             max_new=r.max_new,
                             priority=r.priority,
                             deadline_s=r.deadline_s))
        for r in sched.requests
    ]
    return sched, prompts, arrivals, classes


def _run_router(args, nprocs: int) -> int:
    from hpc_patterns_tpu.harness import slo as slolib
    from hpc_patterns_tpu.harness.runlog import RunLog
    from hpc_patterns_tpu.serving_plane import service

    sched, prompts, arrivals, classes = _schedule(args)
    handles = service.connect_replicas(
        args.rdv, range(1, nprocs), wait_s=args.plane_timeout,
        timeout_s=args.plane_timeout)
    print(f"router: {len(handles)} replica(s) connected "
          f"(roles {[h.role for h in handles]}, "
          f"policy {args.policy})", flush=True)
    emit = (RunLog(args.log, truncate=False).emit
            if args.log else None)
    if args.fitted is not None:
        # fitted placement (policy + per-replica weights) applies
        # unless the user picked a non-default --policy explicitly
        kw = ({"policy": args.policy}
              if args.policy != "least_loaded" else {})
        router = service.PlaneRouter.from_fitted(
            handles, args.fitted,
            slo_targets=slolib.targets_from_classes(classes),
            emit=emit, **kw)
        print(f"router: autofit placement from {args.autofit} "
              f"(policy {router.policy})", flush=True)
    else:
        router = service.PlaneRouter(
            handles, policy=args.policy,
            slo_targets=slolib.targets_from_classes(classes),
            emit=emit)
    if explain_enabled(args):
        # router-stamped request tracing: one recorder, one clock —
        # the PlaneRouter class contract (serving_plane/service.py)
        from hpc_patterns_tpu.harness import reqtrace as reqtracelib

        reqtracelib.configure(enabled=True)
    report = router.run(arrivals, timeout_s=args.plane_timeout)

    ok = True
    if args.stub:
        # the stub oracle: every served stream must equal the pure
        # token function of its ORIGINAL prompt — resumed-on-survivor
        # rows included (that is the point of the drill). Sampled
        # mode walks the key CHAIN from key_0: a resume is only
        # byte-equal to it when the router's checkpoint carried the
        # chain state across the death
        for rid, toks in sorted(router.finished.items()):
            if router.stats[rid].get("outcome") != "ok":
                continue
            if args.temperature > 0:
                want = service.stub_sampled_stream(prompts[rid],
                                                   len(toks))
            else:
                want = [service.stub_token(prompts[rid], k)
                        for k in range(len(toks))]
            if list(toks) != want:
                print(f"ORACLE FAIL: rid {rid} tokens diverge "
                      f"(got {list(toks)[:6]}.., want {want[:6]}..)",
                      flush=True)
                ok = False
    for rid, rec in sorted(router.stats.items()):
        if rec.get("outcome") == "ok" \
                and rec["tokens"] != sched.requests[rid].max_new:
            print(f"ORACLE FAIL: rid {rid} served {rec['tokens']} "
                  f"!= budget {sched.requests[rid].max_new}",
                  flush=True)
            ok = False
    unresolved = [rid for rid, rec in router.stats.items()
                  if rec.get("outcome") is None]
    if unresolved:
        print(f"ORACLE FAIL: unresolved requests {unresolved}",
              flush=True)
        ok = False

    tot = report["slo"]["total"]
    print(slolib.format_slo(report["slo"]), flush=True)
    if explain_enabled(args):
        from hpc_patterns_tpu.harness import explain as explainlib
        from hpc_patterns_tpu.harness import reqtrace as reqtracelib

        rtr = reqtracelib.active()
        if rtr is not None:
            snap = rtr.snapshot(router.stats)
            if emit is not None:
                emit(kind="reqtrace", **snap)
            dig = explainlib.digest([snap])
            print(explainlib.format_explain(dig), flush=True)
            if args.explain_out:
                import json

                from pathlib import Path

                Path(args.explain_out).write_text(
                    json.dumps(dig) + "\n")
                print(f"explain digest -> {args.explain_out}",
                      flush=True)
    print(f"plane: served {report['served']}/{report['n']} "
          f"shed={report['shed']} deaths={report['deaths']} "
          f"resumed={report['resumed']} "
          f"migrations={report['migrations']} "
          f"goodput_tok_s={tot['goodput_tok_s']:.1f}", flush=True)
    print("PLANE SUCCESS" if ok else "PLANE FAILURE", flush=True)
    return 0 if ok else 1


def _run_replica(args, rank: int, role: str) -> int:
    from hpc_patterns_tpu.harness import trace as tracelib
    from hpc_patterns_tpu.serving_plane import service

    pages_per_seq = -(-(args.prompt_len + args.budget)
                      // args.page_size)
    pool = args.pool_pages or args.slots * pages_per_seq
    if args.stub:
        adapter = service.StubAdapter(
            slots=args.slots, pool_pages=pool,
            pages_per_seq=pages_per_seq, page_size=args.page_size,
            chunk=args.chunk, role=role,
            sampled=args.temperature > 0)
    else:
        import jax

        from hpc_patterns_tpu.models import (
            TransformerConfig,
            init_params,
        )
        from hpc_patterns_tpu.models.serving import (
            EngineCore,
            bucket_ladder,
        )

        cfg = TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=max(64, args.prompt_len + args.budget),
            dtype="float32", decode_attn="gather")
        # identical seed on every replica: request_key(sid) must not
        # depend on placement (the plane's routing-invariance contract)
        params = init_params(jax.random.PRNGKey(0), cfg)
        kw = dict(
            slots=args.slots, pool_pages=pool,
            pages_per_seq=pages_per_seq, page_size=args.page_size,
            chunk=args.chunk, temperature=args.temperature,
            top_k=8 if args.temperature > 0 else 0, seed=0)
        if args.fitted is not None:
            # fitted ladder when present; default ladder otherwise
            engine = EngineCore.from_fitted(
                params, cfg, args.fitted, **kw)
            if engine.prompt_buckets is None:
                engine = EngineCore(
                    params, cfg,
                    prompt_buckets=bucket_ladder(args.prompt_len),
                    **kw)
        else:
            engine = EngineCore(
                params, cfg,
                prompt_buckets=bucket_ladder(args.prompt_len), **kw)
        adapter = service.RealAdapter(engine, role=role)
    return service.serve_replica(
        adapter, rank=rank, rdv_dir=args.rdv,
        timeout_s=args.plane_timeout, rec=tracelib.active())


def run(args) -> int:
    from hpc_patterns_tpu.apps import common
    from hpc_patterns_tpu.harness import RunLog

    if common.refuse_backend(args, RunLog()):
        return 1
    pid = int(os.environ.get("HPCPAT_PROCESS_ID") or 0)
    nprocs = int(os.environ.get("HPCPAT_NUM_PROCESSES") or 1)
    if nprocs < 2:
        print("ERROR: plane_app needs a launcher (-np >= 2: one "
              "router + at least one replica); see docs/serving_plane.md")
        return 2
    # one load point for every rank: the router applies fitted
    # placement, real replicas the fitted ladder (cli.load_autofit)
    args.fitted = None
    if args.autofit:
        from hpc_patterns_tpu.harness.cli import load_autofit

        try:
            args.fitted = load_autofit(args.autofit)
        except (OSError, ValueError) as e:
            print(f"ERROR: bad --autofit {args.autofit}: {e}")
            return 2
    os.makedirs(args.rdv, exist_ok=True)
    roles = _roles_for(nprocs - 1, args.roles)
    t0 = time.perf_counter()
    if pid == 0:
        # replica roles are discovered via the hello handshake; the
        # router only needs to know how many replicas to expect
        rc = _run_router(args, nprocs)
    else:
        rc = _run_replica(args, pid, roles[pid - 1])
    print(f"rank {pid} done in {time.perf_counter() - t0:.2f}s rc={rc}",
          flush=True)
    return rc


def main(argv=None) -> int:
    from hpc_patterns_tpu.apps import common

    args = build_parser().parse_args(argv)
    # ranks talk over the plane's own sockets, not jax.distributed
    return common.run_instrumented(run, args, join_rendezvous=False)


if __name__ == "__main__":
    sys.exit(main())
