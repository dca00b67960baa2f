"""Trainer app: the flagship transformer end-to-end on a mesh.

The framework's full-stack exercise — everything the other apps prove in
isolation, composed: mesh construction (topology), Megatron TP + dp/sp
batch sharding (models/sharding), ring attention over sp (parallel/),
the jitted+donated train step (models/train), min-of-reps timing
(harness), checkpoint/resume (utils/checkpoint).

Self-validating (§4 style): loss must be finite every step and decrease
over the run on the synthetic corpus; with --resume-check, the state is
checkpointed, restored, and one step from each is compared.

Reports steady-state step time and tokens/s (the model-level throughput
headline).
"""

from __future__ import annotations

import sys
import time
from functools import partial

import jax

from hpc_patterns_tpu import topology
from hpc_patterns_tpu.apps import common
from hpc_patterns_tpu.harness import RunLog, Verdict
from hpc_patterns_tpu.harness.cli import base_parser
from hpc_patterns_tpu.models import ATTENTION_IMPLS, TransformerConfig
from hpc_patterns_tpu.models.train import (
    init_train_state,
    make_batch,
    make_train_step,
    record_step_metrics,
)


def build_parser():
    p = base_parser(__doc__.splitlines()[0])
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-kv-heads", type=int, default=0,
                   help="grouped-query attention KV heads (0 = MHA)")
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("--attention", default="full",
                   choices=list(ATTENTION_IMPLS))
    p.add_argument("--remat", action="store_true")
    p.add_argument("--remat-policy", default="split",
                   choices=["nothing", "attn", "dots", "dots_attn", "split"],
                   help="what remat saves (see TransformerConfig; "
                        "'split' = attention outside the remat region, "
                        "the MFU default; 'nothing' = max memory saving "
                        "for long context)")
    p.add_argument("--loss-chunk", type=int, default=0, metavar="C",
                   help="online-logsumexp cross-entropy over vocab "
                        "chunks of C (must divide --vocab): the "
                        "(B,T,V) f32 logits never materialize — the "
                        "long-context memory wall remover (0 = dense)")
    p.add_argument("--pos-embed", default="learned",
                   choices=["learned", "rope"],
                   help="positional scheme: learned table or rotary (RoPE)")
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--dcn-dp", action="store_true",
                   help="multi-slice placement: lay the dp axis ACROSS "
                        "TPU slices (DCN) and all other axes within one "
                        "slice (ICI) via topology.make_hybrid_mesh; "
                        "--dp must equal the slice count (-1 = auto, "
                        "which on a single slice degenerates to dp=1)")
    p.add_argument("--fsdp", type=int, default=1,
                   help="fully-sharded data parallelism (ZeRO-3): params/"
                        "grads/optimizer state shard over this many "
                        "ranks, batch shards over dp*fsdp")
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel stages (1F1B schedule, "
                        "models/pp.py); layers must divide by it")
    p.add_argument("--microbatches", type=int, default=4,
                   help="microbatches per step for --pp (batch must "
                        "divide by microbatches*dp)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel axis (requires --n-experts)")
    p.add_argument("--n-experts", type=int, default=0,
                   help="MoE experts per layer (0 = dense MLP)")
    p.add_argument("--n-experts-top-k", type=int, default=1,
                   help="experts consulted per token (1 = Switch top-1; "
                        "k>=2 = normalized top-k gates, GShard style)")
    p.add_argument("--moe-dispatch", default="auto",
                   choices=["auto", "einsum", "scatter"],
                   help="routing dispatch: one-hot einsum (oracle form) "
                        "or stable-sort scatter (O(N+E*C) memory); auto "
                        "switches to scatter past ~16 MB of one-hots")
    p.add_argument("--mlp-impl", default="dense",
                   choices=["dense", "fused"],
                   help="dense-layer MLP: XLA einsums, or the Pallas "
                        "fused matmul-gelu-matmul kernel (the d_ff "
                        "activation never materializes in HBM)")
    p.add_argument("--drop-rate-every", type=int, default=10, metavar="N",
                   help="sample the MoE routing-drop telemetry every N "
                        "steps (0 = off). The diagnostic is a second "
                        "forward pass — at every step it would cost "
                        "~25-30%% wall clock, so it is sampled")
    p.add_argument("--prefetch", type=int, default=0, metavar="DEPTH",
                   help="stream fresh synthetic batches through the async "
                        "prefetch loader (0 = one static batch)")
    p.add_argument("--data", default=None, metavar="TOKENS.bin",
                   help="raw binary token file (uint16/uint32/int32, "
                        "--data-dtype) streamed via np.memmap instead of "
                        "synthetic batches; implies --prefetch 2 unless set")
    p.add_argument("--data-dtype", default="uint16",
                   choices=["uint16", "uint32", "int32"])
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--schedule", default="constant",
                   choices=["constant", "cosine"])
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--accum", type=int, default=1,
                   help="gradient-accumulation micro-steps per update "
                        "(batch must divide by it)")
    p.add_argument("--offload-opt", action="store_true",
                   help="park optimizer moments in host RAM "
                        "(pinned_host), streamed to HBM per step — "
                        "frees 2x the f32 param footprint of HBM "
                        "(TPU backend only)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--resume-check", action="store_true",
                   help="save+restore mid-run and verify identical losses")
    p.add_argument("--generate", type=int, default=0, metavar="N",
                   help="after training, greedy-decode N tokens from a "
                        "prompt with the trained params (KV-cache decode, "
                        "models/decode.py) and validate them")
    return p


def _make_cli_optimizer(args, log):
    """Build the optimizer from --lr/--schedule/--warmup-steps (shared by
    the sharded and --pp paths). Returns None after logging the app's
    ERROR/FAILURE protocol on invalid schedule parameters."""
    from hpc_patterns_tpu.models.train import make_optimizer

    try:
        return make_optimizer(
            args.lr, schedule=args.schedule,
            warmup_steps=args.warmup_steps, total_steps=args.steps,
        )
    except ValueError as e:
        log.print(f"ERROR: {e}")
        log.print("FAILURE")
        return None


def _train_loop(args, log, cfg, mesh, params, opt_state, step_fn, *,
                name, result_extra):
    """The shared training loop + self-validation: prefetch (optional),
    timed steps, finite/decreasing-loss checks, --resume-check, verdict.
    Both the sharded-train path and the --pp 1F1B path run through here
    so the loss/verdict semantics cannot drift between them."""
    tokens = make_batch(jax.random.PRNGKey(1), cfg, args.batch, args.seq,
                        mesh)

    prefetch = args.prefetch or (2 if args.data else 0)
    if prefetch:
        from hpc_patterns_tpu.models.sharding import batch_sharding
        from hpc_patterns_tpu.utils.data import (
            PrefetchLoader,
            memmap_tokens,
            synthetic_tokens,
        )

        if mesh is not None:
            sharding = batch_sharding(mesh, cfg)
            place = lambda b: jax.device_put(b, sharding)
        else:
            place = jax.device_put
        if args.data:
            source = memmap_tokens(
                args.data, batch=args.batch, seq=args.seq,
                dtype=args.data_dtype, steps=args.steps, vocab=cfg.vocab,
            )
        else:
            source = synthetic_tokens(
                jax.random.PRNGKey(1), batch=args.batch, seq=args.seq,
                vocab=cfg.vocab, steps=args.steps,
            )
        batch_iter = iter(PrefetchLoader(source, depth=prefetch,
                                         place=place))
    else:
        batch_iter = None

    losses = []
    t_steps = []
    ckpt_path = None
    diverged = False
    drop_rates_fn = None
    if cfg.n_experts and args.pp <= 1 and args.drop_rate_every > 0:
        # routing-drop telemetry: built ONCE (a fresh jit wrapper per
        # step would re-trace the whole forward every step)
        from hpc_patterns_tpu.models.transformer import moe_drop_rates

        drop_rates_fn = jax.jit(partial(moe_drop_rates, cfg=cfg, mesh=mesh))
    for i in range(args.steps):
        t0 = time.perf_counter()
        batch = next(batch_iter) if batch_iter is not None else tokens
        loss, params, opt_state = step_fn(params, opt_state, batch)
        loss_val = float(loss)  # blocks: readback is the completion fence
        t_steps.append(time.perf_counter() - t0)
        losses.append(loss_val)
        record_step_metrics(i, loss_val, t_steps[-1],
                            args.batch * args.seq)
        extra = {}
        if drop_rates_fn is not None and i % args.drop_rate_every == 0:
            # capacity drops during training are otherwise invisible
            # (they surface only as quality loss): one diagnostic
            # forward on the sampled step's batch
            drops = drop_rates_fn(params, batch)
            extra["moe_drop_rate"] = round(float(drops.max()), 4)
        log.emit(kind="step", step=i, loss=loss_val, dt_s=t_steps[-1],
                 **extra)
        if loss_val != loss_val or abs(loss_val) == float("inf"):
            # failure detection: a diverged run must halt at the first
            # bad step with a diagnostic, not burn the remaining budget
            # training on garbage (the reference's fail-fast error()
            # style, allreduce-mpi-sycl.cpp:79-86, applied to training)
            log.print(f"ERROR: non-finite loss {loss_val} at step {i} — "
                      f"halting early ({args.steps - 1 - i} steps skipped)")
            diverged = True
            break

    finite = all(l == l and abs(l) != float("inf") for l in losses)
    # a 1-step run has nothing to compare, and with --prefetch each step
    # sees a fresh i.i.d. batch (loss noise can exceed a few steps of
    # progress) — finiteness is the check in those modes
    learned = args.steps < 2 or bool(prefetch) or losses[-1] < losses[0]

    if diverged and (args.resume_check or args.checkpoint_dir
                     or args.generate):
        # never persist or decode from a NaN state: a garbage checkpoint
        # stamped with a step count that never ran would poison later
        # restores, and the verdict below is already FAILURE
        log.print("note: checkpoint/resume/generate legs skipped "
                  "(diverged state)")

    resume_ok = True
    if diverged:
        pass
    elif args.resume_check:
        from hpc_patterns_tpu.utils.checkpoint import (
            restore_checkpoint,
            save_checkpoint,
        )
        import tempfile

        ckdir = args.checkpoint_dir or tempfile.mkdtemp(prefix="hpcpat_ckpt_")
        ckpt_path = save_checkpoint(ckdir, params, opt_state, step=args.steps)
        r_params, r_opt, r_step = restore_checkpoint(ckdir, params, opt_state)
        check_batch = tokens
        loss_a, *_ = step_fn(params, opt_state, check_batch)
        loss_b, *_ = step_fn(r_params, r_opt, check_batch)
        resume_ok = float(loss_a) == float(loss_b) and r_step == args.steps
        log.print(f"resume-check: saved {ckpt_path}, losses "
                  f"{float(loss_a):.6f} vs {float(loss_b):.6f}")
    elif args.checkpoint_dir:
        # --checkpoint-dir alone means "save the trained state" (the
        # README's train -> eval lifecycle), not only the resume test
        from hpc_patterns_tpu.utils.checkpoint import save_checkpoint

        ckpt_path = save_checkpoint(args.checkpoint_dir, params, opt_state,
                                    step=args.steps)
        log.print(f"saved {ckpt_path}")

    generate_ok = True
    if diverged:
        pass
    elif args.generate and name != "train":
        log.print("note: --generate skipped (pp params are stage-local; "
                  "decode serves the unpipelined flagship)")
    elif args.generate:
        # serving leg: greedy KV-cache decode from the trained params
        # (single-controller; sharded params are gathered to host first)
        import jax.numpy as jnp

        from hpc_patterns_tpu.models.decode import greedy_generate

        if jax.process_count() > 1:
            log.print("note: --generate skipped (multi-process run)")
        elif args.seq // 2 + args.generate > cfg.max_seq:
            log.print(f"note: --generate {args.generate} skipped "
                      f"(prompt {args.seq // 2} + N > max_seq {cfg.max_seq})")
        else:
            p_local = jax.device_get(params) if mesh is not None else params
            prompt = jax.device_get(tokens)[:, : args.seq // 2]
            toks = greedy_generate(p_local, jnp.asarray(prompt), cfg,
                                   args.generate)
            arr = jax.device_get(toks)
            generate_ok = (
                arr.shape == (prompt.shape[0], args.generate)
                and int(arr.min()) >= 0 and int(arr.max()) < cfg.vocab
            )
            log.print(f"generate: {arr.shape[0]}x{args.generate} tokens, "
                      f"sample {arr[0, :8].tolist()}")

    ok = finite and learned and resume_ok and generate_ok
    # which devices hold the state and the batch (a mesh run must be
    # spread over all of its devices)
    placement = common.device_placement(params, tokens)
    for p in placement:
        log.print(f"device {p['device']}: params {p['shard_bytes'][0]:,} B"
                  f", batch {p['shard_bytes'][1]:,} B, in use "
                  f"{p['bytes_in_use']}")
    # steady state excludes the compile step
    steady = t_steps[1:] or t_steps
    step_s = min(steady)
    tokens_per_s = args.batch * args.seq / step_s
    log.emit(
        kind="result", name=name, success=ok,
        steps=args.steps, loss_first=losses[0], loss_last=losses[-1],
        step_time_s=step_s, tokens_per_s=tokens_per_s,
        mesh=dict(mesh.shape) if mesh else None,
        placement=placement,
        attention=args.attention, checkpoint=ckpt_path,
        **result_extra,
    )
    label = result_extra.get("label", args.attention)
    log.print(
        f"train[{label}] {args.steps} steps: loss "
        f"{losses[0]:.4f}->{losses[-1]:.4f}, {step_s * 1e3:.1f} ms/step, "
        f"{tokens_per_s:,.0f} tok/s"
    )
    verdict = Verdict(success=ok, messages=("SUCCESS" if ok else "FAILURE",))
    log.print(verdict.summary_line())
    return verdict.exit_code


def _run_pp(args, log, cfg) -> int:
    """--pp path: 1F1B pipeline training (models/pp.py), optionally
    data-parallel (--dp, incl. --dcn-dp across slices), ZeRO-3 stage
    params (--fsdp), Megatron tp inside stages (--tp; dense MLP only),
    host-offloaded optimizer state (--offload-opt), and/or MoE (aux
    loss threaded through the schedule; no sp/ep axes inside stages)."""
    from hpc_patterns_tpu.models import pp as pplib

    if args.sp > 1 or args.ep > 1:
        log.print("ERROR: --pp composes with --dp/--tp/--fsdp/--dcn-dp/"
                  "--offload-opt and --n-experts only (no sp/ep axes "
                  "inside pipeline stages — MoE experts route densely "
                  "per stage)")
        log.print("FAILURE")
        return 1
    tp = args.tp if args.tp > 1 else 1
    if tp > 1:
        try:
            pplib.check_tp(cfg, tp)
        except ValueError as e:
            log.print(f"ERROR: --pp --tp: {e}")
            log.print("FAILURE")
            return 1
    if args.attention not in ("full", "flash"):
        log.print("ERROR: --pp needs a stage-local attention "
                  "(--attention full or flash)")
        log.print("FAILURE")
        return 1
    if args.microbatches < 1:
        log.print(f"ERROR: --microbatches must be >= 1, "
                  f"got {args.microbatches}")
        log.print("FAILURE")
        return 1
    if args.n_layers % args.pp:
        log.print(f"ERROR: --n-layers {args.n_layers} must divide by "
                  f"--pp {args.pp}")
        log.print("FAILURE")
        return 1

    devices = topology.get_devices(args.backend)
    fs = args.fsdp if args.fsdp > 1 else 1
    if args.dcn_dp:
        # dp ACROSS slices: the once-per-step gradient pmean is the
        # latency-tolerant collective; fsdp gathers and the per-tick
        # stage ppermutes stay slice-internal (pp innermost = fastest
        # ICI neighbors)
        groups = topology.group_by_slice(devices)
        n_slices = len(groups)
        dp = n_slices if args.dp == -1 else args.dp
        if dp != n_slices:
            log.print(f"ERROR: --dcn-dp places dp across slices: --dp "
                      f"{args.dp} != slice count {n_slices} (use -1 for "
                      "auto)")
            log.print("FAILURE")
            return 1
        ici = ({"fsdp": fs} if fs > 1 else {}) | {"pp": args.pp}
        if tp > 1:
            ici["tp"] = tp  # innermost: tp rides nearest ICI neighbors
        picked = [d for s in sorted(groups)
                  for d in groups[s][:fs * args.pp * tp]]
        try:
            mesh = topology.make_hybrid_mesh({"dp": dp}, ici, picked)
        except topology.TopologyError as e:
            log.print(f"ERROR: --dcn-dp: {e}")
            log.print("FAILURE")
            return 1
    else:
        dp = args.dp
        axes = {}
        if dp > 1:
            axes["dp"] = dp
        if fs > 1:
            axes["fsdp"] = fs
        axes["pp"] = args.pp
        if tp > 1:
            axes["tp"] = tp  # innermost: tp rides nearest ICI neighbors
        mesh = topology.make_mesh(
            axes, devices[:max(dp, 1) * fs * args.pp * tp])
    if common.refuse_backend(args, log, mesh.devices.flat):
        return 1
    if args.batch % (args.microbatches * max(dp, 1) * fs):
        log.print(f"ERROR: --batch {args.batch} must divide by "
                  f"--microbatches*--dp*--fsdp = "
                  f"{args.microbatches * max(dp, 1) * fs}")
        log.print("FAILURE")
        return 1
    optimizer = _make_cli_optimizer(args, log)
    if optimizer is None:
        return 1
    axis_fsdp = "fsdp" if fs > 1 else None
    params, opt_state = pplib.init_pp_train_state(
        jax.random.PRNGKey(0), cfg, optimizer=optimizer,
        mesh=mesh if axis_fsdp else None, axis_fsdp=axis_fsdp,
    )
    offload_example = None
    if args.offload_opt:
        # same platform gating as the sharded-train path: host-memory
        # compute annotations are TPU-only
        if mesh.devices.flat[0].platform != "tpu":
            log.print("note: --offload-opt needs a TPU backend "
                      "(host-memory compute annotations); ignoring")
        else:
            from hpc_patterns_tpu.models.train import offload_opt_state

            hosted = offload_opt_state(opt_state)
            if hosted is opt_state:
                # probe-gated identity fallback: say so instead of
                # logging an offload that did not happen
                log.print("note: pinned_host unusable on this "
                          "backend; optimizer state left in place")
            else:
                opt_state = hosted
                offload_example = opt_state
                log.print("optimizer state offloaded to pinned_host")
    step_fn = pplib.make_pp_train_step(
        cfg, mesh, microbatches=args.microbatches,
        axis_dp="dp" if dp > 1 else None, axis_fsdp=axis_fsdp,
        axis_tp="tp" if tp > 1 else None,
        optimizer=optimizer, offload_opt_example=offload_example,
    )
    label = f"pp={args.pp} 1f1b"
    if tp > 1:
        label += f" tp={tp}"
    if fs > 1:
        label += f" fsdp={fs}"
    if args.dcn_dp:
        label += f" dcn-dp={dp}"
    return _train_loop(
        args, log, cfg, mesh, params, opt_state, step_fn, name="train_pp",
        result_extra={"microbatches": args.microbatches, "label": label},
    )


def run(args) -> int:
    log = RunLog(args.log, truncate=not args.log_append)
    # under a launcher (apps/launch.py ≙ mpirun) run_instrumented has
    # joined the rendezvous: the mesh below is then global and the
    # train step is true multi-process SPMD — the multi-host training
    # path, minus hardware
    if args.prefetch < 0:
        log.print(f"ERROR: --prefetch must be >= 0, got {args.prefetch}")
        log.print("FAILURE")
        return 1
    if args.steps < 1:
        log.print(f"ERROR: --steps must be >= 1, got {args.steps}")
        log.print("FAILURE")
        return 1
    if args.accum > 1 and args.pp > 1:
        log.print("ERROR: --accum composes with the sharded-train path; "
                  "--pp already micro-batches via --microbatches")
        log.print("FAILURE")
        return 1
    if args.remat_policy != "split" and not args.remat:
        log.print("ERROR: --remat-policy has no effect without --remat "
                  "(no checkpointing happens; all activations are saved)")
        log.print("FAILURE")
        return 1
    if args.accum > 1 and args.batch % args.accum:
        log.print(f"ERROR: --batch {args.batch} must divide by "
                  f"--accum {args.accum}")
        log.print("FAILURE")
        return 1
    if args.ep > 1 and not args.n_experts:
        log.print("ERROR: --ep requires --n-experts")
        log.print("FAILURE")
        return 1
    if args.n_experts and args.n_experts % max(args.ep, 1):
        log.print(f"ERROR: --n-experts {args.n_experts} must divide by "
                  f"--ep {args.ep}")
        log.print("FAILURE")
        return 1
    try:
        cfg = TransformerConfig(
            vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
            n_layers=args.n_layers, d_ff=4 * args.d_model, max_seq=args.seq,
            attention=args.attention, remat=args.remat, n_experts=args.n_experts,
            n_experts_top_k=args.n_experts_top_k,
            moe_dispatch=args.moe_dispatch,
            n_kv_heads=args.n_kv_heads, pos_embed=args.pos_embed,
            fsdp=args.fsdp > 1, remat_policy=args.remat_policy,
            loss_chunk=args.loss_chunk,
            mlp_impl=args.mlp_impl,
        )
    except ValueError as e:
        log.print(f"ERROR: {e}")
        log.print("FAILURE")
        return 1
    if args.pp > 1:
        return _run_pp(args, log, cfg)
    if args.attention == "flash" and args.sp > 1:
        log.print("ERROR: attention='flash' needs the sequence unsharded "
                  "(--sp 1); use ring_flash for a sharded sequence")
        log.print("FAILURE")
        return 1
    mesh = None
    if args.dcn_dp:
        # multi-slice placement: dp ACROSS slices (the gradient psum is
        # the latency-tolerant, once-per-step collective), every other
        # axis inside one slice so tp/sp/fsdp collectives ride ICI.
        # Devices must be taken per slice, never as a flat prefix.
        devices = topology.get_devices(args.backend)
        groups = topology.group_by_slice(devices)
        n_slices = len(groups)
        dp = n_slices if args.dp == -1 else args.dp
        if dp != n_slices:
            log.print(f"ERROR: --dcn-dp places dp across slices: --dp "
                      f"{args.dp} != slice count {n_slices} (use -1 for "
                      "auto)")
            log.print("FAILURE")
            return 1
        ici = {"sp": args.sp, "tp": args.tp}
        if args.fsdp > 1:
            ici = {"fsdp": args.fsdp, **ici}
        if args.ep > 1:
            ici["ep"] = args.ep
        ici_size = args.sp * args.tp * args.ep * args.fsdp
        picked = [d for s in sorted(groups)
                  for d in groups[s][:ici_size]]
        try:
            mesh = topology.make_hybrid_mesh({"dp": dp}, ici, picked)
        except topology.TopologyError as e:
            log.print(f"ERROR: --dcn-dp: {e}")
            log.print("FAILURE")
            return 1
    else:
        n_mesh = args.dp * args.sp * args.tp * args.ep * args.fsdp
        # every impl except the two single-path ones needs a mesh
        use_mesh = n_mesh > 1 or args.attention not in ("full", "flash")
        if use_mesh:
            devices = topology.get_devices(args.backend)
            axes = {"dp": args.dp, "sp": args.sp, "tp": args.tp}
            if args.fsdp > 1:
                # fsdp between dp and sp: param all-gathers ride links
                # as close as possible without stealing tp/sp's fastest
                axes = {"dp": args.dp, "fsdp": args.fsdp, "sp": args.sp,
                        "tp": args.tp}
            if args.ep > 1:
                axes["ep"] = args.ep
            mesh = topology.make_mesh(axes, devices[:n_mesh])
    # a meshless run places nothing and lands on the default device
    if common.refuse_backend(
            args, log, mesh.devices.flat if mesh is not None else None):
        return 1

    optimizer = _make_cli_optimizer(args, log)
    if optimizer is None:
        return 1
    params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, mesh,
                                         optimizer=optimizer)
    offload_example = None
    if args.offload_opt:
        # the platform of the devices the state actually lives on (a
        # --backend cpu mesh on a TPU host must NOT offload)
        platform = (mesh.devices.flat[0].platform if mesh is not None
                    else jax.default_backend())
        if platform != "tpu":
            log.print("note: --offload-opt needs a TPU backend "
                      "(host-memory compute annotations); ignoring")
        else:
            from hpc_patterns_tpu.models.train import offload_opt_state

            hosted = offload_opt_state(opt_state)
            if hosted is opt_state:
                # probe-gated identity fallback: say so instead of
                # logging an offload that did not happen
                log.print("note: pinned_host unusable on this "
                          "backend; optimizer state left in place")
            else:
                opt_state = hosted
                offload_example = opt_state
                log.print("optimizer state offloaded to pinned_host")
    step_fn = make_train_step(cfg, mesh, optimizer=optimizer,
                              accum_steps=args.accum,
                              offload_opt_example=offload_example)
    return _train_loop(
        args, log, cfg, mesh, params, opt_state, step_fn, name="train",
        result_extra={},
    )


def main(argv=None) -> int:
    return common.run_instrumented(run, build_parser().parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
