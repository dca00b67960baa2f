"""The in-process serving plane: router + N engine replicas.

One process, N :class:`~hpc_patterns_tpu.models.serving.EngineCore`
replicas (optionally pinned to distinct devices), one front-end
:class:`ServingPlane` routing an open-loop request stream across them.
This is the plane's ORACLE tier: everything runs where the tests can
see it, on the 8-device CPU mesh, and the disaggregation claim — a
request routed prefill → KV-migration → decode emits byte-identical
tokens to the same request on a colocated single engine, greedy and
sampled — is asserted here (tests/test_serving_plane.py) before the
cross-process plane (``serving_plane/service.py``) is believed.

Placement policies (``policy=``):

- ``least_loaded``  — the replica with the most free pages (ties:
  shallowest queue, then submission order) among those that can EVER
  fit the request;
- ``round_robin``   — cycle through the eligible replicas;
- ``prefill_decode``— role-aware: fresh requests go to prefill-role
  replicas (least-loaded among them); decode-role replicas receive
  work only through KV migration. This IS the disaggregated mode —
  constructing a plane with any ``role="prefill"`` replica selects it
  implicitly.

The migration pipeline per plane round (the overlap discipline):

1. each prefill replica runs an admission-only round
   (``service_round(decode=False)``): bucket-padded prefill + first
   token, no decode chunk ever;
2. rows whose first token resolved are EXPORTED and their transfer is
   DISPATCHED toward the chosen decode replica over the plane's
   transport tier (``migration=`` kwarg, see MIGRATION_TRANSPORTS:
   the fused remote-DMA pair of ``comm/migration_dma.py``, the
   ``migration.migrate_pages`` async ``device_put``, or the socket
   codec's byte round-trip), before that replica's decode chunk of
   the round;
3. the decode replica's round dispatches its chunk (after its own
   admissions, if it has any), then installs arrived bundles BEHIND
   it (``service_round``'s ``pre_collect`` hook →
   ``install_migration``) — the handoff hides behind compute;
4. after the chunk readback the install is confirmed
   (``block_until_ready`` on the seeded cursors — completion
   measurement, the ``_ready_in_span`` contract) and the migration
   window closes.

Every migration is fingerprinted into the collective-schedule chain
(``kv_migration`` with the plane-assigned ``seq``) and drawn as a
device-track window named ``plane.kv_migration`` — under ``--trace``
the cross-rank merge threads flow arrows through matched windows and
the schedule verifier catches router/replica desyncs (in-process both
ends share one chain; the launched plane records one chain per side).

``kv_migration_overlap_frac``: Σ over migrations of the window time
spent under an in-flight decode chunk on the DESTINATION replica,
over Σ window time — the measured proof that the handoff hid behind
compute.
``dma_migration_overlap_frac`` is the same ratio restricted to
bundles that actually rode the DMA tier (None when none did — a
fallback can't impersonate the kernel path), and
``migration_bytes_per_round`` pins the dataplane pressure the tier
carries (tests/test_serving_plane.py pins all three).
"""

from __future__ import annotations

import os
import time
import warnings
from collections import Counter, deque
from contextlib import nullcontext

import numpy as np

from hpc_patterns_tpu.analysis import runtime as analysis_runtime
from hpc_patterns_tpu.comm import migration_dma
from hpc_patterns_tpu.harness import chaos as chaoslib
from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness import reqtrace as reqtracelib
from hpc_patterns_tpu.harness import slo as slolib
from hpc_patterns_tpu.harness import trace as tracelib
from hpc_patterns_tpu.models.serving import EngineCore, fit_bucket_ladder
from hpc_patterns_tpu.serving_plane.migration import (
    bundle_from_wire,
    bundle_to_wire,
    migrate_pages,
)
from hpc_patterns_tpu.serving_plane.service import migration_track

ROLES = ("both", "prefill", "decode")

#: KV-handoff transport tiers, fastest first — the fallback ladder
#: :meth:`ServingPlane._resolve_transport` walks LOUDLY (a warning +
#: a ``plane_transport_fallback`` emit per distinct reason):
#: ``dma`` = the paired remote-DMA kernel (comm/migration_dma.py,
#: chips must be ICI-reachable), ``device_put`` = host-staged
#: cross-device copy (today's default; a device-less pair degrades
#: further to the in-place passthrough, recorded as ``local``),
#: ``wire`` = the socket codec's byte round-trip (the DCN analog).
MIGRATION_TRANSPORTS = ("dma", "device_put", "wire")


class Replica:
    """One engine replica in the plane. ``role``: ``"both"`` (admit +
    decode — the homogeneous plane), ``"prefill"`` (admission-prefill
    only; every row leaves via KV migration), or ``"decode"``
    (receives work only through migration — plus resumes the router
    re-queues onto it). ``device``: pin the engine's dispatches to one
    device (``jax.default_device`` around every engine call), so
    replicas model distinct chips and migration is a real
    cross-device copy; None = wherever the engine's arrays live."""

    def __init__(self, engine: EngineCore, *, name: str | None = None,
                 role: str = "both", device=None):
        if role not in ROLES:
            raise ValueError(f"role {role!r} not in {ROLES}")
        if engine.draft_params is not None and role != "both":
            raise ValueError(
                "draft-assisted engines cannot take a migration role "
                "(the draft cache's row state does not migrate)")
        self.engine = engine
        self.role = role
        self.device = device
        self.name = name or role
        self.alive = True
        #: bundles transferred toward this replica, awaiting install
        self.pending_migrations: list = []
        #: plane-assigned ordinal (set at plane construction / scale-up)
        #: — the identity ``die:replica=N`` chaos addresses in-process,
        #: mirroring the launched plane where replica N is rank N
        self.index = -1
        #: replica-local round counter: the chaos ``replica_round``
        #: site's index, and the autoscaler's per-replica clock
        self.rounds = 0
        #: a draining replica serves what it holds but receives no new
        #: routing and no migrations — the voluntary scale-down state
        self.draining = False

    def device_ctx(self):
        if self.device is None:
            return nullcontext()
        import jax

        return jax.default_device(self.device)

    @property
    def can_prefill(self) -> bool:
        return self.role in ("both", "prefill")

    @property
    def can_decode(self) -> bool:
        return self.role in ("both", "decode")


def _eligible(plane: "ServingPlane", prompt_len: int,
              max_new: int) -> list[Replica]:
    return [r for r in plane.replicas
            if r.alive and not r.draining and r.can_prefill
            and r.engine.would_fit(prompt_len, max_new)]


def _least_loaded(plane, prompt_len, max_new):
    cand = _eligible(plane, prompt_len, max_new)
    if not cand:
        return None
    return max(cand, key=lambda r: (r.engine.free_page_count,
                                    -r.engine.queue_depth,
                                    -plane.replicas.index(r)))


def _round_robin(plane, prompt_len, max_new):
    cand = _eligible(plane, prompt_len, max_new)
    if not cand:
        return None
    r = cand[plane._rr % len(cand)]
    plane._rr += 1
    return r


def _weighted(plane, prompt_len, max_new):
    # autofit's fitted capacity shares: route toward the replica with
    # the most fitted weight per unit of present pressure. A replica
    # the fit never saw gets weight 1.0 (neutral), so a fresh spin-up
    # is routable immediately.
    cand = _eligible(plane, prompt_len, max_new)
    if not cand:
        return None
    return max(cand, key=lambda r: (
        plane.placement_weights.get(r.name, 1.0)
        / (1.0 + r.engine.queue_depth),
        r.engine.free_page_count,
        -plane.replicas.index(r)))


PLACEMENT_POLICIES = {
    "least_loaded": _least_loaded,
    "round_robin": _round_robin,
    # role-awareness is structural: _eligible already restricts to
    # prefill-capable replicas, so in a disaggregated plane the
    # least-loaded pick IS the prefill-decode policy
    "prefill_decode": _least_loaded,
    # per-replica weights fitted from a prior run's busy/queue rollups
    # (harness/autofit.py) — plane.placement_weights holds them
    "weighted": _weighted,
}


class ServingPlane:
    """Route a request stream across N replicas (see module docstring).

    ``slo``: ``{priority: harness.slo.SLOTarget}`` — after each
    :meth:`run`, ``last_slo`` holds the PLANE-level attainment rollup
    (goodput next to raw tok/s over the router's own stats table,
    which spans replicas — a migrated request is judged once, end to
    end). Per-replica queue depth / free pages land as
    ``plane.<name>.queue_depth`` / ``.free_pages`` gauges each round.
    """

    def __init__(self, replicas, *, policy: str = "least_loaded",
                 slo: dict | None = None, emit=None,
                 placement_weights: dict | None = None,
                 migration: str = "device_put"):
        self.replicas = list(replicas)
        if not self.replicas:
            raise ValueError("need at least one replica")
        names = [r.name for r in self.replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"replica names must be unique, got {names}")
        if policy not in PLACEMENT_POLICIES:
            raise ValueError(
                f"unknown policy {policy!r} "
                f"(known: {', '.join(sorted(PLACEMENT_POLICIES))})")
        self.policy_name = policy
        self.policy = PLACEMENT_POLICIES[policy]
        #: fitted per-replica capacity shares ({name: weight}), read by
        #: the "weighted" policy; empty = neutral
        self.placement_weights = {
            str(k): float(v)
            for k, v in (placement_weights or {}).items()}
        self.disaggregated = any(r.role != "both" for r in self.replicas)
        if self.disaggregated:
            if not any(r.can_prefill for r in self.replicas):
                raise ValueError("disaggregated plane has no "
                                 "prefill-capable replica")
            if not any(r.can_decode for r in self.replicas):
                raise ValueError("disaggregated plane has no "
                                 "decode-capable replica")
        self._validate_engines()
        # decode-role replicas track chunk windows: the migration-
        # overlap fraction is measured against them
        for r in self.replicas:
            if r.can_decode:
                r.engine.track_chunk_windows = True
        for i, r in enumerate(self.replicas):
            r.index = i
        self.slo = slo
        self._emit = emit or (lambda **kw: None)
        self.stats: dict[int, dict] = {}
        self.finished: dict[int, np.ndarray] = {}
        self._next_rid = 0
        self._assignment: dict[int, Replica] = {}
        self._rr = 0
        self._mig_seq = 0
        self.migrations = 0
        if migration not in MIGRATION_TRANSPORTS:
            raise ValueError(
                f"unknown migration transport {migration!r} "
                f"(known: {', '.join(MIGRATION_TRANSPORTS)})")
        #: requested KV-handoff transport tier (module constant
        #: MIGRATION_TRANSPORTS); per-bundle resolution may fall back
        #: down the ladder — loudly — when a pair can't serve it
        self.migration = migration
        #: bundles dispatched per RESOLVED transport ("dma" /
        #: "device_put" / "local" / "wire") — what the oracle tests
        #: assert so a silent fallback can't impersonate the DMA tier
        self.migration_transports: Counter = Counter()
        #: distinct (requested, actual, reason) fallbacks already
        #: warned about — loud once, not once per bundle
        self._transport_warned: set = set()
        #: Σ payload bytes over dispatched bundles (all transports) —
        #: the numerator of ``migration_bytes_per_round``
        self.migration_bytes = 0
        #: open migration windows: seq -> (t_trace_dispatch, t_host0)
        self._mig_open: dict[int, tuple[float, float]] = {}
        self._mig_overlap_s = 0.0
        self._mig_total_s = 0.0
        # the DMA tier's own overlap ledger (subset of the above):
        # ``dma_migration_overlap_frac`` gates on it, so a plane that
        # silently fell back to device_put reports None, not a number
        # measured on the wrong transport
        self._dma_overlap_s = 0.0
        self._dma_total_s = 0.0
        self._serve_s = 0.0
        #: total plane rounds served (unconditional — unlike
        #: ``_plane_rounds``, which only counts SLO-judged rounds):
        #: the denominator of ``migration_bytes_per_round``
        self.rounds_total = 0
        self.last_slo: dict | None = None
        self.last_kv_migration_overlap_frac: float | None = None
        self.last_dma_migration_overlap_frac: float | None = None
        #: original submit kwargs per request — what replica-death
        #: recovery needs (the elastic plane rebuilds a queued request
        #: or a resume from them; the static plane's shed path only
        #: reads them for accounting)
        self._requests: dict[int, dict] = {}
        #: replicas lost to chaos (by name, death order)
        self.deaths: list[str] = []
        #: requests shed BECAUSE their replica died (the static
        #: plane's degraded mode — the number the elastic comparison
        #: exists to drive to zero)
        self.shed_on_death = 0
        #: Σ over plane rounds of live (serving) replica count — the
        #: denominator of ``goodput_per_replica_round``: the
        #: efficiency metric that rewards holding the SLO with FEWER
        #: replica-rounds, not just holding it
        self.replica_rounds = 0
        #: the sliding-window SLO-attainment signal (satellite of the
        #: autofit round): every request judged as it RESOLVES, the
        #: window fraction emitted per plane round as a gauge, a trace
        #: counter, and a ``kind=plane_attainment`` record — the one
        #: signal the in-process autoscaler, the launched router, and
        #: the offline autofit threshold fitter all consume
        self.attain_window = slolib.AttainmentWindow()
        self._plane_rounds = 0
        self._attain_emitted = (0, 0)  # (judged, attained) last round

    # -- construction checks ----------------------------------------------

    def _validate_engines(self) -> None:
        """Replicas must agree on everything a request's tokens depend
        on, or routing would change outputs: sampling mode (greedy /
        top_k are compile-time constants of the chunk step), eos, the
        per-request key derivation (same seed => same request_key on
        every replica AND on the colocated oracle), and — for planes
        that migrate — the page/pool layout."""
        e0 = self.replicas[0].engine
        for r in self.replicas[1:]:
            e = r.engine
            for attr in ("greedy", "top_k", "temperature", "eos_id"):
                if getattr(e, attr) != getattr(e0, attr):
                    raise ValueError(
                        f"replica {r.name!r} disagrees on {attr}: "
                        f"{getattr(e, attr)} vs {getattr(e0, attr)} — "
                        "routing would change outputs")
            if not e0.greedy and not np.array_equal(
                    np.asarray(e._req_key_base),
                    np.asarray(e0._req_key_base)):
                raise ValueError(
                    f"replica {r.name!r} was built with a different "
                    "seed: request_key(sid) would differ by placement")
        if self.disaggregated:
            for r in self.replicas:
                e = r.engine
                if e.page_size != e0.page_size or e.cfg != e0.cfg:
                    raise ValueError(
                        f"replica {r.name!r}: migration needs identical "
                        "model config and page_size across replicas")

    @classmethod
    def from_fitted(cls, replicas, fitted, *, slo: dict | None = None,
                    emit=None, **kw):
        """Build a plane from an autofit ``FittedConfig``: the fitted
        ``placement`` section picks the policy (``weighted`` routes by
        the fitted per-replica capacity shares) — a config with no
        placement signal yields the default least-loaded plane. An
        explicit ``policy=`` kwarg wins over the fit."""
        from hpc_patterns_tpu.harness import autofit as autofitlib

        fitted = autofitlib.validate_fitted(fitted)
        section = fitted.get("placement") or {}
        if "policy" not in kw and section.get("policy"):
            kw["policy"] = section["policy"]
        if "placement_weights" not in kw and section.get("weights"):
            kw["placement_weights"] = section["weights"]
        return cls(replicas, slo=slo, emit=emit, **kw)

    # -- submission (the router transport) ---------------------------------

    @staticmethod
    def fit_buckets(lengths, max_rungs: int, *, max_len=None):
        """Ladder autotuning hook: fit the prompt-length bucket ladder
        to an observed/loadgen length sample before building replica
        engines (``serving.fit_bucket_ladder``)."""
        return fit_bucket_ladder(lengths, max_rungs, max_len=max_len)

    def submit(self, prompt, max_new: int, *, priority: int = 0,
               deadline_s: float | None = None,
               temperature: float | None = None, key=None,
               resume_prefix=None) -> int:
        """Route one request: the placement policy picks a replica NOW
        (load is what the policy reads), the request enters that
        replica's queue under a plane-global id, and the plane's stats
        row opens. Raises when no live replica could ever fit it."""
        prompt = np.asarray(prompt, np.int32)
        rid = self._next_rid
        self._next_rid += 1
        target = self.policy(self, int(prompt.size), int(max_new))
        if target is None:
            raise ValueError(
                f"no live replica can serve prompt {prompt.size} + "
                f"budget {max_new} (table width / ladder / max_seq)")
        if target.role == "prefill":
            # the row will LEAVE via migration: some decode-capable
            # replica must be able to hold the donor's pages, or the
            # request would park on the prefill replica forever and
            # surface later as a mid-stream plane deadlock instead of
            # a submit-time rejection
            need = target.engine._pages_for(int(prompt.size),
                                            int(max_new))
            if not any(r.alive and r.can_decode
                       and need <= min(r.engine.pages_per_seq,
                                       r.engine.pool_pages)
                       for r in self.replicas):
                raise ValueError(
                    f"no decode-capable replica can hold the "
                    f"{need}-page migrated row of prompt "
                    f"{prompt.size} + budget {max_new}")
        target.engine.submit(
            prompt, max_new, seq_id=rid, priority=priority,
            deadline_s=deadline_s, temperature=temperature, key=key,
            resume_prefix=resume_prefix)
        self._requests[rid] = {
            "prompt": prompt, "max_new": int(max_new),
            "priority": int(priority), "deadline_s": deadline_s,
            "temperature": temperature, "key": key,
        }
        now = time.perf_counter()
        self.stats[rid] = {
            "priority": int(priority), "t_submit": now, "t_first": None,
            "t_finish": None, "tokens": 0, "outcome": None,
            "preemptions": 0, "replica": target.name,
        }
        self._assignment[rid] = target
        self._emit(kind="plane_route", seq_id=rid, replica=target.name,
                   policy=self.policy_name, prompt_len=int(prompt.size),
                   budget=int(max_new), priority=int(priority))
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("plane.routed").inc()
            m.gauge(f"plane.{target.name}.queue_depth").set(
                target.engine.queue_depth)
        return rid

    # -- migration pipeline ------------------------------------------------

    def _reserved_pages(self, r: Replica) -> int:
        return sum(b.n_pages for b in r.pending_migrations)

    def _pick_target(self, n_pages: int, src: Replica) -> Replica | None:
        """The decode replica this bundle should land on: alive,
        decode-capable, not the donor, with capacity left AFTER the
        bundles already in flight toward it (reservations — two
        exports must not race one free slot). Least-loaded first."""
        cand = []
        for r in self.replicas:
            if not (r.alive and r.can_decode) or r is src \
                    or r.draining:
                continue
            e = r.engine
            free_slots = (sum(1 for s in e._slots if not s.active)
                          - len(r.pending_migrations))
            if free_slots < 1:
                continue
            if (self._reserved_pages(r) + n_pages > e.free_page_count
                    or n_pages > e.pages_per_seq):
                continue
            cand.append(r)
        if not cand:
            return None
        return max(cand, key=lambda r: (
            r.engine.free_page_count - self._reserved_pages(r),
            -r.engine.queue_depth))

    def _export_ready(self, src: Replica) -> int:
        """Export every migration-ready row of a prefill replica whose
        transfer has a destination with capacity, and DISPATCH the
        transfer immediately — before the destination's decode chunk
        of this round, so the copy flies under the chunk. A row with
        no destination stays parked on the donor (its pages keep their
        state; nothing is dropped)."""
        n = 0
        for slot in src.engine.exportable_slots():
            need = len(src.engine._slots[slot].pages)
            dst = self._pick_target(need, src)
            if dst is None:
                # no capacity for THIS row yet — smaller rows behind
                # it may still fit somewhere; a head-of-line break
                # here would starve them behind one big parked row
                continue
            self._dispatch_migration(src, slot, dst)
            n += 1
        return n

    def _transport_fallback(self, requested: str, actual: str,
                            reason: str) -> None:
        """The LOUD half of the fallback ladder: a warning (once per
        distinct reason), an emit record, and a counter — a plane
        asked for DMA must never quietly serve on a slower tier."""
        key = (requested, actual, reason)
        if key not in self._transport_warned:
            self._transport_warned.add(key)
            warnings.warn(
                f"plane migration transport fell back "
                f"{requested} -> {actual}: {reason}",
                RuntimeWarning, stacklevel=3)
        self._emit(kind="plane_transport_fallback", requested=requested,
                   actual=actual, reason=reason)
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("plane.transport_fallbacks").inc()

    def _resolve_transport(self, src: Replica,
                           dst: Replica) -> tuple[str, str]:
        """(transport to attempt, fallback reason so far) for one
        (src, dst) pair under the plane's requested tier. ``dma``
        demands an ICI-reachable device pair
        (:func:`migration_dma.dma_reachable`); the per-bundle VMEM
        gate inside ``send_migration`` may still drop an oversized
        slab to ``device_put`` at dispatch time."""
        if self.migration == "dma":
            ok, reason = migration_dma.dma_reachable(src.device,
                                                     dst.device)
            if ok:
                return "dma", ""
            self._transport_fallback("dma", "device_put", reason)
            return "device_put", reason
        return self.migration, ""

    def _dispatch_migration(self, src: Replica, slot: int,
                            dst: Replica) -> None:
        """Export + transfer dispatch (dispatch-only: the gather and
        the cross-device copy enqueue async; the deliberate cursor
        snapshot inside export_migration is the chunk-boundary resume
        contract). Opens the migration's device-track window and
        fingerprints it into the schedule chain — with the RESOLVED
        transport as the entry's ``algorithm``, so a fallback is
        visible in the verifier's chain, not just the logs."""
        bundle = src.engine.export_migration(slot)
        bundle.seq = self._mig_seq
        self._mig_seq += 1
        rtr = reqtracelib.active()
        if rtr is not None:
            # the engine opened `migrating` at export; the router owns
            # the plane seq — tag the open segment so the cross-rank
            # merge can thread the request lane into THIS migration's
            # device window (harness/collect.py flow arrows)
            rtr.annotate_open(bundle.seq_id, seq=bundle.seq)
        self.migration_bytes += sum(
            int(a.nbytes) for arrs in bundle.pages_payload.values()
            for a in arrs)
        transport, _ = self._resolve_transport(src, dst)
        if transport == "dma":
            try:
                bundle = migration_dma.send_migration(
                    bundle, src.device, dst.device)
            except migration_dma.MigrationDmaError as e:
                self._transport_fallback("dma", "device_put", str(e))
                transport = "device_put"
        if transport == "device_put":
            # dst.device None degrades further to the in-place
            # passthrough; the bundle then says "local" truthfully
            bundle = migrate_pages(bundle, dst.device)
        elif transport == "wire":
            # the byte codec round-trip IS the transport: the installed
            # payload crossed the same encode/decode the socket plane
            # ships, so the oracle covers the codec end to end
            w = bundle_to_wire(bundle)
            w["transport"] = "wire"
            bundle = bundle_from_wire(w)
        self.migration_transports[bundle.transport] += 1
        ps = self.stats.get(bundle.seq_id)
        if ps is not None and ps["t_first"] is None:
            ps["t_first"] = bundle.t_first
        rec = tracelib.active()
        t_disp = 0.0
        if rec is not None:
            t_disp = rec.mark_dispatch(
                "plane.kv_migration",
                {"seq": bundle.seq, "src": src.name, "dst": dst.name,
                 "pages": bundle.n_pages, "seq_id": bundle.seq_id},
                track=migration_track(bundle.seq))
        if rec is not None \
                or analysis_runtime.ENV_TRACE_DIR in os.environ:
            kdt = str(bundle.pages_payload["k"][0].dtype)
            analysis_runtime.record_collective(
                "kv_migration", bundle.seq,
                shape=(bundle.n_pages, bundle.page_size), dtype=kdt,
                axis="plane", algorithm=bundle.transport)
        self._mig_open[bundle.seq] = (t_disp, time.perf_counter())
        dst.pending_migrations.append(bundle)
        self._emit(kind="plane_migrate", seq=bundle.seq,
                   seq_id=bundle.seq_id, src=src.name, dst=dst.name,
                   pages=bundle.n_pages)
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("plane.migrations").inc()

    def _install_pending(self, r: Replica, overlapped: bool) -> list:
        """The decode side of the handoff, run from ``service_round``'s
        ``pre_collect`` hook — BEHIND the in-flight chunk when there is
        one (``overlapped``). Installs every arrived bundle the engine
        can take, in arrival order."""
        installed = []
        while r.pending_migrations and r.engine.migration_admissible(
                r.pending_migrations[0].n_pages):
            b = r.pending_migrations.pop(0)
            if b.transport == "dma":
                # metadata-only landing check (device residency /
                # chunk-shape sanity) — raises MigrationDmaError
                # rather than scattering a misdelivered payload
                migration_dma.recv_migration(b, r.device)
            r.engine.install_migration(b)
            installed.append((b, overlapped))
            self.migrations += 1
            self.stats.setdefault(b.seq_id, {})["replica"] = r.name
        if r.pending_migrations:
            # a bundle is parked for lack of pages: on a tiered-memory
            # replica (EngineCore(residency=...)) ask the manager to
            # evict for it at this round's balance point — the install
            # retries next round against the freed arena
            r.engine.request_pages(r.pending_migrations[0].n_pages)
        return installed

    def _complete_migrations(self, r: Replica, installed: list) -> None:
        """Close the installed bundles' windows: the install's device
        work resolved (block on the last seeded array — completion
        measurement, the _ready_in_span contract), stamp the overlap
        against the destination's chunk windows, and mark the
        device-track completion the cross-rank merge threads its flow
        arrows through."""
        import jax

        # jaxlint: disable=host-sync-in-dispatch — completion
        # measurement at the round boundary (the chunk readback already
        # happened); the window must not close before the install's
        # device work it claims to cover has finished
        jax.block_until_ready(r.engine.temps)
        t_done = time.perf_counter()
        rec = tracelib.active()
        # prune chunk windows no open migration can still intersect
        # (the installed bundles are still in _mig_open here — they
        # pop below): without this, every completion rescans up to
        # the deque's full history for intersections that are zero by
        # construction (windows that ended before any open migration
        # began)
        floor = min((t0 for _, t0 in self._mig_open.values()),
                    default=t_done)
        while r.engine.chunk_windows \
                and r.engine.chunk_windows[0][1] < floor:
            r.engine.chunk_windows.popleft()
        windows = list(r.engine.chunk_windows)
        for bundle, overlapped in installed:
            t_disp, t0 = self._mig_open.pop(bundle.seq, (0.0, t_done))
            span = max(t_done - t0, 1e-9)
            under_chunk = sum(
                max(0.0, min(t_done, e) - max(t0, s))
                for s, e in windows)
            self._mig_total_s += span
            self._mig_overlap_s += min(under_chunk, span)
            if bundle.transport == "dma":
                self._dma_total_s += span
                self._dma_overlap_s += min(under_chunk, span)
            if rec is not None and t_disp:
                rec.mark_complete(
                    "plane.kv_migration", t_disp,
                    {"seq": bundle.seq, "dst": r.name,
                     "overlapped": overlapped},
                    track=migration_track(bundle.seq))

    # -- result collection -------------------------------------------------

    def _collect_finished(self, r: Replica) -> int:
        """Pull finished/shed rows out of a replica into the plane's
        tables, merging the replica-side timing into the plane's
        end-to-end stats row (a migrated request keeps the t_first its
        user actually saw on the prefill replica)."""
        eng = r.engine
        n = 0
        for sid in list(eng.finished):
            ps = self.stats.get(sid)
            if ps is None or ps.get("outcome") is not None:
                continue
            toks = eng.finished.pop(sid)
            es = eng.stats.get(sid, {})
            self.finished[sid] = toks
            if ps["t_first"] is None:
                ps["t_first"] = es.get("t_first")
            ps["t_finish"] = es.get("t_finish", time.perf_counter())
            ps["tokens"] = int(es.get("tokens") or len(toks))
            ps["outcome"] = es.get("outcome") or "ok"
            ps["preemptions"] = int(es.get("preemptions") or 0)
            ps["replica"] = r.name
            self._judge_window(ps)
            # the recovery record resolves with the request (death
            # recovery only ever reads UNRESOLVED rows): a long-lived
            # plane must not grow one prompt array per served request
            self._requests.pop(sid, None)
            n += 1
        return n

    def _judge_window(self, ps: dict) -> None:
        """Fold one RESOLVED stats row (served or shed) into the
        sliding attainment window — at resolution time, so the window
        tracks recent service quality rather than the end-of-run
        average."""
        if self.slo is None:
            return
        target = self.slo.get(int(ps.get("priority") or 0),
                              slolib.SLOTarget())
        self.attain_window.judge(ps, target)

    def _emit_attainment(self) -> None:
        """The per-round sliding-window SLO-attainment gauge: one
        number in three mediums (metrics gauge, trace counter, RunLog
        record), emitted from the SAME window the elastic controller
        reads — so autofit's offline threshold replay sees exactly the
        trajectory the live autoscaler saw."""
        if self.slo is None:
            return
        self._plane_rounds += 1
        snap = self.attain_window.snapshot()
        judged, attained = (self.attain_window.judged,
                            self.attain_window.attained)
        judged_round = judged - self._attain_emitted[0]
        attained_round = attained - self._attain_emitted[1]
        self._attain_emitted = (judged, attained)
        queued = sum(r.engine.queue_depth for r in self.replicas
                     if r.alive)
        active = sum(1 for r in self.replicas if r.alive
                     for s in r.engine._slots if s.active)
        live = sum(1 for r in self.replicas
                   if r.alive and not r.draining)
        m = metricslib.get_metrics()
        if m.enabled and snap["overall"] is not None:
            m.gauge("plane.attainment").set(snap["overall"])
            for prio, frac in snap["per_class"].items():
                m.gauge(f"plane.attainment.p{prio}").set(frac)
        rec = tracelib.active()
        if rec is not None and snap["overall"] is not None:
            rec.counter("plane.attainment", {
                "overall": snap["overall"],
                **{f"p{prio}": frac
                   for prio, frac in snap["per_class"].items()}})
        self._emit(kind="plane_attainment", round=self._plane_rounds,
                   overall=snap["overall"],
                   per_class={str(p): f
                              for p, f in snap["per_class"].items()},
                   window_n=snap["n"], judged_round=judged_round,
                   attained_round=attained_round, queued=queued,
                   active=active, replicas=live)

    def _update_gauges(self) -> None:
        m = metricslib.get_metrics()
        if not m.enabled:
            return
        for r in self.replicas:
            m.gauge(f"plane.{r.name}.queue_depth").set(
                r.engine.queue_depth)
            m.gauge(f"plane.{r.name}.free_pages").set(
                r.engine.free_page_count)

    # -- replica-level chaos + death recovery ------------------------------

    def _probe_replica_chaos(self, r: Replica) -> bool:
        """The ``replica_round`` chaos site for the IN-PROCESS plane,
        probed once per replica per plane round against the replica's
        ORDINAL (``die:replica=N`` addresses the same identity the
        launched plane's rank-N process has). Executed here rather
        than through ``maybe_inject`` because every in-process replica
        shares one OS process — a literal SIGKILL would take the whole
        plane down instead of one replica. Stalls sleep their
        (deterministic) delay; ``die`` marks the replica dead through
        :meth:`_kill_replica`. Returns True when the replica died."""
        for f in chaoslib.matching("replica_round", r.rounds, r.index):
            if f.kind == "die":
                chaoslib.record_injection("replica_round", r.rounds,
                                          "die", rank=r.index)
                self._kill_replica(r)
                return True
            delay = f.delay_at("replica_round", r.rounds)
            chaoslib.record_injection("replica_round", r.rounds,
                                      f.kind, rank=r.index,
                                      delay_s=delay)
            if delay > 0.0:
                time.sleep(delay)
        return False

    def _kill_replica(self, r: Replica) -> None:
        """An involuntary replica loss: its engine's device state is
        gone (in-process, the plane simply never touches it again).
        Everything the replica held — active rows, queued requests,
        bundles parked toward it — goes to
        :meth:`_recover_casualties`: the base (fixed-replica) plane
        SHEDS them, counted in the SLO table and ``shed_on_death``,
        never silently — which is exactly the degraded mode the
        elastic plane's checkpoint-resume recovery exists to beat."""
        if not r.alive:
            return
        r.alive = False
        self.deaths.append(r.name)
        active = [s.seq_id for s in r.engine._slots if s.active]
        queued = [req.seq_id for req in r.engine._queue]
        bundles = list(r.pending_migrations)
        r.pending_migrations.clear()
        for b in bundles:
            # the handoff died with its destination: its window can
            # never complete (don't let it rot in the overlap floor)
            self._mig_open.pop(b.seq, None)
        self._emit(kind="plane_replica_death", replica=r.name,
                   active=len(active), queued=len(queued),
                   bundles=len(bundles))
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("plane.replica_deaths").inc()
        self._recover_casualties(r, active, queued, bundles)

    def _recover_casualties(self, r: Replica, active_sids, queued_sids,
                            bundles) -> None:
        """Fixed-replica recovery: SHED every casualty (the static
        plane cannot adapt — a death today ends in shedding). The
        elastic plane overrides this with checkpoint resume +
        re-routing (serving_plane/autoscaler.py)."""
        for sid in [*active_sids, *queued_sids,
                    *(b.seq_id for b in bundles)]:
            self._shed_request(sid, on_death=True)

    def _shed_request(self, sid: int, *, on_death: bool = False) -> None:
        ps = self.stats.get(sid)
        if ps is None or ps.get("outcome") is not None:
            return
        ps["outcome"] = "shed"
        ps["t_finish"] = time.perf_counter()
        rtr = reqtracelib.active()
        if rtr is not None:
            # plane-side shed (death / unplaceable arrival): the
            # request may never have reached an engine's recorder —
            # open its queued span retroactively so the shed life
            # still tiles instead of finalizing as one untracked gap
            if rtr.segments(sid) is None:
                rtr.begin_request(sid, ps["t_submit"])
            rtr.finish_request(sid, ps["t_finish"], final="shed")
        self._judge_window(ps)  # a shed never attains — it counts
        self.finished[sid] = np.zeros((0,), np.int32)
        self._requests.pop(sid, None)  # resolved: recovery never
        if on_death:                   # reads it again
            self.shed_on_death += 1
        self._emit(kind="plane_shed", seq_id=sid, on_death=on_death)
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("plane.shed").inc()

    def _autoscale_round(self) -> bool:
        """Post-round scaling hook — the base plane is FIXED (the
        ROADMAP's nobody-closes-the-loop baseline); the elastic plane
        overrides this with the SLO-feedback controller. Returns True
        when the plane changed shape (counts as progress)."""
        return False

    # -- the plane loop ----------------------------------------------------

    def _round_order(self) -> list[Replica]:
        # prefill replicas first: their exports of THIS round must be
        # in flight before the decode replicas dispatch their chunks
        return ([r for r in self.replicas if r.role == "prefill"]
                + [r for r in self.replicas if r.role != "prefill"])

    def _has_work(self) -> bool:
        return any(
            r.alive and (r.engine.has_work() or r.pending_migrations)
            for r in self.replicas)

    def run(self, *, arrivals=None, max_rounds: int | None = None):
        """Serve until every replica's queue/slots and (open-loop)
        arrivals drain; returns the plane's ``finished`` table.
        ``arrivals``: ``(t_rel_s, submit_kwargs)`` pairs on the
        schedule's clock, exactly like ``ContinuousBatcher.run`` —
        TTFT/goodput charge the queueing delay the user actually saw.
        ``max_rounds``: park after this many plane rounds (every
        replica at a chunk boundary) and return."""
        t_run0 = time.perf_counter()
        pending_arrivals = (deque(sorted(arrivals, key=lambda a: a[0]))
                            if arrivals else None)
        rounds = 0
        while True:
            if pending_arrivals:
                now_rel = time.perf_counter() - t_run0
                while pending_arrivals \
                        and pending_arrivals[0][0] <= now_rel:
                    t_arr, kw = pending_arrivals.popleft()
                    try:
                        rid = self.submit(**kw)
                    except ValueError:
                        if not self.deaths:
                            raise  # a config error, not degradation
                        # an arrival no surviving replica can place:
                        # the degraded plane sheds it, counted — the
                        # run must keep serving what it can
                        rid = self._next_rid
                        self._next_rid += 1
                        self.stats[rid] = {
                            "priority": int(kw.get("priority", 0)),
                            "t_submit": t_run0 + t_arr,
                            "t_first": None, "t_finish": None,
                            "tokens": 0, "outcome": None,
                            "preemptions": 0, "replica": None,
                        }
                        self._shed_request(rid, on_death=True)
                        continue
                    t_abs = t_run0 + t_arr
                    # the schedule's instant, end to end: the plane
                    # row, the replica's queue entry, and the replica's
                    # stats row all charge the user-visible wait
                    self.stats[rid]["t_submit"] = t_abs
                    eng = self._assignment[rid].engine
                    eng._queue[-1].t_submit = t_abs
                    eng.stats[rid]["t_submit"] = t_abs
                    rtr = reqtracelib.active()
                    if rtr is not None:
                        rtr.restamp_submit(rid, t_abs)
            if not self._has_work():
                if not pending_arrivals:
                    break
                if max_rounds is not None:
                    break
                wait = pending_arrivals[0][0] - (time.perf_counter()
                                                 - t_run0)
                time.sleep(min(max(wait, 0.0), 0.005))
                continue
            if max_rounds is not None and rounds >= max_rounds:
                break
            rounds += 1
            self.rounds_total += 1
            progressed = False
            for r in self._round_order():
                if not r.alive:
                    continue
                if chaoslib.active() is not None \
                        and self._probe_replica_chaos(r):
                    progressed = True  # the death recovery moved work
                    continue
                with r.device_ctx():
                    if r.role == "prefill":
                        st = r.engine.service_round(decode=False)
                        progressed |= bool(st["admitted"])
                        progressed |= self._export_ready(r) > 0
                    else:
                        installed: list = []
                        pre = None
                        if r.pending_migrations:
                            def pre(overlapped, r=r, box=installed):
                                box.extend(
                                    self._install_pending(r, overlapped))
                        st = r.engine.service_round(pre_collect=pre)
                        progressed |= (bool(st["admitted"])
                                       or st["active"]
                                       or bool(installed))
                        if installed:
                            self._complete_migrations(r, installed)
                r.rounds += 1
                self.replica_rounds += 1
                progressed |= self._collect_finished(r) > 0
            self._update_gauges()
            self._emit_attainment()
            progressed |= self._autoscale_round()
            if not progressed and not pending_arrivals:
                queued = {r.name: r.engine.queue_depth
                          for r in self.replicas if r.alive}
                raise RuntimeError(
                    f"serving-plane deadlock: work remains but no "
                    f"replica can make progress (queues {queued}, "
                    f"pending migrations "
                    f"{[len(r.pending_migrations) for r in self.replicas]}"
                    ") — pools too small for the waiting requests?")
        total = time.perf_counter() - t_run0
        self._serve_s += total
        if self._mig_total_s > 0:
            self.last_kv_migration_overlap_frac = (
                self._mig_overlap_s / self._mig_total_s)
        if self._dma_total_s > 0:
            self.last_dma_migration_overlap_frac = (
                self._dma_overlap_s / self._dma_total_s)
        m = metricslib.get_metrics()
        if m.enabled:
            m.gauge("plane.migrations").set(self.migrations)
            if self.last_kv_migration_overlap_frac is not None:
                m.gauge("plane.kv_migration_overlap_frac").set(
                    self.last_kv_migration_overlap_frac)
            if self.last_dma_migration_overlap_frac is not None:
                m.gauge("plane.dma_migration_overlap_frac").set(
                    self.last_dma_migration_overlap_frac)
            m.gauge("plane.migration_bytes_per_round").set(
                self.migration_bytes_per_round)
        if self.slo is not None:
            self.last_slo = slolib.attainment(self.stats, self.slo,
                                              self._serve_s)
            if m.enabled:
                tot = self.last_slo["total"]
                m.gauge("plane.tok_s").set(tot["tok_s"])
                m.gauge("plane.goodput_tok_s").set(
                    tot["goodput_tok_s"])
                if self.replica_rounds:
                    m.gauge("plane.goodput_per_replica_round").set(
                        self.goodput_per_replica_round or 0.0)
        return self.finished

    @property
    def goodput_per_replica_round(self) -> float | None:
        """SLO-attained tokens per (live replica × plane round) — the
        EFFICIENCY headline of the elastic trajectory: a plane that
        holds attainment by over-provisioning pays for it here, one
        that sheds pays in the numerator.
        None until a run with ``slo=`` completed."""
        if self.last_slo is None or not self.replica_rounds:
            return None
        tot = self.last_slo["total"]
        good_tokens = tot["goodput_tok_s"] * self.last_slo["wall_s"]
        return good_tokens / self.replica_rounds

    @property
    def migration_bytes_per_round(self) -> float:
        """Σ dispatched KV-payload bytes per plane round — the
        dataplane-pressure headline the transport tier exists to hide:
        the SAME bytes cross whichever transport resolved, so this
        number is transport-invariant: a workload-shape
        pin rather than a speed score. 0.0 before any round ran."""
        return self.migration_bytes / max(1, self.rounds_total)
