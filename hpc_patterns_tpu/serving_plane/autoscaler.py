"""Elastic serving plane: SLO-feedback autoscaling with residency-
backed warm replica spin-up.

The plane had fixed replica counts, the loadgen produces diurnal and
bursty schedules, the SLO layer computes per-class attainment and
goodput, and chaos can kill replicas — but nobody closed the loop: a
diurnal ramp or a replica death ended in shedding, not adaptation.
This module closes it, in the first-touch spirit of the BLAS
offloading line (arxiv 2501.00279): the signals the observability
stack already records become the controller's inputs.

Three pieces:

- :class:`Autoscaler` — the DECISION half, deliberately pure: it
  observes one :class:`Signals` snapshot per plane round (queue
  pressure, sliding-window SLO attainment, live replica count) and
  emits one :class:`Decision` (``up`` / ``down`` / ``hold``) under an
  :class:`AutoscalerPolicy` with hysteresis bands (``up_queue`` >
  ``down_queue``; attainment must RECOVER past ``down_attainment``
  before a scale-down, not merely clear the scale-up bar), a cooldown
  between actions, and per-plane min/max clamps. No randomness, no
  clock: the same signal trajectory always yields the same decision
  log — which is what lets a chaos run replay against a fix
  (tests/test_autoscaler.py pins hysteresis/cooldown/clamp/
  determinism jax-free).
- :class:`WarmParamPool` — the WARM SPIN-UP half: replica weights
  parked ONCE in the host tier through the PR 10
  :class:`~hpc_patterns_tpu.memory.ResidencyManager` (the manager
  already streams params for training), so scaling up pages bytes
  back instead of re-running ``init_params``. Each spin-up is a
  ``plane.spinup`` device-track window (dispatch at the pull,
  completion when the new engine's state resolves) — the number the
  elastic bench proves is measurably smaller than a cold init.
- :class:`ElasticServingPlane` — the ACTUATION half over the PR 9
  router: scale-UP builds a new replica on warm params;
  scale-DOWN drains — the victim stops receiving routing, its queued
  requests re-route, its in-flight rows EXPORT to survivors through
  the existing ``export_migration``/``install_migration`` path
  (byte-exact; nothing sheds on a voluntary drain), and the replica
  retires only when empty. Involuntary death (the router's
  ``die:replica=N`` chaos) recovers from the plane's RESUME
  CHECKPOINT: per-row observed tokens plus — in sampled mode — the
  per-row PRNG key state snapshotted at each round boundary, so a
  dead replica's streams continue on survivors byte-exact, greedy
  AND sampled (the same contract preemption and migration already
  carry).

The robustness verdict lives in tests/test_autoscaler.py and
tests/test_serving_plane.py (TestReplicaDeathStaticPlane): under
replica-death chaos this plane serves every request while the fixed
plane demonstrably sheds; ``goodput_per_replica_round`` is the
efficiency reading beside it (docs/serving_plane.md "Elastic plane")
and no benchmark cell holds either yet (ROADMAP Design).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from dataclasses import fields as dataclasses_fields

import numpy as np

from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness import slo as slolib
from hpc_patterns_tpu.harness import trace as tracelib
from hpc_patterns_tpu.serving_plane.migration import migrate_pages
from hpc_patterns_tpu.serving_plane.router import Replica, ServingPlane

#: device-subtrack band for ``plane.spinup`` windows — declared in
#: harness/trace.py's TRACK_BANDS between the migration band and the
#: residency band, so a spin-up overlapping either never shares a
#: Chrome sync track with it
SPINUP_TRACK_BASE, SPINUP_TRACKS = tracelib.track_band("spinup")


def spinup_track(ordinal: int) -> int:
    """The device subtrack a replica spin-up's window lands on."""
    return SPINUP_TRACK_BASE + int(ordinal) % SPINUP_TRACKS


# ---------------------------------------------------------------------------
# the decision half (pure, jax-free)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AutoscalerPolicy:
    """The control law's knobs.

    ``up_queue``/``down_queue``: queue-pressure thresholds in QUEUED
    REQUESTS PER LIVE REPLICA, averaged over the signal window. The
    gap between them IS the hysteresis band: scale up only STRICTLY
    above ``up_queue``, scale down only STRICTLY below ``down_queue``
    — a steady load sitting on either boundary holds (no flap).
    ``up_attainment``/``down_attainment``: window SLO-attainment
    thresholds — attainment below ``up_attainment`` scales up even at
    modest queues (latency is the SLO, not depth), and a scale-down
    additionally requires attainment at/above ``down_attainment``
    (capacity is only returned once the SLO has recovered past where
    the scale-up bar sits). ``cooldown_rounds``: rounds after any
    action during which only the min-clamp may act (a death must be
    replaceable immediately; ordinary scaling waits out its own
    transient). ``window``: rounds of signal smoothing."""

    min_replicas: int = 1
    max_replicas: int = 4
    up_queue: float = 3.0
    down_queue: float = 0.5
    up_attainment: float = 0.9
    down_attainment: float = 0.98
    cooldown_rounds: int = 4
    window: int = 8

    @classmethod
    def from_fitted(cls, fitted, **overrides) -> "AutoscalerPolicy":
        """A policy from an autofit ``FittedConfig``: the fitted
        ``autoscaler`` section's hysteresis bands (picked by replaying
        the recorded attainment/queue trajectory through this very
        controller offline and keeping the non-flapping candidate) —
        defaults where the config has no trajectory. Keyword overrides
        win over the fit (deployment clamps like ``max_replicas``
        stay the operator's)."""
        from hpc_patterns_tpu.harness import autofit as autofitlib

        fitted = autofitlib.validate_fitted(fitted)
        section = fitted.get("autoscaler") or {}
        kw = {f.name: section[f.name]
              for f in dataclasses_fields(cls)
              if f.name in section}
        kw.update(overrides)
        return cls(**kw)

    def __post_init__(self):
        if not 1 <= self.min_replicas <= self.max_replicas:
            raise ValueError(
                f"need 1 <= min_replicas <= max_replicas, got "
                f"{self.min_replicas}/{self.max_replicas}")
        if not 0.0 <= self.down_queue < self.up_queue:
            raise ValueError(
                f"hysteresis needs 0 <= down_queue < up_queue, got "
                f"{self.down_queue}/{self.up_queue} — equal thresholds "
                "flap at a steady boundary load")
        if not 0.0 <= self.up_attainment <= self.down_attainment <= 1.0:
            raise ValueError(
                f"need 0 <= up_attainment <= down_attainment <= 1, got "
                f"{self.up_attainment}/{self.down_attainment}")
        if self.cooldown_rounds < 0 or self.window < 1:
            raise ValueError(
                f"cooldown_rounds >= 0 and window >= 1 required, got "
                f"{self.cooldown_rounds}/{self.window}")


@dataclass(frozen=True)
class Signals:
    """One plane round's observed state — everything the controller
    is allowed to see. ``attained``/``judged``: requests resolved
    inside the policy window and how many of them met their class SLO
    (shed counts as judged-and-missed)."""

    round: int
    replicas: int        # live, non-draining
    queued: int          # total queue depth across them
    active: int          # total active rows
    #: requests resolved THIS round (a per-round delta, like every
    #: other field): the controller's own window is the ONLY
    #: smoothing — a producer must not pre-aggregate, or each
    #: judgment counts up to window× and lags decisions ~2×window
    attained: int = 0
    judged: int = 0


@dataclass(frozen=True)
class Decision:
    """One round's verdict, with the evidence that produced it — the
    decision log is the replay/determinism handle."""

    round: int
    action: str          # "up" | "down" | "hold"
    reason: str
    replicas: int        # live count the decision saw
    pressure: float      # window-mean queued-per-replica
    attainment: float | None  # window attainment (None: nothing judged)


class Autoscaler:
    """The pure controller: ``observe(signals) -> Decision``, one call
    per plane round. Holds only the signal window, the cooldown
    counter, and the decision log — a deterministic function of the
    signal sequence (pinned by tests/test_autoscaler.py)."""

    def __init__(self, policy: AutoscalerPolicy | None = None):
        self.policy = policy or AutoscalerPolicy()
        self._window: deque = deque(maxlen=self.policy.window)
        self._cooldown = 0
        self.decisions: list[Decision] = []

    def _decide(self, sig: Signals) -> tuple[str, str]:
        p = self.policy
        pressure = self.pressure
        att = self.attainment
        # the min-clamp outranks the cooldown: a replica death below
        # the floor must be replaceable THIS round, not after waiting
        # out the transient of the very action that dropped the count
        if sig.replicas < p.min_replicas:
            return "up", (f"below min_replicas "
                          f"({sig.replicas} < {p.min_replicas})")
        if self._cooldown > 0:
            return "hold", f"cooldown ({self._cooldown} round(s) left)"
        if sig.replicas < p.max_replicas:
            if pressure > p.up_queue:
                return "up", (f"queue pressure {pressure:.2f} > "
                              f"{p.up_queue}")
            if att is not None and att < p.up_attainment:
                return "up", (f"attainment {att:.2f} < "
                              f"{p.up_attainment}")
        if sig.replicas > p.min_replicas \
                and pressure < p.down_queue and sig.queued == 0 \
                and (att is None or att >= p.down_attainment):
            return "down", (f"queue pressure {pressure:.2f} < "
                            f"{p.down_queue}, attainment recovered")
        return "hold", "inside the hysteresis band"

    @property
    def pressure(self) -> float:
        """Window-mean queued requests per live replica."""
        if not self._window:
            return 0.0
        return sum(s.queued / max(1, s.replicas)
                   for s in self._window) / len(self._window)

    @property
    def attainment(self) -> float | None:
        """Window SLO-attainment fraction; None when nothing was
        judged inside the window (no verdict = no latency evidence)."""
        judged = sum(s.judged for s in self._window)
        if not judged:
            return None
        return sum(s.attained for s in self._window) / judged

    def observe(self, sig: Signals) -> Decision:
        self._window.append(sig)
        action, reason = self._decide(sig)
        if self._cooldown > 0:
            self._cooldown -= 1
        if action != "hold":
            self._cooldown = self.policy.cooldown_rounds
        dec = Decision(round=sig.round, action=action, reason=reason,
                       replicas=sig.replicas, pressure=self.pressure,
                       attainment=self.attainment)
        self.decisions.append(dec)
        return dec


# ---------------------------------------------------------------------------
# the warm spin-up half (residency-backed parked weights)
# ---------------------------------------------------------------------------


class WarmParamPool:
    """Replica weights parked in the HOST tier, pulled per spin-up.

    The params tree is pushed ONCE through the residency manager's
    instrumented pipeline (``mem.evict`` window; pinned-host jax
    arrays where the backend has them, numpy otherwise — the same
    tier model training's opt-state streaming uses) and registered as
    a host-tier group. Each :meth:`pull` dispatches an independent
    host->HBM copy (``mem.prefetch`` window) — a READ-THROUGH of the
    parked template, which stays host-resident for the next spin-up —
    and the caller observes completion via :meth:`complete`. This is
    why elastic scale-up is warm: the bytes already exist, nothing
    re-runs ``init_params``."""

    def __init__(self, params, *, manager=None):
        import jax

        from hpc_patterns_tpu.memory import ResidencyManager

        leaves = jax.tree.leaves(params)
        nbytes = sum(int(getattr(a, "nbytes", 0)) for a in leaves)
        self.manager = manager or ResidencyManager(
            host_blocks=max(1, len(leaves)))
        self.manager.register_group(
            "warm_params", len(leaves), nbytes, tier="host")
        self.host_params = self.manager.push_payload(
            params, attrs={"what": "warm_params"})
        self.manager.drain()  # the park is complete; close its window
        self.pulls = 0

    def pull(self):
        """Dispatch one host->HBM copy of the parked weights; returns
        ``(device_params, handle)`` — dispatch-only, the engine build
        enqueues behind it."""
        payload, handle = self.manager.pull_payload(
            self.host_params, attrs={"what": "warm_params",
                                     "pull": self.pulls})
        self.pulls += 1
        return payload, handle

    def complete(self, handle) -> None:
        """Close the pull's ``mem.prefetch`` window at an observed
        completion (the caller just blocked on the new engine)."""
        self.manager.complete_pull(handle)


# ---------------------------------------------------------------------------
# the actuation half: the elastic plane
# ---------------------------------------------------------------------------


class ElasticServingPlane(ServingPlane):
    """A :class:`~hpc_patterns_tpu.serving_plane.router.ServingPlane`
    that changes shape under the controller (module docstring has the
    design). ``engine_factory(params) -> EngineCore`` builds a new
    replica's engine on warm-pulled weights — it must produce engines
    construction-compatible with the existing ones (same config,
    sampling mode, and seed; validated on every spin-up).

    Death recovery: each replica round ends with a RESUME CHECKPOINT
    (observed tokens per active row, plus the per-row sampling key
    state in sampled mode — the PR 9 remainder); an involuntary death
    re-submits each in-flight row on a survivor as an ordinary resume
    (prompt = original + observed, ``resume_prefix``, the snapshot
    key), which is the byte-exactness contract preemption already
    proved. Queued requests re-route; bundles parked toward the dead
    replica re-target. Only a request NO survivor can hold sheds."""

    def __init__(self, replicas, *, engine_factory, warm_pool,
                 autoscaler: Autoscaler | None = None,
                 new_replica_role: str = "both", **kw):
        super().__init__(replicas, **kw)
        self.engine_factory = engine_factory
        self.warm_pool = warm_pool
        self.autoscaler = autoscaler or Autoscaler()
        self.new_replica_role = new_replica_role
        self._next_replica = len(self.replicas)
        self._round_no = 0
        #: resume checkpoint: sid -> {"out": [...], "key": (2,) uint32
        #: numpy or None, "replica": name} — refreshed at every round
        #: boundary, dropped on resolution
        self._ckpt: dict[int, dict] = {}
        #: requests awaiting an SLO judgment: entered at submit (and
        #: at the unplaceable-arrival shed), removed once judged — so
        #: the per-round judge pass costs O(unresolved), not O(every
        #: request the plane ever served)
        self._unjudged: set[int] = set()
        #: attained? verdicts of requests resolved since the last
        #: signal — drained into ONE Signals delta per plane round
        self._judgments: deque = deque(maxlen=4096)
        #: death-resumes per sid: folded into the stats row's
        #: preemption count at resolution (the engine-side count
        #: _collect_finished copies in cannot know about them — the
        #: engine that held the earlier leg is dead)
        self._death_resumes: dict[int, int] = {}
        self.spinup_s: list[float] = []
        self.resumed: list[int] = []
        self.drained: list[str] = []
        self.retired: list[str] = []

    # -- signals -----------------------------------------------------------

    def _signals(self) -> Signals:
        live = [r for r in self.replicas
                if r.alive and not r.draining]
        # drain THIS round's judgments: the Signals carry per-round
        # deltas and the controller's deque is the only smoothing
        # window (pre-aggregating here would double-window attainment
        # — each judgment counted up to window× and felt ~2×window)
        attained = sum(1 for a in self._judgments if a)
        judged = len(self._judgments)
        self._judgments.clear()
        return Signals(
            round=self._round_no,
            replicas=len(live),
            queued=sum(r.engine.queue_depth for r in live),
            active=sum(r.engine.active_count for r in live),
            attained=attained,
            judged=judged,
        )

    def submit(self, prompt, max_new: int, **kw) -> int:
        rid = super().submit(prompt, max_new, **kw)
        self._unjudged.add(rid)
        return rid

    def _shed_request(self, sid: int, *, on_death: bool = False) -> None:
        # the one resolution path that can create a stats row WITHOUT
        # going through submit (the unplaceable-arrival shed in the
        # base run loop) — make sure the judge pass sees it
        if self.stats.get(sid, {}).get("outcome") is None:
            self._unjudged.add(sid)
        super()._shed_request(sid, on_death=on_death)

    def _judge_resolved(self) -> None:
        """Judge every request that resolved since the last pass into
        the controller's signal (shed = judged-and-missed; the signal
        must see degradation). Once per PLANE round, over the
        ``_unjudged`` set only — O(unresolved), not O(history)."""
        for sid in list(self._unjudged):
            ps = self.stats.get(sid)
            if ps is None or ps.get("outcome") is None:
                continue
            self._unjudged.discard(sid)
            # the serving engine's preemption count (copied in by the
            # base collect on a finish; untouched on a shed) cannot
            # include death-resumes — the engine that held the
            # earlier leg is gone — so they are folded in HERE, once,
            # at resolution (and nowhere in flight, or a
            # resumed-then-shed row would count each resume twice)
            ps["preemptions"] = (int(ps.get("preemptions") or 0)
                                 + self._death_resumes.pop(sid, 0))
            target = (self.slo or {}).get(
                ps.get("priority", 0), slolib.SLOTarget())
            self._judgments.append(slolib.attained(ps, target))
            self._ckpt.pop(sid, None)

    def _collect_finished(self, r: Replica) -> int:
        n = super()._collect_finished(r)
        self._checkpoint_replica(r)
        return n

    # -- the resume checkpoint ---------------------------------------------

    def _checkpoint_replica(self, r: Replica) -> None:
        """Refresh the resume checkpoint for one replica at its round
        boundary: the chunk is collected, so each active row's
        ``out`` and the post-chunk key state are CONSISTENT — exactly
        the (tokens, key) pair ``_preempt``'s snapshot carries, which
        is what makes a death-resume byte-exact in sampled mode."""
        import jax

        eng = r.engine
        act = [(i, s) for i, s in enumerate(eng._slots) if s.active]
        if not act:
            return
        keys = None
        if not eng.greedy:
            # jaxlint: disable=host-sync-in-dispatch — a deliberate
            # round-boundary snapshot (the chunk readback already
            # synced this round); np.array COPIES the device_get view
            # that a later donated _chunk_step would otherwise mutate
            keys = np.array(jax.device_get(eng.keys))
        for i, s in act:
            self._ckpt[s.seq_id] = {
                "out": list(s.out),
                "key": keys[i].copy() if keys is not None else None,
                "replica": r.name,
                # the engine-side first-token stamp: a death-resume
                # must keep the TTFT the user actually saw, not the
                # survivor's post-resume readback (the same invariant
                # _dispatch_migration preserves via bundle.t_first)
                "t_first": eng.stats.get(s.seq_id, {}).get("t_first"),
            }

    # -- death recovery (overrides the static shed) ------------------------

    def _recover_casualties(self, r: Replica, active_sids, queued_sids,
                            bundles) -> None:
        for sid in active_sids:
            ck = self._ckpt.get(sid)
            req = self._requests.get(sid)
            if ck is None or req is None:
                self._shed_request(sid, on_death=True)
                continue
            out = ck["out"]
            if len(out) >= req["max_new"]:
                # fully emitted, finish report lost with the replica:
                # the observed tokens ARE the output
                ps = self.stats[sid]
                ps["outcome"], ps["tokens"] = "ok", len(out)
                if ps["t_first"] is None:
                    ps["t_first"] = ck.get("t_first")
                ps["t_finish"] = time.perf_counter()
                # jaxlint: disable=host-sync-in-dispatch — host-list
                # packing of checkpoint tokens (plain Python ints the
                # collected chunks already materialized), no readback
                self.finished[sid] = np.asarray(out, np.int32)
                self._requests.pop(sid, None)
                continue
            if self._resume_request(sid, req, out, ck):
                self.resumed.append(sid)
            else:
                self._shed_request(sid, on_death=True)
        for sid in queued_sids:
            req = self._requests.get(sid)
            if req is None or not self._route_again(sid, req):
                self._shed_request(sid, on_death=True)
        for b in bundles:
            dst = self._pick_target(b.n_pages, r)
            if dst is None:
                self._shed_request(b.seq_id, on_death=True)
                continue
            self._mig_open[b.seq] = (0.0, time.perf_counter())
            dst.pending_migrations.append(migrate_pages(b, dst.device))

    def _resume_request(self, sid: int, req: dict, out, ck) -> bool:
        """Continue a dead replica's in-flight row on a survivor as an
        ordinary RESUME: prompt = original + observed tokens, the
        checkpoint key seeding the sampled stream where the dead
        engine's left off. Byte-exact by the preemption contract
        (``_admit_row`` consumes the snapshot key with the split/pick
        order ``_chunk_step`` would have)."""
        import jax.numpy as jnp

        key = ck.get("key")
        # jaxlint: disable=host-sync-in-dispatch — host-list packing
        # of checkpoint tokens, not a device readback (the _preempt
        # resume-Request contract)
        out_arr = np.asarray(out, np.int32)
        prompt = (np.concatenate([req["prompt"], out_arr])
                  if len(out_arr) else req["prompt"])
        remaining = req["max_new"] - len(out_arr)
        target = self._pick_survivor(int(prompt.size), remaining)
        if target is None:
            return False
        kw = {}
        if not target.engine.greedy and key is not None:
            # jaxlint: disable=host-sync-in-dispatch — the key is the
            # checkpoint's HOST numpy copy (snapshotted at a prior
            # round boundary); this re-wraps it for upload, no device
            # value is read
            kw["key"] = jnp.asarray(np.asarray(key, np.uint32))
        target.engine.submit(
            prompt, remaining, seq_id=sid,
            priority=req["priority"], deadline_s=req["deadline_s"],
            temperature=req["temperature"],
            resume_prefix=out_arr if len(out_arr) else None, **kw)
        self._assignment[sid] = target
        ps = self.stats[sid]
        # the row's story continues, its clocks do not restart: TTFT
        # keeps the first token the USER saw on the dead replica (the
        # checkpoint carried it — the _dispatch_migration invariant),
        # and the collect-time merge guard (`if t_first is None`)
        # then never overwrites it with the survivor's readback
        if ps["t_first"] is None:
            ps["t_first"] = ck.get("t_first")
        # counted ONLY via _death_resumes, folded in at resolution:
        # an in-flight ps increment would double-count every resume
        # of a row that later sheds (no engine finish ever overwrites
        # the in-flight value for those)
        self._death_resumes[sid] = (
            self._death_resumes.get(sid, 0) + 1)
        ps["replica"] = target.name
        self._emit(kind="plane_resume", seq_id=sid,
                   replica=target.name, tokens=len(out_arr))
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("plane.death_resumes").inc()
        return True

    def _route_again(self, sid: int, req: dict) -> bool:
        """Re-route a queued (no device state) casualty wholesale."""
        target = self._pick_survivor(int(req["prompt"].size),
                                     req["max_new"])
        if target is None:
            return False
        target.engine.submit(
            req["prompt"], req["max_new"], seq_id=sid,
            priority=req["priority"], deadline_s=req["deadline_s"],
            temperature=req["temperature"], key=req["key"])
        # the request's clocks do not restart on re-routing: the shed
        # deadline and TTFT still count from the ORIGINAL submit (the
        # same re-stamp the open-loop arrival path applies), or a
        # re-route would silently grant a fresh deadline_s window
        t0 = self.stats[sid]["t_submit"]
        target.engine._queue[-1].t_submit = t0
        target.engine.stats[sid]["t_submit"] = t0
        self._assignment[sid] = target
        self.stats[sid]["replica"] = target.name
        return True

    def _pick_survivor(self, prompt_len: int,
                       max_new: int) -> Replica | None:
        cand = [r for r in self.replicas
                if r.alive and not r.draining
                and r.engine.would_fit(prompt_len, max_new)]
        if not cand:
            return None
        return max(cand, key=lambda r: (r.engine.free_page_count,
                                        -r.engine.queue_depth,
                                        -r.index))

    # -- the control loop --------------------------------------------------

    def _autoscale_round(self) -> bool:
        self._round_no += 1
        self._judge_resolved()
        changed = self._drain_step()
        dec = self.autoscaler.observe(self._signals())
        if dec.action == "up":
            changed |= self._spin_up(reason=dec.reason)
        elif dec.action == "down":
            changed |= self._begin_drain(reason=dec.reason)
        return changed

    def _spin_up(self, *, reason: str = "") -> bool:
        """Warm scale-up: pull the parked weights from the host tier,
        build a fresh replica on them, and join the plane — the whole
        acquisition measured as ONE ``plane.spinup`` device window
        (dispatch at the pull, completion when the engine's device
        state resolves), which is the number the bench compares
        against a cold ``init_params``."""
        import jax

        name = f"r{self._next_replica}"
        rec = tracelib.active()
        t0 = time.perf_counter()
        t_disp = (rec.mark_dispatch(
            "plane.spinup", {"replica": name, "reason": reason},
            track=spinup_track(self._next_replica))
            if rec is not None else 0.0)
        params, handle = self.warm_pool.pull()
        engine = self.engine_factory(params)
        rep = Replica(engine, name=name, role=self.new_replica_role)
        # jaxlint: disable=host-sync-in-dispatch — completion
        # measurement: the spin-up window must not close before the
        # pulled params and the engine's fresh device state resolved
        jax.block_until_ready((params, engine.temps))
        self.warm_pool.complete(handle)
        dt = time.perf_counter() - t0
        rep.index = self._next_replica
        self._next_replica += 1
        if rep.can_decode:
            engine.track_chunk_windows = True
        self.replicas.append(rep)
        try:
            self._validate_engines()
        except ValueError:
            self.replicas.pop()
            raise
        self.spinup_s.append(dt)
        if rec is not None and t_disp:
            rec.mark_complete(
                "plane.spinup", t_disp,
                {"replica": name, "spinup_s": round(dt, 6)},
                track=spinup_track(rep.index))
        self._emit(kind="plane_spinup", replica=name,
                   spinup_s=dt, reason=reason)
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("plane.spinups").inc()
            m.gauge("plane.replicas").set(
                sum(1 for x in self.replicas
                    if x.alive and not x.draining))
        return True

    def _begin_drain(self, *, reason: str = "") -> bool:
        """Voluntary scale-down: pick the emptiest live replica and
        put it in DRAIN — no new routing, no inbound migrations; its
        work leaves through :meth:`_drain_step`. Refuses a victim
        whose loss would strand a role (the last prefill- or
        decode-capable replica stays)."""
        live = [r for r in self.replicas
                if r.alive and not r.draining]
        if len(live) <= self.autoscaler.policy.min_replicas:
            return False
        cand = []
        for r in live:
            rest = [x for x in live if x is not r]
            if not any(x.can_prefill for x in rest) \
                    or not any(x.can_decode for x in rest):
                continue
            cand.append(r)
        if not cand:
            return False
        victim = min(cand, key=lambda r: (
            r.engine.active_count + r.engine.queue_depth
            + len(r.pending_migrations),
            -r.index))
        victim.draining = True
        self.drained.append(victim.name)
        self._emit(kind="plane_drain", replica=victim.name,
                   reason=reason)
        m = metricslib.get_metrics()
        if m.enabled:
            m.counter("plane.drains").inc()
        return True

    def _drain_step(self) -> bool:
        """Advance every draining replica one step: re-route its
        queued requests, EXPORT its active rows to survivors through
        the PR 9 migration path (in-flight work migrates byte-exact —
        nothing sheds on a voluntary drain; a row with no destination
        this round just waits), and retire the replica once empty."""
        changed = False
        for r in self.replicas:
            if not (r.alive and r.draining):
                continue
            for req in list(r.engine._queue):
                target = self._pick_survivor(int(req.prompt.size),
                                             req.max_new)
                if target is None:
                    continue  # stays queued; retried next round
                r.engine._queue = [q for q in r.engine._queue
                                   if q is not req]
                r.engine.stats.pop(req.seq_id, None)
                target.engine.submit(
                    req.prompt, req.max_new, seq_id=req.seq_id,
                    priority=req.priority, deadline_s=req.deadline_s,
                    temperature=req.temperature, key=req.key,
                    resume_prefix=req.resume_prefix)
                # clocks do not restart on a drain re-route (the
                # _route_again rule): the shed deadline still counts
                # from the request's ORIGINAL submit instant
                target.engine._queue[-1].t_submit = req.t_submit
                target.engine.stats[req.seq_id]["t_submit"] = \
                    req.t_submit
                self._assignment[req.seq_id] = target
                self.stats[req.seq_id]["replica"] = target.name
                changed = True
            with r.device_ctx():
                for slot in r.engine.exportable_slots():
                    need = len(r.engine._slots[slot].pages)
                    dst = self._pick_target(need, r)
                    if dst is None:
                        continue  # parked on the donor; next round
                    self._dispatch_migration(r, slot, dst)
                    changed = True
            if not r.engine.has_work() and not r.pending_migrations:
                r.alive = False
                r.draining = False
                self.retired.append(r.name)
                self._emit(kind="plane_retire", replica=r.name)
                m = metricslib.get_metrics()
                if m.enabled:
                    m.gauge("plane.replicas").set(
                        sum(1 for x in self.replicas
                            if x.alive and not x.draining))
                changed = True
        return changed
