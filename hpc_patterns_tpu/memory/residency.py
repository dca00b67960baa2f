"""The residency manager: per-block tier tracking, eviction policies,
and the overlapped HBM<->host transfer pipeline.

The first-touch BLAS-offloading shape (arxiv 2501.00279) applied to
this repo's two big consumers of HBM: serving KV pages and training
optimizer/param blocks. The manager owns three things:

- **accounting**: every BLOCK (a KV page, an opt-state leaf) has a
  tier (``"hbm"`` / ``"host"``), a pin state, a last-touch round, and
  a priority; blocks belong to GROUPS (a serving row's page set, one
  named state tree) because migration is group-granular — a decode
  row's pages move together or the row cannot run;
- **policy**: pluggable eviction order over the unpinned resident
  groups — :class:`LRUPolicy` (longest-untouched first; for decode
  rows, which are touched every resident round, this degrades to
  longest-RESIDENT first, i.e. fair rotation), :class:`
  PriorityAwarePolicy` (numerically-highest priority class first —
  the round-8 request priorities — then LRU), and
  :class:`ColdAfterNPolicy` (a group resident/untouched for N rounds
  is cold and proactively evictable — the deterministic policy the
  tier-1 tests schedule against);
- **transfers**: the prefetch/evict pipeline, instrumented. Pulls
  (host->HBM) are DISPATCHED before the consumer — the stream-aware
  offloaded-messaging discipline (arxiv 2306.15773): dispatch the
  transfer, then let it hide under the in-flight decode chunk /
  gradient-accumulation phase — and drawn as ``mem.prefetch`` device
  windows whose overlap against the consumer's windows is MEASURED,
  not asserted (``prefetch_overlap_frac``). Evictions (HBM->host) are
  ``mem.evict`` windows dispatched behind the same compute. The
  ``host_transfer`` chaos site fires at every pull dispatch, so a
  degraded-host-bandwidth run is replayable (``slow_host_transfer``).

Tier mechanics per backend: when the backend's pinned-host tier is
real (:func:`~hpc_patterns_tpu.memory.kinds.memory_kind_transfers_work`)
the host side of a block is a ``pinned_host``-kind jax array and both
directions are async ``device_put`` dispatches; otherwise the host
side is a plain numpy copy (the CPU test fallback — the evict then
syncs at its chunk-boundary dispatch site, which is the documented
degraded mode, and the pull stays an async ``device_put``). Either
way the bytes round-trip EXACTLY, which is what the serving oracle
(constrained-HBM engine token-identical to all-HBM, docs/memory.md)
rides on.

Gauges (harness/metrics.py, no-op when disabled): ``mem.hbm_pages`` /
``mem.host_pages`` (resident block counts per tier) and
``mem.prefetch_bytes`` (cumulative bytes pulled host->HBM).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from hpc_patterns_tpu.harness import chaos as chaoslib
from hpc_patterns_tpu.harness import metrics as metricslib
from hpc_patterns_tpu.harness import trace as tracelib
from hpc_patterns_tpu.memory import kinds as kindslib

#: device-subtrack band for ``mem.prefetch`` / ``mem.evict`` windows —
#: declared in harness/trace.py's TRACK_BANDS above the admit-slot
#: band and the serving plane's migration band, so concurrently-open
#: windows never share a Chrome sync track with either
MEM_TRACK_BASE, MEM_TRACKS = tracelib.track_band("residency")


def mem_track(seq: int) -> int:
    """The device subtrack a prefetch/evict window lands on."""
    return MEM_TRACK_BASE + int(seq) % MEM_TRACKS


@dataclass
class BlockState:
    """One tracked block: a KV page or one training-state leaf."""
    key: object          # block id: (group, index)
    group: object        # migration unit: serving seq_id / tree name
    nbytes: int
    tier: str            # "hbm" | "host"
    pinned: bool = False
    priority: int = 0
    last_touch: int = 0
    resident_since: int = 0


@dataclass
class GroupView:
    """Policy-facing summary of one group's blocks."""
    group: object
    n_blocks: int
    nbytes: int
    tier: str
    pinned: bool
    priority: int
    last_touch: int
    resident_since: int


class EvictionPolicy:
    """Victim ordering over resident, unpinned groups. ``victim_order``
    returns groups most-evictable first; ``is_cold`` marks groups the
    manager should evict PROACTIVELY (without demand)."""

    name = "?"

    def victim_order(self, groups: list[GroupView],
                     round_no: int) -> list[GroupView]:
        raise NotImplementedError

    def is_cold(self, group: GroupView, round_no: int) -> bool:
        return False


class LRUPolicy(EvictionPolicy):
    """Least-recently-touched first (ties: longest-resident, then
    group id for determinism). Decode rows are touched every resident
    round, so among them LRU is longest-resident-first — the fair
    rotation that gives swapped rows their turn. Demand-driven only:
    nothing is cold without pressure."""

    name = "lru"

    def victim_order(self, groups, round_no):
        return sorted(groups, key=lambda g: (g.last_touch,
                                             g.resident_since,
                                             str(g.group)))


class PriorityAwarePolicy(LRUPolicy):
    """Numerically-highest priority class first (lower number = more
    important, the round-8 request-priority convention), LRU inside a
    class — background work pages out before interactive work."""

    name = "priority"

    def victim_order(self, groups, round_no):
        return sorted(groups, key=lambda g: (-g.priority,
                                             g.last_touch,
                                             g.resident_since,
                                             str(g.group)))


class ColdAfterNPolicy(LRUPolicy):
    """A group RESIDENT for >= ``n`` rounds is cold: proactively
    evictable even without demand (rotation by residency age — decode
    rows are touched every resident round, so touch-recency cannot be
    the clock). Deterministic given the round schedule — the policy
    the tier-1 rotation tests pin."""

    name = "cold_after_n"

    def __init__(self, n: int):
        if n < 1:
            raise ValueError(f"cold-after-n needs n >= 1, got {n}")
        self.n = int(n)

    def is_cold(self, group, round_no):
        # residency age alone decides: decode rows are touched every
        # resident round, so a touch-based clock would never fire —
        # "resident for n rounds" is the deterministic rotation rule
        return (round_no - group.resident_since) >= self.n


class ResidencyManager:
    """Tiered-residency bookkeeping + the instrumented transfer engine
    (module docstring has the design). One manager serves ONE consumer
    (an :class:`~hpc_patterns_tpu.models.serving.EngineCore` via
    ``EngineCore(residency=...)``, or a training step via
    ``make_train_step(..., residency=...)``) — the tier state is the
    consumer's, not process-global.

    ``host_blocks``: host-tier capacity in blocks (pages); the host
    pool is the larger tier the HBM arena caches. ``policy``: an
    :class:`EvictionPolicy` (default LRU). ``min_resident_rounds``: a
    group prefetched in stays unevictable this many rounds (anti-
    thrash floor). ``prefetch_depth``: advisory cap on concurrently
    in-flight pulls the consumer should dispatch (None = unlimited —
    the engine reads it at its prefetch-dispatch site; autofit sets 1
    when the recorded pulls ran exposed). ``device``: where pulls land
    (default first device)."""

    def __init__(self, *, host_blocks: int, policy: EvictionPolicy
                 | None = None, min_resident_rounds: int = 1,
                 prefetch_depth: int | None = None, device=None):
        if host_blocks < 1:
            raise ValueError(
                f"host_blocks must be >= 1, got {host_blocks}")
        if prefetch_depth is not None and prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1 or None, got "
                f"{prefetch_depth}")
        self.host_blocks = int(host_blocks)
        self.policy = policy or LRUPolicy()
        self.min_resident_rounds = int(min_resident_rounds)
        self.prefetch_depth = (None if prefetch_depth is None
                               else int(prefetch_depth))
        self._device = device
        self.blocks: dict[object, BlockState] = {}
        self.round = 0
        # pinned-host tier or numpy fallback, probed once at first use
        self._host_kind_works: bool | None = None
        # transfer telemetry
        self.swap_outs = 0
        self.swap_ins = 0
        self.prefetch_bytes = 0
        self.evict_bytes = 0
        self._win_seq = 0
        self._chaos_index = 0
        self._prefetch_overlap_s = 0.0
        self._prefetch_total_s = 0.0
        #: open ``mem.evict`` windows awaiting a cheap completion
        #: observation: (trace_stamp, track, payload leaf, attrs)
        self._open_evicts: list[tuple] = []

    @classmethod
    def from_fitted(cls, fitted, *, host_blocks: int, device=None):
        """Build a manager from an autofit ``FittedConfig``: the fitted
        ``residency`` section picks the eviction policy (``lru`` /
        ``priority`` / ``cold_after_n``), the anti-thrash floor, and
        the prefetch depth; a config with no residency section (the
        run never paged) yields the plain default manager. Capacity
        (``host_blocks``) stays the caller's — it is sized by the
        deployment, not the profile."""
        from hpc_patterns_tpu.harness import autofit as autofitlib

        fitted = autofitlib.validate_fitted(fitted)
        section = fitted.get("residency") or {}
        name = section.get("policy") or "lru"
        if name == "priority":
            policy: EvictionPolicy = PriorityAwarePolicy()
        elif name == "cold_after_n":
            policy = ColdAfterNPolicy(int(section.get("cold_after_n")
                                          or 1))
        elif name == "lru":
            policy = LRUPolicy()
        else:
            raise ValueError(
                f"fitted residency policy {name!r} unknown (expected "
                "lru / priority / cold_after_n)")
        return cls(
            host_blocks=host_blocks,
            policy=policy,
            min_resident_rounds=int(
                section.get("min_resident_rounds") or 1),
            prefetch_depth=section.get("prefetch_depth"),
            device=device,
        )

    # -- device / tier plumbing --------------------------------------------

    @property
    def device(self):
        if self._device is None:
            import jax

            self._device = jax.devices()[0]
        return self._device

    def host_tier_is_pinned(self) -> bool:
        """True when the host side is a real ``pinned_host`` jax array
        (async both ways); False = numpy fallback (the CPU mesh)."""
        if self._host_kind_works is None:
            self._host_kind_works = kindslib.memory_kind_transfers_work(
                self.device)
        return self._host_kind_works

    # -- block accounting ---------------------------------------------------

    def register_group(self, group, n_blocks: int, nbytes: int, *,
                       tier: str = "hbm", priority: int = 0) -> None:
        """Track a new group of ``n_blocks`` blocks totaling ``nbytes``
        (evenly attributed). Raises if the group exists or the host
        tier would overflow."""
        if tier not in ("hbm", "host"):
            raise ValueError(f"tier {tier!r} not in ('hbm', 'host')")
        if (group, 0) in self.blocks:
            raise ValueError(f"group {group!r} already registered")
        if tier == "host" and not self.can_host(n_blocks):
            raise ValueError(
                f"host tier full: {n_blocks} blocks over capacity "
                f"{self.host_blocks} (used {self.host_blocks_used()})")
        per = max(1, nbytes // max(1, n_blocks))
        for i in range(n_blocks):
            self.blocks[(group, i)] = BlockState(
                key=(group, i), group=group, nbytes=per, tier=tier,
                priority=priority, last_touch=self.round,
                resident_since=self.round)
        self.update_gauges()

    def release_group(self, group) -> None:
        i = 0
        while (group, i) in self.blocks:
            del self.blocks[(group, i)]
            i += 1
        self.update_gauges()

    def _group_blocks(self, group) -> list[BlockState]:
        # blocks are keyed (group, i) with i dense from register_group,
        # so group operations (touch per active slot per ROUND, pin,
        # retier) are O(group size), not O(all blocks)
        out, i = [], 0
        while (group, i) in self.blocks:
            out.append(self.blocks[(group, i)])
            i += 1
        return out

    def touch_group(self, group) -> None:
        for b in self._group_blocks(group):
            b.last_touch = self.round

    def pin_group(self, group, pinned: bool = True) -> None:
        for b in self._group_blocks(group):
            b.pinned = pinned

    def retier_group(self, group, tier: str) -> None:
        """Move a group's accounting to ``tier`` (the caller moved the
        bytes). To host counts against ``host_blocks``; to HBM stamps
        ``resident_since`` with the current round."""
        blocks = self._group_blocks(group)
        if not blocks:
            raise ValueError(f"group {group!r} not registered")
        if tier == "host" and blocks[0].tier != "host" \
                and not self.can_host(len(blocks)):
            raise ValueError(
                f"host tier full: {len(blocks)} blocks over capacity "
                f"{self.host_blocks} (used {self.host_blocks_used()})")
        for b in blocks:
            if tier == "hbm" and b.tier != "hbm":
                b.resident_since = self.round
                b.last_touch = self.round
            b.tier = tier
        self.update_gauges()

    def hbm_blocks_used(self) -> int:
        return sum(1 for b in self.blocks.values() if b.tier == "hbm")

    def host_blocks_used(self) -> int:
        return sum(1 for b in self.blocks.values() if b.tier == "host")

    def can_host(self, n_blocks: int) -> bool:
        return self.host_blocks_used() + n_blocks <= self.host_blocks

    def groups(self, tier: str | None = None) -> list[GroupView]:
        by_group: dict[object, list[BlockState]] = {}
        for b in self.blocks.values():
            by_group.setdefault(b.group, []).append(b)
        out = []
        for g, bs in by_group.items():
            if tier is not None and bs[0].tier != tier:
                continue
            out.append(GroupView(
                group=g, n_blocks=len(bs),
                nbytes=sum(b.nbytes for b in bs), tier=bs[0].tier,
                pinned=any(b.pinned for b in bs),
                priority=max(b.priority for b in bs),
                last_touch=max(b.last_touch for b in bs),
                resident_since=max(b.resident_since for b in bs)))
        return out

    # -- policy -------------------------------------------------------------

    def victims(self, need_blocks: int, *, exclude=(),
                min_priority: int | None = None) -> list[object]:
        """Groups to evict, policy-ordered, until ``need_blocks`` HBM
        blocks would be free — or every eligible victim if even that
        falls short (the caller decides whether partial progress is
        progress). Pinned groups and groups inside their
        ``min_resident_rounds`` floor are never offered.
        ``min_priority``: only groups whose priority number is >= it
        (the serving engine's demand rules: a queued request may only
        displace STRICTLY less urgent residents, rotation stays within
        same-or-less-urgent classes)."""
        cand = [g for g in self.groups("hbm")
                if not g.pinned and g.group not in exclude
                and self.round - g.resident_since
                >= self.min_resident_rounds
                and (min_priority is None
                     or g.priority >= min_priority)]
        chosen, freed = [], 0
        for g in self.policy.victim_order(cand, self.round):
            if freed >= need_blocks:
                break
            # host capacity is consumed CUMULATIVELY across this
            # pass's picks (freed blocks land on the host tier) — a
            # per-group check against the pre-pass state would
            # overbook the tier
            if not self.can_host(freed + g.n_blocks):
                continue
            chosen.append(g.group)
            freed += g.n_blocks
        # partial progress is still progress: even when the eligible
        # victims cannot cover the whole need, freeing what they hold
        # lets smaller consumers (or next round) move
        return chosen

    def cold_groups(self, *, exclude=()) -> list[object]:
        """Groups the policy marks proactively evictable this round."""
        return [g.group for g in self.groups("hbm")
                if not g.pinned and g.group not in exclude
                and self.round - g.resident_since
                >= self.min_resident_rounds
                and self.can_host(g.n_blocks)
                and self.policy.is_cold(g, self.round)]

    # -- rounds / gauges ----------------------------------------------------

    def begin_round(self) -> None:
        self.round += 1
        self._close_ripe_evicts()

    def update_gauges(self) -> None:
        m = metricslib.get_metrics()
        if not m.enabled:
            return
        m.gauge("mem.hbm_pages").set(self.hbm_blocks_used())
        m.gauge("mem.host_pages").set(self.host_blocks_used())
        m.gauge("mem.prefetch_bytes").set(self.prefetch_bytes)

    # -- transfers (the instrumented pipeline) ------------------------------

    @staticmethod
    def _payload_bytes(payload) -> int:
        import jax

        return sum(int(getattr(a, "nbytes", 0))
                   for a in jax.tree.leaves(payload))

    def push_payload(self, payload, *, attrs: dict | None = None,
                     shardings=None):
        """HBM -> host: move a payload tree to the host tier and open
        its ``mem.evict`` device window (closed lazily at the next
        round boundary — :meth:`begin_round` — or :meth:`drain`).
        ``shardings``: explicit per-leaf target shardings (the
        training path's mesh-aware host placements); default is the
        manager's tier — async per-leaf ``device_put`` when the
        pinned-host tier is real, else a synchronous numpy copy (the
        caller sits at a chunk boundary — the deliberate-sync contract
        eviction shares with preemption's snapshot)."""
        import jax

        nbytes = self._payload_bytes(payload)
        seq = self._win_seq
        self._win_seq += 1
        rec = tracelib.active()
        t_disp = 0.0
        track = mem_track(seq)
        win_attrs = {**(attrs or {}), "bytes": nbytes}
        if rec is not None:
            t_disp = rec.mark_dispatch(
                "mem.evict", {**win_attrs, "seq": seq}, track=track)
        if shardings is not None:
            out = jax.tree.map(jax.device_put, payload, shardings)
        elif self.host_tier_is_pinned():
            sh = kindslib.kind_sharding(self.device, "pinned_host")
            out = jax.tree.map(lambda a: jax.device_put(a, sh), payload)
        else:
            # jaxlint: disable=host-sync-in-dispatch — the numpy
            # fallback tier IS a host copy; the caller dispatches
            # evictions at a chunk boundary (collected), so the sync
            # stalls nothing in flight
            out = jax.tree.map(lambda a: np.asarray(jax.device_get(a)),
                               payload)
        self.swap_outs += 1
        self.evict_bytes += nbytes
        if rec is not None and t_disp:
            self._open_evicts.append((t_disp, track, out, seq,
                                      win_attrs))
        self.update_gauges()
        return out

    def pull_payload(self, payload, *, attrs: dict | None = None,
                     shardings=None):
        """Host -> HBM: dispatch the pull for a host payload tree and
        open its ``mem.prefetch`` window. ``shardings``: explicit
        per-leaf HBM targets (the training path); default pulls onto
        the manager's device. Returns ``(device_payload, handle)``;
        the caller completes the window with :meth:`complete_pull`
        once it OBSERVES completion (after the consumer's sync point)
        — the window must cover real transfer time, not dispatch
        time. The ``host_transfer`` chaos site fires here, between the
        window open and the transfer dispatch, so an injected
        ``slow_host_transfer`` delay widens exactly the window it
        claims to (and delays the real transfer behind it)."""
        import jax

        nbytes = self._payload_bytes(payload)
        seq = self._win_seq
        self._win_seq += 1
        rec = tracelib.active()
        t_disp = 0.0
        track = mem_track(seq)
        win_attrs = {**(attrs or {}), "bytes": nbytes}
        if rec is not None:
            t_disp = rec.mark_dispatch(
                "mem.prefetch", {**win_attrs, "seq": seq}, track=track)
        if chaoslib.active() is not None:
            chaoslib.maybe_inject("host_transfer", self._chaos_index)
        self._chaos_index += 1
        if shardings is not None:
            out = jax.tree.map(jax.device_put, payload, shardings)
        else:
            dev = self.device
            out = jax.tree.map(lambda a: jax.device_put(a, dev),
                               payload)
        self.swap_ins += 1
        self.prefetch_bytes += nbytes
        self.update_gauges()
        return out, (t_disp, track, seq, time.perf_counter(),
                     win_attrs)

    def complete_pull(self, handle, *, chunk_windows=()) -> None:
        """Close a pull's ``mem.prefetch`` window at an OBSERVED
        completion (the caller synced past the consumer) and fold its
        overlap against the consumer's ``chunk_windows`` — host-stamp
        ``(t0, t1)`` pairs, the serving chunk / training accumulation
        windows — into ``prefetch_overlap_frac``."""
        t_disp, track, seq, t0, attrs = handle
        t_done = time.perf_counter()
        span = max(t_done - t0, 1e-9)
        under = sum(max(0.0, min(t_done, e) - max(t0, s))
                    for s, e in chunk_windows)
        self._prefetch_total_s += span
        self._prefetch_overlap_s += min(under, span)
        rec = tracelib.active()
        if rec is not None and t_disp:
            rec.mark_complete("mem.prefetch", t_disp,
                              {**attrs, "seq": seq}, track=track)

    @property
    def prefetch_overlap_frac(self) -> float | None:
        """Measured fraction of prefetch-window time spent under the
        consumer's in-flight compute windows (a device reading only
        on the chip; no benchmark cell holds it yet, ROADMAP Design).
        None until a pull completed."""
        if self._prefetch_total_s <= 0:
            return None
        return self._prefetch_overlap_s / self._prefetch_total_s

    def _close_ripe_evicts(self) -> None:
        """Close open ``mem.evict`` windows whose payloads are ready —
        a cheap block at the round boundary (the transfer had a whole
        round to land; numpy-fallback payloads are ready at dispatch)."""
        if not self._open_evicts:
            return
        import jax

        rec = tracelib.active()
        for t_disp, track, payload, seq, attrs in self._open_evicts:
            # jaxlint: disable=host-sync-in-dispatch — completion
            # measurement at the round boundary (the window must not
            # close before the device->host copy it covers resolved)
            jax.block_until_ready(payload)
            if rec is not None and t_disp:
                rec.mark_complete("mem.evict", t_disp,
                                  {**attrs, "seq": seq}, track=track)
        self._open_evicts.clear()

    def drain(self) -> None:
        """Close every open window (end of a run / a test's flush)."""
        self._close_ripe_evicts()
