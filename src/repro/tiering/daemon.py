"""Multiplexed NeoMem daemon: one cadence, N tiered resources, one budget.

The software analogue of one NeoProf device serving every consumer of slow
memory (paper §III): the serve engine / trainer registers each resource
(KV pages, MoE experts, embedding rows, ...) once, and a single host-side
loop drives all of them on the shared cadence hierarchy

    migration  <<  threshold-update  <=  sketch-clear

with ONE migration-quota budget per interval, split across resources in
proportion to their *servable* queued demand (each share capped by that
resource's own promotion-batch quota) — the multiplexed form of
Algorithm 1's quota constraint: a bursty resource is throttled toward its
fair share instead of starving the others, and demand it could not promote
anyway never draws budget away from resources that can.

Resources with bound payload buffers get each epoch's promotion batch
applied as one fused copy through the migration data plane, with the moved
bytes metered per resource (DESIGN.md §8).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.spans import pull, span
from repro.tiering.memory import (DaemonParams, MigrationEvent, TieredMemory,
                                  TieredMemoryState, lookup)
from repro.tiering.resource import TieredResource
from repro.tiering.stats import TierStats


def split_quota(budget: int, demands: dict[str, int],
                caps: dict[str, int] | None = None,
                weights: dict[str, float] | None = None) -> dict[str, int]:
    """Largest-remainder proportional split of the shared migration budget.

    ``caps`` bounds each share by what that resource can actually promote in
    one batch (its static quota width) — un-servable backlog must not draw
    budget away from resources that could use it.

    ``weights`` are isolation weights (default 1.0 each, DESIGN.md §9): when
    the budget binds, shares are proportional to ``weight x servable demand``
    and any share that would exceed its own demand is clamped there, with the
    freed budget redistributed among the rest (weighted max-min).  An entry
    with weight <= 0 is isolated out entirely under contention — it only
    receives budget when the total demand fits.  The same split serves two
    layers: the daemon's per-resource migration budget and the request
    scheduler's per-tenant decode-lane allocation (serve/sched.py).
    """
    eff = {n: min(d, caps[n]) if caps else d for n, d in demands.items()}
    total = sum(eff.values())
    if total <= budget:
        return eff
    w = {n: 1.0 if weights is None else float(weights.get(n, 1.0))
         for n in eff}
    shares = {n: 0 for n in eff}
    open_ = [n for n in eff if eff[n] > 0 and w[n] > 0]
    remaining = budget
    while open_ and remaining > 0:
        tot = sum(w[n] * eff[n] for n in open_)
        exact = {n: remaining * w[n] * eff[n] / tot for n in open_}
        clamped = [n for n in open_ if exact[n] >= eff[n]]
        if not clamped:
            for n in open_:
                shares[n] = int(exact[n])
            leftover = remaining - sum(shares[n] for n in open_)
            for n in sorted(open_, key=lambda n: exact[n] - shares[n],
                            reverse=True):
                if leftover <= 0:
                    break
                shares[n] += 1   # stays <= eff[n]: exact < eff, eff integral
                leftover -= 1
            break
        for n in clamped:            # demand-bound: give it all, redistribute
            shares[n] = eff[n]
            remaining -= eff[n]
        open_ = [n for n in open_ if n not in clamped]
    return shares


class ResourceHandle:
    """A registered resource's live view: state pytree + stats + encoder."""

    def __init__(self, name: str, resource: TieredResource, mem: TieredMemory,
                 weight: float = 1.0):
        self.name = name
        self.resource = resource
        self.mem = mem
        self.weight = weight          # isolation weight in the quota split
        self.state: TieredMemoryState = mem.init()
        self.stats = TierStats(name=name)

    def observe(self, *observation, **kw) -> None:
        """Encode a model-side observation and feed profiler + tier."""
        stream = self.resource.encode_stream(*observation)
        cap = self.resource.spec.touch_cap
        self.state = self.mem.observe(self.state, stream,
                                      touch_pages=stream[:cap], **kw)

    def observe_pages(self, pages, *, touch_pages=None, **kw) -> None:
        """Feed an already-encoded page-id stream (bypasses the encoder)."""
        self.state = self.mem.observe(self.state, pages,
                                      touch_pages=touch_pages, **kw)

    def lookup(self, page_ids) -> tuple[jax.Array, jax.Array]:
        return lookup(self.state, page_ids)

    # -- data plane (DESIGN.md §8) -------------------------------------------
    def bind_data(self, slow_data, initially_valid: bool = True) -> None:
        """Attach the resource's payload; promotions then move real bytes.
        ``initially_valid=False`` starts every page un-witnessed (the KV
        scratch store) — see :meth:`TieredMemory.pages_written`."""
        self.mem.bind_data(slow_data, initially_valid=initially_valid)
        self.stats.quota_bytes = self.mem.quota_bytes

    def pages_written(self, page_ids) -> np.ndarray:
        """Per-page write-witness query (the segment-residency gate)."""
        return self.mem.pages_written(page_ids)

    def tier_view(self) -> dict[str, jax.Array]:
        """Device-array view for in-jit reads: ``{"fast", "slow",
        "page_slot", "scale"}`` (``scale`` is the int8 codec's per-row
        scales, ``None`` otherwise), to be threaded as jit arguments into a
        step that calls :func:`repro.tiering.migrate.lookup_rows`
        (DESIGN.md §10, §14).
        Reads served this way are metered by the observation stream's touch
        accounting, not the host ``read_rows`` counters."""
        return self.mem.tier_view(self.state)

    def lookup_rows(self, page_ids) -> jax.Array:
        """Pure jittable read (no host metering): see ``TieredMemory.lookup_rows``."""
        return self.mem.lookup_rows(self.state, page_ids)

    def read_rows(self, page_ids) -> jax.Array:
        """Serve payload rows: fast-buffer copy on hit, slow-tier fallback.

        Served reads are metered into ``stats.fast_reads``/``slow_reads`` —
        they are real tier accesses, exactly like the observation stream's
        touch accounting (invalid ids < 0 are padding and not counted).
        """
        ids = jnp.asarray(page_ids, jnp.int32)
        # the ONE placement lookup — against the COMMITTED view, so reads
        # issued mid-epoch resolve exactly like the payload gather below
        slots = self.mem.lookup_slots(self.state, ids)
        hits = int(np.sum(pull(slots, "lookup_slots") >= 0))
        valid = int(np.sum(pull(ids, "lookup_slots") >= 0))
        self.stats.fast_reads += hits
        self.stats.slow_reads += valid - hits
        return self.mem.read_rows(self.state, ids, slots=slots)

    def write_rows(self, page_ids, rows) -> None:
        """Owner payload refresh, both tiers kept coherent; bytes metered."""
        n = self.mem.write_rows(self.state, page_ids, rows)
        self.stats.flush_bytes += n * self.mem.row_bytes

    def write_pages(self, page_ids, k_pages, v_pages) -> None:
        """Bulk KV ring-page flush (one donated fused op); bytes metered."""
        n = self.mem.write_pages(self.state, page_ids, k_pages, v_pages)
        self.stats.flush_bytes += n * self.mem.row_bytes

    def copy_rows(self, src_ids, dst_ids) -> None:
        """Store-to-store page duplication (the content-addressed publish
        verb, one donated fused op); bytes metered as flush traffic."""
        n = self.mem.copy_rows(self.state, src_ids, dst_ids)
        self.stats.flush_bytes += n * self.mem.row_bytes

    def hit_rate(self) -> float:
        return self.mem.hit_rate(self.state, self.stats)

    def snapshot(self) -> dict:
        row = self.stats.as_row()
        # merge the not-yet-drained device-side period counters so the read
        # counts are consistent with hit_rate() (which always merged them) —
        # a row must never report 0 reads next to a nonzero hit rate
        row["fast_reads"] += int(pull(self.state.tier.fast_reads,
                                      "tier_stats"))
        row["slow_reads"] += int(pull(self.state.tier.slow_reads,
                                      "tier_stats"))
        row["hit_rate"] = self.hit_rate()
        # fold the in-flight epoch the same way: a snapshot taken mid-epoch
        # must still satisfy last_epoch <= max_epoch <= quota row-level
        # conservation — the issued bytes count against the epoch quota the
        # moment they are in flight, not only once committed
        if self.stats.inflight_bytes:
            row["max_epoch_bytes"] = max(row["max_epoch_bytes"],
                                         self.stats.inflight_bytes)
        return row


def _placed_together(state: TieredMemoryState) -> TieredMemoryState:
    """``state`` with its uncommitted arrays committed where its first
    committed array lives; unchanged when none is committed."""
    arrays = [x for x in jax.tree.leaves(state) if isinstance(x, jax.Array)]
    home = next((x.sharding for x in arrays if x.committed), None)
    if home is None:
        return state
    return jax.tree.map(
        lambda x: jax.device_put(x, home)
        if isinstance(x, jax.Array) and not x.committed else x, state)


class NeoMemDaemon:
    """One daemon loop multiplexed across every registered tiered resource."""

    def __init__(self, params: DaemonParams | None = None):
        self.dp = params or DaemonParams()
        self.resources: dict[str, ResourceHandle] = {}
        self._tick = 0

    # -- registration --------------------------------------------------------
    def register(self, resource: TieredResource, *,
                 policy_params=None, fixed_theta=None,
                 weight: float = 1.0) -> ResourceHandle:
        """Register a resource; its ResourceSpec is the single sizing source.

        ``weight`` is the resource's isolation weight in the shared-budget
        split (``split_quota``): under contention a resource's share is
        proportional to ``weight x servable demand``.
        """
        spec = resource.spec
        if spec.name in self.resources:
            raise ValueError(f"resource {spec.name!r} already registered")
        mem = TieredMemory.from_spec(
            spec, daemon_params=DaemonParams(
                migration_interval=self.dp.migration_interval,
                threshold_update_period=self.dp.threshold_update_period,
                clear_interval=self.dp.clear_interval,
                quota_pages=spec.quota_pages,
                async_plane=self.dp.async_plane),
            policy_params=policy_params, fixed_theta=fixed_theta)
        handle = ResourceHandle(spec.name, resource, mem, weight=weight)
        self.resources[spec.name] = handle
        return handle

    def __getitem__(self, name: str) -> ResourceHandle:
        return self.resources[name]

    def __contains__(self, name: str) -> bool:
        return name in self.resources

    def observe(self, name: str, *observation, **kw) -> None:
        self.resources[name].observe(*observation, **kw)

    # -- the multiplexed loop ------------------------------------------------
    @property
    def budget(self) -> int:
        """Shared promotion budget per migration interval."""
        if self.dp.quota_pages is not None:
            return self.dp.quota_pages
        return sum(h.mem.quota for h in self.resources.values())

    def tick(self) -> dict[str, MigrationEvent]:
        """One daemon tick: run whatever cadences are due, for ALL resources.

        The leaves a cadence rebuilds on the host go back where the rest of
        the resource's state lives: jitted tier programs key on which inputs
        are committed to a device, so a state that changed its mix from
        tick to tick would compile them anew."""
        with span("tier/tick"):
            events = self._run_cadences()
            for h in self.resources.values():
                h.state = _placed_together(h.state)
            return events

    def _run_cadences(self) -> dict[str, MigrationEvent]:
        self._tick += 1
        t, dp = self._tick, self.dp
        events: dict[str, MigrationEvent] = {}

        if t % dp.migration_interval == 0:
            # COMMIT phase first (async plane, DESIGN.md §15): witness each
            # in-flight epoch's readiness token and pointer-swap — never
            # blocks; an epoch whose copy has not landed stays in flight
            with span("tier/commit"):
                for h in self.resources.values():
                    if h.mem.async_on:
                        h.mem.commit_migration(h.stats)
            # PLAN phase (unchanged policy): drain hot pages, split the
            # shared budget.  A busy resource (epoch still uncommitted) is
            # capped at 0 — no N+2 issue before N+1 commits, and its share
            # flows to the others via the weighted max-min redistribution.
            demands: dict[str, int] = {}
            with span("tier/collect"):
                for name, h in self.resources.items():
                    h.state, demands[name] = h.mem.collect(h.state, h.stats)
            caps = {n: (0 if h.mem.busy else h.mem.quota)
                    for n, h in self.resources.items()}
            weights = {n: h.weight for n, h in self.resources.items()}
            shares = split_quota(self.budget, demands, caps, weights)
            # ISSUE phase: promote + dispatch the epoch's data movement
            # (async: non-blocking issue; sync: fused donated copy, with
            # the blocking wait metered as stall_s)
            for name, h in self.resources.items():
                if h.mem.busy:
                    continue
                with span("tier/migrate", resource=name):
                    h.state, event = h.mem.migrate(h.state, h.stats,
                                                   quota=shares.get(name, 0))
                    if event is not None:
                        # data plane first (bytes metered), then the
                        # resource's own hook
                        h.mem.dispatch_migration(h.state, event, h.stats)
                        h.resource.apply_migration(event.promoted,
                                                   event.victims)
                        events[name] = event

        if t % dp.threshold_update_period == 0:
            with span("tier/threshold"):
                for h in self.resources.values():
                    h.state = h.mem.update_threshold(h.state, h.stats)

        if t % dp.clear_interval == 0:
            with span("tier/clear"):
                for h in self.resources.values():
                    h.state = h.mem.clear(h.state)
        return events

    # -- checkpointing (DESIGN.md §6) ----------------------------------------
    def state_dict(self) -> dict[str, TieredMemoryState]:
        """Every resource's TieredMemoryState, as ONE pure pytree.

        The returned tree checkpoints directly through ``ckpt/manager.py``;
        a restored server resumes with a warm placement map.  The host-side
        pending FIFOs are best-effort (DESIGN.md §6) and not included — they
        are re-derived from the next sketch epoch after restore.

        Any in-flight async epoch is FINALIZED (force-committed) first: the
        persisted placement map is the control table, so the payload the
        checkpoint implies must match it deterministically (DESIGN.md §15).
        """
        self.finalize()
        return {n: h.state for n, h in self.resources.items()}

    def finalize(self) -> None:
        """Force-commit every in-flight async epoch (accounting barrier:
        checkpoint save, benchmark end-of-run byte parity, shutdown)."""
        for h in self.resources.values():
            h.mem.finalize_epoch(h.stats)

    def load_state(self, states: dict[str, TieredMemoryState]) -> None:
        """Restore a ``state_dict()`` pytree into the registered resources.

        Structure and leaf shapes must match the registered geometry.  For
        resources with bound payload buffers, the fast copies of every
        resident page are re-gathered from the slow store, so the restored
        placement map never serves a cold fast row.
        """
        for name, st in states.items():
            if name not in self.resources:
                raise KeyError(f"state for unregistered resource {name!r}")
            h = self.resources[name]
            if jax.tree.structure(st) != jax.tree.structure(h.state):
                raise ValueError(
                    f"{name}: checkpointed state structure does not match")
            for cur, new in zip(jax.tree.leaves(h.state),
                                jax.tree.leaves(st)):
                if jnp.shape(cur) != jnp.shape(new):
                    raise ValueError(
                        f"{name}: leaf shape {jnp.shape(new)} != registered "
                        f"geometry {jnp.shape(cur)}")
            h.state = jax.tree.map(
                lambda cur, new: jnp.asarray(new, jnp.asarray(cur).dtype), h.state, st)
            # the pending backlog belongs to the PRE-restore stream — keeping
            # it would promote stale pages into the restored placement map,
            # and so does any issued-but-uncommitted epoch: DROP it (the
            # deterministic half of commit-or-drop, DESIGN.md §15)
            h.mem.clear_pending()
            h.stats.pending = 0
            h.mem.drop_inflight(h.stats)
            h.mem.refill_fast(h.state)
            h.mem.reset_committed(h.state)

    # -- telemetry -----------------------------------------------------------
    def stats(self) -> dict[str, TierStats]:
        return {n: h.stats for n, h in self.resources.items()}

    def hit_rates(self) -> dict[str, float]:
        return {n: h.hit_rate() for n, h in self.resources.items()}

    def snapshot(self) -> dict[str, dict]:
        """Per-resource flat telemetry rows (benchmark / logging schema)."""
        return {n: h.snapshot() for n, h in self.resources.items()}
