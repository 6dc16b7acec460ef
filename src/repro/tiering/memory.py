"""TieredMemory — profiling + placement for ONE resource, as a pytree facade.

Replaces the mutable ``self.prof`` / ``self.tier`` pattern of the old
adapters: all device-resident state (NeoProf sketch/buffers, TieredStore
placement, Algorithm-1 scalars) lives in a single :class:`TieredMemoryState`
pytree threaded through pure functions, so profiling composes with
jit/pjit/shard_map.  The split mirrors the paper's hardware/software line:

  * :func:`observe` / :func:`lookup` — pure, jittable, run inside the model
    step (the device side: NeoProf snoop + tier hit accounting);
  * :meth:`TieredMemory.tick` — host side, runs the daemon cadences
    (migration << threshold-update <= clear, paper §V) against the state and
    returns promotion batches for the owner to apply.

The host side keeps exactly two non-pytree artifacts: the overflow queue of
hot pages awaiting quota (a numpy FIFO, as in the kernel daemon) and the
:class:`~repro.tiering.stats.TierStats` telemetry accumulator — plus, when
payload data is bound via :meth:`TieredMemory.bind_data`, the
:class:`~repro.tiering.migrate.TierBuffers` pair the migration data plane
copies through (DESIGN.md §8: one fused donated copy per epoch, bytes
metered against the per-epoch quota).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tiering
from repro.core.neoprof import (NeoProfCommands, NeoProfParams, NeoProfState,
                                neoprof_init, neoprof_observe)
from repro.core.policy import PolicyParams, PolicyState
from repro.core.policy import update_threshold as _algorithm1
from repro.core.tiering import TierParams, TierState
from repro.spans import pull, span
from repro.tiering import codec as codec_lib
from repro.tiering import migrate as migrate_lib
from repro.tiering.stats import TierStats, drain_tier_stats
from repro.tiering.stats import hit_rate as _hit_rate

MAX_PENDING = 1 << 14        # overflow queue bound (pages awaiting quota)


@dataclasses.dataclass
class DaemonParams:
    """Cadence hierarchy (DESIGN.md §1.3): migration ticks are the base rate.

    ``quota_pages=None`` resolves context-dependently: a single-resource
    TieredMemory uses its TierParams quota; the multiplexed daemon uses the
    sum of its resources' quotas as the shared budget.
    """

    migration_interval: int = 1        # ticks between promotion batches
    threshold_update_period: int = 8   # ticks between Algorithm-1 runs
    clear_interval: int = 64           # ticks between sketch resets
    quota_pages: int | None = None     # promotion budget per interval
    # Asynchronous data plane (DESIGN.md §15): epochs are ISSUED as
    # non-donated async copies and COMMITTED by pointer swap at a later
    # tick, once the copy's readiness token is witnessed — decode keeps
    # reading the previous epoch's committed views in between.
    async_plane: bool = False


class TieredMemoryState(NamedTuple):
    """Everything the tiering layer knows about one resource, as one pytree."""

    prof: NeoProfState   # NeoProf: sketch + hot buffer + state monitor (+ θ)
    tier: TierState      # TieredStore: placement maps + 2Q bits + counters
    p: jax.Array         # () f32 — Algorithm-1 hot-fraction scalar
    tick: jax.Array      # () i32 — daemon tick counter


@dataclasses.dataclass
class MigrationEvent:
    """One promotion batch: copy slow[promoted[i]] into fast victims[i],
    after writing the slot's previous occupant ``evicted[i]`` back down."""

    promoted: jax.Array   # (k,) int32 page ids, -1 = no-op lane
    victims: jax.Array    # (k,) int32 slot ids, -1 = no-op lane
    n_promoted: int
    evicted: jax.Array | None = None   # (k,) int32 demoted page ids, -1 no-op


@dataclasses.dataclass
class InFlightEpoch:
    """One issued-but-uncommitted migration epoch (DESIGN.md §15).

    ``fast`` is the NEXT epoch's fast buffer, produced by a non-donated
    async gather (:func:`migrate.issue_migrate`); ``page_slot`` the
    placement table it was built against (the control state already
    points at it — decode keeps reading the previous committed table
    until the pointer swap).  ``token`` is the cheap device→host
    readiness witness: a () int32 from the same XLA executable as the
    copy, so ``token.is_ready()`` implies the buffer is materialized.
    """

    fast: jax.Array
    page_slot: jax.Array
    token: jax.Array
    bytes: int


@functools.partial(jax.jit, static_argnames=("prof_params",))
def observe(
    state: TieredMemoryState,
    pages: jax.Array,
    prof_params: NeoProfParams,
    touch_pages: jax.Array | None = None,
    rd_bytes=0.0, wr_bytes=0.0, budget_bytes=0.0,
) -> TieredMemoryState:
    """Pure device-side step: NeoProf snoop + tier hit/2Q accounting.

    ``touch_pages`` lets callers profile one stream but account hits on a
    (typically capped) other — defaults to ``pages``.
    """
    prof = neoprof_observe(state.prof, pages, prof_params,
                           rd_bytes=rd_bytes, wr_bytes=wr_bytes,
                           budget_bytes=budget_bytes)
    tier = tiering.touch(state.tier,
                         pages if touch_pages is None else touch_pages)
    return state._replace(prof=prof, tier=tier)


def lookup(state: TieredMemoryState,
           page_ids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Pure: (fast-slot or -1, hit mask) for a batch of page ids."""
    return tiering.lookup(state.tier, page_ids)


class TieredMemory:
    """Facade owning the params + host-side daemon verbs for one resource.

    Construct from explicit params or via ``ResourceSpec.memory()`` /
    ``TieredMemory.from_spec`` — either way ONE object sources the prof,
    tier, and quota geometry (no way to hand the daemon a different
    TierParams than the tier was initialized with).
    """

    def __init__(
        self,
        prof_params: NeoProfParams,
        tier_params: TierParams,
        daemon_params: DaemonParams | None = None,
        policy_params: PolicyParams | None = None,
        fixed_theta: int | None = None,
    ):
        self.pp = prof_params
        self.tp = tier_params
        self.dp = daemon_params or DaemonParams()
        self.quota = (self.dp.quota_pages if self.dp.quota_pages is not None
                      else tier_params.quota_pages)
        # policy quota bound: 4x migration capacity per update period
        # (equal-to-capacity degenerates into p starve/flood oscillation)
        self.pol_params = policy_params or PolicyParams(
            m_quota_pages=4 * self.quota * max(
                1, self.dp.threshold_update_period // self.dp.migration_interval))
        self.fixed_theta = fixed_theta
        self.cmd = NeoProfCommands(prof_params)
        self._pending = np.empty((0,), np.int64)
        # migration data plane (DESIGN.md §8) — absent until bind_data
        self.spec = None
        self.buffers: migrate_lib.TierBuffers | None = None
        self.codec = "none"          # slow-store wire format (DESIGN.md §14)
        self.row_bytes = 0           # WIRE bytes per page once data is bound
        self.quota_bytes = 0
        # per-page write witness (None until bind_data): see pages_written
        self.written: np.ndarray | None = None
        # async data plane (DESIGN.md §15): the issued-but-uncommitted
        # epoch, and the placement table decode reads until it commits
        self._inflight: InFlightEpoch | None = None
        self._committed_slot: jax.Array | None = None

    @property
    def _name(self) -> str:
        """The resource's name, for span attributes ("" without a spec)."""
        return self.spec.name if self.spec is not None else ""

    @classmethod
    def from_spec(cls, spec, daemon_params=None, policy_params=None,
                  fixed_theta=None) -> "TieredMemory":
        mem = cls(spec.prof_params(), spec.tier_params(),
                  daemon_params=daemon_params, policy_params=policy_params,
                  fixed_theta=fixed_theta)
        mem.spec = spec
        mem.codec = codec_lib.check_codec(getattr(spec, "slow_codec", "none"))
        return mem

    # -- data plane (DESIGN.md §8) -------------------------------------------
    def bind_data(self, slow_data, initially_valid: bool = True,
                  codec: str | None = None) -> None:
        """Attach payload buffers: ``slow_data`` is (num_pages, *row_shape),
        always in the resource's NATIVE dtype — the slow store is encoded to
        ``codec``'s wire format here (default: the spec's ``slow_codec``;
        DESIGN.md §14), and ``row_bytes`` / ``quota_bytes`` meter WIRE bytes
        from then on.

        After binding, every promotion epoch physically moves rows between
        the fast/slow buffers (:meth:`apply_migration`) and meters the bytes;
        without it the resource stays placement/telemetry-only.

        ``initially_valid=False`` marks every page as not-yet-written: the
        store starts as zero-filled scratch (the KV slow store) and a page
        only becomes *resident* once a write verb lands on it.  The
        :meth:`pages_written` witness backs the disaggregated hand-off's
        segment-residency gate (DESIGN.md §13) — a decode worker must never
        admit a request whose segment the prefill worker has not finished
        flushing.
        """
        slow_data = jnp.asarray(slow_data)
        if slow_data.shape[0] != self.tp.num_pages:
            raise ValueError(
                f"slow_data has {slow_data.shape[0]} pages, tier declares "
                f"{self.tp.num_pages}")
        if self.spec is not None and self.spec.row_shape is not None:
            want = (tuple(self.spec.row_shape), jnp.dtype(self.spec.row_dtype))
            got = (tuple(slow_data.shape[1:]), slow_data.dtype)
            if want != got:
                raise ValueError(
                    f"slow_data rows {got} != ResourceSpec declaration {want}")
        if codec is not None:
            self.codec = codec_lib.check_codec(codec)
        self.buffers = migrate_lib.init_buffers(slow_data, self.tp.num_slots,
                                                codec=self.codec)
        self.row_bytes = migrate_lib.row_bytes(self.buffers)
        self.quota_bytes = 2 * self.quota * self.row_bytes
        self.written = np.full(self.tp.num_pages, bool(initially_valid))

    def apply_migration(self, event: MigrationEvent | None,
                        stats: TierStats) -> int:
        """Execute one epoch's data movement against the bound buffers.

        Returns the WIRE bytes moved (promotions + demotion write-backs, at
        the codec's at-rest row size), metered into ``stats`` against the
        per-epoch byte quota.  A no-op
        (no buffers bound, or an empty event) moves and meters nothing.
        """
        if self.buffers is None or event is None:
            return 0
        evicted = (event.evicted if event.evicted is not None
                   else jnp.full_like(jnp.asarray(event.victims), -1))
        with span("tier/stall", resource=self._name) as sp:
            self.buffers, n_up, n_down = migrate_lib.migrate(
                self.buffers, event.promoted, event.victims, evicted,
                codec=self.codec)
            # the synchronous arm stops the world: the donated fused copy
            # must land before the next decode step can read the swapped
            # buffers — that wait is exactly the stall the async plane
            # (§15) removes
            jax.block_until_ready(self.buffers.fast)
        stats.stall_s += sp.elapsed
        moved = (n_up + n_down) * self.row_bytes
        stats.migration_bytes += moved
        stats.last_epoch_bytes = moved
        stats.max_epoch_bytes = max(stats.max_epoch_bytes, moved)
        stats.quota_bytes = self.quota_bytes
        if moved:
            stats.migration_epochs += 1
        return moved

    # -- async data plane (DESIGN.md §15) ------------------------------------
    @property
    def async_on(self) -> bool:
        """Whether this resource runs the double-buffered async plane."""
        return self.dp.async_plane and self.buffers is not None

    @property
    def busy(self) -> bool:
        """An epoch is issued but not yet committed — the daemon must not
        issue N+2 (and excludes this resource from the quota split)."""
        return self._inflight is not None

    def _view_slot(self, state: TieredMemoryState) -> jax.Array:
        """The placement table READS resolve against: the committed epoch's
        snapshot under the async plane, the live control table otherwise."""
        if self.async_on and self._committed_slot is not None:
            return self._committed_slot
        return state.tier.page_slot

    def lookup_slots(self, state: TieredMemoryState, page_ids) -> jax.Array:
        """Placement lookup against the COMMITTED view (== tiering.lookup's
        slots under the synchronous plane)."""
        ps = self._view_slot(state)
        ids = jnp.asarray(page_ids, jnp.int32)
        return jnp.where(ids >= 0, ps[jnp.maximum(ids, 0)], -1)

    def issue_migration(self, state: TieredMemoryState,
                        event: MigrationEvent | None,
                        stats: TierStats) -> int:
        """Issue phase: dispatch the epoch's promotion gather WITHOUT
        blocking and record the in-flight epoch.  ``state`` is the
        post-promote control state (its ``page_slot`` is the table the new
        buffer is built against).  Returns the epoch's wire bytes, metered
        as ``inflight_bytes`` until :meth:`commit_migration` folds them
        into the lifetime counters.

        The demotion write-back is ELIDED here: under the write-both-tiers
        rule every fast row already has a byte-identical slow copy, so the
        write-back would be a rewrite of identical bytes.  Its wire cost is
        still metered — the epoch moves the same bytes either way.
        """
        if self.buffers is None or event is None:
            return 0
        if self._inflight is not None:
            raise RuntimeError(
                "migration epoch already in flight — commit (or drop) epoch "
                "N+1 before issuing N+2")
        # host-side byte accounting off the tiny promote outputs (these are
        # products of tiering.promote's executable, NOT the bulk copy — the
        # pulls below never wait on payload movement)
        ok = ((pull(event.promoted, "epoch_plan") >= 0)
              & (pull(event.victims, "epoch_plan") >= 0))
        if event.evicted is not None:
            n_down = int(np.sum(ok & (pull(event.evicted, "epoch_plan") >= 0)))
        else:
            n_down = 0
        new_fast, token = migrate_lib.issue_migrate(
            self.buffers, event.promoted, event.victims)
        moved = (int(np.sum(ok)) + n_down) * self.row_bytes
        self._inflight = InFlightEpoch(fast=new_fast,
                                       page_slot=state.tier.page_slot,
                                       token=token, bytes=moved)
        stats.inflight_bytes = moved
        stats.quota_bytes = self.quota_bytes
        return moved

    def commit_ready(self) -> bool:
        """Non-blocking probe: has the in-flight epoch's copy landed?"""
        return (self._inflight is not None
                and migrate_lib.token_ready(self._inflight.token))

    def commit_migration(self, stats: TierStats, block: bool = False) -> int:
        """Commit phase: pointer-swap the in-flight epoch's buffer + table
        into the committed view and fold its bytes into the lifetime
        counters.  Without ``block`` this is a no-op unless the readiness
        token is already witnessed — the swap NEVER waits; ``block=True``
        forces the commit (checkpoint finalize, sync fallback) and meters
        the wait as ``stall_s``."""
        fl = self._inflight
        if fl is None:
            return 0
        if not migrate_lib.token_ready(fl.token):
            if not block:
                return 0
            with span("tier/stall", resource=self._name) as sp:
                jax.block_until_ready(fl.fast)
            stats.stall_s += sp.elapsed
        self.buffers = self.buffers._replace(fast=fl.fast)
        self._committed_slot = fl.page_slot
        self._inflight = None
        moved = fl.bytes
        stats.inflight_bytes = 0
        stats.migration_bytes += moved
        stats.last_epoch_bytes = moved
        stats.max_epoch_bytes = max(stats.max_epoch_bytes, moved)
        stats.quota_bytes = self.quota_bytes
        if moved:
            stats.migration_epochs += 1
        return moved

    def finalize_epoch(self, stats: TierStats) -> int:
        """Force-commit any in-flight epoch (checkpoint save: the persisted
        placement map is the control table, so the payload must match)."""
        return self.commit_migration(stats, block=True)

    def drop_inflight(self, stats: TierStats | None = None) -> None:
        """Abandon the in-flight epoch (checkpoint restore: the issued copy
        belongs to the pre-restore placement stream)."""
        self._inflight = None
        if stats is not None:
            stats.inflight_bytes = 0

    def reset_committed(self, state: TieredMemoryState) -> None:
        """Align the committed view with the control state (restore path):
        no epoch is in flight and decode reads the live table."""
        self._inflight = None
        self._committed_slot = (state.tier.page_slot if self.async_on
                                else None)

    def dispatch_migration(self, state: TieredMemoryState,
                           event: MigrationEvent | None,
                           stats: TierStats) -> int:
        """Route one epoch's data movement: async issue or sync apply."""
        if self.async_on:
            return self.issue_migration(state, event, stats)
        return self.apply_migration(event, stats)

    def _inflight_slots(self, page_ids) -> jax.Array:
        ps = self._inflight.page_slot
        ids = jnp.asarray(page_ids, jnp.int32)
        return jnp.where(ids >= 0, ps[jnp.maximum(ids, 0)], -1)

    def refill_fast(self, state: TieredMemoryState) -> None:
        """Re-gather the fast copy of every resident page from the slow store.

        Used after restoring a checkpointed placement map (DESIGN.md §6):
        the restored ``TierState`` says which pages are resident, but the
        rebuilt fast buffer is cold — without the refill, ``read_rows``
        would serve stale rows for pages the map calls hits.  A no-op when
        no payload is bound.
        """
        if self.buffers is None:
            return
        # a restored store is assumed fully materialized: the write witnesses
        # that produced it did not survive the checkpoint, the payload did
        if self.written is not None:
            self.written[:] = True
        slot_page = np.asarray(state.tier.slot_page)
        occupied = np.flatnonzero(slot_page >= 0)
        if occupied.size == 0:
            return
        rows = migrate_lib.gather_rows(self.buffers, slot_page[occupied])
        fast = self.buffers.fast.at[occupied].set(rows)
        self.buffers = self.buffers._replace(fast=fast)

    def lookup_rows(self, state: TieredMemoryState, page_ids) -> jax.Array:
        """Pure, jittable read path: placement-table gather over the bound
        buffers with in-trace slow fallback (:func:`migrate.lookup_rows`).
        Safe to call INSIDE a jitted step — the placement map
        (``state.tier.page_slot``) and both buffers are device arrays, so
        the read costs one fused gather and no host round-trip.  For a
        jit-compatible argument pytree, see :meth:`tier_view`."""
        if self.buffers is None:
            raise ValueError("no payload bound — call bind_data() first")
        return migrate_lib.lookup_rows(self.buffers.fast, self.buffers.slow,
                                       self._view_slot(state), page_ids,
                                       scale=self.buffers.scale)

    def tier_view(self, state: TieredMemoryState) -> dict[str, jax.Array]:
        """The device-array pytree an in-jit consumer threads into its step:
        ``{"fast", "slow", "page_slot", "scale"}`` (``scale`` is ``None``
        except under the ``int8`` codec — a valid pytree leaf either way) —
        pass these as jit ARGUMENTS (not closure constants) so daemon epochs
        swap buffers without retracing."""
        if self.buffers is None:
            raise ValueError("no payload bound — call bind_data() first")
        return {"fast": self.buffers.fast, "slow": self.buffers.slow,
                "page_slot": self._view_slot(state),
                "scale": self.buffers.scale}

    def read_rows(self, state: TieredMemoryState, page_ids,
                  slots: jax.Array | None = None) -> jax.Array:
        """Serve page payloads: fast-tier copy on hit, slow-tier fallback.

        The gathers are partitioned host-side by the hit mask, so fast-tier
        hits never touch the slow store — on real hardware a 100% hit batch
        costs zero pinned-host bandwidth.  (:func:`migrate.read_rows` is the
        fused single-gather variant for in-jit consumers.)  ``slots`` lets a
        caller that already looked the ids up (e.g. the daemon handle's read
        metering) skip the second placement lookup.
        """
        if self.buffers is None:
            raise ValueError("no payload bound — call bind_data() first")
        page_ids = jnp.asarray(page_ids, jnp.int32)
        if slots is None:
            slots = self.lookup_slots(state, page_ids)
        slots_np = pull(slots, "lookup_slots")
        ids_np = np.maximum(pull(page_ids, "lookup_slots"), 0)
        hit = slots_np >= 0
        if hit.all():
            return self.buffers.fast[slots]
        if not hit.any():
            return migrate_lib.gather_rows(self.buffers, ids_np)
        rows = jnp.empty(page_ids.shape + self.buffers.fast.shape[1:],
                         self.buffers.fast.dtype)
        rows = rows.at[np.flatnonzero(hit)].set(
            self.buffers.fast[slots_np[hit]])
        return rows.at[np.flatnonzero(~hit)].set(
            migrate_lib.gather_rows(self.buffers, ids_np[~hit]))

    def write_rows(self, state: TieredMemoryState, page_ids, rows) -> int:
        """Refresh page payloads in both tiers (owners with mutating data):
        the slow store always takes the write, fast copies of promoted pages
        are refreshed for coherence.  Returns the rows written."""
        if self.buffers is None:
            raise ValueError("no payload bound — call bind_data() first")
        page_ids = jnp.asarray(page_ids, jnp.int32)
        slots = self.lookup_slots(state, page_ids)
        self.buffers = migrate_lib.write_rows(self.buffers, page_ids, slots,
                                              rows, codec=self.codec)
        if self._inflight is not None:
            # replay onto the in-flight epoch's buffer under ITS table, so a
            # page promoted by the issued-but-uncommitted copy does not keep
            # a stale fast row past the commit (DESIGN.md §15)
            self._inflight.fast = migrate_lib.refresh_rows(
                self._inflight.fast, self._inflight_slots(page_ids), rows)
        return self._mark_written(page_ids)

    def write_pages(self, state: TieredMemoryState, page_ids, k_pages,
                    v_pages) -> int:
        """Bulk KV ring-page flush (:func:`migrate.write_pages`): the
        written slots' gather, [K|V] concat and dual-tier scatter fuse in
        one donated jit — the chunked-prefill data-plane verb.  ``k_pages``
        / ``v_pages`` are (G, L, S, T, hkv, d) ring views; ``page_ids`` the
        (L*S,) slot map (-1 = not written), compacted here so only written
        pages move.  Returns the pages written."""
        if self.buffers is None:
            raise ValueError("no payload bound — call bind_data() first")
        ring, page_ids = migrate_lib.ring_selection(page_ids)
        slots = self.lookup_slots(state, page_ids)
        self.buffers = migrate_lib.write_pages(self.buffers, page_ids, slots,
                                               ring, k_pages, v_pages,
                                               codec=self.codec)
        if self._inflight is not None:
            self._inflight.fast = migrate_lib.refresh_pages(
                self._inflight.fast, self._inflight_slots(page_ids), ring,
                k_pages, v_pages)
        return self._mark_written(page_ids)

    def copy_rows(self, state: TieredMemoryState, src_ids, dst_ids) -> int:
        """Duplicate page payloads store-to-store (`migrate.copy_rows`):
        the content-addressed publish path copies a finished request's
        segment pages into shared pool pages in one fused donated op.
        Returns the pages copied."""
        if self.buffers is None:
            raise ValueError("no payload bound — call bind_data() first")
        src_ids = jnp.asarray(src_ids, jnp.int32)
        dst_ids = jnp.asarray(dst_ids, jnp.int32)
        dst_slots = self.lookup_slots(state, dst_ids)
        self.buffers = migrate_lib.copy_rows(self.buffers, src_ids, dst_ids,
                                             dst_slots)
        if self._inflight is not None:
            self._inflight.fast = migrate_lib.refresh_copy(
                self._inflight.fast, self.buffers.slow, self.buffers.scale,
                src_ids, self._inflight_slots(dst_ids))
        src_np, dst_np = pull(src_ids, "copy_ids"), pull(dst_ids, "copy_ids")
        valid = (src_np >= 0) & (dst_np >= 0)
        if self.written is not None:
            self.written[dst_np[valid]] = True
        return int(np.sum(valid))

    def _mark_written(self, page_ids) -> int:
        """Record the write witnesses for a batch of page ids (-1 dropped)."""
        ids = np.asarray(page_ids)
        ids = ids[ids >= 0]
        if self.written is not None and ids.size:
            self.written[ids] = True
        return int(ids.size)

    def pages_written(self, page_ids) -> np.ndarray:
        """Per-page write witness: True where a write verb has landed since
        binding (or where the payload was valid at bind time).  The
        segment-residency query behind disaggregated decode admission
        (DESIGN.md §13); invalid ids (< 0) report False."""
        if self.written is None:
            raise ValueError("no payload bound — call bind_data() first")
        ids = np.asarray(page_ids, np.int64)
        out = np.zeros(ids.shape, bool)
        valid = (ids >= 0) & (ids < self.written.shape[0])
        out[valid] = self.written[ids[valid]]
        return out

    # -- state ---------------------------------------------------------------
    def init(self, key: jax.Array | None = None) -> TieredMemoryState:
        prof = neoprof_init(self.pp, key)
        theta0 = (self.fixed_theta if self.fixed_theta is not None
                  else self.pol_params.theta_min)
        return TieredMemoryState(
            prof=self.cmd.set_threshold(prof, theta0),
            tier=tiering.tier_init(self.tp),
            p=jnp.float32(self.pol_params.p_init),
            tick=jnp.zeros((), jnp.int32),
        )

    def observe(self, state: TieredMemoryState, pages, *, touch_pages=None,
                rd_bytes=0.0, wr_bytes=0.0, budget_bytes=0.0) -> TieredMemoryState:
        return observe(state, pages, self.pp, touch_pages=touch_pages,
                       rd_bytes=rd_bytes, wr_bytes=wr_bytes,
                       budget_bytes=budget_bytes)

    def profile(self, state: TieredMemoryState, pages, *, rd_bytes=0.0,
                wr_bytes=0.0, budget_bytes=0.0) -> TieredMemoryState:
        """NeoProf snoop only (callers that account tier hits separately)."""
        return state._replace(prof=neoprof_observe(
            state.prof, pages, self.pp, rd_bytes=rd_bytes, wr_bytes=wr_bytes,
            budget_bytes=budget_bytes))

    def touch(self, state: TieredMemoryState, pages) -> TieredMemoryState:
        """Tier hit/2Q accounting only."""
        return state._replace(tier=tiering.touch(state.tier, pages))

    def policy_state(self, state: TieredMemoryState,
                     stats: TierStats | None = None) -> PolicyState:
        """Reconstruct the Algorithm-1 view from the pytree (+ telemetry)."""
        last = lambda tr, d: tr[-1] if stats is not None and tr else d
        return PolicyState(
            p=float(pull(state.p, "policy")),
            theta=int(pull(state.prof.theta, "policy")),
            last_B=last(stats.bw_trace if stats else [], 0.0),
            last_P=last(stats.pp_trace if stats else [], 0.0),
            last_E=int(last(stats.err_trace if stats else [], 0)),
        )

    def hit_rate(self, state: TieredMemoryState, stats: TierStats) -> float:
        return _hit_rate(state.tier, stats)

    # -- daemon verbs (host side) ---------------------------------------------
    def collect(self, state: TieredMemoryState,
                stats: TierStats) -> tuple[TieredMemoryState, int]:
        """Drain NeoProf's hot buffer into the pending FIFO; return demand."""
        prof, hot = self.cmd.drain_hotpages(state.prof)
        self.enqueue(hot)
        stats.pending = len(self._pending)
        return state._replace(prof=prof), len(self._pending)

    def clear_pending(self) -> None:
        """Drop the host-side overflow queue (e.g. on checkpoint restore:
        the backlog belongs to the pre-restore stream, DESIGN.md §6)."""
        self._pending = np.empty((0,), np.int64)

    def enqueue(self, pages) -> None:
        """Queue externally-detected hot pages (baseline profilers, tests)."""
        self._pending = np.concatenate(
            [self._pending, np.asarray(pages, np.int64)])[: 4 * MAX_PENDING]

    def migrate(self, state: TieredMemoryState, stats: TierStats,
                quota: int | None = None,
                ) -> tuple[TieredMemoryState, MigrationEvent | None]:
        """Promote up to ``quota`` pending pages (batch width stays static)."""
        k = self.quota                       # static promote width (no retrace)
        if self.async_on:
            # first promote under the async plane: snapshot the pre-promote
            # table as epoch 0's committed view — from here on the control
            # table runs ahead of what decode reads until each commit
            if self._committed_slot is None:
                self._committed_slot = state.tier.page_slot
        else:
            stats.last_epoch_bytes = 0  # an epoch that moves nothing reports 0
        take = min(quota if quota is not None else k, k, len(self._pending))
        if take <= 0:
            stats.pending = len(self._pending)
            return state, None
        batch = np.full((k,), -1, np.int32)
        batch[:take] = self._pending[:take]
        self._pending = self._pending[take:][:MAX_PENDING]
        old_slot_page = state.tier.slot_page
        tier, promoted, victims = tiering.promote(
            state.tier, jnp.asarray(batch), k)
        # the page each victim slot held BEFORE this batch — the demotion
        # write-back targets for the data plane (apply_migration)
        evicted = jnp.where(victims >= 0,
                            old_slot_page[jnp.maximum(victims, 0)], -1)
        n = int(np.sum(pull(promoted, "epoch_plan") >= 0))
        stats.migrated_this_period += n
        stats.pending = len(self._pending)
        return state._replace(tier=tier), MigrationEvent(promoted, victims, n,
                                                         evicted=evicted)

    def drain(self, state: TieredMemoryState,
              stats: TierStats) -> TieredMemoryState:
        """Drain tier period counters into stats (the one shared code path)."""
        return state._replace(tier=drain_tier_stats(state.tier, stats))

    def update_threshold(self, state: TieredMemoryState,
                         stats: TierStats) -> TieredMemoryState:
        """One Algorithm-1 period: read NeoProf, drain stats, retune θ."""
        hist = self.cmd.get_hist(state.prof)
        bw = self.cmd.bandwidth_util(state.prof)
        err = self.cmd.get_error_bound(state.prof, hist)
        state = self.drain(state, stats)
        period = stats.last_period
        # Laplace-damped: a single bounce at low volume must not crash p
        pp_ratio = float(period["ping_pong"]) / max(
            int(period["promoted"]), self.quota // 2, 1)
        if self.fixed_theta is None:
            # M = migration DEMAND (migrated + still-queued): Alg.1's quota
            # constraint throttles when demand exceeds capacity, not merely
            # when the migrator runs at capacity.
            demand = stats.migrated_this_period + len(self._pending)
            pol = _algorithm1(
                PolicyState(p=float(pull(state.p, "policy")),
                            theta=int(pull(state.prof.theta, "policy"))),
                self.pol_params, hist, bandwidth_util=bw,
                ping_pong_ratio=pp_ratio, migrated_pages=demand,
                error_bound=err)
            state = state._replace(
                prof=self.cmd.set_threshold(state.prof, pol.theta),
                p=jnp.float32(pol.p))
        stats.migrated_this_period = 0
        stats.theta_trace.append(int(pull(state.prof.theta, "policy")))
        stats.bw_trace.append(float(bw))
        stats.pp_trace.append(pp_ratio)
        stats.err_trace.append(int(err))
        stats.p_trace.append(float(pull(state.p, "policy")))
        return state

    def clear(self, state: TieredMemoryState) -> TieredMemoryState:
        return state._replace(prof=self.cmd.reset(state.prof))

    def tick(self, state: TieredMemoryState, stats: TierStats,
             ) -> tuple[TieredMemoryState, MigrationEvent | None]:
        """Single-resource cadence driver (the multiplexed daemon drives the
        verbs itself so it can split the quota budget across resources)."""
        state = state._replace(tick=state.tick + 1)
        t, dp, event = int(pull(state.tick, "policy")), self.dp, None
        if t % dp.migration_interval == 0:
            if self.async_on:
                self.commit_migration(stats)   # commit FIRST, never blocks
            state, _ = self.collect(state, stats)
            if not self.busy:                  # no N+2 issue before N+1 commit
                state, event = self.migrate(state, stats)
                self.dispatch_migration(state, event, stats)
        if t % dp.threshold_update_period == 0:
            state = self.update_threshold(state, stats)
        if t % dp.clear_interval == 0:
            state = self.clear(state)
        return state, event
