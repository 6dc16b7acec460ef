"""TieredStore — two-tier page placement with promotion / 2Q demotion.

The TPU-native analogue of the paper's fast (DRAM) / slow (CXL) NUMA pair:
a fixed pool of fast-tier *slots* (HBM-resident cache buffers) in front of a
slow-tier *backing store* (pinned host memory — see DESIGN.md §7).

Faithful pieces:
  * promotion of NeoProf-reported hot pages, bounded by the migration quota;
  * cold-page demotion via the kernel's LRU-2Q — adapted to a vectorized
    rank eviction with the same preference order
    (free < inactive-unreferenced < inactive-ref < active-unref < active-ref,
    ties by last touch).  New promotions enter the inactive (A1in) list and
    graduate to active (Am) on re-reference, exactly as 2Q;
  * the ``PG_demoted`` ping-pong flag: a promotion of a previously-demoted
    page counts as a ping-pong event (policy input P).

Everything is a pytree of device arrays updated by jitted pure functions, so
tier management composes with pjit/shard_map and never leaves the device.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class TierParams(NamedTuple):
    num_pages: int           # logical pages in the slow tier's address space
    num_slots: int           # fast-tier capacity (pages)
    quota_pages: int = 4096  # max promotions per migration interval


class TierState(NamedTuple):
    page_slot: jax.Array    # (num_pages,) int32 -> slot id, -1 if slow-tier
    slot_page: jax.Array    # (num_slots,) int32 -> page id, -1 if free
    active: jax.Array       # (num_slots,) bool — 2Q list: False=A1in, True=Am
    referenced: jax.Array   # (num_slots,) bool — touched since last scan
    last_touch: jax.Array   # (num_slots,) int32 — step of last touch
    demoted: jax.Array      # (num_pages,) bool — PG_demoted flag
    step: jax.Array         # () int32
    # Period statistics (drained by the daemon each policy interval).
    promoted: jax.Array     # () int32
    demoted_cnt: jax.Array  # () int32
    ping_pong: jax.Array    # () int32
    slow_reads: jax.Array   # () int32 — page-granular slow-tier read count
    fast_reads: jax.Array   # () int32


def tier_init(params: TierParams) -> TierState:
    z = jnp.zeros((), jnp.int32)
    return TierState(
        page_slot=jnp.full((params.num_pages,), -1, jnp.int32),
        slot_page=jnp.full((params.num_slots,), -1, jnp.int32),
        active=jnp.zeros((params.num_slots,), jnp.bool_),
        referenced=jnp.zeros((params.num_slots,), jnp.bool_),
        last_touch=jnp.zeros((params.num_slots,), jnp.int32),
        demoted=jnp.zeros((params.num_pages,), jnp.bool_),
        step=z, promoted=z, demoted_cnt=z, ping_pong=z,
        slow_reads=z, fast_reads=z,
    )


@jax.jit
def touch(state: TierState, page_ids: jax.Array) -> TierState:
    """Record accesses: hit/miss counts + 2Q reference/A1->Am graduation."""
    valid = page_ids >= 0
    slots = state.page_slot[jnp.where(valid, page_ids, 0)]
    hit = valid & (slots >= 0)
    n_slots = state.slot_page.shape[0]
    # misses scatter to an out-of-bounds index and are DROPPED — routing
    # them to index 0 would race with legitimate writes to slot 0.
    idx = jnp.where(hit, slots, n_slots)
    safe_slots = jnp.where(hit, slots, 0)
    upd = lambda arr, val: arr.at[idx].set(val, mode="drop")
    # re-referenced pages graduate to the active list (2Q A1 -> Am)
    new_active = upd(state.active, state.referenced[safe_slots] | state.active[safe_slots])
    new_ref = upd(state.referenced, jnp.ones_like(hit))
    new_lt = upd(state.last_touch, jnp.broadcast_to(state.step, hit.shape))
    return state._replace(
        active=new_active, referenced=new_ref, last_touch=new_lt,
        fast_reads=state.fast_reads + jnp.sum(hit, dtype=jnp.int32),
        slow_reads=state.slow_reads + jnp.sum(valid & ~hit, dtype=jnp.int32),
        step=state.step + 1,
    )


def _victim_rank(state: TierState) -> jax.Array:
    """2Q eviction preference as a sortable key (lower = evict first).

    Class order: free(0) < A1-unref(1) < A1-ref(2) < Am-unref(3) < Am-ref(4),
    i.e. occupied slots rank 1 + 2*active + referenced.
    """
    free = state.slot_page < 0
    klass = jnp.where(
        free, 0,
        1 + 2 * state.active.astype(jnp.int32)
        + state.referenced.astype(jnp.int32))
    # within a class, older last_touch evicts first (int32-safe packing:
    # class in the top bits, wrapped step counter below)
    return klass.astype(jnp.int32) * (1 << 24) + (state.last_touch & ((1 << 24) - 1))


@functools.partial(jax.jit, static_argnames=("k",))
def promote(
    state: TierState,
    hot_pages: jax.Array,   # (k,) int32, -1 padded — drained NeoProf buffer
    k: int,
) -> tuple[TierState, jax.Array, jax.Array]:
    """Promote up to k hot pages (quota already applied by the daemon).

    Returns (state, promoted_page_ids (k,), victim_slots (k,)): entry i says
    "copy slow[promoted[i]] into fast slot victim_slots[i]" (-1 = no-op), and
    the evicted page (if any) was written back.  Data movement is performed
    by the caller against its fast/slow buffers so this module stays
    data-layout agnostic.
    """
    hot_pages = hot_pages[:k]
    valid = hot_pages >= 0
    safe = jnp.where(valid, hot_pages, 0)
    # intra-batch dedup (duplicates can survive across sketch epochs)
    eq = (safe[:, None] == safe[None, :]) & valid[None, :]
    first = valid & ~jnp.any(eq & jnp.tril(jnp.ones((k, k), jnp.bool_), k=-1), axis=1)
    need = first & (state.page_slot[safe] < 0)     # not already resident

    # Rank-based 2Q victim selection: cheapest slots first.
    n_victims = min(k, state.slot_page.shape[0])
    rank = _victim_rank(state)
    _, victim_slots = jax.lax.top_k(-rank, n_victims)   # ascending rank
    # Assign the i-th needed page the i-th victim slot.
    order = jnp.cumsum(need.astype(jnp.int32)) - 1
    need = need & (order < n_victims)   # more hot pages than slots: defer
    slot_for = jnp.where(need, victim_slots[jnp.clip(order, 0, n_victims - 1)], -1)

    evicted_page = jnp.where(slot_for >= 0, state.slot_page[jnp.maximum(slot_for, 0)], -1)
    ev_valid = evicted_page >= 0
    n_pages = state.page_slot.shape[0]
    n_slots = state.slot_page.shape[0]
    # out-of-bounds + mode="drop" for all no-op lanes (index-0 routing would
    # race with legitimate writes to page/slot 0)
    ev_idx = jnp.where(ev_valid, evicted_page, n_pages)
    pg_idx = jnp.where(need, safe, n_pages)
    sl_idx = jnp.where(need, slot_for, n_slots)

    # Ping-pong: promoting a page whose PG_demoted flag is set.
    pp = jnp.sum(need & state.demoted[safe], dtype=jnp.int32)

    # demote victims
    page_slot = state.page_slot.at[ev_idx].set(-1, mode="drop")
    demoted = state.demoted.at[ev_idx].set(True, mode="drop")
    # install promotions (clear PG_demoted on promotion, per the kernel flag)
    page_slot = page_slot.at[pg_idx].set(slot_for, mode="drop")
    demoted = demoted.at[pg_idx].set(False, mode="drop")
    slot_page = state.slot_page.at[sl_idx].set(safe, mode="drop")
    active = state.active.at[sl_idx].set(False, mode="drop")   # enter A1in
    referenced = state.referenced.at[sl_idx].set(False, mode="drop")
    last_touch = state.last_touch.at[sl_idx].set(state.step, mode="drop")

    n_promoted = jnp.sum(need, dtype=jnp.int32)
    new_state = state._replace(
        page_slot=page_slot, slot_page=slot_page, active=active,
        referenced=referenced, last_touch=last_touch, demoted=demoted,
        promoted=state.promoted + n_promoted,
        demoted_cnt=state.demoted_cnt + jnp.sum(ev_valid, dtype=jnp.int32),
        ping_pong=state.ping_pong + pp,
    )
    return new_state, jnp.where(need, safe, -1), slot_for


@jax.jit
def migrate_data(
    fast: jax.Array, slow: jax.Array,
    promoted_pages: jax.Array, victim_slots: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Apply the data movement for a promotion batch (low-level helper).

    fast: (num_slots, *page_shape); slow: (num_pages, *page_shape).
    Victims are written back to the slow tier first, then hot pages are
    copied into their slots.  On real TPU ``slow`` carries a pinned_host
    memory-kind sharding; XLA emits the H2D/D2H copies.

    The full data plane — buffer placement, donation, demotion write-back
    targets, byte metering — lives in :mod:`repro.tiering.migrate`
    (DESIGN.md §8); prefer ``TieredMemory.bind_data`` + the daemon verbs.
    """
    ok = (promoted_pages >= 0) & (victim_slots >= 0)
    safe_page = jnp.maximum(promoted_pages, 0)
    safe_slot = jnp.maximum(victim_slots, 0)
    # Tiers are inclusive: ``slow`` is the full backing store, so read-mostly
    # victims need no write-back (dirty pages are written back by the adapter
    # that owns the data, e.g. the KV-tier flushes victim slots explicitly).
    gathered = slow[safe_page]
    mask = ok.reshape((-1,) + (1,) * (fast.ndim - 1))
    fast = fast.at[safe_slot].set(jnp.where(mask, gathered, fast[safe_slot]))
    return fast, slow


@jax.jit
def drain_period_stats(state: TierState) -> tuple[TierState, dict]:
    """Read & clear the per-period counters (daemon policy inputs)."""
    stats = {
        "promoted": state.promoted,
        "demoted": state.demoted_cnt,
        "ping_pong": state.ping_pong,
        "slow_reads": state.slow_reads,
        "fast_reads": state.fast_reads,
    }
    z = jnp.zeros((), jnp.int32)
    # 2Q aging: clear reference bits each period (CLOCK-style second chance).
    return state._replace(
        promoted=z, demoted_cnt=z, ping_pong=z, slow_reads=z, fast_reads=z,
        referenced=jnp.zeros_like(state.referenced),
    ), stats


def lookup(state: TierState, page_ids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(slot_or_minus1, hit_mask) for a batch of page ids."""
    valid = page_ids >= 0
    slots = jnp.where(valid, state.page_slot[jnp.where(valid, page_ids, 0)], -1)
    return slots, slots >= 0
