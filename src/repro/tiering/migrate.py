"""The migration data plane: real byte movement behind ``apply_migration``.

The placement layer (:mod:`repro.core.tiering`) decides WHICH pages move;
this module moves them.  A resource that binds payload data gets a
:class:`TierBuffers` set (DESIGN.md §8):

  * ``fast``: ``(num_slots, *row_shape)`` — promoted copies, device memory,
    always in the resource's NATIVE row dtype;
  * ``slow``: ``(num_pages, *row_shape)`` — the full backing store in the
    resource's wire format (:mod:`repro.tiering.codec`, DESIGN.md §14):
    native dtype under the ``none`` codec, fp32 under ``fp32``, int8 under
    ``int8``.  Always placed in the ``pinned_host`` slow tier
    (:mod:`repro.dist.host_offload`); a device without one is an error;
  * ``scale``: ``(num_pages,)`` fp32 per-row quantization scales — present
    only under the ``int8`` codec (``None`` otherwise).

Each daemon epoch applies ONE fused copy (:func:`migrate`): victims are
written back to their old slow-tier pages (demotion — re-ENCODED to the
wire format), then the promoted pages are gathered into the freed fast
slots (DECODED back to native dtype inside the same jit).  Both buffers
are donated on accelerators, so the epoch costs exactly the moved WIRE
bytes — which the caller meters against the per-epoch byte quota in
:class:`~repro.tiering.stats.TierStats`.

Every verb reaches the host store through :func:`host_offload.host_take`
/ :func:`host_offload.host_put`: only the rows they name cross the
host/device boundary, so a verb's transfer scales with its batch, never
with the store.

The read verbs (:func:`read_rows` / :func:`lookup_rows`) never take a
codec name: decode dispatches on the payload dtype and scale presence
(both trace-time static, see :func:`repro.tiering.codec.decode_rows`), so
the jitted decode step's tier view stays a plain array pytree.  The write
verbs encode, so they take ``codec`` as a static argument and key their
cached jit builders on it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import host_offload as ho
from repro.spans import pull
from repro.tiering import codec as codec_lib


class TierBuffers(NamedTuple):
    """Payload buffers for one resource: fast copies over a slow store."""

    fast: jax.Array   # (num_slots, *row_shape) — native dtype
    slow: jax.Array   # (num_pages, *row_shape) — full store, wire format
    scale: jax.Array | None = None   # (num_pages,) fp32 — int8 codec only


def row_bytes(buffers: TierBuffers) -> int:
    """WIRE bytes of one page row (the migration byte unit): what the slow
    store actually holds per page — int8 payload plus its fp32 scale under
    the ``int8`` codec, the stored dtype otherwise."""
    n = int(np.prod(buffers.slow.shape[1:], dtype=np.int64)
            * buffers.slow.dtype.itemsize)
    if buffers.scale is not None:
        n += int(buffers.scale.dtype.itemsize)
    return n


def place_slow(x: jax.Array) -> jax.Array:
    """Place the backing store in the ``pinned_host`` slow tier of the
    device ``x`` lives on (DESIGN.md §7).  Raises when that device has no
    host tier or the store did not land there."""
    from jax.sharding import Mesh, PartitionSpec as P
    x = jnp.asarray(x)
    (device,) = x.devices()
    out = ho.to_slow_tier(x, Mesh(np.asarray([device]), ("_tier",)), P())
    if out.sharding.memory_kind != ho.SLOW_KIND:
        raise RuntimeError(f"slow store landed in {out.sharding.memory_kind!r}"
                           f", not {ho.SLOW_KIND!r}")
    return out


def init_buffers(slow_data: jax.Array, num_slots: int,
                 codec: str = "none") -> TierBuffers:
    """Build the buffer set around an existing payload array.

    ``slow_data`` arrives in the resource's native dtype; the store is
    encoded to the codec's wire format at bind time (the per-row scales
    ride in the slow tier next to the payload).  The fast buffer keeps the
    NATIVE dtype — promoted rows are decoded once, on promotion, so every
    fast-tier hit serves full-precision rows with zero decode cost.
    """
    slow_data = jnp.asarray(slow_data)
    payload, scale = codec_lib.encode_store(codec, slow_data)
    slow = place_slow(payload)
    if scale is not None:
        scale = place_slow(scale)
    # committed to the store's device, like every later verb's output, so
    # the first jitted read compiles the same program as the rest
    (device,) = slow.devices()
    fast = jax.device_put(
        jnp.zeros((num_slots,) + slow.shape[1:], slow_data.dtype), device)
    return TierBuffers(fast=fast, slow=slow, scale=scale)


def segment_page_ids(segment: int, n_tokens: int, page_t: int,
                     pages_per_seq: int,
                     table: np.ndarray | None = None) -> np.ndarray:
    """Global page ids of a request's first ``n_tokens`` worth of KV pages.

    A lane-mode KV segment is ``pages_per_seq`` consecutive pages starting
    at ``segment * pages_per_seq``; a request that has consumed ``n_tokens``
    occupies the first ``ceil(n_tokens / page_t)`` of them (the final,
    possibly partial, page included — a hand-off force-flush writes it too).
    ``table`` is the lane's copy-on-write page-table row (local idx -> pool
    gid, -1 = private): shared pool pages resolve through it, exactly as the
    read path does (DESIGN.md §12/§13).  This is the id set the
    segment-residency gate checks against ``TieredMemory.pages_written``.
    """
    n_pages = -(-max(n_tokens, 0) // page_t)
    local = np.arange(min(n_pages, pages_per_seq), dtype=np.int64)
    gids = segment * pages_per_seq + local
    if table is not None:
        tabled = np.asarray(table, np.int64)[local]
        gids = np.where(tabled >= 0, tabled, gids)
    return gids


def _donate(n_buffers: int):
    # donation frees the pre-copy buffers on accelerators; the CPU backend
    # ignores donation with a warning, so only request it where it works
    return tuple(range(n_buffers)) if jax.default_backend() != "cpu" else ()


def _scale_at(scale, idx):
    """Per-row scales for a gathered id batch (None under scale-less codecs)."""
    return None if scale is None else ho.host_take(scale, idx)


def _slow_rows(slow, scale, idx, dtype):
    """Decoded slow-store rows for an id batch (host gather + decode)."""
    return codec_lib.decode_rows(ho.host_take(slow, idx),
                                 _scale_at(scale, idx), dtype)


def _put_store(slow, scale, idx, payload, row_scale):
    """Scatter wire-format rows (and their scales) into the slow store."""
    slow = ho.host_put(slow, idx, payload)
    if scale is not None:
        scale = ho.host_put(scale, idx, row_scale)
    return slow, scale


def _rehosted(buffers: TierBuffers, fast, slow, scale) -> TierBuffers:
    """New buffers from a write verb, the store back under ``buffers``'
    own placement (:func:`host_offload.rehost`)."""
    return TierBuffers(
        fast=fast, slow=ho.rehost(slow, buffers.slow.sharding),
        scale=None if scale is None else ho.rehost(scale,
                                                   buffers.scale.sharding))


@jax.jit
def _gather_jit(fast, slow, scale, idx):
    return _slow_rows(slow, scale, idx, fast.dtype)


def gather_rows(buffers: TierBuffers, page_ids) -> jax.Array:
    """Decoded slow-store rows for ``page_ids`` (a host verb: one jitted
    host-side gather; only the named rows cross to the device)."""
    return _gather_jit(buffers.fast, buffers.slow, buffers.scale,
                       jnp.asarray(page_ids, jnp.int32))


def _migrate_impl(codec, fast, slow, scale, promoted, victims, evicted):
    ok = (promoted >= 0) & (victims >= 0)
    ev_ok = ok & (evicted >= 0)
    n_pages, n_slots = slow.shape[0], fast.shape[0]
    # gather promoted rows BEFORE the write-back scatter (a page promoted in
    # this batch is never also evicted in it, but order still documents it);
    # promotion is the decode point — fast rows are native dtype
    up_idx = jnp.where(ok, promoted, 0)
    gathered = _slow_rows(slow, scale, up_idx, fast.dtype)
    # no-op lanes scatter out of bounds and are dropped — routing them to
    # index 0 would race with a legitimate write to page/slot 0
    ev_idx = jnp.where(ev_ok, evicted, n_pages)
    sl_idx = jnp.where(ok, victims, n_slots)
    # demotion write-back: the victim slot's current row returns to its page,
    # re-encoded to the wire format (the codec's quantize point)
    down, down_scale = codec_lib.encode_rows(
        codec, fast[jnp.where(ev_ok, victims, 0)])
    slow, scale = _put_store(slow, scale, ev_idx, down, down_scale)
    # promotion: hot rows land in the freed slots
    fast = fast.at[sl_idx].set(gathered, mode="drop")
    return (fast, slow, scale, jnp.sum(ok, dtype=jnp.int32),
            jnp.sum(ev_ok, dtype=jnp.int32))


@functools.lru_cache(maxsize=None)
def _migrate_jit(codec: str):
    return jax.jit(functools.partial(_migrate_impl, codec),
                   donate_argnums=_donate(3))


def migrate(buffers: TierBuffers, promoted: jax.Array, victims: jax.Array,
            evicted: jax.Array, codec: str = "none"
            ) -> tuple[TierBuffers, int, int]:
    """Apply one promotion batch as ONE fused copy (the epoch's data plane).

    ``promoted[i]`` is copied into fast slot ``victims[i]`` after the slot's
    previous occupant ``evicted[i]`` is written back to the slow store
    (-1 = no-op lane everywhere).  Decode-on-promote / encode-on-demote
    happen inside the same jit under the resource's codec.  Returns the new
    buffers plus the promoted / demoted row counts actually moved (multiply
    by :func:`row_bytes` for the metered wire traffic).
    """
    fast, slow, scale, n_up, n_down = _migrate_jit(codec)(
        buffers.fast, buffers.slow, buffers.scale,
        jnp.asarray(promoted, jnp.int32), jnp.asarray(victims, jnp.int32),
        jnp.asarray(evicted, jnp.int32))
    return (_rehosted(buffers, fast, slow, scale),
            int(pull(n_up, "epoch_plan")), int(pull(n_down, "epoch_plan")))


def read_rows(fast: jax.Array, slow: jax.Array, slots: jax.Array,
              page_ids: jax.Array, scale: jax.Array | None = None
              ) -> jax.Array:
    """Serve a batch of page reads: fast copy when resident, slow fallback.

    ``slots`` is the placement lookup result (-1 = not resident).  The slow
    fallback decodes in the same fused gather (per-row ``scale`` under the
    int8 codec — dtype-dispatched, see :func:`codec.decode_rows`), so the
    result is always native-dtype rows.  Pure jnp — runs inside the
    caller's jit (the decode step) or eagerly from host verbs.  Rows for
    invalid page ids (< 0) read slow page 0 — callers mask them.
    """
    hit = slots >= 0
    safe_page = jnp.where(page_ids >= 0, page_ids, 0)
    slow_rows = _slow_rows(slow, scale, safe_page, fast.dtype)
    mask = hit.reshape(hit.shape + (1,) * (fast.ndim - 1))
    return jnp.where(mask, fast[jnp.where(hit, slots, 0)], slow_rows)


def lookup_rows(fast: jax.Array, slow: jax.Array, page_slot: jax.Array,
                page_ids: jax.Array, scale: jax.Array | None = None
                ) -> jax.Array:
    """The in-jit tiered read fast path (DESIGN.md §10): placement lookup +
    fused dual-tier gather, entirely inside the caller's jit.

    ``page_slot`` is the device-resident placement table
    (``TierState.page_slot``); ``page_ids`` may have ANY leading shape —
    the result has ``page_ids.shape + row_shape``.  Fast-buffer rows are
    gathered for resident pages, with the slow store as the in-trace
    fallback — decoded from the wire format where the codec quantizes
    (DESIGN.md §14), bit-exact under the ``none`` codec (tiers are
    inclusive).  This is what the jitted decode step binds embedding/expert
    reads to — no host verb, no per-step round-trip;
    ``TieredMemory.read_rows`` remains the host-side verb whose
    hit-partitioned gather spares pinned-host bandwidth.
    Rows for invalid page ids (< 0) read slow page 0 — callers mask them.
    """
    page_ids = jnp.asarray(page_ids, jnp.int32)
    slots = jnp.where(page_ids >= 0,
                      page_slot[jnp.maximum(page_ids, 0)], -1)
    return read_rows(fast, slow, slots, page_ids, scale=scale)


def _write_rows_impl(codec, fast, slow, scale, page_ids, slots, rows):
    payload, row_scale = codec_lib.encode_rows(codec, rows)
    slow_idx = jnp.where(page_ids >= 0, page_ids, slow.shape[0])
    slow, scale = _put_store(slow, scale, slow_idx, payload, row_scale)
    # keep promoted copies coherent: a page resident in the fast tier gets
    # its fast row refreshed too (native dtype — the fast tier never holds
    # wire format), so later reads/write-backs never serve a stale snapshot
    fast_idx = jnp.where((page_ids >= 0) & (slots >= 0), slots,
                         fast.shape[0])
    fast = fast.at[fast_idx].set(rows.astype(fast.dtype), mode="drop")
    return fast, slow, scale


@functools.lru_cache(maxsize=None)
def _write_rows_jit(codec: str):
    return jax.jit(functools.partial(_write_rows_impl, codec),
                   donate_argnums=_donate(3))


def write_rows(buffers: TierBuffers, page_ids: jax.Array, slots: jax.Array,
               rows: jax.Array, codec: str = "none") -> TierBuffers:
    """Refresh page payloads in BOTH tiers (owners with mutating payloads,
    e.g. the serve engine flushing freshly-filled KV pages).

    The slow store always takes the write — encoded to the wire format —
    and pages currently promoted (``slots[i] >= 0``) also get their fast
    copy refreshed so the tiers stay coherent.  -1 page ids are dropped
    lanes.
    """
    fast, slow, scale = _write_rows_jit(codec)(
        buffers.fast, buffers.slow, buffers.scale,
        jnp.asarray(page_ids, jnp.int32), jnp.asarray(slots, jnp.int32),
        rows)
    return _rehosted(buffers, fast, slow, scale)


def ring_selection(page_ids) -> tuple[np.ndarray, np.ndarray]:
    """Compact a (L*S,) ring-slot -> page map (-1 = slot not written) to
    the slots that write: ``(ring slot indices, their page ids)``, padded
    to the next power of two with slot 0 / page -1 (a dropped lane), so a
    flush moves only the written pages and compiles once per size bucket."""
    ids = np.asarray(page_ids).reshape(-1)
    sel = np.flatnonzero(ids >= 0)
    n = 1 << max(int(sel.size) - 1, 0).bit_length()
    ring = np.zeros(n, np.int32)
    ring[:sel.size] = sel
    pids = np.full(n, -1, np.int32)
    pids[:sel.size] = ids[sel]
    return ring, pids


def _pages_to_rows(k_pages, v_pages, ring):
    # ring layout (G, L, S, T, hkv, d), ring slots ``ring`` of the flattened
    # L*S axis -> page-row layout (n, G, T, hkv, dk+dv)
    def take(x):
        return x.reshape((x.shape[0], -1) + x.shape[3:])[:, ring]
    rows = jnp.concatenate([take(k_pages), take(v_pages)], axis=-1)
    return jnp.moveaxis(rows, 0, 1)


def _write_pages_impl(codec, fast, slow, scale, page_ids, slots, ring,
                      k_pages, v_pages):
    rows = _pages_to_rows(k_pages, v_pages, ring)
    return _write_rows_impl(codec, fast, slow, scale, page_ids, slots, rows)


@functools.lru_cache(maxsize=None)
def _write_pages_jit(codec: str):
    return jax.jit(functools.partial(_write_pages_impl, codec),
                   donate_argnums=_donate(3))


def write_pages(buffers: TierBuffers, page_ids: jax.Array, slots: jax.Array,
                ring: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                codec: str = "none") -> TierBuffers:
    """Bulk KV-page write: flush paged-ring slots into the tier store as ONE
    donated fused op (the chunked-prefill / lane-flush data-plane verb).

    ``k_pages`` / ``v_pages`` are ring views shaped (G, L, S, T, hkv, dk|dv)
    — layer groups x lanes x ring slots; ``ring`` / ``page_ids`` are the
    written ring slots (indices into the flattened L*S axis) and their
    logical pages, as :func:`ring_selection` compacts them (-1 = dropped
    lane), and ``slots`` the pages' placement lookup.  The slot gather,
    [K | V] concat, codec encode and dual-tier scatter all fuse inside one
    jit; only the selected pages cross to the host store.
    """
    fast, slow, scale = _write_pages_jit(codec)(
        buffers.fast, buffers.slow, buffers.scale,
        jnp.asarray(page_ids, jnp.int32), jnp.asarray(slots, jnp.int32),
        jnp.asarray(ring, jnp.int32), k_pages, v_pages)
    return _rehosted(buffers, fast, slow, scale)


# -- async data plane (DESIGN.md §15) ---------------------------------------
#
# The asynchronous epoch is the promotion gather ONLY, dispatched without
# donation: the committed fast buffer stays alive (decode keeps reading the
# stale epoch bit-exactly) while XLA produces the NEXT epoch's fast buffer —
# the "double buffer".  The demotion write-back is elided: under the
# write-both-tiers rule every resident fast row equals decode(slow row), so
# the write-back would re-write identical wire bytes; its traffic is still
# metered by the caller (the bytes are real on a CXL port).  Writes landing
# while an epoch is in flight are replayed onto the in-flight buffer by the
# ``refresh_*`` verbs below, so commit never serves a pre-write snapshot.


@jax.jit
def _issue_migrate_jit(fast, slow, scale, promoted, victims):
    ok = (promoted >= 0) & (victims >= 0)
    up_idx = jnp.where(ok, promoted, 0)
    gathered = _slow_rows(slow, scale, up_idx, fast.dtype)
    sl_idx = jnp.where(ok, victims, fast.shape[0])
    new_fast = fast.at[sl_idx].set(gathered, mode="drop")
    return new_fast, jnp.sum(ok, dtype=jnp.int32)


def issue_migrate(buffers: TierBuffers, promoted: jax.Array,
                  victims: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Dispatch one epoch's promotion copy asynchronously (no donation, no
    host block): returns ``(new_fast, token)`` where ``new_fast`` is the
    NEXT epoch's fast buffer and ``token`` a cheap () int32 readiness
    witness (the promoted-row count — an output of the same executable, so
    it completes exactly when the copy does).  The caller commits by
    pointer swap once :func:`token_ready` says so."""
    return _issue_migrate_jit(
        buffers.fast, buffers.slow, buffers.scale,
        jnp.asarray(promoted, jnp.int32), jnp.asarray(victims, jnp.int32))


def token_ready(token: jax.Array) -> bool:
    """Non-blocking readiness probe of an issued epoch's witness token."""
    try:
        return bool(token.is_ready())
    except AttributeError:      # no probe on this runtime: degrade to sync
        token.block_until_ready()
        return True


def _refresh_rows_impl(fast, slots, rows):
    idx = jnp.where(slots >= 0, slots, fast.shape[0])
    return fast.at[idx].set(rows.astype(fast.dtype), mode="drop")


@functools.lru_cache(maxsize=None)
def _refresh_rows_jit():
    return jax.jit(_refresh_rows_impl, donate_argnums=_donate(1))


def refresh_rows(fast: jax.Array, slots: jax.Array, rows: jax.Array
                 ) -> jax.Array:
    """Replay an owner write onto the IN-FLIGHT fast buffer (native dtype,
    no slow-store touch — the committed write verb already encoded there):
    keeps a write that lands mid-epoch coherent with the epoch about to
    commit.  ``slots`` is the lookup under the in-flight placement table."""
    return _refresh_rows_jit()(fast, jnp.asarray(slots, jnp.int32), rows)


def _refresh_pages_impl(fast, slots, ring, k_pages, v_pages):
    return _refresh_rows_impl(fast, slots,
                              _pages_to_rows(k_pages, v_pages, ring))


@functools.lru_cache(maxsize=None)
def _refresh_pages_jit():
    return jax.jit(_refresh_pages_impl, donate_argnums=_donate(1))


def refresh_pages(fast: jax.Array, slots: jax.Array, ring: jax.Array,
                  k_pages: jax.Array, v_pages: jax.Array) -> jax.Array:
    """Bulk-flush analogue of :func:`refresh_rows` for KV ring views
    (``ring`` as in :func:`write_pages`)."""
    return _refresh_pages_jit()(fast, jnp.asarray(slots, jnp.int32),
                                jnp.asarray(ring, jnp.int32), k_pages,
                                v_pages)


def _refresh_copy_impl(fast, slow, scale, src_ids, dst_slots):
    src_safe = jnp.maximum(src_ids, 0)
    rows = _slow_rows(slow, scale, src_safe, fast.dtype)
    idx = jnp.where((src_ids >= 0) & (dst_slots >= 0), dst_slots,
                    fast.shape[0])
    return fast.at[idx].set(rows, mode="drop")


@functools.lru_cache(maxsize=None)
def _refresh_copy_jit():
    return jax.jit(_refresh_copy_impl, donate_argnums=_donate(1))


def refresh_copy(fast: jax.Array, slow: jax.Array, scale: jax.Array | None,
                 src_ids: jax.Array, dst_slots: jax.Array) -> jax.Array:
    """:func:`copy_rows` replay onto the in-flight fast buffer: re-decode
    the (already copied) destination rows from the slow store into the
    destinations' in-flight slots."""
    return _refresh_copy_jit()(fast, slow, scale,
                               jnp.asarray(src_ids, jnp.int32),
                               jnp.asarray(dst_slots, jnp.int32))


def _copy_rows_impl(fast, slow, scale, src_ids, dst_ids, dst_slots):
    # the slow store is coherent by construction (every write verb and the
    # demotion write-back lands there), so the gather reads slow only —
    # and copies the WIRE format verbatim (payload + scale): a quantized
    # page publishes without a decode/re-encode round trip
    src_safe = jnp.maximum(src_ids, 0)
    rows = ho.host_take(slow, src_safe)
    src_scale = _scale_at(scale, src_safe)   # gather BEFORE the scatter below
    valid = (src_ids >= 0) & (dst_ids >= 0)
    slow_idx = jnp.where(valid, dst_ids, slow.shape[0])
    slow, scale = _put_store(slow, scale, slow_idx, rows, src_scale)
    fast_idx = jnp.where(valid & (dst_slots >= 0), dst_slots, fast.shape[0])
    fast = fast.at[fast_idx].set(
        codec_lib.decode_rows(rows, src_scale, fast.dtype), mode="drop")
    return fast, slow, scale


@functools.lru_cache(maxsize=None)
def _copy_rows_jit():
    return jax.jit(_copy_rows_impl, donate_argnums=_donate(3))


def copy_rows(buffers: TierBuffers, src_ids: jax.Array, dst_ids: jax.Array,
              dst_slots: jax.Array) -> TierBuffers:
    """Duplicate page payloads store-to-store as ONE donated fused op —
    the content-addressed publish verb (DESIGN.md §12): a finished
    request's private segment pages are copied into shared pool pages
    without a host round-trip.  Wire format travels verbatim (no codec
    transcode — the scales ride along), so the publish costs exactly the
    compressed bytes.  Destinations currently promoted
    (``dst_slots[i] >= 0``) get their fast copy refreshed for coherence;
    -1 in either id array drops that pair.
    """
    fast, slow, scale = _copy_rows_jit()(
        buffers.fast, buffers.slow, buffers.scale,
        jnp.asarray(src_ids, jnp.int32), jnp.asarray(dst_ids, jnp.int32),
        jnp.asarray(dst_slots, jnp.int32))
    return _rehosted(buffers, fast, slow, scale)
