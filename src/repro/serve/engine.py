"""Serving engine: batched prefill + decode with NeoMem-tiered resources.

ServeEngine drives a small continuous-batching loop on top of the
models.decode steps:

  * prefill(tokens)           — full-sequence forward, returns first token +
                                dense cache (short contexts), or seeds the
                                paged fast tier (long contexts);
  * step()                    — one decode step for the active batch;
  * NeoMem integration        — ANY set of registered tiered resources
                                ("kv", "experts", "embeddings", or custom
                                registry kinds) multiplexed on ONE daemon:
                                per migration_interval the daemon promotes
                                sketch-hot pages for every resource under a
                                shared quota budget, between steps (never
                                inside the jitted hot path);
  * migration data plane      — each built-in resource binds REAL payload
                                (embedding-table pages, expert weight
                                blocks, flushed KV pages) to fast/slow
                                TierBuffers, so daemon epochs physically
                                move rows and meter bytes; ``read_rows``
                                serves lookups from the fast buffer with
                                slow-tier fallback (DESIGN.md §8).

Access streams fed per decode step (DESIGN.md §3): the token column
(embedding rows), the router's token->expert ids surfaced by
``decode_step(..., return_streams=True)`` (experts), and the resident
paged-KV window weighted by the KERNEL-exported per-page softmax mass
(``streams["kv_mass"]``, DESIGN.md §10; ``ServeConfig.kv_mass_source=
"fill"`` keeps the old page-fill proxy as the A/B baseline).

In-jit tiered reads (DESIGN.md §10): the jitted decode step itself reads
embedding rows and the first MoE position's expert weight blocks THROUGH
the device-resident placement tables (``tiering.migrate.lookup_rows``) —
fast-buffer gather on residency, slow-store fallback in the same fused
gather, no host verb on the hot path.  The tier views are passed as jit
ARGUMENTS each step, so daemon epochs swap buffers without retracing.

Two serving modes share the machinery:

  * single-request (``prefill``/``step``/``generate``) — one batched
    prompt decoded lockstep, scalar position;
  * continuous-batching lanes (``ServeConfig.lanes > 0``; DESIGN.md §9) —
    the batch becomes independent decode *lanes* with per-lane positions,
    driven one token per lane per ``advance_lanes`` call by the request
    scheduler (serve/sched.py); the KV slow store is carved into
    per-request segments, lanes reset/preempt/resume mid-flight
    (``reset_lane``/``preempt_lane``/``resume_lane``, bit-exact), and
    ``save_tiering``/``load_tiering`` checkpoint the placement maps.

This is the substrate behind examples/serve_longctx.py and the serving
benchmarks; the dry-run lowers the same step functions at production shapes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import tiering as tm
from repro.cache import KVReuseStore
from repro.configs.base import ArchConfig
from repro.models import decode as dec
from repro.models import transformer as tr
from repro.serve.clock import TickClock
from repro.spans import pull, span


@jax.jit
def _live_mass(mass: jax.Array, live) -> jax.Array:
    """(L, S) per-page mass with the pages outside ``live`` zeroed, flat —
    the KV observation's mass, built where the kernel left it."""
    return jnp.where(live, mass.astype(jnp.float32), 0.0).reshape(-1)


@jax.jit
def _mass_split(mass: jax.Array, gids, base) -> tuple[jax.Array, jax.Array]:
    """(resident, shared-pool) sums of a flat observation mass."""
    return (jnp.sum(jnp.where(gids >= 0, mass, 0.0)),
            jnp.sum(jnp.where(gids >= base, mass, 0.0)))


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 4096
    page_t: int = 64
    hot_slots: int = 16
    paged: bool = False
    migration_interval: int = 8     # decode steps between daemon ticks
    # Tiered resources to register ("kv" is implied by paged=True).
    resources: tuple[str, ...] = ()
    kv_quota: int = 64
    kv_mass_threshold: float = 0.02
    expert_hot_slots: int = 4       # HBM-resident experts per layer group
    expert_quota: int = 32
    embed_hot_slots: int = 64       # hot vocab row-blocks kept HBM-resident
    embed_quota: int = 64
    embed_rows_per_page: int = 0    # vocab rows per page (0 -> package default)
    # Continuous-batching lane mode (serve/sched.py, DESIGN.md §9): the
    # engine batch becomes `lanes` independent decode lanes with per-lane
    # positions; the KV slow store is carved into `kv_segments` per-request
    # address spaces of max_seq//page_t pages each.
    lanes: int = 0                  # decode lanes (0 = single-request mode)
    kv_segments: int = 0            # slow-store KV segments (0 -> lanes)
    kv_tier_slots: int = 0          # kv fast-tier slots (0 -> hot_slots)
    # "kv" hotness stream source (DESIGN.md §10): "kernel" feeds the
    # flash-decode kernel's per-page softmax mass; "fill" keeps the old
    # host-computed page_len proxy (the A/B baseline for the fidelity gate).
    kv_mass_source: str = "kernel"
    # Bind embedding/expert reads of the jitted decode step to the tiered
    # store (in-jit lookup_rows; off = dense params, reads stay host-only).
    jit_tier_reads: bool = True
    # Slow-store wire format for every tiered resource (tiering/codec.py,
    # DESIGN.md §14): "none" = native rows (byte-exact data path), "fp32" =
    # full-precision store (the compression A/B's fp arm — numerically the
    # identity for bf16 rows), "int8" = per-row symmetric quantization
    # (~4x fewer wire bytes; reads dequantize in the fused tier gather).
    slow_codec: str = "none"
    # Content-addressed KV reuse (repro.cache, DESIGN.md §12): extra shared
    # pool pages appended to the KV slow store behind a refcounted index so
    # admission can install matched prompt pages pre-resident.  Lane mode
    # only; 0 = off.
    reuse_pages: int = 0
    # Asynchronous migration data plane (DESIGN.md §15): daemon epochs are
    # issued as non-blocking double-buffered copies and committed by pointer
    # swap at the NEXT tick — decode reads the previous committed epoch's
    # views (bit-exact, both tiers coherent) instead of stalling on the
    # fused copy.  Off = the synchronous stop-the-world plane.
    async_migration: bool = False


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, scfg: ServeConfig,
                 ep_axes=None, attach_to: "ServeEngine | None" = None):
        """``attach_to`` builds a WORKER engine over another engine's tiered
        store (DESIGN.md §13): the daemon, every resource handle (placement
        maps + payload buffers) and the content-addressed reuse store are
        SHARED with ``attach_to`` — the two engines are two workers on one
        hand-off fabric.  The attached engine may differ in lane count but
        must match the owner's cache/store geometry exactly (its preemption
        residuals transplant onto the owner's lanes); it never ticks the
        shared daemon — migration cadence belongs to the owning engine."""
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.ep = ep_axes
        if scfg.lanes and not scfg.paged:
            raise ValueError("lane mode (ServeConfig.lanes) requires paged=True")
        if scfg.kv_mass_source not in ("kernel", "fill"):
            raise ValueError(
                f"kv_mass_source must be 'kernel' or 'fill', "
                f"got {scfg.kv_mass_source!r}")
        if scfg.reuse_pages:
            if not scfg.lanes:
                raise ValueError(
                    "reuse_pages requires lane mode (ServeConfig.lanes > 0)")
            if not dec.reuse_eligible(cfg):
                raise ValueError(
                    f"arch {cfg.name!r} is not reuse-eligible: the KV slow "
                    f"store must carry the whole per-position state (single "
                    f"attention pattern position, no recurrent blocks, no "
                    f"dense prologue)")
        self._daemon_owner = attach_to is None
        self._embed_rpp = scfg.embed_rows_per_page or tm.EMBED_ROWS_PER_PAGE
        if attach_to is not None:
            self._check_attach_geometry(attach_to)
            self.daemon = attach_to.daemon
            self.reuse = attach_to.reuse
            self.reuse_mass = attach_to.reuse_mass
        else:
            self.daemon = tm.NeoMemDaemon(tm.DaemonParams(
                async_plane=scfg.async_migration))
            self._register_resources()
            # content-addressed shared pool (repro.cache, DESIGN.md §12):
            # pool page ids sit ABOVE every private segment in the KV
            # address space
            self.reuse = None
            self.reuse_mass = {"shared": 0.0, "total": 0.0}
            if scfg.reuse_pages:
                n_segments = scfg.kv_segments or scfg.lanes
                self.reuse = KVReuseStore(
                    scfg.reuse_pages,
                    base_gid=n_segments * self.pages_per_seq,
                    page_t=scfg.page_t)
        self._kernel_mass = scfg.paged and scfg.kv_mass_source == "kernel"
        self._want_streams = "experts" in self.daemon or \
            ("kv" in self.daemon and self._kernel_mass)
        self._decode = jax.jit(self._decode_fn)
        self._decode_paged = jax.jit(self._decode_paged_fn)
        self._prefill_dense_jit = jax.jit(self._prefill_dense_fn)
        self._prefill_paged_jit = jax.jit(self._prefill_paged_fn)
        self.cache = None
        self._clock = TickClock(scfg.migration_interval)
        self._decode_s = 0.0            # decode wall time (overlap metering)
        self._last_kv_mass = None       # (B, n_slots) kernel mass, post-step
        # host copy of every row's cache position (lane mode: per lane;
        # single-request mode: the lockstep counter per batch row) — the
        # ring view is derived from it, never read back (_ring_view)
        self._pos = np.zeros(max(scfg.lanes, 1), np.int32)
        # (lane, slot) -> (page id, fill) change tracking for the KV flush
        # (single-request mode uses lane 0)
        self._kv_flushed: dict[tuple[int, int], tuple[int, int]] = {}
        self._lane_active = np.zeros(max(scfg.lanes, 1), bool)
        self._lane_segments = np.full(max(scfg.lanes, 1), -1, np.int32)
        # per-lane page table (copy-on-write indirection): local page idx ->
        # global store page; -1 = the private affine default
        # segment*pages_per_seq + local.  Matched shared pages point into
        # the reuse pool instead, so every referencing lane observes the
        # SAME pool gid and the daemon aggregates their mass (DESIGN.md §12).
        pps = self.pages_per_seq if scfg.paged else 1
        self._lane_pages = np.full((max(scfg.lanes, 1), pps), -1, np.int64)
        # locals whose slow-store row holds a complete page (publish witness)
        self._lane_full = np.zeros((max(scfg.lanes, 1), pps), bool)

    def _check_attach_geometry(self, owner: "ServeEngine") -> None:
        """An attached worker engine must agree with the owner on every
        field that shapes the shared store or the per-lane cache geometry —
        a residual snapshotted on one engine's lane is installed verbatim
        onto the other's (ring arrays sized by hot_slots/page_t, segment
        address space sized by max_seq/kv_segments).  Only the lane count
        may differ: that is the worker-pool split."""
        if not (self.lane_mode and owner.lane_mode):
            raise ValueError("attach_to requires lane mode on both engines")
        mine = dataclasses.asdict(self.scfg)
        theirs = dataclasses.asdict(owner.scfg)
        mine.pop("lanes"), theirs.pop("lanes")
        diff = [k for k in mine if mine[k] != theirs[k]]
        if diff:
            raise ValueError(
                f"attached engine geometry differs from owner on {diff} — "
                "only ServeConfig.lanes may differ between workers")

    def _register_resources(self) -> None:
        cfg, scfg = self.cfg, self.scfg
        kinds = set(scfg.resources)
        if scfg.paged:
            kinds.add("kv")
        for kind in sorted(kinds):
            if kind == "kv":
                if not scfg.paged:
                    raise ValueError("the 'kv' resource requires paged=True")
                row_shape = self._kv_row_shape()
                # lane mode: the slow store is carved into per-request
                # segments, each a max_seq-worth of logical pages; the
                # content-addressed reuse pool's pages sit above them
                n_segments = scfg.kv_segments or scfg.lanes or 1
                spec = tm.ResourceSpec(
                    "kv", n_pages=n_segments * self.pages_per_seq
                    + scfg.reuse_pages,
                    hot_slots=scfg.kv_tier_slots or scfg.hot_slots,
                    quota_pages=scfg.kv_quota,
                    row_shape=row_shape, row_dtype="bfloat16",
                    slow_codec=scfg.slow_codec)
                res = tm.make_resource(
                    "kv", spec, mass_threshold=scfg.kv_mass_threshold)
                # the slow tier starts empty: pages are flushed down from the
                # paged cache as decode fills them (_flush_kv_slow)
                payload = jnp.zeros((spec.n_pages,) + row_shape, jnp.bfloat16)
            elif kind == "experts":
                if cfg.moe is None or "moe" not in cfg.pattern:
                    raise ValueError(
                        f"arch {cfg.name!r} has no MoE layers to tier")
                payload = self._expert_payload()
                spec = tm.ResourceSpec(
                    "experts", n_pages=cfg.n_groups * cfg.moe.n_experts,
                    hot_slots=cfg.n_groups * scfg.expert_hot_slots,
                    quota_pages=scfg.expert_quota,
                    row_shape=tuple(payload.shape[1:]),
                    row_dtype=str(payload.dtype),
                    slow_codec=scfg.slow_codec)
                res = tm.make_resource("experts", spec,
                                       n_experts=cfg.moe.n_experts)
            elif kind == "embeddings":
                rows = self._embed_rpp
                payload = self._embed_payload(rows)
                spec = tm.ResourceSpec(
                    "embeddings", n_pages=(cfg.vocab + rows - 1) // rows,
                    hot_slots=scfg.embed_hot_slots,
                    quota_pages=scfg.embed_quota,
                    row_shape=tuple(payload.shape[1:]),
                    row_dtype=str(payload.dtype),
                    slow_codec=scfg.slow_codec)
                res = tm.make_resource("embeddings", spec,
                                       rows_per_page=rows)
            else:
                raise KeyError(f"unknown serve resource kind {kind!r}; "
                               f"known: {tm.resource_kinds()}")
            handle = self.daemon.register(res)
            # the KV slow store starts as zero scratch — pages only become
            # resident (write-witnessed) once a flush lands on them; every
            # other resource binds a payload that is valid from step 0
            handle.bind_data(payload, initially_valid=(kind != "kv"))
            # the KV observation is built on the step's device from its
            # outputs, so the tier programs take committed state from the
            # first call on (the daemon's ticks keep it so)
            handle.state = self._commit(handle.state)

    # -- payload construction (the migration data plane, DESIGN.md §8) -------
    def _kv_row_shape(self) -> tuple[int, ...]:
        """One logical KV page across all layer groups: K and V payloads of
        the representative paged-attention entry, concatenated on the last
        axis (MLA: latent + rope widths; GQA: 2 x head_dim)."""
        cfg = self.cfg
        if cfg.mla is not None:
            hkv, dk, dv = 1, cfg.mla.kv_lora + cfg.mla.d_rope, cfg.mla.kv_lora
        else:
            hkv, dk, dv = cfg.n_kv_heads, cfg.head_dim, cfg.head_dim
        return (cfg.n_groups, self.scfg.page_t, hkv, dk + dv)

    def _expert_payload(self) -> jax.Array:
        """(G*E, flat) expert weight blocks, page_id = group*n_experts+expert.

        Uses the first MoE position in the layer pattern as the weight block
        (one representative block per expert; per-position payloads would
        multiply the slow tier by the MoE depth without changing placement).
        """
        i = self.cfg.pattern.index("moe")
        ffn = self.params["blocks"][i]["ffn"]
        g, e = ffn["w_in"].shape[:2]
        parts = [ffn[k].reshape(g * e, -1) for k in ("w_gate", "w_in", "w_out")]
        return jnp.concatenate(parts, axis=-1)

    def _embed_payload(self, rows_per_page: int) -> jax.Array:
        """(n_pages, rows_per_page, d) vocab row-blocks of the live table."""
        table = self.params["embed"]["table"]
        v, d = table.shape
        n_pages = (v + rows_per_page - 1) // rows_per_page
        pad = n_pages * rows_per_page - v
        if pad:
            table = jnp.concatenate(
                [table, jnp.zeros((pad, d), table.dtype)], axis=0)
        return table.reshape(n_pages, rows_per_page, d)

    def _commit(self, cache):
        """Commit a fresh cache to the device the params live on.  A jitted
        step's outputs are committed arrays; a first call with uncommitted
        cache leaves would compile the step a second time on the next
        call.  Params spread over several devices (EP serving) leave the
        cache to the step's own sharding."""
        devices = jax.tree_util.tree_leaves(self.params)[0].devices()
        if len(devices) != 1:
            return cache
        return jax.device_put(cache, next(iter(devices)))

    # -- jitted step bodies -------------------------------------------------
    def _decode_fn(self, params, cache, token, aux, tiered):
        return dec.decode_step(self.cfg, params, cache, token,
                               aux_embeds=aux, ep_axes=self.ep,
                               return_streams=self._want_streams,
                               tiered=tiered)

    def _decode_paged_fn(self, params, cache, token, tiered, active):
        out = dec.decode_step_paged(self.cfg, params, cache, token,
                                    page_t=self.scfg.page_t, ep_axes=self.ep,
                                    return_streams=self._want_streams,
                                    tiered=tiered,
                                    collect_mass=self._kernel_mass)
        if active is None:
            return out
        # lane mode: inactive lanes' cache leaves stay frozen — their
        # positions/rings must not drift while another lane chunk-prefills
        if self._want_streams:
            logits, new_cache, streams = out
            return logits, dec.merge_cache(cache, new_cache, active), streams
        logits, new_cache = out
        return logits, dec.merge_cache(cache, new_cache, active)

    def _prefill_dense_fn(self, params, cache, tokens, aux, tiered):
        return dec.prefill_dense(self.cfg, params, cache, tokens,
                                 aux_embeds=aux, ep_axes=self.ep,
                                 tiered=tiered)

    def _prefill_paged_fn(self, params, cache, tokens, valid, active, tiered):
        return dec.prefill_paged(self.cfg, params, cache, tokens,
                                 page_t=self.scfg.page_t, valid=valid,
                                 active=active, ep_axes=self.ep, tiered=tiered,
                                 collect_mass=self._kernel_mass)

    def _tier_reads(self) -> dict:
        """Tier views for the in-jit read path (DESIGN.md §10): device-array
        ``{"fast", "slow", "page_slot"}`` triples per resource, rebuilt each
        step so migration epochs are picked up as fresh jit arguments (same
        pytree structure — no retrace).  Empty when ``jit_tier_reads`` is
        off; the KV ring needs no view (it IS the fast tier, in-cache)."""
        out: dict = {}
        if not self.scfg.jit_tier_reads:
            return out
        if "embeddings" in self.daemon:
            h = self.daemon["embeddings"]
            if h.mem.buffers is not None:
                view = h.tier_view()
                view["rows_per_page"] = self._embed_rpp
                out["embeddings"] = view
        # EP-sharded serving keeps the shard_map dispatch (moe_apply_ep's
        # "residency" path shards hot experts over the EP axis); the
        # replicated per-token row gather is the single-device tiered path
        if "experts" in self.daemon and self.ep is None:
            h = self.daemon["experts"]
            if h.mem.buffers is not None:
                out["experts"] = h.tier_view()
        return out

    # -- public API -----------------------------------------------------------
    @property
    def _chunk_cap(self) -> int:
        """Ring-wrap safety bound on one prefill chunk: a chunk scan must
        never overwrite a page that has not been flushed to the slow store,
        so it spans at most the ring minus the slot it may be mid-filling."""
        return max((self.scfg.hot_slots - 1) * self.scfg.page_t, 1)

    def prefill(self, tokens: np.ndarray, aux_embeds=None):
        if self.lane_mode:
            raise ValueError("lane mode serves through prefill_lane/"
                             "advance_lanes (the request scheduler), not "
                             "prefill/generate")
        b, s = tokens.shape
        self._pos = np.zeros(b, np.int32)
        self.aux = aux_embeds
        if self.cfg.encoder_layers and aux_embeds is not None:
            self.aux = tr.encode(self.cfg, self.params, aux_embeds)
        if self.scfg.paged:
            self.cache = self._commit(dec.init_paged_cache(
                self.cfg, b, self.scfg.hot_slots, self.scfg.page_t))
            self._kv_flushed.clear()         # fresh ring: re-flush everything
            # chunked prefill: scan the paged decode body over the prompt in
            # ring-capacity chunks (bit-exact with token-at-a-time streaming;
            # dec.prefill_paged), flushing each chunk's pages down before the
            # ring can wrap over them
            cap = self._chunk_cap
            logits = None
            for off in range(0, s, cap):
                logits = self._prefill_chunk(jnp.asarray(tokens[:, off:off + cap]))
            return pull(jnp.argmax(logits, -1), "logits")
        # dense path: ONE scan fills the cache and yields the last-token
        # logits together — the prompt runs exactly once, and the tiering
        # streams are replayed as one masked observation batch
        self.cache = self._commit(dec.init_cache(self.cfg, b,
                                                 self.scfg.max_seq))
        logits, self.cache, streams = self._prefill_dense_jit(
            self.params, self.cache, jnp.asarray(tokens), self.aux,
            self._tier_reads())
        self._pos += s
        self._observe_prefill(tokens, streams)
        self._maybe_tick(s)
        return pull(jnp.argmax(logits, -1), "logits")

    def _prefill_chunk(self, tok: jax.Array):
        """One single-request paged prefill chunk: scan-advance the cache,
        observe the chunk's streams once, flush its pages, tick the daemon
        for the chunk's worth of steps.  Returns (B, V) last logits."""
        n = tok.shape[1]
        logits, self.cache, streams = self._prefill_paged_jit(
            self.params, self.cache, tok, None, None, self._tier_reads())
        self._pos += n
        self._observe_prefill(pull(tok, "tokens"), streams)
        if "kv" in self.daemon:
            with span("tier/observe", resource="kv"):
                mass, ids = self._kv_page_stream()
                km = streams.get("kv_mass")
                if self._kernel_mass and km is not None:
                    # chunk-summed kernel mass over the post-chunk window:
                    # the (C, G, n_attn, B, S) stream head-averaged over
                    # groups, positions and lockstep batch rows, summed over
                    # the chunk — the aggregate of the per-step NeoProf
                    # streams (DESIGN.md §10)
                    mass = jnp.sum(jnp.mean(km, axis=(1, 2, 3)), axis=0)
                if ids.size:
                    self.daemon.observe("kv", mass, ids)
        self._flush_kv_slow()
        self._maybe_tick(n)
        return logits

    def _observe_prefill(self, tokens: np.ndarray, streams: dict) -> None:
        """Replay a prefilled chunk's embedding/expert streams as ONE
        observation batch each (not one per prompt token)."""
        if "embeddings" in self.daemon:
            with span("tier/observe", resource="embeddings"):
                self.daemon.observe("embeddings",
                                    jnp.asarray(tokens, jnp.int32))
        if "experts" in self.daemon and streams.get("router") is not None:
            with span("tier/observe", resource="experts"):
                self.daemon.observe("experts", streams["router"])

    def step(self, token: np.ndarray) -> np.ndarray:
        logits = self._advance(jnp.asarray(token)[:, None])
        return pull(jnp.argmax(logits[:, -1], -1), "logits")

    def generate(self, prompt: np.ndarray, n_tokens: int,
                 aux_embeds=None) -> np.ndarray:
        nxt = self.prefill(prompt, aux_embeds)
        out = [nxt]
        for _ in range(n_tokens - 1):
            nxt = self.step(nxt)
            out.append(nxt)
        return np.stack(out, axis=1)

    # -- continuous-batching lane mode (serve/sched.py, DESIGN.md §9) ---------
    @property
    def pages_per_seq(self) -> int:
        """Logical KV pages per request segment (= per max_seq sequence)."""
        return self.scfg.max_seq // self.scfg.page_t

    @property
    def lane_mode(self) -> bool:
        return self.scfg.lanes > 0

    def start_lanes(self) -> None:
        """Initialize the lane substrate: ``lanes`` independent decode lanes
        over one paged ring with per-lane positions.  No prompt — the
        scheduler streams prompt tokens through :meth:`advance_lanes`."""
        scfg = self.scfg
        if not self.lane_mode:
            raise ValueError("start_lanes requires ServeConfig.lanes > 0")
        self.cache = self._commit(dec.init_paged_cache(
            self.cfg, scfg.lanes, scfg.hot_slots, scfg.page_t,
            per_lane_pos=True))
        # pristine one-lane template: reset_lane restores INITIAL values,
        # which are not all zero (the m/sLSTM stabilizer state inits to -inf)
        self._lane_init = dec.init_paged_cache(self.cfg, 1, scfg.hot_slots,
                                               scfg.page_t, per_lane_pos=True)
        self.aux = None
        self._kv_flushed.clear()
        self._pos = np.zeros(scfg.lanes, np.int32)
        self._lane_active = np.zeros(scfg.lanes, bool)
        self._lane_segments = np.full(scfg.lanes, -1, np.int32)
        self._lane_pages = np.full((scfg.lanes, self.pages_per_seq), -1,
                                   np.int64)
        self._lane_full = np.zeros((scfg.lanes, self.pages_per_seq), bool)

    def advance_lanes(self, tokens, active, segments) -> np.ndarray:
        """One continuous-batching decode step for ALL lanes at once.

        ``tokens`` (L,) — the next token of each lane's stream: a prompt
        token while the lane prefills, the last sampled token while it
        decodes, don't-care for inactive lanes (their compute is masked out
        of every observation stream and never flushed).  ``active`` (L,)
        bool, ``segments`` (L,) int — the lane's slow-store KV segment
        (-1 = none).  Returns the last-position logits (L, vocab)."""
        if not self.lane_mode:
            raise ValueError("advance_lanes requires ServeConfig.lanes > 0")
        if self.cache is None:
            self.start_lanes()
        with span("engine/advance") as sp:
            self._lane_active = np.asarray(active, bool).copy()
            self._lane_segments = np.asarray(segments, np.int32).copy()
            tokens = np.asarray(tokens, np.int32)
            tok = jnp.asarray(tokens)[:, None]
            with span("engine/dispatch"):
                out = self._decode_paged(self.params, self.cache, tok,
                                         self._tier_reads(),
                                         jnp.asarray(self._lane_active))
            if self._want_streams:
                logits, self.cache, streams = out
            else:
                (logits, self.cache), streams = out, {}
            self._pos[self._lane_active] += 1
            # the step's one host sync: its logits' copy starts now and is
            # read last, so the observations and the tick are dispatched
            # behind the step while the device runs it
            last = logits[:, -1]
            last.copy_to_host_async()
            self._set_kv_mass(streams)
            self._observe_lanes(tokens, streams)
            self._maybe_tick()
            out_logits = pull(last, "logits")
        self._decode_s += sp.elapsed
        return out_logits

    def prefill_lane(self, lane: int, tokens, segment: int,
                     chunk: int | None = None) -> np.ndarray:
        """Chunked prefill of ONE lane's prompt through the paged ring
        (DESIGN.md §11): the prompt is consumed ``chunk`` tokens at a time
        by a single jitted scan of the paged decode body (bit-exact with
        token-at-a-time streaming), every other lane's decode state frozen
        by the active-lane mask — so the scheduler can interleave chunk
        writes with other lanes' decode steps, no stop-the-world.

        Per chunk the engine bulk-flushes the lane's freshly-filled ring
        pages down to its slow-store ``segment`` (one donated scatter,
        ``tiering.migrate.write_pages``), feeds the KV observation stream
        with the chunk's resident page ids so the daemon profiles prefilled
        pages immediately, and advances the daemon cadence by the chunk
        length.  Lane-addressed on purpose: this is the hand-off verb a
        disaggregated prefill tier would call against the shared slow
        store.  Returns the last prompt position's logits (vocab,) f32.
        """
        if not self.lane_mode:
            raise ValueError("prefill_lane requires ServeConfig.lanes > 0")
        if self.cache is None:
            self.start_lanes()
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size == 0:
            raise ValueError("prefill_lane needs at least one token")
        chunk = min(chunk or tokens.size, self._chunk_cap)
        self._lane_segments[lane] = segment
        active = np.zeros(self.scfg.lanes, bool)
        active[lane] = True
        logits = None
        for off in range(0, tokens.size, chunk):
            logits = self._prefill_lane_chunk(lane, tokens[off:off + chunk],
                                              chunk, active)
        return logits

    def _prefill_lane_chunk(self, lane: int, piece: np.ndarray, chunk: int,
                            active: np.ndarray) -> np.ndarray:
        """One lane-chunk scan: ragged pieces are padded to the fixed chunk
        width with valid=False no-op steps (one traced shape per chunk
        size), so a prompt tail never retraces the scan."""
        with span("engine/prefill_chunk"):
            n = piece.size
            tok = np.zeros((self.scfg.lanes, chunk), np.int32)
            tok[lane, :n] = piece
            valid = np.zeros((self.scfg.lanes, chunk), bool)
            valid[lane, :n] = True
            logits, self.cache, streams = self._prefill_paged_jit(
                self.params, self.cache, jnp.asarray(tok), jnp.asarray(valid),
                jnp.asarray(active), self._tier_reads())
            self._pos[lane] += n
            self._lane_active = active.copy()
            self._observe_lane_chunk(lane, tok, valid, streams, active)
            self._flush_kv_lanes(lanes=[lane])
            self._maybe_tick(n)
            return pull(logits[lane], "logits")

    def _observe_lane_chunk(self, lane: int, tok: np.ndarray,
                            valid: np.ndarray, streams: dict,
                            active: np.ndarray) -> None:
        """Feed one chunk's tiering streams in ONE observation batch per
        resource, other lanes (and tail padding) masked to -1."""
        if "embeddings" in self.daemon:
            with span("tier/observe", resource="embeddings"):
                self.daemon.observe("embeddings", jnp.asarray(
                    np.where(valid, tok, -1), jnp.int32))
        if "experts" in self.daemon and streams.get("router") is not None:
            with span("tier/observe", resource="experts"):
                router = streams["router"]      # (C, G, n_moe, L, 1, k)
                mask = jnp.asarray(valid.T)[:, None, None, :, None, None]
                self.daemon.observe("experts", jnp.where(mask, router, -1))
        if "kv" in self.daemon:
            with span("tier/observe", resource="kv"):
                sv = self._kv_lane_stream(active=active)
                if sv is None:
                    return
                mass, gids = sv                 # (L, S) post-chunk window
                km = streams.get("kv_mass")
                if self._kernel_mass and km is not None:
                    # per-step (C, G, n_attn, L, S) kernel mass:
                    # head-averaged, summed over the chunk's valid steps —
                    # the bulk analogue of the one-step stream
                    # advance_lanes feeds
                    per_step = jnp.mean(km, axis=(1, 2))      # (C, L, S)
                    agg = jnp.sum(
                        per_step * jnp.asarray(valid.T)[:, :, None],
                        axis=0)                               # (L, S)
                    mass = _live_mass(agg, gids >= 0)
                else:
                    mass = jnp.asarray(mass.reshape(-1))
                self._count_shared_mass(mass, gids)
                self.daemon.observe("kv", mass,
                                    jnp.asarray(gids.reshape(-1), jnp.int32))

    def _observe_lanes(self, tokens: np.ndarray, streams: dict) -> None:
        """Feed the tiering streams with inactive lanes masked to -1 pads."""
        act = self._lane_active
        if "embeddings" in self.daemon:
            with span("tier/observe", resource="embeddings"):
                toks = np.where(act, tokens, -1)
                self.daemon.observe("embeddings",
                                    jnp.asarray(toks, jnp.int32))
        if "experts" in self.daemon and streams.get("router") is not None:
            with span("tier/observe", resource="experts"):
                router = streams["router"]        # (G, n_moe, L, 1, k)
                mask = jnp.asarray(act)[None, None, :, None, None]
                self.daemon.observe("experts", jnp.where(mask, router, -1))
        if "kv" in self.daemon:
            with span("tier/observe", resource="kv"):
                sv = self._kv_lane_stream()
                if sv is None:
                    return
                mass, gids = sv
                if self._kernel_mass and self._last_kv_mass is not None:
                    # per-lane kernel mass, masked on the device to the live
                    # lanes' segment-mapped pages (same mask the gids carry)
                    mass = _live_mass(self._last_kv_mass, gids >= 0)
                else:
                    mass = jnp.asarray(mass.reshape(-1))
                self._count_shared_mass(mass, gids)
                self.daemon.observe("kv", mass,
                                    jnp.asarray(gids.reshape(-1), jnp.int32))

    def reset_lane(self, lane: int) -> None:
        """Return a lane to its initial state for a fresh request admission:
        ring bookkeeping, O(1) recurrent states, and the lane position go
        back to their INIT values from the pristine template (page payloads
        may stay — ``page_len`` masks them)."""
        def clear(entry: dict, tmpl: dict, idx, tmpl_idx) -> None:
            for k, v in entry.items():
                if k in ("k_pages", "v_pages"):
                    continue
                entry[k] = v.at[idx].set(tmpl[k][tmpl_idx])
        for entry, tmpl in zip(self.cache["blocks"],
                               self._lane_init["blocks"]):
            if isinstance(entry, dict):
                clear(entry, tmpl, (slice(None), lane), (slice(None), 0))
        for entry, tmpl in zip(self.cache.get("prologue", []),
                               self._lane_init.get("prologue", [])):
            clear(entry, tmpl, lane, 0)
        self.cache["pos"] = self.cache["pos"].at[lane].set(0)
        self._pos[lane] = 0
        self._invalidate_lane_flush(lane)
        self._lane_pages[lane] = -1
        self._lane_full[lane] = False

    def preempt_lane(self, lane: int) -> dict:
        """Evict a lane's request so the lane can serve someone else.

        The lane's resident ring pages are force-flushed down to its KV
        slow-store segment (the migration data plane — an exact snapshot of
        the ring survives outside it), while the per-lane bookkeeping and
        everything the tiered KV payload does not carry (O(1) recurrent
        states, sibling attention positions beyond the representative entry,
        the dense prologue ring) is snapshotted host-side into the returned
        residual.  :meth:`resume_lane` restores bit-exactly."""
        self._flush_kv_lanes(lanes=[lane], force=True)
        residual = {"pos": int(self._pos[lane]),
                    "segment": int(self._lane_segments[lane]),
                    # page-table row + publish witnesses travel with the
                    # request: its claim on shared pool pages survives the
                    # lane (refcounts are the scheduler's, unchanged here)
                    "pages": self._lane_pages[lane].copy(),
                    "full": self._lane_full[lane].copy(),
                    "blocks": [], "prologue": []}
        rep = self._paged_entry()
        for entry in self.cache["blocks"]:
            if not isinstance(entry, dict):
                residual["blocks"].append({})
                continue
            skip = ("k_pages", "v_pages") if entry is rep else ()
            residual["blocks"].append(
                {k: pull(v[:, lane], "lane_state") for k, v in entry.items()
                 if k not in skip})
        for entry in self.cache.get("prologue", []):
            residual["prologue"].append(
                {k: pull(v[lane], "lane_state") for k, v in entry.items()})
        return residual

    def resume_lane(self, lane: int, residual: dict) -> int:
        """Re-install a preempted request into a lane: residual bookkeeping
        is restored and the representative entry's resident ring pages are
        gathered back through the tiered KV store (fast-tier copy when
        promoted, slow-tier fallback — bit-exact either way).  Returns the
        number of ring pages gathered back up (the consumer-side hand-off
        volume, DESIGN.md §13)."""
        for entry, snap in zip(self.cache["blocks"], residual["blocks"]):
            for k, v in snap.items():
                entry[k] = entry[k].at[:, lane].set(
                    jnp.asarray(v, entry[k].dtype))
        for entry, snap in zip(self.cache.get("prologue", []),
                               residual["prologue"]):
            for k, v in snap.items():
                entry[k] = entry[k].at[lane].set(jnp.asarray(v, entry[k].dtype))
        self.cache["pos"] = self.cache["pos"].at[lane].set(residual["pos"])
        self._pos[lane] = residual["pos"]
        self._invalidate_lane_flush(lane)
        self._lane_pages[lane] = residual.get("pages", -1)
        self._lane_full[lane] = residual.get("full", False)
        segment = residual["segment"]
        # restore the lane->segment binding NOW, not at the next
        # advance_lanes: a hand-off install may flush or publish this lane
        # (e.g. a max_new=1 request finishing at install) before any step
        self._lane_segments[lane] = segment
        entry = self._paged_entry()
        if entry is None or segment < 0:
            return 0
        pos = self._pos[lane:lane + 1]
        plen, cur = self._ring_geometry(pos, self.scfg.hot_slots,
                                        self.scfg.page_t)     # (1, S), (1,)
        local = self._ring_page_ids(plen, cur, pos, self.scfg.page_t)[0]
        slots = np.flatnonzero(local >= 0)
        if slots.size == 0:
            return 0
        # shared pool pages re-gather from the pool, private ones from the
        # segment — the page-table row restored above decides per page
        tabled = self._lane_pages[lane, local[slots]]
        gids = np.where(tabled >= 0, tabled,
                        segment * self.pages_per_seq + local[slots])
        rows = self.daemon["kv"].read_rows(jnp.asarray(gids, jnp.int32))
        rows = jnp.moveaxis(rows, 0, 1)          # (G, n, T, hkv, dk+dv)
        dk = self._kv_split_width()
        entry["k_pages"] = entry["k_pages"].at[:, lane, slots].set(
            rows[..., :dk].astype(entry["k_pages"].dtype))
        entry["v_pages"] = entry["v_pages"].at[:, lane, slots].set(
            rows[..., dk:].astype(entry["v_pages"].dtype))
        for i, s in enumerate(slots):
            self._kv_flushed[(lane, int(s))] = (int(gids[i]),
                                                int(plen[0, s]))
        return int(slots.size)

    def _kv_split_width(self) -> int:
        """Last-axis K width inside a concatenated [K | V] payload row."""
        cfg = self.cfg
        if cfg.mla is not None:
            return cfg.mla.kv_lora + cfg.mla.d_rope
        return cfg.head_dim

    # -- disaggregated prefill/decode hand-off (DESIGN.md §13) ----------------
    def handoff_lane(self, lane: int) -> dict:
        """Producer-side hand-off: detach a finished prefill from its lane.

        Mechanically a preemption — the force-flush pushes every resident
        ring page down into the request's slow-store segment
        (``migrate.write_pages``) and the residual snapshots everything the
        KV payload does not carry — plus the fabric metering:
        ``handoff_bytes`` counts the whole consumed prefix once, the bulk
        KV bytes that crossed the slow tier producer-side (each page was
        flushed exactly once as prefill filled it, or here if partial).
        The residual is the hand-off token a decode worker passes to
        :meth:`install_handoff`."""
        residual = self.preempt_lane(lane)
        n_pages = -(-residual["pos"] // self.scfg.page_t)
        row = self.daemon["kv"].mem.row_bytes if "kv" in self.daemon else 0
        residual["handoff_bytes"] = n_pages * row
        return residual

    def segment_resident(self, residual: dict) -> bool:
        """Consumer-side admission gate (DESIGN.md §13): is the hand-off's
        consumed prefix fully write-witnessed in the slow store?  Checks
        every page up to ``residual["pos"]`` — the final, possibly partial,
        page included (the hand-off force-flush writes it) — through the
        request's copy-on-write page table, so admission-matched shared
        pool pages count via their pool row (DESIGN.md §12)."""
        if "kv" not in self.daemon or residual["segment"] < 0:
            return True
        gids = tm.segment_page_ids(
            residual["segment"], residual["pos"], self.scfg.page_t,
            self.pages_per_seq, table=residual.get("pages"))
        return bool(self.daemon["kv"].pages_written(gids).all())

    def install_handoff(self, lane: int, residual: dict) -> int:
        """Consumer-side hand-off: install a prefilled request into a decode
        lane, pulling its ring window back up THROUGH the placement-table
        read path (``resume_lane``'s ``read_rows`` — fast-tier copy when the
        daemon already promoted the page, slow-tier gather otherwise, so the
        tiering daemon treats the new request's pages exactly like any
        slow-resident data).  Refuses a segment the producer has not fully
        flushed — callers gate admission on :meth:`segment_resident` first.
        Returns the consumer-side hand-off bytes (gathered pages x row)."""
        if not self.segment_resident(residual):
            raise RuntimeError(
                f"segment {residual['segment']} not fully resident — "
                "hand-off installed before the prefill flush completed")
        gathered = self.resume_lane(lane, residual)
        row = self.daemon["kv"].mem.row_bytes if "kv" in self.daemon else 0
        return gathered * row

    def _invalidate_lane_flush(self, lane: int) -> None:
        for key in [k for k in self._kv_flushed if k[0] == lane]:
            del self._kv_flushed[key]

    # -- content-addressed KV reuse (repro.cache, DESIGN.md §12) --------------
    def install_lane_pages(self, lane: int, run: dict[int, int]
                           ) -> tuple[int, int]:
        """Fast-forward a lane over one CONSECUTIVE run of admission-matched
        pages: install the run's ring-window tail from the shared pool and
        jump the lane position past the run, no forward pass (DESIGN.md
        §12).  ``run`` maps local page idx -> pool gid; pages before the
        window tail fall outside the attention ring and carry no payload
        (streaming would have wrapped over them identically) but still
        count as prefill tokens saved.  Installed slots are marked clean in
        the flush tracker — copy-on-write: the ring never writes a shared
        page back.  Returns the pool reads' (fast, slow) placement split so
        the scheduler can charge them to the admitting tenant (the reads
        themselves are metered on the "kv" resource by read_rows)."""
        if self.reuse is None:
            raise ValueError("install_lane_pages requires reuse_pages > 0")
        locals_ = np.asarray(sorted(run), np.int64)
        if locals_.size == 0:
            return 0, 0
        if not np.all(np.diff(locals_) == 1):
            raise ValueError("install run must be consecutive local pages")
        gids = np.asarray([run[int(j)] for j in locals_], np.int64)
        S, T = self.scfg.hot_slots, self.scfg.page_t
        sel, gsel = locals_[-S:], gids[-S:]
        h = self.daemon["kv"]
        _, hit = h.lookup(jnp.asarray(gsel, jnp.int32))
        fast_n = int(pull(hit, "reuse_hit").sum())
        rows = h.read_rows(jnp.asarray(gsel, jnp.int32))
        rows = jnp.moveaxis(rows, 0, 1)          # (G, n, T, hkv, dk+dv)
        new_pos = int(locals_[-1] + 1) * T
        dec.install_pages(self.cache, lane, sel % S, rows,
                          dk=self._kv_split_width(), page_t=T,
                          new_pos=new_pos)
        self._pos[lane] = new_pos
        self._lane_pages[lane, locals_] = gids
        cur = (new_pos // T) % S
        for j, g in zip(sel % S, gsel):
            if int(j) != cur:                    # cur slot was re-zeroed
                self._kv_flushed[(lane, int(j))] = (int(g), T)
        self.reuse.note_consumed(locals_.size)   # tokens_saved: consumed runs
        return fast_n, int(gsel.size - fast_n)

    def publish_lane(self, lane: int, tokens) -> int:
        """Publish a finishing request's completed KV pages into the shared
        pool: force-flush the lane (its segment becomes an exact ring
        snapshot), index every full page of its appended token stream whose
        slow row is witnessed complete, and copy NEW pages' payloads
        segment -> pool in ONE fused ``copy_rows``.  Pages already indexed
        (e.g. installed at admission) deduplicate to an LRU touch.
        Returns the number of newly published pages."""
        if self.reuse is None:
            return 0
        toks = np.asarray(tokens).ravel()
        pos = int(self._pos[lane])
        n_pages = min(toks.size, pos) // self.scfg.page_t
        if n_pages <= 0 or self._lane_segments[lane] < 0:
            return 0
        self._flush_kv_lanes(lanes=[lane], force=True)
        witness = self._lane_full[lane] | (self._lane_pages[lane] >= 0)
        new = self.reuse.publish(toks, n_pages, mask=witness)
        if not new:
            return 0
        seg = int(self._lane_segments[lane])
        src = [int(self._lane_pages[lane, j]) if self._lane_pages[lane, j] >= 0
               else seg * self.pages_per_seq + j for j, _ in new]
        dst = [gid for _, gid in new]
        self.daemon["kv"].copy_rows(np.asarray(src, np.int32),
                                    np.asarray(dst, np.int32))
        return len(new)

    def _count_shared_mass(self, mass: jax.Array, gids: np.ndarray) -> None:
        """Accumulate the flat observation mass landing on shared pool pages
        vs all resident pages — the shared-page mass share (BENCH kv_reuse).
        The sums stay on the device; :meth:`reuse_stats` reads them."""
        if self.reuse is None:
            return
        total, shared = _mass_split(
            mass, jnp.asarray(gids.reshape(-1), jnp.int32),
            self.reuse.base_gid)
        self.reuse_mass["total"] = self.reuse_mass["total"] + total
        self.reuse_mass["shared"] = self.reuse_mass["shared"] + shared

    def reuse_stats(self) -> dict | None:
        """Content-addressed store telemetry + the shared-page mass share."""
        if self.reuse is None:
            return None
        row = self.reuse.stats()
        total = float(self.reuse_mass["total"])
        row["shared_mass_share"] = (float(self.reuse_mass["shared"]) / total
                                    if total > 0 else 0.0)
        return row

    # -- tiering-state checkpoint (DESIGN.md §6) ------------------------------
    def save_tiering(self, mgr, step: int) -> None:
        """Checkpoint every resource's placement/profiling state through
        ``ckpt/manager.py`` (one pure pytree; the pending FIFOs are
        best-effort and re-derived from the next sketch epoch)."""
        mgr.save(step, self.daemon.state_dict())

    def load_tiering(self, mgr, step: int) -> None:
        """Warm-restore the placement maps from a checkpoint; resident fast
        rows are refilled from the bound slow stores (daemon.load_state), so
        a restarted server serves with a warm placement map immediately."""
        self.daemon.load_state(mgr.restore(step, self.daemon.state_dict()))

    # -- decode + NeoMem observation/cadence ----------------------------------
    def _advance(self, tok: jax.Array):
        """One decode step: run the jitted body, feed the tiering streams,
        tick the multiplexed daemon on its cadence."""
        with span("engine/advance") as sp:
            with span("engine/dispatch"):
                if self.scfg.paged:
                    out = self._decode_paged(self.params, self.cache, tok,
                                             self._tier_reads(), None)
                else:
                    out = self._decode(self.params, self.cache, tok,
                                       self.aux, self._tier_reads())
            if self._want_streams:
                logits, self.cache, streams = out
            else:
                (logits, self.cache), streams = out, {}
            self._pos += 1
            self._set_kv_mass(streams)
            self._observe(tok, streams)
            self._maybe_tick()
        self._decode_s += sp.elapsed
        return logits

    def _set_kv_mass(self, streams: dict) -> None:
        """Hold the step's kernel-exported (B, n_slots) page mass: the
        per-position (G, n_attn, B, S) stream head-averaged over layer
        groups and attention positions — the aggregate line-rate view one
        NeoProf device would see across the chip (DESIGN.md §10)."""
        km = streams.get("kv_mass")
        self._last_kv_mass = (jnp.mean(km, axis=(0, 1))
                              if km is not None else None)

    def _observe(self, tok: jax.Array, streams: dict) -> None:
        if "embeddings" in self.daemon:
            with span("tier/observe", resource="embeddings"):
                self.daemon.observe("embeddings", tok)
        if "experts" in self.daemon and streams.get("router") is not None:
            with span("tier/observe", resource="experts"):
                self.daemon.observe("experts", streams["router"])
        if "kv" in self.daemon:
            with span("tier/observe", resource="kv"):
                mass, ids = self._kv_page_stream()
                if self._kernel_mass and self._last_kv_mass is not None:
                    # kernel-true hotness: batch rows advance in lockstep
                    # over the same page ids, so the row-mean is the
                    # device's aggregate view of the step's attention mass
                    mass = jnp.mean(self._last_kv_mass, axis=0)
                if ids.size:
                    self.daemon.observe("kv", mass, ids)

    def _paged_entry(self) -> dict | None:
        """The representative paged-attention cache entry (first in-pattern).

        Its pages are the KV payload rows the tiered store carries; sibling
        attention positions (and the dense prologue) share the same ring
        geometry and travel in preemption residuals (see preempt_lane)."""
        return next((c for c in self.cache["blocks"]
                     if isinstance(c, dict) and "page_len" in c), None)

    def _ring_view(self) -> tuple[np.ndarray, np.ndarray, np.ndarray] | None:
        """Host view of the paged ring: (page_len (B, S), cur_slot (B,),
        pos (B,)) int32, equal to the device's group-0 ``page_len``,
        ``cur_slot`` and ``pos`` — but derived from the host-held positions
        (``self._pos``), never read back from the device.

        The ring's geometry is a pure function of a row's position: every
        paged entry and layer group appends one token per step by the rule
        of ``models/decode.py`` ``_append_attend_local`` (lines 440-449;
        ``_append_attend_sharded`` follows it), ``merge_cache`` (161-184)
        freezes an inactive lane or padded chunk step whole, ``pos``
        included, and ``install_pages`` (697-722) lays a lane out as
        streaming to ``new_pos`` would.  See :meth:`_ring_geometry`."""
        if self._paged_entry() is None:
            return None
        pos = self._pos.copy()
        plen, cur = self._ring_geometry(pos, self.scfg.hot_slots,
                                        self.scfg.page_t)
        return plen, cur, pos

    @staticmethod
    def _ring_geometry(pos: np.ndarray, n_slots: int, page_t: int
                       ) -> tuple[np.ndarray, np.ndarray]:
        """(page_len (B, S), cur_slot (B,)) int32 of rows standing at
        ``pos``, with T = page_t and S = n_slots: ``cur_slot = (p // T) %
        S`` holds ``p % T`` tokens, and the slot k behind it, ``(cur_slot -
        k) % S`` for k = 1..S-1, holds T once page ``p // T - k`` exists
        (``p // T - k >= 0``), else 0.  A page that fills moves
        ``cur_slot`` on at once and empties the next slot, so a row on a
        page boundary holds 0 tokens at ``cur_slot``."""
        pos = np.asarray(pos, np.int64)
        page = pos // page_t
        cur = page % n_slots
        back = (cur[:, None] - np.arange(n_slots)[None]) % n_slots
        plen = np.where(back == 0, (pos % page_t)[:, None],
                        np.where(page[:, None] >= back, page_t, 0))
        return plen.astype(np.int32), cur.astype(np.int32)

    @staticmethod
    def _ring_page_ids(plen: np.ndarray, cur: np.ndarray, pos: np.ndarray,
                       page_t: int) -> np.ndarray:
        """Per-row logical page id of every ring slot ((B, S); -1 = empty).

        cur_slot advances eagerly when a page fills, so the page being
        filled at cur is always floor(pos / page_t) — also on boundaries."""
        n_slots = plen.shape[1]
        cur_page = pos // page_t                             # (B,)
        slots = np.arange(n_slots)[None]                     # (1, S)
        ids = cur_page[:, None] - (cur[:, None] - slots) % n_slots
        return np.where((plen > 0) & (ids >= 0), ids, -1)

    def _ring_row0(self) -> tuple[np.ndarray, np.ndarray] | None:
        """Batch row 0's (page_len (S,), logical page ids (S,)) on the host,
        from the position-derived :meth:`_ring_view`."""
        view = self._ring_view()
        if view is None:
            return None
        plen, cur, pos = view
        return plen[0], self._ring_page_ids(plen, cur, pos,
                                            self.scfg.page_t)[0]

    def _kv_page_stream(self) -> tuple[jax.Array, jax.Array]:
        """Resident paged-KV window as (per-page fill, logical page ids).

        The fill (page_len) is the PROXY mass (``kv_mass_source="fill"``,
        and the change-tracking key for the slow-store flush); with the
        default kernel source the observer overrides it with the decode
        kernel's true per-page softmax mass (DESIGN.md §10).  Batch row 0
        is representative: all rows advance in lockstep.  Both come from
        the host-held lockstep position (:meth:`_ring_view`: ``cur_slot =
        (p // T) % S``, ``page_len`` T behind it while the page exists),
        with no read from the device."""
        row = self._ring_row0()
        if row is None:
            return jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.int32)
        fill, ids = row
        return jnp.asarray(fill, jnp.float32), jnp.asarray(ids, jnp.int32)

    def _kv_lane_stream(self, active: np.ndarray | None = None,
                        ) -> tuple[np.ndarray, np.ndarray] | None:
        """Lane mode: (mass (L, S), global page ids (L, S)) — each lane's
        resident ring pages mapped into its slow-store segment's address
        space; lanes outside ``active`` (default: the live mask) are -1.
        Host arrays built from the host-held lane positions
        (:meth:`_ring_view`: ``cur_slot = (p // T) % S``, ``page_len`` T
        behind it while the page exists), with no read from the device."""
        view = self._ring_view()
        if view is None:
            return None
        plen, cur, pos = view
        local = self._ring_page_ids(plen, cur, pos, self.scfg.page_t)
        act = self._lane_active if active is None else np.asarray(active, bool)
        gids = self._map_gids(local, act)
        mass = np.where(gids >= 0, plen, 0).astype(np.float32)
        return mass, gids

    def _map_gids(self, local: np.ndarray, act: np.ndarray) -> np.ndarray:
        """Resolve (L, S) local page ids to global store page ids through
        the per-lane page table: table entries (shared pool pages) win,
        everything else falls back to the private affine mapping
        ``segment * pages_per_seq + local``; invalid lanes/slots are -1."""
        seg = self._lane_segments[:, None].astype(np.int64)
        affine = seg * self.pages_per_seq + local
        lanes = np.arange(local.shape[0])[:, None]
        tabled = self._lane_pages[lanes, np.maximum(local, 0)]
        gids = np.where(tabled >= 0, tabled, affine)
        return np.where((local >= 0) & act[:, None] & (seg >= 0), gids, -1)

    def _flush_kv_slow(self) -> None:
        """Flush the resident paged-cache window down to the KV data plane.

        The ring of hot page slots is the authoritative copy of recent pages
        (DESIGN.md §3.2); before each daemon epoch the engine writes their
        payloads through ``write_rows`` — slow store always, plus the fast
        copies of promoted pages so neither reads nor demotion write-backs
        ever serve a stale snapshot.  Ring pages unchanged since the last
        flush (same page id, same fill) are skipped, and the flushed bytes
        are metered as ``flush_bytes``.  Batch row 0 is the representative
        payload, matching the mass proxy in _kv_page_stream.
        """
        with span("tier/flush"):
            h = self.daemon["kv"]
            if h.mem.buffers is None:
                return
            entry = self._paged_entry()
            if entry is None:
                return
            fill, ids = self._ring_row0()                 # page_len, ids
            changed = np.array([
                self._kv_flushed.get((0, slot))
                != (int(ids[slot]), int(fill[slot]))
                for slot in range(ids.shape[0])])
            ids = np.where(changed, ids, -1)             # -1 lanes are dropped
            if not (ids >= 0).any():
                return
            # batch row 0 is the representative payload; the [K|V] concat +
            # slot-major transpose + dual-tier scatter fuse in ONE donated op
            h.write_pages(ids, entry["k_pages"][:, :1],
                          entry["v_pages"][:, :1])
            for slot in np.flatnonzero(ids >= 0):
                self._kv_flushed[(0, slot)] = (int(ids[slot]), int(fill[slot]))

    def _flush_kv_lanes(self, lanes=None, force: bool = False) -> None:
        """Lane-mode KV flush: every active lane's resident ring pages go
        down to its slow-store segment through ``write_rows`` (real per-lane
        payloads, unlike the single-request row-0 representative).  Pages
        unchanged since the last flush are skipped unless ``force`` —
        preemption forces a full flush of the evicted lane so the slow store
        is an exact snapshot of its ring.

        Copy-on-write over shared pool pages (DESIGN.md §12): a ring slot
        holding a CLEAN shared page (installed at admission, fill
        unchanged) is never written back — the pool is authoritative, even
        under ``force``.  A slot whose shared mapping went stale (the ring
        wrote into it) forks: the page-table entry reverts to the lane's
        private segment page and the payload flushes there, so other
        referencing lanes keep the pool copy untouched."""
        with span("tier/flush"):
            h = self.daemon["kv"]
            if h.mem.buffers is None:
                return
            entry = self._paged_entry()
            if entry is None:
                return
            view = self._ring_view()
            if view is None:
                return
            plen, cur, pos = view
            local = self._ring_page_ids(plen, cur, pos, self.scfg.page_t)
            if lanes is None:
                act = self._lane_active
            else:
                act = np.zeros(self.scfg.lanes, bool)
                act[np.asarray(lanes, int)] = True
            gids = self._map_gids(local, act)            # (L, S)
            fill = np.where(gids >= 0, plen, 0).astype(np.int64)
            base = self.reuse.base_gid if self.reuse is not None else None
            ids = gids.copy()
            for lane, slot in np.argwhere(ids >= 0):
                key = (int(lane), int(slot))
                state = (int(gids[lane, slot]), int(fill[lane, slot]))
                if base is not None and gids[lane, slot] >= base:
                    if self._kv_flushed.get(key) == state:
                        ids[lane, slot] = -1         # clean shared page: CoW
                        continue
                    lp = int(local[lane, slot])          # dirty: private fork
                    self._lane_pages[lane, lp] = -1
                    priv = (int(self._lane_segments[lane]) * self.pages_per_seq
                            + lp)
                    ids[lane, slot] = gids[lane, slot] = priv
                    state = (priv, int(fill[lane, slot]))
                if not force and self._kv_flushed.get(key) == state:
                    ids[lane, slot] = -1
            if not (ids >= 0).any():
                return
            # bulk page-write verb: the (G, L, S, T, hkv, d) ring views go down
            # as ONE donated fused [K|V]-concat + transpose + dual-tier scatter
            h.write_pages(ids.reshape(-1), entry["k_pages"], entry["v_pages"])
            for lane, slot in np.argwhere(ids >= 0):
                self._kv_flushed[(int(lane), int(slot))] = (
                    int(gids[lane, slot]), int(fill[lane, slot]))
                if fill[lane, slot] >= self.scfg.page_t:
                    # witness: this local's slow row holds the complete page
                    self._lane_full[lane, local[lane, slot]] = True

    def read_rows(self, name: str, page_ids) -> jax.Array:
        """Serve payload rows for a resource: fast-tier copy when the page
        is resident, slow-tier fallback otherwise (bit-exact either way)."""
        return self.daemon[name].read_rows(page_ids)

    @property
    def step_count(self) -> int:
        """Engine steps so far (decode steps + prefilled prompt positions)."""
        return self._clock.steps

    def _maybe_tick(self, n: int = 1) -> None:
        """Advance the engine step counter by ``n`` (1 for a decode step, the
        chunk length for a prefill chunk) and run one daemon tick per
        migration-interval boundary crossed, flushing the KV ring first."""
        ticks = self._clock.advance(n)
        if not self.daemon.resources:
            return
        if not self._daemon_owner:
            # an attached worker engine (DESIGN.md §13) never drives the
            # shared daemon: migration cadence is the owner's; this worker's
            # dirty pages flush per chunk / at hand-off, not per tick
            return
        for _ in range(ticks):
            if "kv" in self.daemon:
                if self.lane_mode:
                    self._flush_kv_lanes()
                else:
                    self._flush_kv_slow()
            self.daemon.tick()

    # -- telemetry ------------------------------------------------------------
    def tier_stats(self) -> dict[str, dict]:
        """Per-resource telemetry rows (the BENCH_serve.json schema)."""
        for h in self.daemon.resources.values():
            h.stats.decode_s = self._decode_s
        return self.daemon.snapshot()

    @property
    def kv_tier(self) -> tm.ResourceHandle | None:
        """Deprecated: the KV resource handle (None when not paged)."""
        return self.daemon["kv"] if "kv" in self.daemon else None
