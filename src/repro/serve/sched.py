"""Continuous-batching request scheduler: many tenants, one tiered engine.

The request lifecycle (DESIGN.md §9) over the ServeEngine lane substrate:

    arrive ──> admit ──> prefill ──> decode ──> finish
                 ^                     │
                 └──── preempt <───────┘   (resume is bit-exact)

* **arrive/admit** — requests queue per tenant; free decode lanes are
  filled by a weighted-fair policy that reuses the daemon's
  demand-proportional quota split (`tiering.daemon.split_quota`) with
  per-tenant isolation weights: a tenant's target lane share is
  proportional to ``weight x (running + queued)``, clamped at its own
  demand.  Admission needs a free lane AND a free KV slow-store segment —
  when either is exhausted (the paper's "slow tier full" condition at the
  request level) arrivals stay queued.
* **prefill** — iteration-level continuous batching: every lane consumes
  exactly one token per engine step, a prompt token while prefilling, its
  last sampled token while decoding, so new requests join the running
  batch without draining it (the Orca-style schedule).
* **decode** — one `advance_lanes` call per step serves all lanes; the
  NeoMem daemon observes every tenant's streams and migrates on its own
  cadence between steps.  The paged ring is the per-lane fast tier; filled
  pages are flushed down to the lane's slow-store segment, so the ring
  wrapping over old pages is a real fast-tier eviction, not data loss.
* **preempt/finish** — the starvation guard: a tenant whose queue head has
  waited longer than ``preempt_patience`` steps while the tenant holds no
  lane in that pool preempts the most over-served tenant's youngest
  request.  Preemption force-flushes the lane's resident pages to the slow
  tier and snapshots the residual (`ServeEngine.preempt_lane`); resuming
  restores bit-exactly.

**Disaggregated prefill/decode** (DESIGN.md §13, ``SchedConfig.
prefill_lanes > 0``): the scheduler splits into two worker pools over the
SAME tiered slow store — the CXL-pooled hand-off fabric.  A dedicated
prefill engine (attached to the decode engine's daemon, its own lanes/
ring) runs only `ServeEngine.prefill_lane` chunks; each finished chunk's
pages flush down into the request's slow-store segment via the migration
data plane (``migrate.write_pages``).  When the last chunk lands the
request detaches as a hand-off residual (`ServeEngine.handoff_lane`) and
queues for the decode pool, which admits it only once its segment is
fully write-witnessed in the slow tier (`ServeEngine.segment_resident`)
and pulls the ring window back up THROUGH the placement-table read path
(`ServeEngine.install_handoff`) — the daemon promotes the new request's
hot pages exactly like any slow-resident data.  The first output token is
emitted (TTFT stamped) at hand-off completion, from the final chunk's
last-position logits.  Outputs are bit-exact against the unified
scheduler: sampling keys derive from (seed, rid, token index) and the
chunked scan equals streaming, so the split changes WHERE work runs,
never what is computed.

Each pool accrues wall time on its own **virtual worker clock**
(``Scheduler.clock``): a worker's clock only advances while its own
engine/host work runs, so on a single host the decode clock measures
decode-lane latency the way a dedicated decode box would experience it —
hand-off install and gather costs included, the other worker's prefill
scans excluded.  The unified scheduler runs everything on the decode
clock, which is how a colocated deployment experiences a long prompt.

Per-tenant telemetry rides the same `TierStats` schema the daemon uses:
each step the scheduler looks the lanes' resident pages up in the KV
placement map and meters fast/slow reads per tenant, so tenant isolation is
observable in the same units as resource tiering (`benchmarks/
traffic_bench.py` emits both).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode as dec
from repro.serve.engine import ServeEngine
from repro.spans import pull, span
from repro.tiering.daemon import split_quota
from repro.tiering.stats import TierStats


@dataclasses.dataclass(frozen=True)
class Tenant:
    """One traffic source multiplexed onto the engine."""

    name: str
    weight: float = 1.0        # isolation weight in the lane/quota split


@dataclasses.dataclass
class SchedConfig:
    preempt_patience: int = 16   # steps a lane-less tenant waits before
    #                              its queue head may preempt someone
    max_queue: int = 4096        # hard bound on queued requests
    # Chunked prefill (DESIGN.md §11): a prefilling lane consumes up to
    # `prefill_chunk` prompt tokens per scheduler step through ONE jitted
    # scan (`ServeEngine.prefill_lane`) instead of one engine step per
    # token; decode lanes keep stepping between chunks.  0 = legacy
    # token-at-a-time streaming; prompts no longer than the chunk also
    # fall back to the streaming loop (bit-exact either way).
    prefill_chunk: int = 0
    # Disaggregation (DESIGN.md §13): > 0 reserves a DISJOINT pool of that
    # many prefill-worker lanes on an attached engine; the decode pool
    # keeps the owning engine's lanes.  Requests prefill chunk-by-chunk on
    # the prefill pool, hand off through the shared slow store, and decode
    # on the decode pool — requires prefill_chunk > 0 (the chunked scan is
    # the prefill worker's unit of work).  Size the KV slow store for both
    # pools: ServeConfig.kv_segments >= lanes + prefill_lanes, plus slack
    # for hand-offs in flight.  0 = unified scheduling (unchanged).
    prefill_lanes: int = 0
    # Sampling (models/decode.py::sample_tokens): temperature <= 0 is exact
    # argmax (the default — zero overhead); with temperature > 0 each
    # emitted token is drawn with a per-request PRNG key folded from
    # (seed, request id, tokens emitted), so a trace replays bit-identically
    # regardless of lane assignment, admission order, or preemptions.
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0                # sampling seed (seeded per trace)
    # Content-addressed admission matching (repro.cache, DESIGN.md §12),
    # active when the engine has a reuse pool (ServeConfig.reuse_pages):
    # "substring" verifies every full prompt page independently and skips
    # holes; "prefix" stops at the first miss (the vLLM-style baseline —
    # strictly a subset of substring, kept for the kv_reuse A/B).
    reuse_match: str = "substring"


@dataclasses.dataclass
class Request:
    """One request's lifecycle record (see module docstring).

    ``state`` walks: queued -> running -> finished in the unified
    scheduler (preempted in between on a starvation guard); the
    disaggregated scheduler inserts the hand-off leg — queued -> prefill
    (on a prefill-pool lane) -> handoff (detached, waiting for slow-tier
    residency + a decode lane) -> running (decode pool) -> finished."""

    rid: int
    tenant: str
    prompt: np.ndarray           # (P,) int32 prompt tokens
    max_new: int                 # output tokens to generate
    arrival_step: int = 0
    state: str = "queued"  # queued | prefill | handoff | running | preempted
    #                        | finished
    lane: int = -1               # pool-local lane index (state names the pool)
    segment: int = -1            # KV slow-store segment (kept while preempted)
    pos: int = 0                 # tokens consumed so far (prompt + generated)
    out: list = dataclasses.field(default_factory=list)
    residual: dict | None = None  # preemption/hand-off snapshot (engine)
    queued_since: int = 0
    admitted_step: int = -1
    finished_step: int = -1
    preemptions: int = 0
    arrival_time: float = 0.0
    token_times: list = dataclasses.field(default_factory=list)
    # per-token worker-clock stamps + the step each token was emitted on:
    # the disagg A/B classifies decode gaps by what the prefill worker was
    # doing between the two stamps (benchmarks/traffic_bench.py)
    token_clock: list = dataclasses.field(default_factory=list)
    token_steps: list = dataclasses.field(default_factory=list)
    key: np.ndarray | None = None  # per-request PRNG key (sampling mode)
    # admission-matched shared pages not yet installed: local page -> pool
    # gid (install consumes runs as prefill reaches them)
    matched: dict = dataclasses.field(default_factory=dict)
    # every pool gid this request holds a reference on (released at finish;
    # survives preemption — the claim belongs to the request, not the lane)
    shared_gids: list = dataclasses.field(default_factory=list)

    @property
    def n_prompt(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def prefilling(self) -> bool:
        return self.pos < self.n_prompt


class Scheduler:
    """Multiplexes tenants' requests onto one ServeEngine/NeoMemDaemon."""

    def __init__(self, engine: ServeEngine, tenants: list[Tenant],
                 scfg: SchedConfig | None = None):
        if not engine.lane_mode:
            raise ValueError("Scheduler requires an engine with "
                             "ServeConfig.lanes > 0")
        if not tenants:
            raise ValueError("at least one tenant required")
        self.eng = engine
        self.tenants = {t.name: t for t in tenants}
        self.scfg = scfg or SchedConfig()
        self.n_lanes = engine.scfg.lanes
        n_seg = engine.scfg.kv_segments or self.n_lanes
        self.free_segments = list(range(n_seg))
        self.lanes: list[Request | None] = [None] * self.n_lanes
        self.queue: list[Request] = []      # arrival order (incl. preempted)
        self.finished: list[Request] = []
        self.step_count = 0
        self.preemptions = 0
        self.queued_peak = 0
        self._next_rid = 0
        self.tenant_stats = {t: TierStats(name=t) for t in self.tenants}
        self._sample_master = jax.random.PRNGKey(self.scfg.seed)
        # per-worker virtual clocks (module docstring): unified mode runs
        # everything on "decode"; disagg charges each pool's engine/host
        # work to its own worker
        self.clock = {"prefill": 0.0, "handoff": 0.0, "decode": 0.0}
        self._turn_open: tuple[str, span] | None = None   # (role, its span)
        # prefill_busy[s]: was a prefill in flight during step s?  (the
        # disagg A/B's gap classifier — maintained in both modes)
        self.prefill_busy: list[bool] = []
        # -- disaggregated pools (DESIGN.md §13) --
        self.disagg = self.scfg.prefill_lanes > 0
        self.handoff: list[Request] = []    # detached, awaiting decode admit
        self.handoffs = 0
        self.handoff_bytes_out = 0          # producer flush (prefill -> slow)
        self.handoff_bytes_in = 0           # consumer gather (slow -> decode)
        self.handoff_peak = 0
        if self.disagg:
            if self.scfg.prefill_chunk <= 0:
                raise ValueError(
                    "disaggregated scheduling (prefill_lanes > 0) requires "
                    "prefill_chunk > 0 — the chunked scan is the prefill "
                    "worker's unit of work (DESIGN.md §13)")
            pcfg = dataclasses.replace(engine.scfg,
                                       lanes=self.scfg.prefill_lanes)
            self.peng = ServeEngine(engine.cfg, engine.params, pcfg,
                                    ep_axes=engine.ep, attach_to=engine)
            self.pre_lanes: list[Request | None] = \
                [None] * self.scfg.prefill_lanes
        else:
            self.peng = None
            self.pre_lanes = []
        if engine.cache is None:
            engine.start_lanes()
        if self.peng is not None and self.peng.cache is None:
            self.peng.start_lanes()

    # -- request intake -------------------------------------------------------
    def submit(self, tenant: str, prompt: np.ndarray,
               max_new: int) -> Request:
        """Queue a request (the *arrive* stage).  Raises when the queue is
        at its bound — backpressure belongs to the caller, not silent drop."""
        if tenant not in self.tenants:
            raise KeyError(f"unknown tenant {tenant!r}")
        if len(self.queue) >= self.scfg.max_queue:
            raise RuntimeError(f"queue full ({self.scfg.max_queue})")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0 or max_new < 1:
            raise ValueError("need a non-empty prompt and max_new >= 1")
        if prompt.size + max_new > self.eng.scfg.max_seq:
            raise ValueError(
                f"request length {prompt.size}+{max_new} exceeds the "
                f"max_seq={self.eng.scfg.max_seq} KV segment")
        req = Request(rid=self._next_rid, tenant=tenant, prompt=prompt,
                      max_new=max_new, arrival_step=self.step_count,
                      queued_since=self.step_count,
                      arrival_time=time.perf_counter())
        if self.scfg.temperature > 0.0:
            # identity-derived key: (seed, rid) — lane/preemption-invariant
            req.key = pull(jax.random.fold_in(self._sample_master, req.rid),
                           "sample_key")
        self._next_rid += 1
        self.queue.append(req)
        self.queued_peak = max(self.queued_peak, len(self.queue))
        return req

    # -- worker clocks --------------------------------------------------------
    @contextlib.contextmanager
    def _turn(self, role: str):
        """Charge the block's wall time, its ``sched/turn`` span, to
        ``role``'s virtual clock.  A hand-off install inside it is charged
        to the hand-off clock instead (``_install``)."""
        sp = span("sched/turn", role=role)
        self._turn_open = (role, sp)
        try:
            with sp:
                yield
        finally:
            self._turn_open = None
            self.clock[role] += sp.elapsed

    def _now(self, role: str) -> float:
        """``role``'s virtual clock reading, the open turn included."""
        t = self.clock[role]
        if self._turn_open is not None and self._turn_open[0] == role:
            t += self._turn_open[1].elapsed
        return t

    # -- admission / preemption ----------------------------------------------
    def _pool(self, role: str) -> tuple[ServeEngine, list]:
        if role == "prefill":
            return self.peng, self.pre_lanes
        return self.eng, self.lanes

    def _running_by_tenant(self, lanes: list) -> dict[str, int]:
        counts = {t: 0 for t in self.tenants}
        for r in lanes:
            if r is not None:
                counts[r.tenant] += 1
        return counts

    def _candidates(self, role: str) -> list[Request]:
        """Admissible requests for a pool, in service order.

        Unified mode: the whole queue competes for the decode pool.  Disagg
        prefill pool: fresh arrivals and mid-prefill preemptions, queue
        (arrival) order.  Disagg decode pool: hand-offs whose segment has
        become fully slow-tier resident (the fabric admission gate) plus
        decode-phase preemptions, oldest wait first."""
        if not self.disagg:
            return list(self.queue)
        if role == "prefill":
            return [r for r in self.queue
                    if r.state == "queued"
                    or (r.state == "preempted" and r.prefilling)]
        ready = [r for r in self.handoff
                 if self.eng.segment_resident(r.residual)]
        ready += [r for r in self.queue
                  if r.state == "preempted" and not r.prefilling]
        return sorted(ready, key=lambda r: (r.queued_since, r.rid))

    def _lane_shares(self, role: str, cands: list[Request]) -> dict[str, int]:
        """Target lane allocation per tenant for one pool: the daemon's
        quota split applied to lanes — demand = running + waiting,
        weighted, clamped."""
        _, lanes = self._pool(role)
        n_pool = len(lanes)
        demands = self._running_by_tenant(lanes)
        for r in cands:
            demands[r.tenant] += 1
        caps = {t: n_pool for t in self.tenants}
        weights = {t: self.tenants[t].weight for t in self.tenants}
        return split_quota(n_pool, demands, caps, weights)

    def _admit_pool(self, role: str) -> None:
        with span("sched/admit", pool=role):
            _, lanes = self._pool(role)
            if self._candidates(role):
                self._maybe_preempt(role)
            free = [ln for ln, r in enumerate(lanes) if r is None]
            while free:
                cands = self._candidates(role)
                if not cands:
                    break
                shares = self._lane_shares(role, cands)
                running = self._running_by_tenant(lanes)
                heads: dict[str, Request] = {}
                for r in cands:              # service order: first is head
                    heads.setdefault(r.tenant, r)
                # the waiting tenant with the largest share deficit wins
                # the lane; deficit <= 0 everywhere falls back to FIFO
                pick = max(heads.values(),
                           key=lambda r: (shares.get(r.tenant, 0)
                                          - running[r.tenant],
                                          -r.queued_since, -r.rid))
                if shares.get(pick.tenant, 0) - running[pick.tenant] <= 0:
                    pick = cands[0]
                if not self._install(pick, free[0], role):
                    # no free KV segment for a fresh request — a preempted
                    # one (which kept its segment) can still take the lane
                    pre = next((r for r in cands
                                if r.state == "preempted"), None)
                    if pre is None or not self._install(pre, free[0], role):
                        break
                free.pop(0)

    def _install(self, req: Request, lane: int, role: str = "decode") -> bool:
        with span("sched/install", rid=req.rid):
            return self._install_request(req, lane, role)

    def _install_request(self, req: Request, lane: int, role: str) -> bool:
        eng, lanes = self._pool(role)
        if req.state == "handoff":
            # decode-side hand-off completion (DESIGN.md §13): pull the
            # ring window up through the placement table, then emit the
            # first output token from the final chunk's logits — TTFT is
            # stamped HERE, when the hand-off completes
            residual = req.residual
            logits_row = residual.pop("logits")
            # the gather itself is the fabric transfer (CXL port / DMA
            # engine), charged to its own clock: the decode worker's clock
            # keeps only what decode actually executes — the placement-
            # table slow-tier pulls during advance — so hand-off traffic
            # shows up in clock.handoff_s and bytes_in, not as fake TPOT
            with span("sched/handoff", rid=req.rid) as sp:
                self.handoff_bytes_in += eng.install_handoff(lane, residual)
            self.clock["handoff"] += sp.elapsed
            if self._turn_open is not None:
                self.clock[self._turn_open[0]] -= sp.elapsed
            req.residual = None
            self.handoff.remove(req)
            req.state, req.lane = "running", lane
            lanes[lane] = req
            self._emit(req, logits_row)
            return True
        if req.state == "preempted":
            eng.resume_lane(lane, req.residual)
            req.residual = None
        else:
            if not self.free_segments:
                return False
            req.segment = self.free_segments.pop(0)
            req.admitted_step = self.step_count
            eng.reset_lane(lane)
            if eng.reuse is not None:
                # content-addressed admission matching (DESIGN.md §12):
                # matched pages install as prefill reaches them, so the
                # lane only scans the unmatched gaps; the match acquires
                # one reference per page, released when the request ends
                res = eng.reuse.match(req.prompt,
                                      mode=self.scfg.reuse_match)
                req.matched = dict(res.pages)
                req.shared_gids = list(res.pages.values())
        req.state = "prefill" if role == "prefill" else "running"
        req.lane = lane
        lanes[lane] = req
        self.queue.remove(req)
        return True

    def _maybe_preempt(self, role: str = "decode") -> None:
        """Per-pool starvation guard: one preemption per step, only for a
        tenant that holds NO lane in this pool and whose waiting head has
        out-waited the patience.  On the prefill pool the victim is mid-
        prefill — its chunk boundary is the preemption point."""
        _, lanes = self._pool(role)
        if any(r is None for r in lanes):
            return                            # a free lane serves them first
        running = self._running_by_tenant(lanes)
        starving = None
        for r in self._candidates(role):      # service order
            waited = self.step_count - r.queued_since
            if running[r.tenant] == 0 and waited >= self.scfg.preempt_patience:
                starving = r
                break
        if starving is None:
            return
        if starving.state == "queued" and not self.free_segments:
            return                            # nowhere to hold its KV yet
        # victim tenant: most over-served per unit weight; victim request:
        # its youngest admission (least sunk work discarded)
        cands = [t for t, n in running.items()
                 if n > 0 and t != starving.tenant]
        if not cands:
            return
        # a zero-weight tenant holding lanes is infinitely over-served
        victim_t = max(cands,
                       key=lambda t: running[t] / max(self.tenants[t].weight,
                                                      1e-9))
        victim = max((r for r in lanes
                      if r is not None and r.tenant == victim_t),
                     key=lambda r: r.admitted_step)
        lane = victim.lane
        self._preempt(victim)
        # the freed lane goes to the starving head DIRECTLY — handing it to
        # the weighted-fair pick would return it to the hog and thrash
        self._install(starving, lane, role)

    def _preempt(self, req: Request) -> None:
        eng, lanes = (self.peng, self.pre_lanes) if req.state == "prefill" \
            else (self.eng, self.lanes)
        lane = req.lane
        req.residual = eng.preempt_lane(lane)
        lanes[lane] = None
        req.state, req.lane = "preempted", -1
        req.queued_since = self.step_count
        req.preemptions += 1
        self.preemptions += 1
        self.queue.append(req)
        self.queued_peak = max(self.queued_peak, len(self.queue))

    def _to_handoff(self, lane: int, req: Request,
                    logits_row: np.ndarray) -> None:
        """Producer-side hand-off: detach a finished prefill from its lane
        (force-flushing its pages down the fabric) and queue it for decode
        admission, final-chunk logits riding along for the first token."""
        residual = self.peng.handoff_lane(lane)
        self.handoff_bytes_out += residual.pop("handoff_bytes")
        residual["logits"] = logits_row
        self.handoffs += 1
        self.pre_lanes[lane] = None
        req.residual = residual
        req.state, req.lane = "handoff", -1
        req.queued_since = self.step_count   # now waiting on the decode pool
        self.handoff.append(req)
        self.handoff_peak = max(self.handoff_peak, len(self.handoff))

    def _finish(self, req: Request) -> None:
        with span("sched/finish", rid=req.rid):
            if self.eng.reuse is not None:
                # publish BEFORE the segment is recycled (the pool copy
                # sources from it), then drop this request's claims on
                # shared pages
                stream = (np.concatenate(
                    [req.prompt, np.asarray(req.out[:-1], np.int32)])
                    if len(req.out) > 1 else req.prompt)
                self.eng.publish_lane(req.lane, stream)
                if req.shared_gids:
                    self.eng.reuse.release(req.shared_gids)
                    req.shared_gids = []
            self.lanes[req.lane] = None
            self.free_segments.append(req.segment)
            req.state, req.lane = "finished", -1
            req.finished_step = self.step_count
            self.finished.append(req)

    # -- token emission -------------------------------------------------------
    def _emit(self, req: Request, logits_row: np.ndarray) -> None:
        """Emit one output token for ``req`` outside the batched decode
        sweep (the hand-off first token): same identity-derived key fold,
        so the draw is bit-identical to the unified scheduler's."""
        req.out.append(self._sample_one(req, logits_row))
        req.token_times.append(time.perf_counter())
        req.token_clock.append(self._now("decode"))
        req.token_steps.append(self.step_count)
        if len(req.out) >= req.max_new:
            self._finish(req)

    def _sample_one(self, req: Request, logits_row: np.ndarray) -> int:
        row = np.asarray(logits_row, np.float32)
        if self.scfg.temperature <= 0.0:
            return int(np.argmax(row))
        folded = dec.fold_lane_keys(
            jnp.asarray(req.key[None, :]),
            jnp.asarray([len(req.out)], jnp.uint32))
        return int(pull(dec.sample_tokens(
            jnp.asarray(row[None]), folded,
            temperature=self.scfg.temperature, top_p=self.scfg.top_p),
            "sample")[0])

    # -- the serving loop -----------------------------------------------------
    def step(self) -> None:
        """One scheduler iteration.

        Unified mode: admit, advance every lane (one decode token, or one
        prefill CHUNK for long-prompt admissions), sample/finish, meter
        per-tenant tier stats.  With ``SchedConfig.prefill_chunk > 0`` a
        prefilling request whose prompt is longer than one chunk goes
        through the chunked path: its lane consumes up to ``prefill_chunk``
        prompt tokens via ``ServeEngine.prefill_lane`` while the other
        lanes take their normal decode step — no stop-the-world.  The first
        output token is emitted (and its TTFT stamped) the step the LAST
        chunk lands, from the same last-prompt-position logits the
        streaming path would produce.

        Disaggregated mode (``prefill_lanes > 0``): decode-side hand-off
        admission, then the prefill worker's turn (one chunk or matched
        install per busy prefill lane) on the prefill clock, then the
        decode worker's turn (one batched decode step over the decode
        lanes) on the decode clock."""
        with span("sched/step", step=self.step_count):
            if self.disagg:
                self._step_disagg()
            else:
                with self._turn("decode"):
                    self._step_unified()

    def _step_disagg(self) -> None:
        with self._turn("decode"):
            self._admit_pool("decode")       # hand-offs may emit first tokens
        with self._turn("prefill"):
            self._admit_pool("prefill")
            self.prefill_busy.append(
                any(r is not None for r in self.pre_lanes))
            self._prefill_turn()
        with self._turn("decode"):
            self._decode_turn()
            self.step_count += 1

    def _prefill_turn(self) -> None:
        """The prefill worker's step: each busy prefill lane consumes one
        matched-page install OR one chunk scan; a lane whose last chunk
        lands detaches its request into the hand-off queue."""
        chunk = self.scfg.prefill_chunk
        page_t = self.eng.scfg.page_t
        for lane, req in enumerate(list(self.pre_lanes)):
            if req is None:
                continue
            if req.matched:
                # content-addressed fast-forward (DESIGN.md §12) — cannot
                # complete the prompt (the final page is never matchable),
                # so the hand-off always ends on a real chunk scan
                j = req.pos // page_t
                if req.pos % page_t == 0 and j in req.matched:
                    run: dict[int, int] = {}
                    while j in req.matched:
                        run[j] = req.matched.pop(j)
                        j += 1
                    fast_n, slow_n = self.peng.install_lane_pages(lane, run)
                    st = self.tenant_stats[req.tenant]
                    st.fast_reads += fast_n
                    st.slow_reads += slow_n
                    req.pos += len(run) * page_t
                    continue
            end = req.pos + chunk
            gap = min((jj * page_t for jj in req.matched
                       if jj * page_t >= req.pos), default=end)
            piece = req.prompt[req.pos:min(end, gap)]
            with span("sched/prefill", rid=req.rid):
                logits = self.peng.prefill_lane(lane, piece, req.segment,
                                                chunk=chunk)
            req.pos += int(piece.size)
            if not req.prefilling:
                self._to_handoff(lane, req, np.asarray(logits))
        if any(r is not None for r in self.pre_lanes):
            self._meter_pool(self.peng, self.pre_lanes)

    def _decode_turn(self) -> None:
        """The decode worker's step: one batched engine step over the
        decode lanes (every occupant is past its prompt — hand-off
        admission emitted the first token already)."""
        tokens = np.zeros(self.n_lanes, np.int32)
        active = np.zeros(self.n_lanes, bool)
        segments = np.full(self.n_lanes, -1, np.int32)
        for lane, req in enumerate(self.lanes):
            if req is None:
                continue
            segments[lane] = req.segment
            active[lane] = True
            tokens[lane] = req.out[-1]
        if not active.any():
            return
        logits = np.asarray(
            self.eng.advance_lanes(tokens, active, segments)
        ).astype(np.float32)
        self._meter_pool(self.eng, self.lanes)
        now = time.perf_counter()
        clock_now = self._now("decode")
        with span("sched/sample"):
            sampled = self._sample(logits, active.astype(np.int32))
            for lane, req in enumerate(list(self.lanes)):
                if req is None:
                    continue
                req.pos += 1
                tok = (int(sampled[lane]) if sampled is not None
                       else int(np.argmax(logits[lane])))
                req.out.append(tok)
                req.token_times.append(now)
                req.token_clock.append(clock_now)
                req.token_steps.append(self.step_count)
                if len(req.out) >= req.max_new:
                    self._finish(req)

    def _step_unified(self) -> None:
        self._admit_pool("decode")
        chunk = self.scfg.prefill_chunk
        # a step is prefill-busy when a lane is mid-CHUNKED-prefill: its
        # chunk scan (or matched install) is the serialized host wall that
        # delays the batched decode.  Streaming prefill rides the decode
        # batch itself and stalls nobody, so it does not count.
        self.prefill_busy.append(any(
            r is not None and r.prefilling and chunk > 0
            and r.n_prompt > chunk for r in self.lanes))
        tokens = np.zeros(self.n_lanes, np.int32)
        active = np.zeros(self.n_lanes, bool)
        segments = np.full(self.n_lanes, -1, np.int32)
        consumed = np.zeros(self.n_lanes, np.int32)
        chunk_logits: dict[int, np.ndarray] = {}
        page_t = self.eng.scfg.page_t
        for lane, req in enumerate(self.lanes):
            if req is None:
                continue
            segments[lane] = req.segment
            if req.prefilling and req.matched:
                # content-addressed fast-forward (DESIGN.md §12): when the
                # page at the lane position is matched, install the whole
                # consecutive run from the shared pool — no forward pass —
                # and charge the pool reads to the admitting tenant
                j = req.pos // page_t
                if req.pos % page_t == 0 and j in req.matched:
                    run: dict[int, int] = {}
                    while j in req.matched:
                        run[j] = req.matched.pop(j)
                        j += 1
                    fast_n, slow_n = self.eng.install_lane_pages(lane, run)
                    st = self.tenant_stats[req.tenant]
                    st.fast_reads += fast_n
                    st.slow_reads += slow_n
                    consumed[lane] = len(run) * page_t
                    continue
            if chunk > 0 and req.prefilling and req.n_prompt > chunk:
                # a chunk scan must stop at the next matched page — scanning
                # past it would recompute what the pool already holds
                end = req.pos + chunk
                gap = min((jj * page_t for jj in req.matched
                           if jj * page_t >= req.pos), default=end)
                piece = req.prompt[req.pos:min(end, gap)]
                with span("sched/prefill", rid=req.rid):
                    chunk_logits[lane] = self.eng.prefill_lane(
                        lane, piece, req.segment, chunk=chunk)
                consumed[lane] = piece.size
                continue
            active[lane] = True
            consumed[lane] = 1
            tokens[lane] = (req.prompt[req.pos] if req.prefilling
                            else req.out[-1])
        if not (active.any() or chunk_logits):
            # install-only step (or nothing to do): no engine step ran and
            # no lane can emit — just advance the fast-forwarded positions
            for lane, req in enumerate(self.lanes):
                if req is not None and consumed[lane]:
                    req.pos += int(consumed[lane])
            self.step_count += 1
            return
        logits = (self.eng.advance_lanes(tokens, active, segments)
                  if active.any() else None)
        if logits is None:
            logits = np.zeros(
                (self.n_lanes, next(iter(chunk_logits.values())).shape[-1]),
                np.float32)
        else:
            logits = np.asarray(logits).astype(np.float32)
        for lane, row in chunk_logits.items():
            logits[lane] = row
        # meter BEFORE the finish sweep (each request's final step of
        # resident-page reads must still be charged to its tenant)
        self._meter_tenants()
        now = time.perf_counter()
        clock_now = self._now("decode")
        with span("sched/sample"):
            sampled = self._sample(logits, consumed)
            for lane, req in enumerate(list(self.lanes)):
                if req is None or consumed[lane] == 0:
                    continue
                req.pos += int(consumed[lane])
                if not req.prefilling:       # last prompt token or decoding
                    tok = (int(sampled[lane]) if sampled is not None
                           else int(np.argmax(logits[lane])))
                    req.out.append(tok)
                    req.token_times.append(now)
                    req.token_clock.append(clock_now)
                    req.token_steps.append(self.step_count)
                    if len(req.out) >= req.max_new:
                        self._finish(req)
        self.step_count += 1

    def _sample(self, logits: np.ndarray,
                consumed: np.ndarray) -> np.ndarray | None:
        """Batched lane sampling (None in greedy mode -> argmax fallback).

        One jitted :func:`models.decode.sample_tokens` call covers every
        lane that emits this step; each lane's key is its request's
        identity key folded with the emitted-token index, so the draw
        stream is a pure function of (seed, rid, token index) — chunked
        and streamed prefill sample identically."""
        if self.scfg.temperature <= 0.0:
            return None
        keys = np.zeros((self.n_lanes, 2), np.uint32)
        idx = np.zeros(self.n_lanes, np.uint32)
        emitting = False
        for lane, req in enumerate(self.lanes):
            if req is None or consumed[lane] == 0 \
                    or req.pos + consumed[lane] < req.n_prompt:
                continue                      # still prefilling this step
            keys[lane] = req.key
            idx[lane] = len(req.out)
            emitting = True
        if not emitting:
            return None
        folded = dec.fold_lane_keys(jnp.asarray(keys), jnp.asarray(idx))
        return pull(dec.sample_tokens(
            jnp.asarray(logits), folded,
            temperature=self.scfg.temperature, top_p=self.scfg.top_p),
            "sample")

    @property
    def active(self) -> bool:
        """Any request still in flight (queued, pooled, or in hand-off)?"""
        return bool(self.queue or self.handoff
                    or any(r is not None for r in self.lanes)
                    or any(r is not None for r in self.pre_lanes))

    def run(self, max_steps: int = 10_000) -> None:
        """Drain: run until every submitted request finished (or the bound)."""
        while self.active:
            if self.step_count >= max_steps:
                raise RuntimeError(f"undrained after {max_steps} steps")
            self.step()

    # -- telemetry ------------------------------------------------------------
    def _meter_tenants(self) -> None:
        self._meter_pool(self.eng, self.lanes)

    def _meter_pool(self, eng: ServeEngine, lanes: list) -> None:
        """Account each lane's resident KV pages against its tenant: a page
        the placement map holds fast is a per-tenant fast read.  Runs BEFORE
        the finish sweep over the explicit occupancy mask, so a finishing
        request's final step — and a chunk-prefilling lane the engine's own
        active mask no longer carries — is still charged."""
        if eng is None or "kv" not in eng.daemon:
            return
        with span("sched/meter"):
            occupied = np.array([r is not None for r in lanes], bool)
            sv = eng._kv_lane_stream(active=occupied)
            if sv is None:
                return
            _, gids = sv
            h = eng.daemon["kv"]
            _, hit = h.lookup(jnp.asarray(gids.reshape(-1), jnp.int32))
            hit = pull(hit, "meter_hit").reshape(gids.shape)
            valid = gids >= 0
            for lane, req in enumerate(lanes):
                if req is None:
                    continue
                st = self.tenant_stats[req.tenant]
                f = int(np.sum(hit[lane] & valid[lane]))
                st.fast_reads += f
                st.slow_reads += int(np.sum(valid[lane])) - f

    @staticmethod
    def _pct_row(gaps) -> dict:
        if not len(gaps):
            return {"p50": 0.0, "p99": 0.0, "mean": 0.0, "n": 0}
        g = np.asarray(gaps) * 1e3
        return {"p50": float(np.percentile(g, 50)),
                "p99": float(np.percentile(g, 99)),
                "mean": float(np.mean(g)), "n": int(g.size)}

    @classmethod
    def _latency_rows(cls, reqs: list[Request]) -> dict:
        """Split latency schema: ``ttft_ms`` (arrival -> first emitted token)
        and ``tpot_ms`` (gaps between a request's consecutive output tokens)
        are DIFFERENT distributions — folding them together makes the
        "per-token p99" just TTFT in disguise.  (The combined ``latency_ms``
        row served its one-release deprecation and is gone.)"""
        ttft, tpot = [], []
        for r in reqs:
            if r.token_times:
                ttft.append(r.token_times[0] - r.arrival_time)
                tpot.extend(np.diff(r.token_times))
        return {"ttft_ms": cls._pct_row(ttft),
                "tpot_ms": cls._pct_row(tpot)}

    def report(self) -> dict:
        """The traffic-bench schema row for this run (BENCH_serve.json)."""
        done = self.finished
        tenants = {}
        for name, ten in self.tenants.items():
            reqs = [r for r in done if r.tenant == name]
            st = self.tenant_stats[name]
            total = st.fast_reads + st.slow_reads
            tenants[name] = {
                "weight": ten.weight,
                "completed": len(reqs),
                "tokens": sum(len(r.out) for r in reqs),
                "kv_hit_rate": st.fast_reads / max(total, 1),
                **self._latency_rows(reqs),
            }
        return {
            "steps": self.step_count,
            "submitted": self._next_rid,
            "completed": len(done),
            "tokens": sum(len(r.out) for r in done),
            "preemptions": self.preemptions,
            "queued_peak": self.queued_peak,
            "mode": "disagg" if self.disagg else "unified",
            "prefill_lanes": self.scfg.prefill_lanes,
            "clock": {"prefill_s": self.clock["prefill"],
                      "handoff_s": self.clock["handoff"],
                      "decode_s": self.clock["decode"]},
            "handoff": {"count": self.handoffs,
                        "bytes_out": self.handoff_bytes_out,
                        "bytes_in": self.handoff_bytes_in,
                        "depth_peak": self.handoff_peak},
            **self._latency_rows(done),
            "tenants": tenants,
            "resources": self.eng.tier_stats(),
        }
