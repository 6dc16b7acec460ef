"""One run of a serving cell: build the engine, drive the closed loop,
measure a window of whole scheduler steps, and check what it served.

The system under test is ``Scheduler.step()`` over a ``ServeEngine`` in
lane mode with the paged ring, the async migration plane, the ``kv`` tier
and the tiers the configuration's ``serve`` block names (``embeddings``
unless it says otherwise; slow stores in pinned host memory),
kernel-exported KV mass and the ``none`` slow codec.  The harness records
its own spans around the calls into each layer (``sched.step``,
``advance_lanes``, ``prefill_lane``, ``daemon.tick``) from an engine
subclass.  What depends on the architecture (engine fields, weights, the
reference, work counts) comes from the configuration's module
``bench/arch/<model_type>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from bench import model, trace as tr_lib, work
from bench.spec import Cell, arch_module, reader
from bench.traffic import ClosedLoop, make_requests
from repro.configs.base import ArchConfig
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.sched import SchedConfig, Scheduler, Tenant
from repro.tiering import migrate

TENANT = "clients"
TRACE_S = 20.0
# How long the untimed steps after the window may run: for first tokens
# (a request sent in the window still without one then has failed), and
# for the requests the check waits for.
DRAIN_LIMIT_S = 150.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Step:
    start: float
    end: float
    tokens: int          # prompt and output tokens consumed by the step
    occupied: int        # lanes that held a request


class SpannedEngine(ServeEngine):
    """ServeEngine that records the harness's spans around the calls into
    the engine, and the positions each decode body attends from (for the
    kernel's work).  ``sched`` is set once the scheduler exists."""

    sched: Scheduler | None = None

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.spans: list[tuple[str, float, float]] = []
        # (time, positions of the active lanes) per decode body
        self.bodies: list[tuple[float, list[int]]] = []
        tick = self.daemon.tick

        def spanned_tick():
            with jax.profiler.TraceAnnotation("daemon.tick"):
                t0 = time.perf_counter()
                out = tick()
            self.spans.append(("daemon.tick", t0, time.perf_counter()))
            return out
        self.daemon.tick = spanned_tick

    def advance_lanes(self, tokens, active, segments):
        lanes = self.sched.lanes
        pos = [lanes[ln].pos for ln in np.flatnonzero(np.asarray(active))]
        with jax.profiler.TraceAnnotation("advance_lanes"):
            t0 = time.perf_counter()
            out = super().advance_lanes(tokens, active, segments)
        self.spans.append(("advance_lanes", t0, time.perf_counter()))
        self.bodies.append((t0, pos))
        return out

    def prefill_lane(self, lane, tokens, segment, chunk=None):
        p0 = self.sched.lanes[lane].pos
        with jax.profiler.TraceAnnotation("prefill_lane"):
            t0 = time.perf_counter()
            out = super().prefill_lane(lane, tokens, segment, chunk=chunk)
        self.spans.append(("prefill_lane", t0, time.perf_counter()))
        self.bodies += [(t0, [p0 + i]) for i in range(len(tokens))]
        return out


def serve_config(geo: dict) -> ServeConfig:
    """The engine's settings from a configuration's ``serve`` block.  Its
    optional keys: ``resources``, the tiered resources besides the KV ring
    (default the embedding table), and ``expert_hot_slots`` and
    ``expert_quota`` for an ``experts`` tier (default ServeConfig's)."""
    experts = {k: geo[k] for k in ("expert_hot_slots", "expert_quota")
               if k in geo}
    return ServeConfig(
        max_seq=geo["max_seq"], page_t=geo["page_t"],
        hot_slots=geo["ring_pages"], paged=True,
        migration_interval=geo["migration_interval"],
        resources=tuple(geo.get("resources", ("embeddings",))),
        kv_quota=geo["kv_quota"],
        embed_hot_slots=geo["embed_hot_pages"],
        embed_quota=geo["embed_quota"],
        embed_rows_per_page=geo["embed_rows_per_page"], lanes=geo["lanes"],
        kv_segments=geo["kv_segments"], kv_mass_source="kernel",
        slow_codec="none", async_migration=True, **experts)


class Loop:
    """The closed loop over the scheduler, with per-step records."""

    def __init__(self, sched: Scheduler, clients: ClosedLoop):
        self.sched = sched
        self.clients = clients
        self.live = []
        self.all = []
        self.steps: list[Step] = []
        self.sending = True        # off once the window has closed
        for _ in range(clients.clients):
            self._send()

    def _send(self) -> None:
        r = self.clients.next()
        req = self.sched.submit(TENANT, r.prompt, r.max_new)
        self.live.append(req)
        self.all.append(req)

    def step(self) -> Step:
        before = [(r, r.pos) for r in self.live]
        with jax.profiler.TraceAnnotation("sched.step"):
            t0 = time.perf_counter()
            self.sched.step()
            t1 = time.perf_counter()
        moved = [r.pos - p for r, p in before]
        st = Step(t0, t1, int(sum(moved)), int(sum(m > 0 for m in moved)))
        self.steps.append(st)
        done = [r for r in self.live if r.state == "finished"]
        if done:
            self.live = [r for r in self.live if r.state != "finished"]
            for _ in done if self.sending else ():
                self._send()
        return st


@dataclasses.dataclass
class Context:
    """What a metric reader may read (bench/metrics/<name>.py)."""

    cell: Cell
    arch: object               # bench/arch/<model_type>.py of the config
    f: dict                    # ArchConfig fields (arch.fields)
    geo: dict                  # serving geometry
    peaks: dict
    t0: float                  # window start and end, host clock
    t1: float
    steps: list                # Step records of the window
    requests: list             # every request the loop sent
    spans: list                # (name, start, end) host spans in the window
    bodies: list               # (time, positions) decode bodies in the window
    tier_delta: dict           # resource -> counter deltas over the window
    setup_s: float
    memory_peak_bytes: int
    trace: dict | None = None  # trace extract (trace runs)


def _tier_counts(eng: ServeEngine) -> dict:
    keys = ("fast_reads", "slow_reads", "migration_bytes", "flush_bytes",
            "migration_epochs")
    return {n: {k: row[k] for k in keys} for n, row in eng.tier_stats().items()}


def warm_programs(eng: ServeEngine, geo: dict, prefill: bool) -> None:
    """Compile (or load from the cache) the lane decode step and, where the
    traffic chunk-prefills, the prefill chunk scan."""
    import jax.numpy as jnp
    lanes = geo["lanes"]
    idle = jnp.zeros(lanes, bool)
    jax.block_until_ready(eng._decode_paged(
        eng.params, eng.cache, jnp.zeros((lanes, 1), jnp.int32),
        eng._tier_reads(), idle)[0])
    if prefill:
        c = geo["prefill_chunk"]
        jax.block_until_ready(eng._prefill_paged_jit(
            eng.params, eng.cache, jnp.zeros((lanes, c), jnp.int32),
            jnp.zeros((lanes, c), bool), idle, eng._tier_reads())[0])


def warm_flush_buckets(eng: ServeEngine, geo: dict) -> None:
    """Load the KV flush programs at every size they take.  A lane-mode
    flush compacts the written ring slots to the next power of two
    (``migrate.ring_selection``), so its placement lookup, its fused write
    and, while an epoch is in flight, its replay onto the epoch's buffer
    each take one shape per bucket up to ``lanes * ring_pages``.  Each is
    run here once per bucket with every lane dropped (page id -1): the
    write then rewrites store row 0 with its own bytes, and the replay goes
    to a scratch copy of the fast buffer."""
    import jax.numpy as jnp
    if "kv" not in eng.daemon:
        return
    h = eng.daemon["kv"]
    mem, entry = h.mem, eng._paged_entry()
    if mem.buffers is None or entry is None:
        return
    k_pages, v_pages = entry["k_pages"], entry["v_pages"]
    fast = mem.buffers.fast
    n, top = 1, geo["lanes"] * geo["ring_pages"]
    while True:
        ids = np.full(n, -1, np.int32)
        ring = np.zeros(n, np.int32)
        slots = mem.lookup_slots(h.state, ids)
        mem.buffers = migrate.write_pages(mem.buffers, ids, slots, ring,
                                          k_pages, v_pages, codec=mem.codec)
        scratch = jax.device_put(jnp.zeros(fast.shape, fast.dtype),
                                 mem.buffers.fast.sharding)
        jax.block_until_ready(migrate.refresh_pages(scratch, slots, ring,
                                                    k_pages, v_pages))
        if n >= top:
            break
        n *= 2
    jax.block_until_ready(mem.buffers)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, device_kind: str, control: bool = False) -> dict:
    """One run; returns the result line (without the device fields).
    With ``control`` (bench/control.py; the benchmark's own runs never set
    it) the fp8 control is put in the program's place: the check judges
    the tokens it puts first, by the same rule and limit, and the line's
    ``correct`` and ``compared`` are its verdict and readings; the
    program's own are under ``program``."""
    conf, geo, traffic = cell.conf, cell.conf["serve"], cell.traffic
    arch = arch_module(cell)
    f = arch.fields(conf)
    eps = arch.norm_eps(conf)
    peaks = work.device_peaks(device_kind)
    params = arch.make_weights(f, model.weight_key(seed))
    jax.block_until_ready(params)
    reqs = make_requests(traffic, f["vocab"], seed)
    eng = SpannedEngine(ArchConfig(**f), params, serve_config(geo))
    sched = Scheduler(eng, [Tenant(TENANT)],
                      SchedConfig(prefill_chunk=geo["prefill_chunk"]))
    eng.sched = sched
    chunked = any(r.prompt.size > geo["prefill_chunk"] for r in reqs)
    warm_programs(eng, geo, chunked)
    warm_flush_buckets(eng, geo)
    loop = Loop(sched, ClosedLoop(reqs, geo["lanes"]))
    for _ in range(int(traffic["warmup_steps"])):
        loop.step()
    # every daemon cadence (migration, threshold update, sketch clear)
    # runs once before the window, so none of their programs is first
    # needed inside it
    for _ in range(eng.daemon.dp.clear_interval):
        eng.daemon.tick()
    compiles = _CompileCounter()

    logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # spans only, no per-call tracing
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(logdir, profiler_options=opts)
    before = _tier_counts(eng)
    first_step = len(loop.steps)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    if trace:
        # a traced window lasts at most TRACE_S seconds of whole steps:
        # reading a longer trace costs minutes of host time
        with jax.profiler.TraceAnnotation(tr_lib.WINDOW_SPAN):
            t1 = measure(loop.step, t0, min(seconds, TRACE_S))
        jax.profiler.stop_trace()
    else:
        t1 = measure(loop.step, t0, seconds)
    window_compiles = compiles.stop()
    after = _tier_counts(eng)
    ext = None
    if trace:
        ext = tr_lib.extract(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
    readers = {m["name"]: reader(cell, m["name"])
               for m in (cell.per_layer if trace else cell.end_to_end)}
    drained = any(getattr(r, "DRAIN", False) for r in readers.values())
    if drained:
        _drain_first_tokens(loop, t0, t1)
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    ctx = Context(
        cell=cell, arch=arch, f=f, geo=geo, peaks=peaks, t0=t0, t1=t1,
        steps=loop.steps[first_step:], requests=list(loop.all),
        spans=[s for s in eng.spans if t0 <= s[1] < t1],
        bodies=[b for b in eng.bodies if t0 <= b[0] < t1],
        tier_delta={n: {k: after[n][k] - before[n][k] for k in after[n]}
                    for n in after},
        setup_s=setup_s, memory_peak_bytes=peak, trace=ext)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = readers[m["name"]].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    attempted = sum(1 for r in loop.all if _in_window(r, t0, t1))
    # a request sent in the window that had no first token by the end of
    # the drain (only where a metric drains) has failed
    failed = sum(1 for r in loop.all
                 if drained and t0 <= r.arrival_time < t1
                 and not r.token_times)
    log(f"window {t1 - t0:.3f} s over {len(ctx.steps)} steps, "
        f"{sum(s.tokens for s in ctx.steps)} tokens; setup {setup_s:.3f} s; "
        f"peak {peak} of {stats.get('bytes_limit')} bytes; "
        f"compiles in window {window_compiles}; tiers {ctx.tier_delta}")

    loop.sending = False
    served = _sample(loop, t0, t1, seed, int(traffic["check_requests"]),
                     int(traffic["check_tokens"]))
    del eng, sched, loop
    gc.collect()
    t_check = time.perf_counter()
    check = check_served(arch, f, eps, params, geo, served, control=control)
    log(f"check: {len(served)} requests, {check['tokens']} served tokens "
        f"against the reference in {time.perf_counter() - t_check:.1f} s")
    limit = float(cell.limits["max_logit_gap"])
    correct, compared = verdict(check["gap"], check["tokens"], limit)
    program = None
    if control:
        program = {"correct": correct, "compared": compared}
        correct, compared = verdict(check["control_gap"], check["tokens"],
                                    limit)
    out = {"correct": correct, "attempted": attempted,
           "failed": failed, "metrics": metrics,
           "device": {"memory_peak_bytes": peak}}
    if trace:
        out["device"]["busy_s"] = tr_lib.busy_s(ext)
        out["device"]["window_s"] = tr_lib.window_s(ext)
        out["breakdown"] = tr_lib.breakdown(ext)
    if control:
        out["program"] = program
    out["compared"] = compared
    return out


def verdict(gap: float, tokens: int, limit: float) -> tuple[bool, dict]:
    """``correct`` and the numbers compared: some tokens were compared, and
    none lies further below the reference's best than ``limit``."""
    compared = {"max_logit_gap": {"value": gap, "limit": limit},
                "tokens_compared": {"value": tokens, "limit": "at least 1"}}
    return bool(tokens > 0 and gap <= limit), compared


def measure(step, t0: float, seconds: float) -> float:
    """Run whole steps from ``t0`` until one ends ``seconds`` or more after
    it; returns that step's end, the window's end."""
    while True:
        st = step()
        if st.end - t0 >= seconds:
            return st.end


def _in_window(r, t0: float, t1: float) -> bool:
    """Was the request in flight at some time inside the window?"""
    if r.arrival_time >= t1:
        return False
    return not (r.token_times and r.state == "finished"
                and r.token_times[-1] < t0)


def _drain_first_tokens(loop: Loop, t0: float, t1: float) -> None:
    """Step on (untimed) until every request sent in the window has its
    first token, so the window's time-to-first-token has no survivor
    bias."""
    waiting = [r for r in loop.all if t0 <= r.arrival_time < t1]
    deadline = time.perf_counter() + DRAIN_LIMIT_S
    while any(not r.token_times for r in waiting) and \
            time.perf_counter() < deadline:
        loop.step()


def _sample(loop: Loop, t0: float, t1: float, seed: int, n: int,
            min_tokens: int) -> list:
    """The requests the check compares: drawn from the seed among those
    the window finished, always with the one that served the most, at
    least ``n`` of them and on until they hold ``min_tokens`` served
    tokens.  Where the window's finished requests hold fewer, the loop
    steps on (untimed, no new sends) until every request in flight at the
    close has finished, and those join the draw."""
    done = [r for r in loop.all if r.state == "finished"
            and r.token_times and t0 <= r.token_times[-1] <= t1]
    if sum(len(r.out) for r in done) < min_tokens:
        flight = [r for r in loop.all if _in_window(r, t0, t1)
                  and r.state != "finished"]
        deadline = time.perf_counter() + DRAIN_LIMIT_S
        while loop.live and time.perf_counter() < deadline:
            loop.step()
        done += [r for r in flight if r.state == "finished"]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.n_prompt + len(r.out), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 5]))
    pick, tokens = [longest], len(longest.out)
    for i in rng.permutation(len(rest)):
        if len(pick) >= n and tokens >= min_tokens:
            break
        pick.append(rest[i])
        tokens += len(rest[i].out)
    return [(r.prompt.copy(), list(r.out)) for r in pick]


def check_served(arch, f: dict, eps: float, params, geo: dict, served,
                 control: bool = False) -> dict:
    """The widest gap of any served token below the best of ``arch``'s
    reference, over the sampled requests (see bench/model.served_gaps)."""
    gap, ctl, tokens = 0.0, 0.0, 0
    for prompt, out in served:
        res = model.served_gaps(arch, f, eps, params, prompt, out,
                                geo["page_t"], geo["ring_pages"],
                                geo["max_seq"], control=control)
        gap = max(gap, res["gap"])
        ctl = max(ctl, res.get("control_gap", 0.0))
        tokens += res["tokens"]
    out = {"gap": gap, "tokens": tokens}
    if control:
        out["control_gap"] = ctl
    return out


class _CompileCounter:
    """Names the programs compiled or loaded from the persistent cache from
    its creation until ``stop()``: a warm-up that covers every shape
    leaves none in the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.names: list[str] = []
        self.on = True
        from jax import monitoring

        def listen(event, duration, fun_name="", **_):
            if self.on and event == self.EVENT:
                self.names.append(fun_name)
        monitoring.register_event_duration_secs_listener(listen)

    def stop(self) -> list[str]:
        self.on = False
        return self.names
