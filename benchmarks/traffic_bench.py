"""Traffic benchmark: the full serving stack under multi-tenant traces.

Drives the continuous-batching scheduler (`repro.serve.sched`) over the
three workload traces (`repro.workloads`): zipf-hot, diurnal-shift, and
scan-antagonist, each with >= 2 tenants multiplexed onto one ServeEngine /
NeoMemDaemon.  Per trace it records throughput, p50/p99 per-token latency,
hit rates (lifetime + steady-state second-half window), migration bytes/s,
preemptions, and per-tenant rows into the ``traffic`` section of
``BENCH_serve.json`` (schema in benchmarks/README.md, validated in CI by
validate_bench.py).

The NeoMem adaptivity signal asserted here: identical arrival load, only
token content differs (workloads/traces.py), so the zipf-hot trace must
reach a HIGHER steady-state hit rate than scan-antagonist — a stable hot
set the sketch can find and pin versus an antagonist scan thrashing it.

Arrivals follow the bursty MMPP process (2-state modulated Bernoulli,
workloads/traces.py): same mean offered load as plain Bernoulli, but the
queueing/preemption pressure — and thus the p99 story — lives in the
bursts, as in production serving traces.  The "kv" resource profiles the
kernel-exported softmax mass (ServeConfig.kv_mass_source, DESIGN.md §10);
the fill-vs-kernel fidelity A/B itself lives in serve_bench.py
(``mass_ab``).

Latency is reported SPLIT (DESIGN.md §11): ``ttft_ms`` (arrival -> first
token) and ``tpot_ms`` (inter-token decode gaps) are different
distributions (the old combined ``latency_ms`` row served its one-release
deprecation window and is gone).  Every trace gets an untimed per-case
warmup that traces+compiles
the engine's jitted bodies first, recorded as ``compile_s``, so wall_s /
tokens_per_s / migration_bytes_per_s are steady-state numbers, not XLA.

The ``prefill`` section is the chunked-prefill TTFT A/B (DESIGN.md §11):
one 512-token prompt served twice through the Scheduler on the same seed —
token-at-a-time streaming (prefill_chunk=0) vs the chunked scan
(prefill_chunk=64 >= page_t) — each arm warmed by an untimed full request
first.  CI gates chunked TTFT <= 1/4 of streaming with bit-exact output
tokens (validate_bench.py): the prompt-length tail latency fix, measured.

The ``kv_reuse`` section (DESIGN.md §12) replays the SAME agentic
multi-turn trace through three arms — reuse off, prefix matching, and
substring matching over the content-addressed KV page store
(``ServeConfig.reuse_pages``) — greedy, same seed.  CI gates: bit-exact
outputs across all three arms (reuse must never change tokens), substring
prefill-tokens-saved > 0, substring page-hit rate > prefix (hole-skipping
over evicted / unflushed front-of-history pages is the point), and the
substring arm's steady-state KV hit rate no worse than reuse-off.

The ``disagg`` section (DESIGN.md §13) is the prefill/decode
disaggregation A/B: the prefill-heavy trace (chat = short prompts / long
outputs, doc = long prompts / short outputs) served by the unified
scheduler and by split prefill-worker/decode-worker pools over the
slow-tier hand-off fabric, SAME total lane budget, greedy, one seed.
Decode inter-token gaps are read off each arm's decode-worker virtual
clock and split by whether a chunk scan was in flight.  CI gates:
bit-exact outputs across arms, hand-off bytes > 0 both directions (zero
unified), disagg during-prefill TPOT p50 within 10% of quiet vs the
unified arm measurably degrading on the identical trace.

    PYTHONPATH=src:. python benchmarks/traffic_bench.py \
        [--quick] [--reuse] [--disagg]
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_smoke_config
from repro.models import transformer as tr
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.sched import SchedConfig, Scheduler, Tenant
from repro.workloads import (DEFAULT_TENANTS, TenantProfile, make_trace,
                             play)

from benchmarks.common import emit, steady_start, update_bench_json

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")

# The traffic section runs the three CONTENT kinds (identical arrival load,
# only token content differs — the adaptivity-gap premise); the agentic
# kind has its own session-structured arrivals and is the kv_reuse A/B's
# workload below.
CONTENT_KINDS = ("zipf-hot", "diurnal-shift", "scan-antagonist")
# Ride-along kinds benched with the trio (same schema row, own load shape):
# prod-mixture replays the bimodal public-trace prompt-length mixture
# (repro.workloads §prod-mixture) — zipf-hot content under realistic
# length dispersion.  Selectable via --kinds.
BENCH_KINDS = CONTENT_KINDS + ("prod-mixture",)

ARCH = "llama3.2-3b"
LANES = 4
ARRIVAL = "mmpp"
SERVE_KW = dict(
    max_seq=64, paged=True, page_t=4, hot_slots=6, migration_interval=4,
    resources=("embeddings",), embed_hot_slots=6, embed_quota=8,
    embed_rows_per_page=8,            # 256-token vocab -> 32 row pages
    kv_quota=16, kv_tier_slots=12, kv_mass_threshold=0.01,
    lanes=LANES, kv_segments=LANES + 2,
)

# The chunked-prefill TTFT A/B (DESIGN.md §11): a >= 512-token prompt, chunk
# >= page_t, chunk <= the ring-wrap cap (hot_slots-1)*page_t = 80.
PREFILL_KW = dict(
    max_seq=640, paged=True, page_t=16, hot_slots=6, migration_interval=8,
    kv_quota=16, kv_tier_slots=12, kv_mass_threshold=0.01,
    lanes=2, kv_segments=2,
)
PREFILL_PROMPT = 512
PREFILL_CHUNK = 64
PREFILL_NEW = 4


def _warm_engine(eng, chunk: int = 0) -> float:
    """Untimed per-case warmup: trace+compile the engine's jitted bodies by
    calling the jit wrappers directly on the live lane shapes with every
    lane masked inactive — pure calls, outputs discarded, no daemon or
    cache state touched.  Returns the trace+compile wall (``compile_s``)
    so the timed window that follows is steady-state execution."""
    t0 = time.perf_counter()
    if eng.cache is None:
        eng.start_lanes()
    lanes = eng.scfg.lanes
    idle = jnp.zeros(lanes, bool)
    out = eng._decode_paged(eng.params, eng.cache,
                            jnp.zeros((lanes, 1), jnp.int32),
                            eng._tier_reads(), idle)
    jax.block_until_ready(out[0])
    if chunk > 0:
        out = eng._prefill_paged_jit(eng.params, eng.cache,
                                     jnp.zeros((lanes, chunk), jnp.int32),
                                     jnp.zeros((lanes, chunk), bool), idle,
                                     eng._tier_reads())
        jax.block_until_ready(out[0])
    return time.perf_counter() - t0


def _read_counts(eng) -> dict[str, tuple[int, int]]:
    """Merged (fast, slow) read counts per resource, for windowed rates."""
    return {n: (row["fast_reads"], row["slow_reads"])
            for n, row in eng.tier_stats().items()}


def _window_rate(before: dict, after: dict) -> tuple[float, dict[str, float]]:
    """(combined, per-resource) hit rate over the [before, after) window."""
    per, tot_f, tot_r = {}, 0, 0
    for n, (f1, s1) in before.items():
        f2, s2 = after[n]
        df, dr = f2 - f1, (f2 + s2) - (f1 + s1)
        per[n] = df / max(dr, 1)
        tot_f += df
        tot_r += dr
    return tot_f / max(tot_r, 1), per


def _bench_trace(kind: str, params, n_steps: int, seed: int) -> dict:
    cfg = get_smoke_config(ARCH)
    eng = ServeEngine(cfg, params, ServeConfig(**SERVE_KW))
    compile_s = _warm_engine(eng)
    tenants = [Tenant(t.name, t.weight) for t in DEFAULT_TENANTS]
    sched = Scheduler(eng, tenants, SchedConfig(preempt_patience=24,
                                                seed=seed))
    trace = make_trace(kind, n_steps=n_steps, vocab=cfg.vocab, seed=seed,
                       arrival=ARRIVAL)
    mid_counts: list[dict] = []

    def snap_mid(s):                             # steady-state window start
        if not mid_counts and s.step_count >= steady_start(trace.n_steps):
            mid_counts.append(_read_counts(eng))

    t0 = time.perf_counter()
    play(trace, sched, on_step=snap_mid)
    wall = time.perf_counter() - t0
    rep = sched.report()
    steady, steady_per = _window_rate(mid_counts[0], _read_counts(eng))
    resources = rep["resources"]
    fast = sum(r["fast_reads"] for r in resources.values())
    reads = fast + sum(r["slow_reads"] for r in resources.values())
    moved = sum(r["migration_bytes"] for r in resources.values())
    assert rep["completed"] == rep["submitted"], "requests left undrained"
    return {
        "trace": kind,
        "seed": trace.seed,
        "arrival": trace.arrival,
        "kv_mass_source": eng.scfg.kv_mass_source,
        "trace_steps": trace.n_steps,
        "steps": rep["steps"],
        "lanes": LANES,
        "submitted": rep["submitted"],
        "completed": rep["completed"],
        "tokens": rep["tokens"],
        "compile_s": compile_s,
        "wall_s": wall,
        "tokens_per_s": rep["tokens"] / wall,
        "ttft_ms": rep["ttft_ms"],
        "tpot_ms": rep["tpot_ms"],
        "hit_rate": fast / max(reads, 1),
        "hit_rate_steady": steady,
        "resource_hit_steady": steady_per,
        "migration_bytes": moved,
        "migration_bytes_per_s": moved / wall,
        "preemptions": rep["preemptions"],
        "queued_peak": rep["queued_peak"],
        "tenants": rep["tenants"],
        "resources": resources,
    }


def _prefill_arm(params, chunk: int) -> dict:
    """One arm of the chunked-prefill TTFT A/B: a fresh engine + scheduler,
    one UNTIMED warmup request that traces+compiles the arm's whole path
    (streaming decode step or chunk scan, plus the flush scatter), then the
    measured request — its TTFT is steady-state arrival -> first-token
    wall, not XLA compile.  The warmup wall is recorded as ``compile_s``."""
    cfg = get_smoke_config(ARCH)
    eng = ServeEngine(cfg, params, ServeConfig(**PREFILL_KW))
    sched = Scheduler(eng, [Tenant("a")],
                      SchedConfig(prefill_chunk=chunk, seed=0))
    rng = np.random.default_rng(11)
    warm = rng.integers(0, cfg.vocab, PREFILL_PROMPT).astype(np.int32)
    prompt = rng.integers(0, cfg.vocab, PREFILL_PROMPT).astype(np.int32)
    t0 = time.perf_counter()
    sched.submit("a", warm, max_new=PREFILL_NEW)
    sched.run(max_steps=4 * PREFILL_PROMPT)
    compile_s = time.perf_counter() - t0
    req = sched.submit("a", prompt, max_new=PREFILL_NEW)
    sched.run(max_steps=8 * PREFILL_PROMPT)
    rows = Scheduler._latency_rows([req])
    return {
        "chunk": chunk,
        "compile_s": compile_s,
        "steps": sched.step_count,
        "ttft_ms": rows["ttft_ms"]["mean"],        # one request: exact
        "tpot_ms": rows["tpot_ms"],
        "tokens": [int(t) for t in req.out],
    }


def _bench_prefill(params) -> dict:
    """The prompt-length tail-latency A/B (DESIGN.md §11): the identical
    512-token request served token-at-a-time (prefill_chunk=0) and through
    the chunked scan (prefill_chunk=64 >= page_t), same seed, greedy
    sampling — chunked must land the first token in <= 1/4 the time with
    bit-exact output tokens (gated in validate_bench.py)."""
    token = _prefill_arm(params, chunk=0)
    chunked = _prefill_arm(params, chunk=PREFILL_CHUNK)
    match = token["tokens"] == chunked["tokens"]
    ratio = chunked["ttft_ms"] / max(token["ttft_ms"], 1e-9)
    assert match, (
        "chunked prefill diverged from token-at-a-time streaming: "
        f"{chunked['tokens']} != {token['tokens']}")
    assert ratio <= 0.25, (
        f"chunked TTFT {chunked['ttft_ms']:.1f}ms not <= 1/4 of "
        f"token-at-a-time {token['ttft_ms']:.1f}ms (ratio {ratio:.3f})")
    return {
        "arch": ARCH,
        "prompt_len": PREFILL_PROMPT,
        "max_new": PREFILL_NEW,
        "page_t": PREFILL_KW["page_t"],
        "chunk": PREFILL_CHUNK,
        "lanes": PREFILL_KW["lanes"],
        "seed": 0,
        "tokens_match": bool(match),
        "ttft_ratio": ratio,
        "token": token,
        "chunked": chunked,
    }


# The kv_reuse A/B (DESIGN.md §12): agentic multi-turn sessions over the
# content-addressed page store.  Tenant prompt_len bounds the per-TURN user
# block; the pool is sized BELOW the trace's distinct-page footprint so LRU
# eviction punches front-of-history holes that only substring matching can
# skip past.  prefill_chunk is on so gap scans interleave with installs.
REUSE_TENANTS = (
    TenantProfile("agent-a", weight=1.0, prompt_len=(3, 6), out_len=(3, 5)),
    TenantProfile("agent-b", weight=1.0, prompt_len=(3, 6), out_len=(3, 5)),
)
REUSE_TRACE_KW = dict(turn_gap=16, sys_len=12, n_convs=2, work_len=4,
                      max_total=56)
# Pool sized BELOW the trace's live footprint (~4 conversations x ~13 pages)
# so LRU eviction reaches live front-of-history pages: the shared system
# pages stay hot (re-touched by the sibling conversation), early history
# evicts, and only substring matching recovers the surviving tail.
REUSE_PAGES = 32
REUSE_CHUNK = 8
REUSE_STEPS = 224          # enough steps for deep (7-8 turn) conversations


def _reuse_arm(params, trace, mode: str, reuse_pages: int) -> dict:
    """One arm of the reuse A/B: a fresh engine + scheduler replaying the
    identical agentic trace, greedy.  ``reuse_pages=0`` disables the store
    (the baseline arm); otherwise ``mode`` selects prefix vs substring
    admission matching (SchedConfig.reuse_match)."""
    cfg = get_smoke_config(ARCH)
    eng = ServeEngine(cfg, params, ServeConfig(**SERVE_KW,
                                               reuse_pages=reuse_pages))
    compile_s = _warm_engine(eng, chunk=REUSE_CHUNK)
    tenants = [Tenant(t.name, t.weight) for t in trace.tenants]
    sched = Scheduler(eng, tenants,
                      SchedConfig(preempt_patience=24, seed=0,
                                  prefill_chunk=REUSE_CHUNK,
                                  reuse_match=mode))
    mid_counts: list[dict] = []

    def snap_mid(s):
        if not mid_counts and s.step_count >= steady_start(trace.n_steps):
            mid_counts.append(_read_counts(eng))

    t0 = time.perf_counter()
    play(trace, sched, on_step=snap_mid)
    wall = time.perf_counter() - t0
    rep = sched.report()
    assert rep["completed"] == rep["submitted"], "requests left undrained"
    _, steady_per = _window_rate(mid_counts[0], _read_counts(eng))
    return {
        "mode": "off" if reuse_pages == 0 else mode,
        "reuse_pages": reuse_pages,
        "steps": rep["steps"],
        "completed": rep["completed"],
        "tokens": rep["tokens"],
        "compile_s": compile_s,
        "wall_s": wall,
        "kv_hit_steady": steady_per["kv"],
        "ttft_ms": rep["ttft_ms"],
        "reuse": eng.reuse_stats() if eng.reuse is not None else None,
        "outputs": {int(r.rid): [int(t) for t in r.out]
                    for r in sched.finished},
    }


def _bench_reuse(params, n_steps: int, seed: int) -> dict:
    """Cross-request KV reuse A/B (DESIGN.md §12): the identical agentic
    trace served with reuse off, prefix matching, and substring matching.
    Gates (asserted here AND in validate_bench.py): outputs bit-exact
    across arms, substring saves prefill tokens, substring page-hit rate
    beats prefix (hole-skipping), substring steady KV hit >= off."""
    cfg = get_smoke_config(ARCH)
    trace = make_trace("agentic", n_steps=max(n_steps, REUSE_STEPS),
                       vocab=cfg.vocab, tenants=REUSE_TENANTS, seed=seed,
                       **REUSE_TRACE_KW)
    off = _reuse_arm(params, trace, "substring", reuse_pages=0)
    prefix = _reuse_arm(params, trace, "prefix", REUSE_PAGES)
    substr = _reuse_arm(params, trace, "substring", REUSE_PAGES)
    match = off["outputs"] == prefix["outputs"] == substr["outputs"]
    assert match, "KV reuse changed output tokens — bit-exactness gate lost"
    saved = substr["reuse"]["tokens_saved"]
    assert saved > 0, "substring reuse saved no prefill tokens"
    hp, hs = prefix["reuse"]["hit_rate"], substr["reuse"]["hit_rate"]
    assert hs > hp, (
        f"substring page-hit rate {hs:.3f} must beat prefix {hp:.3f} — "
        "hole-skipping found nothing beyond the shared prefix")
    assert substr["kv_hit_steady"] >= off["kv_hit_steady"], (
        f"reuse degraded the steady KV hit rate: {substr['kv_hit_steady']:.3f}"
        f" < {off['kv_hit_steady']:.3f}")
    for arm in (off, prefix, substr):
        del arm["outputs"]                 # compared above; too bulky to keep
    return {
        "arch": ARCH,
        "trace": "agentic",
        "seed": seed,
        "trace_steps": trace.n_steps,
        "turns": len(trace.arrivals),
        "lanes": LANES,
        "page_t": SERVE_KW["page_t"],
        "reuse_pages": REUSE_PAGES,
        "prefill_chunk": REUSE_CHUNK,
        "tenants": {t.name: t.weight for t in REUSE_TENANTS},
        "tokens_match": bool(match),
        "prefill_tokens_saved": saved,
        "hit_rate_gap": hs - hp,
        "off": off,
        "prefix": prefix,
        "substring": substr,
    }


# The disaggregation A/B (DESIGN.md §13): the identical prefill-heavy
# trace — a "chat" tenant streaming short prompts with long outputs, a
# "doc" tenant dropping long prompts with short outputs — served by the
# unified scheduler (3 lanes, chunked prefill in-pool) and by the split
# scheduler (2 decode lanes + 1 dedicated prefill-worker lane: the same
# total hardware budget) over the slow-tier hand-off fabric.  Decode
# inter-token gaps are measured on each arm's DECODE worker virtual clock
# (serve/sched.py module docstring) and split by whether a chunked prefill
# was in flight during the gap: the unified arm inherits every chunk-scan
# wall, the disagg arm must stay flat (<= 10% p50 degradation) because the
# walls run on the prefill worker's clock — while the hand-off install /
# gather costs it DOES pay stay on the decode clock, honestly counted.
DISAGG_KW = dict(
    max_seq=56, paged=True, page_t=4, hot_slots=6, migration_interval=4,
    kv_quota=16, kv_tier_slots=12, kv_mass_threshold=0.01,
)
DISAGG_TOTAL_LANES = 3
DISAGG_PRE_LANES = 1
DISAGG_SEGMENTS = 6          # both pools + hand-offs in flight
DISAGG_CHUNK = 16            # <= the ring-wrap cap (hot_slots-1)*page_t = 20
DISAGG_STEPS = 240
DISAGG_VICTIM = "chat"       # the decode-heavy tenant whose TPOT we gate


def _decode_gaps(sched, tenant: str) -> tuple[list[float], list[float]]:
    """One tenant's decode inter-token gaps on the decode worker's virtual
    clock, split into (during, quiet) by whether any step in the gap's
    window had a chunked prefill in flight (Scheduler.prefill_busy)."""
    busy = sched.prefill_busy
    during, quiet = [], []
    for r in sched.finished:
        if r.tenant != tenant:
            continue
        for i in range(1, len(r.token_clock)):
            gap = r.token_clock[i] - r.token_clock[i - 1]
            s1, s2 = r.token_steps[i - 1], r.token_steps[i]
            overlapped = any(busy[s] for s in range(s1 + 1, s2 + 1))
            (during if overlapped else quiet).append(gap)
    return during, quiet


def _disagg_arm(params, trace, prefill_lanes: int) -> dict:
    """One arm of the disaggregation A/B: unified (prefill_lanes=0) or the
    split scheduler, same chunk size, same total lane budget, greedy."""
    cfg = get_smoke_config(ARCH)
    lanes = DISAGG_TOTAL_LANES - prefill_lanes
    eng = ServeEngine(cfg, params, ServeConfig(
        **DISAGG_KW, lanes=lanes, kv_segments=DISAGG_SEGMENTS))
    # unified prefills in-pool (warm that shape); the disagg decode engine
    # never scans a chunk — its prefill worker is warmed separately below
    compile_s = _warm_engine(
        eng, chunk=DISAGG_CHUNK if prefill_lanes == 0 else 0)
    tenants = [Tenant(t.name, t.weight) for t in trace.tenants]
    sched = Scheduler(eng, tenants, SchedConfig(
        preempt_patience=24, seed=trace.seed,
        prefill_chunk=DISAGG_CHUNK, prefill_lanes=prefill_lanes))
    if sched.peng is not None:
        compile_s += _warm_engine(sched.peng, chunk=DISAGG_CHUNK)
    t0 = time.perf_counter()
    play(trace, sched)
    wall = time.perf_counter() - t0
    rep = sched.report()
    assert rep["completed"] == rep["submitted"], "requests left undrained"
    during, quiet = _decode_gaps(sched, DISAGG_VICTIM)
    p_d = float(np.percentile(np.asarray(during), 50) * 1e3) if during else 0.0
    p_q = float(np.percentile(np.asarray(quiet), 50) * 1e3) if quiet else 0.0
    return {
        "mode": rep["mode"],
        "lanes": lanes,
        "prefill_lanes": prefill_lanes,
        "compile_s": compile_s,
        "steps": rep["steps"],
        "wall_s": wall,
        "completed": rep["completed"],
        "tokens": rep["tokens"],
        "preemptions": rep["preemptions"],
        "tpot_quiet_ms": p_q,
        "tpot_during_ms": p_d,
        "tpot_n": {"during": len(during), "quiet": len(quiet)},
        "tpot_degradation": p_d / max(p_q, 1e-9) - 1.0,
        "ttft_ms": rep["ttft_ms"],
        "handoff": rep["handoff"],
        "clock": rep["clock"],
        "resources": rep["resources"],
        "outputs": {int(r.rid): [int(t) for t in r.out]
                    for r in sched.finished},
    }


def _bench_disagg(params, seed: int) -> dict:
    """Prefill/decode disaggregation A/B (DESIGN.md §13).  Gates (asserted
    here AND in validate_bench.py): outputs bit-exact across arms, the
    disagg arm's hand-off fabric carried bytes both ways, decode-lane TPOT
    under concurrent prefill degrades <= 10% in the disagg arm and
    measurably more in the unified arm on the identical trace.  Always runs
    the full DISAGG_STEPS trace (even under --quick): the gate compares
    p50s of the during/quiet gap populations, and shrinking the trace
    shrinks the 'during' sample below where the medians are stable."""
    cfg = get_smoke_config(ARCH)
    trace = make_trace("prefill-heavy", n_steps=DISAGG_STEPS,
                       vocab=cfg.vocab, seed=seed, arrival=ARRIVAL)
    uni = _disagg_arm(params, trace, prefill_lanes=0)
    dis = _disagg_arm(params, trace, prefill_lanes=DISAGG_PRE_LANES)
    match = uni.pop("outputs") == dis.pop("outputs")
    assert match, ("disaggregation changed output tokens — "
                   "bit-exactness gate lost")
    ho = dis["handoff"]
    assert ho["count"] > 0 and ho["bytes_out"] > 0 and ho["bytes_in"] > 0, \
        f"hand-off fabric idle: {ho}"
    dd, ud = dis["tpot_degradation"], uni["tpot_degradation"]
    assert dd <= 0.10, (
        f"disagg decode TPOT degraded {dd:+.1%} under concurrent prefill "
        "(gate <= 10%) — the dedicated prefill lane did not isolate decode")
    assert ud > dd, (
        f"unified degradation {ud:+.1%} not above disagg {dd:+.1%} — "
        "the trace carries no prefill contention to isolate")
    return {
        "arch": ARCH,
        "trace": trace.kind,
        "seed": seed,
        "arrival": trace.arrival,
        "trace_steps": trace.n_steps,
        "page_t": DISAGG_KW["page_t"],
        "chunk": DISAGG_CHUNK,
        "total_lanes": DISAGG_TOTAL_LANES,
        "victim_tenant": DISAGG_VICTIM,
        "tokens_match": bool(match),
        "unified": uni,
        "disagg": dis,
    }


def run(quick: bool = False, reuse_only: bool = False,
        disagg_only: bool = False, kinds: tuple[str, ...] = BENCH_KINDS):
    n_steps = 120 if quick else 320
    params = tr.init_params(get_smoke_config(ARCH), jax.random.PRNGKey(0))
    if reuse_only:
        kr = _bench_reuse(params, n_steps, seed=0)
        emit("traffic_kv_reuse", 0.0,
             f"saved={kr['prefill_tokens_saved']} "
             f"hit sub={kr['substring']['reuse']['hit_rate']:.3f} "
             f"pre={kr['prefix']['reuse']['hit_rate']:.3f} "
             f"match={kr['tokens_match']}")
        update_bench_json(OUT_PATH, kv_reuse=kr)
        emit("traffic_bench_json", 0.0, os.path.normpath(OUT_PATH))
        return kr
    if disagg_only:
        dg = _bench_disagg(params, seed=0)
        emit("traffic_disagg", dg["disagg"]["tpot_during_ms"],
             f"tpot dur/quiet disagg={dg['disagg']['tpot_during_ms']:.1f}/"
             f"{dg['disagg']['tpot_quiet_ms']:.1f}ms "
             f"deg={dg['disagg']['tpot_degradation']:+.1%} "
             f"vs unified={dg['unified']['tpot_degradation']:+.1%} "
             f"handoffs={dg['disagg']['handoff']['count']} "
             f"match={dg['tokens_match']}")
        update_bench_json(OUT_PATH, disagg=dg)
        emit("traffic_bench_json", 0.0, os.path.normpath(OUT_PATH))
        return dg
    rows = [_bench_trace(kind, params, n_steps, seed=0)
            for kind in dict.fromkeys(CONTENT_KINDS + tuple(kinds))]
    by_kind = {r["trace"]: r for r in rows}
    gap = (by_kind["zipf-hot"]["hit_rate_steady"]
           - by_kind["scan-antagonist"]["hit_rate_steady"])
    assert gap > 0, (
        "adaptivity signal lost: zipf-hot steady hit rate "
        f"{by_kind['zipf-hot']['hit_rate_steady']:.3f} <= scan-antagonist "
        f"{by_kind['scan-antagonist']['hit_rate_steady']:.3f}")
    for r in rows:
        emit(f"traffic_{r['trace']}",
             r["tpot_ms"]["p50"] * 1e3,
             f"tok_s={r['tokens_per_s']:.1f} "
             f"ttft_p99={r['ttft_ms']['p99']:.1f}ms "
             f"tpot_p99={r['tpot_ms']['p99']:.1f}ms "
             f"hit={r['hit_rate']:.3f} steady={r['hit_rate_steady']:.3f} "
             f"mig_B_s={r['migration_bytes_per_s']:.0f} "
             f"preempt={r['preemptions']}")
    emit("traffic_adaptivity_gap", 0.0,
         f"zipf-scan steady hit gap={gap:+.3f}")
    pf = _bench_prefill(params)
    emit("traffic_prefill", pf["chunked"]["ttft_ms"] * 1e3,
         f"ttft chunked={pf['chunked']['ttft_ms']:.1f}ms "
         f"token={pf['token']['ttft_ms']:.1f}ms "
         f"ratio={pf['ttft_ratio']:.3f} match={pf['tokens_match']}")
    kr = _bench_reuse(params, n_steps, seed=0)
    emit("traffic_kv_reuse", 0.0,
         f"saved={kr['prefill_tokens_saved']} "
         f"hit sub={kr['substring']['reuse']['hit_rate']:.3f} "
         f"pre={kr['prefix']['reuse']['hit_rate']:.3f} "
         f"match={kr['tokens_match']}")
    dg = _bench_disagg(params, seed=0)
    emit("traffic_disagg", dg["disagg"]["tpot_during_ms"],
         f"tpot dur/quiet disagg={dg['disagg']['tpot_during_ms']:.1f}/"
         f"{dg['disagg']['tpot_quiet_ms']:.1f}ms "
         f"deg={dg['disagg']['tpot_degradation']:+.1%} "
         f"vs unified={dg['unified']['tpot_degradation']:+.1%} "
         f"handoffs={dg['disagg']['handoff']['count']} "
         f"match={dg['tokens_match']}")
    update_bench_json(OUT_PATH, traffic={
        "quick": quick,
        "arch": ARCH,
        "lanes": LANES,
        "arrival": ARRIVAL,
        "tenants": {t.name: t.weight for t in DEFAULT_TENANTS},
        "traces": rows,
    }, prefill=pf, kv_reuse=kr, disagg=dg)
    emit("traffic_bench_json", 0.0, os.path.normpath(OUT_PATH))
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--reuse", action="store_true",
                    help="run only the kv_reuse A/B section")
    ap.add_argument("--disagg", action="store_true",
                    help="run only the prefill/decode disaggregation A/B")
    ap.add_argument("--kinds", default=",".join(BENCH_KINDS),
                    help="comma-separated trace kinds for the traffic "
                    "section (the adaptivity-gap trio always runs)")
    args = ap.parse_args()
    run(quick=args.quick, reuse_only=args.reuse, disagg_only=args.disagg,
        kinds=tuple(k for k in args.kinds.split(",") if k))
