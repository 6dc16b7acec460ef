"""Serving benchmark: tokens/s + tier hit rates + measured migration bytes/s.

Run with ``--compress`` for the codec A/B (the ``compress`` section): the
same lane-scheduler trace served under each slow-store codec
(``none`` / ``fp32`` / ``int8``, tiering/codec.py, DESIGN.md §14) at the
same page quota, gating the wire-byte cut, hit-rate parity, logit drift,
and the zero1 ``compress_collective`` parity + collective byte cut.

Drives the ServeEngine's multi-resource tiering path (paged KV + embedding
rows, plus experts on the MoE arch) on smoke-scale models and records the
perf trajectory into ``BENCH_serve.json`` — one row per served arch with
throughput, the unified TierStats snapshot of every registered resource,
and the migration data plane's measured traffic (payload bytes the daemon
epochs physically moved, next to the hit rates they bought).  The decode
steps read embedding/expert rows in-jit through the tiered store and the
"kv" resource profiles kernel-exported softmax mass (DESIGN.md §10).

It also runs the hotness-fidelity A/B (the ``mass_ab`` section): the
zipf-hot trace served twice, once with the old ``page_len`` fill proxy and
once with the kernel-true mass stream — identical trace, identical model,
only the profiling stream differs.  CI gates kernel >= fill on the
steady-state KV hit rate (validate_bench.py): the paper's claim that
proxy quality, not policy, limits tiering, measured in-repo.

The emitted schema is documented key-by-key in benchmarks/README.md and
validated in CI by benchmarks/validate_bench.py.
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
from repro.workloads import DEFAULT_TENANTS, make_trace, play

from benchmarks.common import emit, steady_start, update_bench_json

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")

CASES = [
    ("llama3.2-3b", dict(max_seq=256, paged=True, page_t=8, hot_slots=6,
                         migration_interval=4, resources=("embeddings",),
                         embed_hot_slots=4), 2, 16),
    ("kimi-k2-1t-a32b", dict(max_seq=256, paged=True, page_t=8, hot_slots=6,
                             migration_interval=4,
                             resources=("experts", "embeddings"),
                             expert_hot_slots=2, embed_hot_slots=2), 2, 16),
]

# The fidelity A/B: kv-only lane serving over the zipf-hot trace, fill proxy
# vs kernel mass (ServeConfig.kv_mass_source) — everything else identical.
AB_ARCH = "llama3.2-3b"
AB_ARRIVAL = "mmpp"
AB_KW = dict(max_seq=64, paged=True, page_t=4, hot_slots=6,
             migration_interval=4, kv_quota=16, kv_tier_slots=12,
             kv_mass_threshold=0.01, lanes=4, kv_segments=6)

# The codec A/B (DESIGN.md §14): the fidelity-A/B serving shape plus tiered
# embeddings, so both the KV flush path and the in-jit embedding read path
# run through the slow-store codec.  The fp arm is the ``fp32`` codec — a
# full-precision store that is numerically the identity for the engine's
# bf16 rows — so the int8/fp32 byte ratio measures compression against a
# true full-precision slow tier at the SAME page quota.
COMPRESS_ARMS = ("none", "fp32", "int8")
COMPRESS_KW = dict(AB_KW, resources=("embeddings",), embed_hot_slots=6,
                   embed_quota=8, embed_rows_per_page=8)
# Logit-drift probe: single-request decode sized to stay inside the paged
# ring (prompt + steps <= (hot_slots-1)*page_t), so drift isolates the
# embedding read path's dequantization.
# The overlap A/B (DESIGN.md §15): the MoE smoke arch served twice —
# synchronous data plane vs the double-buffered async one — so the gate
# covers every resource class at once (paged KV + experts + embeddings).
# Identical model/trace/quota: same tokens, same migration bytes; only
# WHEN decode pays for the copies differs (sync: a metered block every
# epoch; async: the copy overlaps decode and the commit is a pointer swap).
OVERLAP_ARCH = "kimi-k2-1t-a32b"
OVERLAP_KW = dict(max_seq=64, paged=True, page_t=4, hot_slots=6,
                  migration_interval=4, kv_quota=16,
                  resources=("experts", "embeddings"),
                  expert_hot_slots=2, embed_hot_slots=2)
OVERLAP_STALL_RATIO = 0.25   # async stall gate: <= 1/4 of the sync arm's

PROBE_PROMPT, PROBE_STEPS = 12, 8
PROBE_DRIFT_BOUND = 0.25     # max |logit(int8) - logit(none)|, fp32 compare
COMPRESS_BYTES_RATIO = 0.35  # int8/fp32 migration-byte gate (expect ~0.26)
COMPRESS_HIT_EPS = 0.02      # steady hit-rate degradation allowance
ZERO1_STEPS = 6
ZERO1_DRIFT_TOL = 1e-3       # max |param(fp32) - param(int8+EF)| after run
ZERO1_BYTES_RATIO = 0.30     # collective byte gate (expect ~0.25)


def _bench(arch: str, scfg_kw: dict, batch: int, prompt_len: int,
           n_tokens: int) -> dict:
    cfg = get_smoke_config(arch)
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, ServeConfig(**scfg_kw))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (batch, prompt_len)).astype(np.int32)
    # untimed warmup: one short generate traces+compiles every jitted body
    # the timed run uses (prefill scan, decode step, flush scatter), so the
    # throughput window below measures steady-state execution, not XLA.
    # The trace/compile wall is recorded separately as ``compile_s``.
    t0 = time.perf_counter()
    eng.generate(prompts, n_tokens=2)
    compile_s = time.perf_counter() - t0
    moved0 = {n: r["migration_bytes"]
              for n, r in eng.tier_stats().items()}
    t0 = time.perf_counter()
    out = eng.generate(prompts, n_tokens=n_tokens)
    dt = time.perf_counter() - t0
    assert out.shape == (batch, n_tokens)
    resources = eng.tier_stats()
    # migration traffic of the timed window only (warmup bytes excluded)
    moved = sum(r["migration_bytes"] - moved0[n]
                for n, r in resources.items())
    return {
        "arch": arch,
        "batch": batch,
        "prompt_len": prompt_len,
        "n_tokens": n_tokens,
        "compile_s": compile_s,
        "tokens_per_s": batch * n_tokens / dt,
        "wall_s": dt,
        "migration_bytes": moved,
        "migration_bytes_per_s": moved / dt,
        "resources": resources,
    }


def _kv_counts(eng) -> tuple[int, int]:
    row = eng.tier_stats()["kv"]
    return row["fast_reads"], row["slow_reads"]


def _mass_ab_run(source: str, n_steps: int) -> dict:
    """One arm of the fidelity A/B: the zipf-hot trace through the lane
    scheduler with the given "kv" mass source; the steady-state window is
    ``common.steady_start`` — the same convention traffic_bench uses."""
    cfg = get_smoke_config(AB_ARCH)
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params,
                      ServeConfig(**AB_KW, kv_mass_source=source))
    sched = Scheduler(eng, [Tenant(t.name, t.weight) for t in DEFAULT_TENANTS],
                      SchedConfig(preempt_patience=24))
    trace = make_trace("zipf-hot", n_steps=n_steps, vocab=cfg.vocab, seed=0,
                       arrival=AB_ARRIVAL)
    mid: list[tuple[int, int]] = []

    def snap(s):
        if not mid and s.step_count >= steady_start(trace.n_steps):
            mid.append(_kv_counts(eng))

    t0 = time.perf_counter()
    play(trace, sched, on_step=snap)
    wall = time.perf_counter() - t0
    rep = sched.report()
    f1, s1 = mid[0]
    f2, s2 = _kv_counts(eng)
    return {
        "kv_mass_source": source,
        "steps": rep["steps"],
        "tokens": rep["tokens"],
        "wall_s": wall,
        "kv_hit": f2 / max(f2 + s2, 1),
        "kv_hit_steady": (f2 - f1) / max((f2 + s2) - (f1 + s1), 1),
        "kv_promoted": rep["resources"]["kv"]["promoted"],
        "migration_bytes": rep["resources"]["kv"]["migration_bytes"],
    }


def _mass_ab(quick: bool) -> dict:
    # even the quick arm needs enough steps for the placement map to
    # converge past its cold start — the fidelity signal lives in the
    # steady-state window, not the warmup
    n_steps = 160 if quick else 320
    rows = {src: _mass_ab_run(src, n_steps) for src in ("fill", "kernel")}
    return {"arch": AB_ARCH, "trace": "zipf-hot", "arrival": AB_ARRIVAL,
            "lanes": AB_KW["lanes"], "seed": 0, "trace_steps": n_steps,
            "fill": rows["fill"], "kernel": rows["kernel"]}


def _tier_counts(eng) -> dict[str, tuple[int, int]]:
    return {n: (row["fast_reads"], row["slow_reads"])
            for n, row in eng.tier_stats().items()}


def _compress_run(codec: str, n_steps: int) -> tuple[dict, list]:
    """One codec arm: the zipf-hot trace through the lane scheduler with the
    slow stores encoded as ``codec``; same trace, same page quota, same
    model — only the wire format differs.  Returns the arm row plus the
    finished requests' exact output streams (for the bit-exactness gate)."""
    cfg = get_smoke_config(AB_ARCH)
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, ServeConfig(**COMPRESS_KW,
                                               slow_codec=codec))
    sched = Scheduler(eng, [Tenant(t.name, t.weight) for t in DEFAULT_TENANTS],
                      SchedConfig(preempt_patience=24))
    trace = make_trace("zipf-hot", n_steps=n_steps, vocab=cfg.vocab, seed=0,
                       arrival=AB_ARRIVAL)
    mid: list[dict] = []

    def snap(s):
        if not mid and s.step_count >= steady_start(trace.n_steps):
            mid.append(_tier_counts(eng))

    t0 = time.perf_counter()
    play(trace, sched, on_step=snap)
    wall = time.perf_counter() - t0
    rep = sched.report()
    assert rep["completed"] == rep["submitted"], "requests left undrained"
    after = _tier_counts(eng)
    steady = {}
    for name, (f1, s1) in mid[0].items():
        f2, s2 = after[name]
        steady[name] = (f2 - f1) / max((f2 + s2) - (f1 + s1), 1)
    resources = rep["resources"]
    outputs = [(r.tenant, r.prompt.tobytes(), tuple(r.out))
               for r in sched.finished]
    return {
        "codec": codec,
        "steps": rep["steps"],
        "tokens": rep["tokens"],
        "wall_s": wall,
        "hit_steady": steady,
        "wire_row_bytes": {n: eng.daemon[n].mem.row_bytes
                           for n in resources},
        "migration_bytes": sum(r["migration_bytes"]
                               for r in resources.values()),
        "max_epoch_bytes": sum(r["max_epoch_bytes"]
                               for r in resources.values()),
        "quota_bytes": sum(r["quota_bytes"] for r in resources.values()),
        "resources": resources,
    }, outputs


def _logit_probe() -> dict:
    """Single-request decode under each codec, logits captured per step.

    The ``fp32`` arm must match ``none`` EXACTLY (bf16 -> fp32 -> bf16 is
    the identity — this is what makes it the fp arm, and what proves the
    codec plumbing itself is transparent); the ``int8`` arm's drift is
    bounded: every embedding row decodes within scale/2 per element.
    """
    cfg = get_smoke_config(AB_ARCH)
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(7).integers(
        0, cfg.vocab, (1, PROBE_PROMPT)).astype(np.int32)
    kw = dict(COMPRESS_KW)
    for k in ("lanes", "kv_segments"):
        kw.pop(k)                         # single-request mode
    logits, tokens = {}, {}
    for codec in COMPRESS_ARMS:
        eng = ServeEngine(cfg, params, ServeConfig(**kw, slow_codec=codec))
        tok = eng.prefill(prompt)
        steps, toks = [], [int(tok[0])]
        for _ in range(PROBE_STEPS):
            lg = eng._advance(jnp.asarray(tok)[:, None])
            steps.append(np.asarray(lg[:, -1], np.float32))
            tok = np.asarray(jnp.argmax(lg[:, -1], -1))
            toks.append(int(tok[0]))
        logits[codec] = np.stack(steps)
        tokens[codec] = toks
    drift_fp32 = float(np.max(np.abs(logits["fp32"] - logits["none"])))
    drift_int8 = float(np.max(np.abs(logits["int8"] - logits["none"])))
    return {
        "prompt_len": PROBE_PROMPT,
        "n_steps": PROBE_STEPS,
        "tokens_match_none_fp32": tokens["fp32"] == tokens["none"],
        "drift_fp32": drift_fp32,
        "drift_int8": drift_int8,
        "drift_bound": PROBE_DRIFT_BOUND,
    }


def _zero1_compress() -> dict:
    """The codec subsystem's second consumer: ZeRO-1's delta gather
    quantized per shard with error feedback vs the fp32 baseline —
    same grads, same schedule, parity-bounded params, ~4x fewer
    collective bytes (optim/zero1.py)."""
    from repro.optim import zero1
    from repro.optim.optimizers import OptConfig

    cfg = OptConfig(lr=1e-2, weight_decay=0.0, warmup_steps=0,
                    total_steps=100)
    rng = np.random.default_rng(11)
    params = {"w": jnp.asarray(rng.normal(size=(32, 48)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(96,)), jnp.float32)}
    st_f, spec = zero1.zero1_init(params, None)
    st_c, _ = zero1.zero1_init(params, None, compress_collective=True)
    pf, pc = params, params
    bytes_f = bytes_c = 0
    for i in range(ZERO1_STEPS):
        grads = jax.tree.map(
            lambda p: jnp.asarray(rng.normal(size=p.shape) * 0.1,
                                  jnp.float32), params)
        pf, st_f, om_f = zero1.zero1_update(cfg, pf, grads, st_f, spec, None)
        pc, st_c, om_c = zero1.zero1_update(cfg, pc, grads, st_c, spec, None,
                                            compress_collective=True)
        bytes_f += int(om_f["collective_bytes"])
        bytes_c += int(om_c["collective_bytes"])
    drift = max(float(jnp.max(jnp.abs(pf[k] - pc[k]))) for k in params)
    return {
        "steps": ZERO1_STEPS,
        "padded": spec.size,
        "bytes_fp32": bytes_f,
        "bytes_int8": bytes_c,
        "byte_ratio": bytes_c / bytes_f,
        "byte_ratio_bound": ZERO1_BYTES_RATIO,
        "update_drift": drift,
        "drift_tolerance": ZERO1_DRIFT_TOL,
    }


def _compress_ab(quick: bool) -> dict:
    n_steps = 160 if quick else 320
    arms, outputs = {}, {}
    for codec in COMPRESS_ARMS:
        arms[codec], outputs[codec] = _compress_run(codec, n_steps)
    ratio = (arms["int8"]["migration_bytes"]
             / max(arms["fp32"]["migration_bytes"], 1))
    return {
        "arch": AB_ARCH, "trace": "zipf-hot", "arrival": AB_ARRIVAL,
        "lanes": COMPRESS_KW["lanes"], "seed": 0, "trace_steps": n_steps,
        "quick": quick,
        "arms": arms,
        "bytes_ratio_int8_fp32": ratio,
        "bytes_ratio_bound": COMPRESS_BYTES_RATIO,
        "hit_eps": COMPRESS_HIT_EPS,
        # the bit-exactness gate: the fp32 store changes NOTHING about the
        # served stream (every request's every output token identical),
        # which also certifies the codec plumbing as the identity under
        # codec="none" — the pre-codec data path
        "tokens_match_none_fp32": outputs["fp32"] == outputs["none"],
        "probe": _logit_probe(),
        "zero1": _zero1_compress(),
    }


def _overlap_run(async_on: bool, batch: int, prompt_len: int,
                 n_tokens: int) -> tuple[np.ndarray, dict]:
    cfg = get_smoke_config(OVERLAP_ARCH)
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, ServeConfig(async_migration=async_on,
                                               **OVERLAP_KW))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (batch, prompt_len)).astype(np.int32)
    t0 = time.perf_counter()
    eng.generate(prompts, n_tokens=2)       # trace+compile warmup
    compile_s = time.perf_counter() - t0
    # close the warmup's books so the timed window meters only itself: the
    # forced finalize commits any epoch the warmup left in flight (its
    # block time lands in the warmup stall baseline, subtracted below)
    eng.daemon.finalize()
    res0 = eng.tier_stats()
    t0 = time.perf_counter()
    out = eng.generate(prompts, n_tokens=n_tokens)
    wall = time.perf_counter() - t0
    eng.daemon.finalize()                   # equal-bytes accounting barrier
    res = eng.tier_stats()
    moved = sum(r["migration_bytes"] - res0[n]["migration_bytes"]
                for n, r in res.items())
    stall = sum(r["stall_s"] - res0[n]["stall_s"] for n, r in res.items())
    return out, {
        "mode": "async" if async_on else "sync",
        "steps": n_tokens,
        "compile_s": compile_s,
        "wall_s": wall,
        "tokens_per_s": batch * n_tokens / wall,
        "stall_s": stall,
        "migration_bytes": moved,
        "resources": res,
    }


def _overlap_ab(quick: bool) -> dict:
    batch, prompt_len = 2, 12
    n_tokens = 16 if quick else 32
    out_sync, arm_sync = _overlap_run(False, batch, prompt_len, n_tokens)
    out_async, arm_async = _overlap_run(True, batch, prompt_len, n_tokens)
    return {
        "arch": OVERLAP_ARCH,
        "batch": batch,
        "prompt_len": prompt_len,
        "n_tokens": n_tokens,
        "tokens_match": bool(np.array_equal(out_sync, out_async)),
        "stall_ratio_bound": OVERLAP_STALL_RATIO,
        "sync": arm_sync,
        "async": arm_async,
    }


def run_overlap(quick: bool = False) -> dict:
    ov = _overlap_ab(quick)
    s, a = ov["sync"], ov["async"]
    emit("serve_overlap", 0.0,
         f"match={ov['tokens_match']} "
         f"stall sync={s['stall_s']:.3f}s async={a['stall_s']:.3f}s "
         f"(gate <= {ov['stall_ratio_bound']} x) "
         f"bytes sync={s['migration_bytes']} async={a['migration_bytes']}")
    update_bench_json(OUT_PATH, overlap=ov)
    emit("serve_bench_json", 0.0, os.path.normpath(OUT_PATH))
    return ov


def run_compress(quick: bool = False) -> dict:
    comp = _compress_ab(quick)
    emit("serve_compress_bytes", 0.0,
         f"int8/fp32 mig bytes={comp['bytes_ratio_int8_fp32']:.3f} "
         f"(gate <= {comp['bytes_ratio_bound']}) "
         f"int8={comp['arms']['int8']['migration_bytes']} "
         f"fp32={comp['arms']['fp32']['migration_bytes']}")
    emit("serve_compress_fidelity", 0.0,
         f"match(none,fp32)={comp['tokens_match_none_fp32']} "
         f"drift fp32={comp['probe']['drift_fp32']:.2e} "
         f"int8={comp['probe']['drift_int8']:.3f} "
         f"(gate <= {comp['probe']['drift_bound']})")
    z = comp["zero1"]
    emit("serve_compress_zero1", 0.0,
         f"drift={z['update_drift']:.2e} (tol {z['drift_tolerance']}) "
         f"bytes ratio={z['byte_ratio']:.3f} (gate <= {z['byte_ratio_bound']})")
    update_bench_json(OUT_PATH, compress=comp)
    emit("serve_bench_json", 0.0, os.path.normpath(OUT_PATH))
    return comp


def run(quick: bool = False):
    n_tokens = 8 if quick else 32
    rows = [_bench(arch, kw, batch, plen, n_tokens)
            for arch, kw, batch, plen in CASES]
    for r in rows:
        hits = " ".join(f"{name}_hit={res['hit_rate']:.3f}"
                        for name, res in sorted(r["resources"].items()))
        emit(f"serve_{r['arch']}", r["wall_s"] * 1e6 / (r['batch'] * n_tokens),
             f"tok_s={r['tokens_per_s']:.1f} "
             f"mig_B_s={r['migration_bytes_per_s']:.0f} {hits}")
    ab = _mass_ab(quick)
    emit("serve_mass_ab", 0.0,
         f"kv_hit_steady kernel={ab['kernel']['kv_hit_steady']:.3f} "
         f"fill={ab['fill']['kv_hit_steady']:.3f} "
         f"gap={ab['kernel']['kv_hit_steady'] - ab['fill']['kv_hit_steady']:+.3f}")
    update_bench_json(OUT_PATH, quick=quick, cases=rows, mass_ab=ab)
    emit("serve_bench_json", 0.0, os.path.normpath(OUT_PATH))
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="shorter traces / fewer decode tokens")
    ap.add_argument("--compress", action="store_true",
                    help="run only the codec A/B (the `compress` section)")
    ap.add_argument("--overlap", action="store_true",
                    help="run only the async-migration A/B (the `overlap` "
                         "section, DESIGN.md §15)")
    ns = ap.parse_args()
    if ns.compress:
        run_compress(quick=ns.quick)
    elif ns.overlap:
        run_overlap(quick=ns.quick)
    else:
        run(quick=ns.quick)
