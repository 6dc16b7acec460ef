"""Chip smoke test: the serving main path at full width on one TPU.

    python chip_smoke.py             # one chip: serve qwen1.5-4b
    python chip_smoke.py --chips 4   # four chips: ZeRO-1 data-parallel train

One chip: qwen1.5-4b at its published widths, bf16 weights drawn from
``--seed``, served through ``ServeEngine`` + ``Scheduler`` in lane mode
with a paged KV ring, the ``kv`` and ``embeddings`` tiers (slow stores in
pinned host memory) and the kernel-exported KV mass stream.  The same
requests are served twice, once per migration data plane (sync, async);
both must emit identical tokens and move equal migration bytes.  The
logits of one request's first decode step are compared with a float32
dense forward of the same tokens.

Four chips: a few ``build_train_step`` steps of the data-parallel train
step with ZeRO-1 optimizer state parked in host memory and the int8+EF
compressed gradient all-reduce, on a 4-chip ``data`` mesh and on one chip
with the same global batch; losses must agree and every chip must hold
its own shard of the optimizer state.

Every phase that fails raises; the run then exits non-zero.  The last
line of standard output is one JSON object naming the device.  With no
TPU the script exits non-zero before printing it.
"""
from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.core.neoprof import NeoProfParams, neoprof_init  # noqa: E402
from repro.core.sketch import SketchParams  # noqa: E402
from repro.dist import compression  # noqa: E402
from repro.dist import host_offload as ho  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import transformer as tr  # noqa: E402
from repro.models.layers import logits_apply  # noqa: E402
from repro.optim import zero1  # noqa: E402
from repro.optim.optimizers import OptConfig, make_optimizer  # noqa: E402
from repro.serve.engine import ServeConfig, ServeEngine  # noqa: E402
from repro.serve.sched import SchedConfig, Scheduler, Tenant  # noqa: E402
from repro.train.step import TrainConfig, build_train_step  # noqa: E402

ARCH = "qwen1.5-4b"
# Relative L2 distance allowed between the served bf16 logits and the
# float32 reference.  bf16 keeps 8 significant bits (unit roundoff 2^-9);
# the residual stream is rounded about twice per layer, so over 40 layers
# ~80 roundings of relative size <= 2^-9 accumulate, as a random walk, to
# ~sqrt(80) * 2^-9 / sqrt(3) ~= 1e-2 (uniform rounding error has rms
# u / sqrt(3)).  5e-2 leaves 5x headroom; a wrong kernel, a stale tier read
# or a misrouted page lands at O(1).
LOGIT_REL_L2_BOUND = 5e-2
# Loss agreement between the 4-chip step and the 1-chip plain AdamW step.
# The first loss is taken before any update, so the two differ only in
# reduction order: at most LOSS_FIRST_BOUND.  Later losses follow updates
# whose gradients differ by bf16 rounding of per-chip partial sums and by
# the int8 all-reduce (an element under half a quantum is sent as 0, so
# Adam moves it 0 instead of ~lr); those elements carry a small share of
# the step, so the 4-chip loss stays within LOSS_REL_BOUND of the distance
# the reference itself moved.  Measured at smoke widths on four virtual
# CPU devices: 1.7% and 3.5% of that distance (lr 1e-4).  At the
# published widths the reference moves ~0.1 per step at lr 1e-5 (one CPU
# device), a near-linear regime; at lr 1e-4 it falls 11.4 -> 3.4 in two
# steps, where small differences grow.  A chip that reduced the wrong
# rows follows another direction and misses a large share of the step.
LOSS_FIRST_BOUND = 1e-3
LOSS_REL_BOUND = 0.1
# Relative gap between the first step's global gradient norms (same params,
# same batch).  Losses cannot show the gradient's scale: clipping and Adam
# cancel a uniform factor, so a 4-chip reduce that summed the per-chip mean
# gradients instead of averaging them (a 4x norm, gap 3) would pass the
# loss bounds.  The int8 all-reduce rounds each chip's sum to half a
# quantum per element, which moves the norm by a small share: measured at
# smoke widths on four virtual CPU devices, 3.0e-4 (the fp32 manual reduce
# against the partitioner's: 1.1e-5).  1e-2 leaves 30x headroom.
GNORM_REL_BOUND = 1e-2


@dataclasses.dataclass(frozen=True)
class ServeSizes:
    """Serving geometry: lanes, KV ring and tiers, and the request mix."""

    lanes: int = 4
    page_t: int = 64
    # ring pages per lane (HBM): 8, not 16 — at 16 the chunked-prefill scan
    # (params 7.1 GB + ring in and out 2 x 1.7 GB + 6.6 GB of scan
    # temporaries) needs 17.1 GB of the v5e's 15.75 GB (AOT compile for v5e)
    hot_slots: int = 8
    max_seq: int = 2048
    kv_segments: int = 6           # host KV store: segments x max_seq tokens
    kv_quota: int = 4              # KV pages promoted per epoch
    embed_rows_per_page: int = 64
    embed_hot_slots: int = 64
    embed_quota: int = 16
    migration_interval: int = 16
    prefill_chunk: int = 256
    # the first request is the one compared with the reference: its prompt
    # plus one decode token stays inside the ring (8 x 64 tokens), so the
    # paged attention window is the whole sequence; the longer prompts wrap
    # the ring, as long contexts do
    prompt_lens: tuple[int, ...] = (448, 320, 704, 384, 576, 512)
    max_new: int = 32


@dataclasses.dataclass(frozen=True)
class TrainSizes:
    """Train geometry for the four-chip phase (depth cut from published)."""

    n_layers: int = 2
    global_batch: int = 8
    seq_len: int = 256
    microbatches: int = 2      # 4 rows each: one per chip
    steps: int = 3
    lr: float = 1e-5


T0 = time.perf_counter()
# a phase that has not returned after this long dumps every thread's stack
# to stderr (and again each period), so a stalled run shows where it is
STACK_DUMP_S = 600


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f} s] {msg}", flush=True)


def hbm() -> str:
    """Device memory now in use and its peak so far (first device)."""
    stats = jax.devices()[0].memory_stats() or {}
    return (f"HBM bytes_in_use {stats.get('bytes_in_use')} "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def tpu_device():
    """The first device, which must be a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    return dev


def random_params(cfg, seed: int):
    """bf16 weights of ``cfg`` drawn from ``seed`` (no checkpoint)."""
    return jax.jit(lambda k: tr.init_params(cfg, k))(jax.random.PRNGKey(seed))


def param_bytes(params) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(params))


def make_prompts(vocab: int, lens, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


class FirstDecodeEngine(ServeEngine):
    """ServeEngine that keeps the input token and logits row of the first
    decode step served on KV segment ``watch_segment``."""

    def __init__(self, *args, watch_segment: int = 0, **kw):
        super().__init__(*args, **kw)
        self.watch_segment = watch_segment
        self.watched: tuple[int, np.ndarray] | None = None

    def advance_lanes(self, tokens, active, segments):
        out = super().advance_lanes(tokens, active, segments)
        if self.watched is None:
            lanes = np.flatnonzero(np.asarray(active) & (
                np.asarray(segments) == self.watch_segment))
            if lanes.size:
                lane = int(lanes[0])
                self.watched = (int(np.asarray(tokens)[lane]),
                                np.asarray(out[lane], np.float32))
        return out


def serve_config(sizes: ServeSizes, async_migration: bool) -> ServeConfig:
    return ServeConfig(
        max_seq=sizes.max_seq, page_t=sizes.page_t, hot_slots=sizes.hot_slots,
        paged=True, migration_interval=sizes.migration_interval,
        resources=("embeddings",), kv_quota=sizes.kv_quota,
        embed_hot_slots=sizes.embed_hot_slots, embed_quota=sizes.embed_quota,
        embed_rows_per_page=sizes.embed_rows_per_page, lanes=sizes.lanes,
        kv_segments=sizes.kv_segments, kv_mass_source="kernel",
        async_migration=async_migration)


def slow_memory_kinds(eng: ServeEngine) -> dict[str, list[str]]:
    """Memory kind of every slow-tier buffer (store and int8 scales)."""
    out = {}
    for name, h in eng.daemon.resources.items():
        bufs = h.mem.buffers
        out[name] = [b.sharding.memory_kind for b in (bufs.slow, bufs.scale)
                     if b is not None]
    return out


def decode_hlo(eng: ServeEngine) -> str:
    """Compiled HLO text of the engine's jitted lane decode step."""
    lanes = eng.scfg.lanes
    return eng._decode_paged.lower(
        eng.params, eng.cache, jnp.zeros((lanes, 1), jnp.int32),
        eng._tier_reads(), jnp.zeros(lanes, bool)).compile().as_text()


def serve(cfg, params, sizes: ServeSizes, prompts, async_migration: bool
          ) -> dict:
    """Serve ``prompts`` through ServeEngine + Scheduler; return the tokens,
    tier telemetry, device checks and the watched first-decode logits."""
    eng = FirstDecodeEngine(cfg, params, serve_config(sizes, async_migration),
                            watch_segment=0)
    sched = Scheduler(eng, [Tenant("smoke")],
                      SchedConfig(prefill_chunk=sizes.prefill_chunk))
    log(f"engine up (async={async_migration}): slow stores bound")
    t0 = time.perf_counter()
    lanes, idle = sizes.lanes, jnp.zeros(sizes.lanes, bool)
    jax.block_until_ready(eng._decode_paged(
        params, eng.cache, jnp.zeros((lanes, 1), jnp.int32),
        eng._tier_reads(), idle)[0])
    jax.block_until_ready(eng._prefill_paged_jit(
        params, eng.cache, jnp.zeros((lanes, sizes.prefill_chunk), jnp.int32),
        jnp.zeros((lanes, sizes.prefill_chunk), bool), idle,
        eng._tier_reads())[0])
    compile_s = time.perf_counter() - t0
    log(f"decode step and prefill chunk compiled in {compile_s:.1f} s")
    reqs = [sched.submit("smoke", p, max_new=sizes.max_new) for p in prompts]
    t0 = time.perf_counter()
    while sched.active:
        sched.step()
        if sched.step_count % 25 == 0:
            log(f"step {sched.step_count}: {len(sched.finished)} requests "
                f"done, {sum(r.pos for r in reqs)} tokens consumed")
    eng.daemon.finalize()
    serve_s = time.perf_counter() - t0
    first = reqs[0]
    if first.segment != eng.watch_segment or first.preemptions:
        raise RuntimeError("the watched request left its KV segment")
    if eng.watched is None or eng.watched[0] != first.out[0]:
        raise RuntimeError("first decode step of the watched request not seen")
    if int(np.argmax(eng.watched[1])) != first.out[1]:
        raise RuntimeError("watched logits do not yield the emitted token")
    log(f"served {len(reqs)} requests in {serve_s:.1f} s; checking the "
        f"compiled decode step for the kernel")
    result = {
        "tokens": [list(map(int, r.out)) for r in reqs],
        "n_tokens": sum(len(r.out) for r in reqs),
        "stats": eng.tier_stats(),
        "memory_kinds": slow_memory_kinds(eng),
        "kernel_in_decode": "tpu_custom_call" in decode_hlo(eng),
        "watched_tokens": np.concatenate(
            [prompts[0], np.asarray(first.out[:1], np.int32)]),
        "watched_logits": eng.watched[1],
        "compile_s": compile_s,
        "serve_s": serve_s,
    }
    del eng, sched
    return result


def reference_logits(cfg, params, tokens) -> np.ndarray:
    """Last-position logits of a float32 dense forward (models/transformer)
    over ``tokens``: activations and matmuls in float32 at the highest
    matmul precision, weights the same bf16 values the engine serves."""
    table32 = params["embed"]["table"].astype(jnp.float32)

    @jax.jit
    def fwd(p, table, toks):
        p = dict(p, embed={"table": table})
        with jax.default_matmul_precision("highest"):
            x, _ = tr.forward(cfg, p, toks[None], remat=False)
            return logits_apply(p["embed"], x[:, -1:], cfg.final_softcap)[0, 0]

    return np.asarray(fwd(params, table32, jnp.asarray(tokens, jnp.int32)),
                      np.float32)


def logit_error(got: np.ndarray, ref: np.ndarray) -> dict:
    diff = got.astype(np.float64) - ref.astype(np.float64)
    return {"rel_l2": float(np.linalg.norm(diff) / np.linalg.norm(ref)),
            "max_abs": float(np.max(np.abs(diff))),
            "top1_agree": bool(np.argmax(got) == np.argmax(ref))}


def check_serving(sync: dict, anc: dict) -> dict:
    """The serving invariants; raises on the first broken one."""
    if sync["tokens"] != anc["tokens"]:
        raise RuntimeError("sync and async data planes emitted different "
                           "tokens")
    moved = {}
    for name, row in sync["stats"].items():
        a = anc["stats"][name]["migration_bytes"]
        if row["migration_bytes"] != a:
            raise RuntimeError(f"{name}: migration bytes sync "
                               f"{row['migration_bytes']} != async {a}")
        moved[name] = a
    if not sum(moved.values()):
        raise RuntimeError("no migration epoch moved bytes")
    for res in (sync, anc):
        bad = {n: k for n, k in res["memory_kinds"].items()
               if set(k) != {ho.SLOW_KIND}}
        if bad:
            raise RuntimeError(f"slow stores outside {ho.SLOW_KIND}: {bad}")
        fast = sum(r["fast_reads"] for r in res["stats"].values())
        slow = sum(r["slow_reads"] for r in res["stats"].values())
        if not (fast and slow):
            raise RuntimeError(f"tiers served fast={fast} slow={slow} reads")
    return moved


def run_serving(cfg, sizes: ServeSizes, seed: int) -> None:
    params = random_params(cfg, seed)
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv, head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}; bf16 params "
        f"{param_bytes(params)} bytes from seed {seed}")
    log(f"sizes {dataclasses.asdict(sizes)}")
    prompts = make_prompts(cfg.vocab, sizes.prompt_lens, seed)
    arms = {}
    for async_migration in (False, True):
        arm = "async" if async_migration else "sync"
        res = arms[arm] = serve(cfg, params, sizes, prompts, async_migration)
        log(f"[{arm}] compile {res['compile_s']:.1f} s (chip), serve "
            f"{res['serve_s']:.1f} s (chip), {res['n_tokens']} tokens, "
            f"slow stores {res['memory_kinds']}, tpu_custom_call in decode "
            f"step: {res['kernel_in_decode']}; {hbm()}")
        for name, row in res["stats"].items():
            log(f"[{arm}] {name}: fast_reads {row['fast_reads']} slow_reads "
                f"{row['slow_reads']} hit_rate {row['hit_rate']} "
                f"migration_bytes {row['migration_bytes']} epochs "
                f"{row['migration_epochs']} flush_bytes {row['flush_bytes']}")
        gc.collect()      # the engine's jitted bound methods form a cycle
    moved = check_serving(arms["sync"], arms["async"])
    if not arms["sync"]["kernel_in_decode"]:
        raise RuntimeError("decode step holds no tpu_custom_call: the paged "
                           "attention kernel was not compiled")
    log(f"sync == async tokens, migration bytes {moved}; float32 reference")
    ref = reference_logits(cfg, params, arms["sync"]["watched_tokens"])
    err = logit_error(arms["sync"]["watched_logits"], ref)
    log(f"first decode logits vs float32 reference: {err} "
        f"(bound rel_l2 <= {LOGIT_REL_L2_BOUND}); {hbm()}")
    if not err["rel_l2"] <= LOGIT_REL_L2_BOUND:
        raise RuntimeError(f"logits off the float32 reference: {err}")


def sharded_config(lr: float, microbatches: int) -> TrainConfig:
    """The four-chip step: ZeRO-1 state parked in host memory, gradients
    reduced once per step through the int8+EF compressed all-reduce."""
    return TrainConfig(
        opt=OptConfig(lr=lr, warmup_steps=0, total_steps=100),
        microbatches=microbatches, remat=True, zero1=True,
        offload_master=True, local_grads=True, grad_compression=True)


def reference_config(lr: float, microbatches: int) -> TrainConfig:
    """The one-chip reference: plain per-tensor AdamW, fp32 reduction."""
    return TrainConfig(
        opt=OptConfig(lr=lr, warmup_steps=0, total_steps=100),
        microbatches=microbatches, remat=True)


def train_run(cfg, sizes: TrainSizes, tcfg: TrainConfig, devices, seed: int):
    """``sizes.steps`` train steps under ``tcfg`` on a ``data`` mesh over
    ``devices``; returns (losses, gradient norms, final state)."""
    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    params = jax.device_put(random_params(cfg, seed), rep)
    if tcfg.zero1:
        opt, _ = zero1.zero1_init(params, mesh,
                                  offload=tcfg.offload_master)
    else:
        opt = jax.jit(make_optimizer(tcfg.opt)[0])(params)
    state = {"params": params, "opt": opt,
             "prof": jax.device_put(neoprof_init(NeoProfParams(
                 sketch=SketchParams(width=tcfg.sketch_width))), rep)}
    if tcfg.grad_compression:
        state["ef"] = jax.device_put(compression.ef_init(params), rep)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (sizes.global_batch, sizes.seq_len), 0,
                                cfg.vocab)
    data = jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec("data", None))
    batch = {"tokens": jax.device_put(tokens, data),
             "labels": jax.device_put(tokens, data)}
    step = jax.jit(build_train_step(cfg, mesh, tcfg))
    losses, gnorms = [], []
    for _ in range(sizes.steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        gnorms.append(float(metrics["gnorm"]))
    return losses, gnorms, state


def zero1_shards(opt_state) -> dict:
    """Per-vector ZeRO-1 placement: the memory kinds its leaves live in,
    and the bytes each device holds against the vector's total."""
    out = {}
    for k in ("m", "v", "ef"):
        if k not in opt_state:
            continue
        leaves = jax.tree.leaves(opt_state[k])
        held: dict[int, int] = {}
        for x in leaves:
            for sh in x.addressable_shards:
                held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
        out[k] = {"memory_kinds": sorted({x.sharding.memory_kind
                                          for x in leaves}),
                  "bytes_per_device": dict(sorted(held.items())),
                  "bytes": sum(x.nbytes for x in leaves)}
    return out


def check_zero1(shards: dict, device_ids, kind: str = ho.SLOW_KIND) -> None:
    """Every device holds its own share of each ZeRO-1 vector (at most a
    hundredth over an even split: only tiny leaves may be replicated),
    and the vectors are parked in memory ``kind`` (host memory)."""
    for k, row in shards.items():
        held = row["bytes_per_device"]
        if sorted(held) != sorted(device_ids):
            raise RuntimeError(f"ZeRO-1 {k} not spread over {device_ids}: "
                               f"{row}")
        share = row["bytes"] / len(device_ids)
        if max(held.values()) > 1.01 * share:
            raise RuntimeError(f"ZeRO-1 {k} not sharded: {row}")
        if row["memory_kinds"] != [kind]:
            raise RuntimeError(f"ZeRO-1 {k} not offloaded: {row}")


def run_training(cfg, sizes: TrainSizes, devices, seed: int) -> None:
    cut = dataclasses.replace(cfg, n_layers=sizes.n_layers)
    log(f"model {cfg.name} at published widths (d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}); depth cut {cfg.n_layers} -> "
        f"{cut.n_layers} layers so the one-chip reference's Adam state fits")
    log(f"sizes {dataclasses.asdict(sizes)}")
    t0 = time.perf_counter()
    many, many_g, state = train_run(
        cut, sizes, sharded_config(sizes.lr, sizes.microbatches), devices,
        seed)
    log(f"[{len(devices)} chips] ZeRO-1 + host-parked state + int8+EF "
        f"all-reduce: losses {many}, gradient norms {many_g} "
        f"({time.perf_counter() - t0:.1f} s on the chips, compile included)")
    shards = zero1_shards(state["opt"])
    log(f"[{len(devices)} chips] ZeRO-1 state {shards}")
    del state
    t0 = time.perf_counter()
    one, one_g, _ = train_run(
        cut, sizes, reference_config(sizes.lr, sizes.microbatches),
        devices[:1], seed)
    log(f"[1 chip] plain AdamW: losses {one}, gradient norms {one_g} "
        f"({time.perf_counter() - t0:.1f} s on the chip, compile included)")
    check_zero1(shards, [d.id for d in devices])
    log(f"loss gaps {check_losses(many, one)} (bounds: first "
        f"{LOSS_FIRST_BOUND}, then {LOSS_REL_BOUND} x the reference's own "
        f"movement); first-step gradient norm gap "
        f"{check_gnorm(many_g, one_g)} (bound {GNORM_REL_BOUND} relative)")


def check_losses(many: list[float], one: list[float]) -> list[float]:
    """The 4-chip losses against the 1-chip reference (see the bounds
    above); returns the gaps, raises on the first one out of bound."""
    gaps = [abs(a - b) for a, b in zip(many, one)]
    limits = [LOSS_FIRST_BOUND] + [LOSS_REL_BOUND * abs(one[0] - b)
                                   for b in one[1:]]
    if not (np.isfinite(many + one).all()
            and all(g <= lim for g, lim in zip(gaps, limits))):
        raise RuntimeError(f"losses disagree: {many} vs {one} (limits "
                           f"{limits})")
    return gaps


def check_gnorm(many: list[float], one: list[float]) -> float:
    """The first step's global gradient norm against the reference (both
    steps start from the same params and batch); returns the relative
    gap, raises when it is out of bound."""
    gap = abs(many[0] - one[0]) / abs(one[0])
    if not gap <= GNORM_REL_BOUND:
        raise RuntimeError(f"first-step gradient norm {many[0]} vs {one[0]} "
                           f"(relative gap {gap} > {GNORM_REL_BOUND})")
    return gap


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = tpu_device()
    faulthandler.dump_traceback_later(STACK_DUMP_S, repeat=True)
    cache = enable_compile_cache()
    log(f"device {dev.device_kind} x {len(jax.devices())}; compile cache "
        f"{cache}")
    cfg = get_config(ARCH)
    if args.chips == 4:
        devices = jax.devices()
        if len(devices) < 4:
            raise SystemExit(f"chip_smoke: --chips 4 needs 4 chips, found "
                             f"{len(devices)}")
        run_training(cfg, TrainSizes(), devices[:4], args.seed)
    else:
        run_serving(cfg, ServeSizes(), args.seed)
    faulthandler.cancel_dump_traceback_later()
    log(hbm())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
