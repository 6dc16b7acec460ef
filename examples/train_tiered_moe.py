"""End-to-end driver: train a ~100M-param MoE LM for a few hundred steps with
NeoMem expert-stream profiling + checkpointing + (optional) crash resume.

    PYTHONPATH=src python examples/train_tiered_moe.py --steps 200
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import tiering as tm
from repro.ckpt.manager import CheckpointManager
from repro.configs.base import ArchConfig, MoECfg
from repro.data.pipeline import DataConfig, make_dataset
from repro.models import transformer as tr
from repro.optim.optimizers import OptConfig, make_optimizer

# ~100M params: 8L, d=512, 16 experts of ff=1024 top-2, vocab 32K
CFG = ArchConfig(
    name="moe-100m", family="moe", n_layers=9, d_model=512, n_heads=8,
    n_kv_heads=4, d_ff=2048, vocab=32000, head_dim=64, pattern=("moe",),
    moe=MoECfg(n_experts=16, top_k=2, expert_ff=1024, shared_ff=1024,
               n_dense_prologue=1, dense_ff=2048),
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/neomem_moe_ckpt")
    args = ap.parse_args()

    n = CFG.total_params()
    print(f"model: {n/1e6:.0f}M params ({CFG.active_params()/1e6:.0f}M active)")
    data = make_dataset(DataConfig(seq_len=args.seq, global_batch=args.batch,
                                   vocab=CFG.vocab))
    opt_init, opt_update = make_optimizer(OptConfig(
        lr=1e-3, warmup_steps=20, total_steps=args.steps, weight_decay=0.01))
    params = tr.init_params(CFG, jax.random.PRNGKey(0))
    opt_state = opt_init(params)
    mgr = CheckpointManager(args.ckpt, keep=2)
    # NeoMem: register the router stream as an "experts" TieredResource on a
    # multiplexed daemon (a trainer would register more resources here).
    daemon = tm.NeoMemDaemon()
    experts = daemon.register(tm.make_resource("experts", tm.ResourceSpec(
        "experts", n_pages=CFG.n_groups * 16,
        hot_slots=CFG.n_groups * 4, quota_pages=32), n_experts=16))

    start = mgr.latest_step() or 0
    if start:
        print(f"resuming from checkpoint step {start}")
        params = mgr.restore(start, params)

    @jax.jit
    def step(params, opt_state, batch):
        (loss, (metrics, aux)), grads = jax.value_and_grad(
            lambda p: tr.train_loss(CFG, p, batch), has_aux=True)(params)
        params, opt_state, om = opt_update(params, grads, opt_state)
        return params, opt_state, loss, aux.get("router_streams")

    t0 = time.time()
    for s in range(start, args.steps):
        batch = jax.tree.map(jnp.asarray, data.batch(s, 0, 1))
        params, opt_state, loss, streams = step(params, opt_state, batch)
        if streams is not None:
            experts.observe(streams)      # NeoMem: profile the router stream
            daemon.tick()
        if s % 20 == 0 or s == args.steps - 1:
            tput = (s - start + 1) * args.batch * args.seq / (time.time() - t0)
            print(f"step {s:4d} loss={float(loss):.3f} "
                  f"tok/s={tput:,.0f} expert_hit={experts.hit_rate():.2f}")
        if s and s % 100 == 0:
            mgr.save(s, params, blocking=False)
    mgr.wait()
    mgr.save(args.steps, params)
    print("final expert residency (hot experts per group):")
    res = np.asarray(experts.state.tier.page_slot).reshape(CFG.n_groups, 16)
    print((res >= 0).sum(axis=1))


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
