"""Serve a small LM with NeoMem tiering on the unified TieredResource API:
paged-KV decode over fast-tier hot pages plus embedding-row tiering, both
multiplexed on ONE daemon with a shared migration budget.

    PYTHONPATH=src python examples/serve_longctx.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs.registry import get_smoke_config
from repro.models import transformer as tr
from repro.serve.engine import ServeConfig, ServeEngine


def main():
    cfg = get_smoke_config("llama3.2-3b")
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, ServeConfig(
        max_seq=512, paged=True, page_t=16, hot_slots=8,
        migration_interval=8, resources=("embeddings",), embed_hot_slots=4))

    batch = 4
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (batch, 48)).astype(np.int32)
    print(f"prefill {batch} requests x {prompts.shape[1]} tokens (paged KV,"
          f" {eng.scfg.hot_slots} hot slots x {eng.scfg.page_t} tokens; "
          f"tiered resources: {sorted(eng.daemon.resources)})")
    t0 = time.time()
    out = eng.generate(prompts, n_tokens=32)
    dt = time.time() - t0
    print(f"generated {out.shape} in {dt:.1f}s "
          f"({batch*32/dt:.1f} tok/s interpret-mode)")
    for name, row in sorted(eng.tier_stats().items()):
        print(f"{name:12s} fast-tier hit rate: {row['hit_rate']:.2f}")
    print("sample:", out[0][:16])


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
