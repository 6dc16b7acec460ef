"""Embedding-row tiering demo on the unified TieredResource API: gemma2-scale
256K-row vocab, zipf token stream; NeoMem keeps the hot rows HBM-resident.

    PYTHONPATH=src python examples/profile_embeddings.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp
import numpy as np

from repro.launch.cache import enable_compile_cache
from repro import tiering as tm

enable_compile_cache()

VOCAB = 256_000
ROWS = tm.EMBED_ROWS_PER_PAGE
daemon = tm.NeoMemDaemon()
rows = daemon.register(tm.make_resource("embeddings", tm.ResourceSpec(
    "embeddings", n_pages=(VOCAB + ROWS - 1) // ROWS, hot_slots=256,
    quota_pages=64)))
rng = np.random.default_rng(0)
for step in range(96):
    toks = (rng.zipf(1.3, 4096) - 1) % VOCAB
    rows.observe(jnp.asarray(toks.astype(np.int32)))
    daemon.tick()
    if step % 16 == 15:
        theta = rows.stats.theta_trace[-1] if rows.stats.theta_trace else 1
        print(f"step {step:3d} hot-row page hit rate: {rows.hit_rate():.3f} "
              f"theta={theta}")
