"""Quickstart: NeoMem's sketch-profiled tiering on a synthetic access stream.

Shows the full paper loop in ~40 lines on the unified ``repro.tiering``
surface: one :class:`ResourceSpec` declares the geometry, NeoProf observes
the stream on device, Algorithm 1 adapts the hotness threshold, the 2Q
tier promotes hot pages under quota, and the hit rate converges.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp
import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.tiering import (DaemonParams, NeoMemDaemon, ResourceSpec,
                           StreamResource)

enable_compile_cache()

N_PAGES, N_SLOTS = 8192, 1024
spec = ResourceSpec(name="demo", n_pages=N_PAGES, hot_slots=N_SLOTS,
                    quota_pages=128, sketch_width=1 << 14)
daemon = NeoMemDaemon(DaemonParams(
    migration_interval=1, threshold_update_period=4, clear_interval=16))
h = daemon.register(StreamResource(spec))
h.state = h.state._replace(prof=h.mem.cmd.set_threshold(h.state.prof, 4))

rng = np.random.default_rng(0)
for step in range(128):
    # 85% of traffic to a 600-page hot region, 15% uniform
    hot = rng.integers(7000, 7600, 1740)
    uni = rng.integers(0, N_PAGES, 308)
    pages = np.concatenate([hot, uni]).astype(np.int32)
    # profile ONLY slow-tier traffic (NeoProf sits in the slow tier);
    # the tier's touch accounting still sees every access
    slot = np.asarray(h.state.tier.page_slot)
    slow = pages[slot[pages] < 0]
    blk = np.full(len(pages), -1, np.int32)
    blk[: len(slow)] = slow
    h.observe_pages(jnp.asarray(blk), touch_pages=jnp.asarray(pages))
    daemon.tick()
    if step % 16 == 15:
        pol = h.mem.policy_state(h.state, h.stats)
        print(f"step {step:4d}  theta={pol.theta:4d}  "
              f"hit={h.hit_rate():.3f}  promoted={h.stats.promoted}")
print("hot pages resident:",
      int((np.asarray(h.state.tier.page_slot)[7000:7600] >= 0).sum()),
      "/ 600")
