"""Paper Fig. 15: sensitivity to migration interval, quota, sketch W and D.

Claims: short migration intervals win (NeoProf affords them); quota sweet
spot at moderate rates; wider sketches drive the error bound to 0 with
performance peaking near W=256K-equivalent; D=2 suffices.
"""
from __future__ import annotations

import numpy as np

from repro.core.simulator import WORKLOADS, run_sim
from repro.core.sketch import SketchParams
from repro.core import sketch as sk

from benchmarks.common import BLOCK, FAST_RATIO, N_BLOCKS, N_PAGES, SIM_KW, Timer, emit


def _sim(wl="pagerank", seed=51, n_blocks=None, **over):
    kw = dict(SIM_KW)
    kw.update(over)
    stream = WORKLOADS[wl](n_pages=N_PAGES, block=BLOCK,
                           n_blocks=n_blocks, seed=seed)
    return run_sim("neomem", stream, n_pages=N_PAGES, fast_ratio=FAST_RATIO,
                   **kw)


def run(quick: bool = False):
    n_blocks = N_BLOCKS // 4 if quick else N_BLOCKS
    with Timer() as t:
        # (a) migration interval (blocks between promotion batches)
        for mi in (1, 4, 16):
            r = _sim(n_blocks=n_blocks, migration_interval=mi)
            emit(f"fig15a_migration_interval{mi}", t.s * 1e6,
                 f"runtime_ms={r.runtime*1e3:.2f} hit={r.hit_rate:.3f}")
        # (b) migration quota
        for q in (16, 64, 128, 512):
            r = _sim(n_blocks=n_blocks, quota_pages=q)
            emit(f"fig15b_quota{q}", 0.0,
                 f"runtime_ms={r.runtime*1e3:.2f} hit={r.hit_rate:.3f}")
        # (c) sketch width: error bound + performance
        for w_log in (10, 12, 14):
            w = 1 << w_log
            r = _sim(n_blocks=n_blocks, sketch_width=w)
            # standalone error-bound measurement at this width
            sp = SketchParams(width=w, depth=2)
            st = sk.sketch_init(sp)
            rng = np.random.default_rng(0)
            import jax.numpy as jnp
            for _ in range(8):
                st, _ = sk.sketch_update(
                    st, jnp.asarray(rng.integers(0, N_PAGES, 2048),
                                    jnp.int32), jnp.int32(1 << 30), sp)
            eb = int(sk.error_bound_from_hist(sk.sketch_histogram(st, sp), sp))
            emit(f"fig15c_width{w}", 0.0,
                 f"runtime_ms={r.runtime*1e3:.2f} hit={r.hit_rate:.3f} "
                 f"error_bound={eb}")
        # (d) sketch depth
        for d in (1, 2, 4):
            r = _sim(n_blocks=n_blocks, sketch_depth=d)
            emit(f"fig15d_depth{d}", 0.0,
                 f"runtime_ms={r.runtime*1e3:.2f} hit={r.hit_rate:.3f}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run()
