"""Paper Fig. 3: tiered-memory characterization.

(a) tier latency gap (cost model constants vs paper's measured 430ns/120ns);
(b) end-to-end slowdown running fully on the slow tier vs fully fast —
reproduced by pinning the simulator's fast ratio to ~0 / 1.
"""
from __future__ import annotations

from repro.core.simulator import MemModel, WORKLOADS, run_sim

from benchmarks.common import BLOCK, N_PAGES, SKETCH_W, Timer, emit

WL = ["deathstar", "pagerank", "xsbench", "gups"]


def run(quick: bool = False):
    mem = MemModel()
    emit("fig03a_latency_ratio", 0.0,
         f"slow/fast={mem.slow_lat/mem.fast_lat:.2f}x "
         f"(paper: 430ns/120ns=3.6x)")
    n_blocks = 30 if quick else 60
    with Timer() as t:
        for wl in WL:
            rs = {}
            for ratio, tag in ((0.999, "fast"), (0.001, "slow")):
                stream = WORKLOADS[wl](n_pages=N_PAGES, block=BLOCK,
                                       n_blocks=n_blocks, seed=2)
                rs[tag] = run_sim("first-touch", stream, n_pages=N_PAGES,
                                  fast_ratio=ratio, sketch_width=SKETCH_W)
            slowdown = rs["slow"].runtime / rs["fast"].runtime - 1.0
            emit(f"fig03b_slowdown_{wl}", t.s * 1e6 / len(WL),
                 f"slow-tier-only +{100*slowdown:.0f}% (paper: +64%..+295%)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run()
