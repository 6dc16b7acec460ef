"""Paper Fig. 13: slow-tier traffic + promotion counts per method.

Claim: NeoMem shows the lowest slow-tier traffic; its promotion count is far
below AutoNUMA's (accurate detection) and in PTE-scan's range.
"""
from __future__ import annotations

from repro.core.simulator import WORKLOADS, run_sim

from benchmarks.common import (BLOCK, FAST_RATIO, METHODS, N_BLOCKS, N_PAGES,
                               SIM_KW, Timer, emit)

WL = ["gups", "silo", "pagerank"]


def run(quick: bool = False):
    n_blocks = N_BLOCKS // 4 if quick else N_BLOCKS
    with Timer() as t:
        for wl in WL:
            rows = {}
            for m in METHODS:
                stream = WORKLOADS[wl](n_pages=N_PAGES, block=BLOCK,
                                       n_blocks=n_blocks, seed=31)
                rows[m] = run_sim(m, stream, n_pages=N_PAGES,
                                  fast_ratio=FAST_RATIO, **SIM_KW)
            base = max(rows["pebs"].slow_hits, 1)
            traffic = " ".join(f"{m}={rows[m].slow_hits/base:.2f}"
                               for m in METHODS)
            promos = " ".join(f"{m}={rows[m].promoted}" for m in METHODS)
            emit(f"fig13_{wl}_slow_traffic_norm_pebs", t.s * 1e6, traffic)
            emit(f"fig13_{wl}_promotions", 0.0, promos)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run()
