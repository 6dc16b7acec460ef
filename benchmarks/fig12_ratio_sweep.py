"""Paper Fig. 12: performance across fast:slow memory ratios (1:2, 1:4, 1:8).

NeoMem vs PEBS (the paper's second-best); claim: NeoMem's lead widens as the
fast tier shrinks (higher classification accuracy matters more).
"""
from __future__ import annotations

from repro.core.simulator import WORKLOADS, run_sim

from benchmarks.common import BLOCK, N_BLOCKS, N_PAGES, SIM_KW, Timer, emit

WL = ["pagerank", "btree", "gups", "xsbench"]
RATIOS = {"1:2": 1 / 3, "1:4": 1 / 5, "1:8": 1 / 9}


def run(quick: bool = False):
    n_blocks = N_BLOCKS // 4 if quick else N_BLOCKS
    with Timer() as t:
        for wl in WL:
            parts = []
            for tag, ratio in RATIOS.items():
                rs = {}
                for m in ("neomem", "pebs"):
                    stream = WORKLOADS[wl](n_pages=N_PAGES, block=BLOCK,
                                           n_blocks=n_blocks, seed=21)
                    rs[m] = run_sim(m, stream, n_pages=N_PAGES,
                                    fast_ratio=ratio, **SIM_KW)
                parts.append(f"{tag}={rs['pebs'].runtime/rs['neomem'].runtime:.2f}x")
            emit(f"fig12_{wl}_speedup_vs_pebs",
                 t.s * 1e6 / (len(WL) * len(RATIOS) * 2), " ".join(parts))


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run()
