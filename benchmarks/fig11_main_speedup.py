"""Paper Fig. 11: end-to-end speedup of NeoMem vs 5 baselines, 8 workloads.

Modeled runtime = access time (hit/miss x tier latency) + migration time +
profiling overhead, driven by the REAL NeoMem components (JAX sketch,
Algorithm-1 policy, TieredStore) on structure-preserving workload streams.
Paper claim under reproduction: 32%..67% geomean speedup.
"""
from __future__ import annotations

from repro.core.simulator import WORKLOADS, geomean_speedup, run_sim

from benchmarks.common import (BLOCK, FAST_RATIO, METHODS, N_BLOCKS, N_PAGES,
                               SIM_KW, Timer, emit)

WL = ["deathstar", "pagerank", "xsbench", "gups", "silo", "btree",
      "bwaves", "roms"]


def run(quick: bool = False):
    n_blocks = N_BLOCKS // 4 if quick else N_BLOCKS
    results: dict[str, dict[str, float]] = {m: {} for m in METHODS}
    hit: dict[str, dict[str, float]] = {m: {} for m in METHODS}
    with Timer() as t:
        for wl in WL:
            for m in METHODS:
                stream = WORKLOADS[wl](n_pages=N_PAGES, block=BLOCK,
                                       n_blocks=n_blocks, seed=11)
                r = run_sim(m, stream, n_pages=N_PAGES,
                            fast_ratio=FAST_RATIO, **SIM_KW)
                results[m][wl] = r.runtime
                hit[m][wl] = r.hit_rate
    for m in METHODS:
        if m == "neomem":
            continue
        sp = geomean_speedup([results[m][w] for w in WL],
                             [results["neomem"][w] for w in WL])
        per_wl = " ".join(f"{w}={results[m][w]/results['neomem'][w]:.2f}x"
                          for w in WL)
        emit(f"fig11_geomean_speedup_vs_{m}",
             t.s * 1e6 / (len(WL) * len(METHODS)),
             f"{sp:.3f}x | {per_wl}")
    emit("fig11_neomem_hit_rates", 0.0,
         " ".join(f"{w}={hit['neomem'][w]:.2f}" for w in WL))
    return results


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run()
